"""Pivots of B&B's root and node LPs (SolveReport.ilp_lp_pivots,
carried on pq.solve), per query of the traced window."""
from bench.lib.program_spans import counter


def read(rec):
    return counter(rec, "ilp_lp_pivots")
