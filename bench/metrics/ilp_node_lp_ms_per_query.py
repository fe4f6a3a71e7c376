"""Seconds in B&B's node LP solves (SolveReport.ilp_node_lp_s, carried
on pq.solve), ms per query of the traced window."""
from bench.lib.program_spans import counter


def read(rec):
    return counter(rec, "ilp_node_lp_s", 1e3)
