"""Sub-ILPs stopped at the node, time or budget cap
(SolveReport.ilp_capped, carried on pq.solve), per query of the traced
window."""
from bench.lib.program_spans import counter


def read(rec):
    return counter(rec, "ilp_capped")
