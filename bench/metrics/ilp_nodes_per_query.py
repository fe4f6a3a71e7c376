"""Branch-and-bound nodes per query over the window
(SolveReport.ilp_nodes)."""
from bench.lib.stats import mean


def read(rec):
    q = rec["queries"]
    return mean([r["ilp_nodes"] for r in q]) if q else None
