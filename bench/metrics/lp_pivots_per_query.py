"""Simplex pivots per query over the window (SolveReport.lp_pivots)."""
from bench.lib.stats import mean


def read(rec):
    q = rec["queries"]
    return mean([r["lp_pivots"] for r in q]) if q else None
