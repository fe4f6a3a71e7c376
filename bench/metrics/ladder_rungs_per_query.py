"""Degradation-ladder rungs per query over the window
(len(SolveReport.fallbacks))."""
from bench.lib.stats import mean


def read(rec):
    q = rec["queries"]
    return mean([r["rungs"] for r in q]) if q else None
