"""Queries answered over the window, start to last answer."""
from bench.lib.stats import rate


def read(rec):
    n = len(rec["queries"])
    return rate(n, rec["window_s"]) if n else None
