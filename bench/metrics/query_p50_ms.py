"""Median latency of all queries answered in the window, in ms."""
from bench.lib.stats import percentile


def read(rec):
    lat = [q["latency_s"] for q in rec["queries"]]
    return 1e3 * percentile(lat, 50) if lat else None
