"""Percentiles and rates over the window: a stall inside the window moves
the 95th percentile and the rate, which the metrics take over every
query of the window."""
import time
import types

import numpy as np
import pytest

from bench.lib import stats
from bench.lib.harness import reader, window


def test_percentile_nearest_rank():
    v = list(range(1, 101))
    assert stats.percentile(v, 50) == 50
    assert stats.percentile(v, 95) == 95
    assert stats.percentile([7.0], 95) == 7.0
    assert stats.percentile([3, 1, 2], 50) == 2
    assert stats.rate(30, 2.0) == 15.0
    with pytest.raises(ValueError):
        stats.percentile([], 50)


class _Engine:
    """Answers in ``fast`` seconds, or ``slow`` inside [a, b) seconds
    after the first query."""

    def __init__(self, fast, slow=None, a=0.0, b=0.0):
        self.fast, self.slow, self.a, self.b = fast, slow, a, b
        self.t0 = None

    def session(self, seed):
        return self

    def solve(self, pq):
        now = time.monotonic()
        self.t0 = self.t0 or now
        stalled = self.slow and self.a <= now - self.t0 < self.b
        time.sleep(self.slow if stalled else self.fast)
        report = types.SimpleNamespace(status="ok", lp_pivots=1,
                                       ilp_nodes=2, fallbacks=["r"],
                                       cache_hits=0, cache_misses=0)
        return types.SimpleNamespace(report=report, idx=np.zeros(1, int),
                                     mult=np.ones(1), obj=0.0)


class _Stream:
    def get(self, i):
        return (types.SimpleNamespace(session_seed=i, kind="cold",
                                      hardness=1.0), None, None)


def _metrics(eng, seconds=1.0):
    recs, window_s = window(eng, [_Stream()], seconds, None)
    rec = {"queries": recs, "window_s": window_s}
    return {m: reader(m)(rec) for m in ("query_p50_ms", "query_p95_ms",
                                        "queries_per_s",
                                        "lp_pivots_per_query",
                                        "ilp_nodes_per_query",
                                        "ladder_rungs_per_query")}, window_s


def test_a_stall_moves_p95_and_rate():
    steady, w0 = _metrics(_Engine(0.005))
    stalled, w1 = _metrics(_Engine(0.005, slow=0.02, a=0.3, b=0.7))
    assert w0 >= 1.0 and w1 >= 1.0
    assert steady["query_p95_ms"] < 10 <= stalled["query_p95_ms"]
    assert stalled["queries_per_s"] < 0.8 * steady["queries_per_s"]
    assert stalled["query_p50_ms"] < 10
    assert steady["lp_pivots_per_query"] == 1
    assert steady["ilp_nodes_per_query"] == 2
    assert steady["ladder_rungs_per_query"] == 1


def test_the_query_in_flight_at_the_close_counts():
    eng = _Engine(0.3)
    recs, window_s = window(eng, [_Stream()], 0.5, None)
    assert len(recs) == 2 and window_s >= 0.6
