"""The program's ``pq.*`` spans read back from a trace, query by query:
self times by nesting on each host thread, and the counters carried on
``pq.solve``."""
import pytest

from bench.lib import program_spans as ps

# Thread 1: bench.query over [0, 10] ms holds pq.solve over [0, 10] with
# pq.shade [1, 3], pq.dr.lp [3, 4], pq.ilp.incumbent [4, 5] and
# pq.ilp.search [5, 9]; then pq.solve [12, 15] holds pq.shade [12, 14].
# Thread 2: pq.solve [2, 6], overlapping thread 1's spans in time only.
MS = 1000000000          # picoseconds in a millisecond
SPANS = [(1, 0, 10, 1), (2, 0, 10, 1), (3, 1, 2, 1), (4, 3, 1, 1),
         (5, 4, 1, 1), (6, 5, 4, 1), (2, 12, 3, 1), (3, 12, 2, 1),
         (2, 2, 4, 2)]
NAMES = ["bench.query", "pq.solve", "pq.shade", "pq.dr.lp",
         "pq.ilp.incumbent", "pq.ilp.search"]


def _events(line):
    out = []
    for meta, at, dur, ln in SPANS:
        if ln != line:
            continue
        stats = ""
        if meta == 2 and at == 0:
            stats = ("stats { metadata_id: 1 int64_value: 7 } "
                     "stats { metadata_id: 2 double_value: 0.002 } "
                     "stats { metadata_id: 3 int64_value: 1 }")
        elif meta == 2 and line == 1:
            stats = "stats { metadata_id: 1 int64_value: 3 }"
        out.append(f"events {{ metadata_id: {meta} offset_ps: {at * MS} "
                   f"duration_ps: {dur * MS} {stats} }}")
    return "\n".join(out)


SYNTHETIC = '''
planes { id: 3 name: "/host:CPU"
  lines { id: 1 name: "bench-client-0" timestamp_ns: 0
''' + _events(1) + '''
  }
  lines { id: 2 name: "bench-client-1" timestamp_ns: 0
''' + _events(2) + '''
  }
''' + "\n".join(f'  event_metadata {{ key: {i} value {{ id: {i} '
                f'name: "{n}" }} }}' for i, n in enumerate(NAMES, 1)) + '''
  stat_metadata { key: 1 value { id: 1 name: "ilp_lp_pivots" } }
  stat_metadata { key: 2 value { id: 2 name: "ilp_node_lp_s" } }
  stat_metadata { key: 3 value { id: 3 name: "ilp_capped" } }
}
'''


@pytest.fixture
def queries(tmp_path):
    from jax.profiler import ProfileData
    path = tmp_path / "h.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(SYNTHETIC))
    return ps.read(str(path))


def test_self_times_nest_by_thread(queries):
    ms = 1e-3
    assert len(queries) == 3
    got = sorted((round(q["self"]["solve"] / ms, 6), dict(q["stats"]))
                 for q in queries)
    assert got[0] == (1.0, {"ilp_lp_pivots": 3})
    assert got[1] == (2.0, {"ilp_lp_pivots": 7, "ilp_node_lp_s": 0.002,
                            "ilp_capped": 1})
    assert got[2] == (4.0, {})                  # thread 2: nothing nested
    first = next(q for q in queries if q["stats"].get("ilp_capped"))
    assert {k: round(v / ms, 6) for k, v in first["self"].items()} == {
        "solve": 2.0, "shade": 2.0, "dr.lp": 1.0, "ilp.incumbent": 1.0,
        "ilp.search": 4.0}


def test_means_over_the_window(queries, monkeypatch):
    monkeypatch.setattr(ps, "window_queries", lambda rec: queries)
    assert ps.self_ms({}, "solve") == pytest.approx(7.0 / 3)
    assert ps.self_ms({}, "shade") == pytest.approx(4.0 / 3)
    # a counter is averaged over the queries that carry it
    assert ps.counter({}, "ilp_lp_pivots") == pytest.approx(5.0)
    assert ps.counter({}, "ilp_node_lp_s", 1e3) == pytest.approx(2.0)
    assert ps.counter({}, "missing") is None


def test_no_trace_reads_nothing():
    assert ps.window_queries({"trace": None}) == []
    assert ps.self_ms({"trace": None}, "solve") is None
    assert ps.counter({}, "ilp_capped") is None


def test_traced_rehearsal_reports_the_span_metrics(small_cell):
    from bench.lib.harness import run
    out = run(small_cell(), 4000000000, 1.0, True, require_chip=False)
    m = {k: v["value"] for k, v in out["metrics"].items()}
    for name in ("shade_ms_per_query", "dr_lp_ms_per_query",
                 "ilp_incumbent_ms_per_query", "ilp_search_ms_per_query",
                 "ilp_node_lp_ms_per_query", "ilp_lp_pivots_per_query",
                 "ilp_capped_per_query", "solve_self_ms_per_query"):
        assert name in m and m[name] >= 0, name
    assert len(ps.window_queries(out["record"])) == len(
        out["record"]["queries"])
    mean_ms = 1e3 * sum(q["latency_s"] for q in out["record"]["queries"]) \
        / len(out["record"]["queries"])
    assert m["solve_self_ms_per_query"] < mean_ms
