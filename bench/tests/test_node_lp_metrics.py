"""The node-LP metrics of branch and bound, read from the counters that
``pq.solve`` carries: the share of node LPs resumed from their parent's
factorization, and the microseconds a node LP takes."""
import pytest

from bench.lib import program_spans as ps
from bench.lib.harness import reader

MS = 1000000000          # picoseconds in a millisecond
# pq.solve [0, 10] ms with 10 node LPs, all carried, 4 ms of them;
# pq.solve [12, 20] ms with 30, 29 carried, 8 ms; pq.solve [22, 23] ms
# without the counters, as a program that has none of them records it
STATS = [{"lps": 10, "carried": 10, "s": 0.004},
         {"lps": 30, "carried": 29, "s": 0.008}, None]
SPANS = [(0, 10), (12, 8), (22, 1)]


def _trace(stats):
    events = []
    for (at, dur), st in zip(SPANS, stats):
        body = "" if st is None else (
            f"stats {{ metadata_id: 1 int64_value: {st['lps']} }} "
            f"stats {{ metadata_id: 2 int64_value: {st['carried']} }} "
            f"stats {{ metadata_id: 3 double_value: {st['s']} }}")
        events.append(f"events {{ metadata_id: 1 offset_ps: {at * MS} "
                      f"duration_ps: {dur * MS} {body} }}")
    return '''
planes { id: 3 name: "/host:CPU"
  lines { id: 1 name: "bench-client-0" timestamp_ns: 0
''' + "\n".join(events) + '''
  }
  event_metadata { key: 1 value { id: 1 name: "pq.solve" } }
  stat_metadata { key: 1 value { id: 1 name: "ilp_node_lps" } }
  stat_metadata { key: 2 value { id: 2 name: "ilp_node_lps_carried" } }
  stat_metadata { key: 3 value { id: 3 name: "ilp_node_lp_s" } }
}
'''


def _queries(tmp_path, stats):
    from jax.profiler import ProfileData
    path = tmp_path / "h.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(
        _trace(stats)))
    return ps.read(str(path))


def test_sums_over_the_window(tmp_path, monkeypatch):
    queries = _queries(tmp_path, STATS)
    assert len(queries) == 3
    monkeypatch.setattr(ps, "window_queries", lambda rec: queries)
    assert reader("ilp_node_carried_share")({}) == pytest.approx(39 / 40)
    assert reader("ilp_node_lp_us_per_lp")({}) == pytest.approx(
        1e6 * 0.012 / 40)


@pytest.mark.parametrize("stats", [[None] * 3,
                                   [{"lps": 0, "carried": 0, "s": 0.0}] * 3],
                         ids=["no-counters", "no-node-lps"])
def test_nothing_to_read(tmp_path, monkeypatch, stats):
    queries = _queries(tmp_path, stats)
    monkeypatch.setattr(ps, "window_queries", lambda rec: queries)
    for name in ("ilp_node_carried_share", "ilp_node_lp_us_per_lp"):
        assert reader(name)({}) is None


def test_untraced_run_reads_nothing():
    for name in ("ilp_node_carried_share", "ilp_node_lp_us_per_lp"):
        assert reader(name)({"trace": None}) is None


def test_traced_rehearsal_reports_them(small_cell):
    from bench.lib.harness import run
    out = run(small_cell(), 4000000001, 1.0, True, require_chip=False)
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert m["ilp_node_carried_share"] == 1.0
    assert 0 < m["ilp_node_lp_us_per_lp"] < 1e6
