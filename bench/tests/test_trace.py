"""The reduction from a profiler trace to device busy time and idle gaps,
on a trace whose intervals are known."""
import os

import pytest

from bench.lib import trace as tr

# Device 0 runs fusion.1 over [1, 3] ms and fusion.2 over [2, 4] ms, then
# fusion.1 again over [8, 9] ms; device 1 runs one op over [1, 2] ms.  The
# host holds bench.window over [0, 10] ms and bench.query over [5, 9] ms.
SYNTHETIC = '''
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Modules" timestamp_ns: 1000000
    events { metadata_id: 3 offset_ps: 0 duration_ps: 9000000000 } }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 1000000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000000 }
    events { metadata_id: 2 offset_ps: 1000000000 duration_ps: 2000000000 }
    events { metadata_id: 1 offset_ps: 7000000000 duration_ps: 1000000000 } }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
  event_metadata { key: 2 value { id: 2 name: "fusion.2" } }
  event_metadata { key: 3 value { id: 3 name: "jit_step" } }
}
planes { id: 2 name: "/device:TPU:1"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 1000000000 } }
  event_metadata { key: 1 value { id: 1 name: "fusion.9" } }
}
planes { id: 3 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000000 }
    events { metadata_id: 2 offset_ps: 5000000000 duration_ps: 4000000000 }
    events { metadata_id: 3 offset_ps: 100000000 duration_ps: 100000000 } }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "bench.query" } }
  event_metadata { key: 3 value { id: 3 name: "other" } }
}
'''


@pytest.fixture
def synthetic(tmp_path):
    from jax.profiler import ProfileData
    path = tmp_path / "plugins" / "profile" / "t" / "h.xplane.pb"
    path.parent.mkdir(parents=True)
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(SYNTHETIC))
    return str(tmp_path)


def test_union_and_gaps():
    u = tr.union([(3, 4), (1, 2), (1.5, 2.5), (2.5, 2.6), (6, 7)])
    assert u == [(1, 2.6), (3, 4), (6, 7)]
    assert tr.covered([(1, 3), (2, 4)]) == 3
    assert tr.gaps(u, 0, 8) == [(0, 1), (2.6, 3), (4, 6), (7, 8)]
    assert tr.gaps([(0, 5)], 1, 4) == []


def test_known_busy_intervals(synthetic):
    s = tr.summarize(tr.find_xplane(synthetic), "bench.slice")
    ms = 1e-3
    # device 0: [1, 4] and [8, 9] ms = 4 ms; device 1: 1 ms; mean 2.5 ms
    assert s["devices"] == 2
    assert s["busy_s"] == pytest.approx(2.5 * ms)
    ops = dict(s["ops"])
    assert ops["fusion.1"] == pytest.approx(3 * ms)
    assert ops["fusion.2"] == pytest.approx(2 * ms)
    assert "jit_step" not in ops           # the module line is not read
    # the extent is [0, 10] ms: idle [4, 8], [0, 1] and [9, 10] ms
    gaps = [(name, round(sec / ms, 6)) for name, sec in s["gaps"]]
    assert gaps[0] == ("bench.query", 4.0)
    assert sorted(gaps[1:]) == [("bench.window", 1.0), ("bench.window", 1.0)]


def test_recorded_tpu_trace():
    """A trace recorded on one v5e (``record_trace.py``): three matmuls
    with 50 ms of host sleep between them."""
    path = os.path.join(os.path.dirname(__file__), "data", "tiny.xplane.pb")
    s = tr.summarize(path, "bench.slice")
    assert s["devices"] == 1
    runs = tr.union(tr.read(path)["devices"]["/device:TPU:0"])
    assert 0 < s["busy_s"] < 1e-3
    # the two sleeps between the three matmuls are idle gaps of the
    # bench.query span; the other long gaps lie outside it
    between = [sec for name, sec in s["gaps"]
               if name == "bench.query" and sec > 0.04]
    assert len(between) == 2 and all(sec < 0.06 for sec in between)
    fusions = [k for k, _ in s["ops"] if k.startswith("%fusion")]
    assert len(fusions) == 1 and len(runs) >= 3


def test_build_slice_opens_at_the_first_executable_and_closes_in_time():
    import threading
    import time
    from bench.lib.harness import built_in_thread

    class Clock:
        count = 0

        def read(self):
            return 0.0, self.count

    class Prof:
        def __init__(self):
            self.events = []

        def start(self, name):
            self.events.append(("start", name, threading.current_thread()))

        def stop(self):
            self.events.append(("stop", None, threading.current_thread()))

    def build(clock, prof, on_thread, traced):
        def fn():
            on_thread.append(threading.current_thread())
            time.sleep(0.1)
            assert prof.events == []          # nothing built yet
            clock.count = 1
            time.sleep(0.05)
            assert [e[0] for e in prof.events] == ["start"] * traced
            time.sleep(0.4)
            return "hierarchy"
        return fn

    for traced in (True, False):
        clock, prof, on = Clock(), Prof(), []
        got = built_in_thread(build(clock, prof, on, traced),
                              prof if traced else None, clock, 0.2)
        assert got == "hierarchy"
        # the same path either way: the build on a thread of its own
        assert on[0] is not threading.current_thread()
        if traced:
            assert [e[:2] for e in prof.events] == [("start", "build"),
                                                    ("stop", None)]
            assert prof.events[0][2] is threading.current_thread()

    prof = Prof()
    assert built_in_thread(lambda: 7, prof, Clock(), 10.0) == 7
    assert prof.events == []              # a build that built nothing
