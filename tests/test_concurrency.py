"""Deterministic race harness + thread-safety of the shared serving
path.

Three layers:

* the harness itself — a seeded :class:`ScheduleController` replays the
  SAME interleaving for the same seed, and a pinned known-bad schedule
  deterministically reproduces the duplicate-cold-solve race on an
  intentionally UNLOCKED cache double (what ``QCache.get_or_populate``
  would be without its claim protocol);
* the fixed implementations — ``QCache`` and ``BoundedStepCache`` pass
  every seeded schedule with exactly one cold solve per key, the fault
  injector keeps per-thread deterministic streams, and preemptive
  hammer tests hold the counter invariants;
* the serving integration — concurrent ``engine.solve`` sessions over a
  shared cache return the same packages as sequential solves, and the
  scheduler never loses a request under concurrent submits.
"""
import threading
from types import SimpleNamespace

import numpy as np
import pytest

from repro.core.distributed import BoundedStepCache
from repro.core.engine import PackageQueryEngine
from repro.core.hardness import Q2_TPCH, Q4_TPCH, column_stats, instantiate
from repro.core.qcache import QCache
from repro.data.synth_tables import make_table
from repro.runtime import faults, racecheck
from repro.runtime.racecheck import (Deadlock, InstrumentedLock,
                                     ScheduleController, run_threads)

ATTRS = ["price", "quantity", "discount", "tax"]
ILP_KW = dict(max_nodes=200, time_limit_s=15)


# ---------------------------------------------------- harness test doubles


class _UnlockedCacheDouble:
    """QCache.get_or_populate WITHOUT the claim protocol — the pre-fix
    shape.  Probe and store are separate unlocked steps, so two threads
    interleaved between them both run the cold solve."""

    def __init__(self):
        self.entries = {}
        self.solves = 0

    def get_or_populate(self, key, solve):
        racecheck.checkpoint("double.probe")
        if key in self.entries:
            return "hit", self.entries[key]
        racecheck.checkpoint("double.solve")
        v = solve()
        self.solves += 1
        racecheck.checkpoint("double.store")
        self.entries[key] = v
        return "solved", v


class _FakeHier:
    """Just enough hierarchy for QCache.store: a fingerprint, layer-1
    group ids, and a no-op invalidation hook."""

    def __init__(self, fingerprint="fp0"):
        self.fingerprint = fingerprint
        self.layers = {1: SimpleNamespace(
            part=SimpleNamespace(gid=np.zeros(64, np.int64)))}

    def add_invalidation_hook(self, fn):
        pass


class _Sig:
    def __init__(self, tag):
        self.tag = tag

    def __hash__(self):
        return hash(self.tag)

    def __eq__(self, other):
        return isinstance(other, _Sig) and self.tag == other.tag

    def contained_in(self, other):
        return self == other


# The pinned known-bad interleaving (decisions in consumption order):
# start->T0; T0 parks at its probe -> T1; T1 probes, solves, then parks
# at its store -> T0; T0 re-checks (store not yet published!), solves
# again.  Two cold solves for one key — the check-then-act race.
_BAD_SCHEDULE = [0, 1, 1, 1, 0, 0, 0]
# Fully serial: T0 runs to completion, T1 takes the hit.
_SERIAL_SCHEDULE = [0] * 16


def _double_case():
    cache = _UnlockedCacheDouble()

    def body():
        return cache.get_or_populate("k", lambda: "v")[0]

    return cache, [body, body]


def test_pinned_schedule_reproduces_unlocked_race():
    cache, fns = _double_case()
    ctl = ScheduleController(schedule=list(_BAD_SCHEDULE))
    kinds = ctl.run(fns)
    assert cache.solves == 2, \
        f"known-bad schedule must duplicate the cold solve; {ctl.trace}"
    assert kinds == ["solved", "solved"]


def test_serial_schedule_passes_unlocked_double():
    cache, fns = _double_case()
    kinds = ScheduleController(schedule=list(_SERIAL_SCHEDULE)).run(fns)
    assert cache.solves == 1
    assert sorted(kinds) == ["hit", "solved"]


def test_seeded_schedules_replay_exactly():
    """Same seed => same interleaving => same outcome; and the sweep
    finds at least one racy seed on the unlocked double (the pre-fix
    regression the fixed QCache must survive below)."""
    outcomes = {}
    for seed in range(24):
        runs = []
        for _ in range(2):
            cache, fns = _double_case()
            ctl = ScheduleController(seed=seed)
            ctl.run(fns)
            runs.append((cache.solves, tuple(ctl.trace)))
        assert runs[0] == runs[1], f"seed {seed} did not replay"
        outcomes[seed] = runs[0][0]
    assert set(outcomes.values()) == {1, 2}, \
        f"sweep should see both clean and racy interleavings: {outcomes}"


# --------------------------------------------------------- fixed QCache


def _qcache_case(n_threads=3):
    qc = QCache()
    hier = _FakeHier()
    sig = _Sig("q")
    solves = []

    def body():
        def solve():
            solves.append(1)
            qc.store("fp0", sig, hier=hier, cands={1: np.arange(8)},
                     layer_warms={}, dr_warm=None, lp_bound=1.0)
            return "cold"

        kind, _val = qc.get_or_populate("fp0", sig, solve)
        return kind

    return qc, solves, [body] * n_threads


@pytest.mark.parametrize("seed", range(12))
def test_qcache_get_or_populate_atomic_under_schedule(seed):
    """The fixed claim protocol: every seeded interleaving (including
    the class of the known-bad one above) runs exactly ONE cold solve;
    every other session takes the hit — no duplicate solves, no lost
    stores."""
    qc, solves, fns = _qcache_case()
    kinds = ScheduleController(seed=seed).run(fns, timeout_s=30)
    assert sum(solves) == 1, f"seed {seed}: duplicate cold solve"
    assert sorted(kinds) == ["hit", "hit", "solved"]
    assert len(qc) == 1
    st = qc.stats_snapshot()
    assert st.stores == 1 and st.hits >= 2


def test_qcache_populate_protocol_single_thread():
    qc = QCache()
    sig = _Sig("a")
    assert qc.begin_populate("fp", sig) is True
    assert qc.begin_populate("fp", sig) is False      # already claimed
    assert qc.wait_populate("fp", sig, timeout=0.01) is False
    qc.end_populate("fp", sig)
    assert qc.wait_populate("fp", sig, timeout=0.01) is True
    assert qc.begin_populate("fp", sig) is True       # claim reusable
    qc.end_populate("fp", sig)


def test_qcache_failed_solve_releases_claim():
    qc, _solves, _fns = _qcache_case()
    sig = _Sig("q")

    def boom():
        raise RuntimeError("cold solve died")

    with pytest.raises(RuntimeError):
        qc.get_or_populate("fp0", sig, boom)
    # the claim is released: the next caller becomes the owner
    assert qc.begin_populate("fp0", sig) is True
    qc.end_populate("fp0", sig)


def test_qcache_lock_stats_counters():
    qc, _solves, fns = _qcache_case()
    ScheduleController(seed=3).run(fns)
    ls = qc.lock_stats()
    assert ls["name"] == "qcache"
    assert ls["acquisitions"] > 0
    assert ls["wait_s"] >= 0.0 and ls["hold_s"] >= 0.0


# ------------------------------------------------------ BoundedStepCache


def test_step_cache_hammer_counter_invariant():
    """8 preemptive threads over 6 overlapping keys: each key is built
    exactly once (claim token), and hits + misses == lookups even under
    contention (unresolved waiter probes are never charged)."""
    cache = BoundedStepCache(maxsize=64)
    built = []
    build_lock = threading.Lock()

    def body(t):
        def run():
            out = []
            for rep in range(5):
                for k in range(6):
                    def factory(k=k):
                        with build_lock:
                            built.append(k)
                        return ("steps", k)

                    out.append(cache.get_or_create(("key", k), factory))
            return out

        return run

    results = run_threads([body(t) for t in range(8)])
    assert sorted(built) == list(range(6)), \
        f"every key must be built exactly once, got {built}"
    st = cache.stats()
    assert st["hits"] + st["misses"] == st["lookups"]
    assert st["misses"] == 6 and st["lookups"] == 8 * 5 * 6
    for out in results:
        assert out == [("steps", k) for _ in range(5) for k in range(6)]


def test_step_cache_atomic_under_schedules():
    cases = []

    def make_case():
        cache = BoundedStepCache(maxsize=8)
        built = []
        cases.append((cache, built))

        def body():
            return cache.get_or_create(
                "k", lambda: built.append(1) or "entry")

        return [body, body, body]

    ctls = racecheck.run_schedules(make_case, seeds=range(10))
    assert len(ctls) == len(cases) == 10
    for cache, built in cases:
        assert len(built) == 1                 # one build per schedule
        st = cache.stats()
        assert st["hits"] + st["misses"] == st["lookups"] == 3


# ------------------------------------------------------- fault injector


def test_faults_single_thread_stream_matches_legacy_seed():
    """Stream 0 is bit-identical to the pre-PR10 single-rng injector:
    single-threaded fault schedules (and every recorded experiment)
    reproduce exactly."""
    inj = faults.FaultInjector(seed=5)
    legacy = np.random.default_rng(5)
    assert np.allclose(inj.rng.random(8), legacy.random(8))
    assert inj.thread_index() == 0


def test_faults_two_thread_streams_deterministic():
    """Each thread gets its own deterministic stream: per-thread draw
    sequences equal the spawned SeedSequence streams regardless of
    interleaving, and per-thread fire budgets apply independently."""
    site = "test.site"

    def expected(idx, seed=9):
        ss = np.random.SeedSequence(seed) if idx == 0 \
            else np.random.SeedSequence(seed, spawn_key=(idx - 1,))
        return np.random.default_rng(ss).random(4)

    for trial in range(3):                     # stable across repeats
        inj = faults.FaultInjector(seed=9).arm(site, times=1)

        def body():
            fires = 0
            for _ in range(3):                 # budget is per-thread
                try:
                    inj.maybe_raise(site)
                except OSError:
                    fires += 1
            return inj.thread_index(), tuple(inj.rng.random(4)), fires

        out = run_threads([body, body])
        idxs = sorted(t[0] for t in out)
        assert idxs == [0, 1], "each thread must own a distinct stream"
        for idx, draws, fires in out:
            assert np.allclose(draws, expected(idx))
            assert fires == 1                  # times=1 PER THREAD
        assert inj.fire_count(site) == 2       # aggregate across streams
        assert sorted(s for _site, s, _k in inj.log) == [0, 1]


def test_faults_thread_scoped_injection_is_confined():
    site = "test.scoped"
    ev_armed = threading.Event()
    ev_checked = threading.Event()

    def armed_thread():
        with faults.injected(seed=1, arms={site: dict(times=1)},
                             scope="thread") as inj:
            with pytest.raises(OSError):
                inj.maybe_raise(site)
            ev_armed.set()
            assert ev_checked.wait(10)
            return inj.fire_count(site)

    def other_thread():
        assert ev_armed.wait(10)
        assert faults.get() is None            # activation never leaks
        faults.maybe_raise(site)               # must be a no-op
        ev_checked.set()
        return True

    fired, ok = run_threads([armed_thread, other_thread])
    assert fired == 1 and ok is True
    assert faults.get() is None


# -------------------------------------------------- instrumented locks


def test_instrumented_lock_contention_counters():
    lk = InstrumentedLock("bench")
    held = []

    def body():
        for _ in range(50):
            with lk:
                held.append(1)
        return True

    run_threads([body] * 4)
    st = lk.stats()
    assert st["acquisitions"] == 200 and len(held) == 200
    assert 0 <= st["contended"] <= 200
    assert st["wait_s"] >= 0.0 and st["hold_s"] >= 0.0
    lk.reset_stats()
    assert lk.stats()["acquisitions"] == 0


def test_controller_detects_self_deadlock():
    lk = InstrumentedLock("stuck")
    lk.acquire()                               # held by the main thread

    def body():
        with lk:
            return True

    with pytest.raises(Deadlock):
        ScheduleController(seed=0, max_switches=500).run([body],
                                                         timeout_s=5)
    lk.release()


# ------------------------------------------------- serving integration


@pytest.fixture(scope="module")
def dataset():
    table = make_table("tpch", 4_000, seed=1)
    return table, column_stats(table, ATTRS)


def _pkg(res):
    order = np.argsort(res.idx, kind="stable")
    return np.asarray(res.idx)[order], np.asarray(res.mult)[order]


def test_engine_concurrent_sessions_match_sequential(dataset):
    """Concurrent sessions over ONE shared engine + QCache return the
    same packages as sequential solves of the same queries."""
    table, stats = dataset
    queries = [instantiate(Q2_TPCH, stats, 2.0),
               instantiate(Q4_TPCH, stats, 2.0)]

    def build():
        eng = PackageQueryEngine(table, ATTRS, d_f=20, alpha=600,
                                 seed=0, cache=QCache())
        eng.partition()
        return eng

    seq = build()
    baseline = [seq.session(seed=100 + i).solve(q, ilp_kwargs=ILP_KW)
                for i, q in enumerate(queries)]
    assert all(r.feasible for r in baseline)

    conc = build()

    def body(i):
        def run():
            # two sessions per query, same seeds as the baseline pass
            return conc.session(seed=100 + (i % 2)).solve(
                queries[i % 2], ilp_kwargs=ILP_KW)

        return run

    results = run_threads([body(i) for i in range(4)], timeout_s=300)
    for i, res in enumerate(results):
        assert res.feasible, f"thread {i} infeasible: {res.status}"
        want_idx, want_mult = _pkg(baseline[i % 2])
        got_idx, got_mult = _pkg(res)
        assert np.array_equal(got_idx, want_idx)
        assert np.array_equal(got_mult, want_mult)
        # same package, so obj may differ only by summation order
        assert np.isclose(res.obj, baseline[i % 2].obj, rtol=1e-12)
    st = conc.cache.stats_snapshot()
    assert st.stores >= 1
    assert st.hits + st.misses >= len(results)


def test_scheduler_concurrent_submits_lose_nothing(lm_request):
    from repro.serving import PackageScheduler

    sched = PackageScheduler(hbm_budget_bytes=2e9, flop_budget=1e14,
                             max_batch=16)
    rng = np.random.default_rng(0)
    reqs = [[lm_request(t * 1000 + i, int(rng.integers(16, 256)),
                        int(rng.integers(16, 128)),
                        float(rng.uniform(0.01, 1.0))) for i in range(40)]
            for t in range(4)]

    admitted = []
    adm_lock = threading.Lock()

    def submitter(t):
        def run():
            for r in reqs[t]:
                sched.submit(r)
            return True

        return run

    def ticker():
        for _ in range(6):
            batch = sched.tick()
            with adm_lock:
                admitted.extend(r.rid for r in batch)
        return True

    run_threads([submitter(t) for t in range(4)] + [ticker, ticker],
                timeout_s=120)
    # drain what is left
    for _ in range(40):
        batch = sched.tick()
        with adm_lock:
            admitted.extend(r.rid for r in batch)
        if not batch and len(sched.queue) == 0:
            break
    all_rids = {r.rid for group in reqs for r in group}
    assert sorted(admitted) == sorted(all_rids), \
        "requests were lost or duplicated across concurrent submits"
    assert sched.admitted_total == len(all_rids)
    assert len(sched.queue) == 0 and len(sched._store) == 0
