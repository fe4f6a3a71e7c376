"""Resilience: every Solve Guard promise is pinned by a forced failure.

Deterministic fault injection (``repro.runtime.faults``) drives each
degradation path the guard contract advertises — transient-read retries,
Binv drift recovery, budget preemption, dead-shard fallback, the
degradation ladder — and every test asserts the pipeline comes back with
a defined status instead of hanging or raising.
"""
import numpy as np
import pytest

from repro.core import guard
from repro.core import relation as relation_mod
from repro.core.bucketing import ArraySource
from repro.core.dual_reducer import dual_reducer
from repro.core.engine import PackageQueryEngine
from repro.core.hardness import TEMPLATES, column_stats, instantiate
from repro.core.lp import BUDGET, OPTIMAL, solve_lp, solve_lp_np
from repro.core.paql import Constraint, PackageQuery
from repro.core.relation import (MemmapRelation, SourceRelation,
                                 configure_retries)
from repro.data.synth_tables import make_table
from repro.runtime import faults

ILP_KW = dict(max_nodes=100, time_limit_s=10)


@pytest.fixture(autouse=True)
def fast_retries():
    old = configure_retries()
    configure_retries(base_s=1e-4, max_s=1e-3)
    yield
    configure_retries(**old)


def _mat(n=20, k=3):
    return np.arange(float(n * k)).reshape(n, k)


# ------------------------------------------------------- transient reads


def test_chunk_read_retry_recovers():
    X = _mat()
    rel = MemmapRelation(X, ["a", "b", "c"], chunk_rows=5)
    with faults.injected(seed=1,
                         arms={faults.CHUNK_READ: dict(times=2)}) as inj:
        got = np.vstack(list(rel.chunks()))
    np.testing.assert_allclose(got, X)
    assert inj.fire_count(faults.CHUNK_READ) == 2


def test_chunk_read_retry_gives_up():
    rel = MemmapRelation(_mat(), ["a", "b", "c"], chunk_rows=5)
    with faults.injected(seed=1,
                         arms={faults.CHUNK_READ: dict(times=None)}):
        with pytest.raises(OSError, match="giving up after 4 attempts"):
            list(rel.chunks())


def test_gather_read_retry_recovers():
    X = _mat()
    rel = MemmapRelation(X, ["a", "b", "c"])
    idx = np.array([7, 0, 13, 7])
    with faults.injected(seed=2,
                         arms={faults.GATHER_READ: dict(times=1)}) as inj:
        out = rel.gather_rows(idx, ("b",))["b"]
    np.testing.assert_allclose(out, X[idx, 1])
    assert inj.fire_count(faults.GATHER_READ) == 1


def test_backoff_capped_and_deterministic(monkeypatch):
    """Delays follow min(max_s, base_s * 2^k) with seeded jitter — the
    schedule is capped and replays identically."""
    configure_retries(tries=4, base_s=0.1, max_s=0.15, seed=5)
    rel = MemmapRelation(_mat(), ["a", "b", "c"], chunk_rows=100)

    def _delays():
        slept = []
        monkeypatch.setattr(relation_mod.time, "sleep", slept.append)
        with faults.injected(seed=1,
                             arms={faults.CHUNK_READ: dict(times=3)}):
            list(rel.chunks())
        return slept

    d1, d2 = _delays(), _delays()
    assert d1 == d2                      # deterministic replay
    rng = np.random.default_rng(5)
    exp = [min(0.15, 0.1 * 2.0 ** k) * (0.5 + rng.random())
           for k in range(3)]
    np.testing.assert_allclose(d1, exp)
    assert max(d1) <= 0.15 * 1.5 + 1e-12  # capped


def test_flaky_source_scan_delivers_rows_exactly_once():
    X = _mat(23, 3)
    src = faults.FlakySource(ArraySource(X), fail_chunks=(1,), fail_times=2)
    rel = SourceRelation(src, ["a", "b", "c"], chunk_rows=4)
    got = np.vstack(list(rel.chunks()))
    np.testing.assert_allclose(got, X)
    assert src.raised == 2


def test_flaky_source_scan_gives_up():
    src = faults.FlakySource(ArraySource(_mat()), fail_chunks=(0,),
                             fail_times=99)
    rel = SourceRelation(src, ["a", "b", "c"], chunk_rows=4)
    with pytest.raises(OSError, match="source scan: giving up"):
        list(rel.chunks())


# -------------------------------------------------- numerical health / LP


def _random_lp(seed, n=160, m=6):
    rng = np.random.default_rng(seed)
    c = rng.normal(size=n)
    A = rng.normal(size=(m, n))
    ub = rng.integers(1, 4, size=n).astype(float)
    x0 = rng.uniform(0, 1, n) * ub
    act = A @ x0
    width = np.abs(rng.normal(size=m)) * 2
    bl = act - width * rng.uniform(0, 1, m)
    bu = act + width * rng.uniform(0, 1, m)
    return c, A, bl, bu, ub


def test_binv_perturbation_detected_and_recovered():
    """An injected Binv corruption trips the drift monitor, forces a
    refactorization, and the solve still reaches the clean optimum."""
    c, A, bl, bu, ub = _random_lp(7, n=240, m=14)
    clean = solve_lp_np(c, A, bl, bu, ub)
    assert clean.status == OPTIMAL and clean.iters > 20
    mon = guard.NumericalMonitor(drift_check_every=4)
    with faults.injected(seed=0, arms={faults.BINV: dict(times=2, after=1,
                                                         scale=1e-2)}) as inj:
        res = solve_lp_np(c, A, bl, bu, ub, monitor=mon)
    assert inj.fire_count(faults.BINV) >= 1
    assert res.status == OPTIMAL
    assert mon.drift_refactors >= 1
    assert abs(res.obj - clean.obj) <= 1e-6 * (1 + abs(clean.obj))


def test_budget_pivot_truncation_is_reported():
    c, A, bl, bu, ub = _random_lp(4)
    b = guard.SolveBudget(max_pivots=3).start()
    res = solve_lp_np(c, A, bl, bu, ub, budget=b)
    assert res.status == BUDGET
    assert any(n.startswith("budget:") for n in res.notes)
    assert b.pivots_spent > 0


def test_budget_deadline_preempts_lp():
    c, A, bl, bu, ub = _random_lp(5)
    b = guard.SolveBudget(deadline_s=0.0).start()
    res = solve_lp(c, A, bl, bu, ub, budget=b)
    assert res.status == BUDGET
    res_np = solve_lp_np(c, A, bl, bu, ub, budget=b)
    assert res_np.status == BUDGET


def test_warm_start_rejection_is_surfaced():
    c, A, bl, bu, ub = _random_lp(6)
    m, n = A.shape
    bad = (np.zeros(m, np.int64), np.zeros(n + m, bool))  # duplicate basis
    res = solve_lp_np(c, A, bl, bu, ub, warm_start=bad)
    assert res.status == OPTIMAL
    assert any("warm_start_rejected" in note for note in res.notes)


def test_dist_shard_fault_falls_back_to_single_host():
    jax = pytest.importorskip("jax")
    from repro.core.distributed import solve_lp_dist
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    c, A, bl, bu, ub = _random_lp(7)
    ref = solve_lp_np(c, A, bl, bu, ub)
    with faults.injected(seed=0,
                         arms={faults.SHARD: dict(times=1)}) as inj:
        res = solve_lp_dist(c, A, bl, bu, ub, mesh=mesh)
    assert inj.fire_count(faults.SHARD) == 1
    assert any("single_host_fallback" in note for note in res.notes)
    assert res.pivot_stats.get("fallback") == 1
    assert res.status == ref.status == OPTIMAL
    assert abs(res.obj - ref.obj) <= 1e-6 * (1 + abs(ref.obj))
    # the report a caller of engine.solve reads shows the fallback
    report = guard.SolveReport()
    report.absorb_lp(res)
    assert report.fallbacks == [guard.HOST_FALLBACK]
    assert report.finalize(True).status == guard.OK


def _failing_update_step(monkeypatch, fail_on_call):
    """Make the mesh update step raise an XLA runtime error on its
    ``fail_on_call``-th call (1 = the call that compiles it)."""
    import jax
    from repro.core import distributed
    real = distributed._cached_steps

    def steps(*a, **k):
        pq, update, refresh = real(*a, **k)
        calls = []

        def failing(*args):
            calls.append(1)
            if len(calls) == fail_on_call:
                raise jax.errors.JaxRuntimeError("INTERNAL: shard lost")
            return update(*args)
        return pq, failing, refresh

    monkeypatch.setattr(distributed, "_cached_steps", steps)


def test_dist_runtime_error_after_first_run_falls_back(monkeypatch):
    jax = pytest.importorskip("jax")
    from repro.core.distributed import solve_lp_dist
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    c, A, bl, bu, ub = _random_lp(7)
    ref = solve_lp_np(c, A, bl, bu, ub)
    _failing_update_step(monkeypatch, fail_on_call=2)
    res = solve_lp_dist(c, A, bl, bu, ub, mesh=mesh)
    assert any("single_host_fallback: ShardFailure" in note
               for note in res.notes)
    assert res.status == ref.status == OPTIMAL
    assert abs(res.obj - ref.obj) <= 1e-6 * (1 + abs(ref.obj))


def test_dist_error_while_compiling_propagates(monkeypatch):
    """An XLA error from a step's first (compiling) call is no shard
    failure: it reaches the caller instead of a silent host re-solve."""
    jax = pytest.importorskip("jax")
    from repro.core.distributed import solve_lp_dist
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    c, A, bl, bu, ub = _random_lp(7)
    _failing_update_step(monkeypatch, fail_on_call=1)
    with pytest.raises(jax.errors.JaxRuntimeError, match="shard lost"):
        solve_lp_dist(c, A, bl, bu, ub, mesh=mesh)


# ------------------------------------------------------ degradation ladder


def _dr_query(lo=10, hi=20):
    return PackageQuery("obj", maximize=True, constraints=(
        Constraint(None, lo, hi), Constraint("a", lo=4.5 * lo, hi=5.5 * hi)))


def test_dual_reducer_degraded_rounding_rung(monkeypatch):
    """With the sub-ILP solver dead, the ladder's terminal rung rounds
    and repairs the LP relaxation instead of failing dry."""
    from repro.core import ilp as ilp_mod

    def _dead_ilp(*a, **k):
        n = len(a[0])
        return ilp_mod.ILPResult(ilp_mod.ILP_LIMIT, np.zeros(n), np.inf,
                                 0, 0.0)

    monkeypatch.setattr("repro.core.dual_reducer.ilp_mod.solve_ilp",
                        _dead_ilp)
    rng = np.random.default_rng(0)
    table = {"obj": rng.normal(10, 3, 2000), "a": rng.normal(5, 1, 2000)}
    q = _dr_query()
    report = guard.SolveReport(budget=guard.SolveBudget(),
                               monitor=guard.NumericalMonitor())
    res = dual_reducer(q, table, np.arange(2000), q=50,
                       budget=report.budget, report=report)
    assert res.feasible
    assert res.status == "degraded_rounded"
    assert "degraded_rounded" in report.fallbacks
    assert q.check_package(table, res.idx, res.mult)


def test_dual_reducer_no_ladder_fails_dry(monkeypatch):
    from repro.core import ilp as ilp_mod

    def _dead_ilp(*a, **k):
        n = len(a[0])
        return ilp_mod.ILPResult(ilp_mod.ILP_LIMIT, np.zeros(n), np.inf,
                                 0, 0.0)

    monkeypatch.setattr("repro.core.dual_reducer.ilp_mod.solve_ilp",
                        _dead_ilp)
    rng = np.random.default_rng(0)
    table = {"obj": rng.normal(10, 3, 500), "a": rng.normal(5, 1, 500)}
    res = dual_reducer(_dr_query(), table, np.arange(500), q=50,
                       ladder=False)
    assert not res.feasible
    assert res.status == "ilp_infeasible"


# --------------------------------------------------------- engine contract


def _memmap_engine(n=2000, seed=0):
    t = make_table("tpch", n, seed=seed)
    attrs = ["price", "quantity", "discount", "tax"]
    X = np.stack([np.asarray(t[a], np.float64) for a in attrs], axis=1)
    rel = MemmapRelation(X, attrs, chunk_rows=max(n // 7, 16))
    eng = PackageQueryEngine(rel, attrs, d_f=8, alpha=300, seed=seed)
    eng._stats = column_stats(t, attrs)  # stats off the resident dict
    return eng


def _query(eng, h=2.0, template="Q2_TPCH"):
    return instantiate(TEMPLATES[template], eng._stats, h)


@pytest.mark.parametrize("site,arm", [
    (faults.CHUNK_READ, dict(times=2)),
    (faults.GATHER_READ, dict(times=None, prob=0.3)),
    (faults.BINV, dict(times=3, after=1, scale=1e-3)),
    (faults.SHARD, dict(times=1)),
])
def test_engine_never_raises_under_faults(site, arm):
    """The guard contract: under injected faults every engine.solve
    returns a report with a defined status — no hangs, no exceptions."""
    eng = _memmap_engine()
    eng.partition()
    q = _query(eng)
    with faults.injected(seed=3, arms={site: arm}):
        res = eng.solve(q, ilp_kwargs=ILP_KW)
    assert res.report is not None
    assert res.report.status in guard.STATUSES
    if res.feasible:
        assert q.check_package(eng.table, res.idx, res.mult)


def test_engine_reports_fault_retries():
    eng = _memmap_engine()
    eng.partition()
    q = _query(eng)
    with faults.injected(seed=3,
                         arms={faults.GATHER_READ: dict(times=3)}) as inj:
        res = eng.solve(q, ilp_kwargs=ILP_KW)
    assert inj.fire_count(faults.GATHER_READ) == 3
    assert res.report.fault_retries >= 3
    assert res.report.status in (guard.OK, guard.DEGRADED)


def test_engine_budget_exhaustion_has_defined_status():
    eng = _memmap_engine()
    eng.partition()
    q = _query(eng, h=9.0)
    b = guard.SolveBudget(max_pivots=1)
    res = eng.solve(q, ilp_kwargs=ILP_KW, budget=b)
    r = res.report
    assert r.status in guard.STATUSES
    # the cascade must have either descended on budget or stopped with
    # the budget status — never a silent full-effort run
    assert ("budget_descend" in r.fallbacks
            or r.status in (guard.BUDGET_EXHAUSTED, guard.DEGRADED))
    assert b.pivots_spent <= 64  # floor-granularity slack, not a full run


def test_engine_contains_unexpected_errors():
    eng = _memmap_engine()
    eng.partition()
    q = _query(eng)

    def _boom(*a, **k):
        raise ValueError("synthetic pipeline bug")

    import repro.core.engine as engine_mod
    orig = engine_mod.progressive_shading
    engine_mod.progressive_shading = _boom
    try:
        res = eng.solve(q)
    finally:
        engine_mod.progressive_shading = orig
    assert res.report.status == guard.ERROR
    assert not res.feasible
    assert any("synthetic pipeline bug" in note for note in res.report.notes)
    with pytest.raises(ValueError):
        engine_mod.progressive_shading = _boom
        try:
            eng.solve(q, guarded=False)
        finally:
            engine_mod.progressive_shading = orig
