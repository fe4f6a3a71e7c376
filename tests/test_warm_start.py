"""Warm-start contract: warm-started solves return the same
objective/certificate as cold solves (all three twins), across the
dual_reducer auxiliary-LP path, an added-columns shading-style case, and
the progressive-shading cascade; invalid warm bases fall back to cold.

These are seed-parametrised property tests so they run even without
hypothesis; a hypothesis-widened sweep is added when it is installed.
"""
import importlib.util

import numpy as np
import pytest

from repro.core.lp import (OPTIMAL, WarmStart, prepare_lp, solve_lp,
                           solve_lp_np, solve_lp_resume, verify_optimality)
from repro.core.lp_kernel import solve_lp_kernel

HAS_HYPOTHESIS = importlib.util.find_spec("hypothesis") is not None


def _random_lp(seed, one_sided=True):
    rng = np.random.default_rng(seed)
    n = int(rng.integers(5, 60))
    m = int(rng.integers(1, 6))
    c = rng.normal(size=n)
    A = rng.normal(size=(m, n))
    ub = rng.integers(1, 4, size=n).astype(float)
    x0 = rng.uniform(0, 1, n) * ub
    act = A @ x0
    width = np.abs(rng.normal(size=m)) * 2
    bl = act - width * rng.uniform(0, 1, m)
    bu = act + width * rng.uniform(0, 1, m)
    if one_sided:
        for i in range(m):
            r = rng.random()
            if r < 0.2:
                bl[i] = -np.inf
            elif r < 0.3:
                bu[i] = np.inf
    return c, A, bl, bu, ub


TWINS = [("np", solve_lp_np), ("jax", solve_lp), ("kernel", solve_lp_kernel)]


@pytest.mark.parametrize("name,solver",
                         TWINS, ids=[t[0] for t in TWINS])
def test_warm_restart_from_own_basis(name, solver):
    """Re-solving from a solve's own final basis is optimal immediately
    with the same objective and a valid certificate."""
    seeds = range(12) if name == "np" else range(6)
    for seed in seeds:
        c, A, bl, bu, ub = _random_lp(seed)
        cold = solver(c, A, bl, bu, ub)
        if cold.status != OPTIMAL:
            continue
        warm = solver(c, A, bl, bu, ub, warm_start=cold)
        assert warm.status == OPTIMAL
        assert warm.obj == pytest.approx(cold.obj, rel=1e-6, abs=1e-6)
        ok, msg = verify_optimality(warm, c, A, bl, bu, ub)
        assert ok, (seed, msg)
        assert warm.iters <= 2, (seed, warm.iters)


@pytest.mark.parametrize("name,solver",
                         TWINS, ids=[t[0] for t in TWINS])
def test_warm_tightened_ub_matches_cold(name, solver):
    """Dual Reducer auxiliary-LP shape: same LP with tightened upper
    bounds, warm-started from the loose solve's basis (the textbook
    dual-simplex warm start).  Same optimum as cold, fewer total iters."""
    seeds = range(15) if name == "np" else range(6)
    warm_total = cold_total = compared = 0
    for seed in seeds:
        c, A, bl, bu, ub = _random_lp(seed, one_sided=False)
        lp1 = solver(c, A, bl, bu, ub)
        if lp1.status != OPTIMAL:
            continue
        E = float(np.sum(lp1.x))
        ub_aux = np.minimum(ub, max(E / 7.0, 1e-9))
        cold = solver(c, A, bl, bu, ub_aux)
        warm = solver(c, A, bl, bu, ub_aux, warm_start=lp1)
        assert warm.status == cold.status, seed
        if cold.status != OPTIMAL:
            continue
        compared += 1
        assert warm.obj == pytest.approx(cold.obj, rel=1e-6, abs=1e-6)
        ok, msg = verify_optimality(warm, c, A, bl, bu, ub_aux)
        assert ok, (seed, msg)
        warm_total += warm.iters
        cold_total += cold.iters
    assert compared > 0
    assert warm_total <= cold_total, (warm_total, cold_total)


def test_warm_added_columns_shading_style():
    """Shading cascade shape: a 'parent' LP whose columns are group
    representatives, and a 'child' LP whose columns are perturbed copies
    (members) of each parent column.  The parent basis is re-mapped to one
    child per basic parent (what shading.map_warm_basis does); answers
    match the cold solve and the warm cascade needs fewer total pivots."""
    warm_total = cold_total = compared = 0
    for seed in range(12):
        rng = np.random.default_rng(1000 + seed)
        n_par = int(rng.integers(20, 50))
        m = int(rng.integers(2, 5))
        kids = 3
        c_par = rng.normal(size=n_par)
        A_par = rng.normal(size=(m, n_par))
        # children cluster tightly around their parent representative
        A_full = (np.repeat(A_par, kids, axis=1)
                  + 0.05 * rng.normal(size=(m, n_par * kids)))
        c_full = np.repeat(c_par, kids) + 0.05 * rng.normal(size=n_par * kids)
        ub_par = np.full(n_par, 2.0)
        ub_full = np.full(n_par * kids, 2.0)
        x0 = rng.uniform(0, 1, n_par) * ub_par
        act = A_par @ x0
        width = np.abs(rng.normal(size=m)) * 2
        bl = act - width * rng.uniform(0, 1, m)
        bu = act + width * rng.uniform(0, 1, m)

        parent = solve_lp_np(c_par, A_par, bl, bu, ub_par)
        if parent.status != OPTIMAL:
            continue
        n_full = n_par * kids
        # basic parent j -> its first child (j * kids); slack i shifts
        basis = np.where(parent.basis >= n_par,
                         n_full + (parent.basis - n_par),
                         np.minimum(parent.basis, n_par - 1) * kids)
        at_upper = np.zeros(n_full + m, bool)
        at_upper[:n_full] = np.repeat(parent.at_upper[:n_par], kids)
        at_upper[n_full:] = parent.at_upper[n_par:]
        cold = solve_lp_np(c_full, A_full, bl, bu, ub_full)
        warm = solve_lp_np(c_full, A_full, bl, bu, ub_full,
                           warm_start=WarmStart(basis, at_upper))
        assert warm.status == cold.status, seed
        if cold.status != OPTIMAL:
            continue
        compared += 1
        assert warm.obj == pytest.approx(cold.obj, rel=1e-6, abs=1e-6)
        ok, msg = verify_optimality(warm, c_full, A_full, bl, bu, ub_full)
        assert ok, (seed, msg)
        warm_total += warm.iters
        cold_total += cold.iters
    assert compared > 0
    assert warm_total < cold_total, (warm_total, cold_total)


def test_invalid_warm_start_falls_back_to_cold():
    """Garbage warm bases (duplicates, out-of-range, singular) are
    rejected by validation and produce the cold-start answer."""
    c, A, bl, bu, ub = _random_lp(3)
    m, n = A.shape
    cold = solve_lp_np(c, A, bl, bu, ub)
    bad_bases = [
        np.zeros(m, np.int64),                      # duplicates (m > 1)
        np.full(m, n + m + 99),                     # out of range
        np.arange(m),                               # possibly singular
        np.arange(m + 1),                           # wrong shape
    ]
    for bad in bad_bases:
        res = solve_lp_np(c, A, bl, bu, ub,
                          warm_start=WarmStart(bad, None))
        assert res.status == cold.status
        if cold.status == OPTIMAL:
            assert res.obj == pytest.approx(cold.obj, rel=1e-9)


def test_dual_reducer_warm_aux_path():
    """dual_reducer with warm starts (aux LP + fallback re-solves) returns
    the same package quality as before; lp_bound unchanged."""
    from repro.core.dual_reducer import dual_reducer
    from repro.core.paql import Constraint, PackageQuery

    rng = np.random.default_rng(11)
    n = 4000
    table = {"count1": np.ones(n), "val": rng.normal(14, 1.5, n),
             "obj": rng.normal(size=n)}
    query = PackageQuery(
        objective_attr="obj", maximize=False,
        constraints=(Constraint(None, 15, 45),
                     Constraint("val", 14 * 30 - 9, 14 * 30 + 9)),
        repeat=0)
    S = np.arange(n)
    res = dual_reducer(query, table, S, q=60, rng=np.random.default_rng(0))
    assert res.feasible, res.status
    # warm-starting lp1 from its own previous basis must not change anything
    from repro.core.lp import solve_lp_np as _s
    c, A, bl, bu, ub = query.matrices(table, S)
    lp1 = _s(c, A, bl, bu, ub)
    res_w = dual_reducer(query, table, S, q=60,
                         rng=np.random.default_rng(0), warm_start=lp1)
    assert res_w.feasible
    assert res_w.lp_obj == pytest.approx(res.lp_obj, rel=1e-9)
    assert res_w.obj == pytest.approx(res.obj, rel=1e-6)


def test_progressive_shading_warm_equals_cold():
    """The warm-started cascade produces the same package quality as the
    all-cold cascade (identical LPs, only iteration counts may differ)."""
    from repro.core.engine import PackageQueryEngine
    from repro.core.hardness import Q1_SDSS, column_stats, instantiate
    from repro.core.shading import progressive_shading
    from repro.data.synth_tables import make_table

    table = make_table("sdss", 8000, seed=5)
    attrs = ["tmass_prox", "j", "h", "k"]
    eng = PackageQueryEngine(table, attrs, d_f=20, alpha=800, seed=0)
    eng.partition()
    q = instantiate(Q1_SDSS, column_stats(table, attrs), 3)
    kw = dict(ilp_kwargs=dict(max_nodes=150, time_limit_s=10),
              rng=np.random.default_rng(0))
    res_w = progressive_shading(eng.hierarchy, q, table,
                                warm_starts=True, **kw)
    res_c = progressive_shading(eng.hierarchy, q, table,
                                warm_starts=False, **kw)
    assert res_w.feasible == res_c.feasible
    if res_w.feasible:
        assert res_w.obj == pytest.approx(res_c.obj, rel=0.05, abs=0.5)


def _child_bounds(res, lb, ub, lo_side):
    """A B&B child of ``res``: the bounds of its most fractional (hence
    basic) variable cut at the floor, ``lo_side`` keeping the lower
    part; None where ``res.x`` is integral."""
    frac = np.abs(res.x - np.round(res.x))
    j = int(np.argmax(frac))
    if frac[j] < 1e-6:
        return None
    lb2, ub2 = np.array(lb, np.float64), np.array(ub, np.float64)
    if lo_side:
        ub2[j] = np.floor(res.x[j])
    else:
        lb2[j] = np.floor(res.x[j]) + 1
    return lb2, ub2


def _same_result(a, b):
    assert a.status == b.status
    assert a.iters == b.iters
    assert np.array_equal(a.x, b.x)
    assert a.obj == b.obj
    assert np.array_equal(a.basis, b.basis)
    assert np.array_equal(a.at_upper, b.at_upper)
    assert np.array_equal(a.y, b.y)


def _branching_lps(seeds):
    """(lp, optimum) of the seeds whose LP optimum is fractional."""
    for seed in seeds:
        lp = _random_lp(seed, one_sided=False)
        res = solve_lp_np(*lp)
        if res.status == OPTIMAL and _child_bounds(
                res, np.zeros(len(res.x)), lp[4], True) is not None:
            yield lp, res


@pytest.mark.parametrize("block", range(5))
@pytest.mark.parametrize("lo_side", [True, False], ids=["floor", "ceil"])
def test_resume_matches_warm_start(block, lo_side):
    """A bound-variant resumed from its parent's carried factors gives
    the LPResult of the same LP warm-started from the parent's basis,
    and so does its own child resumed from it."""
    compared = 0
    for (c, A, bl, bu, ub), parent in _branching_lps(
            range(8 * block, 8 * block + 8)):
        form = prepare_lp(c, A, bl, bu)
        lb, res = np.zeros(len(ub)), parent
        for _ in range(2):
            lb, ub = _child_bounds(res, lb, ub, lo_side)
            got = solve_lp_resume(form, lb, ub, res.factors)
            assert got is not None
            _same_result(got, solve_lp_np(
                c, A, bl, bu, ub, lb=lb,
                warm_start=(res.basis, res.at_upper)))
            compared += 1
            if got.status != OPTIMAL or _child_bounds(
                    got, lb, ub, lo_side) is None:
                break
            res = got
    assert compared >= 3


def test_siblings_share_a_parent_untouched():
    """Both children of one node resume from its one set of factors; the
    first child's pivots write none of the parent's arrays."""
    tried = 0
    for (c, A, bl, bu, ub), parent in _branching_lps(range(30)):
        f = parent.factors
        before = [a.copy() for a in (f.basis, f.at_upper, f.Binv, f.y, f.d)]
        form = prepare_lp(c, A, bl, bu)
        for lo_side in (True, False):
            lb2, ub2 = _child_bounds(parent, np.zeros(len(ub)), ub, lo_side)
            _same_result(solve_lp_resume(form, lb2, ub2, f),
                         solve_lp_np(c, A, bl, bu, ub2, lb=lb2,
                                     warm_start=parent.warm))
        for a, b in zip((f.basis, f.at_upper, f.Binv, f.y, f.d), before):
            assert np.array_equal(a, b)
        tried += 1
    assert tried >= 5


@pytest.mark.parametrize("max_iters", [5000, 2, 3],
                         ids=["optimal", "cut-at-2", "cut-at-3"])
def test_final_factors_are_fresh(max_iters):
    """The answer and the carried factors are those of a fresh
    factorization of the final basis, bit for bit, also where the pivot
    limit cuts the solve on rank-1-updated factors."""
    for seed in range(12):
        c, A, bl, bu, ub = _random_lp(seed)
        res = solve_lp_np(c, A, bl, bu, ub, max_iters=max_iters)
        form = prepare_lp(c, A, bl, bu)
        f = res.factors
        Binv = np.linalg.inv(form.A[:, f.basis])
        y = Binv.T @ form.cf[f.basis]
        d = form.cf - form.A.T @ y
        d[f.basis] = 0.0
        assert np.array_equal(f.Binv, Binv)
        assert np.array_equal(f.y, y) and np.array_equal(f.d, d)
        assert np.array_equal(res.y, y * form.scale)


def test_resume_returns_none_on_an_empty_box():
    c, A, bl, bu, ub = _random_lp(2)
    parent = solve_lp_np(c, A, bl, bu, ub)
    lb = np.zeros(len(ub))
    lb[0] = ub[0] + 1.0
    assert solve_lp_resume(prepare_lp(c, A, bl, bu), lb, ub,
                           parent.factors) is None


if HAS_HYPOTHESIS:
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=40, deadline=None)
    @given(st.integers(0, 10_000))
    def test_warm_matches_cold_property(seed):
        """Property: warm-started numpy solves agree with cold solves."""
        c, A, bl, bu, ub = _random_lp(seed)
        cold = solve_lp_np(c, A, bl, bu, ub)
        if cold.status != OPTIMAL:
            return
        rng = np.random.default_rng(seed)
        ub2 = np.minimum(ub, np.maximum(rng.uniform(0.3, 1.0) * ub, 1.0))
        c2 = solve_lp_np(c, A, bl, bu, ub2)
        w2 = solve_lp_np(c, A, bl, bu, ub2, warm_start=cold)
        assert w2.status == c2.status
        if c2.status == OPTIMAL:
            assert abs(w2.obj - c2.obj) <= 1e-6 * (1 + abs(c2.obj))
            ok, msg = verify_optimality(w2, c, A, bl, bu, ub2)
            assert ok, msg
