"""ILP branch & bound + heuristics vs exhaustive enumeration."""
import math
from statistics import NormalDist

import numpy as np
import pytest

pytest.importorskip("hypothesis", reason="hypothesis not installed")
from hypothesis import given, settings, strategies as st

import repro.core.ilp as ilp_mod
from repro.core.guard import SolveReport
from repro.core.ilp import (ILP_OPTIMAL, brute_force_ilp, solve_ilp,
                            _swap_search)


def _random_ilp(seed):
    rng = np.random.default_rng(seed)
    n = int(rng.integers(3, 9))
    m = int(rng.integers(1, 4))
    c = rng.normal(size=n)
    A = rng.normal(size=(m, n))
    ub = rng.integers(1, 3, size=n).astype(float)
    x0 = rng.integers(0, 2, n).astype(float)
    act = A @ x0
    bl = act - np.abs(rng.normal(size=m))
    bu = act + np.abs(rng.normal(size=m))
    return c, A, bl, bu, ub


@settings(max_examples=40, deadline=None)
@given(st.integers(0, 10_000))
def test_ilp_matches_brute_force(seed):
    c, A, bl, bu, ub = _random_ilp(seed)
    r1 = solve_ilp(c, A, bl, bu, ub)
    r2 = brute_force_ilp(c, A, bl, bu, ub)
    assert r1.feasible == r2.feasible
    if r1.feasible and r1.status == ILP_OPTIMAL:
        assert abs(r1.obj - r2.obj) < 1e-6


def test_ilp_infeasible():
    c = np.ones(4)
    A = np.ones((1, 4))
    r = solve_ilp(c, A, np.array([10.0]), np.array([np.inf]), np.ones(4))
    assert not r.feasible


def test_ilp_solution_is_integral_and_feasible():
    rng = np.random.default_rng(5)
    n = 200
    c = rng.normal(size=n)
    A = np.stack([np.ones(n), rng.normal(10, 2, n)])
    bl = np.array([10.0, 95.0])
    bu = np.array([20.0, 160.0])
    r = solve_ilp(c, A, bl, bu, np.ones(n))
    assert r.feasible
    assert np.all(np.abs(r.x - np.round(r.x)) < 1e-9)
    act = A @ r.x
    assert np.all(act >= bl - 1e-6) and np.all(act <= bu + 1e-6)


def test_swap_search_repairs_tight_window():
    """The tight-BETWEEN regime that defeats naive rounding."""
    rng = np.random.default_rng(11)
    n = 1500
    vals = rng.normal(14, 1.2, n)
    c = np.abs(rng.normal(1, 0.5, n))
    A = np.stack([np.ones(n), vals])
    target = 30 * 14.0
    bl = np.array([15.0, target - 0.5])
    bu = np.array([45.0, target + 0.5])     # width-1 window on a sum of ~30
    from repro.core.lp import solve_lp_np
    root = solve_lp_np(c, A, bl, bu, np.ones(n))
    assert root.status == 0
    x, obj = _swap_search(root.x, c, A, bl, bu, np.zeros(n), np.ones(n), 1e-6)
    assert x is not None
    act = A @ x
    assert np.all(act >= bl - 1e-6) and np.all(act <= bu + 1e-6)


def _q2_ilp(seed, hardness, n=500):
    """A Dual Reducer sub-ILP of Q2_TPCH's shape: maximise price over n
    lineitem-like tuples, each used at most once, COUNT in [15, 45],
    quantity >=, discount <=, tax between, the bounds drawn at
    ``hardness`` as the paper's Sec. 4.1 draws them."""
    rng = np.random.default_rng(seed)
    qty = rng.integers(1, 51, n).astype(float)
    price = qty * rng.uniform(900.0, 2100.0, n)
    disc = price * rng.integers(0, 11, n) / 100
    tax = price * rng.integers(0, 9, n) / 100
    E, p = 30.0, 10.0 ** (-hardness / 3)
    z = NormalDist().inv_cdf

    def at(v, k):
        return E * v.mean() + k * math.sqrt(E) * v.std()

    zb = z(0.5 * (1 + p))
    A = np.stack([np.ones(n), qty, disc, tax])
    bl = np.array([15.0, at(qty, z(1 - p)), -np.inf, at(tax, -zb)])
    bu = np.array([45.0, np.inf, at(disc, z(p)), at(tax, zb)])
    return -price, A, bl, bu, np.ones(n)


CASES = ([("q2", s, h) for s, h in ((0, 1.0), (0, 7.0), (4, 7.0),
                                    (1, 3.0), (2, 5.0))]
         + [("small", s, None) for s in range(12)])


@pytest.mark.parametrize("kind,seed,hardness", CASES,
                         ids=[f"{k}-{s}-{h}" for k, s, h in CASES])
def test_carried_factorization_matches_warm_start(kind, seed, hardness,
                                                  monkeypatch):
    """Node LPs resumed from the parent's factorization search the same
    tree as node LPs warm-started from the parent's basis: the same
    status, nodes, pivots, objective and package."""
    ilp = _q2_ilp(seed, hardness) if kind == "q2" else _random_ilp(seed)
    runs = []
    for carry in (True, False):
        if not carry:   # no factors carried: every node goes the warm way
            monkeypatch.setattr(ilp_mod, "solve_lp_resume",
                                lambda *a, **k: None)
        rep = SolveReport()
        runs.append((solve_ilp(*ilp, max_nodes=300, report=rep), rep))
    (r1, rep1), (r0, rep0) = runs
    assert (r1.status, r1.nodes, r1.lp_iters) == \
        (r0.status, r0.nodes, r0.lp_iters)
    assert r1.obj == r0.obj and np.array_equal(r1.x, r0.x)
    assert rep1.ilp_node_lps == rep0.ilp_node_lps
    assert rep1.ilp_node_lps_carried == rep1.ilp_node_lps
    assert rep0.ilp_node_lps_carried == 0
    if kind == "q2":
        assert rep1.ilp_node_lps > 100


@pytest.mark.parametrize("kw", [dict(wave_width=4), dict(warm_nodes=False)],
                         ids=["wave4", "cold-nodes"])
def test_only_the_node_loop_carries(kw):
    """Batched waves and cold node LPs carry no factorization."""
    rep = SolveReport()
    r = solve_ilp(*_q2_ilp(0, 7.0), max_nodes=100, report=rep, **kw)
    assert r.feasible
    assert rep.ilp_node_lps > 0 and rep.ilp_node_lps_carried == 0
