"""Additional coverage: hardness properties (hypothesis),
hierarchy/neighbor invariants, local predicates, paql."""
import numpy as np
import pytest

pytest.importorskip("hypothesis", reason="hypothesis not installed")
from hypothesis import given, settings, strategies as st

from repro.core.hardness import (Q1_SDSS, instantiate, ndtri)
from repro.core.paql import Constraint, PackageQuery


# ------------------------------------------------------------- hardness


@settings(max_examples=50, deadline=None)
@given(st.floats(1e-9, 1 - 1e-9))
def test_ndtri_inverts_cdf(p):
    import math
    x = ndtri(p)
    phi = 0.5 * math.erfc(-x / math.sqrt(2))
    assert phi == pytest.approx(p, abs=1e-9)


@settings(max_examples=30, deadline=None)
@given(st.floats(0.5, 15.0), st.floats(0.6, 15.0))
def test_hardness_ordering_shrinks_feasible_region(h1, h2):
    stats = {"j": (14.82, 1.562), "h": (14.05, 1.657), "k": (13.73, 1.727),
             "tmass_prox": (14.45, 14.96)}
    lo, hi = min(h1, h2), max(h1, h2)
    if hi - lo < 1e-6:
        return
    qa = {c.attr: c for c in instantiate(Q1_SDSS, stats, lo).constraints
          if c.attr}
    qb = {c.attr: c for c in instantiate(Q1_SDSS, stats, hi).constraints
          if c.attr}
    assert qb["j"].lo >= qa["j"].lo            # >= bound tightens up
    assert qb["h"].hi <= qa["h"].hi            # <= bound tightens down
    assert (qb["k"].hi - qb["k"].lo) <= (qa["k"].hi - qa["k"].lo)


# -------------------------------------------------------------- engine


def test_local_predicate_excludes_tuples():
    from repro.core.engine import PackageQueryEngine
    rng = np.random.default_rng(0)
    n = 5000
    table = {
        "v": rng.normal(10, 2, n),
        "w": rng.uniform(0.5, 2.0, n),
        "ok": (rng.random(n) < 0.5).astype(np.float64),
    }
    q = PackageQuery("v", maximize=True,
                     constraints=(Constraint(None, 5, 15),
                                  Constraint("w", hi=20.0)),
                     predicate_attr="ok")
    eng = PackageQueryEngine(table, ["v", "w"], d_f=10, alpha=1000, seed=0)
    res = eng.solve(q)
    assert res.feasible
    assert np.all(table["ok"][res.idx] == 1.0)


def test_repeat_allows_multiplicity():
    from repro.core.engine import PackageQueryEngine
    rng = np.random.default_rng(1)
    n = 200
    table = {"v": rng.normal(10, 2, n), "w": rng.uniform(1, 2, n)}
    q = PackageQuery("v", maximize=True, repeat=2,
                     constraints=(Constraint(None, 10, 10),))
    eng = PackageQueryEngine(table, ["v", "w"], d_f=10, alpha=200, seed=0)
    res = eng.solve(q)
    assert res.feasible
    assert np.all(res.mult <= 3)               # REPEAT 2 -> up to 3 copies
    assert res.mult.sum() == 10
    # optimum takes the best tuple 3 times
    assert res.mult.max() == 3


def test_neighbor_sampling_respects_alpha():
    from repro.core.hierarchy import Hierarchy
    from repro.core.neighbor import neighbor_sampling
    rng = np.random.default_rng(2)
    table = {"a": rng.normal(size=20000), "b": rng.normal(size=20000)}
    hier = Hierarchy(table, ["a", "b"], d_f=20, alpha=500)
    assert hier.L >= 1
    s_prime = np.arange(min(5, hier.layers[hier.L].size))
    cand = neighbor_sampling(hier, hier.L, 500, s_prime, "a", True)
    assert len(cand) <= 500
    assert len(np.unique(cand)) == len(cand)
    # candidates are valid layer-(L-1) indices
    assert cand.min() >= 0
    assert cand.max() < hier.layers[hier.L - 1].size


def test_avg_constraint_linearisation():
    """AVG(P.a) >= t == SUM(a - t) >= 0."""
    rng = np.random.default_rng(3)
    n = 3000
    table = {"v": rng.normal(5, 1, n), "a": rng.normal(10, 3, n)}
    q = PackageQuery("v", maximize=True,
                     constraints=(Constraint(None, 8, 12),
                                  Constraint("a", lo=0.0, avg_target=12.0)))
    from repro.core.engine import PackageQueryEngine
    eng = PackageQueryEngine(table, ["v", "a"], d_f=10, alpha=1000, seed=0)
    res = eng.solve(q)
    assert res.feasible
    sel_avg = np.average(table["a"][res.idx], weights=res.mult)
    assert sel_avg >= 12.0 - 1e-6
