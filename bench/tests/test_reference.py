"""The plain reference: a package one unit outside a bound fails, a
malformed package fails, the LP bound is the LP optimum over the whole
relation, and a sound hierarchy passes while its float32 recomputation
(the control) does not."""
import numpy as np
import pytest

from bench.lib import queries as Q
from bench.lib import reference as ref

INF = float("inf")


def _cols(n=50, seed=0):
    rng = np.random.default_rng(seed)
    return {"p": rng.uniform(100, 200, n), "a": rng.integers(1, 50, n) * 1.0,
            "b": rng.uniform(0, 10, n)}


def _answer(q, idx, cols, obj=None):
    idx = np.asarray(idx, np.int64)
    mult = np.ones(len(idx))
    if obj is None:
        obj = float(cols[q.objective][idx].sum())
    return ref.Answer(q, "ok", idx, mult, obj)


def test_feasible_package_passes():
    cols = _cols()
    idx = [1, 4, 9]
    s = float(cols["a"][idx].sum())
    q = Q.Query("p", True, ((None, 3, 3), ("a", s, INF), ("b", -INF, 100.0)))
    r = ref.check_answers(cols, [_answer(q, idx, cols)], 50)
    assert r == {"not_ok": 0, "malformed": 0, "violation": 0.0,
                 "obj_rel_err": 0.0}


@pytest.mark.parametrize("which", ["ge", "le", "count"])
def test_one_unit_outside_a_bound_fails(which):
    cols = _cols()
    idx = [2, 3, 5, 7]
    s = float(cols["a"][idx].sum())
    cons = {"ge": ("a", s + 1.0, INF), "le": ("a", -INF, s - 1.0),
            "count": (None, 5, 45)}[which]
    q = Q.Query("p", False, ((None, 1, 45), cons))
    r = ref.check_answers(cols, [_answer(q, idx, cols)], 50)
    assert r["violation"] == pytest.approx(1.0)
    assert r["violation"] > 1e-6          # the configurations' limit


def test_malformed_and_not_ok_packages_count():
    cols = _cols()
    q = Q.Query("p", True, ((None, 1, 45),))
    dup = _answer(q, [1, 1, 2], cols)
    out = ref.Answer(q, "ok", np.array([1, 50]), np.ones(2), 0.0)
    frac = ref.Answer(q, "ok", np.array([1, 2]), np.array([1.0, 0.5]), 0.0)
    bad = ref.Answer(q, "degraded", np.array([1]), np.array([1.0]), 1.0)
    r = ref.check_answers(cols, [dup, out, frac, bad], 50)
    assert r["malformed"] == 3 and r["not_ok"] == 1


def test_wrong_objective_is_seen():
    cols = _cols()
    q = Q.Query("p", True, ((None, 1, 45),))
    a = _answer(q, [3, 4], cols)
    a.obj *= 1 + 1e-9
    assert ref.check_answers(cols, [a], 50)["obj_rel_err"] > 1e-10


def _hierarchy(n=30000, alpha=200, seed=0):
    from repro.core.hierarchy import Hierarchy
    from repro.data.synth_tables import make_table
    from bench.lib.harness import hierarchy_layers
    cols = make_table("sdss", n, seed)
    attrs = ["tmass_prox", "j", "h", "k"]
    h = Hierarchy(cols, attrs, d_f=100, alpha=alpha,
                  rng=np.random.default_rng(seed))
    X0 = np.stack([cols[a] for a in attrs], axis=1)
    return X0, hierarchy_layers(h)


def test_sound_hierarchy_passes_and_control_fails():
    X0, layers = _hierarchy()
    assert len(layers) == 2
    r = ref.check_hierarchy(X0, layers, 200, np.random.default_rng(1))
    assert r["partition_faults"] == 0 and r["rep_rel_err"] < 1e-13
    given = ref.control_reps(X0, layers)
    c = ref.check_hierarchy(X0, layers, 200, np.random.default_rng(1),
                            given=given)
    assert c["rep_rel_err"] > 1e-9


def test_hierarchy_faults_are_seen():
    X0, layers = _hierarchy()
    lay = layers[0]
    rng = np.random.default_rng(1)
    moved = lay.gid.copy()
    moved[lay.order[0]] += 1
    r = ref.check_hierarchy(X0, [ref.Layer(moved, lay.order, lay.offsets,
                                           lay.reps, lay.lo, lay.hi,
                                           lay.tree)], 10**9, rng)
    assert r["partition_faults"] > 0
    swapped = lay.order.copy()
    swapped[[0, -1]] = swapped[[-1, 0]]
    r = ref.check_hierarchy(X0, [ref.Layer(lay.gid, swapped, lay.offsets,
                                           lay.reps, lay.lo, lay.hi,
                                           lay.tree)], 10**9, rng)
    assert r["partition_faults"] > 0
    half = lay.reps.copy()
    half[0] *= 1 + 1e-6
    r = ref.check_hierarchy(X0, [ref.Layer(lay.gid, lay.order, lay.offsets,
                                           half, lay.lo, lay.hi, lay.tree)],
                            10**9, rng)
    assert r["partition_faults"] == 0 and r["rep_rel_err"] > 1e-7
    r = ref.check_hierarchy(X0, layers, 2, rng)
    assert r["partition_faults"] == 1     # top layer above alpha


def _lp_case(n, seed, maximize):
    from bench.lib import data
    from bench.tests.conftest import read_json
    cols = data.tpch_lineitem(n, seed, 30)
    tmpl = dict(read_json("traffic", "q2-h1to7")["template"],
                maximize=maximize)
    stats = Q.column_stats(cols, Q.template_attrs(tmpl))
    return cols, [Q.instantiate(tmpl, stats, h) for h in (1.0, 4.0, 7.0)]


def _full_lp(cols, q):
    from scipy.optimize import linprog
    n = len(cols["price"])
    A, b = [], []
    for attr, lo, hi in q.constraints:
        v = np.ones(n) if attr is None else cols[attr]
        if np.isfinite(hi):
            A.append(v)
            b.append(hi)
        if np.isfinite(lo):
            A.append(-v)
            b.append(-lo)
    sgn = 1.0 if q.maximize else -1.0
    res = linprog(-sgn * cols[q.objective], A_ub=np.array(A),
                  b_ub=np.array(b), bounds=(0, q.repeat + 1), method="highs")
    assert res.status == 0
    return -sgn * res.fun, res.x


@pytest.mark.parametrize("maximize", [True, False])
def test_lp_bound_is_the_whole_relations_lp_optimum(maximize):
    cols, qs = _lp_case(20000, 5, maximize)
    for q in qs:
        want, x = _full_lp(cols, q)
        start = np.flatnonzero(x > 1e-9)      # a feasible restricted LP
        assert ref.lp_bound(cols, q, start) == pytest.approx(want, rel=1e-9)


def test_lp_gap_of_a_poor_package_and_of_the_optimum():
    from scipy.optimize import milp, LinearConstraint, Bounds
    cols, qs = _lp_case(3000, 6, True)
    q = qs[1]
    lo = np.array([c[1] for c in q.constraints])
    hi = np.array([c[2] for c in q.constraints])
    A = np.stack([np.ones(3000)] + [cols[a] for a, _, _ in q.constraints[1:]])
    best = milp(-cols["price"], constraints=LinearConstraint(A, lo, hi),
                integrality=np.ones(3000), bounds=Bounds(0, 1))
    worst = milp(cols["price"], constraints=LinearConstraint(A, lo, hi),
                 integrality=np.ones(3000), bounds=Bounds(0, 1))
    rng = np.random.default_rng(0)
    good = _answer(q, np.flatnonzero(best.x > 0.5), cols)
    poor = _answer(q, np.flatnonzero(worst.x > 0.5), cols)
    assert ref.lp_gap(cols, [good], 3000, rng, 8) < 0.01
    assert ref.lp_gap(cols, [good, poor], 3000, rng, 8) > 0.5
    # a package that breaks a bound is violation's to count, not lp_gap's
    assert ref.lp_gap(cols, [poor, _answer(q, [0], cols)], 3000, rng, 1) \
        == ref.lp_gap(cols, [poor], 3000, rng, 1)
