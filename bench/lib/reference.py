"""The plain reference that decides ``correct``.

Plain ``numpy``, ``math`` and ``scipy``; it imports nothing of the
program and takes nothing the program made except the answers it
checks: the packages, the objectives and statuses the window's queries
reported, and the hierarchy the build produced (group membership,
representatives, boxes, split tree).

Every reading is computed in float64, the precision the
configurations state.  ``control_answers`` and ``control_reps`` recompute
the program's outputs in float32, the nearest precision below, to be put
in the program's place: the same comparison must then fail.  ``lp_gap``
holds each sampled package against its query's LP bound over the whole
relation (``scipy``'s HiGHS on a restricted set of rows, every row priced
in ``numpy``).
"""
from __future__ import annotations

import dataclasses
import math
from typing import List, Optional, Sequence

import numpy as np

TOL = 1e-6      # the engine's validation tolerance, absolute


@dataclasses.dataclass
class Answer:
    """One query's answer as the program reported it."""
    query: object            # bench.lib.queries.Query
    status: str
    idx: np.ndarray
    mult: np.ndarray
    obj: float


@dataclasses.dataclass
class Layer:
    """Layer l >= 1 of a hierarchy: the partition of layer l-1."""
    gid: np.ndarray
    order: np.ndarray
    offsets: np.ndarray
    reps: np.ndarray
    lo: np.ndarray
    hi: np.ndarray
    tree: tuple              # (attr, bound_off, bounds, children, root)


# ------------------------------------------------------------ packages


def package_sums(cols, q, idx, mult):
    """Exactly rounded constraint sums and objective of a package."""
    sums = []
    for attr, _, _ in q.constraints:
        vals = mult if attr is None else cols[attr][idx] * mult
        sums.append(math.fsum(vals.tolist()))
    obj = math.fsum((cols[q.objective][idx] * mult).tolist())
    return sums, obj


def malformed(q, idx, mult, n: int) -> bool:
    """Ids outside the relation or repeated, or multiplicities that are
    not whole numbers in [1, repeat + 1]."""
    idx = np.asarray(idx)
    mult = np.asarray(mult, np.float64)
    return bool(len(idx) != len(mult) or len(idx) == 0
                or idx.dtype.kind not in "iu"
                or (idx < 0).any() or (idx >= n).any()
                or len(np.unique(idx)) != len(idx)
                or (mult != np.round(mult)).any() or (mult < 1).any()
                or (mult > q.repeat + 1).any())


def check_answers(cols, answers: Sequence[Answer], n: int) -> dict:
    """not_ok: answers whose status is not ``ok``; malformed: packages
    that are not a set of valid tuples; violation: the largest amount
    (absolute) by which a package's exact constraint sum leaves its
    bounds; obj_rel_err: the largest relative gap between a reported
    objective and the package's exact objective."""
    not_ok = bad = 0
    viol = err = 0.0
    for a in answers:
        if a.status != "ok":
            not_ok += 1
            continue
        if malformed(a.query, a.idx, a.mult, n):
            bad += 1
            continue
        idx = np.asarray(a.idx, np.int64)
        mult = np.asarray(a.mult, np.float64)
        sums, obj = package_sums(cols, a.query, idx, mult)
        for (_, lo, hi), s in zip(a.query.constraints, sums):
            viol = max(viol, lo - s, s - hi)
        err = max(err, abs(a.obj - obj) / max(abs(obj), 1e-300))
    return {"not_ok": not_ok, "malformed": bad, "violation": viol,
            "obj_rel_err": err}


# ------------------------------------------------------------- quality


def lp_bound(cols, q, start: np.ndarray, rounds: int = 40,
             add: int = 256) -> float:
    """A bound on the objective of every package of ``q``: the query's LP
    relaxation over the whole relation (each x_i in [0, repeat + 1]),
    by column generation from the rows ``start`` (a feasible package, so
    that every restricted LP is feasible) and the top rows by objective.

    Each round solves the restricted LP (HiGHS dual simplex) and prices
    every row with its duals; the bound is the Lagrangian dual
    ``lam . b + u * sum(max(0, c - lam . A))``, valid for any duals
    ``lam >= 0`` and equal to the LP optimum once no row prices in."""
    from scipy.optimize import linprog
    sgn = 1.0 if q.maximize else -1.0
    c = sgn * np.asarray(cols[q.objective], np.float64)
    n = len(c)
    u = float(q.repeat + 1)
    cons = [(None if a is None else np.asarray(cols[a], np.float64), lo, hi)
            for a, lo, hi in q.constraints]
    top = np.argpartition(-c, min(add, n - 1))[:add]
    S = np.unique(np.concatenate([np.asarray(start, np.int64), top]))
    tol = 1e-9 * max(1.0, float(np.abs(c).max()))
    best = math.inf
    for _ in range(rounds):
        A, b, rows = [], [], []
        for j, (col, lo, hi) in enumerate(cons):
            a = np.ones(len(S)) if col is None else col[S]
            if math.isfinite(hi):
                A.append(a)
                b.append(hi)
                rows.append((j, 1.0))
            if math.isfinite(lo):
                A.append(-a)
                b.append(-lo)
                rows.append((j, -1.0))
        res = linprog(-c[S], A_ub=np.array(A), b_ub=np.array(b),
                      bounds=(0.0, u), method="highs-ds")
        if res.status != 0:
            raise RuntimeError(f"restricted LP: {res.message}")
        lam = np.maximum(-np.asarray(res.ineqlin.marginals), 0.0)
        y = np.zeros(len(cons))
        for (j, s), l in zip(rows, lam):
            y[j] += s * l
        r = c.copy()
        for (col, _, _), yj in zip(cons, y):
            if yj:
                r -= yj if col is None else yj * col
        best = min(best, math.fsum(l * bb for l, bb in zip(lam, b))
                   + u * float(np.sum(r, where=r > 0)))
        r[S] = -math.inf
        out = np.flatnonzero(r > tol)
        if not len(out):
            break
        if len(out) > add:
            out = out[np.argpartition(-r[out], add)[:add]]
        S = np.union1d(S, out)
    return sgn * best


def lp_gaps(cols, answers: Sequence[Answer], n: int,
            rng: np.random.Generator, sample: int) -> List[tuple]:
    """(answer index, relative gap) between each sampled package's exact
    objective and its query's LP bound over the whole relation, for
    ``sample`` of the answers drawn by ``rng``.  Only packages that are
    well formed and meet their bounds take part: ``check_answers`` counts
    the rest."""
    ok = []
    for i, a in enumerate(answers):
        if a.status != "ok" or malformed(a.query, a.idx, a.mult, n):
            continue
        idx = np.asarray(a.idx, np.int64)
        sums, obj = package_sums(cols, a.query, idx,
                                 np.asarray(a.mult, np.float64))
        if all(lo - TOL <= s <= hi + TOL
               for (_, lo, hi), s in zip(a.query.constraints, sums)):
            ok.append((i, idx, obj))
    if not ok:
        return []
    pick = rng.choice(len(ok), size=min(sample, len(ok)), replace=False)
    out = []
    for j in sorted(pick):
        i, idx, obj = ok[int(j)]
        q = answers[i].query
        bound = lp_bound(cols, q, idx)
        d = (bound - obj) if q.maximize else (obj - bound)
        out.append((i, d / max(abs(bound), abs(obj), 1e-300)))
    return out


def lp_gap(cols, answers: Sequence[Answer], n: int,
           rng: np.random.Generator, sample: int) -> float:
    """The widest of ``lp_gaps``: 0 where no package takes part."""
    return max((g for _, g in lp_gaps(cols, answers, n, rng, sample)),
               default=0.0)


# ----------------------------------------------------------- hierarchy


def _descend(tree, t) -> int:
    """GetGroup of one tuple: follow the split tree's bounds."""
    attr, bound_off, bounds, children, root = tree
    node = int(root)
    while node >= 0:
        b0, b1 = int(bound_off[node]), int(bound_off[node + 1])
        pos = b0 + int(np.searchsorted(bounds[b0:b1], t[int(attr[node])],
                                       side="right"))
        node = int(children[node + pos])
    return ~node


def partition_faults(prev: np.ndarray, lay: Layer,
                     rng: np.random.Generator, probes: int) -> int:
    """Structural faults of one layer's partition of ``prev`` (n rows):
    not a permutation, groups that are empty or not contiguous, group ids
    that disagree with the layout, representative arrays of the wrong
    shape, and sampled rows the split tree routes to another group."""
    n, k = prev.shape
    order = np.asarray(lay.order, np.int64)
    off = np.asarray(lay.offsets, np.int64)
    G = len(off) - 1
    if (len(order) != n or order.min() < 0 or order.max() >= n
            or (np.bincount(order, minlength=n) != 1).any()):
        return 1
    if off[0] != 0 or off[-1] != n or (np.diff(off) <= 0).any():
        return 1
    want = np.repeat(np.arange(G), np.diff(off))
    faults = int((np.asarray(lay.gid)[order] != want).sum())
    for arr in (lay.reps, lay.lo, lay.hi):
        faults += int(np.shape(arr) != (G, k))
    for i in rng.choice(n, size=min(probes, n), replace=False):
        faults += int(_descend(lay.tree, prev[i]) != lay.gid[i])
    return faults


def group_reps(prev: np.ndarray, lay: Layer, dtype) -> np.ndarray:
    """Member means, then member minima and maxima, as (3, G, k), in
    ``dtype``: float64 sums by ``bincount``, float32 sums accumulated in
    float32."""
    prev = prev.astype(dtype)
    gid = np.asarray(lay.gid, np.int64)
    off = np.asarray(lay.offsets, np.int64)
    G = len(off) - 1
    sorted_rows = prev[np.asarray(lay.order, np.int64)]
    cnt = np.diff(off).astype(dtype)
    if dtype == np.float64:
        sums = np.stack([np.bincount(gid, weights=prev[:, j], minlength=G)
                         for j in range(prev.shape[1])], axis=1)
    else:
        sums = np.add.reduceat(sorted_rows, off[:-1], axis=0, dtype=dtype)
    mean = (sums / cnt[:, None]).astype(dtype)
    return np.stack([mean,
                     np.minimum.reduceat(sorted_rows, off[:-1], axis=0),
                     np.maximum.reduceat(sorted_rows, off[:-1], axis=0)])


def rel_err(got: np.ndarray, ref: np.ndarray) -> float:
    got = np.asarray(got, np.float64)
    ref = np.asarray(ref, np.float64)
    if not len(ref.reshape(-1)):
        return 0.0
    d = np.abs(got - ref) / np.maximum(np.abs(ref), 1e-300)
    return float(np.max(np.where(got == ref, 0.0, d)))


def check_hierarchy(X0: np.ndarray, layers: Sequence[Layer], alpha: int,
                    rng: np.random.Generator, probes: int = 2048,
                    given: Optional[List[np.ndarray]] = None) -> dict:
    """partition_faults: structural faults over all layers, plus one if
    the top layer holds more than ``alpha`` tuples; rep_rel_err: the
    largest relative gap between a layer's representatives and boxes and
    the float64 means, minima and maxima of its members.  ``given``
    replaces the program's (3, G, k) arrays layer by layer."""
    faults = int(len(layers[-1].reps) > alpha if layers else 0)
    err = 0.0
    prev = ref_prev = np.asarray(X0, np.float64)
    for li, lay in enumerate(layers):
        f = partition_faults(prev, lay, rng, probes)
        faults += f
        if f:
            break
        ref = group_reps(ref_prev, lay, np.float64)
        got = given[li] if given is not None else np.stack(
            [lay.reps, lay.lo, lay.hi])
        err = max(err, rel_err(got, ref))
        prev, ref_prev = np.asarray(lay.reps, np.float64), ref[0]
    return {"partition_faults": faults, "rep_rel_err": err}


# -------------------------------------------------------------- control


def control_answers(cols, answers: Sequence[Answer]) -> List[Answer]:
    """The answers with each package's objective recomputed in float32."""
    f32 = np.float32
    out = []
    for a in answers:
        col = cols[a.query.objective].astype(f32)
        obj = a.obj
        if a.status == "ok" and len(a.idx):
            obj = float(np.sum(col[np.asarray(a.idx, np.int64)]
                               * np.asarray(a.mult, f32), dtype=f32))
        out.append(dataclasses.replace(a, obj=obj))
    return out


def control_reps(X0: np.ndarray, layers: Sequence[Layer]) -> List[np.ndarray]:
    """Every layer's representatives and boxes recomputed in float32 from
    the float32 representatives of the layer below."""
    reps = []
    prev = np.asarray(X0, np.float32)
    for lay in layers:
        r = group_reps(prev, lay, np.float32)
        reps.append(r)
        prev = r[0]
    return reps
