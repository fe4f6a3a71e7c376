"""Revised (Parallel) Dual Simplex with Bound-Flipping Ratio Test —
paper §2.3 + App. B/C.

Solves the package-query LP in bounded standard form:

    min  cᵀx̃   s.t.  bl <= Ãx̃ <= bu,   0 <= x̃ <= ũ

internally rewritten (Appendix B.1) with slacks s = Ãx̃:

    min cᵀx   s.t.  Ax = 0,  l <= x <= u,   A = [-Ã | I],  x = [x̃ | s],
    l = [0 | bl], u = [ũ | bu].

Structure exploited exactly as the paper does:
  * m is tiny (3–20) and n is huge -> the basis inverse is a dense m×m
    matrix (App. C.2),
  * phase-1 is free: ANY nonsingular basis is dual-feasible after setting
    each nonbasic variable to the bound matching the sign of its reduced
    cost (App. C.1) — this is also what makes warm starting safe,
  * the two O(n) steps per iteration — pricing (alpha = rho @ A) and the
    BFRT breakpoint scan — are embarrassingly parallel over n (App. C.3);
    here they are vectorised (numpy / jnp) and, on TPU, backed by the
    Pallas kernels in ``repro.kernels`` and the shard_map distribution in
    ``repro.core.distributed``.

Revised-simplex invariants (maintained between pivots, App. C custom loop):
  * ``Binv``    — basis inverse, updated by a Sherman–Morrison /
    product-form rank-1 update per pivot (O(m^2)), refactorized from
    scratch every ``REFACTOR_EVERY`` pivots for f64 stability;
  * ``d``       — reduced costs c - Aᵀy, updated by one O(n) axpy
    ``d -= theta * alpha`` per pivot (exact zeros pinned on the basis);
  * ``y``       — duals, updated by ``y += theta * rho`` (O(m));
  * ``xB``      — basic primal values, updated incrementally after bound
    flips (O(m * |flips|) in the numpy twin; one masked matvec in the
    fixed-shape JAX twins) and the basis exchange (O(m)).
  The ONLY O(mn) sweep of A inside the pivot loop is the pricing pass
  ``alpha = rho @ A`` (the Pallas kernel in ``repro.kernels.pricing``).
  Whenever optimality or dual unboundedness is about to be declared on
  stale (rank-1-updated) factors, the engine refactorizes first and
  re-checks, so the ``verify_optimality`` certificate is always produced
  from a fresh factorization.

Warm-start contract:
  ``solve_lp_np`` / ``solve_lp`` / ``solve_lp_kernel`` accept
  ``warm_start=`` — an ``LPResult``, a ``WarmStart``, or a
  ``(basis, at_upper)`` tuple.  ``basis`` must hold m column indices into
  THIS LP's n+m columns (callers re-map indices when the column set
  changed, cf. ``repro.core.shading.map_warm_basis``); ``at_upper`` is an
  optional (n+m,) hint used only for columns with a ~zero reduced cost.
  The engine validates the basis (shape, uniqueness, nonsingularity,
  no dual-infeasible column pinned at an infinite bound) and silently
  falls back to the cold all-slack start when invalid — a warm start can
  only change the iteration count, never the answer.

  ``solve_lp_resume(form, lb, ub, factors)`` is the warm start without
  its set-up, for LPs that differ only in x̃'s bounds (branch & bound's
  nodes): ``form = prepare_lp(c, A_t, bl, bu)`` is built once, and
  ``factors`` is another variant's ``LPResult.factors`` — its final basis
  with the fresh Binv, y and d in the scaled space.  Only the bound
  placement is redone; the pivots and the answer are those of
  ``solve_lp_np(..., warm_start=(basis, at_upper))``.  The numpy twin
  answers from the factors of its last refactorization when no pivot
  came after it, and factorizes afresh otherwise.

Two twin implementations with identical pivot rules:
  solve_lp_np  — numpy, used by branch & bound re-solves and as the oracle,
  solve_lp     — jax.lax.while_loop under jit (f64), used by the benchmarks
                 and the distributed/multi-pod path.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import numpy as np

from repro.core.guard import (DRIFT_TOL, NumericalMonitor, STALL_BLAND,
                              STALL_REFACTOR, SolveBudget, THETA_EPS)
from repro.runtime import faults

OPTIMAL, ITER_LIMIT, INFEASIBLE, BUDGET = 0, 1, 2, 3
_TOL = 1e-9
REFACTOR_EVERY = 64   # pivots between full refactorizations (f64 stability)


@dataclasses.dataclass
class LPResult:
    status: int
    x: np.ndarray            # primal solution over the original n variables
    obj: float               # objective in the ORIGINAL sense (pre-negation)
    iters: int
    basis: np.ndarray        # final basis (indices into n+m)
    at_upper: np.ndarray     # nonbasic-at-upper flags (n+m)
    y: np.ndarray            # duals (m,)
    notes: Tuple[str, ...] = ()   # solver events (warm rejection, stalls,
                                  # budget truncation) for the SolveReport
    # the numpy twin's final fresh factors, for a bound-variant to resume
    # from (``solve_lp_resume``); None from the other twins
    factors: Optional["Factors"] = dataclasses.field(default=None,
                                                     repr=False)

    @property
    def feasible(self) -> bool:
        return self.status == OPTIMAL

    @property
    def warm(self) -> "WarmStart":
        """Warm-start handle for a sibling LP over the same columns."""
        return WarmStart(self.basis, self.at_upper)


@dataclasses.dataclass
class WarmStart:
    """Starting basis for the dual simplex (see module docstring)."""
    basis: np.ndarray
    at_upper: Optional[np.ndarray] = None


@dataclasses.dataclass(frozen=True)
class Factors:
    """A basis with its fresh factors in the scaled standard form
    (``prepare_lp``).  Read-only: a solve that resumes from it copies
    what it updates, so siblings can share one parent's."""
    basis: np.ndarray
    at_upper: np.ndarray
    Binv: np.ndarray         # inverse of A[:, basis]
    y: np.ndarray            # duals, scaled units
    d: np.ndarray            # reduced costs cf - Aᵀy, zero on the basis


@dataclasses.dataclass(frozen=True)
class LPForm:
    """The row-scaled standard form of ``(c, A_t, bl, bu)``, shared by
    the LPs that differ from it only in x̃'s bounds."""
    cf: np.ndarray
    A: np.ndarray
    bl: np.ndarray           # scaled row bounds: the slacks' bounds
    bu: np.ndarray
    scale: np.ndarray

    def box(self, lb, ub):
        """Bounds (l, u) over [x̃ | s] for lb <= x̃ <= ub (lb None: 0)."""
        n = self.A.shape[1] - len(self.bl)
        l = np.concatenate([np.zeros(n), self.bl])
        if lb is not None:
            l[:n] = lb
        return l, np.concatenate([np.asarray(ub, np.float64), self.bu])


def _unpack_warm(warm_start):
    """Accept LPResult / WarmStart / (basis, at_upper) / None."""
    if warm_start is None:
        return None, None
    if hasattr(warm_start, "basis"):
        return warm_start.basis, getattr(warm_start, "at_upper", None)
    basis, at_upper = warm_start
    return basis, at_upper


def standard_form(c, A_t, bl, bu, ub):
    """Build [x̃ | s] arrays. Returns (c_f, A_f, l_f, u_f)."""
    m, n = A_t.shape
    c_f = np.concatenate([c, np.zeros(m)])
    A_f = np.concatenate([-A_t, np.eye(m)], axis=1)
    l_f = np.concatenate([np.zeros(n), bl])
    u_f = np.concatenate([ub, bu])
    return c_f, A_f, l_f, u_f


def row_scaling(A_t) -> np.ndarray:
    """Row equilibration factors: package-query rows can differ by 12+
    orders of magnitude (count=1 vs FLOPs=1e12); unscaled, the transformed
    pivot rows lose the small rows to cancellation."""
    mx = np.max(np.abs(A_t), axis=1)
    return np.where(mx > 0, 1.0 / mx, 1.0)


def _cold_start(cf, l, n, N):
    """All-slack basis, nonbasic at the bound matching sign(c) (App. C.1)."""
    basis = np.arange(n, N)
    in_basis = np.zeros(N, bool)
    in_basis[basis] = True
    at_upper = np.zeros(N, bool)
    at_upper[:n] = (cf[:n] < 0) | np.isinf(l[:n])
    return basis, in_basis, at_upper


def _warm_state(cf, A, l, u, warm_basis, at_upper_hint, tol):
    """Validate a warm basis; returns
    ((basis, in_basis, at_upper, Binv, y, d), None) or (None, reason).

    Dual feasibility is restored for free by placing every nonbasic column
    at the bound matching the sign of its reduced cost (``_place``).  The
    factors computed for validation (Binv, y, d) are returned so the
    solver can seed its state without refactorizing again.

    A rejected basis is never an error — the caller falls back to the
    cold all-slack start — but it is no longer *silent*: the reason is
    surfaced through ``LPResult.notes`` / the SolveReport so a bad basis
    can never be proceeded on unnoticed.
    """
    m, N = A.shape
    basis = np.asarray(warm_basis, np.int64).ravel()
    if basis.shape != (m,):
        return None, f"basis shape {basis.shape} != ({m},)"
    if basis.min() < 0 or basis.max() >= N or len(np.unique(basis)) != m:
        return None, "basis indices out of range or duplicated"
    try:
        Binv = np.linalg.inv(A[:, basis])
    except np.linalg.LinAlgError:
        return None, "singular basis"
    y = Binv.T @ cf[basis]
    d = cf - A.T @ y
    d[basis] = 0.0
    placed, why = _place(basis, Binv, d, l, u, at_upper_hint, tol)
    if placed is None:
        return None, why
    return (basis.copy(), *placed, Binv, y, d), None


def _place(basis, Binv, d, l, u, at_upper_hint, tol):
    """Bound placement of a factored basis: ((in_basis, at_upper), None)
    or (None, reason).  Each nonbasic column sits at the bound its
    reduced cost's sign asks for; the ``at_upper`` hint decides only
    columns whose reduced cost is ~zero (degenerate), which preserves the
    warm solve's primal point."""
    if not np.all(np.isfinite(Binv)) or np.abs(Binv).max() > 1e12:
        return None, "ill-conditioned basis"
    N = len(d)
    in_basis = np.zeros(N, bool)
    in_basis[basis] = True
    hint = np.zeros(N, bool)
    if at_upper_hint is not None:
        h = np.asarray(at_upper_hint, bool).ravel()
        if h.shape == (N,):
            hint = h
    neg, pos = d < -tol, d > tol
    l_inf, u_inf = np.isinf(l), np.isinf(u)
    at_upper = neg | (hint & ~pos)
    at_upper |= l_inf                  # -inf lower: must sit at upper
    at_upper &= ~u_inf                 # +inf upper: must sit at lower
    # a nonbasic column whose reduced-cost sign demands an infinite bound
    # cannot be made dual-feasible by bound placement -> reject the basis
    if np.any(~in_basis & ((neg & u_inf) | (pos & l_inf)
                           | (l_inf & u_inf))):
        return None, "dual-infeasible column pinned at an infinite bound"
    at_upper[in_basis] = False
    return (in_basis, at_upper), None


def fill_warm_basis(new_basis, n_new: int, m: int):
    """Shared warm-basis remap tail (shading / dual_reducer): replace
    unmapped (-1) entries with unused slack columns of the new LP;
    returns an int64 basis or None if duplicates remain."""
    used = set(int(b) for b in new_basis if b >= 0)
    free = [n_new + i for i in range(m) if n_new + i not in used]
    out = []
    for b in new_basis:
        if b < 0:
            if not free:
                return None
            b = free.pop(0)
        out.append(int(b))
    if len(set(out)) != m:
        return None
    return np.asarray(out, np.int64)


def prepare_lp(c, A_t, bl, bu) -> LPForm:
    """Row scaling and standard form of ``(c, A_t, bl, bu)``, built once
    for every bound-variant of the LP (``solve_lp_resume``)."""
    c = np.asarray(c, np.float64)
    A_t = np.atleast_2d(np.asarray(A_t, np.float64))
    scale = row_scaling(A_t)
    A_t = A_t * scale[:, None]
    bl = np.asarray(bl, np.float64) * scale
    bu = np.asarray(bu, np.float64) * scale
    cf, A, _, _ = standard_form(c, A_t, bl, bu, np.zeros(A_t.shape[1]))
    return LPForm(cf, A, bl, bu, scale)


def _prep(c, A_t, bl, bu, ub, lb, warm_start, tol=1e-7):
    """Shared solver setup: scale, standard form, warm-basis validation.

    Returns (arrs, scale, m, n, (basis0, at_upper0, winit, wnote)) where
    arrs is None for an infeasible box, winit is the validated warm state
    (basis, in_basis, at_upper, Binv, y, d) or None for a cold start, and
    wnote records why a requested warm basis was rejected (else None).
    """
    form = prepare_lp(c, A_t, bl, bu)
    cf, A, scale = form.cf, form.A, form.scale
    l, u = form.box(lb, ub)
    m, N = A.shape
    n = N - m
    if np.any(l > u + tol):
        return None, scale, m, n, None
    wb, wh = _unpack_warm(warm_start)
    winit, wnote = (None, None) if wb is None else \
        _warm_state(cf, A, l, u, wb, wh, tol)
    if wnote is not None:
        wnote = f"warm_start_rejected: {wnote}; cold start used"
    if winit is None:
        basis0, _, at_upper0 = _cold_start(cf, l, n, N)
    else:
        basis0, _, at_upper0 = winit[:3]
    return (cf, A, l, u), scale, m, n, (basis0, at_upper0, winit, wnote)


def solve_lp_np(c, A_t, bl, bu, ub, *, lb: Optional[np.ndarray] = None,
                max_iters: int = 5000, tol: float = 1e-7,
                warm_start=None,
                refactor_every: int = REFACTOR_EVERY,
                budget: Optional[SolveBudget] = None,
                monitor: Optional[NumericalMonitor] = None) -> LPResult:
    """Bounded revised dual simplex with BFRT (numpy twin).

    Maintains Binv (rank-1 product-form updates), reduced costs d (one
    O(n) axpy per pivot) and xB (O(m*|flips|)) incrementally; the pricing
    matvec ``rho @ A`` is the only O(mn) work per iteration.

    ``budget=`` bounds wall clock and pivots (status BUDGET on
    truncation); ``monitor=`` collects numerical-health events.  The
    solver checks Binv residual drift every ``monitor.drift_check_every``
    pivots and tracks degenerate-pivot streaks: a streak of
    ``stall_refactor`` forces a refactorization, ``stall_bland``
    escalates to Bland's-rule pivoting (smallest-index row/column, no
    bound flips) until a non-degenerate pivot resumes progress.
    """
    arrs, scale, m, n, start = _prep(c, A_t, bl, bu, ub, lb, warm_start,
                                     tol)
    if arrs is None:
        return LPResult(INFEASIBLE, np.zeros(n), 0.0, 0, np.arange(n, n + m),
                        np.zeros(n + m, bool), np.zeros(m))
    basis0, at_upper0, winit, wnote = start
    return _dual_simplex(*arrs, scale, basis0.copy(), at_upper0.copy(),
                         None if winit is None else winit[3:],
                         [] if wnote is None else [wnote],
                         max_iters=max_iters, tol=tol,
                         refactor_every=refactor_every, budget=budget,
                         monitor=monitor)


def solve_lp_resume(form: LPForm, lb, ub, start: Factors, *,
                    max_iters: int = 5000, tol: float = 1e-7,
                    refactor_every: int = REFACTOR_EVERY,
                    budget: Optional[SolveBudget] = None,
                    monitor: Optional[NumericalMonitor] = None
                    ) -> Optional[LPResult]:
    """The LP of ``form`` with lb <= x̃ <= ub, resumed from ``start``: the
    ``factors`` of a solve of the same form under other bounds.

    The same pivots and answer as ``solve_lp_np(...,
    warm_start=(start.basis, start.at_upper))``, without rebuilding the
    form or factorizing the basis again.  None where the box is empty or
    ``start`` cannot be placed in it; ``solve_lp_np`` handles both.
    """
    l, u = form.box(lb, ub)
    if np.any(l > u + tol):
        return None
    placed, _ = _place(start.basis, start.Binv, start.d, l, u,
                       start.at_upper, tol)
    if placed is None:
        return None
    return _dual_simplex(form.cf, form.A, l, u, form.scale,
                         start.basis.copy(), placed[1],
                         (start.Binv.copy(), start.y.copy(),
                          start.d.copy()), [],
                         max_iters=max_iters, tol=tol,
                         refactor_every=refactor_every, budget=budget,
                         monitor=monitor)


def _dual_simplex(cf, A, l, u, scale, basis, at_upper, factors, notes, *,
                  max_iters, tol, refactor_every, budget, monitor
                  ) -> LPResult:
    """The numpy twin's pivot loop over a scaled standard form from
    ``basis`` / ``at_upper`` and, where given, that basis's fresh
    ``factors`` (Binv, y, d); all five are the loop's own, updated in
    place.  Without factors it factorizes first."""
    m, N = A.shape
    n = N - m
    mon = monitor if monitor is not None else NumericalMonitor()
    if budget is not None:
        budget.start()
    in_basis = np.zeros(N, bool)
    in_basis[basis] = True
    if factors is not None:
        Binv, y, d = factors
        xN = np.where(in_basis, 0.0, np.where(at_upper, u, l))
        xN[basis] = 0.0
        xB = -Binv @ (A @ xN)
        since = 0
        fresh = Binv
    else:
        Binv = np.eye(m)
        xB = np.zeros(m)
        y = np.zeros(m)
        d = cf.copy()
        since = refactor_every      # force a full factorization first
        fresh = None

    def refresh():
        nonlocal Binv, xB, y, d, since, fresh
        Binv = np.linalg.inv(A[:, basis])
        xN = np.where(in_basis, 0.0, np.where(at_upper, u, l))
        xN[basis] = 0.0
        xB = -Binv @ (A @ xN)
        y = Binv.T @ cf[basis]
        d = cf - A.T @ y
        d[basis] = 0.0
        since = 0
        fresh = Binv

    width = u - l
    status = ITER_LIMIT
    iters = 0
    stall = 0
    bland = False
    for iters in range(1, max_iters + 1):
        if budget is not None and (budget.out_of_time()
                                   or iters > budget.remaining_pivots()):
            status = BUDGET
            notes.append(f"budget: truncated at pivot {iters - 1}")
            break
        if since >= refactor_every:
            refresh()
        Binv = faults.perturb(faults.BINV, Binv)
        if iters % mon.drift_check_every == 0:
            resid = float(np.abs(Binv @ A[:, basis] - np.eye(m)).max())
            if mon.record_resid(resid):
                if mon.drift_refactors <= 3:
                    notes.append(f"drift: |BinvB-I|={resid:.2e} -> "
                                 "refactorize")
                refresh()
        lB, uB = l[basis], u[basis]
        viol_lo = lB - xB
        viol_hi = xB - uB
        viol = np.maximum(viol_lo, viol_hi)
        r = int(np.argmax(viol))
        if viol[r] <= tol and since > 0:
            # about to declare optimality on drifted factors: refactorize
            # and re-check so the certificate is exact
            refresh()
            viol_lo = lB - xB
            viol_hi = xB - uB
            viol = np.maximum(viol_lo, viol_hi)
            r = int(np.argmax(viol))
        if viol[r] <= tol:
            status = OPTIMAL
            break
        if bland:
            # Bland anti-cycling: leave the violated row whose BASIC
            # VARIABLE index is smallest — row position alone does not
            # carry the finiteness guarantee (bases reorder across pivots)
            r = int(np.argmin(np.where(viol > tol, basis, N)))
        above = viol_hi[r] >= viol_lo[r]
        delta = xB[r] - (uB[r] if above else lB[r])
        s = 1.0 if delta > 0 else -1.0

        rho = Binv[r]
        alpha = rho @ A           # pricing: the single O(mn) sweep, ∥ over n

        sa = s * alpha
        # entering candidates: nonbasic columns that can leave their bound
        # in the direction that reduces the leaving row's infeasibility
        cand = np.flatnonzero(~in_basis & np.where(at_upper, sa < -tol,
                                                   sa > tol))
        if not len(cand):
            if since > 0:         # could be drift: retry on fresh factors
                refresh()
                continue
            status = INFEASIBLE
            break
        ratio = np.maximum(d[cand] / sa[cand], 0.0)   # |sa| > tol on cand

        if bland:
            # Bland's rule: smallest-index min-ratio column, no bound
            # flips — finite (anti-cycling) at the cost of progress/pivot
            rmin = float(np.min(ratio))
            q = int(cand[np.argmax(ratio <= rmin + 1e-12)])
            flips = np.empty(0, np.int64)
            mon.bland_pivots += 1
        else:
            # ---- BFRT: walk breakpoints in ratio order, flipping bounds
            # while the remaining infeasibility budget allows (App. C.3).
            cand = cand[np.argsort(ratio, kind="stable")]
            csum = np.cumsum(np.abs(alpha[cand]) * width[cand])
            flip_budget = abs(delta)
            cross = int(np.searchsorted(csum, flip_budget - 1e-12))
            if cross >= len(cand):
                if since > 0:     # dual unbounded on stale factors: re-check
                    refresh()
                    continue
                status = INFEASIBLE   # dual unbounded: flips cannot absorb
                break
            q = int(cand[cross])
            flips = cand[:cross]

        # ---- incremental pivot (no inv, no full d recompute) ----
        leave = basis[r]
        w = Binv @ A[:, q]                    # entering column in B coords
        if abs(w[r]) < 1e-11:
            # numerically unsafe pivot on drifted factors; fresh factors
            # guarantee |w[r]| = |alpha_q| > tol.  Checked BEFORE any flip
            # is applied so the retry restarts from a consistent state.
            if since > 0:
                refresh()
                continue
            break                             # cannot happen; keep ITER_LIMIT
        if len(flips):
            # bound flips move xB by -Binv A[:,flips] dx: O(m * |flips|)
            dxf = np.where(at_upper[flips], l[flips] - u[flips],
                           u[flips] - l[flips])
            xB -= Binv @ (A[:, flips] @ dxf)
            at_upper[flips] = ~at_upper[flips]
        target = uB[r] if above else lB[r]
        t = (xB[r] - target) / w[r]
        xq = u[q] if at_upper[q] else l[q]
        xB -= t * w
        xB[r] = xq + t
        theta = d[q] / w[r]
        d -= theta * alpha                    # one O(n) axpy
        d[q] = 0.0
        d[leave] = -theta
        y += theta * rho
        # Sherman–Morrison / product-form rank-1 update of Binv
        Binv_r = Binv[r] / w[r]
        Binv -= np.outer(w, Binv_r)
        Binv[r] = Binv_r
        at_upper[leave] = above
        at_upper[q] = False
        in_basis[leave] = False
        in_basis[q] = True
        basis[r] = q
        since += 1

        # ---- anti-cycling: degenerate (theta ~ 0) pivot streaks ----
        if abs(theta) <= THETA_EPS:
            stall += 1
            if stall == mon.stall_refactor:
                mon.stall_refactors += 1
                mon.stall_events += 1
                since = refactor_every          # force refresh next pivot
            if stall >= mon.stall_bland and not bland:
                bland = True
                mon.stall_events += 1
                notes.append(f"stall: {stall} degenerate pivots -> "
                             "Bland's rule")
        elif stall:
            stall = 0
            bland = False                       # progress resumed

    if budget is not None:
        budget.charge_pivots(iters)
    # the answer always comes from a fresh factorization: the last one's
    # where no pivot (nor an injected perturbation of Binv) came since
    if since or Binv is not fresh:
        refresh()
    xN = np.where(in_basis, 0.0, np.where(at_upper, u, l))
    xN[basis] = 0.0
    x = xN.copy()
    x[basis] = xB
    obj_min = float(cf @ np.where(np.isfinite(x), x, 0.0))
    basis = basis.copy()
    at_upper = at_upper.copy()
    return LPResult(status, x[:n], obj_min, iters, basis, at_upper,
                    y * scale,   # duals in original units
                    notes=tuple(notes),
                    factors=Factors(basis, at_upper, Binv, y, d))


# ----------------------------------------------------------------- JAX twin

import jax
import jax.numpy as jnp
from functools import partial


def basis_inverse(B):
    """Inverse of the m x m basis by Gauss–Jordan with partial pivoting.

    ``jnp.linalg.inv`` lowers to an LU decomposition that XLA:TPU
    implements for f32 and c64 only; this loop is plain elementwise
    jnp, so it compiles in f64 on every backend.  m is tiny (3–20), so
    the O(m^3) sweep costs nothing next to one pricing pass.  Row swaps
    and pivot-row reads are one-hot selects, not gathers, so the
    batched engine can vmap it.  A singular basis yields non-finite
    entries, as ``jnp.linalg.inv`` does.
    """
    m = B.shape[0]
    rows = jnp.arange(m)
    M = jnp.concatenate([B, jnp.eye(m, dtype=B.dtype)], axis=1)

    def eliminate(k, M):
        col = M[:, k]
        p = jnp.argmax(jnp.where(rows >= k, jnp.abs(col), -1.0))
        at_k, at_p = (rows == k)[:, None], (rows == p)[:, None]
        row_k = jnp.sum(jnp.where(at_k, M, 0.0), axis=0)
        row_p = jnp.sum(jnp.where(at_p, M, 0.0), axis=0)
        M = jnp.where(at_k, row_p, jnp.where(at_p, row_k, M))
        piv = row_p / row_p[k]
        return jnp.where(at_k, piv, M - jnp.outer(M[:, k], piv))

    return jax.lax.fori_loop(0, m, eliminate, M)[:, m:]


def _refreshed(cf, A, l, u, basis, in_basis, at_upper):
    """Full refactorization of the revised-simplex factor state.  Shared
    by the single-instance jitted twin and the batched bound-variant
    engine (``repro.core.lp_batch``), which vmaps it over instances."""
    Binv = basis_inverse(A[:, basis])
    # NOTE: masked selects, not ``.at[basis].set`` scatters — a vmapped
    # scatter lowers to a K*m-trip sequential loop on CPU; ``in_basis``
    # is the exact membership mask of ``basis`` by invariant
    xN = jnp.where(in_basis, 0.0, jnp.where(at_upper, u, l))
    xB = -Binv @ (A @ xN)
    y = Binv.T @ cf[basis]
    d = jnp.where(in_basis, 0.0, cf - A.T @ y)
    return Binv, xB, d, y


def _init_pivot_state(cf, A, basis0, at_upper0, refactor_every):
    """Loop-carried state tuple for ``_pivot_iter``.  ``since`` starts at
    ``refactor_every`` so the first iteration factorizes from the basis,
    cold and warm alike."""
    m = A.shape[0]
    N = A.shape[1]
    in_basis0 = jnp.any(jnp.arange(N) == basis0[:, None], axis=0)
    at_upper0 = at_upper0 & ~in_basis0
    return (basis0, in_basis0, at_upper0, jnp.eye(m, dtype=A.dtype),
            jnp.zeros(m, A.dtype), cf, jnp.zeros(m, A.dtype),
            jnp.int32(0), jnp.bool_(False), jnp.int32(0), jnp.int32(0),
            jnp.int32(ITER_LIMIT), jnp.int32(0),
            jnp.int32(refactor_every))


# state-tuple field positions shared with repro.core.lp_batch
_STATE_STATUS = 11
_STATE_IT = 12


def _factor_refresh(cf, A, l, u, state):
    """Unconditional refactorization of the loop-carried state — the
    shared body of both refresh sites in ``_pivot_iter``.  The batched
    engine (``repro.core.lp_batch``) calls this directly under a
    batch-level ``lax.cond`` so the O(m^3) inverse only lowers when some
    lane actually needs it (a vmapped per-lane cond would execute it for
    every lane on every iteration)."""
    (basis, in_basis, at_upper, Binv, xB, d, y, stall, bland, n_bland,
     n_drift, status, it, since) = state
    Binv, xB, d, y = _refreshed(cf, A, l, u, basis, in_basis, at_upper)
    return (basis, in_basis, at_upper, Binv, xB, d, y, stall, bland,
            n_bland, n_drift, status, it, jnp.int32(0))


def _drift_gate(A, refactor_every, state):
    """Numerical-health check: residual drift of the rank-1-updated
    inverse (or the periodic cadence) demands a refactorization.  The
    m×m residual costs nothing next to the O(mn) pricing pass.  Returns
    ``(state with the drift event counted, need_refresh)``."""
    (basis, in_basis, at_upper, Binv, xB, d, y, stall, bland, n_bland,
     n_drift, status, it, since) = state
    m = A.shape[0]
    resid = jnp.abs(Binv @ A[:, basis]
                    - jnp.eye(m, dtype=A.dtype)).max()
    drift = (resid > DRIFT_TOL) & (since > 0)
    n_drift = n_drift + drift.astype(jnp.int32)
    state = (basis, in_basis, at_upper, Binv, xB, d, y, stall, bland,
             n_bland, n_drift, status, it, since)
    return state, drift | (since >= refactor_every)


def _optimal_suspect_gate(l, u, tol, state):
    """Optimality suspected on stale factors -> the caller must
    refactorize and re-check before declaring."""
    basis, xB, since = state[0], state[4], state[13]
    lB, uB = l[basis], u[basis]
    viol = jnp.maximum(lB - xB, xB - uB)
    return (viol[jnp.argmax(viol)] <= tol) & (since > 0)


def _pivot_iter(cf, A, l, u, tol, refactor_every, state):
    """One revised-dual-simplex pivot — the jitted twin's while body.

    Pure function of ``(cf, A, l, u, tol)`` and the loop-carried
    ``state`` tuple (see ``_init_pivot_state``).  ``repro.core.lp_batch``
    runs the same pieces (``_drift_gate`` / ``_factor_refresh`` /
    ``_pivot_core``) vmapped over K bound-variants ``(l, u, tol, state)``
    of one shared ``(cf, A)`` with the refresh conds hoisted to batch
    level, so any change to the pivot rule here applies to both engines
    identically.
    """
    state, need = _drift_gate(A, refactor_every, state)
    # repro: allow[REPRO001] each refresh lambda below is a fresh
    # function identity per trace of this body capturing the same
    # (cf, A, l, u), so the identity-cached branch jaxpr is correct
    state = jax.lax.cond(
        need, lambda s: _factor_refresh(cf, A, l, u, s), lambda s: s,
        state)
    # repro: allow[REPRO001] fresh lambda identity, same captures
    state = jax.lax.cond(
        _optimal_suspect_gate(l, u, tol, state),
        lambda s: _factor_refresh(cf, A, l, u, s), lambda s: s, state)
    return _pivot_core(cf, A, l, u, tol, refactor_every, state)


def _bfrt_walk(ratio, flip_cost, elig, budget, iN):
    """BFRT crossing without a sort: ``(q, crossed)``, where q is the
    first eligible breakpoint in (ratio, index) order at which the
    running flip cost reaches ``budget``.

    Each trip takes the next breakpoint by one masked argmin and adds
    its cost, so the running sum is ``np.cumsum``'s left-to-right sum
    over the stable argsort — the host twin's crossing, bit for bit.
    Trips = flips + 1.  A length-N sort or ``jnp.cumsum`` in f64 takes
    XLA:TPU minutes to compile; this loop compiles in seconds.
    """
    target = budget - 1e-12

    def cond(c):
        return ~c[0]

    def body(c):
        _, run, r_prev, i_prev, _, _ = c
        after = elig & ((ratio > r_prev) | ((ratio == r_prev) & (iN > i_prev)))
        j = jnp.argmin(jnp.where(after, ratio, jnp.inf))
        run = run + flip_cost[j]
        hit = after[j] & (run >= target)
        return (hit | ~after[j], run, ratio[j], iN[j], j, hit)

    zero = jnp.zeros((), ratio.dtype)
    init = (~jnp.any(elig), zero, -jnp.inf + zero, iN[0] - 1, iN[0],
            jnp.bool_(False))
    _, _, _, _, q, crossed = jax.lax.while_loop(cond, body, init)
    return q, crossed


def _pivot_core(cf, A, l, u, tol, refactor_every, state, active=None):
    """The pivot proper: BFRT column selection + Sherman–Morrison
    update, on factors the caller has already refreshed as needed.

    ``active`` (batched engine only): a scalar bool tracer; when False
    the WHOLE state passes through unchanged.  The array fields are
    already gated by ``do_pivot``, so freezing a lane costs a handful
    of scalar selects instead of the full 14-array tree-select the
    batched loop body used to pay per trip."""
    (basis, in_basis, at_upper, Binv, xB, d, y, stall, bland, n_bland,
     n_drift, status, it, since) = state
    N = A.shape[1]
    lB, uB = l[basis], u[basis]
    viol_lo = lB - xB
    viol_hi = xB - uB
    viol = jnp.maximum(viol_lo, viol_hi)
    r_max = jnp.argmax(viol)
    done = viol[r_max] <= tol
    # Bland mode: violated row with the smallest BASIC VARIABLE index
    # (row position alone does not carry the finiteness guarantee)
    r_bland = jnp.argmin(jnp.where(viol > tol, basis, N))
    r = jnp.where(bland, r_bland, r_max)

    above = viol_hi[r] >= viol_lo[r]
    delta = jnp.where(above, xB[r] - uB[r], xB[r] - lB[r])
    s = jnp.where(delta > 0, 1.0, -1.0)
    rho = Binv[r]
    alpha = rho @ A                 # pricing: the single O(mn) sweep

    sa = s * alpha
    elig = (~in_basis) & (
        ((~at_upper) & (sa > tol)) | (at_upper & (sa < -tol)))
    any_elig = jnp.any(elig)
    ratio = jnp.where(elig,
                      jnp.maximum(d / jnp.where(jnp.abs(sa) > tol, sa, 1.0),
                                  0.0), jnp.inf)
    width = u - l
    flip_cost = jnp.where(elig, jnp.abs(alpha) * width, 0.0)

    iN = jnp.arange(N)
    q_walk, crossed = _bfrt_walk(ratio, flip_cost, elig & ~bland,
                                 jnp.abs(delta), iN)
    # Bland mode: smallest-index min-ratio column, no bound flips
    rmin = jnp.min(ratio)
    q_bland = jnp.argmax(elig & (ratio <= rmin + 1e-12))
    has_cross = jnp.where(bland, any_elig, crossed)
    q = jnp.where(bland, q_bland, q_walk)
    # only flip breakpoints strictly before the crossing in the walk's
    # (ratio, index) order — no inverse-permutation scatter (which
    # lowers to a K*N-trip sequential loop when vmapped)
    flip_mask = (elig & ~bland
                 & ((ratio < ratio[q])
                    | ((ratio == ratio[q]) & (iN < q))))

    stale = since > 0
    w = Binv @ A[:, q]
    # numerically unsafe pivot (possible only on drifted factors;
    # fresh factors guarantee |w[r]| = |alpha_q| > tol) -> no pivot,
    # force a refactorize-and-retry like the numpy twin
    unsafe = jnp.abs(w[r]) < 1e-11
    no_pivot = ~any_elig | ~has_cross
    # infeasibility on stale factors: force a refactorize-and-retry
    # instead of declaring; on fresh factors it is genuine
    new_status = jnp.where(done, OPTIMAL,
                           jnp.where(no_pivot & ~stale, INFEASIBLE,
                                     ITER_LIMIT)).astype(jnp.int32)
    do_pivot = (new_status == ITER_LIMIT) & ~no_pivot & ~unsafe
    if active is not None:
        do_pivot = do_pivot & active

    # ---- incremental pivot ----
    # single-index updates are one-hot selects, not ``.at[i].set``
    # scatters: a vmapped 1-element scatter lowers to a K-trip
    # sequential loop on CPU, ~10 of which used to dominate the batched
    # engine's per-iteration cost
    leave = basis[r]
    im = jnp.arange(Binv.shape[0])
    dxN = jnp.where(flip_mask,
                    jnp.where(at_upper, l - u, u - l), 0.0)
    xB2 = xB - Binv @ (A @ dxN)     # flip absorption (masked matvec)
    at_upper_f = at_upper ^ flip_mask
    wr = jnp.where(unsafe, 1.0, w[r])
    target = jnp.where(above, uB[r], lB[r])
    t = (xB2[r] - target) / wr
    xq = jnp.where(at_upper_f[q], u[q], l[q])
    xB3 = jnp.where(im == r, xq + t, xB2 - t * w)
    theta = d[q] / wr
    d2 = jnp.where(iN == leave, -theta,
                   jnp.where(iN == q, 0.0, d - theta * alpha))
    y2 = y + theta * rho
    Binv_r = Binv[r] / wr
    Binv2 = jnp.where((im == r)[:, None], Binv_r[None, :],
                      Binv - jnp.outer(w, Binv_r))
    at_upper2 = jnp.where(iN == q, False,
                          jnp.where(iN == leave, above, at_upper_f))
    in_basis2 = jnp.where(iN == q, True,
                          jnp.where(iN == leave, False, in_basis))
    basis2 = jnp.where(im == r, q.astype(basis.dtype), basis)

    basis = jnp.where(do_pivot, basis2, basis)
    in_basis = jnp.where(do_pivot, in_basis2, in_basis)
    at_upper = jnp.where(do_pivot, at_upper2, at_upper)
    Binv = jnp.where(do_pivot, Binv2, Binv)
    xB = jnp.where(do_pivot, xB3, xB)
    d = jnp.where(do_pivot, d2, d)
    y = jnp.where(do_pivot, y2, y)
    since = jnp.where(do_pivot, since + 1,
                      jnp.where((no_pivot | unsafe) & stale,
                                jnp.int32(refactor_every), since))

    # ---- anti-cycling: degenerate (theta ~ 0) pivot streaks ----
    degen = do_pivot & (jnp.abs(theta) <= THETA_EPS)
    progress = do_pivot & (jnp.abs(theta) > THETA_EPS)
    n_bland = n_bland + (bland & do_pivot).astype(jnp.int32)
    stall = jnp.where(progress, 0,
                      jnp.where(degen, stall + 1, stall))
    bland = jnp.where(progress, False,
                      bland | (stall >= STALL_BLAND))
    since = jnp.where(degen & (stall == STALL_REFACTOR),
                      jnp.int32(refactor_every), since)
    it2 = it + 1
    if active is not None:
        # frozen lane: every scalar field passes through (array fields
        # are already unchanged because do_pivot is False)
        st0 = state
        new_status = jnp.where(active, new_status, st0[11])
        it2 = jnp.where(active, it2, st0[12])
        since = jnp.where(active, since, st0[13])
        stall = jnp.where(active, stall, st0[7])
        bland = jnp.where(active, bland, st0[8])
        n_bland = jnp.where(active, n_bland, st0[9])
    return (basis, in_basis, at_upper, Binv, xB, d, y,
            stall.astype(jnp.int32), bland, n_bland, n_drift,
            new_status, it2.astype(jnp.int32),
            since.astype(jnp.int32))


def _gather_solution(cf, l, u, basis, in_basis, at_upper, xB):
    """Assemble the FULL (n+m,) primal vector and objective from basic
    values ``xB`` (factors already fresh — see ``_extract_solution``)."""
    xN = jnp.where(in_basis, 0.0, jnp.where(at_upper, u, l))
    # scatter-free x[basis[i]] = xB[i]: gather the basis row position of
    # each in-basis column (a vmapped scatter would run as a sequential
    # K*m-trip loop on CPU)
    iN = jnp.arange(xN.shape[0])
    pos = jnp.argmax(basis[:, None] == iN[None, :], axis=0)
    x = jnp.where(in_basis, xB[pos], xN)
    obj = cf @ jnp.where(jnp.isfinite(x), x, 0.0)
    return x, obj


def _extract_solution(cf, A, l, u, basis, in_basis, at_upper):
    """Final answer from a fresh factorization (mirrors the numpy twin's
    exit path); returns the FULL (n+m,) primal vector."""
    _, xB, _, y = _refreshed(cf, A, l, u, basis, in_basis, at_upper)
    x, obj = _gather_solution(cf, l, u, basis, in_basis, at_upper, xB)
    return x, obj, y


@partial(jax.jit, static_argnames=("max_iters", "refactor_every"))
def _solve_lp_jax(cf, A, l, u, basis0, at_upper0, max_iters: int,
                  refactor_every: int = REFACTOR_EVERY):
    n = A.shape[1] - A.shape[0]
    tol = 1e-7

    def cond(state):
        status, it = state[_STATE_STATUS], state[_STATE_IT]
        return (status == ITER_LIMIT) & (it < max_iters)

    def body(state):
        return _pivot_iter(cf, A, l, u, tol, refactor_every, state)

    # since=refactor_every in the initial state: factorize on entry
    state = _init_pivot_state(cf, A, basis0, at_upper0, refactor_every)
    state = jax.lax.while_loop(cond, body, state)
    (basis, in_basis, at_upper, _, _, _, _, _, _, n_bland, n_drift,
     status, it, _) = state
    x, obj, y = _extract_solution(cf, A, l, u, basis, in_basis, at_upper)
    return status, x[:n], obj, it, basis, at_upper, y, n_bland, n_drift


def solve_lp(c, A_t, bl, bu, ub, *, lb: Optional[np.ndarray] = None,
             max_iters: int = 5000, warm_start=None,
             mesh=None, budget: Optional[SolveBudget] = None,
             monitor: Optional[NumericalMonitor] = None) -> LPResult:
    """JAX revised dual simplex (jit + while_loop).  Same conventions as
    solve_lp_np, including the warm-start and budget/monitor contracts.
    (Wall-clock cannot be polled inside jit, so the deadline is enforced
    between LP calls and via the pivot cap, which is rounded to a coarse
    granularity so the jitted twin sees few distinct static ``max_iters``
    values instead of retracing per call.)

    ``mesh=``: a ``jax.sharding.Mesh`` routes the solve through the
    DISTRIBUTED pricing backend (``repro.core.distributed.solve_lp_dist``):
    A and the maintained reduced costs stay resident as column-sharded
    arrays across pivots, pricing is the lone O(mn/p) pass per pivot on
    each device, and only the O(num_buckets) BFRT histogram (+ the tiny
    exact in-bucket candidate gather) moves between devices.  ``mesh=None``
    keeps the single-host jit path.
    """
    if mesh is not None:
        from repro.core.distributed import solve_lp_dist
        return solve_lp_dist(c, A_t, bl, bu, ub, lb=lb,
                             max_iters=max_iters, warm_start=warm_start,
                             mesh=mesh, budget=budget, monitor=monitor)
    arrs, scale, m, n, start = _prep(c, A_t, bl, bu, ub, lb, warm_start)
    if arrs is None:
        return LPResult(INFEASIBLE, np.zeros(n), 0.0, 0,
                        np.arange(n, n + m), np.zeros(n + m, bool),
                        np.zeros(m))
    cf, A, l, u = arrs
    basis0, at_upper0, _, wnote = start
    notes = [] if wnote is None else [wnote]
    cap = max_iters
    if budget is not None:
        budget.start()
        if budget.out_of_time() or budget.remaining_pivots() <= 0:
            notes.append("budget: exhausted before LP solve")
            return LPResult(BUDGET, np.zeros(n), 0.0, 0,
                            np.asarray(basis0),
                            np.asarray(at_upper0, bool), np.zeros(m),
                            notes=tuple(notes))
        cap = budget.lp_iter_cap(max_iters)
    # one explicit device->host pull for the whole result tuple: implicit
    # scalar syncs (int(status), float(obj)) are each a separate blocking
    # transfer and fail under the strict_numerics transfer guard
    status, x, obj, it, basis, at_upper, y, n_bland, n_drift = \
        jax.device_get(_solve_lp_jax(
            jnp.asarray(cf), jnp.asarray(A), jnp.asarray(l),
            jnp.asarray(u), jnp.asarray(basis0), jnp.asarray(at_upper0),
            cap))
    status, it = int(status), int(it)
    n_bland, n_drift = int(n_bland), int(n_drift)
    if n_bland:
        notes.append(f"stall: Bland's rule for {n_bland} pivots")
    if n_drift:
        notes.append(f"drift: {n_drift} forced refactorizations")
    if monitor is not None:
        monitor.bland_pivots += n_bland
        monitor.drift_refactors += n_drift
        if n_bland:
            monitor.stall_events += 1
    if budget is not None:
        budget.charge_pivots(it)
        if status == ITER_LIMIT and (cap < max_iters
                                     or budget.exhausted()):
            status = BUDGET
            notes.append(f"budget: truncated at pivot cap {cap}")
    return LPResult(status, np.asarray(x), float(obj), it,
                    np.asarray(basis), np.asarray(at_upper),
                    np.asarray(y) * scale, notes=tuple(notes))


# ------------------------------------------------------- certificate check


def verify_optimality(res: LPResult, c, A_t, bl, bu, ub,
                      lb: Optional[np.ndarray] = None,
                      tol: float = 1e-5) -> Tuple[bool, str]:
    """Independent optimality certificate (numpy, no solver internals).

    x* is optimal iff (i) primal feasible and (ii) there exist duals y with
    reduced costs d = c - Aᵀy satisfying d_j >= 0 at lower bounds,
    d_j <= 0 at upper bounds, d_j = 0 for strictly interior x_j.  We check
    the basis-derived y, which by LP theory certifies optimality if valid.
    """
    c = np.asarray(c, np.float64)
    A_t = np.atleast_2d(np.asarray(A_t, np.float64))
    m, n = A_t.shape
    cf, A, l, u = standard_form(c, A_t, np.asarray(bl, np.float64),
                                np.asarray(bu, np.float64),
                                np.asarray(ub, np.float64))
    if lb is not None:
        l[:n] = lb
    x = res.x
    # primal feasibility
    if np.any(x < l[:n] - tol) or np.any(x > u[:n] + tol):
        return False, "primal bounds violated"
    act = A_t @ x
    if np.any(act < np.asarray(bl) - tol) or np.any(act > np.asarray(bu) + tol):
        return False, "constraint bounds violated"
    # dual feasibility + complementary slackness
    sf = np.concatenate([x, act])
    d = cf - A.T @ res.y
    at_lo = sf <= l + tol
    at_hi = sf >= u - tol
    interior = ~(at_lo | at_hi)
    if np.any(np.abs(d[interior]) > tol * (1 + np.abs(cf[interior]))):
        return False, "nonzero reduced cost at interior variable"
    bad_lo = at_lo & ~at_hi & (d < -tol)
    bad_hi = at_hi & ~at_lo & (d > tol)
    if np.any(bad_lo) or np.any(bad_hi):
        return False, "reduced-cost sign violation"
    return True, "optimal certificate valid"
