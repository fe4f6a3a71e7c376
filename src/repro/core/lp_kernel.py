"""Revised dual simplex driven by the Pallas kernels.

Same pivot rules and revised-simplex invariants as ``core.lp``
(incrementally-maintained Binv / reduced costs / xB, periodic
refactorization, warm starts) but the O(n) inner procedures run through
the TPU kernels:

  * pricing (alpha, BFRT ratios, flip costs) -> kernels.pricing — with
    reduced costs maintained by an O(n) axpy between pivots, the kernel
    is a single fused pass over A (one rank-1 matvec, one HBM read);
  * BFRT breakpoint selection -> kernels.bfrt (bucketed two-pass select).

The pricing operands are f32 on every backend, because Mosaic has no
f64: the kernel's alpha, ratios and flip costs steer the pivot choice,
while the factor state (Binv, xB, y, d) stays in the input dtype and the
answer is rebuilt from a fresh f64 factorization, so
``verify_optimality`` certifies it.  ``repro.kernels.ops`` decides
whether the kernels are compiled (TPU) or interpreted (elsewhere: slow,
correctness only).  Tested against solve_lp_np on random LPs in
tests/test_lp_kernel.py and tests/test_warm_start.py.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.guard import (DRIFT_TOL, NumericalMonitor, STALL_REFACTOR,
                              SolveBudget, THETA_EPS)
from repro.core.lp import (BUDGET, INFEASIBLE, ITER_LIMIT, OPTIMAL,
                           LPResult, REFACTOR_EVERY, _prep, basis_inverse)
from repro.kernels.bfrt import bfrt_select
from repro.kernels.ops import interpret_kernels
from repro.kernels.pricing import pricing


@partial(jax.jit, static_argnames=("max_iters", "interpret",
                                   "refactor_every"))
def _solve_lp_kernel_jax(cf, A, l, u, basis0, at_upper0, max_iters: int,
                         interpret: bool,
                         refactor_every: int = REFACTOR_EVERY):
    N = A.shape[1]
    m = A.shape[0]
    n = N - m
    tol = 1e-7

    A32 = A.astype(jnp.float32)          # resident f32 copy for pricing
    zeros32 = jnp.zeros(N, jnp.float32)
    in_basis0 = jnp.zeros(N, bool).at[basis0].set(True)
    at_upper0 = at_upper0 & ~in_basis0

    def refreshed(basis, in_basis, at_upper):
        Binv = basis_inverse(A[:, basis])
        xN = jnp.where(in_basis, 0.0, jnp.where(at_upper, u, l))
        xN = xN.at[basis].set(0.0)
        xB = -Binv @ (A @ xN)
        y = Binv.T @ cf[basis]
        d = (cf - A.T @ y).at[basis].set(0.0)
        return Binv, xB, d, y

    def cond(state):
        status, it = state[-3], state[-2]
        return (status == ITER_LIMIT) & (it < max_iters)

    def body(state):
        (basis, in_basis, at_upper, Binv, xB, d, y, stall, n_drift,
         status, it, since) = state

        # refresh branches take the factor state as an explicit operand
        # (lax.cond caches branch jaxprs by function identity; a closure
        # reused across cond calls replays stale captured tracers)
        def do_ref(ops):
            return refreshed(basis, in_basis, at_upper) + (jnp.int32(0),)

        # Binv residual drift -> forced refactorization (guard contract;
        # Bland escalation lives in the non-kernel twins, where the
        # entering-column selection is host-visible)
        resid = jnp.abs(Binv @ A[:, basis]
                        - jnp.eye(m, dtype=A.dtype)).max()
        drift = (resid > DRIFT_TOL) & (since > 0)
        n_drift = n_drift + drift.astype(jnp.int32)
        # repro: allow[REPRO001] do_ref captures the SAME loop-carried
        # tracers at both cond sites within one trace of this body
        Binv, xB, d, y, since = jax.lax.cond(
            drift | (since >= refactor_every), do_ref, lambda ops: ops,
            (Binv, xB, d, y, since))
        lB, uB = l[basis], u[basis]
        viol = jnp.maximum(lB - xB, xB - uB)
        # repro: allow[REPRO001] same captured tracers as the cond above
        Binv, xB, d, y, since = jax.lax.cond(
            (viol[jnp.argmax(viol)] <= tol) & (since > 0), do_ref,
            lambda ops: ops, (Binv, xB, d, y, since))
        viol_lo = lB - xB
        viol_hi = xB - uB
        viol = jnp.maximum(viol_lo, viol_hi)
        r = jnp.argmax(viol)
        done = viol[r] <= tol

        above = viol_hi[r] >= viol_lo[r]
        delta = jnp.where(above, xB[r] - uB[r], xB[r] - lB[r])
        s = jnp.where(delta > 0, 1.0, -1.0)
        rho = Binv[r]

        # ---- Pallas: fused pricing, the single O(mn) sweep over A ----
        state_code = jnp.where(in_basis, 2,
                               jnp.where(at_upper, 1, 0)).astype(jnp.int32)
        width = jnp.where(jnp.isfinite(u - l), u - l, 1e30)
        f32 = jnp.float32
        alpha, ratio, cost = pricing(A32, rho.astype(f32), d.astype(f32),
                                     state_code, zeros32, width.astype(f32),
                                     s.astype(f32), block=min(2048, N),
                                     tol=tol, interpret=interpret)
        alpha = alpha.astype(A.dtype)
        # ---- Pallas: bucketed BFRT select ----
        q, flip_mask, has_cross = bfrt_select(ratio, cost, jnp.abs(delta),
                                              interpret=interpret)

        stale = since > 0
        w = Binv @ A[:, q]
        # unsafe pivot on drifted factors -> refactorize-and-retry
        # (parity with the numpy twin; impossible on fresh factors)
        unsafe = jnp.abs(w[r]) < 1e-11
        no_pivot = ~has_cross
        new_status = jnp.where(done, OPTIMAL,
                               jnp.where(no_pivot & ~stale, INFEASIBLE,
                                         ITER_LIMIT)).astype(jnp.int32)
        do_pivot = (new_status == ITER_LIMIT) & ~no_pivot & ~unsafe

        # ---- incremental pivot (no inv, no full d recompute) ----
        leave = basis[r]
        dxN = jnp.where(flip_mask,
                        jnp.where(at_upper, l - u, u - l), 0.0)
        xB2 = xB - Binv @ (A @ dxN)     # flip absorption (masked matvec)
        at_upper_f = at_upper ^ flip_mask
        wr = jnp.where(unsafe, 1.0, w[r])
        target = jnp.where(above, uB[r], lB[r])
        t = (xB2[r] - target) / wr
        xq = jnp.where(at_upper_f[q], u[q], l[q])
        xB3 = (xB2 - t * w).at[r].set(xq + t)
        theta = d[q] / wr
        d2 = (d - theta * alpha).at[q].set(0.0).at[leave].set(-theta)
        y2 = y + theta * rho
        Binv_r = Binv[r] / wr
        Binv2 = (Binv - jnp.outer(w, Binv_r)).at[r].set(Binv_r)
        at_upper2 = at_upper_f.at[leave].set(above).at[q].set(False)
        in_basis2 = in_basis.at[leave].set(False).at[q].set(True)
        basis2 = basis.at[r].set(q)

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
        # degenerate-pivot streak -> forced refactorization (anti-cycling)
        degen = do_pivot & (jnp.abs(theta) <= THETA_EPS)
        progress = do_pivot & (jnp.abs(theta) > THETA_EPS)
        stall = jnp.where(progress, 0,
                          jnp.where(degen, stall + 1, stall))
        since = jnp.where(degen & (stall == STALL_REFACTOR),
                          jnp.int32(refactor_every), since)
        return (basis, in_basis, at_upper, Binv, xB, d, y,
                stall.astype(jnp.int32), n_drift, new_status,
                (it + 1).astype(jnp.int32), since.astype(jnp.int32))

    state = (basis0, in_basis0, at_upper0, jnp.eye(m, dtype=A.dtype),
             jnp.zeros(m, A.dtype), cf, jnp.zeros(m, A.dtype),
             jnp.int32(0), jnp.int32(0),
             jnp.int32(ITER_LIMIT), jnp.int32(0),
             jnp.int32(refactor_every))  # since=K: factorize on entry
    state = jax.lax.while_loop(cond, body, state)
    (basis, in_basis, at_upper, _, _, _, _, _, n_drift, status, it,
     _) = state
    Binv, xB, d, y = refreshed(basis, in_basis, at_upper)
    xN = jnp.where(in_basis, 0.0, jnp.where(at_upper, u, l))
    xN = xN.at[basis].set(0.0)
    x = xN.at[basis].set(xB)
    obj = cf @ jnp.where(jnp.isfinite(x), x, 0.0)
    return status, x[:n], obj, it, basis, at_upper, y, n_drift


def solve_lp_kernel(c, A_t, bl, bu, ub, *, lb: Optional[np.ndarray] = None,
                    max_iters: int = 5000,
                    warm_start=None,
                    budget: Optional[SolveBudget] = None,
                    monitor: Optional[NumericalMonitor] = None) -> LPResult:
    """Kernel-backed twin of core.lp.solve_lp (same conventions, including
    the warm-start and budget/monitor contracts)."""
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
    status, x, obj, it, basis, at_upper, y, n_drift = _solve_lp_kernel_jax(
        jnp.asarray(cf), jnp.asarray(A), jnp.asarray(l), jnp.asarray(u),
        jnp.asarray(basis0), jnp.asarray(at_upper0), cap,
        interpret_kernels())
    status, it, n_drift = int(status), int(it), int(n_drift)
    if n_drift:
        notes.append(f"drift: {n_drift} forced refactorizations")
    if monitor is not None:
        monitor.drift_refactors += n_drift
    if budget is not None:
        budget.charge_pivots(it)
        if status == ITER_LIMIT and (cap < max_iters
                                     or budget.exhausted()):
            status = BUDGET
            notes.append(f"budget: truncated at pivot cap {cap}")
    return LPResult(status, np.asarray(x), float(obj), it,
                    np.asarray(basis), np.asarray(at_upper),
                    np.asarray(y) * scale, notes=tuple(notes))
