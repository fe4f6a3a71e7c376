"""Batched bound-variant LP engine — one jitted solve for a whole wave.

Branch & bound, the Dual Reducer's auxiliary re-solves and the shading
ladder's retry rungs all generate *flights* of LPs that share one
``(c, A)`` and differ only in variable bounds (branching pins
``lb_j = ub_j = v``, aux rungs shrink ``ub``, ladder lanes mask columns
out by ``ub = 0``).  Solved one at a time through ``solve_lp_np`` each
tiny LP pays full Python/dispatch overhead per *pivot*; here the whole
flight runs as ONE jitted ``lax.while_loop`` whose body is the single
twin's pivot step (``repro.core.lp._pivot_iter``) vmapped over the K
bound variants — the classic inference-stack batching shape (padding,
shape classes, masked convergence) applied to the optimizer.

Design points (see ``docs/BATCHING.md``):

* **Shape classes** — m pads to a pow2, n and K to multiples of 16
  and 4 (the vmapped trip is memory-bound in (K, N) passes, so pow2
  rounding would stream up to 2x padded garbage); one compiled
  executable per class, kept in a ``BoundedStepCache`` with
  hit/miss/eviction counters, so recompiles are bounded and *counted*
  (no per-K recompile).  Padding is inert by construction: padded
  columns have ``c = 0``, a zero A-column and ``l = u = 0`` (never
  eligible to enter); padded rows are zero with ``l = u = 0`` slacks
  (never violated, their slack never leaves the basis) — the padded
  solve is the unpadded solve embedded, pivot for pivot.
* **Masked convergence** — every lane executes the vmapped pivot step
  each iteration, but a finished (or invalid/padded) lane's state is
  frozen by a per-lane ``jnp.where`` select, so it never perturbs its
  neighbors.  The loop exits when all lanes are done or the shared
  pivot budget is spent (``spent += sum(active)`` per iteration, a
  *traced* cap — budget changes never retrace).
* **Warm starts** — per-lane bases with the PR-1 validation semantics:
  each basis is validated on the padded arrays (same checks as
  ``solve_lp_np``) and rejected-to-cold per lane, surfaced via
  ``warm_start_rejected`` notes exactly like the single twins.
* **Numpy fallback** — for K = 1, or when the caller knows the flight
  is too small for batching to win (``backend="np"``), the engine
  degrades to the sequential ``solve_lp_np`` loop with identical
  per-call budget charging — bit-compatible with today's callers.

Budget contract: the shared pivot budget is charged as the SUM of
per-lane pivots through ``guard.SolveBudget`` (one ``charge_pivots``
per dispatch on the jax path; per call on the numpy path).
"""
from __future__ import annotations

import threading
from typing import List, Optional

import numpy as np

import jax
import jax.numpy as jnp

from repro.core.distributed import BoundedStepCache
from repro.core.guard import NumericalMonitor, SolveBudget
from repro.core.lp import (BUDGET, INFEASIBLE, ITER_LIMIT, LPResult,
                           REFACTOR_EVERY, _STATE_IT, _STATE_STATUS,
                           _drift_gate, _factor_refresh, _gather_solution,
                           _init_pivot_state, _optimal_suspect_gate,
                           _pivot_core, _unpack_warm, row_scaling,
                           solve_lp_np)

_M_FLOOR = 4        # smallest row shape class
_CACHE_MAXSIZE = 32  # distinct (m, n, K, cap) compiled classes kept

_K_STEP = 4         # lane-count shape classes are multiples of this
# structural columns round up to a multiple of this, NOT to a power of
# two: on a single core the vmapped trip is memory-bound in (K, N)
# passes, so pow2 rounding (e.g. n = 150 -> 256) would spend ~40% of
# every trip streaming padded columns.  A run touches only a handful of
# distinct n, so the class count stays bounded (and LRU-evicted) anyway
_N_STEP = 16

# ``backend="auto"`` crossover: a warm sequential numpy solve costs
# ~0.4 ms/lane on this class of instance, while a batched jit dispatch
# carries ~1 ms of fixed cost (trace-cache lookup, lane packing, device
# transfer, warm-basis validation, unpack).  Flights at or below this
# width route to the numpy loop; measured on the single-core CI image
# (see benchmarks/batch_lp.py and docs/BATCHING.md)
_AUTO_NP_MAX = 2

_COMPILE_CACHE = BoundedStepCache(maxsize=_CACHE_MAXSIZE)

# dispatch accounting (observability: benches record these to prove the
# shape-class policy holds — bounded classes, no per-K recompile)
_STATS = {"dispatches": 0, "instances": 0, "np_fallbacks": 0,
          "batched_pivots": 0, "prep_hits": 0, "prep_misses": 0}

_STATS_LOCK = threading.Lock()
_PREP_LOCK = threading.Lock()

# Registered with the static concurrency checker (REPRO010): mutations
# of these module globals must hold the matching lock (_STATS under
# _STATS_LOCK, _PREPPED under _PREP_LOCK).  Lock order: _PREP_LOCK may
# take _STATS_LOCK; never the reverse.
SHARED_MUTABLE = ("_STATS", "_PREPPED")


def batch_cache_stats() -> dict:
    """Counters of the compile-class cache (observability API)."""
    return _COMPILE_CACHE.stats()


def batch_stats() -> dict:
    """Dispatch counters of the batched engine (atomic snapshot)."""
    with _STATS_LOCK:
        return dict(_STATS)


def reset_batch_stats() -> None:
    with _STATS_LOCK:
        for k in _STATS:
            _STATS[k] = 0


def _pow2(v: int, floor: int) -> int:
    return max(floor, 1 << max(int(v) - 1, 0).bit_length())


def _batched_core(m_pad: int, n_pad: int, K_pad: int, max_iters: int,
                  refactor_every: int):
    """Jitted batched solver for one (m, n, K, cap) shape class.

    A fresh ``jax.jit`` wrapper is built per class so that evicting a
    cache entry actually releases its compiled executable.

    Host I/O is packed: single-core dispatch overhead is ~0.2 ms per
    device transfer, so ALL per-lane operands travel as ONE f64 array
    ``in_pack`` = [l | u | tol | basis0 | at_upper0 | valid |
    pivot_cap] (integer/bool fields are exact in f64 — indices and
    pivot counts are far below 2^53) and the ten result fields return
    as ONE f64 array ``out_pack`` = [x | y | obj | basis | status | it
    | n_bland | n_drift | at_upper | spent].
    """
    N = n_pad + m_pad

    def factory():
        def core(cf, A, in_pack):
            l_b = in_pack[:, :N]
            u_b = in_pack[:, N:2 * N]
            tol_b = in_pack[:, 2 * N]
            basis0_b = in_pack[:, 2 * N + 1:2 * N + 1 + m_pad] \
                .astype(jnp.int64)
            at_upper0_b = in_pack[:, 2 * N + 1 + m_pad:
                                  3 * N + 1 + m_pad] != 0.0
            valid_b = in_pack[:, 3 * N + 1 + m_pad] != 0.0
            pivot_cap = in_pack[0, 3 * N + 2 + m_pad].astype(jnp.int64)

            def init_one(b0, au0):
                return _init_pivot_state(cf, A, b0, au0, refactor_every)

            def gate1_one(st):
                return _drift_gate(A, refactor_every, st)

            def refresh_one(l, u, st):
                return _factor_refresh(cf, A, l, u, st)

            def gate2_one(l, u, tol, st):
                return _optimal_suspect_gate(l, u, tol, st)

            def core_one(l, u, tol, a, st):
                return _pivot_core(cf, A, l, u, tol, refactor_every, st,
                                   active=a)

            def lanes_active(st):
                return (valid_b & (st[_STATE_STATUS] == ITER_LIMIT)
                        & (st[_STATE_IT] < max_iters))

            def cond(carry):
                st, spent = carry
                return jnp.any(lanes_active(st)) & (spent < pivot_cap)

            def _sel_lanes(mask):
                def sel(a, b):
                    msk = mask.reshape((-1,) + (1,) * (a.ndim - 1))
                    return jnp.where(msk, a, b)
                return sel

            def body(carry):
                st, spent = carry
                act = lanes_active(st)

                # The single twin's pivot runs its two refresh sites as
                # per-instance lax.cond; vmapped, a cond lowers to select
                # and BOTH branches execute for every lane on every
                # iteration — K O(m^3) inverses per pivot.  Here the
                # gates are vmapped but the refresh sits behind ONE
                # batch-level scalar cond (a REAL branch), firing only on
                # the rare iterations where some active lane needs it.
                # Fusing the two sites is exact: a drift-gate refresh
                # zeroes `since`, which makes the optimal-suspect gate
                # (`... & since > 0`) False afterwards, so at most one
                # refresh per lane per trip fires either way — and the
                # suspect gate does not read the one field (n_drift) the
                # drift gate updates, so evaluating it pre-refresh gives
                # the same bit.  The refreshed state is tree-selected per
                # lane on its own `need` bit (need ⊆ act: frozen lanes
                # are never touched — their scalar fields are gated
                # inside _pivot_core via `active`).
                def refresh_where(need):
                    def go(s):
                        ref = jax.vmap(refresh_one)(l_b, u_b, s)
                        return jax.tree_util.tree_map(
                            _sel_lanes(need), ref, s)
                    return go

                st1, need1 = jax.vmap(gate1_one)(st)
                # drift events on frozen lanes don't count (the numpy
                # twin stopped looking when the lane finished)
                st1 = st1[:10] + (jnp.where(act, st1[10], st[10]),) \
                    + st1[11:]
                need2 = jax.vmap(gate2_one)(l_b, u_b, tol_b, st1)
                need = (need1 | need2) & act
                # repro: allow[REPRO001] refresh_where(need) is a fresh
                # identity per trace capturing this body's own tracers
                st1 = jax.lax.cond(jnp.any(need),
                                   refresh_where(need), lambda s: s, st1)
                new = jax.vmap(core_one)(l_b, u_b, tol_b, act, st1)
                return new, spent + jnp.sum(act.astype(spent.dtype))

            state0 = jax.vmap(init_one)(basis0_b, at_upper0_b)
            # eager factorization (like the numpy twin): refresh every
            # lane ONCE before the loop so the first trips — where most
            # warm-started lanes already converge — never enter the
            # refresh branch
            state0 = jax.vmap(refresh_one)(l_b, u_b, state0)
            st, spent = jax.lax.while_loop(
                cond, body, (state0, jnp.asarray(0, jnp.int64)))

            # exit contract of the numpy twin: the final answer comes
            # from a fresh factorization.  A lane exiting with since=0
            # was refreshed on the very trip it settled (the optimal-
            # suspect gate, or the eager factorization above), so its
            # carried xB / y ARE the fresh-factor values — recomputing
            # them is the identity.  Only lanes truncated mid-streak
            # (iteration cap / shared budget) still carry stale factors;
            # the batched refactorization lowers behind a scalar cond
            # that in the common all-optimal dispatch never fires.
            need_exit = st[13] > 0

            def exit_refresh(s):
                ref = jax.vmap(refresh_one)(l_b, u_b, s)
                return jax.tree_util.tree_map(
                    _sel_lanes(need_exit), ref, s)

            # repro: allow[REPRO001] fresh identity per trace, capturing
            # this core's own tracers
            st = jax.lax.cond(jnp.any(need_exit), exit_refresh,
                              lambda s: s, st)
            basis, in_basis, at_upper, xB, y = (st[0], st[1], st[2],
                                                st[4], st[6])
            n_bland, n_drift = st[9], st[10]
            status, it = st[_STATE_STATUS], st[_STATE_IT]

            def fin_one(l, u, b, ib, au, xb):
                return _gather_solution(cf, l, u, b, ib, au, xb)

            x, obj = jax.vmap(fin_one)(l_b, u_b, basis, in_basis,
                                       at_upper, xB)
            # pack in the TRACE dtype (f64 in production; an f32 trace —
            # the IRC005 contract probe — must not introduce f64)
            ff = lambda a: a.astype(in_pack.dtype)  # noqa: E731
            spent_col = jnp.broadcast_to(ff(spent), (K_pad,))
            return jnp.concatenate(
                [x, y, obj[:, None], ff(basis),
                 ff(status)[:, None], ff(it)[:, None],
                 ff(n_bland)[:, None], ff(n_drift)[:, None],
                 ff(at_upper), spent_col[:, None]], axis=1)

        return jax.jit(core)

    key = (m_pad, n_pad, K_pad, max_iters, refactor_every)
    return _COMPILE_CACHE.get_or_create(key, factory)


_PREP_MAX = 8        # prepared shared-(c, A) standard forms kept resident
_PREPPED: List[dict] = []


def _prep_shared(c, A_t, bl, bu, m_pad: int, n_pad: int) -> dict:
    """Build (or reuse) the padded shared standard form + its device
    arrays.  A B&B wave loop re-dispatches the SAME (c, A, bl, bu) every
    wave; re-padding and re-transferring the matrix per dispatch costs
    more than the solve for small flights, so prepared forms are cached
    by content (a memcmp-style compare — in-place caller mutations are
    therefore safe) and bounded FIFO.

    ``_PREP_LOCK`` is held for the whole scan-build-insert (the build is
    numpy padding, cheap relative to a solve), so the check-then-act is
    one atomic scope and concurrent waves share one prepared form."""
    with _PREP_LOCK:
        for e in _PREPPED:
            if (e["m_pad"] == m_pad and e["n_pad"] == n_pad
                    and e["c"].shape == c.shape
                    and e["A_t"].shape == A_t.shape
                    and np.array_equal(e["c"], c)
                    and np.array_equal(e["A_t"], A_t)
                    and np.array_equal(e["bl"], bl)
                    and np.array_equal(e["bu"], bu)):
                with _STATS_LOCK:
                    _STATS["prep_hits"] += 1
                return e
        with _STATS_LOCK:
            _STATS["prep_misses"] += 1
        m, n = A_t.shape
        N_pad = n_pad + m_pad
        scale = row_scaling(A_t)
        cf = np.zeros(N_pad)
        cf[:n] = c
        A = np.zeros((m_pad, N_pad))
        A[:m, :n] = -(A_t * scale[:, None])
        A[:, n_pad:] = np.eye(m_pad)
        e = {"c": c.copy(), "A_t": A_t.copy(), "bl": bl.copy(),
             "bu": bu.copy(), "m_pad": m_pad, "n_pad": n_pad,
             "scale": scale, "cf": cf, "A": A,
             "bls": bl * scale, "bus": bu * scale,
             "cf_dev": jnp.asarray(cf), "A_dev": jnp.asarray(A)}
        _PREPPED.append(e)
        if len(_PREPPED) > _PREP_MAX:
            _PREPPED.pop(0)
        return e


def _validate_warm_batch(A, cf, l_rows, u_rows, tol_rows, WB, HT):
    """Vectorized per-lane warm-basis validation — the same acceptance
    rules as ``lp._warm_state``, applied to all W candidate bases at
    once (one batched inverse instead of W host factorizations).

    Returns ``(ok, at_up, reasons)``: accept mask (W,), the derived
    bound patterns (W, N) for accepted lanes, and a rejection reason
    per lane (None when accepted)."""
    W, m = WB.shape
    N = A.shape[1]
    ok = np.ones(W, bool)
    reasons: List[Optional[str]] = [None] * W
    at_up = np.zeros((W, N), bool)
    srt = np.sort(WB, axis=1)
    bad_idx = (WB.min(axis=1) < 0) | (WB.max(axis=1) >= N) | \
        np.any(srt[:, 1:] == srt[:, :-1], axis=1)
    for i in np.flatnonzero(bad_idx):
        ok[i] = False
        reasons[i] = "basis indices out of range or duplicated"
    good = np.flatnonzero(ok)
    if not good.size:
        return ok, at_up, reasons
    WBg = WB[good]
    B = np.transpose(A[:, WBg], (1, 0, 2))        # (G, m, m)
    try:
        Binv = np.linalg.inv(B)
    except np.linalg.LinAlgError:
        Binv = np.full_like(B, np.inf)
        for gi in range(len(B)):
            try:
                Binv[gi] = np.linalg.inv(B[gi])
            except np.linalg.LinAlgError:
                reasons[good[gi]] = "singular basis"
    with np.errstate(invalid="ignore"):
        illcond = ~np.all(np.isfinite(Binv), axis=(1, 2)) | \
            (np.max(np.abs(np.where(np.isfinite(Binv), Binv, np.inf)),
                    axis=(1, 2)) > 1e12)
    cB = cf[WBg]                                   # (G, m)
    y = (np.transpose(Binv, (0, 2, 1)) @ cB[..., None])[..., 0]
    d = cf[None, :] - y @ A                        # (G, N)
    np.put_along_axis(d, WBg, 0.0, axis=1)
    IB = np.zeros((len(good), N), bool)
    np.put_along_axis(IB, WBg, True, axis=1)
    tg = tol_rows[good][:, None]
    Lg, Ug = l_rows[good], u_rows[good]
    au = np.where(d < -tg, True, np.where(d > tg, False, HT[good]))
    inf_l = np.isinf(Lg)
    inf_u = np.isinf(Ug)
    if inf_l.any() or inf_u.any():
        au |= inf_l
        au &= ~inf_u
        bad_dual = np.any((~IB) & (((d < -tg) & inf_u)
                                   | ((d > tg) & inf_l)
                                   | (inf_l & inf_u)), axis=1)
    else:
        # all-finite bounds (every B&B / aux-rung / ladder flight): no
        # pinned-at-infinity patterns exist, skip their (G, N) passes
        bad_dual = np.zeros(len(good), bool)
    au[IB] = False
    for gi, i in enumerate(good):
        if reasons[i] is not None:                 # singular (fallback)
            ok[i] = False
        elif illcond[gi]:
            ok[i] = False
            reasons[i] = "ill-conditioned basis"
        elif bad_dual[gi]:
            ok[i] = False
            reasons[i] = \
                "dual-infeasible column pinned at an infinite bound"
        else:
            at_up[i] = au[gi]
    return ok, at_up, reasons


def _as_bound_arr(batch, K: int, n: int, default: float,
                  name: str) -> np.ndarray:
    """Normalize ub_batch / lb_batch into one (K, n) float64 array."""
    if batch is None:
        return np.full((K, n), default)
    try:
        # fast path: uniform (n,) rows stack in one numpy call (the B&B
        # wave always lands here — per-lane python only on odd payloads)
        arr = np.asarray(batch, np.float64)
        if arr.shape == (K, n):
            return arr
    except (ValueError, TypeError):
        pass
    rows = []
    for k in range(K):
        b = batch[k]
        if b is None:
            rows.append(np.full(n, default))
            continue
        b = np.asarray(b, np.float64).ravel()
        if b.shape != (n,):
            raise ValueError(f"{name}[{k}] shape {b.shape} != ({n},)")
        rows.append(b)
    return np.stack(rows)


def _infeasible_result(n: int, m: int, note: Optional[str] = None,
                       status: int = INFEASIBLE) -> LPResult:
    return LPResult(status, np.zeros(n), 0.0, 0, np.arange(n, n + m),
                    np.zeros(n + m, bool), np.zeros(m),
                    notes=() if note is None else (note,))


def solve_lp_batch(c, A_t, bl, bu, ub_batch, lb_batch=None, *,
                   tol=1e-7, max_iters: int = 5000, warm_starts=None,
                   budget: Optional[SolveBudget] = None,
                   monitor: Optional[NumericalMonitor] = None,
                   backend: str = "auto",
                   refactor_every: int = REFACTOR_EVERY) -> List[LPResult]:
    """Solve K bound-variants of one shared LP as one batched dispatch.

    ``(c, A_t, bl, bu)`` are shared; ``ub_batch`` / ``lb_batch`` are
    length-K sequences of per-variable bounds (entries may be ``None``
    for the defaults ``ub = +inf`` is NOT assumed — ``ub_batch`` entries
    must be given; ``lb`` defaults to 0).  ``tol`` is a scalar or a
    length-K sequence (the shading ladder relaxes tolerance per lane).
    ``warm_starts`` is ``None`` or a length-K sequence of per-lane
    ``LPResult`` / ``WarmStart`` / ``(basis, at_upper)`` / ``None``.

    Returns a list of K ``LPResult`` in input order, each carrying the
    same status codes, notes and warm-start semantics as the single
    twins.  ``backend="auto"`` falls back to the sequential numpy twin
    for K <= 2 (K = 1 is bit-compatible with ``solve_lp_np``; at K = 2
    the jitted dispatch's fixed cost still exceeds two warm sequential
    solves — see docs/BATCHING.md); ``"np"`` forces the fallback,
    ``"jax"`` forces the batched path.
    """
    if backend not in ("auto", "np", "jax"):
        raise ValueError(f"unknown backend {backend!r}")
    ub_batch = list(ub_batch)
    K = len(ub_batch)
    if K == 0:
        return []
    c = np.asarray(c, np.float64)
    A_t = np.atleast_2d(np.asarray(A_t, np.float64))
    m, n = A_t.shape
    ub_arr = _as_bound_arr(ub_batch, K, n, np.inf, "ub_batch")
    lb_arr = _as_bound_arr(lb_batch, K, n, 0.0, "lb_batch")
    tol_arr = (np.full(K, float(tol)) if np.isscalar(tol)
               else np.asarray([float(t) for t in tol], np.float64))
    if tol_arr.shape != (K,):
        raise ValueError(f"tol length {tol_arr.shape[0]} != K={K}")
    warm_list = list(warm_starts) if warm_starts is not None \
        else [None] * K
    if len(warm_list) != K:
        raise ValueError(f"warm_starts length {len(warm_list)} != K={K}")

    with _STATS_LOCK:
        _STATS["instances"] += K
    if backend == "np" or (backend == "auto" and K <= _AUTO_NP_MAX):
        # sequential fallback: per-call budget charging, identical to the
        # existing caller loops
        with _STATS_LOCK:
            _STATS["np_fallbacks"] += 1
        return [solve_lp_np(c, A_t, bl, bu, ub_arr[k], lb=lb_arr[k],
                            max_iters=max_iters, tol=float(tol_arr[k]),
                            warm_start=warm_list[k], budget=budget,
                            monitor=monitor)
                for k in range(K)]

    with _STATS_LOCK:
        _STATS["dispatches"] += 1
    # ---- shared standard form, padded to the (m, n, K) shape class ----
    # m rounds up to pow2 (rows are tiny); n and K round up to multiples
    # of _N_STEP / _K_STEP — on a single core the vmapped body's cost is
    # proportional to K_pad * N_pad, so pow2 rounding would waste up to
    # 2x compute streaming padded lanes and padded columns.  Class count
    # stays bounded: K <= 2*wave_width gives at most 2W/_K_STEP classes
    # per geometry, and a run touches a handful of distinct n, all
    # within the LRU's maxsize
    m_pad = _pow2(m, _M_FLOOR)
    n_pad = -(-n // _N_STEP) * _N_STEP
    K_pad = -(-K // _K_STEP) * _K_STEP
    N_pad = n_pad + m_pad
    shared = _prep_shared(c, A_t, np.asarray(bl, np.float64),
                          np.asarray(bu, np.float64), m_pad, n_pad)
    cf, A = shared["cf"], shared["A"]
    bls, bus, scale = shared["bls"], shared["bus"], shared["scale"]

    cap = max_iters
    notes_pre: List[List[str]] = [[] for _ in range(K)]
    if budget is not None:
        budget.start()
        if budget.out_of_time() or budget.remaining_pivots() <= 0:
            return [_infeasible_result(
                n, m, "budget: exhausted before LP solve", BUDGET)
                for _ in range(K)]
        cap = budget.lp_iter_cap(max_iters)

    # ---- vectorized lane assembly (no per-lane python work) ----
    # ALL per-lane operands are packed into ONE f64 array: on a single
    # core every extra device transfer costs ~0.2 ms, which at B&B wave
    # rates adds up to more than the solve itself (layout documented in
    # ``_batched_core``; views below alias in_pack, writes land in it)
    in_pack = np.zeros((K_pad, 3 * N_pad + m_pad + 3))
    l_b = in_pack[:, :N_pad]
    u_b = in_pack[:, N_pad:2 * N_pad]
    basis0_b = in_pack[:, 2 * N_pad + 1:2 * N_pad + 1 + m_pad]
    at_upper0_b = in_pack[:, 2 * N_pad + 1 + m_pad:
                          3 * N_pad + 1 + m_pad]
    valid_b = in_pack[:, 3 * N_pad + 1 + m_pad]
    l_b[:K, :n] = lb_arr
    u_b[:K, :n] = ub_arr
    l_b[:K, n_pad:n_pad + m] = bls
    u_b[:K, n_pad:n_pad + m] = bus
    in_pack[:, 2 * N_pad] = 1e-7
    in_pack[:K, 2 * N_pad] = tol_arr
    box_infeasible = np.any(l_b[:K] > u_b[:K] + tol_arr[:, None], axis=1)
    valid_b[:K] = ~box_infeasible
    # cold start for every lane (vectorized lp._cold_start; warm lanes
    # overwrite below).  Padded lanes keep the all-slack basis over the
    # all-zero padded LP and stay valid_b=0, so they never step.
    basis0_b[:] = np.arange(n_pad, N_pad, dtype=np.int64)
    at_upper0_b[:, :n_pad] = (cf[None, :n_pad] < 0) | \
        np.isinf(l_b[:, :n_pad])

    # ---- warm bases: remap into padded space, validate all at once ----
    # per-lane python here is just ``_unpack_warm`` + a shape check; the
    # pad-space remap, hint packing and acceptance writes are all (L, .)
    # numpy ops (at B&B wave rates the old per-lane remap alone cost
    # more than the device transfer)
    warm_lanes: List[int] = []
    wb_raw: List[np.ndarray] = []
    ht_raw: List[Optional[np.ndarray]] = []
    for k in range(K):
        if not valid_b[k]:
            continue
        wb, wh = _unpack_warm(warm_list[k])
        if wb is None:
            continue
        wb = np.asarray(wb, np.int64).ravel()
        if wb.shape != (m,):
            notes_pre[k].append(
                f"warm_start_rejected: basis shape {wb.shape} != "
                f"({m},); cold start used")
            continue
        warm_lanes.append(k)
        wb_raw.append(wb)
        ht_raw.append(wh)
    if warm_lanes:
        lanes = np.asarray(warm_lanes)
        L = len(warm_lanes)
        # caller (n+m)-space indices into the padded space; padded
        # slacks sit on the padded rows
        WBr = np.stack(wb_raw)
        WB = np.empty((L, m_pad), np.int64)
        WB[:, :m] = np.where(WBr < n, WBr, n_pad + (WBr - n))
        WB[:, m:] = np.arange(n_pad + m, N_pad, dtype=np.int64)
        HT = np.zeros((L, N_pad), bool)
        hs = [None if wh is None else np.asarray(wh, bool).ravel()
              for wh in ht_raw]
        if all(h is not None and h.shape == (n + m,) for h in hs):
            WHr = np.stack(hs)
            HT[:, :n] = WHr[:, :n]
            HT[:, n_pad:n_pad + m] = WHr[:, n:]
        else:  # mixed / odd hint payloads: rare, keep the lane loop
            for i, h in enumerate(hs):
                if h is not None and h.shape == (n + m,):
                    HT[i, :n] = h[:n]
                    HT[i, n_pad:n_pad + m] = h[n:]
        ok, au, reasons = _validate_warm_batch(
            A, cf, l_b[lanes], u_b[lanes], tol_arr[lanes], WB, HT)
        acc = lanes[ok]
        basis0_b[acc] = WB[ok]
        at_upper0_b[acc] = au[ok]
        for i in np.flatnonzero(~ok):
            notes_pre[lanes[i]].append(
                f"warm_start_rejected: {reasons[i]}; cold start used")

    results: List[Optional[LPResult]] = [None] * K
    for k in np.flatnonzero(box_infeasible):
        results[k] = _infeasible_result(n, m)

    if not np.any(valid_b):
        return results  # every lane decided on the host

    pivot_cap = K * cap
    if budget is not None:
        pivot_cap = int(min(pivot_cap, max(budget.remaining_pivots(), 1)))
    in_pack[0, 3 * N_pad + 2 + m_pad] = pivot_cap

    core = _batched_core(m_pad, n_pad, K_pad, cap, refactor_every)
    out = jax.device_get(core(shared["cf_dev"], shared["A_dev"],
                              jnp.asarray(in_pack)))
    # unpack + un-pad ALL lanes vectorized (layout in ``_batched_core``)
    o = N_pad + m_pad
    x_b = out[:K, :n]
    y_b = out[:K, N_pad:N_pad + m] * scale
    obj_b = out[:K, o]
    basis_b = out[:K, o + 1:o + 1 + m].astype(np.int64)
    basis_b = np.where(basis_b < n_pad, basis_b, n + (basis_b - n_pad))
    stats_i = out[:K, o + 1 + m_pad:o + 5 + m_pad].astype(np.int64)
    status_l, it_l, n_bland_l, n_drift_l = stats_i.T.tolist()
    au = out[:K, o + 5 + m_pad:o + 5 + m_pad + N_pad]
    at_upper_b = np.concatenate(
        [au[:, :n], au[:, n_pad:n_pad + m]], axis=1) != 0.0

    spent = int(out[0, 2 * N_pad + 2 * m_pad + 5])
    with _STATS_LOCK:
        _STATS["batched_pivots"] += spent
    shared_hit = spent >= pivot_cap
    if budget is not None:
        budget.charge_pivots(spent)
    lane_ok = valid_b[:K] != 0.0
    n_bland_tot = int(stats_i[lane_ok, 2].sum())
    n_drift_tot = int(stats_i[lane_ok, 3].sum())
    if monitor is not None:
        monitor.bland_pivots += n_bland_tot
        monitor.drift_refactors += n_drift_tot
        if n_bland_tot:
            monitor.stall_events += 1

    truncatable = budget is not None and (cap < max_iters or shared_hit
                                          or budget.exhausted())
    for k in range(K):
        if results[k] is not None:
            continue
        st = status_l[k]
        notes = list(notes_pre[k])
        if n_bland_l[k]:
            notes.append(f"stall: Bland's rule for {n_bland_l[k]} "
                         "pivots")
        if n_drift_l[k]:
            notes.append(f"drift: {n_drift_l[k]} forced "
                         "refactorizations")
        if st == ITER_LIMIT and truncatable:
            st = BUDGET
            notes.append(f"budget: truncated at pivot cap {cap}")
        results[k] = LPResult(st, x_b[k], float(obj_b[k]), it_l[k],
                              basis_b[k], at_upper_b[k], y_b[k],
                              notes=tuple(notes))
    return results
