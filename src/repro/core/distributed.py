"""Distributed pricing backend for the revised dual simplex — the paper's
80-core Parallel Dual Simplex (Mini-Exp 3) mapped onto a TPU pod with
shard_map, promoted from a dry-run lowering proof to the engine's actual
multi-device execution path (``solve_lp_dist`` / ``solve_lp(mesh=...)``).

Tuple columns (the A matrix) and the per-column simplex state — the
MAINTAINED reduced costs ``d``, the nonbasic position codes and the bounds
— live sharded over the data axes and stay device-resident across pivots;
the m x m basis state (basis inverse, duals, basic primal values) is tiny
and replicated on the host.  Three shard_map programs per pivot:

``pq_step``   — pricing + BFRT selection.  Per device:
  1. pricing: alpha = rho @ A_shard                  (the LONE O(m n/p)
     sweep of A; ``d`` arrives maintained, there is NO ``c - y @ A``
     recompute — the redundancy PR 1 removed from the single-host twins)
  2. BFRT pass 1: local breakpoint histogram          (local O(n/p))
  3. psum of histograms + crossing-bucket selection   (collective, O(NB))
  4. pass 2: EXACT in-crossing-bucket walk — each shard contributes its
     K smallest in-bucket breakpoints (top_k), one all_gather of the
     (p, K) candidate block, and the replicated exact merge locates the
     entering variable precisely as the sequential BFRT would.  When a
     shard holds more than K in-bucket breakpoints below the crossing
     point (detected, never assumed), the step falls back to the valid
     conservative pivot at the bucket minimum for that iteration only.

``update_step`` — the post-pivot O(n/p) axpy ``d -= theta * alpha`` plus
  bound-flip / basis-exchange bookkeeping on the state codes.  Purely
  local: zero collective traffic.

``refresh_step`` — periodic refactorization support (every
  ``REFACTOR_EVERY`` pivots): recomputes ``d = c - A^T y`` from fresh
  duals and returns ``A @ xN`` for the basic-value rebuild.  This is the
  ONLY place the full reduced-cost recompute exists, mirroring the
  single-host engines' ``refreshed()``.

Per-iteration collective traffic is O(num_buckets + p*K + m): the design
point of the TPU adaptation.  ``launch/dryrun.py --pq`` lowers the step
for the 2x16x16 pod mesh to prove it; ``benchmarks/warm_start.py``
benchmarks multi-pivot solves through this path against ``solve_lp_np``.
"""
from __future__ import annotations

import threading
from collections import OrderedDict
from typing import Dict, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.kernels.pricing import pricing_math
from repro.runtime import racecheck

NUM_BUCKETS = 128
GATHER_K = 128        # per-shard in-bucket candidates for the exact walk
_TOL = 1e-9
WIDTH_CAP = 1e30      # stand-in for infinite bound widths (flip cost = huge)


def big_sentinel(dtype):
    """Largest-finite sentinel for masked min/max reductions.

    Derived from the dtype so it is exact under any x64 setting —
    ``jnp.float64(1e300)`` warns and truncates to inf when jax runs with
    default 32-bit floats, which silently breaks the masked reductions.
    """
    return jnp.asarray(jnp.finfo(jnp.dtype(dtype)).max, dtype)


class ShardFailure(RuntimeError):
    """A mesh shard or collective failed while ``solve_lp_dist`` ran:
    the ``dist.shard`` fault site, or an XLA runtime error from a step
    that had already compiled and run.  The one failure the solver
    answers with its single-host fallback."""


def _run_step(step, ran, *args):
    """Call a mesh step.  Its first call compiles it, and an XLA error
    there (a lowering the backend refuses, an executable that does not
    fit) propagates as it is: that is no shard failure.  Once the step
    has run, an XLA runtime error from it is one."""
    if step not in ran:
        out = step(*args)
        ran.add(step)
        return out
    try:
        return step(*args)
    except jax.errors.JaxRuntimeError as e:
        raise ShardFailure(str(e)) from e


def _pull(*xs):
    """One device->host transfer of step outputs; an XLA runtime error
    that surfaces here (the steps ran asynchronously) is a shard
    failure."""
    try:
        return jax.device_get(xs)
    except jax.errors.JaxRuntimeError as e:
        raise ShardFailure(str(e)) from e


def _running_sum(x):
    """Inclusive prefix sum.  ``jnp.cumsum`` lowers on TPU to a
    reduce-window that XLA takes minutes to compile in f64; the
    associative scan compiles in seconds."""
    return jax.lax.associative_scan(jnp.add, x)


def _mesh_axes(mesh) -> Tuple[str, ...]:
    return tuple(a for a in ("pod", "data", "model") if a in mesh.shape)


def _my_rank(mesh, axes):
    rank = jax.lax.axis_index(axes[0]).astype(jnp.int64)
    for ax in axes[1:]:
        rank = rank * mesh.shape[ax] + jax.lax.axis_index(ax)
    return rank


def make_pq_step(mesh: Mesh, m: int, n: int,
                 num_buckets: int = NUM_BUCKETS, gather_k: int = GATHER_K):
    """Builds the distributed pricing + BFRT-selection step.

    ``step(A, d, l, u, state, rho, s, budget)`` with A ``(m, n)`` sharded
    on columns over the mesh's data axes; ``d``/``l``/``u`` ``(n,)`` and
    ``state`` int32 ``(n,)`` (0 = at-lower, 1 = at-upper, 2 = basic)
    sharded alike; ``rho`` (the pivot row of Binv), ``s`` (sign of the
    primal infeasibility) and ``budget`` (|delta|) replicated.

    Returns ``(alpha, flip_mask, r_best, q, d_q, at_up_q, Acol, fvec,
    n_flips, has_cross, exact)``:

      alpha     (n,)  sharded — kept on-device for the post-pivot axpy
      flip_mask (n,)  sharded bool — bound flips below the entering ratio
                      (capped at the K smallest per shard, a valid BFRT
                      early stop, so absorption needs only K gathered
                      columns instead of a second dense sweep of A)
      r_best    ()    entering BFRT ratio
      q         ()    global entering column index (int64)
      d_q       ()    maintained reduced cost of the entering column
      at_up_q   ()    whether q currently sits at its upper bound
      Acol      (m,)  the entering column of A (for w = Binv @ Acol)
      fvec      (m,)  A @ dx over flipped columns (flip absorption)
      n_flips   ()    number of bound flips this pivot
      has_cross ()    False => dual unbounded (no eligible crossing)
      exact     ()    True  => the in-bucket walk was exact (not the
                      conservative bucket-minimum fallback)

    Consumes the MAINTAINED reduced costs: no ``c - y @ A`` matvec occurs
    anywhere in this step; ``alpha = rho @ A_shard`` is the lone O(mn/p)
    pass over A.
    """
    axes = _mesh_axes(mesh)
    col_spec = P(None, axes)
    vec_spec = P(axes)
    rep = P()

    def step(A_loc, d_loc, l_loc, u_loc, state_loc, rho, s, budget):
        n_loc = A_loc.shape[1]
        alpha = rho @ A_loc               # pricing: the lone O(mn/p) sweep
        width = u_loc - l_loc
        width = jnp.where(jnp.isfinite(width), width, WIDTH_CAP)
        ratio, cost = pricing_math(alpha, d_loc, state_loc, width, s, _TOL)
        finite = jnp.isfinite(ratio)
        big = big_sentinel(ratio.dtype)

        # ---- BFRT pass 1: bucket the breakpoint ratios (psum: O(NB)) ----
        # the global ratio range from one all_gather of (max, -min):
        # XLA:TPU lowers only SUM all-reduces in f64, so pmax/pmin of an
        # f64 scalar do not compile for the chip
        ext = jax.lax.all_gather(
            jnp.stack([jnp.max(jnp.where(finite, ratio, -big)),
                       -jnp.min(jnp.where(finite, ratio, big))]),
            axes).reshape(-1, 2)
        rmax, rmin = jnp.max(ext[:, 0]), -jnp.max(ext[:, 1])
        span = jnp.maximum(rmax - rmin, 1e-12)
        # keep the edge grid in the pricing dtype: under x64 the bare
        # int-arange / int division promotes to f64 and silently drags
        # every downstream comparison with it on f32 problems
        grid = jnp.arange(1, num_buckets + 1,
                          dtype=ratio.dtype) / num_buckets
        edges = rmin + span * grid
        bucket = jnp.clip(jnp.searchsorted(edges, ratio), 0, num_buckets - 1)
        hist_l = jnp.zeros(num_buckets, cost.dtype).at[bucket].add(
            jnp.where(finite, cost, 0.0))
        hist = jax.lax.psum(hist_l, axes)
        csum = _running_sum(hist)
        crossed = csum >= budget - 1e-12
        bidx = jnp.argmax(crossed)
        has_cross = jnp.any(crossed)
        lo_edge = jnp.where(bidx == 0, -jnp.inf,
                            edges[jnp.maximum(bidx - 1, 0)])
        hi_edge = edges[bidx]
        base = jnp.where(bidx == 0, 0.0, csum[jnp.maximum(bidx - 1, 0)])

        # ---- pass 2: exact walk inside the crossing bucket.  Each shard
        # contributes its K smallest in-bucket breakpoints; the gathered
        # (p, K) block is tiny and replicated, so the merge reproduces the
        # sequential BFRT exactly whenever no shard truncates below the
        # crossing point (checked; conservative fallback otherwise). ----
        k = min(gather_k, n_loc)
        in_b = finite & (ratio > lo_edge) & (ratio <= hi_edge)
        r_in = jnp.where(in_b, ratio, big)
        neg_top, idx = jax.lax.top_k(-r_in, k)
        r_k = -neg_top                               # k smallest in-bucket
        valid_k = r_k < big
        cost_k = jnp.where(valid_k, cost[idx], 0.0)
        d_k = d_loc[idx]
        up_k = state_loc[idx] == 1
        rank = _my_rank(mesh, axes)
        g_k = rank * n_loc + idx.astype(jnp.int64)
        cnt_in = jnp.sum(in_b)
        trunc = cnt_in > k                           # shard truncated?
        kth = r_k[k - 1]                             # largest gathered

        gat = lambda x: jax.lax.all_gather(x, axes).reshape(-1)
        r_g, cost_g, d_g, up_g, valid_g = map(
            gat, (r_k, cost_k, d_k, up_k, valid_k))
        g_g = gat(g_k)
        trunc_g = jax.lax.all_gather(trunc, axes).reshape(-1)    # (p,)
        kth_g = jax.lax.all_gather(kth, axes).reshape(-1)        # (p,)

        order = jnp.argsort(jnp.where(valid_g, r_g, big))
        r_s = r_g[order]
        valid_s = valid_g[order]
        csum_in = base + _running_sum(jnp.where(valid_s, cost_g[order],
                                                0.0))
        crossed_in = (csum_in >= budget - 1e-12) & valid_s
        pos = jnp.argmax(crossed_in)
        found = jnp.any(crossed_in)
        # exact iff the walk crossed within the gathered prefix and no
        # truncated shard could hide a breakpoint below the entering ratio
        r_exact = r_s[pos]
        ok = found & jnp.all(~trunc_g | (r_exact <= kth_g))
        sel = jnp.where(ok, pos, 0)                  # fallback: bucket min
        q = g_g[order][sel]
        r_best = r_s[sel]
        d_q = d_g[order][sel]
        at_up_q = up_g[order][sel]

        # ---- flips: everything strictly below the entering ratio PLUS
        # the gathered tie breakpoints the exact walk consumed before the
        # crossing position (degenerate pivots carry most of their
        # progress in equal-ratio flips, so skipping ties would stall the
        # solve exactly like the textbook non-BFRT dual simplex). ----
        flip_strict = finite & (ratio < r_best)
        # merged positions of THIS shard's gathered candidates
        merged_rank = jnp.empty_like(order).at[order].set(
            jnp.arange(order.shape[0]))
        mine = jax.lax.dynamic_slice(
            merged_rank, (rank.astype(jnp.int32) * k,), (k,))
        tie_sel = valid_k & (mine < sel) & (r_k >= r_best)
        flip_mask = flip_strict.at[idx].max(tie_sel)
        n_flips = jax.lax.psum(jnp.sum(flip_mask), axes)

        # ---- flip absorption fvec = A @ dx (psum: O(m)).  The strict
        # flips are the globally smallest ratios, so when a shard has at
        # most K of them the columns are fetched sparsely (O(mK) gather,
        # pricing stays the lone dense O(mn/p) sweep); a shard only falls
        # back to the dense masked matvec on the rare pivot whose local
        # flip count exceeds K — a per-shard runtime branch, not a
        # different global program. ----
        at_up = state_loc == 1
        neg_f, fidx = jax.lax.top_k(-jnp.where(finite, ratio, big), k)
        fsel = (-neg_f < r_best) & (-neg_f < big)
        over = jnp.sum(flip_strict) > k

        def fvec_sparse(_):
            up_f = at_up[fidx]
            dxf = jnp.where(fsel, jnp.where(up_f, -width[fidx],
                                            width[fidx]), 0.0)
            s1 = A_loc[:, fidx] @ dxf                  # (m, K) gather
            up_t = at_up[idx]
            dxt = jnp.where(tie_sel, jnp.where(up_t, -width[idx],
                                               width[idx]), 0.0)
            return s1 + A_loc[:, idx] @ dxt
        def fvec_dense(_):
            dx = jnp.where(flip_mask, jnp.where(at_up, -width, width), 0.0)
            return A_loc @ dx
        # repro: allow[REPRO001] one call site per trace: the captured
        # shard state is identical for both branches of this single cond
        fvec = jax.lax.cond(over, fvec_dense, fvec_sparse, None)
        fvec = jax.lax.psum(fvec, axes)
        # entering column, contributed by its owner shard
        j_loc = jnp.clip(q - rank * n_loc, 0, n_loc - 1)
        owner = (q >= rank * n_loc) & (q < (rank + 1) * n_loc)
        Acol = jax.lax.psum(
            jnp.where(owner, A_loc[:, j_loc], jnp.zeros(A_loc.shape[0],
                                                        A_loc.dtype)), axes)
        return (alpha, flip_mask, r_best, q, d_q, at_up_q, Acol, fvec,
                n_flips, has_cross, ok)

    fn = shard_map(
        step, mesh=mesh,
        in_specs=(col_spec, vec_spec, vec_spec, vec_spec, vec_spec,
                  rep, rep, rep),
        out_specs=(vec_spec, vec_spec, rep, rep, rep, rep, rep, rep,
                   rep, rep, rep),
        check_vma=False)
    return jax.jit(fn), col_spec, vec_spec


def make_update_step(mesh: Mesh):
    """Builds the post-pivot maintenance step: the O(n/p) axpy
    ``d -= theta * alpha`` plus bound-flip / basis-exchange bookkeeping on
    the state codes.  Purely shard-local — no collective traffic at all.

    ``update(d, state, alpha, flip_mask, theta, q, leave, leave_up)``
    returns the new sharded ``(d, state)``.
    """
    axes = _mesh_axes(mesh)
    vec_spec = P(axes)
    rep = P()

    def update(d_loc, state_loc, alpha_loc, flip_loc, theta, q, leave,
               leave_up):
        n_loc = d_loc.shape[0]
        rank = _my_rank(mesh, axes)
        g = rank * n_loc + jnp.arange(n_loc, dtype=jnp.int64)
        d = d_loc - theta * alpha_loc            # the O(n/p) axpy
        d = jnp.where(g == q, 0.0, d)
        d = jnp.where(g == leave, -theta, d)
        st = jnp.where(flip_loc, 1 - state_loc, state_loc)   # bound flips
        st = jnp.where(g == q, 2, st)                        # q enters
        st = jnp.where(g == leave,                           # leave exits
                       jnp.where(leave_up, 1, 0), st)
        return d, st.astype(state_loc.dtype)

    fn = shard_map(
        update, mesh=mesh,
        in_specs=(vec_spec, vec_spec, vec_spec, vec_spec, rep, rep, rep,
                  rep),
        out_specs=(vec_spec, vec_spec),
        check_vma=False)
    return jax.jit(fn)


def make_refresh_step(mesh: Mesh):
    """Builds the refactorization support step: from fresh duals ``y``,
    recompute the sharded reduced costs ``d = c - A^T y`` (the ONLY place
    this full recompute exists — between refactorizations ``d`` is
    maintained by ``update_step``) and return ``A @ xN`` so the host can
    rebuild ``xB = -Binv @ (A @ xN)``.
    """
    axes = _mesh_axes(mesh)
    col_spec = P(None, axes)
    vec_spec = P(axes)
    rep = P()

    def refresh(A_loc, cf_loc, state_loc, l_loc, u_loc, y):
        d = cf_loc - y @ A_loc
        d = jnp.where(state_loc == 2, 0.0, d)
        xN = jnp.where(state_loc == 1, u_loc,
                       jnp.where(state_loc == 0, l_loc, 0.0))
        xN = jnp.where(jnp.isfinite(xN), xN, 0.0)
        axn = jax.lax.psum(A_loc @ xN, axes)
        return d, axn

    fn = shard_map(
        refresh, mesh=mesh,
        in_specs=(col_spec, vec_spec, vec_spec, vec_spec, vec_spec, rep),
        out_specs=(vec_spec, rep),
        check_vma=False)
    return jax.jit(fn)


# ------------------------------------------------------ distributed solver


STEP_CACHE_MAXSIZE = 64   # distinct (mesh, shape) step triples kept


class BoundedStepCache:
    """LRU cache for the jitted (pq, update, refresh) step triples.

    Replaces a bare ``functools.lru_cache``: same bound, but with
    explicit hit/miss/eviction counters (compiled-executable churn is a
    real cost — an eviction storm means shapes are cycling faster than
    the cache can hold and should be visible, not silent).

    Thread-safe: entries and counters are guarded by ``_lock``, and each
    resolved ``get_or_create`` is exactly one hit or one miss, so
    ``hits + misses == lookups`` always holds.  A cold key is built by
    exactly one thread — the first caller claims the key with an
    in-flight event and runs ``factory()`` *outside* the lock (jit
    tracing is seconds-slow; holding the lock there would serialize every
    other shape-class behind it — the REPRO011 discipline), while later
    callers wait on the event and re-probe.
    """

    __guarded_by__ = {"_entries": "_lock", "hits": "_lock",
                      "misses": "_lock", "evictions": "_lock",
                      "lookups": "_lock", "_building": "_lock"}

    def __init__(self, maxsize: int = STEP_CACHE_MAXSIZE):
        self.maxsize = int(maxsize)
        self._entries: "OrderedDict[tuple, tuple]" = OrderedDict()
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        self.lookups = 0
        self._lock = threading.Lock()
        self._building: Dict[tuple, threading.Event] = {}

    # The probe and the insert live in different lock scopes by design:
    # the in-flight event in ``_building`` is the claim token that makes
    # the check-then-act atomic (waiters re-probe after the owner
    # publishes), so the REPRO009 shape here is the sanctioned pattern.
    # repro: allow[REPRO009] claim-token get-or-create: _building event
    # serializes builders; waiters re-probe after the owner's insert
    def get_or_create(self, key: tuple, factory):
        while True:
            racecheck.checkpoint("step_cache.probe")
            with self._lock:
                entry = self._entries.get(key)
                if entry is not None:
                    self._entries.move_to_end(key)
                    self.hits += 1
                    self.lookups += 1
                    return entry
                ev = self._building.get(key)
                if ev is None:
                    # We own the build for this key.
                    ev = self._building[key] = threading.Event()
                    self.misses += 1
                    self.lookups += 1
                    break
            # Another thread is building this key: wait, then re-probe.
            # Unresolved probes are not charged, so each resolved call is
            # exactly one lookup and one of hit/miss.
            racecheck.wait_event(ev, "step_cache.wait")
        try:
            entry = factory()
        # repro: allow[REPRO004] claim-release path: the failure is
        # RE-RAISED after waking waiters (nothing is swallowed) — not
        # releasing the claim would park every waiter forever
        except BaseException:
            with self._lock:
                self._building.pop(key, None)
            ev.set()
            raise
        racecheck.checkpoint("step_cache.publish")
        with self._lock:
            self._entries[key] = entry
            while len(self._entries) > self.maxsize:
                self._entries.popitem(last=False)
                self.evictions += 1
            self._building.pop(key, None)
        ev.set()
        return entry

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    def stats(self) -> dict:
        """Atomic snapshot — never torn: hits+misses == lookups."""
        with self._lock:
            return {"hits": self.hits, "misses": self.misses,
                    "evictions": self.evictions, "lookups": self.lookups,
                    "size": len(self._entries), "maxsize": self.maxsize}

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()


_STEP_CACHE = BoundedStepCache()


def step_cache_stats() -> dict:
    """Counters of the module step-triple cache (observability API)."""
    return _STEP_CACHE.stats()


def _cached_steps(mesh: Mesh, m: int, npad: int, num_buckets: int,
                  gather_k: int):
    """One jitted (pq, update, refresh) triple per (mesh, shape) so
    repeated solves — cascades, benchmarks, B&B re-solves — reuse the
    compiled executables instead of re-tracing every call."""
    def _build():
        pq, _, _ = make_pq_step(mesh, m, npad, num_buckets=num_buckets,
                                gather_k=gather_k)
        return pq, make_update_step(mesh), make_refresh_step(mesh)
    return _STEP_CACHE.get_or_create((mesh, m, npad, num_buckets, gather_k),
                                     _build)


def _put(v, sharding, dtype=None):
    """Host value -> device array at its final (replicated) sharding in
    ONE explicit device_put.  Feeding a bare Python scalar to jnp.asarray
    is an IMPLICIT host-to-device transfer, and handing a single-device
    array to the sharded step jits is an implicit device-to-device
    reshard — the strict_numerics guard (jax.transfer_guard) rejects
    both; explicit device_put is the sanctioned path."""
    return jax.device_put(np.asarray(v, dtype), sharding)


def solve_lp_dist(c, A_t, bl, bu, ub, *, mesh: Mesh, lb=None,
                  max_iters: int = 5000, tol: float = 1e-7,
                  warm_start=None, refactor_every: int = None,
                  num_buckets: int = NUM_BUCKETS,
                  gather_k: int = GATHER_K,
                  budget=None, monitor=None):
    """Revised dual simplex with DISTRIBUTED pricing (the ``mesh=`` path
    of ``repro.core.lp.solve_lp``).

    Same conventions and pivot rules as ``solve_lp_np`` — including the
    warm-start and budget/monitor contracts — but the per-column state
    (A, maintained reduced costs d, bounds, nonbasic position codes)
    lives sharded across ``mesh``'s data axes and stays device-resident
    across pivots, while the m x m basis state (Binv, y, xB, basis) is
    replicated on the host.  Per pivot: one ``pq_step`` (pricing + exact
    BFRT, O(mn/p) compute, O(num_buckets + p*K + m) collective traffic)
    and one ``update_step`` (the O(n/p) d-axpy + bookkeeping, no
    collectives).

    ``LPResult.pivot_stats`` counts exact and conservative BFRT pivots
    and the ``shards`` (devices) that hold A.

    Resilience: a :class:`ShardFailure` (the ``dist.shard`` fault site,
    or an XLA runtime error from a step that has already compiled and
    run) or a degenerate stall past ``stall_bland`` (Bland mode is
    host-side only) falls back to ``solve_lp_np`` on a single host,
    warm-started from the basis snapshot at the point of failure, with
    the same budget — noted as ``single_host_fallback`` in
    ``LPResult.notes``, which ``SolveReport.absorb_lp`` records as a
    ladder rung.  An error while a step lowers or compiles propagates.
    """
    from repro.core.guard import HOST_FALLBACK, THETA_EPS, NumericalMonitor
    from repro.core.lp import (BUDGET, INFEASIBLE, ITER_LIMIT, OPTIMAL,
                               LPResult, REFACTOR_EVERY, _prep,
                               solve_lp_np)
    from repro.runtime import faults
    if refactor_every is None:
        refactor_every = REFACTOR_EVERY
    arrs, scale, m, n, start = _prep(c, A_t, bl, bu, ub, lb, warm_start,
                                     tol)
    N = n + m
    if arrs is None:
        res = LPResult(INFEASIBLE, np.zeros(n), 0.0, 0,
                       np.arange(n, N), np.zeros(N, bool), np.zeros(m))
        res.pivot_stats = {"exact": 0, "conservative": 0}
        return res
    cf, A, l, u = arrs
    basis0, at_upper0, winit, wnote = start
    notes = [] if wnote is None else [wnote]
    mon = monitor if monitor is not None else NumericalMonitor()
    if budget is not None:
        budget.start()
    axes = _mesh_axes(mesh)
    p = int(np.prod([mesh.shape[a] for a in axes]))
    Npad = -(-N // p) * p

    def pad(v, fill=0.0):
        return np.concatenate([v, np.full(Npad - N, fill, v.dtype)])

    basis = np.asarray(basis0, np.int64).copy()
    state0 = np.full(Npad, 2, np.int32)   # padding columns: never priced
    state0[:N] = np.where(at_upper0, 1, 0)
    state0[basis] = 2

    col_sh = NamedSharding(mesh, P(None, axes))
    vec_sh = NamedSharding(mesh, P(axes))
    rep_sh = NamedSharding(mesh, P())
    A_pad = np.concatenate([A, np.zeros((m, Npad - N))], axis=1)
    A_dev = jax.device_put(A_pad, col_sh)
    cf_dev = jax.device_put(pad(cf), vec_sh)
    l_dev = jax.device_put(pad(l), vec_sh)
    u_dev = jax.device_put(pad(u), vec_sh)
    state_dev = jax.device_put(state0, vec_sh)
    shards = len(A_dev.sharding.device_set)     # devices pricing A

    pq_step, update_step, refresh_step = _cached_steps(
        mesh, m, Npad, num_buckets, gather_k)

    if winit is not None:
        # reuse the factors computed during warm-basis validation (twin
        # parity with solve_lp_np): no refactorization, no d recompute
        _, _, _, Binv, y, d0 = winit
        Binv = Binv.copy()
        y = y.copy()
        d_dev = jax.device_put(pad(d0), vec_sh)
        xN = np.where(state0[:N] == 1, u, np.where(state0[:N] == 0, l, 0.0))
        xB = -Binv @ (A @ xN)
        since = 0
    else:
        d_dev = jax.device_put(pad(cf), vec_sh)    # overwritten by refresh
        Binv = np.eye(m)
        xB = np.zeros(m)
        y = np.zeros(m)
        since = refactor_every      # force a factorization on entry

    ran = set()                     # steps that have compiled and run

    def refresh():
        nonlocal Binv, xB, y, d_dev, since
        Binv = np.linalg.inv(A[:, basis])
        y = Binv.T @ cf[basis]
        d_dev, axn = _run_step(refresh_step, ran, A_dev, cf_dev, state_dev,
                               l_dev, u_dev, _put(y, rep_sh))
        (axn,) = _pull(axn)
        xB = -Binv @ np.asarray(axn)
        since = 0

    status = ITER_LIMIT
    iters = 0
    stall = 0
    n_exact = n_cons = 0
    fallback_reason = None
    try:
        with mesh:
            for iters in range(1, max_iters + 1):
                if budget is not None and (
                        budget.out_of_time()
                        or iters > budget.remaining_pivots()):
                    status = BUDGET
                    notes.append(f"budget: truncated at pivot {iters - 1}")
                    break
                if since >= refactor_every:
                    refresh()
                lB, uB = l[basis], u[basis]
                viol_lo = lB - xB
                viol_hi = xB - uB
                viol = np.maximum(viol_lo, viol_hi)
                r = int(np.argmax(viol))
                if viol[r] <= tol and since > 0:
                    refresh()
                    viol_lo = lB - xB
                    viol_hi = xB - uB
                    viol = np.maximum(viol_lo, viol_hi)
                    r = int(np.argmax(viol))
                if viol[r] <= tol:
                    status = OPTIMAL
                    break
                above = bool(viol_hi[r] >= viol_lo[r])
                delta = xB[r] - (uB[r] if above else lB[r])
                s = 1.0 if delta > 0 else -1.0

                faults.maybe_raise(faults.SHARD, ShardFailure)
                rho = _put(Binv[r], rep_sh)
                (alpha_dev, flip_dev, r_best, q, d_q, at_up_q, Acol, fvec,
                 n_flips, has_cross, exact) = _run_step(
                    pq_step, ran, A_dev, d_dev, l_dev, u_dev, state_dev, rho,
                    _put(s, rep_sh), _put(abs(delta), rep_sh))
                # ONE explicit device->host pull for everything the host
                # loop consumes this pivot (alpha/flip stay sharded).
                # Implicit scalar syncs (bool(x), int(x)) are banned here:
                # each is a separate blocking transfer, and the
                # strict_numerics test fixture (jax.transfer_guard)
                # rejects them outright.
                (q, d_q, at_up_q, Acol, fvec, has_cross, exact) = \
                    _pull(q, d_q, at_up_q, Acol, fvec, has_cross, exact)
                if not bool(has_cross):
                    if since > 0:   # could be drift: retry on fresh factors
                        refresh()
                        continue
                    status = INFEASIBLE
                    break
                q = int(q)
                w = Binv @ np.asarray(Acol)
                if abs(w[r]) < 1e-11:
                    if since > 0:
                        refresh()
                        continue
                    break           # cannot happen on fresh factors
                n_exact += int(bool(exact))
                n_cons += int(not bool(exact))
                leave = int(basis[r])
                # flip absorption: xB -= Binv @ (A[:, flips] @ dx)
                xB = xB - Binv @ np.asarray(fvec)
                target = uB[r] if above else lB[r]
                t = (xB[r] - target) / w[r]
                xq = u[q] if bool(at_up_q) else l[q]
                xB = xB - t * w
                xB[r] = xq + t
                theta = float(d_q) / w[r]
                y = y + theta * Binv[r]
                Binv_r = Binv[r] / w[r]
                Binv = Binv - np.outer(w, Binv_r)
                Binv[r] = Binv_r
                basis[r] = q
                d_dev, state_dev = _run_step(
                    update_step, ran, d_dev, state_dev, alpha_dev, flip_dev,
                    _put(theta, rep_sh), _put(q, rep_sh, np.int64),
                    _put(leave, rep_sh, np.int64), _put(above, rep_sh))
                since += 1
                # anti-cycling: degenerate streaks force a refactorize;
                # past stall_bland, fall back to the host twin (which has
                # the Bland's-rule mode; selection here is in-kernel)
                if abs(theta) <= THETA_EPS:
                    stall += 1
                    if stall == mon.stall_refactor:
                        mon.stall_refactors += 1
                        mon.stall_events += 1
                        since = refactor_every
                    if stall >= mon.stall_bland:
                        mon.stall_events += 1
                        fallback_reason = (f"{stall} degenerate pivots "
                                           "(Bland mode is host-side)")
                        break
                else:
                    stall = 0
    # guard contract: only a shard failure falls back to the single-host
    # twin; a bug or a refused compile propagates
    except ShardFailure as e:       # dead shard / collective failure
        fallback_reason = f"{type(e).__name__}: {e}"

    if budget is not None:
        budget.charge_pivots(iters)

    if fallback_reason is not None:
        # single-host fallback, warm-started from the failure-point basis
        state_np = np.asarray(state_dev)[:N]
        notes.append(f"{HOST_FALLBACK}: {fallback_reason}")
        res = solve_lp_np(c, A_t, bl, bu, ub, lb=lb, max_iters=max_iters,
                          tol=tol, warm_start=(basis.copy(),
                                               state_np == 1),
                          budget=budget, monitor=monitor)
        res.notes = tuple(notes) + res.notes
        res.pivot_stats = {"exact": n_exact, "conservative": n_cons,
                           "shards": shards, "fallback": 1}
        return res

    # final answer always from a fresh factorization (twin parity)
    state_np = np.asarray(state_dev)[:N]
    at_upper = state_np == 1
    in_basis = np.zeros(N, bool)
    in_basis[basis] = True
    at_upper[in_basis] = False
    Binv = np.linalg.inv(A[:, basis])
    xN = np.where(in_basis, 0.0, np.where(at_upper, u, l))
    xN[basis] = 0.0
    xB = -Binv @ (A @ xN)
    x = xN.copy()
    x[basis] = xB
    y = Binv.T @ cf[basis]
    obj_min = float(cf @ np.where(np.isfinite(x), x, 0.0))
    res = LPResult(status, x[:n], obj_min, iters, basis.copy(),
                   at_upper.copy(), y * scale, notes=tuple(notes))
    res.pivot_stats = {"exact": n_exact, "conservative": n_cons,
                       "shards": shards}
    return res


def pq_input_specs(m: int, n: int,
                   dtype=jnp.float64):  # repro: allow[REPRO002] x64 production dtype; the f32 contract grid passes dtype=f32
    """Abstract inputs for the pq_step dry-run cell:
    (A, d, l, u, state, rho, s, budget)."""
    f = lambda shape: jax.ShapeDtypeStruct(shape, dtype)
    return (f((m, n)), f((n,)), f((n,)), f((n,)),
            jax.ShapeDtypeStruct((n,), jnp.int32),
            f((m,)), f(()), f(()))
