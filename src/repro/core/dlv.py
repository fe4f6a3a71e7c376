"""Dynamic Low Variance partitioning — paper §3 (Algorithms 5, 6, 7).

1-D DLV is a running-variance reset scan over sorted attribute values
(Algorithm 5), run over many partitions' concatenated spans at once in one
of two exact forms, chosen per length class by cost: a *column* scan that
steps all spans of a class row by row together (on a TPU the jitted
``lax.scan``, tiny carry, with Kahan-compensated accumulators so the cut
decisions stay identical to a float64 host reference even when jax runs
without x64; elsewhere its numpy twin), or the host's vectorized *jump*
scan from cut to cut of one span.  On a TPU the chip takes wide classes of
short spans (a frontier round's thousands of small partitions); lone long
spans (a layer's first round, Algorithm 7's samples) are cut on the host.

DLV (Algorithm 6) is divisive hierarchical clustering keyed by *total
variance* (|P| * max_j var_j) with bounding variance beta = c_j sigma^2/d_f^2
(GetScaleFactors, Algorithm 7).  Two builds share the machinery:

* ``method="rounds"`` (default) — batched frontier rounds: every round
  selects ALL splittable partitions above the total-variance bar, runs ONE
  segmented sort (lexsort) + ONE segmented 1-D scan over their concatenated
  spans, and derives every child's per-attribute count/sum/sum-of-squares
  from a single ``segment_stats`` pass (Pallas kernel on TPU, ``bincount``
  twin on hosts) — no per-split ``argsort``/``np.var`` re-scans, no
  shape-polymorphic recompiles.
* ``method="heap"`` — the original one-pop-per-iteration reference build
  (kept as the quality/benchmark baseline).

Both produce :class:`repro.core.partitioner.Partition`: contiguous slices
of a permutation array (the paper's cache-friendly layout) plus the flat
array split tree for sub-linear GetGroup (the PostgreSQL GiST role,
Appendix D.2).
"""
from __future__ import annotations

import heapq
from typing import Dict, List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from repro.core.partitioner import (Partition, SplitTree, finalize,
                                    register_backend)
from repro.core.spans import SpanLog, span

# ------------------------------------------------------------- 1-D DLV


@jax.jit
def _dlv_scan_cols(V: jax.Array, beta: jax.Array) -> jax.Array:
    """Column-parallel Algorithm-5 scan: cuts[i, j] = True iff a delimiter
    is placed immediately before V[i, j] in segment (column) j.

    ``V`` is (rows, cols) with every column an independent segment, sorted
    ascending and centered on its own mean; ``beta`` is the per-column
    split bar.  One sequential pass over rows drives ALL columns at once
    (vectorized carry), which is what makes the batched-frontier build
    fast on CPU/TPU: a round with s segments of length L costs L steps,
    not s*L.  The running count/sum/sum-of-squares carry uses Kahan
    compensation and the computation dtype is derived from ``V`` — under
    no-x64 the f32 path keeps cut parity with the float64 host reference
    for mean-centered segment values.
    """
    zero = jnp.zeros((V.shape[1],), V.dtype)

    def step(carry, x):
        k, s1, c1, s2, c2 = carry
        k1 = k + 1.0
        x2 = x * x
        # compensated adds: s1 += x, s2 += x^2
        y1 = x - c1
        t1 = s1 + y1
        c1n = (t1 - s1) - y1
        y2 = x2 - c2
        t2 = s2 + y2
        c2n = (t2 - s2) - y2
        mean = t1 / k1
        var = t2 / k1 - mean * mean
        cut = (var > beta) & (k > 0)     # a segment's first row never cuts
        carry = (jnp.where(cut, 1.0, k1),
                 jnp.where(cut, x, t1), jnp.where(cut, zero, c1n),
                 jnp.where(cut, x2, t2), jnp.where(cut, zero, c2n))
        return carry, cut

    _, cuts = jax.lax.scan(step, (zero,) * 5, V, unroll=8)
    return cuts


def _dlv_scan_np(vals: np.ndarray, beta) -> np.ndarray:
    """float64 host reference of the scan over ONE segment (test oracle)."""
    v = np.asarray(vals, np.float64)
    n = len(v)
    beta = np.broadcast_to(np.asarray(beta, np.float64), (n,))
    cuts = np.zeros(n, bool)
    k = s1 = s2 = 0.0
    for i in range(n):
        x = v[i]
        k1, s1n, s2n = k + 1.0, s1 + x, s2 + x * x
        if s2n / k1 - (s1n / k1) ** 2 > beta[i] and k > 0:
            cuts[i] = True
            k, s1, s2 = 1.0, x, x * x
        else:
            k, s1, s2 = k1, s1n, s2n
    return cuts


def _scan_dtype():
    """The device scan dtype, derived from jax's current default float."""
    return jnp.result_type(float)


def _scan_cols_np(V: np.ndarray, B: np.ndarray) -> np.ndarray:
    """Host twin of ``_dlv_scan_cols``: identical compensated arithmetic in
    float64, one numpy-vectorized row step per iteration.  Used for WIDE
    classes (many segments): no compile cost and the per-row python
    overhead amortizes across columns."""
    C, m = V.shape
    z = np.zeros(m)
    k, s1, c1, s2, c2 = z, z.copy(), z.copy(), z.copy(), z.copy()
    cuts = np.zeros((C, m), bool)
    for i in range(C):
        x = V[i]
        k1 = k + 1.0
        x2 = x * x
        y1 = x - c1
        t1 = s1 + y1
        c1n = (t1 - s1) - y1
        y2 = x2 - c2
        t2 = s2 + y2
        c2n = (t2 - s2) - y2
        var = t2 / k1 - (t1 / k1) ** 2
        cut = (var > B) & (k > 0)
        cuts[i] = cut
        k = np.where(cut, 1.0, k1)
        s1 = np.where(cut, x, t1)
        c1 = np.where(cut, 0.0, c1n)
        s2 = np.where(cut, x2, t2)
        c2 = np.where(cut, 0.0, c2n)
    return cuts


def _jump_scan_np(v: np.ndarray, beta: float) -> np.ndarray:
    """Exact Algorithm-5 scan of ONE long sorted (centered) segment via
    vectorized cut-to-cut jumps.

    The running stats reset at every delimiter, so from each cut the next
    one is found with a window-doubling lookahead: prefix count/sum/sumsq
    over the window give var(V u {x}) for every candidate position in one
    shot.  Cost is O(cuts) numpy calls + ~O(n) total vectorized work —
    the host path for long segments, where a sequential per-element scan
    is dispatch-bound.
    """
    n = len(v)
    cuts = np.zeros(n, bool)
    s = 0
    jump = 256                            # adapts to the observed cut pitch
    while s < n:
        W = max(64, 4 * jump)
        found = -1
        while True:
            e = min(s + W, n)
            w = v[s:e]
            kk = np.arange(1.0, e - s + 1.0)
            S1 = np.cumsum(w)
            S2 = np.cumsum(w * w)
            var = S2 / kk - (S1 / kk) ** 2
            hit = var > beta
            hit[0] = False                # a run's first element never cuts
            nz = np.flatnonzero(hit)
            if len(nz):
                found = s + int(nz[0])
                break
            if e >= n:
                break
            W *= 4
        if found < 0:
            break
        cuts[found] = True
        jump = max(found - s, 1)
        s = found
    return cuts


def _pad_rows(n: int, lo: int = 1024) -> int:
    """Pow2 length classes: bounded scan-shape set (and jit cache)."""
    return max(lo, 1 << int(n - 1).bit_length()) if n > 1 else lo


def _device_scanner(rows: int):
    """A ``_batch_cols`` scanner running the jitted Kahan column scan with
    rows padded to the pow2 class size and columns to pow2 (bounded jit
    shape set for the TPU path)."""
    def scan(Vr: np.ndarray, B: np.ndarray) -> np.ndarray:
        dt = _scan_dtype()
        cols = Vr.shape[1]
        m = 1 << int(cols - 1).bit_length() if cols > 1 else 1
        V = np.zeros((rows, m))
        V[:Vr.shape[0], :cols] = Vr
        Bp = np.full(m, np.inf)
        Bp[:cols] = B
        out = np.asarray(_dlv_scan_cols(jnp.asarray(V, dt),
                                        jnp.asarray(Bp, dt)))
        return out[:, :cols]
    return scan


_COL_BUDGET = 1 << 23        # max padded elements per scan launch
_BATCH_MIN_COLS = 16         # below this, per-segment jump scan wins
_MAX_COLS = 1024             # numpy row-step width sweet spot

# The TPU side of the cost model, in seconds, measured on one TPU v5e
# host with float64 on.  A device column scan launch of C padded rows and
# m padded columns costs LAUNCH + C * ROW1 for one column (the one-column
# scan steps ~16x slower than a two-column one) and LAUNCH + C * (ROW +
# m * ELEM) otherwise, ELEM being the host gather and transfer of a
# round's wide classes (1024 x 8192, 2048 x 4096).  The host jump scan of
# one segment costs C * JUMP_ROW + JUMP_SEG, fitted over every class of
# three 10^7-row TPC-H builds at d_f = 100.
_TPU_LAUNCH_S = 1.5e-3
_TPU_ROW1_S = 6.85e-6
_TPU_ROW_S = 4e-7
_TPU_ELEM_S = 4e-8
_JUMP_ROW_S = 1.5e-8
_JUMP_SEG_S = 1.8e-4


def _cut_plan(Ls: np.ndarray, pitch: int, device: bool):
    """The forms ``_seg_cuts`` cuts its segments with: yields
    ``(segments, rows, column_scan)`` per length class.

    Classes are pow2 row classes on a TPU (the column scan is the jitted
    device scan, whose jit shapes stay bounded) and groups of sorted
    lengths within 2x elsewhere (the column scan is the numpy twin, whose
    shapes are free).  Each class takes the column scan or the
    per-segment jump scan, whichever the platform's cost model finds
    cheaper: off the TPU from its columns and ``pitch``, on a TPU from
    its padded rows and columns (the constants above)."""
    if device:
        classes = np.fromiter((_pad_rows(int(l)) for l in Ls), np.int64,
                              len(Ls))
        for C in np.unique(classes):
            C = int(C)
            segs = np.flatnonzero(classes == C)
            max_cols = max(1, _COL_BUDGET // C)
            for a in range(0, len(segs), max_cols):
                sub = segs[a:a + max_cols]
                m = 1 << int(len(sub) - 1).bit_length()   # padded columns
                step = _TPU_ROW1_S if m == 1 else _TPU_ROW_S + m * _TPU_ELEM_S
                scan = _TPU_LAUNCH_S + C * step
                jump = len(sub) * (C * _JUMP_ROW_S + _JUMP_SEG_S)
                yield sub, C, scan < jump
        return
    ord_len = np.argsort(Ls, kind="stable")
    i = 0
    while i < len(ord_len):
        L0 = int(Ls[ord_len[i]])
        j = i + 1
        while (j < len(ord_len) and j - i < _MAX_COLS
               and Ls[ord_len[j]] <= max(2 * L0, L0 + 64)):
            j += 1
        group = ord_len[i:j]
        i = j
        cols = len(group)
        # jump cost ~ cols*rows/pitch window ops; row scan ~ rows steps
        yield group, int(Ls[group[-1]]), not (
            cols < _BATCH_MIN_COLS or cols < max(1, pitch) // 2)


def _batch_cols(cuts, vals_shifted, starts, Ls, beta_seg, sub,
                scanner=None) -> None:
    """Scan segments ``sub`` as columns of one (Lmax, cols) matrix; padding
    rows repeat each segment's last value (harmless — outputs beyond a
    segment's length are discarded).  ``scanner(V, B) -> (rows, cols)``
    defaults to the numpy row-step twin; the TPU path passes a jitted
    scanner that handles its own shape padding."""
    ridx = np.arange(int(Ls[sub].max()))[:, None]
    gather = starts[sub][None, :] + np.minimum(ridx, Ls[sub][None, :] - 1)
    out = (scanner or _scan_cols_np)(vals_shifted[gather], beta_seg[sub])
    valid = ridx < Ls[sub][None, :]
    cuts[(starts[sub][None, :] + ridx)[valid]] = \
        out[:ridx.shape[0]][valid]


def _snap_cuts_to_run_starts(vals: np.ndarray, cuts: np.ndarray,
                             seg_starts: np.ndarray) -> np.ndarray:
    """Move each cut to the first element of its equal-value run (dropping
    cuts whose run begins a segment).

    The scan may place a delimiter mid-run of equal values (adding a
    duplicate CAN raise the running variance), but a split boundary inside
    a run makes the split tree inconsistent with the stored gids: descent
    routes a value equal to the bound entirely to the right child while
    tied members sit left.  Snapping the cut to the run start keeps every
    tied tuple on the right of its boundary — GetGroup == gid even on
    duplicate-heavy data.  At most one cut per run exists (after a cut the
    remaining duplicates have zero variance), so snaps never collide.
    """
    n = len(vals)
    if not n or not cuts.any():
        return cuts
    change = np.empty(n, bool)
    change[0] = True
    change[1:] = vals[1:] != vals[:-1]
    change[seg_starts] = True
    run_start = np.maximum.accumulate(np.where(change, np.arange(n), -1))
    pos = np.flatnonzero(cuts)
    tgt = run_start[pos]
    if np.array_equal(tgt, pos):
        return cuts
    out = np.zeros(n, bool)
    is_seg_start = np.zeros(n, bool)
    is_seg_start[seg_starts] = True
    out[tgt[~is_seg_start[tgt]]] = True
    return out


def _seg_cuts(vals_shifted: np.ndarray, Ls: np.ndarray,
              beta_seg: np.ndarray, *, pitch: int = 256,
              spans: Optional[SpanLog] = None) -> np.ndarray:
    """Delimiters for many independent sorted segments, concatenated in
    ``vals_shifted`` with lengths ``Ls`` (each centered on its own mean).

    ``_cut_plan`` groups the segments into length classes and gives each
    class the cheaper of two exact forms: ONE column-parallel row-step
    scan over the class, or the vectorized jump scan per segment (a
    10^7-row round-1 segment costs ~one vectorized pass, not 10^7
    sequential steps).  Off the TPU the column scan is the numpy twin
    over <= 2x length groups, and ``pitch``, the expected inter-cut
    distance (~d_f), steers the choice.  On a TPU it is the jitted Kahan
    scan over pow2 length classes, which the chip takes only for wide
    classes of short segments (a frontier round's thousands of ~10^3-row
    spans); lone long segments and narrow classes (a layer's first
    rounds, Algorithm 7's sample columns) go to the jump scan on the
    host, which cuts a row in ~15 ns where the chip's one-column scan
    steps one in ~7 us.  ``spans`` counts the rows each side cut
    (``dlv_cut_rows_device``, ``dlv_cut_rows_host``).  All forms end with
    cuts snapped to equal-value run starts (split-tree/gid consistency on
    ties).
    """
    n = len(vals_shifted)
    Ls = np.asarray(Ls, np.int64)
    cuts = np.zeros(n, bool)
    if n == 0 or not len(Ls):
        return cuts
    starts = np.concatenate([[0], np.cumsum(Ls)[:-1]])
    beta_seg = np.asarray(beta_seg, np.float64)
    from repro.kernels.ops import on_tpu
    device = on_tpu()
    device_rows = 0
    for group, rows, column_scan in _cut_plan(Ls, pitch, device):
        if column_scan:
            _batch_cols(cuts, vals_shifted, starts, Ls, beta_seg, group,
                        scanner=_device_scanner(rows) if device else None)
            device_rows += int(Ls[group].sum()) if device else 0
        else:
            for s in group:
                a = starts[s]
                cuts[a:a + Ls[s]] = _jump_scan_np(
                    vals_shifted[a:a + Ls[s]], float(beta_seg[s]))
    if spans is not None:
        spans.add("dlv_cut_rows_device", device_rows)
        spans.add("dlv_cut_rows_host", n - device_rows)
    return _snap_cuts_to_run_starts(vals_shifted, cuts, starts)


def dlv_1d(values: np.ndarray, beta: float) -> np.ndarray:
    """Delimiter positions for sorted ``values``; returns cut flags (n,)."""
    v = np.asarray(values, np.float64)
    n = len(v)
    if n == 0:
        return np.zeros(0, bool)
    shift = v.mean()         # center: keeps the low-precision path accurate
    return _seg_cuts(v - shift, np.array([n]), np.array([float(beta)]))


# The SEED scan, kept verbatim as the benchmark baseline: jitted without
# padding, so every distinct span length triggers a fresh XLA compile —
# the cost profile the batched-frontier build eliminates.  (Only the
# float64-literal footgun is fixed: dtype derives from the input.)
@jax.jit
def _dlv_scan_seed(vals: jax.Array, beta: jax.Array) -> jax.Array:
    def step(carry, x):
        k, s1, s2 = carry
        k1 = k + 1.0
        s1n, s2n = s1 + x, s2 + x * x
        var = s2n / k1 - (s1n / k1) ** 2
        cut = var > beta
        return ((jnp.where(cut, 1.0, k1), jnp.where(cut, x, s1n),
                 jnp.where(cut, x * x, s2n)), cut)
    zero = jnp.zeros((), vals.dtype)
    _, cuts = jax.lax.scan(step, (zero, zero, zero), vals)
    return cuts


def dlv_1d_seed(values: np.ndarray, beta: float) -> np.ndarray:
    """The seed build's per-span scan (shape-polymorphic jit)."""
    v = np.asarray(values, np.float64)
    if not len(v):
        return np.zeros(0, bool)
    shift = v.mean()
    dt = _scan_dtype()
    cuts = np.array(_dlv_scan_seed(jnp.asarray(v - shift, dt),
                                   jnp.asarray(beta, dt)))
    cuts[0] = False
    return cuts


def dlv_1d_partition(values: np.ndarray, beta: float):
    """(group_id per element, boundary values d_1..d_{p-1}) for sorted input."""
    cuts = dlv_1d(values, beta)
    gid = np.cumsum(cuts)
    bounds = values[np.flatnonzero(cuts)]
    return gid, bounds


def ratio_score(values: np.ndarray, gid: np.ndarray, *,
                weighted: bool = False) -> float:
    """Definition 2: sum of per-partition variances / total variance.

    Single vectorised pass: per-group count/sum/sum-of-squares via
    ``np.bincount`` (O(n + G)).  Sparse / negative / non-integer ids are
    compacted with ONE ``np.unique`` call (the compacted ids feed bincount
    directly, no second pass).

    ``weighted=True`` weights each group's variance by its share of tuples
    (the within-group variance fraction, in [0, 1]) — the bounded quality
    metric the partitioning benchmarks track across attributes, where the
    paper's unweighted sum is only meaningful per split attribute."""
    values = np.asarray(values, np.float64)
    tot = float(np.var(values))
    if tot <= 0:
        return 0.0
    gid = np.asarray(gid)
    if gid.dtype.kind not in "iu" or (
            len(gid) and (gid.min() < 0 or gid.max() >= len(gid))):
        gid = np.unique(gid, return_inverse=True)[1]
    shift = values.mean()              # numerical stabilisation
    v = values - shift
    cnt = np.bincount(gid)
    s1 = np.bincount(gid, weights=v)
    s2 = np.bincount(gid, weights=v * v)
    nz = cnt > 0
    var_g = np.maximum(s2[nz] / cnt[nz] - (s1[nz] / cnt[nz]) ** 2, 0.0)
    if weighted:
        return float((var_g * cnt[nz]).sum() / len(values)) / tot
    return float(var_g.sum()) / tot


# ------------------------------------------------------ GetScaleFactors


def get_scale_factors(X: np.ndarray, d_f: int, *, sample: int = 10_000,
                      eps: float = 1e-9, max_steps: int = 60,
                      rng: Optional[np.random.Generator] = None,
                      spans: Optional[SpanLog] = None) -> np.ndarray:
    """Algorithm 7: per-attribute constants c_j with beta = c_j sigma^2/d_f^2.

    All attributes' binary searches advance in lock-step: each iteration
    runs ONE segmented scan over the k sorted sample columns with
    per-attribute betas, instead of k independent scan sequences.
    ``spans`` receives the scans' row counters (``_seg_cuts``).
    """
    rng = rng or np.random.default_rng(0)
    n, k = X.shape
    take = min(sample, n)
    idx = rng.choice(n, size=take, replace=False) if take < n else np.arange(n)
    V = np.sort(X[idx], axis=0)                  # per-column sorted sample
    Vc = V - V.mean(axis=0)
    var = V.var(axis=0)
    out = np.full(k, 13.5)                       # paper's default c
    searching = var > 0
    lo = np.zeros(k)
    hi = 0.25 * (V[-1] - V[0]) ** 2
    beta = hi.copy()
    target = max(2, min(d_f, take))
    vflat = Vc.T.reshape(-1)                     # k contiguous sorted segments
    Lk = np.full(k, take, np.int64)
    for _ in range(max_steps):
        run = searching & (hi - lo > eps * np.maximum(hi, 1.0))
        if not run.any():
            break
        beta = np.where(run, 0.5 * (lo + hi), beta)
        B = np.where(run, beta, np.inf)          # frozen columns never cut
        p = _seg_cuts(vflat, Lk, B, spans=spans).reshape(k, take).sum(
            axis=1) + 1
        searching &= ~(run & (p == target))      # converged exactly
        hi = np.where(run & (p < target), beta, hi)
        lo = np.where(run & (p > target), beta, lo)
    pos = var > 0
    out[pos] = beta[pos] * d_f * d_f / var[pos]
    return out


# ----------------------------------------------------- legacy split nodes


class SplitNode:
    """Pointer-tree node used only while the heap build runs; converted to
    the flat :class:`SplitTree` arrays at finalization."""

    __slots__ = ("attr", "bounds", "children")

    def __init__(self, attr: int, bounds: np.ndarray, children: List[int]):
        self.attr = attr
        self.bounds = bounds
        self.children = children


def _tree_from_nodes(nodes: List[SplitNode], root: int) -> SplitTree:
    if root < 0 or not nodes:
        return SplitTree.single_leaf()
    attr = np.fromiter((nd.attr for nd in nodes), np.int32, len(nodes))
    nb = np.fromiter((len(nd.bounds) for nd in nodes), np.int64, len(nodes))
    bound_off = np.concatenate([[0], np.cumsum(nb)])
    bounds = np.concatenate([nd.bounds for nd in nodes]) \
        if bound_off[-1] else np.zeros(0, np.float64)
    children = np.concatenate([np.asarray(nd.children, np.int64)
                               for nd in nodes])
    return SplitTree(attr, bound_off, np.asarray(bounds, np.float64),
                     children, root)


# -------------------------------------------------------- heap-based build


_PID_TAG = 1 << 40   # children >= _PID_TAG are unresolved leaf pids


def dlv_heap(X: np.ndarray, d_f: int, *, c: Optional[np.ndarray] = None,
             min_groups: Optional[int] = None,
             rng: Optional[np.random.Generator] = None,
             scan: str = "fast", mesh=None,
             chunk_rows: Optional[int] = None,
             time_budget_s: Optional[float] = None) -> Partition:
    """Algorithm 6, one heap pop (= one split) per iteration.

    The reference build the batched ``dlv_rounds`` is validated against;
    O(G) python iterations, each with its own span argsort + scan launch.
    ``scan="seed"`` restores the seed's shape-polymorphic jitted scan (one
    XLA compile per distinct span length — the benchmark baseline);
    ``time_budget_s`` raises TimeoutError mid-build when exceeded, so
    benchmarks can lower-bound the seed build without running it to the
    bitter end.
    """
    import time as _time
    t0 = _time.time()
    scan_1d = dlv_1d_seed if scan == "seed" else dlv_1d
    X = np.asarray(X, np.float64)
    n, k = X.shape
    target = min_groups if min_groups is not None else max(1, n // d_f)
    if c is None:
        c = get_scale_factors(X, d_f, rng=rng)

    order = np.arange(n)
    spans: Dict[int, Tuple[int, int]] = {0: (0, n)}
    var_cache: Dict[int, np.ndarray] = {0: np.var(X, axis=0)}
    next_pid = 1
    heap: List[Tuple[float, int]] = []

    def push(pid):
        s, e = spans[pid]
        tv = (e - s) * float(var_cache[pid].max())
        if e - s >= 2 and tv > 0:
            heapq.heappush(heap, (-tv, pid))

    push(0)
    nodes: List[SplitNode] = []
    child_slot: Dict[int, Tuple[int, int]] = {}   # pid -> (node_id, slot)
    root = -1

    while len(spans) < target and heap:
        if time_budget_s is not None and _time.time() - t0 > time_budget_s:
            raise TimeoutError(f"dlv_heap(scan={scan!r}) exceeded "
                               f"{time_budget_s}s at {len(spans)} groups")
        _, pid = heapq.heappop(heap)
        if pid not in spans:
            continue
        s, e = spans[pid]
        v = var_cache[pid]
        j = int(np.argmax(v))
        sigma2 = float(v[j])
        if sigma2 <= 0:
            continue
        beta = c[j] * sigma2 / (d_f * d_f)
        idx = order[s:e]
        vals = X[idx, j]
        perm = np.argsort(vals, kind="stable")
        idx = idx[perm]
        vals = vals[perm]
        cuts = scan_1d(vals, beta)
        p = int(cuts.sum()) + 1
        tries = 0
        while p == 1 and tries < 30:
            beta *= 0.25
            cuts = scan_1d(vals, beta)
            p = int(cuts.sum()) + 1
            tries += 1
        if p == 1:
            continue  # unsplittable (all-equal values)
        order[s:e] = idx
        bpos = np.flatnonzero(cuts)
        starts = np.concatenate([[0], bpos, [e - s]])
        node_id = len(nodes)
        node = SplitNode(j, vals[bpos], [])
        nodes.append(node)
        if pid in child_slot:
            pn, slot = child_slot[pid]
            nodes[pn].children[slot] = node_id
        elif root == -1:
            root = node_id
        del spans[pid]
        del var_cache[pid]
        for i in range(len(starts) - 1):
            cs, ce = s + int(starts[i]), s + int(starts[i + 1])
            cp = next_pid
            next_pid += 1
            spans[cp] = (cs, ce)
            var_cache[cp] = np.var(X[order[cs:ce]], axis=0) if ce - cs > 1 \
                else np.zeros(k)
            node.children.append(_PID_TAG + cp)
            child_slot[cp] = (node_id, i)
            push(cp)

    # compact group ids in slice order; resolve tagged leaf pids to ~gid
    pids = sorted(spans, key=lambda p: spans[p][0])
    offsets = np.fromiter((spans[p][0] for p in pids), np.int64, len(pids))
    offsets = np.concatenate([offsets, [n]])
    pid_to_gid = {p: g for g, p in enumerate(pids)}
    for node in nodes:
        node.children = [
            ~pid_to_gid[ch - _PID_TAG] if ch >= _PID_TAG else ch
            for ch in node.children]
    return finalize(X, order, offsets, _tree_from_nodes(nodes, root),
                    mesh=mesh, chunk_rows=chunk_rows)


# ----------------------------------------------- batched frontier rounds


def _segment_stats_auto(vals: np.ndarray, ids: np.ndarray, num_groups: int):
    """Child count/sum/sumsq in one pass: Pallas segstats kernel on TPU,
    ``np.bincount`` twin elsewhere (the kernel interprets on CPU, which
    would serialize the hot loop)."""
    from repro.kernels.ops import segment_stats_auto
    return segment_stats_auto(vals, ids, num_groups)


def dlv_rounds(X: np.ndarray, d_f: int, *, c: Optional[np.ndarray] = None,
               min_groups: Optional[int] = None,
               rng: Optional[np.random.Generator] = None,
               mesh=None, chunk_rows: Optional[int] = None,
               spans: Optional[SpanLog] = None) -> Partition:
    """Algorithm 6 as batched frontier rounds (the tentpole build).

    Every round: (1) rank the frontier by total variance and select the
    splittable partitions above the bar (at most ``remaining/avg_children``
    of them, so the group count lands near the target exactly like the heap
    build's stop rule); (2) concatenate the selected spans and sort them
    with ONE ``np.lexsort`` keyed by (segment, value); (3) place all
    delimiters with ONE segmented scan launch; (4) obtain every child's
    per-attribute stats from ONE ``segment_stats`` pass.  ``spans`` (the
    build's :class:`~repro.core.spans.SpanLog`) records ``dlv.scale``
    (Algorithm 7's scale factors), each round's ``dlv.sort`` (gather and
    per-span sort), ``dlv.cuts`` (delimiter scans with their beta/4
    retries) and ``dlv.stats`` (children's stats), the
    ``build.finalize`` tail, the ``dlv_rounds`` counter and the rows each
    form cut (``dlv_cut_rows_device``, ``dlv_cut_rows_host``).
    """
    X = np.asarray(X, np.float64)
    n, k = X.shape
    target = min_groups if min_groups is not None else max(1, n // d_f)
    if c is None:
        with span(spans, "dlv.scale"):
            c = get_scale_factors(X, d_f, rng=rng, spans=spans)
    gshift = X.mean(axis=0)

    order = np.arange(n)
    # frontier state (one row per live partition)
    S = np.zeros(1, np.int64)
    E = np.full(1, n, np.int64)
    Xc0 = X - gshift
    SU = Xc0.sum(axis=0, keepdims=True)            # (P, k) centered sums
    SQ = (Xc0 * Xc0).sum(axis=0, keepdims=True)    # (P, k) centered sumsqs
    frozen = np.zeros(1, bool)
    del Xc0
    pid = np.zeros(1, np.int64)                    # tree linkage handles
    next_pid = 1

    nodes: List[SplitNode] = []
    child_slot: Dict[int, Tuple[int, int]] = {}
    root = -1
    avg_children = float(max(2, min(d_f, n)))      # round-1 estimate

    while len(S) < target:
        cnt = (E - S).astype(np.float64)
        var = np.maximum(SQ / cnt[:, None] - (SU / cnt[:, None]) ** 2, 0.0)
        vmax = var.max(axis=1)
        jbest = var.argmax(axis=1)
        tv = cnt * vmax
        cand = np.flatnonzero((cnt >= 2) & (tv > 0) & ~frozen)
        if not len(cand):
            break
        if spans is not None:
            spans.add("dlv_rounds")
        remaining = target - len(S)
        take = max(1, int(np.ceil(remaining / max(avg_children - 1.0, 1.0))))
        if len(cand) > take:
            # the total-variance bar: the take-th largest tv among candidates
            sel = cand[np.argpartition(-tv[cand], take - 1)[:take]]
        else:
            sel = cand
        with span(spans, "dlv.sort"):
            nseg = len(sel)
            Ls = (E - S)[sel]
            total = int(Ls.sum())
            seg_off = np.concatenate([[0], np.cumsum(Ls)])
            segid = np.repeat(np.arange(nseg), Ls)
            base = np.repeat(S[sel] - seg_off[:-1], Ls)
            pos = base + np.arange(total)          # order slots, per segment
            idxc = order[pos]
            jel = np.repeat(jbest[sel], Ls)
            vals = X[idxc, jel]
            # segmented sort: per-span stable argsort into one permutation
            # (beats a 2-key lexsort ~10x — span slices are contiguous)
            perm = np.empty(total, np.int64)
            for si in range(nseg):
                a, b = seg_off[si], seg_off[si + 1]
                perm[a:b] = a + np.argsort(vals[a:b], kind="stable")
            idxs = idxc[perm]
            vals_s = vals[perm]

        with span(spans, "dlv.cuts"):
            # per-segment center (raw partition mean on the split attribute)
            mean_sel = SU[sel, jbest[sel]] / Ls + gshift[jbest[sel]]
            beta_sel = c[jbest[sel]] * vmax[sel] / (d_f * d_f)
            reset = np.zeros(total, bool)
            reset[seg_off[:-1]] = True
            vs = vals_s - np.repeat(mean_sel, Ls)
            cuts = _seg_cuts(vs, Ls, beta_sel, pitch=d_f, spans=spans)

            # segments that produced no delimiter retry with beta/4 (the heap
            # build's rule); all-equal segments can never split -> frozen
            ncuts = np.bincount(segid[cuts], minlength=nseg)
            alleq = vals_s[seg_off[1:] - 1] == vals_s[seg_off[:-1]]
            fail = np.flatnonzero((ncuts == 0) & ~alleq)
            tries = 0
            while len(fail) and tries < 30:
                beta_sel[fail] *= 0.25
                fmask = np.zeros(nseg, bool)
                fmask[fail] = True
                elm = fmask[segid]
                cuts[elm] = _seg_cuts(vs[elm], Ls[fail], beta_sel[fail],
                                      pitch=d_f, spans=spans)
                ncuts = np.bincount(segid[cuts], minlength=nseg)
                fail = np.flatnonzero((ncuts == 0) & ~alleq)
                tries += 1

        order[pos] = idxs                          # spans are now sorted
        split = np.flatnonzero(ncuts > 0)
        if not len(split):
            frozen[sel] = True
            continue
        frozen[sel[ncuts == 0]] = True
        # accept splits in total-variance order only until the target is
        # reached (the heap build's stop rule, applied batch-wise): the
        # rejected tail stays on the frontier un-split, so the final group
        # count matches the one-pop-at-a-time build's instead of
        # overshooting by a whole round
        split = split[np.argsort(-tv[sel[split]], kind="stable")]
        gain = np.cumsum(ncuts[split])             # children-1 per split
        need = target - len(S)
        split = split[:int(np.searchsorted(gain, need, side="left")) + 1]
        split.sort()

        # contiguous child ids across the concatenated array
        boundary = cuts | reset
        cid = np.cumsum(boundary) - 1
        n_children = int(cid[-1]) + 1
        ccnt = np.bincount(cid, minlength=n_children).astype(np.float64)
        child_start = pos[boundary]                # order slot of each child

        # tree nodes for the split partitions (python loop is O(#splits)
        # with list appends only — no numeric work)
        keep = np.ones(len(S), bool)
        new_rows = []                              # frontier child row ranges
        cstart_of_seg = np.searchsorted(np.flatnonzero(boundary),
                                        seg_off[:-1])
        for si in split:
            i = sel[si]
            keep[i] = False
            c0, c1 = cstart_of_seg[si], (cstart_of_seg[si + 1]
                                         if si + 1 < nseg else n_children)
            bvals = vals_s[seg_off[si]:seg_off[si + 1]][
                cuts[seg_off[si]:seg_off[si + 1]]]
            node_id = len(nodes)
            node = SplitNode(int(jbest[i]), bvals, [])
            nodes.append(node)
            p = int(pid[i])
            if p in child_slot:
                pn, slot = child_slot[p]
                nodes[pn].children[slot] = node_id
                del child_slot[p]
            elif root == -1:
                root = node_id
            for ci in range(c0, c1):
                cp = next_pid
                next_pid += 1
                node.children.append(_PID_TAG + cp)
                child_slot[cp] = (node_id, ci - c0)
            new_rows.append((c0, c1, next_pid - (c1 - c0)))

        # frontier update: drop split rows, append their children
        ch_sel = np.concatenate([np.arange(c0, c1) for c0, c1, _ in new_rows])
        ch_pid = np.concatenate([np.arange(p0, p0 + (c1 - c0))
                                 for c0, c1, p0 in new_rows])
        ch_cnt = ccnt[ch_sel].astype(np.int64)
        # children sums/sumsqs feed the NEXT round's selection; the final
        # round (``done`` — the loop breaks below on the same flag, so the
        # zero placeholders are provably never ranked) skips the pass and
        # lets finalize recompute exact reps
        done = int(keep.sum()) + len(ch_sel) >= target
        with span(spans, "dlv.stats"):
            if done:
                csum = np.zeros((n_children, k))
                csq = np.zeros((n_children, k))
            else:
                _, csum, csq = _segment_stats_auto(X[idxs] - gshift, cid,
                                                   n_children)
        ch_S = child_start[ch_sel]
        S = np.concatenate([S[keep], ch_S])
        E = np.concatenate([E[keep], ch_S + ch_cnt])
        SU = np.concatenate([SU[keep], csum[ch_sel]])
        SQ = np.concatenate([SQ[keep], csq[ch_sel]])
        frozen = np.concatenate([frozen[keep], ch_cnt <= 1])
        pid = np.concatenate([pid[keep], ch_pid])
        avg_children = len(ch_sel) / max(len(split), 1)
        if done:
            break

    with span(spans, "build.finalize"):
        # groups in slice order, unresolved leaf pids -> ~gid
        gorder = np.argsort(S, kind="stable")
        offsets = np.concatenate([S[gorder], [n]])
        pid_to_gid = {int(pid[r]): g for g, r in enumerate(gorder)}
        for node in nodes:
            node.children = [
                ~pid_to_gid[ch - _PID_TAG] if ch >= _PID_TAG else ch
                for ch in node.children]
        return finalize(X, order, offsets, _tree_from_nodes(nodes, root),
                        mesh=mesh, chunk_rows=chunk_rows)


# ------------------------------------------------------------- entry point


@register_backend("dlv")
def dlv(X: np.ndarray, d_f: int = 100, *, c: Optional[np.ndarray] = None,
        min_groups: Optional[int] = None,
        rng: Optional[np.random.Generator] = None,
        method: str = "rounds", spans: Optional[SpanLog] = None,
        **kwargs) -> Partition:
    """Algorithm 6 over tuples X (n, k); produces ~n/d_f groups.
    ``spans`` receives the rounds' phases (the heap build has none)."""
    if method == "rounds":
        return dlv_rounds(X, d_f, c=c, min_groups=min_groups, rng=rng,
                          spans=spans, **kwargs)
    if method == "heap":
        # forward everything: unknown options raise instead of silently
        # configuring nothing
        return dlv_heap(X, d_f, c=c, min_groups=min_groups, rng=rng,
                        **kwargs)
    raise ValueError(f"unknown dlv method {method!r}")


# Back-compat: old callers imported DLVResult; a Partition is the same shape.
DLVResult = Partition
