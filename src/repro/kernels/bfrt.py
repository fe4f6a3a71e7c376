"""Pallas TPU kernel: BFRT bucketed histogram (paper App. C.3, procedure 2).

The Bound-Flipping Ratio Test walks breakpoints in increasing ratio order
until the flip budget |delta| is exhausted.  The paper parallelises this
with Map-Sort + per-core heaps; neither global sorts nor heaps map to the
TPU's vector units, so we use the TPU idiom instead (same trick as TPU
top-k): a two-pass *bucketed select*:

  pass 1 (this kernel): histogram the breakpoint ratios into NB buckets,
     accumulating per-bucket flip-cost sums and counts — one-hot comparisons
     against the bucket edges, reduced across lanes, accumulated into the
     resident output block across the sequential grid;
  pass 2 (ops.py): a scalar cumsum over NB buckets locates the crossing
     bucket; only that bucket's elements (tiny) are resolved exactly.

Output matches the sequential BFRT exactly (tests sweep shapes/dtypes).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

DEFAULT_BLOCK = 2048
NUM_BUCKETS = 128


def _bfrt_hist_kernel(ratio_ref, cost_ref, lo_ref, hi_ref,
                      sums_ref, counts_ref):
    i = pl.program_id(0)
    ratio = ratio_ref[...]               # (1, B)
    cost = cost_ref[...]                 # (1, B)
    lo = lo_ref[...]                     # (NB, 1) lower edges, lo[0] = -inf
    hi = hi_ref[...]                     # (NB, 1) upper edges

    # bucket b holds lo[b] < ratio <= hi[b]: the first b with
    # ratio <= hi[b], as the edges ascend.  Buckets on sublanes, tuples
    # on lanes; the lane reductions below stay exact in f32.
    finite = jnp.isfinite(ratio)
    onehot = (ratio > lo) & (ratio <= hi) & finite          # (NB, B)
    sums = jnp.sum(jnp.where(onehot, cost, 0.0), axis=1,
                   keepdims=True)                           # (NB, 1)
    counts = jnp.sum(onehot.astype(jnp.float32), axis=1, keepdims=True)

    @pl.when(i == 0)
    def _init():
        sums_ref[...] = jnp.zeros_like(sums_ref)
        counts_ref[...] = jnp.zeros_like(counts_ref)

    sums_ref[...] += sums.astype(sums_ref.dtype)
    counts_ref[...] += counts.astype(counts_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("block", "num_buckets", "interpret"))
def bfrt_histogram(ratio, cost, edges, *, interpret: bool,
                   block: int = DEFAULT_BLOCK,
                   num_buckets: int = NUM_BUCKETS):
    """Pass 1: (per-bucket flip-cost sums, counts).

    ratio/cost: (n,); edges: (num_buckets,) ascending upper edges with
    edges[-1] = +inf so every finite ratio lands in a bucket.
    """
    n = ratio.shape[0]
    block = min(block, n)
    pad = (-n) % block
    ratio = ratio.astype(jnp.float32)
    cost = cost.astype(jnp.float32)
    if pad:
        ratio = jnp.pad(ratio, (0, pad), constant_values=jnp.inf)
        cost = jnp.pad(cost, (0, pad))
    npad = ratio.shape[0]
    grid = (npad // block,)
    hi = edges.astype(jnp.float32)
    lo = jnp.concatenate([jnp.full((1,), -jnp.inf, jnp.float32), hi[:-1]])
    zero = np.int32(0)                   # index maps stay i32 under x64
    row = pl.BlockSpec((1, block), lambda i: (zero, i))
    col = pl.BlockSpec((num_buckets, 1), lambda i: (zero, zero))
    sums, counts = pl.pallas_call(
        _bfrt_hist_kernel,
        grid=grid,
        in_specs=[row, row, col, col],
        out_specs=[col, col],
        out_shape=[jax.ShapeDtypeStruct((num_buckets, 1), jnp.float32)] * 2,
        interpret=interpret,
    )(ratio.reshape(1, npad), cost.reshape(1, npad),
      lo.reshape(num_buckets, 1), hi.reshape(num_buckets, 1))
    return sums[:, 0], counts[:, 0]


def bfrt_select(ratio, cost, budget, *, interpret: bool,
                num_buckets: int = NUM_BUCKETS):
    """Full two-pass BFRT: returns (entering index, flip mask).

    Equivalent to: sort eligible by ratio; flip until cumulative cost
    reaches budget; the crossing element enters the basis.
    Assumes ineligible entries have ratio=inf / cost=0 (pricing kernel).
    Both passes run in f32, the kernel's dtype: an f64 ratio is rounded
    once here, so pass 2 buckets the very values pass 1 counted.
    """
    ratio = ratio.astype(jnp.float32)
    cost = cost.astype(jnp.float32)
    finite = jnp.isfinite(ratio)
    any_elig = jnp.any(finite)
    rmax = jnp.max(jnp.where(finite, ratio, 0.0))
    rmin = jnp.min(jnp.where(finite, ratio, rmax))
    # NB-2 interior edges + final +inf edge; epsilon-widened
    span = jnp.maximum(rmax - rmin, 1e-12)
    grid = jnp.arange(1, num_buckets, dtype=ratio.dtype) / (num_buckets - 1)
    interior = rmin + span * grid
    edges = jnp.concatenate([interior, jnp.array([jnp.inf], ratio.dtype)])
    sums, _ = bfrt_histogram(ratio, cost, edges, num_buckets=num_buckets,
                             interpret=interpret)
    csum = jnp.cumsum(sums)
    # crossing bucket: first whose cumulative cost reaches the budget
    crossed = csum >= budget - 1e-12
    bidx = jnp.argmax(crossed)
    has_cross = jnp.any(crossed)
    lo_edge = jnp.where(bidx == 0, -jnp.inf, edges[jnp.maximum(bidx - 1, 0)])
    hi_edge = edges[bidx]
    base = jnp.where(bidx == 0, 0.0, csum[jnp.maximum(bidx - 1, 0)])

    # pass 2: exact walk inside the crossing bucket.  The stable sort
    # carries i32 positions: under x64 ``jnp.argsort`` sorts i64 ones,
    # which XLA:TPU emulates and takes about a minute to compile
    in_bucket = (ratio > lo_edge) & (ratio <= hi_edge) & finite
    r_in = jnp.where(in_bucket, ratio, jnp.inf)
    pos = jnp.arange(ratio.shape[0], dtype=jnp.int32)
    r_sorted, order = jax.lax.sort_key_val(r_in, pos)
    cost_sorted = cost[order] * jnp.isfinite(r_sorted)
    csum_in = base + jnp.cumsum(cost_sorted)
    cross_pos = jnp.argmax((csum_in >= budget - 1e-12)
                           & jnp.isfinite(r_sorted))
    q = order[cross_pos]
    # flips: every eligible entry with ratio strictly below the entering one
    # plus earlier same-bucket entries (by sorted position)
    rank = jnp.empty_like(order).at[order].set(pos)
    flips = finite & ((ratio < ratio[q]) | (in_bucket & (rank < rank[q])))
    flips = flips & (pos != q)
    return q, flips, has_cross & any_elig
