"""Pallas TPU kernel: segment statistics for representative-tuple building.

Building each hierarchy layer needs per-group (count, sum, sum-of-squares)
over up to 10^9 tuples — the hot loop of DLV partitioning (the paper does
this inside PostgreSQL).  After the DLV sort, group ids are contiguous and
sorted, so a block of BLOCK tuples touches at most BLOCK distinct groups:
each grid step builds a (BLOCK x BLOCK) one-hot of (id - block_base) and
reduces with MXU matmuls, emitting per-block partial stats that ops.py
scatter-adds into the (G, k) result — one pass over HBM, no host sort, no
scatter inside the kernel (TPU has no efficient scatter; this one-hot
matmul formulation is the TPU-native replacement for a CUDA atomic-add
histogram).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

DEFAULT_BLOCK = 512


def _segstats_kernel(vals_ref, ids_ref, cnt_ref, sum_ref, sq_ref):
    vals = vals_ref[...]                 # (k, B): attributes on sublanes
    ids = ids_ref[...]                   # (1, B) int32, sorted ascending
    B = ids.shape[1]
    rel = ids - jnp.min(ids)             # block-local ids; min = first id
    rows = jax.lax.broadcasted_iota(jnp.int32, (B, B), 0)
    # onehot[g, t] = tuple t is in local group g; rel >= B never matches
    onehot = (rows == rel).astype(jnp.float32)               # (B, B)
    nt = (((1,), (1,)), ((), ()))        # contract the tuple (lane) axes
    hi = jax.lax.Precision.HIGHEST       # no bf16 passes on the MXU
    cnt_ref[...] = jax.lax.dot_general(
        jnp.ones_like(ids, jnp.float32), onehot, nt,
        preferred_element_type=jnp.float32)                  # (1, B)
    sum_ref[...] = jax.lax.dot_general(
        vals, onehot, nt, precision=hi,
        preferred_element_type=jnp.float32)                  # (k, B)
    sq_ref[...] = jax.lax.dot_general(
        vals * vals, onehot, nt, precision=hi,
        preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def segstats_partials(vals, ids, *, interpret: bool,
                      block: int = DEFAULT_BLOCK):
    """Per-block partial (count, sum, sumsq) keyed by block-local group ids.

    vals: (n, k); ids: (n,) int32 sorted ascending.  The kernel reads
    ``vals`` transposed, (k, n), so the long tuple axis lies on lanes.
    Returns (bases (nb,), counts (nb, B), sums (nb, B, k), sqs (nb, B, k)).
    """
    n, k = vals.shape
    block = min(block, n)
    pad = (-n) % block
    vals = vals.astype(jnp.float32)
    if pad:
        vals = jnp.pad(vals, ((0, pad), (0, 0)))
        # pad ids far beyond any real group so rel-id masking rejects them
        ids = jnp.pad(ids, (0, pad), constant_values=1 << 30)
    npad = vals.shape[0]
    nb = npad // block
    bases = ids.reshape(nb, block)[:, 0]
    zero = np.int32(0)                   # index maps stay i32 under x64

    cnt, sm, sq = pl.pallas_call(
        _segstats_kernel,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((k, block), lambda i: (zero, i)),
            pl.BlockSpec((1, block), lambda i: (zero, i)),
        ],
        out_specs=[
            pl.BlockSpec((1, block), lambda i: (zero, i)),
            pl.BlockSpec((k, block), lambda i: (zero, i)),
            pl.BlockSpec((k, block), lambda i: (zero, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, npad), jnp.float32),
            jax.ShapeDtypeStruct((k, npad), jnp.float32),
            jax.ShapeDtypeStruct((k, npad), jnp.float32),
        ],
        interpret=interpret,
    )(vals.T, ids.reshape(1, npad))
    return (bases, cnt.reshape(nb, block), sm.T.reshape(nb, block, k),
            sq.T.reshape(nb, block, k))


def segment_stats_np(vals, ids, num_groups: int):
    """The kernel's host twin: per-group (count, sum, sumsq) via bincount.

    Exact float64 accumulation — the partitioner's default on hosts
    without a TPU, where interpreting the Pallas kernel would serialize
    the hot loop.  Same contract as :func:`segment_stats`.
    """
    vals = np.asarray(vals, np.float64)
    ids = np.asarray(ids)
    n, k = vals.shape
    if n and np.all(ids[1:] >= ids[:-1]):
        # sorted ids (the post-DLV layout): contiguous reduceat beats the
        # bincount scatter
        bpos = np.concatenate([[0], np.flatnonzero(np.diff(ids)) + 1])
        present = ids[bpos]
        cnt = np.zeros(num_groups)
        cnt[present] = np.diff(np.concatenate([bpos, [n]]))
        sums = np.zeros((num_groups, k))
        sqs = np.zeros((num_groups, k))
        for j in range(k):
            w = np.ascontiguousarray(vals[:, j])
            sums[present, j] = np.add.reduceat(w, bpos)
            sqs[present, j] = np.add.reduceat(w * w, bpos)
        return cnt, sums, sqs
    cnt = np.bincount(ids, minlength=num_groups).astype(np.float64)
    sums = np.empty((num_groups, k))
    sqs = np.empty((num_groups, k))
    for j in range(k):
        sums[:, j] = np.bincount(ids, weights=vals[:, j],
                                 minlength=num_groups)
        sqs[:, j] = np.bincount(ids, weights=vals[:, j] ** 2,
                                minlength=num_groups)
    return cnt, sums, sqs


def segment_stats(vals, ids, num_groups: int, *, interpret: bool,
                  block: int = DEFAULT_BLOCK):
    """Full segment stats: (counts (G,), sums (G, k), sumsqs (G, k))."""
    vals = jnp.asarray(vals)
    ids = jnp.asarray(ids, jnp.int32)
    bases, cnt, sm, sq = segstats_partials(vals, ids, block=block,
                                           interpret=interpret)
    nb, B = cnt.shape
    # scatter-add per-block partials (tiny: nb*B rows)
    tgt = bases[:, None] + jnp.arange(B)[None, :]            # (nb, B)
    tgt = jnp.clip(tgt, 0, num_groups)                       # extra row = junk
    flat = tgt.reshape(-1)
    counts = jnp.zeros(num_groups + 1, jnp.float32).at[flat].add(
        cnt.reshape(-1))
    sums = jnp.zeros((num_groups + 1, vals.shape[1]), jnp.float32).at[
        flat].add(sm.reshape(-1, vals.shape[1]))
    sqs = jnp.zeros((num_groups + 1, vals.shape[1]), jnp.float32).at[
        flat].add(sq.reshape(-1, vals.shape[1]))
    return counts[:num_groups], sums[:num_groups], sqs[:num_groups]
