"""Pallas TPU kernel: flash attention (causal / sliding-window / full).

The serving and training stacks' pure-XLA path uses the chunked
online-softmax scan in ``repro.models.attention``; this kernel is the
TPU-native replacement for the prefill/train hot spot: q/k/v tiles staged
through VMEM, online softmax state (m, l, acc) in VMEM scratch, causal and
sliding-window masking done on block indices so fully-masked tiles are
skipped at trace time via the grid structure.

Layout: q (BH, S, d), k/v (BH, S, d) with batch*heads folded (GQA expansion
in ops.py).  Grid (BH, nq, nk) with the kv axis innermost ("arbitrary"
semantics): scratch carries softmax state across the kv loop.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale: float, causal: bool, window: int,
                  block_q: int, block_k: int, nk: int):
    kv_i = pl.program_id(2)
    q_i = pl.program_id(1)

    @pl.when(kv_i == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0]                          # (bq, d)
    k = k_ref[0]                          # (bk, d)
    v = v_ref[0]
    s = jnp.dot(q.astype(jnp.float32) * scale, k.astype(jnp.float32).T,
                preferred_element_type=jnp.float32)       # (bq, bk)
    q_pos = q_i * block_q + jax.lax.broadcasted_iota(jnp.int32,
                                                     (block_q, block_k), 0)
    k_pos = kv_i * block_k + jax.lax.broadcasted_iota(jnp.int32,
                                                      (block_q, block_k), 1)
    mask = jnp.ones_like(s, dtype=bool)
    if causal:
        mask = q_pos >= k_pos
    if window > 0:
        mask = mask & ((q_pos - k_pos) < window)
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=1, keepdims=True)
    acc_scr[...] = acc_scr[...] * corr + jnp.dot(
        p, v.astype(jnp.float32), preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(kv_i == nk - 1)
    def _done():
        denom = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / denom).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "block_q", "block_k", "interpret"))
def flash_attention(q, k, v, *, interpret: bool, causal: bool = True,
                    window: int = 0, block_q: int = 128, block_k: int = 128):
    """q/k/v: (BH, S, d).  Returns (BH, S, d)."""
    BH, S, d = q.shape
    block_q = min(block_q, S)
    block_k = min(block_k, S)
    assert S % block_q == 0 and S % block_k == 0, (S, block_q, block_k)
    nq, nk = S // block_q, S // block_k
    scale = 1.0 / math.sqrt(d)
    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        block_q=block_q, block_k=block_k, nk=nk)
    return pl.pallas_call(
        kernel,
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, S, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(q, k, v)
