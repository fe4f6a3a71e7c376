"""Pallas TPU kernel: fused dual-simplex pricing (paper App. C.3, procedure 1).

Per iteration the revised dual simplex needs, for every column j of A
(m x n, m tiny):
    alpha_j = rho . A[:, j]            (pivot row)
    ratio_j = d_j / (s * alpha_j)  masked by BFRT eligibility
    cost_j  = |alpha_j| * width_j      (bound-flip budget use)

The reduced costs d are MAINTAINED by the revised simplex (one O(n) axpy
``d -= theta * alpha`` per pivot — see ``repro.core.lp``), so unlike the
textbook loop there is no second matvec ``c - y @ A`` here: this kernel
performs the single O(mn) sweep of A per simplex iteration — an m-row
sublane reduction + VPU elementwise, one HBM read of A total.  This is ~45% of
dual-simplex time in the paper (OpenMP over n).

Block layout: A tile (m, B) in VMEM; rho as a resident (m, 1) column;
d/state/lo/hi as (1, B) tiles; out tiles (1, B).  n is padded to a
multiple of BLOCK.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

DEFAULT_BLOCK = 2048


def pricing_math(alpha, d, state, width, s, tol: float):
    """BFRT eligibility / ratio / flip-cost from a priced pivot row.

    Shared by the Pallas kernel below and the shard_map distributed step
    (``repro.core.distributed``) so every backend applies the exact same
    pivot rules.  ``d`` is the MAINTAINED reduced-cost vector (no
    ``c - y @ A`` recompute anywhere downstream of this function);
    ``state`` is 0 = nonbasic-at-lower, 1 = nonbasic-at-upper, 2 = basic.
    Returns (ratio, cost): ratio is +inf for ineligible columns, cost is 0.
    """
    sa = s * alpha
    nonbasic = state < 2
    at_up = state == 1
    elig = nonbasic & (((~at_up) & (sa > tol)) | (at_up & (sa < -tol)))
    safe = jnp.where(jnp.abs(sa) > tol, sa, 1.0)
    ratio = jnp.where(elig, jnp.maximum(d / safe, 0.0), jnp.inf)
    cost = jnp.where(elig, jnp.abs(alpha) * width, 0.0)
    return ratio, cost


def _pricing_kernel(A_ref, rho_ref, d_ref, state_ref,
                    lo_ref, hi_ref, s_ref,
                    alpha_ref, ratio_ref, cost_ref, *, tol: float):
    A = A_ref[...]                       # (m, B)
    rho = rho_ref[...]                   # (m, 1)
    d = d_ref[...]                       # (1, B) maintained reduced costs
    state = state_ref[...]               # (1, B) 0=at_lo, 1=at_up, 2=basic
    lo = lo_ref[...]
    hi = hi_ref[...]
    s = s_ref[...]                       # (1, 1): +-1

    # m is tiny: a sublane reduction on the VPU, exact in the input dtype
    alpha = jnp.sum(rho * A, axis=0, keepdims=True)               # (1, B)
    ratio, cost = pricing_math(alpha, d, state, hi - lo, s, tol)

    alpha_ref[...] = alpha
    ratio_ref[...] = ratio
    cost_ref[...] = cost


@functools.partial(jax.jit, static_argnames=("block", "interpret", "tol"))
def pricing(A, rho, d, state, lo, hi, s, *, interpret: bool,
            block: int = DEFAULT_BLOCK, tol: float = 1e-9):
    """Fused pricing over columns.  A: (m, n) -> (alpha, ratio, cost).

    d: (n,) maintained reduced costs.  state: int32 (n,) with
    0 = nonbasic-at-lower, 1 = nonbasic-at-upper, 2 = basic.
    s: scalar sign of the primal infeasibility delta.  Compiled for the
    TPU the operands must be f32 (Mosaic has no f64); interpreted, any
    float dtype runs.
    """
    m, n = A.shape
    dt = A.dtype
    if not interpret and jnp.dtype(dt).itemsize > 4:
        raise TypeError("pricing: the TPU kernel takes f32 operands "
                        "(Mosaic has no float64)")
    block = min(block, n)
    pad = (-n) % block
    if pad:
        A = jnp.pad(A, ((0, 0), (0, pad)))
        d = jnp.pad(d, (0, pad))
        state = jnp.pad(state, (0, pad), constant_values=2)  # basic = ignore
        lo = jnp.pad(lo, (0, pad))
        hi = jnp.pad(hi, (0, pad))
    npad = A.shape[1]
    grid = (npad // block,)
    zero = np.int32(0)                   # index maps stay i32 under x64
    row = pl.BlockSpec((1, block), lambda i: (zero, i))

    kernel = functools.partial(_pricing_kernel, tol=tol)
    alpha, ratio, cost = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((m, block), lambda i: (zero, i)),
            pl.BlockSpec((m, 1), lambda i: (zero, zero)),
            row, row, row, row,
            pl.BlockSpec((1, 1), lambda i: (zero, zero)),
        ],
        out_specs=[row, row, row],
        out_shape=[jax.ShapeDtypeStruct((1, npad), dt)] * 3,
        interpret=interpret,
    )(A, rho.reshape(m, 1).astype(dt), d.reshape(1, npad).astype(dt),
      state.reshape(1, npad).astype(dt), lo.reshape(1, npad).astype(dt),
      hi.reshape(1, npad).astype(dt), jnp.asarray(s, dt).reshape(1, 1))
    return alpha[0, :n], ratio[0, :n], cost[0, :n]
