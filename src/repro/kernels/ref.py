"""Pure-jnp oracles for every Pallas kernel (the allclose targets)."""
from __future__ import annotations

import jax.numpy as jnp


def pricing_ref(A, rho, d, state, lo, hi, s, tol: float = 1e-9):
    """Oracle for kernels.pricing.pricing (d = maintained reduced costs)."""
    alpha = rho @ A
    sa = s * alpha
    nonbasic = state < 2
    at_up = state == 1
    elig = nonbasic & (((~at_up) & (sa > tol)) | (at_up & (sa < -tol)))
    safe = jnp.where(jnp.abs(sa) > tol, sa, 1.0)
    ratio = jnp.where(elig, jnp.maximum(d / safe, 0.0), jnp.inf)
    cost = jnp.where(elig, jnp.abs(alpha) * (hi - lo), 0.0)
    return alpha, ratio, cost


def bfrt_histogram_ref(ratio, cost, edges):
    """Oracle for kernels.bfrt.bfrt_histogram."""
    finite = jnp.isfinite(ratio)
    bucket = jnp.searchsorted(edges, ratio, side="left")
    bucket = jnp.clip(bucket, 0, len(edges) - 1)
    nb = edges.shape[0]
    sums = jnp.zeros(nb, jnp.float32).at[bucket].add(
        jnp.where(finite, cost, 0.0).astype(jnp.float32))
    counts = jnp.zeros(nb, jnp.float32).at[bucket].add(
        finite.astype(jnp.float32))
    return sums, counts


def bfrt_sequential_ref(ratio, cost, budget):
    """Sequential BFRT walk (the numpy twin in core.lp uses the same rule):
    sort by ratio; flip while cumulative cost stays below budget; crossing
    element enters."""
    import numpy as np
    ratio = np.asarray(ratio)
    cost = np.asarray(cost)
    finite = np.isfinite(ratio)
    order = np.argsort(ratio, kind="stable")
    order = order[finite[order]]
    csum = np.cumsum(cost[order])
    cross = int(np.searchsorted(csum, budget - 1e-12))
    if cross >= len(order):
        return -1, np.zeros_like(finite), False
    q = int(order[cross])
    flips = np.zeros_like(finite)
    flips[order[:cross]] = True
    return q, flips, True


def segment_stats_ref(vals, ids, num_groups):
    """Oracle for kernels.segstats.segment_stats."""
    ids = jnp.asarray(ids)
    vals = jnp.asarray(vals, jnp.float32)
    counts = jnp.zeros(num_groups, jnp.float32).at[ids].add(1.0)
    sums = jnp.zeros((num_groups, vals.shape[1]), jnp.float32).at[ids].add(vals)
    sqs = jnp.zeros((num_groups, vals.shape[1]), jnp.float32).at[ids].add(
        vals * vals)
    return counts, sums, sqs

