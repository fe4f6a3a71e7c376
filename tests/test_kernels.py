"""Per-kernel shape/dtype sweeps vs the ref.py pure-jnp oracles
(interpret=True executes kernel bodies on CPU)."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.ops import bfrt_select_op, pricing_op, segment_stats_op
from repro.kernels.ref import bfrt_sequential_ref


@pytest.mark.parametrize("m,n,block", [(3, 1000, 256), (8, 3000, 512),
                                       (1, 257, 128), (16, 4096, 1024)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.float64])
def test_pricing_kernel(m, n, block, dtype, rng):
    A = jnp.asarray(rng.normal(size=(m, n)), dtype)
    rho = jnp.asarray(rng.normal(size=m), dtype)
    d = jnp.asarray(rng.normal(size=n), dtype)
    state = jnp.asarray(rng.integers(0, 3, n), jnp.int32)
    lo = jnp.zeros(n, dtype)
    hi = jnp.asarray(rng.uniform(1, 3, n), dtype)
    for s in (1.0, -1.0):
        a1, r1, c1 = pricing_op(A, rho, d, state, lo, hi, s, block=block)
        a2, r2, c2 = ref.pricing_ref(A, rho, d, state, lo, hi, s)
        tol = 1e-5 if dtype == jnp.float32 else 1e-10
        np.testing.assert_allclose(a1, a2, rtol=tol, atol=tol)
        np.testing.assert_allclose(np.where(np.isfinite(r1), r1, -1),
                                   np.where(np.isfinite(r2), r2, -1),
                                   rtol=tol, atol=tol)
        np.testing.assert_allclose(c1, c2, rtol=tol, atol=tol)


@pytest.mark.parametrize("n", [300, 2048, 5000])
@pytest.mark.parametrize("frac_elig", [0.05, 0.5])
def test_bfrt_select_matches_sequential(n, frac_elig, rng):
    ratio = np.where(rng.random(n) < frac_elig,
                     rng.uniform(0, 10, n), np.inf)
    cost = np.where(np.isfinite(ratio), rng.uniform(0.1, 2, n), 0.0)
    for budget in (0.5, 10.0, 100.0):
        q1, f1, ok1 = bfrt_select_op(jnp.asarray(ratio), jnp.asarray(cost),
                                     budget)
        q2, f2, ok2 = bfrt_sequential_ref(ratio, cost, budget)
        assert bool(ok1) == ok2
        if ok2:
            assert int(q1) == q2
            np.testing.assert_array_equal(np.asarray(f1), f2)


def test_bfrt_dual_unbounded(rng):
    """Total flip capacity below budget => no crossing (infeasible LP)."""
    n = 500
    ratio = np.where(rng.random(n) < 0.1, rng.uniform(0, 1, n), np.inf)
    cost = np.where(np.isfinite(ratio), 0.01, 0.0)
    _, _, ok = bfrt_select_op(jnp.asarray(ratio), jnp.asarray(cost), 1e9)
    assert not bool(ok)


@pytest.mark.parametrize("n,k,G,block", [(1000, 1, 11, 128),
                                         (5000, 4, 57, 256),
                                         (777, 2, 9, 512)])
def test_segstats_kernel(n, k, G, block, rng):
    ids = np.sort(rng.integers(0, G, n)).astype(np.int32)
    vals = rng.normal(size=(n, k)).astype(np.float32)
    c1, s1, q1 = segment_stats_op(jnp.asarray(vals), jnp.asarray(ids), G,
                                  block=block)
    c2, s2, q2 = ref.segment_stats_ref(vals, ids, G)
    np.testing.assert_allclose(c1, c2, atol=1e-3)
    np.testing.assert_allclose(s1, s2, rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(q1, q2, rtol=2e-3, atol=2e-3)


def test_segstats_builds_representatives(rng):
    """count/sum/sumsq -> means and variances (the DLV rep builder)."""
    n, k, G = 4000, 3, 40
    ids = np.sort(rng.integers(0, G, n)).astype(np.int32)
    vals = rng.normal(size=(n, k)).astype(np.float32)
    cnt, sm, sq = segment_stats_op(jnp.asarray(vals), jnp.asarray(ids), G)
    cnt = np.maximum(np.asarray(cnt), 1)
    means = np.asarray(sm) / cnt[:, None]
    for g in range(0, G, 7):
        mask = ids == g
        if mask.sum():
            np.testing.assert_allclose(means[g], vals[mask].mean(0),
                                       rtol=1e-2, atol=1e-2)

