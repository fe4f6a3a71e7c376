"""Ahead-of-time compiles for a described TPU v5e chip, at real widths.

Nothing here runs on a chip: XLA:TPU and Mosaic compile each program for
one chip of a described ``v5e:2x2`` topology and refuse what the chip
would refuse (block layouts off the 8x128 tiling, f64 in a kernel, ops
the TPU backend does not implement in f64).  Widths are the main
path's: m = 4 LP rows (Q2_TPCH), n = 100,000 columns (alpha), k = 4
attributes over a 2^20-row DLV round, 128 BFRT buckets, and a B&B wave
of K = 64 lanes.

The topology is described inside a module fixture, never at import, so
every test worker collects the same tests and only the one that runs
this file loads the TPU compiler.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

import repro.core  # noqa: F401  (x64 on, as the engine runs)

M, N_COLS, K_ATTRS, DLV_ROWS, BUCKETS, LANES = 4, 100_000, 4, 1 << 20, 128, 64


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    # repro: allow[REPRO004] the fixture pattern: skip where no TPU
    # topology can be described
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One chip's sharding, with the persistent compilation cache off:
    an entry written for a described chip cannot be read back here."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


def _spec(sharding):
    return lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,
                                                  sharding=sharding)


def test_segstats_kernel_compiles_for_v5e(one_chip):
    from repro.kernels.segstats import segstats_partials
    S = _spec(one_chip)
    txt = _compile(lambda v, i: segstats_partials(v, i, interpret=False),
                   S((DLV_ROWS, K_ATTRS), jnp.float32),
                   S((DLV_ROWS,), jnp.int32))
    assert "tpu_custom_call" in txt


def test_pricing_kernel_compiles_for_v5e(one_chip):
    from repro.kernels.pricing import pricing
    S = _spec(one_chip)
    f = jnp.float32
    txt = _compile(
        lambda A, rho, d, st, lo, hi, s: pricing(A, rho, d, st, lo, hi, s,
                                                 interpret=False),
        S((M, N_COLS), f), S((M,), f), S((N_COLS,), f),
        S((N_COLS,), jnp.int32), S((N_COLS,), f), S((N_COLS,), f), S((), f))
    assert "tpu_custom_call" in txt


def test_pricing_kernel_refuses_f64_on_tpu():
    from repro.kernels.pricing import pricing
    z = jnp.zeros((M, 8), jnp.float64)
    v = jnp.zeros(8, jnp.float64)
    with pytest.raises(TypeError, match="f32"):
        pricing(z, jnp.zeros(M), v, jnp.zeros(8, jnp.int32), v, v, 1.0,
                interpret=False)


def test_bfrt_histogram_kernel_compiles_for_v5e(one_chip):
    from repro.kernels.bfrt import bfrt_histogram
    S = _spec(one_chip)
    f = jnp.float32
    txt = _compile(lambda r, c, e: bfrt_histogram(r, c, e, interpret=False),
                   S((N_COLS,), f), S((N_COLS,), f), S((BUCKETS,), f))
    assert "tpu_custom_call" in txt


def test_batched_lp_core_compiles_in_f64_for_v5e(one_chip):
    """The B&B wave engine, f64 end to end: its basis inverse and BFRT
    walk must lower without LU decompositions or f64 sorts."""
    from repro.core import lp_batch
    S = _spec(one_chip)
    m_pad, n_pad = M, 2000
    Np = n_pad + m_pad
    core = lp_batch._batched_core(m_pad, n_pad, LANES, 5000, 64)
    txt = jax.jit(core).lower(
        S((Np,), jnp.float64), S((m_pad, Np), jnp.float64),
        S((LANES, 3 * Np + m_pad + 3), jnp.float64)).compile().as_text()
    assert "while" in txt


def test_dlv_column_scan_compiles_for_v5e(one_chip):
    """The build's TPU branch scans in the engine's dtype (f64)."""
    from repro.core.dlv import _dlv_scan_cols, _scan_dtype
    S = _spec(one_chip)
    dt = _scan_dtype()
    _compile(_dlv_scan_cols, S((DLV_ROWS, 1), dt), S((1,), dt))


def test_basis_inverse_matches_numpy():
    """The Gauss-Jordan inverse the device engines factorize with."""
    from repro.core.lp import basis_inverse
    rng = np.random.default_rng(0)
    for m in (1, 4, 7):
        B = rng.normal(size=(m, m))
        if m > 1:
            B[0, 0] = 0.0      # forces a row swap at the first pivot
        got = np.asarray(jax.jit(basis_inverse)(jnp.asarray(B)))
        np.testing.assert_allclose(got, np.linalg.inv(B), rtol=1e-10,
                                   atol=1e-12)
