"""The IR contract layer: the real hot paths satisfy their contracts on
the host-device mesh grid, and each checker actually fires on a seeded
violation (tiny budget, f64 promotion, callback-in-loop)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.core  # noqa: F401  (x64 on, before any tracing)
from repro.analysis import contracts
from repro.analysis.contracts import (callback_prims, check_lp_batch,
                                      check_lp_twin, check_pq_step,
                                      check_refresh_step, check_update_step,
                                      collective_prims, dense_dot_counts,
                                      f64_introductions,
                                      pq_collective_budget, run_contracts)


def _mesh():
    if len(jax.devices()) < 2:
        pytest.skip("needs >= 2 devices")
    return jax.make_mesh((1, 2), ("data", "model"))


# ------------------------------------------------------- jaxpr primitives


def test_f64_introduction_detector():
    f = lambda x: x.astype(jnp.float64) * 2.0
    jx = jax.make_jaxpr(f)(jax.ShapeDtypeStruct((4,), jnp.float32)).jaxpr
    assert "convert_element_type" in f64_introductions(jx)
    g = lambda x: x * 2.0
    jx = jax.make_jaxpr(g)(jax.ShapeDtypeStruct((4,), jnp.float32)).jaxpr
    assert f64_introductions(jx) == []


def test_collective_prims_found_through_shard_map():
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    mesh = _mesh()
    f = shard_map(lambda x: jax.lax.psum(x, "model"), mesh=mesh,
                  in_specs=P("model"), out_specs=P())
    jx = jax.make_jaxpr(f)(jax.ShapeDtypeStruct((4,), jnp.float64)).jaxpr
    # under check_vma the primitive is psum_invariant: match the family
    assert any(p.startswith("psum") for p, _ in collective_prims(jx))


def test_callback_prims_context_includes_while():
    def f(x):
        def body(c):
            y = jax.pure_callback(
                lambda v: np.asarray(v) * 2, jax.ShapeDtypeStruct((), x.dtype),
                c)
            return y
        return jax.lax.while_loop(lambda c: c < 10.0, body, x)

    jx = jax.make_jaxpr(f)(jax.ShapeDtypeStruct((), jnp.float64)).jaxpr
    found = callback_prims(jx)
    assert found and any("while" in ctx for _, ctx in found)


def test_dense_dot_counts_top_vs_cond():
    def f(A, x):
        top = A @ x
        return jax.lax.cond(top.sum() > 0, lambda _: A @ x,
                            lambda _: jnp.zeros_like(top), None)

    jx = jax.make_jaxpr(f)(jax.ShapeDtypeStruct((64, 64), jnp.float64),
                           jax.ShapeDtypeStruct((64,), jnp.float64)).jaxpr
    top, cond = dense_dot_counts(jx, 64 * 64)
    assert (top, cond) == (1, 1)


# ----------------------------------------------------- hot-path contracts


def test_update_step_lowers_with_zero_collectives():
    r = check_update_step(_mesh(), m=8, n=1 << 12)
    assert r.ok, [v.format() for v in r.violations]
    assert r.record["collective_counts"] == {}
    assert r.record["dense_passes"] == {"top": 0, "cond": 0}


def test_pq_step_within_declared_budget():
    r = check_pq_step(_mesh(), m=8, n=1 << 12)
    assert r.ok, [v.format() for v in r.violations]
    assert 0 < r.record["budget_used_frac"] < 1
    assert r.record["dense_passes"]["top"] == 1


def test_refresh_step_is_the_recompute_site():
    r = check_refresh_step(_mesh(), m=8, n=1 << 12)
    assert r.ok, [v.format() for v in r.violations]
    assert 1 <= r.record["dense_passes"]["top"] <= 2


def test_lp_twin_clean_and_trip_bounded():
    r = check_lp_twin(m=4, N=64, max_iters=32)
    assert r.ok, [v.format() for v in r.violations]
    # the pivot body is scatter-free (one-hot selects, stable-sort rank
    # compare), so the only inner while loops left are the LU sweeps of
    # the refresh factorization — bound by m, never by N or max_iters
    assert r.record["max_trip"] == 4


def test_lp_batch_core_clean():
    r = check_lp_batch(m=4, n=16, K=4, max_iters=16)
    assert r.ok, [v.format() for v in r.violations]
    # single-device batch: the record must carry the while trip bounds
    assert r.record["max_trip"] > 0


def test_budget_formula_scales_with_p():
    assert pq_collective_budget(512, 8) > pq_collective_budget(2, 8)
    # O(1) in n by construction: n does not appear in the signature


def test_seeded_budget_violation_fires(monkeypatch):
    monkeypatch.setattr(contracts, "pq_collective_budget",
                        lambda *a, **k: 1.0)
    r = check_pq_step(_mesh(), m=8, n=1 << 12)
    assert any(v.rule == "IRC004" for v in r.violations)


def test_run_contracts_host_grid_green():
    violations, records, wall_s = run_contracts("host")
    assert violations == [], "\n".join(v.format() for v in violations)
    names = {r["hot_path"].split("@")[0] for r in records}
    assert {"distributed.pq_step", "distributed.update_step",
            "distributed.refresh_step", "lp.twin_step", "lp_batch.core",
            "kernels.pricing", "kernels.segstats",
            "partitioner.descend_batch"} <= names
    assert wall_s > 0
