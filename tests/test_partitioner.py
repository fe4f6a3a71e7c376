"""Unified Partitioner subsystem: backend registry, batch-vs-scalar
GetGroup parity across all backends, round-based vs heap-based DLV quality,
sharded/chunked group stats, and the paper's DLV-beats-KD-tree property
through the common API."""
import numpy as np
import pytest

from repro.core import partitioner
from repro.core.bucketing import ArraySource
from repro.core.dlv import dlv, dlv_heap, dlv_rounds, ratio_score
from repro.core.hierarchy import Hierarchy, _min_gap
from repro.core.partitioner import fit, group_stats, mesh_stats_counts

BACKENDS = ["dlv", "kdtree", "bucketing"]


@pytest.fixture(scope="module")
def X():
    rng = np.random.default_rng(7)
    return np.concatenate([
        rng.normal(0, 1, (9000, 3)),
        rng.normal(7, 2, (9000, 3)),
    ]) * np.array([1.0, 4.0, 0.3])


@pytest.fixture(scope="module", params=BACKENDS)
def fitted(request, X):
    return request.param, fit(X, backend=request.param, d_f=60)


def test_registry_lists_all_backends():
    assert set(BACKENDS) <= set(partitioner.available_backends())
    with pytest.raises(ValueError):
        fit(np.zeros((4, 2)), backend="no-such-backend")


def test_partition_invariants(fitted, X):
    name, part = fitted
    n = len(X)
    assert part.offsets[0] == 0 and part.offsets[-1] == n
    assert len(np.unique(part.order)) == n          # a permutation
    assert np.all(part.counts >= 1)
    assert part.gid.min() == 0 and part.gid.max() == part.num_groups - 1
    # gid constant within each contiguous slice
    rng = np.random.default_rng(0)
    for g in rng.integers(0, part.num_groups, 25):
        sl = part.order[part.offsets[g]:part.offsets[g + 1]]
        assert np.all(part.gid[sl] == g), name


def test_reps_and_boxes_are_member_stats(fitted, X):
    _, part = fitted
    for g in (0, part.num_groups // 2, part.num_groups - 1):
        m = part.members(g)
        np.testing.assert_allclose(part.reps[g], X[m].mean(0), rtol=1e-9)
        np.testing.assert_allclose(part.boxes_lo[g], X[m].min(0))
        np.testing.assert_allclose(part.boxes_hi[g], X[m].max(0))


def test_members_batch_matches_scalar(fitted):
    _, part = fitted
    gs = np.array([0, part.num_groups // 3, part.num_groups - 1])
    got = part.members_batch(gs)
    want = np.concatenate([part.members(int(g)) for g in gs])
    np.testing.assert_array_equal(got, want)


def test_batch_get_group_matches_scalar_descent(fitted, X):
    """Acceptance: vectorized descent == scalar split-tree descent on 10k
    random probes, for every backend, in numpy AND the jitted while_loop."""
    name, part = fitted
    rng = np.random.default_rng(1)
    T = X[rng.choice(len(X), 10_000, replace=True)]
    scalar = np.fromiter((part.get_group(t) for t in T), np.int64, len(T))
    np.testing.assert_array_equal(part.get_group_batch(T), scalar, err_msg=name)
    np.testing.assert_array_equal(
        np.asarray(part.get_group_batch(T, jit=True)), scalar, err_msg=name)
    # membership probes agree with assigned ids
    idx = rng.choice(len(X), 2_000, replace=False)
    np.testing.assert_array_equal(part.get_group_batch(X[idx]),
                                  part.gid[idx], err_msg=name)


def test_rounds_match_heap_quality(X):
    """Round-based DLV reproduces the heap build's ratio score (tolerance)
    at a comparable group count."""
    heap = dlv_heap(X, 60)
    rounds = dlv_rounds(X, 60)
    assert abs(rounds.num_groups - heap.num_groups) <= \
        max(10, heap.num_groups // 3)
    for j in range(X.shape[1]):
        z_h = ratio_score(X[:, j], heap.gid, weighted=True)
        z_r = ratio_score(X[:, j], rounds.gid, weighted=True)
        assert z_r <= z_h * 1.25 + 5e-3, (j, z_r, z_h)


def test_dlv_beats_kdtree_through_registry():
    """Fig. 7 through the common API: DLV ratio score <= KD-tree's at equal
    group count (the paper's headline partitioning property)."""
    rng = np.random.default_rng(2)
    X = rng.normal(size=(20_000, 1))
    res = fit(X, backend="dlv", d_f=100)
    kd = fit(X, backend="kdtree", tau=max(2, 20_000 // res.num_groups))
    assert ratio_score(X[:, 0], res.gid) < ratio_score(X[:, 0], kd.gid)


def test_bucketing_source_and_array_agree(X):
    a = fit(X, backend="bucketing", d_f=60, memory_rows=4000)
    b = fit(ArraySource(X), backend="bucketing", d_f=60, memory_rows=4000)
    np.testing.assert_array_equal(a.gid, b.gid)


# ------------------------------------------------------------ group stats


def test_group_stats_chunked_matches_dense(X):
    part = fit(X, backend="dlv", d_f=60)
    dense = group_stats(X, part.order, part.offsets)
    chunked = group_stats(X, part.order, part.offsets, chunk_rows=700)
    for d, c in zip(dense, chunked):
        np.testing.assert_allclose(c, d, rtol=1e-9, atol=1e-12)


def test_group_stats_sharded_on_mesh(X):
    """Chunk-wise segstats accumulation across a real (host-device) mesh
    reproduces the dense reduceat pass."""
    jax = pytest.importorskip("jax")
    if len(jax.devices()) < 2:
        pytest.skip("needs >= 2 devices (conftest provides host devices)")
    from repro.launch.mesh import make_local_mesh
    mesh = make_local_mesh(data=2, model=1)
    part = fit(X, backend="dlv", d_f=60)
    dense = group_stats(X, part.order, part.offsets)
    before = mesh_stats_counts()["chunks"]
    sharded = group_stats(X, part.order, part.offsets, mesh=mesh,
                          chunk_rows=2048)
    for d, s in zip(dense, sharded):
        np.testing.assert_allclose(s, d, rtol=1e-8, atol=1e-8)
    after = mesh_stats_counts()
    assert after["chunks"] - before == -(-len(X) // 2048)
    assert after["devices"] >= 2


def test_hierarchy_chunked_build_matches_in_memory(X):
    tbl = {f"a{j}": X[:, j] for j in range(X.shape[1])}
    h_mem = Hierarchy(tbl, list(tbl), d_f=40, alpha=200,
                      rng=np.random.default_rng(0))
    h_chk = Hierarchy(tbl, list(tbl), d_f=40, alpha=200,
                      rng=np.random.default_rng(0), chunk_rows=1500)
    assert h_mem.L == h_chk.L
    for l in range(1, h_mem.L + 1):
        np.testing.assert_allclose(h_chk.layers[l].X, h_mem.layers[l].X,
                                   rtol=1e-9)
        np.testing.assert_array_equal(h_chk.layers[l].part.gid,
                                      h_mem.layers[l].part.gid)


def test_hierarchy_backend_selection(X):
    tbl = {f"a{j}": X[:, j] for j in range(X.shape[1])}
    for be in BACKENDS:
        h = Hierarchy(tbl, list(tbl), d_f=40, alpha=400,
                      rng=np.random.default_rng(0), backend=be)
        assert h.L >= 1
        part = h.layers[1].part
        rng = np.random.default_rng(3)
        idx = rng.choice(len(X), 300, replace=False)
        np.testing.assert_array_equal(h.get_group_batch(1, X[idx]),
                                      part.gid[idx], err_msg=be)


# ------------------------------------------------------------- edge cases


def test_duplicate_heavy_membership_consistency():
    """Cuts snap to equal-value run starts: get_group == gid even when the
    data is mostly ties (boundaries can otherwise land mid-run and route
    tied tuples to the wrong side of the split tree)."""
    rng = np.random.default_rng(11)
    X = np.repeat(rng.normal(size=(50, 2)), 20, axis=0)
    for method in ("rounds", "heap"):
        res = dlv(X, 10, method=method, rng=np.random.default_rng(0))
        got = res.get_group_batch(X)
        np.testing.assert_array_equal(got, res.gid, err_msg=method)


def test_jit_descent_on_boundless_tree():
    """A merged single-bucket tree can have nodes with zero bounds; the
    jitted descent must not gather from an empty bounds array."""
    X = np.full((3000, 2), 5.0)
    part = fit(X, backend="bucketing")
    out = np.asarray(part.get_group_batch(X[:50], jit=True))
    np.testing.assert_array_equal(out, part.gid[:50])


def test_bucketing_survives_concentrated_data():
    """Point-mass clusters that equal-width edge refinement cannot isolate
    degrade to an oversized in-memory bucket instead of crashing."""
    rng = np.random.default_rng(12)
    X = np.concatenate([rng.normal(0, 0.01, (5000, 2)),
                        rng.normal(1000, 0.01, (5000, 2))])
    with pytest.warns(UserWarning, match="oversized bucket"):
        part = fit(X, backend="bucketing", d_f=50, memory_rows=3000)
    assert part.counts.sum() == len(X)
    idx = rng.choice(len(X), 500, replace=False)
    np.testing.assert_array_equal(part.get_group_batch(X[idx]),
                                  part.gid[idx])


# ---------------------------------------------------------------- min gap


def test_min_gap_exact_and_sampled():
    rng = np.random.default_rng(5)
    X = rng.integers(0, 50, size=(30_000, 2)).astype(np.float64) * 0.25
    exact = _min_gap(X)
    assert exact == pytest.approx(0.25)
    # sampled path (force it) can only overestimate the true minimum gap
    est = _min_gap(X, exact_limit=1000, sample=5000,
                   rng=np.random.default_rng(0))
    assert est >= exact - 1e-12
