"""DLV partitioning: the paper's Theorems 1-2, tree lookups, KD-tree
comparison (Fig. 7 qualitative), scale factors."""
import numpy as np
import pytest

from repro.core.dlv import (dlv, dlv_1d, dlv_1d_partition, get_scale_factors,
                            ratio_score)
from repro.core.kdtree import kdtree_partition

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:          # property test skips; the rest of the file runs
    HAVE_HYPOTHESIS = False


def _theorem2_case(seed, n):
    rng = np.random.default_rng(seed)
    kind = seed % 3
    if kind == 0:
        vals = rng.normal(size=n)
    elif kind == 1:
        vals = rng.exponential(size=n)
    else:
        vals = np.concatenate([rng.normal(-5, 0.1, n // 2),
                               rng.normal(5, 3.0, n - n // 2)])
    vals = np.sort(vals)
    if np.var(vals) <= 0:
        return
    beta = 24 * np.var(vals) / n ** 2
    gid, _ = dlv_1d_partition(vals, beta)
    p = int(gid.max()) + 1
    assert ratio_score(vals, gid) <= 24 / n + 1e-9
    assert p <= 0.75 * n + 0.5


if HAVE_HYPOTHESIS:
    @settings(max_examples=20, deadline=None)
    @given(st.integers(0, 1000), st.sampled_from([100, 500, 2000]))
    def test_theorem2_universal_ratio_score(seed, n):
        """1-D DLV, beta = 24 sigma^2/n^2: z <= 24/n and p <= 3n/4 + 1/2."""
        _theorem2_case(seed, n)
else:
    @pytest.mark.parametrize("seed,n", [(0, 100), (1, 500), (2, 2000),
                                        (3, 500), (7, 100), (11, 2000)])
    def test_theorem2_universal_ratio_score(seed, n):
        """Fixed-seed fallback when hypothesis is not installed."""
        _theorem2_case(seed, n)


def test_theorem1_construction():
    """KD-tree ratio score explodes; 1-D DLV's goes to 0."""
    omega, n = 1.0, 400
    eps = 3 * omega / n
    S = np.sort(np.concatenate([[-omega, omega], np.full(n, omega + eps)]))
    # DLV
    beta = 24 * np.var(S) / len(S) ** 2
    gid, _ = dlv_1d_partition(S, beta)
    assert ratio_score(S, gid) == pytest.approx(0.0, abs=1e-12)
    # KD-tree with radius limit omega: groups {-w, w} together
    kd = kdtree_partition(S[:, None], tau=2, omega=omega)
    z_kd = ratio_score(S, kd.gid)
    assert z_kd > 1.0   # catastrophically bad (unbounded as n grows)


def test_dlv_beats_kdtree_ratio_score():
    """Fig. 7: DLV's ratio score beats KD-tree's at equal #groups."""
    rng = np.random.default_rng(2)
    X = rng.normal(size=(20_000, 1))
    res = dlv(X, d_f=100)
    kd = kdtree_partition(X, tau=max(2, 20_000 // res.num_groups))
    z_dlv = ratio_score(X[:, 0], res.gid)
    z_kd = ratio_score(X[:, 0], kd.gid)
    assert z_dlv < z_kd


def test_dlv_group_membership_tree():
    rng = np.random.default_rng(3)
    X = rng.normal(size=(5000, 3)) * np.array([1.0, 5.0, 0.2])
    res = dlv(X, d_f=50)
    assert res.num_groups >= 5000 // 50 * 0.5
    for i in rng.choice(5000, 100, replace=False):
        assert res.get_group(X[i]) == res.gid[i]


def test_dlv_reps_and_boxes():
    rng = np.random.default_rng(4)
    X = rng.normal(size=(2000, 2))
    res = dlv(X, d_f=20)
    for g in (0, res.num_groups // 2, res.num_groups - 1):
        m = res.members(g)
        np.testing.assert_allclose(res.reps[g], X[m].mean(0), rtol=1e-10)
        np.testing.assert_allclose(res.boxes_lo[g], X[m].min(0), rtol=1e-10)
        np.testing.assert_allclose(res.boxes_hi[g], X[m].max(0), rtol=1e-10)


def test_dlv_groups_are_contiguous_slices():
    """The cache-friendly layout the paper designs for."""
    rng = np.random.default_rng(5)
    X = rng.normal(size=(3000, 2))
    res = dlv(X, d_f=30)
    assert res.offsets[0] == 0 and res.offsets[-1] == 3000
    assert np.all(np.diff(res.offsets) >= 1)
    # order is a permutation; gid is constant within each slice
    assert len(np.unique(res.order)) == 3000
    for g in rng.integers(0, res.num_groups, 20):
        sl = res.order[res.offsets[g]:res.offsets[g + 1]]
        assert np.all(res.gid[sl] == g)


def test_get_scale_factors_hits_target():
    rng = np.random.default_rng(6)
    X = rng.normal(size=(5000, 2))
    c = get_scale_factors(X, d_f=50, rng=rng)
    for j in range(2):
        vals = np.sort(X[:, j])
        beta = c[j] * np.var(vals) / 50 ** 2
        p = int(dlv_1d(vals, beta).sum()) + 1
        # binary search on a sample: within 3x of the target split count
        assert 50 / 3 <= p <= 50 * 3


# ------------------------------------------------- scan numerics satellite


def test_scan_f32_cut_parity_on_wide_magnitude_values():
    """The compensated, dtype-derived scan: even in float32 (the no-x64
    footgun path) the cut decisions match the float64 host reference for
    mean-centered wide-magnitude values — where the seed's unshifted scan
    produces ~60x too many cuts."""
    import jax.numpy as jnp

    from repro.core.dlv import _dlv_scan_cols, _dlv_scan_np
    for mag in (1e6, 3e7):
        for seed in range(3):
            rng = np.random.default_rng(seed)
            v = np.sort(rng.normal(mag, 1.0, 5000))
            beta = 13.5 * np.var(v) / 100 ** 2
            vc = v - v.mean()
            ref = _dlv_scan_np(vc, beta)
            f32 = np.asarray(_dlv_scan_cols(
                jnp.asarray(vc[:, None], jnp.float32),
                jnp.asarray([beta], jnp.float32)))[:, 0]
            assert ref.sum() > 10          # the case actually splits
            np.testing.assert_array_equal(f32, ref)


def test_scan_segmented_matches_per_segment_reference():
    """_seg_cuts over concatenated segments == per-segment f64 reference,
    across both the batched-columns and jump-scan paths."""
    from repro.core.dlv import _dlv_scan_np, _seg_cuts
    rng = np.random.default_rng(8)
    for lens in ([4000], [900] * 40, [17, 2500, 300, 41] * 8):
        segs = [np.sort(rng.normal(rng.uniform(-5, 5), 1.0, L))
                for L in lens]
        beta = np.array([13.5 * max(np.var(s), 1e-12) / 60 ** 2
                         for s in segs])
        shifted = np.concatenate([s - s.mean() for s in segs])
        got = _seg_cuts(shifted, np.array(lens), beta)
        want = np.concatenate([_dlv_scan_np(s - s.mean(), b)
                               for s, b in zip(segs, beta)])
        np.testing.assert_array_equal(got, want)


# ---------------------------------------------------- ratio_score satellite


def test_ratio_score_sparse_and_negative_ids():
    """Sparse / negative / non-integer gids compact to the same score as
    their dense relabeling (single np.unique pass)."""
    rng = np.random.default_rng(9)
    vals = rng.normal(size=2000)
    dense = rng.integers(0, 20, 2000)
    z_dense = ratio_score(vals, dense)
    remap = np.array([-7, 3, 10**6, 55, -1, 17, 999_999, 123456, 42, 8,
                      -100, 7_000_000, 31, 2, 900_000, 64, -3, 5, 77, 88])
    z_sparse = ratio_score(vals, remap[dense])
    assert z_sparse == pytest.approx(z_dense, rel=1e-12)
    z_float = ratio_score(vals, remap[dense].astype(np.float64))
    assert z_float == pytest.approx(z_dense, rel=1e-12)
    # weighted variant stays within [0, 1] and agrees too
    zw = ratio_score(vals, remap[dense], weighted=True)
    assert 0.0 <= zw <= 1.0 + 1e-12
    assert zw == pytest.approx(ratio_score(vals, dense, weighted=True),
                               rel=1e-12)


# ------------------------------------------- cut plan on a TPU (forced here)


def _segments(rng, lens):
    """Sorted segments, each centered on its own mean, with the build's
    beta at d_f = 100 (scale factor 13.5)."""
    segs = [np.sort(rng.normal(rng.uniform(-5, 5), rng.uniform(0.5, 2), L))
            for L in lens]
    beta = np.array([13.5 * np.var(s) / 100 ** 2 for s in segs])
    return np.concatenate([s - s.mean() for s in segs]), beta


@pytest.fixture
def tpu_plan(monkeypatch):
    """``_seg_cuts`` planning as on a TPU; its jitted device scan then runs
    on this backend."""
    import repro.kernels.ops as ops
    monkeypatch.setattr(ops, "on_tpu", lambda: True)


@pytest.mark.parametrize("lens", [
    [200_000],
    [900] * 40,
    [17, 2500, 300, 41] * 8,
    [150_000] + [700] * 600,
], ids=["long", "equal", "mixed", "long_beside_short"])
def test_seg_cuts_tpu_plan_matches_reference(tpu_plan, lens):
    """With the TPU's plan, whichever form each class takes, the cuts are
    the float64 reference's, segment by segment."""
    from repro.core.dlv import _dlv_scan_np, _seg_cuts
    from repro.core.spans import SpanLog
    vals, beta = _segments(np.random.default_rng(len(lens)), lens)
    log = SpanLog()
    got = _seg_cuts(vals, np.array(lens), beta, pitch=100, spans=log)
    starts = np.concatenate([[0], np.cumsum(lens)])
    for s, b in enumerate(beta):
        a, e = starts[s], starts[s + 1]
        np.testing.assert_array_equal(got[a:e], _dlv_scan_np(vals[a:e], b))
    assert got.sum() > len(lens)                 # the cases actually split
    assert (log.counters["dlv_cut_rows_device"]
            + log.counters["dlv_cut_rows_host"]) == sum(lens)
    if len(lens) > 600:                          # both forms ran
        assert log.counters["dlv_cut_rows_device"] == 600 * 700
        assert log.counters["dlv_cut_rows_host"] == 150_000


def test_tpu_cut_plan_by_shape():
    """On a TPU a lone long segment goes to the host jump scan and a wide
    class of short segments to the device column scan, in pow2 classes."""
    from repro.core.dlv import _cut_plan
    [(segs, rows, column_scan)] = _cut_plan(np.array([10_000_000]), 100,
                                            True)
    assert list(segs) == [0] and rows == 1 << 24 and not column_scan
    [(segs, rows, column_scan)] = _cut_plan(np.array([900] * 2000), 100,
                                            True)
    assert len(segs) == 2000 and rows == 1024 and column_scan
    plan = list(_cut_plan(np.array([3000, 200_000, 600, 5000]), 100, True))
    assert [(list(s), r) for s, r, _ in plan] == [
        ([2], 1024), ([0], 4096), ([3], 8192), ([1], 262144)]


def test_host_cut_plan_by_shape():
    """Off the TPU: sorted lengths grouped within 2x, each group a numpy
    column scan when at least max(16, pitch // 2) wide, else jump scans."""
    from repro.core.dlv import _cut_plan
    [(segs, _, column_scan)] = _cut_plan(np.array([900] * 40), 256, False)
    assert len(segs) == 40 and not column_scan
    [(segs, _, column_scan)] = _cut_plan(np.array([900] * 60), 100, False)
    assert len(segs) == 60 and column_scan
    groups = [(sorted(s), c) for s, _, c in
              _cut_plan(np.array([100] * 20 + [250] + [10_000]), 8, False)]
    assert groups == [(list(range(20)), True), ([20], False), ([21], False)]


def test_seg_cuts_counts_rows_on_the_build_log():
    """``dlv_rounds`` hands its log to every ``_seg_cuts`` call: the rows
    each side cut add up to the rows of every scan; off the TPU all are
    the host's."""
    from repro.core.spans import SpanLog
    X = np.random.default_rng(5).normal(size=(3000, 3))
    log = SpanLog()
    dlv(X, d_f=10, spans=log)
    assert log.counters["dlv_cut_rows_device"] == 0
    assert log.counters["dlv_cut_rows_host"] >= 3000 + 3 * 3000
