"""Streaming-compression validation: the O(n) histogram-quantile threshold
vs ``jnp.quantile``, the suffix-count search vs an explicit numpy
histogram of the same bins, and the end-to-end ``compress_packed``
pipeline vs the seed per-leaf path."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (CompressionConfig, compress, compress_packed,
                        decompress, pack_tree, unpack_tree)
from repro.core.compeft import _build_segment_buffer
from repro.kernels.histogram_quantile import (NBINS,
                                              segmented_quantile_moments)

DENSITIES = (0.05, 0.1, 0.5)


def _dists(n=20_000):
    rng = np.random.default_rng(0)
    return {
        "normal": rng.normal(0, 1, n).astype(np.float32),
        "constant": np.full(n, 0.7, np.float32),
        "bimodal": np.where(rng.random(n) < 0.5, 0.1, 10.0
                            ).astype(np.float32) * rng.choice([-1, 1], n),
        "heavy_tail": (rng.standard_t(2, n) * 3).astype(np.float32),
        "with_zeros": np.where(rng.random(n) < 0.8, 0.0,
                               rng.normal(0, 1, n)).astype(np.float32),
    }


def _segbuf(arrays, cols=512):
    leaves = [jnp.asarray(a) for a in arrays]
    return _build_segment_buffer(leaves, cols)


@pytest.mark.parametrize("density", DENSITIES)
def test_threshold_matches_order_statistic(density):
    """thr must sit within one refined histogram bin below the k-th largest
    magnitude — for every distribution, including ties and heavy tails."""
    arrays = list(_dists().values())
    buf, row_seg, row_valid, seg_count, _ = _segbuf(arrays)
    out = segmented_quantile_moments(buf, row_seg, row_valid, seg_count,
                                     density, n_seg=len(arrays))
    for i, a in enumerate(arrays):
        mag = np.abs(a)
        n = a.size
        k = max(1, round(density * n))
        kth = np.partition(mag, n - k)[n - k]          # k-th largest
        thr = float(out["threshold"][i])
        bin_w = float(out["max"][i]) / NBINS           # coarse-bin width
        assert thr <= kth + 1e-7, (i, thr, kth)
        assert kth - thr <= bin_w + 1e-7, (i, thr, kth, bin_w)
        # the kept set contains the top-k (ties may keep a few more)
        kept = int((mag >= thr).sum())
        assert kept >= k


@pytest.mark.parametrize("density", DENSITIES)
def test_threshold_close_to_jnp_quantile_smooth(density):
    """On smooth distributions the threshold also matches the interpolating
    jnp.quantile within a coarse bin width."""
    rng = np.random.default_rng(1)
    for scale in (1e-3, 1.0, 50.0):
        a = (rng.normal(0, scale, 30_000)).astype(np.float32)
        buf, row_seg, row_valid, seg_count, _ = _segbuf([a])
        out = segmented_quantile_moments(buf, row_seg, row_valid, seg_count,
                                         density, n_seg=1)
        want = float(jnp.quantile(jnp.abs(jnp.asarray(a)), 1.0 - density))
        bin_w = float(out["max"][0]) / NBINS
        assert abs(float(out["threshold"][0]) - want) <= bin_w + 1e-7


def test_moments_match_numpy():
    arrays = list(_dists().values())
    buf, row_seg, row_valid, seg_count, _ = _segbuf(arrays)
    out = segmented_quantile_moments(buf, row_seg, row_valid, seg_count,
                                     0.1, n_seg=len(arrays))
    for i, a in enumerate(arrays):
        assert float(out["std"][i]) == pytest.approx(float(a.std()),
                                                     rel=2e-3, abs=1e-6)
        assert float(out["mean_abs"][i]) == pytest.approx(
            float(np.abs(a).mean()), rel=2e-3, abs=1e-6)
        assert float(out["max"][i]) == pytest.approx(
            float(np.abs(a).max()), rel=1e-6)


def test_all_zero_segment_threshold_is_zero():
    buf, row_seg, row_valid, seg_count, _ = _segbuf(
        [np.zeros(1000, np.float32), np.ones(1000, np.float32)])
    out = segmented_quantile_moments(buf, row_seg, row_valid, seg_count,
                                     0.1, n_seg=2)
    assert float(out["threshold"][0]) == 0.0
    assert float(out["std"][0]) == 0.0


def _histogram_threshold_np(arrays, density, nbins):
    """Oracle: the two-pass scheme with explicit np.bincount histograms."""
    thrs = []
    for a in arrays:
        mag = np.abs(a.astype(np.float32))
        keep = max(int(np.round(a.size * np.float64(density))), 1)
        smax = np.float32(mag.max())
        if smax <= 0:
            thrs.append(0.0)
            continue

        def suffix(lo, width):
            w = np.float32(max(width, np.float32(1e-30)))
            pos = (mag - lo) * np.float32(nbins / w)
            inr = (mag >= lo) & (mag <= lo + w)
            b = np.clip(pos.astype(np.int64), 0, nbins - 1)[inr]
            h = np.bincount(b, minlength=nbins)
            return np.cumsum(h[::-1])[::-1]

        s1 = suffix(np.float32(0.0), smax)
        cb = max(int(np.nonzero(s1 >= keep)[0].max(initial=0)), 0)
        cw = np.float32(max(smax, np.float32(1e-30)) / np.float32(nbins))
        lo1 = np.float32(cb) * cw
        above = int(s1[cb + 1]) if cb + 1 < nbins else 0
        s2 = suffix(lo1, cw)
        rb = max(int(np.nonzero(s2 >= max(keep - above, 1))[0].max(
            initial=0)), 0)
        thrs.append(float(lo1 + np.float32(rb) * (cw / np.float32(nbins))))
    return np.asarray(thrs, np.float32)


@pytest.mark.parametrize("cols,nbins,density", [(512, 256, 0.1),
                                                (256, 256, 0.05),
                                                (512, 2048, 0.1),
                                                (128, 64, 0.5)])
def test_threshold_matches_histogram_oracle(cols, nbins, density):
    """The suffix-count binary search lands on exactly the bins an explicit
    histogram selects (rows not a multiple of 8, ragged last rows)."""
    rng = np.random.default_rng(3)
    arrays = [rng.normal(0, 1, 4321).astype(np.float32),
              rng.normal(0, 5, 777).astype(np.float32),
              np.where(rng.random(999) < 0.5, 0.1, 10.0).astype(np.float32)]
    buf, row_seg, row_valid, seg_count, _ = _segbuf(arrays, cols=cols)
    out = segmented_quantile_moments(buf, row_seg, row_valid, seg_count,
                                     density, n_seg=len(arrays),
                                     nbins=nbins)
    np.testing.assert_array_equal(
        np.asarray(out["threshold"]),
        _histogram_threshold_np(arrays, density, nbins))


@pytest.mark.parametrize("per_tensor", [True, False])
def test_compress_packed_matches_seed_path(per_tensor):
    """Streaming pipeline vs the seed sort-based per-leaf path: identical
    scales, same packed layout, and kept sets equal up to quantile ties."""
    rng = np.random.default_rng(7)
    tau = {"w1": jnp.asarray(rng.normal(0, 0.02, (300, 77)), jnp.float32),
           "b": jnp.asarray(rng.normal(0, 0.5, (13,)), jnp.float32),
           "w2": jnp.asarray(rng.standard_t(2, (64, 129)) * 0.1,
                             jnp.float32)}
    for density in DENSITIES:
        cfg = CompressionConfig(density=density, per_tensor=per_tensor)
        legacy = pack_tree(compress(tau, cfg))
        stream = compress_packed(tau, cfg)
        for k in tau:
            assert stream[k].shape == legacy[k].shape
            assert stream[k].pos.shape == legacy[k].pos.shape
            np.testing.assert_allclose(float(stream[k].scale),
                                       float(legacy[k].scale), rtol=1e-5)
            sl = unpack_tree({k: legacy[k]})[k].signs
            ss = unpack_tree({k: stream[k]})[k].signs
            nl = int(np.abs(np.asarray(sl)).sum())
            ns = int(np.abs(np.asarray(ss)).sum())
            # thresholds differ by < one refined bin -> at most a couple of
            # tie-adjacent elements flip in/out of the kept set
            assert abs(nl - ns) <= max(2, int(0.001 * sl.size)), (k, nl, ns)
            diff = (np.asarray(sl).reshape(-1)
                    != np.asarray(ss).reshape(-1)).sum()
            assert diff <= max(2, int(0.001 * sl.size)), (k, diff)


def test_compress_packed_roundtrip_decompress():
    rng = np.random.default_rng(8)
    tau = {"w": jnp.asarray(rng.normal(0, 0.02, (48, 64)), jnp.float32)}
    packed = compress_packed(tau, CompressionConfig(density=0.2))
    dense = decompress(unpack_tree(packed))["w"]
    vals = np.unique(np.asarray(dense))
    assert len(vals) <= 3                      # {-s, 0, +s}
    achieved = float((np.asarray(dense) != 0).mean())
    assert achieved == pytest.approx(0.2, abs=0.02)


@pytest.mark.parametrize("shape", [(6, 64, 96), (40, 2048), (3, 5, 77),
                                   (1000,)])
def test_leaf_by_leaf_matches_whole_tree(shape):
    """A one-leaf call takes the leaf's word-aligned last dim as its row
    width; the bits and thresholds equal the whole-tree call's (rows of
    ``STREAM_COLS``), and the scales agree to f32 summation order."""
    rng = np.random.default_rng(9)
    tau = {"a": jnp.asarray(rng.normal(0, 0.02, shape), jnp.float32),
           "b": jnp.asarray(rng.standard_t(3, (33, 64)), jnp.bfloat16)}
    cfg = CompressionConfig(density=0.1, per_tensor=True)
    tree, tstats = compress_packed(tau, cfg, return_stats=True)
    for i, k in enumerate(sorted(tau)):
        one, ostats = compress_packed(tau[k], cfg, return_stats=True)
        np.testing.assert_array_equal(np.asarray(one.pos),
                                      np.asarray(tree[k].pos))
        np.testing.assert_array_equal(np.asarray(one.neg),
                                      np.asarray(tree[k].neg))
        assert float(ostats["threshold"][0]) == float(tstats["threshold"][i])
        np.testing.assert_allclose(float(one.scale), float(tree[k].scale),
                                   rtol=1e-6)
        assert one.shape == tree[k].shape
        assert one.orig_dtype == tree[k].orig_dtype
