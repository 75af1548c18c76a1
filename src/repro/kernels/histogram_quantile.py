"""O(n) streaming threshold for Algorithm 1: histogram quantile + moments.

The seed compression path computed the top-k magnitude cut-off with a
sort-based ``jnp.quantile`` per leaf (O(n log n), one dispatch per leaf) and
then re-read the data for ``std``.  This module replaces it with a two-pass
segmented histogram scheme over a single flat buffer holding *all* leaves of
a pytree:

  pass 1 (coarse)  — 2048 bins of |tau| per segment over ``[0, max_s]``;
  pass 2 (refine)  — 2048 sub-bins inside the coarse bin that contains the
                     k-th largest magnitude.

The returned threshold is the lower edge of the refined bin holding the
k-th order statistic, so it is within ``max_s / 2048^2`` of the exact
quantile and — crucially for Algorithm 1 — ``|x| >= thr`` keeps the same
top-k set as the exact threshold for every distribution, including ties.

How a pass finds its bin.  The scheme needs only the histogram's suffix
counts: ``S(b)`` = how many in-range magnitudes fall in bin ``b`` or
above, and the bin holding the k-th largest is the last ``b`` with
``S(b) >= k``.  ``S`` is monotone, so each pass finds that bin by an
11-step binary search, one fused compare-and-count reduction over the
buffer per step.  No histogram array, no scatter over elements and no
per-element bin-index array is ever built, so memory stays at the buffer
itself (a 3.25 GB f32 leaf compresses on one 16 GB chip) and every step is
a bandwidth-bound sweep.  The same jnp code runs on every platform.  The
moments (sum, sum of squares, sum |x|, max) take one more sweep.

Layout contract (shared with :func:`repro.core.compeft.compress_packed`):
leaves are flattened C-order, each padded to a multiple of ``cols`` so a
row belongs to exactly one segment; ``row_seg[r]`` maps rows to segments
and ``row_valid[r]`` counts non-padding elements in row ``r``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

NBINS = 2048


def _seg_sum(rows, row_seg, n_seg: int):
    return jax.ops.segment_sum(rows, row_seg, num_segments=n_seg)


def _suffix_count(buf, valid, row_seg, lo, width, cand, *, n_seg: int,
                  nbins: int):
    """Per segment: in-range magnitudes whose bin is >= ``cand`` [S].

    Bins are ``(|x| - lo_s) * (nbins / width_s)`` floored and clipped to
    [0, nbins-1]; in range means ``lo_s <= |x| <= lo_s + width_s``.  For
    ``0 <= cand < nbins`` the clipped bin is >= cand exactly when the
    unfloored position is, so no bin index is materialised."""
    mag = jnp.abs(buf.astype(jnp.float32))
    w = jnp.maximum(width, 1e-30)
    lo_r = lo[row_seg][:, None]
    pos = (mag - lo_r) * (nbins / w)[row_seg][:, None]
    hit = (valid & (mag >= lo_r) & (mag <= lo_r + w[row_seg][:, None])
           & (pos >= cand.astype(jnp.float32)[row_seg][:, None]))
    return _seg_sum(jnp.sum(hit, axis=1, dtype=jnp.int32), row_seg, n_seg)


def _last_bin_reaching(count, keep, *, n_seg: int, nbins: int):
    """Largest bin b with ``count(b) >= keep`` per segment (0 when none):
    binary search over the monotone suffix count."""
    ans = jnp.zeros((n_seg,), jnp.int32)
    for bit in reversed(range((nbins - 1).bit_length())):
        cand = ans + (1 << bit)
        ok = (cand < nbins) & (count(jnp.minimum(cand, nbins - 1)) >= keep)
        ans = jnp.where(ok, cand, ans)
    return ans


@functools.partial(jax.jit, static_argnames=("n_seg", "nbins"))
def _quantile_moments(buf, row_seg, row_valid, seg_count, keep, *,
                      n_seg: int, nbins: int):
    R, C = buf.shape
    x = buf.astype(jnp.float32)
    valid = (jax.lax.broadcasted_iota(jnp.int32, (R, C), 1)
             < row_valid[:, None])
    xm = jnp.where(valid, x, 0.0)
    magm = jnp.abs(xm)
    smax = jax.ops.segment_max(jnp.max(magm, axis=1), row_seg,
                               num_segments=n_seg)
    smax = jnp.maximum(smax, 0.0)                  # empty segments -> 0
    ssum = _seg_sum(jnp.sum(xm, axis=1), row_seg, n_seg)
    ssq = _seg_sum(jnp.sum(xm * xm, axis=1), row_seg, n_seg)
    sabs = _seg_sum(jnp.sum(magm, axis=1), row_seg, n_seg)

    count = functools.partial(_suffix_count, buf, valid, row_seg,
                              n_seg=n_seg, nbins=nbins)
    search = functools.partial(_last_bin_reaching, n_seg=n_seg, nbins=nbins)

    zeros = jnp.zeros((n_seg,), jnp.float32)
    cb = search(lambda c: count(zeros, smax, c), keep)          # coarse bin
    cw = jnp.maximum(smax, 1e-30) / nbins
    lo1 = cb.astype(jnp.float32) * cw
    # rank of the target inside the selected coarse bin
    above = jnp.where(cb + 1 < nbins,
                      count(zeros, smax, jnp.minimum(cb + 1, nbins - 1)), 0)
    keep_in_bin = jnp.maximum(keep - above, 1)
    rb = search(lambda c: count(lo1, cw, c), keep_in_bin)       # refined bin
    thr = jnp.where(smax > 0.0, lo1 + rb.astype(jnp.float32) * (cw / nbins),
                    0.0)

    n = jnp.maximum(seg_count.astype(jnp.float32), 1.0)
    mean = ssum / n
    var = jnp.maximum(ssq / n - mean * mean, 0.0)
    return {"threshold": thr, "mean": mean, "std": jnp.sqrt(var),
            "mean_abs": sabs / n, "max": smax, "sum": ssum, "sumsq": ssq,
            "keep": keep}


def segmented_quantile_moments(buf, row_seg, row_valid, seg_count, density,
                               *, n_seg: int, nbins: int = NBINS):
    """Two-pass histogram threshold + moments over a segment buffer.

    Args:
      buf:       [R, C] f32 flat segment buffer (padding rows/cols zeroed).
      row_seg:   [R] int32 row -> segment id.
      row_valid: [R] int32 valid element count per row.
      seg_count: [S] int32 total element count per segment.
      density:   fraction of entries to keep (Algorithm 1 ``k``).

    Returns dict with per-segment f32 vectors: ``threshold``, ``mean``,
    ``std``, ``mean_abs``, ``max`` — everything Algorithm 1 needs — plus
    ``sum``, ``sumsq`` and the int32 ``keep`` count.  Counts are int32, so
    one segment holds at most 2**31 - 1 elements.
    """
    return _quantile_moments(buf, jnp.asarray(row_seg, jnp.int32),
                             jnp.asarray(row_valid, jnp.int32),
                             jnp.asarray(seg_count, jnp.int32),
                             jnp.asarray(keep_counts(seg_count, density)),
                             n_seg=n_seg, nbins=nbins)


def keep_counts(seg_count, density) -> np.ndarray:
    """Algorithm 1's ``k`` per segment: round(n * density), at least 1."""
    n = np.asarray(seg_count, np.float64)
    return np.maximum(np.round(n * density), 1.0).astype(np.int32)
