"""ComPEFT (Algorithm 1): sparsify + ternary-quantize task vectors.

The paper's core contribution.  Given a task vector ``tau = theta_ft -
theta_init`` (a pytree of arrays), ComPEFT:

  1. decomposes ``tau`` into sign ``gamma = sgn(tau)`` and magnitude
     ``mu = |tau|``;
  2. keeps the signs of the top-``k`` fraction of entries by magnitude and
     zeroes the rest (``density = k``);
  3. replaces all surviving magnitudes with one scalar ``alpha * std(tau)``.

Everything here is pure JAX and jit-friendly.  Compression granularity is
configurable: per-tensor (default, matches the paper's per-module treatment)
or global (one threshold across the whole pytree).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

PyTree = Any


@dataclasses.dataclass(frozen=True)
class CompressionConfig:
    """Hyper-parameters of Algorithm 1.

    Attributes:
      density: fraction ``k`` of entries whose sign survives (paper sweeps
        {0.05, 0.1, 0.2, 0.3, 0.5}).
      alpha: scaling multiplier on ``std(tau)`` (paper sweeps
        {0.5, 1, 2, 3, 4, 5, 6, 8, 10}; alpha=1 recommended for >=13B).
      per_tensor: if True, top-k threshold and sigma are computed per leaf;
        if False, once over the concatenated vector (global).
      scale_mode: 'std' (paper), 'mean_abs' (STC-style, used by baselines),
        or 'none'.
    """

    density: float = 0.05
    alpha: float = 1.0
    per_tensor: bool = True
    scale_mode: str = "std"

    def __post_init__(self):
        if not (0.0 < self.density <= 1.0):
            raise ValueError(f"density must be in (0, 1], got {self.density}")
        if self.alpha <= 0.0:
            raise ValueError(f"alpha must be > 0, got {self.alpha}")
        if self.scale_mode not in ("std", "mean_abs", "none"):
            raise ValueError(f"unknown scale_mode {self.scale_mode!r}")


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class CompressedTensor:
    """One ComPEFT-compressed leaf: a ternary sign tensor and one scalar.

    ``signs`` is stored as int8 in {-1, 0, +1}; ``scale`` is the f32 scalar
    ``alpha * sigma(tau)``.  ``shape``/``dtype`` record the original leaf so
    decompression is exact.  The *packed* (bitplane) representation lives in
    :mod:`repro.core.packing`; this object is the device-compute-friendly
    form.
    """

    signs: jax.Array  # int8, original shape
    scale: jax.Array  # f32 scalar
    orig_dtype: Any = dataclasses.field(default=jnp.bfloat16)

    def tree_flatten(self):
        return (self.signs, self.scale), (self.orig_dtype,)

    @classmethod
    def tree_unflatten(cls, aux, children):
        signs, scale = children
        return cls(signs=signs, scale=scale, orig_dtype=aux[0])

    @property
    def shape(self):
        return self.signs.shape

    @property
    def density(self):
        return jnp.mean(jnp.abs(self.signs).astype(jnp.float32))

    def decompress(self) -> jax.Array:
        return (self.signs.astype(jnp.float32) * self.scale).astype(self.orig_dtype)


def _topk_threshold(mag: jax.Array, density: float) -> jax.Array:
    """Magnitude cut-off such that ~density fraction of entries survive.

    Uses a quantile over the flattened magnitudes.  ``jnp.quantile`` is a
    sort-based exact implementation — fine for compression which runs once
    per expert, not per step.
    """
    q = jnp.clip(1.0 - density, 0.0, 1.0)
    return jnp.quantile(mag.reshape(-1).astype(jnp.float32), q)


def _scale_of(tau: jax.Array, mode: str) -> jax.Array:
    t = tau.astype(jnp.float32)
    if mode == "std":
        return jnp.std(t)
    if mode == "mean_abs":
        return jnp.mean(jnp.abs(t))
    return jnp.asarray(1.0, jnp.float32)


def compress_leaf(tau: jax.Array, cfg: CompressionConfig,
                  threshold: jax.Array | None = None,
                  scale: jax.Array | None = None) -> CompressedTensor:
    """Algorithm 1 on a single array."""
    mag = jnp.abs(tau.astype(jnp.float32))
    thr = _topk_threshold(mag, cfg.density) if threshold is None else threshold
    keep = mag >= thr
    signs = jnp.where(keep, jnp.sign(tau.astype(jnp.float32)), 0.0).astype(jnp.int8)
    sigma = _scale_of(tau, cfg.scale_mode) if scale is None else scale
    return CompressedTensor(
        signs=signs,
        scale=jnp.asarray(cfg.alpha, jnp.float32) * sigma,
        orig_dtype=tau.dtype,
    )


def compress(tau: PyTree, cfg: CompressionConfig | None = None) -> PyTree:
    """Apply Algorithm 1 over a pytree of task-vector leaves.

    Returns a pytree with the same structure whose leaves are
    :class:`CompressedTensor`.
    """
    cfg = cfg or CompressionConfig()
    leaves, treedef = jax.tree_util.tree_flatten(tau)
    if cfg.per_tensor:
        out = [compress_leaf(l, cfg) for l in leaves]
    else:
        flat = jnp.concatenate([l.reshape(-1).astype(jnp.float32) for l in leaves])
        thr = _topk_threshold(jnp.abs(flat), cfg.density)
        sigma = _scale_of(flat, cfg.scale_mode)
        out = [compress_leaf(l, cfg, threshold=thr, scale=sigma) for l in leaves]
    return jax.tree_util.tree_unflatten(treedef, out)


def decompress(compressed: PyTree) -> PyTree:
    """Inverse map back to dense task-vector leaves."""
    return jax.tree_util.tree_map(
        lambda c: c.decompress(),
        compressed,
        is_leaf=lambda x: isinstance(x, CompressedTensor),
    )


def apply_compressed(theta_init: PyTree, compressed: PyTree) -> PyTree:
    """Reconstruct expert parameters: ``theta = theta_init + tau_tilde``."""
    return jax.tree_util.tree_map(
        lambda w, c: (w.astype(jnp.float32)
                      + c.signs.astype(jnp.float32) * c.scale).astype(w.dtype),
        theta_init,
        compressed,
        is_leaf=lambda x: isinstance(x, CompressedTensor),
    )


# ---------------------------------------------------------------------------
# Streaming compression: one batched pass over all leaves (perf fast path)
# ---------------------------------------------------------------------------

STREAM_COLS = 8192  # segment-buffer row width; multiple of the pack kernel's
                    # 32-bit lane and of its default 512-column block


def _segment_cols(leaves) -> int:
    """Row width of the segment buffer.

    A one-leaf call whose last dim is a whole number of 32-bit words keeps
    that dim as the row width (the rule ``ops._merge_view`` applies to
    merges): the buffer is then the leaf with its leading dims merged, and
    on a TPU that view keeps the tiled layout, where ``[R, STREAM_COLS]``
    would cost a full-leaf relayout copy (3.25 GB for a 3B model's f32
    FFN stack).  The planes are packed over the flat C-order leaf, so the
    bits do not depend on the row width.  Other calls use ``STREAM_COLS``.
    """
    from repro.core.packing import LANE
    if len(leaves) == 1:
        shape = leaves[0].shape
        if len(shape) >= 2 and shape[-1] % LANE == 0:
            return int(shape[-1])
    return STREAM_COLS


def _segment_layout(shapes, cols: int):
    """Host-side layout of the segment buffer over leaves of ``shapes``.

    Each leaf is padded to a whole number of rows so every row belongs to
    exactly one leaf (segment); that is what lets one kernel launch carry
    per-leaf thresholds as a per-row vector.  Returns the row->segment
    map, per-row valid counts, per-segment element counts and each leaf's
    (row_start, row_end).
    """
    row_seg, row_valid, counts, spans = [], [], [], []
    r = 0
    for i, shape in enumerate(shapes):
        n = int(np.prod(shape))
        rows = -(-n // cols)
        row_seg.append(np.full(rows, i, np.int32))
        valid = np.full(rows, cols, np.int32)
        valid[-1] = n - (rows - 1) * cols
        row_valid.append(valid)
        counts.append(n)
        spans.append((r, r + rows))
        r += rows
    return (np.concatenate(row_seg), np.concatenate(row_valid),
            np.asarray(counts, np.int32), tuple(spans))


def _segment_rows(leaves, cols: int):
    """The ``[R, cols]`` f32 segment buffer of ``leaves`` (traceable).
    Inside one compiled program a leaf that needs no padding is a view:
    its leading dims merge, and with ``cols`` its own last dim the TPU's
    tiled layout is unchanged."""
    chunks = []
    for leaf in leaves:
        n = int(np.prod(leaf.shape))
        rows = -(-n // cols)
        x = leaf.astype(jnp.float32)
        if rows * cols != n:
            x = jnp.pad(x.reshape(-1), (0, rows * cols - n))
        chunks.append(x.reshape(rows, cols))
    return jnp.concatenate(chunks) if len(chunks) > 1 else chunks[0]


def _build_segment_buffer(leaves, cols: int):
    """Segment buffer plus its layout (see :func:`_segment_layout`)."""
    row_seg, row_valid, seg_count, spans = _segment_layout(
        [l.shape for l in leaves], cols)
    return (_segment_rows(leaves, cols), jnp.asarray(row_seg),
            jnp.asarray(row_valid), jnp.asarray(seg_count), spans)


@functools.partial(jax.jit,
                   static_argnames=("cols", "spans", "n_seg", "interpret"))
def _stream_compress(leaves, seg_ids, row_valid, seg_count, keep, *,
                     cols: int, spans, n_seg: int, interpret: bool):
    """Segment buffer -> histogram threshold + moments -> packed planes, as
    one program, so the buffer is never an eager copy of the leaves.
    Returns the per-segment stats and each leaf's flat (pos, neg) words."""
    from repro.core.packing import LANE
    from repro.kernels.histogram_quantile import NBINS, _quantile_moments
    from repro.kernels.pack import (pack_ternary_planes_segmented,
                                    pack_ternary_planes_segmented_ref)

    buf = _segment_rows(leaves, cols)
    stats = _quantile_moments(buf, seg_ids, row_valid, seg_count, keep,
                              n_seg=n_seg, nbins=NBINS)
    thr_rows = stats["threshold"][seg_ids]
    if interpret:   # vectorised jnp mirror: same math, no interpreter tax
        pos, neg = pack_ternary_planes_segmented_ref(buf, thr_rows)
    else:
        pos, neg = pack_ternary_planes_segmented(buf, thr_rows,
                                                 interpret=False)
    planes = []
    for leaf, (r0, r1) in zip(leaves, spans):
        nw = -(-int(np.prod(leaf.shape)) // LANE)
        planes.append((pos[r0:r1].reshape(-1)[:nw],
                       neg[r0:r1].reshape(-1)[:nw]))
    return stats, planes


def compress_packed(tau: PyTree, cfg: CompressionConfig | None = None, *,
                    return_stats: bool = False) -> PyTree:
    """Algorithm 1 straight to packed bitplanes, in one streaming pipeline.

    Replaces the per-leaf ``jnp.quantile`` + sign + pack loop (one sort and
    ~5 dispatches per leaf) with: (1) a two-pass O(n) histogram quantile
    over a single segment buffer holding every leaf, which also yields the
    std/mean_abs scale for free, and (2) one batched threshold+sign+pack
    launch with per-row thresholds.  Returns a pytree of
    :class:`~repro.core.packing.PackedTernary` (2 bits/param), the format
    the serving cache keeps resident and the merge kernels consume.

    :func:`_segment_cols` picks the segment buffer's row width.  Under
    ``per_tensor=True`` compressing a tree one leaf at a time keeps the
    same thresholds and bits (scales may differ in the last f32 ulp, from
    the summation order), and a call then holds only that leaf's f32
    buffer.
    """
    from repro.core.packing import PackedTernary
    from repro.kernels.histogram_quantile import keep_counts
    from repro.kernels.ops import INTERPRET

    cfg = cfg or CompressionConfig()
    leaves, treedef = jax.tree_util.tree_flatten(tau)
    if not leaves:
        return jax.tree_util.tree_unflatten(treedef, [])
    cols = _segment_cols(leaves)
    row_seg, row_valid, seg_count, spans = _segment_layout(
        [l.shape for l in leaves], cols)

    if cfg.per_tensor:
        n_seg, seg_ids = len(leaves), row_seg
    else:       # one global threshold/scale over the concatenated vector
        n_seg, seg_ids = 1, np.zeros_like(row_seg)
        seg_count = np.sum(seg_count, keepdims=True).astype(np.int32)
    stats, planes = _stream_compress(
        tuple(leaves), jnp.asarray(seg_ids), jnp.asarray(row_valid),
        jnp.asarray(seg_count), jnp.asarray(keep_counts(seg_count,
                                                        cfg.density)),
        cols=cols, spans=spans, n_seg=n_seg, interpret=INTERPRET)

    if cfg.scale_mode == "std":
        sigma = stats["std"]
    elif cfg.scale_mode == "mean_abs":
        sigma = stats["mean_abs"]
    else:
        sigma = jnp.ones((n_seg,), jnp.float32)
    scales = jnp.asarray(cfg.alpha, jnp.float32) * sigma

    out = [PackedTernary(pos=pos, neg=neg,
                         scale=scales[i if cfg.per_tensor else 0],
                         shape=tuple(leaf.shape), orig_dtype=leaf.dtype)
           for i, (leaf, (pos, neg)) in enumerate(zip(leaves, planes))]
    packed = jax.tree_util.tree_unflatten(treedef, out)
    if return_stats:
        return packed, stats
    return packed


# ---------------------------------------------------------------------------
# Alpha calibration (§2.1: "alpha is the only parameter tuned")
# ---------------------------------------------------------------------------

ALPHA_GRID = (0.5, 1.0, 2.0, 3.0, 4.0, 5.0, 6.0, 8.0, 10.0)
DENSITY_GRID = (0.05, 0.1, 0.2, 0.3, 0.5)


def rescale(compressed: PyTree, old_alpha: float, new_alpha: float) -> PyTree:
    """Cheaply retarget a compressed tree to a different alpha (scales only)."""
    r = new_alpha / old_alpha

    def f(c: CompressedTensor) -> CompressedTensor:
        return CompressedTensor(signs=c.signs, scale=c.scale * r,
                                orig_dtype=c.orig_dtype)

    return jax.tree_util.tree_map(
        f, compressed, is_leaf=lambda x: isinstance(x, CompressedTensor))


def calibrate_alpha(
    tau: PyTree,
    eval_fn: Callable[[PyTree], float],
    density: float,
    alpha_grid: tuple[float, ...] = ALPHA_GRID,
    per_tensor: bool = True,
) -> tuple[float, float, PyTree]:
    """Grid-search alpha on a validation metric (higher is better).

    ``eval_fn`` maps a *reconstructed task vector* (dense pytree) to a score.
    Signs/threshold are computed once; only the scalar is swept — this is
    exactly the cheap knob the paper exploits.

    Returns (best_alpha, best_score, best_compressed_tree).
    """
    base = compress(tau, CompressionConfig(density=density, alpha=1.0,
                                           per_tensor=per_tensor))
    best = (None, -np.inf, None)
    for a in alpha_grid:
        cand = rescale(base, 1.0, a)
        score = float(eval_fn(decompress(cand)))
        if score > best[1]:
            best = (a, score, cand)
    return best


def compression_summary(tau: PyTree, compressed: PyTree) -> dict:
    """Diagnostics: density achieved, reconstruction stats, bit accounting."""
    from repro.core import packing  # local import to avoid cycle

    taus = jax.tree_util.tree_leaves(tau)
    comps = jax.tree_util.tree_leaves(
        compressed, is_leaf=lambda x: isinstance(x, CompressedTensor))
    n = sum(int(np.prod(t.shape)) for t in taus)
    nnz = sum(int(jnp.sum(jnp.abs(c.signs).astype(jnp.int32))) for c in comps)
    dense_bits = 16 * n
    ent_bits = sum(
        packing.entropy_bits(int(np.prod(c.shape)),
                             float(jnp.mean(jnp.abs(c.signs).astype(jnp.float32))))
        for c in comps)
    bitplane_bits = sum(2 * int(np.prod(c.shape)) + 16 for c in comps)
    err = 0.0
    for t, c in zip(taus, comps):
        d = c.decompress().astype(jnp.float32) - t.astype(jnp.float32)
        err += float(jnp.sum(d * d))
    norm = sum(float(jnp.sum(t.astype(jnp.float32) ** 2)) for t in taus)
    return {
        "n_params": n,
        "nnz": nnz,
        "density": nnz / max(n, 1),
        "dense_bits": dense_bits,
        "entropy_bits": ent_bits,
        "bitplane_bits": bitplane_bits,
        "compression_x_entropy": dense_bits / max(ent_bits, 1e-9),
        "compression_x_bitplane": dense_bits / max(bitplane_bits, 1),
        "rel_recon_err": float(np.sqrt(err / max(norm, 1e-30))),
    }
