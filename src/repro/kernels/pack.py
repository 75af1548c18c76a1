"""Pallas TPU kernel: fused threshold + sign + bitplane pack (compression).

Given a task-vector tile and the pre-computed top-k magnitude threshold,
emit the two uint32 bitplanes in one pass:

    keep = |tau| >= thr
    pos_bits = pack(keep & (tau > 0));  neg_bits = pack(keep & (tau < 0))

Two entry points:

* :func:`pack_ternary_planes` — one tensor, one scalar threshold (the seed
  per-leaf path and the unit-test surface);
* :func:`pack_ternary_planes_segmented` — the streaming-compression fast
  path: a single launch over the flat ``[R, C]`` segment buffer holding
  *all* leaves of a pytree, with a per-row threshold vector (each row
  belongs to exactly one leaf, so a per-row threshold is a per-leaf
  threshold).  This is what turns N python-level compress calls into one
  batched kernel.

Thresholds come from :mod:`repro.kernels.histogram_quantile` — O(n), no
sort; the kernels here are the bandwidth-bound part writing 2 bits/param.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl

from repro.kernels.tpu_params import (lane_block, streaming_cost,
                                      sublane_block, tpu_compiler_params)

LANE = 32


def gather_matrix(words: int) -> jax.Array:
    """[32*words, words] bf16 matrix ``G[c, w] = 2**(c % 8)`` where
    ``c // 32 == w``: contracting a 0/1 byte lane group with it sums the
    group into that byte's value (at most 255, exact in f32)."""
    c = np.arange(words * LANE)
    hit = c[:, None] // LANE == np.arange(words)[None, :]
    return jnp.asarray(np.where(hit, 2.0 ** (c[:, None] % 8), 0.0),
                       jnp.bfloat16)


def pack_lanes(keep, gather) -> jax.Array:
    """[BM, 32*BW] bool -> [BM, BW] uint32 words (bit b of word w is column
    32w + b).  The lane compaction runs on the MXU one byte at a time:
    the bits of byte j are weighted 1..128 and summed per word by
    ``gather``; bytes are then shifted into place on int32."""
    lane = lax.broadcasted_iota(jnp.int32, (1, keep.shape[1]), 1) % LANE
    word = jnp.zeros((keep.shape[0], gather.shape[1]), jnp.int32)
    for j in range(4):
        bits = jnp.where(keep & (lane // 8 == j), 1.0, 0.0)
        byte = jnp.dot(bits.astype(jnp.bfloat16), gather,
                       preferred_element_type=jnp.float32).astype(jnp.int32)
        word = word | lax.shift_left(byte, jnp.full(byte.shape, 8 * j,
                                                    jnp.int32))
    return lax.bitcast_convert_type(word, jnp.uint32)


def _kernel_rows(tau_ref, thr_ref, g_ref, pos_ref, neg_ref):
    t = tau_ref[...].astype(jnp.float32)               # [BM, BN]
    keep = jnp.abs(t) >= thr_ref[...]                   # thr [BM, 1]
    g = g_ref[...]
    pos_ref[...] = pack_lanes(keep & (t > 0), g)
    neg_ref[...] = pack_lanes(keep & (t < 0), g)


def pack_ternary_planes(tau: jax.Array, thr: jax.Array, *, bm: int = 128,
                        bn: int = 4096, interpret: bool = True):
    """tau: [M, N] float; thr: scalar f32.  Returns (pos, neg) uint32
    [M, ceil(N/32)] planes (zero bits in padding)."""
    M, N = tau.shape
    pad_n = (-N) % LANE
    if pad_n:
        tau = jnp.pad(tau, ((0, 0), (0, pad_n)))
    thr_rows = jnp.broadcast_to(jnp.asarray(thr, jnp.float32), (M,))
    return pack_ternary_planes_segmented(tau, thr_rows, bm=bm, bn=bn,
                                         interpret=interpret)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "interpret"))
def pack_ternary_planes_segmented(tau: jax.Array, thr_rows: jax.Array, *,
                                  bm: int = 128, bn: int = 4096,
                                  interpret: bool = True):
    """Batched pack over a segment buffer: tau [R, C] (C % 32 == 0),
    thr_rows [R] f32 per-row thresholds.  One launch for a whole pytree.

    Returns (pos, neg) uint32 [R, C//32].  Padding rows pack to zero words
    as long as their elements are zero and their threshold is > 0 — zeros
    never set a bit in either plane regardless of the threshold.  Edge
    blocks overhang the buffer (rows and words are independent), so
    nothing is padded.
    """
    R, C = tau.shape
    assert C % LANE == 0, C
    W = C // LANE
    bm = sublane_block(bm, R)
    bw = lane_block(max(bn // LANE, 1), W)
    grid = (pl.cdiv(R, bm), pl.cdiv(W, bw))
    pos, neg = pl.pallas_call(
        _kernel_rows,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bw * LANE), lambda i, j: (i, j)),
            pl.BlockSpec((bm, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((bw * LANE, bw), lambda i, j: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bm, bw), lambda i, j: (i, j)),
            pl.BlockSpec((bm, bw), lambda i, j: (i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((R, W), jnp.uint32),
            jax.ShapeDtypeStruct((R, W), jnp.uint32),
        ],
        compiler_params=tpu_compiler_params(("parallel", "parallel"),
                                            interpret=interpret),
        cost_estimate=streaming_cost(R * C, in_bytes_per_elem=4.0,
                                     out_bytes_per_elem=0.25),
        interpret=interpret,
    )(tau.astype(jnp.float32), thr_rows.reshape(-1, 1).astype(jnp.float32),
      gather_matrix(bw))
    return pos, neg


def pack_ternary_planes_segmented_ref(tau, thr_rows):
    """Vectorised jnp mirror of the segmented kernel (CPU fast path)."""
    t = tau.astype(jnp.float32)
    thr = thr_rows.astype(jnp.float32)[:, None]
    keep = jnp.abs(t) >= thr
    R, C = t.shape
    w = (jnp.uint32(1) << jnp.arange(LANE, dtype=jnp.uint32))
    posm = (keep & (t > 0)).astype(jnp.uint32).reshape(R, C // LANE, LANE)
    negm = (keep & (t < 0)).astype(jnp.uint32).reshape(R, C // LANE, LANE)
    return (jnp.sum(posm * w, axis=-1, dtype=jnp.uint32),
            jnp.sum(negm * w, axis=-1, dtype=jnp.uint32))
