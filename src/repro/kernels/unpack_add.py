"""Pallas TPU kernel: fused ternary decompress + add (expert loading).

    W_out[M, N] = W_base[M, N] + sum_e scale[e] * (pos_e - neg_e)[M, N]

planes packed along the last dim: [E, M, ceil(N/32)] uint32 (bits >= N in
the last word must be zero — that is what the pack kernels emit).  One
pass over the base weight: HBM traffic is  base(2B) + E * 2bits  per param
instead of E full read-modify-write sweeps (base 3*2B each) of applying the
experts one at a time — the multi-expert generalisation of the paper's
Table-5 swap claim.  The expert grid dimension accumulates with a
round-trip through the output dtype per expert, so the fused result is
bit-identical to applying the experts one at a time; the single-expert
:func:`unpack_add` is the E=1 case.

Unpacking to natural column order.  Column 32w + b of the output is bit b
of word w: every word feeds 32 adjacent lanes, a lane interleave the TPU
vector unit has no instruction for.  The MXU does it exactly instead: each
byte of the word tile (0..255, exact in bf16) is spread over its word's 32
lanes by a 0/1 matrix ``R[w, c] = (c // 32 == w)``, every lane keeps the
byte that holds its bit, and a per-lane shift and mask extract it.  All
shifts and masks run on int32.
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


def spread_matrix(words: int) -> jax.Array:
    """[words, 32*words] bf16 0/1 matrix sending word w to lanes 32w..32w+31."""
    c = np.arange(words * LANE)
    return jnp.asarray(c[None, :] // LANE == np.arange(words)[:, None],
                       jnp.bfloat16)


def unpack_lanes(words, spread) -> jax.Array:
    """[BM, BW] uint32 words -> [BM, 32*BW] int32 bits in column order."""
    w = lax.bitcast_convert_type(words, jnp.int32)
    n = spread.shape[1]
    lane = lax.broadcasted_iota(jnp.int32, (1, n), 1) % LANE
    held = jnp.zeros((w.shape[0], n), jnp.int32)
    for j in range(4):                 # byte j holds bits 8j..8j+7
        byte = (lax.shift_right_logical(w, jnp.full(w.shape, 8 * j,
                                                    jnp.int32)) & 255)
        wide = jnp.dot(byte.astype(jnp.float32).astype(jnp.bfloat16), spread,
                       preferred_element_type=jnp.float32)
        held += jnp.where(lane // 8 == j, wide.astype(jnp.int32), 0)
    shift = jnp.broadcast_to(lane % 8, held.shape)
    return lax.shift_right_logical(held, shift) & 1


def _kernel_many(base_ref, pos_ref, neg_ref, scale_ref, r_ref, o_ref):
    e = pl.program_id(2)

    @pl.when(e == 0)
    def _init():
        o_ref[...] = base_ref[...]

    r = r_ref[...]
    delta = (unpack_lanes(pos_ref[0], r)
             - unpack_lanes(neg_ref[0], r)).astype(jnp.float32)
    acc = o_ref[...].astype(jnp.float32) + scale_ref[0] * delta
    o_ref[...] = acc.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "interpret"))
def unpack_add_many(base: jax.Array, pos: jax.Array, neg: jax.Array,
                    scales: jax.Array, *, bm: int = 128, bn: int = 4096,
                    interpret: bool = True) -> jax.Array:
    """Fused multi-expert merge: one sweep over base applies E experts.

    base: [M, N]; pos/neg: [E, M, ceil(N/32)] uint32 stacked planes;
    scales: [E] f32 per-expert scales.  Returns
    ``base + sum_e scales[e] * (pos_e - neg_e)`` in base.dtype, accumulated
    expert-by-expert through base.dtype so the result is bit-identical to
    looping :func:`unpack_add`.  ``bm``/``bn`` are upper bounds; edge
    blocks overhang the array instead of padding it (rows and words are
    independent), so a merge never copies the base.
    """
    M, N = base.shape
    E = pos.shape[0]
    Wn = -(-N // LANE)
    assert pos.shape == (E, M, Wn), (pos.shape, base.shape)
    assert scales.shape == (E,), scales.shape
    pad_n = Wn * LANE - N
    if pad_n:                 # ragged last word: only for N % 32 != 0
        base = jnp.pad(base, ((0, 0), (0, pad_n)))
    bm = sublane_block(bm, M)
    bw = lane_block(max(bn // LANE, 1), Wn)
    out = pl.pallas_call(
        _kernel_many,
        grid=(pl.cdiv(M, bm), pl.cdiv(Wn, bw), E),
        in_specs=[
            pl.BlockSpec((bm, bw * LANE), lambda i, j, e: (i, j)),
            pl.BlockSpec((1, bm, bw), lambda i, j, e: (e, i, j)),
            pl.BlockSpec((1, bm, bw), lambda i, j, e: (e, i, j)),
            pl.BlockSpec((1, 1, 1), lambda i, j, e: (e, 0, 0)),
            pl.BlockSpec((bw, bw * LANE), lambda i, j, e: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bw * LANE), lambda i, j, e: (i, j)),
        out_shape=jax.ShapeDtypeStruct(base.shape, base.dtype),
        # i/j tiles independent; e accumulates into the output block
        compiler_params=tpu_compiler_params(
            ("parallel", "parallel", "arbitrary"), interpret=interpret),
        cost_estimate=streaming_cost(
            M * Wn * LANE,
            in_bytes_per_elem=base.dtype.itemsize + 0.25 * E,
            out_bytes_per_elem=float(base.dtype.itemsize)),
        interpret=interpret,
    )(base, pos, neg, scales.reshape(-1, 1, 1).astype(jnp.float32),
      spread_matrix(bw))
    return out[:, :N] if pad_n else out


def unpack_add(base: jax.Array, pos: jax.Array, neg: jax.Array,
               scale: jax.Array, *, bm: int = 128, bn: int = 4096,
               interpret: bool = True) -> jax.Array:
    """base: [M, N]; pos/neg: [M, ceil(N/32)] uint32; scale scalar.  Returns
    base + scale*(pos-neg) in base.dtype (the one-expert merge)."""
    return unpack_add_many(base, pos[None], neg[None],
                           jnp.reshape(scale, (1,)), bm=bm, bn=bn,
                           interpret=interpret)
