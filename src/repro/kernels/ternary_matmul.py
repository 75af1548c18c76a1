"""Pallas TPU kernels: dense × packed-ternary matmul.

Grouped (per-row-expert) form — the zero-merge serving hot path:

    y[m, :] = scale[e(m)] * ( x[m, :] @ T_{e(m)} )

with E experts' planes stacked as [E, K, N//32] (two uint32 bitplanes
packed along the *output* dim, C-order of a [K, N] weight) and a per-row
``expert_idx`` vector.  One launch contracts a decode batch that mixes
experts against all resident ternary deltas; the caller adds
``x @ W_base`` (the base weights are never re-materialised per expert, and
the experts are never merged).  ``transpose_rhs=True`` takes planes packed
along the *contraction* dim ([N, K//32], e.g. an embedding table reused as
a tied LM head) and computes ``x @ T^t`` without repacking.  The
single-expert :func:`ternary_matmul` is the E=1 case.

TPU adaptation of the paper's §2.2 "binary vector" computation: the ternary
delta streams HBM→VMEM at 2 bits/param (16x less bandwidth than bf16), is
unpacked to ±1 tiles in-register, and contracts on the MXU.

Bit order.  Bit b of plane word w is column 32w + b.  Spreading each
word's 32 bits over 32 adjacent lanes is a lane interleave the TPU has no
cheap instruction for, so the kernels never build the natural-order tile.
Instead bit b of a whole [rows, words] tile is one lane-dense ±1 slab
(shift + mask on int32), and the interleave moves to the small operand:

* planes packed along N: slab b holds output columns {32w + b}; the
  kernel computes them as slab @ x^T (the plane tile streams through the
  MXU against the small x tile) into a bit-major [32, N//32, M] result
  that the wrapper transposes back (an activation, not the planes);
* planes packed along K (``transpose_rhs``): slab b contracts against
  x columns {32w + b}, so the wrapper feeds x bit-major as [32, M, K//32]
  and the kernel sums x_b @ slab_b over the 32 bits.

The stored plane layout is the wire layout; nothing is rearranged per
step.  Grid: (M/BM, N/BN, K/BK) with K innermost, accumulating in the VMEM
output block; blocks follow the (8, 128) tiling rules of
:mod:`repro.kernels.tpu_params`.

Expert skip.  A row tile runs the unpack and dots of only the experts it
has rows of (:func:`tile_presence`; their count and ids reach the kernel
by scalar prefetch): a prefill tile lies inside one request, so it does
one expert's work whatever E is, while a decode tile that mixes every
expert runs the same E-expert loop as without the skip.  A skipped term
was an exact zero, so every row's result is unchanged.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tpu_params import (divisor_block, grouped_matmul_cost,
                                      lane_block, sublane_block,
                                      tpu_compiler_params)

LANE = 32
_HI = lax.Precision.HIGHEST


def plane_signs(pos_words, neg_words, b) -> jax.Array:
    """Bit ``b`` of a uint32 plane-word tile pair as an f32 ±1/0 slab.

    Shifts and masks run on int32 (the planes are bitcast, not converted),
    which is what the TPU vector unit lowers."""
    p = lax.bitcast_convert_type(pos_words, jnp.int32)
    n = lax.bitcast_convert_type(neg_words, jnp.int32)
    sh = jnp.full(p.shape, b, jnp.int32)
    d = (lax.shift_right_logical(p, sh) & 1) - (lax.shift_right_logical(n, sh)
                                                & 1)
    return d.astype(jnp.float32)


def _row_scale(eid, scales_ref, n_e: int):
    """Per-row scale ``scales[e(m)]`` (0 for rows outside [0, E))."""
    s = jnp.zeros(eid.shape, jnp.float32)
    for e in range(n_e):
        s += jnp.where(eid == e, scales_ref[e:e + 1, :], 0.0)
    return s


def tile_presence(eid, bm: int, n_e: int, xp=jnp):
    """``present[i, e]``: 1 when row tile ``i`` (rows ``i*bm`` up to
    ``(i+1)*bm``) holds a row of expert ``e``, else 0; -1 rows and the
    padding of a last partial tile hold none.  int32 [ceil(M/bm), n_e].

    The grouped kernels run an expert's unpack and dots in a row tile only
    where this reads 1 (the skipped terms are exact zeros).  ``xp`` is
    ``jnp`` in the kernel wrappers and ``numpy`` where the serving engine
    counts the skipped passes on the host."""
    eid = xp.pad(eid, (0, (-eid.shape[0]) % bm), constant_values=-1)
    hit = eid.reshape(-1, bm, 1) == xp.arange(n_e, dtype=eid.dtype)
    return hit.any(axis=1).astype(xp.int32)


def row_block(m: int, bm: int = 128) -> int:
    """The grouped kernel's row block for ``m`` rows under the bound
    ``bm``: the same in both plane forms for the default bound (whole
    when ``m <= bm``, else ``bm``)."""
    return lane_block(bm, m)


def tile_schedule(eid, bm: int, n_e: int) -> jax.Array:
    """Per row tile, the count of its present experts and their ids in
    ascending order (padding after them): int32 [tiles, 1 + n_e],
    flattened for scalar prefetch."""
    present = tile_presence(eid, bm, n_e)
    ids = jnp.argsort(1 - present, axis=1, stable=True)
    return jnp.concatenate([present.sum(axis=1, keepdims=True), ids],
                           axis=1).astype(jnp.int32).reshape(-1)


def _for_present_experts(sched_ref, n_e: int, body) -> None:
    """``body(es)`` once per grid step, ``es`` the experts with a row in
    the step's row tile (:func:`tile_schedule`, in SMEM): one unrolled
    specialisation per count, so a tile of all E runs the E-expert loop
    with static ids and a tile of one expert runs a one-expert loop."""
    row = pl.program_id(0) * (n_e + 1)
    for c in range(1, n_e + 1):
        es = (list(range(n_e)) if c == n_e else
              [sched_ref[row + 1 + s] for s in range(c)])
        pl.when(sched_ref[row] == c)(functools.partial(body, es))


def _kernel_cols(sched_ref, xt_ref, pos_ref, neg_ref, scales_ref, eid_ref,
                 o_ref, *, n_k: int, n_e: int):
    """Planes packed along N, word-major.  xt [BK, BM] (x transposed);
    planes [E, BW, BK]; eid [1, BM]; o [32, BW, BM] (bit-major y^T)."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    eid = eid_ref[...]

    def experts(es):
        xt = xt_ref[...].astype(jnp.float32)
        xs = [xt * (eid == e).astype(jnp.float32) for e in es]

        def bit(b, carry):
            acc = jnp.zeros(o_ref.shape[1:], jnp.float32)
            for e, x in zip(es, xs):        # unrolled over the tile's experts
                t = plane_signs(pos_ref[e], neg_ref[e], b)    # [BW, BK]
                acc += jnp.dot(t, x, precision=_HI,
                               preferred_element_type=jnp.float32)
            o_ref[b] += acc
            return carry

        lax.fori_loop(0, LANE, bit, 0)

    _for_present_experts(sched_ref, n_e, experts)

    @pl.when(k == n_k - 1)
    def _scale():
        o_ref[...] *= _row_scale(eid, scales_ref, n_e)[None]


def _kernel_rows(sched_ref, x_ref, pos_ref, neg_ref, scales_ref, eid_ref,
                 o_ref, *, n_k: int, n_e: int):
    """Planes packed along K, word-major.  x [32, BM, BW] (bit-major);
    planes [E, BW, BN]; eid [BM, 1]; o [BM, BN]."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    eid = eid_ref[...]

    def experts(es):
        sel = [(eid == e).astype(jnp.float32) for e in es]

        def bit(b, carry):
            xb = x_ref[b].astype(jnp.float32)               # [BM, BW]
            acc = jnp.zeros(o_ref.shape, jnp.float32)
            for e, s in zip(es, sel):
                t = plane_signs(pos_ref[e], neg_ref[e], b)  # [BW, BN]
                acc += jnp.dot(xb * s, t, precision=_HI,
                               preferred_element_type=jnp.float32)
            o_ref[...] += acc
            return carry

        lax.fori_loop(0, LANE, bit, 0)

    _for_present_experts(sched_ref, n_e, experts)

    @pl.when(k == n_k - 1)
    def _scale():
        o_ref[...] *= _row_scale(eid, scales_ref, n_e)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret",
                                             "transpose_rhs"))
def ternary_matmul_grouped(x: jax.Array, pos: jax.Array, neg: jax.Array,
                           scales: jax.Array, expert_idx: jax.Array, *,
                           transpose_rhs: bool = False, bm: int = 128,
                           bn: int = 1024, bk: int = 512,
                           interpret: bool = True) -> jax.Array:
    """Per-row-expert delta contraction over stacked planes, one launch.

    x: [M, K] float; pos/neg: [E, K, N//32] uint32 ([E, N, ceil(K/32)] when
    ``transpose_rhs``); scales: [E] f32; expert_idx: [M] int32 in [0, E)
    (-1 rows get a zero delta).  Returns [M, N] f32 with
    ``y[m] = scales[expert_idx[m]] * (x[m] @ T_{expert_idx[m]})``.

    ``bm``/``bn``/``bk`` are upper bounds in elements; the kernel picks the
    legal TPU blocks under them (a contraction block always divides K).
    Rows and output columns are independent, so mixed-expert rows are
    bitwise what single-expert runs produce.

    The kernel reads the planes word-major ([E, words, K] / [E, words, N]).
    A TPU stores a plane stack with a narrow word dimension in exactly that
    order (the word dim is not the minor one in its default layout), so the
    swap below is a relabelling, not a copy.
    """
    M, K = x.shape
    E = pos.shape[0]
    assert scales.shape == (E,), scales.shape
    assert expert_idx.shape == (M,), (expert_idx.shape, M)
    eid = expert_idx.astype(jnp.int32)
    scales2 = scales.reshape(E, 1).astype(jnp.float32)
    pos_w, neg_w = jnp.swapaxes(pos, 1, 2), jnp.swapaxes(neg, 1, 2)
    if transpose_rhs:
        return _grouped_rows(x, pos_w, neg_w, scales2, eid, bm=bm, bn=bn,
                             bk=bk, interpret=interpret)
    return _grouped_cols(x, pos_w, neg_w, scales2, eid, bm=bm, bn=bn, bk=bk,
                         interpret=interpret)


def _grouped_cols(x, pos, neg, scales2, eid, *, bm, bn, bk, interpret):
    """pos/neg word-major [E, N//32, K]."""
    M, K = x.shape
    E, Wn, Kp = pos.shape
    assert Kp == K, (pos.shape, K)
    bm = lane_block(bm, M)
    bk = divisor_block(bk, K)
    bw = sublane_block(max(bn // LANE, 1), Wn)
    pad_m = (-M) % bm
    if pad_m:                          # activations only; planes never copy
        x = jnp.pad(x, ((0, pad_m), (0, 0)))
        eid = jnp.pad(eid, (0, pad_m), constant_values=-1)
    Mp = M + pad_m
    n_k = K // bk
    out = pl.pallas_call(
        functools.partial(_kernel_cols, n_k=n_k, n_e=E),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(Mp // bm, pl.cdiv(Wn, bw), n_k),
            in_specs=[
                pl.BlockSpec((bk, bm), lambda i, j, k, _: (k, i)),
                pl.BlockSpec((E, bw, bk), lambda i, j, k, _: (0, j, k)),
                pl.BlockSpec((E, bw, bk), lambda i, j, k, _: (0, j, k)),
                pl.BlockSpec((E, 1), lambda i, j, k, _: (0, 0)),
                pl.BlockSpec((1, bm), lambda i, j, k, _: (0, i)),
            ],
            out_specs=pl.BlockSpec((LANE, bw, bm),
                                   lambda i, j, k, _: (0, j, i))),
        out_shape=jax.ShapeDtypeStruct((LANE, Wn, Mp), jnp.float32),
        # i/j tiles are independent; k accumulates into the output block
        compiler_params=tpu_compiler_params(
            ("parallel", "parallel", "arbitrary"), interpret=interpret),
        cost_estimate=grouped_matmul_cost(Mp, Wn * LANE, K, E,
                                          elem_bytes=x.dtype.itemsize),
        interpret=interpret,
    )(tile_schedule(eid, bm, E), x.T, pos, neg, scales2,
      eid.reshape(1, -1))
    # o[b, w, m] = y[m, 32w + b]
    return jnp.transpose(out, (2, 1, 0)).reshape(Mp, Wn * LANE)[:M]


def _grouped_rows(x, pos, neg, scales2, eid, *, bm, bn, bk, interpret):
    """pos/neg word-major [E, ceil(K/32), N]."""
    M, K = x.shape
    E, Wk, N = pos.shape
    assert Wk == -(-K // LANE), (pos.shape, K)
    bm = sublane_block(bm, M)
    bn = lane_block(bn, N)
    bw = divisor_block(max(bk // LANE, 1), Wk)
    pad_m = (-M) % bm
    x = jnp.pad(x, ((0, pad_m), (0, Wk * LANE - K)))
    if pad_m:
        eid = jnp.pad(eid, (0, pad_m), constant_values=-1)
    Mp = M + pad_m
    # x[m, 32w + b] -> xb[b, m, w]: bit-major (activations only)
    xb = jnp.transpose(x.reshape(Mp, Wk, LANE), (2, 0, 1))
    n_k = Wk // bw
    out = pl.pallas_call(
        functools.partial(_kernel_rows, n_k=n_k, n_e=E),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(Mp // bm, pl.cdiv(N, bn), n_k),
            in_specs=[
                pl.BlockSpec((LANE, bm, bw), lambda i, j, k, _: (0, i, k)),
                pl.BlockSpec((E, bw, bn), lambda i, j, k, _: (0, k, j)),
                pl.BlockSpec((E, bw, bn), lambda i, j, k, _: (0, k, j)),
                pl.BlockSpec((E, 1), lambda i, j, k, _: (0, 0)),
                pl.BlockSpec((bm, 1), lambda i, j, k, _: (i, 0)),
            ],
            out_specs=pl.BlockSpec((bm, bn), lambda i, j, k, _: (i, j))),
        out_shape=jax.ShapeDtypeStruct((Mp, N), jnp.float32),
        compiler_params=tpu_compiler_params(
            ("parallel", "parallel", "arbitrary"), interpret=interpret),
        cost_estimate=grouped_matmul_cost(Mp, N, Wk * LANE, E,
                                          elem_bytes=x.dtype.itemsize),
        interpret=interpret,
    )(tile_schedule(eid, bm, E), xb, pos, neg, scales2,
      eid.reshape(-1, 1))
    return out[:M]


def ternary_matmul(x: jax.Array, pos: jax.Array, neg: jax.Array,
                   scale: jax.Array, *, bm: int = 128, bn: int = 1024,
                   bk: int = 512, interpret: bool = True) -> jax.Array:
    """x: [M, K] float; pos/neg: [K, N//32] uint32; scale: scalar f32.
    Returns ``scale * (x @ T)`` as [M, N] f32 — the one-expert grouped
    kernel."""
    M = x.shape[0]
    return ternary_matmul_grouped(
        x, pos[None], neg[None], jnp.reshape(scale, (1,)),
        jnp.zeros((M,), jnp.int32), bm=bm, bn=bn, bk=bk,
        interpret=interpret)
