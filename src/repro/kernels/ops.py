"""Public entry points for the Pallas kernels.  On a TPU they compile the
Pallas kernels; off-TPU the bandwidth-bound serving ops route to their
vectorised jnp mirrors in :mod:`repro.kernels.ref` (same math, no
interpreter tax).  Interpret-mode Pallas stays test-only.  ``INTERPRET``
records which of the two this process runs: it is False exactly when JAX's
default backend is a TPU."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.packing import PackedTernary
from repro.kernels import ref
from repro.kernels.pack import pack_ternary_planes
from repro.kernels.ternary_matmul import ternary_matmul, ternary_matmul_grouped
from repro.kernels.unpack_add import unpack_add, unpack_add_many

INTERPRET = jax.default_backend() != "tpu"

_unpack_add_ref = jax.jit(ref.unpack_add_ref)
_unpack_add_many_ref = jax.jit(ref.unpack_add_many_ref)
_ternary_matmul_ref = jax.jit(ref.ternary_matmul_ref)
_grouped_ref = jax.jit(ref.ternary_matmul_grouped_ref,
                       static_argnames=("transpose_rhs", "n_out"))


def _fused_unpack_add(base, pos, neg, scale):
    if INTERPRET:
        return _unpack_add_ref(base, pos, neg, scale)
    return unpack_add(base, pos, neg, scale, interpret=False)


def _fused_unpack_add_many(base, pos, neg, scales):
    if INTERPRET:
        return _unpack_add_many_ref(base, pos, neg, scales)
    return unpack_add_many(base, pos, neg, scales, interpret=False)


def apply_ternary_delta(base: jax.Array, pt: PackedTernary) -> jax.Array:
    """Expert loading: base [M, N] + decompressed delta, fused."""
    M, N = base.shape
    pos = pt.pos.reshape(M, -1)
    neg = pt.neg.reshape(M, -1)
    return _fused_unpack_add(base, pos, neg, pt.scale)


MERGE_COLS = 4096  # flat-view row width for leaves with a ragged last dim


def _merge_rows(arr: jax.Array, n: int, rows: int, cols: int):
    """``arr`` (the leaf, or its flat plane words) as ``[rows, cols]``,
    zero-padded at the end when the view is larger than ``n``."""
    if rows * cols == n:
        return arr.reshape(rows, cols)
    flat = arr.reshape(-1)
    pad = jnp.zeros((rows * cols - n,), flat.dtype)
    return jnp.concatenate([flat, pad]).reshape(rows, cols)


def _merge_view(base: jax.Array):
    """Row geometry for a fused merge of one leaf: (n, rows, cols).

    A leaf whose last dim is a whole number of 32-bit words keeps it as
    the row width, so the planes split into rows of whole words and only
    leading dims merge — on a TPU that leaves the tiled layout unchanged,
    where a flat view would cost a full-leaf relayout copy on the way in
    and another on the way out.  Other leaves use a zero-padded flat
    ``[R, MERGE_COLS]`` view."""
    LANE = 32
    n = int(np.prod(base.shape))
    if base.ndim >= 2 and base.shape[-1] % LANE == 0:
        return n, n // base.shape[-1], base.shape[-1]
    cols = min(MERGE_COLS, ((n + LANE - 1) // LANE) * LANE)
    return n, -(-n // cols), cols


def _merged_leaf(out, base, n, rows, cols):
    if rows * cols == n:
        return out.reshape(base.shape)
    return out.reshape(-1)[:n].reshape(base.shape)


def apply_ternary_delta_flat(base: jax.Array, pt: PackedTernary) -> jax.Array:
    """Rank-agnostic fused merge: base (any shape) + scale * (pos - neg).

    The planes are bit-packed over the *flattened* C-order tensor, so the
    merge views both operands as ``[rows, cols]`` with ``cols`` a whole
    number of words (:func:`_merge_view`) and runs the same
    bandwidth-bound unpack_add math.  This is the packed-resident swap
    path: HBM traffic is base + 2 bits/param, no dense delta is ever
    materialised.
    """
    return apply_ternary_delta_many_flat(base, [pt])


@jax.jit
def apply_ternary_delta_many_flat(base: jax.Array, pts, weights=None
                                  ) -> jax.Array:
    """Fused multi-expert merge of one leaf: base + sum_e w_e*scale_e*Δ_e.

    ``pts`` is a sequence of PackedTernary over the same leaf shape;
    ``weights`` (optional, len E) are the merged-ensemble mixing
    coefficients α_e.  One sweep over base instead of E round-trips —
    bit-identical to looping :func:`apply_ternary_delta_flat` with the
    scaled deltas.  One compiled program per leaf shape, so the row views
    are free relabellings and the merged leaf is the only new buffer.
    """
    LANE = 32
    n, rows, cols = _merge_view(base)
    nw = -(-n // LANE)
    words = cols // LANE
    pos = jnp.stack([_merge_rows(pt.pos, nw, rows, words) for pt in pts])
    neg = jnp.stack([_merge_rows(pt.neg, nw, rows, words) for pt in pts])
    scales = jnp.stack([pt.scale.astype(jnp.float32) for pt in pts])
    if weights is not None:
        scales = scales * jnp.asarray(weights, jnp.float32)
    out = _fused_unpack_add_many(_merge_rows(base, n, rows, cols), pos, neg,
                                 scales)
    return _merged_leaf(out, base, n, rows, cols)


def ternary_matvec(x: jax.Array, pt: PackedTernary) -> jax.Array:
    """y = x @ (scale * ternary[K, N]) without materialising the matrix."""
    K, N = pt.shape
    pos = pt.pos.reshape(K, -1)
    neg = pt.neg.reshape(K, -1)
    squeeze = x.ndim == 1
    x2 = x[None] if squeeze else x
    if INTERPRET:
        y = _ternary_matmul_ref(x2, pos, neg, pt.scale)[:, :N]
    else:
        y = ternary_matmul(x2, pos, neg, pt.scale, interpret=False)[:, :N]
    return y[0] if squeeze else y


def _grouped(x, pos, neg, scales, expert_idx, transpose_rhs):
    if INTERPRET:
        return _grouped_ref(x, pos, neg, scales, expert_idx,
                            transpose_rhs=transpose_rhs)
    return ternary_matmul_grouped(x, pos, neg, scales, expert_idx,
                                  transpose_rhs=transpose_rhs,
                                  interpret=False)


def expert_parallel(fn, mesh):
    """Run ``fn(data, eid, pos, neg, scales)`` once per ``expert`` shard.

    ``data`` and the per-row expert ids ``eid`` are replicated; ``pos``,
    ``neg`` and ``scales`` are ``[E, ...]`` stacks sharded along
    ``expert``.  Each shard renumbers the ids into its own block of the
    stack (-1 for rows served elsewhere, which ``fn`` maps to an exact
    zero), and the shards' results are summed: one nonzero term per row,
    so the sum is exact and matches the one-device result bitwise.  A
    Pallas kernel cannot be partitioned by the compiler, so this is how it
    runs on a serving mesh."""
    from jax.sharding import PartitionSpec as P

    def local(data, eid, pos, neg, scales):
        lo = jax.lax.axis_index("expert") * pos.shape[0]
        mine = (eid >= lo) & (eid < lo + pos.shape[0])
        y = fn(data, jnp.where(mine, eid - lo, -1), pos, neg, scales)
        return jax.lax.psum(y, "expert")

    stack = P("expert")
    return jax.shard_map(local, mesh=mesh,
                         in_specs=(P(), P(), stack, stack, stack),
                         out_specs=P(), check_vma=False)


def grouped_delta_matmul(x: jax.Array, pos: jax.Array, neg: jax.Array,
                         scales: jax.Array, expert_idx: jax.Array, *,
                         transpose_rhs: bool = False,
                         n_out: int | None = None, mesh=None) -> jax.Array:
    """Zero-merge hot path: per-row-expert delta contraction.

    x: [M, K]; pos/neg: stacked [E, K, N//32] ([E, N, ceil(K/32)] when
    ``transpose_rhs``); scales [E]; expert_idx [M] int32 (-1 → zero delta).
    Returns the f32 delta [M, N] to add onto ``x @ W_base``.  With a
    serving ``mesh`` the stacks are expert-parallel and each shard
    contracts its own experts (:func:`expert_parallel`).
    """
    def run(x, eid, pos, neg, scales):
        return _grouped(x, pos, neg, scales, eid, transpose_rhs)

    if mesh is not None:
        run = expert_parallel(run, mesh)
    y = run(x, expert_idx.astype(jnp.int32), pos, neg, scales)
    return y if n_out is None else y[:, :n_out]


def compress_to_planes(tau: jax.Array, thr: jax.Array):
    """Fused threshold+sign+pack for a [M, N] task-vector leaf."""
    return pack_ternary_planes(tau, thr, interpret=INTERPRET)


def expert_dot(a: PackedTernary, b: PackedTernary) -> jax.Array:
    """Scaled ternary dot via AND+POPCNT (one fused XLA reduction)."""
    from repro.core.ternary_ops import scaled_dot
    return scaled_dot(a, b)
