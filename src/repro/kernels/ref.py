"""Pure-jnp oracles for every Pallas kernel (the allclose references)."""

from __future__ import annotations

import jax
import jax.numpy as jnp

LANE = 32


def _unpack(words: jax.Array, n_last: int) -> jax.Array:
    """[..., W] uint32 -> [..., W*32] int32 in {0,1}, truncated to n_last."""
    shifts = jnp.arange(LANE, dtype=jnp.uint32)
    bits = (words[..., None] >> shifts) & jnp.uint32(1)
    return bits.reshape(words.shape[:-1] + (-1,))[..., :n_last].astype(
        jnp.int32)


def dense_of_planes(pos: jax.Array, neg: jax.Array, n: int) -> jax.Array:
    """[M, W] planes -> [M, n] float ternary matrix."""
    return (_unpack(pos, n) - _unpack(neg, n)).astype(jnp.float32)


def ternary_matmul_ref(x, pos, neg, scale):
    K = x.shape[1]
    N = pos.shape[1] * LANE
    w = dense_of_planes(pos, neg, N)            # [K, N]
    return (x.astype(jnp.float32) @ w) * scale


def unpack_add_ref(base, pos, neg, scale):
    M, N = base.shape
    delta = dense_of_planes(pos, neg, N)
    return (base.astype(jnp.float32) + scale * delta).astype(base.dtype)


def pack_ternary_planes_ref(tau, thr):
    t = tau.astype(jnp.float32)
    keep = jnp.abs(t) >= thr
    M, N = t.shape
    padn = (-N) % LANE
    posm = jnp.pad((keep & (t > 0)).astype(jnp.uint32), ((0, 0), (0, padn)))
    negm = jnp.pad((keep & (t < 0)).astype(jnp.uint32), ((0, 0), (0, padn)))
    w = (jnp.uint32(1) << jnp.arange(LANE, dtype=jnp.uint32))
    pos = jnp.sum(posm.reshape(M, -1, LANE) * w, axis=-1, dtype=jnp.uint32)
    neg = jnp.sum(negm.reshape(M, -1, LANE) * w, axis=-1, dtype=jnp.uint32)
    return pos, neg


def unpack_add_many_ref(base, pos, neg, scales):
    """Loop of unpack_add_ref — the bit-exact oracle for the fused
    multi-expert merge (round-trips through base.dtype per expert)."""
    out = base
    for e in range(pos.shape[0]):
        out = unpack_add_ref(out, pos[e], neg[e], scales[e])
    return out


def ternary_matmul_grouped_ref(x, pos, neg, scales, expert_idx,
                               transpose_rhs: bool = False, n_out=None):
    """Per-row-expert delta: y[m] = scales[e(m)] * (x[m] @ T_{e(m)}).

    pos/neg: [E, K, N//32] ([E, N, ceil(K/32)] when ``transpose_rhs``).
    Rows with expert_idx == -1 get a zero delta.  Mirrors the grouped
    kernel's accumulation order (per-expert masked matmuls, scale last) so
    mixed-batch rows are bitwise what a single-expert run produces.
    """
    E = pos.shape[0]
    x32 = x.astype(jnp.float32)
    M, K = x32.shape
    if transpose_rhs:
        N = pos.shape[1]
        n_dense = K
    else:
        N = pos.shape[2] * LANE if n_out is None else n_out
        n_dense = pos.shape[2] * LANE
    acc = jnp.zeros((M, N), jnp.float32)
    eid = expert_idx.astype(jnp.int32)[:, None]
    for e in range(E):
        w = dense_of_planes(pos[e], neg[e], n_dense)
        if transpose_rhs:
            w = w.T                                   # [K, N]
        sel = (eid == e).astype(jnp.float32)
        acc += jnp.dot(x32 * sel, w[:, :N])
    srow = jnp.zeros((M, 1), jnp.float32)
    for e in range(E):
        srow += jnp.where(eid == e, scales[e].astype(jnp.float32), 0.0)
    return acc * srow
