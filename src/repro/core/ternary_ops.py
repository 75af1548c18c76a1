"""Bitwise algebra on packed ternary vectors (§2.2 "Efficient Computation").

The paper: with two binary masks per vector, dot products and distances
reduce to AND/XOR + POPCNT.  On TPU, ``lax.population_count`` runs on the
VPU over uint32 lanes (32 params/lane).  These are the pure-jnp versions;
:func:`repro.kernels.ops.expert_dot` is the serving-side entry point.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.compeft import CompressedTensor
from repro.core.packing import PackedTernary, pack_ternary, unpack_ternary


def _popcount_sum(words: jax.Array) -> jax.Array:
    return jnp.sum(lax.population_count(words).astype(jnp.int32))


def ternary_dot(a: PackedTernary, b: PackedTernary) -> jax.Array:
    """<a, b> for ternary a,b (excluding scales).

    positive contributions: (a+ & b+) | (a- & b-)
    negative contributions: (a+ & b-) | (a- & b+)
    dot = popcount(pos) - popcount(neg), then * scale_a * scale_b outside.
    """
    pp = _popcount_sum(a.pos & b.pos) + _popcount_sum(a.neg & b.neg)
    pn = _popcount_sum(a.pos & b.neg) + _popcount_sum(a.neg & b.pos)
    return (pp - pn).astype(jnp.float32)


def scaled_dot(a: PackedTernary, b: PackedTernary) -> jax.Array:
    return ternary_dot(a, b) * a.scale * b.scale


def hamming_distance(a: PackedTernary, b: PackedTernary) -> jax.Array:
    """# positions where the ternary values differ (paper: XOR + POPCNT).

    sign mismatch at a position iff (a+ xor b+) or (a- xor b-) is set there.
    """
    diff = (a.pos ^ b.pos) | (a.neg ^ b.neg)
    return _popcount_sum(diff).astype(jnp.int32)


def nnz(a: PackedTernary) -> jax.Array:
    return _popcount_sum(a.pos) + _popcount_sum(a.neg)


def cosine_similarity(a: PackedTernary, b: PackedTernary) -> jax.Array:
    num = ternary_dot(a, b)
    den = jnp.sqrt(nnz(a).astype(jnp.float32)) * jnp.sqrt(nnz(b).astype(jnp.float32))
    return num / jnp.maximum(den, 1e-9)


def ternary_add(a: PackedTernary, b: PackedTernary) -> CompressedTensor:
    """a + b in the *decompressed* ternary domain (values in scale units).

    Addition leaves the ternary lattice, so the result is a dense-but-cheap
    int16 sum times a common scale; used as the merge fast path
    (Task Arithmetic adds task vectors).  Scales must be combined by the
    caller (see merging.merge_packed).
    """
    sa = unpack_ternary(a).signs.astype(jnp.int16)
    sb = unpack_ternary(b).signs.astype(jnp.int16)
    return CompressedTensor(signs=(sa + sb).astype(jnp.int8), scale=a.scale,
                            orig_dtype=a.orig_dtype)


def sign_agreement(a: PackedTernary, b: PackedTernary) -> jax.Array:
    """Fraction of mutually-nonzero positions whose signs agree (TIES stat)."""
    both = (a.pos | a.neg) & (b.pos | b.neg)
    agree = (a.pos & b.pos) | (a.neg & b.neg)
    n_both = _popcount_sum(both).astype(jnp.float32)
    return _popcount_sum(agree).astype(jnp.float32) / jnp.maximum(n_both, 1.0)


def packed_matvec(p: PackedTernary, x: jax.Array) -> jax.Array:
    """y = scale * (signs.reshape(shape) @ x) computed from packed planes.

    Reference implementation (unpack then MXU matmul) — mirrors what the
    Pallas kernel does tile-by-tile without materialising the full matrix
    in HBM.
    """
    ct = unpack_ternary(p)
    w = ct.signs.astype(x.dtype).reshape(p.shape)
    return (w @ x) * p.scale.astype(x.dtype)
