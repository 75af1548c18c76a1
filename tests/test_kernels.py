"""Per-kernel validation: Pallas (interpret=True) vs pure-jnp ref across a
shape/dtype sweep, plus hypothesis property tests and integration with the
PackedTernary container."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
    HAVE_HYPOTHESIS = True
except ModuleNotFoundError:  # optional dev dep; fall back to a seed sweep
    HAVE_HYPOTHESIS = False

from repro.core import CompressionConfig, compress, pack_ternary
from repro.core.compeft import CompressedTensor
from repro.kernels import ops, ref
from repro.kernels.pack import pack_ternary_planes
from repro.kernels.ternary_matmul import (ternary_matmul,
                                          ternary_matmul_grouped,
                                          tile_presence)
from repro.kernels.unpack_add import unpack_add, unpack_add_many

LANE = 32


def rand_planes(key, m, n):
    rng = np.random.default_rng(key)
    assert n % LANE == 0
    pos = rng.integers(0, 2 ** 32, (m, n // LANE), dtype=np.uint32)
    neg = rng.integers(0, 2 ** 32, (m, n // LANE), dtype=np.uint32)
    neg = neg & ~pos  # disjoint
    return jnp.asarray(pos), jnp.asarray(neg)


def rand_plane_stack(key, e, m, n):
    ps, ns = zip(*[rand_planes(key + 17 * i, m, n) for i in range(e)])
    return jnp.stack(ps), jnp.stack(ns)


MATMUL_CASES = [
    # (M, K, N, bm, bk, bn)
    (8, 32, 32, 8, 32, 32),
    (16, 64, 128, 8, 32, 64),
    (1, 128, 96, 1, 64, 32),
    (33, 96, 64, 16, 32, 64),    # padding on every dim
    (128, 128, 128, 128, 128, 128),
]


@pytest.mark.parametrize("M,K,N,bm,bk,bn", MATMUL_CASES)
def test_ternary_matmul_matches_ref(M, K, N, bm, bk, bn):
    pos, neg = rand_planes(0, K, N)
    x = jnp.asarray(np.random.default_rng(1).normal(0, 1, (M, K)),
                    jnp.float32)
    scale = jnp.float32(0.37)
    got = ternary_matmul(x, pos, neg, scale, bm=bm, bk=bk, bn=bn,
                         interpret=True)
    want = ref.ternary_matmul_ref(x, pos, neg, scale)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5,
                               atol=1e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ternary_matmul_dtypes(dtype):
    pos, neg = rand_planes(2, 64, 64)
    x = jnp.asarray(np.random.default_rng(3).normal(0, 1, (8, 64)), dtype)
    got = ternary_matmul(x, pos, neg, jnp.float32(1.0), bm=8, bk=32, bn=32,
                         interpret=True)
    want = ref.ternary_matmul_ref(x.astype(jnp.float32), pos, neg, 1.0)
    tol = 5e-2 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=tol,
                               atol=tol)


UNPACK_CASES = [(8, 32, 8, 32), (32, 128, 16, 64), (17, 96, 8, 64),
                (256, 512, 256, 512)]


@pytest.mark.parametrize("M,N,bm,bn", UNPACK_CASES)
def test_unpack_add_matches_ref(M, N, bm, bn):
    pos, neg = rand_planes(4, M, N)
    base = jnp.asarray(np.random.default_rng(5).normal(0, 1, (M, N)),
                       jnp.bfloat16)
    got = unpack_add(base, pos, neg, jnp.float32(0.25), bm=bm, bn=bn,
                     interpret=True)
    want = ref.unpack_add_ref(base, pos, neg, jnp.float32(0.25))
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), rtol=1e-2,
                               atol=1e-2)
    assert got.dtype == base.dtype


@pytest.mark.parametrize("M,N,bm,bn", [(8, 64, 8, 64), (30, 100, 16, 64),
                                       (256, 512, 128, 256)])
def test_pack_matches_ref(M, N, bm, bn):
    tau = jnp.asarray(np.random.default_rng(6).normal(0, 1, (M, N)),
                      jnp.float32)
    thr = jnp.float32(1.0)
    gp, gn = pack_ternary_planes(tau, thr, bm=bm, bn=bn, interpret=True)
    wp, wn = ref.pack_ternary_planes_ref(tau, thr)
    np.testing.assert_array_equal(np.asarray(gp), np.asarray(wp))
    np.testing.assert_array_equal(np.asarray(gn), np.asarray(wn))


def test_pack_then_matmul_roundtrip():
    """compress -> kernel-pack -> kernel-matmul == dense delta matmul."""
    rng = np.random.default_rng(7)
    K, N, M = 64, 96, 4
    tau = jnp.asarray(rng.normal(0, 0.02, (K, N)), jnp.float32)
    thr = jnp.quantile(jnp.abs(tau), 0.8)
    pos, neg = ops.compress_to_planes(tau, thr)
    x = jnp.asarray(rng.normal(0, 1, (M, K)), jnp.float32)
    scale = jnp.float32(0.01)
    got = ternary_matmul(x, pos, neg, scale, bm=4, bk=32, bn=32,
                         interpret=True)
    dense = jnp.where(jnp.abs(tau) >= thr, jnp.sign(tau), 0.0) * scale
    want = x @ dense
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def _expert_dot_property(seed):
    rng = np.random.default_rng(seed)
    n = int(rng.integers(1, 40)) * LANE - int(rng.integers(0, LANE))
    a = CompressedTensor(signs=jnp.asarray(rng.integers(-1, 2, (n,)),
                                           jnp.int8),
                         scale=jnp.float32(rng.normal()))
    b = CompressedTensor(signs=jnp.asarray(rng.integers(-1, 2, (n,)),
                                           jnp.int8),
                         scale=jnp.float32(rng.normal()))
    got = float(ops.expert_dot(pack_ternary(a), pack_ternary(b)))
    want = float(np.dot(np.asarray(a.signs, np.int64),
                        np.asarray(b.signs, np.int64))) \
        * float(a.scale) * float(b.scale)
    assert got == pytest.approx(want, rel=1e-6, abs=1e-6)


if HAVE_HYPOTHESIS:
    @settings(max_examples=20, deadline=None)
    @given(st.integers(1, 8))
    def test_expert_dot_property(seed):
        _expert_dot_property(seed)
else:
    @pytest.mark.parametrize("seed", range(1, 9))
    def test_expert_dot_property(seed):
        _expert_dot_property(seed)


# ---------------------------------------------------------------------------
# Batched kernels (PR 2): stacked-plane variants must be bit-identical to
# looping the single-expert kernels
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("M,N,E,bm,bn", [(8, 64, 1, 8, 64),
                                         (17, 96, 3, 8, 64),
                                         (33, 160, 5, 16, 96)])
def test_unpack_add_many_bit_identical_to_loop(M, N, E, bm, bn):
    pos, neg = rand_plane_stack(10, E, M, N)
    base = jnp.asarray(np.random.default_rng(11).normal(0, 1, (M, N)),
                       jnp.bfloat16)
    scales = jnp.asarray(np.random.default_rng(12).normal(0, 0.3, E),
                         jnp.float32)
    got = unpack_add_many(base, pos, neg, scales, bm=bm, bn=bn,
                          interpret=True)
    want = base
    for e in range(E):
        want = unpack_add(want, pos[e], neg[e], scales[e], bm=bm, bn=bn,
                          interpret=True)
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))
    # jnp mirror used by the CPU serve path agrees too
    np.testing.assert_array_equal(
        np.asarray(ref.unpack_add_many_ref(base, pos, neg, scales),
                   np.float32),
        np.asarray(want, np.float32))


def test_unpack_add_many_ragged_expert_set():
    """Zero planes + zero scale slots (experts missing a leaf) are no-ops."""
    M, N, E = 16, 64, 3
    pos, neg = rand_plane_stack(13, E, M, N)
    z = jnp.zeros_like(pos[0])
    pos = pos.at[1].set(z)
    neg = neg.at[1].set(z)
    scales = jnp.asarray([0.5, 0.0, -0.25], jnp.float32)
    base = jnp.asarray(np.random.default_rng(14).normal(0, 1, (M, N)),
                       jnp.float32)
    got = unpack_add_many(base, pos, neg, scales, bm=8, bn=64, interpret=True)
    two = unpack_add(base, pos[0], neg[0], scales[0], bm=8, bn=64,
                     interpret=True)
    two = unpack_add(two, pos[2], neg[2], scales[2], bm=8, bn=64,
                     interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(two))


def test_unpack_add_small_shape_regression():
    """N < LANE (and N % LANE != 0) used to break the bn % LANE assert."""
    for M, N in [(5, 16), (8, 40), (3, 1)]:
        n_words = -(-N // LANE)
        pos, neg = rand_planes(20 + N, M, n_words * LANE)
        mask = ((1 << (N % LANE)) - 1) if N % LANE else 0xFFFFFFFF
        pos = pos.at[:, -1].set(pos[:, -1] & jnp.uint32(mask))
        neg = neg.at[:, -1].set(neg[:, -1] & jnp.uint32(mask))
        base = jnp.asarray(np.random.default_rng(21).normal(0, 1, (M, N)),
                           jnp.float32)
        got = unpack_add(base, pos, neg, jnp.float32(0.5), interpret=True)
        want = ref.unpack_add_ref(base, pos, neg, jnp.float32(0.5))
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_ternary_matmul_small_bn_regression():
    """A non-LANE-multiple bn is clamped, not asserted on."""
    pos, neg = rand_planes(22, 64, 32)
    x = jnp.asarray(np.random.default_rng(23).normal(0, 1, (4, 64)),
                    jnp.float32)
    got = ternary_matmul(x, pos, neg, jnp.float32(1.0), bm=4, bk=32, bn=48,
                         interpret=True)
    want = ref.ternary_matmul_ref(x, pos, neg, 1.0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def tiled_eid(M, E, bm, seed):
    """Per-row expert ids whose row tiles of ``bm`` rows hold, in turn, one
    expert's rows (the experts in turn, from the last), only -1 rows, and a
    mix of -1 with experts 1..E-1 (0 alone when E == 1): the layouts the
    kernel's per-tile expert skip sees, with present ids that differ from
    the first ones of the stack."""
    rng = np.random.default_rng(seed)
    mixed = [-1, *range(min(1, E - 1), E)]
    eid = np.empty(M, np.int32)
    for t, lo in enumerate(range(0, M, bm)):
        n = min(bm, M - lo)
        kind = t % 3
        eid[lo:lo + n] = (E - 1 - (t // 3) % E if kind == 0
                          else -1 if kind == 1 else rng.choice(mixed, n))
    return jnp.asarray(eid)


def integer_x(M, K, seed):
    """Integer-valued activations: every partial sum of x @ T is exact in
    f32, so any summation order gives the same bits and the kernel can be
    held bitwise to the oracle."""
    return jnp.asarray(np.random.default_rng(seed).integers(-3, 4, (M, K)),
                       jnp.float32)


def single_expert_rows(x, pos, neg, scales, eid, transpose=False, **kw):
    """Each row from a one-expert run of its own expert's planes (the
    single-expert kernel, or the E=1 grouped kernel for ``transpose``),
    zero for -1 rows."""
    M = x.shape[0]
    if transpose:
        per = [ternary_matmul_grouped(x, pos[e:e + 1], neg[e:e + 1],
                                      scales[e:e + 1],
                                      jnp.zeros((M,), jnp.int32),
                                      transpose_rhs=True, **kw)
               for e in range(pos.shape[0])]
    else:
        per = [ternary_matmul(x, pos[e], neg[e], scales[e], **kw)
               for e in range(pos.shape[0])]
    per = np.stack([np.asarray(p) for p in per])
    eid = np.asarray(eid)
    return np.where((eid >= 0)[:, None], per[np.maximum(eid, 0),
                                             np.arange(M)], 0.0)


def planes_for(key, E, K, N, transpose):
    """A plane stack in the kernel's form: [E, K, N//32], or [E, N, K//32]
    for ``transpose`` (K a multiple of 32)."""
    return (rand_plane_stack(key, E, N, K) if transpose
            else rand_plane_stack(key, E, K, N))


@pytest.mark.parametrize("M,K,N,E,layout,transpose", [
    pytest.param(8, 32, 32, 1, "random", False, id="8-32-32-1"),
    pytest.param(13, 96, 64, 3, "random", False, id="13-96-64-3"),
    pytest.param(33, 64, 128, 4, "random", False, id="33-64-128-4"),
    # row tiles of one expert, of -1 rows and mixed; M % bm != 0
    (300, 64, 128, 3, "tiles", False),
    (43, 64, 128, 3, "tiles", True),
    (300, 64, 64, 1, "tiles", False),
    (20, 64, 64, 1, "tiles", True),
])
def test_grouped_matmul_bit_identical_to_single(M, K, N, E, layout,
                                                transpose):
    """Row-wise, the grouped kernel == the single-expert kernel run per
    expert (same block shapes) with rows selected by expert id; where row
    tiles skip the experts they lack, also bitwise the oracle."""
    pos, neg = planes_for(30, E, K, N, transpose)
    scales = jnp.asarray(np.random.default_rng(32).normal(0, 0.5, E),
                         jnp.float32)
    if layout == "random":
        x = jnp.asarray(np.random.default_rng(31).normal(0, 1, (M, K)),
                        jnp.float32)
        eid = jnp.asarray(np.random.default_rng(33).integers(0, E, M),
                          jnp.int32)
    else:
        # row tiles: 8 rows in the rows form; a lane block (128) in the
        # columns form, where rows are the lane dim of x^T
        x = integer_x(M, K, 31)
        eid = tiled_eid(M, E, 8 if transpose else 128, 33)
    kw = dict(bm=8, bk=32, bn=32, interpret=True)
    got = np.asarray(ternary_matmul_grouped(x, pos, neg, scales, eid,
                                            transpose_rhs=transpose, **kw))
    np.testing.assert_array_equal(
        got, single_expert_rows(x, pos, neg, scales, eid, transpose, **kw))
    if layout == "tiles":
        np.testing.assert_array_equal(got, np.asarray(
            ref.ternary_matmul_grouped_ref(x, pos, neg, scales, eid,
                                           transpose_rhs=transpose)))


@pytest.mark.parametrize("eid,transpose", [
    pytest.param([0, -1, 1, -1, 0, 1, -1, 0, 1], False, id="mixed"),
    # tiles of 8: all -1, one expert, mixed, and a partial all -1 tile
    pytest.param([-1] * 8 + [1] * 8 + [0, -1, 1, -1, 0, 1, -1, 0] + [-1] * 3,
                 False, id="tiles"),
    pytest.param([-1] * 8 + [1] * 8 + [0, -1, 1, -1, 0, 1, -1, 0] + [-1] * 3,
                 True, id="tiles-transpose"),
])
def test_grouped_matmul_negative_rows_zero(eid, transpose):
    """expert_idx == -1 rows (base-only requests) get an exact zero delta;
    a tile of -1 rows skips every expert, and the other rows are bitwise
    their single-expert runs."""
    M, K, N, E = len(eid), 64, 64, 2
    pos, neg = planes_for(34, E, K, N, transpose)
    x = jnp.asarray(np.random.default_rng(35).normal(0, 1, (M, K)),
                    jnp.float32)
    eid = jnp.asarray(eid, jnp.int32)
    kw = dict(bm=8, bk=32, bn=32, interpret=True)
    scales = jnp.ones((E,), jnp.float32)
    got = np.asarray(ternary_matmul_grouped(x, pos, neg, scales, eid,
                                            transpose_rhs=transpose, **kw))
    assert np.all(got[np.asarray(eid) < 0] == 0.0)
    np.testing.assert_array_equal(
        got, single_expert_rows(x, pos, neg, scales, eid, transpose, **kw))


@pytest.mark.parametrize("xp", [np, jnp], ids=["numpy", "jnp"])
def test_tile_presence_on_known_layouts(xp):
    """The per-tile expert rule, in the kernel's jnp form and the host's
    numpy mirror."""
    one = xp.asarray(np.full(256, 1, np.int32))      # 2x128 rows, expert 1
    np.testing.assert_array_equal(tile_presence(one, 128, 3, xp=xp),
                                  [[0, 1, 0], [0, 1, 0]])
    decode = xp.asarray(np.arange(32, dtype=np.int32) % 3)
    np.testing.assert_array_equal(tile_presence(decode, 32, 3, xp=xp),
                                  [[1, 1, 1]])
    # a partial last tile pads with -1; -1 rows hold no expert
    ragged = xp.asarray(np.asarray([2] * 8 + [-1] * 8 + [0, -1], np.int32))
    np.testing.assert_array_equal(tile_presence(ragged, 8, 3, xp=xp),
                                  [[0, 0, 1], [0, 0, 0], [1, 0, 0]])


def test_grouped_matmul_transposed_matches_ref():
    """transpose_rhs consumes [E, N, ceil(K/32)] planes (tied LM head)."""
    M, K, N, E = 7, 48, 64, 3           # K not a lane multiple
    rng = np.random.default_rng(36)
    n_words = -(-K // LANE)
    ps, ns = [], []
    mask = (1 << (K % LANE)) - 1 if K % LANE else 0xFFFFFFFF
    for e in range(E):
        p, n = rand_planes(40 + e, N, n_words * LANE)
        ps.append(p.at[:, -1].set(p[:, -1] & jnp.uint32(mask)))
        ns.append(n.at[:, -1].set(n[:, -1] & jnp.uint32(mask)))
    pos, neg = jnp.stack(ps), jnp.stack(ns)
    x = jnp.asarray(rng.normal(0, 1, (M, K)), jnp.float32)
    scales = jnp.asarray(rng.normal(0, 0.5, E), jnp.float32)
    eid = jnp.asarray(rng.integers(0, E, M), jnp.int32)
    got = ternary_matmul_grouped(x, pos, neg, scales, eid,
                                 transpose_rhs=True, bm=8, bk=32, bn=32,
                                 interpret=True)
    want = ref.ternary_matmul_grouped_ref(x, pos, neg, scales, eid,
                                          transpose_rhs=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5,
                               atol=1e-5)


def test_grouped_ref_mixed_rows_equal_single_expert_runs():
    """The jnp serve-path mirror: a mixed batch is row-wise bitwise what
    single-expert batches produce (the engine's parity contract)."""
    M, K, N, E = 12, 64, 96, 3
    pos, neg = rand_plane_stack(50, E, K, N)
    x = jnp.asarray(np.random.default_rng(51).normal(0, 1, (M, K)),
                    jnp.float32)
    scales = jnp.asarray([0.3, -0.7, 1.1], jnp.float32)
    eid = jnp.asarray(np.random.default_rng(52).integers(0, E, M), jnp.int32)
    mixed = ref.ternary_matmul_grouped_ref(x, pos, neg, scales, eid)
    single = jnp.stack([
        ref.ternary_matmul_grouped_ref(x, pos[e:e + 1], neg[e:e + 1],
                                       scales[e:e + 1],
                                       jnp.zeros((M,), jnp.int32))
        for e in range(E)])
    np.testing.assert_array_equal(np.asarray(mixed),
                                  np.asarray(single[eid, jnp.arange(M)]))


def test_ops_integration_with_compressed_tensor():
    """End-to-end: Algorithm-1 compress -> pack -> kernel expert apply
    equals apply_compressed."""
    rng = np.random.default_rng(8)
    base = jnp.asarray(rng.normal(0, 1, (48, 64)), jnp.bfloat16)
    tau = {"w": jnp.asarray(rng.normal(0, 0.02, (48, 64)), jnp.float32)}
    comp = compress(tau, CompressionConfig(density=0.2))
    pt = pack_ternary(comp["w"])
    got = ops.apply_ternary_delta(base, pt)
    want = (base.astype(jnp.float32)
            + comp["w"].signs.astype(jnp.float32) * comp["w"].scale
            ).astype(jnp.bfloat16)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=1e-2)


def test_ops_expert_dot_matches_core():
    from repro.core.ternary_ops import scaled_dot
    rng = np.random.default_rng(9)
    a = CompressedTensor(signs=jnp.asarray(rng.integers(-1, 2, (128,)),
                                           jnp.int8), scale=jnp.float32(0.5))
    b = CompressedTensor(signs=jnp.asarray(rng.integers(-1, 2, (128,)),
                                           jnp.int8), scale=jnp.float32(2.0))
    pa, pb = pack_ternary(a), pack_ternary(b)
    got = float(ops.expert_dot(pa, pb))
    want = float(scaled_dot(pa, pb))
    assert got == pytest.approx(want)


# ---------------------------------------------------------------------------
# TPU-legal tiling: several blocks per grid dim, overhanging edge blocks
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("M,K,N,transpose,bm,bn,bk,layout", [
    # 200 words: 128 + edge of 72
    pytest.param(5, 512, 6400, False, 128, 4096, 256, "random",
                 id="5-512-6400-False-128-4096-256"),
    # 300 rows: 128, 128, edge 44
    pytest.param(9, 256, 300, True, 8, 128, 512, "random",
                 id="9-256-300-True-8-128-512"),
    # rows pad to 384, 2 k-steps
    pytest.param(260, 256, 96, False, 128, 4096, 128, "random",
                 id="260-256-96-False-128-4096-128"),
    # 128-row tiles of one expert, of -1 rows, and a mixed partial tile
    (300, 256, 96, False, 128, 4096, 128, "tiles"),
    (300, 256, 300, True, 128, 128, 128, "tiles"),
])
def test_grouped_matmul_edge_blocks_match_ref(M, K, N, transpose, bm, bn,
                                              bk, layout):
    E = 3
    rng = np.random.default_rng(60)
    pos, neg = planes_for(61, E, K, N, transpose)
    if layout == "random":
        x = jnp.asarray(rng.normal(0, 1, (M, K)), jnp.float32)
        scales = jnp.asarray(rng.normal(0, 0.5, E), jnp.float32)
        eid = jnp.asarray(rng.integers(-1, E, M), jnp.int32)
    else:
        x, eid = integer_x(M, K, 62), tiled_eid(M, E, bm, 63)
        scales = jnp.asarray(rng.normal(0, 0.5, E), jnp.float32)
    kw = dict(bm=bm, bn=bn, bk=bk, interpret=True)
    got = np.asarray(ternary_matmul_grouped(x, pos, neg, scales, eid,
                                            transpose_rhs=transpose, **kw))
    want = np.asarray(ref.ternary_matmul_grouped_ref(
        x, pos, neg, scales, eid, transpose_rhs=transpose))
    if layout == "random":
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-4)
    else:
        np.testing.assert_array_equal(got, want)
    assert np.all(got[np.asarray(eid) < 0] == 0.0)
    np.testing.assert_array_equal(
        got, single_expert_rows(x, pos, neg, scales, eid, transpose, **kw))


@pytest.mark.parametrize("M,N,bm,bn", [(300, 6400, 128, 4096),
                                       (20, 4096 * 2, 8, 4096)])
def test_pack_unpack_edge_blocks_bit_exact(M, N, bm, bn):
    """Row and word edge blocks overhang the array: results stay
    bit-identical to the oracles (nothing is padded or dropped)."""
    rng = np.random.default_rng(62)
    tau = jnp.asarray(rng.normal(0, 1, (M, N)), jnp.float32)
    gp, gn = pack_ternary_planes(tau, jnp.float32(0.8), bm=bm, bn=bn,
                                 interpret=True)
    wp, wn = ref.pack_ternary_planes_ref(tau, jnp.float32(0.8))
    np.testing.assert_array_equal(np.asarray(gp), np.asarray(wp))
    np.testing.assert_array_equal(np.asarray(gn), np.asarray(wn))
    base = jnp.asarray(rng.normal(0, 1, (M, N)), jnp.bfloat16)
    pos, neg = jnp.stack([gp, wn]), jnp.stack([gn, wp & ~wn])
    scales = jnp.asarray([0.3, -0.2], jnp.float32)
    got = unpack_add_many(base, pos, neg, scales, bm=bm, bn=bn,
                          interpret=True)
    want = ref.unpack_add_many_ref(base, pos, neg, scales)
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))
