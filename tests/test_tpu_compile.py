"""Compile-only checks of the serving path's Pallas kernels for a TPU v5e
chip, at qwen2.5-3b widths with E=3 stacked experts.

Nothing runs: each test lowers a kernel for one chip of a *described*
``v5e:2x2`` topology and lets the TPU compiler accept or refuse it
(block tiling, Mosaic lowering, the scoped VMEM limit).  The topology is
described inside a module fixture, never at import, so that only the
worker that runs this file loads the TPU compiler.
"""

import os

import jax
import jax.numpy as jnp
import pytest

from repro.core.compeft import STREAM_COLS
from repro.kernels.pack import pack_ternary_planes_segmented
from repro.kernels.ternary_matmul import ternary_matmul_grouped

E = 3
D, FF, VOCAB, UNITS = 2048, 11008, 151936, 36      # qwen2.5-3b
DECODE_ROWS = 4


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    return SingleDeviceSharding(topo.devices[0])


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *specs):
    return jax.jit(fn).lower(*specs).compile().as_text()


def _kernel_calls(hlo: str, name: str) -> int:
    return sum(1 for line in hlo.splitlines()
               if "tpu_custom_call" in line and name in line)


def _plane_copies(hlo: str, plane_shape) -> int:
    """Copies of a whole plane stack (a relayout per call)."""
    tag = "u32[" + ",".join(map(str, plane_shape)) + "]"
    return sum(1 for line in hlo.splitlines()
               if " copy(" in line and line.split("=", 1)[1].strip()
               .startswith(tag))


@pytest.mark.parametrize("name,k,n,transpose", [
    ("q_proj", D, D, False),
    ("ffn_up", D, FF, False),
    ("ffn_down", FF, D, False),
    ("tied_lm_head", D, VOCAB, True),
])
@pytest.mark.parametrize("rows,one_expert", [
    pytest.param(DECODE_ROWS, False, id=str(DECODE_ROWS)),
    pytest.param(512, False, id="512"),
    # a prefill: four 128-row tiles of expert 1, the other two skipped
    pytest.param(512, True, id="512-one_expert"),
])
def test_grouped_matmul_compiles(one_chip, name, k, n, transpose, rows,
                                 one_expert):
    plane = (E, n, k // 32) if transpose else (E, k, n // 32)

    def f(x, pos, neg, scales, eid):
        if one_expert:
            eid = jnp.ones_like(eid)
        return ternary_matmul_grouped(x, pos, neg, scales, eid,
                                      transpose_rhs=transpose,
                                      interpret=False)

    hlo = _compile(f, _spec(one_chip, (rows, k), jnp.float32),
                   _spec(one_chip, plane, jnp.uint32),
                   _spec(one_chip, plane, jnp.uint32),
                   _spec(one_chip, (E,), jnp.float32),
                   _spec(one_chip, (rows,), jnp.int32))
    assert _kernel_calls(hlo, "ternary_matmul_grouped") == 1
    # the kernel reads the planes in their stored layout: no per-call copy
    assert _plane_copies(hlo, plane) == 0


def test_unpack_add_many_compiles_on_ffn_leaf(one_chip, monkeypatch):
    """The merge of the scanned FFN stack [36, 2048, 11008] through
    ``ops.apply_ternary_delta_many_flat``: rows of the leaf's own last dim,
    so neither the base nor the merged leaf is relaid out."""
    from repro.core.packing import PackedTernary
    from repro.kernels import ops

    shape = (UNITS, D, FF)
    words = UNITS * D * FF // 32

    def f(base, pos, neg, scales):
        pts = [PackedTernary(pos=pos[e], neg=neg[e], scale=scales[e],
                             shape=shape) for e in range(E)]
        return ops.apply_ternary_delta_many_flat(base, pts)

    # this process runs on the CPU: steer ops to the TPU kernel
    monkeypatch.setattr(ops, "INTERPRET", False)
    hlo = _compile(f, _spec(one_chip, shape, jnp.bfloat16),
                   _spec(one_chip, (E, words), jnp.uint32),
                   _spec(one_chip, (E, words), jnp.uint32),
                   _spec(one_chip, (E,), jnp.float32))
    assert _kernel_calls(hlo, "unpack_add_many") == 1
    relayouts = [line for line in hlo.splitlines()
                 if " copy(" in line and "bf16[" in line]
    assert not relayouts, relayouts[:2]


def test_pack_segmented_compiles_at_stream_cols(one_chip):
    rows = UNITS * D * FF // STREAM_COLS          # the FFN leaf's segment

    def f(tau, thr):
        return pack_ternary_planes_segmented(tau, thr, interpret=False)

    hlo = _compile(f, _spec(one_chip, (rows, STREAM_COLS), jnp.float32),
                   _spec(one_chip, (rows,), jnp.float32))
    assert _kernel_calls(hlo, "pack_ternary_planes_segmented") == 1


def test_compress_ffn_leaf_reads_it_in_place(one_chip):
    """``compress_packed`` of the FFN stack in its leaf shape, one call:
    the segment buffer is the leaf itself (rows of its own last dim), so
    the compiled program never copies the 3.25 GB f32 leaf."""
    from repro.core.compeft import (_segment_cols, _segment_layout,
                                    _stream_compress)

    shape = (UNITS, D, FF)
    leaf = _spec(one_chip, shape, jnp.float32)
    cols = _segment_cols([leaf])
    row_seg, _, _, spans = _segment_layout([shape], cols)
    rows = _spec(one_chip, row_seg.shape, jnp.int32)
    seg = _spec(one_chip, (1,), jnp.int32)
    compiled = _stream_compress.lower(
        (leaf,), rows, rows, seg, seg, cols=cols, spans=spans, n_seg=1,
        interpret=False).compile()
    hlo = compiled.as_text()
    assert cols == FF
    assert _kernel_calls(hlo, "pack_ternary_planes_segmented") == 1
    n = UNITS * D * FF
    copies = [line for line in hlo.splitlines() if " copy(" in line
              and any(f"f32[{dims}]" in line for dims in
                      (f"{UNITS},{D},{FF}", f"{UNITS * D},{FF}",
                       f"{n // STREAM_COLS},{STREAM_COLS}", f"{n}"))]
    assert not copies, copies[:2]
    leaf_bytes = n * 4
    assert compiled.memory_analysis().temp_size_in_bytes < leaf_bytes // 8
