"""The grouped kernel's operations and bytes, counted by hand (CPU)."""

import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import kernel_cost, trace_reduce  # noqa: E402

V5E = trace_reduce.peaks("TPU v5 lite")


def test_decode_ffn_up_counts():
    # 32 rows x [2048, 11008] over 3 experts
    c = kernel_cost.grouped_ternary(32, 2048, 11008, 3)
    assert c["flops"] == 2 * 32 * 2048 * 11008
    planes = 3 * 2048 * 11008 // 4          # two bits an entry
    assert c["bytes"] == planes + 32 * 2048 * 4 + 32 * 11008 * 4
    t, bound = kernel_cost.ideal_seconds(c, V5E)
    assert bound == "memory" and t == pytest.approx(c["bytes"] / 819e9)


def test_work_does_not_grow_with_experts_beyond_their_planes():
    one = kernel_cost.grouped_ternary(4096, 2048, 2048, 1)
    four = kernel_cost.grouped_ternary(4096, 2048, 2048, 4)
    assert one["flops"] == four["flops"]
    assert four["bytes"] - one["bytes"] == 3 * 2048 * 2048 // 4


def test_large_prefill_is_compute_bound():
    c = kernel_cost.grouped_ternary(8192, 2048, 11008, 3)
    assert kernel_cost.ideal_seconds(c, V5E)[1] == "compute"


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        trace_reduce.peaks("TPU v9000")


@pytest.mark.parametrize("text,want", [
    ("%c = f32[32,344,32]{2,1,0} custom-call(f32[2048,32]{1,0} %a, "
     "u32[3,344,2048]{2,1,0} %p, u32[3,344,2048]{2,1,0} %n, "
     "f32[3,1]{1,0} %s, s32[1,32]{1,0} %e)", (32, 2048, 11008, 3, False)),
    ("%c = f32[32,151936]{1,0} custom-call(f32[32,32,64]{2,1,0} %a, "
     "u32[3,64,151936]{2,1,0} %p, u32[3,64,151936]{2,1,0} %n, "
     "f32[3,1]{1,0} %s, s32[32,1]{1,0} %e)", (32, 2048, 151936, 3, True)),
    ("%f = bf16[32,1024]{1,0} fusion(bf16[32,2048]{1,0} %x)", None),
])
def test_grouped_shapes_from_hlo_text(text, want):
    assert trace_reduce.grouped_shapes(text) == want
