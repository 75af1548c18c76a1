"""Shared Mosaic tiling rules and compiler hints for the Pallas kernels.

``dimension_semantics`` tells the TPU lowering which grid dimensions are
embarrassingly parallel (safe to pipeline/reorder across cores) and which
carry a sequential accumulation ("arbitrary").  Interpret mode (CPU CI)
ignores compiler hints, so we return ``None`` there and keep the kernels
runnable on any backend.

Block shapes follow the TPU's (8, 128) tiling: the last dimension of every
block is a multiple of 128 lanes or the whole array dimension, and the
second-to-last a multiple of 8 sublanes or the whole dimension.  Blocks
along independent output dimensions may overhang the array (Pallas masks
the edge block); blocks along a contraction must divide it exactly.
"""

from __future__ import annotations

from jax.experimental import pallas as pl

LANE = 32       # bits per uint32 plane word
LANES = 128     # TPU vector lanes (last-dim tile)
SUBLANES = 8    # TPU sublanes (second-to-last-dim tile)


def lane_block(b: int, n: int) -> int:
    """Block along a lane (last) dimension of extent ``n``: the whole
    dimension when ``b`` covers it, else ``b`` rounded down to a multiple
    of 128 (at least 128).  Edge blocks may overhang ``n``."""
    if n <= b:
        return n
    return max(LANES, (b // LANES) * LANES)


def sublane_block(b: int, n: int) -> int:
    """Block along a second-to-last dimension: whole, or a multiple of 8."""
    if n <= b:
        return n
    return max(SUBLANES, (b // SUBLANES) * SUBLANES)


def divisor_block(b: int, n: int, align: int = LANES) -> int:
    """Largest multiple of ``align`` that is <= ``b`` and divides ``n``
    (a contraction block must tile it exactly); the whole ``n`` when none
    does."""
    for cand in range((min(b, n) // align) * align, 0, -align):
        if n % cand == 0:
            return cand
    return n


def tpu_compiler_params(dimension_semantics: tuple[str, ...], *,
                        interpret: bool = False):
    """CompilerParams with the given grid semantics, or None off-TPU."""
    if interpret:
        return None
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.CompilerParams(dimension_semantics=dimension_semantics)


def streaming_cost(n_elems: int, *, in_bytes_per_elem: float,
                   out_bytes_per_elem: float) -> pl.CostEstimate:
    """CostEstimate for a bandwidth-bound streaming kernel (pack/unpack)."""
    return pl.CostEstimate(
        flops=4 * n_elems,   # compare/shift/mask per element, roughly
        bytes_accessed=int(n_elems * (in_bytes_per_elem + out_bytes_per_elem)),
        transcendentals=0,
    )


def grouped_matmul_cost(m: int, n: int, k: int, n_experts: int, *,
                        elem_bytes: int = 4) -> pl.CostEstimate:
    """CostEstimate for the per-row-expert grouped ternary matmul.

    Each of the E stacked experts contracts a row-masked copy of x on the
    MXU (E full matmuls of FLOPs), but the bytes are x once + E sets of
    2-bit planes + the f32 output — the kernel stays bandwidth-cheap even
    though the masked-contraction FLOPs scale with E.
    """
    plane_bytes = n_experts * 2 * (k * n // 8)      # two planes, 1 bit each
    return pl.CostEstimate(
        flops=2 * m * n * k * max(n_experts, 1),
        bytes_accessed=m * k * elem_bytes + plane_bytes + m * n * 4,
        transcendentals=0,
    )
