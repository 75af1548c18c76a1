"""Operations and bytes a kernel call needs, from its shapes.

The count is of the work the algorithm needs, not of what an
implementation does, so it reads the same whatever computes it.
"""

from __future__ import annotations

PLANE_BITS = 2          # a ternary entry is two 1-bit planes


def grouped_ternary(m: int, k: int, n: int, e: int,
                    x_bytes: int = 4, out_bytes: int = 4) -> dict:
    """The grouped ternary delta ``y[r] = s[e(r)] * x[r] @ T_e(r)``.

    m rows of x [m, k], each contracted once against its own expert's
    [k, n] ternary matrix: 2*m*k*n operations.  Bytes: the planes of the
    ``e`` stacked experts once (2 bits an entry), x once and y once.
    """
    return {"flops": 2 * m * k * n,
            "bytes": e * k * n * PLANE_BITS // 8 + m * k * x_bytes
            + m * n * out_bytes}


def ideal_seconds(cost: dict, peaks: dict) -> tuple[float, str]:
    """The least time the chip could take, and which bound sets it."""
    t_flops = cost["flops"] / peaks["peak_flops_bf16"]
    t_bytes = cost["bytes"] / peaks["hbm_bw"]
    return (t_flops, "compute") if t_flops >= t_bytes else (t_bytes,
                                                            "memory")
