"""Production meshes.  Defined as functions so importing this module never
touches jax device state (the dry-run sets XLA_FLAGS before any jax use)."""

from __future__ import annotations


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (data=16, model=16) = 256 chips.  Multi-pod adds a 'pod'
    axis: (pod=2, data=16, model=16) = 512 chips."""
    import jax

    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def auto_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``AxisType.Auto``.

    The installed JAX makes explicit-axis meshes by default, under which
    every gather needs an ``out_sharding``; the sharding rules here place
    arrays with ``NamedSharding`` and let the partitioner propagate, which
    is what Auto axes mean."""
    import jax
    from jax.sharding import AxisType

    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


SERVE_AXES = ("expert", "model")


def make_serve_mesh(shape=(1, 1)):
    """Serving mesh: (expert, model).

    The ``expert`` axis shards the stacked ``[E, ...]`` bitplane buffers
    (each device group holds a contiguous block of the resident expert
    set); the ``model`` axis shards the base model tensor-parallel along
    dims where every output element is still computed by exactly one
    device (vocab-parallel embed/lm_head, batch-sharded KV) so that token
    streams stay bit-identical to the single-device engine.

    ``shape=(1, 1)`` is a degenerate single-device mesh — useful for
    exercising the mesh code path without multiple devices.
    """
    import jax

    if len(shape) != 2:
        raise ValueError(f"serve mesh shape must be (expert, model), got {shape!r}")
    n = shape[0] * shape[1]
    avail = len(jax.devices())
    if n > avail:
        raise ValueError(
            f"serve mesh {shape} needs {n} devices but only {avail} are "
            "visible (set --xla_force_host_platform_device_count for CPU)")
    return auto_mesh(shape, SERVE_AXES)


# Published per-chip peaks, keyed by ``jax.Device.device_kind``.
# Source: Google Cloud documentation, "TPU v5e" (system architecture):
# 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s, 1,600 Gbit/s
# of inter-chip interconnect (four links of 50 GB/s).
DEVICE_PEAKS = {
    "TPU v5 lite": {"peak_flops_bf16": 197e12, "peak_ops_int8": 393e12,
                    "hbm_bytes": 16e9, "hbm_bw": 819e9,
                    "ici_bw": 200e9, "ici_link_bw": 50e9},
}


def device_peaks(kind: str) -> dict:
    """Peaks of one chip of ``kind``; a kind not in the table is an error,
    never a default."""
    try:
        return DEVICE_PEAKS[kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {kind!r}; "
                       f"known: {sorted(DEVICE_PEAKS)}") from None
