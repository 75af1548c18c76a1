"""Tests for EF-ternary cross-pod gradient compression.

Leaf-level tests run single-device; the shard_map collective test runs in a
subprocess with XLA_FLAGS=--xla_force_host_platform_device_count=4 so the
main test process keeps seeing exactly one device (per the dry-run rules).
"""

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.gradient_compression import (GradCompressionConfig,
                                             _pack_planes, _unpack_planes,
                                             compress_leaf_for_allgather,
                                             gaussian_topk_threshold,
                                             init_error_state)


def test_gaussian_threshold_density():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(0, 0.3, (50_000,)), jnp.float32)
    for k in (0.05, 0.1, 0.3):
        thr = gaussian_topk_threshold(x, k)
        frac = float(jnp.mean((jnp.abs(x) >= thr).astype(jnp.float32)))
        assert abs(frac - k) < 0.02, (k, frac)


def test_plane_pack_roundtrip():
    rng = np.random.default_rng(1)
    signs = jnp.asarray(rng.integers(-1, 2, (1000,)), jnp.int8)
    pos, neg = _pack_planes(signs)
    back = _unpack_planes(pos, neg, 1000)
    np.testing.assert_array_equal(np.array(back, np.int8), np.array(signs))


def test_error_feedback_reduces_bias():
    """Repeated EF compression of a constant gradient converges: mean of
    reconstructions -> true gradient (the EF guarantee)."""
    rng = np.random.default_rng(2)
    g = jnp.asarray(rng.normal(0, 1, (8_192,)), jnp.float32)
    cfg = GradCompressionConfig(density=0.1)
    err = jnp.zeros_like(g)
    recons = []
    step = jax.jit(lambda e: compress_leaf_for_allgather(g, e, cfg))
    for _ in range(120):
        pos, neg, scale, err = step(err)
        recon = _unpack_planes(pos, neg, g.size) * scale
        recons.append(np.array(recon))
    early = np.linalg.norm(np.mean(recons[:10], axis=0) - np.array(g))
    late = np.linalg.norm(np.mean(recons, axis=0) - np.array(g))
    rel = late / np.linalg.norm(np.array(g))
    assert rel < 0.12, rel
    assert late < early  # averaging converges (EF guarantee)


def test_compressed_leaf_is_sparse_and_scaled():
    rng = np.random.default_rng(3)
    g = jnp.asarray(rng.normal(0, 1, (4_096,)), jnp.float32)
    cfg = GradCompressionConfig(density=0.05)
    pos, neg, scale, err = compress_leaf_for_allgather(
        g, jnp.zeros_like(g), cfg)
    dens = (float(jnp.sum(jax.lax.population_count(pos)))
            + float(jnp.sum(jax.lax.population_count(neg)))) / g.size
    assert abs(dens - 0.05) < 0.02
    assert float(scale) > 0


def test_init_error_state_shapes():
    params = {"a": jnp.ones((3, 4), jnp.bfloat16), "b": jnp.ones((7,))}
    e = init_error_state(params)
    assert e["a"].shape == (3, 4) and e["a"].dtype == jnp.float32
    assert float(jnp.sum(e["b"])) == 0.0


SHARD_MAP_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P
    from jax import shard_map
    from repro.core.gradient_compression import (
        GradCompressionConfig, compressed_cross_pod_mean, init_error_state)

    from repro.launch.mesh import auto_mesh
    mesh = auto_mesh((4,), ("pod",))
    cfg = GradCompressionConfig(density=0.25)
    rng = np.random.default_rng(0)
    g_all = jnp.asarray(rng.normal(0, 1, (4, 2048)), jnp.float32)

    def f(g):
        g = g.reshape(2048)
        mean, err = compressed_cross_pod_mean(
            {"w": g}, {"w": jnp.zeros_like(g)}, cfg, axis_name="pod")
        return mean["w"][None], err["w"][None]

    fm = shard_map(f, mesh=mesh, in_specs=P("pod"),
                   out_specs=(P("pod"), P("pod")))
    mean, err = jax.jit(fm)(g_all)
    mean = np.array(mean)
    # all pods agree on the mean
    assert np.allclose(mean[0], mean[1]) and np.allclose(mean[0], mean[3])
    # compressed mean correlates strongly with true mean
    true = np.mean(np.array(g_all), axis=0)
    corr = np.corrcoef(mean[0], true)[0, 1]
    assert corr > 0.55, corr
    # error feedback holds the residual
    assert float(np.abs(np.array(err)).sum()) > 0
    print("OK")
""")


def test_cross_pod_mean_shard_map():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    r = subprocess.run([sys.executable, "-c", SHARD_MAP_SCRIPT],
                       capture_output=True, text=True, env=env, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "OK" in r.stdout
