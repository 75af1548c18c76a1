"""Multi-device distribution tests (subprocess with 8 fake devices so the
main process keeps a single device): sharded train step with compressed
cross-pod gradients, SP flash decoding, and sharding-rule sanity."""

import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro.configs import get_config, get_smoke_config
from repro.distributed.sharding import param_pspec


def run_sub(script: str, timeout=600):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, env=env, timeout=timeout)
    assert r.returncode == 0, (r.stdout[-1500:], r.stderr[-3000:])
    return r.stdout


def test_param_pspec_rules():
    import jax as _jax
    cfg = get_config("qwen3_32b")
    mesh = _jax.sharding.Mesh(
        np.array(_jax.devices() * 1).reshape(1, 1), ("data", "model"))
    # embedding vocab-parallel (d_model unsharded: XLA partitioner
    # workaround, see sharding.py)
    spec = param_pspec("embed", (151936, 5120), cfg, mesh)
    assert tuple(spec) == ("model", None)
    # attention head-TP (64 heads % 16 ... here n_model=1 so divisible)
    spec = param_pspec("blocks/block0/attn/wq", (64, 5120, 64, 128), cfg,
                       mesh)
    assert tuple(spec) == (None, "data", "model", None)
    # llama4: head_tp disabled -> FSDP on the NON-contraction head_dim
    # (sharding d_model forces activation regathers; see §Perf E2)
    cfg4 = get_config("llama4_maverick_400b")
    spec = param_pspec("blocks/block0/attn/wq", (24, 5120, 40, 128), cfg4,
                       mesh)
    assert tuple(spec) == (None, None, None, "data")
    # mixtral experts: internal TP
    cfgm = get_config("mixtral_8x7b")
    spec = param_pspec("blocks/block0/ffn/wg_e", (32, 8, 4096, 14336), cfgm,
                       mesh)
    assert tuple(spec) == (None, None, "data", "model")
    # llama4 experts: EP
    spec = param_pspec("blocks/block1/ffn/wg_e", (24, 128, 5120, 8192), cfg4,
                       mesh)
    assert tuple(spec) == (None, "model", "data", None)


TRAIN_SHARDED = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs import get_smoke_config
    from repro.models import build, Runtime
    from repro.distributed.sharding import (make_shard_fn, param_shardings,
                                            batch_shardings, replicated)
    from repro.train import TrainConfig, init_train_state, make_train_step
    from repro.data.pipeline import make_batch_for
    from repro.core.gradient_compression import GradCompressionConfig

    from repro.launch.mesh import auto_mesh
    mesh = auto_mesh((2, 2, 2), ("pod", "data", "model"))
    cfg = get_smoke_config("qwen2_5_3b")
    api = build(cfg)
    rt = Runtime(shard=make_shard_fn(mesh, cfg), attn_chunk_q=16,
                 attn_chunk_k=16, remat_policy="none")
    tcfg = TrainConfig(microbatches=2, peak_lr=5e-3, warmup_steps=2,
                       total_steps=50,
                       grad_compression=GradCompressionConfig(
                           enabled=True, density=0.3))
    with jax.set_mesh(mesh):
        params = api.init(jax.random.PRNGKey(0))
        state = init_train_state(params, tcfg, multi_pod=True)
        pshard = param_shardings(jax.eval_shape(lambda: params), cfg, mesh)
        state = jax.device_put(state, {
            "params": pshard,
            "opt": {"mu": pshard, "nu": pshard,
                    "count": replicated(mesh)},
            "ef": pshard,
            "step": replicated(mesh)})
        step_fn = jax.jit(make_train_step(api, rt, tcfg, mesh=mesh))
        losses = []
        for s in range(12):
            batch = make_batch_for(cfg, s, 32, 8)
            state, m = step_fn(state, batch)
            losses.append(float(m["loss"]))
    assert np.isfinite(losses).all()
    assert np.mean(losses[-3:]) < np.mean(losses[:3]), losses
    print("TRAIN_OK", losses[0], losses[-1])
""")


def test_compressed_multipod_train_step():
    out = run_sub(TRAIN_SHARDED)
    assert "TRAIN_OK" in out


SP_DECODE = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs import get_smoke_config
    from repro.models import build, Runtime
    from repro.distributed.sharding import make_shard_fn
    from repro.distributed.collectives import make_sp_decode_attn

    from repro.launch.mesh import auto_mesh
    mesh = auto_mesh((2, 4), ("data", "model"))
    cfg = get_smoke_config("qwen2_5_3b", n_units=2)
    api = build(cfg)
    rt_local = Runtime(attn_chunk_q=16, attn_chunk_k=16, remat_policy="none")
    rt_sp = Runtime(shard=make_shard_fn(mesh, cfg),
                    decode_attn=make_sp_decode_attn(mesh),
                    attn_chunk_q=16, attn_chunk_k=16, remat_policy="none")
    params = api.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    toks = jnp.asarray(rng.integers(1, cfg.vocab, (2, 12)), jnp.int32)
    batch = {"tokens": toks}

    # local reference (cache_len multiple of model axis = 4)
    lp_l, cache_l = api.prefill(params, batch, rt_local, cache_len=16)
    prefill_sp = jax.jit(lambda p, b: api.prefill(p, b, rt_sp, 16))
    decode_sp = jax.jit(lambda p, t, c: api.decode_step(p, t, c, rt_sp))
    with jax.set_mesh(mesh):
        lp_s, cache_s = prefill_sp(params, batch)
        np.testing.assert_allclose(np.asarray(lp_l, np.float32),
                                   np.asarray(lp_s, np.float32),
                                   atol=2e-3, rtol=2e-3)
        tok = jnp.argmax(lp_l[:, -1], -1).astype(jnp.int32)[:, None]
        ld_l, cache_l = api.decode_step(params, tok, cache_l, rt_local)
        ld_s, cache_s = decode_sp(params, tok, cache_s)
        np.testing.assert_allclose(np.asarray(ld_l, np.float32),
                                   np.asarray(ld_s, np.float32),
                                   atol=2e-3, rtol=2e-3)
        ld_l2, _ = api.decode_step(params, tok, cache_l, rt_local)
        ld_s2, _ = decode_sp(params, tok, cache_s)
        np.testing.assert_allclose(np.asarray(ld_l2, np.float32),
                                   np.asarray(ld_s2, np.float32),
                                   atol=2e-3, rtol=2e-3)
    print("SP_OK")
""")


def test_sp_decode_matches_local():
    out = run_sub(SP_DECODE)
    assert "SP_OK" in out
