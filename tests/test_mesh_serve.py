"""Cross-mesh parity suite for the sharded serving engine.

The mesh contract is *bitwise*: greedy AND seeded-sampled token streams
must be identical between ``mesh=None`` and every swept mesh shape —
covering mixed waves with mid-chunk admissions, paged KV, and an expert
set larger than a shard's budget (per-shard eviction churn included).
Runs on 8 forced host devices in a subprocess (the main pytest process
keeps its single-device view).
"""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_sub(script: str, timeout: int = 600):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src") + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    p = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, timeout=timeout, env=env)
    assert p.returncode == 0, (
        f"subprocess failed\nstdout:\n{p.stdout[-1500:]}\n"
        f"stderr:\n{p.stderr[-3000:]}")
    return p.stdout


HEADER = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
import repro.api as capi
from repro.configs import get_smoke_config
from repro.models import build, Runtime
from repro.launch.mesh import make_serve_mesh
from repro.serve.engine import Request

assert len(jax.devices()) == 8

cfg = get_smoke_config("qwen2_5_3b", n_units=1)
api = build(cfg)
rt = Runtime(attn_chunk_q=16, attn_chunk_k=16, remat_policy="none")
base = api.init(jax.random.PRNGKey(0))

experts = []
for i in range(6):
    k = jax.random.PRNGKey(100 + i)
    leaves, treedef = jax.tree_util.tree_flatten(base)
    ks = jax.random.split(k, len(leaves))
    ft = jax.tree_util.tree_unflatten(
        treedef, [l + 0.02 * jax.random.normal(kk, l.shape, l.dtype)
                  for l, kk in zip(leaves, ks)])
    experts.append(capi.compress(base, ft, name=f"e{i}", density=0.2,
                                 alpha=1.0))

# budget smaller than the 6-expert resident set: serving all experts
# forces evictions (per-shard accounting on the mesh path)
BUDGET = 96 * 1024


def mk_requests(n=10):
    # mixed experts, varied prompt lengths and budgets; n > max_batch so
    # the wave loop exercises mid-chunk continuous admission
    rng = np.random.default_rng(0)
    out = []
    for u in range(n):
        plen = int(rng.integers(3, 12)) if u % 3 else 11
        out.append(Request(
            uid=u, expert=f"e{u % 6}",
            prompt=jnp.asarray(np.arange(1, plen + 1) + u, jnp.int32),
            max_new_tokens=int(3 + u % 5)))
    return out


def run(mesh, samp, kv):
    reg = capi.registry(experts=experts, device_cache_bytes=BUDGET,
                        mesh=mesh)
    eng = capi.serve(api, rt, base, reg, max_batch=4, cache_len=64,
                     decode_chunk=4, kv_layout=kv, mesh=mesh, **samp)
    done = eng.run(mk_requests())
    toks = {r.uid: (r.status, list(r.out_tokens)) for r in done}
    return toks, eng.swap_summary()


def check(kv, samp):
    ref, ref_summ = run(None, samp, kv)
    assert all(s == "done" for s, _ in ref.values())
    for shape in ((1, 1), (2, 1), (2, 4)):
        got, summ = run(make_serve_mesh(shape), samp, kv)
        assert got == ref, (
            f"kv={kv} samp={samp} mesh={shape}: token streams diverged\n"
            f"ref={ref}\ngot={got}")
        assert summ["n_expert_shards"] == shape[0]
        assert summ["admitted"] > 0, "no mid-wave admissions exercised"
        assert summ["evictions"] + summ["stack_evictions"] > 0, \
            "budget never forced an eviction"
        shards = summ["shards"]
        assert len(shards) == shape[0]
        counts = [s["resident_experts"] for s in shards]
        if max(counts):
            assert max(counts) <= 2 * max(min(counts), 1), \
                f"shard imbalance > 2x: {counts}"
        for s in shards:
            assert s["capacity_bytes"] == BUDGET
    print(f"OK kv={kv} samp={samp}")
"""


@pytest.mark.parametrize("kv", ["dense", "paged"])
def test_cross_mesh_parity(kv):
    out = run_sub(HEADER + f"""
check({kv!r}, {{}})
check({kv!r}, {{"temperature": 0.8, "top_k": 5, "seed": 7}})
print("ALL_OK")
""")
    assert "ALL_OK" in out


def test_cross_mesh_parity_packed_overlay():
    """The packed overlay branches a TPU takes (grouped contraction,
    packed embedding rows, packed tied head) run once per expert shard on
    a mesh and stay bitwise equal to the one-device engine."""
    out = run_sub(HEADER + """
import functools
from repro.models import delta as delta_mod
from repro.serve import engine as engine_mod
engine_mod.build_overlay = functools.partial(delta_mod.build_overlay,
                                             materialize=False)
check("dense", {})
print("ALL_OK")
""")
    assert "ALL_OK" in out


def test_mesh_device_cache_shards():
    """DeviceCache on a mesh: stacks pad E to the shard count with inert
    zero slots, per-shard budget accounting, and shard gauges."""
    out = run_sub(HEADER + """
from repro.serve.expert_cache import BASE

mesh = make_serve_mesh((2, 4))
reg = capi.registry(experts=experts, device_cache_bytes=BUDGET, mesh=mesh)
cache = reg.device()
assert cache.n_shards == 2
stacks = cache.stacked(("e0", "e1", "e2"))          # E=3 pads to 4
for pos, neg, scales, shape in stacks.values():
    assert pos.shape[0] == 4 and scales.shape[0] == 4
    assert float(jnp.abs(scales[3])) == 0.0          # pad slot is inert
    assert "expert" in str(pos.sharding.spec)
sh = cache.shard_summary()
assert [s["resident_experts"] for s in sh] == [2, 1]
assert cache.shard_resident_bytes() <= cache.resident_bytes()

# mesh=None registry keeps today's path: no padding, shard count 1
reg1 = capi.registry(experts=experts, device_cache_bytes=BUDGET)
c1 = reg1.device()
assert c1.n_shards == 1
s1 = c1.stacked(("e0", "e1", "e2"))
for pos, neg, scales, shape in s1.values():
    assert pos.shape[0] == 3
print("CACHE_OK")
""")
    assert "CACHE_OK" in out
