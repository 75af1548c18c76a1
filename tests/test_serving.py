"""Serving tier: expert registry/store/cache hierarchy, LRU eviction, swap
accounting, end-to-end multi-expert engine, and the compressed-expert
export/import round trip — all through the ``repro.api`` facade."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api as rapi
from repro.configs import get_smoke_config
from repro.expert import GOLOMB, PACKED
from repro.models import Runtime, build
from repro.serve import (EngineConfig, ExpertRegistry, ExpertStore, Request,
                         ServeEngine, uncompressed_baseline_bytes)

RT = Runtime(attn_chunk_q=16, attn_chunk_k=16, remat_policy="none")


def make_experts(api, base, n=3, scale=0.01, density=0.2,
                 **registry_kw) -> ExpertRegistry:
    """Fake fine-tunes: base + random deltas, ComPEFT-compressed into a
    registry (the facade path — no hand-flattening, no ExpertArtifact)."""
    reg = rapi.registry(**registry_kw)
    for i in range(n):
        key = jax.random.PRNGKey(100 + i)
        leaves, tdef = jax.tree_util.tree_flatten(base)
        keys = jax.random.split(key, len(leaves))
        ft = jax.tree_util.tree_unflatten(tdef, [
            (l.astype(jnp.float32)
             + scale * jax.random.normal(k, l.shape)).astype(l.dtype)
            for l, k in zip(leaves, keys)])
        reg.add(rapi.compress(base, ft, name=f"expert{i}", density=density))
    return reg


def test_store_and_cache_lru():
    cfg = get_smoke_config("qwen2_5_3b", n_units=1)
    api = build(cfg)
    base = api.init(jax.random.PRNGKey(0))
    reg = make_experts(api, base, n=3)
    one = reg.get("expert0")
    packed_bytes = one.nbytes(PACKED)
    cache = reg.device(int(packed_bytes * 1.5))

    cache.fetch("expert0")
    cache.fetch("expert1")           # evicts expert0 (capacity 1.5 experts)
    assert cache.stats.evictions >= 1
    cache.fetch("expert1")
    assert cache.stats.hits == 1
    # packed residency: device bytes are the compressed bytes, far below
    # what dense f32 deltas would have cost for the same promotions
    dense_bytes = uncompressed_baseline_bytes(one) * 2  # f32 deltas
    assert cache.stats.host_to_device_bytes < 2 * dense_bytes / 8
    assert cache.stats.host_to_device_bytes == cache.stats.store_to_host_bytes


def test_packed_residency_capacity_multiplier():
    """Under one byte budget the packed-resident cache must hold >= 8x the
    experts a dense-delta cache would (the tentpole capacity claim)."""
    cfg = get_smoke_config("qwen2_5_3b", n_units=1)
    api = build(cfg)
    base = api.init(jax.random.PRNGKey(0))
    reg = make_experts(api, base, n=10)
    dense_bytes = uncompressed_baseline_bytes(reg.get("expert0")) * 2
    budget = int(dense_bytes * 1.5)   # seed layout: fits 1 dense expert
    cache = reg.device(budget)
    for i in range(10):
        cache.fetch(f"expert{i}")
    assert cache.stats.evictions == 0
    assert len(cache.resident()) >= 8
    assert cache.resident_bytes() <= budget


def test_stack_bytes_count_against_budget():
    """Stack-aware HBM accounting: an over-capacity stack build must
    trigger eviction (other stacks first, then LRU non-member trees), and
    resident_bytes() includes the stack buffers."""
    cfg = get_smoke_config("qwen2_5_3b", n_units=1)
    api = build(cfg)
    base = api.init(jax.random.PRNGKey(0))
    reg = make_experts(api, base, n=3)
    one = reg.get("expert0").nbytes(PACKED)
    # room for all three packed trees, but NOT for trees + two stacks
    cache = reg.device(int(one * 4.5))
    cache.stacked(("expert0", "expert1"))
    assert cache.stats.stack_bytes > 0
    assert cache.resident_bytes() <= cache.capacity
    # second stack overflows the budget -> the first stack must be evicted
    cache.stacked(("expert1", "expert2"))
    assert cache.stats.stack_evictions >= 1
    assert not cache.has_stack(("expert0", "expert1"))
    assert cache.has_stack(("expert1", "expert2"))
    assert cache.resident_bytes() <= cache.capacity


def test_tiny_budget_stack_evicts_trees():
    """With a budget that can't hold trees + stack, LRU non-member packed
    trees are evicted to make room for the active stack."""
    cfg = get_smoke_config("qwen2_5_3b", n_units=1)
    api = build(cfg)
    base = api.init(jax.random.PRNGKey(0))
    reg = make_experts(api, base, n=3)
    one = reg.get("expert0").nbytes(PACKED)
    cache = reg.device(int(one * 3.5))
    cache.fetch("expert2")          # non-member: the eviction victim
    cache.stacked(("expert0", "expert1"))   # 2 trees + stack > budget
    assert "expert2" not in cache.resident()
    assert cache.stats.evictions >= 1
    # the active set itself is protected even when over budget
    assert cache.has_stack(("expert0", "expert1"))


def test_engine_end_to_end_multi_expert():
    """Default (mixed) scheduling: heterogeneous waves, ZERO merges."""
    cfg = get_smoke_config("qwen2_5_3b", n_units=1)
    api = build(cfg)
    base = api.init(jax.random.PRNGKey(0))
    reg = make_experts(api, base, n=2)
    eng = rapi.serve(api, RT, base, reg, max_batch=4, cache_len=48)
    rng = np.random.default_rng(0)
    reqs = [Request(uid=i,
                    expert=f"expert{i % 2}",
                    prompt=jnp.asarray(rng.integers(1, cfg.vocab, 12),
                                       jnp.int32),
                    max_new_tokens=4)
            for i in range(6)]
    out = eng.run(reqs)
    for r in out:
        assert len(r.out_tokens) == 4
        assert all(0 <= t < cfg.vocab for t in r.out_tokens)
    s = eng.swap_summary()
    assert s["n_swaps"] == 0           # zero-merge hot path
    assert s["n_waves"] >= 1
    assert s["stack_builds"] >= 1
    assert s["store_to_host_bytes"] > 0


def test_engine_grouped_mode_still_merges():
    """scheduling='grouped' keeps the PR-1 merge-on-swap baseline."""
    cfg = get_smoke_config("qwen2_5_3b", n_units=1)
    api = build(cfg)
    base = api.init(jax.random.PRNGKey(0))
    reg = make_experts(api, base, n=2)
    eng = rapi.serve(api, RT, base, reg, max_batch=4, cache_len=48,
                     scheduling="grouped")
    rng = np.random.default_rng(0)
    reqs = [Request(uid=i, expert=f"expert{i % 2}",
                    prompt=jnp.asarray(rng.integers(1, cfg.vocab, 12),
                                       jnp.int32), max_new_tokens=4)
            for i in range(6)]
    eng.run(reqs)
    s = eng.swap_summary()
    assert s["n_swaps"] == 2           # one merge per expert
    assert s["n_waves"] == 0
    for r in reqs:
        assert len(r.out_tokens) == 4


def test_mixed_wave_bit_identical_to_sequential():
    """The tentpole correctness contract: a mixed-expert wave produces
    exactly the tokens each request gets when its expert is served alone
    through the same zero-merge path."""
    cfg = get_smoke_config("qwen2_5_3b", n_units=1)
    api = build(cfg)
    base = api.init(jax.random.PRNGKey(0))
    reg = make_experts(api, base, n=3, scale=0.03)
    rng = np.random.default_rng(1)
    prompts = [jnp.asarray(rng.integers(1, cfg.vocab, 10), jnp.int32)
               for _ in range(6)]

    def mk():
        return [Request(uid=i, expert=f"expert{i % 3}", prompt=prompts[i],
                        max_new_tokens=4) for i in range(6)]

    eng = rapi.serve(api, RT, base, reg, max_batch=6, cache_len=48)
    mixed = mk()
    eng.run(mixed)

    eng2 = rapi.serve(api, RT, base, make_experts(api, base, n=3,
                                                  scale=0.03),
                      max_batch=6, cache_len=48)
    seq = mk()
    for e in range(3):
        eng2.run([r for r in seq if r.expert == f"expert{e}"])
    assert ({r.uid: r.out_tokens for r in mixed}
            == {r.uid: r.out_tokens for r in seq})


def test_mixed_wave_base_rows():
    """__base__ requests ride in a mixed wave with a zero delta."""
    cfg = get_smoke_config("qwen2_5_3b", n_units=1)
    api = build(cfg)
    base = api.init(jax.random.PRNGKey(0))
    reg = make_experts(api, base, n=1, scale=0.05)
    rng = np.random.default_rng(2)
    prompt = jnp.asarray(rng.integers(1, cfg.vocab, 10), jnp.int32)
    reqs = [Request(uid=0, expert="__base__", prompt=prompt,
                    max_new_tokens=4),
            Request(uid=1, expert="expert0", prompt=prompt,
                    max_new_tokens=4)]
    eng = rapi.serve(api, RT, base, reg, max_batch=2, cache_len=48)
    eng.run(reqs)
    solo = Request(uid=2, expert="__base__", prompt=prompt, max_new_tokens=4)
    eng2 = rapi.serve(api, RT, base, make_experts(api, base, n=1,
                                                  scale=0.05),
                      max_batch=2, cache_len=48)
    eng2.run([solo])
    assert reqs[0].out_tokens == solo.out_tokens
    assert eng.swap_summary()["n_swaps"] == 0


def test_continuous_admission_refills_slots():
    """More requests than batch slots: finished rows are refilled in place
    (one wave, spliced prefills) instead of starting fresh waves."""
    cfg = get_smoke_config("qwen2_5_3b", n_units=1)
    api = build(cfg)
    base = api.init(jax.random.PRNGKey(0))
    reg = make_experts(api, base, n=2)
    rng = np.random.default_rng(3)
    reqs = [Request(uid=i, expert=f"expert{i % 2}",
                    prompt=jnp.asarray(rng.integers(1, cfg.vocab, 8),
                                       jnp.int32),
                    max_new_tokens=2 + (i % 3))
            for i in range(7)]
    eng = rapi.serve(api, RT, base, reg, max_batch=3, cache_len=64)
    eng.run(reqs)
    for r in reqs:
        assert len(r.out_tokens) == r.max_new_tokens
        assert all(0 <= t < cfg.vocab for t in r.out_tokens)
    s = eng.swap_summary()
    assert s["admitted"] >= 1
    assert s["n_swaps"] == 0


def test_admitted_row_matches_solo_serve():
    """Per-row pad-mask regression: a request spliced into a running wave
    (left-padded single-row prefill + KV splice) must produce the same
    tokens as the same prompt served solo — the pad tokens are masked out
    of its attention."""
    cfg = get_smoke_config("qwen2_5_3b", n_units=1)
    api = build(cfg)
    base = api.init(jax.random.PRNGKey(0))
    reg = make_experts(api, base, n=2, scale=0.03)
    rng = np.random.default_rng(7)
    pa = jnp.asarray(rng.integers(1, cfg.vocab, 9), jnp.int32)
    pb = jnp.asarray(rng.integers(1, cfg.vocab, 5), jnp.int32)   # shorter!
    a = Request(uid=0, expert="expert0", prompt=pa, max_new_tokens=3)
    b = Request(uid=1, expert="expert1", prompt=pb, max_new_tokens=4)
    eng = rapi.serve(api, RT, base, reg, max_batch=1, cache_len=64)
    eng.run([a, b])
    assert eng.swap_summary()["admitted"] == 1   # b spliced into a's slot

    solo = Request(uid=2, expert="expert1", prompt=pb, max_new_tokens=4)
    eng2 = rapi.serve(api, RT, base, make_experts(api, base, n=2,
                                                  scale=0.03),
                      max_batch=1, cache_len=64)
    eng2.run([solo])
    assert b.out_tokens == solo.out_tokens


def test_ragged_wave_rows_match_solo_serve():
    """Rows left-padded at wave start (ragged prompt lengths in one batch)
    also ignore their pads: every row matches its solo serve."""
    cfg = get_smoke_config("qwen2_5_3b", n_units=1)
    api = build(cfg)
    base = api.init(jax.random.PRNGKey(0))
    reg = make_experts(api, base, n=2, scale=0.03)
    rng = np.random.default_rng(8)
    lens = (6, 10, 8)
    prompts = [jnp.asarray(rng.integers(1, cfg.vocab, L), jnp.int32)
               for L in lens]
    reqs = [Request(uid=i, expert=f"expert{i % 2}", prompt=prompts[i],
                    max_new_tokens=3) for i in range(3)]
    eng = rapi.serve(api, RT, base, reg, max_batch=3, cache_len=48)
    eng.run(reqs)
    for i in range(3):
        solo = Request(uid=10 + i, expert=f"expert{i % 2}",
                       prompt=prompts[i], max_new_tokens=3)
        engs = rapi.serve(api, RT, base, make_experts(api, base, n=2,
                                                      scale=0.03),
                          max_batch=1, cache_len=48)
        engs.run([solo])
        assert reqs[i].out_tokens == solo.out_tokens, f"row {i} diverged"


def test_unsupported_family_falls_back_to_merge():
    """A family the overlay cannot express (MoE) serves via merge-on-swap
    even under mixed scheduling."""
    cfg = get_smoke_config("mixtral_8x7b", n_units=1)
    api = build(cfg)
    base = api.init(jax.random.PRNGKey(0))
    reg = make_experts(api, base, n=2, scale=0.02)
    eng = rapi.serve(api, RT, base, reg, max_batch=4, cache_len=48)
    assert eng._plan is None
    rng = np.random.default_rng(4)
    reqs = [Request(uid=i, expert=f"expert{i % 2}",
                    prompt=jnp.asarray(rng.integers(1, cfg.vocab, 8),
                                       jnp.int32), max_new_tokens=2)
            for i in range(4)]
    eng.run(reqs)
    for r in reqs:
        assert len(r.out_tokens) == 2
    assert eng.swap_summary()["n_swaps"] == 2   # fallback merged per expert


def test_merged_ensemble_single_sweep():
    """unpack_add_many consumer: W + sum_e a_e D_e in one sweep equals
    applying the scaled experts one at a time."""
    from repro.core.packing import PackedTernary
    from repro.kernels.ops import apply_ternary_delta_flat
    cfg = get_smoke_config("qwen2_5_3b", n_units=1)
    api = build(cfg)
    base = api.init(jax.random.PRNGKey(0))
    reg = make_experts(api, base, n=3, scale=0.03)
    eng = rapi.serve(api, RT, base, reg, cache_len=32)
    weights = [0.5, 1.0, 0.25]
    got = eng.merged_ensemble_params([f"expert{i}" for i in range(3)],
                                     weights)

    from repro.peft.lora import _path_str
    flat, treedef = jax.tree_util.tree_flatten_with_path(base)
    want = []
    packs = [reg.get(f"expert{i}").packed for i in range(3)]
    for path, leaf in flat:
        ps = _path_str(path)
        acc = leaf
        for pk, w in zip(packs, weights):
            if ps in pk:
                pt = pk[ps]
                scaled = PackedTernary(pos=pt.pos, neg=pt.neg,
                                       scale=pt.scale * w, shape=pt.shape,
                                       orig_dtype=pt.orig_dtype)
                acc = apply_ternary_delta_flat(acc, scaled)
        want.append(acc)
    want = jax.tree_util.tree_unflatten(treedef, want)
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_array_equal(np.asarray(g, np.float32),
                                      np.asarray(w, np.float32))


def test_golomb_cold_store_roundtrip():
    """cold_golomb registry tier: promotion decodes all leaves in one
    batched pass and reproduces the exact packed planes."""
    cfg = get_smoke_config("qwen2_5_3b", n_units=1)
    api = build(cfg)
    base = api.init(jax.random.PRNGKey(0))
    warm = make_experts(api, base, n=1)
    art = warm.get("expert0")
    cold = rapi.registry(cold_golomb=True)
    cold.add(art)
    assert cold.nbytes("expert0") < art.nbytes(PACKED)  # golomb < bitplanes
    back = cold.get("expert0")
    for path, pt in art.packed.items():
        bpt = back.packed[path]
        np.testing.assert_array_equal(np.asarray(pt.pos),
                                      np.asarray(bpt.pos))
        np.testing.assert_array_equal(np.asarray(pt.neg),
                                      np.asarray(bpt.neg))
        np.testing.assert_allclose(float(pt.scale), float(bpt.scale),
                                   rtol=1e-6)


def test_admitted_row_keeps_first_token():
    """Regression: a slot-refilled request's first generated token is the
    argmax of its (left-padded, pad-masked) prefill — it must not be
    dropped."""
    cfg = get_smoke_config("qwen2_5_3b", n_units=1)
    api = build(cfg)
    base = api.init(jax.random.PRNGKey(0))
    reg = make_experts(api, base, n=2, scale=0.03)
    rng = np.random.default_rng(5)
    pa = jnp.asarray(rng.integers(1, cfg.vocab, 8), jnp.int32)
    pb = jnp.asarray(rng.integers(1, cfg.vocab, 6), jnp.int32)
    a = Request(uid=0, expert="expert0", prompt=pa, max_new_tokens=1)
    b = Request(uid=1, expert="expert1", prompt=pb, max_new_tokens=2)
    eng = rapi.serve(api, RT, base, reg, max_batch=1, cache_len=32)
    eng.run([a, b])
    assert eng.swap_summary()["admitted"] == 1

    # expected: B prefilled left-padded to cur=8 (A's prompt len, A decoded
    # 0 steps past prefill) with its pads masked (start=2), then one decode
    # step — through the same zero-merge overlay
    overlay = eng._overlay_for(("expert0", "expert1"))
    eid = jnp.asarray([1], jnp.int32)
    start = jnp.asarray([8 - pb.shape[0]], jnp.int32)
    padded = jnp.pad(pb, (8 - pb.shape[0], 0), constant_values=1)[None]
    logits, cache = api.prefill(base, {"tokens": padded}, RT, 32,
                                delta=overlay, eid=eid, start=start)
    t1 = int(jnp.argmax(logits[0, -1]))
    logits2, _ = api.decode_step(base, jnp.asarray([[t1]], jnp.int32),
                                 cache, RT, delta=overlay, eid=eid)
    t2 = int(jnp.argmax(logits2[0, -1]))
    assert b.out_tokens == [t1, t2]


def test_mixed_unknown_expert_raises():
    """A typo'd expert name must fail loudly under mixed scheduling, not
    silently serve base weights (only __base__ gets the zero slot)."""
    cfg = get_smoke_config("qwen2_5_3b", n_units=1)
    api = build(cfg)
    base = api.init(jax.random.PRNGKey(0))
    reg = make_experts(api, base, n=1)
    eng = rapi.serve(api, RT, base, reg, max_batch=2, cache_len=32)
    bad = Request(uid=0, expert="expert_9",
                  prompt=jnp.ones((6,), jnp.int32), max_new_tokens=2)
    with pytest.raises(KeyError):
        eng.run([bad])


def test_stacked_buffers_invalidated_on_eviction():
    cfg = get_smoke_config("qwen2_5_3b", n_units=1)
    api = build(cfg)
    base = api.init(jax.random.PRNGKey(0))
    reg = make_experts(api, base, n=3)
    one = reg.get("expert0").nbytes(PACKED)
    cache = reg.device(int(one * 4.5))
    cache.stacked(("expert0", "expert1"))
    assert cache.stats.stack_builds == 1
    cache.stacked(("expert0", "expert1"))
    assert cache.stats.stack_hits == 1
    cache.fetch("expert2")                 # evicts expert0 -> stack dropped
    assert cache.stats.evictions >= 1
    assert cache.stats.stack_bytes == 0
    cache.stacked(("expert0", "expert1"))  # rebuilt
    assert cache.stats.stack_builds == 2


def test_packed_swap_bitwise_matches_dense_path():
    """The fused plane merge must reproduce the seed dense round-trip
    (decompress to {path: f32 delta}, add, cast) bit for bit."""
    from repro.peft.lora import _path_str
    cfg = get_smoke_config("qwen2_5_3b", n_units=1)
    api = build(cfg)
    base = api.init(jax.random.PRNGKey(0))
    reg = make_experts(api, base, n=1, scale=0.03)
    eng = rapi.serve(api, RT, base, reg, cache_len=32)
    got = eng._params_for("expert0")

    recon = reg.get("expert0").to_dense_tau()   # {nested}: tau_tilde
    flat_r, _ = jax.tree_util.tree_flatten_with_path(recon)
    tau_dense = {_path_str(p): np.asarray(l) for p, l in flat_r}
    flat, treedef = jax.tree_util.tree_flatten_with_path(base)
    want = []
    for path, leaf in flat:
        d = tau_dense.get(_path_str(path))
        if d is None:
            want.append(leaf)
        else:
            want.append((leaf.astype(jnp.float32)
                         + jnp.asarray(d).reshape(leaf.shape)
                         ).astype(leaf.dtype))
    want = jax.tree_util.tree_unflatten(treedef, want)
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_experts_change_behaviour():
    """A compressed expert must actually alter logits vs base."""
    cfg = get_smoke_config("qwen2_5_3b", n_units=1)
    api = build(cfg)
    base = api.init(jax.random.PRNGKey(0))
    reg = make_experts(api, base, n=1, scale=0.05)
    eng = rapi.serve(api, RT, base, reg, cache_len=32)
    p_exp = eng._params_for("expert0")
    toks = jnp.ones((1, 8), jnp.int32)
    l_base, _ = api.forward(base, {"tokens": toks}, RT)
    l_exp, _ = api.forward(p_exp, {"tokens": toks}, RT)
    assert float(jnp.max(jnp.abs(l_base - l_exp))) > 1e-3


def test_legacy_store_and_artifact_still_work():
    """Deprecated entry points: compress_expert + ExpertStore wired
    straight into ServeEngine keep serving (with warnings)."""
    from repro.peft import compress_expert
    from repro.peft.lora import _path_str
    from repro.peft.task_vector import task_vector
    cfg = get_smoke_config("qwen2_5_3b", n_units=1)
    api = build(cfg)
    base = api.init(jax.random.PRNGKey(0))
    store = ExpertStore()
    leaves, tdef = jax.tree_util.tree_flatten(base)
    keys = jax.random.split(jax.random.PRNGKey(100), len(leaves))
    ft = jax.tree_util.tree_unflatten(tdef, [
        (l.astype(jnp.float32)
         + 0.02 * jax.random.normal(k, l.shape)).astype(l.dtype)
        for l, k in zip(leaves, keys)])
    tau = task_vector(base, ft)
    flat, _ = jax.tree_util.tree_flatten_with_path(tau)
    with pytest.deprecated_call():
        art = compress_expert("expert0", "full",
                              {_path_str(p): l for p, l in flat},
                              density=0.2, alpha=1.0)
    store.put(art)
    with pytest.deprecated_call():
        eng = ServeEngine(api, RT, base, store,
                          EngineConfig(max_batch=2, cache_len=32))
    req = Request(uid=0, expert="expert0",
                  prompt=jnp.ones((6,), jnp.int32), max_new_tokens=2)
    eng.run([req])
    assert len(req.out_tokens) == 2


def test_export_import_expert_roundtrip(tmp_path):
    """Legacy checkpoint shims still work (now over Expert.save/load)."""
    from repro.checkpoint.manager import export_expert, import_expert
    cfg = get_smoke_config("qwen2_5_3b", n_units=1)
    api = build(cfg)
    base = api.init(jax.random.PRNGKey(0))
    leaves, tdef = jax.tree_util.tree_flatten(base)
    keys = jax.random.split(jax.random.PRNGKey(5), len(leaves))
    ft = jax.tree_util.tree_unflatten(tdef, [
        (l.astype(jnp.float32) + 0.01 * jax.random.normal(k, l.shape)
         ).astype(l.dtype) for l, k in zip(leaves, keys)])

    with pytest.deprecated_call():
        stats = export_expert(base, ft, str(tmp_path / "e.npz"), density=0.1)
    assert stats["ratio"] > 8.0   # paper: >= 8x
    with pytest.deprecated_call():
        taus, manifest = import_expert(str(tmp_path / "e.npz"))
    assert manifest["density"] == 0.1
    # decompressed values are ternary * scale
    anyleaf = next(iter(taus.values()))
    vals = np.unique(anyleaf)
    assert len(vals) <= 3


@pytest.mark.parametrize("kv_layout", ["dense", "paged"])
def test_packed_overlay_matches_materialized(monkeypatch, kv_layout):
    """The overlay branches a TPU takes — packed planes through
    ``grouped_delta_matmul``, the packed embedding-row gather and the
    packed tied LM head — serve the same tokens as the materialized sign
    stacks the CPU path builds by default."""
    import functools

    from repro.models import delta as delta_mod
    from repro.serve import engine as engine_mod

    cfg = get_smoke_config("qwen2_5_3b", n_units=2)
    assert cfg.tie_embeddings
    api = build(cfg)
    base = api.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(4)
    prompts = [jnp.asarray(rng.integers(1, cfg.vocab, 9), jnp.int32)
               for _ in range(5)]

    def serve(materialize):
        monkeypatch.setattr(engine_mod, "build_overlay", functools.partial(
            delta_mod.build_overlay, materialize=materialize))
        eng = rapi.serve(api, RT, base, make_experts(api, base, n=3,
                                                     scale=0.03),
                         max_batch=4, cache_len=32, kv_layout=kv_layout)
        reqs = [Request(uid=i, expert=f"expert{i % 3}", prompt=prompts[i],
                        max_new_tokens=5) for i in range(5)]
        eng.run(reqs)
        overlays = list(eng._overlays.values())
        assert overlays and all(
            (leaf.dense is None) == (not materialize)
            for ov in overlays
            for leaf in jax.tree_util.tree_leaves(
                ov, is_leaf=lambda x: isinstance(
                    x, (delta_mod.MatmulDelta, delta_mod.EmbedDelta)))
            if isinstance(leaf, (delta_mod.MatmulDelta,
                                 delta_mod.EmbedDelta)))
        assert eng.swap_summary()["n_swaps"] == 0
        return {r.uid: list(r.out_tokens) for r in reqs}

    assert serve(materialize=False) == serve(materialize=True)
