"""Perf hillclimbing lab (EXPERIMENTS.md §Perf).

Lowers dry-run cells with experiment knobs (sharding overrides, remat
policy, compression on/off, kernel form switches) and records the roofline
deltas, so every hypothesis -> change -> measure cycle is reproducible:

    PYTHONPATH=src python -m benchmarks.perf_lab --exp <name>

Each experiment writes benchmarks/results/perf/<name>.json.
"""

from __future__ import annotations

# XLA device count must be set before jax import (same rule as dryrun) —
# and scoped PER EXPERIMENT: the dry-run lowering experiments emulate the
# full 512-chip production pod, the sharded serving sweep needs the
# 8-device forced-host mesh, and everything else is single-device (a
# forced 512-device view makes eager CPU jax dispatch pathologically
# slow, which used to tax every serving/transport experiment).
import os
import sys

_POD_EXPS = ("compression_ablation", "rwkv_chunk", "llama4_prefill", "all")


def _device_count_for(argv) -> int:
    exp = None
    for i, a in enumerate(argv):
        if a == "--exp" and i + 1 < len(argv):
            exp = argv[i + 1]
        elif a.startswith("--exp="):
            exp = a.split("=", 1)[1]
    if exp in _POD_EXPS:
        return 512
    if exp == "sharded_serve":
        return 8
    return 1


os.environ.setdefault(
    "XLA_FLAGS",
    f"--xla_force_host_platform_device_count={_device_count_for(sys.argv)}")

import argparse
import dataclasses
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

OUT = os.path.join(os.path.dirname(__file__), "results", "perf")


def run_cell(arch: str, shape: str, multi_pod: bool = False,
             tcfg_override=None, cfg_override=None, rt_override=None,
             tag: str = "") -> dict:
    """lower_cell with knob injection."""
    import repro.launch.dryrun as dr
    from repro.configs import get_config
    from repro.configs.registry import normalize

    orig_train_cfg = dr._train_cfg_for
    orig_get = dr.get_config
    orig_runtime = dr.make_runtime
    try:
        if tcfg_override:
            def patched_tcfg(cfg, shape_, mp=False):
                t = orig_train_cfg(cfg, shape_, mp)
                return dataclasses.replace(t, **tcfg_override)
            dr._train_cfg_for = patched_tcfg
        if cfg_override:
            def patched_get(a):
                c = orig_get(a)
                if normalize(a) == normalize(arch):
                    c = dataclasses.replace(c, **cfg_override)
                return c
            dr.get_config = patched_get
        if rt_override:
            def patched_rt(mesh, cfg, gb=None):
                rt = orig_runtime(mesh, cfg, gb)
                return dataclasses.replace(rt, **rt_override)
            dr.make_runtime = patched_rt
        res = dr.lower_cell(arch, shape, multi_pod, extra_tags=tag)
    finally:
        dr._train_cfg_for = orig_train_cfg
        dr.get_config = orig_get
        dr.make_runtime = orig_runtime
    res["tag"] = tag
    return res


def summarize(res: dict) -> dict:
    from benchmarks.roofline import analyze_record
    a = analyze_record(res)
    a["collective_kinds"] = {k: v for k, v in res["collectives"].items()
                             if k not in ("ops", "total")}
    a["tag"] = res.get("tag", "")
    return a


def save_raw(name: str, records: list):
    os.makedirs(OUT, exist_ok=True)
    with open(os.path.join(OUT, f"{name}.json"), "w") as f:
        json.dump(records, f, indent=1, default=float)


def save(name: str, records: list):
    save_raw(name, records)
    for r in records:
        print(f"[{r['tag']:>28s}] comp={r['t_compute_s']:.3e}s "
              f"mem={r['t_memory_s']:.3e}s coll={r['t_collective_s']:.3e}s "
              f"bound={r['bottleneck']} roofline={r['roofline_frac']:.4f}")


def bench_update(fname: str, key: str, rec: dict):
    """Merge one experiment's record into a repo-root BENCH_*.json snapshot
    keyed by experiment, preserving the other experiments' entries (so
    e.g. BENCH_serve.json carries mixed_serve AND decode_loop side by
    side).  Legacy single-record snapshots are lifted under their tag."""
    path = os.path.join(os.path.dirname(__file__), "..", fname)
    data = {}
    if os.path.exists(path):
        try:
            with open(path) as f:
                data = json.load(f)
        except (OSError, json.JSONDecodeError):
            data = {}
    if "tag" in data:                      # legacy layout: one bare record
        data = {data["tag"]: data}
    data[key] = rec
    with open(path, "w") as f:
        json.dump(data, f, indent=1, default=float)


# ---------------------------------------------------------------------------
# Experiments
# ---------------------------------------------------------------------------


def exp_compression_ablation():
    """Paper-representative cell: multi-pod train with the EF-ternary
    cross-pod exchange ON (beyond-paper) vs OFF (paper-faithful dense DP
    baseline).  Hypothesis: compression cuts cross-pod wire bytes ~16x and
    the total collective term measurably."""
    rows = []
    for on, tag in ((False, "dense-crosspod-baseline"),
                    (True, "ef-ternary-crosspod")):
        from repro.core.gradient_compression import GradCompressionConfig
        r = run_cell("qwen3_32b", "train_4k", multi_pod=True,
                     tcfg_override={"grad_compression":
                                    GradCompressionConfig(enabled=on,
                                                          density=0.05)},
                     tag=tag)
        rows.append(summarize(r))
    save("compression_ablation", rows)


def exp_rwkv_chunk():
    """rwkv6 train is the worst-roofline cell: the chunked time-mix
    materialises a [B,L,L,H,dh] decay tensor.  Hypothesis: the matmul-form
    intra-chunk product (stabilised exp factored into the operands) plus a
    smaller chunk cuts the memory term by ~L/dh."""
    rows = []
    for impl, chunk, tag in (("einsum", 64, "baseline-einsum-L64"),
                             ("matmul", 64, "matmul-form-L64"),
                             ("matmul", 32, "matmul-form-L32"),
                             ("matmul", 128, "matmul-form-L128")):
        r = run_cell("rwkv6_3b", "train_4k",
                     rt_override={"rwkv_chunk": chunk,
                                  "rwkv_impl": impl},
                     tag=tag)
        rows.append(summarize(r))
    save("rwkv_chunk", rows)


def exp_llama4_prefill():
    """Most collective-bound cell.  Hypotheses tested:
    h1: replicated-attention (head_tp=False) causes per-layer activation
        all-gathers -> padded head-TP (40 heads over 16 shards) trades 20%
        pad compute for removing them.
    h2: remat policy 'none' (prefill has no backward) — the unit-remat
        wrapper is wasted here."""
    from repro.configs.base import ShardingOverrides
    rows = []
    r = run_cell("llama4_maverick_400b", "prefill_32k", tag="baseline")
    rows.append(summarize(r))
    r = run_cell("llama4_maverick_400b", "prefill_32k",
                 cfg_override={"sharding": ShardingOverrides(
                     head_tp=True, expert_parallel=True)},
                 tag="padded-head-tp")
    rows.append(summarize(r))
    r = run_cell("llama4_maverick_400b", "prefill_32k",
                 rt_override={"remat_policy": "none"}, tag="no-remat")
    rows.append(summarize(r))
    save("llama4_prefill", rows)


def _time(fn, reps=3):
    """Best-of-reps wall time; blocks on all jax leaves."""
    best = float("inf")
    out = None
    for _ in range(reps):
        t0 = time.perf_counter()
        out = fn()
        jax.block_until_ready(jax.tree_util.tree_leaves(
            out, is_leaf=lambda x: hasattr(x, "pos")))
        best = min(best, time.perf_counter() - t0)
    return best, out


def _synth_expert(n_params=50_000_000, seed=0):
    """Synthetic >=50M-param task vector shaped like a transformer block."""
    rng = np.random.default_rng(seed)
    d = 4096
    tau, total, i = {}, 0, 0
    while total < n_params:
        tau[f"blocks/block{i}/w"] = jnp.asarray(
            rng.normal(0, 0.02, (d, d)).astype(np.float32))
        total += d * d
        i += 1
    return tau, total


def exp_compress_swap():
    """Tentpole measurement: single-pass streaming compression vs the seed
    per-leaf quantile path, and packed-resident vs dense-resident expert
    capacity/swap parity, on CPU interpret mode — expert lifecycle through
    ``repro.api`` (method='exact' is the seed path, 'streaming' the PR-1
    pipeline)."""
    from repro import api as capi
    from repro.expert import PACKED
    from repro.kernels.ops import apply_ternary_delta_flat

    density, alpha = 0.05, 1.0
    tau, n_params = _synth_expert()
    rec = {"tag": "compress_swap", "n_params": n_params,
           "density": density}

    # --- compression throughput: seed per-leaf loop vs streaming ---------
    t_seed, packed_seed = _time(
        lambda: capi.compress(tau, density=density, alpha=alpha,
                              method="exact").as_(PACKED), reps=2)
    t_stream, packed_new = _time(
        lambda: capi.compress(tau, density=density, alpha=alpha,
                              method="streaming").as_(PACKED), reps=2)
    rec["compress_seed_s"] = t_seed
    rec["compress_stream_s"] = t_stream
    rec["compress_speedup_x"] = t_seed / t_stream
    rec["compress_stream_gbps"] = n_params * 4 / t_stream / 1e9
    for k in tau:
        np.testing.assert_allclose(float(packed_new[k].scale),
                                   float(packed_seed[k].scale), rtol=1e-4)

    # --- packed-resident capacity under a fixed HBM budget ---------------
    registry = capi.registry()
    small = {k: v[:512, :512] for k, v in list(tau.items())[:2]}
    n_experts = 24
    for i in range(n_experts):
        rng = np.random.default_rng(100 + i)
        e = {k: v + jnp.asarray(rng.normal(0, 0.01, v.shape), jnp.float32)
             for k, v in small.items()}
        registry.add(capi.compress(e, name=f"e{i}", density=density,
                                   alpha=alpha))
    dense_bytes = sum(int(np.prod(v.shape)) * 4 for v in small.values())
    budget = int(dense_bytes * 1.5)        # seed layout: one dense expert
    cache = registry.device(budget)
    for i in range(n_experts):
        cache.fetch(f"e{i}")
    rec["budget_bytes"] = budget
    rec["resident_packed"] = len(cache.resident())
    rec["resident_dense_equiv"] = max(1, budget // dense_bytes)
    rec["capacity_multiplier_x"] = (rec["resident_packed"]
                                    / rec["resident_dense_equiv"])

    # --- swap latency + numerical parity: fused plane merge vs dense -----
    art = registry.get("e0")
    base = {k: jnp.asarray(np.random.default_rng(1).normal(0, 1, v.shape),
                           jnp.float32) for k, v in small.items()}

    def merge_packed():
        return {k: apply_ternary_delta_flat(base[k], art.packed[k])
                for k in base}

    def merge_dense():
        taud = art.to_dense_tau()
        return {k: (base[k].astype(jnp.float32)
                    + jnp.asarray(taud[k]).reshape(base[k].shape)
                    ).astype(base[k].dtype) for k in base}

    t_packed, merged_p = _time(merge_packed)
    t_dense, merged_d = _time(merge_dense)
    for k in base:
        np.testing.assert_array_equal(np.asarray(merged_p[k]),
                                      np.asarray(merged_d[k]))
    rec["swap_packed_s"] = t_packed
    rec["swap_dense_s"] = t_dense
    rec["swap_bitwise_identical"] = True
    rec["packed_expert_bytes"] = art.nbytes(PACKED)
    rec["dense_expert_bytes"] = dense_bytes

    save_raw("compress_swap", [rec])
    with open(os.path.join(os.path.dirname(__file__), "..",
                           "BENCH_compress.json"), "w") as f:
        json.dump(rec, f, indent=1, default=float)
    print(f"compress: seed={t_seed:.2f}s stream={t_stream:.2f}s "
          f"({rec['compress_speedup_x']:.1f}x); "
          f"capacity: {rec['resident_packed']} packed vs "
          f"{rec['resident_dense_equiv']} dense "
          f"({rec['capacity_multiplier_x']:.0f}x); "
          f"swap: packed={t_packed*1e3:.1f}ms dense={t_dense*1e3:.1f}ms "
          f"bitwise_identical={rec['swap_bitwise_identical']}")
    assert rec["compress_speedup_x"] >= 3.0, rec["compress_speedup_x"]
    assert rec["capacity_multiplier_x"] >= 8.0, rec["capacity_multiplier_x"]


def _serve_fixture(n_experts=4, density=0.2, scale=0.02):
    """Smoke LM + ComPEFT Expert artifacts (fake fine-tunes of base)."""
    import jax
    import jax.numpy as jnp

    from repro import api as capi
    from repro.configs import get_smoke_config
    from repro.models import Runtime, build

    rt = Runtime(attn_chunk_q=16, attn_chunk_k=16, remat_policy="none")
    cfg = get_smoke_config("qwen2_5_3b", n_units=1)
    api = build(cfg)
    base = api.init(jax.random.PRNGKey(0))
    experts = []
    for i in range(n_experts):
        leaves, tdef = jax.tree_util.tree_flatten(base)
        keys = jax.random.split(jax.random.PRNGKey(100 + i), len(leaves))
        ft = jax.tree_util.tree_unflatten(tdef, [
            (l.astype(jnp.float32)
             + scale * jax.random.normal(k, l.shape)).astype(l.dtype)
            for l, k in zip(leaves, keys)])
        experts.append(capi.compress(base, ft, name=f"expert{i}",
                                     density=density, alpha=1.0))
    return api, rt, cfg, base, experts


def exp_mixed_serve(smoke: bool = False):
    """Tentpole measurement: continuous mixed-expert zero-merge serving vs
    the PR-1 merge-on-swap path on a round-robin request stream.

    The stream interleaves 4 experts (the paper's many-experts-per-device
    scenario).  The grouped baseline must split it into per-expert batches
    and pay a full-model merge per expert; the mixed scheduler serves one
    heterogeneous wave through the grouped ternary kernels with zero
    merges.  Also checks the correctness contract: mixed-wave outputs are
    bit-identical (token-exact AND prefill-logit-exact) to serving each
    expert separately through the same zero-merge path.
    """
    import jax.numpy as jnp

    from repro import api as capi
    from repro.serve import Request

    n_experts = 4
    n_reqs = 8 if smoke else 16
    max_new = 4 if smoke else 8
    prompt_len = 12
    api, rt, cfg, base, experts = _serve_fixture(n_experts=n_experts)
    rng = np.random.default_rng(0)
    prompts = [jnp.asarray(rng.integers(1, cfg.vocab, prompt_len), jnp.int32)
               for _ in range(n_reqs)]

    def mk_reqs():
        # round-robin arrival over the expert set
        return [Request(uid=i, expert=f"expert{i % n_experts}",
                        prompt=prompts[i], max_new_tokens=max_new)
                for i in range(n_reqs)]

    def run(scheduling):
        # fresh registry per run: each engine gets its own device tier, so
        # swap stats and promotions are not shared across measurements
        eng = capi.serve(api, rt, base, capi.registry(experts=experts),
                         max_batch=n_reqs, cache_len=64,
                         scheduling=scheduling)
        # warm pass with the identical workload: compiles every step
        # executable both paths will use, so the timed pass is steady-state
        eng.run(mk_reqs())
        eng._merged_name = None    # drop the warmed merge cache
        eng._merged_params = None
        eng.swap_log.clear()
        eng.wave_log.clear()
        reqs = mk_reqs()
        t0 = time.perf_counter()
        eng.run(reqs)
        dt = time.perf_counter() - t0
        return dt, eng, reqs

    t_grouped, eng_g, reqs_grouped = run("grouped")
    t_mixed, eng_m, reqs_mixed = run("mixed")

    tokens = n_reqs * max_new
    rec = {"tag": "mixed_serve", "n_experts": n_experts, "n_reqs": n_reqs,
           "max_new_tokens": max_new, "tokens": tokens,
           "grouped_s": t_grouped, "mixed_s": t_mixed,
           "grouped_tok_s": tokens / t_grouped,
           "mixed_tok_s": tokens / t_mixed,
           "decode_speedup_x": t_grouped / t_mixed,
           "grouped_summary": eng_g.swap_summary(),
           "mixed_summary": eng_m.swap_summary()}

    # correctness: mixed wave == sequential per-expert zero-merge serving
    reqs_seq = mk_reqs()
    eng_s = capi.serve(api, rt, base, capi.registry(experts=experts),
                       max_batch=n_reqs, cache_len=64)
    for e in range(n_experts):
        eng_s.run([r for r in reqs_seq if r.expert == f"expert{e}"])
    tok_mixed = {r.uid: r.out_tokens for r in reqs_mixed}
    tok_seq = {r.uid: r.out_tokens for r in reqs_seq}
    rec["mixed_equals_sequential"] = tok_mixed == tok_seq
    assert rec["mixed_equals_sequential"], "mixed wave diverged"

    save_raw("mixed_serve", [rec])
    bench_update("BENCH_serve.json", "mixed_serve", rec)
    print(f"serve: grouped={t_grouped:.2f}s ({rec['grouped_tok_s']:.1f} "
          f"tok/s, {rec['grouped_summary']['n_swaps']} merges) "
          f"mixed={t_mixed:.2f}s ({rec['mixed_tok_s']:.1f} tok/s, "
          f"{rec['mixed_summary']['n_waves']} waves, 0 merges); "
          f"speedup={rec['decode_speedup_x']:.2f}x; "
          f"parity={rec['mixed_equals_sequential']}")
    if not smoke:
        assert rec["decode_speedup_x"] >= 2.0, rec["decode_speedup_x"]


def exp_decode_loop(smoke: bool = False):
    """Tentpole measurement: device-resident chunked decode (scan-compiled
    wave loop, one host sync per K steps, donated KV cache) vs the eager
    per-token loop (one dispatch + one blocking ``np.asarray`` sync per
    generated token).

    Sweeps K ∈ {1, 4, 8, 16, 32} against eager at B ∈ {1, 8}, mixed and
    grouped scheduling, on a request stream with more requests than slots
    so mid-wave admissions (slot refills) are exercised.  Two gates:

    * **parity** — greedy chunked decode must reproduce the eager loop's
      tokens exactly, per request, for every (scheduling, B, K) cell,
      admissions included (asserted in smoke mode too);
    * **speedup** — ≥ 1.5x decode tokens/s over eager at B=8, K=16
      (full runs only).
    """
    import jax.numpy as jnp

    from repro import api as capi
    from repro.serve import Request

    n_experts = 4
    max_new = 8 if smoke else 32     # decode-dominated sweep workload
    adm_max_new = 8                  # admission workload: 2 fills per slot
    prompt_len = 12
    cache_len = 96
    api, rt, cfg, base, experts = _serve_fixture(n_experts=n_experts)
    rng = np.random.default_rng(0)
    prompt_pool = [jnp.asarray(rng.integers(1, cfg.vocab, prompt_len),
                               jnp.int32) for _ in range(16)]

    def mk_reqs(n, new_tokens):
        return [Request(uid=i, expert=f"expert{i % n_experts}",
                        prompt=prompt_pool[i], max_new_tokens=new_tokens)
                for i in range(n)]

    def engine(sched, B, K):
        return capi.serve(api, rt, base, capi.registry(experts=experts),
                          max_batch=B, cache_len=cache_len,
                          scheduling=sched, decode_chunk=K)

    def run_timed(sched, B, K):
        """One wave-sized batch (n_reqs = B), warm pass first, so the
        timed pass isolates steady-state decode throughput."""
        eng = engine(sched, B, K)
        eng.run(mk_reqs(B, max_new))   # warm: compiles every executable
        reqs = mk_reqs(B, max_new)
        t0 = time.perf_counter()
        eng.run(reqs)
        dt = time.perf_counter() - t0
        return dt, {r.uid: list(r.out_tokens) for r in reqs}

    def run_admissions(sched, B, K):
        """2x oversubscribed queue: finished slots refill mid-wave."""
        eng = engine(sched, B, K)
        reqs = mk_reqs(2 * B, adm_max_new)
        eng.run(reqs)
        admitted = sum(w["admitted"] for w in eng.wave_log)
        return {r.uid: list(r.out_tokens) for r in reqs}, admitted

    scheds = ("mixed",) if smoke else ("mixed", "grouped")
    batches = (8,) if smoke else (1, 8)
    chunk_sizes = (8,) if smoke else (1, 4, 8, 16, 32)
    rows, parity = [], True
    tok_s = {}
    for sched in scheds:
        for B in batches:
            t_eager, tok_eager = run_timed(sched, B, 0)
            total = sum(len(v) for v in tok_eager.values())
            tok_s[(sched, B, 0)] = total / t_eager
            rows.append({"sched": sched, "B": B, "K": 0, "mode": "eager",
                         "tokens": total, "seconds": t_eager,
                         "tok_s": total / t_eager})
            for K in chunk_sizes:
                t, toks = run_timed(sched, B, K)
                ok = toks == tok_eager
                parity = parity and ok
                tok_s[(sched, B, K)] = total / t
                rows.append({"sched": sched, "B": B, "K": K,
                             "mode": "chunked", "tokens": total,
                             "seconds": t, "tok_s": total / t,
                             "speedup_vs_eager_x": t_eager / t,
                             "token_parity_vs_eager": ok})
                print(f"[{sched:>7s} B={B} K={K:>2d}] "
                      f"{total / t:8.1f} tok/s "
                      f"({t_eager / t:4.2f}x eager) parity={ok}")

    # parity gate WITH mid-wave admissions: greedy chunked decode must
    # reproduce the eager loop's per-request tokens exactly while slots
    # are being refilled (spliced prefills folded into the device state)
    adm_B = 8
    adm_parity = True
    for sched in scheds:
        tok_eager, _ = run_admissions(sched, adm_B, 0)
        for K in chunk_sizes:
            toks, admitted = run_admissions(sched, adm_B, K)
            ok = toks == tok_eager
            adm_parity = adm_parity and ok
            print(f"[{sched:>7s} admissions K={K:>2d}] refills={admitted} "
                  f"parity={ok}")

    gate_B, gate_K = (8, 8) if smoke else (8, 16)
    speedup = tok_s[("mixed", gate_B, gate_K)] / tok_s[("mixed", gate_B, 0)]
    rec = {"tag": "decode_loop", "n_experts": n_experts,
           "max_new_tokens": max_new, "prompt_len": prompt_len,
           "rows": rows, "token_parity": parity,
           "admission_token_parity": adm_parity,
           "gate": {"B": gate_B, "K": gate_K,
                    "speedup_vs_eager_x": speedup}}
    save_raw("decode_loop", [rec])
    bench_update("BENCH_serve.json", "decode_loop", rec)
    print(f"decode_loop: parity={parity} (admissions: {adm_parity}); "
          f"chunked K={gate_K} B={gate_B} is {speedup:.2f}x eager decode")
    assert parity, "chunked decode diverged from the eager loop"
    assert adm_parity, "chunked decode diverged under mid-wave admissions"
    if not smoke:
        assert speedup >= 1.5, speedup


def exp_serve_load(smoke: bool = False):
    """Tentpole measurement: paged KV + SLO-aware scheduling under seeded
    open-loop traffic (Poisson arrivals + bursts, Zipf expert popularity,
    short/long prompt and output mix — :mod:`benchmarks.traffic`).

    Three engine configurations serve the same workload:

    * ``dense_fifo`` — left-padded KV slots + FIFO admission (the
      historical engine, parity baseline);
    * ``paged_fifo`` — block-table KV, same FIFO order;
    * ``paged_affinity`` — block-table KV + priority/deadline scheduler
      with expert-affinity wave packing (canonical stack tuples).

    Gates (smoke included unless noted):

    * **token parity** — all three produce identical per-request tokens,
      greedy AND sampled (streams are keyed by (seed, uid, draw), so they
      are invariant to KV layout, wave composition and admission timing);
    * **affinity stack hits** — the affinity scheduler's stacked-plane
      hit-rate beats FIFO's on the same Zipf traffic;
    * **determinism** — ``generate()`` replays bit-identically and a
      repeated paged_affinity run reproduces tokens and statuses;
    * **latency/throughput** (full runs only) — paged_affinity p99 TTFT
      <= dense_fifo and tokens/s >= dense_fifo at B >= 16.
    """
    from benchmarks import traffic
    from repro import api as capi

    if smoke:
        n_experts, B, max_stack = 6, 6, 3
        tcfg = traffic.TrafficConfig(
            seed=11, n_requests=24, base_rate=60.0, burst_every_s=0.2,
            burst_duration_s=0.05, burst_rate_x=4.0, n_experts=n_experts,
            zipf_alpha=1.2, prompt_len_short=6, prompt_len_long=24,
            long_frac=0.25, max_new_short=4, max_new_long=8,
            long_out_frac=0.25, vocab=512)
        cache_len = 48
    else:
        n_experts, B, max_stack = 8, 16, 4
        tcfg = traffic.TrafficConfig(
            seed=11, n_requests=96, base_rate=24.0, burst_every_s=2.0,
            burst_duration_s=0.5, burst_rate_x=4.0, n_experts=n_experts,
            zipf_alpha=1.1, prompt_len_short=6, prompt_len_long=40,
            long_frac=0.25, max_new_short=8, max_new_long=16,
            long_out_frac=0.25, vocab=512)
        cache_len = 64
    api, rt, cfg, base, experts = _serve_fixture(n_experts=n_experts)

    CONFIGS = {
        "dense_fifo": dict(kv_layout="dense", scheduler="fifo"),
        "paged_fifo": dict(kv_layout="paged", scheduler="fifo"),
        "paged_affinity": dict(kv_layout="paged", scheduler="affinity"),
    }

    def engine(name, **samp):
        kw = dict(CONFIGS[name])
        if kw["kv_layout"] == "paged":
            kw["kv_block_size"] = 8
        return capi.serve(api, rt, base, capi.registry(experts=experts),
                          max_batch=B, cache_len=cache_len,
                          max_stack=max_stack, **kw, **samp)

    def workload(immediate=False):
        reqs = traffic.generate(tcfg)
        if immediate:
            for r in reqs:
                r.arrival_s = 0.0
        return reqs

    def toks(reqs):
        return {r.uid: list(r.out_tokens) for r in reqs}

    # -- phase 1: three-way token parity, greedy and sampled -------------
    parity = {}
    for samp in ({}, {"temperature": 0.8, "top_k": 5, "seed": 7}):
        label = "sampled" if samp else "greedy"
        outs = {}
        for name in CONFIGS:
            reqs = engine(name, **samp).run(workload(immediate=True))
            outs[name] = toks(reqs)
        ok = (outs["dense_fifo"] == outs["paged_fifo"]
              == outs["paged_affinity"])
        parity[label] = ok
        print(f"[serve_load] {label} parity "
              f"dense_fifo == paged_fifo == paged_affinity: {ok}")

    # -- phase 2: timed open-loop replay (warm pass compiles first) ------
    results = {}
    for name in ("dense_fifo", "paged_affinity"):
        eng = engine(name)
        eng.run(workload(immediate=True))        # warm: compile everything
        eng.swap_log.clear()
        eng.wave_log.clear()
        eng.cache.stats.stack_hits = 0
        eng.cache.stats.stack_builds = 0
        reqs = workload()
        eng.run(reqs)
        s = eng.swap_summary()
        results[name] = {"load": traffic.summarize(reqs),
                         "stack_hit_rate": s["stack_hit_rate"],
                         "stack_hits": s.get("stack_hits", 0),
                         "stack_builds": s.get("stack_builds", 0),
                         "scheduler": s["scheduler"], "kv": s["kv"],
                         "n_waves": s["n_waves"], "admitted": s["admitted"]}
        ld = results[name]["load"]
        print(f"[serve_load] {name:>15s}: ttft p50={ld['ttft_p50_s']:.3f}s "
              f"p99={ld['ttft_p99_s']:.3f}s tok/s={ld['tokens_per_s']:.1f} "
              f"stack_hit_rate={s['stack_hit_rate']:.2f} "
              f"waves={s['n_waves']}")

    # -- phase 3: determinism -------------------------------------------
    g1, g2 = traffic.generate(tcfg), traffic.generate(tcfg)
    gen_ok = all(
        a.uid == b.uid and a.expert == b.expert
        and a.arrival_s == b.arrival_s and a.priority == b.priority
        and a.deadline_s == b.deadline_s
        and a.max_new_tokens == b.max_new_tokens
        and np.array_equal(np.asarray(a.prompt), np.asarray(b.prompt))
        for a, b in zip(g1, g2)) and len(g1) == len(g2)
    ra = engine("paged_affinity").run(workload())
    rb = engine("paged_affinity").run(workload())
    replay_ok = (toks(ra) == toks(rb)
                 and [r.status for r in ra] == [r.status for r in rb])
    print(f"[serve_load] generator determinism={gen_ok} "
          f"replay determinism={replay_ok}")

    rec = {"tag": "serve_load", "smoke": smoke, "n_experts": n_experts,
           "max_batch": B, "max_stack": max_stack,
           "traffic": dataclasses.asdict(tcfg),
           "token_parity": parity, "generator_deterministic": gen_ok,
           "replay_deterministic": replay_ok, "results": results}
    save_raw("serve_load", [rec])
    bench_update("BENCH_serve.json", "serve_load", rec)

    assert parity["greedy"], "paged/scheduled engines diverged (greedy)"
    assert parity["sampled"], "paged/scheduled engines diverged (sampled)"
    assert gen_ok, "traffic generator is not deterministic"
    assert replay_ok, "seeded replay is not deterministic"
    hit_fifo = results["dense_fifo"]["stack_hit_rate"]
    hit_aff = results["paged_affinity"]["stack_hit_rate"]
    if smoke:
        assert hit_aff >= hit_fifo, (hit_aff, hit_fifo)
    else:
        assert hit_aff > hit_fifo, (hit_aff, hit_fifo)
        ld_d = results["dense_fifo"]["load"]
        ld_a = results["paged_affinity"]["load"]
        assert ld_a["ttft_p99_s"] <= ld_d["ttft_p99_s"], (ld_a, ld_d)
        assert ld_a["tokens_per_s"] >= ld_d["tokens_per_s"], (ld_a, ld_d)


def exp_remote_fetch(smoke: bool = False):
    """Tentpole measurement: the paper's communication-cost argument as a
    measured curve.

    Publishes experts through a :class:`SimulatedNetworkTransport` and
    sweeps wire representation (DENSE bf16 baseline / PACKED bitplanes /
    GOLOMB streams) x link speed, measuring bytes-on-wire and
    **time-to-first-token**: a cold request whose expert must be fetched
    over the link before the wave can prefill.  Per configuration the
    engine is first warmed on a different expert (same shapes), so the
    timed run isolates fetch + decode + promote + prefill — not XLA
    compilation.  Gate: GOLOMB TTFT beats DENSE on the slow link, and the
    fetched planes are bit-identical to the locally built ones.
    """
    import jax.numpy as jnp

    from repro import api as capi
    from repro.expert import DENSE, GOLOMB, PACKED
    from repro.serve import Request
    from repro.transport import InMemoryTransport, SimulatedNetworkTransport

    prompt_len = 12
    api, rt, cfg, base, experts = _serve_fixture(n_experts=3)
    ref_packed = {e.name: e.packed for e in experts}
    rng = np.random.default_rng(0)
    prompt = jnp.asarray(rng.integers(1, cfg.vocab, prompt_len), jnp.int32)

    # The slow link is a ~2 Mbit/s high-latency consumer line — the regime
    # the paper's retrieval-over-the-network claim targets.  On this
    # fixture the dense bf16 blob takes ~0.5 s of pure transfer there,
    # so TTFT differences dwarf CPU timing noise.
    links = {"slow": dict(bandwidth_bps=0.25e6, latency_s=0.1),
             "fast": dict(bandwidth_bps=1e9, latency_s=0.002)}
    rows = []
    identical = True
    for rep in (DENSE, PACKED, GOLOMB):
        inner = InMemoryTransport()
        pubs = {e.name: inner.publish(e, rep=rep) for e in experts}
        for link, lp in links.items():
            tr = SimulatedNetworkTransport(inner=inner, seed=0, **lp)
            reg = capi.registry(transport=tr)
            eng = capi.serve(api, rt, base, reg, max_batch=1, cache_len=64)
            # warm: compiles prefill/decode on expert0's (identical) shapes
            eng.run([Request(uid=0, expert="expert0", prompt=prompt,
                             max_new_tokens=1)])
            # TTFT = cold request whose expert must cross the link first;
            # best-of-2 over two distinct cold experts to shed CPU noise
            ttft, first_token = float("inf"), None
            for uid, cold in ((1, "expert1"), (2, "expert2")):
                r = Request(uid=uid, expert=cold, prompt=prompt,
                            max_new_tokens=1)
                t0 = time.perf_counter()
                eng.run([r])
                dt = time.perf_counter() - t0
                if dt < ttft:
                    ttft, first_token = dt, list(r.out_tokens)
            fetched = reg.get("expert1").packed
            for p, pt in ref_packed["expert1"].items():
                ok = ((np.asarray(pt.pos) == np.asarray(fetched[p].pos)).all()
                      and (np.asarray(pt.neg)
                           == np.asarray(fetched[p].neg)).all()
                      and float(pt.scale) == float(fetched[p].scale))
                identical = identical and bool(ok)
            reg.close()           # stop this config's prefetch workers
            rows.append({"rep": rep, "link": link,
                         "bytes_on_wire": pubs["expert1"]["nbytes"],
                         "ttft_s": ttft,
                         "link_bandwidth_bps": lp["bandwidth_bps"],
                         "link_latency_s": lp["latency_s"],
                         "first_token": first_token})
            print(f"[{rep:>6s} | {link:>4s}] "
                  f"wire={rows[-1]['bytes_on_wire']:>9,d} B  "
                  f"ttft={ttft*1e3:8.1f} ms")

    by = {(r["rep"], r["link"]): r for r in rows}
    rec = {"tag": "remote_fetch", "rows": rows,
           "bit_identical": identical,
           "golomb_vs_dense_wire_x": (by[(DENSE, "slow")]["bytes_on_wire"]
                                      / by[(GOLOMB, "slow")]["bytes_on_wire"]),
           "golomb_vs_dense_slow_ttft_x": (by[(DENSE, "slow")]["ttft_s"]
                                           / by[(GOLOMB, "slow")]["ttft_s"])}
    save_raw("remote_fetch", [rec])
    bench_update("BENCH_transport.json", "remote_fetch", rec)
    print(f"remote_fetch: golomb wire is "
          f"{rec['golomb_vs_dense_wire_x']:.1f}x smaller than dense; "
          f"slow-link TTFT {rec['golomb_vs_dense_slow_ttft_x']:.2f}x faster; "
          f"bit_identical={identical}")
    assert identical, "fetched expert diverged from local planes"
    assert rec["golomb_vs_dense_slow_ttft_x"] > 1.0, rec
    if not smoke:
        assert rec["golomb_vs_dense_wire_x"] >= 8.0, rec


def exp_chaos_serve(smoke: bool = False):
    """Robustness gate: serving under an injected fault schedule.

    Publishes 4 experts through a :class:`ChaosTransport` whose schedule
    injects one timeout (expert1), one payload bit-flip (expert2) and a
    persistent replica blackout (expert3) into a round-robin request
    stream, with a 1-failure quarantine trip.  Gates (all deterministic
    under the seed):

    * every healthy request completes with tokens **bit-identical** to
      the no-fault run — transient faults are absorbed by retry/refetch
      without touching decode results;
    * every expert3 request ends in the terminal ``FAILED`` status with
      error detail, returned via the normal results path (the engine
      degrades per-request instead of crashing the wave);
    * ``SwapStats`` match the schedule exactly: 5 transport retries
      (1 timeout + 1 checksum refetch + 3 blackout retries), 1 quarantine
      trip, ≥1 prefetch error — and a second chaos run reproduces the
      same tokens, statuses and fired-fault log bit-for-bit.
    """
    import jax.numpy as jnp

    from repro import api as capi
    from repro.serve import DONE, FAILED, Request
    from repro.transport import (ChaosFault, ChaosTransport,
                                 InMemoryTransport)

    n_experts = 4
    n_reqs = 8 if smoke else 16
    max_new = 4 if smoke else 8
    # full mode serves two waves of 8, so the second wave's expert3 rows
    # arrive through the continuous-admission path while quarantined
    max_batch = 8
    prompt_len = 8
    api, rt, cfg, base, experts = _serve_fixture(n_experts=n_experts)
    rng = np.random.default_rng(0)
    prompts = [jnp.asarray(rng.integers(1, cfg.vocab, prompt_len), jnp.int32)
               for _ in range(n_reqs)]

    def mk_reqs():
        return [Request(uid=i, expert=f"expert{i % n_experts}",
                        prompt=prompts[i], max_new_tokens=max_new)
                for i in range(n_reqs)]

    schedule = [ChaosFault("expert1", 0, "timeout"),
                ChaosFault("expert2", 0, "bitflip")]

    def run(chaotic):
        inner = InMemoryTransport()
        for e in experts:
            capi.publish(e, inner)
        tr = (ChaosTransport(inner, faults=schedule, blackout=["expert3"],
                             seed=0) if chaotic else inner)
        reg = capi.registry(transport=tr, quarantine_after=1,
                            quarantine_probe_s=1000.0)
        eng = capi.serve(api, rt, base, reg, max_batch=max_batch,
                         cache_len=64)
        reqs = mk_reqs()
        t0 = time.perf_counter()
        eng.run(reqs)
        dt = time.perf_counter() - t0
        reg.close()
        return dt, eng, reqs, tr

    t_base, eng_b, base_reqs, _ = run(chaotic=False)
    assert all(r.status == DONE for r in base_reqs)
    base_toks = {r.uid: list(r.out_tokens) for r in base_reqs}

    t_chaos, eng_c, reqs, tr = run(chaotic=True)
    healthy = [r for r in reqs if r.expert != "expert3"]
    dead = [r for r in reqs if r.expert == "expert3"]
    stats = eng_c.swap_summary()
    parity = all(r.out_tokens == base_toks[r.uid] for r in healthy)

    # determinism: an identical chaos run reproduces everything.  The
    # fired log is compared order-independently: per-name fault order is
    # deterministic (per-name fetch counters), but the prefetch pool may
    # interleave fetches of DIFFERENT names either way round.
    def fired_sorted(t):
        return sorted(t.fired(), key=lambda f: (f["name"], f["fetch"]))

    _, eng_c2, reqs2, tr2 = run(chaotic=True)
    reproduced = (
        [(r.uid, r.status, list(r.out_tokens)) for r in reqs]
        == [(r.uid, r.status, list(r.out_tokens)) for r in reqs2]
        and fired_sorted(tr) == fired_sorted(tr2)
        and {k: eng_c2.swap_summary()[k]
             for k in ("retries", "quarantines", "failed")}
        == {k: stats[k] for k in ("retries", "quarantines", "failed")})

    rec = {"tag": "chaos_serve", "n_reqs": n_reqs, "max_batch": max_batch,
           "max_new_tokens": max_new, "baseline_s": t_base,
           "chaos_s": t_chaos,
           "healthy": len(healthy), "failed": len(dead),
           "healthy_bit_identical": parity,
           "all_failed_typed": all(r.status == FAILED and r.error
                                   and not r.out_tokens for r in dead),
           "retries": stats["retries"],
           "quarantines": stats["quarantines"],
           "prefetch_errors": stats["prefetch_errors"],
           "fired": tr.fired(),
           "health": eng_c.registry.health(),
           "deterministic": reproduced}
    save_raw("chaos_serve", [rec])
    bench_update("BENCH_serve.json", "chaos_serve", rec)
    print(f"chaos_serve: {len(healthy)} healthy (bit_identical={parity}), "
          f"{len(dead)} failed, retries={rec['retries']}, "
          f"quarantines={rec['quarantines']}, "
          f"prefetch_errors={rec['prefetch_errors']}, "
          f"deterministic={reproduced}")
    assert all(r.status == DONE for r in healthy), rec
    assert parity, "healthy requests diverged from the no-fault run"
    assert rec["all_failed_typed"], rec
    assert stats["failed"] == len(dead) == n_reqs // n_experts, rec
    # the schedule, exactly: 1 timeout retry + 1 checksum refetch +
    # (max_attempts-1)=3 blackout retries; ONE quarantine trip keeps every
    # later expert3 fetch off the wire
    assert rec["retries"] == 5, rec
    assert rec["quarantines"] == 1, rec
    assert rec["prefetch_errors"] >= 1, rec
    assert [f["kind"] for f in rec["fired"]].count("blackout") == 4, rec
    assert reproduced, "chaos run is not reproducible under the seed"


def exp_chaos_cdn(smoke: bool = False):
    """Robustness gate: the replicated expert CDN losing a replica
    mid-fetch.

    A 3-replica heterogeneous fleet (fast / medium / slow simulated
    links, each behind a :class:`ChaosTransport`) serves a round-robin
    request stream with ``replication_factor=3``.  The *fast* replica —
    the one EWMA selection always tries first — blacks out at per-name
    op index 2: the probe and the first leaf range of every expert are
    delivered, the rest never arrive, so every fetch fails over
    **mid-blob**.  Gates (deterministic under the seeds):

    * token parity — every request completes ``DONE`` with tokens
      bit-identical to the same fleet without the fault;
    * zero-waste failover — only undelivered leaves are re-requested:
      the CDN's ``bytes_in`` equals the published bytes-on-wire exactly,
      ``bytes_wasted == 0``, and the per-replica ledgers sum to the same
      total (the new byte accounting makes this assertable);
    * exactly one failover per expert (``retries == n_experts``) and one
      ``replica_blackout`` fired per name on the dead replica;
    * an R=1 control fleet of just the faulty replica fails every
      request with a typed ``FAILED`` status (never a crashed engine);
    * a second chaos run reproduces tokens, statuses, fired logs and
      fleet byte totals bit-for-bit.

    Also measures the cold-start TTFT-vs-replica-count curve (fleet of
    R ∈ {1, 2, 3} slowest-first links, hedged and unhedged, cold and
    EWMA-probed) and merges it into ``BENCH_transport.json``.
    """
    import jax.numpy as jnp

    from repro import api as capi
    from repro.expert import PACKED
    from repro.serve import DONE, FAILED, Request
    from repro.transport import (ChaosTransport, ReplicaFault,
                                 ReplicatedTransport, RetryPolicy,
                                 SimulatedNetworkTransport)

    n_experts = 3
    n_reqs = 6 if smoke else 12
    max_new = 4 if smoke else 8
    prompt_len = 8
    probe = 4096        # < blob size: the probe leaves leaves in flight
    pol = RetryPolicy(max_attempts=3, backoff_base_s=0.0)
    api, rt, cfg, base, experts = _serve_fixture(n_experts=n_experts + 1)
    warm, experts = experts[-1], experts[:-1]
    rng = np.random.default_rng(0)
    prompts = [jnp.asarray(rng.integers(1, cfg.vocab, prompt_len), jnp.int32)
               for _ in range(n_reqs)]
    links = [dict(bandwidth_bps=1e8, latency_s=0.001),   # fast (faulty)
             dict(bandwidth_bps=2e7, latency_s=0.005),   # medium
             dict(bandwidth_bps=5e6, latency_s=0.02)]    # slow

    def mk_fleet(faulty):
        chaos = [ChaosTransport(
            SimulatedNetworkTransport(seed=i, **links[i]),
            replica_faults=([ReplicaFault("blackout", at=2)]
                            if faulty and i == 0 else ()))
            for i in range(3)]
        cdn = ReplicatedTransport(chaos, replication_factor=3,
                                  probe_bytes=probe, quarantine_after=99,
                                  retry=pol)
        return cdn, chaos

    def run(faulty):
        cdn, chaos = mk_fleet(faulty)
        pubs = [cdn.publish(e, rep=PACKED) for e in experts]
        reg = capi.registry(transport=cdn)
        eng = capi.serve(api, rt, base, reg, max_batch=8, cache_len=64)
        reqs = [Request(uid=i, expert=f"expert{i % n_experts}",
                        prompt=prompts[i], max_new_tokens=max_new)
                for i in range(n_reqs)]
        t0 = time.perf_counter()
        eng.run(reqs)
        dt = time.perf_counter() - t0
        reg.close()
        return dt, reqs, cdn, chaos, pubs

    t_base, base_reqs, _, _, _ = run(faulty=False)
    assert all(r.status == DONE for r in base_reqs)
    base_toks = {r.uid: list(r.out_tokens) for r in base_reqs}

    def fired_sorted(chaos):
        return sorted((f for c in chaos for f in c.fired()),
                      key=lambda f: (f["name"], f["fetch"]))

    t_chaos, reqs, cdn, chaos, pubs = run(faulty=True)
    expected_bytes = sum(p["nbytes"] for p in pubs)
    parity = all(r.status == DONE and list(r.out_tokens) == base_toks[r.uid]
                 for r in reqs)
    fleet_bytes_in = sum(c.stats.bytes_in for c in chaos)

    # R=1 control: the same faulty replica with nobody to fail over to
    cdn1 = ReplicatedTransport(
        [ChaosTransport(SimulatedNetworkTransport(seed=0, **links[0]),
                        replica_faults=[ReplicaFault("blackout", at=2)])],
        replication_factor=1, probe_bytes=probe,
        retry=RetryPolicy(max_attempts=2, backoff_base_s=0.0))
    for e in experts:
        cdn1.publish(e, rep=PACKED)
    reg1 = capi.registry(transport=cdn1, quarantine_after=1)
    eng1 = capi.serve(api, rt, base, reg1, max_batch=8, cache_len=64)
    ctrl = [Request(uid=i, expert=f"expert{i}", prompt=prompts[i],
                    max_new_tokens=max_new) for i in range(n_experts)]
    eng1.run(ctrl)
    reg1.close()
    control_failed = all(r.status == FAILED and r.error for r in ctrl)

    # determinism: an identical chaos run reproduces everything
    _, reqs2, cdn2, chaos2, _ = run(faulty=True)
    reproduced = (
        [(r.uid, r.status, list(r.out_tokens)) for r in reqs]
        == [(r.uid, r.status, list(r.out_tokens)) for r in reqs2]
        and fired_sorted(chaos) == fired_sorted(chaos2)
        and (cdn2.stats.retries, cdn2.stats.bytes_in,
             cdn2.stats.bytes_wasted)
        == (cdn.stats.retries, cdn.stats.bytes_in, cdn.stats.bytes_wasted)
        and sum(c.stats.bytes_in for c in chaos2) == fleet_bytes_in)

    # cold-start TTFT vs replica count: slowest-first fleets, so the
    # cold (unprobed) path pays the worst link and hedging/EWMA recover
    curve_links = [dict(bandwidth_bps=1e6, latency_s=0.05),    # slow
                   dict(bandwidth_bps=2e7, latency_s=0.005),   # medium
                   dict(bandwidth_bps=1e8, latency_s=0.001)]   # fast
    curve = []
    for R in (1, 2, 3):
        for hedge_ms in (None, 25.0):
            fleet = [SimulatedNetworkTransport(seed=10 + i, **curve_links[i])
                     for i in range(R)]
            ttft_cdn = ReplicatedTransport(fleet, replication_factor=R,
                                           probe_bytes=probe,
                                           hedge_ms=hedge_ms, retry=pol)
            for e in experts[:2]:
                ttft_cdn.publish(e, rep=PACKED)
            reg = capi.registry(transport=ttft_cdn)
            reg.add(warm)       # local overlay: warm-up never probes links
            eng = capi.serve(api, rt, base, reg, max_batch=1, cache_len=64)
            eng.run([Request(uid=0, expert=warm.name, prompt=prompts[0],
                             max_new_tokens=1)])
            row = {"replicas": R, "hedge_ms": hedge_ms}
            # cold: no EWMA yet, selection is index order (the slow link);
            # probed: the cold fetch taught the EWMAs, selection recovers
            for regime, uid, name in (("cold", 1, experts[0].name),
                                      ("probed", 2, experts[1].name)):
                r = Request(uid=uid, expert=name, prompt=prompts[0],
                            max_new_tokens=1)
                t0 = time.perf_counter()
                eng.run([r])
                row[f"ttft_{regime}_s"] = time.perf_counter() - t0
            row["bytes_wasted"] = ttft_cdn.stats.bytes_wasted
            reg.close()
            curve.append(row)
            print(f"[cdn ttft | R={R} hedge={hedge_ms}] "
                  f"cold={row['ttft_cold_s']*1e3:7.1f} ms  "
                  f"probed={row['ttft_probed_s']*1e3:7.1f} ms")

    by = {(r["replicas"], r["hedge_ms"]): r for r in curve}
    rec = {"tag": "chaos_cdn", "n_reqs": n_reqs, "max_new_tokens": max_new,
           "baseline_s": t_base, "chaos_s": t_chaos,
           "bytes_on_wire": expected_bytes,
           "cdn_bytes_in": cdn.stats.bytes_in,
           "fleet_bytes_in": fleet_bytes_in,
           "bytes_wasted": cdn.stats.bytes_wasted,
           "retries": cdn.stats.retries,
           "healthy_bit_identical": parity,
           "control_r1_all_failed": control_failed,
           "fired": fired_sorted(chaos),
           "health": cdn.health(),
           "deterministic": reproduced,
           "ttft_curve": curve}
    save_raw("chaos_cdn", [rec])
    bench_update("BENCH_transport.json", "chaos_cdn", rec)
    print(f"chaos_cdn: parity={parity}, bytes_in={cdn.stats.bytes_in} "
          f"(expected {expected_bytes}), wasted={cdn.stats.bytes_wasted}, "
          f"retries={cdn.stats.retries}, r1_control_failed={control_failed}, "
          f"deterministic={reproduced}")
    assert parity, "requests diverged from the no-fault fleet"
    # the zero-waste invariant, through the new byte accounting: failover
    # refetched ONLY undelivered leaves, so the fleet moved exactly the
    # published bytes and threw none of them away
    assert cdn.stats.bytes_in == expected_bytes, rec
    assert fleet_bytes_in == expected_bytes, rec
    assert cdn.stats.bytes_wasted == 0, rec
    assert cdn.stats.retries == n_experts, rec
    assert (rec["fired"]
            == [{"name": e.name, "fetch": 2, "kind": "replica_blackout"}
                for e in experts]), rec
    assert control_failed, "R=1 control should fail every request"
    assert reproduced, "chaos_cdn run is not reproducible under the seeds"
    assert (by[(3, 25.0)]["ttft_cold_s"]
            < by[(1, None)]["ttft_cold_s"]), rec


def exp_sharded_serve(smoke: bool = False):
    """Tentpole measurement: the mesh-sharded serving engine swept over
    mesh shapes on 8 forced host devices.

    Per shape ``(expert, model)`` the same oversubscribed request stream
    (10 requests into 4 slots — continuous admission exercised) is served
    greedy AND seeded-sampled on paged KV, timed after a warm pass, and
    compared token-for-token against the ``mesh=None`` single-device
    engine.  Gates:

    * **parity** — every swept shape reproduces the single-device token
      streams bitwise, both sampling modes, admissions included;
    * **balance** — per-shard resident expert counts stay within 2x on
      every multi-shard shape (block partition of the stacked planes);
    * the throughput-vs-mesh-shape curve is merged into
      ``BENCH_serve.json`` (forced host devices share one CPU, so the
      curve measures partitioning overhead, not speedup — the point is
      the *shape* of the cost, and that parity holds while paying it).
    """
    import jax.numpy as jnp

    from repro import api as capi
    from repro.launch.mesh import make_serve_mesh
    from repro.serve import Request

    if len(jax.devices()) < 8:
        raise SystemExit("sharded_serve needs 8 devices — run via "
                         "`--exp sharded_serve` so the XLA flag is set "
                         "before jax imports")

    n_experts = 6
    n_reqs = 10 if smoke else 16
    max_batch = 4
    max_new = 4 if smoke else 8
    prompt_len = 12
    api, rt, cfg, base, experts = _serve_fixture(n_experts=n_experts)
    rng = np.random.default_rng(0)
    prompts = [jnp.asarray(rng.integers(1, cfg.vocab, prompt_len), jnp.int32)
               for _ in range(n_reqs)]

    def mk_reqs():
        return [Request(uid=i, expert=f"expert{i % n_experts}",
                        prompt=prompts[i], max_new_tokens=max_new)
                for i in range(n_reqs)]

    SAMP = {"greedy": {},
            "sampled": {"temperature": 0.8, "top_k": 5, "seed": 7}}

    def engine(mesh, samp):
        # fresh registry per engine: per-mesh device caches and stats
        reg = capi.registry(experts=experts, device_cache_bytes=1 << 18,
                            mesh=mesh)
        return capi.serve(api, rt, base, reg, max_batch=max_batch,
                          cache_len=64, decode_chunk=4, kv_layout="paged",
                          kv_block_size=8, mesh=mesh, **samp)

    shapes = [(1, 1), (2, 4)] if smoke else \
        [(1, 1), (2, 1), (1, 2), (2, 2), (2, 4), (4, 2)]

    base_toks = {}
    for label, samp in SAMP.items():
        reqs = mk_reqs()
        engine(None, samp).run(reqs)
        base_toks[label] = {r.uid: (r.status, list(r.out_tokens))
                            for r in reqs}

    rows, parity_all, balance_all = [], True, True
    for shape in shapes:
        mesh = make_serve_mesh(shape)
        row = {"mesh": list(shape)}
        summ = None
        for label, samp in SAMP.items():
            eng = engine(mesh, samp)
            eng.run(mk_reqs())            # warm: compiles on this mesh
            reqs = mk_reqs()
            t0 = time.perf_counter()
            eng.run(reqs)
            dt = time.perf_counter() - t0
            toks = {r.uid: (r.status, list(r.out_tokens)) for r in reqs}
            ok = toks == base_toks[label]
            parity_all = parity_all and ok
            total = sum(len(t) for _, t in toks.values())
            summ = eng.swap_summary()
            row[label] = {"seconds": dt, "tok_s": total / dt, "parity": ok}
        row["admitted"] = summ["admitted"]
        if shape[0] > 1:
            counts = [s["resident_experts"] for s in summ["shards"]]
            row["resident_experts_per_shard"] = counts
            balanced = max(counts) <= 2 * max(min(counts), 1)
            balance_all = balance_all and balanced
        rows.append(row)
        print(f"[mesh={shape}] greedy={row['greedy']['tok_s']:7.1f} tok/s "
              f"sampled={row['sampled']['tok_s']:7.1f} tok/s "
              f"parity={row['greedy']['parity'] and row['sampled']['parity']}"
              + (f" shards={row.get('resident_experts_per_shard')}"
                 if shape[0] > 1 else ""))

    rec = {"tag": "sharded_serve", "smoke": smoke, "n_experts": n_experts,
           "n_reqs": n_reqs, "max_batch": max_batch,
           "max_new_tokens": max_new, "kv_layout": "paged",
           "rows": rows, "token_parity": parity_all,
           "shard_balance_within_2x": balance_all}
    save_raw("sharded_serve", [rec])
    bench_update("BENCH_serve.json", "sharded_serve", rec)
    print(f"sharded_serve: parity={parity_all} "
          f"balance_within_2x={balance_all} over {len(shapes)} shapes")
    assert parity_all, "a mesh shape diverged from the single-device engine"
    assert balance_all, "per-shard resident counts exceeded 2x imbalance"
    assert all(r["admitted"] > 0 for r in rows), \
        "admission path not exercised"


_RESTART_SCENARIOS = {
    # engine kwargs per chaos_restart scenario; "mesh_shape" is popped and
    # turned into a live mesh by _restart_setup
    "dense_greedy": {},
    "paged_sampled": {"kv_layout": "paged", "kv_block_size": 8,
                      "temperature": 0.8, "top_k": 20, "seed": 7},
    "paged_greedy_mesh": {"kv_layout": "paged", "kv_block_size": 8,
                          "mesh_shape": (2, 4)},
}


def _restart_setup(scenario: str, smoke: bool, mesh_shape=None):
    """Deterministic engine ingredients for one chaos_restart scenario.

    Shared by the baseline, kill and resume child processes
    (``benchmarks/restart_child.py``): all must build the exact same
    model, experts, registry and request stream so the journal +
    snapshot written by the killed child replays cleanly on resume.
    ``mesh_shape`` overrides the scenario's default mesh — the resume
    child uses this to resume onto a DIFFERENT shape than the one that
    crashed.  Returns ``(api, rt, base, reg, mk_reqs, engine_kw)``.
    """
    import jax.numpy as jnp

    from repro import api as capi
    from repro.serve import Request

    kw = dict(_RESTART_SCENARIOS[scenario])
    if mesh_shape is None:
        mesh_shape = kw.pop("mesh_shape", None)
    else:
        kw.pop("mesh_shape", None)
    n_experts = 3
    n_reqs = 6 if smoke else 9
    # max_new chosen so rows are mid-generation at the kill chunk: the
    # run must cross the snapshot-REPLAY tier, not just journal +
    # re-prefill (4 chunks per wave at decode_chunk=2, kill at 3)
    max_new = 8 if smoke else 10
    api, rt, cfg, base, experts = _serve_fixture(n_experts)
    rng = np.random.default_rng(0)
    prompts = [jnp.asarray(rng.integers(1, cfg.vocab, 8), jnp.int32)
               for _ in range(n_reqs)]

    def mk_reqs():
        return [Request(uid=i, expert=f"expert{i % n_experts}",
                        prompt=prompts[i], max_new_tokens=max_new)
                for i in range(n_reqs)]

    reg_kw = {}
    if mesh_shape is not None:
        from repro.launch.mesh import make_serve_mesh
        mesh = make_serve_mesh(tuple(mesh_shape))
        kw["mesh"] = mesh
        reg_kw["mesh"] = mesh
    reg = capi.registry(experts=experts, **reg_kw)
    engine_kw = dict(max_batch=4, cache_len=48, decode_chunk=2, **kw)
    return api, rt, base, reg, mk_reqs, engine_kw


def exp_chaos_restart(smoke: bool = False):
    """Robustness gate: kill–restart recovery with bit-identical resume.

    For each scenario (dense+greedy, paged+sampled, paged+greedy on a
    (2,4) mesh) a child process serves the seeded stream with per-chunk
    snapshots and ``SIGKILL``s itself from a chunk hook at a seeded
    chunk index — no atexit, no flush-on-exit: whatever survives is what
    the journal/snapshot machinery made durable.  Another child then
    resumes from that snapshot directory, and the parent (which never
    touches JAX, so each child can hold the device) gates:

    * **kill** — the child really died by signal (``-SIGKILL``), having
      journaled at least one chunk first;
    * **parity** — every resumed request finishes with tokens
      bit-identical to an uninterrupted run in a third child (the mesh
      scenario resumes onto a DIFFERENT shape, (4,2), than it crashed
      on);
    * **determinism** — a second kill–resume trial reproduces the same
      tokens, statuses and recovery plan;
    * **recovery time** — resume seconds and time-to-first-resumed-token
      are recorded per trial and merged into ``BENCH_serve.json``.
    """
    import signal as _signal
    import subprocess
    import sys as _sys
    import tempfile

    from repro.serve import DONE

    # One process per device: this parent never touches JAX; the
    # baseline, the killed run and each resume are children in turn.
    child = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "restart_child.py")
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)    # each child picks its own device count

    def run_child(*args, expect=0):
        proc = subprocess.run(
            [_sys.executable, child, *map(str, args)], env=env,
            capture_output=True, text=True, timeout=1800)
        assert proc.returncode == expect, (
            f"{args[:2]}: rc={proc.returncode} (want {expect})\n"
            f"{proc.stdout}\n{proc.stderr}")

    def load(path):
        with open(path) as f:
            rec = json.load(f)
        rec["tokens"] = {int(k): (v[0], v[1])
                         for k, v in rec["tokens"].items()}
        return rec

    kill_at = 3
    n_trials = 2
    resume_mesh = {"paged_greedy_mesh": (4, 2)}
    rows, parity_all, determ_all = [], True, True

    for scenario in _RESTART_SCENARIOS:
        with tempfile.TemporaryDirectory() as tmp:
            # uninterrupted baseline (scenario's own mesh shape)
            run_child("baseline", scenario, int(smoke),
                      os.path.join(tmp, "want.json"))
            want = load(os.path.join(tmp, "want.json"))["tokens"]
            assert all(st == DONE for st, _ in want.values())

            trials, outcomes = [], []
            for trial in range(n_trials):
                snap_dir = os.path.join(tmp, f"snap{trial}")
                out_path = os.path.join(tmp, f"resume{trial}.json")
                run_child("kill", scenario, int(smoke), snap_dir, kill_at,
                          expect=-_signal.SIGKILL)
                mesh = resume_mesh.get(scenario)
                run_child("resume", scenario, int(smoke), snap_dir,
                          out_path,
                          *(["x".join(map(str, mesh))] if mesh else []))
                res = load(out_path)
                got, plan = res["tokens"], res["plan"]
                ok = got == want
                parity_all = parity_all and ok
                outcomes.append((sorted(got.items()), plan))
                trials.append({
                    "parity": ok,
                    "resume_seconds": res["resume_seconds"],
                    "first_resumed_token_s": res["first_resumed_token_s"],
                    **plan})
        deterministic = outcomes[0] == outcomes[-1]
        determ_all = determ_all and deterministic
        row = {"scenario": scenario, "kill_at": kill_at,
               "resume_mesh": list(resume_mesh.get(scenario) or []),
               "trials": trials, "deterministic": deterministic}
        rows.append(row)
        t = trials[0]
        print(f"[{scenario:>18s}] parity={t['parity']} "
              f"resume={t['resume_seconds']:.2f}s "
              f"first_tok={t['first_resumed_token_s']:.2f}s "
              f"replayed={t['replayed_rows']} "
              f"reprefilled={t['reprefilled_rows']} "
              f"deterministic={deterministic}")

    rec = {"tag": "chaos_restart", "smoke": smoke, "kill_at": kill_at,
           "n_trials": n_trials, "scenarios": rows,
           "token_parity": parity_all, "deterministic": determ_all}
    save_raw("chaos_restart", [rec])
    bench_update("BENCH_serve.json", "chaos_restart", rec)
    assert parity_all, "a resumed run diverged from the uninterrupted run"
    assert determ_all, "kill-resume trials were not deterministic"
    assert all(t["replayed_rows"] > 0
               for row in rows for t in row["trials"]), \
        "snapshot-replay tier never exercised (rows all re-prefilled)"


EXPS = {
    "compression_ablation": exp_compression_ablation,
    "rwkv_chunk": exp_rwkv_chunk,
    "llama4_prefill": exp_llama4_prefill,
    "compress_swap": exp_compress_swap,
    "mixed_serve": exp_mixed_serve,
    "decode_loop": exp_decode_loop,
    "serve_load": exp_serve_load,
    "remote_fetch": exp_remote_fetch,
    "chaos_serve": exp_chaos_serve,
    "chaos_cdn": exp_chaos_cdn,
    "sharded_serve": exp_sharded_serve,
    "chaos_restart": exp_chaos_restart,
}


def main():
    import inspect
    ap = argparse.ArgumentParser()
    ap.add_argument("--exp", required=True, choices=list(EXPS) + ["all"])
    ap.add_argument("--smoke", action="store_true",
                    help="reduced sizes for CI (skips the speedup gate)")
    args = ap.parse_args()
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()

    def call(f):
        if args.smoke and "smoke" in inspect.signature(f).parameters:
            f(smoke=True)
        else:
            f()

    if args.exp == "all":
        for f in EXPS.values():
            call(f)
    else:
        call(EXPS[args.exp])


if __name__ == "__main__":
    main()
