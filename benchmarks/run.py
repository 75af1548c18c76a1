"""Benchmark harness — one function per paper table/figure (DESIGN.md §8).

    PYTHONPATH=src python -m benchmarks.run [--quick]

Prints ``name,us_per_call,derived`` CSV rows and writes JSON detail to
benchmarks/results/paper/.  All model-based benchmarks train real (reduced)
models on CPU; compression numbers are exact (same math at any scale).
"""

from __future__ import annotations

import argparse
import json
import os
import time
from functools import lru_cache

import jax
import jax.numpy as jnp
import numpy as np

from repro import api as capi
from repro.configs import get_smoke_config
from repro.core import ALPHA_GRID, golomb_total_bits, rescale
from repro.core.baselines import METHODS, method_bits, run_method
from repro.core.golomb import decode as golomb_decode
from repro.core.golomb import encode as golomb_encode
from repro.core.merging import compose_lora, lorahub_search
from repro.data.pipeline import eval_loss, make_batch_for
from repro.expert import PACKED, TERNARY
from repro.models import Runtime, build
from repro.peft import LoraConfig, apply_lora, init_lora, task_vector
from repro.train import TrainConfig, init_train_state, make_train_step

RT = Runtime(attn_chunk_q=16, attn_chunk_k=16, remat_policy="none")
OUT_DIR = os.path.join(os.path.dirname(__file__), "results", "paper")

ROWS: list[str] = []


def emit(name: str, us: float, derived: str):
    ROWS.append(f"{name},{us:.1f},{derived}")
    print(f"{name},{us:.1f},{derived}", flush=True)


def save_json(name: str, obj):
    os.makedirs(OUT_DIR, exist_ok=True)
    with open(os.path.join(OUT_DIR, f"{name}.json"), "w") as f:
        json.dump(obj, f, indent=1, default=float)


# ---------------------------------------------------------------------------
# Shared setup: base model + LoRA experts on distinct tasks
# ---------------------------------------------------------------------------


@lru_cache(maxsize=1)
def setup(quick: bool = False):
    cfg = get_smoke_config("qwen2_5_3b")
    api = build(cfg)
    tcfg = TrainConfig(peak_lr=1e-2, warmup_steps=5, total_steps=100,
                       optimizer="adamw")
    step_fn = jax.jit(make_train_step(api, RT, tcfg))

    # base model: brief pretraining on task 0 for nonzero competence
    state = init_train_state(api.init(jax.random.PRNGKey(0)), tcfg, False)
    n_base = 20 if quick else 60
    for s in range(n_base):
        state, _ = step_fn(state, make_batch_for(cfg, s, 48, 8, task_id=0))
    base = state["params"]

    # LoRA experts per task
    lcfg = LoraConfig(rank=4, alpha=8.0)
    experts = {}
    n_exp = 12 if quick else 50
    for task in (1, 2, 3):
        lora0 = init_lora(jax.random.PRNGKey(10 + task), base, lcfg)

        def loss_fn(lp, batch):
            merged = apply_lora(base, lp, lcfg)
            return api.loss_and_logits(merged, batch, RT)[0]

        grad_fn = jax.jit(jax.grad(loss_fn))
        lora = lora0
        for s in range(n_exp):
            b = make_batch_for(cfg, s, 48, 8, task_id=task)
            lora = jax.tree_util.tree_map(
                lambda p, g: p - 0.5 * g, lora, grad_fn(lora, b))
        experts[task] = (lora0, lora)
    return cfg, api, base, lcfg, experts


def expert_eval(cfg, api, base, lcfg, lora, task) -> float:
    merged = apply_lora(base, lora, lcfg)
    return eval_loss(api, merged, RT, cfg, task, n_batches=2, seq_len=48,
                     global_batch=8)


def tau_of(experts, task):
    lora0, lora = experts[task]
    return task_vector(lora0, lora)


def apply_tau(experts, task, tau):
    lora0, _ = experts[task]
    return jax.tree_util.tree_map(
        lambda a, d: (a.astype(jnp.float32) + d.astype(jnp.float32)
                      ).astype(a.dtype), lora0, tau)


# ---------------------------------------------------------------------------
# §Compression-ratios (paper Tables 1-4): size + quality vs density
# ---------------------------------------------------------------------------


def bench_compression_ratio(quick=False):
    cfg, api, base, lcfg, experts = setup(quick)
    results = {}
    t0 = time.perf_counter()
    for task in (1,):
        tau = tau_of(experts, task)
        l_orig = expert_eval(cfg, api, base, lcfg, experts[task][1], task)
        l_base = expert_eval(cfg, api, base, lcfg, experts[task][0], task)
        for k in (0.05, 0.1, 0.2, 0.3, 0.5):
            ex = capi.compress(tau, name=f"task{task}_k{k}", kind="lora",
                               density=k, alpha=1.0)
            summ = ex.summary()
            lora_hat = apply_tau(experts, task, ex.to_dense_tau())
            l_comp = expert_eval(cfg, api, base, lcfg, lora_hat, task)
            results[f"task{task}_k{k}"] = {
                "ratio_entropy": summ["compression_x_entropy"],
                "ratio_bitplane": summ["compression_x_bitplane"],
                "loss_orig": l_orig, "loss_comp": l_comp,
                "loss_base": l_base,
                "recovery": ((l_base - l_comp) / max(l_base - l_orig, 1e-9)),
            }
    us = (time.perf_counter() - t0) * 1e6 / max(len(results), 1)
    save_json("compression_ratio", results)
    r10 = results["task1_k0.1"]
    emit("compression_ratio", us,
         f"k=0.1:{r10['ratio_entropy']:.1f}x recov={r10['recovery']:.2f}")
    # paper claim: 8x-50x across k in [0.05, 0.2]
    assert results["task1_k0.05"]["ratio_entropy"] > 40
    assert results["task1_k0.2"]["ratio_entropy"] > 8


# ---------------------------------------------------------------------------
# §Ablation (Fig. 5): ComPEFT vs STC vs Pruned vs BitDelta vs DARE
# ---------------------------------------------------------------------------


def bench_ablation(quick=False):
    cfg, api, base, lcfg, experts = setup(quick)
    task = 1
    tau = tau_of(experts, task)
    n = sum(int(np.prod(l.shape)) for l in jax.tree_util.tree_leaves(tau))
    results = {}
    t0 = time.perf_counter()
    for k in (0.05, 0.2, 0.5):
        for m in METHODS:
            if m == "compeft":
                # alpha picked on validation (held-out batches), as §2.1
                best = None
                for a in (0.5, 1.0, 2.0, 3.0):
                    th = run_method(m, tau, k, alpha=a)
                    l = expert_eval(cfg, api, base, lcfg,
                                    apply_tau(experts, task, th), task)
                    if best is None or l < best[0]:
                        best = (l, a)
                l, alpha = best
            else:
                th = run_method(m, tau, k, key=jax.random.PRNGKey(0))
                l = expert_eval(cfg, api, base, lcfg,
                                apply_tau(experts, task, th), task)
                alpha = None
            results[f"{m}_k{k}"] = {"loss": l, "alpha": alpha,
                                    "bits": method_bits(m, n, k)}
    us = (time.perf_counter() - t0) * 1e6 / len(results)
    save_json("ablation", results)
    comp, stc = results["compeft_k0.05"]["loss"], results["stc_k0.05"]["loss"]
    pru = results["pruned_k0.05"]["loss"]
    emit("ablation_fig5", us,
         f"k=0.05 compeft={comp:.3f} stc={stc:.3f} pruned={pru:.3f}")
    assert comp <= stc + 1e-3   # paper: ComPEFT >= STC (tuned alpha)


# ---------------------------------------------------------------------------
# §Alpha-sweep (Fig. 6)
# ---------------------------------------------------------------------------


def bench_alpha_sweep(quick=False):
    cfg, api, base, lcfg, experts = setup(quick)
    task = 2
    tau = tau_of(experts, task)
    results = {}
    t0 = time.perf_counter()
    grid = ALPHA_GRID if not quick else (0.5, 1.0, 2.0, 4.0)
    for k in (0.05, 0.2, 0.5):
        from repro.core import decompress
        comp = capi.compress(tau, density=k, alpha=1.0).as_(TERNARY)
        for a in grid:
            th = decompress(rescale(comp, 1.0, a))
            l = expert_eval(cfg, api, base, lcfg,
                            apply_tau(experts, task, th), task)
            results[f"k{k}_a{a}"] = l
    us = (time.perf_counter() - t0) * 1e6 / len(results)
    save_json("alpha_sweep", results)
    # optimum alpha shifts down as density rises (paper obs. 2)
    best_a_lo = min((a for a in grid), key=lambda a: results[f"k0.05_a{a}"])
    best_a_hi = min((a for a in grid), key=lambda a: results[f"k0.5_a{a}"])
    emit("alpha_sweep_fig6", us,
         f"argmin_a@k0.05={best_a_lo} argmin_a@k0.5={best_a_hi}")


# ---------------------------------------------------------------------------
# §Latency (Table 5): transmission + load times, measured + modeled
# ---------------------------------------------------------------------------


def bench_transmission_latency(quick=False):
    cfg, api, base, lcfg, experts = setup(quick)
    tau = tau_of(experts, 1)
    results = {}
    t0 = time.perf_counter()
    for k in (0.05, 0.2):
        ex = capi.compress(tau, density=k)
        comp = ex.as_(TERNARY)
        dense_bytes = sum(l.size * 2 for l in jax.tree_util.tree_leaves(tau))
        golomb_bytes = 0
        enc_t = dec_t = 0.0
        for leaf in jax.tree_util.tree_leaves(
                comp, is_leaf=lambda x: hasattr(x, "signs")):
            signs = np.asarray(leaf.signs).reshape(-1)
            t1 = time.perf_counter()
            blob = golomb_encode(signs, float(leaf.scale))
            enc_t += time.perf_counter() - t1
            golomb_bytes += len(blob)
            t1 = time.perf_counter()
            golomb_decode(blob)
            dec_t += time.perf_counter() - t1
        # modeled links: 1 Gb/s internet, 16 GB/s host->device
        results[f"k{k}"] = {
            "dense_bytes": dense_bytes,
            "golomb_bytes": golomb_bytes,
            "bitplane_bytes": ex.nbytes(PACKED),
            "net_s_dense": dense_bytes / 125e6,
            "net_s_comp": golomb_bytes / 125e6,
            "pcie_ms_dense": dense_bytes / 16e9 * 1e3,
            "pcie_ms_comp": ex.nbytes(PACKED) / 16e9 * 1e3,
            "encode_s": enc_t, "decode_s": dec_t,
        }
    us = (time.perf_counter() - t0) * 1e6 / len(results)
    save_json("transmission_latency", results)
    r = results["k0.05"]
    emit("latency_table5", us,
         f"net {r['net_s_dense']:.2e}s->{r['net_s_comp']:.2e}s "
         f"({r['dense_bytes'] / max(r['golomb_bytes'], 1):.0f}x)")


# ---------------------------------------------------------------------------
# §Merging (Table 6): TA + TIES on raw vs compressed experts
# ---------------------------------------------------------------------------


def bench_merging(quick=False):
    cfg, api, base, lcfg, experts = setup(quick)
    tasks = (1, 2, 3)
    taus = [tau_of(experts, t) for t in tasks]
    arts = [capi.compress(t, name=f"task{i}", kind="lora", density=0.2,
                          alpha=1.0) for i, t in enumerate(taus)]

    def avg_loss(tau_merged):
        losses = []
        for t in tasks:
            lora_m = apply_tau(experts, t, tau_merged)
            losses.append(expert_eval(cfg, api, base, lcfg, lora_m, t))
        return float(np.mean(losses))

    t0 = time.perf_counter()
    results = {
        "ta_raw": avg_loss(capi.merge(taus, "task_arithmetic", lam=0.7)),
        "ta_compeft": avg_loss(capi.merge(arts, "task_arithmetic", lam=0.7)),
        "ties_raw": avg_loss(capi.merge(taus, "ties", lam=0.7, density=0.3)),
        "ties_compeft": avg_loss(capi.merge(arts, "ties", lam=0.7,
                                            density=0.3)),
        "zero": avg_loss(jax.tree_util.tree_map(jnp.zeros_like, taus[0])),
    }
    us = (time.perf_counter() - t0) * 1e6 / len(results)
    save_json("merging", results)
    emit("merging_table6", us,
         f"TA raw={results['ta_raw']:.3f} comp={results['ta_compeft']:.3f} "
         f"TIES raw={results['ties_raw']:.3f} comp={results['ties_compeft']:.3f}")


# ---------------------------------------------------------------------------
# §Pareto (Fig. 3): storage vs performance across PEFT methods
# ---------------------------------------------------------------------------


def bench_pareto(quick=False):
    cfg, api, base, lcfg, experts = setup(quick)
    task = 1
    tau = tau_of(experts, task)
    n = sum(int(np.prod(l.shape)) for l in jax.tree_util.tree_leaves(tau))
    t0 = time.perf_counter()
    results = {"lora_r4": {
        "bytes": n * 2,
        "loss": expert_eval(cfg, api, base, lcfg, experts[task][1], task)}}
    for k in (0.05, 0.2):
        th = capi.compress(tau, density=k).to_dense_tau()
        results[f"comlora_k{k}"] = {
            "bytes": golomb_total_bits(n, k) / 8,
            "loss": expert_eval(cfg, api, base, lcfg,
                                apply_tau(experts, task, th), task)}
    # IA3 expert trained fresh (much smaller)
    from repro.peft import apply_ia3, init_ia3
    ia3 = init_ia3(base)
    def loss_fn(ip, b):
        return api.loss_and_logits(apply_ia3(base, ip), b, RT)[0]
    g = jax.jit(jax.grad(loss_fn))
    for s in range(12 if quick else 40):
        ia3 = jax.tree_util.tree_map(
            lambda p, gg: p - 0.5 * gg, ia3,
            g(ia3, make_batch_for(cfg, s, 48, 8, task_id=task)))
    n_ia3 = sum(int(np.prod(l.shape))
                for l in jax.tree_util.tree_leaves(ia3))
    results["ia3"] = {
        "bytes": n_ia3 * 2,
        "loss": eval_loss(api, apply_ia3(base, ia3), RT, cfg, task,
                          n_batches=2, seq_len=48, global_batch=8)}
    tau_i = task_vector(init_ia3(base), ia3)
    th = capi.compress(tau_i, density=0.2).to_dense_tau()
    ia3_hat = jax.tree_util.tree_map(
        lambda a, d: a + d, init_ia3(base), th)
    results["comia3_k0.2"] = {
        "bytes": golomb_total_bits(n_ia3, 0.2) / 8,
        "loss": eval_loss(api, apply_ia3(base, ia3_hat), RT, cfg, task,
                          n_batches=2, seq_len=48, global_batch=8)}
    us = (time.perf_counter() - t0) * 1e6 / len(results)
    save_json("pareto", results)
    emit("pareto_fig3", us,
         " ".join(f"{k}:{v['bytes']:.0f}B/{v['loss']:.3f}"
                  for k, v in results.items()))


# ---------------------------------------------------------------------------
# §CG / LoraHub (Fig. 4): compose experts for an unseen task
# ---------------------------------------------------------------------------


def bench_lorahub(quick=False):
    cfg, api, base, lcfg, experts = setup(quick)
    unseen = 100  # mixture of tasks 1-3: solvable by composition
    modules_raw = [tau_of(experts, t) for t in (1, 2, 3)]
    modules_comp = [capi.compress(t, density=0.2).to_dense_tau()
                    for t in modules_raw]

    def few_shot_loss(tau_comb):
        lora_c = apply_tau(experts, 1, tau_comb)
        merged = apply_lora(base, lora_c, lcfg)
        b = make_batch_for(cfg, 0, 48, 8, task_id=unseen)
        return float(api.loss_and_logits(merged, b, RT)[0])

    t0 = time.perf_counter()
    iters = 15 if quick else 40
    w_raw, l_raw = lorahub_search(modules_raw, few_shot_loss, n_iters=iters,
                                  seed=0)
    w_comp, l_comp = lorahub_search(modules_comp, few_shot_loss,
                                    n_iters=iters, seed=0)
    zero = few_shot_loss(jax.tree_util.tree_map(jnp.zeros_like,
                                                modules_raw[0]))
    us = (time.perf_counter() - t0) * 1e6 / 2
    save_json("lorahub", {"loss_raw": l_raw, "loss_comp": l_comp,
                          "loss_zero": zero, "w_raw": list(w_raw),
                          "w_comp": list(w_comp)})
    emit("lorahub_fig4", us,
         f"zero={zero:.3f} raw={l_raw:.3f} compeft={l_comp:.3f}")


# ---------------------------------------------------------------------------
# Kernel micro-benchmarks (wall time of the jitted paths)
# ---------------------------------------------------------------------------


def bench_kernels(quick=False):
    from repro.core.compeft import CompressedTensor
    from repro.core.packing import pack_ternary
    from repro.kernels import ops

    rng = np.random.default_rng(0)
    M = 256 if quick else 512
    signs = jnp.asarray(rng.integers(-1, 2, (M, M)), jnp.int8)
    pt = pack_ternary(CompressedTensor(signs=signs, scale=jnp.float32(0.5)))
    base = jnp.asarray(rng.normal(0, 1, (M, M)), jnp.bfloat16)
    x = jnp.asarray(rng.normal(0, 1, (8, M)), jnp.float32)

    def timeit(f, *a, n=3):
        f(*a)  # compile
        t0 = time.perf_counter()
        for _ in range(n):
            jax.block_until_ready(f(*a))
        return (time.perf_counter() - t0) / n * 1e6

    emit("kernel_unpack_add", timeit(ops.apply_ternary_delta, base, pt),
         f"{M}x{M} interpret={ops.INTERPRET}")
    emit("kernel_ternary_matmul", timeit(ops.ternary_matvec, x, pt),
         f"8x{M}x{M}")
    emit("kernel_expert_dot", timeit(ops.expert_dot, pt, pt),
         f"{M * M}params")
    thr = jnp.float32(0.5)
    tau = jnp.asarray(rng.normal(0, 1, (M, M)), jnp.float32)
    emit("kernel_pack", timeit(ops.compress_to_planes, tau, thr),
         f"{M}x{M}")


BENCHES = [bench_compression_ratio, bench_ablation, bench_alpha_sweep,
           bench_transmission_latency, bench_merging, bench_pareto,
           bench_lorahub, bench_kernels]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", type=str, default=None)
    args = ap.parse_args()
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    print("name,us_per_call,derived")
    for b in BENCHES:
        if args.only and args.only not in b.__name__:
            continue
        b(args.quick)
        jax.clear_caches()  # bound JIT-artifact memory across benches
    os.makedirs(OUT_DIR, exist_ok=True)
    with open(os.path.join(OUT_DIR, "summary.csv"), "w") as f:
        f.write("name,us_per_call,derived\n" + "\n".join(ROWS) + "\n")


if __name__ == "__main__":
    main()
