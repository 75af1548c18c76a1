#!/usr/bin/env python3
"""Chip smoke test: the ComPEFT zero-merge serving path on a TPU.

Drives the main path once, through the entry points a user calls, at
qwen2.5-3b's published widths (36 units, d_model 2048, vocab 151936,
bf16) with random weights made from ``--seed``:

  init      base parameters, built on the chip
  compress  3 seeded task vectors, each compressed on the chip by
            ``compress_packed`` one leaf at a time, in the leaf's own shape
            (the same thresholds and bits as the whole tree under
            ``per_tensor=True``), and assembled with ``Expert.from_packed``
  kernels   the grouped ternary kernel against its jnp oracle at one FFN
            shape and the transposed tied-lm_head shape; the pack and
            merge kernels bit for bit against theirs
  compile   ``api.registry`` + ``api.serve``; a warm-up ``engine.run`` of
            the same traffic compiles prefill and the decode chunk
  serve     8 requests (128-token prompts, 32 new tokens) round-robin over
            the 3 experts on the zero-merge overlay path, ``max_batch=4``,
            ``cache_len=256``
  checks    every request DONE with 32 tokens and none failed; the
            engine's own compiled decode chunk holds the grouped kernel's
            ``tpu_custom_call``; first-step logits through the overlay
            agree with a forward over merged weights
            (``ExpertRegistry.merged_params``) for 2 requests, and both
            greedy tokens equal the first token the engine served

    python chip_smoke.py [--seed 0]
    python chip_smoke.py --four-chips   # only the mesh phase: the same
                                        # traffic on a (expert=2, model=2)
                                        # serve mesh vs one device

Exits non-zero, printing no result line, when JAX finds no TPU or a check
fails.  The last line of standard output is one JSON object:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(HERE, "src")

ARCH = "qwen2_5_3b"
N_EXPERTS = 3
N_REQUESTS = 8
PROMPT_LEN = 128
NEW_TOKENS = 32
MAX_BATCH = 4
CACHE_LEN = 256
DENSITY = 0.1
TAU_STD = 4e-3          # task-vector scale: ~20% of the init weight std
PARITY_REQUESTS = 2

# Tolerances (see CHANGES.md).  Kernel: the MXU contracts f32 at HIGHEST
# precision, so against the f32 oracle only accumulation order differs:
# |err| <= KERNEL_RTOL * scale * sum_k |x_k| bounds it with a wide margin,
# while a wrong bit or word order moves outputs by ~scale * sqrt(nnz).
KERNEL_RTOL = 2.0 ** -16
# Logits: the overlay adds the f32 delta to each bf16 projection output;
# the merged forward rounds W + delta to bf16 before the matmul.  Both are
# bf16 models, so they differ by bf16 rounding at each of 36 layers.  The
# bound is a fraction of the expert's own effect on the logits (merged
# minus base): a path that drops or misroutes the delta misses it by ~1.
LOGIT_TOL_OF_EFFECT = 0.25


def fail(msg: str) -> None:
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def log(msg: str) -> None:
    print(msg, flush=True)


def _imports():
    if not os.path.isdir(os.path.join(SRC, "repro")):
        fail(f"the repro package is not at {SRC}: run chip_smoke.py from a "
             "checkout of the repository")
    sys.path.insert(0, SRC)


def _require_tpu(n_min: int):
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        fail(f"needs a TPU, but JAX's devices are on platform "
             f"{devs[0].platform!r}")
    if len(devs) < n_min:
        fail(f"needs {n_min} TPU chips, found {len(devs)}")
    from repro.kernels import ops
    if ops.INTERPRET:
        fail("repro.kernels.ops.INTERPRET is True on a TPU: the serving "
             "ops would run their jnp mirrors")
    return devs


def _device_info(devs, count: int) -> dict:
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": count}


def _peak_bytes(dev) -> int:
    stats = dev.memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", -1))


def build_base(seed: int):
    import jax

    from repro.configs import get_config
    from repro.models import Runtime, build

    cfg = get_config(ARCH)
    api = build(cfg)
    base = jax.jit(api.init)(jax.random.PRNGKey(seed))
    jax.block_until_ready(base)
    rt = Runtime(remat_policy="none")
    return cfg, api, rt, base


def make_experts(base, seed: int):
    """Seeded f32 task vectors in their leaves' shapes, each compressed on
    the device by one default ``compress_packed`` call."""
    import functools

    import jax
    import jax.numpy as jnp

    from repro.core import CompressionConfig, compress_packed
    from repro.expert import Expert

    @functools.partial(jax.jit, static_argnums=(1,))
    def task_vector(key, shape):
        return TAU_STD * jax.random.normal(key, shape, jnp.float32)

    ccfg = CompressionConfig(density=DENSITY, alpha=1.0, per_tensor=True)
    leaves, treedef = jax.tree_util.tree_flatten(base)
    experts = []
    for i in range(N_EXPERTS):
        keys = jax.random.split(jax.random.PRNGKey(seed * 1000 + 100 + i),
                                len(leaves))
        packed = []
        for leaf, k in zip(leaves, keys):
            tau = task_vector(k, leaf.shape)
            packed.append(compress_packed(tau, ccfg))
            del tau
        jax.block_until_ready(packed)
        experts.append(Expert.from_packed(
            f"expert{i}", "full", jax.tree_util.tree_unflatten(treedef,
                                                               packed),
            density=DENSITY, alpha=1.0))
    return experts


def make_requests(cfg, seed: int):
    import jax.numpy as jnp
    import numpy as np

    from repro.serve import Request

    rng = np.random.default_rng(seed)
    prompts = [jnp.asarray(rng.integers(1, cfg.vocab, PROMPT_LEN), jnp.int32)
               for _ in range(N_REQUESTS)]

    def mk():
        return [Request(uid=i, expert=f"expert{i % N_EXPERTS}",
                        prompt=prompts[i], max_new_tokens=NEW_TOKENS)
                for i in range(N_REQUESTS)]
    return mk


def cache_budget(experts) -> int:
    from repro.expert import PACKED
    per = max(ex.nbytes(PACKED) for ex in experts)
    return int(2 * N_EXPERTS * per * 1.05)    # trees + their stack


def check_served(reqs, eng) -> None:
    from repro.serve import DONE
    bad = [(r.uid, r.status, len(r.out_tokens)) for r in reqs
           if r.status != DONE or len(r.out_tokens) != NEW_TOKENS]
    if bad:
        fail(f"requests not served in full (uid, status, tokens): {bad}")
    if eng.failed_total:
        fail(f"{eng.failed_total} requests failed")


def check_kernels(experts) -> None:
    """Grouped kernel vs ``ref.ternary_matmul_grouped_ref`` on the chip."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ref
    from repro.kernels.ternary_matmul import ternary_matmul_grouped

    packs = [ex.packed for ex in experts]
    rng = np.random.default_rng(0)

    def stack(path, rows, words):
        pos = jnp.stack([p[path].pos[:rows * words].reshape(rows, words)
                         for p in packs])
        neg = jnp.stack([p[path].neg[:rows * words].reshape(rows, words)
                         for p in packs])
        scales = jnp.stack([p[path].scale for p in packs])
        return pos, neg, scales

    cases = []
    ffn = "blocks/block0/ffn/wu"          # unit 0 of [36, 2048, 11008]
    shape = packs[0][ffn].shape
    cases.append(("ffn_up", False, *stack(ffn, shape[1], shape[2] // 32),
                  shape[1]))
    emb = "embed"                          # tied lm_head: [151936, 2048]
    V, d = packs[0][emb].shape
    cases.append(("tied_head", True, *stack(emb, V, d // 32), d))
    eid = jnp.asarray([0, 1, 2, -1, 2, 1, 0, 0], jnp.int32)
    for name, tr, pos, neg, scales, K in cases:
        x = jnp.asarray(rng.normal(0, 1, (eid.shape[0], K)), jnp.float32)
        got = ternary_matmul_grouped(x, pos, neg, scales, eid,
                                     transpose_rhs=tr, interpret=False)
        with jax.default_matmul_precision("highest"):
            want = jax.jit(ref.ternary_matmul_grouped_ref,
                           static_argnames=("transpose_rhs",))(
                x, pos, neg, scales, eid, transpose_rhs=tr)
        got, want = np.asarray(got), np.asarray(want)
        srow = np.abs(np.asarray(scales))[np.asarray(eid)]
        srow[np.asarray(eid) < 0] = 0.0
        bound = KERNEL_RTOL * srow * np.abs(np.asarray(x)).sum(1)
        err = np.abs(got - want).max(axis=1)
        ratio = float((err / np.maximum(bound, 1e-30)).max())
        log(f"kernel {name}: shape {tuple(got.shape)} max_abs_err "
            f"{float(err.max())!r} worst err/bound {ratio!r}")
        if not np.all(np.isfinite(got)) or np.any(err > bound):
            fail(f"grouped kernel disagrees with its oracle at {name}: "
                 f"max err/bound {ratio}")
        if np.any(got[np.asarray(eid) < 0] != 0.0):
            fail(f"{name}: rows with expert -1 got a nonzero delta")


def check_pack_merge_kernels() -> None:
    """Compression's pack kernel and the merge kernel on the chip, bit for
    bit: pack and a one-expert merge against their jnp oracles, and the
    fused three-expert merge against three one-expert merges."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.compeft import STREAM_COLS
    from repro.kernels import ref
    from repro.kernels.pack import (pack_ternary_planes_segmented,
                                    pack_ternary_planes_segmented_ref)
    from repro.kernels.unpack_add import unpack_add_many

    key = jax.random.PRNGKey(7)
    rows = 300                         # not a multiple of the row block
    tau = jax.random.normal(key, (N_EXPERTS * rows, STREAM_COLS))
    thr = jnp.full((N_EXPERTS * rows,), 1.2, jnp.float32)
    got = pack_ternary_planes_segmented(tau, thr, interpret=False)
    want = jax.jit(pack_ternary_planes_segmented_ref)(tau, thr)
    for g, w in zip(got, want):
        if not np.array_equal(np.asarray(g), np.asarray(w)):
            fail("pack kernel disagrees with its oracle")
    pos = got[0].reshape(N_EXPERTS, rows, -1)
    neg = got[1].reshape(N_EXPERTS, rows, -1)
    base = jax.random.normal(key, (rows, STREAM_COLS), jnp.bfloat16)
    scales = jnp.asarray([0.01, -0.02, 0.03], jnp.float32)
    # one expert: one rounding to bf16, so XLA's oracle is exact too
    one = unpack_add_many(base, pos[:1], neg[:1], scales[:1],
                          interpret=False)
    if not np.array_equal(
            np.asarray(one, np.float32),
            np.asarray(jax.jit(ref.unpack_add_ref)(base, pos[0], neg[0],
                                                   scales[0]), np.float32)):
        fail("merge kernel disagrees with its oracle")
    # E experts in one sweep == E one-expert merges in turn (the kernel
    # rounds through bf16 after each expert; XLA may keep the oracle's
    # intermediate sums in f32, so the loop runs the kernel itself)
    merged = unpack_add_many(base, pos, neg, scales, interpret=False)
    loop = base
    for e in range(N_EXPERTS):
        loop = unpack_add_many(loop, pos[e:e + 1], neg[e:e + 1],
                               scales[e:e + 1], interpret=False)
    if not np.array_equal(np.asarray(merged, np.float32),
                          np.asarray(loop, np.float32)):
        fail("fused multi-expert merge differs from merging one by one")
    log(f"kernel pack [{N_EXPERTS * rows}, {STREAM_COLS}] and merge "
        f"[{rows}, {STREAM_COLS}] x {N_EXPERTS}: bit-identical to oracles "
        "and to one-by-one merges")


def probe_decode_chunk(eng) -> dict:
    """Wrap the engine's compiled decode chunk.  On its first launch the
    wrapper checks that the overlay it is given holds packed planes only
    (no materialized sign stacks), and compiles the same jitted function
    with the same arguments to keep the program's text; every launch then
    runs the engine's own function."""
    import jax

    from repro.models.delta import EmbedDelta, MatmulDelta

    kinds = (MatmulDelta, EmbedDelta)
    seen: dict = {}
    fn = eng._chunk_fn

    def chunk(params, overlay, *rest):
        if "hlo" not in seen:
            if overlay is None:
                fail("the engine launched a decode chunk without an overlay")
            dense = [leaf for leaf in jax.tree_util.tree_leaves(
                overlay, is_leaf=lambda x: isinstance(x, kinds))
                if isinstance(leaf, kinds) and leaf.dense is not None]
            if dense:
                fail(f"{len(dense)} overlay leaves hold materialized sign "
                     "stacks")
            seen["hlo"] = fn.lower(params, overlay,
                                   *rest).compile().as_text()
        return fn(params, overlay, *rest)

    eng._chunk_fn = chunk
    return seen


def grouped_kernel_calls(hlo: str) -> int:
    """The grouped Pallas kernel's ``tpu_custom_call`` ops in a compiled
    program: proof that the overlay ran through Pallas, not the jnp
    mirror."""
    n = sum(1 for line in hlo.splitlines()
            if "tpu_custom_call" in line
            and "ternary_matmul_grouped" in line)
    if n == 0:
        fail("the engine's compiled decode chunk holds no grouped-kernel "
             "tpu_custom_call: the overlay did not run through Pallas")
    return n


def check_logit_parity(cfg, api, rt, base, experts, mk, overlay_logits,
                       served) -> None:
    """First-step logits: overlay vs a forward over merged weights, judged
    against the expert's effect (merged minus base); the greedy token of
    both equals the first token the engine served (``served``)."""
    import jax
    import numpy as np

    from repro import api as capi

    reg = capi.registry(experts=experts)
    prefill = jax.jit(api.prefill, static_argnums=(2, 3))

    def last_logits(params, prompt):
        logits, _ = prefill(params, {"tokens": prompt[None]}, rt, CACHE_LEN)
        return np.asarray(logits[0, -1], np.float32)

    for j, r in enumerate(mk()[:PARITY_REQUESTS]):
        plain = last_logits(base, r.prompt)
        merged = reg.merged_params(base, [r.expert])
        want = last_logits(merged, r.prompt)
        del merged
        gc.collect()
        got = overlay_logits[j]
        effect = float(np.abs(want - plain).max())
        err = float(np.abs(got - want).max())
        tok_o, tok_m = int(got.argmax()), int(want.argmax())
        tok_e = served[r.uid][0]
        log(f"logits uid {r.uid} [{r.expert}]: overlay vs merged max_abs_err "
            f"{err!r}, expert effect {effect!r} (ratio {err / effect!r}, "
            f"bound {LOGIT_TOL_OF_EFFECT}); greedy overlay {tok_o} merged "
            f"{tok_m} served {tok_e}")
        if not np.all(np.isfinite(got)) or not effect > 0.0:
            fail(f"uid {r.uid}: non-finite logits or no expert effect")
        if err > LOGIT_TOL_OF_EFFECT * effect:
            fail(f"uid {r.uid}: overlay and merged logits disagree "
                 f"(max abs err {err}, expert effect {effect})")
        if tok_o != tok_m:
            fail(f"uid {r.uid}: greedy token differs: overlay {tok_o}, "
                 f"merged {tok_m}")
        if tok_e != tok_o:
            fail(f"uid {r.uid}: the engine served first token {tok_e}, "
                 f"the overlay's greedy token is {tok_o}")


def one_chip(seed: int) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro import api as capi
    from repro.expert import PACKED
    from repro.models.delta import build_overlay, plan_overlay

    devs = _require_tpu(1)
    from repro.launch.cache import enable_compile_cache
    log(f"compile cache: {enable_compile_cache()}")
    t = {}

    t0 = time.perf_counter()
    cfg, api, rt, base = build_base(seed)
    t["init"] = time.perf_counter() - t0
    n_params = sum(int(np.prod(x.shape))
                   for x in jax.tree_util.tree_leaves(base))
    log(f"init: {ARCH} {n_params} params, d_model {cfg.d_model}, "
        f"{cfg.n_units} units, vocab {cfg.vocab}: {t['init']!r} s")

    t0 = time.perf_counter()
    experts = make_experts(base, seed)
    t["compress"] = time.perf_counter() - t0
    log(f"compress: {N_EXPERTS} experts at density {DENSITY}, "
        f"{experts[0].nbytes(PACKED)} packed bytes each: "
        f"{t['compress']!r} s")

    t0 = time.perf_counter()
    check_kernels(experts)
    check_pack_merge_kernels()
    t["kernels"] = time.perf_counter() - t0

    mk = make_requests(cfg, seed)
    reg = capi.registry(experts=experts,
                        device_cache_bytes=cache_budget(experts))
    t0 = time.perf_counter()
    eng = capi.serve(api, rt, base, reg, max_batch=MAX_BATCH,
                     cache_len=CACHE_LEN)
    chunk = probe_decode_chunk(eng)
    warm = mk()
    eng.run(warm)
    check_served(warm, eng)
    t["compile"] = time.perf_counter() - t0

    reqs = mk()
    t0 = time.perf_counter()
    eng.run(reqs)
    t["serve"] = time.perf_counter() - t0
    check_served(reqs, eng)
    n_tok = sum(len(r.out_tokens) for r in reqs)
    if [r.out_tokens for r in reqs] != [r.out_tokens for r in warm]:
        fail("the warm-up and the timed run served different tokens")
    summ = eng.swap_summary()
    if summ["n_swaps"]:
        fail(f"{summ['n_swaps']} merge-on-swap fallbacks on the zero-merge "
             "path")
    n_calls = grouped_kernel_calls(chunk.get("hlo", ""))
    served = {r.uid: list(r.out_tokens) for r in reqs}
    log(f"compile (warm-up run, compilation included): "
        f"{t['compile']!r} s")
    log(f"engine decode chunk: {n_calls} grouped-kernel tpu_custom_call "
        "ops")
    log(f"serve: {len(reqs)} requests DONE, {n_tok} tokens, "
        f"{t['serve']!r} s, waves {summ['n_waves']}, "
        f"stack builds {summ['stack_builds']}")
    del eng
    gc.collect()

    t0 = time.perf_counter()
    names = tuple(f"expert{i}" for i in range(N_EXPERTS))
    overlay = build_overlay(plan_overlay(base, cfg), reg.stacked(names))
    parity = mk()[:PARITY_REQUESTS]
    prompts = jnp.stack([r.prompt for r in parity])
    eid = jnp.asarray([names.index(r.expert) for r in parity], jnp.int32)
    logits, cache = jax.jit(api.prefill, static_argnums=(2, 3))(
        base, {"tokens": prompts}, rt, CACHE_LEN, overlay, eid)
    overlay_logits = np.asarray(logits[:, -1], np.float32)
    reg.close()
    del overlay, logits, cache, reg
    gc.collect()
    check_logit_parity(cfg, api, rt, base, experts, mk, overlay_logits,
                       served)
    t["checks"] = time.perf_counter() - t0

    log("phase seconds: " + json.dumps(t))
    log(f"peak_bytes_in_use: {_peak_bytes(devs[0])}")
    return _device_info(devs, len(devs))


def four_chips(seed: int) -> dict:
    """The mesh phase alone: (expert=2, model=2) vs one device."""
    import jax
    import numpy as np

    from repro import api as capi
    from repro.distributed.sharding import serve_param_shardings
    from repro.expert import PACKED, Expert
    from repro.launch.mesh import make_serve_mesh

    devs = _require_tpu(4)
    from repro.launch.cache import enable_compile_cache
    log(f"compile cache: {enable_compile_cache()}")
    t0 = time.perf_counter()
    cfg, api, rt, base = build_base(seed)
    experts = make_experts(base, seed)
    mk = make_requests(cfg, seed)
    log(f"init + compress: {time.perf_counter() - t0!r} s")

    def serve(params, mesh):
        reg = capi.registry(experts=experts, mesh=mesh,
                            device_cache_bytes=cache_budget(experts))
        eng = capi.serve(api, rt, params, reg, max_batch=MAX_BATCH,
                         cache_len=CACHE_LEN, mesh=mesh)
        reqs = mk()
        t1 = time.perf_counter()
        eng.run(reqs)
        dt = time.perf_counter() - t1
        check_served(reqs, eng)
        out = {r.uid: list(r.out_tokens) for r in reqs}
        reg.close()
        del eng, reg
        gc.collect()
        return out, dt

    want, dt1 = serve(base, None)
    log(f"one device ({devs[0]}): {dt1!r} s, compilation included")
    # diagnostic: the base model's first-step logits (no overlay) on one
    # device, compared below with the same forward over the sharded base
    prefill = jax.jit(api.prefill, static_argnums=(2, 3))
    probe = {"tokens": mk()[0].prompt[None]}

    def base_logits(params):
        return np.asarray(prefill(params, probe, rt, CACHE_LEN)[0][0, -1],
                          np.float32)
    ref_logits = base_logits(base)
    # the mesh registry replicates the planes onto every chip; the copies
    # on device 0 are not needed beside them, so the experts move to host
    # memory (the cold tier) first
    experts = [Expert.from_packed(
        ex.name, ex.kind, jax.tree_util.tree_map(np.asarray, ex.as_(PACKED)),
        density=ex.density, alpha=ex.alpha) for ex in experts]
    gc.collect()
    mesh = make_serve_mesh((2, 2))
    sharded = jax.device_put(base, serve_param_shardings(base, mesh))
    del base
    gc.collect()
    got, dt4 = serve(sharded, mesh)
    log(f"mesh (expert=2, model=2): {dt4!r} s, compilation included")
    same = sum(got[u] == want[u] for u in want)
    log(f"token parity with the one-device engine: {same}/{len(want)} "
        "requests identical")
    first = {u: next((i for i, (a, b) in enumerate(zip(got[u], want[u]))
                      if a != b), None) for u in want}
    log(f"first differing token index per request: {first}")
    mesh_logits = base_logits(sharded)
    log("base model first-step logits (no overlay), sharded vs one "
        f"device: max_abs_diff "
        f"{float(np.abs(mesh_logits - ref_logits).max())!r}, greedy "
        f"{int(mesh_logits.argmax())} vs {int(ref_logits.argmax())}")
    if got != want:
        fail("mesh token streams differ from the one-device engine")
    log(f"peak_bytes_in_use per device: "
        f"{[_peak_bytes(d) for d in devs[:4]]}")
    return _device_info(devs, 4)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the mesh phase, on four chips")
    args = ap.parse_args()
    _imports()
    device = four_chips(args.seed) if args.four_chips else one_chip(args.seed)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
