"""One run of one cell: set-up, the measured window, the check, the line.

:func:`run` is what ``bench/run.py`` calls; tests call it too, on the CPU
and at a tiny size, with ``require_chip=False``.
"""

from __future__ import annotations

import gc
import json
import math
import os
import shutil
import sys
import time
from pathlib import Path

import numpy as np

from bench import model, traffic

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
TRACE_DIR = ROOT / ".bench_trace"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
WARMUP_SEED_SALT = 0x5EED


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


class WindowClosed(Exception):
    """Raised at the first chunk boundary after the window's end."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_bench(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def cell_files(bench: dict, name: str, bench_dir: Path = HERE) -> dict:
    """Everything one cell needs, found by the names in BENCHMARK.json."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                       f"{sorted(cells)}")
    w = cells[name]
    with open(bench_dir / "workloads" / f"{name}.json") as f:
        settings = json.load(f)
    with open(bench_dir / "mixes" / f"{w['traffic']}.json") as f:
        mix = traffic.Mix.from_dict(json.load(f))
    cfg = model.load(w["config"], bench_dir / "configs")
    e2e = [m for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    per_layer = [m for m in bench["per_layer"]
                 if name in m.get("workloads", [name])]
    return {"cell": w, "settings": settings, "mix": mix, "config": cfg,
            "end_to_end": e2e, "per_layer": per_layer}


def check_devices(chips: int, require_chip: bool):
    import jax

    devs = jax.devices()
    if require_chip:
        if devs[0].platform != "tpu":
            raise NoChip(f"JAX found no TPU: platform {devs[0].platform!r}")
        if len(devs) < chips:
            raise NoChip(f"the cell needs {chips} chips, JAX found "
                         f"{len(devs)}")
        from repro.kernels import ops
        if ops.INTERPRET:
            raise NoChip("repro.kernels.ops.INTERPRET is set on a TPU")
    return devs


def device_info(devs) -> dict:
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devs)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def to_requests(specs):
    import jax.numpy as jnp

    from repro.serve import Request

    return [Request(uid=s.uid, expert=s.expert,
                    prompt=jnp.asarray(s.prompt, jnp.int32),
                    max_new_tokens=s.max_new_tokens, arrival_s=s.arrival_s)
            for s in specs]


class HostSpans:
    """The benchmark's own host spans around calls into the engine's
    layers (traced runs only), with the counts the per-layer metrics
    read.  Each method is looked up by name and its arguments by
    parameter name; an engine without one of them is an error, so no
    metric goes silent or miscounts when the engine changes."""

    SPANS = {"_drive_chunk": "engine.decode_chunk",
             "_paged_prefill": "engine.prefill",
             "_try_admissions": "engine.admit",
             "_overlay_for": "engine.overlay"}
    ARGS = {"_drive_chunk": "rows", "_paged_prefill": "reqs"}

    def __init__(self, eng):
        import inspect

        import jax

        self.records: list = []
        self._on = False
        for meth, label in self.SPANS.items():
            orig = getattr(eng, meth, None)
            if orig is None:
                raise RuntimeError(f"the engine has no {meth}: the span "
                                   f"{label} and the metrics that read it "
                                   "need a new hook")
            sig = inspect.signature(orig)
            arg = self.ARGS.get(meth)
            if arg is not None and arg not in sig.parameters:
                raise RuntimeError(f"{meth} takes no {arg!r} argument: "
                                   f"{sig}")
            setattr(eng, meth, self._wrap(jax, meth, label, orig, sig, arg))

    def _wrap(self, jax, meth, label, orig, sig, arg):
        def wrapped(*a, **kw):
            rows = sig.bind(*a, **kw).arguments[arg] if arg else ()
            before = sum(len(r.out_tokens) for r in rows)
            with jax.profiler.TraceAnnotation(label):
                out = orig(*a, **kw)
            if self._on:
                rec = {"span": meth}
                if meth == "_paged_prefill":
                    rec["prompt_tokens"] = sum(int(r.prompt.shape[0])
                                               for r in rows)
                elif meth == "_drive_chunk":
                    _, _, steps, launched = out
                    rec.update(launched=bool(launched), steps=int(steps),
                               tokens=sum(len(r.out_tokens) for r in rows)
                               - before)
                self.records.append(rec)
            return out
        return wrapped


def _sample(reqs, mix, seed: int, per_expert: int):
    """The requests the check compares: ``per_expert`` finished requests
    of every expert, drawn from the seed, with the longest finished
    request among them."""
    rng = np.random.default_rng([seed, 0xC0DE])
    done = [r for r in reqs if r.t_done_s is not None
            and r.status != "failed"]
    if not done:
        raise RuntimeError("the window finished no request")
    by_e: dict = {}
    for r in done:
        by_e.setdefault(r.expert, []).append(r)
    longest = max(done, key=lambda r: (int(r.prompt.shape[0])
                                       + len(r.out_tokens), -r.uid))
    groups = []
    for e in sorted(by_e):
        rs = by_e[e]
        pick = [rs[i] for i in rng.choice(len(rs), min(per_expert, len(rs)),
                                          replace=False)]
        if longest.expert == e and longest not in pick:
            pick[0] = longest
        pick += [pick[0]] * (per_expert - len(pick))
        groups.append((e, pick))
    return groups


def _check_arrays(groups, mix):
    L = mix.prompt.hi + mix.output.hi
    P = mix.output.hi
    G, S = len(groups), len(groups[0][1])
    tokens = np.zeros((G, S, L), np.int32)
    pos = np.full((G, S, P), -1, np.int32)
    picked = np.full((G, S, P), -1, np.int32)
    experts = np.zeros((G,), np.int32)
    for g, (e, rs) in enumerate(groups):
        experts[g] = int(e.removeprefix("expert"))
        for s, r in enumerate(rs):
            prompt = np.asarray(r.prompt)
            out = np.asarray(r.out_tokens, np.int32)
            seq = np.concatenate([prompt, out[:-1]])
            tokens[g, s, :len(seq)] = seq
            n = len(out)
            pos[g, s, :n] = len(prompt) - 1 + np.arange(n)
            picked[g, s, :n] = out
    return tokens, experts, pos, picked


def check(files, shapes, seed: int, reqs, control: bool = False) -> dict:
    """The comparison that decides ``correct``: the widest gap by which a
    served token's logit lies below the float32 reference's best, over a
    sample of the window's finished requests (see PERF.md).

    With ``control`` the float8 reference takes the program's place: at
    the same positions of the same prompts and served tokens, the token
    it puts first is judged instead of the served one, under the same
    limit.  The program's own gap is kept as ``program_gap_max``."""
    mix, cfg = files["mix"], files["config"]
    limits = files["settings"]["check"]["limits"]
    groups = _sample(reqs, mix, seed,
                     files["settings"]["check"]["sample_per_expert"])
    tokens, experts, pos, picked = _check_arrays(groups, mix)
    ref = model.reference(cfg)
    t0 = time.perf_counter()
    gap, _ = ref.served_gaps(cfg, shapes, seed, tokens, experts, pos, picked)
    out = {"name": "served_gap_max", "gap_max": float(gap.max()),
           "served_tokens": int((pos >= 0).sum()),
           "reference_s": time.perf_counter() - t0}
    if control:
        _, low_top = ref.served_gaps(cfg, shapes, seed, tokens, experts, pos,
                                     picked, precision="fp8")
        cgap, _ = ref.served_gaps(cfg, shapes, seed, tokens, experts, pos,
                                  np.where(pos >= 0, low_top, -1))
        out.update(name="control_gap_max", gap_max=float(cgap.max()),
                   program_gap_max=out["gap_max"])
    out["limit"] = limits["served_gap_max"]
    return out


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        t_start: float, require_chip: bool = True, control: bool = False,
        bench: dict | None = None, bench_dir: Path = HERE,
        engine_patch=None) -> dict:
    """One run of one cell.  Returns the result line as a dict."""
    bench = bench if bench is not None else load_bench()
    files = cell_files(bench, workload, bench_dir)
    cell, settings, mix, cfg = (files["cell"], files["settings"],
                                files["mix"], files["config"])

    import jax

    from repro import api as capi
    from repro.models import Runtime, build

    devs = check_devices(int(cell["chips"]), require_chip)
    mcfg = model.to_model_config(cfg)
    api = build(mcfg)
    rt = Runtime(remat_policy="none")
    shapes = jax.eval_shape(api.init, jax.random.PRNGKey(0))

    from bench import weights
    base = weights.make_base(shapes, seed)
    experts = weights.make_experts(shapes, seed, mix.n_experts)
    jax.block_until_ready((base, [e.packed for e in experts]))
    from repro.expert import PACKED
    per = max(e.nbytes(PACKED) for e in experts)
    reg = capi.registry(experts=experts,
                        device_cache_bytes=int(2.05 * mix.n_experts * per))
    eng = capi.serve(api, rt, base, reg, **settings["engine"])
    if engine_patch is not None:
        engine_patch(eng)

    log(f"bench: weights, planes, engine ready at "
        f"{time.perf_counter() - t_start:.3f} s")
    warm = to_requests(traffic.warmup(mix, seed ^ WARMUP_SEED_SALT,
                                      cfg["vocab_size"],
                                      settings["engine"]["kv_block_size"]))
    eng.run(warm)
    if any(r.status == "failed" for r in warm):
        raise RuntimeError("requests failed in the warm-up pass")
    del warm
    reqs = to_requests(traffic.generate(mix, seed, cfg["vocab_size"]))
    spans = HostSpans(eng) if trace else None
    engine0 = eng.swap_summary()
    jax.block_until_ready(eng.base)
    setup_s = time.perf_counter() - t_start
    log(f"bench: set-up {setup_s:.3f} s")

    compiles = {"n": 0, "open": False}

    def on_event(event, duration, **kw):
        if event == COMPILE_EVENT and compiles["open"]:
            compiles["n"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_event)
    tr = {"start": seconds / 3.0,
          "len": min(settings.get("trace_seconds", 6.0), seconds / 2.0),
          "t0": None, "t1": None}
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)

    def hook(_):
        now = time.perf_counter()
        if trace:
            if tr["t0"] is None and now - tw0 >= tr["start"]:
                jax.profiler.start_trace(str(TRACE_DIR))
                tr["span"] = jax.profiler.TraceAnnotation("bench.window")
                tr["span"].__enter__()
                spans._on = True
                tr["t0"] = time.perf_counter()
            elif (tr["t0"] is not None and tr["t1"] is None
                  and now - tr["t0"] >= tr["len"]):
                _stop_trace(jax, tr, spans)
        if now - tw0 >= seconds:
            tw["close"] = now
            raise WindowClosed

    tw: dict = {}
    eng.chunk_hooks.append(hook)
    compiles["open"] = True
    tw0 = time.perf_counter()
    try:
        eng.run(reqs)
    except WindowClosed:
        pass
    compiles["open"] = False
    jax.monitoring.unregister_event_duration_listener(on_event)
    if "close" not in tw:
        raise RuntimeError(f"the backlog of {len(reqs)} requests ran out "
                           "before the window closed: raise n_requests")
    if trace and tr["t0"] is not None and tr["t1"] is None:
        _stop_trace(jax, tr, spans)
    window_s = tw["close"] - tw0
    summary = traffic.summarize(reqs, window_s)
    dev = device_info(devs)
    eng_summary = eng.swap_summary()
    del eng, reg, experts, base
    gc.collect()

    chk = check(files, shapes, seed, reqs, control=control)
    correct = (chk["gap_max"] <= chk["limit"]
               and summary["failed"] == 0 and summary["finished"] > 0)
    rec = {"summary": summary, "engine": eng_summary, "engine0": engine0,
           "window_compiles": compiles["n"],
           "params": model.param_count(cfg), "trace": None, "host": None}
    result = {"correct": bool(correct), "attempted": summary["attempted"],
              "failed": summary["failed"]}
    not_read: list = []
    if trace:
        from bench import trace_reduce
        rec["peaks"] = trace_reduce.peaks(dev["kind"])
        rec["trace"] = trace_reduce.reduce_dir(TRACE_DIR)
        rec["host"] = trace_reduce.host_counts(spans.records)
        result["metrics"], not_read = read_per_layer(files["per_layer"],
                                                     rec)
        dev["busy_s"] = rec["trace"]["busy_s"]
        dev["window_s"] = rec["trace"]["window_s"]
        result["device"] = dev
        result["breakdown"] = rec["trace"]["breakdown"]
    else:
        result["metrics"] = end_to_end(files["end_to_end"], summary,
                                       setup_s)
        result["device"] = dev
    compared = {chk["name"]: {"value": chk["gap_max"],
                              "limit": chk["limit"]},
                "failed": {"value": summary["failed"], "limit": 0}}
    result["info"] = {"served_tokens_checked": chk["served_tokens"],
                      "reference_s": chk["reference_s"],
                      "window_compiles": compiles["n"],
                      "finished": summary["finished"],
                      "tokens": summary["tokens"], "window_s": window_s}
    if not_read:
        result["info"]["metrics_not_read"] = not_read
    if control:
        result["info"]["program_gap_max"] = chk["program_gap_max"]
    result["check"] = compared
    return result


def _stop_trace(jax, tr, spans) -> None:
    tr["t1"] = time.perf_counter()
    spans._on = False
    tr["span"].__exit__(None, None, None)
    jax.profiler.stop_trace()


def end_to_end(metrics: list, summary: dict, setup_s: float) -> dict:
    values = {"setup_s": setup_s, **{k: summary[k] for k in (
        "tokens_per_s", "tpot_p90_ms", "ttft_p50_s", "ttft_p90_s")}}
    out = {}
    for m in metrics:
        v = values.get(m["name"])
        if v is None or not math.isfinite(v):
            raise RuntimeError(f"end-to-end metric {m['name']} has no "
                               f"finite value ({v})")
        out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def _has(rec: dict, path) -> bool:
    """Whether the run record holds a value at ``path`` (a key sequence)."""
    for key in path:
        if not isinstance(rec, dict) or rec.get(key) is None:
            return False
        rec = rec[key]
    return True


def read_per_layer(metrics: list, rec: dict) -> tuple[dict, list]:
    """Each per-layer metric listed for the cell, from its reader
    ``metrics/<name>.py``, and the names of those left out.

    A reader may declare what it reads as ``NEEDS``, a tuple of key paths
    into the run record (``(("engine", "counters", "admit_host_s"),)``).
    Where one of them is absent, as in a run of a program without that
    counter, the metric is left out of the line and named in the list.
    Otherwise a reader that finds nothing to read returns None, and for a
    metric the cell lists that is an error, not a metric left out."""
    import importlib.util

    out, not_read = {}, []
    for m in metrics:
        path = HERE / "metrics" / f"{m['name']}.py"
        spec = importlib.util.spec_from_file_location(
            f"bench_metric_{m['name'].replace('.', '_').replace('-', '_')}",
            path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        if not all(_has(rec, p) for p in getattr(mod, "NEEDS", ())):
            not_read.append(m["name"])
            continue
        v = mod.read(rec)
        if v is None:
            raise RuntimeError(f"per-layer metric {m['name']} found "
                               "nothing to read in this traced run")
        out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out, not_read


def print_result(result: dict) -> None:
    for name, c in result["check"].items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(result), flush=True)


def set_env() -> str:
    """JAX's persistent compilation cache at a fixed path in the checkout
    (or where ``JAX_COMPILATION_CACHE_DIR`` already says), every program
    cached, and no TPU runtime logs under a fixed ``/tmp`` path.  Returns
    the cache directory."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    path = os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                                 str(ROOT / ".jax_cache"))
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "0")
    return path
