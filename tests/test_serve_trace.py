"""The serving engine's own host spans and counters (``serve/trace.py``):
every span of the paged path lands in a profiler trace with its
attributes, its token and step counts agree with the benchmark's host
spans, nothing is built while the profiler is off, the GC hook never
outlives a run, and the counters count what the run did."""

import gc
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api as rapi
from repro.configs import get_smoke_config
from repro.models import Runtime, build
from repro.serve import Request
from repro.serve import trace as trace_mod

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from bench import harness, trace_reduce  # noqa: E402
from bench.metrics import decode_step_ms, prefill_ms_per_ktok  # noqa: E402

RT = Runtime(attn_chunk_q=16, attn_chunk_k=16, remat_policy="none")

# span -> the attributes the engine gives it
ATTRS = {
    "engine.wave": {"wave", "rows", "experts"},
    "engine.schedule": {"wave", "ready"},
    "engine.overlay": {"experts", "hit"},
    "engine.stack_build": {"experts", "bytes"},
    "engine.promote": {"expert", "bytes"},
    "engine.admit": {"wave", "slots", "admitted", "candidates_ranked"},
    "engine.prefill": {"wave", "rows", "bucket", "prompt_tokens"},
    "engine.prefill.pack": set(),
    "engine.prefill.launch": set(),
    "engine.decode_chunk": {"wave", "chunk", "rows", "steps", "tokens"},
    "engine.decode_chunk.launch": set(),
    "engine.decode_chunk.sync": set(),
    "engine.decode_chunk.flush": set(),
    "engine.gc": {"generation"},
}


class Stop(Exception):
    pass


@pytest.fixture(scope="module")
def smoke():
    cfg = get_smoke_config("qwen2_5_3b", n_units=1)
    api = build(cfg)
    base = api.init(jax.random.PRNGKey(0))
    experts = []
    for i in range(3):
        leaves, tdef = jax.tree_util.tree_flatten(base)
        keys = jax.random.split(jax.random.PRNGKey(100 + i), len(leaves))
        ft = jax.tree_util.tree_unflatten(tdef, [
            (l.astype(jnp.float32)
             + 0.03 * jax.random.normal(k, l.shape)).astype(l.dtype)
            for l, k in zip(leaves, keys)])
        experts.append(rapi.compress(base, ft, name=f"expert{i}",
                                     density=0.2))
    return cfg, api, base, experts


def _engine(smoke, **kw):
    _, api, base, experts = smoke
    kw = {"max_batch": 3, "cache_len": 64, "max_stack": 2,
          "decode_chunk": 2, "kv_layout": "paged", "kv_block_size": 8,
          "scheduler": "affinity", **kw}
    return rapi.serve(api, RT, base, rapi.registry(experts=experts), **kw)


def _reqs(cfg, n=7, seed=0):
    rng = np.random.default_rng(seed)
    return [Request(uid=i, expert=f"expert{i % 2}",
                    prompt=jnp.asarray(rng.integers(1, cfg.vocab,
                                                    5 + 3 * (i % 3)),
                                       jnp.int32),
                    max_new_tokens=2 + i % 3)
            for i in range(n)]


def _spans(trace_dir):
    """name -> [stats of each event] of every ``engine.`` host span."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(trace_reduce.latest_xplane(trace_dir)))
    out: dict = {}
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("engine."):
                    out.setdefault(ev.name, []).append(dict(ev.stats))
    return out


@pytest.fixture(scope="module")
def traced(smoke, tmp_path_factory):
    """One paged run under the profiler, with the benchmark's host spans
    attached and one forced collection inside the run."""
    cfg = smoke[0]
    eng = _engine(smoke)
    host = harness.HostSpans(eng)
    host._on = True
    collected = []

    def collect(_):
        if not collected:
            collected.append(gc.collect())
    eng.chunk_hooks.append(collect)
    trace_dir = tmp_path_factory.mktemp("trace")
    jax.profiler.start_trace(str(trace_dir))
    try:
        eng.run(_reqs(cfg))
    finally:
        jax.profiler.stop_trace()
    return eng, host, _spans(trace_dir)


def test_every_span_of_the_paged_path_with_its_attributes(traced):
    _, _, spans = traced
    assert set(ATTRS) <= set(spans)
    for name, want in ATTRS.items():
        # the benchmark's own spans of the same name carry no stats
        assert any(want <= set(st) for st in spans[name]), (name, spans[name])
    one_row = [st for st in spans["engine.prefill"] if st.get("rows") == 1]
    assert one_row and all("uid" in st for st in one_row)
    assert any(st.get("admitted", 0) >= 1 for st in spans["engine.admit"])


def test_prefill_and_chunk_counts_match_the_benchmark_spans(traced):
    _, host, spans = traced
    want = trace_reduce.host_counts(host.records)
    prog = lambda name, key: sum(st[key] for st in spans[name]  # noqa: E731
                                 if key in st)
    assert want["prompt_tokens"] > 0 and want["decode_steps"] > 0
    assert prog("engine.prefill", "prompt_tokens") == want["prompt_tokens"]
    assert prog("engine.decode_chunk", "steps") == want["decode_steps"]
    assert prog("engine.decode_chunk", "tokens") == want["output_tokens"]


def test_merge_span_on_the_grouped_path(smoke, tmp_path):
    eng = _engine(smoke, kv_layout="dense")
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng.run(_reqs(smoke[0], n=3), scheduling="grouped")
    finally:
        jax.profiler.stop_trace()
    spans = _spans(tmp_path)
    assert {st["expert"] for st in spans["engine.merge"]} == \
        {"expert0", "expert1"}
    assert any(ATTRS["engine.prefill"] <= set(st)
               for st in spans["engine.prefill"])
    assert "engine.prefill.pack" in spans


def test_profiler_off_builds_no_annotation(smoke, monkeypatch):
    made = []

    class Counting(trace_mod.TraceAnnotation):
        def __init__(self, *a, **kw):
            made.append(a)
            super().__init__(*a, **kw)
    monkeypatch.setattr(trace_mod, "TraceAnnotation", Counting)
    eng = _engine(smoke)
    eng.chunk_hooks.append(lambda _: gc.collect())
    eng.run(_reqs(smoke[0]))
    assert made == []
    c = eng.swap_summary()["counters"]
    assert c["prefill_calls"] > 0 and c["gc_collections"] > 0


def test_gc_hook_leaves_when_a_chunk_hook_raises(smoke):
    eng = _engine(smoke)
    before = list(gc.callbacks)
    c0 = dict(eng.counters)

    def stop(_):
        gc.collect()
        raise Stop
    eng.chunk_hooks.append(stop)
    with pytest.raises(Stop):
        eng.run(_reqs(smoke[0]))
    assert gc.callbacks == before
    c = eng.swap_summary()["counters"]
    assert c["gc_collections"] > c0["gc_collections"]
    assert c["gc_pause_s"] > c0["gc_pause_s"]
    n = c["gc_collections"]
    gc.collect()                     # after the run: no longer counted
    assert eng.counters["gc_collections"] == n


def test_counter_deltas_are_the_runs_admissions_and_prefills(smoke):
    cfg = smoke[0]
    eng = _engine(smoke)
    eng.run(_reqs(cfg, seed=1))      # counters are totals: take deltas
    calls = []
    real = eng._prefill

    def counting(*a, **kw):
        calls.append(1)
        return real(*a, **kw)
    eng._prefill = counting
    c0 = eng.swap_summary()["counters"]
    admitted0 = sum(w["admitted"] for w in eng.wave_log)
    eng.run(_reqs(cfg, seed=2))
    c = eng.swap_summary()["counters"]
    admitted = sum(w["admitted"] for w in eng.wave_log) - admitted0
    assert admitted >= 1
    assert c["admissions"] - c0["admissions"] == admitted
    assert c["prefill_calls"] - c0["prefill_calls"] == len(calls) > 0
    assert c["prefill_pack_s"] > c0["prefill_pack_s"]
    assert c["admit_host_s"] > c0["admit_host_s"]
    assert set(c) == set(trace_mod.COUNTERS)


def test_admission_wait_is_kept_per_priority_as_running_totals(smoke):
    cfg = smoke[0]
    eng = _engine(smoke)
    reqs = _reqs(cfg)
    for r in reqs:
        r.priority = r.uid % 2
    eng.run(reqs)
    waits = eng.swap_summary()["scheduler"]["admission_wait_s"]
    for p in (0, 1):
        got = [r.t_admit_s - r.arrival_s for r in reqs if r.priority == p]
        w = waits[str(p)]
        assert w["n"] == len(got)
        assert w["mean"] == pytest.approx(sum(got) / len(got))
        assert w["max"] == max(got)
    assert all(len(v) == 3 for v in eng._adm_wait.values())


def test_benchmark_host_spans_attach_to_the_real_engine(smoke):
    eng = _engine(smoke)
    harness.HostSpans(eng)
    assert "jit_" + eng._chunk_fn.__name__ == decode_step_ms.CHUNK_PROGRAM
    assert "jit_" + eng._prefill.__name__ == \
        prefill_ms_per_ktok.PREFILL_PROGRAM


@pytest.mark.parametrize("n_experts,skipped", [(3, 2 / 3), (1, 0.0)],
                         ids=["three_experts", "one_expert"])
def test_prefill_expert_passes_follow_the_kernels_tile_rule(
        smoke, tmp_path, n_experts, skipped):
    """One FIFO wave of ``n_experts`` stacked experts (then one-row
    refills) on 128-position KV blocks: every prefill row tile lies in one
    request, so the grouped kernel skips all but one stacked expert per
    tile; each program ``engine.prefill`` span names the stack's width."""
    cfg = smoke[0]
    eng = _engine(smoke, max_stack=3, cache_len=256, kv_block_size=128,
                  scheduler="fifo")
    reqs = _reqs(cfg, n=6)
    for r in reqs:
        r.expert = f"expert{r.uid % n_experts}"
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng.run(reqs)
    finally:
        jax.profiler.stop_trace()
    c = eng.swap_summary()["counters"]
    # the first prefill holds 3 rows of 128 positions: 3 tiles
    assert c["prefill_expert_passes"] == n_experts * (
        3 + c["prefill_calls"] - 1)
    assert c["prefill_expert_passes_skipped"] / c["prefill_expert_passes"] \
        == pytest.approx(skipped)
    prog = [st for st in _spans(tmp_path)["engine.prefill"] if st]
    assert len(prog) == c["prefill_calls"] > 1
    assert all(st["stack_experts"] == n_experts for st in prog)
