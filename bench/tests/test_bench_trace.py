"""The trace reduction on a small recorded TPU trace (CPU).

``data/grouped_kernel.xplane.pb`` was recorded on one v5e chip by
``record_trace.py``: three rounds of the grouped ternary kernel in its
columns form and its rows form (32 rows, K 2048, N 1024, 2 experts) and a
plain matrix product, under ``bench.window``, with the rows-form call
inside ``engine.decode_chunk``."""

import collections
import json
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path[:0] = [str(Path(__file__).resolve().parents[2]),
                str(Path(__file__).resolve().parents[2] / "src")]

from bench import harness, kernel_cost, trace_reduce  # noqa: E402
from bench.metrics import (device_idle_share,  # noqa: E402
                           grouped_kernel_roofline)

TRACE = Path(__file__).resolve().parent / "data" / "grouped_kernel.xplane.pb"


@pytest.fixture(scope="module")
def red():
    from jax.profiler import ProfileData
    return trace_reduce.reduce(ProfileData.from_file(str(TRACE)))


def test_busy_within_window(red):
    assert 0 < red["busy_s"] < red["window_s"] < 1.0


def test_grouped_kernel_calls_and_shapes(red):
    shapes = sorted(c["shape"] for c in red["grouped_kernel"])
    assert shapes == [(32, 2048, 1024, 2, False)] * 3 + \
        [(32, 2048, 1024, 2, True)] * 3
    assert all(c["seconds"] > 0 for c in red["grouped_kernel"])


def test_programs_and_breakdown(red):
    assert set(red["programs"]) == {"jit_bitwise_and", "jit__lambda"}
    ops = dict(red["breakdown"]["device_ops"])
    assert "ternary_matmul_grouped" in ops
    assert len(red["breakdown"]["device_ops"]) <= 10
    gaps = dict(red["breakdown"]["idle_gaps"])
    assert set(gaps) <= {"bench.window", "engine.decode_chunk",
                         "outside bench spans"}
    assert sum(gaps.values()) == pytest.approx(
        red["window_s"] - red["busy_s"], rel=1e-6)


def test_kernels_hold_every_grouped_kernel_call(red):
    calls = red["kernels"]["ternary_matmul_grouped"]
    assert set(red["kernels"]) == {"ternary_matmul_grouped"}
    assert [c["seconds"] for c in calls] == \
        [c["seconds"] for c in red["grouped_kernel"]]
    cols = [c for c in calls if c["result"] == [("f32", (32, 32, 32))]]
    assert len(cols) == 3
    assert cols[0]["operands"] == [
        ("f32", (2048, 32)), ("u32", (2, 32, 2048)), ("u32", (2, 32, 2048)),
        ("f32", (2, 1)), ("s32", (1, 32))]


def test_spans_count_what_the_idle_attribution_reads(red):
    from jax.profiler import ProfileData

    lines = trace_reduce._host_span_lines(ProfileData.from_file(str(TRACE)))
    found = collections.Counter(
        name for _, _, name in trace_reduce._host_spans(lines))
    assert {k: v["count"] for k, v in red["spans"].items()} == found
    win = red["spans"]["bench.window"]
    chunk = red["spans"]["engine.decode_chunk"]
    assert win["seconds"] == pytest.approx(red["window_s"])
    assert win["self_seconds"] == pytest.approx(
        win["seconds"] - chunk["seconds"])


def test_breakdown_as_before(red):
    assert red["breakdown"] == {
        "device_ops": [["ternary_matmul_grouped", 0.000239028],
                       ["copy-done", 2.2932999999999992e-05],
                       ["broadcast_and_fusion", 1.2414e-05],
                       ["copy", 1.0157e-05], ["fusion", 7.709999999999999e-06],
                       ["reshape", 6.253e-06],
                       ["copy-start", 1.4599999999999998e-07]],
        "idle_gaps": [["bench.window", 0.005144179999999999],
                      ["engine.decode_chunk", 0.004027209999999998]]}


def test_readers_on_the_trace(red):
    rec = {"trace": red, "peaks": trace_reduce.peaks("TPU v5 lite")}
    share = grouped_kernel_roofline.read(rec)
    assert 0 < share < 100
    want = sum(kernel_cost.ideal_seconds(
        kernel_cost.grouped_ternary(*c["shape"][:4]), rec["peaks"])[0]
        for c in red["grouped_kernel"])
    got = sum(c["seconds"] for c in red["grouped_kernel"])
    assert share == pytest.approx(100 * want / got)
    idle = device_idle_share.read(rec)
    assert 0 < idle < 100


def test_union_merges_overlaps():
    assert trace_reduce.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == \
        [[0, 3], [5, 8]]


class Ev:
    def __init__(self, name, start, end, stats=()):
        self.name, self.start_ns, self.end_ns = name, start, end
        self.duration_ns = end - start
        self.stats = list(stats)


def test_self_times_take_nested_operations_out():
    evs = [Ev("%while.3 = (s32[]) while(...)", 0, 100),
           Ev("%fusion.1 = f32[8] fusion(...)", 10, 40),
           Ev("%ternary_matmul_grouped.2 = f32[8] custom-call(...)", 50, 90),
           Ev("%copy.7 = f32[8] copy(...)", 120, 130)]
    got = {k: v for k, v in trace_reduce.self_times(evs)}
    assert got == pytest.approx({"while": 30e-9, "fusion": 30e-9,
                                 "ternary_matmul_grouped": 40e-9,
                                 "copy": 10e-9})


def test_span_totals_inside_the_window():
    line = [Ev("bench.window", 0, 1000),
            Ev("engine.prefill", 100, 400),
            Ev("engine.prefill", 110, 390,
               [("prompt_tokens", 12), ("uid", 3), ("expert", "expert0"),
                ("hit", 1), ("load", 0.5)]),
            Ev("engine.prefill.pack", 120, 200),
            Ev("engine.decode_chunk", 500, 700, [("steps", 16)]),
            Ev("engine.decode_chunk", 900, 1100, [("steps", 16)])]
    got = trace_reduce.span_totals([line, [Ev("engine.gc", 10, 20)]],
                                   0, 1000)
    assert set(got) == {"bench.window", "engine.prefill",
                        "engine.prefill.pack", "engine.decode_chunk",
                        "engine.gc"}
    pre = got["engine.prefill"]
    assert pre["count"] == 2
    assert pre["seconds"] == pytest.approx(580e-9)
    assert pre["self_seconds"] == pytest.approx(580e-9 - 280e-9 - 80e-9)
    assert pre["attrs"] == {"prompt_tokens": 12, "uid": 3, "hit": 1,
                            "load": 0.5}
    chunk = got["engine.decode_chunk"]
    assert (chunk["count"], chunk["attrs"]) == (1, {"steps": 16})
    assert got["bench.window"]["self_seconds"] == pytest.approx(
        (1000 - 300 - 200) * 1e-9)
    assert got["engine.gc"]["self_seconds"] == pytest.approx(10e-9)


def test_span_attributes_of_a_traced_engine_run_match_the_host_counts(
        tmp_path):
    """The program's span attributes, as ``span_totals`` sums them from a
    real profiler trace, against the harness's own counts (tiny cell)."""
    import jax

    from bench import model, traffic, weights
    from repro import api as capi
    from repro.models import Runtime, build

    data = Path(__file__).resolve().parent / "data" / "tiny"
    cfg = model.load("tiny", data / "configs")
    mix = traffic.Mix.from_dict(
        json.loads((data / "mixes" / "tiny.json").read_text()))
    settings = json.loads((data / "workloads" / "tiny_cell.json").read_text())
    api = build(model.to_model_config(cfg))
    shapes = jax.eval_shape(api.init, jax.random.PRNGKey(0))
    reg = capi.registry(experts=weights.make_experts(shapes, 5,
                                                     mix.n_experts))
    eng = capi.serve(api, Runtime(remat_policy="none"),
                     weights.make_base(shapes, 5), reg, **settings["engine"])
    host = harness.HostSpans(eng)
    host._on = True
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng.run(harness.to_requests(traffic.generate(mix, 5, 512, 12)))
    finally:
        jax.profiler.stop_trace()
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(trace_reduce.latest_xplane(tmp_path)))
    got = trace_reduce.span_totals(trace_reduce._host_span_lines(pd),
                                   0, float("inf"))
    want = trace_reduce.host_counts(host.records)
    assert want["prompt_tokens"] > 0 and want["decode_steps"] > 0
    assert got["engine.prefill"]["attrs"]["prompt_tokens"] == \
        want["prompt_tokens"]
    assert got["engine.decode_chunk"]["attrs"]["steps"] == \
        want["decode_steps"]
    assert got["engine.decode_chunk"]["attrs"]["tokens"] == \
        want["output_tokens"]
    # the harness's span and the program's, one inside the other
    n_prefill = sum(r["span"] == "_paged_prefill" for r in host.records)
    assert got["engine.prefill"]["count"] == 2 * n_prefill


@pytest.mark.parametrize("text,want", [
    ("%k.3 = (bf16[8,128]{1,0}, s32[8]{0}) custom-call(s8[8,256]{1,0} %a, "
     "f8e4m3fn[256,128]{1,0:T(8,128)} %w), custom_call_target="
     "\"tpu_custom_call\", operand_layout_constraints={s8[8,256]{1,0}}",
     ([("bf16", (8, 128)), ("s32", (8,))],
      [("s8", (8, 256)), ("f8e4m3fn", (256, 128))])),
    ("%f = bf16[32,1024]{1,0} fusion(bf16[32,2048]{1,0} %x)", None),
])
def test_call_shapes_from_hlo_text(text, want):
    assert trace_reduce.call_shapes(text) == want


class Engine:
    """The engine methods the benchmark's host spans wrap."""

    def _drive_chunk(self, params, overlay, eid, tok, cache, rows, keys):
        for r in rows:
            r.out_tokens += [0, 0]
        return tok, cache, 2, True

    def _paged_prefill(self, reqs, js, lp, cache, tok, overlay, eid,
                       keys_rows, row_blocks):
        return tok, cache

    def _try_admissions(self, *a):
        return a

    def _overlay_for(self, experts):
        return None


class Row:
    def __init__(self, n):
        self.out_tokens, self.prompt = [], np.zeros((n,), np.int32)


def test_host_spans_count_by_argument_name():
    eng = Engine()
    spans = harness.HostSpans(eng)
    spans._on = True
    rows = [Row(5), Row(7)]
    eng._drive_chunk(None, None, None, 0, None, keys=None, rows=rows)
    eng._paged_prefill(rows, [0, 1], 128, None, 0, None, None, None, None)
    assert spans.records == [
        {"span": "_drive_chunk", "launched": True, "steps": 2, "tokens": 4},
        {"span": "_paged_prefill", "prompt_tokens": 12}]


@pytest.mark.parametrize("broken", ["missing", "renamed"])
def test_host_spans_refuse_an_engine_without_their_hooks(broken):
    class Changed(Engine):
        pass
    if broken == "missing":
        Changed._overlay_for = None
    else:
        def _drive_chunk(self, params, overlay, eid, tok, cache, batch,
                         keys):
            return tok, cache, 0, False
        Changed._drive_chunk = _drive_chunk
    with pytest.raises(RuntimeError):
        harness.HostSpans(Changed())


def test_a_listed_metric_that_reads_nothing_fails_the_run():
    m = {"name": "decode_step_ms", "unit": "ms"}
    with pytest.raises(RuntimeError, match="decode_step_ms"):
        harness.read_per_layer([m], {"trace": None, "host": None})


def test_a_metric_whose_declared_input_is_absent_is_left_out(monkeypatch,
                                                             tmp_path):
    (tmp_path / "metrics").mkdir()
    (tmp_path / "metrics" / "admit_host_ms.py").write_text(
        'NEEDS = (("engine", "counters", "admit_host_s"),)\n'
        "def read(rec):\n"
        '    return rec["engine"]["counters"]["admit_host_s"] or None\n')
    monkeypatch.setattr(harness, "HERE", tmp_path)
    m = [{"name": "admit_host_ms", "unit": "ms"},
         {"name": "device_idle_share", "unit": "%"}]
    (tmp_path / "metrics" / "device_idle_share.py").write_text(
        (Path(harness.__file__).parent / "metrics" /
         "device_idle_share.py").read_text())
    trace = {"window_s": 2.0, "busy_s": 1.5}
    got, left = harness.read_per_layer(m, {"engine": {}, "trace": trace})
    assert got == {"device_idle_share": {"value": 25.0, "unit": "%"}}
    assert left == ["admit_host_ms"]
    counters = {"engine": {"counters": {"admit_host_s": 0.0}},
                "trace": trace}
    with pytest.raises(RuntimeError, match="admit_host_ms"):
        harness.read_per_layer(m, counters)
    counters["engine"]["counters"]["admit_host_s"] = 0.25
    got, left = harness.read_per_layer(m, counters)
    assert got["admit_host_ms"]["value"] == 0.25 and left == []
