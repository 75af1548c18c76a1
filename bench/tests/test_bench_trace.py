"""The trace reduction on a small recorded TPU trace (CPU).

``data/grouped_kernel.xplane.pb`` was recorded on one v5e chip by
``record_trace.py``: three rounds of the grouped ternary kernel in its
columns form and its rows form (32 rows, K 2048, N 1024, 2 experts) and a
plain matrix product, under ``bench.window``, with the rows-form call
inside ``engine.decode_chunk``."""

import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

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
    def __init__(self, name, start, end):
        self.name, self.start_ns, self.end_ns = name, start, end
        self.duration_ns = end - start


def test_self_times_take_nested_operations_out():
    evs = [Ev("%while.3 = (s32[]) while(...)", 0, 100),
           Ev("%fusion.1 = f32[8] fusion(...)", 10, 40),
           Ev("%ternary_matmul_grouped.2 = f32[8] custom-call(...)", 50, 90),
           Ev("%copy.7 = f32[8] copy(...)", 120, 130)]
    got = {k: v for k, v in trace_reduce.self_times(evs)}
    assert got == pytest.approx({"while": 30e-9, "fusion": 30e-9,
                                 "ternary_matmul_grouped": 40e-9,
                                 "copy": 10e-9})


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
