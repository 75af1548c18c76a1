"""From a profiler trace (``.xplane.pb``) to the numbers the per-layer
metrics read: device busy and idle time, device time per program and per
operation, the grouped kernel's calls with their shapes, every custom
call's (kernel's) calls with their shapes, the benchmark's and the
program's host spans with their attributes, and the breakdown the result
line carries.

Read through ``jax.profiler.ProfileData``; nothing else is needed.  A TPU
trace has one plane per chip (``/device:TPU:<n>``) whose ``XLA Modules``
line holds one event per program run and whose ``XLA Ops`` line holds
one event per operation, and host planes whose lines hold the
benchmark's own spans (``jax.profiler.TraceAnnotation``).
"""

from __future__ import annotations

import collections
import json
import re
from pathlib import Path

HERE = Path(__file__).resolve().parent
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIXES = ("bench.", "engine.")
WINDOW_SPAN = "bench.window"      # the traced part of the measured window
TOP_N = 10
# the grouped ternary kernel's custom call (kernels/ternary_matmul.py)
GROUPED_KERNEL = "ternary_matmul_grouped"


def peaks(kind: str) -> dict:
    """Published peaks of one chip of ``kind`` (``peaks.json``).  A kind
    not in the table is an error, never a default."""
    with open(HERE / "peaks.json") as f:
        table = json.load(f)["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json; "
                       f"known: {sorted(table)}")
    return table[kind]


def union(intervals) -> list:
    """Merged, sorted [start, end) intervals."""
    out: list = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _module_name(name: str) -> str:
    return re.sub(r"\(\d+\)$", "", name)


def op_name(event_name: str) -> str:
    """The HLO instruction's name from an ``XLA Ops`` event, whose name is
    the instruction's text (``%fusion.12 = bf16[...] fusion(...)``)."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def _op_kind(name: str) -> str:
    """Instruction name without its numeric suffix (``fusion.12`` ->
    ``fusion``)."""
    return re.sub(r"[.\d]+$", "", op_name(name)) or name


_SHAPE = re.compile(r"(f32|bf16|u32|s32)\[([\d,]*)\]")
# any element type (s8, f8e4m3fn, pred, ...) for the kernels of ``kernels``
_TYPED_SHAPE = re.compile(r"\b([a-z][a-z0-9]*)\[([\d,]*)\]")
CUSTOM_CALL = " custom-call("
CALL_TARGET = ", custom_call_target="


def grouped_shapes(text: str):
    """(m, k, n, e, transposed) of a grouped-kernel call from its HLO text
    (result shape first, then operands), or None.

    Columns form: x^T [k, m], planes [e, n/32, k], result [32, n/32, m].
    Rows form: x bit-major [32, m, k/32], planes [e, k/32, n], result
    [m, n]."""
    shapes = [(t, [int(d) for d in dims.split(",") if d])
              for t, dims in _SHAPE.findall(text)]
    res = shapes[0][1] if shapes else None
    planes = [d for t, d in shapes[1:] if t == "u32" and len(d) == 3]
    if res is None or not planes:
        return None
    e = planes[0][0]
    if len(res) == 3 and res[0] == 32:            # columns form
        w_n, m = res[1], res[2]
        k = planes[0][2]
        return m, k, 32 * w_n, e, False
    if len(res) == 2:                             # rows form
        m, n = res
        k = 32 * planes[0][1]
        return m, k, n, e, True
    return None


def call_shapes(text: str):
    """``(result, operands)`` of a custom call from its HLO text, each a
    list of ``(dtype, dims)``; None where the text is no custom call."""
    if CUSTOM_CALL not in text:
        return None
    res, ops = text.split(CUSTOM_CALL, 1)

    def typed(t):
        return [(dt, tuple(int(d) for d in dims.split(",") if d))
                for dt, dims in _TYPED_SHAPE.findall(t)]
    return typed(res.split(" = ", 1)[-1]), typed(ops.split(CALL_TARGET)[0])


def self_times(events, kind=lambda ev: _op_kind(ev.name)) -> list:
    """(kind, seconds) of each event less the events nested in it on the
    same line (a ``while`` holds its body's operations)."""
    evs = sorted(events, key=lambda e: (e.start_ns, -e.end_ns))
    out, stack = [], []            # stack: [end_ns, index into out]
    for ev in evs:
        while stack and stack[-1][0] <= ev.start_ns:
            stack.pop()
        if stack and ev.end_ns <= stack[-1][0]:
            out[stack[-1][1]][1] -= ev.duration_ns / 1e9
        out.append([kind(ev), ev.duration_ns / 1e9])
        stack.append([ev.end_ns, len(out) - 1])
    return out


def _host_span_lines(pd) -> list:
    """The benchmark's and the program's span events of each host line
    (one thread each)."""
    return [[ev for ev in line.events if ev.name.startswith(SPAN_PREFIXES)]
            for plane in pd.planes if not plane.name.startswith("/device:")
            for line in plane.lines]


def _host_spans(lines) -> list:
    """(start, end, name) of every span of :func:`_host_span_lines`."""
    return [(ev.start_ns, ev.end_ns, ev.name) for line in lines
            for ev in line]


def span_totals(lines, w0, w1) -> dict:
    """name -> count, seconds, self seconds (less the spans nested in it
    on its line) and the sum of each numeric attribute, over the span
    events of each line (:func:`_host_span_lines`) that lie inside
    [w0, w1]."""
    out: dict = {}
    for line in lines:
        evs = [ev for ev in line if w0 <= ev.start_ns and ev.end_ns <= w1]
        for ev in evs:
            t = out.setdefault(ev.name, {"count": 0, "seconds": 0.0,
                                         "self_seconds": 0.0, "attrs": {}})
            t["count"] += 1
            t["seconds"] += ev.duration_ns / 1e9
            for k, v in ev.stats:
                if isinstance(v, (int, float)):
                    t["attrs"][k] = t["attrs"].get(k, 0) + v
        for name, sec in self_times(evs, kind=lambda ev: ev.name):
            out[name]["self_seconds"] += sec
    return out


def _span_at(spans, t: float) -> str:
    """The innermost benchmark span covering time ``t``."""
    best = None
    for s, e, name in spans:
        if s <= t < e and (best is None or e - s < best[1] - best[0]):
            best = (s, e, name)
    return best[2] if best else "outside bench spans"


def profile_window(pd) -> tuple:
    """[start, end) of the traced session in the events' time base (ns
    from the session's start), from the ``Task Environment`` plane; the
    extent of all events where that plane is missing."""
    for plane in pd.planes:
        if plane.name == "Task Environment":
            st = dict(plane.stats)
            if "profile_start_time" in st and "profile_stop_time" in st:
                return (0, int(st["profile_stop_time"])
                        - int(st["profile_start_time"]))
    ev = [(e.start_ns, e.end_ns) for p in pd.planes for ln in p.lines
          for e in ln.events]
    return min(s for s, _ in ev), max(e for _, e in ev)


def reduce(pd) -> dict:
    """The reduction of one trace (a ``ProfileData``) over the
    benchmark's ``bench.window`` span, or over the whole session where
    the trace has no such span."""
    planes = [p for p in pd.planes if p.name.startswith(DEVICE_PREFIX)]
    if not planes:
        raise ValueError("the trace holds no TPU device plane")
    span_lines = _host_span_lines(pd)
    spans = _host_spans(span_lines)
    w0, w1 = next(((s, e) for s, e, name in spans if name == WINDOW_SPAN),
                  None) or profile_window(pd)
    busy_total = 0.0
    programs: dict = collections.defaultdict(float)
    ops: dict = collections.defaultdict(float)
    kernels: list = []
    calls: dict = collections.defaultdict(list)
    gaps: dict = collections.defaultdict(float)
    for plane in planes:
        lines = {ln.name: ln for ln in plane.lines}
        op_ev = list(lines[OPS_LINE].events) if OPS_LINE in lines else []
        busy = union((max(ev.start_ns, w0), min(ev.end_ns, w1))
                     for ev in op_ev)
        busy_total += sum(e - s for s, e in busy)
        prev = w0
        for s, e in busy + [[w1, w1]]:
            if s > prev:
                gaps[_span_at(spans, (prev + s) / 2)] += (s - prev) / 1e9
            prev = max(prev, e)
        for kind, sec in self_times(op_ev):
            ops[kind] += sec
        for ev in op_ev:
            kind = _op_kind(ev.name)
            if kind == GROUPED_KERNEL:
                kernels.append({"seconds": ev.duration_ns / 1e9,
                                "shape": grouped_shapes(ev.name)})
            shapes = call_shapes(ev.name)
            if shapes is not None:
                calls[kind].append({"seconds": ev.duration_ns / 1e9,
                                    "result": shapes[0],
                                    "operands": shapes[1]})
        if MODULES_LINE in lines:
            for ev in lines[MODULES_LINE].events:
                name = _module_name(ev.name)
                programs[name] += ev.duration_ns / 1e9
    n = len(planes)
    top_ops = sorted(ops.items(), key=lambda kv: -kv[1])[:TOP_N]
    top_gaps = sorted(gaps.items(), key=lambda kv: -kv[1])[:TOP_N]
    return {
        "window_s": (w1 - w0) / 1e9,
        "busy_s": busy_total / 1e9 / n,
        "programs": {k: v / n for k, v in programs.items()},
        "grouped_kernel": kernels,
        "kernels": dict(calls),
        "spans": span_totals(span_lines, w0, w1),
        "breakdown": {"device_ops": [[k, v / n] for k, v in top_ops],
                      "idle_gaps": [[k, v / n] for k, v in top_gaps]},
    }


def latest_xplane(trace_dir: Path) -> Path:
    found = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def reduce_dir(trace_dir: Path) -> dict:
    from jax.profiler import ProfileData

    return reduce(ProfileData.from_file(str(latest_xplane(trace_dir))))


def host_counts(records: list) -> dict:
    """Counts from the benchmark's host spans inside the traced window."""
    pre = [r for r in records if r["span"] == "_paged_prefill"]
    chunks = [r for r in records if r["span"] == "_drive_chunk"
              and r["launched"]]
    return {"prompt_tokens": sum(r["prompt_tokens"] for r in pre),
            "decode_steps": sum(r["steps"] for r in chunks),
            "output_tokens": sum(r["tokens"] for r in chunks)}
