"""The serving engine's host spans and counters.

Spans go into the JAX profiler's own trace, so they share the device
operations' clock: an ``.xplane.pb`` stamps every event in ns from the
session's ``profile_start_time``, host spans and device ops alike, and a
gap in device activity can be read against what the host was doing then.
With the profiler off, :func:`span` returns one shared no-op context, so a
span costs one call into the profiler's C++ switch.

Counters are plain numbers on the engine, kept whether or not a trace
runs (``ServeEngine.counters``, surfaced as ``swap_summary()["counters"]``).
:func:`gc_meter` adds every garbage collection to them while an engine
runs: the count and the host pause, and an ``engine.gc`` span while
profiling.
"""

from __future__ import annotations

import contextlib
import gc
import time

from jax.profiler import TraceAnnotation

# every counter the engine keeps; all are totals since the engine was made
COUNTERS = ("prefill_calls", "prefill_pack_s", "admissions", "admit_host_s",
            "gc_collections", "gc_pause_s", "prefill_expert_passes",
            "prefill_expert_passes_skipped")


class _Off:
    """The span handed out while no trace runs: enters, exits and takes
    metadata without recording anything."""

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set_metadata(self, **attrs):
        pass


_OFF = _Off()


def span(name: str, **attrs):
    """A host span ``name`` with ``attrs`` as its stats while the profiler
    runs, else a no-op.  Use it as a context manager; attributes known
    only at the end go in through ``set_metadata`` on what it returns."""
    if TraceAnnotation.is_enabled():
        return TraceAnnotation(name, **attrs)
    return _OFF


def new_counters() -> dict:
    """Every counter at zero: counts as ints, host seconds as floats."""
    return {k: 0.0 if k.endswith("_s") else 0 for k in COUNTERS}


@contextlib.contextmanager
def gc_meter(counters: dict):
    """Count every garbage collection that runs inside the block
    (``gc_collections``, and its host time in ``gc_pause_s``), with an
    ``engine.gc`` span per collection while profiling.  The hook leaves
    ``gc.callbacks`` however the block ends."""
    current: dict = {}

    def hook(phase, info):
        if phase == "start":
            current["t0"] = time.perf_counter()
            current["span"] = span("engine.gc",
                                   generation=info["generation"])
            current["span"].__enter__()
            return
        if "span" not in current:      # installed during a collection
            return
        current.pop("span").__exit__(None, None, None)
        counters["gc_collections"] += 1
        counters["gc_pause_s"] += time.perf_counter() - current.pop("t0")

    gc.callbacks.append(hook)
    try:
        yield
    finally:
        gc.callbacks.remove(hook)


@contextlib.contextmanager
def timed(counters: dict, key: str, name: str, **attrs):
    """:func:`span` that also adds its host time to ``counters[key]``."""
    t0 = time.perf_counter()
    try:
        with span(name, **attrs) as s:
            yield s
    finally:
        counters[key] += time.perf_counter() - t0
