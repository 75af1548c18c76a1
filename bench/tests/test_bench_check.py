"""The comparison that decides ``correct``, driven through a whole run at
a CPU size (the chip check skipped): a sound run passes, the float8
control put in the program's place comes out not correct, and so does a
run whose served tokens are altered where they are produced."""

import json
import sys
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness  # noqa: E402

DATA = Path(__file__).resolve().parent / "data" / "tiny"
CELL = "tiny_cell"


def _bench():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["workloads"] = [{"name": CELL, "config": "tiny", "traffic": "tiny",
                           "chips": 1, "why": "CPU test size"}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        m.pop("workloads", None)
    return bench


def _run(seed, **kw):
    return harness.run(CELL, seed, 2.0, False, t_start=time.perf_counter(),
                       require_chip=False, bench=_bench(), bench_dir=DATA,
                       **kw)


SEED = 2**31 + 3


@pytest.fixture(scope="module")
def sound():
    return _run(SEED)


def test_sound_run_is_correct(sound):
    assert sound["correct"], sound["check"]
    assert sound["failed"] == 0 and sound["attempted"] > 0
    assert set(sound["metrics"]) == {"tokens_per_s", "tpot_p90_ms",
                                     "setup_s"}
    assert list(sound)[-1] == "check"
    assert sound["info"]["served_tokens_checked"] > 0


def test_float8_control_fails_the_limit(sound):
    res = _run(SEED, control=True)
    assert not res["correct"], res["check"]
    c = res["check"]["control_gap_max"]
    assert c["limit"] == sound["check"]["served_gap_max"]["limit"]
    assert c["value"] > c["limit"]
    assert res["info"]["program_gap_max"] <= c["limit"]


def test_altered_token_is_not_correct():
    def alter(eng):
        orig = eng._drive_chunk

        def drive(*a, **kw):
            rows = a[5]
            before = [len(r.out_tokens) for r in rows]
            out = orig(*a, **kw)
            for r, n in zip(rows, before):
                if len(r.out_tokens) > n:
                    r.out_tokens[-1] = (r.out_tokens[-1] + 1) % 512
            return out
        eng._drive_chunk = drive

    res = _run(11, engine_patch=alter)
    assert not res["correct"]
    c = res["check"]["served_gap_max"]
    assert c["value"] > c["limit"]
