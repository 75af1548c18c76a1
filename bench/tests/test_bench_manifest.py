"""BENCHMARK.json against the files it names, and the run path's refusal
to report without a chip (CPU)."""

import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import harness, model, traffic  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["bench"]
    assert 1 <= BENCH["run_seconds"] <= 51
    assert "setup_s" in [m["name"] for m in BENCH["end_to_end"]]


def test_names_and_units():
    names = ([c["name"] for c in BENCH["configs"]] + CELLS
             + [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]])
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    for w in BENCH["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_to_its_files(cell):
    files = harness.cell_files(BENCH, cell)
    cfg = files["config"]
    entry = {c["name"]: c for c in BENCH["configs"]}[files["cell"]["config"]]
    assert entry["file"] == f"bench/configs/{cfg['name']}.json"
    assert entry["source"] == cfg["source"]
    assert sorted(entry["reduced"]) == sorted(cfg["reduced"])
    assert model.reference(cfg).served_gaps
    assert files["mix"].mode in traffic.MODES
    assert files["settings"]["check"]["limits"]["served_gap_max"] > 0
    assert files["end_to_end"] and files["per_layer"]


def test_configs_and_metrics_have_files():
    for c in BENCH["configs"]:
        assert (ROOT / c["file"]).is_file()
    for m in BENCH["per_layer"]:
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").is_file()
    for w in BENCH["workloads"]:
        assert (ROOT / "bench" / "mixes" / f"{w['traffic']}.json").is_file()


def test_moves_name_an_end_to_end_metric_each_cell_reports():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    layers = {}
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        for cell in m.get("workloads", CELLS):
            assert cell in e2e[m["moves"]].get("workloads", CELLS)
        layers.setdefault(m["layer"], set()).add(m["name"])
    for cell in CELLS:
        files = harness.cell_files(BENCH, cell)
        names = {m["name"] for m in files["end_to_end"]}
        assert "setup_s" in names and len(names) >= 2


def test_no_chip_no_result():
    """On the CPU the run path raises before it measures anything."""
    with pytest.raises(harness.NoChip):
        harness.check_devices(1, require_chip=True)


def test_run_refuses_without_tpu_and_prints_nothing():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        CELLS[0], "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""


def test_benchmark_files_alone_do_not_run(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        CELLS[0], "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=tmp_path,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode != 0 and p.stdout.strip() == ""
