"""Architectures as files (CPU): the dense builder gives the program the
same model as before, every leaf keeps its seeded scale, and a routed-
expert configuration of a ``model_type`` the harness has never seen
enters a copy of the benchmark as added files alone."""

import hashlib
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import model, weights  # noqa: E402
from repro.configs.base import (AttnCfg, BlockCfg, FFNCfg,  # noqa: E402
                                ModelConfig)


def _dense(name, d, f, hq, hkv, units, vocab, tied, qwen3):
    return ModelConfig(
        name=name, family="dense", d_model=d, vocab=vocab,
        pattern=(BlockCfg(kind="attn", attn=AttnCfg(
            n_q=hq, n_kv=hkv, head_dim=128, qkv_bias=not qwen3,
            qk_norm=qwen3, rope_theta=1e6),
            ffn=FFNCfg(d_ff=f, activation="swiglu")),),
        n_units=units, tie_embeddings=tied, rms_eps=1e-6, dtype="bfloat16")


# the program's model and the parameter count, as the dense formula gave them
DENSE = {
    "qwen2_5_3b": (_dense("qwen2_5_3b", 2048, 11008, 16, 2, 36, 151936,
                          True, False), 3_085_938_688),
    "qwen3_32b_l6_v8": (_dense("qwen3_32b_l6_v8", 5120, 25600, 64, 8, 6,
                               19072, False, True), 3_120_892_416),
}


@pytest.mark.parametrize("name", sorted(DENSE))
def test_dense_builder_gives_the_same_model_and_count(name):
    c = model.load(name)
    want, params = DENSE[name]
    assert c["architecture"] == "qwen_dense"
    assert model.to_model_config(c) == want
    assert model.param_count(c) == params


def test_a_configuration_without_a_builder_is_refused():
    c = dict(model.load("qwen2_5_3b"))
    del c["architecture"]
    with pytest.raises(ValueError, match="architecture"):
        model.to_model_config(c)


@pytest.mark.parametrize("key,value", [("model_type", "qwen3_moe"),
                                       ("hidden_act", "gelu")])
def test_the_dense_builder_names_the_key_it_cannot_take(key, value):
    c = dict(model.load("qwen2_5_3b"), **{key: value})
    with pytest.raises(ValueError, match=key):
        model.to_model_config(c)


def _fan(n):
    return float(1.0 / np.sqrt(n))


# leaf -> std of its base values, as the seeded weights drew them
LEAF_STD = {
    "qwen2_5_3b": {
        "blocks/block0/attn/bk": 0.02, "blocks/block0/attn/bq": 0.02,
        "blocks/block0/attn/bv": 0.02,
        "blocks/block0/attn/wk": _fan(2048), "blocks/block0/attn/wq":
        _fan(2048), "blocks/block0/attn/wv": _fan(2048),
        "blocks/block0/attn/wo": _fan(16 * 128),
        "blocks/block0/ffn/wg": _fan(2048), "blocks/block0/ffn/wu":
        _fan(2048), "blocks/block0/ffn/wo": _fan(11008),
        "blocks/block0/ffn_norm": 0.0, "blocks/block0/pre_norm": 0.0,
        "embed": 0.02, "final_norm": 0.0},
    "qwen3_32b_l6_v8": {
        "blocks/block0/attn/k_norm": 0.0, "blocks/block0/attn/q_norm": 0.0,
        "blocks/block0/attn/wk": _fan(5120), "blocks/block0/attn/wq":
        _fan(5120), "blocks/block0/attn/wv": _fan(5120),
        "blocks/block0/attn/wo": _fan(64 * 128),
        "blocks/block0/ffn/wg": _fan(5120), "blocks/block0/ffn/wu":
        _fan(5120), "blocks/block0/ffn/wo": _fan(25600),
        "blocks/block0/ffn_norm": 0.0, "blocks/block0/pre_norm": 0.0,
        "embed": 0.02, "final_norm": 0.0, "lm_head": _fan(5120)},
}


@pytest.mark.parametrize("name", sorted(LEAF_STD))
def test_every_leaf_keeps_its_scale(name):
    from repro.models import build

    c = model.load(name)
    shapes = jax.eval_shape(build(model.to_model_config(c)).init,
                            jax.random.PRNGKey(0))
    got = {lf.path: (lf.std, lf.expert_scale)
           for lf in weights.leaves(shapes)}
    want = {p: (s, weights.NORM_EXPERT_SCALE if s == 0.0 else s / 4)
            for p, s in LEAF_STD[name].items()}
    assert got == want


# A routed-expert architecture the harness has never seen, as the files a
# later change would add: configuration, builder, reference, metric reader,
# mix, cell settings, and the cell's entries in BENCHMARK.json.
TOY = {
    "name": "toy_routed", "source": "a CPU test size of a routed-expert "
    "decoder", "reference": "toy_routed_reference",
    "architecture": "toy_routed_builder", "model_type": "toy_routed",
    "hidden_act": "silu", "hidden_size": 64, "moe_intermediate_size": 32,
    "num_experts": 8, "num_experts_per_tok": 2, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32,
    "rms_norm_eps": 1e-6, "rope_theta": 1e6, "tie_word_embeddings": False,
    "torch_dtype": "bfloat16", "vocab_size": 512, "reduced": []}

BUILDER = '''
"""Builder of a routed-expert decoder: qk-norm attention and top-k of E
experts in every layer, no shared expert."""

MODEL_TYPES = ("toy_routed",)


def to_model_config(c):
    from repro.configs.base import (AttnCfg, BlockCfg, FFNCfg, MoECfg,
                                    ModelConfig)

    if c["model_type"] not in MODEL_TYPES:
        raise ValueError(f"model_type {c['model_type']!r} not in "
                         f"{MODEL_TYPES}")
    moe = MoECfg(n_experts=c["num_experts"], top_k=c["num_experts_per_tok"],
                 d_ff_expert=c["moe_intermediate_size"])
    block = BlockCfg(
        kind="attn",
        attn=AttnCfg(n_q=c["num_attention_heads"],
                     n_kv=c["num_key_value_heads"], head_dim=c["head_dim"],
                     qk_norm=True, rope_theta=float(c["rope_theta"])),
        ffn=FFNCfg(d_ff=c["moe_intermediate_size"], moe=moe))
    return ModelConfig(
        name=c["name"], family="moe", d_model=c["hidden_size"],
        vocab=c["vocab_size"], pattern=(block,),
        n_units=c["num_hidden_layers"],
        tie_embeddings=bool(c["tie_word_embeddings"]),
        rms_eps=float(c["rms_norm_eps"]), dtype=c["torch_dtype"])


def param_count(c):
    """Attention, qk-norms, norms, router and the top-k experts of every
    layer, then embedding, head and final norm."""
    d, f, hd = c["hidden_size"], c["moe_intermediate_size"], c["head_dim"]
    hq, hkv = c["num_attention_heads"], c["num_key_value_heads"]
    layer = (2 * d + d * hd * (2 * hq + 2 * hkv) + 2 * hd
             + d * c["num_experts"] + c["num_experts_per_tok"] * 3 * d * f)
    head = 0 if c["tie_word_embeddings"] else d * c["vocab_size"]
    return c["num_hidden_layers"] * layer + c["vocab_size"] * d + head + d
'''

REFERENCE = '''
"""The plain reference's entry point, as the harness calls it."""


def served_gaps(cfg, shapes, seed, tokens, experts, pos, picked,
                precision="f32"):
    raise NotImplementedError("a CPU test of the benchmark's layout")
'''

READER = '''
"""Admission host time per admitted request, from the program's
counters (engine and scheduler)."""

NEEDS = (("engine", "counters", "admit_host_s"),
         ("engine0", "counters", "admit_host_s"))


def read(rec):
    c, c0 = rec["engine"]["counters"], rec["engine0"]["counters"]
    n = c["admissions"] - c0["admissions"]
    return 1e3 * (c["admit_host_s"] - c0["admit_host_s"]) / n if n else None
'''

# run in the copy, so that ``bench`` is the copy's package
PROBE = '''
import json, sys
import jax
sys.path[:0] = [sys.argv[1], sys.argv[2]]
from bench import harness, model, weights
from repro.models import build

bench = harness.load_bench()
files = harness.cell_files(bench, "toy_routed_cell")
c = files["config"]
mcfg = model.to_model_config(c)
shapes = jax.eval_shape(build(mcfg).init, jax.random.PRNGKey(0))
std = {lf.name: lf.std for lf in weights.leaves(shapes)}
a3b = dict(c, hidden_size=2048, moe_intermediate_size=768, num_experts=128,
           num_experts_per_tok=8, num_attention_heads=32,
           num_key_value_heads=4, head_dim=128, num_hidden_layers=1)
per_layer = (model.param_count(dict(a3b, num_hidden_layers=2))
             - model.param_count(a3b))
counters = {"admissions": 4, "admit_host_s": 0.04}
with_counters = {"engine": {"counters": counters},
                 "engine0": {"counters": {"admissions": 0,
                                          "admit_host_s": 0.0}}}
print(json.dumps({
    "moe": [mcfg.family, mcfg.pattern[0].ffn.moe.n_experts,
            mcfg.pattern[0].ffn.moe.top_k],
    "params": model.param_count(c), "total": mcfg.param_count(),
    "active": mcfg.active_param_count(), "std": std,
    "a3b_layer_active": per_layer,
    "reference": callable(model.reference(c).served_gaps),
    "mix_experts": files["mix"].n_experts,
    "per_layer": [m["name"] for m in files["per_layer"]],
    "read_without": harness.read_per_layer(
        files["per_layer"], {"engine": {}, "engine0": {}}),
    "read_with": harness.read_per_layer(files["per_layer"], with_counters),
}))
'''


def _hashes(root: Path) -> dict:
    return {str(p.relative_to(root)): hashlib.sha256(p.read_bytes())
            .hexdigest() for p in sorted(root.rglob("*"))
            if p.is_file() and "__pycache__" not in p.parts}


def test_a_new_architecture_enters_as_added_files(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = _hashes(tmp_path)
    old = json.loads((tmp_path / "BENCHMARK.json").read_text())
    b = tmp_path / "bench"
    (b / "configs" / "toy_routed.json").write_text(json.dumps(TOY))
    (b / "configs" / "toy_routed_builder.py").write_text(BUILDER)
    (b / "configs" / "toy_routed_reference.py").write_text(REFERENCE)
    (b / "metrics" / "toy_admit_host_ms.py").write_text(READER)
    shutil.copy(ROOT / "bench/tests/data/tiny/mixes/tiny.json",
                b / "mixes" / "toy_mix.json")
    shutil.copy(ROOT / "bench/tests/data/tiny/workloads/tiny_cell.json",
                b / "workloads" / "toy_routed_cell.json")
    new = json.loads(json.dumps(old))
    new["configs"].append({"name": "toy_routed", "source": TOY["source"],
                           "file": "bench/configs/toy_routed.json",
                           "reduced": [], "why": "CPU test size"})
    new["workloads"].append({"name": "toy_routed_cell",
                             "config": "toy_routed", "traffic": "toy_mix",
                             "chips": 1, "why": "CPU test size"})
    new["per_layer"].append({
        "name": "toy_admit_host_ms", "unit": "ms", "better": "lower",
        "source": "program_counter", "layer": "engine and scheduler",
        "moves": "tokens_per_s", "workloads": ["toy_routed_cell"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(new))

    p = subprocess.run([sys.executable, "-c", PROBE, str(tmp_path),
                        str(ROOT / "src")], cwd=tmp_path, capture_output=True,
                       text=True, timeout=300,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode == 0, p.stderr[-3000:]
    got = json.loads(p.stdout.strip().splitlines()[-1])

    assert got["moe"] == ["moe", 8, 2]
    assert got["params"] == got["active"] < got["total"]
    assert got["a3b_layer_active"] == 56_889_600      # Qwen3-30B-A3B sizes
    assert got["std"]["wg_e"] == got["std"]["wu_e"] == _fan(64)
    assert got["std"]["wo_e"] == _fan(32)
    assert got["std"]["router"] == _fan(64)
    assert got["reference"] and got["mix_experts"] == 4
    assert got["per_layer"] == ["toy_admit_host_ms"]
    assert got["read_without"] == [{}, ["toy_admit_host_ms"]]
    assert got["read_with"] == [{"toy_admit_host_ms": {
        "value": pytest.approx(10.0), "unit": "ms"}}, []]

    after = _hashes(tmp_path)
    changed = {k for k in before if after.get(k) != before[k]}
    assert changed == {"BENCHMARK.json"}
    assert all(new[k][:len(v)] == v if isinstance(v, list) else new[k] == v
               for k, v in old.items())
