"""A configuration file of ``configs/`` as the program's model config.

The file holds the published config's keys (Hugging Face names) as run,
and the name of the plain reference module beside it.
"""

from __future__ import annotations

import importlib
import json
from pathlib import Path

HERE = Path(__file__).resolve().parent
CONFIG_DIR = HERE / "configs"
MODEL_TYPES = ("qwen2", "qwen3")


def load(name: str, config_dir: Path = CONFIG_DIR) -> dict:
    with open(config_dir / f"{name}.json") as f:
        return json.load(f)


def head_dim(c: dict) -> int:
    return int(c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"])


def to_model_config(c: dict):
    """The program's ``ModelConfig`` for a configuration file."""
    from repro.configs.base import AttnCfg, BlockCfg, FFNCfg, ModelConfig

    if c["model_type"] not in MODEL_TYPES:
        raise ValueError(f"model_type {c['model_type']!r} not in "
                         f"{MODEL_TYPES}")
    if c["hidden_act"] != "silu":
        raise ValueError(f"hidden_act {c['hidden_act']!r}: only silu")
    block = BlockCfg(
        kind="attn",
        attn=AttnCfg(n_q=c["num_attention_heads"],
                     n_kv=c["num_key_value_heads"], head_dim=head_dim(c),
                     qkv_bias=c["model_type"] == "qwen2",
                     qk_norm=c["model_type"] == "qwen3",
                     rope_theta=float(c["rope_theta"])),
        ffn=FFNCfg(d_ff=c["intermediate_size"], activation="swiglu"))
    return ModelConfig(
        name=c["name"], family="dense", d_model=c["hidden_size"],
        vocab=c["vocab_size"], pattern=(block,),
        n_units=c["num_hidden_layers"],
        tie_embeddings=bool(c["tie_word_embeddings"]),
        rms_eps=float(c["rms_norm_eps"]), dtype=c["torch_dtype"])


def reference(c: dict):
    """The plain reference module named by the configuration."""
    return importlib.import_module(f"bench.configs.{c['reference']}")


def param_count(c: dict) -> int:
    """Parameters of the configuration as run (embedding, head, layers)."""
    d, f = c["hidden_size"], c["intermediate_size"]
    hq, hkv, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                   head_dim(c))
    layer = (2 * d + d * hd * (2 * hq + 2 * hkv) + 3 * d * f
             + ((hq + 2 * hkv) * hd if c["model_type"] == "qwen2" else 0)
             + (2 * hd if c["model_type"] == "qwen3" else 0))
    head = 0 if c["tie_word_embeddings"] else d * c["vocab_size"]
    return (c["num_hidden_layers"] * layer + c["vocab_size"] * d + head + d)
