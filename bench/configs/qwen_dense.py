"""Builder of the dense Qwen2 / Qwen3 decoder (``"architecture":
"qwen_dense"``).

A builder module is named by a configuration file's ``"architecture"`` key
and sits beside it in ``bench/configs/``.  It holds what the benchmark
needs to know of one architecture, so that a new one enters as new files:

- ``to_model_config(c)``: the program's ``ModelConfig`` for the
  configuration ``c`` (the file's keys, Hugging Face names);
- ``param_count(c)``: the parameters one token's forward pass multiplies
  by on this chip, which ``step_mfu`` takes as the work per token (2 x it
  in operations).  For a dense model that is all of them; an architecture
  with routed experts counts attention, router, norms, its top-k experts'
  share and the head;
- it refuses what it cannot build with a ``ValueError`` that names the
  key it cannot take.
"""

from __future__ import annotations

MODEL_TYPES = ("qwen2", "qwen3")


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
