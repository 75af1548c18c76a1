"""Plain float32 reference of the Qwen2 / Qwen3 dense decoder.

Written from the published architecture (Hugging Face ``Qwen2`` and
``Qwen3`` modelling code): RMSNorm before attention and before the MLP,
grouped-query attention with rotary embeddings on the first and second
halves of each head, a bias on q/k/v (``model_type`` qwen2) or an RMSNorm
on each query and key head (qwen3), a SwiGLU MLP, a final RMSNorm and a
tied or separate head.  No kernels, no cache, no batching tricks: every
matrix product is float32 at HIGHEST precision.  Departures from the
published models: none in the layer equations; the weights are random.

The forward runs over the merged weights ``W + scale * T`` of one expert
per sequence, one layer at a time, regenerating each layer from the seed
(``bench.weights``), so nothing the program made is read.

``precision="fp8"`` is the control: every matrix-product operand (weights
and activations) is rounded to float8 e4m3 before a float32 product, the
nearest step below the configuration's bfloat16.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights

_HI = jax.lax.Precision.HIGHEST
PRECISIONS = ("f32", "fp8")


def _q(x, precision: str):
    if precision == "fp8":
        return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return x


def _mm(spec: str, a, b, precision: str):
    return jnp.einsum(spec, _q(a, precision), _q(b, precision),
                      precision=_HI, preferred_element_type=jnp.float32)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta: float):
    """x [S, L, H, D], positions 0..L-1."""
    L, D = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, D, 2, dtype=np.float64) / D))
    ang = np.arange(L, dtype=np.float64)[:, None] * inv[None, :]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[None, :, None, :]
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _merged(root, leaf, expert, unit):
    """One unit of one leaf of one expert's merged weights, float32."""
    base = weights.base_unit(root, leaf, unit).astype(jnp.float32)
    t = weights.ternary_unit(root, leaf, expert, unit).astype(jnp.float32)
    return base + jnp.float32(leaf.expert_scale) * t


def _layer(x, w: dict, hp: dict, precision: str):
    """One decoder layer on x [S, L, d]."""
    L = x.shape[1]
    G = hp["n_q"] // hp["n_kv"]
    h = _rms(x, w["pre_norm"], hp["eps"])
    q = _mm("sld,dhk->slhk", h, w["wq"], precision)
    k = _mm("sld,dhk->slhk", h, w["wk"], precision)
    v = _mm("sld,dhk->slhk", h, w["wv"], precision)
    if hp["qkv_bias"]:
        q, k, v = q + w["bq"], k + w["bk"], v + w["bv"]
    if hp["qk_norm"]:
        q = _rms(q, w["q_norm"], hp["eps"])
        k = _rms(k, w["k_norm"], hp["eps"])
    q, k = _rope(q, hp["theta"]), _rope(k, hp["theta"])
    k = jnp.repeat(k, G, axis=2)
    v = jnp.repeat(v, G, axis=2)
    s = _mm("slhk,smhk->shlm", q, k, precision) / np.sqrt(q.shape[-1])
    causal = np.tril(np.ones((L, L), bool))
    s = jnp.where(causal[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = _mm("shlm,smhk->slhk", p, v, precision)
    x = x + _mm("slhk,hkd->sld", o, w["wo"], precision)
    h = _rms(x, w["ffn_norm"], hp["eps"])
    g = _mm("sld,df->slf", h, w["ffn/wg"], precision)
    u = _mm("sld,df->slf", h, w["ffn/wu"], precision)
    return x + _mm("slf,fd->sld", jax.nn.silu(g) * u, w["ffn/wo"], precision)


def _hparams(cfg: dict) -> dict:
    n_q = cfg["num_attention_heads"]
    return {"n_q": n_q, "n_kv": cfg["num_key_value_heads"],
            "eps": float(cfg["rms_norm_eps"]),
            "theta": float(cfg["rope_theta"]),
            "qkv_bias": cfg["model_type"] == "qwen2",
            "qk_norm": cfg["model_type"] == "qwen3",
            "tied": bool(cfg["tie_word_embeddings"])}


def _block_leaf_name(leaf) -> str:
    """'blocks/block0/attn/wq' -> 'wq'; 'blocks/block0/ffn/wo' -> 'ffn/wo'."""
    parts = leaf.path.split("/")[2:]
    if parts[0] == "attn":
        parts = parts[1:]
    return "/".join(parts)


def served_gaps(cfg: dict, shapes, seed: int, tokens: np.ndarray,
                experts: np.ndarray, pos: np.ndarray, picked: np.ndarray,
                precision: str = "f32"):
    """Reference logits at the served positions of groups of sequences.

    ``tokens`` [G, S, L] int32: G groups of S sequences (prompt then
    served tokens, right-padded), every sequence of group g served by
    expert ``experts[g]``; ``pos`` [G, S, P] the positions whose
    next-token logits are read (-1 = none); ``picked`` [G, S, P] the
    token each position is judged for.  Returns ``(gap, top)``, both
    [G, S, P]: how far the picked token's logit lies below this forward's
    best, and the token this forward puts first.  Groups run one after
    another, so one expert's merged layer is resident at a time.
    """
    if precision not in PRECISIONS:
        raise ValueError(f"precision {precision!r} not in {PRECISIONS}")
    hp = _hparams(cfg)
    root = weights.root_key(seed)    # an argument, so programs are reused
    lv = weights.leaves(shapes)
    by_path = {lf.path: lf for lf in lv}
    blocks = [lf for lf in lv if lf.units]
    n_units = blocks[0].units
    emb = by_path["embed"]

    @jax.jit
    def embed(root, tok, eid):
        return jax.lax.map(
            lambda a: _merged(root, emb, a[1], 0)[a[0]], (tok, eid))

    @functools.partial(jax.jit, donate_argnums=(1,))
    def unit_step(root, x, eid, u):
        def one(a):
            xs, e = a
            w = {_block_leaf_name(lf): _merged(root, lf, e, u)
                 for lf in blocks}
            return _layer(xs, w, hp, precision)
        return jax.lax.map(one, (x, eid))

    @jax.jit
    def head(root, x, eid, pos, picked):
        def one(a):
            xs, e, ps, pk = a
            h = jnp.take_along_axis(xs, jnp.maximum(ps, 0)[..., None], 1)
            h = _rms(h, _merged(root, by_path["final_norm"], e, 0),
                     hp["eps"])
            head_w = (_merged(root, emb, e, 0).T if hp["tied"]
                      else _merged(root, by_path["lm_head"], e, 0))
            logits = _mm("spd,dv->spv", h, head_w, precision)
            best = jnp.max(logits, -1)
            got = jnp.take_along_axis(logits, pk[..., None], -1)[..., 0]
            return best - got, jnp.argmax(logits, -1).astype(jnp.int32)
        return jax.lax.map(one, (x, eid, pos, jnp.maximum(picked, 0)))

    eid = jnp.asarray(experts, jnp.int32)
    x = embed(root, jnp.asarray(tokens, jnp.int32), eid)
    for u in range(n_units):
        x = unit_step(root, x, eid, jnp.int32(u))
    gap, top = head(root, x, eid, jnp.asarray(pos, jnp.int32),
                    jnp.asarray(picked, jnp.int32))
    valid = np.asarray(pos) >= 0
    return (np.where(valid, np.asarray(gap), 0.0),
            np.where(valid, np.asarray(top), -1))
