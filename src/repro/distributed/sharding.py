"""Logical-axis sharding rules: map every parameter / activation to a
PartitionSpec from its tree path and shape (MaxText-style, but path-regex
driven so model code stays annotation-free).

Mesh axes:
  pod   — pure data parallelism across pods (slow DCI links; gradients cross
          it ComPEFT-compressed, params replicated)
  data  — FSDP: batch + parameter/optimizer-state sharding (ZeRO-3)
  model — tensor/expert/sequence parallelism

Per-arch overrides (``ShardingOverrides``):
  head_tp=False        attention weights FSDP-only (llama4 40H, internvl2 14H,
                       rwkv6 40 heads — not divisible by |model|)
  expert_parallel=False  TP inside experts instead of expert sharding
                       (mixtral: 8 experts < |model|)
"""

from __future__ import annotations

import re
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig

PyTree = Any


def _last(path: str) -> str:
    return path.split("/")[-1]


def batch_axes(mesh: Mesh):
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def param_pspec(path: str, shape: tuple[int, ...], cfg: ModelConfig,
                mesh: Mesh) -> P:
    """PartitionSpec for one parameter."""
    name = _last(path)
    head_tp = cfg.sharding.head_tp
    ep = cfg.sharding.expert_parallel
    n_model = mesh.shape["model"]
    stacked = path.startswith(("blocks", "enc_blocks"))

    def S(*spec):  # prepend the scan-unit axis for stacked weights
        return P(*((None,) + spec if stacked else spec))

    # embeddings / head
    if name == "embed":
        # vocab-parallel only: sharding d_model here trips an XLA SPMD
        # partitioner CHECK (spmd_partitioner_util.cc:504) when the token
        # gather sits inside the pod-manual compressed-gradient shard_map
        # (jax 0.8.2 / bundled XLA).  Vocab-sharded gather is fine and the
        # table is small relative to HBM once /16 over `model`.  Odd vocab
        # sizes (internvl2 151655, seamless 256206) cannot shard evenly ->
        # replicated (both are <=0.5 GB tables).
        if shape[0] % n_model == 0:
            return P("model", None)
        return P(None, None)
    if name == "lm_head":
        if shape[1] % n_model == 0:
            return P("data", "model")
        return P("data", None)
    if name == "frontend_proj":
        return P(None, "data")

    # attention.  FSDP shards must sit on NON-contraction dims: sharding
    # d_model (the contraction) makes GSPMD reshard the [B,T,D] activations
    # instead of gathering the (small) weights — measured at 127 TB/device
    # of all-gathers on llama4 prefill (EXPERIMENTS.md §Perf E2).
    if name in ("wq", "wo") and len(shape) - stacked == 3:
        hq = shape[1] if stacked else shape[0]
        if name == "wq":
            hq = shape[2] if stacked else shape[1]
        if head_tp and hq % n_model == 0:
            return S("data", "model", None) if name == "wq" \
                else S("model", None, "data")
        return S(None, None, "data") if name == "wq" \
            else S(None, None, "data")
    if name in ("wk", "wv") and len(shape) - stacked == 3:
        hkv = shape[2] if stacked else shape[1]
        if head_tp and hkv % n_model == 0:
            return S("data", "model", None)
        return S(None, None, "data")
    if name in ("bq", "bk", "bv"):
        h = shape[1] if stacked else shape[0]
        if head_tp and h % n_model == 0:
            return S("model", None)
        return S(None, None)

    # dense / shared-expert FFN
    if name in ("wg", "wu", "wg_s", "wu_s", "cm_Wk"):
        return S("data", "model")
    if name in ("wo", "wo_s", "cm_Wv"):
        return S("model", "data")

    # MoE experts
    if name == "router":
        return S("data", None)
    if name in ("wg_e", "wu_e"):
        E = shape[1] if stacked else shape[0]
        if ep and E % n_model == 0:
            return S("model", "data", None)
        return S(None, "data", "model")
    if name == "wo_e":
        E = shape[1] if stacked else shape[0]
        if ep and E % n_model == 0:
            return S("model", None, "data")
        return S(None, "model", "data")

    # mamba (TP over d_inner)
    if name == "in_proj":
        return S("data", "model")
    if name == "conv_w":
        return S(None, "model")
    if name in ("conv_b", "dt_bias", "D_skip"):
        return S("model")
    if name in ("x_proj", "A_log"):
        return S("model", None)
    if name == "dt_proj":
        return S(None, "model")
    if name == "out_proj":
        return S("model", "data")

    # rwkv time-mix (head_tp=False for rwkv6 -> FSDP on OUTPUT dims)
    if name in ("Wr", "Wk", "Wv", "Wg", "cm_Wr"):
        return S(None, "data")
    if name == "Wo":
        return S(None, "data")
    if name in ("mix_w1", "decay_w1"):
        return S(None, "data")
    if name == "mix_w2":
        return S(None, None, "data")
    if name == "decay_w2":
        return S(None, "data")

    # cross-attention weights share attention rules via recursion
    # (handled by name above since they reuse wq/wk/wv/wo keys)

    # norms, scalars, small vectors: replicate
    return P(*([None] * len(shape)))


ACT_RULES_BASE = {
    "batch": "__BATCH__",
    "seq": None,
    "embed_act": None,
    "vocab_act": "model",
    "heads": "model",       # dropped if head_tp False / non-divisible
    "kv_heads": "model",
}


def make_shard_fn(mesh: Mesh, cfg: ModelConfig,
                  drop_axes: tuple = ()) -> Callable:
    """Activation-constraint callback for Runtime.shard.

    ``drop_axes``: mesh axes to omit from constraints — used inside
    manual shard_map regions (e.g. the 'pod'-manual compressed-gradient
    scope, where 'pod' may not appear in GSPMD constraints).
    """
    # Inside a manual region the batch dim is left to the partitioner
    # (UNCONSTRAINED; None would pin it replicated).  A ('data',)
    # constraint there, with 'pod' Manual and 'data' Auto, aborts SPMD
    # partitioning on the installed XLA (a replica-group check).  The
    # partitioner then keeps the pod batch replicated over 'data': each
    # data shard computes the whole pod microbatch (see PERF.md, open
    # questions).
    baxes = P.UNCONSTRAINED if drop_axes else batch_axes(mesh)
    n_model = mesh.shape["model"]

    def shard(x, axes):
        # 'model' may appear at most once per spec; head axes take priority
        # over the flash-carry cq axis
        model_taken = any(
            a in ("heads", "kv_heads") and cfg.sharding.head_tp
            and x.shape[i] % n_model == 0
            for i, a in enumerate(axes))
        spec = []
        for i, a in enumerate(axes):
            if a is None:
                spec.append(None)
            elif a == "batch":
                spec.append(baxes)
            elif a in ("heads", "kv_heads"):
                ok = cfg.sharding.head_tp and (x.shape[i] % n_model == 0)
                spec.append("model" if ok else None)
            elif a == "flash_cq":
                ok = (not model_taken) and x.shape[i] % n_model == 0
                spec.append("model" if ok else None)
            elif a == "vocab_act":
                # constraints tolerate uneven dims (GSPMD pads); only input
                # shardings require divisibility
                spec.append("model")
            else:
                spec.append(None)
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, P(*spec)))

    def heads_shardable(n_heads: int) -> bool:
        return cfg.sharding.head_tp and n_heads % n_model == 0

    shard.heads_shardable = heads_shardable
    return shard


def param_shardings(params_shape: PyTree, cfg: ModelConfig,
                    mesh: Mesh) -> PyTree:
    """NamedSharding tree matching an eval_shape param tree."""
    from repro.peft.lora import _path_str

    def f(path, leaf):
        ps = _path_str(path)
        return NamedSharding(mesh, param_pspec(ps, tuple(leaf.shape), cfg,
                                               mesh))

    return jax.tree_util.tree_map_with_path(f, params_shape)


def decode_layout(mesh: Mesh, global_batch: int):
    """(batch_axes_or_None, seq_axes) for decode-cache sharding.

    Normal serving: batch over (pod,)data, cache sequence over model.
    Long-context batch=1 (or any batch < dp extent): batch unsharded and
    the cache sequence sharded over EVERY mesh axis — flash decoding across
    all chips, the only viable 500k-cache layout."""
    baxes = batch_axes(mesh)
    dp = int(np.prod([mesh.shape[a] for a in baxes]))
    if global_batch % dp == 0:
        return baxes, ("model",)
    return None, tuple(mesh.axis_names)


def cache_pspec(path: str, shape: tuple[int, ...], mesh: Mesh,
                global_batch: int, seq_shard: bool = True) -> P:
    """Decode-cache shardings.  KV caches [U, B, S, Hkv, D]: batch over
    data(+pod), sequence over model (SP flash decoding); recurrent states
    batch-sharded.  Batch-unshardable cells flip to all-axis sequence
    sharding (see decode_layout)."""
    name = _last(path)
    baxes, seq_axes = decode_layout(mesh, global_batch)
    if name in ("k", "v") and len(shape) == 5:
        seq = seq_axes if seq_shard else None
        return P(None, baxes, seq, None, None)
    if name == "pos" and len(shape) == 2:
        return P(None, seq_axes if seq_shard else None)
    if name in ("h", "conv"):  # mamba states: shard d_inner over model
        if baxes is None:
            return P(*((None, None) + (None,) * (len(shape) - 3) + ("model",))) \
                if name == "conv" else P(None, None, "model", None)
        return P(*((None, baxes) + (None,) * (len(shape) - 2)))
    if name in ("S", "tm", "cm") or len(shape) >= 2:
        if baxes is None:
            return P(*([None] * len(shape)))
        return P(*((None, baxes) + (None,) * (len(shape) - 2)))
    return P(*([None] * len(shape)))


def cache_shardings(cache_shape: PyTree, mesh: Mesh, global_batch: int,
                    seq_shard: bool = True) -> PyTree:
    from repro.peft.lora import _path_str

    def f(path, leaf):
        return NamedSharding(mesh, cache_pspec(_path_str(path),
                                               tuple(leaf.shape), mesh,
                                               global_batch, seq_shard))

    return jax.tree_util.tree_map_with_path(f, cache_shape)


def batch_shardings(batch_shape: PyTree, mesh: Mesh) -> PyTree:
    baxes = batch_axes(mesh)

    def f(leaf):
        return NamedSharding(
            mesh, P(*((baxes,) + (None,) * (len(leaf.shape) - 1))))

    return jax.tree_util.tree_map(f, batch_shape)


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


# ---------------------------------------------------------------------------
# Serving rules (mesh axes: ("expert", "model"))
#
# The serving engine's contract is *bitwise* parity with the single-device
# path, which rules out any spec that shards a contraction dim (partial
# sums + psum reorder f32 accumulation).  The rules below only shard dims
# where every output element is still computed by exactly one device:
#   - embed / lm_head: vocab-parallel along "model" (the all-gather the
#     tentpole allows is exactly the logits gather this induces),
#   - stacked [E, ...] bitplane buffers: expert-parallel along "expert"
#     (each row contracts against exactly one expert's delta; pad experts
#     carry zero scales so partial sums only ever add exact zeros),
#   - KV caches: batch rows along "model" (rows are independent end to
#     end), paged block pools along the block dim (pure gather/scatter).
# Verified empirically on forced-host meshes up to (2, 4): full-TP rules
# from param_pspec diverge (psum reorder), these stay bit-identical.
# ---------------------------------------------------------------------------


def serve_mesh_axes(mesh: Mesh) -> tuple[int, int]:
    """(n_expert_shards, n_model_shards) of a serving mesh."""
    shape = dict(mesh.shape)
    return shape.get("expert", 1), shape.get("model", 1)


def serve_param_pspec(path: str, shape: tuple, mesh: Mesh) -> P:
    n_model = dict(mesh.shape).get("model", 1)
    leaf = path.rsplit("/", 1)[-1]
    if leaf == "embed" and len(shape) >= 2 and shape[0] % n_model == 0:
        return P("model", *([None] * (len(shape) - 1)))
    if leaf in ("lm_head", "unembed") and len(shape) >= 2 \
            and shape[-1] % n_model == 0:
        return P(*([None] * (len(shape) - 1)), "model")
    return P(*([None] * len(shape)))


def serve_param_shardings(params: PyTree, mesh: Mesh) -> PyTree:
    from repro.peft.lora import _path_str

    def f(path, leaf):
        return NamedSharding(mesh, serve_param_pspec(
            _path_str(path), tuple(leaf.shape), mesh))

    return jax.tree_util.tree_map_with_path(f, params)


def serve_stack_shardings(mesh: Mesh) -> tuple[NamedSharding, NamedSharding]:
    """(plane_sharding, scale_sharding) for one stacked-plane entry.

    Planes are ``[E, W]`` uint32 bitplanes (or ``[E, ...]`` dense deltas);
    scales are ``[E]``.  Both shard dim 0 along "expert"; ``build_overlay``
    propagates the expert axis onto every overlay leaf it stacks."""
    return (NamedSharding(mesh, P("expert")),
            NamedSharding(mesh, P("expert")))


def serve_kv_sharding(mesh: Mesh, shape: tuple, *,
                      layout: str = "dense") -> NamedSharding:
    """Sharding for one 5-D KV buffer on the serving mesh.

    dense  [U, B,  S,  Hkv, D]: shard batch rows along "model" — rows are
           independent through attention, so this is exact.
    paged  [U, NB, BS, Hkv, D]: shard the block pool along "model" — block
           reads/writes are gathers/scatters, also exact.
    Non-dividing dims fall back to replication (smoke configs are tiny)."""
    n_model = dict(mesh.shape).get("model", 1)
    if len(shape) == 5 and shape[1] % n_model == 0:
        return NamedSharding(mesh, P(None, "model", None, None, None))
    return NamedSharding(mesh, P(*([None] * len(shape))))


def serve_cache_shardings(cache: PyTree, mesh: Mesh, *,
                          layout: str = "dense") -> PyTree:
    """Shardings for a whole decode-cache pytree: 5-D KV buffers get
    :func:`serve_kv_sharding`; everything else (lens, starts, tables,
    active flags) stays replicated — they are host-roundtripped scalars
    and row vectors."""

    def f(leaf):
        if hasattr(leaf, "ndim") and leaf.ndim == 5:
            return serve_kv_sharding(mesh, tuple(leaf.shape), layout=layout)
        return NamedSharding(mesh, P(*([None] * getattr(leaf, "ndim", 0))))

    return jax.tree_util.tree_map(f, cache)


def train_state_shardings(state_shape: PyTree, cfg: ModelConfig,
                          mesh: Mesh) -> PyTree:
    """Shardings for a full TrainState (params / optimizer slots / EF).

    AdamW moments and EF buffers shard like their parameters; Adafactor's
    factored slots inherit the param spec minus the reduced dim."""
    from repro.peft.lora import _path_str

    def f(path, leaf):
        ps = _path_str(path)
        parts = ps.split("/")
        top = parts[0]
        if top == "step" or parts[-1] == "count":
            return NamedSharding(mesh, P())
        if top in ("params", "ef"):
            return NamedSharding(
                mesh, param_pspec("/".join(parts[1:]), tuple(leaf.shape),
                                  cfg, mesh))
        if top == "opt":
            rest = parts[1:]
            if rest and rest[0] in ("mu", "nu"):
                return NamedSharding(
                    mesh, param_pspec("/".join(rest[1:]), tuple(leaf.shape),
                                      cfg, mesh))
            if rest and rest[0] == "slots":
                slot = rest[-1]                      # vr | vc | v
                ppath = "/".join(rest[1:-1])
                # param shape is unknown here; re-derive from slot shape:
                if slot == "v":
                    spec = param_pspec(ppath, tuple(leaf.shape), cfg, mesh)
                    return NamedSharding(mesh, spec)
                # factored: vr drops the last param dim, vc the 2nd-to-last
                if slot == "vr":
                    pshape = tuple(leaf.shape) + (1,)
                    spec = param_pspec(ppath, pshape, cfg, mesh)
                    return NamedSharding(mesh, P(*tuple(spec)[:-1]))
                if slot == "vc":
                    pshape = tuple(leaf.shape[:-1]) + (1, leaf.shape[-1])
                    spec = param_pspec(ppath, pshape, cfg, mesh)
                    sp = tuple(spec)
                    return NamedSharding(mesh, P(*(sp[:-2] + (sp[-1],))))
        return NamedSharding(mesh, P(*([None] * len(leaf.shape))))

    return jax.tree_util.tree_map_with_path(f, state_shape)
