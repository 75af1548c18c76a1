"""Zero-merge expert overlays: per-row ternary deltas applied inside forward.

The serving engine historically merged a ComPEFT expert into a full copy of
the base parameters before a batch could run (`unpack_add` per leaf), which
serialises a mixed-expert request stream on swap-merge round trips.  This
module is the alternative the paper's cheap-expert claim enables: the packed
bitplanes of *several* experts stay stacked in HBM, and every projection in
the decode path computes

    y[m] = x[m] @ W_base + scale[e(m)] * (x[m] @ T_{e(m)})

with the grouped ternary kernel — no merged parameters ever exist, and one
decode batch can mix experts freely (S-LoRA-style heterogeneous batching
over compressed full-rank modules).

Three leaf-delta forms cover a dense transformer:

* :class:`MatmulDelta` — projection weights (wq/wk/wv/wo, ffn, lm_head):
  stacked planes consumed by ``ternary_matmul_grouped``.
* :class:`EmbedDelta` — the embedding table: per-token row gather on the
  embed side, transposed grouped matmul on the tied-logits side (the planes
  are packed along d, which *is* the contraction dim of the tied head).
* :class:`VectorDelta` — norm scales / biases: tiny leaves kept as dense
  per-expert stacks, gathered per row.

``plan_overlay`` decides whether a model family is coverable (dense
attention + gated-MLP stacks); anything else makes the engine fall back to
merge-on-swap.  ``build_overlay`` assembles the per-leaf stacks from the
experts' packed path-dicts; block-level leaves carry the unit axis in front
so the overlay threads through the model's ``lax.scan`` like the parameters
themselves.

The delta leaves are registered pytree nodes whose static aux data
(``n_out``/``transpose``) is plain hashable tuples: an overlay built once
per expert set has a **stable treedef**, so the compiled decode loop
(``repro.serve.decode_loop``) can close over it as a scan invariant and
re-trigger no compilation across chunk launches.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.packing import LANE

PyTree = Any


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class MatmulDelta:
    """Stacked planes of one projection leaf, matmul view [K, N].

    ``pos``/``neg``: uint32 [(U,) E, K, N//32] ([(U,) E, N, K//32] when
    ``transpose``); ``scales``: f32 [(U,) E].  The optional leading unit
    axis is stripped by the model's unit scan.

    ``mesh``: the serving mesh when the stacks are expert-parallel (the
    Pallas kernel then runs per ``expert`` shard); static, like the
    shapes.

    ``dense``: optional f32 sign stack [(U,) E, K, N] (unscaled).  On TPU
    it stays None — the grouped Pallas kernel unpacks the 2-bit planes
    in-register under the MXU contraction, so HBM traffic is the packed
    bytes.  Off-TPU (jnp reference path) re-unpacking every step is real
    ALU cost, so the overlay build materialises the active stack once
    (the S-LoRA memory/compute trade, scoped to the resident expert set).
    """

    pos: jax.Array
    neg: jax.Array
    scales: jax.Array
    n_out: int = 0
    transpose: bool = False
    dense: Optional[jax.Array] = None
    mesh: Any = None          # serving mesh of expert-parallel stacks

    def tree_flatten(self):
        return ((self.pos, self.neg, self.scales, self.dense),
                (self.n_out, self.transpose, self.mesh))

    @classmethod
    def tree_unflatten(cls, aux, children):
        pos, neg, scales, dense = children
        return cls(pos=pos, neg=neg, scales=scales, n_out=aux[0],
                   transpose=aux[1], dense=dense, mesh=aux[2])


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class EmbedDelta:
    """Stacked planes of the embedding table [V, d] (d % 32 == 0).

    ``pos``/``neg`` [E, V, d//32] feed the tied-head grouped kernel; a TPU
    stores them vocab-minor.  ``rows_pos``/``rows_neg`` [E, V*d//32] are
    the same words as the flat stacked buffers (the cache tier's, shared,
    not copied): a token's row is 32-bit-contiguous there, so the per-token
    embedding gather slices it without relaying out the table.

    ``dense``: optional f32 sign stack [E, V, d] (unscaled), materialised
    off-TPU exactly like :class:`MatmulDelta`.
    """

    pos: jax.Array      # [E, V, d//32]
    neg: jax.Array
    scales: jax.Array   # [E]
    rows_pos: jax.Array   # [E, V * d//32]
    rows_neg: jax.Array
    dense: Optional[jax.Array] = None
    mesh: Any = None

    def tree_flatten(self):
        return (self.pos, self.neg, self.scales, self.rows_pos,
                self.rows_neg, self.dense), (self.mesh,)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, mesh=aux[0])


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class VectorDelta:
    """Dense per-expert delta stack for small leaves: f32 [(U,) E, *shape]
    (scale already folded in)."""

    values: jax.Array

    def tree_flatten(self):
        return (self.values,), ()

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(values=children[0])


# ---------------------------------------------------------------------------
# Per-row application helpers (called from the model forward)
# ---------------------------------------------------------------------------


def _row_scales(eid_rows: jax.Array, scales: jax.Array) -> jax.Array:
    """[M, E] selection-and-scale matrix: S[m, e] = scales[e]·1[e(m)=e]."""
    E = scales.shape[0]
    sel = (eid_rows[:, None] == jnp.arange(E, dtype=jnp.int32)[None, :])
    return sel.astype(jnp.float32) * scales[None, :].astype(jnp.float32)


def delta_proj(x: jax.Array, md: Optional[MatmulDelta],
               eid: Optional[jax.Array]):
    """f32 delta of a projection: x [B, T, K] -> [B, T, n_out] or None."""
    if md is None or eid is None:
        return None
    B, T, K = x.shape
    rows = x.reshape(B * T, K).astype(jnp.float32)
    eid_rows = jnp.repeat(eid.astype(jnp.int32), T)
    if md.dense is not None:
        spec = "mk,enk->emn" if md.transpose else "mk,ekn->emn"
        per_e = jnp.einsum(spec, rows, md.dense, optimize=True)  # [E, M, N]
        d = jnp.einsum("emn,me->mn", per_e, _row_scales(eid_rows, md.scales),
                       optimize=True)
    else:
        from repro.kernels.ops import grouped_delta_matmul
        d = grouped_delta_matmul(rows, md.pos, md.neg, md.scales, eid_rows,
                                 transpose_rhs=md.transpose, n_out=md.n_out,
                                 mesh=md.mesh)
    return d.reshape(B, T, md.n_out)


def add_delta(y: jax.Array, d: Optional[jax.Array]) -> jax.Array:
    """y + d in f32, cast back to y.dtype (no-op when d is None)."""
    if d is None:
        return y
    return (y.astype(jnp.float32) + d.reshape(y.shape)).astype(y.dtype)


def eff_param(base: jax.Array, vd: Optional[VectorDelta],
              eid: Optional[jax.Array], expand: int = 1) -> jax.Array:
    """Per-row effective small parameter: (base + delta[e(m)]).astype.

    Returns ``base`` unchanged without a delta; otherwise a [B, 1*expand,
    *base.shape] array that broadcasts over the time (and head) axes —
    bitwise the per-row gather of the merged parameter.
    """
    if vd is None or eid is None:
        return base
    v = vd.values[eid.astype(jnp.int32)]          # [B, *shape]
    v = v.reshape(v.shape[:1] + (1,) * expand + v.shape[1:])
    return (base.astype(jnp.float32) + v).astype(base.dtype)


def embed_delta_rows(ed: Optional[EmbedDelta], tokens: jax.Array,
                     eid: Optional[jax.Array], d_model: int):
    """Per-(row, token) embedding delta: f32 [B, T, d] or None."""
    if ed is None or eid is None:
        return None
    e = eid.astype(jnp.int32)[:, None]                       # [B, 1]
    if ed.dense is not None:
        delta = ed.dense[e, tokens]                          # [B, T, d]
        return delta * ed.scales[e][..., None]
    W = ed.pos.shape[-1]

    def row(flat, ei, t):                                    # [W] words
        return jax.lax.dynamic_slice(flat, (jnp.maximum(ei, 0), t * W),
                                     (1, W))[0]

    take = jax.vmap(jax.vmap(row, (None, None, 0)), (None, 0, 0))

    def rows(tok, ei, rows_pos, rows_neg, scales):
        """Scaled ±1 rows of each token's expert; 0 where ``ei`` < 0."""
        pw = take(rows_pos, ei, tok)                         # [B, T, W]
        nw = take(rows_neg, ei, tok)
        shifts = jnp.arange(LANE, dtype=jnp.uint32)
        pb = ((pw[..., None] >> shifts) & jnp.uint32(1)).astype(jnp.float32)
        nb = ((nw[..., None] >> shifts) & jnp.uint32(1)).astype(jnp.float32)
        d = (pb - nb).reshape(pw.shape[:2] + (-1,))[..., :d_model]
        s = jnp.where(ei >= 0, scales[jnp.maximum(ei, 0)], 0.0)
        return d * s[:, None, None]

    if ed.mesh is not None:
        from repro.kernels.ops import expert_parallel
        rows = expert_parallel(rows, ed.mesh)
    return rows(tokens.astype(jnp.int32), e[:, 0], ed.rows_pos,
                ed.rows_neg, ed.scales)


def tied_logits_delta(x: jax.Array, ed: Optional[EmbedDelta],
                      eid: Optional[jax.Array], vocab: int):
    """f32 delta of the tied LM head: x [B, T, d] -> [B, T, V] or None."""
    if ed is None or eid is None:
        return None
    md = MatmulDelta(pos=ed.pos, neg=ed.neg, scales=ed.scales, n_out=vocab,
                     transpose=True, dense=ed.dense, mesh=ed.mesh)
    return delta_proj(x, md, eid)


# ---------------------------------------------------------------------------
# Overlay planning / construction
# ---------------------------------------------------------------------------

_VEC_NAMES = {"pre_norm", "ffn_norm", "post_attn_norm", "post_ffn_norm",
              "final_norm", "bq", "bk", "bv", "q_norm", "k_norm"}
_IN_PROJ = {"wq", "wk", "wv", "wg", "wu"}


@dataclasses.dataclass(frozen=True)
class LeafSpec:
    kind: str                 # "matmul" | "vector" | "embed"
    units: int                # leading unit axis length (0 = no unit axis)
    core: tuple[int, ...]     # per-unit shape
    k: int = 0                # matmul view contraction dim
    n: int = 0                # matmul view output dim


def _classify(parts: list[str], core: tuple[int, ...], units: int):
    name = parts[-1]
    if parts == ["embed"]:
        if core[1] % LANE:
            return None
        return LeafSpec("embed", 0, core)
    if parts == ["lm_head"]:
        k, n = core
        return LeafSpec("matmul", 0, core, k, n) if n % LANE == 0 else None
    if name in _VEC_NAMES:
        return LeafSpec("vector", units, core)
    if name in _IN_PROJ and len(core) >= 2:
        k, n = core[0], int(np.prod(core[1:]))
        return LeafSpec("matmul", units, core, k, n) if n % LANE == 0 else None
    if name == "wo" and len(core) == 3:       # attn out: [H, D, d]
        k, n = int(np.prod(core[:2])), core[-1]
        return LeafSpec("matmul", units, core, k, n) if n % LANE == 0 else None
    if name == "wo" and len(core) == 2:       # ffn out: [f, d]
        k, n = core
        return LeafSpec("matmul", units, core, k, n) if n % LANE == 0 else None
    return None


def plan_overlay(params: PyTree, cfg) -> Optional[dict]:
    """Map every base-param path to a LeafSpec, or None if the family is
    not coverable by the zero-merge path (MoE, mamba/rwkv, enc-dec,
    cross-attn, multimodal frontends fall back to merge-on-swap)."""
    if cfg.enc_n_units or cfg.cross_attn or cfg.frontend is not None:
        return None
    for b in cfg.pattern:
        if b.kind != "attn" or (b.ffn is not None and b.ffn.moe is not None):
            return None
    from repro.peft.lora import _path_str
    flat, _ = jax.tree_util.tree_flatten_with_path(params)
    plan = {}
    for path, leaf in flat:
        ps = _path_str(path)
        parts = ps.split("/")
        if parts[0] == "blocks":
            units, core = leaf.shape[0], tuple(leaf.shape[1:])
            if int(np.prod(core)) % LANE:
                return None     # unit rows must stay word-aligned
        else:
            units, core = 0, tuple(leaf.shape)
        spec = _classify(parts, core, units)
        if spec is None:
            return None
        plan[ps] = spec
    return plan


def _dense_values(pos: jax.Array, neg: jax.Array, scales: jax.Array,
                  n: int) -> jax.Array:
    """[E, W] word stacks -> dense f32 [E, n] with scales folded in."""
    shifts = jnp.arange(LANE, dtype=jnp.uint32)
    pb = ((pos[..., None] >> shifts) & jnp.uint32(1)).astype(jnp.float32)
    nb = ((neg[..., None] >> shifts) & jnp.uint32(1)).astype(jnp.float32)
    signs = (pb - nb).reshape(pos.shape[0], -1)[:, :n]
    return signs * scales[:, None]


def build_overlay(plan: dict, stacks: dict,
                  materialize: Optional[bool] = None,
                  mesh=None) -> Optional[dict]:
    """Shape the cache tier's stacked plane buffers into an overlay tree.

    ``stacks`` is {path: (pos [E, W], neg [E, W], scales [E], shape)} as
    produced by :func:`repro.core.packing.stack_packed` (what
    ``DeviceCache.stacked`` keeps resident).  Returns a nested dict
    mirroring the parameter tree (block leaves carry the unit axis in front
    for the scan), or None when a delta lands on a path the plan cannot
    express — the engine then falls back to merge-on-swap.

    ``materialize`` (default: off-TPU) additionally unpacks each projection
    stack to dense f32 signs once, so the jnp serve path pays zero
    per-step unpacking; on TPU the planes stay packed for the Pallas
    kernels.  ``mesh`` marks stacks that are expert-parallel on a
    serving mesh (``DeviceCache(mesh=...)``), so the packed branches run
    per ``expert`` shard.
    """
    if materialize is None:
        materialize = jax.default_backend() != "tpu"
    for path in stacks:
        if path not in plan:
            return None
    overlay: dict = {}
    for path, (pos, neg, scales, _) in stacks.items():
        spec = plan[path]
        E = pos.shape[0]
        n = int(np.prod(spec.core)) * max(spec.units, 1)
        ones = jnp.ones((E,), jnp.float32)
        if spec.kind == "vector":
            vals = _dense_values(pos, neg, scales, n)            # [E, n]
            if spec.units:
                vals = vals.reshape((E, spec.units) + spec.core)
                vals = jnp.swapaxes(vals, 0, 1)                  # [U, E, ...]
            else:
                vals = vals.reshape((E,) + spec.core)
            entry: Any = VectorDelta(values=vals)
        elif spec.kind == "embed":
            V, d = spec.core
            dense = (_dense_values(pos, neg, ones, n).reshape(E, V, d)
                     if materialize else None)
            entry = EmbedDelta(pos=pos.reshape(E, V, d // LANE),
                               neg=neg.reshape(E, V, d // LANE),
                               scales=scales, rows_pos=pos, rows_neg=neg,
                               dense=dense, mesh=mesh)
        else:                                                    # matmul
            U = max(spec.units, 1)
            shape = (E, U, spec.k, spec.n // LANE)
            dense = (_dense_values(pos, neg, ones, n)
                     .reshape(E, U, spec.k, spec.n)
                     if materialize else None)
            if spec.units:
                entry = MatmulDelta(
                    pos=jnp.swapaxes(pos.reshape(shape), 0, 1),
                    neg=jnp.swapaxes(neg.reshape(shape), 0, 1),
                    scales=jnp.broadcast_to(scales[None], (spec.units, E)),
                    n_out=spec.n,
                    dense=(jnp.swapaxes(dense, 0, 1)
                           if dense is not None else None), mesh=mesh)
            else:
                entry = MatmulDelta(pos=pos.reshape(shape)[:, 0],
                                    neg=neg.reshape(shape)[:, 0],
                                    scales=scales, n_out=spec.n,
                                    dense=(dense[:, 0]
                                           if dense is not None else None),
                                    mesh=mesh)
        node = overlay
        parts = path.split("/")
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = entry
    return overlay
