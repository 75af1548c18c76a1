"""Paged KV cache: block tables + a free-list allocator (vLLM-style).

The dense-slot engine left-pads every row of a wave to one width and, on
each slot refill, splices a whole per-row KV tensor into the running
batch cache — admission is then hostage to the wave's position (a prompt
longer than ``cur`` cannot be left-padded down, and a budget that would
wrap the ring blocks the queue head).  This module removes both
constraints by storing KV in **fixed-size blocks** drawn from one pooled
buffer per attention layer:

* ``k``/``v`` pools: ``[U, NB, BS, Hkv, D]`` — ``NB`` blocks of ``BS``
  token slots, shared by every row (block 0 is reserved as the *trash*
  block: rows that have exhausted their generation budget keep stepping
  with the batch, and their dead writes are redirected there so they can
  never corrupt a live row's blocks).
* ``tables``: ``[B, MAXB]`` int32 per-row block lists (-1 = unallocated).
  Row ``b``'s token at position ``p`` lives in block ``tables[b, p //
  BS]`` at slot ``p % BS`` — one table shared by all layers, because
  every layer writes the same logical positions.
* ``lens``: ``[B]`` int32 per-row write positions; ``start``: ``[B]``
  first real (non-pad) position; ``active``: ``[B]`` bool, rows still
  generating.

Admission becomes "allocate ``ceil((Lp + max_new) / BS)`` blocks and
scatter the row's prefill KV into them" — no re-padding of the batch, no
full-row splice, and any prompt length is admissible whenever enough
blocks are free.  Prompts are left-padded only up to the next block
boundary (``Lp = ceil(L / BS) * BS``), which bounds prefill compilation
variants to one per *bucket* instead of one per length; the pad
positions are masked via ``start`` exactly like the dense path.

Attention reads are gather-based: ``pool[tables[b]]`` materialises the
row's positions in order, so the per-position validity mask is just
``start[b] <= s <= lens[b]`` (see
:func:`repro.models.attention.paged_attention_partial`).  The allocator
is host-side and O(1) per block; the device never sees the free list.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.common import dtype_of

PyTree = Any

# block 0 is never handed out: dead rows' writes are redirected to it and
# gathers of unallocated table entries are clamped onto it (then masked)
TRASH_BLOCK = 0


def round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def blocks_for(prompt_len: int, max_new: int, block_size: int) -> tuple:
    """(bucketed prompt length Lp, blocks needed for Lp + max_new).

    The prompt is left-padded to the next block boundary (compile-variant
    bucketing); decode then writes positions ``Lp .. Lp + max_new - 1``.
    """
    lp = round_up(max(prompt_len, 1), block_size)
    need = -(-(lp + max_new) // block_size)
    return lp, need


class BlockAllocator:
    """Host-side free-list allocator over ``n_blocks`` fixed-size blocks.

    Block :data:`TRASH_BLOCK` is reserved.  ``alloc`` is all-or-nothing:
    it returns ``None`` (allocating nothing) when fewer than ``n`` blocks
    are free, so admission control is one ``available`` comparison.
    """

    def __init__(self, n_blocks: int, block_size: int):
        if n_blocks < 2:
            raise ValueError("paged pool needs >= 2 blocks "
                             "(block 0 is reserved)")
        self.n_blocks = n_blocks
        self.block_size = block_size
        self._free = list(range(n_blocks - 1, 0, -1))  # pop() -> low ids first
        self.peak_in_use = 0

    @property
    def available(self) -> int:
        return len(self._free)

    @property
    def in_use(self) -> int:
        return (self.n_blocks - 1) - len(self._free)

    def alloc(self, n: int) -> Optional[list]:
        if n > len(self._free):
            return None
        out = [self._free.pop() for _ in range(n)]
        self.peak_in_use = max(self.peak_in_use, self.in_use)
        return out

    def free(self, blocks) -> None:
        for b in blocks:
            if not (0 < b < self.n_blocks):
                raise ValueError(f"freeing invalid block id {b}")
            if b in self._free:
                raise ValueError(f"double free of block {b}")
            self._free.append(b)

    # -- crash-consistency (repro.serve.snapshot) -------------------------

    def state(self) -> list:
        """Free-list snapshot in exact order.  ``alloc`` pops from the
        tail, so the order IS the future allocation order — restoring it
        verbatim makes post-resume block assignment deterministic."""
        return list(self._free)

    @classmethod
    def from_state(cls, n_blocks: int, block_size: int,
                   free: list) -> "BlockAllocator":
        """Rebuild an allocator from a snapshotted free list."""
        a = cls(n_blocks, block_size)
        ids = [int(b) for b in free]
        if len(set(ids)) != len(ids) or any(
                not (0 < b < n_blocks) for b in ids):
            raise ValueError(f"invalid snapshotted free list: {ids}")
        a._free = ids
        a.peak_in_use = a.in_use
        return a


def init_paged_cache(cfg: ModelConfig, batch: int, n_blocks: int,
                     block_size: int, max_blocks: int, dtype=None,
                     mesh=None) -> PyTree:
    """Empty paged decode state (pure-attention patterns only).

    The returned dict is what :func:`repro.models.transformer.decode_step`
    dispatches on: the presence of ``"tables"`` selects the paged
    write/attend path and per-row positions (``lens``) instead of the
    dense ring buffer's shared scalar ``cur``.

    With ``mesh=`` the per-layer block pools are partitioned along the
    mesh's ``model`` axis on their block dim (each device owns a shard of
    the pool; paged reads/writes are gathers/scatters, so sharding the
    storage dim leaves the math bit-identical).  Tables / lens / start /
    active stay replicated — they are host-roundtripped row vectors.
    """
    dtype = dtype or dtype_of(cfg)

    def pool(shape):
        # with a mesh the pool is born sharded: no full copy on device 0
        if mesh is None:
            return jnp.zeros(shape, dtype)
        from repro.distributed.sharding import serve_kv_sharding
        return jnp.zeros(shape, dtype, device=serve_kv_sharding(
            mesh, shape, layout="paged"))

    layers = {}
    for i, b in enumerate(cfg.pattern):
        if b.kind != "attn":
            raise ValueError("paged KV covers pure-attention patterns only; "
                             f"block {i} is {b.kind!r}")
        shape = (cfg.n_units, n_blocks, block_size, b.attn.n_kv,
                 b.attn.head_dim)
        layers[f"block{i}"] = {"k": pool(shape), "v": pool(shape)}
    return {
        "layers": layers,
        "tables": jnp.full((batch, max_blocks), -1, jnp.int32),
        "lens": jnp.zeros((batch,), jnp.int32),
        "start": jnp.zeros((batch,), jnp.int32),
        "active": jnp.zeros((batch,), bool),
    }


@partial(jax.jit, donate_argnums=(0,))
def insert_prefill_rows(cache: PyTree, row_layers: PyTree, js: jax.Array,
                        prompt_blocks: jax.Array, row_tables: jax.Array,
                        lens_new: jax.Array, start_new: jax.Array) -> PyTree:
    """Scatter N freshly-prefilled rows into the pooled cache.

    ``row_layers``: ``{block_i: {"k"/"v": [U, N, Lp, Hkv, D]}}`` — the
    per-row ring caches a dense prefill at ``cache_len = Lp`` produced
    (``Lp`` a multiple of the block size, so slot order IS position
    order); ``js`` [N] the batch rows being (re)filled; ``prompt_blocks``
    [N, Lp // BS] the pool blocks receiving the prompt KV; ``row_tables``
    [N, MAXB] the complete per-row block lists (prompt + decode-growth
    blocks, -1 padded).  One fused donated update per admission group —
    this replaces the dense path's whole-batch KV splice.
    """

    def put(pool, row):
        U, NB, BS, H, D = pool.shape
        N, nb = prompt_blocks.shape
        r = row.reshape(U, N, nb, BS, H, D).astype(pool.dtype)
        # [U, N, nb, BS, H, D] scattered onto blocks [N, nb]
        return pool.at[:, prompt_blocks].set(r)

    layers = {
        name: {"k": put(cache["layers"][name]["k"], row_layers[name]["k"]),
               "v": put(cache["layers"][name]["v"], row_layers[name]["v"])}
        for name in cache["layers"]
    }
    return {
        "layers": layers,
        "tables": cache["tables"].at[js].set(row_tables),
        "lens": cache["lens"].at[js].set(lens_new),
        "start": cache["start"].at[js].set(start_new),
        "active": cache["active"].at[js].set(True),
    }
