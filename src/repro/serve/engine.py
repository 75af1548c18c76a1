"""Multi-expert serving engine: continuous mixed-expert batching over
packed ternary experts.

Requests name an expert.  Since PR 2 the default scheduler is **mixed**:
requests are admitted FIFO into waves of up to ``max_batch`` rows *across*
experts, and a wave runs prefill/decode against the **base** parameters
plus a zero-merge overlay — the stacked bitplanes of every expert in the
wave, contracted per row by the grouped ternary kernels
(S-LoRA-style heterogeneous batching over ComPEFT modules; cf. "Composing
Parameter-Efficient Modules with Arithmetic Operations", Zhang et al.
2023, for why merged/composed ternary experts behave).  No merged
parameter tree is ever materialised, so a mixed request stream never pays
swap-merge round trips.  When a row finishes its generation budget and
requests are still queued, the slot is refilled in place: the newcomer's
prompt is left-padded to the wave's current position, prefilled as a
single row, and its KV state spliced into the running batch (continuous
batching).

Merge-on-swap (the PR-1 path: ``unpack_add`` every leaf into a copy of the
base) survives as a fallback for model families the overlay cannot express
(MoE/mamba/rwkv/enc-dec) and for waves whose expert set exceeds the stack
budget.  ``scheduling="grouped"`` forces the old greedy same-expert
scheduler — kept as the measured baseline of ``perf_lab --exp
mixed_serve``.

Since PR 5 decode is **device-resident**: ``decode_chunk=K`` (the default)
compiles K decode steps — including stopping masks and greedy/sampled
token selection — into one ``lax.scan`` launch with a donated KV cache
(:mod:`repro.serve.decode_loop`), and the wave loop becomes a segmented
driver that syncs with the host once per chunk to flush tokens and run
continuous admission.  ``decode_chunk=0`` keeps the eager per-token loop
as the measured baseline of ``perf_lab --exp decode_loop``; greedy
chunked decode is bit-identical to it, mid-wave admissions included."""

from __future__ import annotations

import dataclasses
import itertools
import os
import time
from collections import defaultdict, deque
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.distributed.fault import RecoveryPlan
from repro.kernels.ternary_matmul import row_block, tile_presence
from repro.models.delta import build_overlay, plan_overlay
from repro.models.model import ModelApi
from repro.models.transformer import Runtime
from repro.serve import decode_loop, paged_kv
from repro.serve import journal as journal_mod
from repro.serve import scheduler as scheduler_mod
from repro.serve import snapshot as snapshot_mod
from repro.serve import trace
from repro.serve.decode_loop import SamplingConfig
from repro.serve.expert_cache import (BASE, DeviceCache, ExpertRegistry,
                                      ExpertStore, ExpertUnavailable,
                                      as_registry)

PyTree = Any

# Request.status lifecycle: PENDING -> DONE | FAILED (terminal).  FAILED
# requests carry the error detail and are returned through the normal
# results path — an unavailable expert never crashes the wave.
PENDING = "pending"
DONE = "done"
FAILED = "failed"


@dataclasses.dataclass
class Request:
    uid: int
    expert: str
    prompt: jax.Array          # [T] int32
    max_new_tokens: int = 8
    out_tokens: list = dataclasses.field(default_factory=list)
    status: str = PENDING      # PENDING -> DONE | FAILED
    error: Optional[str] = None   # detail when status == FAILED
    # --- scheduling / SLO metadata (engine clock = seconds since run()) ---
    # All engine timing below is time.monotonic() based (immune to NTP
    # slew / wall-clock resets); t_wall is the ONE epoch stamp per
    # request, taken at run() entry, for correlating with external logs.
    priority: int = 1          # lower value = more urgent class
    deadline_s: Optional[float] = None   # absolute SLO deadline (EDF tiebreak)
    arrival_s: float = 0.0     # open-loop arrival offset; 0 = already queued
    t_wall: Optional[float] = None       # epoch seconds at arrival
    t_admit_s: Optional[float] = None    # first placed into a wave
    t_first_s: Optional[float] = None    # first token selected (TTFT anchor)
    t_done_s: Optional[float] = None     # generation budget exhausted


@dataclasses.dataclass
class EngineConfig:
    max_batch: int = 8
    cache_len: int = 128
    # None -> use the registry's configured HBM budget; an explicit value
    # sets/overrides it (ExpertRegistry.device semantics)
    device_cache_bytes: Optional[int] = None
    scheduling: str = "mixed"     # "mixed" (zero-merge) | "grouped" (merge)
    max_stack: int = 8            # max distinct experts stacked per wave
    continuous: bool = True       # refill finished slots mid-wave
    # decode steps per compiled launch (scan-compiled wave loop with one
    # host sync per chunk); 0 = the eager per-token loop
    decode_chunk: int = 16
    sampling: SamplingConfig = dataclasses.field(
        default_factory=SamplingConfig)
    # what an ExpertUnavailable at admission does: "request" fails ONLY
    # the affected requests (terminal FAILED status, wave proceeds);
    # "raise" propagates — the pre-fault-tolerance behaviour
    degrade: str = "request"
    # admission policy for the mixed path: "fifo" (bit-identical to the
    # historical deque), "priority" (classes + deadline EDF), "affinity"
    # (priority + expert-affinity wave packing) — repro.serve.scheduler
    scheduler: str = "fifo"
    # KV memory layout: "dense" = per-wave left-padded slots + ring buffer
    # (the parity baseline); "paged" = block-table pools with a free-list
    # allocator (repro.serve.paged_kv) — admission allocates blocks
    # instead of splicing KV, so any prompt length fits any wave position
    kv_layout: str = "dense"
    kv_block_size: int = 16       # token positions per KV block (paged)
    # total pool blocks (incl. the reserved trash block); None sizes the
    # pool so a full batch at cache_len never blocks on allocation
    kv_blocks: Optional[int] = None
    # serving mesh (repro.launch.mesh.make_serve_mesh, axes
    # ("expert", "model")): base params go vocab-parallel, KV pools
    # batch/block-sharded, stacked [E, ...] bitplanes expert-parallel —
    # all along dims where every output element is computed by exactly
    # one device, so token streams stay bit-identical to mesh=None.
    # None keeps today's single-device placement byte-for-byte.
    mesh: Optional[Any] = None
    # crash consistency: a directory arms the write-ahead journal
    # (repro.serve.journal) for every run() and receives periodic
    # engine snapshots (repro.serve.snapshot); snapshot_every_chunks=N
    # commits one atomic snapshot every N compiled chunks (0 = journal
    # only — resume then replays from the prompt instead of from KV)
    snapshot_dir: Optional[str] = None
    snapshot_every_chunks: int = 0


class ServeEngine:
    """Single-host engine; the model functions are the pjit'd serve path."""

    def __init__(self, api: ModelApi, rt: Runtime, base_params: PyTree,
                 registry: ExpertRegistry, ecfg: EngineConfig,
                 peft_state: Optional[dict] = None):
        self.api = api
        self.rt = rt
        self.base = base_params
        self.registry = as_registry(registry)
        self.store = self.registry.store
        self.cfg = ecfg
        self.mesh = ecfg.mesh
        if self.mesh is not None:
            axes = dict(self.mesh.shape)
            if "expert" not in axes or "model" not in axes:
                raise ValueError(
                    "EngineConfig.mesh needs ('expert', 'model') axes "
                    f"(make_serve_mesh); got {tuple(axes)}")
            from repro.distributed import sharding as shard_rules
            self._shard_rules = shard_rules
            # vocab-parallel embed / lm_head; everything else replicated
            # (contraction-dim TP would break bitwise parity — see the
            # serve rules in distributed/sharding.py)
            self.base = jax.device_put(
                base_params,
                shard_rules.serve_param_shardings(base_params, self.mesh))
        self.cache = self.registry.device(ecfg.device_cache_bytes,
                                          mesh=self.mesh)
        self._merged_name: Optional[str] = None
        self._merged_params: Optional[PyTree] = None
        self._plan = plan_overlay(base_params, api.cfg)
        self._overlays: dict[tuple, Any] = {}
        # the serve step functions are jitted once per (batch shape, overlay
        # structure); rt and cache_len are static — as is kv_sharding, a
        # hashable NamedSharding the mesh path uses to place the wave's KV
        # inside the prefill launch itself
        self._prefill = jax.jit(api.prefill, static_argnums=(2, 3),
                                static_argnames=("kv_sharding",))
        self._decode = jax.jit(api.decode_step, static_argnums=(3,))
        if ecfg.decode_chunk < 0:
            raise ValueError("decode_chunk must be >= 0")
        if ecfg.degrade not in ("request", "raise"):
            raise ValueError('degrade must be "request" or "raise", '
                             f"got {ecfg.degrade!r}")
        if ecfg.scheduler not in scheduler_mod.SCHEDULERS:
            raise ValueError(f"unknown scheduler {ecfg.scheduler!r}; "
                             f"expected one of "
                             f"{sorted(scheduler_mod.SCHEDULERS)}")
        if ecfg.kv_layout not in ("dense", "paged"):
            raise ValueError('kv_layout must be "dense" or "paged", '
                             f"got {ecfg.kv_layout!r}")
        if ecfg.kv_layout == "paged":
            if not ecfg.decode_chunk:
                raise ValueError("kv_layout='paged' needs the compiled "
                                 "decode loop; set decode_chunk > 0")
            if not self._row_mask_ok():
                raise ValueError("kv_layout='paged' needs a pure-attention "
                                 "decoder-only pattern (recurrent blocks "
                                 "and frontends keep state outside KV)")
            if ecfg.kv_block_size < 1:
                raise ValueError("kv_block_size must be >= 1")
            for b in api.cfg.pattern:
                if b.attn.window is not None and b.attn.window < ecfg.cache_len:
                    # a window < cache_len shrinks the dense per-layer ring;
                    # paged prefill needs the full position range resident
                    raise ValueError(
                        "kv_layout='paged' needs attention windows >= "
                        f"cache_len (got window={b.attn.window}, "
                        f"cache_len={ecfg.cache_len})")
        self._bs = ecfg.kv_block_size
        self._max_blocks = -(-ecfg.cache_len // max(self._bs, 1))
        self._kv_blocks = (ecfg.kv_blocks if ecfg.kv_blocks is not None
                           else ecfg.max_batch * self._max_blocks + 1)
        if ecfg.kv_layout == "paged" and self._kv_blocks < 2:
            raise ValueError("kv_blocks must be >= 2 (block 0 is reserved)")
        if ecfg.snapshot_dir is not None and not ecfg.decode_chunk:
            raise ValueError("snapshot_dir needs the compiled decode loop "
                             "(journal/snapshot commit at chunk "
                             "boundaries); set decode_chunk > 0")
        if ecfg.snapshot_every_chunks < 0:
            raise ValueError("snapshot_every_chunks must be >= 0")
        if ecfg.snapshot_every_chunks and ecfg.snapshot_dir is None:
            raise ValueError("snapshot_every_chunks needs snapshot_dir")
        self._chunk_fn = (decode_loop.make_decode_chunk(
            api, rt, ecfg.decode_chunk, ecfg.sampling, mesh=self.mesh)
            if ecfg.decode_chunk else None)
        self._select = decode_loop.make_token_select(ecfg.sampling,
                                                     mesh=self.mesh)
        # bounded rings: a long-lived engine must not grow host memory
        # with its own accounting.  Evictions are counted per ring and
        # surfaced via swap_summary()["log_dropped"]; counters that must
        # survive eviction (failed_total) are kept separately.
        self.swap_log: deque = deque(maxlen=512)
        self.wave_log: deque = deque(maxlen=4096)
        self.failed_log: deque = deque(maxlen=1024)
        self.failed_total = 0
        self._log_dropped = {"swap": 0, "wave": 0, "failed": 0}
        self._sched = None                  # last run's scheduler instance
        self._t0 = time.monotonic()         # run() resets; engine clock zero
        # per priority: [admitted, summed wait, longest wait] (bounded)
        self._adm_wait: dict[int, list] = {}
        # host spans and counters (repro.serve.trace); the prefill clock
        # lets admission host time leave out the prefills it launches
        self.counters = trace.new_counters()
        self._host_s = {"prefill": 0.0}
        self._wave_idx = 0                  # waves started, for span attrs
        self._kv_peak = 0                   # peak pool blocks in use
        self._kv_in_use = 0
        # --- crash consistency (repro.serve.journal / .snapshot) ---
        self._journal = None                # JournalWriter while run() lives
        self._chunk_idx = 0                 # global chunk counter = snap step
        self.chunk_hooks: list = []         # fired(chunk_idx) after a flush
        self._recovery_t0: Optional[float] = None
        self.recovery_stats: dict = {}
        self.resumed_requests: list = []

    # ---------------- expert management ----------------

    def _params_for(self, expert: str) -> PyTree:
        """Merge-on-swap fallback: full merged params for one expert.

        The fused plane merge itself lives in
        :meth:`ExpertRegistry.merged_params`; the engine only memoises the
        last merged expert and keeps the swap log.
        """
        if expert == BASE:
            return self.base
        if self._merged_name == expert:
            return self._merged_params
        with trace.span("engine.merge", expert=expert):
            params = self.registry.merged_params(self.base, [expert])
        self._merged_name = expert
        self._merged_params = params
        self._ring_append("swap", {"expert": expert})
        return params

    def merged_ensemble_params(self, experts: list[str],
                               weights: Optional[list[float]] = None
                               ) -> PyTree:
        """Merged-ensemble mode: W_base + sum_e α_e Δ_e in ONE sweep
        (``unpack_add_many`` via the registry — bit-identical to applying
        the α-scaled experts one at a time)."""
        return self.registry.merged_params(self.base, experts, weights)

    def _overlay_for(self, experts: tuple) -> Optional[dict]:
        """Zero-merge overlay for an ordered expert set (None → fallback)."""
        if self._plan is None:
            return None
        # an eviction of any member drops the underlying stack; the shaped
        # overlay must not outlive it (HBM accounting + staleness)
        hit = experts in self._overlays and self.cache.has_stack(experts)
        with trace.span("engine.overlay", experts=len(experts),
                        hit=int(hit)):
            if hit:
                # overlay reuse rides the resident stack — count it as a
                # stack hit so stack_hit_rate reflects plane reuse even
                # when the shaped overlay short-circuits cache.stacked()
                self.cache.stats.stack_hits += 1
                return self._overlays[experts]
            self._overlays.pop(experts, None)
            stacks = self.cache.stacked(experts)
            overlay = build_overlay(self._plan, stacks, mesh=self.mesh)
            if overlay is not None:
                while len(self._overlays) >= DeviceCache.MAX_STACKS:
                    self._overlays.pop(next(iter(self._overlays)))
                self._overlays[experts] = overlay
            return overlay

    # ---------------- graceful degradation ----------------

    def _fail(self, reqs: list[Request], err: Exception) -> None:
        """Terminal per-request failure.  ``degrade="request"`` marks ONLY
        the affected requests FAILED (error detail attached, returned via
        the normal results path) and lets the rest of the wave proceed;
        ``degrade="raise"`` propagates — the pre-fault-tolerance
        behaviour."""
        if self.cfg.degrade != "request":
            raise err
        for r in reqs:
            r.status = FAILED
            r.error = str(err)
            self.failed_total += 1
            self._ring_append("failed", {"uid": r.uid, "expert": r.expert,
                                         "error": str(err)})
            self._journal_append("fail", {"uid": r.uid, "expert": r.expert,
                                          "error": str(err)}, flush=True)

    # ---------------- bounded accounting rings ----------------

    def _ring_append(self, name: str, item: dict) -> None:
        """Append to one of the bounded logs, counting evictions (the
        ``log_dropped`` gauge) so a capped ring is never mistaken for a
        complete history."""
        ring = getattr(self, f"{name}_log")
        if getattr(ring, "maxlen", None) is not None \
                and len(ring) == ring.maxlen:
            self._log_dropped[name] += 1
        ring.append(item)

    # ---------------- write-ahead journal ----------------

    def _journal_append(self, kind: str, data: dict,
                        flush: bool = False) -> None:
        if self._journal is not None:
            self._journal.append(kind, data, t=self._now())
            if flush:
                self._journal.flush()

    def _journal_admit(self, r: Request, j: int) -> None:
        self._journal_append("admit", {
            "uid": r.uid, "expert": r.expert, "slot": j,
            "arrival_s": r.arrival_s,
            "prompt_len": int(r.prompt.shape[0])})

    def _run_meta(self, requests: list[Request], mode: str) -> dict:
        """run_start payload: everything needed to rebuild every Request
        from the journal alone (prompts included — a resumed process has
        no other source for them)."""
        return {
            "sampling": self.cfg.sampling.to_meta(),
            "scheduler": self.cfg.scheduler,
            "scheduling": mode,
            "kv_layout": self.cfg.kv_layout,
            "decode_chunk": self.cfg.decode_chunk,
            "max_batch": self.cfg.max_batch,
            "cache_len": self.cfg.cache_len,
            "wall": time.time(),
            "requests": [{
                "uid": r.uid, "expert": r.expert,
                "prompt": [int(t) for t in np.asarray(r.prompt)],
                "max_new": r.max_new_tokens, "priority": r.priority,
                "deadline_s": r.deadline_s, "arrival_s": r.arrival_s,
                "t_wall": r.t_wall,
            } for r in requests],
        }

    def _open_journal(self, requests: list[Request], mode: str) -> None:
        if self.cfg.snapshot_dir is None:
            return
        path = os.path.join(self.cfg.snapshot_dir,
                            journal_mod.JOURNAL_NAME)
        self._journal = journal_mod.JournalWriter(path, fresh=True)
        self._journal.append("run_start", self._run_meta(requests, mode))
        self._journal.sync()

    def _close_journal(self) -> None:
        if self._journal is not None:
            self._journal.close()
            self._journal = None

    # ---------------- serving loop ----------------

    def run(self, requests: list[Request],
            scheduling: Optional[str] = None) -> list[Request]:
        self._t0 = time.monotonic()     # engine clock zero for arrivals
        wall = time.time()              # the one epoch stamp per run
        for r in requests:
            if r.t_wall is None:
                r.t_wall = wall + r.arrival_s
        mode = scheduling or self.cfg.scheduling
        self._open_journal(requests, mode)
        try:
            with trace.gc_meter(self.counters):
                if mode == "grouped":
                    self._run_grouped(requests)
                else:
                    self._run_mixed(requests)
            for r in requests:
                if r.status == PENDING:
                    r.status = DONE
            self._journal_append("run_end", {"requests": len(requests)},
                                 flush=True)
        finally:
            self._close_journal()
        self._export_gauges()
        return requests

    # ---------------- kill–restart recovery ----------------

    def resume(self) -> list[Request]:
        """Recover a killed run from ``snapshot_dir``'s journal (+ latest
        snapshot, if any) and serve it to completion.

        The determinism foundation makes this exact: every row's token
        stream is a pure function of (sampling seed, uid, draw index)
        plus prompt and expert — invariant to chunk size, admission
        timing, KV layout and mesh shape.  So recovery is:

        1. replay the journal → which requests existed, what each had
           emitted, which finished/failed (``run_end`` absent = crash);
        2. restore the last snapshot's wave (KV + pending token at a
           chunk boundary, allocator free list on the paged path) and
           continue it — the regenerated tail is verified against the
           journaled suffix;
        3. every other incomplete request re-serves from its prompt
           (its KV postdates the snapshot, or it was never admitted) —
           bit-identical because streams are uid-keyed.

        Experts are refetched through the normal registry tiers (an
        unavailable expert degrades to per-request FAILED, exactly like
        live serving).  The resumed run does NOT journal or snapshot —
        single-crash tolerance; re-arm with a fresh ``run()``.  Returns
        the rebuilt request list; ``recovery_stats`` carries timing and
        the :class:`~repro.distributed.fault.RecoveryPlan`.
        """
        cfg = self.cfg
        if cfg.snapshot_dir is None:
            raise ValueError("resume() needs EngineConfig.snapshot_dir")
        if self._plan is None:
            raise ValueError("resume() supports the mixed overlay path "
                             "only (this model family is not coverable)")
        t_resume0 = time.monotonic()
        self._recovery_t0 = t_resume0
        self.recovery_stats = {}
        path = os.path.join(cfg.snapshot_dir, journal_mod.JOURNAL_NAME)
        state = journal_mod.replay(path)
        meta = state.meta
        if SamplingConfig.from_meta(meta["sampling"]) != cfg.sampling:
            raise ValueError(
                "resume(): sampling mismatch — journaled "
                f"{meta['sampling']}, engine {cfg.sampling.to_meta()}; "
                "token streams would diverge")
        if meta.get("scheduling") == "grouped":
            raise ValueError("resume() supports mixed scheduling only")
        if meta["scheduler"] != cfg.scheduler:
            raise ValueError(f"resume(): scheduler mismatch — journaled "
                             f"{meta['scheduler']!r}, engine "
                             f"{cfg.scheduler!r}")
        if meta["kv_layout"] != cfg.kv_layout:
            raise ValueError(f"resume(): kv_layout mismatch — journaled "
                             f"{meta['kv_layout']!r}, engine "
                             f"{cfg.kv_layout!r}")
        snap = None
        if state.snapshots:
            snap = snapshot_mod.load_snapshot(
                cfg.snapshot_dir, int(state.snapshots[-1]["step"]))

        # rebuild every Request from the run_start manifest, then apply
        # the journaled facts (tokens / terminal states)
        requests: list[Request] = []
        for d in meta["requests"]:
            requests.append(Request(
                uid=int(d["uid"]), expert=d["expert"],
                prompt=jnp.asarray(d["prompt"], jnp.int32),
                max_new_tokens=int(d["max_new"]),
                priority=int(d.get("priority", 1)),
                deadline_s=d.get("deadline_s"),
                arrival_s=float(d.get("arrival_s", 0.0)),
                t_wall=d.get("t_wall")))
        by_uid = {r.uid: r for r in requests}
        snap_uids = set(snap.row_uids) if snap is not None else set()
        replayed: list[Request] = []
        reserve: list[Request] = []
        for r in requests:
            toks = state.tokens.get(r.uid, [])
            if r.uid in state.failed:
                r.status = FAILED
                r.error = state.failed[r.uid]
                r.out_tokens = list(toks)
            elif len(toks) >= r.max_new_tokens:
                r.status = DONE
                r.out_tokens = list(toks[:r.max_new_tokens])
            elif snap is not None and r.uid in snap_uids:
                # continue from restored KV: tokens past the snapshot
                # regenerate deterministically (verified against the
                # journaled suffix below)
                r.out_tokens = list(toks[:snap.emitted[r.uid]])
                replayed.append(r)
            else:
                # KV postdates the snapshot (admitted after it) or the
                # request was never admitted: full re-serve, prefill
                # re-runs — bit-identical because streams are uid-keyed
                r.out_tokens = []
                reserve.append(r)

        self._t0 = time.monotonic()        # resume-run engine clock zero
        sched = scheduler_mod.make_scheduler(cfg.scheduler)
        self._sched = sched
        if cfg.kv_layout == "paged":
            self._validate_paged(reserve)
        for r in reserve:
            if r.status == PENDING:
                # arrival offsets are relative to the ORIGINAL clock zero;
                # anything already due at crash time is due now
                r.arrival_s = max(0.0, r.arrival_s - state.last_t)
                sched.push(r)
        if snap is not None:
            resident = [n for n in snap.meta.get("resident", ())
                        if n != BASE]
            if resident:
                try:          # warm the device cache; purely opportunistic
                    self.registry.prefetch(resident)
                except ExpertUnavailable:
                    pass
        continued = demoted = 0
        with trace.gc_meter(self.counters):
            if snap is not None and any(by_uid[u].status == PENDING
                                        for u in snap_uids):
                with self._wave_span(len(snap.row_uids),
                                     len(snap.meta["experts"])):
                    continued, demoted = self._resume_wave(snap, by_uid,
                                                           sched)
            self._drain(sched)
        for r in requests:
            if r.status == PENDING:
                r.status = DONE
        self._verify_journal_prefix(requests, state)
        self.recovery_stats.update({
            "resume_seconds": time.monotonic() - t_resume0,
            "plan": RecoveryPlan(
                snapshot_step=snap.step if snap is not None else None,
                journal_records=state.n_records,
                replayed_rows=continued,
                reprefilled_rows=len(reserve) + demoted)})
        self._recovery_t0 = None
        self.resumed_requests = requests
        self._export_gauges()
        return requests

    def _resume_wave(self, snap, by_uid: dict, sched) -> tuple:
        """Restore the snapshotted in-flight wave (KV, pending tokens,
        slot composition, paged allocator) and run it to completion via
        the shared chunk loop.  Returns ``(continued, demoted)`` row
        counts; on a failed expert refetch the dead expert's rows FAIL
        and every other incomplete row is demoted to a full re-serve."""
        experts = list(snap.meta["experts"])
        live = [u for u in snap.row_uids
                if by_uid[u].status == PENDING]
        try:
            overlay = self._overlay_for(tuple(experts))
        except ExpertUnavailable as e:
            demoted = 0
            for u in live:
                r = by_uid[u]
                if r.expert == e.name:
                    self._fail([r], e)
                else:
                    r.out_tokens = []
                    sched.push(r)
                    demoted += 1
            return 0, demoted
        if overlay is None:
            raise RuntimeError("resume(): snapshotted wave is not "
                               "coverable by the zero-merge overlay")
        rows = [by_uid[u] for u in snap.row_uids]
        self._mark_admitted(rows)
        slot = {e: i for i, e in enumerate(experts)}
        eid = jnp.asarray([slot[r.expert] for r in rows], jnp.int32)
        keys = decode_loop.row_keys(self.cfg.sampling.seed,
                                    [r.uid for r in rows])
        # logical arrays -> this engine's placement (possibly a different
        # mesh shape than the writer's; values are placement-invariant)
        cache, tok = snap.device_state(self)
        if self.cfg.kv_layout == "paged":
            alloc = paged_kv.BlockAllocator.from_state(
                self._kv_blocks, self._bs, snap.meta["alloc_free"])
            row_blocks = {int(j): [int(b) for b in bl]
                          for j, bl in snap.meta["row_blocks"].items()}
            self._kv_in_use = alloc.in_use
            self._kv_peak = max(self._kv_peak, alloc.peak_in_use)
            try:
                admitted, chunks = self._chunk_loop(
                    rows, experts, slot, overlay, eid, tok, keys, cache,
                    sched, alloc=alloc, row_blocks=row_blocks)
            finally:
                for j in list(row_blocks):
                    alloc.free(row_blocks.pop(j))
                self._kv_in_use = alloc.in_use
                assert alloc.in_use == 0, (
                    f"paged KV leak on resume: {alloc.in_use} blocks "
                    "still allocated at wave teardown")
        else:
            admitted, chunks = self._chunk_loop(
                rows, experts, slot, overlay, eid, tok, keys, cache,
                sched, cur=int(snap.meta["cur"]))
        self._ring_append("wave", {"rows": len(rows),
                                   "experts": len(experts),
                                   "admitted": admitted, "chunks": chunks,
                                   "resumed": True})
        return len(live), 0

    @staticmethod
    def _verify_journal_prefix(requests: list[Request], state) -> None:
        """Bit-identity guard: every journaled token must be a prefix of
        the post-resume stream.  A mismatch means the restored state or
        the refetched experts diverged — the resume is unsound and must
        fail loudly rather than return silently different tokens."""
        for r in requests:
            if r.status == FAILED:
                continue
            pre = [int(t) for t in
                   state.tokens.get(r.uid, [])][:r.max_new_tokens]
            got = [int(t) for t in r.out_tokens[:len(pre)]]
            if got != pre:
                raise RuntimeError(
                    f"resume(): request {r.uid} diverged from the "
                    f"journal (journaled {pre[:8]}, regenerated "
                    f"{got[:8]})")

    # -- engine clock / SLO bookkeeping --

    def _now(self) -> float:
        return time.monotonic() - self._t0

    def _mark_admitted(self, reqs: list[Request]) -> None:
        now = self._now()
        for r in reqs:
            if r.t_admit_s is None:
                r.t_admit_s = now
                wait = now - r.arrival_s
                w = self._adm_wait.setdefault(r.priority, [0, 0.0, wait])
                w[0] += 1
                w[1] += wait
                w[2] = max(w[2], wait)

    def _mark_first(self, reqs: list[Request]) -> None:
        now = self._now()
        for r in reqs:
            if r.t_first_s is None and r.max_new_tokens > 0:
                r.t_first_s = now

    def _mark_done(self, r: Request) -> None:
        if r.t_done_s is None and len(r.out_tokens) >= r.max_new_tokens:
            r.t_done_s = self._now()

    def _prefetch_upcoming(self, upcoming, extra=()) -> None:
        """Admission-time prefetch: stage promotions for every distinct
        expert named by queued-but-nonresident requests (bounded
        lookahead), plus ``extra`` (the wave about to be served, so its E
        cold fetches run concurrently instead of serially inside the
        stack build).  A wave then never stalls on a cold fetch that
        could have overlapped the previous wave's decode steps."""
        names = list(dict.fromkeys(extra))
        seen = set(names)
        for r in itertools.islice(upcoming, 0, 4 * self.cfg.max_batch):
            if r.expert not in seen:
                seen.add(r.expert)
                names.append(r.expert)
        if names:
            self.registry.prefetch(names)

    def _run_grouped(self, requests: list[Request]) -> list[Request]:
        """PR-1 baseline: greedy same-expert batching, merge per expert."""
        groups: dict[str, list[Request]] = defaultdict(list)
        for r in requests:
            groups[r.expert].append(r)
        order = list(groups)
        for gi, expert in enumerate(order):
            if gi + 1 < len(order):
                # overlap the next group's cold fetch with this group's
                # merge + decode steps
                self.registry.prefetch([order[gi + 1]])
            try:
                params = self._params_for(expert)
            except ExpertUnavailable as e:
                # one dead expert fails ITS group; every other group serves
                self._fail(groups[expert], e)
                continue
            reqs = groups[expert]
            for i in range(0, len(reqs), self.cfg.max_batch):
                self._serve_batch(params, reqs[i:i + self.cfg.max_batch])
        return requests

    def _validate_paged(self, requests: list[Request]) -> None:
        """Push-time feasibility: a request that can NEVER be placed (needs
        more blocks than the whole pool, or more positions than
        ``cache_len``) fails terminally instead of deadlocking the queue."""
        for r in requests:
            lp, need = paged_kv.blocks_for(int(r.prompt.shape[0]),
                                           r.max_new_tokens, self._bs)
            if (lp + r.max_new_tokens > self._max_blocks * self._bs
                    or need > min(self._max_blocks, self._kv_blocks - 1)):
                self._fail([r], ValueError(
                    f"request {r.uid} needs {need} KV blocks "
                    f"({lp}+{r.max_new_tokens} positions); pool holds "
                    f"{self._kv_blocks - 1} usable blocks of {self._bs} "
                    f"with {self._max_blocks} per row"))

    def _run_mixed(self, requests: list[Request]) -> list[Request]:
        """Continuous mixed-expert batching (zero-merge hot path).

        Admission order is delegated to the configured scheduler
        (``scheduler="fifo"`` replicates the historical deque
        bit-identically); requests with a future ``arrival_s`` are held
        back until the engine clock reaches them, which is what lets
        :mod:`benchmarks.traffic` replay open-loop timelines."""
        if self._plan is None:
            # family not coverable at all: hand the WHOLE list to the
            # grouped scheduler so it merges once per expert, not per wave
            return self._run_grouped(requests)
        if self.cfg.kv_layout == "paged":
            self._validate_paged(requests)
        sched = scheduler_mod.make_scheduler(self.cfg.scheduler)
        self._sched = sched
        sched.on_decision = lambda d: self._journal_append("sched", d)
        for r in requests:
            if r.status == PENDING:
                sched.push(r)
        self._drain(sched)
        return requests

    def _drain(self, sched) -> None:
        """Serve the scheduler dry: build waves, serve them, honor future
        arrivals.  Shared by :meth:`_run_mixed` and :meth:`resume` (which
        seeds the scheduler with re-served requests by hand)."""
        while sched.pending():
            sched.release(self._now())
            if not sched.ready_count():
                nxt = sched.next_arrival()
                if nxt is None:
                    break
                # open-loop idle: sleep toward the next arrival (bounded,
                # so a clock hiccup never wedges the loop)
                time.sleep(min(max(nxt - self._now(), 0.0), 0.05))
                continue
            with trace.span("engine.schedule", wave=self._wave_idx + 1,
                            ready=sched.ready_count()):
                wave, experts = sched.take_wave(self.cfg.max_batch,
                                                self.cfg.max_stack)
                if wave:
                    self._prefetch_upcoming(
                        sched.peek(4 * self.cfg.max_batch), extra=experts)
            if not wave:
                continue
            overlay = None
            while wave:
                try:
                    overlay = self._overlay_for(tuple(experts))
                    break
                except ExpertUnavailable as e:
                    # evict the dead expert's rows from the wave and retry
                    # the (shrunken) stack build; the healthy rows serve
                    hit = [r for r in wave if r.expert == e.name]
                    if not hit:
                        raise    # not from this wave: don't loop forever
                    self._fail(hit, e)
                    wave = [r for r in wave if r.expert != e.name]
                    experts = [x for x in experts if x != e.name]
            if not wave:
                continue
            if overlay is None:
                # family/leaf not coverable -> merge-on-swap fallback
                self._run_grouped(wave)
                continue
            self._serve_wave(wave, experts, overlay, sched)

    def _pad_prompts(self, reqs: list[Request]) -> tuple:
        """Left-pad prompts to one width.  Returns (tokens [B, T],
        start [B] — each row's first real position, for the pad mask)."""
        T = max(int(r.prompt.shape[0]) for r in reqs)
        toks = jnp.stack([jnp.pad(r.prompt, (T - r.prompt.shape[0], 0),
                                  constant_values=1) for r in reqs]
                         ).astype(jnp.int32)
        start = jnp.asarray([T - int(r.prompt.shape[0]) for r in reqs],
                            jnp.int32)
        return toks, start

    def _kv_sharding_for(self, batch: int):
        """Static ``kv_sharding`` for a wave prefill: batch rows sharded
        along the mesh's ``model`` axis when they divide evenly (rows are
        independent end to end, so placement never changes a value).
        None on the single-device path and for single-row admission
        prefills — their KV is spliced/scattered into the wave cache,
        which keeps its own placement."""
        if self.mesh is None:
            return None
        n = dict(self.mesh.shape).get("model", 1)
        if n <= 1 or batch % n != 0:
            return None
        return self._shard_rules.serve_kv_sharding(
            self.mesh, (0, batch, 0, 0, 0))

    def _row_mask_ok(self) -> bool:
        # per-row left-pad masking needs every position to live in
        # attention KV state (recurrent blocks consume pads through their
        # state; frontends prepend non-text positions)
        c = self.api.cfg
        return (all(b.kind == "attn" for b in c.pattern)
                and c.frontend is None and not c.cross_attn
                and not c.enc_n_units)

    def _can_admit(self) -> bool:
        # slot refill splices per-row KV state; only the pure-attention
        # families keep all decode state per-row
        return (self.cfg.continuous
                and all(b.kind == "attn" for b in self.api.cfg.pattern))

    def _serve_wave(self, wave: list[Request], experts: list[str],
                    overlay: dict, sched) -> None:
        with self._wave_span(len(wave), len(experts)):
            if self.cfg.kv_layout == "paged":
                return self._serve_wave_paged(wave, experts, overlay, sched)
            if self.cfg.decode_chunk:
                return self._serve_wave_chunked(wave, experts, overlay,
                                                sched)
            return self._serve_wave_eager(wave, experts, overlay, sched)

    def _wave_span(self, rows: int, experts: int):
        """``engine.wave`` around one wave, under the next wave index
        (the ``wave`` attribute of every span inside it)."""
        self._wave_idx += 1
        return trace.span("engine.wave", wave=self._wave_idx, rows=rows,
                          experts=experts)

    def _prefill_span(self, reqs: list[Request], bucket: int,
                      slot: Optional[dict] = None):
        """``engine.prefill`` around one prefill call: counted in
        ``prefill_calls`` and timed on the prefill clock, which admission
        host time leaves out.

        A prefill through the wave's overlay (``slot``: expert -> stack
        index) also counts the grouped kernel's expert passes over its
        rows, each prompt padded to ``bucket``: row tiles × stacked
        experts in ``prefill_expert_passes``, and in
        ``prefill_expert_passes_skipped`` those whose expert has no row
        in the tile (the kernel's own rule, from host-side ids)."""
        self.counters["prefill_calls"] += 1
        attrs = {"wave": self._wave_idx, "rows": len(reqs),
                 "bucket": bucket,
                 "prompt_tokens": sum(int(r.prompt.shape[0]) for r in reqs)}
        if len(reqs) == 1:
            attrs["uid"] = reqs[0].uid
        if slot is not None:
            attrs["stack_experts"] = len(slot)
            eid_rows = np.repeat(np.asarray([slot[r.expert] for r in reqs],
                                            np.int32), bucket)
            present = tile_presence(eid_rows, row_block(eid_rows.size),
                                    len(slot), xp=np)
            self.counters["prefill_expert_passes"] += present.size
            self.counters["prefill_expert_passes_skipped"] += int(
                present.size - present.sum())
        return trace.timed(self._host_s, "prefill", "engine.prefill",
                           **attrs)

    def _pack_span(self):
        """``engine.prefill.pack``: host work before the prefill program
        is enqueued, timed into ``prefill_pack_s``."""
        return trace.timed(self.counters, "prefill_pack_s",
                           "engine.prefill.pack")

    def _admission_block_reason(self, nxt: Request, cur: int, slot: dict,
                                alloc) -> Optional[str]:
        """Why ``nxt`` cannot be placed into a finished slot right now
        (None = placeable).  Dense slots are hostage to the wave position
        (no left-pad down, no ring wrap); paged slots only need free
        blocks."""
        if (nxt.expert not in slot
                and len(slot) >= self.cfg.max_stack):
            return "stack"
        if alloc is None:
            if int(nxt.prompt.shape[0]) > cur:
                return "position"     # cannot left-pad down
            if cur + nxt.max_new_tokens > self.cfg.cache_len:
                return "wrap"         # would wrap the KV ring
        else:
            _, need = paged_kv.blocks_for(int(nxt.prompt.shape[0]),
                                          nxt.max_new_tokens, self._bs)
            if need > alloc.available:
                return "kv_blocks"
        return None

    def _try_admissions(self, rows, done, cur, experts, slot, overlay,
                        eid, tok, keys, cache, sched,
                        alloc=None, row_blocks=None):
        """Refill finished slots in place from the scheduler (host-side
        continuous-admission logic, shared by the eager, chunked and
        paged drivers).  ``cur`` is the host-mirrored wave position on the
        dense path (unused when ``alloc`` is given — paged rows carry
        their own positions).  Returns the updated device state plus the
        list of slots refilled this round.

        Blocked-head semantics are scheduler-defined: ``strict_fifo``
        preserves the historical head-of-line block (an unplaceable head
        stops ALL refills — the bit-identical baseline), while the
        priority/affinity schedulers scan past a blocked candidate, so a
        head whose KV blocks are exhausted defers only itself instead of
        starving placeable requests behind it."""
        t0 = time.perf_counter()
        prefill0 = self._host_s["prefill"]
        ranked = 0
        with trace.span("engine.admit", wave=self._wave_idx,
                        slots=len(done)) as sp:
            sched.release(self._now())
            refilled = []
            if alloc is not None:
                # reclaim every finished row's blocks up front so this
                # round's candidates see the whole reclaimable pool
                for j in done:
                    if j in row_blocks:
                        alloc.free(row_blocks.pop(j))
                self._kv_in_use = alloc.in_use
            blocked = False               # strict-FIFO head-of-line block
            for j in done:
                if blocked:
                    break
                admitted = rescan = True
                while rescan and not blocked:
                    admitted = False
                    rescan = False
                    cands = sched.candidates(slot)
                    ranked += len(cands)
                    for nxt in cands:
                        reason = self._admission_block_reason(nxt, cur, slot,
                                                              alloc)
                        if reason is not None:
                            if sched.strict_fifo:
                                blocked = True
                                break
                            sched.note_deferred(reason)
                            continue      # try the next placeable candidate
                        if nxt.expert not in slot:
                            try:
                                grown = self._overlay_for(
                                    tuple(experts + [nxt.expert]))
                            except ExpertUnavailable as e:
                                # fail ONLY this request and rescan — a dead
                                # expert must not block the admission queue
                                sched.remove(nxt)
                                self._fail([nxt], e)
                                rescan = True
                                break
                            if grown is None:
                                if sched.strict_fifo:
                                    blocked = True    # newcomer not coverable
                                    break
                                sched.note_deferred("overlay")
                                continue
                            experts.append(nxt.expert)
                            slot[nxt.expert] = len(experts) - 1
                            overlay = grown
                        else:
                            # the row is served entirely from the wave's
                            # resident stacked planes — the affinity lever
                            self.cache.stats.stack_hits += 1
                        sched.remove(nxt)
                        rows[j] = nxt
                        eid = eid.at[j].set(slot[nxt.expert])
                        key_j = decode_loop.row_keys(self.cfg.sampling.seed,
                                                     [nxt.uid])
                        keys = keys.at[j].set(key_j[0])
                        if alloc is not None:
                            tok, cache = self._admit_row_paged(
                                nxt, j, cache, tok, overlay, eid, key_j,
                                alloc, row_blocks, slot)
                        else:
                            tok, cache = self._admit_row(nxt, j, cur, cache,
                                                         tok, overlay, eid,
                                                         key_j, slot)
                        self._mark_admitted([nxt])
                        self._mark_first([nxt])
                        self._journal_admit(nxt, j)
                        refilled.append(j)
                        admitted = True
                        break             # slot j filled; move to the next
                    if admitted:
                        break
            sp.set_metadata(admitted=len(refilled),
                            candidates_ranked=ranked)
        self.counters["admissions"] += len(refilled)
        self.counters["admit_host_s"] += (time.perf_counter() - t0 - (
            self._host_s["prefill"] - prefill0))
        return rows, experts, overlay, eid, tok, keys, cache, refilled

    def _serve_wave_eager(self, wave: list[Request], experts: list[str],
                          overlay: dict, sched) -> None:
        """PR-2 baseline: one jitted decode dispatch + one host sync per
        generated token.  Kept (``decode_chunk=0``) as the measured
        baseline of ``perf_lab --exp decode_loop``.  Token selection goes
        through the same on-device selector as the compiled loop, so
        temperature/top-k sampling is eager-vs-chunked reproducible: row
        streams depend only on (seed, uid, draw index)."""
        self._mark_admitted(wave)
        slot = {e: i for i, e in enumerate(experts)}
        eid = jnp.asarray([slot[r.expert] for r in wave], jnp.int32)
        cache, tok, keys, cur = self._wave_prefill(wave, eid, overlay,
                                                   slot)
        rows: list[Optional[Request]] = list(wave)
        admitted = 0
        while True:
            tok_np = np.asarray(tok).ravel()   # one host sync per step
            for j, r in enumerate(rows):
                if r is not None and len(r.out_tokens) < r.max_new_tokens:
                    r.out_tokens.append(int(tok_np[j]))
                    self._mark_done(r)
            done = [j for j, r in enumerate(rows) if r is None
                    or r.status == FAILED
                    or len(r.out_tokens) >= r.max_new_tokens]
            # continuous admission: refill finished slots in place
            if sched is not None and sched.pending() and self._can_admit():
                (rows, experts, overlay, eid, tok, keys, cache,
                 refilled) = self._try_admissions(
                     rows, done, cur, experts, slot, overlay, eid, tok,
                     keys, cache, sched)
                for j in refilled:
                    # the newcomer's prefill selection IS its first
                    # generated token; record it now — the next loop-top
                    # append only sees the decode output that consumes it
                    if rows[j].max_new_tokens > 0:
                        rows[j].out_tokens.append(int(tok[j, 0]))
                        self._mark_done(rows[j])
                admitted += len(refilled)
                done = [j for j, r in enumerate(rows) if r is None
                        or r.status == FAILED
                        or len(r.out_tokens) >= r.max_new_tokens]
            if len(done) == len(rows):
                break
            logits, cache = self._decode(self.base, tok, cache, self.rt,
                                         delta=overlay, eid=eid)
            # draw index = tokens already emitted (pending tok was just
            # appended above) — matches the compiled loop's gen stream
            gen = jnp.asarray([len(r.out_tokens) if r is not None else 0
                               for r in rows], jnp.int32)
            tok = self._select(logits, keys, gen)
            cur += 1
        self._ring_append("wave", {"rows": len(wave),
                                   "experts": len(experts),
                                   "admitted": admitted, "chunks": 0})

    def _drive_chunk(self, params, overlay, eid, tok, cache, rows, keys):
        """Launch ONE compiled K-step chunk and flush its ``[B, K]`` token
        buffer into the rows (a single host sync).  Shared by the mixed
        wave and the grouped batch drivers — the flush count
        (``min(K, remaining)``) and the ``gen`` stream indices must match
        the scan body's emit semantics exactly, in one place.  Returns
        ``(tok, cache, decode_steps, launched)`` where ``decode_steps``
        advances the host-side position mirror and ``launched`` is False
        when every row was already done (no launch happened)."""
        K = self.cfg.decode_chunk
        # FAILED rows are terminal mid-wave (resume can restore a wave
        # containing them): they emit nothing and free their slot
        rem = [0 if r.status == FAILED
               else max(r.max_new_tokens - len(r.out_tokens), 0)
               for r in rows]
        if max(rem) == 0:
            return tok, cache, 0, False
        steps = decode_loop.host_decode_steps(max(rem), K)
        with trace.span("engine.decode_chunk", wave=self._wave_idx,
                        chunk=self._chunk_idx + 1, rows=len(rows)) as sp:
            with trace.span("engine.decode_chunk.launch"):
                # gen = tokens each row has generated so far (the pending
                # ``tok`` counts); indexes fold_in for reproducible sampling
                gen = jnp.asarray([len(r.out_tokens) + 1 for r in rows],
                                  jnp.int32)
                tok, cache, buf = self._chunk_fn(
                    params, overlay, eid, tok, cache,
                    jnp.asarray(rem, jnp.int32), gen, keys)
            with trace.span("engine.decode_chunk.sync"):
                buf_np = np.asarray(buf)   # ONE host sync per K steps
            with trace.span("engine.decode_chunk.flush"):
                flushed = []
                for j, r in enumerate(rows):
                    n = min(K, rem[j])
                    if n:
                        toks = [int(t) for t in buf_np[j, :n]]
                        r.out_tokens.extend(toks)
                        self._mark_done(r)
                        flushed.append({"uid": r.uid, "n": n, "toks": toks,
                                        "total": len(r.out_tokens)})
                self._chunk_idx += 1
                # the chunk boundary IS the WAL sync point: tokens reach
                # the OS before the next launch, so a SIGKILL costs at
                # most one chunk
                self._journal_append("chunk", {"i": self._chunk_idx,
                                               "rows": flushed}, flush=True)
            sp.set_metadata(steps=steps,
                            tokens=sum(min(K, n) for n in rem))
        if (self._recovery_t0 is not None
                and "first_resumed_token_s" not in self.recovery_stats):
            self.recovery_stats["first_resumed_token_s"] = (
                time.monotonic() - self._recovery_t0)
        # outside the span: a hook's time is never counted as the engine's
        for hook in list(self.chunk_hooks):
            hook(self._chunk_idx)
        return tok, cache, steps, True

    @staticmethod
    def _done_rows(rows) -> list:
        """Slots eligible for refill: budget exhausted OR terminally
        FAILED (a failed row must never keep decoding — without the
        status check a restored FAILED row would spin the wave loop
        forever at rem=0)."""
        return [j for j, r in enumerate(rows)
                if r.status == FAILED
                or len(r.out_tokens) >= r.max_new_tokens]

    def _maybe_snapshot(self, rows, experts, cache, tok, cur,
                        alloc=None, row_blocks=None) -> None:
        """Commit a crash-consistent snapshot at the configured chunk
        cadence (post-flush device state = the exact restart point)."""
        every = self.cfg.snapshot_every_chunks
        if (self._journal is None or not every
                or self._chunk_idx % every != 0):
            return
        snapshot_mod.write_snapshot(self, rows=rows, experts=experts,
                                    cache=cache, tok=tok, cur=cur,
                                    alloc=alloc, row_blocks=row_blocks)

    def _chunk_loop(self, rows, experts, slot, overlay, eid, tok, keys,
                    cache, sched, cur=0, alloc=None, row_blocks=None):
        """Shared chunked wave driver (dense and paged): launch a chunk,
        flush + journal its tokens, snapshot at the configured cadence,
        then refill finished slots from the scheduler.  The newcomer's
        first token stays ON DEVICE: it is the pending ``tok[j]`` the next
        chunk emits first — no int(tok[j, 0]) read-back per admission.
        Returns ``(admitted, chunks)``."""
        admitted = chunks = 0
        while True:
            tok, cache, steps, launched = self._drive_chunk(
                self.base, overlay, eid, tok, cache, rows, keys)
            cur += steps                   # host mirror (dense path only)
            chunks += int(launched)
            if launched:
                self._maybe_snapshot(rows, experts, cache, tok, cur,
                                     alloc=alloc, row_blocks=row_blocks)
            done = self._done_rows(rows)
            if sched is not None and sched.pending() and self._can_admit():
                (rows, experts, overlay, eid, tok, keys, cache,
                 refilled) = self._try_admissions(
                     rows, done, cur, experts, slot, overlay, eid, tok,
                     keys, cache, sched, alloc=alloc,
                     row_blocks=row_blocks)
                admitted += len(refilled)
                done = self._done_rows(rows)
            if len(done) == len(rows):
                return admitted, chunks

    def _serve_wave_chunked(self, wave: list[Request], experts: list[str],
                            overlay: dict, sched) -> None:
        """Device-resident wave loop: K decode steps (stopping masks,
        token selection, KV writes) per compiled launch, ONE host sync per
        chunk to flush the ``[B, K]`` token buffer, then host-side
        admission via the shared :meth:`_chunk_loop` driver."""
        self._mark_admitted(wave)
        slot = {e: i for i, e in enumerate(experts)}
        eid = jnp.asarray([slot[r.expert] for r in wave], jnp.int32)
        cache, tok, keys, cur = self._wave_prefill(wave, eid, overlay,
                                                   slot)
        rows: list[Request] = list(wave)
        for j, r in enumerate(rows):
            self._journal_admit(r, j)
        admitted, chunks = self._chunk_loop(rows, experts, slot, overlay,
                                            eid, tok, keys, cache, sched,
                                            cur=cur)
        self._ring_append("wave", {"rows": len(wave),
                                   "experts": len(experts),
                                   "admitted": admitted, "chunks": chunks})

    def _wave_prefill(self, wave: list[Request], eid, overlay,
                      slot: dict) -> tuple:
        """Dense wave prefill: left-pad the prompts to one width, prefill
        the whole wave and select each row's first token.  Returns
        ``(cache, tok, keys, cur)``, ``cur`` the host mirror of
        ``cache["cur"]``."""
        width = max(int(r.prompt.shape[0]) for r in wave)
        with self._prefill_span(wave, width, slot):
            with self._pack_span():
                toks, start = self._pad_prompts(wave)
            with trace.span("engine.prefill.launch"):
                logits, cache = self._prefill(
                    self.base, {"tokens": toks}, self.rt, self.cfg.cache_len,
                    delta=overlay, eid=eid, start=start,
                    kv_sharding=self._kv_sharding_for(len(wave)))
                keys = decode_loop.row_keys(self.cfg.sampling.seed,
                                            [r.uid for r in wave])
                tok = self._select(logits, keys,
                                   jnp.zeros((len(wave),), jnp.int32))
        self._mark_first(wave)
        return cache, tok, keys, width

    def _admit_row(self, r: Request, j: int, cur: int, cache, tok,
                   overlay, eid, key_row, slot: dict):
        """Prefill one newcomer left-padded to the wave position and splice
        its KV state into row j of the running batch.  The row's ``start``
        (= cur - prompt length) rides along, so the spliced row's decode
        attention ignores the left-pad positions — an admitted request
        matches the same prompt served solo."""
        row_start = cur - int(r.prompt.shape[0])

        def splice(c, rc):
            if c.ndim >= 2 and rc.ndim == c.ndim and rc.shape[1] == 1:
                return c.at[:, j].set(rc[:, 0])
            return c
        with self._prefill_span([r], cur, slot):
            with self._pack_span():
                prompt = jnp.pad(r.prompt, (row_start, 0),
                                 constant_values=1)[None].astype(jnp.int32)
                row_eid = eid[j][None]
            with trace.span("engine.prefill.launch"):
                row_logits, row_cache = self._prefill(
                    self.base, {"tokens": prompt}, self.rt,
                    self.cfg.cache_len, delta=overlay, eid=row_eid,
                    start=jnp.asarray([row_start], jnp.int32))
                new_cache = dict(cache)
                new_cache["layers"] = jax.tree_util.tree_map(
                    splice, cache["layers"], row_cache["layers"])
                new_cache["start"] = cache["start"].at[j].set(row_start)
                first = self._select(row_logits, key_row,
                                     jnp.zeros((1,), jnp.int32))   # [1, 1]
                tok = tok.at[j].set(first[0])
        return tok, new_cache

    # ---------------- paged-KV wave driver ----------------

    def _paged_prefill(self, reqs: list[Request], js: list[int], lp: int,
                       cache, tok, overlay, eid, keys_rows, row_blocks,
                       slot: dict):
        """Prefill N rows (all bucketed to prompt width ``lp``) and scatter
        their KV into the block pool.  The rows run a *dense* prefill at
        ``cache_len = lp`` — with T == S the ring fill is the identity, so
        slot order is position order and the per-row caches drop straight
        into ``lp // block_size`` pool blocks.  No batch re-padding, no
        per-row splice into a running cache."""
        with self._prefill_span(reqs, lp, slot):
            with self._pack_span():
                jsa = jnp.asarray(js, jnp.int32)
                toks = jnp.stack([jnp.pad(r.prompt,
                                          (lp - r.prompt.shape[0], 0),
                                          constant_values=1) for r in reqs]
                                 ).astype(jnp.int32)
                start = jnp.asarray([lp - int(r.prompt.shape[0])
                                     for r in reqs], jnp.int32)
                N, nbp = len(js), lp // self._bs
                ptab = np.asarray([row_blocks[j][:nbp] for j in js],
                                  np.int32)
                tables = np.full((N, self._max_blocks), -1, np.int32)
                for i, j in enumerate(js):
                    tables[i, :len(row_blocks[j])] = row_blocks[j]
            with trace.span("engine.prefill.launch"):
                logits, row_cache = self._prefill(
                    self.base, {"tokens": toks}, self.rt, lp, delta=overlay,
                    eid=eid[jsa], start=start)
                row_layers = {name: {"k": st["k"], "v": st["v"]}
                              for name, st in row_cache["layers"].items()}
                cache = paged_kv.insert_prefill_rows(
                    cache, row_layers, jsa, jnp.asarray(ptab),
                    jnp.asarray(tables), jnp.full((N,), lp, jnp.int32),
                    start)
                first = self._select(logits, keys_rows,
                                     jnp.zeros((N,), jnp.int32))
                tok = tok.at[jsa].set(first)
        return tok, cache

    def _admit_row_paged(self, r: Request, j: int, cache, tok, overlay,
                         eid, key_row, alloc, row_blocks, slot: dict):
        """Paged slot refill: allocate the row's blocks and write its
        prefill KV.  Unlike the dense path there is no wave position to
        left-pad against and no ring to wrap — any prompt length admits
        whenever enough blocks are free (the feasibility check already
        passed in ``_admission_block_reason``)."""
        lp, need = paged_kv.blocks_for(int(r.prompt.shape[0]),
                                       r.max_new_tokens, self._bs)
        row_blocks[j] = alloc.alloc(need)
        self._kv_in_use = alloc.in_use
        self._kv_peak = max(self._kv_peak, alloc.peak_in_use)
        return self._paged_prefill([r], [j], lp, cache, tok, overlay, eid,
                                   key_row, row_blocks, slot)

    def _serve_wave_paged(self, wave: list[Request], experts: list[str],
                          overlay: dict, sched) -> None:
        """Block-table wave loop: per-bucket batched prefill into pool
        blocks, then the same compiled K-step chunk driver as the dense
        path (the paged cache rides through ``decode_step`` via its
        ``tables``/``lens`` fields).  Admission control is the free list:
        a finished row's blocks return to the pool and any queued request
        whose block need fits is placeable — regardless of prompt length
        or how far the wave has decoded."""
        alloc = paged_kv.BlockAllocator(self._kv_blocks, self._bs)
        row_blocks: dict[int, list] = {}
        kept: list[Request] = []
        buckets: list[int] = []
        for r in wave:
            lp, need = paged_kv.blocks_for(int(r.prompt.shape[0]),
                                           r.max_new_tokens, self._bs)
            blocks = alloc.alloc(need)
            if blocks is None:
                # pool smaller than the wave: the overflow re-queues and
                # re-enters via a later wave or a slot refill
                sched.push(r)
                continue
            row_blocks[len(kept)] = blocks
            kept.append(r)
            buckets.append(lp)
        if not kept:
            return
        wave = kept
        self._mark_admitted(wave)
        slot = {e: i for i, e in enumerate(experts)}
        eid = jnp.asarray([slot[r.expert] for r in wave], jnp.int32)
        keys = decode_loop.row_keys(self.cfg.sampling.seed,
                                    [r.uid for r in wave])
        cache = paged_kv.init_paged_cache(self.api.cfg, len(wave),
                                          self._kv_blocks, self._bs,
                                          self._max_blocks, mesh=self.mesh)
        tok = jnp.zeros((len(wave), 1), jnp.int32)
        rows: list[Request] = list(wave)
        groups: dict[int, list] = defaultdict(list)
        for j, lp in enumerate(buckets):
            groups[lp].append(j)
        for lp in sorted(groups):
            js = groups[lp]
            tok, cache = self._paged_prefill(
                [rows[j] for j in js], js, lp, cache, tok, overlay, eid,
                keys[jnp.asarray(js, jnp.int32)], row_blocks, slot)
        self._mark_first(rows)
        for j, r in enumerate(rows):
            self._journal_admit(r, j)
        self._kv_in_use = alloc.in_use
        self._kv_peak = max(self._kv_peak, alloc.peak_in_use)
        try:
            admitted, chunks = self._chunk_loop(
                rows, experts, slot, overlay, eid, tok, keys, cache,
                sched, alloc=alloc, row_blocks=row_blocks)
        finally:
            # leak-proof teardown: every live row's blocks return to the
            # pool on ANY exit (fault paths included), and the allocator
            # must balance — a leak here would starve every later wave
            for j in list(row_blocks):
                alloc.free(row_blocks.pop(j))
            self._kv_in_use = alloc.in_use
            assert alloc.in_use == 0, (
                f"paged KV leak: {alloc.in_use} blocks still allocated "
                "at wave teardown")
        self._ring_append("wave", {"rows": len(wave),
                                   "experts": len(experts),
                                   "admitted": admitted, "chunks": chunks,
                                   "kv_blocks_peak": alloc.peak_in_use})

    def _serve_batch(self, params, reqs: list[Request]) -> None:
        """Merge-path batch (single expert): prefill then decode."""
        self._mark_admitted(reqs)
        with self._prefill_span(reqs, max(int(r.prompt.shape[0])
                                          for r in reqs)):
            with self._pack_span():
                toks, start = self._pad_prompts(reqs)
                batch = {"tokens": toks}
                if self.api.cfg.frontend is not None:
                    n = self.api.cfg.frontend.n_tokens
                    e = self.api.cfg.frontend.embed_dim
                    stub = jnp.zeros((len(reqs), n, e), jnp.float32)
                    key = ("frames" if self.api.cfg.family == "audio"
                           else "mm_embeds")
                    batch[key] = stub
            with trace.span("engine.prefill.launch"):
                logits, cache = self._prefill(
                    params, batch, self.rt, self.cfg.cache_len,
                    start=start if self._row_mask_ok() else None,
                    kv_sharding=self._kv_sharding_for(len(reqs)))
        if self.cfg.decode_chunk:
            return self._decode_batch_chunked(params, reqs, logits, cache)
        keys = decode_loop.row_keys(self.cfg.sampling.seed,
                                    [r.uid for r in reqs])
        tok = self._select(logits, keys, jnp.zeros((len(reqs),), jnp.int32))
        self._mark_first(reqs)
        steps = max(r.max_new_tokens for r in reqs)
        for _ in range(steps):
            tok_np = np.asarray(tok).ravel()   # one host sync per step
            for j, r in enumerate(reqs):
                if len(r.out_tokens) < r.max_new_tokens:
                    r.out_tokens.append(int(tok_np[j]))
                    self._mark_done(r)
            logits, cache = self._decode(params, tok, cache, self.rt)
            gen = jnp.asarray([len(r.out_tokens) for r in reqs], jnp.int32)
            tok = self._select(logits, keys, gen)

    def _decode_batch_chunked(self, params, reqs: list[Request],
                              logits, cache) -> None:
        """Segmented merge-path decode: the same compiled K-step loop as
        mixed waves, with a zero overlay (``delta=None``) and no
        admission (the grouped scheduler refills between batches)."""
        keys = decode_loop.row_keys(self.cfg.sampling.seed,
                                    [r.uid for r in reqs])
        tok = self._select(logits, keys, jnp.zeros((len(reqs),), jnp.int32))
        self._mark_first(reqs)
        launched = True
        while launched:
            tok, cache, _, launched = self._drive_chunk(
                params, None, None, tok, cache, reqs, keys)

    # ---------------- accounting ----------------

    def _scheduler_stats(self) -> dict:
        s = self._sched.stats() if self._sched is not None else {
            "policy": self.cfg.scheduler, "queue_depth_max": 0,
            "deferred": 0}
        s["admission_wait_s"] = {
            str(p): {"n": n, "mean": total / n, "max": longest}
            for p, (n, total, longest) in sorted(self._adm_wait.items())}
        return s

    def _kv_stats(self) -> dict:
        total = (self._kv_blocks - 1 if self.cfg.kv_layout == "paged"
                 else None)
        return {"layout": self.cfg.kv_layout,
                "block_size": self._bs,
                "blocks_total": total,
                "blocks_in_use": self._kv_in_use,
                "blocks_peak": self._kv_peak}

    def swap_summary(self) -> dict:
        s = self.cache.stats.as_dict()
        s["n_swaps"] = len(self.swap_log)
        s["n_waves"] = len(self.wave_log)
        s["admitted"] = sum(x["admitted"] for x in self.wave_log)
        s["failed"] = self.failed_total
        s["log_dropped"] = dict(self._log_dropped)
        hits = s.get("stack_hits", 0)
        builds = s.get("stack_builds", 0)
        s["stack_hit_rate"] = hits / max(hits + builds, 1)
        s["scheduler"] = self._scheduler_stats()
        s["kv"] = self._kv_stats()
        s["counters"] = dict(self.counters)
        if self.mesh is not None:
            s["mesh"] = dict(self.mesh.shape)
            s["shards"] = self.cache.shard_summary()
        return s

    def _export_gauges(self) -> None:
        """Publish serving gauges onto the device cache so
        ``registry.health()`` surfaces them next to swap/straggler state."""
        s = self.cache.stats
        hits = getattr(s, "stack_hits", 0)
        builds = getattr(s, "stack_builds", 0)
        self.cache.gauges = {
            "stack_hit_rate": hits / max(hits + builds, 1),
            "scheduler": self._scheduler_stats(),
            "kv": self._kv_stats(),
        }
        if self.mesh is not None:
            self.cache.gauges["shards"] = self.cache.shard_summary()
