"""Multi-expert memory hierarchy — the paper's headline serving scenario.

:class:`ExpertRegistry` is the front door: one named library of
:class:`~repro.expert.Expert` artifacts whose storage tiers mirror §1 of
the paper:

  RemoteExpertStore (REMOTE tier)    — wire-format blobs behind an
                                       :class:`~repro.transport.ExpertTransport`
                                       (filesystem / simulated link / HTTP);
                                       fetched + checksum-verified on first
                                       use, then cached cold-locally
  ExpertStore   (cold-local tier)    — packed artifacts, or Golomb-coded
                                       blobs (``cold_golomb=True``) decoded
                                       on promotion in one vectorized pass
  DeviceCache   (HBM tier, LRU)      — *packed* bitplane trees, bounded by a
                                       byte budget; evicts LRU

Promotion up the lattice can be **pipelined**: :meth:`DeviceCache.prefetch`
stages fetch → Golomb-decode → plane build on worker threads, so a remote
transfer for expert B overlaps the decode (or the decode steps the engine
is running) for expert A.  ``fetch`` then only pays the device_put.

The device tier is packed-resident: experts stay in the 2-bit bitplane form
end-to-end.  The cache also exposes **stacked plane buffers**
(:meth:`DeviceCache.stacked`): for a set of resident experts, one
``[E, words]`` buffer per leaf path that the batched serving kernels
(``ternary_matmul_grouped`` / ``unpack_add_many``) consume directly — the
zero-merge mixed-expert decode path never materialises merged parameters.
Stacks are invalidated when a member is evicted, and stack bytes count
against the same HBM budget as the packed trees: an over-capacity stack
build evicts (other stacks first, then LRU non-member trees).

Swap cost accounting is explicit: every promotion records bytes moved, so
benchmarks can report transmission bytes and load latency, and the engine
can amortise swaps across batches.
"""

from __future__ import annotations

import dataclasses
import threading
import time
import warnings
from collections import OrderedDict
from concurrent.futures import Future, ThreadPoolExecutor
from typing import Any, Optional

import jax
import numpy as np

from repro.core import tree_packed_bytes
from repro.core.packing import stack_packed, stacked_bytes
from repro.distributed.fault import StragglerMonitor
from repro.expert import GOLOMB, PACKED, Expert, as_expert

# canonical sign->planes bridge lives with the Expert artifact now
from repro.expert import planes_from_signs as _planes_from_signs  # noqa: F401
from repro.serve import trace
from repro.transport.retry import ExpertNotFound
from repro.transport.wire import TransportError, WireFormatError

PyTree = Any

BASE = "__base__"   # pseudo-expert: serve the unmodified base weights

DEFAULT_DEVICE_BYTES = 1 << 28

_UNSET = object()   # "caller did not pass mesh=" sentinel (None is a value)

DEFAULT_QUARANTINE_AFTER = 3     # consecutive fetch failures -> quarantine
DEFAULT_QUARANTINE_PROBE_S = 30.0


class ExpertUnavailable(TransportError):
    """One expert cannot be promoted right now — the typed, per-request
    failure the engine degrades on (the affected request gets a terminal
    ``failed`` status; the rest of the wave proceeds).

    ``terminal=True`` means retrying cannot help (never published, bad
    wire blob); ``quarantined=True`` means the expert's health account
    tripped and fetches are suppressed until the timed re-probe.
    Subclasses :class:`~repro.transport.wire.TransportError` so existing
    ``except TransportError`` callers keep working.
    """

    def __init__(self, name: str, reason: str, *, terminal: bool = False,
                 quarantined: bool = False):
        super().__init__(f"expert {name!r} unavailable: {reason}")
        self.name = name
        self.reason = reason
        self.terminal = terminal
        self.quarantined = quarantined


@dataclasses.dataclass
class SwapStats:
    store_to_host_bytes: int = 0
    host_to_device_bytes: int = 0
    promotions: int = 0
    evictions: int = 0
    hits: int = 0
    misses: int = 0
    seconds: float = 0.0
    stack_builds: int = 0
    stack_hits: int = 0
    stack_bytes: int = 0
    stack_evictions: int = 0
    golomb_decode_seconds: float = 0.0
    prefetch_issued: int = 0
    prefetch_hits: int = 0          # fetch() served from a staged future
    prefetch_seconds: float = 0.0   # off-thread fetch+decode time (overlapped)
    remote_fetches: int = 0
    remote_bytes: int = 0
    remote_seconds: float = 0.0
    cold_evictions: int = 0         # refetchable blobs dropped by the
                                    # cold tier's byte-budget LRU
    prefetch_errors: int = 0        # staged promotions that failed (counted,
                                    # never silently dropped)
    retries: int = 0                # transport-level retry attempts (mirror
                                    # of the transport's ledger)
    quarantines: int = 0            # expert health trips (consecutive
                                    # failures -> timed quarantine)
    transport_bytes_wasted: int = 0  # bytes fetched but never served (mirror
                                     # of the transport's ledger)
    straggler_flags: int = 0        # promotions flagged slow vs the EWMA
    straggler_recommendation: str = "healthy"   # StragglerMonitor verdict
    n_expert_shards: int = 1        # expert-parallel shards of the stacked
                                    # planes (1 = single-device cache)

    def as_dict(self):
        return dataclasses.asdict(self)


class ExpertStore:
    """Cold tier: name -> :class:`~repro.expert.Expert`.

    ``cold_golomb=True`` keeps only Golomb-Rice streams (the paper's
    storage-optimal wire format) instead of bitplanes; promotion then pays
    one *batched* host-side decode over all leaves of the expert
    (:func:`repro.core.golomb.decode_tree` — the vectorized codec, no
    per-bit Python loops) before packing to device planes.

    Accepts both Experts and legacy ``ExpertArtifact`` objects on
    :meth:`put`; :meth:`get` always returns an Expert.

    ``budget_bytes`` bounds the **refetchable** entries (blobs registered
    via :meth:`_account` — in practice the wire blobs a
    :class:`RemoteExpertStore` caches after a fetch) with an LRU: when the
    accounted bytes exceed the budget, least-recently-used entries are
    dropped and re-fetched from their upstream tier on next use.  Experts
    ``put`` directly are the tier's source of truth and are never evicted.
    """

    def __init__(self, cold_golomb: bool = False,
                 budget_bytes: Optional[int] = None):
        self.cold_golomb = cold_golomb
        self.budget_bytes = budget_bytes
        self.cold_evictions = 0
        self._lru: OrderedDict[str, int] = OrderedDict()
        self._store: dict[str, Expert] = {}
        self._blobs: dict[str, dict] = {}
        self._meta: dict[str, dict] = {}

    # ---- cold byte-budget LRU (refetchable entries only) ---------------
    def _account(self, name: str, nbytes: int) -> None:
        """Register ``name`` as a refetchable cached blob of ``nbytes``
        and evict LRU refetchable entries past the budget (the entry just
        touched is always kept — it is the one in use)."""
        if self.budget_bytes is None:
            return
        self._lru[name] = nbytes
        self._lru.move_to_end(name)
        while (sum(self._lru.values()) > self.budget_bytes
               and len(self._lru) > 1):
            victim, _ = self._lru.popitem(last=False)
            self._evict_cold(victim)
            self.cold_evictions += 1

    def _touch(self, name: str) -> None:
        if name in self._lru:
            self._lru.move_to_end(name)

    def _evict_cold(self, name: str) -> None:
        self._store.pop(name, None)
        self._blobs.pop(name, None)
        self._meta.pop(name, None)

    def cold_resident_bytes(self) -> int:
        """Bytes held by the budget-bounded (refetchable) entries."""
        return sum(self._lru.values())

    def put(self, art) -> Expert:
        ex = as_expert(art)
        if not self.cold_golomb:
            self._store[ex.name] = ex
            return ex
        blobs = dict(ex.as_(GOLOMB))
        self._blobs[ex.name] = blobs
        self._meta[ex.name] = {
            "leaf": {p: dict(m) for p, m in ex._leaf_meta.items()},
            "kind": ex.kind, "density": ex.density, "alpha": ex.alpha,
        }
        return ex

    def get(self, name: str) -> Expert:
        ex, decode = self._get_cached(name)
        if decode:
            ex.as_(PACKED)   # one batched decode now, so promotion timing
        return ex            # is attributed to the store tier

    def _get_cached(self, name: str) -> tuple[Expert, bool]:
        """Cheap dict reads only (LRU touch + entry lookup) — callers that
        need thread safety against concurrent LRU eviction wrap THIS in
        their lock and run the returned expert's (expensive) Golomb decode
        outside it.  Returns (expert, needs_decode)."""
        self._touch(name)
        if not self.cold_golomb:
            return self._store[name], False
        m = self._meta[name]
        ex = Expert(name, m["kind"], density=m["density"], alpha=m["alpha"])
        ex._leaf_meta = {p: dict(v) for p, v in m["leaf"].items()}
        ex._reps[GOLOMB] = self._blobs[name]
        return ex, True

    def __contains__(self, name: str) -> bool:
        return name in (self._blobs if self.cold_golomb else self._store)

    def names(self):
        return list(self._blobs if self.cold_golomb else self._store)

    def nbytes(self, name: str) -> int:
        if self.cold_golomb:
            return sum(len(b) for b in self._blobs[name].values())
        return self._store[name].nbytes(PACKED)


def _resolve_transport(transport, replicas, replication_factor, hedge_ms):
    """Normalize the ``transport=`` / ``replicas=`` spelling shared by
    :class:`RemoteExpertStore`, :class:`ExpertRegistry` and
    ``repro.api.registry``: a replica fleet builds a
    :class:`~repro.transport.replication.ReplicatedTransport` (consistent-
    hash placement + leaf-resumable failover + optional hedged reads)."""
    if replicas is not None:
        if transport is not None:
            raise ValueError("pass either transport= or replicas=, not both")
        from repro.transport.replication import ReplicatedTransport
        return ReplicatedTransport(
            list(replicas),
            replication_factor=(replication_factor
                                if replication_factor is not None else 2),
            hedge_ms=hedge_ms)
    if replication_factor is not None or hedge_ms is not None:
        if transport is None or not hasattr(transport, "replication_factor"):
            raise ValueError("replication_factor=/hedge_ms= need replicas= "
                             "(or an existing ReplicatedTransport)")
        if replication_factor is not None:
            transport.replication_factor = min(
                replication_factor, len(transport.replicas))
        transport.hedge_ms = hedge_ms
    if transport is None:
        raise ValueError("a remote store needs transport= or replicas=")
    return transport


class RemoteExpertStore(ExpertStore):
    """REMOTE tier: wire-format experts behind an
    :class:`~repro.transport.ExpertTransport`.

    ``get`` fetches the blob over the transport on first use
    (checksum-verified :func:`~repro.transport.wire.decode_expert`), then
    caches the Expert in the inherited cold-local tier so repeated
    promotions never refetch.  Experts :meth:`put` directly act as a local
    overlay (they shadow same-named remote artifacts); use
    :meth:`publish` to also upload through the transport.

    Thread-safe for concurrent ``get`` of distinct names — the
    :class:`DeviceCache` prefetch pipeline calls it from worker threads.

    ``budget_bytes`` bounds the cold cache of fetched wire blobs: past it,
    LRU blobs are dropped (``cold_evictions`` counts them, mirrored into
    :class:`SwapStats`) and transparently re-fetched over the transport on
    next use.  Unbounded by default, as before.

    **Health accounting**: every name carries a consecutive-failure count.
    ``quarantine_after`` retry-exhausted fetch cycles in a row trip a
    timed quarantine — for ``quarantine_probe_s`` the store raises
    :class:`ExpertUnavailable` *without* touching the transport, then the
    next ``get`` is a re-probe (success clears the account, failure
    re-arms the timer).  Terminal failures (:class:`ExpertNotFound` — the
    expert was never published — and non-checksum wire-format errors)
    surface immediately as terminal :class:`ExpertUnavailable` and do NOT
    count against health: absence is not flakiness.
    """

    def __init__(self, transport=None, cold_golomb: bool = False,
                 budget_bytes: Optional[int] = None,
                 quarantine_after: int = DEFAULT_QUARANTINE_AFTER,
                 quarantine_probe_s: float = DEFAULT_QUARANTINE_PROBE_S,
                 replicas=None, replication_factor: Optional[int] = None,
                 hedge_ms: Optional[float] = None):
        super().__init__(cold_golomb=cold_golomb, budget_bytes=budget_bytes)
        transport = _resolve_transport(
            transport, replicas, replication_factor, hedge_ms)
        self.transport = transport
        self.quarantine_after = quarantine_after
        self.quarantine_probe_s = quarantine_probe_s
        self.quarantines = 0
        self._lock = threading.Lock()
        self._wire_bytes: dict[str, int] = {}
        self._failures: dict[str, int] = {}       # consecutive, per name
        self._quarantined: dict[str, float] = {}  # name -> re-probe time
        self._fetches = 0
        self._fetch_bytes = 0
        self._fetch_seconds = 0.0

    def _local(self, name: str) -> bool:
        return ExpertStore.__contains__(self, name)

    def _check_quarantine(self, name: str) -> None:
        """Raise inside an active quarantine window; past it, let ONE
        fetch through as the re-probe (the entry stays armed until the
        probe's outcome settles it)."""
        until = self._quarantined.get(name)
        if until is not None and time.monotonic() < until:
            raise ExpertUnavailable(
                name, f"quarantined after {self._failures.get(name, 0)} "
                f"consecutive fetch failures; re-probe in "
                f"{until - time.monotonic():.2f}s", quarantined=True)

    def _record_failure(self, name: str) -> None:
        with self._lock:
            fails = self._failures.get(name, 0) + 1
            self._failures[name] = fails
            # a failed re-probe re-arms the timer without re-counting
            # toward a second quarantine event
            if fails >= self.quarantine_after:
                if name not in self._quarantined:
                    self.quarantines += 1
                self._quarantined[name] = (time.monotonic()
                                           + self.quarantine_probe_s)

    def _record_success(self, name: str) -> None:
        with self._lock:
            self._failures.pop(name, None)
            self._quarantined.pop(name, None)

    def get(self, name: str) -> Expert:
        # every read of the cold-local dicts happens under the lock: the
        # byte-budget LRU may evict entries from a concurrent thread's
        # _account, so check-then-read must be atomic.  The expensive
        # Golomb decode still runs OUTSIDE the lock (prefetch threads keep
        # overlapping decodes) — the snapshot holds its own blob refs.
        with self._lock:
            ex, decode = (self._get_cached(name) if self._local(name)
                          else (None, False))
            if ex is None:
                self._check_quarantine(name)
        if ex is None:
            t0 = time.monotonic()
            try:
                # the transport's RetryPolicy spans decode: a corrupt
                # blob (ChecksumError) is refetched, not surfaced
                fetched, nbytes = self.transport.fetch_expert(name)
            except ExpertNotFound as e:
                raise ExpertUnavailable(name, str(e), terminal=True) from e
            except WireFormatError as e:
                # non-checksum by construction: ChecksumError is
                # retryable and only escapes wrapped in RetriesExhausted
                raise ExpertUnavailable(name, str(e), terminal=True) from e
            except TransportError as e:
                self._record_failure(name)
                raise ExpertUnavailable(name, str(e)) from e
            dt = time.monotonic() - t0
            self._record_success(name)
            with self._lock:
                if not self._local(name):   # lost a race: keep first copy
                    super().put(fetched)
                    self._wire_bytes[name] = nbytes
                    self._fetches += 1
                    self._fetch_bytes += nbytes
                    self._fetch_seconds += dt
                    self._account(name, nbytes)      # cold LRU budget
                ex, decode = self._get_cached(name)
        if decode:
            ex.as_(PACKED)      # batched decode, outside the lock
        return ex

    def health(self) -> dict:
        """Snapshot of the per-expert health account (for dashboards and
        tests): consecutive failures, active quarantines, trip count.
        Replicated transports contribute a ``replicas`` section (per-
        replica EWMA latency, failure counts, quarantine state)."""
        now = time.monotonic()
        with self._lock:
            out = {"failures": dict(self._failures),
                   "quarantined": {n: max(0.0, t - now)
                                   for n, t in self._quarantined.items()},
                   "quarantines": self.quarantines}
        transport_health = getattr(self.transport, "health", None)
        if transport_health is not None:
            out["replicas"] = transport_health()
        return out

    def _evict_cold(self, name: str) -> None:
        super()._evict_cold(name)
        self._wire_bytes.pop(name, None)

    def publish(self, expert, rep: Optional[str] = None) -> dict:
        """Upload through the transport AND keep a cold-local copy."""
        out = self.transport.publish(expert, rep=rep)
        self.put(expert)
        return out

    def remote_totals(self) -> dict:
        with self._lock:
            return {"fetches": self._fetches, "bytes": self._fetch_bytes,
                    "seconds": self._fetch_seconds}

    def __contains__(self, name: str) -> bool:
        return self._local(name) or name in self.transport

    def names(self):
        local = set(super().names())
        try:
            remote = set(self.transport.names())
        except Exception:       # e.g. HTTP backends cannot enumerate
            remote = set()
        return sorted(local | remote)

    def nbytes(self, name: str) -> int:
        """Store→host transfer cost: bytes-on-wire for fetched experts."""
        wire = self._wire_bytes.get(name)
        return wire if wire is not None else super().nbytes(name)


class DeviceCache:
    """LRU cache of *packed bitplane trees* under a byte budget (HBM
    residency of ComPEFT experts; 2 bits/param instead of dense deltas),
    plus stacked per-path plane buffers for mixed-expert batches.  Stack
    bytes share the budget: over-capacity builds trigger eviction.

    With ``mesh=`` (a serving mesh from :func:`repro.launch.mesh.
    make_serve_mesh`) the stacked ``[E, ...]`` buffers are partitioned
    expert-parallel along the mesh's ``expert`` axis: E is padded to a
    multiple of the shard count with inert zero-scale slots, planes and
    scales are placed with ``PartitionSpec("expert", ...)``, and
    ``capacity_bytes`` becomes a **per-shard** budget — each device pays
    its packed-tree replicas in full plus ``1/n_shards`` of every resident
    stack, and eviction triggers when any shard's share exceeds the
    budget.  ``mesh=None`` keeps the single-device accounting (shard count
    1) byte-for-byte."""

    MAX_STACKS = 4       # LRU bound on distinct expert-set stacks kept resident
    PREFETCH_WORKERS = 4  # concurrent fetch→decode stages (pipeline depth)

    def __init__(self, store: ExpertStore, capacity_bytes: int, mesh=None):
        self.store = store
        self.capacity = capacity_bytes
        self.mesh = mesh
        self.n_shards = dict(mesh.shape).get("expert", 1) \
            if mesh is not None else 1
        self._stack_real: dict[tuple, int] = {}   # key -> unpadded E
        self._cache: OrderedDict[str, PyTree] = OrderedDict()
        self._sizes: dict[str, int] = {}
        self._stacks: OrderedDict[tuple, dict] = OrderedDict()
        self._pending: dict[str, Future] = {}
        self._pool: Optional[ThreadPoolExecutor] = None
        self.stats = SwapStats(n_expert_shards=self.n_shards)
        # promotion-latency health: every fetch/decode stage (prefetch
        # worker or synchronous) feeds the EWMA; a stage much slower than
        # the running average is flagged and the monitor's
        # recommendation() surfaces in SwapStats / registry.health()
        self.straggler = StragglerMonitor()
        self._straggler_lock = threading.Lock()
        self._straggler_obs = 0
        # serving gauges published by the engine after each run() (queue
        # depth, KV blocks in use/free, stack hit-rate, per-priority
        # admission wait) — surfaced through ExpertRegistry.health()
        self.gauges: dict = {}

    def _observe_promotion(self, seconds: float) -> None:
        with self._straggler_lock:
            self._straggler_obs += 1
            self.straggler.observe(self._straggler_obs, seconds)

    def resident_bytes(self) -> int:
        """Packed trees + stacked buffers — everything under the budget."""
        return sum(self._sizes.values()) + self.stats.stack_bytes

    def shard_resident_bytes(self) -> int:
        """Bytes resident on ONE expert shard: packed trees are replicated
        (staging tier — every shard pays them in full), stacks are
        partitioned evenly along E.  Equals :meth:`resident_bytes` on a
        single-device cache, so budget checks reduce to today's."""
        return sum(self._sizes.values()) \
            + self.stats.stack_bytes // self.n_shards

    def _drop_stack(self, key: tuple) -> None:
        self.stats.stack_bytes -= stacked_bytes(self._stacks.pop(key))
        self.stats.stack_evictions += 1
        self._stack_real.pop(key, None)

    def _evict_one(self) -> None:
        old, _ = self._cache.popitem(last=False)
        self._sizes.pop(old)
        self.stats.evictions += 1
        for key in [k for k in self._stacks if old in k]:
            self._drop_stack(key)

    def _enforce_budget(self, protect: tuple = ()) -> None:
        """Evict until within budget: LRU stacks first (cheap rebuilds),
        then LRU packed trees — never touching ``protect`` members or
        their stack (the expert set being served right now)."""
        protect_key = tuple(protect)
        members = set(protect)
        while self.shard_resident_bytes() > self.capacity:
            other_stacks = [k for k in self._stacks if k != protect_key]
            if other_stacks:
                self._drop_stack(other_stacks[0])
                continue
            victims = [n for n in self._cache if n not in members]
            if not victims:
                break        # only the active set remains: allow overshoot
            old = victims[0]
            self._cache.pop(old)
            self._sizes.pop(old)
            self.stats.evictions += 1
            for key in [k for k in self._stacks if old in k]:
                self._drop_stack(key)

    def prefetch(self, names) -> int:
        """Stage fetch → decode → plane-build for ``names`` on worker
        threads.  Strictly advisory: nothing here blocks on the store or
        the network (membership probes and fetch errors live on the
        worker thread), and a failed stage falls back to the synchronous
        path on the eventual :meth:`fetch` — where unknown names still
        fail loudly.

        The pipeline overlaps the slow, host-side promotion work — remote
        transfer and Golomb decode — across experts and with whatever the
        caller does next (e.g. the engine's decode steps); a later
        :meth:`fetch` of a staged name only pays the device_put.  Returns
        the number of stages issued.
        """
        issued = 0
        for name in names:
            if name == BASE or name in self._cache or name in self._pending:
                continue
            if self._pool is None:
                self._pool = ThreadPoolExecutor(
                    max_workers=self.PREFETCH_WORKERS,
                    thread_name_prefix="expert-prefetch")
            self._pending[name] = self._pool.submit(self._stage, name)
            self.stats.prefetch_issued += 1
            issued += 1
        return issued

    def _stage(self, name: str):
        """Worker-thread half of a promotion: everything up to (but not
        including) the device transfer."""
        t0 = time.monotonic()
        art = self.store.get(name)      # remote fetch / cold Golomb decode
        packed_host = art.packed        # plane build (host)
        dt = time.monotonic() - t0
        self._observe_promotion(dt)
        return packed_host, dt

    def invalidate_pending(self, name: str) -> None:
        """Drop a staged promotion whose cold-tier source changed (e.g. a
        local overlay now shadows the remote artifact) — the next fetch
        re-promotes from the store instead of consuming stale planes."""
        self._pending.pop(name, None)

    def close(self) -> None:
        """Drop staged-but-unconsumed promotions and stop the prefetch
        workers.  Safe to call on caches that never prefetched."""
        self._pending.clear()
        if self._pool is not None:
            self._pool.shutdown(wait=False, cancel_futures=True)
            self._pool = None

    def fetch(self, name: str) -> PyTree:
        """-> tree of PackedTernary, promoted to device-resident if needed."""
        if name in self._cache:
            self._cache.move_to_end(name)
            self.stats.hits += 1
            return self._cache[name]
        self.stats.misses += 1
        t0 = time.monotonic()
        host_packed = None
        fut = self._pending.pop(name, None)
        if fut is not None:
            try:
                host_packed, stage_s = fut.result()
                self.stats.prefetch_hits += 1
                self.stats.prefetch_seconds += stage_s
            except ExpertUnavailable:
                # the store already ran the full retry + health path on
                # the worker thread; repeating it synchronously would
                # only double the damage (and break determinism) —
                # propagate the typed failure to the engine
                self.stats.prefetch_errors += 1
                self._sync_remote_stats()
                raise
            except Exception:
                # transient stage failure (not a store verdict): count
                # it and fall back to the synchronous path
                self.stats.prefetch_errors += 1
        if host_packed is None:
            try:
                art = self.store.get(name)
            except ExpertUnavailable:
                self._sync_remote_stats()    # failures still hit the ledger
                raise
            if self.store.cold_golomb:
                self.stats.golomb_decode_seconds += time.monotonic() - t0
            host_packed = art.packed
            self._observe_promotion(time.monotonic() - t0)
        self._sync_remote_stats()
        self.stats.store_to_host_bytes += self.store.nbytes(name)
        # with a mesh the packed tree is replicated (the staging tier every
        # shard pays in full), never parked on the first device alone
        target = None
        if self.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec
            target = NamedSharding(self.mesh, PartitionSpec())
        with trace.span("engine.promote", expert=name) as sp:
            packed = jax.tree_util.tree_map(
                lambda x: jax.device_put(x, target), host_packed,
                is_leaf=lambda x: hasattr(x, "pos"))
            size = tree_packed_bytes(packed)
            sp.set_metadata(bytes=size)
        while self._cache and (self.shard_resident_bytes() + size
                               > self.capacity):
            self._evict_one()
        self._cache[name] = packed
        self._sizes[name] = size
        self.stats.host_to_device_bytes += size        # packed, not dense
        self.stats.promotions += 1
        self.stats.seconds += time.monotonic() - t0
        return packed

    def _sync_remote_stats(self) -> None:
        """Mirror the remote store's transfer ledger into SwapStats (totals,
        not deltas — safe against concurrent staging threads)."""
        totals = getattr(self.store, "remote_totals", None)
        if totals is not None:
            t = totals()
            self.stats.remote_fetches = t["fetches"]
            self.stats.remote_bytes = t["bytes"]
            self.stats.remote_seconds = t["seconds"]
        self.stats.cold_evictions = getattr(self.store, "cold_evictions", 0)
        self.stats.quarantines = getattr(self.store, "quarantines", 0)
        transport = getattr(self.store, "transport", None)
        if transport is not None:
            self.stats.retries = transport.stats.retries
            self.stats.transport_bytes_wasted = transport.stats.bytes_wasted
        with self._straggler_lock:
            self.stats.straggler_flags = self.straggler.flags
            self.stats.straggler_recommendation = \
                self.straggler.recommendation()

    def stacked(self, names: tuple) -> dict:
        """Stacked plane buffers for an ordered expert set (slot e = names[e]).

        Returns {path: (pos [E, W], neg [E, W], scales [E], shape)}.  Built
        from the resident packed trees (promoting as needed) and cached per
        expert-set; eviction of any member invalidates the stack.  Unknown
        names (e.g. ``__base__``) contribute all-zero slots.  The stack's
        bytes count against the HBM budget — an over-capacity build evicts
        other stacks, then LRU non-member trees.
        """
        key = tuple(names)
        hit = self._stacks.get(key)
        if hit is not None:
            self._stacks.move_to_end(key)
            self.stats.stack_hits += 1
            return hit
        # only the BASE sentinel maps to a zero slot; unknown names must
        # fail loudly, exactly like the merge path's store.get
        trees = [{} if n == BASE else self.fetch(n) for n in key]
        with trace.span("engine.stack_build", experts=len(key)) as sp:
            stacks = stack_packed(trees)
            if self.mesh is not None:
                stacks = self._shard_stacks(stacks, len(key))
            nbytes = stacked_bytes(stacks)
            sp.set_metadata(bytes=nbytes)
        self._stack_real[tuple(key)] = len(key)
        while len(self._stacks) >= self.MAX_STACKS:
            self._drop_stack(next(iter(self._stacks)))
        self._stacks[key] = stacks
        self.stats.stack_builds += 1
        self.stats.stack_bytes += nbytes
        self._enforce_budget(protect=key)
        return stacks

    def _shard_stacks(self, stacks: dict, n_real: int) -> dict:
        """Partition stacked plane buffers expert-parallel along the mesh's
        ``expert`` axis.  E is padded up to a multiple of the shard count
        with zero planes and zero scales — inert slots: every grouped
        contraction multiplies them by an exact 0.0, so the overlay math
        (and therefore the token stream) is unchanged bit-for-bit."""
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        n = self.n_shards
        pad = (-n_real) % n
        plane_sh = NamedSharding(self.mesh, P("expert"))
        out = {}
        for path, (pos, neg, scales, shape) in stacks.items():
            if pad:
                zrow = jnp.zeros((pad,) + tuple(pos.shape[1:]), pos.dtype)
                pos = jnp.concatenate([pos, zrow], axis=0)
                neg = jnp.concatenate([neg, jnp.zeros_like(zrow)], axis=0)
                scales = jnp.concatenate(
                    [scales, jnp.zeros((pad,), scales.dtype)], axis=0)
            out[path] = (jax.device_put(pos, plane_sh),
                         jax.device_put(neg, plane_sh),
                         jax.device_put(scales, plane_sh), shape)
        return out

    def shard_summary(self) -> list[dict]:
        """Per-shard gauges for the expert-parallel stacks: how many *real*
        (non-pad) experts of each resident stack live on each shard, and
        the shard's byte accounting against its budget.  E rows are
        block-partitioned, so shard ``s`` of a stack padded to ``Ep`` rows
        holds rows ``[s*Ep/n, (s+1)*Ep/n)``."""
        shards = [{"shard": s, "resident_experts": 0,
                   "stack_bytes": self.stats.stack_bytes // self.n_shards,
                   "tree_bytes": sum(self._sizes.values()),
                   "capacity_bytes": self.capacity}
                  for s in range(self.n_shards)]
        for key in self._stacks:
            n_real = self._stack_real.get(key, len(key))
            n_pad = n_real + ((-n_real) % self.n_shards)
            per = n_pad // self.n_shards
            for s in range(self.n_shards):
                lo, hi = s * per, (s + 1) * per
                shards[s]["resident_experts"] += \
                    max(0, min(hi, n_real) - lo)
        return shards

    def has_stack(self, names: tuple) -> bool:
        """True while the stack for this expert set is still resident (an
        eviction of any member drops it — consumers must rebuild)."""
        return tuple(names) in self._stacks

    def resident(self):
        return list(self._cache)


class ExpertRegistry:
    """One coherent expert library over the storage tiers.

    Replaces the ad-hoc ``dict[str, ExpertArtifact]`` plumbing: experts go
    in as :class:`~repro.expert.Expert` (or legacy artifacts, normalized),
    the cold tier is an :class:`ExpertStore`, and the HBM tier — created
    lazily by :meth:`device` — is a :class:`DeviceCache` the serving engine
    shares.  Merge-on-demand lives here too (:meth:`merged_params`), so the
    engine no longer hand-rolls plane merges.

    Pass ``transport=`` (an :class:`~repro.transport.ExpertTransport`) to
    construct the registry over a **remote** store: the cold tier becomes
    a :class:`RemoteExpertStore` and experts are fetched over the wire on
    first use; :meth:`prefetch` overlaps those transfers with ongoing
    serving work.
    """

    def __init__(self, store: Optional[ExpertStore] = None, *,
                 cold_golomb: bool = False,
                 device_cache_bytes: int = DEFAULT_DEVICE_BYTES,
                 transport=None, cold_budget_bytes: Optional[int] = None,
                 retry=None,
                 quarantine_after: int = DEFAULT_QUARANTINE_AFTER,
                 quarantine_probe_s: float = DEFAULT_QUARANTINE_PROBE_S,
                 replicas=None, replication_factor: Optional[int] = None,
                 hedge_ms: Optional[float] = None, mesh=None):
        if store is not None and (transport is not None
                                  or replicas is not None):
            raise ValueError("pass either store= or transport=/replicas=, "
                             "not both")
        if (transport is not None or replicas is not None
                or replication_factor is not None or hedge_ms is not None):
            transport = _resolve_transport(transport, replicas,
                                           replication_factor, hedge_ms)
        if retry is not None:
            if transport is None:
                raise ValueError("retry= needs a transport-backed registry")
            transport.retry = retry
        if store is None:
            store = (RemoteExpertStore(transport, cold_golomb=cold_golomb,
                                       budget_bytes=cold_budget_bytes,
                                       quarantine_after=quarantine_after,
                                       quarantine_probe_s=quarantine_probe_s)
                     if transport is not None
                     else ExpertStore(cold_golomb=cold_golomb,
                                      budget_bytes=cold_budget_bytes))
        elif cold_budget_bytes is not None:
            store.budget_bytes = cold_budget_bytes
        self.store = store
        self.device_cache_bytes = device_cache_bytes
        self.mesh = mesh
        self._device: Optional[DeviceCache] = None

    # ---- library management -------------------------------------------
    def add(self, expert, *experts) -> Expert:
        """Register one or more experts; returns the (first) normalized
        Expert.  A staged prefetch for the same name is invalidated so a
        local overlay cannot be shadowed by an in-flight remote fetch."""
        out = []
        for e in (expert,) + experts:
            ex = self.store.put(e)
            if self._device is not None:
                self._device.invalidate_pending(ex.name)
            out.append(ex)
        return out[0]

    put = add   # ExpertStore-compatible spelling

    def get(self, name: str) -> Expert:
        return self.store.get(name)

    def __contains__(self, name: str) -> bool:
        return name in self.store

    def __len__(self) -> int:
        return len(self.store.names())

    def names(self) -> list[str]:
        return self.store.names()

    def nbytes(self, name: str) -> int:
        return self.store.nbytes(name)

    # ---- device tier ---------------------------------------------------
    def device(self, capacity_bytes: Optional[int] = None,
               mesh=_UNSET) -> DeviceCache:
        """The HBM tier (created on first call).  ``capacity_bytes=None``
        keeps the registry's configured budget; an explicit value sets (or
        retargets) the budget — the most recent explicit request wins.
        ``mesh=`` defaults to the registry's mesh; passing a *different*
        mesh rebuilds the tier (resident arrays are placed per-mesh, so
        they cannot be carried across)."""
        mesh = self.mesh if mesh is _UNSET else mesh
        if self._device is not None and mesh is not self._device.mesh:
            self._device.close()
            self._device = None
        if self._device is None:
            self._device = DeviceCache(
                self.store, capacity_bytes or self.device_cache_bytes,
                mesh=mesh)
        elif (capacity_bytes is not None
              and capacity_bytes != self._device.capacity):
            self._device.capacity = capacity_bytes
            self._device._enforce_budget()
        return self._device

    def fetch_packed(self, name: str) -> dict:
        """Device-resident ``{path: PackedTernary}`` for one expert."""
        return {} if name == BASE else self.device().fetch(name)

    def prefetch(self, names) -> int:
        """Stage promotions for ``names`` in the background (see
        :meth:`DeviceCache.prefetch`).  Advisory — never blocks on the
        store; the BASE sentinel is skipped and a name that turns out to
        be unknown still fails loudly on its synchronous fetch.  Returns
        the number of stages issued."""
        if isinstance(names, str):
            names = [names]
        names = [n for n in names if n != BASE]
        if not names:
            return 0
        return self.device().prefetch(names)

    def close(self) -> None:
        """Release the HBM tier's prefetch workers and staged promotions
        (the registry stays usable; a later fetch re-promotes)."""
        if self._device is not None:
            self._device.close()

    def health(self) -> dict:
        """Health snapshot: per-expert failure/quarantine accounts (remote
        registries), per-replica health when the transport is replicated
        (``replicas`` section), the device tier's promotion-latency
        straggler verdict (``straggler`` section), and — once an engine
        has run — its serving gauges (``serving`` section: queue depth,
        KV blocks in use/free, stack hit-rate, per-priority admission
        wait)."""
        h = getattr(self.store, "health", None)
        out = (h() if h is not None
               else {"failures": {}, "quarantined": {}, "quarantines": 0})
        if self._device is not None:
            with self._device._straggler_lock:
                out["straggler"] = {
                    "recommendation":
                        self._device.straggler.recommendation(),
                    "flags": self._device.straggler.flags,
                    "ewma_s": self._device.straggler.ewma,
                }
            if self._device.gauges:
                out["serving"] = dict(self._device.gauges)
        return out

    def publish(self, expert, rep: Optional[str] = None) -> dict:
        """Upload an expert through the registry's transport (remote
        registries only) and keep a cold-local copy."""
        if not isinstance(self.store, RemoteExpertStore):
            raise TypeError("publish() needs a transport-backed registry; "
                            "construct with ExpertRegistry(transport=...) "
                            "or repro.api.registry(transport=...)")
        return self.store.publish(expert, rep=rep)

    def stacked(self, names: tuple) -> dict:
        return self.device().stacked(tuple(names))

    # ---- merge-on-demand ----------------------------------------------
    def merged_params(self, base: PyTree, names, weights=None) -> PyTree:
        """``W_base + sum_e w_e * Delta_e`` in ONE fused sweep per leaf.

        The ``unpack_add_many`` kernel applies every named expert's planes
        during a single pass over the base weights instead of E
        read-modify-write round trips over HBM; bit-identical to applying
        the (w-scaled) experts one at a time.  With a single name this is
        the classic merge-on-swap promotion.
        """
        from repro.kernels.ops import apply_ternary_delta_many_flat
        from repro.peft.lora import _path_str
        names = [names] if isinstance(names, str) else list(names)
        packs = [self.fetch_packed(n) for n in names]
        w = list(weights) if weights is not None else [1.0] * len(names)
        flat, treedef = jax.tree_util.tree_flatten_with_path(base)
        out = []
        for path, leaf in flat:
            ps = _path_str(path)
            pts, ws = [], []
            for pk, wi in zip(packs, w):
                if ps in pk:
                    pts.append(pk[ps])
                    ws.append(wi)
            out.append(leaf if not pts
                       else apply_ternary_delta_many_flat(leaf, pts, ws))
        return jax.tree_util.tree_unflatten(treedef, out)


def as_registry(obj) -> ExpertRegistry:
    """Normalize an ExpertStore (legacy engine wiring) to a registry."""
    if isinstance(obj, ExpertRegistry):
        return obj
    if isinstance(obj, ExpertStore):
        warnings.warn(
            "passing an ExpertStore to ServeEngine is deprecated; wrap it "
            "in repro.api.registry() / ExpertRegistry(store)",
            DeprecationWarning, stacklevel=3)
        return ExpertRegistry(store=obj)
    raise TypeError(f"expected ExpertRegistry or ExpertStore, "
                    f"got {type(obj).__name__}")


def uncompressed_baseline_bytes(art) -> int:
    """What the same swap would cost without ComPEFT (bf16 dense)."""
    packed = art.packed if not isinstance(art, dict) else art
    leaves = jax.tree_util.tree_leaves(
        packed, is_leaf=lambda x: hasattr(x, "pos"))
    return sum(int(np.prod(p.shape)) * 2 for p in leaves)
