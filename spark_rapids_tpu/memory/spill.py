"""Spillable buffer framework: catalog + chained device->host->disk stores.

Reference parity:
- RapidsBuffer.scala:61-123 (spillable buffer: id, size, tier, refcount,
  spill priority) -> `SpillableBuffer`.
- RapidsBufferCatalog.scala:40-99 (id->buffer map, acquire-with-retry) ->
  `BufferCatalog`.
- RapidsBufferStore.scala:148-282 (per-store tracker, chained setSpillStore,
  synchronousSpill(target) loop, copy-on-spill + catalog update) ->
  `BufferStore` and subclasses.
- RapidsDeviceMemoryStore.scala / RapidsHostMemoryStore.scala /
  RapidsDiskStore.scala -> `DeviceStore` / `HostStore` / `DiskStore`.
- SpillPriorities.scala:26-50 -> `SpillPriorities`.
- DeviceMemoryEventHandler.scala:65-89 (alloc failure -> synchronous spill).
  TPU difference (SURVEY.md section 7 hard part #4): XLA owns HBM and gives
  no alloc-failure callback, so `MemoryWatermark.ensure_headroom` spills
  *preemptively* before uploads/materializations instead of reactively.

Tier semantics on TPU:
- DEVICE: the buffer holds live jax device arrays (a ColumnarBatch).
  "Spilling" serializes to host bytes and drops the device references so XLA
  frees the HBM.
- HOST: the buffer holds the serialized bytes (columnar/serde.py format) in
  process memory, bounded by rapids.tpu.memory.host.spillStorageSize.
- DISK: the bytes live in a file under rapids.tpu.memory.spill.dir.

Re-materialization climbs back up: get_device_batch() on a HOST/DISK buffer
deserializes and re-uploads (the RapidsBufferStore copy-back path).
"""

from __future__ import annotations

import itertools
import logging
import os
import tempfile
import threading
from enum import IntEnum
from typing import Callable, Dict, List, Optional

from spark_rapids_tpu import conf as C
from spark_rapids_tpu.columnar.batch import ColumnarBatch, HostColumnarBatch
from spark_rapids_tpu.columnar.serde import deserialize_batch, serialize_batch

log = logging.getLogger(__name__)


class StorageTier(IntEnum):
    """Reference: RapidsBuffer.scala:53-58."""

    DEVICE = 0
    HOST = 1
    DISK = 2


class SpillPriorities:
    """Priority bands (reference: SpillPriorities.scala:26-50). Lower spills
    first."""

    # shuffle output read once then dead: spill first
    OUTPUT_FOR_READ = -100.0
    # generic cached/materialized data
    DEFAULT = 0.0
    # shuffle input actively being consumed: spill last
    INPUT_ACTIVE = 100.0


_id_counter = itertools.count(1)

# process-wide count of buffer tier demotions (benchmark diagnostics: a
# throughput decline past the HBM plateau names spill thrash as its cause
# iff this moved during the measured iterations). Incremented under its own
# lock: concurrent demotions hold only their per-buffer locks, so a bare
# read-modify-write would lose counts.
SPILL_EVENTS = 0
_SPILL_EVENTS_LOCK = threading.Lock()


def next_buffer_id() -> int:
    return next(_id_counter)


class SpillableBuffer:
    """One spillable table (reference: RapidsBufferBase, RapidsBuffer.scala).

    Exactly one of (device_batch, host_bytes, disk_path) is set, matching the
    current tier. `refcount` > 0 pins the buffer against spilling
    (RapidsBufferStore.scala:190-216 skips buffers with active references).
    """

    def __init__(self, buf_id: int, size: int, tier: StorageTier,
                 priority: float = SpillPriorities.DEFAULT):
        self.id = buf_id
        self.size = size  # serialized-bytes size (tier-independent accounting)
        self.tier: Optional[StorageTier] = tier  # None = freed (tombstone)
        self.priority = priority
        self.refcount = 0
        self.device_batch: Optional[ColumnarBatch] = None
        self.host_bytes: Optional[bytes] = None
        self.disk_path: Optional[str] = None
        self.lock = threading.Lock()

    def __repr__(self):
        return (f"SpillableBuffer(id={self.id}, tier={self.tier.name}, "
                f"size={self.size}, rc={self.refcount})")


class BufferCatalog:
    """id -> buffer registry (reference: RapidsBufferCatalog.scala:40-99)."""

    def __init__(self):
        self._buffers: Dict[int, SpillableBuffer] = {}
        self._lock = threading.Lock()

    def register(self, buf: SpillableBuffer) -> None:
        with self._lock:
            self._buffers[buf.id] = buf

    def lookup(self, buf_id: int) -> SpillableBuffer:
        with self._lock:
            buf = self._buffers.get(buf_id)
        if buf is None:
            raise KeyError(f"unknown buffer id {buf_id}")
        return buf

    def remove(self, buf_id: int) -> Optional[SpillableBuffer]:
        with self._lock:
            return self._buffers.pop(buf_id, None)

    def ids(self) -> List[int]:
        with self._lock:
            return list(self._buffers)


class BufferStore:
    """Per-tier tracker with a chained spill target (reference:
    RapidsBufferStore.scala:44-120)."""

    tier: StorageTier

    def __init__(self, catalog: BufferCatalog):
        self.catalog = catalog
        self.spill_store: Optional["BufferStore"] = None
        self._buffers: Dict[int, SpillableBuffer] = {}
        self._lock = threading.Lock()
        self.current_size = 0

    def set_spill_store(self, store: "BufferStore") -> None:
        self.spill_store = store

    # -- tracking ------------------------------------------------------------
    def track(self, buf: SpillableBuffer) -> None:
        with self._lock:
            self._buffers[buf.id] = buf
            self.current_size += buf.size

    def untrack(self, buf: SpillableBuffer) -> None:
        with self._lock:
            if self._buffers.pop(buf.id, None) is not None:
                self.current_size -= buf.size

    def buffer_count(self) -> int:
        with self._lock:
            return len(self._buffers)

    # -- spill ---------------------------------------------------------------
    def _spill_candidate(self, skip=()) -> Optional[SpillableBuffer]:
        """Lowest-priority unpinned buffer (reference: per-store
        HashedPriorityQueue ordering, RapidsBufferStore.scala:88)."""
        with self._lock:
            candidates = [b for b in self._buffers.values()
                          if b.refcount == 0 and b.id not in skip]
        if not candidates:
            return None
        return min(candidates, key=lambda b: (b.priority, b.id))

    def synchronous_spill(self, target_size: int) -> int:
        """Spill until current_size <= target_size; returns bytes spilled
        (reference: RapidsBufferStore.synchronousSpill,
        RapidsBufferStore.scala:148-188). Buffers that race to pinned/freed
        between selection and spill are skipped, not retried forever."""
        spilled = 0
        skip = set()
        while self.current_size > target_size:
            buf = self._spill_candidate(skip)
            if buf is None:
                log.warning(
                    "%s store: cannot reach spill target %d (size=%d, all "
                    "buffers pinned)", self.tier.name, target_size,
                    self.current_size)
                break
            got = self.spill_buffer(buf)
            if got == 0:
                skip.add(buf.id)
            spilled += got
        return spilled

    def spill_buffer(self, buf: SpillableBuffer) -> int:
        """Move one buffer to the next tier (reference: copy-on-spill +
        catalog update, RapidsBufferStore.scala:255-282).

        Lock discipline: cross-buffer work (make_room, overflow push-down)
        happens OUTSIDE buf.lock — a buffer lock is never held while
        acquiring another buffer's lock, so spill chains cannot deadlock."""
        if self.spill_store is None:
            raise RuntimeError(f"{self.tier.name} store has no spill target")
        from spark_rapids_tpu.obs.trace import span as obs_span

        self.spill_store.make_room(buf.size)
        with buf.lock:
            if buf.tier is not self.tier or buf.refcount > 0:
                return 0  # raced: moved, freed, or pinned meanwhile
            global SPILL_EVENTS
            with _SPILL_EVENTS_LOCK:
                SPILL_EVENTS += 1
            # traced timelines show each demotion as a site span (bytes +
            # tier edge in attrs) — spill time is the classic invisible
            # cost the span tree exists to surface
            with obs_span(f"spill:{self.tier.name}->"
                          f"{self.spill_store.tier.name}",
                          bytes=buf.size):
                self._demote(buf)
            self.untrack(buf)
            buf.tier = self.spill_store.tier
            self.spill_store.track(buf)
        # absorb overflow (e.g. buf.size alone exceeds a bounded store's
        # limit, or concurrent spills raced past make_room)
        limit = self.spill_store.size_limit()
        if limit is not None and self.spill_store.current_size > limit:
            self.spill_store.synchronous_spill(limit)
        if log.isEnabledFor(logging.DEBUG):
            log.debug("spilled buffer %d (%d B) %s -> %s", buf.id, buf.size,
                      self.tier.name, buf.tier.name)
        return buf.size

    def make_room(self, nbytes: int) -> None:
        """Ensure this store can absorb nbytes (bounded stores spill down the
        chain first; reference: host store bound
        RapidsHostMemoryStore.scala:28-101)."""
        limit = self.size_limit()
        if limit is not None and self.spill_store is not None:
            self.synchronous_spill(max(0, limit - nbytes))

    def size_limit(self) -> Optional[int]:
        return None

    def _demote(self, buf: SpillableBuffer) -> None:
        """Convert buf's payload from this tier's form to the next tier's."""
        raise NotImplementedError



class DeviceStore(BufferStore):
    """Tier 0: live device batches (reference:
    RapidsDeviceMemoryStore.scala:25-111)."""

    tier = StorageTier.DEVICE

    def add_batch(self, batch: ColumnarBatch,
                  priority: float = SpillPriorities.DEFAULT,
                  host_bytes: Optional[bytes] = None) -> SpillableBuffer:
        """Register a device batch as spillable (reference: addTable).
        `host_bytes` lets callers that already have the serialized form skip
        a device->host download at spill time."""
        size = len(host_bytes) if host_bytes is not None else \
            batch.device_memory_size()
        buf = SpillableBuffer(next_buffer_id(), size, self.tier, priority)
        buf.device_batch = batch
        buf.host_bytes = host_bytes
        self.catalog.register(buf)
        self.track(buf)
        return buf

    def _demote(self, buf: SpillableBuffer) -> None:
        if buf.host_bytes is None:
            from spark_rapids_tpu.columnar.batch import to_host_many

            # keep_encoded: dictionary columns spill as codes + one
            # dictionary copy; unspill re-uploads codes and re-interns
            buf.host_bytes = serialize_batch(to_host_many(
                [buf.device_batch], keep_encoded=True)[0])
        buf.device_batch = None  # drop device refs -> XLA frees HBM


class HostStore(BufferStore):
    """Tier 1: serialized bytes in process memory, bounded (reference:
    RapidsHostMemoryStore.scala:28-101)."""

    tier = StorageTier.HOST

    def __init__(self, catalog: BufferCatalog, limit_bytes: int):
        super().__init__(catalog)
        self.limit_bytes = limit_bytes

    def size_limit(self) -> Optional[int]:
        return self.limit_bytes

    def add_bytes_tracked(self, buf: SpillableBuffer) -> None:
        """Register a new host-tier buffer and push overflow to disk. Safe
        because it is never called under a buffer lock — plain track() (used
        by spill_buffer under buf.lock) must NOT spill; spill_buffer absorbs
        overflow itself after releasing the lock."""
        super().track(buf)
        if self.current_size > self.limit_bytes and self.spill_store:
            self.synchronous_spill(self.limit_bytes)

    def _demote(self, buf: SpillableBuffer) -> None:
        disk: DiskStore = self.spill_store  # type: ignore[assignment]
        buf.disk_path = disk.write_file(buf.id, buf.host_bytes)
        buf.host_bytes = None


class DiskStore(BufferStore):
    """Tier 2: files under the spill dir (reference:
    RapidsDiskStore.scala:30-93)."""

    tier = StorageTier.DISK

    def __init__(self, catalog: BufferCatalog, spill_dir: Optional[str]):
        super().__init__(catalog)
        self._dir = spill_dir or os.path.join(
            tempfile.gettempdir(), f"tpu-spill-{os.getpid()}")

    def write_file(self, buf_id: int, data: bytes) -> str:
        os.makedirs(self._dir, exist_ok=True)
        path = os.path.join(self._dir, f"buffer-{buf_id}.tpb")
        with open(path, "wb") as f:
            f.write(data)
        return path

    def read_file(self, path: str) -> bytes:
        with open(path, "rb") as f:
            return f.read()

    def _demote(self, buf: SpillableBuffer) -> None:
        raise RuntimeError("disk store has no spill target")


class SpillFramework:
    """Bundles catalog + store chain + watermark; one per session process
    (reference: GpuShuffleEnv.initStorage wiring the three stores and the
    OOM handler, GpuShuffleEnv.scala:57-79)."""

    _instance: Optional["SpillFramework"] = None
    _lock = threading.Lock()

    def __init__(self, tpu_conf: "C.TpuConf", hbm_budget: int,
                 bytes_in_use: Callable[[], int]):
        self.catalog = BufferCatalog()
        self.device_store = DeviceStore(self.catalog)
        self.host_store = HostStore(
            self.catalog, tpu_conf.get(C.HOST_SPILL_STORAGE_SIZE))
        self.disk_store = DiskStore(self.catalog, tpu_conf.get(C.SPILL_DIR))
        self.device_store.set_spill_store(self.host_store)
        self.host_store.set_spill_store(self.disk_store)
        self.watermark = MemoryWatermark(
            self.device_store, hbm_budget, bytes_in_use)

    @classmethod
    def initialize(cls, tpu_conf: "C.TpuConf", hbm_budget: int,
                   bytes_in_use: Callable[[], int] = lambda: 0
                   ) -> "SpillFramework":
        with cls._lock:
            fw = cls(tpu_conf, hbm_budget, bytes_in_use)
            cls._instance = fw
            return fw

    @classmethod
    def get(cls) -> Optional["SpillFramework"]:
        return cls._instance

    @classmethod
    def shutdown(cls) -> None:
        with cls._lock:
            cls._instance = None

    # -- telemetry (TpuServer.metrics_snapshot, docs/observability.md) -------
    def snapshot(self) -> dict:
        """Spill-tier occupancy: bytes + buffer count per tier, and the
        process-wide demotion count."""
        with _SPILL_EVENTS_LOCK:
            events = SPILL_EVENTS
        return {
            "events": events,
            "tiers": {
                store.tier.name.lower(): {
                    "bytes": store.current_size,
                    "buffers": store.buffer_count(),
                }
                for store in (self.device_store, self.host_store,
                              self.disk_store)
            },
        }

    # -- plan-time hints (plan/resources.py) ---------------------------------
    def set_plan_hint(self, spill_pressure: float, per_task_peak,
                      ctx=None) -> None:
        """Forward the resource analyzer's prediction for the query about
        to run: `spill_pressure` is predicted-peak / budget (> 1.0 means
        the spill framework is expected to engage) and `per_task_peak` is
        the transient bytes one task is predicted to need. The watermark
        uses them to reserve headroom BEFORE the transients allocate, so
        spill happens at upload boundaries (cheap, chosen victims) instead
        of mid-operator. With a QueryContext the resolved reserve is
        ADDITIONALLY scoped to that query — an AQE re-plan posting a new
        hint mid-query (aqe/loop.py) cannot leak into a concurrent
        tenant's headroom math (docs/serving.md)."""
        self.watermark.set_plan_hint(spill_pressure, per_task_peak,
                                     ctx=ctx)

    # -- buffer API ----------------------------------------------------------
    @staticmethod
    def _scope_to_query(buf: SpillableBuffer) -> None:
        """Record the buffer on the ambient query's reclamation set
        (utils/metrics.QueryContext.spill_buffers): a CANCELLED query
        frees everything it registered, so a dead query's shuffle pieces
        and staged batches cannot linger in the store
        (docs/fault-tolerance.md). No-op outside a query context."""
        from spark_rapids_tpu.utils import metrics as M

        ctx = M.current_query_ctx()
        if ctx is not None:
            ctx.spill_buffers.append(buf)

    def add_device_batch(self, batch: ColumnarBatch,
                         priority: float = SpillPriorities.DEFAULT,
                         host_bytes: Optional[bytes] = None,
                         scope_to_query: bool = True) -> SpillableBuffer:
        """`scope_to_query=False` marks a buffer whose lifetime exceeds
        the registering query (the relation cache, exec/cache.py) —
        cancellation must not free it."""
        self.watermark.ensure_headroom(
            len(host_bytes) if host_bytes is not None
            else batch.device_memory_size())
        buf = self.device_store.add_batch(batch, priority, host_bytes)
        if scope_to_query:
            self._scope_to_query(buf)
        return buf

    def add_host_batch(self, host_batch: HostColumnarBatch,
                       priority: float = SpillPriorities.DEFAULT
                       ) -> SpillableBuffer:
        return self.add_host_bytes(serialize_batch(host_batch), priority)

    def add_host_bytes(self, data: bytes,
                       priority: float = SpillPriorities.DEFAULT,
                       scope_to_query: bool = True) -> SpillableBuffer:
        """Register already-serialized bytes at the host tier (used by the
        serialized shuffle tier so shuffle pieces participate in spill,
        reference: RapidsCachingWriter registering shuffle buffers,
        RapidsShuffleInternalManager.scala:92-141)."""
        buf = SpillableBuffer(next_buffer_id(), len(data), StorageTier.HOST,
                              priority)
        buf.host_bytes = data
        self.catalog.register(buf)
        self.host_store.add_bytes_tracked(buf)
        if scope_to_query:
            self._scope_to_query(buf)
        return buf

    def read_bytes(self, buf: SpillableBuffer) -> bytes:
        with buf.lock:
            return self._read_bytes(buf)

    def get_device_batch(self, buf: SpillableBuffer) -> ColumnarBatch:
        return self.fetch_device_batch(buf)[0]

    def fetch_device_batch(self, buf: SpillableBuffer):
        """(the batch on the device, whether this call had to bring it
        back): materialize on device, re-uploading if spilled (reference:
        RapidsBufferCatalog.acquireBuffer + getColumnarBatch climbing tiers).

        buf.lock is NOT held across ensure_headroom/upload (cross-buffer
        work); a concurrent rematerialization race is resolved by letting
        the first writer win."""
        with buf.lock:
            if buf.device_batch is not None:
                # store-held batches are multi-read by construction: they
                # must never carry the consume-once donation proof
                buf.device_batch.owned = False
                return buf.device_batch, False
            data = self._read_bytes(buf)
        # outside the lock: spill others + upload
        self.watermark.ensure_headroom(len(data))
        batch = deserialize_batch(data).to_device()
        batch.owned = False  # multi-read once stored (see above)
        with buf.lock:
            if buf.device_batch is not None:  # lost the race
                buf.device_batch.owned = False
                return buf.device_batch, False
            if buf.tier is None:  # freed meanwhile
                return batch, True
            # promote back to the device tier so later accesses are free
            store = self._store_for(buf.tier)
            store.untrack(buf)
            buf.device_batch = batch
            buf.host_bytes = data if buf.tier is StorageTier.HOST else None
            if buf.disk_path:
                try:
                    os.unlink(buf.disk_path)
                except OSError:
                    pass
                buf.disk_path = None
            buf.tier = StorageTier.DEVICE
            self.device_store.track(buf)
            return batch, True

    def get_host_batch(self, buf: SpillableBuffer) -> HostColumnarBatch:
        """Materialize on host without touching the device tier placement."""
        with buf.lock:
            if buf.tier is StorageTier.DEVICE and buf.device_batch is not None:
                if buf.host_bytes is not None:
                    return deserialize_batch(buf.host_bytes)
                return buf.device_batch.to_host()
            return deserialize_batch(self._read_bytes(buf))

    def acquire(self, buf: SpillableBuffer) -> SpillableBuffer:
        with buf.lock:
            buf.refcount += 1
        return buf

    def release(self, buf: SpillableBuffer) -> None:
        with buf.lock:
            buf.refcount = max(0, buf.refcount - 1)

    def free(self, buf: SpillableBuffer) -> None:
        """Release a buffer from whatever tier holds it. Runs under buf.lock
        and tombstones the tier so a concurrent spill_buffer (which
        re-checks tier under the lock) backs off instead of demoting a
        half-freed buffer."""
        with buf.lock:
            if buf.tier is None:
                return
            self._store_for(buf.tier).untrack(buf)
            self.catalog.remove(buf.id)
            buf.device_batch = None
            buf.host_bytes = None
            if buf.disk_path:
                try:
                    os.unlink(buf.disk_path)
                except OSError:
                    pass
                buf.disk_path = None
            buf.tier = None

    def _store_for(self, tier: StorageTier) -> BufferStore:
        return {StorageTier.DEVICE: self.device_store,
                StorageTier.HOST: self.host_store,
                StorageTier.DISK: self.disk_store}[tier]

    def _read_bytes(self, buf: SpillableBuffer) -> bytes:
        if buf.host_bytes is not None:
            return buf.host_bytes
        if buf.disk_path is not None:
            return self.disk_store.read_file(buf.disk_path)
        raise RuntimeError(f"buffer {buf.id} has no payload at any tier")


class MemoryWatermark:
    """Preemptive HBM budget enforcement (the DeviceMemoryEventHandler analog;
    reference DeviceMemoryEventHandler.scala:65-89 spills synchronously on
    alloc failure — here we spill *before* the allocation because XLA offers
    no failure callback)."""

    def __init__(self, device_store: DeviceStore, budget: int,
                 bytes_in_use: Callable[[], int]):
        self.device_store = device_store
        self.budget = budget
        self.bytes_in_use = bytes_in_use
        # plan-time transient reserve (set_plan_hint): bytes kept free for
        # the running query's predicted operator transients
        self.plan_reserve = 0

    def _reserve_from_hint(self, spill_pressure: float,
                           per_task_peak) -> int:
        """Reserve predicted-transient headroom only for plans the analyzer
        expects to overrun the budget (pressure > 1.0); light plans keep
        the full budget for resident batches. The reserve is capped at
        half the budget so a wildly pessimistic estimate cannot spill the
        store empty."""
        if (self.budget > 0 and spill_pressure > 1.0
                and per_task_peak is not None
                and per_task_peak == per_task_peak  # not NaN
                and per_task_peak != float("inf")):
            return min(int(per_task_peak), self.budget // 2)
        return 0

    def set_plan_hint(self, spill_pressure: float, per_task_peak,
                      ctx=None) -> None:
        """Resolve and install the reserve. With a QueryContext the value
        is scoped to THAT query (ensure_headroom on its worker threads
        reads it through the ambient context); the watermark-level slot
        stays the last-writer-wins fallback for context-free callers."""
        reserve = self._reserve_from_hint(spill_pressure, per_task_peak)
        if ctx is not None:
            ctx.spill_plan_hint = reserve
        self.plan_reserve = reserve

    def _current_reserve(self) -> int:
        """The reserve governing the calling thread: the ambient query's
        context-scoped hint when one was posted (0 is a valid posted
        hint), else the process-wide slot."""
        from spark_rapids_tpu.utils import metrics as M

        qctx = M.current_query_ctx()
        if qctx is not None and qctx.spill_plan_hint is not None:
            return qctx.spill_plan_hint
        return self.plan_reserve

    def ensure_headroom(self, nbytes: int) -> None:
        """Spill tracked device buffers until `nbytes` fits under the budget.
        Untracked allocations (live intermediates inside jit calls) are
        covered by the bytes_in_use() term when the backend reports it."""
        if self.budget <= 0:
            return
        reserve = self._current_reserve()
        tracked = self.device_store.current_size
        external = max(0, self.bytes_in_use() - tracked)
        avail = self.budget - reserve - external - tracked
        if nbytes > avail:
            self.device_store.synchronous_spill(
                max(0, self.budget - reserve - external - nbytes))
