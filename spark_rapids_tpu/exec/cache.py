"""Cached (in-memory) relation execs.

Reference parity: the reference accelerates Spark's InMemoryTableScan by
storing the cached data columnar and serving it straight to GPU operators
(HostColumnarToGpu.scala:30-260, exercised by cache_test.py). Here the cache
is device-resident: the first execution materializes each partition's
batches in HBM, later executions serve them with zero host->device traffic —
which is the difference between host-link bandwidth and HBM bandwidth.

The cache is keyed by the logical CacheRelation node (weakly, so dropping
the DataFrame frees the HBM copies) and segregated by engine placement:
the CPU oracle caches host batches, the TPU exec caches device batches.

What the device cache shows of itself (docs/observability.md): a
`cache.materialize` span a partition of the cached plan and a
`cache.coalesce` span a resident batch on the first execution, a
`cache.serve` span a batch handed out on every execution, the process-wide
`cachedBatchesServed` / `cacheRestoredBatches` (a batch that had left the
device and was brought back) / `cacheCoalescedPieces` (batches of the
cached plan that were concatenated into resident ones) and the gauge
`resident_bytes()`.
"""

from __future__ import annotations

import threading
import weakref
from typing import Dict, List, NamedTuple, Sequence

import jax

from spark_rapids_tpu import conf as C
from spark_rapids_tpu.columnar.batch import MIN_CAPACITY, concat_in_order
from spark_rapids_tpu.columnar.encoded import is_encoded
from spark_rapids_tpu.engine import cancel as CX
from spark_rapids_tpu.exec.base import (
    CpuExec,
    ExecContext,
    PartitionedBatches,
    PhysicalExec,
    TpuExec,
    count_output,
)
from spark_rapids_tpu.exec.transitions import current_task_id
from spark_rapids_tpu.memory.semaphore import TpuSemaphore
from spark_rapids_tpu.obs import trace as OBS
from spark_rapids_tpu.ops.base import AttributeReference
from spark_rapids_tpu.utils import metrics as M

_LOCK = threading.Lock()
_DEVICE_CACHE: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()
_HOST_CACHE: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()


def cached_row_count(logical_node):
    """Total materialized rows of a cached relation, or None if the cache
    has not been populated yet (planner statistics hook: iteration 2+ of a
    cached query plans with exact input counts)."""
    with _LOCK:
        parts = _DEVICE_CACHE.get(logical_node)
        if parts is None:
            parts = _HOST_CACHE.get(logical_node)
    if parts is None:
        return None
    total = 0
    for part in parts:
        for b in part:
            # device-cache entries are SpillableBuffers wrapping the batch
            b = getattr(b, "device_batch", None) or b
            n = getattr(b, "num_rows", None)
            if not isinstance(n, int):
                return None  # device-resident count: not worth a sync here
            total += n
    return total


def is_materialized(logical_node) -> bool:
    """Whether either engine holds the relation now (plan/signature.py:
    a plan analyzed before it was is not the plan of an action after)."""
    with _LOCK:
        return logical_node in _DEVICE_CACHE or logical_node in _HOST_CACHE


def cached_host_partitions(logical_node):
    """Materialized HOST partitions of a cached relation, or None when the
    cache is empty or device-resident. The resource analyzer
    (plan/resources.py) reads exact per-batch row counts — and, for small
    relations, column stats — from here without any device sync."""
    with _LOCK:
        return _HOST_CACHE.get(logical_node)


def cached_device_partition_rows(logical_node):
    """Per-batch row counts of a device-cached relation as
    [[rows, ...] per partition], or None when unavailable (cache empty, or
    a batch carries a device-resident count — not worth a sync here)."""
    with _LOCK:
        parts = _DEVICE_CACHE.get(logical_node)
    if parts is None:
        return None
    out = []
    for part in parts:
        rows = []
        for b in part:
            b = getattr(b, "device_batch", None) or b
            n = getattr(b, "num_rows", None)
            if not isinstance(n, int):
                return None
            rows.append(n)
        out.append(rows)
    return out


def cached_device_bytes(logical_node):
    """Registered bytes of a device-cached relation's buffers, whatever
    tier each is on (serving brings every one back), or None before it is
    materialized: what the resource analyzer books for the relation."""
    with _LOCK:
        parts = _DEVICE_CACHE.get(logical_node)
    if parts is None:
        return None
    return sum(b.size for part in parts for b in part)


def resident_bytes() -> int:
    """Bytes of every device-cached relation's buffers that are on the
    device now: what was materialized, less what the spill framework took
    away and has not brought back (utils/metrics.cache_resident_bytes)."""
    from spark_rapids_tpu.memory.spill import StorageTier

    with _LOCK:
        bufs = [b for parts in _DEVICE_CACHE.values()
                for part in parts for b in part]
    return sum(b.size for b in bufs if b.tier is StorageTier.DEVICE)


def invalidate(logical_node) -> None:
    with _LOCK:
        # tpulint: shared-state-mutation -- under _LOCK; invalidate is
        # the cache's teardown path
        dropped = _DEVICE_CACHE.pop(logical_node, None)
        # tpulint: shared-state-mutation -- under _LOCK (same teardown)
        _HOST_CACHE.pop(logical_node, None)
    if dropped:
        _free_buffers([b for part in dropped for b in part])


def _free_buffers(bufs) -> None:
    from spark_rapids_tpu.memory.spill import SpillFramework

    fw = SpillFramework.get()
    if fw is not None:
        for b in bufs:
            try:
                fw.free(b)
            # tpulint: swallowed-cancellation -- best-effort free of an
            # already-condemned buffer on a reclamation path; raising
            # here would leak the REST of the buffers
            except Exception:
                pass


class _CachedScanBase(PhysicalExec):
    def __init__(self, logical_node, child: PhysicalExec):
        super().__init__(child)
        self.logical_node = logical_node

    @property
    def output(self) -> List[AttributeReference]:
        return self.children[0].output

    def with_children(self, new_children):
        return type(self)(self.logical_node, new_children[0])

    def _store(self):
        raise NotImplementedError

    def execute(self, ctx: ExecContext) -> PartitionedBatches:
        store = self._store()
        with _LOCK:
            cached = store.get(self.logical_node)
        if cached is None:
            child_pb = self.children[0].execute(ctx)

            def mat(pidx: int):
                out = []
                for b in child_pb.iterator(pidx):
                    n = b.host_rows() if hasattr(b, "host_rows") else b.num_rows
                    if n > 0:
                        out.append(b)
                return out

            from spark_rapids_tpu.engine.scheduler import run_job_or_serial

            parts = run_job_or_serial(ctx.scheduler, child_pb.num_partitions, mat)
            with _LOCK:
                cached = store.setdefault(self.logical_node, parts)

        def factory(pidx: int):
            return count_output(self.metrics, iter(cached[pidx]))

        return PartitionedBatches(len(cached), factory)


class _Piece(NamedTuple):
    """A batch as the cached plan handed it over, registered."""

    buf: object     # its SpillableBuffer
    rows: int
    lanes: int      # its capacity


class _Registered:
    """Every buffer one materialisation has registered with the spill
    framework. A cache entry belongs to no query, so nothing else frees
    what does not end in the relation: a failed attempt's pieces, a
    speculative duplicate's, the pieces an assembled batch replaced."""

    def __init__(self, fw):
        self._fw = fw
        self._lock = threading.Lock()
        self._bufs: Dict[int, object] = {}   # None once closed

    def add(self, batch):
        # cache entries OUTLIVE the registering query: a later
        # cancellation must not free them
        buf = self._fw.add_device_batch(batch, scope_to_query=False)
        with self._lock:
            if self._bufs is not None:
                self._bufs[buf.id] = buf
                return buf
        _free_buffers([buf])   # a straggler's, after the relation stood
        return buf

    def free(self, bufs) -> None:
        with self._lock:
            if self._bufs is not None:
                for b in bufs:
                    self._bufs.pop(b.id, None)
        _free_buffers(bufs)

    def close(self, keep=()) -> None:
        """Free all but `keep`; whatever is added later frees itself."""
        with self._lock:
            left, self._bufs = self._bufs or {}, None
        kept = {b.id for b in keep}
        _free_buffers([b for i, b in left.items() if i not in kept])


def target_lanes(lane_bytes: float, target_bytes: int) -> int:
    """The largest capacity bucket whose batch, at `lane_bytes` a lane,
    stays inside `target_bytes`."""
    lanes = max(int(target_bytes // max(lane_bytes, 1.0)), MIN_CAPACITY)
    return 1 << (lanes.bit_length() - 1)


def fill_groups(rows: Sequence[int], lanes: int) -> List[List[int]]:
    """`range(len(rows))` cut, in order, into runs whose rows fit `lanes`.
    Rows against the capacity bucket, not bytes against the target
    (exec/transitions._coalesce_iter's rule): pieces that fit the target
    by their bytes can pass a bucket by their rows, and the batch then
    takes the next one, half of it padding. A piece is never split: one
    larger than `lanes` is a run of its own."""
    groups: List[List[int]] = []
    filled = 0
    for i, n in enumerate(rows):
        if groups and filled + n <= lanes:
            groups[-1].append(i)
            filled += n
        else:
            groups.append([i])
            filled = n
    return groups


class TpuCachedScanExec(_CachedScanBase, TpuExec):
    """Device-resident cache whose entries are SPILLABLE: each materialized
    batch is registered with the spill framework so the relation cache
    participates in the device->host->disk chain instead of pinning HBM
    (reference: cached GPU data flows through the RapidsBufferCatalog the
    same way, RapidsBufferCatalog.scala:40-99).

    The relation is held in batches of the engine's target size
    (`rapids.tpu.sql.batchSizeBytes`), not of whatever size the child
    handed over (a file reader's): it is written once and read for the
    session, and every operator over it pays its host cost a batch.
    Spark's InMemoryRelation sizes its own batches too, and the reference
    coalesces to the target before a GPU operator (GpuCoalesceBatches)."""

    placement = "tpu"

    def _store(self):
        return _DEVICE_CACHE

    def execute(self, ctx: ExecContext) -> PartitionedBatches:
        from spark_rapids_tpu.memory.spill import SpillFramework

        fw = SpillFramework.get()
        if fw is None:
            return super().execute(ctx)
        with _LOCK:
            cached = _DEVICE_CACHE.get(self.logical_node)
        if cached is None:
            cached = self._materialize(ctx, fw)

        def factory(pidx: int):
            def gen():
                for buf in cached[pidx]:
                    # a cached batch is a task's first data on the device,
                    # as an upload is a scan's: the admission permit is
                    # taken here, or no task of a cached query would hold one
                    TpuSemaphore.get().acquire_if_necessary(current_task_id())
                    with OBS.span("cache.serve", bytes=buf.size):
                        batch, restored = fw.fetch_device_batch(buf)
                        M.record_cached_batch_served(restored)
                        OBS.annotate(restored=int(restored))
                    yield batch
            return count_output(self.metrics, gen())

        return PartitionedBatches(len(cached), factory)

    def _materialize(self, ctx: ExecContext, fw):
        """Two passes. The child runs as it would have, a task a
        partition, and each batch is registered as it comes (a piece:
        spillable, so a relation larger than the budget still
        materializes). Then the pieces' real sizes say how they fill
        batches of the target size (`fill_groups`), and each run of
        pieces becomes one batch on this thread, one at a time: over the
        relation's own bytes the device holds one batch being assembled
        and nothing else, since its pieces go as soon as it stands."""
        from spark_rapids_tpu.engine.scheduler import run_job_or_serial

        child = self.children[0]
        child_pb = child.execute(ctx)
        merged = child.output_partitioning() is None
        made = _Registered(fw)

        def scan(pidx: int):
            out = []
            with OBS.span("cache.materialize", partition=pidx):
                try:
                    dict_columns = 0
                    for b in child_pb.iterator(pidx):
                        n = b.host_rows() if hasattr(b, "host_rows") \
                            else b.num_rows
                        if n > 0:
                            dict_columns = sum(map(is_encoded, b.columns))
                            out.append(_Piece(made.add(b), n, b.capacity))
                except BaseException:
                    # a failed attempt's buffers belong to no query and
                    # no relation: nothing else would free them
                    made.free([p.buf for p in out])
                    raise
                OBS.annotate(
                    rows=sum(p.rows for p in out), batches=len(out),
                    bytes=sum(p.buf.size for p in out),
                    columns=len(self.output), dict_columns=dict_columns)
            return out

        try:
            scanned = run_job_or_serial(
                ctx.scheduler, child_pb.num_partitions, scan)
            if merged:
                # no promise to keep: the batches run across the child's
                # partitions, and each is a partition of the relation
                runs = [[p for part in scanned for p in part]]
            else:
                # a join or an aggregate above may have planned on the
                # promise: the partitions stay
                runs = scanned
            lanes = target_lanes(
                max((p.buf.size / p.lanes for run in runs for p in run),
                    default=1.0),
                ctx.conf.get(C.BATCH_SIZE_BYTES))
            plan = [(ri, [run[i] for i in group])
                    for ri, run in enumerate(runs)
                    for group in fill_groups([p.rows for p in run], lanes)]
            bufs = self._assemble(fw, made, plan)
        except BaseException:
            made.close()
            raise
        if merged:
            parts = [[b] for b in bufs] or [[]]
        else:
            parts = [[] for _ in runs]
            for (ri, _), b in zip(plan, bufs):
                parts[ri].append(b)
        with _LOCK:
            # tpulint: shared-state-mutation -- under _LOCK; setdefault
            # keeps the first materialization on a concurrent race
            cached = _DEVICE_CACHE.setdefault(self.logical_node, parts)
            if cached is parts:
                # free the buffers when the logical node (cache key) dies
                weakref.finalize(self.logical_node, _free_buffers, bufs)
        # lost a concurrent-materialization race: drop our copies
        made.close(keep=bufs if cached is parts else ())
        return cached

    @staticmethod
    def _assemble(fw, made: _Registered, plan) -> list:
        """One buffer a run of `plan`: the piece itself where it is alone,
        else the pieces as one batch, which takes their place."""
        sem, tid = TpuSemaphore.get(), current_task_id()
        held = sem.held_by(tid)
        bufs = []
        try:
            for ri, group in plan:
                CX.check_cancel("cache.coalesce")
                with OBS.span("cache.coalesce", partition=ri,
                              pieces=len(group),
                              rows=sum(p.rows for p in group)):
                    buf, lanes = group[0].buf, group[0].lanes
                    if len(group) > 1:
                        sem.acquire_if_necessary(tid)
                        batch = concat_in_order(
                            [fw.get_device_batch(p.buf) for p in group])
                        # a piece's bytes go when the program that read
                        # them has run, the next batch's are taken when
                        # its program is issued: without the wait the
                        # device holds every piece and half the batches
                        # (once a batch, in the action that materialises)
                        jax.block_until_ready(batch.columns[0].data)
                        buf, lanes = made.add(batch), batch.capacity
                        made.free([p.buf for p in group])
                        M.record_cache_coalesced_pieces(len(group))
                    OBS.annotate(lanes=lanes, bytes=buf.size)
                bufs.append(buf)
        finally:
            if not held:
                sem.release_if_necessary(tid)
        return bufs


class CpuCachedScanExec(_CachedScanBase, CpuExec):
    placement = "cpu"

    def _store(self):
        return _HOST_CACHE
