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
`cache.materialize` span a partition on the first execution, a
`cache.serve` span a batch handed out on every execution, the process-wide
`cachedBatchesServed` / `cacheRestoredBatches` (a batch that had left the
device and was brought back) and the gauge `resident_bytes()`.
"""

from __future__ import annotations

import threading
import weakref
from typing import Dict, List

from spark_rapids_tpu.columnar.encoded import is_encoded
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


class TpuCachedScanExec(_CachedScanBase, TpuExec):
    """Device-resident cache whose entries are SPILLABLE: each materialized
    batch is registered with the spill framework so the relation cache
    participates in the device->host->disk chain instead of pinning HBM
    (reference: cached GPU data flows through the RapidsBufferCatalog the
    same way, RapidsBufferCatalog.scala:40-99)."""

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
            child_pb = self.children[0].execute(ctx)

            def mat(pidx: int):
                out = []
                with OBS.span("cache.materialize", partition=pidx):
                    try:
                        rows = dict_columns = 0
                        for b in child_pb.iterator(pidx):
                            n = b.host_rows() if hasattr(b, "host_rows") \
                                else b.num_rows
                            if n > 0:
                                dict_columns = sum(map(is_encoded, b.columns))
                                # cache entries OUTLIVE the registering
                                # query: a later cancellation must not
                                # free them
                                out.append(fw.add_device_batch(
                                    b, scope_to_query=False))
                                rows += n
                    except BaseException:
                        # a failed attempt's buffers belong to no query
                        # and no relation: nothing else would free them
                        _free_buffers(out)
                        raise
                    OBS.annotate(
                        rows=rows, batches=len(out),
                        bytes=sum(b.size for b in out),
                        columns=len(self.output), dict_columns=dict_columns)
                return out

            from spark_rapids_tpu.engine.scheduler import run_job_or_serial

            parts = run_job_or_serial(ctx.scheduler, child_pb.num_partitions, mat)
            with _LOCK:
                # tpulint: shared-state-mutation -- under _LOCK; setdefault
                # keeps the first materialization on a concurrent race
                cached = _DEVICE_CACHE.setdefault(self.logical_node, parts)
                if cached is parts:
                    # free the buffers when the logical node (cache key) dies
                    bufs = [b for part in parts for b in part]
                    weakref.finalize(self.logical_node, _free_buffers, bufs)
            if cached is not parts:
                # lost a concurrent-materialization race: drop our copies
                _free_buffers([b for part in parts for b in part])

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


class CpuCachedScanExec(_CachedScanBase, CpuExec):
    placement = "cpu"

    def _store(self):
        return _HOST_CACHE
