"""Host/device boundary + batch-coalescing operators.

Reference parity:
- HostToDeviceExec <- GpuRowToColumnarExec / HostColumnarToGpu
  (GpuRowToColumnarExec.scala:400-502, HostColumnarToGpu.scala:30-260):
  uploads host batches, acquiring the admission semaphore before device work.
- DeviceToHostExec <- GpuColumnarToRowExec / GpuBringBackToHost
  (GpuColumnarToRowExec.scala:35-230): downloads to host and releases the
  semaphore at batch end.
- CoalesceGoal algebra (TargetSize / RequireSingleBatch, max-combine,
  GpuCoalesceBatches.scala:90-112) and the accumulate-until-target iterator
  with an on-deck batch (AbstractGpuCoalesceIterator,
  GpuCoalesceBatches.scala:147-362) -> TpuCoalesceBatchesExec.
"""

from __future__ import annotations

import threading
from typing import Iterator, List, Optional

import numpy as np

from spark_rapids_tpu.columnar.batch import (
    ColumnarBatch,
    HostColumnarBatch,
    HostColumnVector,
    concat_batches,
    pack_tally,
)
from spark_rapids_tpu.columnar.dtypes import DataType
from spark_rapids_tpu.exec.base import (
    ExecContext,
    PartitionedBatches,
    PhysicalExec,
    TpuExec,
    count_output,
)
from spark_rapids_tpu.memory.semaphore import TpuSemaphore
from spark_rapids_tpu.obs import trace as OBS
from spark_rapids_tpu.utils import metrics as M

_task_counter = iter(range(1, 1 << 62))
_task_counter_lock = threading.Lock()
_task_local = threading.local()


def current_task_id() -> int:
    """Task-attempt id of the running partition task (TaskContext analog).
    The scheduler sets it; standalone callers get a thread-local fresh id."""
    tid = getattr(_task_local, "task_id", None)
    if tid is None:
        with _task_counter_lock:
            tid = next(_task_counter)
        _task_local.task_id = tid
    return tid


def set_task_id(task_id: Optional[int]) -> None:
    _task_local.task_id = task_id


# ---------------------------------------------------------------------------
# Coalesce goals (reference: CoalesceGoal, GpuCoalesceBatches.scala:90-112)
# ---------------------------------------------------------------------------
class CoalesceGoal:
    def max_combine(self, other: "CoalesceGoal") -> "CoalesceGoal":
        a = self.target_bytes()
        b = other.target_bytes()
        if a is None or b is None:  # RequireSingleBatch dominates
            return RequireSingleBatch()
        return TargetSize(max(a, b))

    def target_bytes(self) -> Optional[int]:
        raise NotImplementedError

    def satisfied_by(self, other: "CoalesceGoal") -> bool:
        a, b = self.target_bytes(), other.target_bytes()
        if a is None:
            return b is None
        return b is None or b >= a


class TargetSize(CoalesceGoal):
    def __init__(self, bytes_: int):
        self.bytes = bytes_

    def target_bytes(self):
        return self.bytes

    def __repr__(self):
        return f"TargetSize({self.bytes})"

    def __eq__(self, other):
        return isinstance(other, TargetSize) and other.bytes == self.bytes


class RequireSingleBatch(CoalesceGoal):
    def target_bytes(self):
        return None

    def __repr__(self):
        return "RequireSingleBatch"

    def __eq__(self, other):
        return isinstance(other, RequireSingleBatch)


def sink_fetch_many(run, keep_encoded: bool = False):
    """The device half of a grouped sink download, with async error
    attribution: the ONE place a query is allowed to block on device
    values. A device-rooted error surfacing here under issue-ahead
    execution belongs to some upstream dispatch, not to the transfer — it
    re-raises as TpuAsyncSinkError so the session's checked replay
    re-attributes it to the originating op (docs/async-execution.md).
    Returns `columnar/batch.fetch_many`'s finish: the call that rebuilds
    the host batches, pure host work that holds no device array.
    `keep_encoded`: a dictionary column comes back as codes + dictionary
    (`HostDictionaryColumn`), for a consumer that takes it so (a file
    writer); otherwise it is expanded to values at the finish, as a
    result's rows need."""
    from spark_rapids_tpu.columnar.batch import fetch_many
    from spark_rapids_tpu.engine.async_exec import async_enabled
    from spark_rapids_tpu.engine.retry import (
        TpuAsyncSinkError,
        as_typed_error,
        with_retry,
    )

    if OBS.current_tracer() is not None:
        # the fence carries its size: on the caller's DeviceToHost span,
        # the device bytes of the columns fetched (dictionaries stay
        # behind; a partial bucket is trimmed before the transfer)
        OBS.annotate(batches=len(run),
                     bytes=sum(c.device_memory_size()
                               for b in run for c in b.columns),
                     keep_encoded=keep_encoded)
    try:
        return with_retry(
            lambda: fetch_many(run, keep_encoded=keep_encoded),
            site="transfer.download")
    except Exception as e:  # noqa: BLE001 — attribution boundary
        typed = as_typed_error(e)
        if typed is None or isinstance(typed, TpuAsyncSinkError) or \
                not async_enabled():
            raise
        raise TpuAsyncSinkError(
            f"device error surfaced at the sink download: {typed}"
        ) from e


def sink_download_many(run, keep_encoded: bool = False):
    """`sink_fetch_many` and its finish in one call: the query-level
    lifted sink, which holds no permit to give back in between."""
    return sink_fetch_many(run, keep_encoded)()


# ---------------------------------------------------------------------------
# Transitions
# ---------------------------------------------------------------------------
class HostToDeviceExec(TpuExec):
    """Upload host batches to the device (reference: GpuRowToColumnarExec /
    HostColumnarToGpu; semaphore acquired before upload,
    GpuRowToColumnarExec.scala:432)."""

    def __init__(self, child: PhysicalExec):
        super().__init__(child)

    @property
    def output(self):
        return self.children[0].output

    def with_children(self, new_children):
        return HostToDeviceExec(new_children[0])

    def execute(self, ctx: ExecContext) -> PartitionedBatches:
        child_pb = self.children[0].execute(ctx)
        total_time = self.metrics[M.TOTAL_TIME]
        peak_mem = self.metrics[M.PEAK_DEVICE_MEMORY]

        def factory(pidx: int) -> Iterator[ColumnarBatch]:
            from spark_rapids_tpu.engine.retry import with_retry
            from spark_rapids_tpu.memory.spill import SpillFramework

            sem = TpuSemaphore.get()
            fw = SpillFramework.get()
            for hb in child_pb.iterator(pidx):
                sem.acquire_if_necessary(current_task_id())
                if fw is not None:
                    # preemptive spill before the upload (the TPU analog of
                    # the RMM alloc-failure hook,
                    # DeviceMemoryEventHandler.scala:65-89)
                    fw.watermark.ensure_headroom(hb.estimated_size_bytes())
                with M.trace_range("HostToDevice", total_time):
                    # an upload OOM spills tracked buffers and re-uploads;
                    # the host batch is intact, so the retry is pure
                    db = with_retry(lambda: hb.to_device(),
                                    site="transfer.upload")
                    size = db.device_memory_size()
                    OBS.annotate(batches=1, bytes=size)
                peak_mem.set_max(size)
                yield db

        return PartitionedBatches(child_pb.num_partitions,
                                  lambda p: count_output(self.metrics, factory(p)))


class DeviceToHostExec(PhysicalExec):
    """Download device batches to host and release the semaphore
    (reference: GpuColumnarToRowExec copies a batch to the host, releases
    the semaphore, and only then iterates its rows; the task takes it
    again at its next device use: GpuColumnarToRowExec.scala:62-155,
    release :109; GpuBringBackToHost.scala:52).

    The child is drained in bounded runs and each run downloaded with ONE
    grouped transfer (per-batch downloads cost one fence each); the run
    size ramps 1 -> 32. A run's download is two halves: the fetch
    (`sink_fetch_many`: pack, wait, transfer: all that touches the
    device) and the finish (host columns rebuilt from the fetched bytes:
    numpy on one thread, 22-31 ms a 24-28 MB fence on the chip's host).
    The permit is for the first half. The child is pulled ONE batch
    ahead of the run, under the permit (a child with buffered device
    work, a coalesce's last concat or an aggregate's emit, does it there,
    never after a release), so a run is fetched knowing whether the child
    has ended. If it has, the run's device batches are dropped after the
    fetch, the task gives the permit back, and the run is finished
    without it, while another task uploads; if not, the run is finished
    under the permit as before, and the batch pulled ahead (resident
    beside the run meanwhile: one batch more than before, where a
    partition has more than one) opens the next run. So the
    `_download_finish` of a partition's LAST run never holds the chip,
    and whatever the consumer does with the host batches after it reaches
    the device only through a path that acquires (`HostToDeviceExec`, a
    scan's upload). The `finally` is the backstop for an exception and
    for a consumer that stops early.

    Early exit (LIMIT): a run's host batches are handed over one child
    batch later than the run holds: the first after at most TWO child
    batches and one fetch (it was one and one before the look-ahead).

    The pull ahead comes before the run's `DeviceToHost` span opens, not
    inside it: a child keeps spans open across its yields
    (`coalesce-concat`), and a span may only close while it is the
    innermost."""

    placement = "cpu"  # output is host data

    def __init__(self, child: PhysicalExec, keep_encoded: bool = False):
        super().__init__(child)
        # the consumer takes a dictionary column as codes + dictionary
        # (io/writer.py sets it for the sink under a WriteFile); any
        # other consumer reads values
        self.keep_encoded = keep_encoded

    @property
    def output(self):
        return self.children[0].output

    def with_children(self, new_children):
        return DeviceToHostExec(new_children[0], self.keep_encoded)

    def execute(self, ctx: ExecContext) -> PartitionedBatches:
        child_pb = self.children[0].execute(ctx)
        total_time = self.metrics[M.TOTAL_TIME]
        keep_encoded = self.keep_encoded

        def factory(pidx: int) -> Iterator[HostColumnarBatch]:
            sem = TpuSemaphore.get()
            try:
                child = iter(child_pb.iterator(pidx))
                run_cap = 1
                ahead = next(child, None)
                while ahead is not None:
                    run, run_bytes = [ahead], ahead.device_memory_size()
                    ahead = next(child, None)
                    while ahead is not None and len(run) < run_cap \
                            and run_bytes <= (128 << 20):
                        run.append(ahead)
                        run_bytes += ahead.device_memory_size()
                        ahead = next(child, None)
                    with M.trace_range("DeviceToHost", total_time):
                        finish = sink_fetch_many(run, keep_encoded)
                        del run
                        if ahead is None:  # the child has ended
                            sem.release_if_necessary(current_task_id())
                        hbs = finish()
                    yield from hbs
                    run_cap = min(run_cap * 2, 32)
            finally:
                sem.release_if_necessary(current_task_id())

        return PartitionedBatches(child_pb.num_partitions,
                                  lambda p: count_output(self.metrics, factory(p)))


# ---------------------------------------------------------------------------
# Batch coalescing
# ---------------------------------------------------------------------------
def _coalesce_iter(it: Iterator, goal: CoalesceGoal, concat, size_of,
                   metrics: M.MetricsMap) -> Iterator:
    """Accumulate-until-target with an on-deck batch (reference:
    AbstractGpuCoalesceIterator, GpuCoalesceBatches.scala:147-362)."""
    target = goal.target_bytes()
    pending: List = []
    pending_bytes = 0
    concat_time = metrics["concatTime"]

    def concat_pending():
        # the span's attrs say what the concat was given and what packing
        # it cost (columnar/batch.PackTally; a host concat packs nothing)
        with pack_tally() as tally:
            out = concat(pending)
        OBS.annotate(pieces=len(pending), operands=tally.operands,
                     programs=tally.programs)
        return out

    for b in it:
        if target is not None and pending and \
                pending_bytes + size_of(b) > target:
            with M.trace_range("coalesce-concat", concat_time):
                yield concat_pending()
            pending, pending_bytes = [], 0
        pending.append(b)
        pending_bytes += size_of(b)
    if pending:
        with M.trace_range("coalesce-concat", concat_time):
            yield concat_pending()


def _concat_host(batches: List[HostColumnarBatch]) -> HostColumnarBatch:
    if len(batches) == 1:
        return batches[0]
    ncols = batches[0].num_columns
    cols = []
    for ci in range(ncols):
        dt = batches[0].columns[ci].dtype
        datas = [b.columns[ci].data[:b.num_rows] for b in batches]
        valids = [b.columns[ci].validity[:b.num_rows] for b in batches]
        cols.append(HostColumnVector(dt, np.concatenate(datas),
                                     np.concatenate(valids)))
    return HostColumnarBatch(cols, sum(b.num_rows for b in batches))


class TpuCoalesceBatchesExec(TpuExec):
    """Reference: GpuCoalesceBatches exec, GpuCoalesceBatches.scala:417-440."""

    def __init__(self, goal: CoalesceGoal, child: PhysicalExec):
        super().__init__(child)
        self.goal = goal

    @property
    def output(self):
        return self.children[0].output

    def with_children(self, new_children):
        return TpuCoalesceBatchesExec(self.goal, new_children[0])

    def node_name(self):
        return f"TpuCoalesceBatches({self.goal!r})"

    def execute(self, ctx: ExecContext) -> PartitionedBatches:
        child_pb = self.children[0].execute(ctx)
        goal = self.goal
        return PartitionedBatches(
            child_pb.num_partitions,
            lambda p: count_output(
                self.metrics,
                _coalesce_iter(child_pb.iterator(p), goal,
                               concat_batches,
                               lambda b: b.device_memory_size(),
                               self.metrics)),
            bucket_costs=child_pb.bucket_costs)


class CpuCoalesceBatchesExec(PhysicalExec):
    placement = "cpu"

    def __init__(self, goal: CoalesceGoal, child: PhysicalExec):
        super().__init__(child)
        self.goal = goal

    @property
    def output(self):
        return self.children[0].output

    def with_children(self, new_children):
        return CpuCoalesceBatchesExec(self.goal, new_children[0])

    def execute(self, ctx: ExecContext) -> PartitionedBatches:
        child_pb = self.children[0].execute(ctx)
        goal = self.goal
        return PartitionedBatches(
            child_pb.num_partitions,
            lambda p: count_output(
                self.metrics,
                _coalesce_iter(child_pb.iterator(p), goal,
                               _concat_host,
                               lambda b: b.estimated_size_bytes(),
                               self.metrics)),
            bucket_costs=child_pb.bucket_costs)
