"""Bounded background prefetch for scan decode (double buffering).

The issue-ahead executor (docs/async-execution.md) removes the host's
mid-query waits on the DEVICE; this module removes the symmetric stall on
the HOST side of a scan: with a prefetch depth of k, a daemon reader
thread decodes batch n+1..n+k while the consumer computes on batch n —
Arrow/pyarrow decode releases the GIL for its I/O and parse work, so the
overlap is real parallelism, not just interleaving. For the device scan
the reader also packs each batch for its upload (io/scan.py
`_read_host_iter(stage=True)`: numpy on host data, so an item of the
queue is a `StagedUpload`). The consumer then uploads on ITS OWN thread
(admission-semaphore acquisition is per task id, and JAX uploads are
asynchronous anyway, so the upload also overlaps compute without the
prefetcher touching device state).

Depth is `rapids.tpu.io.prefetchBatches` (0 = off, decode inline), with a
per-read override via `spark.read.option("prefetchBatches", k)`.

Contract:
- item order is preserved exactly (FIFO);
- an exception in the source iterator propagates to the consumer at the
  position where the item would have appeared (fault-injection and IO
  errors keep their per-batch attribution);
- `close()` (also called by __del__ and at exhaustion) stops the worker
  promptly AND joins the reader thread with a bounded timeout — a
  consumer that abandons the iterator (LIMIT early-exit, task retry,
  cancellation) leaves zero live threads behind (pinned by test);
- cancellation-aware (engine/cancel.py): the consumer's queue waits and
  the worker's puts both watch the constructing query's CancelToken, so
  a cancelled query's reader dies at the next poll instead of decoding
  an unbounded stream for nobody.
"""

from __future__ import annotations

import contextvars
import queue
import threading
from typing import Iterator, Optional, TypeVar

T = TypeVar("T")

_END = object()

# thread-name prefix every reader carries: the live-thread census
# (live_reader_count, the post-cancel reclamation invariant) keys on it
_THREAD_PREFIX = "srt-prefetch:"

# bounded waits: the consumer's queue-poll cadence (each wakeup re-checks
# closed + cancel) and the close()-time thread join bound
_POLL_S = 0.1
_JOIN_S = 5.0


def live_reader_count() -> int:
    """Live prefetch reader threads in the process (the reclamation
    invariant surface: after a cancellation — or any abandoned scan —
    this must return to zero, engine/cancel.reclamation_report)."""
    return sum(1 for t in threading.enumerate()
               if t.name.startswith(_THREAD_PREFIX) and t.is_alive())


def _prefetch_worker(source, q: "queue.Queue", closed: threading.Event,
                     token) -> None:
    """Worker body — a free function on purpose: a bound-method target
    would give the thread a strong reference to the iterator, so an
    abandoned PrefetchIterator could never be garbage-collected and its
    worker (plus the staged batches) would leak for the session's
    lifetime. Every put (items AND the END/error sentinel) retries with a
    timeout so a consumer that stopped draining can never wedge the
    worker — close() (or GC -> __del__ -> close()) sets `closed`, a
    query cancel fires `token`, and the worker exits at the next poll."""
    def dead() -> bool:
        return closed.is_set() or \
            (token is not None and token.cancelled)

    def put(payload) -> bool:
        while not dead():
            try:
                q.put(payload, timeout=_POLL_S)
                return True
            except queue.Full:
                continue
        return False

    try:
        for item in source:
            if not put(("item", item)):
                return
            if dead():
                return
        put((None, _END))
    except BaseException as e:  # noqa: BLE001 - relayed to consumer
        put(("error", e))


class PrefetchIterator:
    """Iterate `source` with up to `depth` items staged ahead by a daemon
    worker thread (depth >= 1; use maybe_prefetch for the 0 = inline
    gate)."""

    def __init__(self, source: Iterator[T], depth: int,
                 name: str = "scan-prefetch"):
        self._depth = max(1, int(depth))
        # exactly `depth` staged items; the END/error sentinel needs no
        # reserved slot because every put retries with a timeout. Total
        # decoded batches live per consumer: depth (queue) + 1 in the
        # worker's hand + the consumer's current one — the (2 + depth)
        # the resource analyzer charges scan leaves (an upper bound for
        # the device scan, whose items are packed buffers: no wider than
        # the decoded batch, a DOUBLE at f32 width on a TPU)
        self._queue: "queue.Queue" = queue.Queue(self._depth)
        self._closed = threading.Event()
        # queue-occupancy telemetry (docs/observability.md): the staged
        # depth observed at each consumer arrival — high-water ~= depth
        # means the reader keeps ahead (prefetch is winning); ~= 0 means
        # decode is the bottleneck. Reported as one completed span on the
        # constructing query's tracer at close(); tracing off = all None
        # checks, no clock reads.
        from spark_rapids_tpu.obs.trace import (
            current_span,
            current_tracer,
            wall_ns,
        )

        self._name = name
        self._tracer = current_tracer()
        # parent captured NOW: close() may run late (GC __del__) on a
        # thread whose current span belongs to a different query
        self._parent_span = current_span() if self._tracer is not None \
            else None
        self._start_ns = wall_ns() if self._tracer is not None else 0
        self._occ_high = 0
        self._items = 0
        self._reported = False
        # the constructing query's CancelToken (engine/cancel.py): both
        # sides of the queue watch it, and the query's reclamation pass
        # closes registered iterators on cancellation
        from spark_rapids_tpu.engine.cancel import current_token
        from spark_rapids_tpu.utils import metrics as _M

        self._token = current_token()
        # registration is paired with DE-registration in close(): the
        # query's reclamation list must not hold strong references to
        # finished iterators (an abandoned-unclosed iterator would also
        # never be GC-collectable while its query runs)
        self._qctx = _M.current_query_ctx()
        if self._qctx is not None:
            self._qctx.prefetchers.append(self)
        # the reader decodes on behalf of the constructing task's QUERY:
        # carry its contextvars (per-tenant QueryContext — metrics, fault
        # injector — docs/serving.md) onto the worker thread
        cctx = contextvars.copy_context()
        self._thread = threading.Thread(
            target=cctx.run,
            args=(_prefetch_worker, source, self._queue, self._closed,
                  self._token),
            name=_THREAD_PREFIX + name, daemon=True)
        self._thread.start()

    def __iter__(self) -> "PrefetchIterator":
        return self

    def __next__(self) -> T:
        if self._closed.is_set():
            raise StopIteration
        if self._tracer is not None:
            occ = self._queue.qsize()
            if occ > self._occ_high:
                self._occ_high = occ
        while True:
            # bounded poll: each wakeup re-checks close and the query's
            # CancelToken, so a cancelled consumer raises promptly
            # instead of outwaiting a dead reader
            try:
                kind, payload = self._queue.get(timeout=_POLL_S)
                break
            except queue.Empty:
                if self._closed.is_set():
                    raise StopIteration from None
                if self._token is not None:
                    try:
                        self._token.check("prefetch")
                    except BaseException:
                        self.close()
                        raise
        if payload is _END:
            self.close()
            raise StopIteration
        if kind == "error":
            self.close()
            raise payload
        self._items += 1
        return payload

    def close(self, join_timeout_s: float = _JOIN_S) -> None:
        """Stop the worker and JOIN its thread (bounded); safe to call
        multiple times / concurrently. The join is the satellite-bugfix
        contract: abandoning an unexhausted scan leaves ZERO live reader
        threads — the worker observes `closed` within one put/poll
        period, so the bound only trips if a source read itself wedges."""
        self._closed.set()
        # unblock a worker waiting on a full queue
        while True:
            try:
                self._queue.get_nowait()
            except queue.Empty:
                break
        thread = self._thread
        if thread is not None and thread is not threading.current_thread():
            thread.join(timeout=max(0.0, join_timeout_s))
        qctx = self._qctx
        if qctx is not None:
            self._qctx = None
            try:
                qctx.prefetchers.remove(self)
            except ValueError:
                pass  # already deregistered (reclamation raced close)
        if self._tracer is not None and not self._reported:
            self._reported = True
            from spark_rapids_tpu.obs.trace import wall_ns

            self._tracer.note_span(
                f"prefetch:{self._name}", self._start_ns, wall_ns(),
                attrs={"depth": self._depth, "items": self._items,
                       "occupancy_high_water": self._occ_high},
                parent=self._parent_span)

    def __del__(self):  # pragma: no cover - GC timing dependent
        try:
            self.close()
        except Exception:
            pass


def maybe_prefetch(source: Iterator[T], depth: int) -> Iterator[T]:
    """`source` staged `depth` ahead on a worker thread, or `source`
    itself when depth <= 0 (prefetch disabled)."""
    if depth <= 0:
        return source
    return PrefetchIterator(source, depth)


def prefetch_depth(conf, split=None) -> int:
    """Effective prefetch depth for a scan: the per-read option
    (`prefetchBatches` on the reader) overrides the session conf."""
    from spark_rapids_tpu import conf as C

    depth = conf.get(C.IO_PREFETCH_BATCHES)
    if split is not None:
        override = split.opt("prefetchBatches")
        if override is not None:
            depth = int(override)
    return max(0, min(16, int(depth)))
