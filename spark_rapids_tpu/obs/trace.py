"""QueryContext-scoped span tree: the engine's tracing substrate.

Reference parity: the plugin wraps every device range in NvtxWithMetrics
(NvtxWithMetrics.scala:27-44) so nsys timelines show WHERE a query spent
its time. XLA has no NVTX, so the analog here is a host-side span tree —
query -> stage -> operator -> site — recorded per query into the ambient
QueryContext (utils/metrics.py) and exported as a Chrome-trace-event
timeline (obs/perfetto.py) or aggregated per stage/operator
(obs/analyze.py, bench.py --obs).

Overhead contract (docs/observability.md):

- HOST CLOCKS ONLY: a span reads the wall clock (time.perf_counter_ns)
  at open and at close — never a device value, never a transfer. The
  few spans named in `CPU_CLOCKED` read their thread's CPU clock
  (time.thread_time_ns) beside it, and a traced query reads the
  process's CPU clock (time.process_time_ns) once at each end.
  Tracing adds ZERO device dispatches and ZERO host fences; the flagship
  deviceDispatches/fencesPerQuery counts are identical with tracing on
  vs off (pinned by tests/test_observability.py). The one place tracing
  calls into jax is the sink's `sink.wait` (columnar/batch.to_host_many:
  block_until_ready on arrays the next line fetches anyway).
- TRUE NO-OP WHEN OFF: with `rapids.tpu.obs.tracing.enabled` off the
  ambient QueryContext carries no tracer, `span(...)` returns one shared
  no-op context manager (no allocation, no clock read), and the metric
  chokepoints' tracer hand-off is a single attribute check.
- BOUNDED: at most `rapids.tpu.obs.trace.maxSpans` spans attach per
  query; further spans are counted in `dropped_spans`, never recorded.

Thread model: the scheduler submits partition tasks with
contextvars.copy_context (engine/scheduler._submit), so the current-span
contextvar propagates onto worker threads exactly like the QueryContext
itself — a task span opened on a worker nests under whatever span was
current at submission. All tree mutation is guarded by one tracer lock
(concurrent worker tasks attach under a shared parent).
"""

from __future__ import annotations

import contextvars
import threading
import time
from typing import Dict, Iterator, List, Optional

from spark_rapids_tpu.utils import metrics as M

# the one sanctioned wall-clock source for engine telemetry: exec//engine//
# shuffle//aqe/ code must time through the span API or this helper (the
# tpulint naked-timer rule), so every duration in the engine shares one
# clock and one unit (ns)
def wall_ns() -> int:
    return time.perf_counter_ns()


# the busy half of a span: CPU time of the calling thread alone. Wall less
# CPU is time the thread spent off a core: queued for the interpreter's
# lock or a core, asleep while Arrow's pool, the device or the disk works
def thread_cpu_ns() -> int:
    return time.thread_time_ns()


# the spans that read it, by name: the ones a per-layer metric or a table
# of PERF.md section 5 reads (one thread's own numpy, Python or encode,
# and the permit wait, which must read about 0). Not every span: where
# the kernel answers this clock with a system call (6 us on the chip's
# host, against 0.09 for the wall clock) a read a span was 3.3 ms of a
# 75 ms action (PERF.md section 6, PR 43)
CPU_CLOCKED = frozenset({
    "scan.convert", "scan.pack", "sink.finish", "write.file",
    "Acquire TPU Semaphore"})


# ambient current span (parallel to utils/metrics._QUERY_CTX; propagated
# onto worker threads by the scheduler's copy_context submission)
_CURRENT_SPAN: "contextvars.ContextVar[Optional[Span]]" = \
    contextvars.ContextVar("srt_obs_span", default=None)

# span kinds, outer to inner (the tree does not enforce strict layering —
# a site span may open directly under the query root)
KIND_QUERY = "query"
KIND_STAGE = "stage"
KIND_OP = "op"
KIND_TASK = "task"
KIND_SITE = "site"


class Span:
    """One timed node of the query span tree. `counts` accumulates the
    metric increments (deviceDispatches, retries, ...) recorded while
    this span was current on its thread."""

    __slots__ = ("name", "kind", "start_ns", "end_ns", "tid", "attrs",
                 "counts", "children", "owner", "cpu_ns", "_cpu_start_ns")

    def __init__(self, name: str, kind: str, start_ns: int,
                 attrs: Optional[dict] = None, owner=None):
        self.name = name
        self.kind = kind
        self.start_ns = start_ns
        self.end_ns: Optional[int] = None
        self.tid = threading.get_ident()
        self.attrs: Dict[str, object] = dict(attrs) if attrs else {}
        self.counts: Dict[str, int] = {}
        self.children: List[Span] = []
        # the QueryTracer this span belongs to: parenting/count fallback
        # checks it so a stale current-span from ANOTHER query's tracer
        # (a contextvar that outlived its query on some thread) can never
        # be mutated under the wrong lock or absorb a foreign child
        self.owner = owner
        # CPU ns of the opening thread between open and close, for a
        # span named in CPU_CLOCKED; None for every other, and for one
        # closed on another thread (a generator resumed elsewhere),
        # closed by finish(), or noted after the fact
        self.cpu_ns: Optional[int] = None
        self._cpu_start_ns: Optional[int] = None

    @property
    def duration_ns(self) -> int:
        if self.end_ns is None:
            return 0
        return self.end_ns - self.start_ns

    def __repr__(self):
        ms = self.duration_ns / 1e6
        return f"Span({self.kind}:{self.name}, {ms:.3f}ms)"


class _NoopSpanCtx:
    """The shared zero-cost stand-in returned by span() when tracing is
    off: no allocation, no clock read, nothing to tear down."""

    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


_NOOP = _NoopSpanCtx()


class QueryTracer:
    """One running query's span collector, carried on its QueryContext.

    The metric layer (utils/metrics.py) talks to this object duck-typed
    — `open_span` / `close_span` / `add_count` — so metrics never imports
    obs and the import graph stays acyclic."""

    def __init__(self, name: str = "query", tenant: str = "default",
                 max_spans: int = 20000, annotate: bool = False):
        self._lock = threading.Lock()
        self.max_spans = max(1, int(max_spans))
        self.dropped_spans = 0
        self.tenant = tenant
        # optional jax.profiler bridge (the NvtxWithMetrics analog for
        # XProf): every live span ALSO enters a TraceAnnotation so an
        # XProf capture shows the same names. Resolved once here; tracing
        # itself never needs jax.
        self._annotation_cls = None
        if annotate:
            try:
                from jax.profiler import TraceAnnotation
                self._annotation_cls = TraceAnnotation
            except Exception:  # pragma: no cover - profiler-less jax
                self._annotation_cls = None
        self.root = Span(f"query:{name}", KIND_QUERY, wall_ns(),
                         {"tenant": tenant}, owner=self)
        # CPU of ALL the process's threads so far (the tasks', Arrow's
        # and XLA's pools); finish() writes the difference onto the root
        # as `proc_cpu_ms`. PROCESS-WIDE: with one client it is one
        # action's, with several it is everybody's
        self._proc_cpu_start_ns = time.process_time_ns()
        self._n_spans = 1
        self._finished = False

    def _parent(self, explicit: Optional[Span] = None) -> Span:
        """The attachment point for a new span/count: the explicit parent
        or the thread's current span — but ONLY when it belongs to THIS
        tracer (structural guard against a stale contextvar from another
        query); otherwise the root."""
        sp = explicit if explicit is not None else _CURRENT_SPAN.get()
        if sp is not None and sp.owner is self:
            return sp
        return self.root

    # -- span lifecycle (duck-typed surface for utils/metrics.py) ------------
    def open_span(self, name: str, kind: str = KIND_SITE,
                  attrs: Optional[dict] = None):
        """Open a span under the current span (or the root) and make it
        current; returns the (span, token, annotation) handle for
        close_span. Past the span cap the span is counted as dropped and
        NOT made current — metric increments during its window fold into
        the retained parent instead of vanishing on an orphan (the
        counts, unlike the dropped span's timing, must stay exact: they
        reconcile against the query's own metrics)."""
        sp = Span(name, kind, wall_ns(), attrs, owner=self)
        if name in CPU_CLOCKED:
            # after the wall clock here and before it in close_span: the
            # CPU interval lies inside the wall interval. (Where the
            # kernel's CPU clock steps in ticks, 10 ms on the chip's
            # host, one span's reading may still pass its wall: there
            # only sums over many spans say anything)
            sp._cpu_start_ns = thread_cpu_ns()
        parent = self._parent()
        token = None
        with self._lock:
            if not self._finished and self._n_spans < self.max_spans:
                parent.children.append(sp)
                self._n_spans += 1
                attached = True
            else:
                self.dropped_spans += 1
                attached = False
        # annotation BEFORE the contextvar set: a raising
        # TraceAnnotation.__enter__ must not leak a token that would pin
        # _CURRENT_SPAN to this span for the rest of the thread's query
        anno = None
        if self._annotation_cls is not None:
            anno = self._annotation_cls(name)
            anno.__enter__()
        if attached:
            token = _CURRENT_SPAN.set(sp)
        return sp, token, anno

    def close_span(self, handle) -> None:
        sp, token, anno = handle
        if anno is not None:
            anno.__exit__(None, None, None)
        if sp._cpu_start_ns is not None and sp.tid == threading.get_ident():
            # closed on the thread whose clock was read at the opening
            sp.cpu_ns = thread_cpu_ns() - sp._cpu_start_ns
        sp.end_ns = wall_ns()
        if token is not None:
            _CURRENT_SPAN.reset(token)

    def note_span(self, name: str, start_ns: int, end_ns: int,
                  kind: str = KIND_SITE,
                  attrs: Optional[dict] = None,
                  parent: Optional[Span] = None) -> Optional[Span]:
        """Attach an already-completed span (for instrumentation that
        only knows its numbers at teardown — the prefetch queue reports
        its occupancy high-water when it closes). Parents under the
        caller-captured `parent` span when given (a late reporter may run
        on a thread whose current span belongs to a DIFFERENT query), the
        calling thread's current span otherwise, then the root. A
        finished tracer drops the span: its tree was already exported."""
        sp = Span(name, kind, start_ns, attrs, owner=self)
        sp.end_ns = end_ns
        parent = self._parent(parent)
        with self._lock:
            if self._finished:
                return None
            if self._n_spans < self.max_spans:
                parent.children.append(sp)
                self._n_spans += 1
            else:
                self.dropped_spans += 1
                return None
        return sp

    def add_count(self, key: str, n: int = 1) -> None:
        """Accumulate a metric increment onto the current span (falling
        back to the root). Called from utils/metrics._note for every
        recorded counter while tracing is on."""
        sp = self._parent()
        with self._lock:
            if self._finished:
                return
            sp.counts[key] = sp.counts.get(key, 0) + n

    def finish(self) -> "QueryTrace":
        with self._lock:
            self._finished = True
        end = wall_ns()
        self.root.attrs["proc_cpu_ms"] = \
            (time.process_time_ns() - self._proc_cpu_start_ns) / 1e6
        # a query killed mid-flight (cancel / deadline expiry / shed,
        # engine/cancel.py) unwinds through exceptions that skip worker
        # threads' close_span calls: close every still-open span at the
        # query-end timestamp, so a cancelled query still exports a
        # COMPLETE tree (valid Perfetto durations, pinned by
        # tests/test_cancel.py). _finished is set first under the lock,
        # so no new span can attach while we walk.
        stack = [self.root]
        while stack:
            sp = stack.pop()
            if sp.end_ns is None:
                sp.end_ns = end
            stack.extend(sp.children)
        return QueryTrace(self.root, self.tenant, self.dropped_spans)


class _SpanCtx:
    """Live context manager returned by span() when tracing is on."""

    __slots__ = ("_tr", "_name", "_kind", "_attrs", "_handle")

    def __init__(self, tr: QueryTracer, name: str, kind: str, attrs: dict):
        self._tr = tr
        self._name = name
        self._kind = kind
        self._attrs = attrs
        self._handle = None

    def __enter__(self) -> Span:
        self._handle = self._tr.open_span(self._name, self._kind,
                                          self._attrs)
        return self._handle[0]

    def __exit__(self, *exc):
        self._tr.close_span(self._handle)
        return False


def current_tracer() -> Optional[QueryTracer]:
    """The ambient query's tracer, or None (tracing off / no query)."""
    ctx = M.current_query_ctx()
    return ctx.trace if ctx is not None else None


def current_span() -> Optional[Span]:
    """The calling thread's currently-open span, or None."""
    return _CURRENT_SPAN.get()


def reset_current_span():
    """Clear the calling context's current span (returns the restore
    token). The session uses this when it installs a fresh tracer for a
    NESTED run — the micro-batcher's packed execution under a traced
    leader — so the inner query's spans root in its own tree instead of
    parenting onto the enclosing query's open span."""
    return _CURRENT_SPAN.set(None)


def restore_current_span(token) -> None:
    _CURRENT_SPAN.reset(token)


def span(name: str, kind: str = KIND_SITE, **attrs):
    """Open a timed span around a block:

        with OBS.span("stage:map", kind="stage", maps=8):
            ...

    Returns the live Span (attrs/counts writable) when tracing is on, or
    a shared no-op context manager when it is off — instrumentation
    sites never need to check the conf themselves."""
    tr = current_tracer()
    if tr is None:
        return _NOOP
    return _SpanCtx(tr, name, kind, attrs)


def annotate(**attrs) -> None:
    """Set attrs on the calling thread's open span — for a site that
    learns a size only inside a span someone else opened (`trace_range`
    yields nothing; `scan.decode` is opened by the scan around the
    decoder). Nothing happens with tracing off, or when the open span
    belongs to another query's tracer."""
    tr = current_tracer()
    if tr is None:
        return
    sp = _CURRENT_SPAN.get()
    if sp is not None and sp.owner is tr:
        sp.attrs.update(attrs)


class QueryTrace:
    """A finished query's immutable span tree + exporters. Stashed on
    `session.last_query_trace` after every traced query."""

    def __init__(self, root: Span, tenant: str, dropped_spans: int = 0):
        self.root = root
        self.tenant = tenant
        self.dropped_spans = dropped_spans

    # -- traversal -----------------------------------------------------------
    def spans(self) -> Iterator[Span]:
        """Depth-first, root first."""
        stack = [self.root]
        while stack:
            sp = stack.pop()
            yield sp
            stack.extend(reversed(sp.children))

    def find(self, needle: str) -> List[Span]:
        return [s for s in self.spans() if needle in s.name]

    @property
    def duration_ns(self) -> int:
        return self.root.duration_ns

    def counts_total(self) -> Dict[str, int]:
        """Every metric increment recorded anywhere in the tree, summed."""
        out: Dict[str, int] = {}
        for sp in self.spans():
            for k, v in sp.counts.items():
                out[k] = out.get(k, 0) + v
        return out

    # -- aggregation ---------------------------------------------------------
    def stage_breakdown(self) -> Dict[str, float]:
        """Wall seconds per TOP-LEVEL stage span (a stage nested inside
        another stage — an exchange materialized within an AQE stage —
        folds into its ancestor): the per-stage cost signal BENCH_r12+
        records for the cost-model roadmap item."""
        out: Dict[str, float] = {}

        def walk(sp: Span, inside_stage: bool) -> None:
            is_stage = sp.kind == KIND_STAGE
            if is_stage and not inside_stage:
                out[sp.name] = out.get(sp.name, 0.0) + sp.duration_ns / 1e9
            for c in sp.children:
                walk(c, inside_stage or is_stage)

        walk(self.root, False)
        return out

    def op_breakdown(self) -> Dict[str, Dict[str, float]]:
        """Per operator-span name: total wall seconds, invocation count,
        and summed per-span counts (dispatches etc.)."""
        out: Dict[str, Dict[str, float]] = {}
        for sp in self.spans():
            if sp.kind != KIND_OP:
                continue
            rec = out.setdefault(sp.name, {"seconds": 0.0, "calls": 0})
            rec["seconds"] += sp.duration_ns / 1e9
            rec["calls"] += 1
            for k, v in sp.counts.items():
                rec[k] = rec.get(k, 0) + v
        return out

    # -- exporters -----------------------------------------------------------
    def to_perfetto(self) -> dict:
        from spark_rapids_tpu.obs.perfetto import trace_to_chrome_events

        return trace_to_chrome_events(self)

    def to_perfetto_json(self) -> str:
        import json

        return json.dumps(self.to_perfetto())

    def render(self, max_depth: int = 12) -> str:
        """Human-readable tree (docs/observability.md examples)."""
        lines: List[str] = []

        def walk(sp: Span, depth: int) -> None:
            if depth > max_depth:
                return
            extras = ""
            if sp.counts:
                extras = " " + " ".join(
                    f"{k}={v}" for k, v in sorted(sp.counts.items()))
            cpu = "" if sp.cpu_ns is None \
                else f" cpu={sp.cpu_ns / 1e6:.3f}ms"
            lines.append("  " * depth
                         + f"[{sp.kind}] {sp.name}"
                         f" {sp.duration_ns / 1e6:.3f}ms{cpu}{extras}")
            for c in sp.children:
                walk(c, depth + 1)

        walk(self.root, 0)
        if self.dropped_spans:
            lines.append(f"(+{self.dropped_spans} spans dropped at the "
                         "maxSpans cap)")
        return "\n".join(lines)
