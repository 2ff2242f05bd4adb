"""Metrics + tracing.

Reference parity:
- GpuMetricNames / GpuExec standard metrics (GpuExec.scala:24-41): numOutputRows,
  numOutputBatches, totalTime, peakDevMemory, plus op-specific metrics.
- NvtxWithMetrics (NvtxWithMetrics.scala:27-44): a profiler range that adds its
  elapsed time to a metric on close. The TPU analog is
  jax.profiler.TraceAnnotation (XProf/TraceMe), falling back to a no-op
  timer when the profiler is unavailable.
"""

from __future__ import annotations

import contextlib
import contextvars
import threading
import time
from typing import Dict, Optional

try:
    from jax.profiler import TraceAnnotation as _TraceAnnotation
except Exception:  # pragma: no cover
    _TraceAnnotation = None

# standard metric names (reference: GpuMetricNames, GpuExec.scala:24-41)
NUM_OUTPUT_ROWS = "numOutputRows"
NUM_OUTPUT_BATCHES = "numOutputBatches"
NUM_INPUT_ROWS = "numInputRows"
NUM_INPUT_BATCHES = "numInputBatches"
TOTAL_TIME = "totalTime"
PEAK_DEVICE_MEMORY = "peakDevMemory"
# whole-stage fusion metrics (plan/fusion.py; Spark's WholeStageCodegen has
# no dispatch analog — on an accelerator every program launch is one host
# round trip, so the dispatch count IS the fusion win's unit)
FUSED_STAGES = "fusedStages"
DEVICE_DISPATCHES = "deviceDispatches"
# fault-tolerance metrics (engine/retry.py; reference: the retry/OOM state
# machine the plugin wraps every GPU allocation in + per-op CPU fallback)
RETRIES = "retries"
SPLIT_RETRIES = "splitRetries"
CPU_FALLBACK_EVENTS = "cpuFallbackEvents"
FETCH_RETRIES = "fetchRetries"
# async issue-ahead metrics (engine/async_exec.py, docs/async-execution.md):
# fences = device->host transfer events the engine issued (the
# site="transfer.download" instrumentation); checkedReplays = whole-query
# re-executions in checked (synchronous) mode after an error surfaced at
# the sink; donatedBytes = input bytes donated into consume-once kernels
FENCES = "fencesPerQuery"
CHECKED_REPLAYS = "checkedReplays"
DONATED_BYTES = "donatedBytes"
# single-program SPMD stage metrics (plan/spmd.py, engine/spmd_exec.py):
# spmdStages = stage pipelines that executed as ONE shard_map program over
# the mesh; collectiveBytes = bytes moved by in-program ICI collectives
# (the all_to_all exchange epoch and the sort-absorbing all_gather)
SPMD_STAGES = "spmdStages"
COLLECTIVE_BYTES = "collectiveBytes"
# serving-runtime metrics (plan/plan_cache.py, engine/admission.py,
# engine/server.py, docs/serving.md): planCacheHits/Misses count
# signature-cache lookups for cache-enabled queries (a hit skips planning,
# verification, AND resource analysis); admissionWaits counts queries that
# blocked in analyzer-driven HBM admission before running;
# microBatches/microBatchedQueries count packed windows and the individual
# queries that rode in one
PLAN_CACHE_HITS = "planCacheHits"
PLAN_CACHE_MISSES = "planCacheMisses"
ADMISSION_WAITS = "admissionWaits"
# admissionWaits counts EVENTS; this accumulates the waited DURATION in
# nanoseconds (engine/admission.py measures it via the obs wall clock) —
# the server snapshot additionally surfaces a p50/p95 from the
# controller's bounded sample reservoir
ADMISSION_WAIT_NS = "admissionWaitNs"
MICRO_BATCHES = "microBatches"
MICRO_BATCHED_QUERIES = "microBatchedQueries"
# encoded columnar execution (columnar/encoded.py,
# docs/compressed-execution.md): encodedColumns counts device columns the
# scan layer emitted ENCODED (codes + shared dictionary, per column per
# decoded chunk); lateMaterializations counts explicit decode events — the
# only path from codes back to values (device materialize() at an operator
# boundary, host expansion at the result sink / serde); encodedBytesSaved
# accumulates the HBM the encoded representation avoided at scan emission,
# rows x (string-estimate bytes - code bytes) per encoded column — the
# same formula the resource analyzer predicts, so containment is testable
ENCODED_COLUMNS = "encodedColumns"
LATE_MATERIALIZATIONS = "lateMaterializations"
ENCODED_BYTES_SAVED = "encodedBytesSaved"
# order-preserving / run-aware compressed compute (PR: rank-space sorts):
# orderPreservingSorts counts sorts / range-bound computations / window
# orderings that ran over rank codes instead of decoding (one count per
# batch kept in rank space); runCollapsedRows accumulates rows the
# run-granular aggregate path collapsed away (rows - runs per collapsed
# update batch)
ORDER_PRESERVING_SORTS = "orderPreservingSorts"
RUN_COLLAPSED_ROWS = "runCollapsedRows"
# adaptive query execution (spark_rapids_tpu/aqe/,
# docs/adaptive-execution.md): aqeReplans counts rule applications that
# rewrote (and statically re-validated) the not-yet-executed remainder;
# skewSplits counts oversized reduce buckets split into sub-partitions;
# joinDemotions/joinPromotions count runtime join-strategy switches
# (shuffled -> broadcast / broadcast -> shuffled)
# single-program SPMD composition (plan/spmd.py, engine/spmd_exec.py):
# spmdJoins counts INNER equi-joins lowered INTO a stage program (build
# broadcast via in-program all_gather); spmdMeasuredCaps counts stage
# segments whose exchange-bucket capacity came from AQE's MEASURED
# MapOutputStats instead of the analyzer's pessimistic interval
SPMD_JOINS = "spmdJoins"
SPMD_MEASURED_CAPS = "spmdMeasuredCaps"
AQE_REPLANS = "aqeReplans"
SKEW_SPLITS = "skewSplits"
JOIN_DEMOTIONS = "joinDemotions"
JOIN_PROMOTIONS = "joinPromotions"
# cooperative cancellation / deadline / overload shedding
# (engine/cancel.py, engine/admission.py, docs/fault-tolerance.md):
# cancelledQueries counts queries that raised TpuQueryCancelled
# (explicit cancel, drain, or a MID-FLIGHT deadline expiry);
# deadlineRejects counts queries rejected BEFORE execution because the
# deadline was already spent or the predicted work could not fit the
# remaining budget (zero device dispatches by construction); shedQueries
# counts queries the overload policy refused (bounded admission queue
# depth / max queue wait / draining server)
CANCELLED_QUERIES = "cancelledQueries"
DEADLINE_REJECTS = "deadlineRejects"
SHED_QUERIES = "shedQueries"
# cost-based placement (plan/placement.py, docs/placement.md):
# hostPlacedOps counts operators the placement analyzer moved host-side
# in the emitted plan; placementReplacements counts re-placements after
# the fact (an AQE re-place on measured stats, or a device failure
# re-placed onto the host instead of a whole-query CPU fallback)
HOST_PLACED_OPS = "hostPlacedOps"
PLACEMENT_REPLACEMENTS = "placementReplacements"
# self-healing execution (engine/scheduler.py speculation,
# engine/watchdog.py, memory/device_manager.py quarantine;
# docs/fault-tolerance.md): speculativeTasks counts straggler duplicates
# launched, speculativeWins the duplicates that finished first;
# watchdogKills counts in-flight dispatches the watchdog classified
# wedged (released for retry or escalated to a query kill); deviceResets
# counts device-loss events that quarantined a device
SPECULATIVE_TASKS = "speculativeTasks"
SPECULATIVE_WINS = "speculativeWins"
WATCHDOG_KILLS = "watchdogKills"
DEVICE_RESETS = "deviceResets"


class Metric:
    """A thread-safe accumulator (the SQLMetric analog)."""

    __slots__ = ("name", "_value", "_lock")

    def __init__(self, name: str):
        self.name = name
        self._value = 0
        self._lock = threading.Lock()

    def add(self, v) -> None:
        with self._lock:
            self._value += v

    def set_max(self, v) -> None:
        with self._lock:
            self._value = max(self._value, v)

    @property
    def value(self):
        return self._value


class MetricsMap:
    """Per-exec metric registry."""

    def __init__(self, *names: str):
        self._metrics: Dict[str, Metric] = {}
        for n in (NUM_OUTPUT_ROWS, NUM_OUTPUT_BATCHES, TOTAL_TIME,
                  PEAK_DEVICE_MEMORY) + names:
            self._metrics[n] = Metric(n)

    def __getitem__(self, name: str) -> Metric:
        if name not in self._metrics:
            self._metrics[name] = Metric(name)
        return self._metrics[name]

    def snapshot(self) -> Dict[str, int]:
        return {k: m.value for k, m in self._metrics.items()}


# ---------------------------------------------------------------------------
# Per-query / per-tenant accumulation context
# ---------------------------------------------------------------------------
# Before the serving runtime, per-query metrics were before/after snapshots
# of the process-wide counters — which cross-talk the moment two queries
# run concurrently. A QueryContext is installed by the session around each
# query (a contextvar, propagated onto scheduler worker threads and the
# prefetch reader by contextvars.copy_context), and every record_* helper
# accumulates into BOTH the global counter (bench/tools keep reading those)
# and the ambient query's context. The context also carries the per-tenant
# policy objects that used to be process singletons: the tenant's circuit
# breaker, the query's fault injector, the per-query retry budget, and the
# analyzer's semaphore admission weight.
_QUERY_CTX: "contextvars.ContextVar[Optional[QueryContext]]" = \
    contextvars.ContextVar("srt_query_ctx", default=None)


class QueryContext:
    """One running query's metric accumulator + per-tenant policy handles
    (docs/serving.md). Thread-safe: partition tasks on the worker pool add
    concurrently."""

    __slots__ = ("tenant", "_lock", "_counters", "breaker", "injector",
                 "fi_scoped", "retry_budget", "_retries_spent", "sem_weight",
                 "resource_report", "retry_policy", "aqe_notes",
                 "spill_plan_hint", "async_dispatch", "donation", "trace",
                 "cancel", "spill_buffers", "prefetchers", "kill_reason",
                 "placement_payload", "predicted_work_ns")

    def __init__(self, tenant: str = "default"):
        self.tenant = tenant
        self._lock = threading.Lock()
        self._counters: Dict[str, int] = {}
        # per-tenant circuit breaker (engine/retry.CircuitBreaker.get
        # consults this before the process default)
        self.breaker = None
        # per-query fault injector; fi_scoped=True means the injector slot
        # is authoritative for this query even when it is None (the query
        # ran with injection off while another tenant's is armed)
        self.injector = None
        self.fi_scoped = False
        # per-query task-retry budget (0 = unlimited); the scheduler's
        # _try_spend_retry charges here when a context is ambient, so
        # concurrent queries cannot drain each other's budget
        self.retry_budget = 0
        self._retries_spent = 0
        # semaphore permits one task of this query holds (the analyzer's
        # admission weight, read by TpuSemaphore.acquire_if_necessary)
        self.sem_weight = 1
        # THIS query's resource-analyzer report (set during planning —
        # including from a plan-cache hit); the admission controller reads
        # it here so concurrent queries on one session cannot read each
        # other's via the session attribute
        self.resource_report = None
        # per-query retry policy (engine/retry.set_policy_from_conf):
        # combinators read policy() through the ambient context, so one
        # tenant's backoff/retry tuning never leaks into another's
        # concurrently running query
        self.retry_policy = None
        # adaptive-execution notes (aqe/loop.py): applied-rule lines the
        # session surfaces as last_adaptive_report / EXPLAIN's
        # '== Adaptive execution ==' section
        self.aqe_notes = []
        # context-scoped spill plan reserve (memory/spill.py): resolved
        # reserve bytes for THIS query's predicted transients. None = no
        # hint posted yet (the watermark falls back to its process-wide
        # slot); an AQE re-plan posting a new hint lands here, so it can
        # never leak into a concurrent tenant's query
        self.spill_plan_hint = None
        # context-scoped issue-ahead flags (engine/async_exec.py): the
        # executing session's asyncDispatch/bufferDonation resolution for
        # THIS query. None = fall back to the process-wide flags
        self.async_dispatch = None
        self.donation = None
        # THIS query's span tracer (obs/trace.QueryTracer; None = tracing
        # off, the zero-cost default). Installed by the session when
        # rapids.tpu.obs.tracing.enabled; every record_* chokepoint
        # mirrors its increment onto the tracer's current span via _note,
        # so the timeline shows WHERE dispatches/retries/fences happened
        self.trace = None
        # THIS query's cancellation token (engine/cancel.CancelToken;
        # None outside session-driven queries). Installed by the session
        # at query start and polled at every engine chokepoint —
        # contextvars propagation carries it onto worker threads and the
        # prefetch reader exactly like the context itself.
        self.cancel = None
        # spill-store buffers registered on behalf of THIS query
        # (memory/spill.py add_* with scope_to_query): the reclamation
        # set a cancellation frees so a dead query's shuffle pieces and
        # staged batches cannot linger in the store
        self.spill_buffers = []
        # live PrefetchIterators decoding for THIS query (io/prefetch.py
        # registers them): cancellation closes them and joins their
        # reader threads (bounded) so no thread outlives the query
        self.prefetchers = []
        # terminal-status tag for the flight recorder (obs/history.py):
        # session._on_query_killed stamps "cancelled"/"deadline"/"shed"
        # so the persisted history record carries how the query ended
        self.kill_reason = None
        # THIS query's placement decision (plan/placement.py
        # PlacementReport.to_payload()): the flight recorder persists it
        # and scores placementRegret against the measured wall
        self.placement_payload = None
        # the admission-time cost-model prediction of THIS query's device
        # work in ns (0 = no prediction): the scheduler's straggler
        # speculation and the watchdog's calibrated timeout divide it by
        # the job's task count to price one task's expected wall
        self.predicted_work_ns = 0

    def add(self, name: str, n: int) -> None:
        with self._lock:
            self._counters[name] = self._counters.get(name, 0) + n

    def value(self, name: str) -> int:
        with self._lock:
            return self._counters.get(name, 0)

    def snapshot(self) -> Dict[str, int]:
        with self._lock:
            return dict(self._counters)

    # -- retry budget (engine/scheduler.py charges here) ---------------------
    def begin_retry_budget(self, budget: int) -> None:
        with self._lock:
            self.retry_budget = max(0, int(budget))
            self._retries_spent = 0

    def try_spend_retry(self) -> bool:
        with self._lock:
            if self.retry_budget and \
                    self._retries_spent >= self.retry_budget:
                return False
            self._retries_spent += 1
            return True

    @property
    def retries_spent(self) -> int:
        with self._lock:
            return self._retries_spent


def current_query_ctx() -> Optional[QueryContext]:
    return _QUERY_CTX.get()


def push_query_ctx(ctx: Optional[QueryContext]):
    """Install `ctx` as the ambient query context; returns the reset token
    for pop_query_ctx."""
    return _QUERY_CTX.set(ctx)


def pop_query_ctx(token) -> None:
    _QUERY_CTX.reset(token)


def _note(name: str, n: int) -> None:
    """Mirror a global-counter increment into the ambient query context —
    and, when the query is traced, onto the tracer's current span (one
    attribute check when tracing is off: the zero-cost contract of
    docs/observability.md)."""
    ctx = _QUERY_CTX.get()
    if ctx is not None:
        ctx.add(name, n)
        tr = ctx.trace
        if tr is not None:
            tr.add_count(name, n)


# ---------------------------------------------------------------------------
# Device-dispatch accounting
# ---------------------------------------------------------------------------
# Process-wide: partition tasks run on a shared worker pool, so per-exec
# counters would need threading context; queries ALSO accumulate into the
# ambient QueryContext (session.execute_batches ->
# session.last_query_metrics), which is what keeps concurrent tenants'
# numbers apart.
_DISPATCHES = Metric(DEVICE_DISPATCHES)

# measurement hook invoked after every record_dispatch (None = disabled).
# Used by TpuDeviceManager's live-bytes peak sampler: dispatches are the
# engine's natural "device state changed" cadence, so sampling here catches
# the high-water mark without instrumenting every allocation site.
_DISPATCH_HOOK = None


def set_dispatch_hook(fn) -> None:
    """Install (or clear, with None) the post-dispatch measurement hook.
    The hook runs on the dispatching thread with no arguments; keep it
    cheap — it fires on every device dispatch while installed."""
    global _DISPATCH_HOOK
    _DISPATCH_HOOK = fn


def record_dispatch(n: int = 1) -> None:
    """Count a device program launch (jitted kernel invocation). Called at
    the engine's kernel entry points — projector/filter/fused-stage/agg
    kernels and the batch gather/compact helpers — NOT per XLA executable
    internals; the unit is 'host->device dispatches the engine issued'."""
    _DISPATCHES.add(n)
    _note(DEVICE_DISPATCHES, n)
    hook = _DISPATCH_HOOK
    if hook is not None:
        hook()


def dispatch_count() -> int:
    return _DISPATCHES.value


# grouped-aggregate update batches by the path they took: the table over
# dictionary codes (exec/dense_agg.py) or the sort (exec/rowkeys.py). An
# ungrouped aggregate counts in neither: a batch whose partial was the one
# program with a one-row output (exec/aggregate.py
# `_build_ungrouped_update_kernel`) counts in ungroupedAggBatches, one that
# stayed on the sort path's keyless form (a STRING min/max, first/last)
# in none
DENSE_AGG_BATCHES = "denseAggBatches"
SORT_AGG_BATCHES = "sortAggBatches"
UNGROUPED_AGG_BATCHES = "ungroupedAggBatches"
_DENSE_AGG_BATCHES = Metric(DENSE_AGG_BATCHES)
_SORT_AGG_BATCHES = Metric(SORT_AGG_BATCHES)
_UNGROUPED_AGG_BATCHES = Metric(UNGROUPED_AGG_BATCHES)


def record_agg_batch(dense: bool) -> None:
    name, metric = (DENSE_AGG_BATCHES, _DENSE_AGG_BATCHES) if dense \
        else (SORT_AGG_BATCHES, _SORT_AGG_BATCHES)
    metric.add(1)
    _note(name, 1)


def record_ungrouped_agg_batch() -> None:
    _UNGROUPED_AGG_BATCHES.add(1)
    _note(UNGROUPED_AGG_BATCHES, 1)


def ungrouped_agg_batch_count() -> int:
    return _UNGROUPED_AGG_BATCHES.value


def dense_agg_batch_count() -> int:
    return _DENSE_AGG_BATCHES.value


def sort_agg_batch_count() -> int:
    return _SORT_AGG_BATCHES.value


# batches whose filter survivors were made dense on the device (the count
# and the move of columnar/batch.py `compact_span`): a filter folded
# into an aggregate's update program moves no row and counts nothing
COMPACTED_BATCHES = "compactedBatches"
_COMPACTED_BATCHES = Metric(COMPACTED_BATCHES)


def record_compacted_batch() -> None:
    _COMPACTED_BATCHES.add(1)
    _note(COMPACTED_BATCHES, 1)


def compacted_batch_count() -> int:
    return _COMPACTED_BATCHES.value


# the device-resident relation cache (exec/cache.py): batches a cached
# scan handed to its consumer, and of those the ones that had left the
# device (spilled to host or disk) and were uploaded again to be served.
# cacheResidentBytes is a gauge, not a count: the bytes of cached batches
# on the device at the moment it is read. cacheCoalescedPieces: batches as
# the cached plan handed them over that a materialisation concatenated
# into a resident batch of the target size (a piece kept as it came
# counts nothing)
CACHED_BATCHES_SERVED = "cachedBatchesServed"
CACHE_RESTORED_BATCHES = "cacheRestoredBatches"
CACHE_RESIDENT_BYTES = "cacheResidentBytes"
CACHE_COALESCED_PIECES = "cacheCoalescedPieces"
_CACHED_BATCHES_SERVED = Metric(CACHED_BATCHES_SERVED)
_CACHE_RESTORED_BATCHES = Metric(CACHE_RESTORED_BATCHES)


def record_cached_batch_served(restored: bool) -> None:
    _CACHED_BATCHES_SERVED.add(1)
    _note(CACHED_BATCHES_SERVED, 1)
    if restored:
        _CACHE_RESTORED_BATCHES.add(1)
        _note(CACHE_RESTORED_BATCHES, 1)


def record_cache_coalesced_pieces(pieces: int) -> None:
    # the materialising query's alone: nothing reads it process-wide
    _note(CACHE_COALESCED_PIECES, pieces)


def cached_batches_served_count() -> int:
    return _CACHED_BATCHES_SERVED.value


def cache_restored_batch_count() -> int:
    return _CACHE_RESTORED_BATCHES.value


def cache_resident_bytes() -> int:
    from spark_rapids_tpu.exec.cache import resident_bytes

    return resident_bytes()


# ---------------------------------------------------------------------------
# Fault-tolerance accounting (engine/retry.py increments; queries snapshot
# before/after, same pattern as the dispatch counter above)
# ---------------------------------------------------------------------------
_RETRIES = Metric(RETRIES)
_SPLIT_RETRIES = Metric(SPLIT_RETRIES)
_CPU_FALLBACKS = Metric(CPU_FALLBACK_EVENTS)
_FETCH_RETRIES = Metric(FETCH_RETRIES)
_FENCES = Metric(FENCES)
_CHECKED_REPLAYS = Metric(CHECKED_REPLAYS)
_DONATED_BYTES = Metric(DONATED_BYTES)
_SPMD_STAGES = Metric(SPMD_STAGES)
_COLLECTIVE_BYTES = Metric(COLLECTIVE_BYTES)


def record_retry(n: int = 1) -> None:
    """Count one device re-dispatch (OOM spill+retry or transient retry)."""
    _RETRIES.add(n)
    _note(RETRIES, n)


def record_split_retry(n: int = 1) -> None:
    """Count one batch bisection performed by split-and-retry."""
    _SPLIT_RETRIES.add(n)
    _note(SPLIT_RETRIES, n)


def record_cpu_fallback(n: int = 1) -> None:
    """Count one degradation to the CPU-oracle path (per batch or per
    query, whichever unit fell back)."""
    _CPU_FALLBACKS.add(n)
    _note(CPU_FALLBACK_EVENTS, n)


def record_fetch_retry(n: int = 1) -> None:
    """Count one shuffle-piece re-execution after a fetch failure."""
    _FETCH_RETRIES.add(n)
    _note(FETCH_RETRIES, n)


def retry_count() -> int:
    return _RETRIES.value


def split_retry_count() -> int:
    return _SPLIT_RETRIES.value


def cpu_fallback_count() -> int:
    return _CPU_FALLBACKS.value


def fetch_retry_count() -> int:
    return _FETCH_RETRIES.value


def record_fence(n: int = 1) -> None:
    """Count one device->host transfer event (a host fence). The engine's
    download chokepoints record here: with_retry(site='transfer.download')
    sink downloads and the shuffle's grouped piece encodes — NOT internal
    flush granularity, so the unit is 'download transfers the engine
    issued'."""
    _FENCES.add(n)
    _note(FENCES, n)


def fence_count() -> int:
    return _FENCES.value


def record_checked_replay(n: int = 1) -> None:
    """Count one whole-query checked-mode re-execution (a device error
    surfaced at the sink under async dispatch / donation; the session
    replays synchronously so the originating op's retry machinery can
    own it)."""
    _CHECKED_REPLAYS.add(n)
    _note(CHECKED_REPLAYS, n)


def checked_replay_count() -> int:
    return _CHECKED_REPLAYS.value


def record_donated_bytes(n: int) -> None:
    """Count input bytes donated into a consume-once kernel (the HBM the
    output reused instead of allocating fresh)."""
    _DONATED_BYTES.add(n)
    _note(DONATED_BYTES, n)


def donated_bytes() -> int:
    return _DONATED_BYTES.value


def record_spmd_stage(n: int = 1) -> None:
    """Count one stage pipeline executed as a single SPMD program over the
    mesh (operators AND exchange compiled into one dispatch)."""
    _SPMD_STAGES.add(n)
    _note(SPMD_STAGES, n)


def spmd_stage_count() -> int:
    return _SPMD_STAGES.value


def record_collective_bytes(n: int) -> None:
    """Count bytes moved by an in-program ICI collective (the all_to_all
    exchange epoch of an SPMD stage or the standalone ICI shuffle tier,
    and the sort-absorbing all_gather)."""
    _COLLECTIVE_BYTES.add(n)
    _note(COLLECTIVE_BYTES, n)


def collective_bytes() -> int:
    return _COLLECTIVE_BYTES.value


_SPMD_JOINS = Metric(SPMD_JOINS)
_SPMD_MEASURED_CAPS = Metric(SPMD_MEASURED_CAPS)


def record_spmd_join(n: int = 1) -> None:
    """Count one INNER equi-join lowered into an SPMD stage program (the
    build side broadcast in-program via lax.all_gather)."""
    _SPMD_JOINS.add(n)
    _note(SPMD_JOINS, n)


def spmd_join_count() -> int:
    return _SPMD_JOINS.value


def record_spmd_measured_cap(n: int = 1) -> None:
    """Count one SPMD stage segment whose capacities came from AQE's
    MEASURED MapOutputStats instead of the analyzer's interval."""
    _SPMD_MEASURED_CAPS.add(n)
    _note(SPMD_MEASURED_CAPS, n)


def spmd_measured_cap_count() -> int:
    return _SPMD_MEASURED_CAPS.value


# ---------------------------------------------------------------------------
# Serving-runtime accounting (plan cache / admission / micro-batching)
# ---------------------------------------------------------------------------
_PLAN_CACHE_HITS = Metric(PLAN_CACHE_HITS)
_PLAN_CACHE_MISSES = Metric(PLAN_CACHE_MISSES)
_ADMISSION_WAITS = Metric(ADMISSION_WAITS)
_ADMISSION_WAIT_NS = Metric(ADMISSION_WAIT_NS)
_MICRO_BATCHES = Metric(MICRO_BATCHES)
_MICRO_BATCHED_QUERIES = Metric(MICRO_BATCHED_QUERIES)


def record_plan_cache_hit(n: int = 1) -> None:
    """Count one signature-cache hit: the query reused a fully planned,
    verified, and analyzed physical plan — zero planning work (and, via
    the shared expression objects, zero retracing in the jit cache)."""
    _PLAN_CACHE_HITS.add(n)
    _note(PLAN_CACHE_HITS, n)


def plan_cache_hit_count() -> int:
    return _PLAN_CACHE_HITS.value


def record_plan_cache_miss(n: int = 1) -> None:
    """Count one signature-cache miss (the query planned from scratch and
    seeded the cache). Only cache-enabled, cacheable queries count."""
    _PLAN_CACHE_MISSES.add(n)
    _note(PLAN_CACHE_MISSES, n)


def plan_cache_miss_count() -> int:
    return _PLAN_CACHE_MISSES.value


def record_admission_wait(n: int = 1) -> None:
    """Count one query that blocked in analyzer-driven HBM admission
    (engine/admission.py) before it could start executing."""
    _ADMISSION_WAITS.add(n)
    _note(ADMISSION_WAITS, n)


def admission_wait_count() -> int:
    return _ADMISSION_WAITS.value


def record_admission_wait_ns(n: int) -> None:
    """Accumulate the DURATION one query spent blocked in analyzer-driven
    admission (ns; the admissionWaits event counter's missing half —
    engine/admission.py measures it with the obs wall clock)."""
    _ADMISSION_WAIT_NS.add(n)
    _note(ADMISSION_WAIT_NS, n)


def admission_wait_ns() -> int:
    return _ADMISSION_WAIT_NS.value


def record_micro_batch(n: int = 1) -> None:
    """Count one packed micro-batch window executed as a single query."""
    _MICRO_BATCHES.add(n)
    _note(MICRO_BATCHES, n)


def micro_batch_count() -> int:
    return _MICRO_BATCHES.value


def record_micro_batched_query(n: int = 1) -> None:
    """Count one individual query that rode in a packed micro-batch."""
    _MICRO_BATCHED_QUERIES.add(n)
    _note(MICRO_BATCHED_QUERIES, n)


def micro_batched_query_count() -> int:
    return _MICRO_BATCHED_QUERIES.value


# ---------------------------------------------------------------------------
# Encoded columnar execution accounting (columnar/encoded.py)
# ---------------------------------------------------------------------------
_ENCODED_COLUMNS = Metric(ENCODED_COLUMNS)
_LATE_MATERIALIZATIONS = Metric(LATE_MATERIALIZATIONS)
_ENCODED_BYTES_SAVED = Metric(ENCODED_BYTES_SAVED)


def record_encoded_column(n: int = 1) -> None:
    """Count one device column emitted ENCODED by the scan layer (codes in
    HBM + shared dictionary; one count per column per decoded chunk)."""
    _ENCODED_COLUMNS.add(n)
    _note(ENCODED_COLUMNS, n)


def encoded_column_count() -> int:
    return _ENCODED_COLUMNS.value


def record_late_materialization(n: int = 1) -> None:
    """Count one explicit decode of an encoded column back to values —
    the materialize() boundary path or the sink/serde host expansion. The
    compressed-execution contract is that this never happens silently
    (tpulint rule eager-materialize)."""
    _LATE_MATERIALIZATIONS.add(n)
    _note(LATE_MATERIALIZATIONS, n)


def late_materialization_count() -> int:
    return _LATE_MATERIALIZATIONS.value


def record_encoded_bytes_saved(n: int) -> None:
    """Accumulate HBM bytes the encoded representation avoided at scan
    emission: rows x (string per-row estimate - encoded per-row bytes),
    the deterministic formula the resource analyzer predicts an interval
    for (containment pinned by tests)."""
    _ENCODED_BYTES_SAVED.add(n)
    _note(ENCODED_BYTES_SAVED, n)


def encoded_bytes_saved() -> int:
    return _ENCODED_BYTES_SAVED.value


_ORDER_PRESERVING_SORTS = Metric(ORDER_PRESERVING_SORTS)
_RUN_COLLAPSED_ROWS = Metric(RUN_COLLAPSED_ROWS)


def record_order_preserving_sort(n: int = 1) -> None:
    """Count one batch whose sort / range-bound / window ordering ran
    over order-preserving rank codes instead of decoding the column."""
    _ORDER_PRESERVING_SORTS.add(n)
    _note(ORDER_PRESERVING_SORTS, n)


def order_preserving_sort_count() -> int:
    return _ORDER_PRESERVING_SORTS.value


def record_run_collapsed_rows(n: int) -> None:
    """Accumulate rows the run-granular aggregate path collapsed away
    (input rows minus merged runs, per collapsed update batch)."""
    _RUN_COLLAPSED_ROWS.add(n)
    _note(RUN_COLLAPSED_ROWS, n)


def run_collapsed_row_count() -> int:
    return _RUN_COLLAPSED_ROWS.value


# ---------------------------------------------------------------------------
# Adaptive-execution accounting (spark_rapids_tpu/aqe/)
# ---------------------------------------------------------------------------
_AQE_REPLANS = Metric(AQE_REPLANS)
_SKEW_SPLITS = Metric(SKEW_SPLITS)
_JOIN_DEMOTIONS = Metric(JOIN_DEMOTIONS)
_JOIN_PROMOTIONS = Metric(JOIN_PROMOTIONS)


_CANCELLED_QUERIES = Metric(CANCELLED_QUERIES)
_DEADLINE_REJECTS = Metric(DEADLINE_REJECTS)
_SHED_QUERIES = Metric(SHED_QUERIES)
_HOST_PLACED_OPS = Metric(HOST_PLACED_OPS)
_PLACEMENT_REPLACEMENTS = Metric(PLACEMENT_REPLACEMENTS)


def record_cancelled_query(n: int = 1) -> None:
    """Count one query that terminated with TpuQueryCancelled (explicit
    cancel, drain, or a mid-flight deadline expiry) — terminal by the
    engine/cancel.py contract: no retry, no fallback, no partial rows."""
    _CANCELLED_QUERIES.add(n)
    _note(CANCELLED_QUERIES, n)


def cancelled_query_count() -> int:
    return _CANCELLED_QUERIES.value


def record_deadline_reject(n: int = 1) -> None:
    """Count one query rejected BEFORE execution because its deadline was
    already spent or its predicted work could not fit the remaining
    budget (zero device dispatches)."""
    _DEADLINE_REJECTS.add(n)
    _note(DEADLINE_REJECTS, n)


def deadline_reject_count() -> int:
    return _DEADLINE_REJECTS.value


def record_shed_query(n: int = 1) -> None:
    """Count one query the overload policy shed (bounded admission queue
    depth, max queue wait, or a draining server) instead of admitting it
    to die waiting."""
    _SHED_QUERIES.add(n)
    _note(SHED_QUERIES, n)


def shed_query_count() -> int:
    return _SHED_QUERIES.value


def record_aqe_replan(n: int = 1) -> None:
    """Count one adaptive re-plan: a rule pass rewrote the not-yet-
    executed remainder and the rewrite passed static re-validation
    (verify + measured-stats resource analysis)."""
    _AQE_REPLANS.add(n)
    _note(AQE_REPLANS, n)


def aqe_replan_count() -> int:
    return _AQE_REPLANS.value


def record_host_placed_ops(n: int = 1) -> None:
    """Count operators the placement analyzer moved host-side in the
    plan this query actually executed."""
    _HOST_PLACED_OPS.add(n)
    _note(HOST_PLACED_OPS, n)


def host_placed_op_count() -> int:
    return _HOST_PLACED_OPS.value


def record_placement_replacement(n: int = 1) -> None:
    """Count one post-plan re-placement: AQE contradicting the static
    estimate with measured stats, or a device failure re-placed onto
    the host instead of degrading the whole query to CPU fallback."""
    _PLACEMENT_REPLACEMENTS.add(n)
    _note(PLACEMENT_REPLACEMENTS, n)


def placement_replacement_count() -> int:
    return _PLACEMENT_REPLACEMENTS.value


def record_skew_split(n: int = 1) -> None:
    """Count oversized reduce buckets split into piece-range
    sub-partitions by the skew-split rule."""
    _SKEW_SPLITS.add(n)
    _note(SKEW_SPLITS, n)


def skew_split_count() -> int:
    return _SKEW_SPLITS.value


def record_join_demotion(n: int = 1) -> None:
    """Count one runtime shuffled->broadcast join rewrite (measured build
    side fit under autoBroadcastJoinThreshold)."""
    _JOIN_DEMOTIONS.add(n)
    _note(JOIN_DEMOTIONS, n)


def join_demotion_count() -> int:
    return _JOIN_DEMOTIONS.value


def record_join_promotion(n: int = 1) -> None:
    """Count one runtime broadcast->shuffled join rewrite (a blown
    plan-time build-size estimate measured past the threshold)."""
    _JOIN_PROMOTIONS.add(n)
    _note(JOIN_PROMOTIONS, n)


def join_promotion_count() -> int:
    return _JOIN_PROMOTIONS.value


# ---------------------------------------------------------------------------
# Self-healing accounting (engine/scheduler.py speculation,
# engine/watchdog.py, memory/device_manager.py quarantine)
# ---------------------------------------------------------------------------
_SPECULATIVE_TASKS = Metric(SPECULATIVE_TASKS)
_SPECULATIVE_WINS = Metric(SPECULATIVE_WINS)
_WATCHDOG_KILLS = Metric(WATCHDOG_KILLS)
_DEVICE_RESETS = Metric(DEVICE_RESETS)


def record_speculative_task(n: int = 1) -> None:
    """Count one speculative duplicate launched for a straggling task
    (an idempotent re-execution from source, never shared buffers)."""
    _SPECULATIVE_TASKS.add(n)
    _note(SPECULATIVE_TASKS, n)


def speculative_task_count() -> int:
    return _SPECULATIVE_TASKS.value


def record_speculative_win(n: int = 1) -> None:
    """Count one speculative duplicate that finished before its original
    (the original was cancelled through its task-scoped token)."""
    _SPECULATIVE_WINS.add(n)
    _note(SPECULATIVE_WINS, n)


def speculative_win_count() -> int:
    return _SPECULATIVE_WINS.value


def record_watchdog_kill(n: int = 1) -> None:
    """Count one in-flight dispatch the watchdog classified wedged:
    released to raise a retryable TpuDispatchWedged, or — past the
    escalation grace — killed through the owning query's token."""
    _WATCHDOG_KILLS.add(n)
    _note(WATCHDOG_KILLS, n)


def watchdog_kill_count() -> int:
    return _WATCHDOG_KILLS.value


def record_device_reset(n: int = 1) -> None:
    """Count one device-loss event (unavailable/reset family): the
    device quarantined and the session entered recovery."""
    _DEVICE_RESETS.add(n)
    _note(DEVICE_RESETS, n)


def device_reset_count() -> int:
    return _DEVICE_RESETS.value


@contextlib.contextmanager
def trace_range(name: str, metric: Optional[Metric] = None):
    """NvtxWithMetrics analog: XProf trace annotation + elapsed-ns metric.

    THE operator-span chokepoint: every kernel/transfer site already
    wraps its device work in trace_range, so when the ambient query is
    traced (obs/trace.py) the same call opens an operator span — the
    span tree gets per-operator timing with no new instrumentation
    sites. Host clock only; no device syncs."""
    ctx = _QUERY_CTX.get()
    tr = ctx.trace if ctx is not None else None
    handle = tr.open_span(name, "op") if tr is not None else None
    start = time.perf_counter_ns()
    if _TraceAnnotation is not None:
        cm = _TraceAnnotation(name)
    else:  # pragma: no cover
        cm = contextlib.nullcontext()
    with cm:
        try:
            yield
        finally:
            if metric is not None:
                metric.add(time.perf_counter_ns() - start)
            if handle is not None:
                tr.close_span(handle)
