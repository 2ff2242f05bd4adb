"""TpuSession: the SparkSession-with-plugin analog.

Bundles what the reference splits across SparkSession + SQLPlugin
(Plugin.scala): conf handling, executor bring-up (device manager + admission
semaphore + scheduler, reference RapidsExecutorPlugin.init Plugin.scala:114-142),
the plan pipeline (planner -> TpuOverrides -> TpuTransitionOverrides, reference
ColumnarOverrideRules Plugin.scala:36-54), and actions (collect/write).

Plan capture for tests mirrors ExecutionPlanCaptureCallback
(Plugin.scala:144-233).
"""

from __future__ import annotations

import contextlib
import logging
import threading
from typing import Any, Dict, Iterator, List, Optional, Sequence

import numpy as np

from spark_rapids_tpu import conf as C
from spark_rapids_tpu.columnar.batch import HostColumnarBatch, HostColumnVector
from spark_rapids_tpu.columnar.dtypes import DataType, from_np
from spark_rapids_tpu.engine.scheduler import TaskScheduler
from spark_rapids_tpu.exec.base import ExecContext, PhysicalExec
from spark_rapids_tpu.memory.device_manager import TpuDeviceManager
from spark_rapids_tpu.memory.semaphore import TpuSemaphore
from spark_rapids_tpu.memory.spill import SpillFramework
from spark_rapids_tpu.ops.base import AttributeReference
from spark_rapids_tpu.plan import logical as L
from spark_rapids_tpu.plan.dataframe import DataFrame
from spark_rapids_tpu.plan.overrides import TpuOverrides
from spark_rapids_tpu.plan.planner import plan_physical
from spark_rapids_tpu.plan.transition_overrides import TpuTransitionOverrides

log = logging.getLogger(__name__)

# ---------------------------------------------------------------------------
# Shared-runtime lifetime (docs/serving.md): N concurrent sessions share
# ONE device manager, admission semaphore, spill framework, admission
# controller, ICI mesh, jit cache, and plan cache. The shared pieces tear
# down only when the LAST live session stops — before this, a second
# session's stop() yanked the mesh and device manager out from under any
# session still running. Liveness is a WeakSet, not a refcount: a session
# that was never stopped and is no longer referenced (a test fixture
# without a finalizer) must not block teardown forever — once collected
# it simply stops counting.
# ---------------------------------------------------------------------------
import weakref

_RUNTIME_LOCK = threading.Lock()
_LIVE_SESSIONS: "weakref.WeakSet" = weakref.WeakSet()


class _QueryRun:
    """What TpuSession._query_scope hands its body: the tenant's breaker,
    and the slot for the physical plan it ran (None until planning
    succeeded), which the scope's accounting reads on exit."""

    __slots__ = ("breaker", "physical")

    def __init__(self, breaker):
        self.breaker = breaker
        self.physical: Optional[PhysicalExec] = None


class PlanCapture:
    """Test hook capturing the final physical plan of each execution
    (reference: ExecutionPlanCaptureCallback, Plugin.scala:144-233).

    Each capture also snapshots every node's metrics AT RECORD TIME
    (before execution): plan-cache-reused physical plans accumulate
    metrics across queries, so EXPLAIN ANALYZE (obs/analyze.py) diffs
    against this snapshot to report THIS execution only."""

    def __init__(self):
        self._lock = threading.Lock()
        self._plans: List[PhysicalExec] = []
        self._pre: List[dict] = []
        self.enabled = False

    def start(self):
        with self._lock:
            self._plans.clear()
            self._pre.clear()
            self.enabled = True

    def stop(self) -> List[PhysicalExec]:
        with self._lock:
            self.enabled = False
            return list(self._plans)

    def pre_metrics(self) -> List[dict]:
        """Per captured plan: {id(node): metrics snapshot} taken when the
        plan was recorded (parallel to stop()'s list)."""
        with self._lock:
            return list(self._pre)

    def record(self, plan: PhysicalExec):
        if self.enabled:
            pre = {}
            plan.foreach(lambda n: pre.__setitem__(id(n),
                                                   n.metrics.snapshot()))
            with self._lock:
                self._plans.append(plan)
                self._pre.append(pre)


class TpuSession:
    _active: Optional["TpuSession"] = None
    _lock = threading.Lock()

    def __init__(self, settings: Optional[Dict[str, Any]] = None,
                 tenant: str = "default"):
        self.conf = C.TpuConf(settings)
        # tenant name for the serving runtime (docs/serving.md): keys the
        # per-tenant circuit breaker, metric attribution, and admission
        # accounting. Single-session flows keep the "default" tenant.
        self.tenant = tenant
        self.plan_capture = PlanCapture()
        # fusion accounting of the most recent execute_batches (fusedStages,
        # deviceDispatches) — read by bench.py and the fusion tests. Under
        # concurrent queries this is last-completed-query-wins; per-query
        # numbers ride the QueryContext (utils/metrics.py)
        self.last_query_metrics: Dict[str, int] = {}
        # static-analysis findings of the most recent plan build: the plan
        # verifier's and the resource analyzer's violations share this one
        # record path (plan/verify.PlanViolation carries the kind tag)
        self.last_plan_violations: List[str] = []
        # the resource analyzer's full report for the most recent plan
        # build (None while resourceAnalysis is disabled)
        self.last_resource_report = None
        # the placement analyzer's report for the most recent plan build
        # (plan/placement.py; None while placement is disabled)
        self.last_placement_report = None
        # failure re-placement pin (set transiently by
        # _degrade_device_failure): operator classes the NEXT plan build
        # must price at device=INF so the faulting subtree lands host-side
        self._placement_pin = None
        # applied-rule notes from the most recent ADAPTIVE execution
        # (aqe/loop.py via the QueryContext); rendered by EXPLAIN's
        # '== Adaptive execution ==' section. Empty when adaptive is off
        # or no rule fired.
        self.last_adaptive_report: List[str] = []
        # the finished span tree of the most recent TRACED query
        # (obs/trace.QueryTrace; None while rapids.tpu.obs.tracing.enabled
        # is off). Under concurrent queries: last-completed-wins per
        # session, same contract as last_query_metrics.
        self.last_query_trace = None
        # lifetime per-tenant accounting for the serving telemetry
        # endpoint (TpuServer.metrics_snapshot): every query's
        # QueryContext counters merge here at completion, plus a query
        # count — one merge per query, not per increment
        self.tenant_metric_totals: Dict[str, int] = {}
        self.queries_run = 0
        self._totals_lock = threading.Lock()
        # wired by TpuServer.connect: queries eligible for cross-query
        # micro-batching route through the server's shared batcher
        self.micro_batcher = None
        # in-flight query registry (docs/fault-tolerance.md): every
        # running query's CancelToken, so cancel_all()/drain/stop can
        # reach queries mid-flight. _draining sheds NEW queries with
        # TpuOverloadedError while in-flight ones finish or cancel.
        self._inflight: set = set()
        self._inflight_lock = threading.Lock()
        self._draining = False
        self._stopped = False
        # planning mutates/reads session conf (the CPU-fallback run swaps
        # sql.enabled); an RLock keeps a concurrent query's signature and
        # plan build consistent with each other
        self._plan_lock = threading.RLock()
        # multi-host bring-up FIRST — the coordination service must join
        # before any backend touch (reference: driver ships conf and
        # executors announce themselves before GPU init, Plugin.scala:
        # 103-142). Env-driven; single-process is a no-op.
        from spark_rapids_tpu.parallel import distributed as _dist

        _dist.init_distributed()
        from spark_rapids_tpu.engine.admission import AdmissionController

        with _RUNTIME_LOCK:
            shared_live = len(_LIVE_SESSIONS) > 0
            # executor bring-up (reference: RapidsExecutorPlugin.init)
            self.device_manager = TpuDeviceManager.initialize(self.conf)
            # spill store chain + watermark (reference:
            # GpuShuffleEnv.initStorage, GpuShuffleEnv.scala:57-79).
            # Budget honors this session's conf when it is the FIRST live
            # session; later concurrent sessions share the live framework
            # (one device, one watermark).
            hbm_total = self.conf.get(C.HBM_SIZE_OVERRIDE) or \
                self.device_manager.hbm_total
            budget = int(hbm_total * self.conf.get(C.MEMORY_FRACTION))
            fw = SpillFramework.get()
            if not (shared_live and fw is not None):
                fw = SpillFramework.initialize(
                    self.conf, budget, self.device_manager.bytes_in_use)
            self.spill = fw
            TpuSemaphore.initialize(self.conf.concurrent_tpu_tasks)
            ctl = AdmissionController.initialize(
                budget, self.conf.get(C.ADMISSION_MAX_BYPASS))
            # overload-shedding bounds (engine/admission.py): one device,
            # one policy — the newest session's conf wins
            ctl.set_overload_policy(
                self.conf.get(C.ADMISSION_MAX_QUEUE_DEPTH),
                self.conf.get(C.ADMISSION_MAX_QUEUE_WAIT_MS))
            _LIVE_SESSIONS.add(self)
        self.scheduler = TaskScheduler(self.conf.task_threads)
        self.conf.sync_int64_narrowing()
        with TpuSession._lock:
            TpuSession._active = self

    # -- builder-style API ----------------------------------------------------
    @staticmethod
    def builder() -> "SessionBuilder":
        return SessionBuilder()

    @classmethod
    def active(cls) -> "TpuSession":
        with cls._lock:
            if cls._active is None:
                cls._active = TpuSession()
            return cls._active

    # -- cancellation / drain (engine/cancel.py, docs/fault-tolerance.md) ----
    def cancel_all(self, reason: str = "cancelled") -> int:
        """Fire every in-flight query's CancelToken; returns how many
        tokens this call fired first. The queries raise TpuQueryCancelled
        at their next chokepoint poll and release everything they hold."""
        with self._inflight_lock:
            tokens = list(self._inflight)
        return sum(1 for t in tokens if t.cancel(reason))

    def inflight_count(self) -> int:
        with self._inflight_lock:
            return len(self._inflight)

    def begin_drain(self) -> None:
        """Stop admitting: new queries on this session shed immediately
        with TpuOverloadedError; in-flight ones are untouched (cancel or
        await them per drain policy — TpuServer.drain / stop)."""
        self._draining = True

    def _await_quiesce(self, timeout_s: float) -> bool:
        """Wait (bounded) until no query is in flight; True = quiesced."""
        from spark_rapids_tpu.obs.trace import wall_ns

        end = wall_ns() + int(max(0.0, timeout_s) * 1e9)
        poll = threading.Event()
        while self.inflight_count() > 0:
            if wall_ns() >= end:
                return False
            poll.wait(0.02)
        return True

    def _drain_for_stop(self) -> None:
        """stop() with queries in flight drains FIRST (the PR's satellite
        bugfix): cancel everything running, then wait (bounded by
        drain.timeoutMs) for the queries to unwind through their own
        finallys — so teardown never yanks the runtime out from under a
        live query, and no semaphore permits or admission bytes leak."""
        self.begin_drain()
        if self.inflight_count() == 0:
            return
        self.cancel_all("session stopped")
        if not self._await_quiesce(
                self.conf.get(C.DRAIN_TIMEOUT_MS) / 1000.0):
            log.warning("session.stop: %d queries still in flight after "
                        "the drain timeout; tearing down anyway",
                        self.inflight_count())

    def stop(self, _sweep_leaked: bool = True):
        from spark_rapids_tpu.engine.retry import CircuitBreaker
        from spark_rapids_tpu.utils import faultinject as FI

        self._drain_for_stop()
        with _RUNTIME_LOCK:
            if self._stopped:
                # idempotent: a double stop() must not re-run teardown (it
                # would tear the shared device manager/mesh out from under
                # a concurrent session)
                return
            self._stopped = True
            _LIVE_SESSIONS.discard(self)
            maybe_last = len(_LIVE_SESSIONS) == 0
        # always per-session: this session's worker pool, this TENANT's
        # breaker state (another tenant's failure history is not ours to
        # reset), and the process-global fault-injection slot — armed
        # injection must not outlive the session that armed it (running
        # queries are unaffected: theirs is context-scoped)
        self.scheduler.shutdown()
        CircuitBreaker.reset(tenant=self.tenant)
        FI.disable_global()
        if not maybe_last and _sweep_leaked:
            # a session that was never stopped but is no longer referenced
            # anywhere (a leaked test fixture) may linger in cyclic
            # garbage; one sweep keeps it from blocking teardown forever.
            # TpuServer.stop() suppresses the sweep for all but its final
            # session — a batch shutdown needs at most one.
            import gc

            gc.collect()
        # teardown decision AND teardown are one atomic step under
        # _RUNTIME_LOCK: a concurrent TpuSession.__init__ (same lock)
        # either adopts the still-live runtime BEFORE this block — then
        # the live-set is non-empty and nothing is torn down — or builds
        # a fresh runtime after it
        with _RUNTIME_LOCK:
            if len(_LIVE_SESSIONS) > 0:
                with TpuSession._lock:
                    if TpuSession._active is self:
                        TpuSession._active = None
                return
            self._teardown_shared_runtime()
        with TpuSession._lock:
            if TpuSession._active is self:
                TpuSession._active = None

    @staticmethod
    def _teardown_shared_runtime() -> None:
        """Tear down everything the live sessions shared (caller holds
        _RUNTIME_LOCK and has verified no live session remains)."""
        from spark_rapids_tpu.engine.admission import AdmissionController
        from spark_rapids_tpu.engine.retry import CircuitBreaker
        from spark_rapids_tpu.utils import faultinject as FI

        TpuSemaphore.shutdown()
        SpillFramework.shutdown()
        AdmissionController.shutdown()
        # fault-tolerance state must not leak into the next session in
        # the process (full reset: default + every tenant)
        CircuitBreaker.reset()
        FI.disable_global()
        # the hung-dispatch watchdog daemon dies with the shared runtime
        # (its in-flight registry is meaningless across sessions)
        from spark_rapids_tpu.engine import pause_clock
        from spark_rapids_tpu.engine.watchdog import DispatchWatchdog

        DispatchWatchdog.shutdown()
        # ... and with it the heartbeat both it and speculation read
        pause_clock.shutdown()
        # symmetric with the semaphore/spill singletons: a later session
        # must size its budget from ITS conf — without this, a test
        # session's hbm.sizeOverride leaks into every session that
        # follows in the process
        TpuDeviceManager.shutdown()
        # the plan cache holds physical plans and resource reports sized
        # against the runtime that just died
        from spark_rapids_tpu.plan import plan_cache as _pc

        _pc.clear()
        # same leak class for the collective meshes (shuffle/ici.py): a
        # test session's mesh must not pin its device set (and cached
        # shard_map programs keyed on it) into later sessions
        from spark_rapids_tpu.shuffle import ici as _ici

        _ici.reset_mesh()
        # flight recorder + calibrated cost model (obs/): the history
        # writer thread and the fitted model are shared-runtime state —
        # a later session must not inherit a prior test's coefficients
        from spark_rapids_tpu.obs import calibrate as _cal
        from spark_rapids_tpu.obs import history as _oh

        _oh.shutdown()
        _cal.reset()

    def set_conf(self, key: str, value: Any) -> None:
        self.conf.set(key, value)

    # -- data sources ---------------------------------------------------------
    def createDataFrame(self, data, schema=None,
                        num_partitions: int = 1) -> DataFrame:
        """data: list of tuples + schema [(name, DataType)], or dict of
        name->list with schema optional, or pandas DataFrame."""
        attrs, batch = _to_host_batch(data, schema)
        parts = _split_batch(batch, num_partitions)
        return DataFrame(L.LocalRelation(attrs, parts), self)

    def range(self, start: int, end: Optional[int] = None, step: int = 1,
              num_partitions: Optional[int] = None) -> DataFrame:
        if end is None:
            start, end = 0, start
        n = num_partitions or self.conf.shuffle_partitions
        return DataFrame(L.RangeRelation(start, end, step, n), self)

    @property
    def read(self) -> "DataFrameReader":
        from spark_rapids_tpu.io.reader import DataFrameReader

        return DataFrameReader(self)

    # -- plan pipeline --------------------------------------------------------
    def _optimized(self, plan: L.LogicalPlan) -> L.LogicalPlan:
        from spark_rapids_tpu.plan.optimizer import optimize

        return optimize(plan, self.conf)

    def _physical_plan(self, plan: L.LogicalPlan,
                       use_cache: bool = True) -> PhysicalExec:
        """Build (or fetch from the plan cache) the final physical plan.

        Serving hot path (docs/serving.md): with the plan cache on, a
        signature hit returns a previously planned, VERIFIED, and
        ANALYZED physical plan — zero planning work — and re-applies the
        cached resource report's admission hints. A checked replay never
        uses the cache (SPMD lowering differs in checked mode)."""
        with self._plan_lock:
            return self._physical_plan_locked(plan, use_cache)

    def _physical_plan_locked(self, plan: L.LogicalPlan,
                              use_cache: bool) -> PhysicalExec:
        from spark_rapids_tpu.engine import async_exec as AX
        from spark_rapids_tpu.plan import plan_cache as PC
        from spark_rapids_tpu.plan.fusion import fuse_stages
        from spark_rapids_tpu.plan.spmd import lower_spmd_stages
        from spark_rapids_tpu.utils import metrics as M

        cache_key = None
        if use_cache and self.conf.get(C.PLAN_CACHE_ENABLED) and \
                not AX.in_checked_mode():
            from spark_rapids_tpu.plan.signature import plan_signature

            sig = plan_signature(plan, self.conf)
            if sig is not None:
                cache_key = sig.cache_key
                entry = PC.lookup(cache_key)
                if entry is not None:
                    M.record_plan_cache_hit()
                    self.last_plan_violations = list(entry.violations)
                    self.last_resource_report = entry.report
                    self.last_placement_report = entry.placement
                    if entry.report is not None:
                        self._apply_resource_hints(entry.report)
                    else:
                        self._reset_resource_hints()
                    self.plan_capture.record(entry.physical)
                    return entry.physical

        cpu_plan = plan_physical(self._optimized(plan), self.conf)
        tpu_plan = TpuOverrides.apply(cpu_plan, self.conf)
        final = TpuTransitionOverrides.apply(tpu_plan, self.conf)
        final = fuse_stages(final, self.conf)
        # single-program SPMD stage lowering (plan/spmd.py) — the wrapped
        # subtree is exactly what the host-loop executor would run, so
        # eligibility fallback is always one children[0].execute() away
        final = lower_spmd_stages(final, self.conf)
        # cost-based placement (plan/placement.py): price every operator
        # device-vs-host and realize the cheaper mixed plan. Runs BEFORE
        # the verifier/analyzer below so the emitted plan is the one
        # that gets verified and admission-priced; best-effort — a
        # pricing bug keeps the all-device plan, never aborts the query
        self.last_placement_report = None
        if self.conf.get(C.PLACEMENT_ENABLED):
            from spark_rapids_tpu.plan.placement import place_plan

            try:
                final, placement = place_plan(
                    final, self.conf,
                    device_manager=self.device_manager,
                    pin_host_classes=self._placement_pin)
                self.last_placement_report = placement
            except Exception:  # noqa: BLE001 - placement is best-effort
                log.warning("placement analysis failed; keeping the "
                            "all-device plan", exc_info=True)
        # LAST: adaptive-execution wrapper (spark_rapids_tpu/aqe/) below
        # the root sink; a no-op unless rapids.tpu.sql.adaptive.enabled
        # and the plan has a stage boundary to re-optimize across. The
        # plan-cache key notes the adaptive flag (plan/signature.py), so
        # cached static plans and AQE plans never cross.
        from spark_rapids_tpu.aqe.loop import maybe_wrap_adaptive

        final = maybe_wrap_adaptive(final, self.conf)
        if self.conf.get(C.PLAN_VERIFY):
            from spark_rapids_tpu.plan.verify import (
                PlanVerificationError,
                check_plan,
            )

            # static plan verification (raises per failOnViolation);
            # violations kept for EXPLAIN/test introspection — recorded
            # even when the check raises, so a caller that catches the
            # error still reads THIS plan's violations, not the last one's
            try:
                self.last_plan_violations = check_plan(final, self.conf)
            except PlanVerificationError as e:
                self.last_plan_violations = list(e.violations)
                raise
        else:
            # verifier skipped: clear rather than carry a previous
            # query's violations into this plan's introspection
            self.last_plan_violations = []
        if self.conf.get(C.RESOURCE_ANALYSIS):
            from spark_rapids_tpu.plan.resources import (
                ResourceAnalysisError,
                check_resources,
            )

            # plan-time resource admission (raises per failOnViolation);
            # the report and its violations are recorded even when the
            # check raises — same contract as the plan verifier above
            try:
                report = check_resources(final, self.conf,
                                         device_manager=self.device_manager)
            except ResourceAnalysisError as e:
                self.last_resource_report = e.report
                self.last_plan_violations = (
                    list(self.last_plan_violations)
                    + list(e.report.violations))
                raise
            except Exception:  # noqa: BLE001 - estimator is best-effort
                # an internal estimator bug must not abort the query: the
                # analyzer only OBSERVES unless a real violation trips
                # failOnViolation — run without a report or hints
                log.warning("resource analysis failed; running without "
                            "admission hints", exc_info=True)
                self.last_resource_report = None
            else:
                self.last_resource_report = report
                if report.violations:
                    self.last_plan_violations = (
                        list(self.last_plan_violations)
                        + list(report.violations))
                self._apply_resource_hints(report)
        else:
            self.last_resource_report = None
            # a previous query's admission weight / spill reserve must not
            # outlive the analysis that produced it
            self._reset_resource_hints()
        if cache_key is not None:
            # seed the cache with the fully built (and verified/analyzed
            # — a raise above never reaches here) plan. insert() keeps
            # the FIRST entry on a concurrent-build race
            M.record_plan_cache_miss()
            entry = PC.insert(
                cache_key,
                PC.CachedPlan(final, self.last_resource_report,
                              self.last_plan_violations, plan,
                              self.last_placement_report),
                self.conf.get(C.PLAN_CACHE_MAX_ENTRIES))
            final = entry.physical
        self.plan_capture.record(final)
        return final

    def _apply_resource_hints(self, report) -> None:
        """Forward the static analysis to the runtime admission paths: the
        semaphore learns how many permits one task of this query should
        hold (heavy plans admit fewer concurrent tasks), and the spill
        framework learns how much transient headroom the plan is predicted
        to need (docs/static-analysis.md). The weight and report also land
        on the ambient QueryContext so concurrent queries keep their own
        (memory/semaphore.py, engine/admission.py)."""
        from spark_rapids_tpu.utils import metrics as M

        sem = TpuSemaphore.get()
        weight = report.admission_weight(sem.max_concurrent)
        sem.set_query_weight(weight)
        qctx = M.current_query_ctx()
        if qctx is not None:
            qctx.sem_weight = weight
            qctx.resource_report = report
        fw = SpillFramework.get()
        if fw is not None:
            fw.set_plan_hint(report.spill_pressure,
                             report.per_task_peak_bytes, ctx=qctx)

    def _reset_resource_hints(self) -> None:
        """No analysis for this plan: nothing may inherit a previous
        query's admission weight or spill reserve."""
        from spark_rapids_tpu.utils import metrics as M

        TpuSemaphore.get().set_query_weight(1)
        qctx = M.current_query_ctx()
        if qctx is not None:
            qctx.sem_weight = 1
            qctx.resource_report = None
        fw = SpillFramework.get()
        if fw is not None:
            fw.set_plan_hint(0.0, None, ctx=qctx)

    def explain_plan(self, plan: L.LogicalPlan, mode: str = "ALL") -> str:
        from spark_rapids_tpu.plan.fusion import fuse_stages
        from spark_rapids_tpu.plan.meta import explain_string
        from spark_rapids_tpu.plan.spmd import lower_spmd_stages

        cpu_plan = plan_physical(self._optimized(plan), self.conf)
        explain_out: List[str] = []
        tpu_plan = TpuOverrides.apply(
            cpu_plan, self.conf.clone_with({"rapids.tpu.sql.explain": "NONE"}),
            explain_out=explain_out)
        final = TpuTransitionOverrides.apply(tpu_plan, self.conf)
        final = fuse_stages(final, self.conf)
        final = lower_spmd_stages(final, self.conf)
        placement_report = None
        if self.conf.get(C.PLACEMENT_ENABLED):
            from spark_rapids_tpu.plan.placement import place_plan

            try:
                final, placement_report = place_plan(
                    final, self.conf, device_manager=self.device_manager)
            except Exception:  # noqa: BLE001 - placement is best-effort
                log.warning("placement analysis failed in EXPLAIN",
                            exc_info=True)
        from spark_rapids_tpu.aqe.loop import maybe_wrap_adaptive

        final = maybe_wrap_adaptive(final, self.conf)
        parts = []
        if explain_out:
            parts.append("== TPU tagging ==\n" + explain_out[0])
        parts.append("== Final plan ==\n" + explain_string(final))
        # static-analysis sections render in a FIXED order after the plan
        # tree: verification, then resources (tests/test_plan_resources.py
        # pins the golden layout), then placement (only when enabled)
        if self.conf.get(C.PLAN_VERIFY):
            from spark_rapids_tpu.plan.verify import verify_plan

            violations = verify_plan(final)
            parts.append("== Plan verification ==\n" + (
                "OK" if not violations
                else "\n".join(f"! {v}" for v in violations)))
        if self.conf.get(C.RESOURCE_ANALYSIS):
            from spark_rapids_tpu.plan.resources import analyze_plan

            report = analyze_plan(final, self.conf,
                                  device_manager=self.device_manager)
            parts.append("== Resource analysis ==\n" + report.render())
        if placement_report is not None:
            parts.append("== Placement ==\n" + placement_report.render())
        if self.conf.get(C.ADAPTIVE_ENABLED):
            from spark_rapids_tpu.aqe.rules import rule_catalog

            lines = ["enabled (runtime re-optimization at stage "
                     "boundaries; docs/adaptive-execution.md)"]
            lines += [f"rule: {r}" for r in rule_catalog()]
            if self.last_adaptive_report:
                lines.append("last execution applied:")
                lines += [f"  + {n}" for n in self.last_adaptive_report]
            else:
                lines.append("last execution applied: (none)")
            parts.append("== Adaptive execution ==\n" + "\n".join(lines))
        return "\n".join(parts)

    def explain_analyze(self, plan: L.LogicalPlan) -> str:
        """EXPLAIN ANALYZE (docs/observability.md): EXECUTE the query with
        tracing forced on, then render the physical plan with measured
        per-operator rows/batches/wall-time beside the resource
        analyzer's predictions, plus the measured-vs-predicted dispatch
        and fence totals. Also leaves session.last_query_trace populated
        for a Perfetto export of the analyzed run."""
        from spark_rapids_tpu.obs.analyze import explain_analyze as _ea

        return _ea(self, plan)

    def _exec_context(self) -> ExecContext:
        return ExecContext(self.conf, self.scheduler, self.device_manager)

    # -- actions --------------------------------------------------------------
    def execute_batches(self, plan: L.LogicalPlan,
                        timeout_s: Optional[float] = None
                        ) -> List[HostColumnarBatch]:
        results = self.execute_partitions(plan, timeout_s=timeout_s)
        return [b for part in results for b in part]

    def execute_partitions(self, plan: L.LogicalPlan,
                           allow_micro_batch: bool = True,
                           use_plan_cache: bool = True,
                           force_tracing: bool = False,
                           timeout_s: Optional[float] = None):
        """Run one query; returns per-partition lists of host batches (in
        partition order). The serving entry point: enters the query scope
        (_query_scope: QueryContext, tracer, accounting), routes eligible
        queries through the server's micro-batcher, and otherwise runs
        the device/degradation pipeline. `timeout_s` overrides
        rapids.tpu.engine.deadlineMs for this call
        (df.collect(timeout=...))."""
        from spark_rapids_tpu.engine import cancel as CX
        from spark_rapids_tpu.engine import retry as R
        from spark_rapids_tpu.utils import faultinject as FI
        from spark_rapids_tpu.utils import metrics as M

        if self._draining:
            # drain/stop sheds NEW work up front: nothing was planned,
            # nothing was admitted, nothing to reclaim. No QueryContext
            # exists yet, so the tenant's lifetime total is bumped here
            # directly — the per-tenant shed counters must see drain-time
            # sheds too (docs/fault-tolerance.md)
            M.record_shed_query()
            with self._totals_lock:
                self.tenant_metric_totals[M.SHED_QUERIES] = \
                    self.tenant_metric_totals.get(M.SHED_QUERIES, 0) + 1
            err = CX.TpuOverloadedError(
                f"session for tenant {self.tenant!r} is draining; "
                "query refused")
            err.counted = True
            raise err

        with self._query_scope(plan, timeout_s=timeout_s,
                               force_tracing=force_tracing) as run:
            breaker = run.breaker
            routed = self._maybe_micro_batch(plan, breaker,
                                             allow_micro_batch)
            if routed is not None:
                return routed
            cpu_fallback_ok = self.conf.get(C.CPU_FALLBACK_ENABLED)
            if breaker.is_open() and cpu_fallback_ok:
                # the tenant's device path is unhealthy: remaining queries
                # plan straight on the CPU engine instead of burning
                # retries. Like the device-failure fallback below, this
                # run is the backstop: injected faults must not chase it
                M.record_cpu_fallback()
                FI.disable()
                run.physical, results = self._execute_on_cpu(
                    plan, use_plan_cache)
            else:
                # half-open recovery (engine/retry.CircuitBreaker): a
                # tripped breaker past its cooldown lets probe queries
                # through — charge the slot so a silent wedge cannot hold
                # the half-open window open forever
                if breaker.state() == "half_open":
                    breaker.note_probe()
                try:
                    run.physical, results = self._execute_device(
                        plan, use_plan_cache)
                    # the probe verdict: a device query completing closes
                    # a tripped breaker (no-op on a closed one)
                    breaker.note_success()
                except Exception as e:  # noqa: BLE001 — degradation boundary
                    if not R.failure_is_device_rooted(e):
                        raise
                    run.physical, results = self._degrade_device_failure(
                        plan, e, breaker, cpu_fallback_ok, use_plan_cache)
            return results

    @contextlib.contextmanager
    def _query_scope(self, plan: L.LogicalPlan,
                     timeout_s: Optional[float] = None,
                     force_tracing: bool = False,
                     deadline_from_conf: bool = True):
        """THE scope of one query, entered by every action (a collect
        through execute_partitions, a write through execute_write):
        installs the per-query QueryContext (tenant metrics + breaker +
        injector + retry budget and policy + issue-ahead flags +
        CancelToken, and the QueryTracer when tracing or history is on),
        yields a `_QueryRun` whose `physical` the body fills in, and on
        the way out — however the body ended — accounts a cancellation,
        pops the context and publishes last_query_metrics,
        last_query_trace, the tenant totals and the history record.
        `deadline_from_conf=False` (a write) leaves
        rapids.tpu.engine.deadlineMs out of the token: it stays
        cancellable, it has no deadline."""
        from spark_rapids_tpu.engine import async_exec as AX
        from spark_rapids_tpu.engine import cancel as CX
        from spark_rapids_tpu.engine import retry as R
        from spark_rapids_tpu.plan.fusion import count_fused_stages
        from spark_rapids_tpu.utils import faultinject as FI
        from spark_rapids_tpu.utils import metrics as M

        # the executing session's conf drives the process-wide narrowing
        # flag (conf.sync_int64_narrowing: covers clone_with copies and
        # interleaved sessions) — and, same contract, the retry policy,
        # the circuit breaker knobs, the fault-injection harness, the
        # issue-ahead/donation flags, and the scheduler's per-query retry
        # budget/timeout. Per-tenant state (breaker, injector, budget,
        # metrics) additionally rides the QueryContext so concurrent
        # tenants cannot cross-talk.
        self.conf.sync_int64_narrowing()
        breaker = R.CircuitBreaker.configure(self.conf, tenant=self.tenant)
        qctx = M.QueryContext(self.tenant)
        # context-scoped issue-ahead flags: the process globals stay the
        # fallback for kernels tracing outside any query, but THIS
        # query's resolution rides its context so concurrent tenants'
        # asyncDispatch/donation settings cannot cross-talk
        AX.configure(self.conf, self.device_manager, ctx=qctx)
        self.scheduler.configure(self.conf)
        # context-scoped: the retry/backoff policy rides the QueryContext
        # (combinators read policy() through it), so concurrent tenants'
        # knobs stay isolated
        R.set_policy_from_conf(self.conf, ctx=qctx)
        qctx.breaker = breaker
        qctx.begin_retry_budget(self.conf.get(C.RETRY_BUDGET))
        # the query's CancelToken (engine/cancel.py): per-call timeout
        # wins over the session deadline conf; no deadline = a plain
        # cancellable token (cancel_all / drain / cancel.race still work)
        deadline_ms = self.conf.get(C.ENGINE_DEADLINE_MS) \
            if deadline_from_conf else 0
        deadline_s = timeout_s if timeout_s is not None else (
            deadline_ms / 1000.0 if deadline_ms > 0 else None)
        qctx.cancel = CX.CancelToken(deadline_s)
        # force_tracing (EXPLAIN ANALYZE) traces THIS run without touching
        # conf: the settings map feeds plan-cache signatures under
        # _plan_lock, so a transient conf flip would both race concurrent
        # signature builds and fork the cache key. The flight recorder
        # (obs/history.py) rides the span tree, so history-enabled
        # queries trace too — tracing adds zero dispatches and zero
        # fences (the pinned overhead contract), and so does history
        # (pinned by tests/test_history.py).
        from spark_rapids_tpu.obs.trace import wall_ns as _wall_ns

        record_history = self.conf.get(C.OBS_HISTORY_ENABLED)
        q_started_ns = _wall_ns()
        span_token = None
        if force_tracing or self.conf.get(C.OBS_TRACING) or record_history:
            from spark_rapids_tpu.obs.trace import QueryTracer, reset_current_span

            qctx.trace = QueryTracer(
                name=type(plan).__name__, tenant=self.tenant,
                max_spans=self.conf.get(C.OBS_TRACE_MAX_SPANS),
                annotate=self.conf.get(C.OBS_TRACE_ANNOTATIONS))
            # a nested run (the micro-batcher's packed execution under the
            # leader's query) must root its spans in ITS OWN tree, not
            # under whatever span the enclosing query has open
            span_token = reset_current_span()
        token = M.push_query_ctx(qctx)
        # registered LAST, adjacent to the try whose finally discards it:
        # an exception in the setup above must not leak a token that
        # would make every later drain/stop burn its full quiesce timeout
        with self._inflight_lock:
            self._inflight.add(qctx.cancel)
        run = _QueryRun(breaker)
        # explicit success flag for the flight recorder's status tag:
        # sys.exc_info() inside the finally would also see an ENCLOSING
        # handler's exception and mislabel a successful nested query
        q_succeeded = False
        try:
            FI.configure(self.conf, ctx=qctx)
            # the hung-dispatch watchdog refreshes from the executing
            # session's conf exactly like the injector (engine/watchdog)
            from spark_rapids_tpu.engine.watchdog import DispatchWatchdog

            DispatchWatchdog.configure(self.conf)
            yield run
            q_succeeded = True
        except (CX.TpuQueryCancelled, CX.TpuOverloadedError) as e:
            # terminal by contract (engine/cancel.py): count it once,
            # note it on the trace, reclaim everything the query holds
            # (query-scoped spill entries, prefetch reader threads —
            # semaphore permits and the admission ticket released in
            # their own finallys), and propagate with NO partial rows
            self._on_query_killed(qctx, e)
            raise
        finally:
            with self._inflight_lock:
                self._inflight.discard(qctx.cancel)
            M.pop_query_ctx(token)
            # per-query accounting from THIS query's context (immune to
            # concurrent tenants, unlike the old global before/after
            # snapshots). Under concurrency last_query_metrics is
            # last-completed-wins per session.
            snap = qctx.snapshot()
            self.last_query_metrics = {
                M.FUSED_STAGES: (count_fused_stages(run.physical)
                                 if run.physical is not None else 0),
            }
            for name in (M.DEVICE_DISPATCHES, M.RETRIES, M.SPLIT_RETRIES,
                         M.CPU_FALLBACK_EVENTS, M.FETCH_RETRIES, M.FENCES,
                         M.CHECKED_REPLAYS, M.DONATED_BYTES, M.SPMD_STAGES,
                         M.COLLECTIVE_BYTES, M.SPMD_JOINS,
                         M.SPMD_MEASURED_CAPS, M.PLAN_CACHE_HITS,
                         M.PLAN_CACHE_MISSES, M.ADMISSION_WAITS,
                         M.ADMISSION_WAIT_NS,
                         M.MICRO_BATCHES, M.MICRO_BATCHED_QUERIES,
                         M.ENCODED_COLUMNS, M.LATE_MATERIALIZATIONS,
                         M.ENCODED_BYTES_SAVED, M.ORDER_PRESERVING_SORTS,
                         M.RUN_COLLAPSED_ROWS, M.AQE_REPLANS,
                         M.SKEW_SPLITS, M.JOIN_DEMOTIONS,
                         M.JOIN_PROMOTIONS, M.CANCELLED_QUERIES,
                         M.DEADLINE_REJECTS, M.SHED_QUERIES,
                         M.HOST_PLACED_OPS, M.PLACEMENT_REPLACEMENTS,
                         M.SPECULATIVE_TASKS, M.SPECULATIVE_WINS,
                         M.WATCHDOG_KILLS, M.DEVICE_RESETS,
                         M.DENSE_AGG_BATCHES, M.SORT_AGG_BATCHES,
                         M.UNGROUPED_AGG_BATCHES, M.COMPACTED_BATCHES,
                         M.CACHED_BATCHES_SERVED,
                         M.CACHE_RESTORED_BATCHES,
                         M.CACHE_COALESCED_PIECES):
                self.last_query_metrics[name] = snap.get(name, 0)
            self.last_query_metrics[M.CACHE_RESIDENT_BYTES] = \
                M.cache_resident_bytes()
            self.last_adaptive_report = list(qctx.aqe_notes)
            finished_trace = None
            if qctx.trace is not None:
                finished_trace = self.last_query_trace = qctx.trace.finish()
                if span_token is not None:
                    from spark_rapids_tpu.obs.trace import restore_current_span

                    restore_current_span(span_token)
            # lifetime tenant totals for the serving telemetry endpoint
            # (TpuServer.metrics_snapshot): one merge per query
            with self._totals_lock:
                self.queries_run += 1
                for name, v in snap.items():
                    self.tenant_metric_totals[name] = \
                        self.tenant_metric_totals.get(name, 0) + v
            if record_history:
                self._record_history(qctx, run.physical, snap,
                                     finished_trace,
                                     _wall_ns() - q_started_ns, q_succeeded)

    def _on_query_killed(self, qctx, e: BaseException) -> None:
        """Account + reclaim for a cancelled/shed/deadline-rejected query
        (runs with the QueryContext still ambient, so the counters land
        on the tenant's totals and the trace)."""
        from spark_rapids_tpu.engine import cancel as CX
        from spark_rapids_tpu.obs.trace import wall_ns
        from spark_rapids_tpu.utils import metrics as M

        if not getattr(e, "counted", False):
            e.counted = True
            if isinstance(e, CX.TpuOverloadedError):
                M.record_shed_query()
            else:
                M.record_cancelled_query()
        kind = ("shed" if isinstance(e, CX.TpuOverloadedError)
                else "deadline" if isinstance(e, CX.TpuDeadlineExceeded)
                else "cancelled")
        # terminal-status tag for the flight recorder: the history record
        # of a killed query carries HOW it died (obs/history.py)
        qctx.kill_reason = kind
        if qctx.trace is not None:
            t = wall_ns()
            qctx.trace.note_span(
                f"query.{kind}", t, t,
                attrs={"reason": getattr(e, "reason", kind),
                       "site": getattr(e, "site", "")})
        self._reclaim_cancelled(qctx)

    @staticmethod
    def _reclaim_cancelled(qctx) -> None:
        """Release everything a dead query still holds: close (and join)
        its prefetch reader threads and free its query-scoped spill-store
        entries (shuffle pieces, staged batches). Semaphore permits and
        admission bytes release in their own finallys; the post-cancel
        invariant (engine/cancel.reclamation_report) pins the union."""
        for pf in list(qctx.prefetchers):  # close() deregisters in place
            try:
                pf.close()
            except Exception:  # noqa: BLE001 — teardown best-effort
                pass
        qctx.prefetchers.clear()
        fw = SpillFramework.get()
        if fw is not None:
            for buf in qctx.spill_buffers:
                try:
                    fw.free(buf)
                except Exception:  # noqa: BLE001 — teardown best-effort
                    pass
        qctx.spill_buffers.clear()

    def _record_history(self, qctx, physical, counters, finished_trace,
                        wall_total_ns, succeeded: bool) -> None:
        """Flight recorder (obs/history.py, docs/observability.md):
        enqueue one record for the finished query onto the write-behind
        store. Everything captured here is already host-resident (the
        counter snapshot, the FINISHED span tree, the resource report);
        flattening, JSON encoding, and disk IO run on the writer thread
        — nothing below adds a dispatch or a fence to the query."""
        from spark_rapids_tpu.obs import history as OH
        from spark_rapids_tpu.utils import metrics as M

        try:
            store = OH.get_store(self.conf)
            if store is None:
                return
            status = qctx.kill_reason
            if status is None:
                status = "ok" if succeeded else "failed"
            qid = OH.next_query_id(self.tenant)
            sig = OH.plan_fingerprint(physical)
            wall = finished_trace.duration_ns if finished_trace is not None \
                else wall_total_ns
            report = qctx.resource_report
            notes = list(qctx.aqe_notes)
            tenant = self.tenant
            placement = qctx.placement_payload
            # zero-dispatch runs: measured output rows of the host-placed
            # operators (Cpu nodes have no kernel span chokepoint, so the
            # trace carries nothing for them) — the host-fit's
            # feature/response pairs (obs/calibrate.fit_host)
            host_rows = None
            if physical is not None and \
                    not counters.get(M.DEVICE_DISPATCHES):
                try:
                    host_rows = [
                        (n.node_name(),
                         int(n.metrics[M.NUM_OUTPUT_ROWS].value))
                        for n in physical.collect_nodes(
                            lambda n: getattr(n, "placement",
                                              "tpu") == "cpu")]
                except Exception:  # noqa: BLE001 - best-effort capture
                    host_rows = None
            store.enqueue(lambda: OH.build_record(
                qid, tenant, status, sig, wall, counters, finished_trace,
                report, notes, placement=placement,
                host_op_rows=host_rows))
        except Exception:  # noqa: BLE001 - the recorder must never
            # surface into a query's result path
            log.warning("history record dropped", exc_info=True)

    def _check_deadline_feasible(self, qctx, report) -> None:
        """Admission-time deadline enforcement (docs/fault-tolerance.md):
        a query whose deadline is already spent — or whose predicted
        work cannot fit the remaining budget — is REJECTED before any
        device dispatch, instead of admitted to die mid-flight (metric:
        deadlineRejects). The work prediction prices each operator class
        at the FITTED cost model when calibration has enough samples
        (engine/admission.predict_query_work_s, obs/calibrate.py); the
        flat costPerDispatchMs stays the cold-start fallback."""
        from spark_rapids_tpu.engine import cancel as CX
        from spark_rapids_tpu.engine.admission import predict_query_work_s
        from spark_rapids_tpu.utils import metrics as M

        tok = qctx.cancel if qctx is not None else None
        predicted_s, source = predict_query_work_s(report, self.conf)
        if qctx is not None and predicted_s > 0:
            # stash the cost-model prediction for the self-healing layer:
            # scheduler speculation and the watchdog's calibrated timeout
            # divide it across the query's tasks (host math only — the
            # zero-dispatch contract of this check is untouched)
            qctx.predicted_work_ns = int(predicted_s * 1e9)
        if tok is None or tok.deadline_ns is None:
            return
        remaining = tok.deadline_remaining_s()
        if remaining > predicted_s:
            return
        M.record_deadline_reject()
        tok.cancel("deadline")
        err = CX.TpuDeadlineExceeded(
            f"rejected at admission: predicted work ~{predicted_s:.3f}s "
            f"({source} cost model) cannot fit the remaining deadline "
            f"{max(0.0, remaining):.3f}s", site="admission")
        err.counted = True
        raise err

    def _maybe_micro_batch(self, plan: L.LogicalPlan, breaker,
                           allow_micro_batch: bool):
        """Route an eligible query through the server's micro-batcher
        (engine/server.py); returns the per-partition results, or None to
        run it as an ordinary query."""
        from spark_rapids_tpu.utils import metrics as M

        if not allow_micro_batch or self.micro_batcher is None or \
                breaker.is_open():
            return None
        window_ms = self.conf.get(C.MICRO_BATCH_WINDOW_MS)
        if window_ms <= 0:
            return None
        from spark_rapids_tpu.engine.server import micro_batch_eligible
        from spark_rapids_tpu.plan.signature import plan_signature

        if not micro_batch_eligible(plan):
            return None
        sig = plan_signature(plan, self.conf)
        if sig is None:
            return None
        M.record_micro_batched_query()
        return self.micro_batcher.submit(self, plan, sig.shape_key,
                                         window_ms / 1000.0)

    def _execute_device(self, plan: L.LogicalPlan,
                        use_plan_cache: bool = True):
        """Plan and run one query on the device engine (the issue-ahead
        fast path; also the body of the checked replay).

        Before executing, the query passes analyzer-driven admission
        (engine/admission.py): its predicted peak-HBM bytes must fit
        beside everything already admitted, so aggregate admitted HBM
        stays under budget — heavy plans queue, light plans interleave.

        When the plan root is the result sink (DeviceToHostExec) and
        issue-ahead execution is on, the sink is lifted to the QUERY
        level: every partition task materializes unblocked DEVICE
        batches, and the whole result downloads in one grouped transfer
        — the query blocks on device values exactly once
        (docs/async-execution.md; was one grouped download per output
        partition, each a fence)."""
        from spark_rapids_tpu.engine import async_exec as AX
        from spark_rapids_tpu.engine.admission import AdmissionController
        from spark_rapids_tpu.exec.transitions import DeviceToHostExec
        from spark_rapids_tpu.obs.trace import span as obs_span
        from spark_rapids_tpu.utils import metrics as M

        with obs_span("plan", kind="stage"):
            physical = self._physical_plan(plan, use_cache=use_plan_cache)
        ticket = ctl = None
        qctx = M.current_query_ctx()
        placement = self.last_placement_report
        if placement is not None:
            # surface the placement decision on the query's metrics and
            # stamp the payload for the flight recorder (obs/history.py
            # computes placementRegret from it post-hoc)
            if qctx is not None:
                qctx.placement_payload = placement.to_payload()
            if placement.host_ops:
                M.record_host_placed_ops(placement.host_ops)
        report = qctx.resource_report if qctx is not None \
            else self.last_resource_report
        # deadline feasibility BEFORE admission: an infeasible query runs
        # zero device dispatches by construction (engine/cancel.py)
        self._check_deadline_feasible(qctx, report)
        if report is not None and self.conf.get(C.ADMISSION_ENABLED):
            ctl = AdmissionController.get()
            if ctl is not None:
                ticket = ctl.admit(report.peak_bytes.hi, tenant=self.tenant)
        try:
            ctx = self._exec_context()
            # the lift streams partitions as they complete (run_job_iter),
            # which has no per-task timeout plumbing — a timeout-configured
            # session keeps the per-partition sink
            if isinstance(physical, DeviceToHostExec) and \
                    AX.async_enabled() and not self.scheduler.task_timeout_s:
                results = self._execute_lifted_sink(physical, ctx)
                return physical, results
            pb = physical.execute(ctx)
            with obs_span("stage:result", kind="stage",
                          partitions=pb.num_partitions):
                results = self.scheduler.run_job(
                    pb.num_partitions, lambda p: list(pb.iterator(p)))
            return physical, results
        finally:
            if ticket is not None:
                ctl.release(ticket)

    # device bytes the lifted sink may hold un-downloaded before flushing
    # a grouped transfer (ONE shared constant with to_host_many's
    # internal run budget, so the two can never drift): bounds sink HBM
    # residency for large results while small interactive results still
    # download in ONE fence
    from spark_rapids_tpu.columnar.batch import (
        DOWNLOAD_BYTE_BUDGET as _SINK_FLUSH_BYTES,
    )

    def _execute_lifted_sink(self, physical, ctx):
        """Run the sink's child; download accumulated device batches in
        grouped per-byte-budget transfers AS PARTITIONS COMPLETE, so sink
        residency is bounded by the flush budget plus whatever the still-
        running tasks hold — not by the whole result set. The sink node's
        own metrics (output rows/batches, DeviceToHost time) are recorded
        here — this path replaces its per-partition iterators."""
        from spark_rapids_tpu.utils import metrics as M

        from spark_rapids_tpu.obs.trace import span as obs_span

        child_pb = physical.children[0].execute(ctx)
        n = child_pb.num_partitions
        results: List[Optional[list]] = [None] * n
        pending: List[tuple] = []  # (pidx, device batches)
        pending_bytes = 0
        total_time = physical.metrics[M.TOTAL_TIME]

        def flush():
            nonlocal pending, pending_bytes
            with M.trace_range("DeviceToHost", total_time):
                hosts = self._sink_download(
                    [b for _, part in pending for b in part])
            hi = 0
            for pidx, part in pending:
                results[pidx] = hosts[hi:hi + len(part)]
                hi += len(part)
            pending, pending_bytes = [], 0

        # the result stage span covers the partition tasks + grouped sink
        # downloads, but NOT the child execute above — exchanges that
        # materialized there opened their own stage spans at top level
        from spark_rapids_tpu.engine import cancel as CX

        with obs_span("stage:result", kind="stage", partitions=n):
            for pidx, part in self.scheduler.run_job_iter(
                    n, lambda p: (p, list(child_pb.iterator(p)))):
                # sink chokepoint: a cancel between partition completions
                # stops the download loop before the next grouped fence
                CX.check_cancel("sink")
                pending.append((pidx, part))
                pending_bytes += sum(b.device_memory_size() for b in part)
                if pending_bytes > self._SINK_FLUSH_BYTES:
                    flush()
            flush()
        physical.metrics[M.NUM_OUTPUT_BATCHES].add(
            sum(len(part) for part in results))
        physical.metrics[M.NUM_OUTPUT_ROWS].add(
            sum(b.num_rows for part in results for b in part))
        return results

    @staticmethod
    def _sink_download(flat):
        """THE query sink: one grouped device->host transfer per byte
        budget for the accumulated device batches, with async error
        attribution (exec/transitions.sink_download_many). An empty
        result still surfaces any sink-deferred injected faults — a
        query is not fault-immune just because nothing survived its
        filters."""
        from spark_rapids_tpu.exec.transitions import sink_download_many
        from spark_rapids_tpu.utils import faultinject as FI

        if not flat:
            FI.raise_deferred_at_sink()
            return []
        return sink_download_many(flat)

    def _degrade_device_failure(self, plan: L.LogicalPlan,
                                e: BaseException, breaker,
                                cpu_fallback_ok: bool,
                                use_plan_cache: bool = True):
        """Graceful degradation after a device-rooted failure, in order:
        (1) one CHECKED replay when issue-ahead behavior was active — the
        error may have surfaced at the sink (or a donated dispatch lost
        its inputs), so re-executing with synchronous dispatch and
        donation off re-attributes it to the originating operator, whose
        spill/split-retry machinery then owns it (docs/async-execution.md);
        (2) the query-level CPU-oracle fallback of PR 4."""
        from spark_rapids_tpu.engine import async_exec as AX
        from spark_rapids_tpu.engine import retry as R
        from spark_rapids_tpu.utils import faultinject as FI
        from spark_rapids_tpu.utils import metrics as M

        if R.failure_is_device_loss(e):
            # the device itself is GONE: its own recovery rung
            # (quarantine + replay-once + breaker/CPU) owns this
            return self._recover_device_loss(plan, e, breaker,
                                             cpu_fallback_ok,
                                             use_plan_cache)
        if AX.replay_warranted() and R.failure_needs_checked_replay(e):
            M.record_checked_replay()
            log.warning(
                "device error surfaced under issue-ahead execution (%r); "
                "re-executing the query in checked (synchronous) mode so "
                "the originating op's retry machinery can own it", e)
            # the replay starts clean: a fresh retry budget, and none of
            # the first run's undelivered sink faults
            self.scheduler.begin_query()
            FI.clear_deferred()
            try:
                with AX.checked_mode():
                    # the checked replay plans fresh (the plan cache is
                    # bypassed while in_checked_mode: SPMD lowering and
                    # donation differ in checked plans)
                    return self._execute_device(plan, use_plan_cache)
            except Exception as e2:  # noqa: BLE001 — degradation boundary
                if not (cpu_fallback_ok and R.failure_is_device_rooted(e2)):
                    raise
                e = e2
        elif not cpu_fallback_ok:
            raise e
        # placement-pinned re-plan BEFORE the whole-query CPU oracle: when
        # the placement analyzer is on, pin the FAILING operator class to
        # the host side and re-plan — the rest of the query keeps its
        # device placement instead of losing the device entirely
        if (self.conf.get(C.PLACEMENT_ENABLED)
                and self._placement_pin is None):
            from spark_rapids_tpu.obs import calibrate as CAL

            site = getattr(e, "origin_site", None)
            if not site:
                # injected/engine faults name their site as a trailing
                # "... at <site>"; fall back to the error class name
                msg = str(e)
                site = msg.rsplit(" at ", 1)[-1].strip() \
                    if " at " in msg else type(e).__name__
            self._placement_pin = {CAL.classify(str(site))}
            log.warning(
                "device execution failed (%r); re-planning with operator "
                "class %s pinned to the host", e, self._placement_pin)
            try:
                # bypass the plan cache: the cached entry is the plan that
                # just failed. Injected faults stay ARMED — the pinned
                # subtree now runs on the host, out of their reach, which
                # is exactly the claim under test.
                self.scheduler.begin_query()
                FI.clear_deferred()
                out = self._execute_device(plan, use_plan_cache=False)
                M.record_placement_replacement()
                return out
            except Exception:  # noqa: BLE001 — degradation boundary
                log.warning("pinned re-plan failed too; falling back to "
                            "the CPU oracle", exc_info=True)
            finally:
                self._placement_pin = None
        # runtime graceful degradation: an operator with device-resident
        # state (aggregate/join/sort/scan) exhausted its retries —
        # re-execute the whole query through the CPU oracle instead of
        # failing the job
        breaker.record_failure()
        M.record_cpu_fallback()
        log.warning("device execution failed (%r); re-executing the query "
                    "on the CPU oracle engine", e)
        # the fallback run is the backstop: injected faults must not chase
        # it (re-armed at the next query start)
        FI.disable()
        return self._execute_on_cpu(plan, use_plan_cache)

    def _recover_device_loss(self, plan: L.LogicalPlan, e: BaseException,
                             breaker, cpu_fallback_ok: bool,
                             use_plan_cache: bool = True):
        """Device-loss recovery (docs/fault-tolerance.md self-healing):
        the failing device QUARANTINES (the mesh rebuilds on survivors,
        admission stops pricing the lost chip's HBM), the in-flight query
        replays ONCE from the plan cache in checked mode (synchronous
        dispatch: a second loss attributes cleanly), and a failed replay
        degrades to the CPU oracle through the per-tenant breaker. Every
        step lands on the flight recorder as structured event rows
        (deviceResets / checkedReplays / cpuFallbackEvents)."""
        from spark_rapids_tpu.engine import async_exec as AX
        from spark_rapids_tpu.engine import retry as R
        from spark_rapids_tpu.engine.admission import AdmissionController
        from spark_rapids_tpu.utils import faultinject as FI
        from spark_rapids_tpu.utils import metrics as M

        M.record_device_reset()
        before = max(1, TpuDeviceManager.healthy_device_count())
        healthy = TpuDeviceManager.quarantine_device(reason=str(e))
        ctl = AdmissionController.get()
        if ctl is not None:
            ctl.note_device_loss(healthy, before)
        log.warning(
            "device lost (%r): device quarantined (%d healthy remain); "
            "replaying the query once in checked mode", e, healthy)
        M.record_checked_replay()
        # the replay starts clean: fresh retry budget, no stale deferred
        # sink faults from the dead run
        self.scheduler.begin_query()
        FI.clear_deferred()
        try:
            with AX.checked_mode():
                return self._execute_device(plan, use_plan_cache)
        except Exception as e2:  # noqa: BLE001 — degradation boundary
            if not (cpu_fallback_ok and R.failure_is_device_rooted(e2)):
                raise
            e = e2
        breaker.record_failure()
        M.record_cpu_fallback()
        log.warning("device-loss replay failed too (%r); re-executing the "
                    "query on the CPU oracle engine", e)
        FI.disable()
        return self._execute_on_cpu(plan, use_plan_cache)

    def _execute_on_cpu(self, plan: L.LogicalPlan,
                        use_plan_cache: bool = True):
        """Plan and run a query entirely on the CPU-oracle engine (runtime
        graceful degradation; strict on-TPU assertion is meaningless for a
        deliberate fallback, so it is disabled for this run)."""
        # the device run may have spent the whole per-query retry budget;
        # the fallback run starts fresh
        self.scheduler.begin_query()
        # conf swap + planning under the plan lock: a CONCURRENT query's
        # signature/plan build must never observe the fallback's
        # sql.enabled=False half-applied (the overridden keys are part of
        # every cache key, so the fallback plan caches separately)
        with self._plan_lock:
            saved = dict(self.conf.settings)
            self.conf.settings.update({
                C.SQL_ENABLED.key: False,
                C.TEST_ENABLED.key: False,
            })
            try:
                physical = self._physical_plan(plan,
                                               use_cache=use_plan_cache)
            finally:
                self.conf.settings.clear()
                self.conf.settings.update(saved)
        ctx = self._exec_context()
        pb = physical.execute(ctx)
        results = self.scheduler.run_job(
            pb.num_partitions, lambda p: list(pb.iterator(p)))
        return physical, results

    def execute_collect(self, plan: L.LogicalPlan,
                        timeout_s: Optional[float] = None) -> List[tuple]:
        rows: List[tuple] = []
        for b in self.execute_batches(plan, timeout_s=timeout_s):
            rows.extend(b.to_pylist_rows())
        return rows

    def execute_write(self, plan: L.WriteFile) -> None:
        """Run one write as a query: the same scope a collect enters
        (context, tracer, last_query_metrics / last_query_trace, tenant
        totals), none of execute_partitions' routing around it — no
        micro-batching, no breaker/CPU ladder, no admission, no deadline
        from conf — so a write plans, retries and fails as it always
        did."""
        from spark_rapids_tpu.io.writer import execute_write

        with self._query_scope(plan, deadline_from_conf=False) as run:
            run.physical = execute_write(self, plan)


class SessionBuilder:
    def __init__(self):
        self._settings: Dict[str, Any] = {}

    def config(self, key: str, value: Any) -> "SessionBuilder":
        self._settings[key] = value
        return self

    def getOrCreate(self) -> TpuSession:
        with TpuSession._lock:
            existing = TpuSession._active
        if existing is not None:
            for k, v in self._settings.items():
                existing.conf.set(k, v)
            return existing
        return TpuSession(self._settings)


# ---------------------------------------------------------------------------
# createDataFrame input coercion
# ---------------------------------------------------------------------------
def _to_host_batch(data, schema):
    if hasattr(data, "to_dict") and hasattr(data, "dtypes"):  # pandas
        cols = {name: data[name].to_numpy() for name in data.columns}
        return _dict_to_batch(cols, schema)
    if isinstance(data, dict):
        return _dict_to_batch(data, schema)
    if isinstance(data, list):
        if schema is None:
            raise ValueError("schema required for list-of-rows input")
        names_types = _normalize_schema(schema)
        cols = {name: [row[i] for row in data]
                for i, (name, _)in enumerate(names_types)}
        attrs = [AttributeReference(n, t, True) for n, t in names_types]
        vecs = [HostColumnVector.from_pylist(cols[n], t)
                for n, t in names_types]
        return attrs, HostColumnarBatch(vecs)
    raise TypeError(f"cannot create DataFrame from {type(data)}")


def _normalize_schema(schema):
    out = []
    for item in schema:
        if isinstance(item, tuple):
            name, t = item
            if isinstance(t, str):
                t = DataType.parse(t)
            out.append((name, t))
        elif isinstance(item, AttributeReference):
            out.append((item.name, item.data_type))
        else:
            raise TypeError(f"bad schema element {item!r}")
    return out


def _dict_to_batch(cols: Dict[str, Any], schema):
    names_types = _normalize_schema(schema) if schema else None
    attrs, vecs = [], []
    for i, (name, values) in enumerate(cols.items()):
        want = names_types[i][1] if names_types else None
        if isinstance(values, np.ndarray):
            vec = HostColumnVector.from_numpy(values, dtype=want)
        else:
            dt = want
            if dt is None:
                dt = _infer_type(values)
            vec = HostColumnVector.from_pylist(list(values), dt)
        attrs.append(AttributeReference(name, vec.dtype, True))
        vecs.append(vec)
    return attrs, HostColumnarBatch(vecs)


def _infer_type(values) -> DataType:
    for v in values:
        if v is None:
            continue
        if isinstance(v, bool):
            return DataType.BOOL
        if isinstance(v, int):
            return DataType.INT64
        if isinstance(v, float):
            return DataType.FLOAT64
        if isinstance(v, str):
            return DataType.STRING
        if isinstance(v, np.datetime64):
            return DataType.TIMESTAMP
        import decimal as _dec

        if isinstance(v, _dec.Decimal):
            from spark_rapids_tpu.ops.decimal_util import infer_decimal_type

            # widest literal wins; scan the full column for the max (p, s)
            from spark_rapids_tpu.columnar.dtypes import DecimalType

            p = s = 0
            for w in values:
                if w is None:
                    continue
                t = infer_decimal_type(w)
                s = max(s, t.scale)
                p = max(p, t.precision - t.scale)
            if p + s > DecimalType.MAX_PRECISION:
                # never clamp: a clamped type would admit unscaled values
                # beyond the precision bound every decimal kernel relies on
                raise ValueError(
                    f"decimal column needs precision {p + s} "
                    f"(> {DecimalType.MAX_PRECISION}, the 64-bit cap); "
                    "pass an explicit narrower schema or use double")
            return DecimalType(p + s, s)
        raise TypeError(f"cannot infer SQL type for {v!r}")
    return DataType.STRING


def _split_batch(batch: HostColumnarBatch, n: int) -> List[List[HostColumnarBatch]]:
    n = max(1, n)
    total = batch.num_rows
    per = -(-total // n) if total else 0
    parts: List[List[HostColumnarBatch]] = []
    for i in range(n):
        lo, hi = i * per, min(total, (i + 1) * per)
        parts.append([batch.slice(lo, hi - lo)] if hi > lo else [])
    return parts
