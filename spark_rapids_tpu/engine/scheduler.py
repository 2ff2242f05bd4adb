"""Partition-task scheduler.

The Spark-executor analog: a pool of worker threads runs partition tasks;
each task gets a task-attempt id (TaskContext analog) and automatically
releases the TPU admission semaphore on completion, mirroring the
completion-listener auto-release in GpuSemaphore.scala:101-161.

Task failure behavior mirrors Spark's retry loop (reference: Spark task
retry + lineage is the reference's whole failure story, SURVEY.md section 5)
with the typed taxonomy of engine/retry.py: shuffle-fetch failures
(`FetchFailedError`, the RapidsShuffleFetchFailedException analog,
shuffle/RapidsShuffleIterator.scala:237-330) and typed/transient device
errors retry up to `max_failures`; DETERMINISTIC errors (planning/type/user
errors) fail fast on the first attempt — retrying them only doubles the
cost of every real failure.

Hardening (docs/fault-tolerance.md):
- retries sleep with exponential backoff + deterministic jitter (a pure
  function of (partition, attempt): reproducible, no thundering herd);
- a per-query retry BUDGET bounds total retries across all of a query's
  jobs (map stages, exchanges, reduces share it);
- an optional per-task wall-clock timeout fails a pooled job whose task
  wedges instead of hanging the query (the worker thread itself cannot be
  interrupted — single-partition jobs run inline and are not covered).

Straggler speculation (docs/fault-tolerance.md self-healing): a pooled
job tracks per-task elapsed against a cost-calibrated prediction — the
admission-time CostModel estimate of the query's work divided across the
job's tasks (QueryContext.predicted_work_ns), falling back to the p95 of
the job's own FINISHED sibling durations when no fitted model is active.
When a task runs past `max(speculation.minRuntimeMs, speculation.
multiplier x predicted_p95)` while at least `speculation.quantile` of
its siblings have finished, the scheduler launches ONE speculative
duplicate. Tasks are idempotent by construction (each attempt re-reads
from its source/piece-range and never shares device buffers — the same
property task RETRY already requires), so racing two attempts is safe:
the first completion wins and the loser is cancelled through a
TASK-scoped CancelToken (engine/cancel.py) that unwinds just that
attempt, never the query. Metrics: speculativeTasks / speculativeWins.
A task's elapsed, and a finished sibling's, is its time AT WORK
(engine/pause_clock.AtWork): from the moment a pool thread picked it up,
less the time programs were being built and less the time the whole
process stood still. The task timeout stays on the wall: it is a promise
to a caller, not a judgement of a task.
"""

from __future__ import annotations

import concurrent.futures as cf
import contextvars
import threading
from typing import Callable, Iterator, List, Optional, TypeVar

from spark_rapids_tpu.engine import cancel as CX
from spark_rapids_tpu.engine import pause_clock
from spark_rapids_tpu.engine import retry as R
from spark_rapids_tpu.exec.transitions import current_task_id, set_task_id
from spark_rapids_tpu.memory.semaphore import TpuSemaphore
from spark_rapids_tpu.obs.trace import span as obs_span
from spark_rapids_tpu.utils import metrics as M

T = TypeVar("T")

_next_task_id = iter(range(1_000_000, 1 << 62))
_next_task_id_lock = threading.Lock()

# future-wait poll cadence: tight when a CancelToken is watching (prompt
# cancellation), relaxed otherwise (standalone schedulers in unit tests —
# still bounded, never an untimed wait); and the bounded drain a cancelled
# job gives its in-flight tasks to observe the token and exit
_RESULT_POLL_S = 0.05
_IDLE_POLL_S = 60.0
_CANCEL_DRAIN_S = 5.0


class TaskFailedError(RuntimeError):
    def __init__(self, pidx: int, attempts: int, cause: BaseException):
        super().__init__(
            f"partition task {pidx} failed after {attempts} attempts: {cause!r}")
        self.pidx = pidx
        self.cause = cause


class FetchFailedError(RuntimeError):
    """A shuffle piece could not be materialized (reference:
    RapidsShuffleFetchFailedException -> Spark stage retry). Always
    retryable; the exchange additionally re-executes the upstream map
    partition in place (shuffle/exchange.py) before this surfaces."""


class TaskTimeoutError(R.TpuTransientDeviceError, TimeoutError):
    """A partition task exceeded rapids.tpu.engine.taskTimeoutSeconds.
    Part of the typed DEVICE hierarchy (a wedged task on a device query is
    a wedged dispatch until proven otherwise) so the query-level CPU
    fallback and the circuit breaker engage — the session degrades to the
    CPU engine, which never acquires the admission semaphore the zombie
    worker may still hold."""


def _is_retryable(e: BaseException) -> bool:
    # classification lives with the typed hierarchy (engine/retry.py) so
    # the dispatch layer and the task layer can never disagree
    return R.is_retryable_failure(e)


class _Attempt:
    """One racing execution attempt of a partition task (primary or
    speculative duplicate), with its task-scoped cancel token."""

    __slots__ = ("future", "token", "work", "speculative")

    def __init__(self, future: "cf.Future", token: "CX.CancelToken",
                 speculative: bool):
        self.future = future
        self.token = token
        # set by the task itself when a pool thread PICKS IT UP:
        # straggler math must never count queue wait as runtime (16 tasks
        # on an 8-thread pool would read the whole second wave as slow)
        self.work: Optional[pause_clock.AtWork] = None
        self.speculative = speculative

    def mark_started(self, now_ns: int) -> None:
        self.work = pause_clock.AtWork(now_ns)

    def runtime_ns(self, now_ns: int) -> int:
        """Time at work since a pool thread picked the task up: a cold
        program is not a straggler, and neither is a stopped process."""
        return self.work.ns(now_ns)


class TaskScheduler:
    def __init__(self, num_threads: int = 8, max_failures: int = 2,
                 task_timeout_s: float = 0.0, retry_budget: int = 0):
        self.num_threads = max(1, num_threads)
        self.max_failures = max(1, max_failures)
        self.task_timeout_s = max(0.0, task_timeout_s)
        # 0 = unlimited (standalone schedulers in unit tests); sessions
        # configure a real budget per query via configure()/begin_query()
        self.retry_budget = max(0, retry_budget)
        self._retries_spent = 0
        self._budget_lock = threading.Lock()
        self._pool: Optional[cf.ThreadPoolExecutor] = None
        self._lock = threading.Lock()
        # straggler speculation: OFF for standalone schedulers (the unit-
        # test surface pins the legacy harvest); sessions arm it from
        # conf via configure()
        self.spec_enabled = False
        self.spec_min_runtime_ms = 500.0
        self.spec_multiplier = 4.0
        self.spec_quantile = 0.5

    def configure(self, tpu_conf) -> None:
        """Refresh scheduler policy from the executing session's conf and
        reset the per-query retry budget (called at query start)."""
        from spark_rapids_tpu import conf as C

        self.task_timeout_s = max(0.0, tpu_conf.get(C.TASK_TIMEOUT_SECONDS))
        self.retry_budget = max(0, tpu_conf.get(C.RETRY_BUDGET))
        self.spec_enabled = bool(tpu_conf.get(C.SPECULATION_ENABLED))
        self.spec_min_runtime_ms = max(
            0.0, tpu_conf.get(C.SPECULATION_MIN_RUNTIME_MS))
        self.spec_multiplier = max(
            1.0, tpu_conf.get(C.SPECULATION_MULTIPLIER))
        self.spec_quantile = min(
            1.0, max(0.0, tpu_conf.get(C.SPECULATION_QUANTILE)))
        self.begin_query()

    def begin_query(self) -> None:
        """Reset the retry budget for a fresh query run (also called before
        a checked replay / CPU fallback run so the degraded run does not
        inherit a drained budget). Resets the ambient QueryContext's
        per-query budget when one is installed, else the scheduler-level
        fallback counter."""
        qctx = M.current_query_ctx()
        if qctx is not None:
            qctx.begin_retry_budget(qctx.retry_budget)
        with self._budget_lock:
            self._retries_spent = 0

    def _try_spend_retry(self) -> bool:
        """Reserve one retry from the query budget; False = exhausted.
        With an ambient QueryContext (the serving runtime) the budget is
        PER QUERY on the context — concurrent tenants cannot drain each
        other's; the scheduler-level counter remains the fallback for
        standalone schedulers with no session in scope."""
        qctx = M.current_query_ctx()
        if qctx is not None:
            return qctx.try_spend_retry()
        with self._budget_lock:
            if self.retry_budget and self._retries_spent >= self.retry_budget:
                return False
            self._retries_spent += 1
            return True

    @property
    def retries_spent(self) -> int:
        with self._budget_lock:
            return self._retries_spent

    def _ensure_pool(self) -> cf.ThreadPoolExecutor:
        with self._lock:
            if self._pool is None:
                self._pool = cf.ThreadPoolExecutor(
                    max_workers=self.num_threads,
                    thread_name_prefix="tpu-task")
            return self._pool

    def shutdown(self):
        with self._lock:
            if self._pool is not None:
                self._pool.shutdown(wait=True)
                self._pool = None

    # -- the task wrapper ----------------------------------------------------
    def _run_task(self, pidx: int, fn: Callable[[int], T]) -> T:
        last: Optional[BaseException] = None
        for attempt in range(self.max_failures):
            # cancellation chokepoint: every attempt (including the
            # first) polls the ambient query's token before doing work,
            # so a cancelled query's queued tasks exit without touching
            # the device (engine/cancel.py)
            CX.check_cancel("task")
            if attempt > 0:
                # exponential backoff, jitter a pure function of the retry
                # identity (docs/fault-tolerance.md); the sleep itself is
                # cancel-aware — a cancel interrupts it mid-wait
                R.backoff_sleep(attempt - 1, "task", pidx)
            with _next_task_id_lock:
                task_id = next(_next_task_id)
            set_task_id(task_id)
            try:
                # the task span nests under whatever span was current at
                # job submission (the submitting thread's contextvars ride
                # into _submit's copy_context), so per-partition work
                # lands under its stage in the traced timeline
                with obs_span(f"task:p{pidx}", kind="task",
                              attempt=attempt):
                    return fn(pidx)
            except Exception as e:  # noqa: BLE001 — task isolation boundary
                last = e
            finally:
                # completion-listener analog: always drop the semaphore
                TpuSemaphore.get().release_if_necessary(task_id)
                set_task_id(None)
            if CX.is_cancellation(last):
                # terminal by contract: propagate RAW (no TaskFailedError
                # wrap, no retry) so the session's cancellation handler
                # sees the typed error directly
                raise last
            if R.failure_is_device_loss(last):
                # the device is GONE — a task-level re-run would dispatch
                # to the same dead chip; the session's recovery rung
                # (quarantine + replay + breaker) owns this failure class
                raise last
            if not _is_retryable(last):
                raise TaskFailedError(pidx, attempt + 1, last) from last
            if attempt + 1 < self.max_failures and \
                    not self._try_spend_retry():
                raise TaskFailedError(pidx, attempt + 1, last) from last
        raise TaskFailedError(pidx, self.max_failures, last) from last

    def _await_result(self, fut: "cf.Future", pidx: int,
                      futures: List["cf.Future"]) -> T:
        """Cancel-aware future wait: polls the ambient query's
        CancelToken between bounded result waits (a bare fut.result()
        would outwait a cancellation forever — the uncancellable-wait
        lint rule's point), and enforces the per-task wall-clock timeout
        exactly as before."""
        from spark_rapids_tpu.obs.trace import wall_ns

        tok = CX.current_token()
        poll = _RESULT_POLL_S if tok is not None else _IDLE_POLL_S
        timeout_at = None
        if self.task_timeout_s:
            timeout_at = wall_ns() + int(self.task_timeout_s * 1e9)
            poll = min(poll, self.task_timeout_s)
        while True:
            try:
                return fut.result(timeout=poll)
            except cf.TimeoutError:
                if tok is not None:
                    # raises on cancel/deadline; run_job's handler drains
                    # the job's remaining futures before propagating
                    tok.check("job.await")
                if timeout_at is not None and wall_ns() >= timeout_at:
                    for f in futures:
                        f.cancel()
                    # the wedged worker thread cannot be interrupted: it
                    # keeps its pool slot AND any semaphore permits until
                    # its device call eventually returns (only then does
                    # _run_task's finally release them). TaskTimeoutError
                    # is part of the typed device hierarchy precisely so
                    # the query-level CPU fallback engages — the CPU plan
                    # never touches the admission semaphore, so a wedged
                    # device cannot wedge the session with it.
                    raise TaskFailedError(
                        pidx, 1, TaskTimeoutError(
                            f"partition task {pidx} exceeded "
                            f"{self.task_timeout_s:.1f}s")) from None

    def _drain_cancelled(self, futures: List["cf.Future"]) -> None:
        """A cancelled job must not leave tasks of the dead query live on
        the pool: unstarted futures cancel outright; in-flight tasks
        observe the token at their next poll (attempt start, backoff
        wait) and exit — wait for them (bounded) so the reclamation
        invariant already holds when the raise reaches the session."""
        for f in futures:
            f.cancel()
        cf.wait(futures, timeout=_CANCEL_DRAIN_S)

    def run_job(self, num_partitions: int,
                fn: Callable[[int], T]) -> List[T]:
        """Run fn over every partition index; returns results in order."""
        if num_partitions == 0:
            return []
        CX.check_cancel("job.submit")
        if num_partitions == 1:
            return [self._run_task(0, fn)]
        pool = self._ensure_pool()
        if self.spec_enabled:
            return self._run_job_speculative(pool, num_partitions, fn)
        futures = [self._submit(pool, p, fn)
                   for p in range(num_partitions)]
        try:
            return [self._await_result(f, p, futures)
                    for p, f in enumerate(futures)]
        except (CX.TpuQueryCancelled, CX.TpuOverloadedError):
            self._drain_cancelled(futures)
            raise

    # -- straggler speculation (self-healing, docs/fault-tolerance.md) -------
    def _speculation_threshold_ns(self, num_partitions: int,
                                  finished_ns: List[int]) -> Optional[float]:
        """The elapsed beyond which a task is a straggler:
        max(minRuntimeMs, multiplier x predicted_p95). The prediction is
        the admission-time CostModel estimate of per-task wall
        (QueryContext.predicted_work_ns / tasks) when calibration priced
        this query, else the p95 of the job's own finished sibling
        durations; None = no prior yet, no speculation."""
        qctx = M.current_query_ctx()
        predicted = getattr(qctx, "predicted_work_ns", 0) if qctx else 0
        candidates = []
        if predicted and predicted > 0:
            candidates.append(predicted / max(1, num_partitions))
        if finished_ns:
            s = sorted(finished_ns)
            candidates.append(s[min(len(s) - 1,
                                    int(round(0.95 * (len(s) - 1))))])
        if not candidates:
            return None
        # the tighter prior wins: an overshooting flat/calibrated estimate
        # must not blind the scheduler to a task 10x slower than every
        # sibling it can SEE finished (minRuntimeMs floors the race)
        pred_task_ns = min(candidates)
        return max(self.spec_min_runtime_ms * 1e6,
                   self.spec_multiplier * pred_task_ns)

    def _spawn_attempt(self, pool: "cf.ThreadPoolExecutor", p: int,
                       fn: Callable[[int], T],
                       speculative: bool) -> _Attempt:
        """Submit one racing attempt with its own task-scoped token, so
        the losing duplicate can be cancelled without touching the query
        token (which is terminal for the whole query)."""
        token = CX.CancelToken()
        attempt = _Attempt(None, token, speculative)
        cctx = contextvars.copy_context()
        attempt.future = pool.submit(cctx.run, self._run_task_scoped, p,
                                     fn, token, speculative, attempt)
        return attempt

    def _run_task_scoped(self, p: int, fn: Callable[[int], T],
                         token: "CX.CancelToken", speculative: bool,
                         attempt: _Attempt) -> T:
        from spark_rapids_tpu.obs.trace import wall_ns

        attempt.mark_started(wall_ns())
        handle = CX.set_task_token(token)
        try:
            if speculative:
                # its own span: the traced timeline shows the duplicate
                # racing the straggler it shadows
                with obs_span(f"speculate:p{p}", kind="site"):
                    return self._run_task(p, fn)
            return self._run_task(p, fn)
        finally:
            CX.reset_task_token(handle)

    @staticmethod
    def _cancel_losers(attempts: List[_Attempt], winner: _Attempt) -> None:
        for a in attempts:
            if a is winner:
                continue
            a.future.cancel()
            a.token.cancel("speculation: sibling attempt won")

    def _run_job_speculative(self, pool: "cf.ThreadPoolExecutor",
                             num_partitions: int,
                             fn: Callable[[int], T]) -> List[T]:
        """run_job's harvest loop with straggler speculation: identical
        results and failure typing, plus at most ONE speculative
        duplicate per straggling task; first completion wins, the loser
        unwinds through its task-scoped token. Idempotency contract:
        `fn` must re-read from its source/piece-range per call and never
        hand shared device buffers across attempts — the same property
        task retry already requires of it."""
        from spark_rapids_tpu.obs.trace import wall_ns

        pause_clock.start()
        tok = CX.current_token()
        # straggler detection needs a steady cadence even with no cancel
        # token to poll: the idle long-wait would sleep through the whole
        # window in which a duplicate could still win
        poll = _RESULT_POLL_S
        deadline_ns = None
        if self.task_timeout_s:
            deadline_ns = wall_ns() + int(self.task_timeout_s * 1e9)
            poll = min(poll, self.task_timeout_s)
        attempts = {p: [self._spawn_attempt(pool, p, fn, False)]
                    for p in range(num_partitions)}
        results: dict = {}
        finished_ns: List[int] = []
        try:
            while len(results) < num_partitions:
                live = [a.future
                        for p, al in attempts.items() if p not in results
                        for a in al if not a.future.done()]
                if live:
                    cf.wait(live, timeout=poll,
                            return_when=cf.FIRST_COMPLETED)
                if tok is not None:
                    tok.check("job.await")
                now = wall_ns()
                for p in range(num_partitions):
                    if p in results:
                        continue
                    al = attempts[p]
                    winner = None
                    errors: List[BaseException] = []
                    for a in al:
                        if not a.future.done():
                            continue
                        try:
                            res = a.future.result(timeout=0)
                        except cf.CancelledError:
                            continue  # loser cancelled before starting
                        except BaseException as e:  # noqa: BLE001 — attempt race harvest; losers re-raise below
                            errors.append(e)
                        else:
                            winner = (a, res)
                            break
                    if winner is not None:
                        a, res = winner
                        results[p] = res
                        # on the straggler's own clock: a sibling that
                        # finished across a pause must not inflate the p95
                        finished_ns.append(a.runtime_ns(now))
                        if a.speculative:
                            M.record_speculative_win()
                        self._cancel_losers(al, a)
                        continue
                    if all(a.future.done() for a in al):
                        # every racing attempt failed: surface the real
                        # failure, never a loser's own cancellation
                        real = [e for e in errors
                                if not CX.is_cancellation(e)] or errors
                        if real:
                            raise real[0]
                        raise TaskFailedError(
                            p, len(al),
                            RuntimeError("all attempts cancelled"))
                    if deadline_ns is not None and now >= deadline_ns:
                        for al2 in attempts.values():
                            for a in al2:
                                a.future.cancel()
                        raise TaskFailedError(
                            p, 1, TaskTimeoutError(
                                f"partition task {p} exceeded "
                                f"{self.task_timeout_s:.1f}s")) from None
                done_frac = len(results) / num_partitions
                if results and done_frac >= self.spec_quantile and \
                        len(results) < num_partitions:
                    thr_ns = self._speculation_threshold_ns(
                        num_partitions, finished_ns)
                    if thr_ns is not None:
                        for p in range(num_partitions):
                            if p in results:
                                continue
                            al = attempts[p]
                            if len(al) > 1:
                                continue  # one duplicate max
                            a0 = al[0]
                            # a still-QUEUED task is not a straggler — a
                            # duplicate would queue right behind it
                            if a0.work is None or \
                                    not a0.future.running():
                                continue
                            if a0.runtime_ns(now) < thr_ns:
                                continue
                            al.append(self._spawn_attempt(
                                pool, p, fn, True))
                            M.record_speculative_task()
            # losers unwind fast (their task tokens fired and every
            # cancel-aware wait polls them) but the query must not report
            # complete while a loser still holds pool slots or semaphore
            # permits — reclamation is part of the result contract
            losers = [a.future for al in attempts.values() for a in al
                      if not a.future.done()]
            if losers:
                cf.wait(losers, timeout=_CANCEL_DRAIN_S)
            return [results[p] for p in range(num_partitions)]
        except (CX.TpuQueryCancelled, CX.TpuOverloadedError):
            self._drain_cancelled([a.future for al in attempts.values()
                                   for a in al])
            raise

    def _submit(self, pool: "cf.ThreadPoolExecutor", p: int,
                fn: Callable[[int], T]) -> "cf.Future":
        """Submit one partition task, carrying the submitting thread's
        contextvars (the ambient QueryContext above all — per-tenant
        metrics, breaker, fault injector, and retry budget must follow
        the query onto the shared worker pool, docs/serving.md)."""
        cctx = contextvars.copy_context()
        return pool.submit(cctx.run, self._run_task, p, fn)

    def run_job_iter(self, num_partitions: int,
                     fn: Callable[[int], T]) -> Iterator[T]:
        """Yield per-partition results as they complete (unordered).
        Mirrors run_job's inline fast path: 0/1-partition jobs never
        touch the pool (single-partition interactive queries are the
        latency case the issue-ahead sink exists for)."""
        if num_partitions == 0:
            return
        CX.check_cancel("job.submit")
        if num_partitions == 1:
            yield self._run_task(0, fn)
            return
        pool = self._ensure_pool()
        futures = [self._submit(pool, p, fn)
                   for p in range(num_partitions)]
        tok = CX.current_token()
        poll = _RESULT_POLL_S if tok is not None else _IDLE_POLL_S
        pending = set(futures)
        try:
            while pending:
                done, pending = cf.wait(pending, timeout=poll,
                                        return_when=cf.FIRST_COMPLETED)
                if not done and tok is not None:
                    tok.check("job.await")
                for f in done:
                    # already completed (cf.wait returned it): timeout=0
                    # can never block
                    yield f.result(timeout=0)
        finally:
            # a finally, not an except: a cancellation observed by the
            # CONSUMER (the sink loop's own check_cancel) aborts this
            # generator with GeneratorExit at the yield, which an except
            # clause would miss. Abandonment (cancel OR early-exit)
            # cancels the unstarted remainder; only a real cancellation
            # additionally WAITS for in-flight tasks — an early-exiting
            # LIMIT consumer must not block behind them.
            if pending:
                for f in futures:
                    f.cancel()
                if tok is not None and tok.cancelled:
                    cf.wait(futures, timeout=_CANCEL_DRAIN_S)


def run_job_or_serial(scheduler: Optional[TaskScheduler],
                      num_partitions: int,
                      fn: Callable[[int], T]) -> List[T]:
    """The one way an exec materializes partitions: the session scheduler
    when one is in scope (task retries, budget, timeout, semaphore
    auto-release), else the serial fallback below — so a scheduler-policy
    change never needs to visit every exec's else-branch."""
    if scheduler is not None:
        return scheduler.run_job(num_partitions, fn)
    return run_serial(num_partitions, fn)


def run_serial(num_partitions: int, fn: Callable[[int], T]) -> List[T]:
    """Serial fallback for execution paths with no scheduler in scope
    (direct exec tests): runs each partition on the caller thread, ALWAYS
    releasing the admission semaphore after each — without this, a partition
    body that acquires and then raises would leak its permits forever on
    the calling thread (the scheduler's completion-listener analog covers
    only pooled tasks)."""
    out: List[T] = []
    for p in range(num_partitions):
        # same cancellation chokepoint the pooled path polls per attempt
        CX.check_cancel("job.serial")
        try:
            out.append(fn(p))
        finally:
            TpuSemaphore.get().release_if_necessary(current_task_id())
    return out
