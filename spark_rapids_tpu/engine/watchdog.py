"""Hung-dispatch watchdog: heartbeat over every in-flight retry-guarded
dispatch (docs/fault-tolerance.md).

A dispatch that goes SILENT — an XLA program that never returns, a fence
that never lands — is the one failure the typed-error machinery cannot
see: nothing raises, the query just burns its deadline budget. This
module closes that gap with ONE scheduler-owned daemon thread that scans
the set of in-flight dispatch registrations on a fixed cadence:

- `with_retry` (engine/retry.py, THE dispatch chokepoint) registers each
  attempt for its whole in-flight window and deregisters the moment the
  attempt returns or raises — the normal path costs one dict insert and
  one delete, no locks on the device path itself.
- An entry silent past its timeout is classified WEDGED (metric:
  watchdogKills): its cooperative release Event is set, so wait-points
  that poll it (today: the injected `wedge` fault kind in
  utils/faultinject.py; a real backend wait loop can adopt the same
  poll) raise a retryable TpuDispatchWedged and the retry combinators
  re-dispatch on fresh buffers.
- An entry STILL silent past 2x its timeout has no cooperative
  wait-point to release (a truly stuck foreign call): the watchdog
  ESCALATES by firing the owning query's CancelToken, so every other
  chokepoint of that query unwinds and reclamation runs instead of the
  whole session wedging behind one thread.

The timeout is cost-calibrated: `watchdog.dispatchTimeoutMs` when set,
else 8x the admission-time CostModel prediction of the query's task wall
(QueryContext.predicted_work_ns, obs/calibrate.py), else a 30s cold-
start default. Silence is time at work (engine/pause_clock.AtWork): time
during which a program is being built is not silence (a cold program's
first dispatch may compile for minutes), and neither is time during which
the whole process stood still. The daemon is deliberately CONTEXT-FREE
(it acts on tokens captured at registration, never on ambient state),
uses only timed waits, and is torn down with the shared session runtime.
"""

from __future__ import annotations

import contextvars
import threading
from typing import Dict, Optional

from spark_rapids_tpu import conf as C
from spark_rapids_tpu.engine import pause_clock
from spark_rapids_tpu.obs.trace import wall_ns
from spark_rapids_tpu.utils import metrics as M

# cold-start silence budget when neither the conf nor the cost model
# offers a prediction
_DEFAULT_TIMEOUT_MS = 30000.0
# calibrated timeout = this multiple of the predicted per-task wall
_CALIBRATED_MULTIPLE = 8.0
# escalation (query kill) fires at this multiple of the wedge timeout
_ESCALATE_MULTIPLE = 2.0

# the registration covering the CURRENT thread's in-flight attempt, so a
# cooperative wait-point (the injected wedge) can find its own entry
_CURRENT_ENTRY: contextvars.ContextVar = contextvars.ContextVar(
    "srt-watchdog-entry", default=None)


class DispatchEntry:
    """One in-flight dispatch attempt under watch."""

    __slots__ = ("site", "token", "ctx", "work", "timeout_ms",
                 "released", "escalated", "_cvar_token")

    def __init__(self, site: str, token, ctx, start_ns: int,
                 timeout_ms: float):
        self.site = site
        self.token = token          # owning query's CancelToken (or None)
        self.ctx = ctx              # owning QueryContext (or None): the
        # daemon attributes its kills here — it runs with NO ambient
        # context of its own, by design
        # a program's first dispatch includes its build (or the wait for
        # another thread's build of it), which is not silence; nor is a
        # stopped process
        self.work = pause_clock.AtWork(start_ns)
        self.timeout_ms = timeout_ms
        # set by the watchdog when the entry is classified wedged: the
        # cooperative release every wait-point of this attempt polls
        self.released = threading.Event()
        self.escalated = False
        self._cvar_token = None

    def silent_ms(self, now_ns: int) -> float:
        """Time in flight with nothing to excuse it."""
        return self.work.ns(now_ns) / 1e6


class DispatchWatchdog:
    """The singleton daemon + in-flight registry (scheduler-owned: the
    session configures it at query start and tears it down with the
    shared runtime, mirroring TaskScheduler's lifecycle)."""

    _instance: Optional["DispatchWatchdog"] = None
    _lock = threading.Lock()

    def __init__(self, timeout_ms: float = 0.0, poll_ms: float = 50.0):
        self.timeout_ms = max(0.0, float(timeout_ms))
        self.poll_ms = max(1.0, float(poll_ms))
        self._mu = threading.Lock()
        self._entries: Dict[int, DispatchEntry] = {}
        self._seq = 0
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        # wedged-site classification for telemetry: site -> kill count
        self._wedged_sites: Dict[str, int] = {}

    # -- lifecycle -----------------------------------------------------------
    @classmethod
    def configure(cls, tpu_conf: "C.TpuConf") -> Optional["DispatchWatchdog"]:
        """Refresh (or disable) the watchdog from the executing session's
        conf; called at every query start like the fault injector."""
        if not tpu_conf.get(C.WATCHDOG_ENABLED):
            cls.shutdown()
            return None
        with cls._lock:
            if cls._instance is None:
                cls._instance = cls()
            inst = cls._instance
        with inst._mu:
            inst.timeout_ms = max(
                0.0, tpu_conf.get(C.WATCHDOG_DISPATCH_TIMEOUT_MS))
            inst.poll_ms = max(1.0, tpu_conf.get(C.WATCHDOG_POLL_MS))
        return inst

    @classmethod
    def get(cls) -> Optional["DispatchWatchdog"]:
        return cls._instance

    @classmethod
    def shutdown(cls) -> None:
        with cls._lock:
            inst = cls._instance
            cls._instance = None
        if inst is not None:
            inst._stop.set()
            th = inst._thread
            if th is not None:
                th.join(timeout=2.0)

    def _ensure_thread(self) -> None:
        """Start the daemon lazily on first registration (a session that
        never dispatches never pays for the thread)."""
        if self._thread is not None:
            return
        with self._mu:
            if self._thread is not None or self._stop.is_set():
                return
            # tpulint: naked-thread -- context-free daemon by design: it
            # acts on tokens captured at registration, never ambient state
            th = threading.Thread(target=self._loop, daemon=True,
                                  name="srt-dispatch-watchdog")
            self._thread = th
        th.start()

    # -- registration (with_retry's chokepoint) ------------------------------
    def _entry_timeout_ms(self) -> float:
        """The silence budget for one dispatch: conf override, else the
        calibrated multiple of the predicted task wall, else cold-start."""
        if self.timeout_ms > 0:
            return self.timeout_ms
        ctx = M.current_query_ctx()
        predicted = getattr(ctx, "predicted_work_ns", 0) if ctx else 0
        if predicted and predicted > 0:
            return max(1.0, _CALIBRATED_MULTIPLE * predicted / 1e6)
        return _DEFAULT_TIMEOUT_MS

    def _register(self, site: str) -> DispatchEntry:
        from spark_rapids_tpu.engine import cancel as CX

        entry = DispatchEntry(site, CX.current_token(),
                              M.current_query_ctx(), wall_ns(),
                              self._entry_timeout_ms())
        with self._mu:
            self._seq += 1
            self._entries[self._seq] = entry
            entry._cvar_token = (self._seq,
                                 _CURRENT_ENTRY.set(entry))
        self._ensure_thread()
        pause_clock.start()
        return entry

    def _deregister(self, entry: DispatchEntry) -> None:
        key, cvar_tok = entry._cvar_token or (None, None)
        with self._mu:
            if key is not None:
                self._entries.pop(key, None)
        if cvar_tok is not None:
            _CURRENT_ENTRY.reset(cvar_tok)
        entry._cvar_token = None

    # -- the daemon ----------------------------------------------------------
    def _loop(self) -> None:
        while not self._stop.wait(self.poll_ms / 1000.0):
            self._scan(wall_ns())

    def _scan(self, now: int) -> None:
        with self._mu:
            entries = list(self._entries.values())
        for entry in entries:
            silent_ms = entry.silent_ms(now)
            if silent_ms < entry.timeout_ms:
                continue
            if not entry.released.is_set():
                # first tier: classify wedged + cooperative release —
                # wait-points polling the event raise a retryable
                # TpuDispatchWedged and the combinators re-dispatch
                entry.released.set()
                with self._mu:
                    self._wedged_sites[entry.site] = \
                        self._wedged_sites.get(entry.site, 0) + 1
                M.record_watchdog_kill()
                if entry.ctx is not None:
                    # per-query attribution: the daemon carries no
                    # ambient context, so _note cannot route this
                    entry.ctx.add(M.WATCHDOG_KILLS, 1)
            elif (not entry.escalated
                  and entry.token is not None
                  and silent_ms >= entry.timeout_ms
                  * _ESCALATE_MULTIPLE):
                # second tier: no cooperative wait-point picked up the
                # release — fire the owning query's token so the rest
                # of the query unwinds and reclaims
                entry.escalated = True
                entry.token.cancel(
                    f"watchdog: dispatch wedged at {entry.site} "
                    f"({silent_ms:.0f}ms silent)")

    # -- introspection -------------------------------------------------------
    def inflight_count(self) -> int:
        with self._mu:
            return len(self._entries)

    def wedged_sites(self) -> Dict[str, int]:
        with self._mu:
            return dict(self._wedged_sites)


# ---------------------------------------------------------------------------
# Module-level chokepoint API (engine/retry.with_retry calls these on every
# attempt: a disabled watchdog costs one None-check)
# ---------------------------------------------------------------------------
def register(site: str) -> Optional[DispatchEntry]:
    inst = DispatchWatchdog._instance
    if inst is None:
        return None
    return inst._register(site)


def deregister(entry: Optional[DispatchEntry]) -> None:
    if entry is None:
        return
    inst = DispatchWatchdog._instance
    if inst is not None:
        inst._deregister(entry)


def simulate_wedge(site: str) -> None:
    """The injected `wedge` fault kind (utils/faultinject.py): model a
    dispatch that hangs until the watchdog intervenes. Waits — cancel-
    aware, bounded — on the current registration's release Event; when
    the watchdog classifies the attempt wedged this raises the retryable
    TpuDispatchWedged exactly as a real released wait-point would. With
    no watchdog running (disabled, or the site is outside with_retry)
    the wait is bounded by the cold-start budget and then raises anyway,
    so an armed wedge can never hang a test run."""
    from spark_rapids_tpu.engine import cancel as CX
    from spark_rapids_tpu.engine.retry import TpuDispatchWedged

    entry = _CURRENT_ENTRY.get()
    inst = DispatchWatchdog._instance
    cap_ms = _DEFAULT_TIMEOUT_MS
    if entry is not None:
        cap_ms = entry.timeout_ms * (_ESCALATE_MULTIPLE + 1.0)
    elif inst is not None and inst.timeout_ms > 0:
        cap_ms = inst.timeout_ms * (_ESCALATE_MULTIPLE + 1.0)
    tok = CX.current_token()
    ttok = CX.current_task_token()
    start = wall_ns()
    released = False
    while (wall_ns() - start) / 1e6 < cap_ms:
        if tok is not None:
            # a cancel/deadline racing the wedge wins (terminal contract)
            tok.check(site)
        if ttok is not None:
            # a speculation loser wedged here must unwind the moment its
            # sibling wins, releasing permits instead of napping the cap
            ttok.check(site)
        if entry is not None and entry.released.wait(timeout=0.02):
            released = True
            break
        if entry is None:
            CX.cancel_aware_sleep(0.02, site=site)
    raise TpuDispatchWedged(
        f"[injected] dispatch wedged at {site}"
        + (" (released by watchdog)" if released
           else " (cold-start cap expired)"))
