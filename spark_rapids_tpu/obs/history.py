"""Flight recorder: a bounded on-disk JSONL query-history store
(docs/observability.md).

PR 11's span tree and EXPLAIN ANALYZE print measured-vs-predicted numbers
— and the signal dies with the process. This module persists it: at query
end the session enqueues one record per query (plan signature, per-
operator measured spans flattened from the trace, the PR 3 analyzer's
predicted intervals, correlated engine events, terminal status), a single
daemon writer appends it as ONE JSON line, and the calibration layer
(obs/calibrate.py) fits per-operator-class cost coefficients from the
accumulated history.

Contracts (pinned by tests/test_history.py):

- WRITE-BEHIND: the query path only snapshots already-host-resident
  state (metric counters, the finished span tree, the resource report)
  and enqueues; flattening + JSON encoding + disk IO run on the writer
  thread. Zero device dispatches, zero host fences — the flagship
  counts are identical with history on vs off.
- ONE LINE = ONE RECORD: the writer serializes whole lines under one
  lock; concurrent tenants can never interleave partial JSON. A corrupt
  trailing line (crash mid-append) is skipped on read, never fatal.
- BOUNDED: `rapids.tpu.obs.history.maxBytes` caps the file — an append
  that would exceed it first compacts the store to the NEWEST records
  totaling at most half the bound. The enqueue queue is bounded too
  (`obs.history.queueDepth`); overflow drops records (counted) rather
  than blocking a completing query.
"""

from __future__ import annotations

import hashlib
import itertools
import json
import os
import tempfile
import threading
from collections import deque
from typing import Dict, List, Optional

from spark_rapids_tpu import conf as C
from spark_rapids_tpu.obs.trace import wall_ns

# engine events correlated into each record (structured rows sharing the
# query id): the counter names whose non-zero per-query values become
# event rows, labeled by kind
_EVENT_COUNTERS = (
    ("retries", "retry"),
    ("splitRetries", "retry"),
    ("fetchRetries", "retry"),
    ("cpuFallbackEvents", "fallback"),
    ("checkedReplays", "replay"),
    ("aqeReplans", "aqe"),
    ("skewSplits", "aqe"),
    ("joinDemotions", "aqe"),
    ("joinPromotions", "aqe"),
    ("shedQueries", "shed"),
    ("cancelledQueries", "cancel"),
    ("deadlineRejects", "deadline"),
    ("admissionWaits", "admission"),
    # self-healing recovery events (docs/fault-tolerance.md): the serving
    # layer and calibration read flaky hardware off these rows
    ("speculativeTasks", "speculation"),
    ("speculativeWins", "speculation"),
    ("watchdogKills", "watchdog"),
    ("deviceResets", "device"),
)

# counters whose presence marks a record's measured walls as POLLUTED by
# self-healing (a speculated straggler, a watchdog-released wedge, a
# device-loss replay): the calibration layer must exclude such records
# from per-class fits exactly like is_host_run excludes host runs
_SELF_HEALED_COUNTERS = ("speculativeTasks", "watchdogKills",
                         "deviceResets")

_QID = itertools.count(1)


def next_query_id(tenant: str) -> str:
    return f"{tenant}-{next(_QID)}"


def plan_fingerprint(physical) -> Optional[str]:
    """Cheap structural signature of a final physical plan: the sha1 of
    its node-name tree. Stable across repeats of the same plan shape,
    cheap enough for the query-completion path (one tree walk, host
    only)."""
    if physical is None:
        return None
    names: List[str] = []
    try:
        physical.foreach(lambda n: names.append(n.node_name()))
    except Exception:  # noqa: BLE001 - a half-built plan still records
        return None
    return hashlib.sha1("|".join(names).encode()).hexdigest()[:16]


def _interval(iv) -> Optional[List[float]]:
    if iv is None:
        return None
    lo = getattr(iv, "lo", None)
    hi = getattr(iv, "hi", None)
    if lo is None:
        return None
    f = float("inf")
    return [float(lo) if lo != f else -1.0, float(hi) if hi != f else -1.0]


def build_record(qid: str, tenant: str, status: str, plan_sig,
                 wall_ns_total: int, counters: Dict[str, int], trace,
                 report, aqe_notes: List[str],
                 placement: Optional[dict] = None,
                 host_op_rows: Optional[List[tuple]] = None) -> dict:
    """Flatten one finished query into its history record (runs on the
    WRITER thread — everything passed in is immutable/finished by the
    time the session enqueued it)."""
    from spark_rapids_tpu.obs import calibrate as CAL

    import time

    rec: dict = {
        "qid": qid,
        "tenant": tenant,
        "status": status,
        "plan_sig": plan_sig,
        # tpulint: naked-timer -- absolute wall date stamped into the
        # persisted record (provenance, not engine timing)
        "ts": time.time(),
        "wall_ns": int(wall_ns_total),
        "metrics": {k: v for k, v in sorted(counters.items()) if v},
    }
    if any(counters.get(k) for k in _SELF_HEALED_COUNTERS):
        # provenance tag (the is_host_run precedent): killed/speculated
        # attempts inflate measured walls, so obs/calibrate.py keeps
        # these records out of the per-class fits
        rec["self_healed"] = True
    # per-operator measured spans flattened from the PR 11 trace
    ops: Dict[str, dict] = {}
    events: List[dict] = []
    sites: Dict[str, dict] = {}
    if trace is not None:
        for sp in trace.spans():
            if sp.kind == "op":
                rec_op = ops.setdefault(
                    sp.name, {"calls": 0, "wall_ns": 0, "dispatches": 0})
                rec_op["calls"] += 1
                rec_op["wall_ns"] += sp.duration_ns
                rec_op["dispatches"] += sp.counts.get("deviceDispatches", 0)
            elif sp.kind == "site":
                # one row a site NAME, not a span: a scan opens a few
                # spans a column chunk (scan.read, scan.decode, ...), and
                # the record must stay a line, not a timeline
                ev = sites.setdefault(sp.name, {"kind": "site",
                                                "name": sp.name,
                                                "calls": 0, "wall_ns": 0})
                ev["calls"] += 1
                ev["wall_ns"] += sp.duration_ns
                for k, v in sp.counts.items():
                    ev[k] = ev.get(k, 0) + v
        events.extend(sites.values())
        rec["dropped_spans"] = trace.dropped_spans
    rec["operators"] = [
        {"name": name, "class": CAL.classify(name), **vals}
        for name, vals in sorted(ops.items())]
    # per-class roll-up: the calibration layer's fitting unit (wall +
    # dispatches from the trace; rows from the analyzer's estimates are
    # plan-time, so the roll-up stays measured-only here)
    classes: Dict[str, dict] = {}
    for op in rec["operators"]:
        cl = classes.setdefault(op["class"],
                                {"wall_ns": 0, "dispatches": 0, "rows": 0,
                                 "bytes": 0})
        cl["wall_ns"] += op["wall_ns"]
        cl["dispatches"] += op["dispatches"]
    for key, kind in _EVENT_COUNTERS:
        n = counters.get(key, 0)
        if n:
            events.append({"kind": kind, "name": key, "count": n})
    for note in aqe_notes or ():
        events.append({"kind": "aqe", "name": "rewrite", "detail": note})
    rec["events"] = events
    if report is not None:
        rec["predicted"] = {
            "dispatches": _interval(getattr(report, "dispatches", None)),
            "fences": _interval(getattr(report, "fences", None)),
            "peak_bytes": _interval(getattr(report, "peak_bytes", None)),
            "wall_ns": _interval(getattr(report, "predicted_wall_ns",
                                         None)),
        }
        # row volume per class from the analyzer's EXACT node estimates
        # (the measured side has no per-node row counter that survives
        # plan-cache reuse without a pre-snapshot on the hot path; an
        # exact plan-time row count is the same number)
        for est in getattr(report, "nodes", ()) or ():
            rows_iv = getattr(est, "rows", None)
            if rows_iv is not None and getattr(rows_iv, "is_exact", False):
                cl = classes.get(CAL.classify(est.name))
                if cl is not None:
                    cl["rows"] += int(rows_iv.lo)
    # fold exchange bytes into the class roll-up where the engine
    # measured them (collective bytes are the one per-query byte signal
    # attributable to the exchange tier)
    cb = counters.get("collectiveBytes", 0)
    if cb and "exchange" in classes:
        classes["exchange"]["bytes"] = cb
    elif cb and "spmd-stage" in classes:
        classes["spmd-stage"]["bytes"] = cb
    # host-run synthesis: Cpu operators have no kernel chokepoint that
    # opens op spans, so a zero-dispatch host run (placement analyzer
    # or CPU fallback) would persist an EMPTY class table and the host
    # fit (obs/calibrate.fit_host) would never train. Apportion the
    # measured query wall across the analyzer's host-placed classes by
    # exact row volume — the host model prices on rows alone, so this
    # is exactly the feature/response pair it regresses.
    if wall_ns_total > 0 and \
            (host_op_rows or report is not None) and \
            not counters.get("deviceDispatches") and \
            (counters.get("hostPlacedOps")
             or counters.get("cpuFallbackEvents")):
        rows_by_cls: Dict[str, int] = {}
        if host_op_rows:
            # measured output rows from the executed Cpu nodes — the
            # preferred (exact) feature source
            for op_name, rows in host_op_rows:
                if rows > 0:
                    cl_name = CAL.classify(op_name)
                    rows_by_cls[cl_name] = (rows_by_cls.get(cl_name, 0)
                                            + int(rows))
        else:
            for est in getattr(report, "nodes", ()) or ():
                if getattr(est, "placement", "tpu") != "cpu":
                    continue
                rows_iv = getattr(est, "rows", None)
                if rows_iv is not None and getattr(rows_iv, "is_exact",
                                                   False):
                    cl_name = CAL.classify(est.name)
                    rows_by_cls[cl_name] = (rows_by_cls.get(cl_name, 0)
                                            + int(rows_iv.lo))
        # span-derived classes (engine-level host work like the shuffle
        # write) measured wall but no rows — backfill the feature so the
        # host fit keeps them instead of dropping an all-zero class
        for cl_name, c in classes.items():
            if not c.get("rows") and rows_by_cls.get(cl_name):
                c["rows"] = rows_by_cls[cl_name]
        missing = {cl_name: rows for cl_name, rows in rows_by_cls.items()
                   if cl_name not in classes and rows > 0}
        spent = sum(c.get("wall_ns", 0) for c in classes.values())
        budget = max(0, int(wall_ns_total) - int(spent))
        total_rows = sum(missing.values())
        if total_rows > 0 and budget > 0:
            for cl_name, rows in missing.items():
                classes[cl_name] = {
                    "wall_ns": max(1, int(budget * rows / total_rows)),
                    "dispatches": 0, "rows": rows, "bytes": 0}
    rec["classes"] = classes
    # placement decision + post-hoc regret (plan/placement.py): when the
    # analyzer moved work and predicted the road NOT taken at `altNs`,
    # a measured wall past that prediction is regret — the self-
    # correction signal bad coefficients surface as
    if placement:
        rec["placement"] = dict(placement)
        alt = placement.get("altNs")
        if isinstance(alt, (int, float)) and alt == alt and \
                alt != float("inf") and wall_ns_total > 0:
            rec["placementRegret"] = max(0, int(wall_ns_total - alt))
    return rec


class QueryHistoryStore:
    """One JSONL history file + its write-behind writer thread."""

    def __init__(self, path: str, max_bytes: int, queue_depth: int = 256):
        self.path = path
        self.max_bytes = max(4096, int(max_bytes))
        self.queue_depth = max(1, int(queue_depth))
        self._io_lock = threading.Lock()
        self._cv = threading.Condition()
        self._pending: deque = deque()
        self._in_flight = False
        self._stop = False
        # whether the file's last byte is a known line terminator; False
        # until the first append inspects a pre-existing file
        self._tail_terminated = self._tail_ends_with_newline()
        self.records_written = 0
        self.records_dropped = 0
        self.build_errors = 0
        self.compactions = 0
        # bounded in-memory tail: the automatic refit path reads recent
        # records here instead of re-parsing the file per refit
        self.recent: deque = deque(maxlen=512)
        self._refit_every = 0
        self._since_refit = 0
        # tpulint: naked-thread -- write-behind daemon: deliberately
        # context-free. It serves EVERY tenant's queue for the store's
        # whole lifetime; record builders are closures that captured
        # their query's state at enqueue time, so no ambient
        # QueryContext belongs on this thread.
        self._writer = threading.Thread(
            target=self._writer_loop, name="srt-history-writer",
            daemon=True)
        self._writer.start()

    # -- enqueue (the query-completion path) ---------------------------------
    def enqueue(self, builder) -> bool:
        """Queue a zero-arg record builder; the writer thread calls it,
        JSON-encodes the result, and appends. Returns False (and counts
        a drop) when the queue is at its depth bound."""
        with self._cv:
            if self._stop or len(self._pending) >= self.queue_depth:
                self.records_dropped += 1
                return False
            self._pending.append(builder)
            self._cv.notify()
        return True

    def set_refit_policy(self, every: int) -> None:
        with self._cv:
            self._refit_every = max(0, int(every))

    def set_queue_depth(self, depth: int) -> None:
        """Apply a changed obs.history.queueDepth to the LIVE store (a
        bigger bound takes effect on the next enqueue, without waiting
        for a path change or restart)."""
        with self._cv:
            self.queue_depth = max(1, int(depth))

    # -- writer thread -------------------------------------------------------
    def _writer_loop(self) -> None:
        while True:
            with self._cv:
                while not self._pending and not self._stop:
                    # timed wait: the uncancellable-wait contract — a
                    # stuck notify can never wedge teardown
                    self._cv.wait(timeout=0.2)
                if self._stop and not self._pending:
                    return
                builder = self._pending.popleft()
                # in-flight marker: flush() must not observe "drained"
                # between the pop and the append landing on disk
                self._in_flight = True
            try:
                rec = builder() if callable(builder) else builder
                self._append(rec)
            except Exception:  # noqa: BLE001 - recorder must never throw
                with self._cv:
                    self.build_errors += 1
            self._maybe_refit()
            with self._cv:
                self._in_flight = False

    def _maybe_refit(self) -> None:
        with self._cv:
            if not self._refit_every:
                return
            self._since_refit += 1
            if self._since_refit < self._refit_every:
                return
            self._since_refit = 0
            records = list(self.recent)
        try:
            from spark_rapids_tpu.obs import calibrate as CAL

            CAL.refit_from_records(records)
        except Exception:  # noqa: BLE001 - calibration is best-effort
            with self._cv:
                self.build_errors += 1

    def _append(self, rec: dict) -> None:
        line = json.dumps(rec, separators=(",", ":"), default=str) + "\n"
        data = line.encode("utf-8")
        if len(data) > self.max_bytes:
            with self._cv:
                self.records_dropped += 1
            return
        with self._io_lock:
            size = self._size_locked()
            if size + len(data) > self.max_bytes:
                self._compact_locked(self.max_bytes // 2 - len(data))
                size = self._size_locked()
            with open(self.path, "ab") as fh:
                if size and not self._tail_terminated:
                    # a pre-existing torn trailing line (crash
                    # mid-append) must not absorb this record: terminate
                    # it — it stays one skippable bad line on read
                    fh.write(b"\n")
                fh.write(data)
            self._tail_terminated = True
        with self._cv:
            self.records_written += 1
            self.recent.append(rec)

    def _size_locked(self) -> int:
        try:
            return os.path.getsize(self.path)
        except OSError:
            return 0

    def _tail_ends_with_newline(self) -> bool:
        try:
            with open(self.path, "rb") as fh:
                fh.seek(-1, os.SEEK_END)
                return fh.read(1) == b"\n"
        except (OSError, ValueError):
            return True  # absent/empty file: nothing to terminate

    def _compact_locked(self, keep_bytes: int) -> None:
        """Rewrite the store keeping only the NEWEST complete lines
        totaling at most `keep_bytes` (atomic replace; a crash leaves
        either the old or the new file, both valid JSONL)."""
        keep_bytes = max(0, keep_bytes)
        try:
            with open(self.path, "rb") as fh:
                lines = fh.read().splitlines(keepends=True)
        except OSError:
            return
        kept: List[bytes] = []
        total = 0
        for ln in reversed(lines):
            if total + len(ln) > keep_bytes:
                break
            kept.append(ln)
            total += len(ln)
        kept.reverse()
        tmp = self.path + ".tmp"
        with open(tmp, "wb") as fh:
            fh.writelines(kept)
        os.replace(tmp, self.path)
        self.compactions += 1

    # -- draining / teardown -------------------------------------------------
    def flush(self, timeout_s: float = 10.0) -> bool:
        """Wait (bounded) until every already-enqueued record is on disk;
        True when the queue drained in time."""
        deadline = wall_ns() + int(max(0.0, timeout_s) * 1e9)
        poll = threading.Event()
        while True:
            with self._cv:
                if not self._pending and not self._in_flight:
                    return True
            if wall_ns() >= deadline:
                return False
            poll.wait(0.01)

    def close(self, timeout_s: float = 5.0) -> None:
        with self._cv:
            self._stop = True
            self._cv.notify_all()
        self._writer.join(timeout=max(0.1, timeout_s))

    # -- introspection (server telemetry, tests) -----------------------------
    def snapshot(self) -> dict:
        with self._io_lock:
            size = self._size_locked()
        with self._cv:
            return {
                "path": self.path,
                "bytes": size,
                "max_bytes": self.max_bytes,
                "occupancy": size / self.max_bytes if self.max_bytes else 0.0,
                "records_written": self.records_written,
                "records_dropped": self.records_dropped,
                "build_errors": self.build_errors,
                "compactions": self.compactions,
                "pending": len(self._pending),
            }


def read_records(path: str) -> List[dict]:
    """Parse a history JSONL file tolerantly: malformed lines (a crash
    mid-append leaves at most one, trailing) are skipped, never fatal."""
    out: List[dict] = []
    try:
        with open(path, "rb") as fh:
            raw = fh.read()
    except OSError:
        return out
    for ln in raw.splitlines():
        if not ln.strip():
            continue
        try:
            rec = json.loads(ln)
        except ValueError:
            continue
        if isinstance(rec, dict):
            out.append(rec)
    return out


# ---------------------------------------------------------------------------
# Process-wide store slot (shared-runtime lifetime: session.py tears it
# down with the rest of the shared runtime)
# ---------------------------------------------------------------------------
_STORE_LOCK = threading.Lock()
_STORE: Optional[QueryHistoryStore] = None


def resolve_path(conf) -> str:
    p = conf.get(C.OBS_HISTORY_PATH) or ""
    if p:
        return p
    return os.path.join(tempfile.gettempdir(),
                        f"srt_query_history-{os.getpid()}.jsonl")


def get_store(conf) -> Optional[QueryHistoryStore]:
    """The active history store per the conf (created on first use; a
    path/bound change swaps the store). None while history is off."""
    global _STORE
    if not conf.get(C.OBS_HISTORY_ENABLED):
        return None
    path = resolve_path(conf)
    max_bytes = conf.get(C.OBS_HISTORY_MAX_BYTES)
    depth = conf.get(C.OBS_HISTORY_QUEUE_DEPTH)
    with _STORE_LOCK:
        st = _STORE
        if st is None or st.path != path or st.max_bytes != max_bytes:
            if st is not None:
                st.close()
            # tpulint: shared-state-mutation -- store swap under
            # _STORE_LOCK (lifecycle: first use or a path/bound change)
            st = _STORE = QueryHistoryStore(path, max_bytes, depth)
        st.set_queue_depth(depth)
        st.set_refit_policy(
            conf.get(C.OBS_CALIBRATION_REFIT_EVERY)
            if conf.get(C.OBS_CALIBRATION_ENABLED) else 0)
        return st


def active_store() -> Optional[QueryHistoryStore]:
    return _STORE


def shutdown() -> None:
    global _STORE
    with _STORE_LOCK:
        st = _STORE
        _STORE = None
    if st is not None:
        st.close()
