"""Flight recorder + calibrated cost observatory tests
(docs/observability.md).

Pins the subsystem's load-bearing contracts:

- STORE BOUNDS: retention/rotation honors `obs.history.maxBytes` under a
  200-record loop; concurrent writers never interleave partial JSON
  lines (one line = one valid record); a corrupt trailing line on
  startup is skipped, never fatal.
- ZERO DEVICE FOOTPRINT: flagship q1/q5 deviceDispatches and
  fencesPerQuery are IDENTICAL with `obs.history.enabled` on vs off
  (the recorder is write-behind — pure host bookkeeping).
- CALIBRATION LOOP: after a >= 20-query warmup the fitted CostModel's
  wall-time prediction for the flagship lands within 3x of measured on
  the CPU backend, EXPLAIN ANALYZE shows the per-operator prediction-
  error column, and the admission-time deadline feasibility check
  PROVABLY consumes the fitted coefficients (a tight deadline the flat
  fallback admits is rejected under a slower calibrated class, and vice
  versa).
- KILLED-QUERY RECORDS: a query killed mid-flight (cancel.race
  injection, tracing on) still closes its open spans, exports valid
  Perfetto JSON, reclaims everything it held, and persists a history
  record tagged with how it died.
"""

import json
import os
import threading

import numpy as np
import pytest

import spark_rapids_tpu as srt
from spark_rapids_tpu import conf as C
from spark_rapids_tpu.engine import cancel as CX
from spark_rapids_tpu.obs import calibrate as CAL
from spark_rapids_tpu.obs import history as OH
from spark_rapids_tpu.obs.history import QueryHistoryStore, read_records
from spark_rapids_tpu.plan import functions as F
from spark_rapids_tpu.utils import metrics as M


def _mk_df(session, seed=7, n=4096, num_partitions=2):
    rng = np.random.default_rng(seed)
    data = {
        "k": rng.integers(0, 32, n).astype(np.int64),
        "a": rng.integers(-1000, 1000, n).astype(np.int64),
        "b": rng.random(n).astype(np.float32),
    }
    return session.createDataFrame(
        data, [("k", "long"), ("a", "long"), ("b", "float")],
        num_partitions=num_partitions)


def _flagship(df):
    return (df.filter((F.col("a") % 3 != 0) & (F.col("b") < 0.9))
              .withColumn("c", F.col("a") * 2 + 1)
              .groupBy("k")
              .agg(F.sum("c").alias("s"), F.count("*").alias("n"),
                   F.max("a").alias("m")))


def _enable_history(session, tmp_path, **extra):
    path = str(tmp_path / "history.jsonl")
    session.set_conf(C.OBS_HISTORY_ENABLED.key, True)
    session.set_conf(C.OBS_HISTORY_PATH.key, path)
    for k, v in extra.items():
        session.set_conf(k, v)
    return path


# ---------------------------------------------------------------------------
# Store bounds (the satellite's 3 pins; driven at the store API so the
# 200-query loop costs milliseconds, not minutes)
# ---------------------------------------------------------------------------
def test_store_rotation_honors_max_bytes(tmp_path):
    path = str(tmp_path / "h.jsonl")
    store = QueryHistoryStore(path, max_bytes=4096, queue_depth=512)
    try:
        payload = "x" * 80
        for i in range(200):
            assert store.enqueue({"qid": f"q-{i}", "pad": payload})
        assert store.flush(10.0)
        snap = store.snapshot()
        assert snap["records_written"] == 200
        assert snap["compactions"] > 0
        # the retention bound holds: never past maxBytes + one record
        assert os.path.getsize(path) <= 4096 + 120, snap
        recs = read_records(path)
        # rotation keeps the NEWEST records (half-bound compaction)
        assert recs, snap
        assert recs[-1]["qid"] == "q-199"
        ids = [int(r["qid"].split("-")[1]) for r in recs]
        assert ids == sorted(ids)
        assert min(ids) > 0  # oldest records were compacted away
    finally:
        store.close()


def test_concurrent_writers_never_interleave_lines(tmp_path):
    path = str(tmp_path / "h.jsonl")
    store = QueryHistoryStore(path, max_bytes=1 << 20, queue_depth=4096)
    try:
        n_threads, per_thread = 8, 50

        def writer(t):
            for i in range(per_thread):
                store.enqueue({"qid": f"t{t}-{i}",
                               "blob": "y" * (37 + (i % 11))})

        threads = [threading.Thread(target=writer, args=(t,))
                   for t in range(n_threads)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        assert store.flush(10.0)
        # EVERY line parses — a single interleaved byte would break one
        with open(path, "rb") as fh:
            lines = [ln for ln in fh.read().splitlines() if ln.strip()]
        assert len(lines) == n_threads * per_thread
        seen = set()
        for ln in lines:
            rec = json.loads(ln)  # raises on any torn line
            seen.add(rec["qid"])
        assert len(seen) == n_threads * per_thread
    finally:
        store.close()


def test_corrupt_trailing_line_skipped_not_fatal(tmp_path):
    path = str(tmp_path / "h.jsonl")
    with open(path, "wb") as fh:
        fh.write(b'{"qid": "good-1"}\n{"qid": "good-2"}\n')
        fh.write(b'{"qid": "torn", "oops": tru')  # crash mid-append
    recs = read_records(path)
    assert [r["qid"] for r in recs] == ["good-1", "good-2"]
    # a store opened over the corrupt file keeps appending whole lines
    store = QueryHistoryStore(path, max_bytes=1 << 20)
    try:
        store.enqueue({"qid": "good-3"})
        assert store.flush(10.0)
        recs = read_records(path)
        assert recs[-1]["qid"] == "good-3"
        assert len(recs) == 3
    finally:
        store.close()


def test_oversized_record_dropped_not_written(tmp_path):
    store = QueryHistoryStore(str(tmp_path / "h.jsonl"), max_bytes=4096)
    try:
        store.enqueue({"qid": "big", "blob": "z" * 8192})
        assert store.flush(10.0)
        assert store.snapshot()["records_dropped"] == 1
        assert store.snapshot()["records_written"] == 0
    finally:
        store.close()


# ---------------------------------------------------------------------------
# Session wiring: records persist with signature/status/operators, and
# the recorder adds ZERO device work
# ---------------------------------------------------------------------------
def test_query_records_persisted_with_signature_and_operators(
        session, tmp_path):
    path = _enable_history(session, tmp_path)
    q = _flagship(_mk_df(session))
    q.collect()
    q.collect()
    store = OH.active_store()
    assert store is not None and store.flush(10.0)
    recs = read_records(path)
    assert len(recs) == 2
    for rec in recs:
        assert rec["status"] == "ok"
        assert rec["tenant"] == "default"
        assert rec["wall_ns"] > 0
        assert rec["metrics"].get(M.DEVICE_DISPATCHES, 0) > 0
        assert rec["operators"], rec
        assert all(op["class"] in CAL.CLASSES for op in rec["operators"])
        assert rec["classes"], rec
        assert rec["predicted"]["dispatches"] is not None
    # same plan -> same structural signature, stable across repeats
    assert recs[0]["plan_sig"] == recs[1]["plan_sig"]
    assert recs[0]["qid"] != recs[1]["qid"]


def test_history_adds_zero_dispatches_and_fences_q1_q5(session, tmp_path):
    """THE acceptance pin: flagship q1/q5 deviceDispatches and
    fencesPerQuery identical with obs.history.enabled on vs off."""
    from spark_rapids_tpu.benchmarks import tpch

    tables = tpch.gen_tables(session, sf=0.0005, num_partitions=2)
    for qname in ("q1", "q5"):
        q = tpch.QUERIES[qname](tables)
        q.collect()  # warm compiles
        q.collect()
        off = dict(session.last_query_metrics)
        _enable_history(session, tmp_path)
        q.collect()  # warm the recorded path
        q.collect()
        on = dict(session.last_query_metrics)
        assert on[M.DEVICE_DISPATCHES] == off[M.DEVICE_DISPATCHES], qname
        assert on[M.FENCES] == off[M.FENCES], qname
        session.set_conf(C.OBS_HISTORY_ENABLED.key, False)
    store = OH.active_store()
    assert store is not None and store.flush(10.0)
    assert store.snapshot()["records_written"] >= 4


# ---------------------------------------------------------------------------
# Calibration: fit quality, EXPLAIN ANALYZE error column, deadline
# feasibility consuming the fitted coefficients
# ---------------------------------------------------------------------------
def test_calibrated_prediction_within_3x_after_warmup(session, tmp_path):
    """>= 20 recorded queries, then the fitted model's wall prediction
    for the flagship lands within 3x of measured (CPU backend), and
    EXPLAIN ANALYZE renders the per-operator prediction-error column."""
    path = _enable_history(session, tmp_path)
    q = _flagship(_mk_df(session))
    for _ in range(21):
        q.collect()
    store = OH.active_store()
    assert store is not None and store.flush(20.0)
    model = CAL.fit_from_store(path)
    assert model.records >= 20
    assert model.coeffs, "no class fitted from 21 records"
    for cc in model.coeffs.values():
        assert cc.samples >= 20
        assert cc.err_p95 >= cc.err_p50 >= 0.0
    CAL.set_active(model)
    # the band is held against the median wall of the recorded queries,
    # not the last one's: one query's wall under busy workers is a draw
    # from a long tail, the fit is a fit of all of them
    walls = [r["wall_ns"] for r in read_records(path)]
    assert len(walls) >= 21
    measured = float(np.median(walls))
    lo, hi, calibrated, _fb = model.predict_report(
        session.last_resource_report, flat_cost_ms=0.0, min_samples=5)
    assert calibrated
    # the 3x acceptance band, both directions
    assert hi >= measured / 3.0, (lo, hi, measured)
    assert lo <= measured * 3.0, (lo, hi, measured)
    text = session.explain_analyze(q._plan)
    assert "pred_wall=" in text, text
    assert "err=" in text, text
    assert "predicted wall time:" in text, text
    # the resource-analysis render gains the calibrated line too
    session.set_conf(C.OBS_HISTORY_ENABLED.key, False)
    explain = session.explain_plan(q._plan)
    assert "predicted wall time:" in explain, explain


def test_auto_refit_installs_model_on_writer_thread(session, tmp_path):
    _enable_history(session, tmp_path,
                    **{C.OBS_CALIBRATION_REFIT_EVERY.key: 5})
    assert CAL.active_model() is None
    q = _flagship(_mk_df(session))
    for _ in range(6):
        q.collect()
    assert OH.active_store().flush(20.0)
    model = CAL.active_model()
    assert model is not None
    assert model.coeffs


def test_deadline_feasibility_consumes_fitted_coefficients(
        session, tmp_path):
    """The acceptance pin: a tight deadline the FLAT fallback would
    admit is rejected once calibration reports a slower measured class —
    and vice versa."""
    q = _flagship(_mk_df(session))
    q.collect()  # warm compiles so the admitted runs stay fast
    session.set_conf("rapids.tpu.engine.deadlineMs", 10000.0)
    session.set_conf("rapids.tpu.engine.deadline.costPerDispatchMs", 0.001)
    # flat fallback: predicted work is microseconds -> admitted
    q.collect()
    assert session.last_query_metrics[M.DEADLINE_REJECTS] == 0
    # calibration reports every class at ~10000s/dispatch -> rejected
    # BEFORE any device dispatch
    d0 = M.dispatch_count()
    CAL.set_active(CAL.CostModel(
        {cls: CAL.ClassCoeffs(ns_per_dispatch=1e13, samples=50)
         for cls in CAL.CLASSES}, source="test"))
    with pytest.raises(CX.TpuDeadlineExceeded) as ei:
        q.collect()
    assert "calibrated cost model" in str(ei.value)
    assert session.last_query_metrics[M.DEADLINE_REJECTS] == 1
    assert M.dispatch_count() == d0
    CX.assert_reclaimed()
    # vice versa: the flat model would reject, the fitted (fast)
    # coefficients admit
    CAL.set_active(CAL.CostModel(
        {cls: CAL.ClassCoeffs(ns_per_dispatch=10.0, samples=50)
         for cls in CAL.CLASSES}, source="test"))
    session.set_conf("rapids.tpu.engine.deadline.costPerDispatchMs", 1e6)
    q.collect()
    assert session.last_query_metrics[M.DEADLINE_REJECTS] == 0
    # below minSamples the same coefficients are NOT trusted: the flat
    # fallback prices again and rejects (the cold-start contract)
    CAL.set_active(CAL.CostModel(
        {cls: CAL.ClassCoeffs(ns_per_dispatch=10.0, samples=1)
         for cls in CAL.CLASSES}, source="test"))
    with pytest.raises(CX.TpuDeadlineExceeded):
        q.collect()


# ---------------------------------------------------------------------------
# Killed queries: closed spans, valid Perfetto, tagged history record
# ---------------------------------------------------------------------------
def test_cancelled_query_closes_spans_and_records_history(
        session, tmp_path):
    """cancel.race injection with tracing + history on: the killed query
    still closes every span (valid Perfetto durations), reclaims what it
    held, and persists a record tagged 'cancelled'."""
    path = _enable_history(session, tmp_path)
    session.set_conf(C.OBS_TRACING.key, True)
    session.set_conf("rapids.tpu.test.faultInjection.enabled", True)
    session.set_conf("rapids.tpu.test.faultInjection.seed", 0)
    session.set_conf("rapids.tpu.test.faultInjection.sites",
                     "cancel.race:cancel")
    session.set_conf("rapids.tpu.test.faultInjection.rate", 1.0)
    with pytest.raises(CX.TpuQueryCancelled):
        _flagship(_mk_df(session)).collect()
    CX.assert_reclaimed()
    trace = session.last_query_trace
    assert trace is not None
    # the satellite pin: a mid-flight kill leaves NO open span behind
    assert all(sp.end_ns is not None for sp in trace.spans()), \
        trace.render()
    doc = json.loads(trace.to_perfetto_json())
    assert all(ev["dur"] >= 0.0 for ev in doc["traceEvents"]
               if ev["ph"] == "X")
    assert trace.find("query.cancelled"), trace.render()
    store = OH.active_store()
    assert store is not None and store.flush(10.0)
    recs = read_records(path)
    assert recs and recs[-1]["status"] == "cancelled"
    assert any(ev["kind"] == "cancel" for ev in recs[-1]["events"])


def test_deadline_rejected_query_records_deadline_status(
        session, tmp_path):
    path = _enable_history(session, tmp_path)
    session.set_conf("rapids.tpu.engine.deadlineMs", 5000.0)
    session.set_conf("rapids.tpu.engine.deadline.costPerDispatchMs",
                     100000.0)
    with pytest.raises(CX.TpuDeadlineExceeded):
        _flagship(_mk_df(session)).collect()
    store = OH.active_store()
    assert store is not None and store.flush(10.0)
    recs = read_records(path)
    assert recs and recs[-1]["status"] == "deadline"


# ---------------------------------------------------------------------------
# Serving surface: snapshots + Prometheus gauges
# ---------------------------------------------------------------------------
def test_server_history_and_calibration_surfacing(tmp_path):
    from spark_rapids_tpu.engine.server import TpuServer

    path = str(tmp_path / "server-history.jsonl")
    server = TpuServer({
        C.OBS_HISTORY_ENABLED.key: True,
        C.OBS_HISTORY_PATH.key: path,
        C.OBS_CALIBRATION_REFIT_EVERY.key: 2,
    })
    try:
        s = server.connect("obs-hist")
        q = _flagship(_mk_df(s))
        for _ in range(3):
            q.collect()
        assert OH.active_store().flush(20.0)
        hist = server.history_snapshot()
        assert hist["records_written"] == 3
        assert hist["bytes"] > 0
        assert 0.0 < hist["occupancy"] < 1.0
        cal = server.calibration_snapshot()
        assert cal["active"] is True
        assert cal["classes"], cal
        for cls, cc in cal["classes"].items():
            assert cls in CAL.CLASSES
            assert cc["samples"] >= 1
            assert "errP50" in cc and "errP95" in cc
        snap = server.metrics_snapshot()
        assert snap["history"]["records_written"] == 3
        assert snap["calibration"]["active"] is True
        text = server.metrics_prometheus()
        assert "srt_history_bytes" in text
        assert "srt_history_records_written_total 3" in text
        assert "srt_calibration_active 1" in text
        assert 'srt_cost_class_prediction_error_ratio{' in text
        assert 'quantile="0.95"' in text
    finally:
        server.stop()
    # teardown clears the shared observatory state
    assert OH.active_store() is None
    assert CAL.active_model() is None


def test_history_off_is_true_noop(session):
    _flagship(_mk_df(session)).collect()
    assert OH.active_store() is None
    assert session.last_query_trace is None  # history off => no tracer


# ---------------------------------------------------------------------------
# Fitting units
# ---------------------------------------------------------------------------
def test_fit_is_robust_to_repeated_query_warmup():
    """A warmup of ONE repeated query (constant dispatches/rows) must
    not destabilize the fit — the median estimator predicts the median
    wall exactly where least squares would be degenerate."""
    recs = [{"classes": {"agg": {"wall_ns": 1e6 + i * 1e4,
                                 "dispatches": 4, "rows": 1000,
                                 "bytes": 0}}}
            for i in range(25)]
    model = CAL.fit(recs)
    cc = model.coeffs["agg"]
    assert cc.samples == 25
    pred = cc.predict_ns(4, 1000)
    mid = 1e6 + 12 * 1e4
    assert 0.5 * mid <= pred <= 2.0 * mid
    assert cc.err_p95 < 0.25


def test_fit_excludes_killed_query_records():
    """A cancelled/deadline query's spans are force-closed at kill time
    — its class walls measure where it died, not what an operator
    costs. Such records persist for observability but never calibrate
    (the review-hardening pin)."""
    good = {"status": "ok", "wall_ns": 2e6,
            "classes": {"agg": {"wall_ns": 1e6, "dispatches": 2,
                                "rows": 0, "bytes": 0}}}
    bad = {"status": "cancelled", "wall_ns": 30e9,
           "classes": {"agg": {"wall_ns": 30e9, "dispatches": 2,
                               "rows": 0, "bytes": 0}}}
    model = CAL.fit([dict(good) for _ in range(6)]
                    + [dict(bad) for _ in range(6)])
    cc = model.coeffs["agg"]
    assert cc.samples == 6
    assert cc.ns_per_dispatch == 0.5e6
    assert model.overhead_samples == 6


def test_fit_excludes_self_healed_records():
    """A self-healed run's measured walls include killed and raced
    attempts (speculation losers, watchdog-released wedges, a
    device-loss replay): obs/history.py tags the record self_healed and
    the calibrator keeps it out of the per-class fits, exactly like
    host runs (the is_host_run precedent)."""
    healed_rec = OH.build_record(
        "q-sh", "default", "ok", None, int(5e6),
        {"speculativeTasks": 1, "speculativeWins": 1}, None, None, [])
    assert healed_rec.get("self_healed") is True
    for counter in ("watchdogKills", "deviceResets"):
        rec = OH.build_record("q-sh2", "default", "ok", None, int(5e6),
                              {counter: 1}, None, None, [])
        assert rec.get("self_healed") is True, counter
    clean_rec = OH.build_record(
        "q-ok", "default", "ok", None, int(5e6),
        {"deviceDispatches": 4}, None, None, [])
    assert "self_healed" not in clean_rec
    good = {"status": "ok",
            "classes": {"agg": {"wall_ns": 1e6, "dispatches": 2,
                                "rows": 0, "bytes": 0}}}
    healed = {"status": "ok", "self_healed": True,
              "classes": {"agg": {"wall_ns": 9e9, "dispatches": 2,
                                  "rows": 0, "bytes": 0}}}
    model = CAL.fit([dict(good) for _ in range(6)]
                    + [dict(healed) for _ in range(6)])
    cc = model.coeffs["agg"]
    assert cc.samples == 6
    assert cc.ns_per_dispatch == 0.5e6


def test_fit_ignores_malformed_records():
    recs = [{"classes": {"sort": {"wall_ns": 5e6, "dispatches": 2,
                                  "rows": 0, "bytes": 0}}},
            {"classes": "not-a-dict"},
            {"no_classes": True},
            {"classes": {"sort": {"wall_ns": "NaN?", "dispatches": []}}}]
    model = CAL.fit(recs)
    assert model.coeffs["sort"].samples == 1


def test_classify_covers_engine_names():
    for name, cls in (
            ("TpuFileScanExec", "scan"),
            ("HostToDeviceExec", "scan"),
            ("TpuFilterExec", "filter-project"),
            ("TpuFusedStage(1)", "filter-project"),
            ("TpuHashAggregateExec(partial)", "agg"),
            ("TpuShuffledHashJoinExec", "join"),
            ("TpuSortExec", "sort"),
            ("TpuShuffleExchangeExec(HashPartitioning)", "exchange"),
            ("DeviceToHost", "exchange"),
            ("TpuSpmdStage(1)[PartialAgg->AllToAll->FinalAgg]",
             "spmd-stage"),
            ("SomethingUnheardOf", "other")):
        assert CAL.classify(name) == cls, name


def test_bench_trajectory_ingestion(tmp_path):
    bench = {"metric": "x", "value": 1.0,
             "op_wall": {"TpuHashAggregateExec(partial)":
                         {"seconds": 0.25, "calls": 3,
                          "deviceDispatches": 5}}}
    with open(tmp_path / "BENCH_r99.json", "w") as fh:
        json.dump(bench, fh)
    recs = CAL.bench_records(str(tmp_path))
    assert len(recs) == 1
    assert recs[0]["classes"]["agg"]["wall_ns"] == 0.25e9
    model = CAL.fit(recs, source="bench")
    assert model.coeffs["agg"].ns_per_dispatch == 0.25e9 / 5
