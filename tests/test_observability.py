"""Query tracing & engine telemetry tests (docs/observability.md).

Pins the subsystem's load-bearing contracts:

- ZERO-COST OFF: with tracing off the span API returns the shared no-op
  and no trace is recorded;
- ZERO DEVICE FOOTPRINT ON: deviceDispatches and fencesPerQuery on the
  flagship query are IDENTICAL with tracing on vs off (tracing is pure
  host bookkeeping — no extra dispatches, no extra fences);
- span-tree correctness under the scheduler's thread pool: stage spans
  contain their partitions' task spans, per-span metric counts sum to
  the query's own metrics (context propagation), and 3 concurrent
  tenants' traces never absorb each other's increments;
- the Chrome-trace exporter emits valid trace-event JSON;
- EXPLAIN ANALYZE shows measured per-operator wall-time with the
  analyzer's predicted intervals containing the measured dispatches;
- admission waits record DURATION (p50/p95 in the controller snapshot,
  admissionWaitNs per query), not just event counts;
- the Prometheus exposition renders the server snapshot with per-tenant
  counters in the text format.
"""

import json
import re
import threading

import numpy as np
import pytest

import spark_rapids_tpu as srt
from spark_rapids_tpu import conf as C
from spark_rapids_tpu.engine.admission import AdmissionController
from spark_rapids_tpu.engine.server import TpuServer
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
    """The bench.py flagship shape: filter + project + hash aggregate."""
    return (df.filter((F.col("a") % 3 != 0) & (F.col("b") < 0.9))
              .withColumn("c", F.col("a") * 2 + 1)
              .groupBy("k")
              .agg(F.sum("c").alias("s"), F.count("*").alias("n"),
                   F.max("a").alias("m")))


# ---------------------------------------------------------------------------
# Zero-cost off / zero-device-footprint on
# ---------------------------------------------------------------------------
def test_span_api_is_noop_outside_traced_query():
    from spark_rapids_tpu.obs.trace import _NOOP, span, wall_ns

    cm = span("anything", kind="site", some_attr=1)
    assert cm is _NOOP
    with cm as sp:
        assert sp is None
    assert isinstance(wall_ns(), int)


def _write_source(session, num_partitions=3):
    """A device plan over a DOUBLE, a LONG and an INT column: the write
    under test in its two encoders (with deviceEncode on, the CPU backend
    — which has f64 — takes the device encoder; a TPU refuses DOUBLE
    there and takes Arrow's, the path deviceEncode=off forces here)."""
    rng = np.random.default_rng(11)
    n = 6000
    df = session.createDataFrame(
        {"k": rng.integers(0, 50, n).astype(np.int64),
         "v": rng.random(n),
         "d": rng.integers(0, 1000, n).astype(np.int32)},
        [("k", "long"), ("v", "double"), ("d", "int")],
        num_partitions=num_partitions)
    return df.filter(F.col("d") >= 0).withColumn("w", F.col("v") * 2.0), n


def _parquet_source(session, tmp_path, row_groups=2, string=False):
    """lineitem-like files on disk (dictionary-encoded snappy, several
    row groups) and the DataFrame that scans them on the device.
    `string`: with a flag column, the kind the device decodes."""
    import pyarrow as pa
    import pyarrow.parquet as pq

    rng = np.random.default_rng(5)
    rows = 4096
    root = tmp_path / "src"
    root.mkdir()
    for i in range(2):
        cols = {
            "q": rng.integers(1, 51, rows * row_groups).astype(np.int64),
            "p": rng.integers(0, 1000, rows * row_groups).astype(np.int32),
            "x": rng.integers(0, 11, rows * row_groups) / 100.0}
        if string:
            cols["s"] = [f"flag{j % 3}" for j in range(rows * row_groups)]
        table = pa.table(cols)
        pq.write_table(table, str(root / f"f{i}.parquet"),
                       row_group_size=rows, compression="snappy")
    return session.read.parquet(str(root)), root


def _collect_flagship(session, tmp_path):
    _flagship(_mk_df(session)).collect()


def _collect_parquet_scan(session, tmp_path):
    df, _ = _parquet_source(session, tmp_path / "scan")
    df.filter(F.col("p") < 500).agg(F.sum("x")).collect()


def _write_parquet(session, tmp_path):
    df, _ = _write_source(session)
    df.write.parquet(str(tmp_path / "out"))


_ACTIONS = {"flagship": _collect_flagship,
            "parquet_scan": _collect_parquet_scan,
            "write": _write_parquet}


@pytest.mark.parametrize("action", sorted(_ACTIONS))
def test_tracing_off_records_no_trace(session, tmp_path, action):
    (tmp_path / "scan").mkdir()
    _ACTIONS[action](session, tmp_path)
    assert session.last_query_trace is None


@pytest.mark.parametrize("action", sorted(_ACTIONS))
def test_tracing_adds_zero_dispatches_and_zero_fences(session, tmp_path,
                                                      action):
    """THE overhead contract: an action's deviceDispatches and
    fencesPerQuery are identical with tracing on vs off — the flagship
    query, a device-decoded parquet scan (the scan.* spans) and a write
    (a query since PR 25: the write.* spans)."""
    run = _ACTIONS[action]
    for sub in ("w0", "w1", "on0", "on1"):
        (tmp_path / sub / "scan").mkdir(parents=True)
    run(session, tmp_path / "w0")  # warm compiles under tracing-off
    before = M.dispatch_count(), M.fence_count()
    run(session, tmp_path / "w1")
    off = dict(session.last_query_metrics)
    off_process = (M.dispatch_count() - before[0],
                   M.fence_count() - before[1])
    session.set_conf(C.OBS_TRACING.key, True)
    run(session, tmp_path / "on0")  # warm any tracing-path plan-cache use
    before = M.dispatch_count(), M.fence_count()
    run(session, tmp_path / "on1")
    on = dict(session.last_query_metrics)
    assert on[M.DEVICE_DISPATCHES] == off[M.DEVICE_DISPATCHES]
    assert on[M.FENCES] == off[M.FENCES]
    # the process-wide counters, as the benchmark reads them around an
    # action, agree with the per-query ones either way
    assert (M.dispatch_count() - before[0],
            M.fence_count() - before[1]) == off_process
    assert session.last_query_trace is not None


# ---------------------------------------------------------------------------
# A write is a query: scope, span tree, accounting
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("encoder", ["device", "arrow"])
def test_traced_write_span_tree_and_accounting(session, tmp_path, encoder):
    import os

    session.set_conf(C.OBS_TRACING.key, True)
    session.set_conf(C.PARQUET_DEVICE_ENCODE.key, encoder == "device")
    df, n = _write_source(session, num_partitions=3)
    # a query first, so that a write that left last_query_metrics alone
    # would show THIS query's numbers
    _flagship(_mk_df(session)).collect()
    earlier = dict(session.last_query_metrics)
    assert earlier[M.FENCES] >= 1
    out = tmp_path / "out"
    before = M.dispatch_count(), M.fence_count()
    df.write.parquet(str(out))
    process = (M.dispatch_count() - before[0], M.fence_count() - before[1])
    trace = session.last_query_trace
    assert trace is not None and trace.root.name == "query:WriteFile"
    names = [s.name for s in trace.spans()]
    assert names.count("plan") == 1
    assert [c.name for c in trace.root.children] == \
        ["plan", "stage:write", "write.commit"], trace.render()
    stage = trace.find("stage:write")[0]
    assert stage.kind == "stage"
    tasks = [c for c in stage.children if c.kind == "task"]
    assert sorted(t.name for t in tasks) == ["task:p0", "task:p1", "task:p2"]
    for t in tasks:
        assert "write.collect" in [c.name for c in t.children]
    # one write.file a file: rows sum to the rows written, bytes are the
    # closed files' sizes, and the encoder that ran is on record
    files = sorted(str(f) for f in out.glob("*.parquet"))
    spans = trace.find("write.file")
    assert sorted(s.attrs["path"] for s in spans) == files
    assert {s.attrs["encoder"] for s in spans} == {encoder}
    assert sum(s.attrs["rows"] for s in spans) == n
    assert {s.attrs["path"]: s.attrs["bytes"] for s in spans} == \
        {f: os.path.getsize(f) for f in files}
    inner = "write.encode" if encoder == "device" else "write.arrow"
    assert names.count(inner) == len(files)
    # every increment landed on some span, the query's own metrics are
    # the write's (not the earlier query's), and both agree with the
    # process-wide counters read around the action
    totals = trace.counts_total()
    mine = session.last_query_metrics
    assert (totals.get(M.DEVICE_DISPATCHES, 0),
            totals.get(M.FENCES, 0)) == process
    assert (mine[M.DEVICE_DISPATCHES], mine[M.FENCES]) == process
    assert process[0] >= 1
    assert mine != earlier
    # the sink's fence carries its size (the Arrow path downloads whole
    # columns through DeviceToHost; the device encoder downloads pages)
    if encoder == "arrow":
        d2h = trace.find("DeviceToHost")
        assert len(d2h) >= len(files) and process[1] >= len(files)
        assert all(s.attrs["bytes"] > 0 and s.attrs["batches"] >= 1
                   for s in d2h)


SINK_STEPS = ["sink.pack", "sink.wait", "sink.transfer", "sink.finish"]


def _sink_rows(session, tmp_path, action):
    """The rows an action hands back: a collect's own, a write's files
    read back by Arrow."""
    import pyarrow.parquet as pq

    if action == "collect":
        rows = _flagship(_mk_df(session)).collect()
        return sorted(tuple(r) for r in rows)
    session.set_conf(C.PARQUET_DEVICE_ENCODE.key, False)  # the chip's path
    df, _ = _write_source(session)
    df.write.parquet(str(tmp_path))
    table = pq.read_table(str(tmp_path))
    return sorted(zip(*(table.column(n).to_pylist()
                        for n in table.column_names)))


@pytest.mark.parametrize("action", ["collect", "write"])
def test_traced_fence_has_its_four_steps(session, tmp_path, action):
    """Every DeviceToHost of a traced action (a collect's query-level
    fence, a write's fence a task: both go through
    columnar/batch.to_host_many) has four children, one after the other
    on its thread: sink.pack, sink.wait (tracing's one call into jax),
    sink.transfer with the host `bytes` fetched, sink.finish. The rows,
    the dispatches and the fences are what the untraced action gives."""
    _sink_rows(session, tmp_path / "warm", action)
    before = M.dispatch_count(), M.fence_count()
    off_rows = _sink_rows(session, tmp_path / "off", action)
    off = M.dispatch_count() - before[0], M.fence_count() - before[1]
    assert session.last_query_trace is None
    session.set_conf(C.OBS_TRACING.key, True)
    before = M.dispatch_count(), M.fence_count()
    on_rows = _sink_rows(session, tmp_path / "on", action)
    on = M.dispatch_count() - before[0], M.fence_count() - before[1]
    assert on_rows == off_rows and len(on_rows) > 0
    assert on == off and on[1] >= 1
    trace = session.last_query_trace
    fences = trace.find("DeviceToHost")
    assert len(fences) == on[1], trace.render()
    for fence in fences:
        names = [c.name for c in fence.children]
        # a live-masked batch (the collect's) is made dense first, in a
        # sink.pack of its own ahead of the group's
        assert names in (SINK_STEPS, ["sink.pack"] + SINK_STEPS), \
            trace.render()
        wait, transfer, finish = fence.children[-3:]
        assert fence.start_ns <= fence.children[0].start_ns and \
            finish.end_ns <= fence.end_ns
        for a, b in zip(fence.children, fence.children[1:]):
            assert a.end_ns <= b.start_ns
        for child in fence.children:
            assert child.tid == fence.tid
            # sink.finish alone reads its thread's CPU clock (a fence's
            # host numpy; obs.trace.CPU_CLOCKED)
            assert (child.cpu_ns is not None) == (child is finish)
        assert finish.cpu_ns >= 0
        assert set(transfer.attrs) == {"bytes"}
        assert transfer.attrs["bytes"] > 0
        assert not (wait.counts or transfer.counts or finish.counts)
    # nothing of the sink outside a fence
    inside = {id(c) for f in fences for c in f.children}
    assert all(id(sp) in inside for sp in trace.spans()
               if sp.name.startswith("sink."))


@pytest.mark.usefixtures("device_string_decoder")
def test_traced_string_scan_host_half_has_its_steps(tmp_path):
    """The HOST half of a scan with a string column the device decodes
    (`_stage_split`): a row group's scan.host_decode holds scan.convert
    and scan.pack (`packed_bytes`: what that row group's scan.upload
    moves), and the split's first one the ONE scan.arrow_read before
    them."""
    session = srt.new_session({"rapids.tpu.sql.spmd.meshDevices": 1,
                               C.OBS_TRACING.key: True})
    try:
        df, _ = _parquet_source(session, tmp_path, string=True)
        df.filter(F.col("p") < 500) \
            .agg(F.sum("x"), F.sum("q"), F.count("s")).collect()
        trace = session.last_query_trace
        assert session.last_query_metrics[M.CPU_FALLBACK_EVENTS] == 0
    finally:
        session.stop()
    tasks = [sp for sp in trace.spans()
             if any(c.name == "scan.split" for c in sp.children)]
    assert len(tasks) == 2
    for task in tasks:
        decodes = [c for c in task.children if c.name == "scan.host_decode"]
        assert [d.attrs["rg"] for d in decodes] == [0, 1]
        assert [[c.name for c in d.children] for d in decodes] == [
            ["scan.arrow_read", "scan.convert", "scan.pack"],
            ["scan.convert", "scan.pack"]]
        uploads = [c for rg in task.children if rg.name == "scan.rowgroup"
                   for c in rg.children if c.name == "scan.upload"]
        assert [d.children[-1].attrs["packed_bytes"] for d in decodes] == \
            [u.attrs["bytes"] for u in uploads]
        assert all("pack_ms" not in d.attrs for d in decodes)


def test_traced_fixed_width_parquet_scan_spans(tmp_path):
    """A scan without a string column is Arrow's (PR 30): a split leaves
    one scan.host_decode (`columns`, `rows`), opened on the prefetcher's
    thread under the task's span and closed before the task asks for its
    permit, with its three steps as children on that thread (PR 43:
    scan.arrow_read, scan.convert, scan.pack with `packed_bytes`; no
    `pack_ms`), and one scan.upload (`bytes`, `staged`) a batch, on the
    task's thread and after its permit; no span of the device decoder. A
    task waiting for the admission permit is no deeper in the tree than a
    permit holder's upload."""
    # one permit: with two splits the second task waits in the semaphore
    session = srt.new_session({C.CONCURRENT_TPU_TASKS.key: 1,
                               "rapids.tpu.sql.spmd.meshDevices": 1,
                               C.OBS_TRACING.key: True})
    try:
        df, _ = _parquet_source(session, tmp_path)
        df.filter(F.col("p") < 500).agg(F.sum("x"), F.sum("q")).collect()
        trace = session.last_query_trace
        assert session.last_query_metrics[M.CPU_FALLBACK_EVENTS] == 0
    finally:
        session.stop()

    for name in ("scan.split", "scan.read", "scan.parse", "scan.rowgroup",
                 "scan.decode"):
        assert not trace.find(name), name
    tasks = [sp for sp in trace.spans()
             if any(c.name == "scan.host_decode" for c in sp.children)]
    assert len(tasks) == 2
    for task in tasks:
        (decode,) = [c for c in task.children if c.name == "scan.host_decode"]
        assert set(decode.attrs) == {"columns", "rows"}
        assert decode.attrs["columns"] == 3
        assert decode.attrs["rows"] == 2 * 4096
        assert decode.tid != task.tid  # the reader thread's
        read, convert, pack = decode.children
        assert [c.name for c in decode.children] == \
            ["scan.arrow_read", "scan.convert", "scan.pack"]
        assert set(pack.attrs) == {"packed_bytes"}
        assert not read.attrs and not convert.attrs
        for child in decode.children:
            # one after the other on the reader's thread; the two that
            # are this thread's own work with the CPU it spent in them
            # (in scan.arrow_read it sleeps while Arrow's pool works)
            assert child.tid == decode.tid and not child.children
        assert read.cpu_ns is None
        assert convert.cpu_ns >= 0 and pack.cpu_ns >= 0
        assert decode.start_ns <= read.start_ns <= read.end_ns \
            <= convert.start_ns <= convert.end_ns <= pack.start_ns \
            <= pack.end_ns <= decode.end_ns
        (asked,) = [c for c in task.children
                    if c.name == "Acquire TPU Semaphore"]
        (upload,) = [c for c in task.children if c.name == "scan.upload"]
        assert upload.tid == task.tid and upload.attrs["columns"] == 3
        assert upload.attrs["staged"] == 1
        # q and p as int32 (q narrows on its value range), x as f64 here,
        # a validity byte each: what was packed is what goes up
        assert upload.attrs["bytes"] >= 2 * 4096 * (4 + 4 + 8)
        assert upload.attrs["bytes"] == pack.attrs["packed_bytes"]
        assert decode.end_ns <= asked.start_ns
        assert asked.end_ns <= upload.start_ns


@pytest.mark.parametrize("path", ["dense", "sort"])
def test_traced_grouped_aggregate_spans(tmp_path, monkeypatch, path):
    """A dictionary-encoded STRING column is Arrow's too (PR 37): the
    split's scan.host_decode says how many columns it kept as codes
    (`dict_columns`, `dict_bytes`), and a group-by over them runs the
    table of exec/dense_agg.py: its update and merge spans say `path`,
    `groups` (the table's slots) and `rows`, and the task counts
    `denseAggBatches`. With the table taken away (in the test) the same
    query says `sort` and counts `sortAggBatches`."""
    from spark_rapids_tpu.exec import dense_agg as DA

    if path == "sort":
        monkeypatch.setattr(DA, "MAX_GROUPS", 0)
    # the streaming operators either way: without the table the planner
    # would give a one-device group-by to the SPMD stage program
    session = srt.new_session({"rapids.tpu.sql.spmd.enabled": False,
                               C.OBS_TRACING.key: True})
    try:
        df, _ = _parquet_source(session, tmp_path, string=True)
        rows = df.groupBy("s").agg(F.sum("q").alias("sq")).collect()
        trace = session.last_query_trace
        assert session.last_query_metrics[M.CPU_FALLBACK_EVENTS] == 0
    finally:
        session.stop()
    assert sorted(r[0] for r in rows) == ["flag0", "flag1", "flag2"]
    assert not trace.find("scan.decode")      # no device decode program
    decodes = trace.find("scan.host_decode")
    assert len(decodes) == 2
    for sp in decodes:
        assert sp.attrs["dict_columns"] == 1 and sp.attrs["columns"] == 2
        # 8192 int32 codes and the bytes of three five-letter values
        assert sp.attrs["dict_bytes"] == 2 * 4096 * 4 + 15
    updates = trace.find("TpuHashAggregate.update")
    assert len(updates) == 2
    for sp in updates:
        assert sp.attrs["path"] == path and sp.attrs["rows"] == 2 * 4096
        # a radix of 4 holds three values and the null key
        assert sp.attrs.get("groups") == (4 if path == "dense" else None)
    merges = trace.find("TpuHashAggregate.merge")
    assert merges and all(sp.attrs["path"] == path for sp in merges)
    counted = {name: sum(sp.counts.get(name, 0) for sp in trace.spans())
               for name in (M.DENSE_AGG_BATCHES, M.SORT_AGG_BATCHES)}
    assert counted == {M.DENSE_AGG_BATCHES: 2 * (path == "dense"),
                       M.SORT_AGG_BATCHES: 2 * (path == "sort")}


@pytest.mark.usefixtures("device_string_decoder")
def test_traced_parquet_scan_spans(tmp_path):
    """A scan with a string column: one scan.read (the host half's, PR 29:
    before the task asks for its permit) and one scan.decode (the device
    half's, under its scan.rowgroup) a (row group, string column); the
    bytes read are the footers' compressed chunk sizes; a split's reads
    all end before the first of its row groups begins; a task waiting for
    the admission permit is SHALLOWER in the tree than a permit holder's
    decode and no deeper than its row group
    (the benchmark labels an idle gap with the deepest open span: the
    worker's step, not the waiters)."""
    import pyarrow.parquet as pq

    # one permit: with two splits the second task waits in the semaphore
    session = srt.new_session({C.CONCURRENT_TPU_TASKS.key: 1,
                               "rapids.tpu.sql.spmd.meshDevices": 1,
                               C.OBS_TRACING.key: True})
    try:
        df, root = _parquet_source(session, tmp_path, string=True)
        df.filter(F.col("p") < 500) \
            .agg(F.sum("x"), F.sum("q"), F.count("s")).collect()
        trace = session.last_query_trace
        assert session.last_query_metrics[M.CPU_FALLBACK_EVENTS] == 0
    finally:
        session.stop()

    expected = {}
    for f in sorted(root.glob("*.parquet")):
        md = pq.ParquetFile(str(f)).metadata
        for rg in range(md.num_row_groups):
            for ci in range(md.num_columns):
                col = md.row_group(rg).column(ci)
                if col.path_in_schema == "s":  # the device decoder's
                    expected[(str(f), rg, "s")] = col.total_compressed_size
    depth = {}

    def walk(sp, d):
        depth[id(sp)] = d
        for c in sp.children:
            walk(c, d + 1)

    walk(trace.root, 0)
    reads, parsed, decodes = {}, {}, {}
    tasks = [sp for sp in trace.spans()
             if any(c.name == "scan.split" for c in sp.children)]
    assert len(tasks) == 2
    for task in tasks:
        # both halves' spans are the task's children
        (split,) = [c for c in task.children if c.name == "scan.split"]
        assert "fallback" not in split.attrs
        assert split.attrs["row_groups"] == 2
        assert split.attrs["device_columns"] == 1
        path = split.attrs["path"]
        for c in task.children:
            if c.name != "scan.read":
                continue
            key = (path, c.attrs["rg"], c.attrs["column"])
            assert key not in reads
            reads[key] = c.attrs["bytes"]
            assert c.tid == task.tid
            # decompression and the page walk moved with the read
            (parse,) = c.children
            assert parse.name == "scan.parse"
            parsed[key] = parse.attrs["bytes_out"]
        rowgroups = [c for c in task.children if c.name == "scan.rowgroup"]
        assert [sp.attrs["rg"] for sp in rowgroups] == [0, 1]
        # the whole split is staged before the task asks for its permit
        (asked,) = [c for c in task.children
                    if c.name == "Acquire TPU Semaphore"]
        assert max(c.end_ns for c in task.children
                   if c.name in ("scan.read", "scan.host_decode")) \
            <= asked.start_ns <= asked.end_ns <= rowgroups[0].start_ns
        for rg_span in rowgroups:
            assert rg_span.attrs["path"] == path
            assert rg_span.tid == task.tid
            # the string's decode, then the upload of what Arrow decoded
            decode, rest = rg_span.children
            assert (decode.name, rest.name) == ("scan.decode", "scan.upload")
            assert rest.attrs["columns"] == 3 and rest.attrs["bytes"] > 0
            key = (path, rg_span.attrs["rg"], decode.attrs["column"])
            assert key not in decodes
            decodes[key] = decode
    assert reads == expected
    assert set(decodes) == set(expected)
    assert len(trace.find("scan.rowgroup")) == 4
    for key, sp in decodes.items():
        assert sp.attrs["codec"] == "SNAPPY" and sp.attrs["pages"] >= 1
        (upload,) = sp.children
        assert upload.name == "scan.upload"
        # what goes up is the decompressed chunk
        assert parsed[key] == upload.attrs["bytes"] > 0
    waits = trace.find("Acquire TPU Semaphore")
    assert waits
    deepest_wait = max(depth[id(s)] for s in waits)
    assert deepest_wait < min(depth[id(s)]
                              for s in trace.find("scan.decode"))
    assert deepest_wait <= min(depth[id(s)]
                               for s in trace.find("scan.rowgroup"))


# ---------------------------------------------------------------------------
# Span-tree structure + context propagation on the worker pool
# ---------------------------------------------------------------------------
def test_span_tree_structure_and_count_attribution(session):
    # the host loop's map-stage/task span tree is under test (the SPMD
    # stage compiler, default on since r14, collapses it to one program)
    session.set_conf("rapids.tpu.sql.spmd.enabled", False)
    session.set_conf(C.OBS_TRACING.key, True)
    q = _flagship(_mk_df(session, num_partitions=3))
    q.collect()
    trace = session.last_query_trace
    assert trace is not None
    kinds = {s.kind for s in trace.spans()}
    assert trace.root.kind == "query"
    assert "stage" in kinds and "task" in kinds and "op" in kinds
    # the map stage contains its partitions' task spans (tasks ran on the
    # pool; the current-span contextvar rode copy_context into _submit)
    map_stages = [s for s in trace.spans()
                  if s.kind == "stage" and s.name.startswith("stage:map:")]
    assert map_stages, trace.render()
    task_children = [c for s in map_stages for c in s.children
                     if c.kind == "task"]
    assert len(task_children) == 3
    # every metric increment recorded during the query is attributed to
    # some span: per-span counts sum exactly to the query's own metrics
    totals = trace.counts_total()
    assert totals.get(M.DEVICE_DISPATCHES, 0) == \
        session.last_query_metrics[M.DEVICE_DISPATCHES]
    assert totals.get(M.FENCES, 0) == \
        session.last_query_metrics[M.FENCES]
    # stage breakdown covers the whole pipeline (plan + map + result)
    breakdown = trace.stage_breakdown()
    assert any(name.startswith("stage:map:") for name in breakdown)
    assert "stage:result" in breakdown
    assert all(secs >= 0.0 for secs in breakdown.values())


def test_concurrent_tenants_traces_do_not_cross():
    """3 tenants run traced queries concurrently on one shared runtime:
    each session's last trace carries its own tenant tag and its span
    counts reconcile exactly with that query's own (context-scoped)
    metrics — a foreign tenant's increments leaking in would break the
    equality."""
    server = TpuServer({C.OBS_TRACING.key: True})
    try:
        tenants = [f"obs{i}" for i in range(3)]
        sessions = {t: server.connect(t) for t in tenants}
        dfs = {t: _mk_df(sessions[t], seed=30 + i, n=2000,
                         num_partitions=2 + i)
               for i, t in enumerate(tenants)}
        errors = []

        def client(t):
            try:
                for _ in range(3):
                    _flagship(dfs[t]).collect()
            except BaseException as e:  # noqa: BLE001 - relay to main
                errors.append(e)

        threads = [threading.Thread(target=client, args=(t,))
                   for t in tenants]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        assert not errors, errors
        for t in tenants:
            s = sessions[t]
            trace = s.last_query_trace
            assert trace is not None
            assert trace.tenant == t
            assert trace.root.attrs.get("tenant") == t
            totals = trace.counts_total()
            assert totals.get(M.DEVICE_DISPATCHES, 0) == \
                s.last_query_metrics[M.DEVICE_DISPATCHES]
    finally:
        server.stop()


def test_trace_span_cap_bounds_memory(session):
    session.set_conf(C.OBS_TRACING.key, True)
    session.set_conf(C.OBS_TRACE_MAX_SPANS.key, 4)
    q = _flagship(_mk_df(session, num_partitions=4))
    q.collect()
    trace = session.last_query_trace
    n_spans = sum(1 for _ in trace.spans())
    assert n_spans <= 4
    assert trace.dropped_spans > 0


# ---------------------------------------------------------------------------
# Perfetto / Chrome-trace exporter
# ---------------------------------------------------------------------------
def test_perfetto_export_is_valid_chrome_trace_json(session):
    session.set_conf(C.OBS_TRACING.key, True)
    _flagship(_mk_df(session)).collect()
    trace = session.last_query_trace
    doc = json.loads(trace.to_perfetto_json())
    events = doc["traceEvents"]
    assert isinstance(events, list) and len(events) >= 3
    phases = set()
    for ev in events:
        assert isinstance(ev["name"], str)
        assert ev["ph"] in ("X", "M")
        phases.add(ev["ph"])
        assert isinstance(ev["pid"], int)
        assert isinstance(ev["tid"], int)
        if ev["ph"] == "X":
            assert ev["ts"] >= 0.0
            assert ev["dur"] >= 0.0
    assert "X" in phases and "M" in phases
    # durations nest: the root query event is the longest
    roots = [ev for ev in events
             if ev["ph"] == "X" and ev["name"].startswith("query:")]
    assert len(roots) == 1
    assert roots[0]["dur"] >= max(
        ev["dur"] for ev in events if ev["ph"] == "X")


# ---------------------------------------------------------------------------
# EXPLAIN ANALYZE
# ---------------------------------------------------------------------------
def test_explain_analyze_measured_beside_predicted(session):
    """The acceptance pin: EXPLAIN ANALYZE on the flagship shows measured
    wall-time per operator, and the analyzer's predicted dispatch
    interval contains the measured count."""
    q = _flagship(_mk_df(session))
    text = session.explain_analyze(q._plan)
    assert "== EXPLAIN ANALYZE ==" in text
    assert "== Query totals ==" in text
    # every operator line carries measured columns
    plan_body = text.split("== Query totals ==")[0]
    op_lines = [ln for ln in plan_body.splitlines()
                if "[rows=" in ln]
    assert len(op_lines) >= 5, text
    times = [float(m.group(1)) for m in
             re.finditer(r"time=(\d+\.\d+)ms", plan_body)]
    assert times and any(t > 0.0 for t in times), text
    # predictions render beside the measurements for analyzed operators
    assert "| predicted rows=" in plan_body
    # measured dispatches sit INSIDE the analyzer's interval
    m = re.search(r"device dispatches: measured (\d+), "
                  r"predicted \[([0-9.a-zA-Z]+), ([0-9.a-zA-Z]+)\] "
                  r"\((within|OUTSIDE) interval\)", text)
    assert m is not None, text
    assert m.group(4) == "within", text
    # the run it analyzed left a trace behind for export
    assert session.last_query_trace is not None
    # and tracing was only FORCED for the analyze run, not left on
    assert not session.conf.get(C.OBS_TRACING)


def test_tpch_q1_dispatch_parity_and_explain_analyze(session):
    """The flagship-q1 acceptance pin: tracing adds zero device
    dispatches and zero host fences on TPC-H q1, and EXPLAIN ANALYZE
    shows measured per-operator wall-time with the measured dispatch
    count inside the analyzer's predicted interval."""
    from spark_rapids_tpu.benchmarks import tpch

    tables = tpch.gen_tables(session, sf=0.0005, num_partitions=2)
    q1 = tpch.QUERIES["q1"](tables)
    q1.collect()  # warm compiles
    q1.collect()
    off = dict(session.last_query_metrics)
    session.set_conf(C.OBS_TRACING.key, True)
    q1.collect()
    q1.collect()
    on = dict(session.last_query_metrics)
    assert on[M.DEVICE_DISPATCHES] == off[M.DEVICE_DISPATCHES]
    assert on[M.FENCES] == off[M.FENCES]
    session.set_conf(C.OBS_TRACING.key, False)
    text = session.explain_analyze(q1._plan)
    times = [float(m.group(1)) for m in
             re.finditer(r"time=(\d+\.\d+)ms", text)]
    assert times and any(t > 0.0 for t in times), text
    m = re.search(r"device dispatches: measured \d+, predicted "
                  r"\[[0-9.a-zA-Z]+, [0-9.a-zA-Z]+\] \((within|OUTSIDE)",
                  text)
    assert m is not None and m.group(1) == "within", text


def test_explain_analyze_dataframe_api(session, capsys):
    q = _flagship(_mk_df(session))
    text = q.explain_analyze()
    assert "== EXPLAIN ANALYZE ==" in text
    assert "== EXPLAIN ANALYZE ==" in capsys.readouterr().out


# ---------------------------------------------------------------------------
# Admission wait DURATION (the admissionWaits-counts-events-not-time fix)
# ---------------------------------------------------------------------------
def test_admission_wait_duration_recorded():
    server = TpuServer({
        # small enough that two concurrent queries cannot both fit
        "rapids.tpu.memory.hbm.sizeOverride": 200 << 10,
    })
    try:
        tenants = [f"w{i}" for i in range(3)]
        sessions = {t: server.connect(t) for t in tenants}
        dfs = {t: _mk_df(sessions[t], seed=40 + i, n=2000)
               for i, t in enumerate(tenants)}
        ns0 = M.admission_wait_ns()
        errors = []

        def client(t):
            try:
                for _ in range(3):
                    (dfs[t].groupBy("k")
                     .agg(F.sum("a").alias("s"))).collect()
            except BaseException as e:  # noqa: BLE001 - relay to main
                errors.append(e)

        threads = [threading.Thread(target=client, args=(t,))
                   for t in tenants]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        assert not errors, errors
        ctl = AdmissionController.get()
        snap = ctl.snapshot()
        assert snap["waits"] > 0
        # duration recorded, not just events: total + quantiles move
        assert M.admission_wait_ns() > ns0
        assert snap["wait_samples"] > 0
        assert snap["wait_total_ms"] > 0.0
        assert snap["wait_p95_ms"] >= snap["wait_p50_ms"] >= 0.0
        # the duration also rode the per-query context of some tenant
        assert any(
            s.tenant_metric_totals.get(M.ADMISSION_WAIT_NS, 0) > 0
            for s in sessions.values())
    finally:
        server.stop()


# ---------------------------------------------------------------------------
# Serving metrics snapshot + Prometheus exposition
# ---------------------------------------------------------------------------
_PROM_SAMPLE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*"
    r'(\{[a-zA-Z_][a-zA-Z0-9_]*="[^"]*"(,[a-zA-Z_][a-zA-Z0-9_]*="[^"]*")*\})?'
    r" -?[0-9.eE+-]+$")


def test_metrics_snapshot_and_prometheus_exposition():
    server = TpuServer()
    try:
        s = server.connect("prom-a")
        _flagship(_mk_df(s)).collect()
        _flagship(_mk_df(s)).collect()
        snap = server.metrics_snapshot()
        assert snap["tenants"]["prom-a"]["queries"] == 2
        assert snap["tenants"]["prom-a"].get(M.DEVICE_DISPATCHES, 0) > 0
        assert "hitRate" in snap["planCache"]
        assert snap["spill"] is not None
        assert "device" in snap["spill"]["tiers"]
        assert snap["admission"] is not None
        assert "wait_p50_ms" in snap["admission"]
        text = server.metrics_prometheus()
        for line in text.strip().splitlines():
            if line.startswith("#"):
                assert line.startswith("# HELP") or \
                    line.startswith("# TYPE"), line
            else:
                assert _PROM_SAMPLE.match(line), line
        assert 'srt_tenant_queries_total{tenant="prom-a"} 2' in text
        assert "srt_plan_cache_hits_total" in text
        assert "srt_spill_tier_bytes" in text
    finally:
        server.stop()


# ---------------------------------------------------------------------------
# Traced timelines surface retry / replan / prefetch detail
# ---------------------------------------------------------------------------
def test_trace_records_aqe_stage_spans(session):
    # AQE stage spans exist only for host-loop exchange boundaries
    session.set_conf("rapids.tpu.sql.spmd.enabled", False)
    session.set_conf(C.OBS_TRACING.key, True)
    session.set_conf(C.ADAPTIVE_ENABLED.key, True)
    session.set_conf(C.SHUFFLE_SERIALIZE.key, True)
    q = _flagship(_mk_df(session))
    q.collect()
    trace = session.last_query_trace
    assert trace is not None
    assert trace.find("stage:aqe:"), trace.render()
    assert trace.find("aqe.replan:"), trace.render()


def test_micro_batch_pack_span_and_nested_trace_isolation():
    """Tracing + micro-batching: the leader's trace carries the
    microbatch.pack span, and the packed inner run roots its spans in
    ITS OWN tree (the current-span contextvar is reset for nested runs)
    — the inner trace must contain the packed execution's task spans,
    not an empty root."""
    server = TpuServer({
        C.OBS_TRACING.key: True,
        "rapids.tpu.serving.microBatch.windowMs": 150,
        "rapids.tpu.serving.microBatch.maxQueries": 2,
    })
    try:
        tenants = ["mb0", "mb1"]
        sessions = {t: server.connect(t) for t in tenants}
        dfs = {t: _mk_df(sessions[t], seed=50 + i)
               for i, t in enumerate(tenants)}
        barrier = threading.Barrier(len(tenants))
        errors = []

        def client(t):
            try:
                barrier.wait(timeout=10)
                dfs[t].filter(F.col("a") % 3 != 0).collect()
            except BaseException as e:  # noqa: BLE001 - relay to main
                errors.append(e)

        threads = [threading.Thread(target=client, args=(t,))
                   for t in tenants]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        assert not errors, errors
        packs = [sp for s in sessions.values()
                 if s.last_query_trace is not None
                 for sp in s.last_query_trace.find("microbatch.pack")]
        if packs:  # scheduling may split the window; pack => pinned shape
            # the packed run executed under the pack span's query but
            # recorded into its OWN tracer: the leader's pack span has no
            # task children of the inner run
            assert all(c.kind != "task" for sp in packs
                       for c in sp.children)
    finally:
        server.stop()


def test_oracle_equality_with_tracing_on(session):
    """Tracing must never change results."""
    from tests.harness import assert_rows_equal, run_on_cpu

    df_fn = lambda s: _flagship(_mk_df(s))  # noqa: E731
    expected = run_on_cpu(session, df_fn)
    session.set_conf(C.OBS_TRACING.key, True)
    got = df_fn(session).collect()
    assert_rows_equal(expected, got, ignore_order=True)
