"""The device parquet scan in two halves (PR 29, io/scan.py): a HOST half
that a task runs for its whole split before it asks for the admission
permit (`TpuFileScanExec._stage_split`: footer, the string chunks' reads,
decompression and page walk, Arrow's decode of the columns the device
decoder does not take: every fixed-width one, since PR 30) and a DEVICE
half that alone runs under it (`_decode_staged`). A scan without a STRING
column is the host decoder's (`_read_host`), and since PR 32 it too packs
a split for its upload (`stage_upload`) ahead of the task's permit, on
the prefetcher's reader thread or, at depth 0, on the task's own: what a
permit covers there is `StagedUpload.upload()` and the program issue.

Pinned here: the host half touches neither jax nor the semaphore and runs
while another task holds the permit; the rows equal the host decoder's at
every prefetch depth (the host decoder's own knob); a page shape the
decoder refuses in the middle of a split sends what is LEFT of the split
to the host decoder, each row once; a device error is retried from the
staged item without re-running the host half; an abandoned or cancelled
scan leaves no reader thread behind. For the host decoder's path, last in
this file: no packing under a held permit at depth 0 and 1, a second
task's packing goes on while the first holds every permit, an upload that
fails is issued again from the same staged buffers, and those buffers are
each split's own and are not written once packed.
"""

import logging
import threading

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

import spark_rapids_tpu as srt
from spark_rapids_tpu import conf as C
from spark_rapids_tpu.columnar.batch import HostColumnarBatch, StagedUpload
from spark_rapids_tpu.engine import cancel as CX
from spark_rapids_tpu.engine import retry as R
from spark_rapids_tpu.exec.transitions import current_task_id
from spark_rapids_tpu.io import parquet_device as PD
from spark_rapids_tpu.io import scan as SCAN
from spark_rapids_tpu.io.arrow_convert import schema_attrs
from spark_rapids_tpu.io.prefetch import live_reader_count
from spark_rapids_tpu.memory.semaphore import TpuSemaphore
from spark_rapids_tpu.plan import functions as F
from spark_rapids_tpu.utils import metrics as M

# the device decoder's two halves, over files of dictionary strings
pytestmark = pytest.mark.usefixtures("device_string_decoder")

PREFETCH = C.IO_PREFETCH_BATCHES.key
DEVICE_DECODE = C.PARQUET_DEVICE_DECODE.key
ROWS = 2048  # a row group


def _write_files(root, files=2, row_groups=3, seed=3):
    """lineitem-like files: an id that names every row, a dictionary INT64
    with nulls, an INT32, a DOUBLE (the `rest`: Arrow's) and a
    low-cardinality string (the device decoder's)."""
    rng = np.random.default_rng(seed)
    root.mkdir(parents=True, exist_ok=True)
    paths = []
    for i in range(files):
        n = ROWS * row_groups
        table = pa.table({
            "id": np.arange(i * n, (i + 1) * n, dtype=np.int64),
            "q": pa.array(rng.integers(1, 51, n).astype(np.int64),
                          mask=rng.random(n) < 0.05),
            "d": rng.integers(8000, 10500, n).astype(np.int32),
            "x": rng.integers(0, 11, n) / 100.0,
            "s": [f"flag{j % 5}" for j in range(n)]})
        paths.append(str(root / f"f{i}.parquet"))
        pq.write_table(table, paths[-1], row_group_size=ROWS,
                       compression="snappy")
    return paths


def _new_session(**conf):
    return srt.new_session({"rapids.tpu.sql.spmd.meshDevices": 1, **conf})


def _rows(session, root, **read_opts):
    reader = session.read
    for k, v in read_opts.items():
        reader = reader.option(k, v)
    return sorted(reader.parquet(str(root)).collect(),
                  key=lambda r: r[0])


# ---------------------------------------------------------------------------
# the host half: host data in, host data out
# ---------------------------------------------------------------------------
class _Forbidden:
    """Stands in for `jnp` / `jax` / the semaphore while the host half
    runs. The one thing it may ask jax is which backend it has (a cached
    name: how wide a DOUBLE goes up, `physical_np_dtype`)."""

    def __init__(self, what):
        self._what = what

    def __getattr__(self, name):
        if (self._what, name) == ("jax", "default_backend"):
            return lambda: "cpu"
        raise AssertionError(f"the host half touched {self._what}.{name}")


def test_host_half_makes_no_jax_call_and_takes_no_permit(
        tmp_path, monkeypatch):
    (path,) = _write_files(tmp_path, files=1)
    conf = C.TpuConf()
    attrs = schema_attrs(pq.read_schema(path))
    (split,) = SCAN.plan_splits("parquet", [path], {}, conf)
    scan = SCAN.TpuFileScanExec(attrs, [split], "parquet")
    plan = SCAN._SplitPlan(split, {})
    import spark_rapids_tpu.columnar.batch as B

    for mod in (PD, B):
        monkeypatch.setattr(mod, "jnp", _Forbidden("jnp"))
        monkeypatch.setattr(mod, "jax", _Forbidden("jax"))
    monkeypatch.setattr(TpuSemaphore, "get",
                        classmethod(lambda cls: _Forbidden("TpuSemaphore")))
    items = list(scan._stage_split(plan))
    monkeypatch.undo()

    # the string is the device decoder's, every fixed-width column Arrow's
    assert [a.name for a in plan.eligible] == ["s"]
    assert [a.name for a in plan.rest] == ["id", "q", "d", "x"]
    assert plan.groups == [0, 1, 2] and [it.rg for it in items] == [0, 1, 2]
    md = pq.ParquetFile(path).metadata
    for it in items:
        assert it.rows == ROWS
        # Arrow's columns, packed for their upload: host arrays only
        assert isinstance(it.host, StagedUpload)
        assert it.host.num_rows == ROWS
        assert all(isinstance(b, np.ndarray) for b in it.host.bufs)
        want = pq.ParquetFile(path).read_row_group(
            it.rg, columns=["x"]).column("x").to_numpy()
        (f64,) = [b for b in it.host.bufs if b.dtype == np.float64]
        assert np.array_equal(f64[:ROWS], want)
        assert sorted(it.chunks) == ["s"]
        (col,) = [md.row_group(it.rg).column(ci)
                  for ci in range(md.num_columns)
                  if md.row_group(it.rg).column(ci).path_in_schema == "s"]
        chunk = it.chunks["s"]
        assert chunk.codec == "SNAPPY"
        # decompressed: what the decoder's own first block would give
        data, pages = PD.normalize_chunk(
            PD.read_chunk_bytes(path, col), "SNAPPY")
        assert chunk.data == data and chunk.pages == pages
        assert isinstance(chunk.data, bytes)


def test_a_staged_chunk_decodes_as_an_unstaged_one(tmp_path):
    """The decoder's seam: `decode_chunk_device` given `stage_chunk`'s
    pages issues the same programs on the same tables as when it parses
    for itself; a fixed-width chunk is refused either way."""
    import jax

    (path,) = _write_files(tmp_path, files=1, row_groups=1)
    pf = pq.ParquetFile(path)
    for ci, attr in enumerate(schema_attrs(pf.schema_arrow)):
        col = pf.metadata.row_group(0).column(ci)
        assert col.path_in_schema == attr.name
        max_def = pf.schema.column(ci).max_definition_level
        raw = PD.read_chunk_bytes(path, col)
        data, pages = PD.stage_chunk(raw, "SNAPPY")
        if attr.name != "s":
            assert not PD.column_eligible(col, attr.data_type)
            with pytest.raises(PD._Unsupported):
                PD.decode_chunk_device(data, attr.data_type, ROWS,
                                       max_def=max_def, codec="SNAPPY",
                                       pages=pages)
            continue
        assert PD.column_eligible(col, attr.data_type)
        alone = PD.decode_chunk_device(raw, attr.data_type, ROWS,
                                       max_def=max_def, codec="SNAPPY")
        staged = PD.decode_chunk_device(data, attr.data_type, ROWS,
                                        max_def=max_def, codec="SNAPPY",
                                        pages=pages)
        for a, b in zip(jax.tree_util.tree_leaves(alone),
                        jax.tree_util.tree_leaves(staged)):
            assert np.array_equal(np.asarray(a), np.asarray(b)), attr.name


@pytest.mark.parametrize("why", ["the_string_chunk_is_not_eligible",
                                 "no_string_attribute"])
def test_no_eligible_column_stages_nothing(tmp_path, monkeypatch, why):
    """Where the decoder takes no column of the file the host half says so
    before any read, and the scan is the host path's: no fallback event.
    A scan without a STRING attribute does not even open the footer to
    find that out: the host half is never entered."""
    (path,) = _write_files(tmp_path, files=1)
    reads, staged = [], []
    monkeypatch.setattr(PD, "read_chunk_bytes",
                        lambda *a: reads.append(a) or b"")
    stage_split = SCAN.TpuFileScanExec._stage_split
    monkeypatch.setattr(
        SCAN.TpuFileScanExec, "_stage_split",
        lambda self, plan: staged.append(plan) or stage_split(self, plan))
    if why == "no_string_attribute":
        columns = ["id", "q", "d", "x"]
    else:
        columns = ["id", "q", "d", "x", "s"]
        monkeypatch.setattr(PD, "column_eligible", lambda col, dt: False)
    session = _new_session(**{C.OBS_TRACING.key: True})
    try:
        rows = sorted(session.read.parquet(str(tmp_path)).select(*columns)
                      .collect(), key=lambda r: r[0])
        metrics = dict(session.last_query_metrics)
        trace = session.last_query_trace
    finally:
        session.stop()
    assert [r[0] for r in rows] == list(range(3 * ROWS))
    assert [r[3] for r in rows] == \
        pq.read_table(path).column("x").to_pylist()
    assert metrics[M.CPU_FALLBACK_EVENTS] == 0 and not reads
    assert not trace.find("scan.rowgroup")
    # the host decoder's spans, whichever way the scan got there
    (decode,) = trace.find("scan.host_decode")
    assert decode.attrs["rows"] == 3 * ROWS
    assert decode.attrs["columns"] == len(columns)
    assert sum(sp.attrs["bytes"] for sp in trace.find("scan.upload")) > 0
    if why == "no_string_attribute":
        assert not staged and not trace.find("scan.split")
    else:
        (split,) = trace.find("scan.split")
        assert "fallback" not in split.attrs and \
            "row_groups" not in split.attrs


# ---------------------------------------------------------------------------
# one task holds the permit, the other's host half runs all the same
# ---------------------------------------------------------------------------
def test_host_half_runs_while_another_task_holds_the_permit(
        tmp_path):
    _write_files(tmp_path, files=2, row_groups=2)
    session = _new_session(**{C.CONCURRENT_TPU_TASKS.key: 1,
                              C.OBS_TRACING.key: True})
    try:
        session.read.parquet(str(tmp_path)) \
            .agg(F.sum("x"), F.sum("q"), F.count("s")).collect()
        trace = session.last_query_trace
        assert session.last_query_metrics[M.CPU_FALLBACK_EVENTS] == 0
    finally:
        session.stop()

    tasks = [sp for sp in trace.spans()
             if sp.kind == "task" and any(c.name == "scan.split"
                                          for c in sp.children)]
    assert len(tasks) == 2

    def wait_of(task):
        (wait,) = [c for c in task.children
                   if c.name == "Acquire TPU Semaphore"]
        return wait

    holder, queued = sorted(tasks, key=lambda t: wait_of(t).duration_ns)
    wait = wait_of(queued)
    # the queued task waited for the whole of the holder's device half
    # (the first query: its programs compile under the permit)
    held = [c for c in holder.children if c.name == "scan.rowgroup"]
    assert wait.start_ns < held[0].end_ns and wait.end_ns >= held[-1].end_ns
    for task in tasks:
        asked = wait_of(task)
        host_half = [c for c in task.children
                     if c.name in ("scan.split", "scan.read",
                                   "scan.host_decode")]
        # 1 split, 2 row groups x (`s` read, `x` and `q` decoded by Arrow:
        # the plan prunes the rest): the WHOLE split staged, on the task's
        # own thread, before the task asked for its permit, so no permit
        # is held through host work
        assert len(host_half) == 1 + 2 * 2
        for sp in host_half:
            assert sp.end_ns <= asked.start_ns and sp.tid == task.tid
        device_half = [c for c in task.children if c.name == "scan.rowgroup"]
        assert [sp.attrs["rg"] for sp in device_half] == [0, 1]
        for sp in device_half:
            assert sp.start_ns >= asked.end_ns and sp.tid == task.tid
    # the queued task's host half did not wait for the holder's permit: it
    # was done before the holder's device half was
    assert max(c.end_ns for c in queued.children
               if c.name == "scan.host_decode") < held[-1].end_ns
    assert not trace.find("prefetch:scan-stage")


def test_the_rest_columns_are_one_read_a_split(tmp_path, monkeypatch):
    """The columns Arrow decodes are read the way the host path reads a
    split — `read_split`, once, on the file the host half has open — and
    sliced a row group."""
    (path,) = _write_files(tmp_path, files=1)
    conf = C.TpuConf()
    attrs = schema_attrs(pq.read_schema(path))
    (split,) = SCAN.plan_splits("parquet", [path], {}, conf)
    scan = SCAN.TpuFileScanExec(attrs, [split], "parquet")
    reads = []
    read_split = SCAN.read_split

    def counting(split, attrs, pf=None):
        reads.append(([a.name for a in attrs], pf))
        return read_split(split, attrs, pf)

    monkeypatch.setattr(SCAN, "read_split", counting)
    items = list(scan._stage_split(SCAN._SplitPlan(split, {})))
    assert len(items) == 3
    ((names, pf),) = reads
    assert names == ["id", "q", "d", "x"] and pf is not None
    x = pq.read_table(path).column("x").to_numpy()
    for i, it in enumerate(items):
        (up,) = [b for b in it.host.bufs if b.dtype == np.float64]
        np.testing.assert_array_equal(up[:ROWS], x[i * ROWS:(i + 1) * ROWS])


def test_read_split_on_an_open_file(tmp_path):
    (path,) = _write_files(tmp_path, files=1)
    attrs = [a for a in schema_attrs(pq.read_schema(path))
             if a.name in ("id", "x")]
    split = SCAN.FileSplit(path, "parquet", (1, 2), (), ())
    want = SCAN.read_split(split, attrs)
    assert want.num_rows == 2 * ROWS and want.column_names == ["id", "x"]
    assert SCAN.read_split(split, attrs, pq.ParquetFile(path)).equals(want)


# ---------------------------------------------------------------------------
# the same rows as the host decoder, at every depth
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("depth", [0, 1, 3])
def test_rows_equal_the_host_decoders_at_every_depth(
        tmp_path, depth):
    _write_files(tmp_path / "k=1", files=2, seed=1)
    _write_files(tmp_path / "k=2", files=1, seed=2)
    session = _new_session(**{PREFETCH: depth, C.OBS_TRACING.key: True})
    try:
        session.set_conf(DEVICE_DECODE, False)
        want = _rows(session, tmp_path)
        assert not session.last_query_trace.find("scan.rowgroup")
        session.set_conf(DEVICE_DECODE, True)
        got = _rows(session, tmp_path)
        trace = session.last_query_trace
        assert session.last_query_metrics[M.CPU_FALLBACK_EVENTS] == 0
        # the per-read option overrides the session's depth
        again = _rows(session, tmp_path, prefetchBatches=3 - depth)
    finally:
        session.stop()
    assert len(want) == 3 * 3 * ROWS
    assert got == want and again == want
    spans = trace.find("scan.rowgroup")
    assert len(spans) == 9
    # the device scan stages on the task thread whatever the depth: the
    # knob is the host decoder's
    assert {sp.tid for sp in trace.find("scan.read")} <= \
        {sp.tid for sp in spans}
    assert not trace.find("prefetch:scan-stage")
    assert live_reader_count() == 0


# ---------------------------------------------------------------------------
# a refused page shape in the middle of a split
# ---------------------------------------------------------------------------
def _refuse_second(monkeypatch, half):
    """`_Unsupported` at the second row group's `s` chunk, raised by the
    host half (`stage_chunk`) or by the device half (`decode_chunk_device`)."""
    seen = []
    if half == "host":
        real = PD.stage_chunk

        def stage_chunk(chunk, codec):
            seen.append(len(seen))
            # one chunk a row group is staged: the string's
            if len(seen) == 2:
                raise PD._Unsupported("test: refused page")
            return real(chunk, codec)

        monkeypatch.setattr(PD, "stage_chunk", stage_chunk)
    else:
        real = PD.decode_chunk_device

        def decode_chunk_device(chunk, dtype, rows, **kw):
            seen.append(len(seen))
            if len(seen) == 2:
                raise PD._Unsupported("test: refused page")
            return real(chunk, dtype, rows, **kw)

        monkeypatch.setattr(PD, "decode_chunk_device", decode_chunk_device)


@pytest.mark.parametrize("depth", [0, 1])
@pytest.mark.parametrize("half", ["host", "device"])
def test_unsupported_at_the_second_row_group_yields_every_row_once(
        tmp_path, monkeypatch, caplog, half, depth):
    (path,) = _write_files(tmp_path, files=1)
    _refuse_second(monkeypatch, half)
    session = _new_session(**{C.OBS_TRACING.key: True, PREFETCH: depth})
    try:
        with caplog.at_level(logging.WARNING, logger=SCAN.__name__):
            got = _rows(session, tmp_path)
        metrics = dict(session.last_query_metrics)
        trace = session.last_query_trace
        monkeypatch.undo()
        session.set_conf(DEVICE_DECODE, False)
        want = _rows(session, tmp_path)
    finally:
        session.stop()
    # every row exactly once, whichever decoder it came through
    assert [r[0] for r in got] == list(range(3 * ROWS))
    assert got == want
    assert metrics[M.CPU_FALLBACK_EVENTS] == 1
    (split,) = trace.find("scan.split")
    assert split.attrs["fallback"] == "s: test: refused page"
    assert split.attrs["row_groups"] == 3
    # a refusal of the host half comes before the split's first device
    # half: the whole split is the host decoder's. One of the device half
    # comes from inside its row group's span: the first row group went
    # through the device decoder and downstream, the other two are the
    # host decoder's (one read of what is left)
    assert [sp.attrs["rg"] for sp in trace.find("scan.rowgroup")] == \
        ([] if half == "host" else [0, 1])
    warned = [r for r in caplog.records if "refused" in r.getMessage()]
    assert len(warned) == 1 and \
        ("[0, 1, 2]" if half == "host" else "[1, 2]") in \
        warned[0].getMessage()
    assert live_reader_count() == 0


# ---------------------------------------------------------------------------
# a device error is retried from the staged item
# ---------------------------------------------------------------------------
def test_device_error_is_retried_from_the_staged_item(
        tmp_path, monkeypatch):
    (path,) = _write_files(tmp_path, files=1)
    reads, halves = [], []
    read_chunk_bytes = PD.read_chunk_bytes
    decode_staged = SCAN.TpuFileScanExec._decode_staged

    def counting_read(path, col):
        reads.append(col.path_in_schema)
        return read_chunk_bytes(path, col)

    def failing_once(self, plan, item, conf):
        halves.append(item)
        if len(halves) == 2:
            raise R.TpuRetryOOM("RESOURCE_EXHAUSTED: injected at rg 1")
        return decode_staged(self, plan, item, conf)

    monkeypatch.setattr(PD, "read_chunk_bytes", counting_read)
    monkeypatch.setattr(SCAN.TpuFileScanExec, "_decode_staged", failing_once)
    session = _new_session()
    try:
        got = _rows(session, tmp_path)
        metrics = dict(session.last_query_metrics)
    finally:
        session.stop()
    assert [r[0] for r in got] == list(range(3 * ROWS))
    table = pq.read_table(path)
    assert [r[3] for r in got] == table.column("x").to_pylist()
    assert [r[1] for r in got] == table.column("q").to_pylist()
    # the failed row group's device half ran again, on the very item the
    # host half staged: no chunk was read twice
    assert [it.rg for it in halves] == [0, 1, 1, 2]
    assert halves[1] is halves[2]
    assert reads == ["s"] * 3
    assert metrics[M.RETRIES] == 1
    assert metrics[M.CPU_FALLBACK_EVENTS] == 0


def test_host_half_error_is_the_tasks_before_any_row_went_downstream(
        tmp_path, monkeypatch):
    """An IO error of the host half is the task's, and comes before the
    split's first device half: nothing went downstream, and the
    task-level retry reads the split again."""
    (path,) = _write_files(tmp_path, files=1)
    read_chunk_bytes = PD.read_chunk_bytes
    calls = []

    def flaky_read(path, col):
        calls.append(col.path_in_schema)
        if len(calls) == 2:
            raise OSError("test: disk hiccup at rg 1")
        return read_chunk_bytes(path, col)

    monkeypatch.setattr(PD, "read_chunk_bytes", flaky_read)
    session = _new_session()
    try:
        got = _rows(session, tmp_path)
        metrics = dict(session.last_query_metrics)
    finally:
        session.stop()
    assert [r[0] for r in got] == list(range(3 * ROWS))
    assert metrics[M.CPU_FALLBACK_EVENTS] == 0
    # one row group and a chunk before the error, then the whole split
    assert len(calls) == 1 + 1 + 3
    assert live_reader_count() == 0


# ---------------------------------------------------------------------------
# nothing is left behind
# ---------------------------------------------------------------------------
def test_abandoned_scan_leaves_no_reader(tmp_path):
    _write_files(tmp_path, files=2, row_groups=4)
    session = _new_session(**{PREFETCH: 1})
    try:
        rows = session.read.parquet(str(tmp_path)).limit(5).collect()
        assert len(rows) == 5
        assert live_reader_count() == 0
        CX.assert_reclaimed()
    finally:
        session.stop()


def test_cancelled_scan_leaves_no_reader(tmp_path, monkeypatch):
    """A deadline that fires while a task is in its device half and the
    others hold staged row groups: no thread is left behind when the
    error reaches the caller, and the permits are back."""
    _write_files(tmp_path, files=2, row_groups=4)
    entered = []

    def grinding(self, plan, item, conf):
        entered.append(item.rg)
        CX.cancel_aware_sleep(60.0, site="test.scan")
        raise AssertionError("the sleep outlived the deadline")

    monkeypatch.setattr(SCAN.TpuFileScanExec, "_decode_staged", grinding)
    session = _new_session(**{PREFETCH: 2})
    try:
        with pytest.raises(CX.TpuDeadlineExceeded):
            # (room for the host half on a loaded machine: the deadline has
            # to find a task in its device half)
            session.read.parquet(str(tmp_path)).collect(timeout=2.0)
        assert entered
        assert session.last_query_metrics["cancelledQueries"] == 1
        assert live_reader_count() == 0
        CX.assert_reclaimed()
    finally:
        session.stop()


# ---------------------------------------------------------------------------
# the host decoder's path (no STRING column): packed ahead of the permit
# ---------------------------------------------------------------------------
FIXED = ["id", "q", "d", "x"]  # `_write_files` without its string


class _Watch:
    """Every `stage_upload` and every `StagedUpload.upload` of a query,
    with whether the task it ran for held its permit just then. A reader
    thread has no task id of its own: `_read_host_iter` is made on the
    task's thread, which is where the id is taken, and pulled wherever
    the prefetch depth puts it. Threads are noted as objects, kept alive
    here: a thread's ident is free to go to the next thread once it has
    ended, a reader's to a pool thread started late."""

    def __init__(self, monkeypatch, before_upload=None):
        self.packs, self.uploads = [], []
        self._task = threading.local()
        watch = self
        read_host_iter = SCAN.TpuFileScanExec._read_host_iter
        stage_upload = HostColumnarBatch.stage_upload
        upload = StagedUpload.upload

        def owned_iter(scan, split, conf, *args):
            task = current_task_id()

            def pulled():
                watch._task.id = task
                yield from read_host_iter(scan, split, conf, *args)

            return pulled()

        def watched_pack(hb):
            task = watch._task.id
            held = TpuSemaphore.get().held_by(task)
            staged = stage_upload(hb)
            held = held or TpuSemaphore.get().held_by(task)
            watch.packs.append((task, held, threading.current_thread(),
                                staged))
            return staged

        def watched_upload(staged):
            if before_upload is not None:
                before_upload(watch, staged)
            bytes_before = [b.tobytes() for b in staged.bufs]
            batch = upload(staged)
            watch.uploads.append(
                (current_task_id(),
                 TpuSemaphore.get().held_by(current_task_id()),
                 threading.current_thread(), staged, bytes_before))
            return batch

        monkeypatch.setattr(SCAN.TpuFileScanExec, "_read_host_iter",
                            owned_iter)
        monkeypatch.setattr(HostColumnarBatch, "stage_upload", watched_pack)
        monkeypatch.setattr(StagedUpload, "upload", watched_upload)


def _fixed_rows(session, root):
    return sorted(session.read.parquet(str(root)).select(*FIXED).collect(),
                  key=lambda r: r[0])


def _assert_every_row_once(rows, paths):
    table = pa.concat_tables([pq.read_table(p, columns=FIXED)
                              for p in paths])
    assert [r[0] for r in rows] == list(range(table.num_rows))
    for i, name in enumerate(FIXED):
        assert [r[i] for r in rows] == table.column(name).to_pylist(), name


@pytest.mark.parametrize("depth", [0, 1])
def test_arrow_path_packs_no_split_under_a_held_permit(
        tmp_path, monkeypatch, depth):
    paths = _write_files(tmp_path, files=3, row_groups=2)
    watch = _Watch(monkeypatch)
    session = _new_session(**{PREFETCH: depth, C.OBS_TRACING.key: True})
    try:
        rows = _fixed_rows(session, tmp_path)
        trace = session.last_query_trace
    finally:
        session.stop()
    _assert_every_row_once(rows, paths)
    # a split a task, packed once, and never while its task held a permit
    assert len(watch.packs) == 3 and len(watch.uploads) == 3
    assert not any(held for _task, held, _thread, _staged in watch.packs)
    assert len({task for task, *_ in watch.packs}) == 3
    # the transfer is what the permit covers: on the task's own thread
    assert all(held for _task, held, *_ in watch.uploads)
    assert {task for task, *_ in watch.uploads} == \
        {task for task, *_ in watch.packs}
    task_threads = {thread for _task, _held, thread, *_ in watch.uploads}
    pack_threads = {thread for _task, _held, thread, _staged in watch.packs}
    if depth:
        assert not pack_threads & task_threads  # the reader's
    else:
        assert pack_threads == task_threads
    # the span tree says the same: the packing inside `scan.host_decode`,
    # which ends before its task asks for the permit; `scan.upload` after
    tasks = [sp for sp in trace.spans() if sp.kind == "task" and
             any(c.name == "scan.host_decode" for c in sp.children)]
    assert len(tasks) == 3
    for task in tasks:
        (decode,) = [c for c in task.children
                     if c.name == "scan.host_decode"]
        (asked,) = [c for c in task.children
                    if c.name == "Acquire TPU Semaphore"]
        (up,) = [c for c in task.children if c.name == "scan.upload"]
        assert decode.end_ns <= asked.start_ns <= asked.end_ns <= up.start_ns
        assert (decode.tid == task.tid) == (depth == 0)
        assert up.tid == task.tid and up.attrs["staged"] == 1
        assert decode.attrs["rows"] == 2 * ROWS
        # the packing is a span of its own under it (PR 43), last of three
        (pack,) = [c for c in decode.children if c.name == "scan.pack"]
        assert decode.children[-1] is pack and pack.tid == decode.tid
        assert 0 < pack.duration_ns <= decode.duration_ns
        assert "pack_ms" not in decode.attrs
        # four columns and their validity, padded to the capacity bucket
        assert pack.attrs["packed_bytes"] == \
            4096 * (8 + 8 + 4 + 8) + 4 * 4096
        assert up.attrs["bytes"] == pack.attrs["packed_bytes"]


@pytest.mark.parametrize("depth", [0, 1])
def test_arrow_path_packs_while_another_task_holds_every_permit(
        tmp_path, monkeypatch, depth):
    """One permit; whichever task gets it stays inside its upload until
    BOTH splits are packed. Packing that waited for a permit would never
    get there."""
    paths = _write_files(tmp_path, files=2, row_groups=2)

    def hold_until_both_are_packed(watch, staged):
        for _ in range(600):
            if len(watch.packs) == 2:
                return
            CX.cancel_aware_sleep(0.05, site="test.scan")
        raise AssertionError(
            "the second split was not packed while the first task held "
            "the only permit")

    watch = _Watch(monkeypatch, hold_until_both_are_packed)
    session = _new_session(**{PREFETCH: depth,
                              C.CONCURRENT_TPU_TASKS.key: 1})
    try:
        rows = _fixed_rows(session, tmp_path)
    finally:
        session.stop()
    _assert_every_row_once(rows, paths)
    assert len(watch.packs) == 2 and len(watch.uploads) == 2
    assert not any(held for _task, held, *_ in watch.packs)


@pytest.mark.parametrize("depth", [0, 1])
def test_arrow_path_upload_error_is_retried_from_the_same_staged_split(
        tmp_path, monkeypatch, depth):
    paths = _write_files(tmp_path, files=2, row_groups=2)
    tried = []

    def fail_the_second(watch, staged):
        tried.append(staged)
        if len(tried) == 2:
            raise R.TpuRetryOOM("RESOURCE_EXHAUSTED: injected at upload 2")

    watch = _Watch(monkeypatch, fail_the_second)
    session = _new_session(**{PREFETCH: depth})
    try:
        rows = _fixed_rows(session, tmp_path)
        metrics = dict(session.last_query_metrics)
    finally:
        session.stop()
    _assert_every_row_once(rows, paths)
    assert metrics[M.RETRIES] == 1
    assert metrics[M.CPU_FALLBACK_EVENTS] == 0
    # the failed upload ran again on the very buffers that were staged:
    # nothing was read or packed a second time
    assert len(tried) == 3 and tried[1] is tried[2]
    assert len(watch.packs) == 2 and len(watch.uploads) == 2
    assert {id(st) for *_, st in watch.packs} == {id(st) for st in tried}


@pytest.mark.parametrize("depth", [0, 1])
def test_arrow_path_staged_splits_own_their_bytes(
        tmp_path, monkeypatch, depth):
    """No pool, no reuse, no fix-up after `upload()`: `jnp.asarray` may
    return before a transfer has finished (and on this backend the device
    array may alias the host one), so a staged buffer is written by
    nobody once it is packed."""
    _write_files(tmp_path, files=3, row_groups=2)
    watch = _Watch(monkeypatch)
    session = _new_session(**{PREFETCH: depth})
    try:
        session.read.parquet(str(tmp_path)).select(*FIXED) \
            .agg(F.sum("x"), F.sum("q"), F.count("d")).collect()
    finally:
        session.stop()
    assert len(watch.uploads) == 3
    staged = [st for *_, st, _before in watch.uploads]
    for i, a in enumerate(staged):
        assert all(isinstance(b, np.ndarray) and b.flags.owndata
                   for b in a.bufs)
        for b in staged[i + 1:]:
            assert not any(np.shares_memory(x, y)
                           for x in a.bufs for y in b.bufs)
    # downstream has consumed the batches; the staged bytes are as packed
    for *_, st, before in watch.uploads:
        assert [b.tobytes() for b in st.bufs] == before
