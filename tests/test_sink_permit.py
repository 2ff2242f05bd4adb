"""The sink's fence gives the chip back before it rebuilds host columns
(PR 44): `DeviceToHostExec` holds the admission permit for a run's fetch
(pack, wait, transfer) and for the look-ahead pull of its child, and the
`_download_finish` of a partition's last run runs without it.

Held here: (i) no upload and no concat of a task happens while it does
not hold the permit, for a partition of one batch, of several (the run
ramp 1, 2, 4) and for a child with buffered device work (a coalesce);
(ii) the last run's finish does not hold it, every earlier one does;
(iii) the run's device arrays are unreferenced when the permit goes back;
(iv) an exception in the fetch or in the finish, and a consumer that
stops early, still release; the two-call download gives `to_host_many`'s
batches to the byte; an early-exit consumer sees its first host batch
after two child batches."""

import threading
import weakref

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

import spark_rapids_tpu as srt
from spark_rapids_tpu import conf as C
from spark_rapids_tpu.columnar import batch as B
from spark_rapids_tpu.columnar.batch import (
    ColumnarBatch,
    HostColumnarBatch,
    HostColumnVector,
)
from spark_rapids_tpu.columnar.dtypes import DataType
from spark_rapids_tpu.columnar.encoded import (
    DeviceDictionary,
    HostDictionaryColumn,
)
from spark_rapids_tpu.exec import transitions as T
from spark_rapids_tpu.exec.base import (
    ExecContext,
    PartitionedBatches,
    PhysicalExec,
)
from spark_rapids_tpu.exec.transitions import (
    DeviceToHostExec,
    TargetSize,
    TpuCoalesceBatchesExec,
    current_task_id,
)
from spark_rapids_tpu.memory.semaphore import TpuSemaphore
from spark_rapids_tpu.plan import functions as F

ROWS = 300


def held() -> bool:
    return TpuSemaphore.get().held_by(current_task_id())


def host_batch(seed: int, rows: int = ROWS, string: bool = False,
               coded: bool = False) -> HostColumnarBatch:
    rng = np.random.default_rng(seed)
    valid = rng.random(rows) > 0.2
    cols = [HostColumnVector(DataType.INT64,
                             rng.integers(-9, 9, rows), valid.copy()),
            HostColumnVector(DataType.FLOAT64, rng.random(rows),
                             np.ones(rows, dtype=bool)),
            HostColumnVector(DataType.INT32,
                             rng.integers(0, 99, rows).astype(np.int32),
                             ~valid)]
    if string:
        strs = np.array([f"s{seed}-{i % 7}" * (i % 3) for i in range(rows)],
                        dtype=object)
        cols.append(HostColumnVector(DataType.STRING, strs, valid.copy()))
    if coded:
        d = DeviceDictionary.from_values([b"A", b"N", b"R"])
        cols.append(HostDictionaryColumn(
            DataType.STRING, rng.integers(0, 3, rows).astype(np.int32),
            valid.copy(), d))
    return HostColumnarBatch(cols, rows)


class Source(PhysicalExec):
    """A device child as a scan is one: each batch of a partition is
    uploaded under the permit when it is asked for. `pulled` counts the
    batches handed over, `arrays` keeps a weak reference to every device
    array made."""

    def __init__(self, partitions, device_count: bool = False):
        super().__init__()
        self.partitions = partitions
        self.device_count = device_count
        self.pulled = 0
        self.ended = 0
        self.arrays = []

    def execute(self, ctx):
        def factory(pidx):
            for hb in self.partitions[pidx]:
                TpuSemaphore.get().acquire_if_necessary(current_task_id())
                db = hb.to_device()
                if self.device_count:
                    import jax.numpy as jnp

                    db = ColumnarBatch(db.columns, jnp.int32(db.num_rows))
                for cv in db.columns:
                    self.arrays.extend(weakref.ref(a) for a in
                                       (cv.data, cv.validity))
                self.pulled += 1
                yield db
                del db
            self.ended += 1

        return PartitionedBatches(len(self.partitions), factory)


@pytest.fixture
def sem():
    """A semaphore of two permits of which a task takes both, as a write
    does (admission weight 2 of 2): the state of the process's own is put
    back afterwards."""
    TpuSemaphore.shutdown()
    s = TpuSemaphore.initialize(2)
    s.set_query_weight(2)
    yield s
    s.release_if_necessary(current_task_id())
    TpuSemaphore.shutdown()


@pytest.fixture
def finishes(monkeypatch):
    """Every `_download_finish` of the test, as (task, permit held, rows
    of the batch it rebuilt)."""
    seen = []
    real = B._download_finish

    def recorded(columns, host, offs, n, trim, keep_encoded=False):
        hb = real(columns, host, offs, n, trim, keep_encoded=keep_encoded)
        seen.append((current_task_id(), held(), hb.num_rows))
        return hb

    monkeypatch.setattr(B, "_download_finish", recorded)
    return seen


@pytest.fixture
def uploads(monkeypatch):
    """Whether the permit was held at every `StagedUpload.upload` (a
    scan's and `HostToDeviceExec`'s both end there)."""
    seen = []
    real = B.StagedUpload.upload

    def recorded(self):
        seen.append(held())
        return real(self)

    monkeypatch.setattr(B.StagedUpload, "upload", recorded)
    return seen


def drain(exec_, pidx=0):
    return list(exec_.execute(ExecContext(None)).iterator(pidx))


def same_bytes(got: HostColumnarBatch, want: HostColumnarBatch):
    assert got.num_rows == want.num_rows
    assert len(got.columns) == len(want.columns)
    for g, w in zip(got.columns, want.columns):
        assert type(g) is type(w) and g.dtype is w.dtype
        assert g.data.dtype == w.data.dtype
        assert np.array_equal(g.validity, w.validity)
        if g.data.dtype == object:
            assert list(g.data) == list(w.data)
        else:
            assert g.data.tobytes() == w.data.tobytes()
        if isinstance(w, HostDictionaryColumn):
            assert g.dictionary is w.dictionary


# ---------------------------------------------------------------------------
# (i), (ii): who holds the permit when
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("batches, last_run", [(1, 1), (2, 1), (3, 2),
                                               (7, 4), (8, 1)])
def test_only_the_last_runs_finish_runs_without_the_permit(
        sem, finishes, uploads, batches, last_run):
    """The run ramp 1, 2, 4, ...: every finish but the last run's holds
    the permit (its look-ahead gave a batch), the last run's does not,
    every upload does, and the host batches come in the child's order."""
    parts = [[host_batch(i, rows=ROWS + i) for i in range(batches)]]
    source = Source(parts)
    out = drain(DeviceToHostExec(source))
    assert [hb.num_rows for hb in out] == [ROWS + i for i in range(batches)]
    assert [rows for _, _, rows in finishes] == [hb.num_rows for hb in out]
    assert [h for _, h, _ in finishes] == \
        [True] * (batches - last_run) + [False] * last_run
    assert uploads == [True] * batches
    assert source.ended == 1 and not held()


def test_an_empty_partition_downloads_nothing(sem, finishes):
    source = Source([[]])
    assert drain(DeviceToHostExec(source)) == []
    assert finishes == [] and source.ended == 1 and not held()


def test_a_coalesces_buffered_concat_runs_under_the_permit(
        sem, finishes, uploads, monkeypatch):
    """A child with device work of its own behind the source: the
    coalesce concatenates what it still holds when the source ends. That
    pull comes before the release, so every concat sees the permit held,
    the last one too."""
    concats = []
    real = T.concat_batches

    def recorded(pieces):
        concats.append((len(pieces), held()))
        return real(pieces)

    monkeypatch.setattr(T, "concat_batches", recorded)
    hbs = [host_batch(i) for i in range(6)]
    one = hbs[0].to_device().device_memory_size()
    uploads.clear()
    plan = DeviceToHostExec(TpuCoalesceBatchesExec(
        TargetSize(int(one * 2.5)), Source([hbs])))
    out = drain(plan)
    assert [hb.num_rows for hb in out] == [2 * ROWS] * 3
    assert concats == [(2, True)] * 3
    assert uploads == [True] * 6
    # the first run's finish comes under the permit (a batch followed
    # it); the second run's two, fetched after the source had ended and
    # the coalesce had emptied itself, without it
    assert [h for _, h, _ in finishes] == [True, False, False]
    assert not held()


def test_a_task_that_uploads_after_the_sink_takes_the_permit_again(
        sem, finishes, uploads):
    """A host operator above the sink that goes back to the device
    (`HostToDeviceExec`): the permit given back at the last finish is
    asked for again before the upload."""
    down = DeviceToHostExec(Source([[host_batch(1), host_batch(2)]]))
    up = T.HostToDeviceExec(down)
    out = drain(up)
    assert [b.num_rows for b in out] == [ROWS, ROWS]
    assert [h for _, h, _ in finishes] == [True, False]
    assert uploads == [True] * 4


# ---------------------------------------------------------------------------
# (iii): nothing of the run is left on the device at the release
# ---------------------------------------------------------------------------
def test_the_runs_device_arrays_are_gone_before_the_release(
        sem, monkeypatch):
    source = Source([[host_batch(i) for i in range(3)]])
    alive_at_release = []
    real = TpuSemaphore.release_if_necessary

    def recorded(self, task_id):
        if self.held_by(task_id):
            alive_at_release.append(
                sum(r() is not None for r in source.arrays))
        return real(self, task_id)

    monkeypatch.setattr(TpuSemaphore, "release_if_necessary", recorded)
    out = drain(DeviceToHostExec(source))
    assert len(out) == 3 and len(source.arrays) == 3 * 3 * 2
    assert alive_at_release == [0]


# ---------------------------------------------------------------------------
# the same batches as the one call
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("keep_encoded", [False, True])
@pytest.mark.parametrize("device_count", [False, True])
def test_host_batches_equal_to_host_many_in_one_call(sem, keep_encoded,
                                                     device_count):
    """In the child's order, to the byte: fixed-width columns with nulls,
    a DOUBLE, a plain STRING column, a dictionary-coded one (kept as
    codes or expanded), the row count on the host or on the device."""
    hbs = [host_batch(i, rows=40 + 3 * i, string=True, coded=True)
           for i in range(5)]
    got = drain(DeviceToHostExec(Source([hbs], device_count),
                                 keep_encoded=keep_encoded))
    sem.release_if_necessary(current_task_id())
    dbs = list(Source([hbs], device_count).execute(None).iterator(0))
    want = B.to_host_many(dbs, keep_encoded=keep_encoded)
    assert len(got) == len(want) == 5
    for g, w in zip(got, want):
        same_bytes(g, w)
    assert isinstance(got[0].columns[-1], HostDictionaryColumn) \
        == keep_encoded
    # a row count that came over with the batch stays with it
    assert all(db.rows_on_host for db in dbs)


def test_fetch_many_finishes_what_to_host_many_does(sem):
    """`fetch_many` alone, over a byte budget that cuts the batches into
    several fences: the finish touches no device batch (they are
    dropped before it) and gives `to_host_many`'s batches."""
    hbs = [host_batch(i, string=True) for i in range(4)]
    dbs = [hb.to_device() for hb in hbs]
    budget = dbs[0].device_memory_size() * 2
    want = B.to_host_many(dbs, byte_budget=budget)
    finish = B.fetch_many(dbs, byte_budget=budget)
    arrays = [weakref.ref(cv.data) for db in dbs for cv in db.columns]
    del dbs
    assert not any(r() is not None for r in arrays)
    for g, w in zip(finish(), want):
        same_bytes(g, w)


# ---------------------------------------------------------------------------
# (iv): the backstop
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("where", ["fetch", "finish"])
def test_an_exception_still_releases_the_permit(sem, monkeypatch, where):
    def boom(*a, **kw):
        raise RuntimeError(f"no {where}")

    if where == "fetch":
        monkeypatch.setattr(B, "_download_grouped", boom)
    else:
        monkeypatch.setattr(B, "_download_finish", boom)
    source = Source([[host_batch(1), host_batch(2)]])
    with pytest.raises(RuntimeError, match=f"no {where}"):
        drain(DeviceToHostExec(source))
    assert source.pulled >= 1 and not held()
    # and the next task gets the chip
    done = threading.Event()

    def other():
        TpuSemaphore.get().acquire_if_necessary(current_task_id())
        TpuSemaphore.get().release_if_necessary(current_task_id())
        done.set()

    t = threading.Thread(target=other)
    t.start()
    t.join(timeout=10)
    assert done.is_set()


def test_an_early_exit_sees_its_first_batch_after_two_child_batches(
        sem, finishes):
    """The docstring's bound: the first host batch after at most two
    child batches (the run of one and the look-ahead), and a consumer
    that stops there leaves the permit released."""
    source = Source([[host_batch(i) for i in range(9)]])
    it = DeviceToHostExec(source).execute(ExecContext(None)).iterator(0)
    first = next(it)
    assert first.num_rows == ROWS and source.pulled == 2
    assert held()                      # the look-ahead's batch is pending
    it.close()
    assert source.pulled == 2 and not held()
    # the second run (two batches) after two more: 1 + 2 + a look-ahead
    it = DeviceToHostExec(source).execute(ExecContext(None)).iterator(0)
    source.pulled = 0
    assert [next(it).num_rows for _ in range(2)] == [ROWS, ROWS]
    assert source.pulled == 4
    it.close()
    assert not held()


# ---------------------------------------------------------------------------
# a parquet write of eight partitions, end to end
# ---------------------------------------------------------------------------
def test_parquet_write_of_eight_partitions(tmp_path, finishes, uploads):
    """Eight files in, eight tasks, eight files out through the chip's
    sink (the device encoder off): in every task the last
    `_download_finish` runs without the permit, every upload with it, the
    traced `sink.finish` says so (`permit_held`), and the rows are the
    source's."""
    src = tmp_path / "src"
    src.mkdir()
    rng = np.random.default_rng(44)
    tables = []
    for i in range(8):
        rows = 2000 + 100 * i
        tables.append(pa.table({
            "q": rng.integers(1, 51, rows).astype(np.int64),
            "x": rng.integers(0, 11, rows) / 100.0,
            "f": pa.array(rng.choice(["A", "N", "R"], rows))}))
        pq.write_table(tables[-1], str(src / f"f{i}.parquet"),
                       compression="snappy")
    session = srt.new_session({"rapids.tpu.sql.spmd.meshDevices": 1,
                               C.PARQUET_DEVICE_ENCODE.key: False,
                               C.OBS_TRACING.key: True})
    try:
        df = session.read.parquet(str(src))
        df.filter(F.col("q") > 0).write.parquet(str(tmp_path / "out"))
        trace = session.last_query_trace
    finally:
        session.stop()
    tasks = {}
    for task, was_held, _rows in finishes:
        tasks.setdefault(task, []).append(was_held)
    assert len(tasks) == 8, tasks
    assert all(seen[-1] is False for seen in tasks.values()), tasks
    assert len(uploads) >= 8 and all(uploads)
    spans = trace.find("sink.finish")
    assert len(spans) == len(finishes)
    assert sorted(sp.attrs["permit_held"] for sp in spans) == \
        sorted(h for _, h, _ in finishes)
    fences = trace.find("DeviceToHost")
    assert all(sp in [c for f in fences for c in f.children] for sp in spans)
    got = pq.read_table(str(tmp_path / "out"))
    want = pa.concat_tables(tables)
    key = [("q", "ascending"), ("x", "ascending"), ("f", "ascending")]
    assert got.select(["q", "x", "f"]).sort_by(key).equals(
        want.sort_by(key))


def test_traced_write_of_partitions_of_several_batches(tmp_path, finishes,
                                                       uploads):
    """Two files of one row group read 1,000 rows a batch, coalesced to
    40 kB: several runs a task, behind a coalesce that keeps its span
    open across its yields. The spans
    stay a tree (every `sink.finish` the last child of a closed
    `DeviceToHost` that lies inside it), each task's last finish runs
    without the permit, every upload with it, the rows are the source's."""
    src = tmp_path / "src"
    src.mkdir()
    rng = np.random.default_rng(45)
    tables = [pa.table({"q": rng.integers(1, 51, 6000).astype(np.int64),
                        "x": rng.integers(0, 11, 6000) / 100.0})
              for _ in range(2)]
    for i, t in enumerate(tables):
        pq.write_table(t, str(src / f"f{i}.parquet"), row_group_size=6000)
    session = srt.new_session({"rapids.tpu.sql.spmd.meshDevices": 1,
                               C.PARQUET_DEVICE_ENCODE.key: False,
                               C.MAX_READ_BATCH_SIZE_ROWS.key: 1000,
                               C.BATCH_SIZE_BYTES.key: 40000,
                               C.OBS_TRACING.key: True})
    try:
        df = session.read.parquet(str(src))
        df.filter(F.col("q") > 0).write.parquet(str(tmp_path / "out"))
        trace = session.last_query_trace
    finally:
        session.stop()
    tasks = {}
    for task, was_held, _rows in finishes:
        tasks.setdefault(task, []).append(was_held)
    assert len(tasks) == 2 and all(uploads)
    for seen in tasks.values():
        assert len(seen) > 1 and seen[-1] is False and all(seen[:1])
    fences = trace.find("DeviceToHost")
    assert len(fences) > 2
    for fence in fences:
        last = fence.children[-1]
        assert last.name == "sink.finish"
        assert fence.start_ns <= last.start_ns <= last.end_ns <= fence.end_ns
    assert len(trace.find("sink.finish")) == len(fences)
    got = pq.read_table(str(tmp_path / "out"))
    key = [("q", "ascending"), ("x", "ascending")]
    assert got.sort_by(key).equals(pa.concat_tables(tables).sort_by(key))
