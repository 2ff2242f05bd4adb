"""Columnar substrate tests (reference test model: GpuColumnVector round-trip
coverage inside tests/ suites; GpuCoalesceBatchesSuite for concat)."""

import itertools

import numpy as np
import pytest

from spark_rapids_tpu.columnar.dtypes import DataType
from spark_rapids_tpu.columnar.batch import (
    ColumnarBatch,
    HostColumnarBatch,
    HostColumnVector,
    bucket_capacity,
    compact_batch,
    concat_batches,
    gather_batch,
    slice_batch_host,
)
import spark_rapids_tpu.columnar.batch as B
from spark_rapids_tpu.columnar import encoded as ENC
from spark_rapids_tpu.columnar.dtypes import DecimalType
import jax.numpy as jnp


def test_bucket_capacity():
    assert bucket_capacity(0) == 8
    assert bucket_capacity(8) == 8
    assert bucket_capacity(9) == 16
    assert bucket_capacity(1000) == 1024


def make_host_batch():
    return HostColumnarBatch(
        [
            HostColumnVector.from_pylist([1, 2, None, 4, 5], DataType.INT32),
            HostColumnVector.from_pylist([1.5, None, 3.5, 4.5, 5.5], DataType.FLOAT64),
            HostColumnVector.from_pylist(["a", "bb", None, "dddd", ""], DataType.STRING),
            HostColumnVector.from_pylist([True, False, True, None, False], DataType.BOOL),
        ]
    )


def test_roundtrip_host_device_host():
    hb = make_host_batch()
    db = hb.to_device()
    assert db.num_rows == 5
    assert db.capacity == 8
    back = db.to_host()
    assert back.to_pylist_rows() == hb.to_pylist_rows()


def test_string_roundtrip_unicode():
    hb = HostColumnarBatch(
        [HostColumnVector.from_pylist(["héllo", "wörld", None, "日本語", ""], DataType.STRING)]
    )
    back = hb.to_device().to_host()
    assert back.columns[0].to_pylist() == ["héllo", "wörld", None, "日本語", ""]


def test_concat_batches():
    hb1 = make_host_batch()
    hb2 = make_host_batch()
    db = concat_batches([hb1.to_device(), hb2.to_device()])
    assert db.num_rows == 10
    rows = db.to_host().to_pylist_rows()
    assert rows == hb1.to_pylist_rows() + hb2.to_pylist_rows()


def test_compact_filter():
    hb = make_host_batch()
    db = hb.to_device()
    keep = jnp.asarray(np.array([True, False, True, False, True, True, True, True]))
    out = compact_batch(db, keep)
    assert out.num_rows == 3
    rows = out.to_host().to_pylist_rows()
    expected = [r for i, r in enumerate(hb.to_pylist_rows()) if i in (0, 2, 4)]
    assert rows == expected


def test_gather_with_null_rows():
    hb = make_host_batch()
    db = hb.to_device()
    idx = jnp.asarray(np.array([4, 0, 99, 1, 0, 0, 0, 0], dtype=np.int32))
    valid = jnp.asarray(np.array([True, True, False, True] + [False] * 4))
    out = gather_batch(db, idx, 4, indices_valid=valid)
    rows = out.to_host().to_pylist_rows()
    src = hb.to_pylist_rows()
    assert rows[0] == src[4]
    assert rows[1] == src[0]
    assert rows[2] == (None, None, None, None)
    assert rows[3] == src[1]


def test_slice():
    hb = make_host_batch()
    db = hb.to_device()
    out = slice_batch_host(db, 1, 3)
    assert out.num_rows == 3
    assert out.to_host().to_pylist_rows() == hb.to_pylist_rows()[1:4]


def test_large_batch_capacity_bucketing():
    n = 1000
    hb = HostColumnarBatch(
        [HostColumnVector.from_numpy(np.arange(n, dtype=np.int64))]
    )
    db = hb.to_device()
    assert db.capacity == 1024
    assert db.to_host().to_pylist_rows() == [(i,) for i in range(n)]


def test_from_numpy_datetime_units():
    # review finding: datetime64 units must normalize to us (TIMESTAMP) / D (DATE)
    ns = np.array(["2020-01-01T00:00:00", "NaT"], dtype="datetime64[ns]")
    hv = HostColumnVector.from_numpy(ns)
    assert hv.dtype == DataType.TIMESTAMP
    assert hv.data[0] == 1577836800000000  # microseconds
    assert list(hv.validity) == [True, False]
    d = np.array(["2020-01-02"], dtype="datetime64[D]")
    hv2 = HostColumnVector.from_numpy(d)
    assert hv2.dtype == DataType.DATE
    assert hv2.data[0] == 18263


def test_from_numpy_object_strings_with_none():
    hv = HostColumnVector.from_numpy(np.array(["a", None], dtype=object))
    assert hv.to_pylist() == ["a", None]
    # must survive upload
    db = HostColumnarBatch([hv]).to_device()
    assert db.to_host().columns[0].to_pylist() == ["a", None]


def test_gather_oob_index_yields_null_row():
    # review finding: OOB index must emit a null row even when the source
    # batch exactly fills its capacity bucket
    hb = HostColumnarBatch(
        [HostColumnVector.from_numpy(np.arange(8, dtype=np.int32))]
    )
    db = hb.to_device()
    assert db.capacity == 8
    idx = jnp.asarray(np.array([99, 0, -1, 7, 0, 0, 0, 0], dtype=np.int32))
    out = gather_batch(db, idx, 4)
    assert out.to_host().to_pylist_rows() == [(None,), (0,), (None,), (7,)]


def test_semaphore_concurrent_same_task():
    # review finding: concurrent same-task acquires must consume one permit
    import threading
    from spark_rapids_tpu.memory.semaphore import TpuSemaphore

    sem = TpuSemaphore(1)
    threads = [
        threading.Thread(target=sem.acquire_if_necessary, args=(7,))
        for _ in range(4)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=5)
    assert all(not t.is_alive() for t in threads)  # no deadlock: 1 permit, same task
    sem.release_if_necessary(7)
    # permit fully restored: a different task can acquire immediately
    done = []
    t = threading.Thread(target=lambda: (sem.acquire_if_necessary(8), done.append(1)))
    t.start(); t.join(timeout=5)
    assert done == [1]


def test_lazy_filter_compact_matches_eager():
    """filterCompactSync=never: the filter emits a suffix-compacted batch
    at the input capacity with a TRACED row count; results must match the
    eager (synced) path exactly, strings included."""
    import numpy as np

    import spark_rapids_tpu as srt
    from spark_rapids_tpu.plan import functions as F

    session = srt.new_session()
    rng = np.random.default_rng(33)
    n = 4000
    df = session.createDataFrame({
        "k": rng.integers(0, 40, n).astype(np.int64),
        "v": rng.integers(-1000, 1000, n).astype(np.int64),
        "s": [None if i % 11 == 0 else f"s{i % 23}" for i in range(n)],
    }).cache()
    q = (df.filter((F.col("v") > -500) & (F.col("v") < 700))
           .filter(F.col("s").isNotNull())      # chained lazy filters
           .groupBy("k").agg(F.sum("v").alias("sv"),
                             F.min("s").alias("mn"),
                             F.count("*").alias("c")))
    try:
        session.conf.set("rapids.tpu.engine.filterCompactSync", "never")
        got = sorted(q.collect(), key=repr)
    finally:
        session.conf.set("rapids.tpu.engine.filterCompactSync", "always")
    want = sorted(q.collect(), key=repr)
    assert got == want
    # empty result through the lazy path
    try:
        session.conf.set("rapids.tpu.engine.filterCompactSync", "never")
        assert df.filter(F.col("v") > 10**9).collect() == []
    finally:
        session.conf.set("rapids.tpu.engine.filterCompactSync", "auto")


# ---------------------------------------------------------------------------
# stage_upload: one pass, byte for byte what five passes gave
# ---------------------------------------------------------------------------
def _reference_stage_upload(hb):
    """The plain reference packer: `HostColumnarBatch.stage_upload` as it
    was until PR 32, five passes over a fixed-width column (zeros, the
    nulls zeroed at the SOURCE width, the assignment with its cast, the
    validity's zeros + copy, one concatenate a dtype group). The packer
    in the package is held to it byte for byte: what goes up, where each
    segment lies and what each column is must not move."""
    n = hb.num_rows
    cap = bucket_capacity(n)
    parts = []  # (group, seg, want_bool)
    specs = []
    for hc in hb.columns:
        validity = np.zeros(cap, dtype=bool)
        validity[:n] = hc.validity[:n]
        if isinstance(hc, ENC.HostDictionaryColumn):
            codes = np.zeros(cap, dtype=np.int32)
            codes[:n] = np.where(hc.validity[:n], hc.data[:n], 0)
            parts.append(("int32", codes, False))
            parts.append(("uint8", validity.view(np.uint8), True))
            specs.append(("dict", hc.dtype, hc.dictionary))
        elif hc.dtype is DataType.STRING:
            encoded = [
                s.encode("utf-8") if isinstance(s, str) else bytes(s)
                for s in hc.data[:n]
            ]
            lengths = np.fromiter(
                (len(b) if validity[i] else 0
                 for i, b in enumerate(encoded)),
                dtype=np.int32, count=n,
            )
            offsets = np.zeros(cap + 1, dtype=np.int32)
            np.cumsum(lengths, out=offsets[1:n + 1])
            offsets[n + 1:] = offsets[n]
            nbytes = int(offsets[n])
            byte_cap = bucket_capacity(max(nbytes, 1))
            buf = np.zeros(byte_cap, dtype=np.uint8)
            if nbytes:
                joined = b"".join(
                    b if validity[i] else b""
                    for i, b in enumerate(encoded))
                buf[:nbytes] = np.frombuffer(joined, dtype=np.uint8)
            parts.append(("int32", offsets, False))
            parts.append(("uint8", buf, False))
            parts.append(("uint8", validity.view(np.uint8), True))
            specs.append(("string",
                          B.len_bucket(int(lengths.max()) if n else 1)))
        else:
            npdt = B.physical_np_dtype(hc.dtype)
            data = np.zeros(cap, dtype=npdt)
            data[:n] = np.where(hc.validity[:n], hc.data[:n], 0)
            if npdt == np.dtype(np.bool_):
                parts.append(("uint8", data.view(np.uint8), True))
            else:
                parts.append((npdt.name, data, False))
            parts.append(("uint8", validity.view(np.uint8), True))
            specs.append(("fixed", hc.dtype,
                          B.host_value_range(hc.dtype, data[:n])))
    order = {}
    for gname, seg, _want in parts:
        order.setdefault(gname, []).append(seg)
    keys = tuple(sorted(order))
    bufs = tuple(np.concatenate(order[k]) for k in keys)
    layout = []
    offs = {k: 0 for k in keys}
    for gname, seg, want in parts:
        layout.append((keys.index(gname), offs[gname], seg.shape[0], want))
        offs[gname] += seg.shape[0]
    return n, specs, bufs, tuple(layout)


def _assert_packs_as_the_reference(hb):
    want_n, want_specs, want_bufs, want_layout = _reference_stage_upload(hb)
    before = [(c.data.copy(), np.array(c.validity)) for c in hb.columns]
    staged = hb.stage_upload()
    assert staged.num_rows == want_n
    assert staged.layout == want_layout
    assert all(type(x) is int for seg in staged.layout for x in seg[:3])
    assert len(staged.specs) == len(want_specs)
    for got, want in zip(staged.specs, want_specs):
        assert got[:2] == want[:2]
        if got[0] == "dict":
            assert got[2] is want[2]
        else:
            assert got == want and type(got[-1]) is type(want[-1])
    assert len(staged.bufs) == len(want_bufs)
    for got, want in zip(staged.bufs, want_bufs):
        assert got.dtype == want.dtype and got.shape == want.shape
        assert got.tobytes() == want.tobytes()
        # its own memory, which the source's does not reach
        assert got.flags.writeable and got.flags.c_contiguous
        for c in hb.columns:
            assert not np.shares_memory(got, c.validity)
            if c.data.dtype != object:
                assert not np.shares_memory(got, c.data)
    # the source is read, never written
    for c, (data, validity) in zip(hb.columns, before):
        assert np.array_equal(c.validity, validity)
        assert np.array_equal(c.data, data) if data.dtype == object \
            else c.data.tobytes() == data.tobytes()
    return staged


_CAP = 32  # a capacity bucket: rows of 31, 32 and 33 sit around its edge
_ROWS = {"0": 0, "1": 1, "cap-1": _CAP - 1, "cap": _CAP, "cap+1": _CAP + 1}
_STRINGS = ["", "a", "héllo", "日本語", "x" * 40, "tab\there", "NUL\x00in"]


def _source(kind, n, rng):
    """(dtype, data) of `n` values whose null lanes would show if they
    went up: no zero among them where the type allows it."""
    if kind == "BOOL":
        return DataType.BOOL, np.ones(n, dtype=bool) ^ (rng.random(n) < 0.3)
    if kind == "INT32":
        return DataType.INT32, rng.integers(-2**31, 2**31, n).astype(np.int32)
    if kind == "INT64_in_int32":
        return DataType.INT64, rng.integers(-1000, 70000, n).astype(np.int64)
    if kind == "INT64_wide":
        return DataType.INT64, rng.integers(-2**62, 2**62, n, dtype=np.int64)
    if kind == "FLOAT32":
        data = (rng.standard_normal(n) * 1e3).astype(np.float32)
        data[::5] = np.resize([-0.0, np.inf, np.nan, 1e-42, -np.inf], len(data[::5]))
        return DataType.FLOAT32, data
    if kind in ("FLOAT64", "FLOAT64_as_f32"):
        data = rng.standard_normal(n) * 1e3
        data[::5] = np.resize([-0.0, 1e300, np.nan, 1e-310, -np.inf], len(data[::5]))
        return DataType.FLOAT64, data
    if kind == "DATE":
        return DataType.DATE, rng.integers(8000, 10500, n).astype(np.int32)
    if kind == "TIMESTAMP":
        return DataType.TIMESTAMP, \
            rng.integers(10**15, 2 * 10**15, n, dtype=np.int64)
    if kind == "DECIMAL":
        return DecimalType(12, 2), \
            rng.integers(-10**11, 10**11, n, dtype=np.int64)
    assert kind == "STRING"
    data = np.empty(n, dtype=object)
    data[:] = [_STRINGS[i] for i in rng.integers(0, len(_STRINGS), n)]
    return DataType.STRING, data


_KINDS = ["BOOL", "INT32", "INT64_in_int32", "INT64_wide", "FLOAT32",
          "FLOAT64", "FLOAT64_as_f32", "DATE", "TIMESTAMP", "DECIMAL",
          "STRING", "DICTIONARY"]
_DICTIONARY = ENC.DeviceDictionary.from_values(["x", "yy", "zzz", ""])


def _column(kind, nulls, n, read_only, rng):
    validity = {"none": np.ones(n, dtype=bool),
                "some": rng.random(n) < 0.6,
                "all": np.zeros(n, dtype=bool)}[nulls]
    if kind == "DICTIONARY":
        col = ENC.HostDictionaryColumn(
            DataType.STRING, rng.integers(1, 4, n).astype(np.int32),
            validity, _DICTIONARY)
    else:
        dt, data = _source(kind, n, rng)
        col = HostColumnVector(dt, data, validity)
    if read_only:
        # what Arrow's zero-copy `to_numpy` hands over
        col.data.setflags(write=False)
        col.validity.setflags(write=False)
    return col


# (a DOUBLE of 1e300 is inf at f32 width, in both packers)
_overflows = pytest.mark.filterwarnings("ignore:overflow encountered in cast")


@_overflows
@pytest.mark.parametrize("read_only", [False, True],
                         ids=["writable", "read_only"])
@pytest.mark.parametrize("rows", list(_ROWS))
@pytest.mark.parametrize("nulls", ["none", "some", "all"])
@pytest.mark.parametrize("kind", _KINDS)
def test_stage_upload_equals_the_reference_packer(
        monkeypatch, kind, nulls, rows, read_only):
    if kind == "FLOAT64_as_f32":
        # a TPU's width for a DOUBLE: the cast rides the copy
        monkeypatch.setattr(B, "device_float64_supported", lambda: False)
    n = _ROWS[rows]
    rng = np.random.default_rng([_KINDS.index(kind), n, read_only])
    col = _column(kind, nulls, n, read_only, rng)
    staged = _assert_packs_as_the_reference(HostColumnarBatch([col], n))
    if kind == "FLOAT64_as_f32":
        assert [b.dtype.name for b in staged.bufs] == ["float32", "uint8"]
    if kind.startswith("INT64") and n:
        (spec,) = staged.specs
        narrow = kind == "INT64_in_int32" or not col.validity.any()
        assert B.fits_int32(spec[2]) == narrow


@_overflows
@pytest.mark.parametrize("f64", [True, False], ids=["f64", "f32"])
@pytest.mark.parametrize("rows", list(_ROWS))
@pytest.mark.parametrize("nulls", ["none", "some", "all"])
def test_stage_upload_of_every_kind_in_one_batch(
        monkeypatch, nulls, rows, f64):
    """Every dtype group at once, two columns a kind: the groups' order,
    each segment's place in its group, BOOL and validity and string bytes
    side by side in the uint8 one."""
    monkeypatch.setattr(B, "device_float64_supported", lambda: f64)
    n = _ROWS[rows]
    rng = np.random.default_rng([n, f64])
    kinds = [k for k in _KINDS if k != "FLOAT64_as_f32"]
    cols = [_column(k, nulls, n, i % 2 == 0, rng)
            for i, k in enumerate(kinds + kinds[::-1])]
    staged = _assert_packs_as_the_reference(HostColumnarBatch(cols, n))
    want = ["float32", "int32", "int64", "uint8"]
    assert [b.dtype.name for b in staged.bufs] == \
        (sorted(want + ["float64"]) if f64 else want)


@pytest.mark.parametrize("case", [
    "bool_held_as_uint8", "int32_held_as_int64", "date_held_as_int64",
    "float32_held_as_float64", "validity_held_as_uint8",
    "fewer_rows_than_the_arrays", "strided_source", "string_bytes_values",
    "no_columns", "no_columns_no_rows"])
def test_stage_upload_casts_as_the_assignment_did(case):
    """Sources the engine's own readers do not make but a hand-built batch
    may: the copy casts as the assignment it replaced did (numpy's
    `unsafe`), where `same_kind` would refuse the first of these."""
    n = 21
    rng = np.random.default_rng(7)
    some = rng.random(n) < 0.7
    if case == "bool_held_as_uint8":
        col = HostColumnVector(DataType.BOOL,
                               rng.integers(0, 4, n).astype(np.uint8), some)
    elif case == "int32_held_as_int64":
        col = HostColumnVector(
            DataType.INT32, rng.integers(-2**40, 2**40, n, dtype=np.int64),
            some)
    elif case == "date_held_as_int64":
        col = HostColumnVector(
            DataType.DATE, rng.integers(8000, 10500, n).astype(np.int64),
            some)
    elif case == "float32_held_as_float64":
        col = HostColumnVector(DataType.FLOAT32, rng.standard_normal(n), some)
    elif case == "validity_held_as_uint8":
        col = HostColumnVector(
            DataType.INT64, rng.integers(1, 99, n).astype(np.int64),
            rng.integers(0, 3, n).astype(np.uint8))
    elif case == "strided_source":
        col = HostColumnVector(
            DataType.FLOAT64, rng.standard_normal(2 * n)[::2],
            np.repeat(some, 2)[::2])
    elif case == "string_bytes_values":
        data = np.empty(n, dtype=object)
        data[:] = [b"raw\xff" if i % 3 else "str" for i in range(n)]
        col = HostColumnVector(DataType.STRING, data, some)
    elif case == "fewer_rows_than_the_arrays":
        col = _column("INT64_wide", "some", n, False, rng)
        _assert_packs_as_the_reference(HostColumnarBatch([col], 8))
        _assert_packs_as_the_reference(
            HostColumnarBatch([col], n).slice(16, 16))
        return
    else:
        rows = n if case == "no_columns" else 0
        staged = _assert_packs_as_the_reference(HostColumnarBatch([], rows))
        assert staged.bufs == () and staged.layout == ()
        assert staged.upload().num_rows == rows
        return
    _assert_packs_as_the_reference(HostColumnarBatch([col], n))


def test_staged_uploads_own_their_buffers():
    """Two packings of one batch share no memory with each other, and an
    upload leaves the staged bytes as they were: nothing is pooled, and
    nothing is written once a transfer may be reading it."""
    rng = np.random.default_rng(11)
    cols = [_column(k, "some", 100, True, rng) for k in _KINDS
            if k != "FLOAT64_as_f32"]
    hb = HostColumnarBatch(cols, 100)
    first, second = hb.stage_upload(), hb.stage_upload()
    for a in first.bufs:
        for b in second.bufs:
            assert not np.shares_memory(a, b)
    before = [b.tobytes() for b in first.bufs]
    up = first.upload()
    again = first.upload()  # a retry: pure over the staged buffers
    assert [b.tobytes() for b in first.bufs] == before
    want = repr(hb.to_pylist_rows())  # (a NaN equals no NaN)
    assert repr(up.to_host().to_pylist_rows()) == want
    assert repr(again.to_host().to_pylist_rows()) == want


# ---------------------------------------------------------------------------
# concat_batches on both sides of _pack3d's operand limit: the same bytes
# ---------------------------------------------------------------------------
_WIDE = ([DataType.FLOAT32] * 6 + [DataType.INT64] * 3
         + [DataType.INT32] * 2 + [DataType.BOOL] * 2)      # 13 columns


def _wide_piece(rng, rows):
    cols = []
    for dt in _WIDE:
        npdt = dt.to_np()
        data = (rng.integers(0, 2, rows).astype(bool) if dt is DataType.BOOL
                else rng.integers(-10**6, 10**6, rows).astype(npdt))
        cols.append(HostColumnVector(dt, data, rng.random(rows) > 0.2))
    return HostColumnarBatch(cols, rows)


def _wide_pieces(seed, pieces, buckets):
    """`pieces` host batches of 13 mixed columns with nulls; `buckets`
    is the capacities they cycle through (one: a single pack group)."""
    rng = np.random.default_rng(seed)
    return [_wide_piece(rng, int(rng.integers(b // 2 + 1, b + 1)))
            for _, b in zip(range(pieces), itertools.cycle(buckets))]


def _assert_rows_equal(got, want_cols):
    """`got`, a host batch, against per column (data, validity) arrays:
    validity lane for lane, data wherever the lane is valid."""
    assert got.num_rows == len(want_cols[0][1])
    for col, (data, valid) in zip(got.columns, want_cols):
        n = got.num_rows
        np.testing.assert_array_equal(col.validity[:n], valid)
        np.testing.assert_array_equal(col.data[:n][valid], data[valid])


@pytest.mark.parametrize("buckets", [(16,), (8, 16, 32)],
                         ids=["one_group", "three_groups"])
@pytest.mark.parametrize("pieces", [3, 8, 64, 70])
@pytest.mark.parametrize("branch", ["plain", "live"])
def test_concat_wide_pieces_equals_numpy(branch, pieces, buckets):
    hosts = _wide_pieces(pieces * 31 + len(buckets), pieces, buckets)
    devs = [hb.to_device() for hb in hosts]
    keeps = [np.ones(hb.num_rows, bool) for hb in hosts]
    if branch == "live":
        # a shuffle's lazy slice: shared columns, a mask of the lanes
        # that are rows of this piece, the count on the device
        rng = np.random.default_rng(pieces)
        keeps = [rng.random(hb.num_rows) > 0.4 for hb in hosts]
        for i, (db, keep) in enumerate(zip(devs, keeps)):
            mask = np.zeros(db.capacity, bool)
            mask[:len(keep)] = keep
            devs[i] = ColumnarBatch(db.columns, jnp.asarray(
                int(keep.sum()), jnp.int32), live=jnp.asarray(mask))
    with B.pack_tally() as tally:
        out = concat_batches(devs)
    want = [(np.concatenate([hb.columns[ci].data[k]
                             for hb, k in zip(hosts, keeps)]),
             np.concatenate([hb.columns[ci].validity[k]
                             for hb, k in zip(hosts, keeps)]))
            for ci in range(len(_WIDE))]
    _assert_rows_equal(out.to_host(), want)
    per_group = -(-pieces // len(buckets))
    # the validity call's: under the limit at 3 pieces (and at 8 in
    # three groups), past it in every other case
    assert tally.operands == len(_WIDE) * per_group


def _in_order_keys():
    from spark_rapids_tpu.engine import jit_cache

    with jit_cache._LOCK:
        return {k for k in jit_cache._CACHE
                if isinstance(k, tuple) and k[0] == "concat_in_order"}


@pytest.mark.parametrize("rows", [
    (16, 9, 16, 3), (5, 3), (8, 8), (1, 1, 1, 1, 1, 1, 1, 1, 1), (7, 120)],
    ids=lambda r: "x".join(map(str, r)))
def test_concat_in_order_equals_numpy_and_pads_with_nothing(rows):
    """A resident relation's concat (exec/cache.py): one program that
    copies each piece to its place. 5 + 3 rows in pieces of 8 lanes fill
    an output of 8, so the last piece's bucket would reach past it: the
    write is not clamped back onto rows. Behind the rows the lanes are
    zero and invalid, as an upload leaves them, whatever the pieces
    held there; and the program is keyed by the capacities alone."""
    rng = np.random.default_rng(sum(rows))
    hosts = [_wide_piece(rng, n) for n in rows]
    devs = [hb.to_device() for hb in hosts]
    for db in devs:     # garbage behind the rows: a concat must not keep it
        for c in db.columns:
            junk = jnp.arange(c.capacity) >= db.num_rows
            c.data = jnp.where(junk, jnp.ones((), c.data.dtype), c.data)
            c.validity = c.validity | junk
    before = _in_order_keys()
    out = B.concat_in_order(devs)
    (key,) = _in_order_keys() - before or [None]
    assert out.rows_on_host and out.live is None and out.owned
    assert out.capacity == bucket_capacity(sum(rows))
    want = [(np.concatenate([hb.columns[ci].data for hb in hosts]),
             np.concatenate([hb.columns[ci].validity for hb in hosts]))
            for ci in range(len(_WIDE))]
    _assert_rows_equal(out.to_host(), want)
    for c in out.columns:
        assert not np.asarray(c.validity)[out.num_rows:].any()
        assert not np.asarray(c.data)[out.num_rows:].any()
    # the same capacities, other row counts: the program is there already
    again = [_wide_piece(rng, max(1, n - 1)).to_device() for n in rows]
    if [b.capacity for b in again] == [b.capacity for b in devs]:
        B.concat_in_order(again)
        assert _in_order_keys() - before == ({key} if key else set())


def test_concat_in_order_keeps_dictionary_codes_and_leaves_the_rest(
        monkeypatch):
    """Dictionary-coded columns are aligned to one dictionary and travel
    as codes; what the one program cannot take (a plain STRING column, a
    live mask, more operands than a jit should trace) is `concat_batches`'
    to do, with the same rows."""
    hb1 = HostColumnarBatch([
        HostColumnVector.from_pylist(["a", "b", None, "a"], DataType.STRING),
        HostColumnVector.from_pylist([1, 2, 3, None], DataType.INT64)])
    hb2 = HostColumnarBatch([
        HostColumnVector.from_pylist(["c", "a", "c"], DataType.STRING),
        HostColumnVector.from_pylist([None, 6, 7], DataType.INT64)])
    want = hb1.to_pylist_rows() + hb2.to_pylist_rows()
    plain = [hb1.to_device(), hb2.to_device()]
    assert B.concat_in_order(plain).to_host().to_pylist_rows() == want

    def coded(batch, values, codes):
        col = ENC.DictionaryColumn(
            DataType.STRING,
            jnp.asarray(np.asarray(codes + [0] * (8 - len(codes)), np.int32)),
            batch.columns[0].validity,
            ENC.DeviceDictionary.from_values(values))
        return ColumnarBatch([col, batch.columns[1]], batch.num_rows)

    out = B.concat_in_order([coded(plain[0], ["a", "b"], [0, 1, 0, 0]),
                             coded(plain[1], ["c", "a"], [0, 1, 0])])
    assert ENC.is_encoded(out.columns[0])
    assert out.columns[0].dictionary.size == 3
    assert ENC.decode_batch(out).to_host().to_pylist_rows() == want
    calls = []
    monkeypatch.setattr(B, "concat_batches",
                        lambda bs: calls.append(len(bs)) or concat_batches(bs))
    B.concat_in_order(plain)                       # a plain STRING column
    ints = [ColumnarBatch([b.columns[1]], b.num_rows) for b in plain]
    masked = [ColumnarBatch(b.columns, jnp.asarray(b.num_rows, jnp.int32),
                            live=jnp.arange(b.capacity) < b.num_rows)
              for b in ints]
    B.concat_in_order(masked)                      # live masks
    B.concat_in_order(ints[:1])                    # nothing to concat
    monkeypatch.setattr(B, "_IN_ORDER_OPERANDS", 3)
    B.concat_in_order(ints)                        # 4 operands
    assert calls == [2, 2, 1, 2]
    monkeypatch.setattr(B, "_IN_ORDER_OPERANDS", 4)
    assert B.concat_in_order(ints).to_host().to_pylist_rows() == [
        (r[1],) for r in want]
    assert calls == [2, 2, 1, 2]


def _pack_keys():
    from spark_rapids_tpu.engine import jit_cache

    with jit_cache._LOCK:
        return {k[0] for k in jit_cache._CACHE
                if isinstance(k[0], tuple) and str(k[0][0]).startswith("pack")}


def test_concat_strings_past_the_operand_limit():
    """_concat_string_cols packs offsets, bytes and validity through
    _pack3d: 70 pieces of one bucket are 70 operands a call."""
    hosts = []
    for i in range(70):
        words = [None if j == i % 8 else "%02d_%02d" % (i, j)
                 for j in range(8)]
        hosts.append(HostColumnarBatch([
            HostColumnVector.from_pylist(words, DataType.STRING),
            HostColumnVector.from_pylist(list(range(8)), DataType.INT32)]))
    before = _pack_keys()
    with B.pack_tally() as tally:
        out = concat_batches([hb.to_device() for hb in hosts])
    assert tally.operands == 70
    # full runs of offsets, bytes and both validities; the INT32 data's
    assert {k[1] for k in _pack_keys() - before if k[0] == "pack3d_run"} \
        == {"int32", "uint8", "bool"}
    assert out.to_host().to_pylist_rows() == [
        r for hb in hosts for r in hb.to_pylist_rows()]


def test_the_run_path_is_bounded_and_never_eager(monkeypatch):
    """Past the limit _pack3d issues no eager concatenate (every pack
    program is a jitted one, and the tally counts them), no program takes
    more than the limit's operands, and a larger piece count brings a
    fixed handful of program keys, not one a piece."""
    from spark_rapids_tpu.engine import jit_cache

    eager = []
    real = jnp.concatenate

    def concatenate(arrays, *a, **kw):
        import jax.core

        arrays = list(arrays)
        if not any(isinstance(x, jax.core.Tracer) for x in arrays):
            eager.append(len(arrays))
        assert len(arrays) <= B._PACK_OPERANDS
        return real(arrays, *a, **kw)

    monkeypatch.setattr(B.jnp, "concatenate", concatenate)
    jit_cache.clear()
    new_keys, programs = {}, {}
    for pieces in (65, 200):
        before = _pack_keys()
        devs = [hb.to_device() for hb in _wide_pieces(pieces, pieces, (16,))]
        with B.pack_tally() as tally:
            concat_batches(devs)
        new_keys[pieces] = _pack_keys() - before
        programs[pieces] = tally.programs
    assert eager == []
    # 13 x 65 = 845 validity operands: 13 full runs, a remainder and a
    # join; the data of 6, 3, 2 and 2 columns likewise; the pack kernel
    assert programs[65] == (14 + 1) + (7 + 1) + (4 + 1) + 2 * (3 + 1) + 1
    assert programs[200] == (41 + 1) + (19 + 1) + (10 + 1) + 2 * (7 + 1) + 1
    # what 200 pieces add to 65's: their remainder runs, a join a call
    # and the kernel: the piece count is in no run's key
    assert len(new_keys[200]) <= len(new_keys[65])
    assert {k[0] for k in new_keys[200]} <= {
        "pack3d_run", "pack3d_join", "pack_fixed"}
    # at the limit and under it: the key and the one program of before
    # (two INT32 columns x 32 pieces are 64 operands, three INT64 are 96)
    devs = [hb.to_device() for hb in _wide_pieces(1, 32, (16,))]
    before = _pack_keys()
    with B.pack_tally() as tally:
        concat_batches(devs)
    added = _pack_keys() - before
    assert ("pack3d", 2, 32, 32, 16, ("int32",) * 64) in added
    assert ("pack3d", 2, 32, 32, 16, ("bool",) * 64) in added
    assert ("pack3d_join", 3, 32, 32, 16, "int64") in added
    assert tally.operands == 13 * 32
    assert tally.programs == 1 + 1 + (2 + 1) + (3 + 1) + (7 + 1) + 1


@pytest.mark.parametrize("operands", [64, 65, 128, 129, 64 * 64 + 3])
def test_pack3d_on_both_sides_of_the_limit(operands):
    """One column of `operands` pieces, padded to the next power of two:
    the matrix numpy stacks, through one program up to the limit, through
    runs past it, through two levels of runs past the limit squared."""
    rng = np.random.default_rng(operands)
    pieces = [rng.integers(0, 100, 8).astype(np.int32)
              for _ in range(operands)]
    m_pad = 1 << (operands - 1).bit_length()
    with B.pack_tally() as tally:
        got = B._pack3d([[jnp.asarray(p) for p in pieces]], m_pad, 8)
    want = np.zeros((1, m_pad, 8), np.int32)
    want[0, :operands] = np.stack(pieces)
    np.testing.assert_array_equal(np.asarray(got), want)
    runs = 0
    left = operands
    while left > B._PACK_OPERANDS:
        full, rest = divmod(left, B._PACK_OPERANDS)
        runs += full + (rest > 1)
        left = full + (rest > 0)
    assert tally.programs == runs + 1 and tally.operands == operands
