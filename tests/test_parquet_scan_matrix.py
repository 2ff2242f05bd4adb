"""Every fixed-width parquet column reaches the device one way: Arrow
decodes it in the split's one read (`read_split` -> `arrow_to_host_batch`)
and `to_device` moves it (PR 30). The device decoder keeps BYTE_ARRAY
strings and is never entered for anything else.

Held here to pyarrow's own read of the same file, row for row and null for
null, over the page shapes the deleted fixed-width device decoder was
tested on: dictionary-index streams of every bit width 1-24 in three page
layouts (files this module assembles itself, page by page), streams with
an RLE run, a padded group in front of later values and a bit-width-0
page, row counts under one 32-value group, and twelve pyarrow-written
columns (dictionary and PLAIN, required and nullable, real nulls, pages
that pad inside a chunk) as v1 and as v2 pages.

And end to end, in the shape of the benchmark's two cells (PR 32: a split
is packed for its upload ahead of the task's permit, at either prefetch
depth): a file of two row groups whose row count is no power of two, one
column with nulls, read, two columns computed, written back as parquet and
read by Arrow, exactly; Q6's filter and sum over the same file against
numpy."""

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

import spark_rapids_tpu as srt
from spark_rapids_tpu import conf as C
from spark_rapids_tpu.columnar.dtypes import DataType
from spark_rapids_tpu.io import parquet_device as PD
from spark_rapids_tpu.io import scan as SCAN
from spark_rapids_tpu.ops.literals import Literal
from spark_rapids_tpu.plan import functions as F
from spark_rapids_tpu.plan.column import Column
from spark_rapids_tpu.utils import metrics as M


# ---------------------------------------------------------------------------
# the test's own hybrid encoder
# ---------------------------------------------------------------------------
def varint(n: int) -> bytes:
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def pack_bits(values, bw: int) -> bytes:
    """LSB-first bit-packing of `values`, padded with zeros to whole
    groups of 8."""
    values = np.asarray(values, np.uint32)
    values = np.pad(values, (0, -len(values) % 8))
    bits = (values[:, None] >> np.arange(bw, dtype=np.uint32)) & 1
    return np.packbits(bits.astype(np.uint8).reshape(-1),
                       bitorder="little").tobytes()


def bitpacked_stream(values, bw: int, groups_a_run: int) -> bytes:
    """Bit-packed runs of at most `groups_a_run` groups; 63 groups keep
    the header to one byte."""
    stream = bytearray()
    step = groups_a_run * 8
    for i in range(0, len(values), step):
        part = values[i:i + step]
        stream += varint(((len(part) + 7) // 8 << 1) | 1) + \
            pack_bits(part, bw)
    return bytes(stream)


def rle_run(value: int, count: int, bw: int) -> bytes:
    return varint(count << 1) + int(value).to_bytes((bw + 7) // 8, "little")


# ---------------------------------------------------------------------------
# a one-column parquet file from hand-made pages: thrift's compact
# protocol, as far as a page header and a footer need it
# ---------------------------------------------------------------------------
_CT = {"i32": 5, "i64": 6, "str": 8, "list": 9, "struct": 12}


def _zigzag(n: int) -> bytes:
    return varint((n << 1) ^ (n >> 63))


def _value(kind, value) -> bytes:
    if kind in ("i32", "i64"):
        return _zigzag(value)
    if kind == "str":
        raw = value.encode()
        return varint(len(raw)) + raw
    if kind == "struct":
        return _struct(value)
    elem, items = value                   # a list: (element kind, items)
    head = bytes([(len(items) << 4) | _CT[elem]]) if len(items) < 15 \
        else bytes([0xF0 | _CT[elem]]) + varint(len(items))
    return head + b"".join(_value(elem, v) for v in items)


def _struct(fields) -> bytes:
    """fields: (field id, kind, value), ids ascending by at most 15."""
    out, last = bytearray(), 0
    for fid, kind, value in fields:
        out.append(((fid - last) << 4) | _CT[kind])
        out += _value(kind, value)
        last = fid
    return bytes(out) + b"\x00"


INT32, PLAIN, RLE, RLE_DICT = 1, 0, 3, 8
DATA_PAGE, DICT_PAGE = 0, 2


def _page(kind: int, payload: bytes, header) -> bytes:
    return _struct([(1, "i32", kind), (2, "i32", len(payload)),
                    (3, "i32", len(payload)), header]) + payload


def write_dictionary_file(path, dictionary, pages):
    """An UNCOMPRESSED file of one required INT32 column `c`: a dictionary
    page of `dictionary` and one v1 RLE_DICTIONARY data page for each of
    `pages` = (rows, bit width, index stream bytes)."""
    body = _page(DICT_PAGE, np.asarray(dictionary, "<i4").tobytes(),
                 (7, "struct", [(1, "i32", len(dictionary)),
                                (2, "i32", PLAIN)]))
    first_data = 4 + len(body)
    rows = 0
    for n, bw, stream in pages:
        body += _page(DATA_PAGE, bytes([bw]) + stream,
                      (5, "struct", [(1, "i32", n), (2, "i32", RLE_DICT),
                                     (3, "i32", RLE), (4, "i32", RLE)]))
        rows += n
    column = [(1, "i32", INT32),
              (2, "list", ("i32", [PLAIN, RLE, RLE_DICT])),
              (3, "list", ("str", ["c"])), (4, "i32", 0),
              (5, "i64", rows), (6, "i64", len(body)), (7, "i64", len(body)),
              (9, "i64", first_data), (11, "i64", 4)]
    footer = _struct([
        (1, "i32", 1),
        (2, "list", ("struct", [
            [(4, "str", "schema"), (5, "i32", 1)],
            [(1, "i32", INT32), (3, "i32", 0), (4, "str", "c")]])),
        (3, "i64", rows),
        (4, "list", ("struct", [[
            (1, "list", ("struct", [[(2, "i64", 4),
                                     (3, "struct", column)]])),
            (2, "i64", len(body)), (3, "i64", rows)]]))])
    with open(path, "wb") as f:
        f.write(b"PAR1" + body + footer +
                len(footer).to_bytes(4, "little") + b"PAR1")


def _indexed(rng, bw: int, rows: int):
    """A dictionary that bit width `bw` can index (cut at 2^16 entries:
    a wider index than its dictionary needs is the writer's to choose)
    and `rows` indices into it."""
    dictionary = rng.integers(-2**31, 2**31, min(1 << bw, 1 << 16))
    return dictionary, rng.integers(0, len(dictionary), rows)


# ---------------------------------------------------------------------------
# scanned through a session, the device decoder never entered
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def scan_session():
    s = srt.new_session({"rapids.tpu.sql.spmd.meshDevices": 1})
    yield s
    s.stop()


@pytest.fixture
def arrow_only(monkeypatch):
    """Any step of the device decoder fails the test."""
    def entered(name):
        def fail(*a, **k):
            raise AssertionError(f"the device decoder was entered: {name}")
        return fail

    for name in ("read_chunk_bytes", "stage_chunk", "decode_chunk_device"):
        monkeypatch.setattr(PD, name, entered(name))
    monkeypatch.setattr(SCAN.TpuFileScanExec, "_stage_split",
                        entered("_stage_split"))


def _assert_scan_equals_arrow(session, path):
    want = pq.read_table(path).column("c")
    if pa.types.is_date32(want.type):
        want = want.cast(pa.int32())  # a DATE collects as its day number
    want = want.to_pylist()
    got = [r[0] for r in session.read.parquet(path).collect()]
    assert session.last_query_metrics[M.CPU_FALLBACK_EVENTS] == 0
    assert len(got) == len(want)
    assert [g is None for g in got] == [w is None for w in want]
    assert got == want
    return want


# streams of a page's values cut into runs: the layouts the packed
# expansion was held to
LAYOUTS = {
    # one page, 1-byte run headers (63 groups a run, as Arrow closes them)
    "one_page": dict(pages=(1000,), groups_a_run=63),
    # three pages, 2-byte run headers (100 groups: header 201)
    "three_pages_long_runs": dict(pages=(1600, 1600, 800), groups_a_run=100),
    # the stream's last run padded to a whole group
    "padded_last_run": dict(pages=(504, 499), groups_a_run=63),
}


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
@pytest.mark.parametrize("bw", range(1, 25))
def test_bit_packed_indices_of_every_width(scan_session, arrow_only,
                                           tmp_path, bw, layout):
    rng = np.random.default_rng(1000 * bw + len(layout))
    pages, groups_a_run = LAYOUTS[layout]["pages"], \
        LAYOUTS[layout]["groups_a_run"]
    dictionary, idx = _indexed(rng, bw, sum(pages))
    built, at = [], 0
    for n in pages:
        built.append((n, bw, bitpacked_stream(idx[at:at + n], bw,
                                              groups_a_run)))
        at += n
    path = str(tmp_path / "c.parquet")
    write_dictionary_file(path, dictionary, built)
    want = _assert_scan_equals_arrow(scan_session, path)
    # and the file says what this module encoded into it
    assert want == dictionary[idx].tolist()


@pytest.mark.parametrize("rows", [5, 13, 29, 61])
def test_row_counts_under_one_group_of_32(scan_session, arrow_only,
                                          tmp_path, rows):
    rng = np.random.default_rng(rows)
    dictionary, idx = _indexed(rng, 5, rows)
    path = str(tmp_path / "c.parquet")
    write_dictionary_file(path, dictionary,
                          [(rows, 5, bitpacked_stream(idx, 5, 63))])
    assert _assert_scan_equals_arrow(scan_session, path) == \
        dictionary[idx].tolist()


@pytest.mark.parametrize("kind", ["rle_run", "padding_inside",
                                  "bit_width_zero_page"])
def test_streams_that_are_not_one_bit_packed_sequence(
        scan_session, arrow_only, tmp_path, kind):
    """An RLE run behind a bit-packed one, a padded group in front of
    later values, a page whose indices have bit width 0 (a writer's way
    to say 'all index 0'; pyarrow writes width 1)."""
    rng = np.random.default_rng(7)
    bw = 7
    dictionary, a = _indexed(rng, bw, 64)
    b = rng.integers(0, len(dictionary), 64)
    second = (64, bw, bitpacked_stream(b, bw, 63))
    if kind == "rle_run":
        a[32:] = 5
        first = (64, bw, bitpacked_stream(a[:32], bw, 63) +
                 rle_run(5, 32, bw))
    elif kind == "padding_inside":
        a = a[:61]
        first = (61, bw, bitpacked_stream(a, bw, 63))
    else:
        a = np.zeros(32, np.int64)
        first = (32, 0, rle_run(0, 32, 0))  # a run header, no value byte
    path = str(tmp_path / "c.parquet")
    write_dictionary_file(path, dictionary, [first, second])
    assert _assert_scan_equals_arrow(scan_session, path) == \
        dictionary[np.concatenate([a, b])].tolist()


# ---------------------------------------------------------------------------
# pyarrow-written columns, as v1 and as v2 pages
# ---------------------------------------------------------------------------
N = 5000


def _column(case: str, rng):
    """(arrow array, write options)."""
    few = rng.integers(0, 16, N)         # bit width 4 from the first page
    many = rng.integers(0, 300, N)       # bit width 9, one page
    pages_of_512 = dict(data_page_size=600, write_batch_size=512)
    pages_of_333 = dict(data_page_size=400, write_batch_size=333)
    plain = {"use_dictionary": False}
    cases = {
        "int64_no_nulls": (pa.array(many.astype(np.int64)), {}),
        "int32_required": (pa.array(many.astype(np.int32)),
                           {"required": True}),
        "double_dictionary": (pa.array(many.astype(np.float64) / 4), {}),
        "date_dictionary": (pa.array(many.astype(np.int32))
                            .cast(pa.date32()), {}),
        "int64_pages": (pa.array(few.astype(np.int64) * 1000),
                        pages_of_512),
        "pages_full_groups": (pa.array(few.astype(np.int64)), pages_of_512),
        "pages_padding_inside": (pa.array(few.astype(np.int64)),
                                 pages_of_333),
        "one_rle_run": (pa.array(np.where(np.arange(N) % 4096 < 700, 5,
                                          many).astype(np.int64)), {}),
        "real_nulls": (pa.array(many.astype(np.int64),
                                mask=rng.random(N) < 0.1), {}),
        "date_real_nulls": (pa.array(many.astype(np.int32),
                                     mask=rng.random(N) < 0.1)
                            .cast(pa.date32()), {}),
        "plain_no_nulls": (pa.array(rng.random(N)), plain),
        "plain_real_nulls": (pa.array(rng.random(N),
                                      mask=rng.random(N) < 0.2), plain),
    }
    return cases[case]


CASES = ["int64_no_nulls", "int32_required", "double_dictionary",
         "date_dictionary", "int64_pages", "pages_full_groups",
         "pages_padding_inside", "one_rle_run", "real_nulls",
         "date_real_nulls", "plain_no_nulls", "plain_real_nulls"]


@pytest.mark.parametrize("page_version", ["1.0", "2.0"])
@pytest.mark.parametrize("case", CASES)
def test_arrow_written_column(scan_session, arrow_only, tmp_path, case,
                              page_version):
    rng = np.random.default_rng(CASES.index(case))
    arr, opts = _column(case, rng)
    opts = dict(opts)
    field = pa.field("c", arr.type, nullable=not opts.pop("required", False))
    path = str(tmp_path / "c.parquet")
    pq.write_table(pa.table([arr], schema=pa.schema([field])), path,
                   compression="snappy", row_group_size=4096,
                   data_page_version=page_version, **opts)
    md = pq.ParquetFile(path).metadata
    assert md.num_row_groups == 2
    if "pages" in case:
        # several data pages a chunk, as the case's name says
        col = md.row_group(0).column(0)
        assert col.total_uncompressed_size > 3 * 600
    want = _assert_scan_equals_arrow(scan_session, path)
    assert want.count(None) == arr.null_count


# ---------------------------------------------------------------------------
# the two cells' shape, end to end on this backend
# ---------------------------------------------------------------------------
LINEITEM_ROWS = 3001  # two row groups of 2048 and 953: no power of two


@pytest.fixture(scope="module")
def lineitem(tmp_path_factory):
    """Q6's four columns of a lineitem, `l_quantity` with nulls, and the
    row's number (a write keeps no order a reader may count on)."""
    rng = np.random.default_rng(32)
    n = LINEITEM_ROWS
    arrays = {
        "id": np.arange(n, dtype=np.int64),
        "l_shipdate": rng.integers(8036, 10592, n).astype(np.int32),
        "l_quantity": rng.integers(1, 51, n).astype(np.float64),
        "l_extendedprice": rng.integers(90000, 10500000, n) / 100.0,
        "l_discount": rng.integers(0, 11, n) / 100.0}
    nulls = rng.random(n) < 0.1
    path = str(tmp_path_factory.mktemp("lineitem") / "lineitem.parquet")
    pq.write_table(pa.table({
        "id": arrays["id"],
        "l_shipdate": pa.array(arrays["l_shipdate"], pa.int32())
        .cast(pa.date32()),
        "l_quantity": pa.array(arrays["l_quantity"], mask=nulls),
        "l_extendedprice": arrays["l_extendedprice"],
        "l_discount": arrays["l_discount"]}), path, row_group_size=2048)
    assert pq.ParquetFile(path).metadata.num_row_groups == 2
    return path, arrays, nulls


@pytest.mark.parametrize("device_encode", [True, False],
                         ids=["device_encoder", "arrow_encoder"])
@pytest.mark.parametrize("depth", [0, 1])
def test_read_compute_write_reads_back_exactly(lineitem, arrow_only,
                                               tmp_path, depth,
                                               device_encode):
    """The write cell's action: every row, Q6's columns and two computed
    from them, through `write.parquet` and back through Arrow. A DOUBLE is
    f64 on this backend, so the products are numpy's to the bit. (On the
    chip Arrow encodes a DOUBLE: `arrow_encoder` walks that sink here.)"""
    path, arrays, nulls = lineitem
    session = srt.new_session({
        "rapids.tpu.sql.spmd.meshDevices": 1,
        C.IO_PREFETCH_BATCHES.key: depth,
        C.PARQUET_DEVICE_ENCODE.key: device_encode})
    out = str(tmp_path / "out")
    try:
        price, disc = F.col("l_extendedprice"), F.col("l_discount")
        session.read.parquet(path).select(
            F.col("id"), F.col("l_shipdate"), F.col("l_quantity"), price,
            disc, (price * disc).alias("revenue"),
            (price * (F.lit(1.0) - disc)).alias("disc_price")) \
            .write.parquet(out)
        assert session.last_query_metrics[M.CPU_FALLBACK_EVENTS] == 0
    finally:
        session.stop()
    got = pq.read_table(out).sort_by("id")
    assert got.num_rows == LINEITEM_ROWS
    assert got.column_names == ["id", "l_shipdate", "l_quantity",
                                "l_extendedprice", "l_discount", "revenue",
                                "disc_price"]
    assert got.column("l_shipdate").type == pa.date32()
    want = dict(
        arrays,
        revenue=arrays["l_extendedprice"] * arrays["l_discount"],
        disc_price=arrays["l_extendedprice"] * (1.0 - arrays["l_discount"]))
    for name in got.column_names:
        col = got.column(name)
        if name == "l_shipdate":
            col = col.cast(pa.int32())
        assert col.null_count == (int(nulls.sum()) if name == "l_quantity"
                                  else 0), name
        if name == "l_quantity":
            assert np.array_equal(
                np.asarray(col.is_null()), nulls)
            col = col.fill_null(0.0)
            want[name] = np.where(nulls, 0.0, want[name])
        have = col.to_numpy()
        assert have.dtype == want[name].dtype, name
        assert have.tobytes() == want[name].tobytes(), name


@pytest.mark.parametrize("depth", [0, 1])
def test_q6_shape_over_the_same_file(lineitem, arrow_only, depth):
    """The query cell's action: three range predicates (a NULL quantity
    keeps no row), one sum of a product."""
    path, arrays, nulls = lineitem
    lo, hi = 8766, 9131  # 1994-01-01, 1995-01-01 as day numbers

    def date_lit(day):
        return Column(Literal(day, DataType.DATE))

    session = srt.new_session({"rapids.tpu.sql.spmd.meshDevices": 1,
                               C.IO_PREFETCH_BATCHES.key: depth})
    try:
        li = session.read.parquet(path)
        rows = (li.filter((li["l_shipdate"] >= date_lit(lo))
                          & (li["l_shipdate"] < date_lit(hi))
                          & (li["l_discount"] >= F.lit(0.05))
                          & (li["l_discount"] <= F.lit(0.07))
                          & (li["l_quantity"] < F.lit(24.0)))
                .withColumn("revenue",
                            F.col("l_extendedprice") * F.col("l_discount"))
                .agg(F.sum("revenue").alias("revenue"),
                     F.count("*").alias("n"))).collect()
        assert session.last_query_metrics[M.CPU_FALLBACK_EVENTS] == 0
    finally:
        session.stop()
    keep = ((arrays["l_shipdate"] >= lo) & (arrays["l_shipdate"] < hi)
            & (arrays["l_discount"] >= 0.05) & (arrays["l_discount"] <= 0.07)
            & (arrays["l_quantity"] < 24.0) & ~nulls)
    assert 0 < keep.sum() < LINEITEM_ROWS
    ((revenue, n),) = rows
    assert n == int(keep.sum())
    want = float((arrays["l_extendedprice"][keep]
                  * arrays["l_discount"][keep]).sum())
    assert revenue == pytest.approx(want, rel=1e-12)
