"""The forms a hybrid (RLE / bit-packed) stream's expansion takes in the
device parquet decoder (io/parquet_device.py, PR 26): a stream that the
host's run table shows to be bit-packed throughout is uploaded as its
payload alone and unpacked with static shapes (`packed`); definition
levels the host counted as all present are not expanded and nothing is
spread to rows; every other stream keeps the per-lane lookup (`runs`).

The packed expansion is held to a numpy bit-unpack of streams this file
encodes itself; whole chunks are held to Arrow's reader."""

import re

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

import jax
import jax.numpy as jnp

import spark_rapids_tpu as srt
from spark_rapids_tpu import conf as C
from spark_rapids_tpu.columnar import encoded as ENC
from spark_rapids_tpu.columnar.batch import bucket_capacity
from spark_rapids_tpu.columnar.dtypes import DataType
from spark_rapids_tpu.engine import compile_clock
from spark_rapids_tpu.io import parquet_device as PD
from spark_rapids_tpu.obs.trace import wall_ns


# ---------------------------------------------------------------------------
# the test's own hybrid encoder and bit-unpack (the reference side)
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


def numpy_unpack(payload: bytes, bw: int, n: int) -> np.ndarray:
    bits = np.unpackbits(np.frombuffer(payload, np.uint8),
                         bitorder="little")[:n * bw].reshape(n, bw)
    return (bits.astype(np.int64) << np.arange(bw)).sum(axis=1)


def bitpacked_stream(values, bw: int, groups_a_run: int):
    """(stream bytes, payload bytes alone): bit-packed runs of at most
    `groups_a_run` groups; 63 groups keep the header to one byte."""
    stream, payload = bytearray(), bytearray()
    step = groups_a_run * 8
    for i in range(0, len(values), step):
        part = values[i:i + step]
        body = pack_bits(part, bw)
        stream += varint(((len(part) + 7) // 8 << 1) | 1) + body
        payload += body
    return bytes(stream), bytes(payload)


def rle_run(value: int, count: int, bw: int) -> bytes:
    return varint(count << 1) + int(value).to_bytes((bw + 7) // 8, "little")


def _ops(text: str) -> set:
    """Operation names of a StableHLO or an HLO module's text (not the
    words of its metadata: a test's name travels in there)."""
    return set(re.findall(r"stablehlo\.(\w+)", text)) \
        | set(re.findall(r" = .*?[\])}] ([a-z][\w-]*)\(", text))


# streams of `present` values cut into pages; between pages the bytes a
# page header and a def-level section would take
LAYOUTS = {
    # one page, 1-byte run headers (63 groups a run, as Arrow closes them)
    "one_page": dict(pages=(1000,), groups_a_run=63),
    # three pages, 2-byte run headers (100 groups: header 201)
    "three_pages_long_runs": dict(pages=(1600, 1600, 800), groups_a_run=100),
    # the stream's last run padded to a whole group; far under capacity
    "padded_last_run": dict(pages=(504, 499), groups_a_run=63),
}


def paged_chunk(rng, bw: int, pages, groups_a_run: int):
    """A chunk's bytes, the (RunTable, n) of each page as the host parses
    them, the values, and the payload bytes alone."""
    chunk = bytearray(rng.integers(0, 256, 11, dtype=np.uint8).tobytes())
    parsed, values, payload = [], [], bytearray()
    for n in pages:
        vals = rng.integers(0, 1 << bw, n, dtype=np.int64)
        stream, body = bitpacked_stream(vals, bw, groups_a_run)
        start = len(chunk)
        chunk += stream
        parsed.append((start, len(chunk), n))
        chunk += rng.integers(0, 256, 23, dtype=np.uint8).tobytes()
        values.append(vals)
        payload += body
    chunk = bytes(chunk)
    tables = [(PD.parse_runs(chunk, s, e, bw, n), n) for s, e, n in parsed]
    return chunk, tables, np.concatenate(values), bytes(payload)


_unpack = jax.jit(PD._unpack_planes, static_argnums=(1, 2))


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
@pytest.mark.parametrize("bw", range(1, 25))
def test_packed_expansion_equals_numpy_unpack(bw, layout):
    rng = np.random.default_rng(1000 * bw + len(layout))
    chunk, tables, values, payload = paged_chunk(rng, bw, **LAYOUTS[layout])
    present = len(values)
    cap = 4096
    assert present < cap
    header_bytes = {len(varint((min(LAYOUTS[layout]["groups_a_run"],
                                    -(-n // 8)) << 1) | 1))
                    for n in LAYOUTS[layout]["pages"]}
    assert header_bytes == ({2} if "long_runs" in layout else {1})
    planes = PD._pack_value_stream(chunk, tables, bw, cap)
    assert planes is not None
    assert planes.shape == (bw, cap // 32) and planes.dtype == np.uint32
    # the payload, headers dropped, is what went into the planes
    flat = np.ascontiguousarray(planes.T).view(np.uint8).reshape(-1)
    assert flat[:len(payload)].tobytes() == payload
    assert not flat[len(payload):].any()
    got = np.asarray(_unpack(jnp.asarray(planes), bw, cap))
    assert got.dtype == np.int32 and got.shape == (cap,)
    assert np.array_equal(got[:present], numpy_unpack(payload, bw, present))
    assert np.array_equal(got[:present], values)


@pytest.mark.parametrize("cap", [8, 16, 32, 64])
def test_packed_expansion_at_small_capacities(cap):
    """Capacities under one 32-value group: the planes hold one group and
    the output is cut to the capacity."""
    rng = np.random.default_rng(cap)
    chunk, tables, values, _ = paged_chunk(rng, 5, (cap - 3,), 63)
    planes = PD._pack_value_stream(chunk, tables, 5, cap)
    assert planes.shape == (5, max(cap, 32) // 32)
    got = np.asarray(_unpack(jnp.asarray(planes), 5, cap))
    assert got.shape == (cap,)
    assert np.array_equal(got[:cap - 3], values)


def _stream_with(kind: str, rng, bw: int = 7):
    """A two-page stream that is NOT one bit-packed sequence."""
    a = rng.integers(0, 1 << bw, 64, dtype=np.int64)
    b = rng.integers(0, 1 << bw, 64, dtype=np.int64)
    if kind == "rle_run":
        first = bitpacked_stream(a[:32], bw, 63)[0] + rle_run(5, 32, bw)
        a[32:] = 5
    elif kind == "padding_inside":
        a = a[:61]
        first = bitpacked_stream(a, bw, 63)[0]
    else:
        first = bitpacked_stream(a, bw, 63)[0]
    second = bitpacked_stream(b, bw, 63)[0]
    chunk = b"\x00" * 5 + first + b"\xff" * 9 + second
    s1 = 5
    s2 = s1 + len(first) + 9
    tables = [(PD.parse_runs(chunk, s1, s1 + len(first), bw, len(a)),
               len(a)),
              (PD.parse_runs(chunk, s2, s2 + len(second), bw, len(b)),
               len(b))]
    if kind == "page_without_runs":
        tables.insert(1, None)
    if kind == "truncated":
        chunk = chunk[:-8]
    return chunk, tables, np.concatenate([a, b])


@pytest.mark.parametrize("kind", ["rle_run", "padding_inside",
                                  "page_without_runs", "truncated"])
def test_streams_the_packed_form_refuses(kind):
    """An RLE run, a padded group in front of later values, a bit-width-0
    page, a payload that runs past the chunk: the general form's."""
    rng = np.random.default_rng(7)
    chunk, tables, values = _stream_with(kind, rng)
    assert PD._pack_value_stream(chunk, tables, 7, 128) is None
    if kind in ("rle_run", "padding_inside"):
        # and the general form reads them right
        shifted, at = [], 0
        for rt, n in tables:
            shifted.append(PD._shifted_tab(rt, at, n))
            at += n
        tab = tuple(jnp.asarray(t) for t in PD._pack_flat_tabs(shifted))
        got = PD._expand_stream(jnp.asarray(np.frombuffer(chunk, np.uint8)),
                                tab, 7, 128, PD._RUNS)
        assert np.array_equal(np.asarray(got)[:len(values)], values)


def test_expand_stream_forms_agree():
    """One stream, both forms: the same codes, bit for bit."""
    rng = np.random.default_rng(3)
    bw, cap = 12, 1024
    chunk, tables, values, _ = paged_chunk(rng, bw, (504, 496), 63)
    shifted = [PD._shifted_tab(tables[0][0], 0, 504),
               PD._shifted_tab(tables[1][0], 504, 496)]
    tab = tuple(jnp.asarray(t) for t in PD._pack_flat_tabs(shifted))
    runs = PD._expand_stream(jnp.asarray(np.frombuffer(chunk, np.uint8)),
                             tab, bw, cap, PD._RUNS)
    planes = PD._pack_value_stream(chunk, tables, bw, cap)
    packed = PD._expand_stream(jnp.asarray(planes), PD._EMPTY_TAB(), bw,
                               cap, PD._PACKED)
    assert np.array_equal(np.asarray(runs)[:1000], values)
    assert np.array_equal(np.asarray(packed)[:1000], values)
    ones = PD._expand_stream(None, PD._EMPTY_TAB(), 1, cap, PD._ONES)
    assert np.asarray(ones).all() and ones.shape == (cap,)


# ---------------------------------------------------------------------------
# whole chunks against Arrow's reader
# ---------------------------------------------------------------------------
N = 5000


def _column(case: str, rng):
    """(arrow array, engine dtype, write options, encoded_ok, the form
    its chunks take, whether the dense values are the rows)."""
    few = rng.integers(0, 16, N)         # bit width 4 from the first page
    many = rng.integers(0, 300, N)       # bit width 9, one page
    pages_of_512 = dict(data_page_size=600, write_batch_size=512)
    pages_of_333 = dict(data_page_size=400, write_batch_size=333)
    cases = {
        "int64_no_nulls": (pa.array(many.astype(np.int64)),
                           DataType.INT64, {}, False, "packed", True),
        "int32_required": (pa.array(many.astype(np.int32)),
                           DataType.INT32, {"required": True}, False,
                           "packed", True),
        "double_dictionary": (pa.array(many.astype(np.float64) / 4),
                              DataType.FLOAT64, {}, False, "packed", True),
        "date_codes": (pa.array(many.astype(np.int32)).cast(pa.date32()),
                       DataType.DATE, {}, True, "packed", True),
        "int64_codes_pages": (pa.array(few.astype(np.int64) * 1000),
                              DataType.INT64, pages_of_512, True,
                              "packed", True),
        "pages_full_groups": (pa.array(few.astype(np.int64)),
                              DataType.INT64, pages_of_512, False,
                              "packed", True),
        "pages_padding_inside": (pa.array(few.astype(np.int64)),
                                 DataType.INT64, pages_of_333, False,
                                 "runs", True),
        "one_rle_run": (pa.array(np.where(np.arange(N) % 4096 < 700, 5,
                                          many).astype(np.int64)),
                        DataType.INT64, {}, False, "runs", True),
        "real_nulls": (pa.array(many.astype(np.int64),
                                mask=rng.random(N) < 0.1),
                       DataType.INT64, {}, False, "runs", False),
        "real_nulls_codes": (pa.array(many.astype(np.int32),
                                      mask=rng.random(N) < 0.1)
                             .cast(pa.date32()),
                             DataType.DATE, {}, True, "runs", False),
        "plain_no_nulls": (pa.array(rng.random(N)), DataType.FLOAT64,
                           {"use_dictionary": False}, False, "plain", True),
        "plain_real_nulls": (pa.array(rng.random(N),
                                      mask=rng.random(N) < 0.2),
                             DataType.FLOAT64, {"use_dictionary": False},
                             False, "runs", False),
    }
    return cases[case]


CASES = ["int64_no_nulls", "int32_required", "double_dictionary",
         "date_codes", "int64_codes_pages", "pages_full_groups",
         "pages_padding_inside", "one_rle_run", "real_nulls",
         "real_nulls_codes", "plain_no_nulls", "plain_real_nulls"]


@pytest.fixture
def forms(monkeypatch):
    """What the decoder tells its `scan.decode` span, and what it asks of
    `_flat_finish`, for the chunks decoded in the test."""
    seen = {"expand": [], "dense_is_rows": []}
    finish = PD._flat_finish

    def spy(dense, validity, nums, cap, dense_is_rows):
        seen["dense_is_rows"].append(dense_is_rows)
        return finish(dense, validity, nums, cap, dense_is_rows)

    def annotate(**attrs):
        if "expand" in attrs:
            seen["expand"].append(attrs["expand"])

    monkeypatch.setattr(PD, "_flat_finish", spy)
    monkeypatch.setattr(PD.OBS, "annotate", annotate)
    return seen


def _decode_file(path, dtype, encoded_ok):
    """Every row group of a one-column file through the device decoder:
    (values, validity) as numpy, over the file's rows."""
    pf = pq.ParquetFile(path)
    max_def = pf.schema.column(0).max_definition_level
    values, valid = [], []
    for g in range(pf.metadata.num_row_groups):
        col = pf.metadata.row_group(g).column(0)
        rows = pf.metadata.row_group(g).num_rows
        out = PD.decode_chunk_device(
            PD.read_chunk_bytes(path, col), dtype, rows, max_def=max_def,
            codec=col.compression, encoded_ok=encoded_ok)
        v = np.asarray(out.validity)[:rows]
        assert not np.asarray(out.validity)[rows:].any()
        if ENC.is_encoded(out):
            codes = np.asarray(out.data)[:rows]
            data = out.dictionary.host_values()[np.where(v, codes, 0)]
        else:
            assert encoded_ok is False
            data = np.asarray(out.data)[:rows]
            # lanes without a value hold zero
            assert not np.asarray(out.data)[rows:].any()
            assert not data[~v].any()
        values.append(data)
        valid.append(v)
    return np.concatenate(values), np.concatenate(valid)


@pytest.mark.parametrize("case", CASES)
def test_chunk_decodes_equal_to_arrow_in_its_form(case, tmp_path, forms):
    rng = np.random.default_rng(CASES.index(case))
    arr, dtype, opts, encoded_ok, form, dense_is_rows = _column(case, rng)
    opts = dict(opts)
    field = pa.field("c", arr.type, nullable=not opts.pop("required", False))
    path = str(tmp_path / "c.parquet")
    pq.write_table(pa.table([arr], schema=pa.schema([field])), path,
                   compression="snappy", row_group_size=4096, **opts)
    got, valid = _decode_file(path, dtype, encoded_ok)
    want = pq.read_table(path).column("c").combine_chunks()
    want_valid = ~np.asarray(want.is_null())
    assert np.array_equal(valid, want_valid)
    if pa.types.is_date32(want.type):
        want = want.cast(pa.int32())
    want_np = want.fill_null(0).to_numpy(zero_copy_only=False)
    assert np.array_equal(got[valid], want_np[valid])
    # two row groups, each chunk in the form its run tables allow
    assert forms["expand"] == [form, form]
    assert forms["dense_is_rows"] == [dense_is_rows, dense_is_rows]


def test_bit_width_zero_page_takes_the_general_form(forms):
    """A page whose dictionary indices have bit width 0 (a writer's way
    to say 'all index 0'; pyarrow writes width 1) in front of a
    bit-packed page. Hand-assembled, so held to the values it encodes."""
    rng = np.random.default_rng(11)
    dict_vals = np.arange(100, 116, dtype=np.int64)
    idx = rng.integers(0, 16, 64)
    chunk = bytearray(dict_vals.tobytes())
    pages = [PD.PageInfo(PD.PAGE_DICT, 16, PD.ENC_PLAIN, 0, len(chunk))]
    start = len(chunk)
    chunk += b"\x00"                       # bit width 0: 32 x index 0
    pages.append(PD.PageInfo(PD.PAGE_DATA_V1, 32, PD.ENC_RLE_DICT, start,
                             len(chunk) - start))
    start = len(chunk)
    chunk += b"\x04" + bitpacked_stream(idx, 4, 63)[0]
    pages.append(PD.PageInfo(PD.PAGE_DATA_V1, 64, PD.ENC_RLE_DICT, start,
                             len(chunk) - start))
    chunk = bytes(chunk)
    sent = []

    def upload():
        sent.append(1)
        return jnp.asarray(np.frombuffer(chunk, np.uint8))

    out = PD._try_flat_fixed(chunk, upload, pages, DataType.INT64, 96, 0,
                             128, np.dtype(np.int64))
    want = np.concatenate([np.full(32, 100), dict_vals[idx]])
    assert np.array_equal(np.asarray(out.data)[:96], want)
    assert np.asarray(out.validity)[:96].all()
    assert forms["expand"] == ["runs"] and sent == [1]
    # without that page the same stream is packed, and the chunk stays down
    del pages[1], sent[:]
    out = PD._try_flat_fixed(chunk, upload, pages, DataType.INT64, 64, 0,
                             128, np.dtype(np.int64))
    assert np.array_equal(np.asarray(out.data)[:64], dict_vals[idx])
    assert forms["expand"] == ["runs", "packed"] and sent == []


def test_all_present_column_spreads_nothing():
    """`_flat_finish` with the host's count in hand: no prefix sum, no
    gather; a capacity above the dense lanes is padded, not looked up."""
    dense = jnp.arange(1, 9, dtype=jnp.int32)
    validity = jnp.ones((16,), bool)
    nums = jnp.asarray([6, 6], jnp.int32)
    data, valid = PD._flat_finish(dense, validity, nums, 16, True)
    assert np.asarray(data).tolist() == [1, 2, 3, 4, 5, 6] + [0] * 10
    assert np.asarray(valid).tolist() == [True] * 6 + [False] * 10
    ops = _ops(PD._flat_finish.lower(dense, validity, nums, 16,
                                     True).as_text())
    assert ops and not ops & {"gather", "reduce_window", "while"}
    assert "gather" in _ops(PD._flat_finish.lower(
        dense, validity, nums, 16, False).as_text())


# ---------------------------------------------------------------------------
# the program: what it holds, and what keys it
# ---------------------------------------------------------------------------
def test_packed_program_is_static_slices_shifts_and_masks():
    bw, cap = 12, 1 << 14
    planes = jax.ShapeDtypeStruct((bw, cap // 32), jnp.uint32)
    chunk = jax.ShapeDtypeStruct((30000,), jnp.uint8)
    empty = PD._EMPTY_TAB()
    tab = tuple(jax.ShapeDtypeStruct((64,), a.dtype) for a in empty)
    packed = PD._flat_dict_codes_kernel.lower(
        planes, empty, empty, bw, cap, cap, PD._ONES, PD._PACKED)
    for text in (packed.as_text(), packed.compile().as_text()):
        ops = _ops(text)
        assert ops and not ops & {"gather", "while", "dynamic_slice",
                                  "dynamic-slice", "scatter"}
    # the general form holds both (the test can see them)
    general = PD._flat_dict_codes_kernel.lower(
        chunk, tab, tab, bw, cap, cap, PD._RUNS, PD._RUNS)
    assert {"gather", "while"} <= _ops(general.as_text())
    # with a dictionary gather the packed program still looks nothing up
    # per lane of the index stream: one gather, the dictionary's
    dict_vals = jax.ShapeDtypeStruct((4096,), jnp.float32)
    decoded = PD._flat_dict_kernel.lower(
        planes, empty, empty, dict_vals, bw, cap, cap, PD._ONES,
        PD._PACKED).as_text()
    assert len(re.findall(r'= "?stablehlo\.gather', decoded)) == 1
    assert "while" not in _ops(decoded)


def _dict_chunk(path, rows, seed, ndv, as_date):
    rng = np.random.default_rng(seed)
    vals = rng.integers(0, ndv, rows)
    arr = pa.array(vals.astype(np.int32)).cast(pa.date32()) if as_date \
        else pa.array(vals.astype(np.float64))
    pq.write_table(pa.table({"c": arr}), path, compression="snappy")
    col = pq.ParquetFile(path).metadata.row_group(0).column(0)
    return PD.read_chunk_bytes(path, col), col.compression, vals


@pytest.mark.parametrize("encoded_ok", [True, False])
def test_chunks_of_one_width_and_capacity_share_a_program(tmp_path,
                                                          encoded_ok):
    """Two chunks of different byte length, run count and dictionary
    length, the same (bit width, capacity): the second builds nothing."""
    dtype = DataType.DATE if encoded_ok else DataType.FLOAT64
    a, codec, vals_a = _dict_chunk(str(tmp_path / "a.parquet"), 4000, 1,
                                   300, encoded_ok)
    b, _, vals_b = _dict_chunk(str(tmp_path / "b.parquet"), 3000, 2,
                               290, encoded_ok)
    assert len(a) != len(b)

    def decode(chunk, rows):
        out = PD.decode_chunk_device(chunk, dtype, rows, max_def=1,
                                     codec=codec, encoded_ok=encoded_ok)
        jax.block_until_ready(out.data)
        return out

    def run_count(chunk):
        raw, pages = PD.normalize_chunk(chunk, codec)
        p = pages[-1]
        dl = int.from_bytes(raw[p.data_start:p.data_start + 4], "little")
        pos = p.data_start + 4 + dl
        rt = PD.parse_runs(raw, pos + 1, p.data_start + p.data_len,
                           raw[pos], p.num_values)
        return raw[pos], len(rt.out_start)

    (bw_a, runs_a), (bw_b, runs_b) = run_count(a), run_count(b)
    assert bw_a == bw_b == 9 and runs_a != runs_b
    assert bucket_capacity(4000) == bucket_capacity(3000)
    decode(a, 4000)
    kernel = PD._flat_dict_codes_kernel if encoded_ok \
        else PD._flat_dict_kernel
    programs = kernel._cache_size()
    before = compile_clock.compiling_ns(wall_ns())
    out = decode(b, 3000)
    assert compile_clock.compiling_ns(wall_ns()) == before
    assert kernel._cache_size() == programs
    if not encoded_ok:
        assert np.array_equal(np.asarray(out.data)[:3000], vals_b)


# ---------------------------------------------------------------------------
# the span's attr
# ---------------------------------------------------------------------------
def test_scan_decode_span_says_which_form(tmp_path):
    rng = np.random.default_rng(5)
    n = 3000
    packed = rng.integers(0, 200, n).astype(np.int64)
    nulls = pa.array(rng.integers(0, 200, n).astype(np.int64),
                     mask=rng.random(n) < 0.1)
    path = str(tmp_path / "t.parquet")
    pq.write_table(pa.table({"p": packed, "r": nulls,
                             "s": [f"s{i % 7}" for i in range(n)]}),
                   path, compression="snappy")
    session = srt.new_session({C.OBS_TRACING.key: True,
                               "rapids.tpu.sql.spmd.meshDevices": 1})
    try:
        rows = session.read.parquet(path).collect()
        trace = session.last_query_trace
    finally:
        session.stop()
    assert sorted(r[0] for r in rows) == sorted(packed.tolist())
    by_column = {sp.attrs["column"]: sp for sp in trace.find("scan.decode")}
    assert by_column["p"].attrs["expand"] == "packed"
    assert by_column["r"].attrs["expand"] == "runs"
    # a string column takes the per-page loop: no form to report
    assert "expand" not in by_column["s"].attrs
    # the page walk is the host half's (PR 29): under the chunk's
    # `scan.read`, ahead of the permit; the upload stays the decode's
    parses = {}
    for sp in trace.find("scan.read"):
        (parse,) = sp.children
        assert parse.name == "scan.parse"
        parses[sp.attrs["column"]] = parse
    # the packed form uploads its payload alone, under the chunk's bytes
    (upload,) = by_column["p"].children
    assert upload.name == "scan.upload"
    assert 0 < upload.attrs["bytes"] < parses["p"].attrs["bytes_out"]
    (upload,) = by_column["r"].children
    assert upload.attrs["bytes"] == parses["r"].attrs["bytes_out"]
