"""The native page decompressor (PR 29, native/srt_native.cpp
srt_snappy_pages): a SNAPPY column chunk's pages in one call that leaves
the interpreter once, instead of one `Codec.decompress` a page, each a
hand-over of the interpreter's lock to whatever other thread wants it
(the scan's readers and tasks run side by side). Pinned: byte equality
with Arrow's codec on every kind of element the format has, refusal
(None, so the per-page loop judges) of what it does not take, and which
native entry points keep the lock."""

import ctypes

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from spark_rapids_tpu.io import parquet_device as PD
from spark_rapids_tpu.native import get_lib

pytestmark = pytest.mark.skipif(get_lib() is None,
                                reason="native library not built")
SNAPPY = pa.Codec("snappy")


def _decompress(blocks):
    """`blocks` (compressed, uncompressed length) as the pages of one
    chunk through srt_snappy_pages: (rc, output bytes)."""
    lib = get_lib()
    chunk = b"".join(b for b, _ in blocks)
    n = len(blocks)
    src_len = np.asarray([len(b) for b, _ in blocks], np.int64)
    src_off = np.concatenate([[0], np.cumsum(src_len)[:-1]]).astype(np.int64)
    dst_len = np.asarray([u for _, u in blocks], np.int64)
    dst_off = np.concatenate([[0], np.cumsum(dst_len)[:-1]]).astype(np.int64)
    total = int(dst_len.sum())
    out = bytearray(total)
    i64 = ctypes.POINTER(ctypes.c_int64)
    rc = lib.srt_snappy_pages(
        chunk, len(chunk), n, src_off.ctypes.data_as(i64),
        src_len.ctypes.data_as(i64), dst_off.ctypes.data_as(i64),
        dst_len.ctypes.data_as(i64),
        (ctypes.c_uint8 * max(total, 1)).from_buffer(
            out if total else bytearray(1)), total)
    return rc, bytes(out)


def _payloads():
    rng = np.random.default_rng(29)
    text = " ".join(f"word{int(i)}" for i in rng.integers(0, 50, 30000))
    out = {
        "one_byte": b"x",
        "short_literal": b"hello, parquet",
        "literal_60": bytes(range(60)),            # length byte follows
        "literal_61": bytes(range(61)),
        "incompressible_70k": rng.bytes(70_000),   # 3-byte literal length
        "zeros_100k": bytes(100_000),              # copies of offset 1
        "text": text.encode(),                     # copies of every kind
        "ints": rng.integers(0, 2526, 1 << 16).astype(np.int32).tobytes(),
        "far_copy": rng.bytes(70_000) * 2,         # 4-byte offsets
    }
    # runs of every period under 20: the overlapping copies, with the
    # 8-byte moves' boundary (offset 8) and the byte loop under it
    for period in range(1, 20):
        out[f"period_{period}"] = bytes(range(1, period + 1)) * 500
    return out


PAYLOADS = _payloads()


@pytest.mark.parametrize("name", sorted(PAYLOADS))
def test_block_equals_arrows_codec(name):
    raw = PAYLOADS[name]
    comp = SNAPPY.compress(raw, asbytes=True)
    assert SNAPPY.decompress(comp, len(raw), asbytes=True) == raw
    rc, out = _decompress([(comp, len(raw))])
    assert rc == 0 and out == raw


def test_pages_land_at_their_offsets():
    names = ["text", "zeros_100k", "one_byte", "incompressible_70k"]
    blocks = [(SNAPPY.compress(PAYLOADS[n], asbytes=True), len(PAYLOADS[n]))
              for n in names]
    blocks.insert(2, (b"", 0))  # a page without a payload
    rc, out = _decompress(blocks)
    assert rc == 0
    assert out == b"".join(PAYLOADS[n] for n in names)


@pytest.mark.parametrize("damage", ["truncated", "wrong_length", "bad_offset",
                                    "garbage"])
def test_a_malformed_block_is_refused_not_read_past(damage):
    raw = PAYLOADS["text"]
    comp = SNAPPY.compress(raw, asbytes=True)
    size = len(raw)
    if damage == "truncated":
        comp = comp[:len(comp) // 2]
    elif damage == "wrong_length":
        size += 1
    elif damage == "bad_offset":
        # a copy that reaches before the start of the output
        comp = bytes([4, 0b000000_00, ord("a"), 0b000_001_01, 9])
        size = 4
    else:
        size = 1000  # varint 0xE8 0x07, then copies with nothing behind
        comp = bytes([0xE8, 0x07]) + b"\xff" * 40
    good = SNAPPY.compress(b"ok", asbytes=True)
    rc, _ = _decompress([(good, 2), (comp, size)])
    assert rc == -2  # the second page


def _chunks(path):
    md = pq.ParquetFile(path).metadata
    for rg in range(md.num_row_groups):
        for ci in range(md.num_columns):
            col = md.row_group(rg).column(ci)
            yield col, PD.read_chunk_bytes(path, col)


def _table(n=40_000):
    rng = np.random.default_rng(7)
    return pa.table({
        "codes": rng.integers(0, 2526, n).astype(np.int32),
        "nulls": pa.array(rng.integers(0, 50, n).astype(np.int64),
                          mask=rng.random(n) < 0.1),
        "price": rng.integers(0, 10_000_000, n) / 100.0,
        "flag": [f"flag{j % 3}" for j in range(n)],
        "comment": [f"the quick brown fox {j % 977} jumps" for j in range(n)],
        "zeros": np.zeros(n, np.int64)})


def test_normalize_chunk_equals_the_per_page_loop(tmp_path, monkeypatch):
    path = str(tmp_path / "t.parquet")
    pq.write_table(_table(), path, compression="snappy",
                   row_group_size=20_000, data_page_size=8 << 10)
    native = [PD.normalize_chunk(chunk, col.compression)
              for col, chunk in _chunks(path)]
    calls = []
    real = PD._normalize_snappy_native
    monkeypatch.setattr(PD, "_normalize_snappy_native",
                        lambda c, p: calls.append(real(c, p)))
    loop = [PD.normalize_chunk(chunk, col.compression)
            for col, chunk in _chunks(path)]
    assert len(native) == 12 and native == loop
    # the native call had an answer for every chunk, several pages each
    assert all(got is not None for got in calls)
    assert max(len(pages) for _, pages in native) > 4
    for data, pages in native:
        assert isinstance(data, bytes)
        assert pages[-1].data_start + pages[-1].data_len == len(data)
        assert not any(p.data_compressed for p in pages)


def test_what_the_native_call_does_not_take(tmp_path, monkeypatch):
    table = _table(5000)
    v2 = str(tmp_path / "v2.parquet")
    pq.write_table(table, v2, compression="snappy", data_page_version="2.0")
    for col, chunk in _chunks(v2):
        pages = PD._parse_pages_py(chunk)
        assert PD._normalize_snappy_native(chunk, pages) is None
        data, _ = PD.normalize_chunk(chunk, col.compression)  # the loop's
        assert len(data) >= col.total_uncompressed_size - 64 * len(pages)
    v1 = str(tmp_path / "v1.parquet")
    pq.write_table(table, v1, compression="snappy")
    col, chunk = next(_chunks(v1))
    pages = PD._parse_pages_py(chunk)
    # a payload that is not what its header says: None, and the per-page
    # loop raises what Arrow's codec makes of it
    last = pages[-1]
    broken = bytearray(chunk)
    broken[last.data_start:last.data_start + 8] = b"\xff" * 8
    assert PD._normalize_snappy_native(bytes(broken), pages) is None
    with pytest.raises(Exception):
        PD.normalize_chunk(bytes(broken), "SNAPPY")
    # no library: the loop
    monkeypatch.setattr("spark_rapids_tpu.native.get_lib", lambda: None)
    assert PD._normalize_snappy_native(chunk, pages) is None
    zstd = str(tmp_path / "zstd.parquet")
    pq.write_table(table, zstd, compression="zstd")
    for col, chunk in _chunks(zstd):
        data, pages = PD.normalize_chunk(chunk, col.compression)
        assert pages[-1].data_start + pages[-1].data_len == len(data)


def test_per_page_helpers_keep_the_interpreter_lock():
    """`srt_parse_runs`, `srt_parse_pages` and `srt_plain_strings` run
    for microseconds, a page at a time: bound through PyDLL, so a call
    does not hand the lock to another thread and queue for it again (on
    the chip's host 56 such calls a chunk took 24.6 ms with eight threads
    side by side, 1.2 ms through PyDLL: PERF.md, PR 29). The calls that
    take a whole chunk or file leave the interpreter."""
    lib = get_lib()
    python_api = 0x4  # ctypes' _FUNCFLAG_PYTHONAPI
    for name in ("srt_parse_runs", "srt_parse_pages", "srt_plain_strings"):
        assert getattr(lib, name)._flags_ & python_api, name
    for name in ("srt_snappy_pages", "srt_csv_plan"):
        assert not getattr(lib, name)._flags_ & python_api, name
