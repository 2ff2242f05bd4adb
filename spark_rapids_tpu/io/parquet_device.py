"""Device-side parquet column decode.

Reference parity: the reference decodes parquet ON the accelerator —
it reassembles a minimal in-memory file from raw column chunks on the host
and hands the bytes to the GPU decoder (`GpuParquetScan.scala:316-458`
host reassembly, `:536-556` device `Table.readParquet`). The TPU-native
split keeps the same shape:

- HOST (control plane, tiny): parse thrift-compact page headers and the
  RLE/bit-packed *run tables* (a few dozen entries per page — runs, not
  values), and locate the dictionary. No value is decoded on the host.
- DEVICE (data plane): ONE jitted program per (shape-bucket) expands
  definition-level runs into the validity mask, expands dictionary-index
  runs (RLE repeats + bit-packed groups extracted straight from the raw
  chunk bytes), and gathers the dictionary — i.e. the decode FLOPs and
  bytes all happen on the accelerator. Upload volume is the raw
  (dictionary-encoded) chunk, typically several times smaller than the
  decoded column.

Scope: flat INT32/INT64 (+DATE/TIMESTAMP, and FLOAT32/FLOAT64 where
the backend has f64) and STRING columns; v1 AND v2 data pages encoded
PLAIN, RLE_DICTIONARY/PLAIN_DICTIONARY, DELTA_BINARY_PACKED (integrals:
the delta recurrence decodes as ONE device cumsum over miniblock-unpacked
deltas, bit widths to 56), DELTA_LENGTH_BYTE_ARRAY (strings: lengths ride
the same delta kernel, byte starts are a device exclusive-sum), or
BYTE_STREAM_SPLIT (fixed-width: strided plane gathers + bitcast), or
DELTA_BYTE_ARRAY (strings: prefix-sharing resolves through a provider
running-max scan, then one gather per output byte; pages whose
values x max-length matrix exceeds the budget fall back). UNCOMPRESSED,
SNAPPY, GZIP, ZSTD and BROTLI codecs.  Compressed pages decompress on the
HOST (block decompression is control-plane: inherently serial bit-stream
work; the reference does it inside cuDF but the data-plane win — run
expansion, dictionary gather, validity spread — is the same either way)
and the decompressed chunk feeds the identical device expansion.  Arrow
remains the oracle and the fallback for everything else (per SURVEY.md
section 7 hard part #2 phasing).
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import List, Optional, Tuple

import numpy as np

from spark_rapids_tpu import _jax_setup  # noqa: F401
import jax
import jax.numpy as jnp

from spark_rapids_tpu.columnar.batch import (
    ColumnarBatch,
    ColumnVector,
    bucket_capacity,
    device_float64_supported,
    physical_np_dtype,
)
from spark_rapids_tpu.columnar.dtypes import DataType
from spark_rapids_tpu.obs import trace as OBS


# ---------------------------------------------------------------------------
# Thrift compact-protocol mini reader (PageHeader only)
# ---------------------------------------------------------------------------
class _Compact:
    """Just enough TCompactProtocol to walk parquet PageHeader structs."""

    def __init__(self, buf: bytes, pos: int = 0):
        self.buf = buf
        self.pos = pos

    def varint(self) -> int:
        out = shift = 0
        while True:
            if shift > 63:
                raise ValueError("malformed varint")
            b = self.buf[self.pos]
            self.pos += 1
            out |= (b & 0x7F) << shift
            if not b & 0x80:
                return out
            shift += 7

    def zigzag(self) -> int:
        v = self.varint()
        return (v >> 1) ^ -(v & 1)

    def struct(self) -> dict:
        """Parse a struct into {field_id: value}; nested structs recurse,
        other types reduce to ints / bytes / skipped."""
        out = {}
        fid = 0
        while True:
            b = self.buf[self.pos]
            self.pos += 1
            if b == 0:
                return out
            delta = b >> 4
            ftype = b & 0x0F
            if delta:
                fid += delta
            else:
                fid = self.zigzag()
            out[fid] = self._value(ftype)

    def _value(self, ftype: int):
        if ftype in (1, 2):          # bool true / false
            return ftype == 1
        if ftype == 3:               # i8
            v = self.buf[self.pos]
            self.pos += 1
            return v
        if ftype in (4, 5, 6):       # i16/i32/i64
            return self.zigzag()
        if ftype == 7:               # double
            v = self.buf[self.pos:self.pos + 8]
            self.pos += 8
            return v
        if ftype == 8:               # binary/string
            n = self.varint()
            v = self.buf[self.pos:self.pos + n]
            self.pos += n
            return v
        if ftype == 9:               # list
            b = self.buf[self.pos]
            self.pos += 1
            n = b >> 4
            et = b & 0x0F
            if n == 15:
                n = self.varint()
            if et in (1, 2):         # bools consume no bytes: nothing to walk
                return []
            if n > len(self.buf) - self.pos:
                # each remaining element needs >= 1 byte; a count beyond the
                # buffer is corruption, not a long loop
                raise ValueError("malformed thrift list length")
            return [self._value(et) for _ in range(n)]
        if ftype == 12:              # struct
            return self.struct()
        raise ValueError(f"unsupported thrift compact type {ftype}")


# PageHeader thrift field ids (parquet.thrift)
_PH_TYPE = 1
_PH_UNCOMPRESSED = 2
_PH_COMPRESSED = 3
_PH_DATA_V1 = 5
_PH_DICT = 7
_PH_DATA_V2 = 8
# DataPageHeader fields
_DP_NUM_VALUES = 1
_DP_ENCODING = 2
_DP_DEF_ENC = 3
# DataPageHeaderV2 fields
_D2_NUM_VALUES = 1
_D2_NUM_NULLS = 2
_D2_NUM_ROWS = 3
_D2_ENCODING = 4
_D2_DEF_LEN = 5
_D2_REP_LEN = 6
_D2_IS_COMPRESSED = 7
# DictionaryPageHeader fields
_DI_NUM_VALUES = 1

PAGE_DATA_V1 = 0
PAGE_DICT = 2
PAGE_DATA_V2 = 3
ENC_PLAIN = 0
ENC_PLAIN_DICT = 2
ENC_RLE = 3
ENC_DELTA_BINARY = 5
ENC_DELTA_LENGTH = 6
ENC_DELTA_BYTE_ARRAY = 7
ENC_RLE_DICT = 8
ENC_BYTE_STREAM_SPLIT = 9

# provider-matrix budget for DELTA_BYTE_ARRAY reconstruction (elements);
# pages whose n_values * max_string_len exceed it fall back to Arrow
_DBA_MATRIX_BUDGET = 64 << 20


@dataclass
class PageInfo:
    kind: int            # PAGE_DATA_V1 | PAGE_DICT | PAGE_DATA_V2
    num_values: int
    encoding: int
    data_start: int      # offset of page payload within the chunk bytes
    data_len: int
    uncompressed_len: int = -1  # -1: same as data_len (uncompressed chunk)
    def_len: int = 0     # v2: definition-levels byte length (never prefixed)
    rep_len: int = 0     # v2: repetition-levels byte length (0 for flat)
    data_compressed: bool = True  # v2: is the data section compressed?


def parse_pages(chunk: bytes) -> List[PageInfo]:
    """Walk the page headers of one raw column chunk (native single pass
    when built, thrift-in-Python fallback; the Python walker also speaks
    v2 data pages, which the native one reports as unsupported)."""
    try:
        pages = _parse_pages_native(chunk)
    except _Unsupported:
        pages = NotImplemented
    if pages is not NotImplemented:
        return pages
    try:
        return _parse_pages_py(chunk)
    except (ValueError, LookupError) as e:
        # headers this reader cannot walk are a page shape out of scope,
        # not a fault of the device: the caller's host decoder judges them
        raise _Unsupported(f"page headers: {e}") from e


def _parse_pages_native(chunk: bytes):
    import ctypes

    from spark_rapids_tpu.native import get_lib

    lib = get_lib()
    if lib is None:
        return NotImplemented
    max_pages = 64
    while True:
        kind = np.empty(max_pages, np.int32)
        num_values = np.empty(max_pages, np.int64)
        encoding = np.empty(max_pages, np.int32)
        data_start = np.empty(max_pages, np.int64)
        data_len = np.empty(max_pages, np.int64)
        n = lib.srt_parse_pages(
            chunk, len(chunk),
            kind.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            num_values.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
            encoding.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            data_start.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
            data_len.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
            max_pages)
        if n == -1:
            max_pages *= 8
            continue
        if n == -4:
            raise _Unsupported("page type not v1/dict")
        if n < 0:
            return NotImplemented  # malformed per native: let python decide
        return [PageInfo(int(kind[i]), int(num_values[i]), int(encoding[i]),
                         int(data_start[i]), int(data_len[i]))
                for i in range(n)]


def _parse_pages_py(chunk: bytes) -> List[PageInfo]:
    pages: List[PageInfo] = []
    pos = 0
    while pos < len(chunk):
        r = _Compact(chunk, pos)
        hdr = r.struct()
        payload = r.pos
        size = hdr[_PH_COMPRESSED]
        usize = hdr.get(_PH_UNCOMPRESSED, size)
        kind = hdr[_PH_TYPE]
        if kind == PAGE_DICT:
            d = hdr[_PH_DICT]
            pages.append(PageInfo(kind, d[_DI_NUM_VALUES], ENC_PLAIN,
                                  payload, size, usize))
        elif kind == PAGE_DATA_V1:
            d = hdr[_PH_DATA_V1]
            pages.append(PageInfo(kind, d[_DP_NUM_VALUES], d[_DP_ENCODING],
                                  payload, size, usize))
        elif kind == PAGE_DATA_V2:
            d = hdr[_PH_DATA_V2]
            pages.append(PageInfo(
                kind, d[_D2_NUM_VALUES], d[_D2_ENCODING], payload, size,
                usize, def_len=d.get(_D2_DEF_LEN, 0),
                rep_len=d.get(_D2_REP_LEN, 0),
                data_compressed=bool(d.get(_D2_IS_COMPRESSED, True))))
        else:  # index pages etc. -> caller falls back to Arrow
            raise _Unsupported(f"page type {kind}")
        pos = payload + size
    return pages


class _Unsupported(Exception):
    pass


# ---------------------------------------------------------------------------
# Host-side page decompression (control plane)
# ---------------------------------------------------------------------------
_CODEC_NAMES = {"SNAPPY": "snappy", "GZIP": "gzip", "ZSTD": "zstd",
                "BROTLI": "brotli"}


@functools.lru_cache(maxsize=None)
def _get_codec(parquet_codec: str):
    """pyarrow block codec for a parquet CompressionCodec name, or None if
    this build of Arrow lacks it. (LZ4/LZO stay unsupported: parquet's LZ4
    framing differs from the lz4-frame codec Arrow exposes.)"""
    name = _CODEC_NAMES.get(parquet_codec)
    if name is None:
        return None
    try:
        import pyarrow as pa

        return pa.Codec(name)
    except Exception:
        return None


def codec_supported(parquet_codec: str) -> bool:
    return parquet_codec == "UNCOMPRESSED" or \
        _get_codec(parquet_codec) is not None


def normalize_chunk(chunk: bytes, codec: str):
    """Decompress every page payload of a raw column chunk, returning
    (uncompressed_chunk_bytes, pages-with-offsets-into-it). v2 pages keep
    their level bytes (stored uncompressed by spec) and decompress only the
    data section. The result feeds the same device expansion kernels as a
    natively UNCOMPRESSED chunk — decompression is host control-plane work,
    the decode data plane stays on the device."""
    pages = _parse_pages_py(chunk)
    if codec == "UNCOMPRESSED":
        return chunk, pages
    dec = _get_codec(codec)
    if dec is None:
        raise _Unsupported(f"codec {codec}")
    if codec == "SNAPPY":
        native = _normalize_snappy_native(chunk, pages)
        if native is not None:
            return native
    out = bytearray()
    new_pages = []
    from dataclasses import replace as _replace

    for p in pages:
        payload = chunk[p.data_start:p.data_start + p.data_len]
        usize = p.uncompressed_len if p.uncompressed_len >= 0 else p.data_len
        if p.kind == PAGE_DATA_V2:
            lvl = p.rep_len + p.def_len
            body = payload[lvl:]
            if p.data_compressed and len(body):
                body = dec.decompress(body, usize - lvl).to_pybytes()
            new_payload = bytes(payload[:lvl]) + bytes(body)
        else:
            new_payload = dec.decompress(payload, usize).to_pybytes() \
                if len(payload) else b""
        start = len(out)
        out += new_payload
        new_pages.append(_replace(p, data_start=start,
                                  data_len=len(new_payload),
                                  uncompressed_len=len(new_payload),
                                  data_compressed=False))
    return bytes(out), new_pages


def _normalize_snappy_native(chunk: bytes, pages: List[PageInfo]):
    """normalize_chunk's page loop for a SNAPPY chunk of v1 and dictionary
    pages as ONE native call (native/srt_native.cpp srt_snappy_pages), or
    None where the library is not built, a page is v2, or a page does not
    decompress to its header's size (the per-page loop then says what
    Arrow's codec makes of it). One call a chunk matters beside other
    threads: every call that leaves the interpreter hands its lock over,
    and on a busy host each hand-over waits its turn behind the other
    threads (PERF.md, PR 29: 0.7 ms a chunk alone, 33 ms a chunk with
    eight scans side by side, a page's `decompress` a hand-over each)."""
    import ctypes
    from dataclasses import replace as _replace

    from spark_rapids_tpu.native import get_lib

    lib = get_lib()
    if lib is None or any(p.kind == PAGE_DATA_V2 for p in pages):
        return None
    n = len(pages)
    src_off = np.fromiter((p.data_start for p in pages), np.int64, n)
    src_len = np.fromiter((p.data_len for p in pages), np.int64, n)
    dst_len = np.fromiter(
        (0 if p.data_len == 0 else
         p.uncompressed_len if p.uncompressed_len >= 0 else p.data_len
         for p in pages), np.int64, n)
    dst_off = np.zeros(n, np.int64)
    np.cumsum(dst_len[:-1], out=dst_off[1:])
    total = int(dst_len.sum())
    out = bytearray(total)
    i64 = ctypes.POINTER(ctypes.c_int64)
    rc = lib.srt_snappy_pages(
        chunk, len(chunk), n, src_off.ctypes.data_as(i64),
        src_len.ctypes.data_as(i64), dst_off.ctypes.data_as(i64),
        dst_len.ctypes.data_as(i64),
        (ctypes.c_uint8 * total).from_buffer(out), total)
    if rc != 0:
        return None
    new_pages = [
        _replace(p, data_start=int(dst_off[i]), data_len=int(dst_len[i]),
                 uncompressed_len=int(dst_len[i]), data_compressed=False)
        for i, p in enumerate(pages)]
    return bytes(out), new_pages


# ---------------------------------------------------------------------------
# RLE/bit-packed hybrid run tables (host: runs only, never values)
# ---------------------------------------------------------------------------
@dataclass
class RunTable:
    """Decoded structure of one RLE/bit-packed hybrid stream: per run its
    output range and either a repeated value or the absolute BIT offset of
    its packed values within the chunk."""

    out_start: np.ndarray   # int32 [n_runs]
    is_rle: np.ndarray      # bool  [n_runs]
    value: np.ndarray       # int32 [n_runs] (RLE runs)
    bit_off: np.ndarray     # int64 [n_runs] (bit-packed runs, absolute bits)
    total: int              # values described (>= logical count; bp pads to 8)


def parse_runs(chunk: bytes, start: int, end: int, bit_width: int,
               num_values: int) -> RunTable:
    """Run-table extraction; uses the native kernel
    (native/srt_native.cpp srt_parse_runs) when built, else pure Python."""
    native = _parse_runs_native(chunk, start, end, bit_width, num_values)
    if native is not None:
        return native
    return _parse_runs_py(chunk, start, end, bit_width, num_values)


def _parse_runs_native(chunk: bytes, start: int, end: int, bit_width: int,
                       num_values: int) -> Optional[RunTable]:
    import ctypes

    from spark_rapids_tpu.native import get_lib

    lib = get_lib()
    if lib is None:
        return None
    # start small (typical streams have few runs); grow on overflow up to
    # the worst case of one RLE header per value
    max_runs = min(max(64, num_values // 64), num_values + 1)
    while True:
        out_start = np.empty(max_runs, np.int64)
        is_rle = np.empty(max_runs, np.uint8)
        value = np.empty(max_runs, np.int32)
        bit_off = np.empty(max_runs, np.int64)
        produced = ctypes.c_int64(0)
        n = lib.srt_parse_runs(
            chunk, start, end, bit_width, num_values,
            out_start.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
            is_rle.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
            value.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            bit_off.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
            max_runs, ctypes.byref(produced))
        if n == -1 and max_runs <= num_values:
            max_runs = min(max_runs * 8, num_values + 1)
            continue
        if n < 0:
            return None
        return RunTable(out_start[:n].astype(np.int32),
                        is_rle[:n].astype(bool),
                        value[:n], bit_off[:n], produced.value)


def _parse_runs_py(chunk: bytes, start: int, end: int, bit_width: int,
                   num_values: int) -> RunTable:
    out_start: List[int] = []
    is_rle: List[bool] = []
    value: List[int] = []
    bit_off: List[int] = []
    r = _Compact(chunk, start)
    produced = 0
    vbytes = (bit_width + 7) // 8
    while produced < num_values and r.pos < end:
        header = r.varint()
        if header & 1:  # bit-packed: (header>>1) groups of 8 values
            groups = header >> 1
            count = groups * 8
            out_start.append(produced)
            is_rle.append(False)
            value.append(0)
            bit_off.append(r.pos * 8)
            r.pos += groups * bit_width
        else:           # RLE run of (header>>1) copies of one LE value
            count = header >> 1
            v = int.from_bytes(chunk[r.pos:r.pos + vbytes], "little")
            r.pos += vbytes
            out_start.append(produced)
            is_rle.append(True)
            value.append(v)
            bit_off.append(0)
        produced += count
    return RunTable(np.asarray(out_start, np.int32),
                    np.asarray(is_rle, bool),
                    np.asarray(value, np.int32),
                    np.asarray(bit_off, np.int64),
                    produced)


# ---------------------------------------------------------------------------
# Device expansion kernels
# ---------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnums=(5, 6))
def _expand_hybrid(chunk_u8, out_start, is_rle, value, bit_off,
                   bit_width: int, cap: int):
    """values[j] for j in [0, cap): find j's run (searchsorted), then either
    the run's repeated value or a bit-window extracted from the raw bytes.
    bit_width <= 24 so a 4-byte LE gather always covers the window."""
    j = jnp.arange(cap, dtype=jnp.int32)
    run = jnp.clip(
        jnp.searchsorted(out_start, j, side="right") - 1,
        0, out_start.shape[0] - 1).astype(jnp.int32)
    k = j - out_start[run]
    bitpos = bit_off[run] + k.astype(jnp.int64) * bit_width
    byte = (bitpos >> 3).astype(jnp.int32)
    shift = (bitpos & 7).astype(jnp.int32)
    nbytes = chunk_u8.shape[0]
    b = jnp.zeros((cap,), dtype=jnp.uint32)
    for o in range(4):
        src = jnp.clip(byte + o, 0, nbytes - 1)
        b = b | (chunk_u8[src].astype(jnp.uint32) << (8 * o))
    mask = jnp.uint32((1 << bit_width) - 1) if bit_width < 32 else \
        jnp.uint32(0xFFFFFFFF)
    packed = (b >> shift.astype(jnp.uint32)) & mask
    return jnp.where(is_rle[run], value[run].astype(jnp.uint32),
                     packed).astype(jnp.int32)


# The forms one hybrid stream's expansion takes. Which one is read off the
# host's run table, never asked for: `_RUNS` is the general per-lane
# lookup above; `_PACKED` a stream that is one contiguous run of
# bit-packed values once its run and page headers are dropped
# (`_pack_value_stream`); `_ONES` a definition-level stream the host has
# counted as all present.
_RUNS, _PACKED, _ONES = "runs", "packed", "ones"
_PACK_LANES = 32  # values a packed group holds: `bw` whole 32-bit words


def _expand_stream(src, tab, bit_width: int, cap: int, form: str):
    """values[j] for j in [0, cap) of one hybrid stream, in the form its
    run table allows. `src` is what that form reads: the chunk's bytes
    (`_RUNS`), the word planes of `_pack_value_stream` (`_PACKED`),
    nothing (`_ONES`)."""
    if form == _ONES:
        return jnp.ones((cap,), jnp.int32)
    if form == _PACKED:
        return _unpack_planes(src, bit_width, cap)
    return _expand_hybrid(src, *tab, bit_width, cap)


def _unpack_planes(planes, bit_width: int, cap: int):
    """Bit-unpack with static shapes only. planes: uint32
    [bit_width, groups]; column g holds the `bit_width` little-endian
    words of values 32g .. 32g+31, so value k of every group is a shift
    and a mask of plane (k * bit_width) // 32 (and of the next plane,
    where the value straddles two words): 32 elementwise passes over
    rows of `groups` lanes, then one interleave. No lane looks anything
    up."""
    mask = jnp.uint32((1 << bit_width) - 1)
    lanes = []
    for k in range(_PACK_LANES):
        w, sh = divmod(k * bit_width, 32)
        v = planes[w] >> jnp.uint32(sh)
        if sh + bit_width > 32:
            v = v | (planes[w + 1] << jnp.uint32(32 - sh))
        lanes.append(v & mask)
    out = jnp.stack(lanes, axis=1).reshape(-1)
    return out[:cap].astype(jnp.int32)


def _pack_value_stream(chunk: bytes, pages, bit_width: int,
                       cap: int) -> Optional[np.ndarray]:
    """Host half of the `_PACKED` form. `pages`: per data page, in order,
    (RunTable, values the page holds), None for a page without a run
    table. The stream qualifies when every run is bit-packed and every
    run but the stream's last is full — no page but the last that holds
    values pads its final group — so the values are ONE bit-packed
    sequence with headers spliced in. Returns the runs' payload bytes,
    headers dropped, zero-padded to `cap` values and laid out as the
    word planes `_unpack_planes` reads; None where the stream needs the
    general form. A byte copy: no value is decoded here."""
    starts, lens = [], []
    padded = False
    for page in pages:
        if page is None:
            return None
        rt, n = page
        if n == 0:
            continue
        if padded or rt.total < n or bool(rt.is_rle.any()):
            return None
        padded = rt.total > n
        counts = np.diff(rt.out_start, append=np.int32(rt.total))
        starts.append(rt.bit_off >> 3)
        lens.append(counts.astype(np.int64) // 8 * bit_width)
    if not starts:
        return None
    starts = np.concatenate(starts)
    ends = starts + np.concatenate(lens)
    capw = max(cap, _PACK_LANES)
    nbytes = capw // 8 * bit_width
    total = int((ends - starts).sum())
    if int(ends.max()) > len(chunk) or total > nbytes:
        return None
    view = memoryview(chunk)
    parts = [view[s:e] for s, e in zip(starts.tolist(), ends.tolist())]
    parts.append(bytes(nbytes - total))
    words = np.frombuffer(b"".join(parts), np.dtype("<u4")).reshape(
        capw // _PACK_LANES, bit_width)
    return np.ascontiguousarray(words.T)


def _parse_delta_header(chunk: bytes, pos: int, end: int, n_values: int):
    """Host control plane for one DELTA_BINARY_PACKED page: walk the block/
    miniblock headers into per-miniblock tables (bit offset, width,
    min_delta) — runs-not-values, same discipline as parse_runs. Returns
    (first_value, vpm, mb_bit_off, mb_width, mb_min_delta, data_base)
    where data_base is the first byte past the delta stream (the value
    bytes of a DELTA_LENGTH_BYTE_ARRAY page start there)."""
    r = _Compact(chunk, pos)
    block_size = r.varint()
    mbs_per_block = r.varint()
    total = r.varint()
    first_value = r.zigzag()
    if total != n_values:
        raise _Unsupported(
            f"delta page count {total} != page num_values {n_values}")
    if mbs_per_block <= 0 or block_size % (8 * mbs_per_block) != 0:
        raise _Unsupported("malformed delta block geometry")
    vpm = block_size // mbs_per_block
    ndeltas = total - 1
    mb_off: List[int] = []
    mb_w: List[int] = []
    mb_md: List[int] = []
    idx = 0
    while idx < ndeltas:
        if r.pos >= end:
            raise _Unsupported("truncated delta page")
        min_delta = r.zigzag()
        widths = chunk[r.pos:r.pos + mbs_per_block]
        if len(widths) < mbs_per_block:
            raise _Unsupported("truncated delta miniblock widths")
        r.pos += mbs_per_block
        for w in widths:
            if idx >= ndeltas:
                break  # trailing miniblocks of the last block carry no data
            if w > 56:
                # the 8-byte LE bit-window below covers w + 7 shift bits
                raise _Unsupported(f"delta miniblock bit width {w}")
            mb_off.append(r.pos * 8)
            mb_w.append(int(w))
            mb_md.append(min_delta)
            r.pos += vpm * int(w) // 8
            idx += vpm
        if r.pos > end:
            raise _Unsupported("delta miniblock data past page end")
    if not mb_off:  # 0- or 1-value page: kernel still wants non-empty tables
        mb_off, mb_w, mb_md = [0], [0], [0]
    return (first_value, vpm, np.asarray(mb_off, np.int64),
            np.asarray(mb_w, np.int32), np.asarray(mb_md, np.int64),
            r.pos)  # r.pos = first byte past the delta stream


@functools.partial(jax.jit, static_argnums=(4, 5))
def _expand_delta(chunk_u8, mb_bit_off, mb_width, mb_min_delta,
                  vpm: int, cap: int):
    """DELTA_BINARY_PACKED device expansion: unpack each miniblock-packed
    delta with an 8-byte LE bit window (width <= 56), add its miniblock's
    min_delta, then ONE cumulative sum rebuilds the prefix — the
    delta-decode recurrence is exactly a cumsum, the most TPU-friendly
    shape it could take. Returns the per-index delta PREFIX (value_i -
    first_value); the caller adds first_value."""
    i = jnp.arange(cap, dtype=jnp.int32)
    d = i - 1                    # delta feeding value i (none for i == 0)
    dc = jnp.clip(d, 0, cap - 1)
    m = jnp.clip(dc // vpm, 0, mb_width.shape[0] - 1)
    w = mb_width[m].astype(jnp.int64)
    bitpos = mb_bit_off[m] + (dc % vpm).astype(jnp.int64) * w
    byte = (bitpos >> 3).astype(jnp.int32)
    shift = (bitpos & 7).astype(jnp.uint64)
    nbytes = chunk_u8.shape[0]
    word = jnp.zeros((cap,), dtype=jnp.uint64)
    for o in range(8):
        src = jnp.clip(byte + o, 0, nbytes - 1)
        word = word | (chunk_u8[src].astype(jnp.uint64) << jnp.uint64(8 * o))
    mask = (jnp.uint64(1) << w.astype(jnp.uint64)) - jnp.uint64(1)
    vbits = (word >> shift) & mask
    delta = vbits.astype(jnp.int64) + mb_min_delta[m]
    return jnp.cumsum(jnp.where(d >= 0, delta, 0))


@functools.partial(jax.jit, static_argnums=(4, 5))
def _expand_dba(chunk_u8, plen, slen, suffix_base, maxlen: int,
                byte_cap: int):
    """DELTA_BYTE_ARRAY reconstruction: string i = first plen[i] bytes of
    string i-1 + suffix i. The recurrence vectorizes through a PROVIDER
    matrix: byte j of string i resolves to the suffix byte (j - plen[p])
    of p = max{p' <= i : plen[p'] <= j} — a per-byte-column running max
    (one associative scan over rows), then every output byte is one
    gather. (cuDF's CUDA decoder resolves the same recurrence with a
    block-parallel scan.) plen/slen must be zero beyond the real values.
    Returns (bytes [byte_cap], offsets [n+1])."""
    n = plen.shape[0]
    out_len = plen + slen
    out_off = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                               jnp.cumsum(out_len, dtype=jnp.int32)])
    i = jnp.arange(n, dtype=jnp.int32)[:, None]
    j = jnp.arange(maxlen, dtype=jnp.int32)[None, :]
    cand = jnp.where(plen[:, None] <= j, i, -1)
    prov = jax.lax.associative_scan(jnp.maximum, cand, axis=0)
    scum = jnp.cumsum(slen, dtype=jnp.int32)
    sstart = suffix_base.astype(jnp.int32) + scum - slen
    pos = jnp.arange(byte_cap, dtype=jnp.int32)
    row = jnp.clip(jnp.searchsorted(out_off[1:], pos, side="right"),
                   0, n - 1).astype(jnp.int32)
    jj = pos - out_off[row]
    p = prov[row, jnp.clip(jj, 0, maxlen - 1)]
    pc = jnp.clip(p, 0, n - 1)
    src = sstart[pc] + (jj - plen[pc])
    valid = (pos < out_off[-1]) & (p >= 0)
    byte = chunk_u8[jnp.clip(src, 0, chunk_u8.shape[0] - 1)]
    return jnp.where(valid, byte, 0).astype(jnp.uint8), out_off


@functools.partial(jax.jit, static_argnums=(2, 3))
def _fold_flba_be(chunk_u8, byte_start, count: int, w: int):
    """FIXED_LEN_BYTE_ARRAY decimals: w-byte big-endian two's-complement
    unscaled values folded to int64 (the logical precision <= 18 guarantees
    the value fits, so bytes beyond the low 8 are sign extension)."""
    i = jnp.arange(count, dtype=jnp.int32)
    base = byte_start + i * w
    nbytes = chunk_u8.shape[0]
    word = jnp.zeros((count,), dtype=jnp.uint64)
    for k in range(min(w, 8)):  # k-th byte from the little end
        src = jnp.clip(base + (w - 1 - k), 0, nbytes - 1)
        word = word | (chunk_u8[src].astype(jnp.uint64) << jnp.uint64(8 * k))
    if w < 8:
        sign = (word >> jnp.uint64(8 * w - 1)) & jnp.uint64(1)
        ext = jnp.uint64(((1 << 64) - 1) ^ ((1 << (8 * w)) - 1))
        word = jnp.where(sign == 1, word | ext, word)
    return word.astype(jnp.int64)


@functools.partial(jax.jit, static_argnums=(3, 4))
def _decode_bss(chunk_u8, pos, n, cap: int, np_dtype_name: str):
    """BYTE_STREAM_SPLIT: value i's byte k lives at pos + k*n + i (one
    plane per byte, improving downstream compression). The device
    re-interleaves with w strided gathers + one bitcast."""
    dt = np.dtype(np_dtype_name)
    w = dt.itemsize
    i = jnp.arange(cap, dtype=jnp.int32)
    nbytes = chunk_u8.shape[0]
    planes = [chunk_u8[jnp.clip(pos + k * n + i, 0, nbytes - 1)]
              for k in range(w)]
    return jax.lax.bitcast_convert_type(
        jnp.stack(planes, axis=1), jnp.dtype(dt))


@functools.partial(jax.jit, static_argnums=(2,))
def _extract_bits_lsb(chunk_u8, byte_start, count: int):
    """PLAIN-encoded booleans: one bit per value, LSB-first per byte."""
    i = jnp.arange(count, dtype=jnp.int32)
    nbytes = chunk_u8.shape[0]
    b = chunk_u8[jnp.clip(byte_start + (i >> 3), 0, nbytes - 1)]
    return ((b >> (i & 7).astype(jnp.uint8)) & jnp.uint8(1)).astype(bool)


@functools.partial(jax.jit, static_argnums=(2, 3))
def _bitcast_values(chunk_u8, byte_start, count: int, np_dtype_name: str):
    """PLAIN-encoded fixed-width values: gather + bitcast from raw bytes."""
    dt = np.dtype(np_dtype_name)
    w = dt.itemsize
    idx = byte_start + jnp.arange(count * w, dtype=jnp.int32)
    seg = chunk_u8[jnp.clip(idx, 0, chunk_u8.shape[0] - 1)]
    return jax.lax.bitcast_convert_type(seg.reshape(count, w), jnp.dtype(dt))


@functools.partial(jax.jit, static_argnums=(2,))
def _assemble(validity, dense_vals, cap: int):
    """Spread the dense present-values stream onto its row positions:
    output j takes dense value #(valid-prefix-count of j) when valid."""
    prefix = jnp.cumsum(validity.astype(jnp.int32)) - 1
    slot = jnp.clip(prefix, 0, dense_vals.shape[0] - 1)
    v = dense_vals[slot]
    zero = jnp.zeros((), dtype=v.dtype)
    return jnp.where(validity, v, zero)


# ---------------------------------------------------------------------------
# Column chunk decode driver
# ---------------------------------------------------------------------------
_PHYS_OK = {"INT32": DataType.INT32, "INT64": DataType.INT64,
            "FLOAT": DataType.FLOAT32, "DOUBLE": DataType.FLOAT64,
            "BOOLEAN": DataType.BOOL}


def column_eligible(col_meta, dtype: DataType) -> bool:
    """Can this column chunk decode on device? (codec, physical type,
    encodings; reference analog: GpuParquetScan tagging)."""
    if not codec_supported(col_meta.compression):
        return False
    ok_enc = {"PLAIN", "RLE", "PLAIN_DICTIONARY", "RLE_DICTIONARY",
              "DELTA_BINARY_PACKED", "DELTA_LENGTH_BYTE_ARRAY",
              "BYTE_STREAM_SPLIT"}
    if col_meta.physical_type == "BYTE_ARRAY":
        ok_enc = ok_enc | {"DELTA_BYTE_ARRAY"}
    if not set(col_meta.encodings) <= ok_enc:
        return False
    if col_meta.physical_type == "BYTE_ARRAY":
        # strings decode via dictionary gather, plain (start, len) walk,
        # device delta-length expansion, or the DELTA_BYTE_ARRAY
        # provider-scan reconstruction (oversized pages raise _Unsupported
        # at decode and fall back)
        if "DELTA_BINARY_PACKED" in col_meta.encodings or \
                "BYTE_STREAM_SPLIT" in col_meta.encodings:
            return False
        return dtype is DataType.STRING
    if col_meta.physical_type == "FIXED_LEN_BYTE_ARRAY":
        # FLBA decimals: big-endian unscaled fold (decode validates the
        # byte length); any other FLBA use falls back
        from spark_rapids_tpu.columnar.dtypes import is_decimal

        return is_decimal(dtype) and "BYTE_STREAM_SPLIT" not in \
            col_meta.encodings and "DELTA_BINARY_PACKED" not in \
            col_meta.encodings
    if col_meta.physical_type not in _PHYS_OK:
        return False
    from spark_rapids_tpu.columnar.dtypes import is_decimal

    if is_decimal(dtype) and col_meta.physical_type != "INT64":
        # int64-width device paths would misread 4-byte unscaled values;
        # INT64- and FLBA-physical decimals are the in-scope layouts
        return False
    if dtype is DataType.FLOAT64 and not device_float64_supported():
        return False
    return True


def _parse_plain_strings(chunk: bytes, pos: int, end: int, n: int):
    """Host control plane for a PLAIN byte-array data page: per-value
    (absolute start, length) tables — native single pass when built. No
    value bytes are touched; the device gathers them."""
    import ctypes

    from spark_rapids_tpu.native import get_lib

    starts = np.empty(max(n, 1), dtype=np.int32)
    lens = np.empty(max(n, 1), dtype=np.int32)
    lib = get_lib()
    if lib is not None:
        rc = lib.srt_plain_strings(
            chunk, pos, end, n,
            starts.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            lens.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)))
        if rc != n:
            raise _Unsupported("truncated PLAIN byte-array page")
        return starts[:n], lens[:n]
    for i in range(n):
        if pos + 4 > end:
            raise _Unsupported("truncated PLAIN byte-array page")
        ln = int.from_bytes(chunk[pos:pos + 4], "little")
        pos += 4
        if ln > end - pos:
            raise _Unsupported("malformed PLAIN byte-array value")
        starts[i] = pos
        lens[i] = ln
        pos += ln
    return starts[:n], lens[:n]


def _parse_dict_strings(chunk: bytes, start: int, n: int):
    """Host control plane for a BYTE_ARRAY dictionary page: entry
    (offset, length) table + one contiguous value-bytes buffer. Value bytes
    copy once; no value is decoded."""
    lens = np.empty(n, dtype=np.int32)
    srcs = np.empty(n, dtype=np.int64)
    pos = start
    limit = len(chunk)
    for i in range(n):
        if pos + 4 > limit:
            raise _Unsupported("truncated dictionary page")
        ln = int.from_bytes(chunk[pos:pos + 4], "little")
        if ln < 0 or pos + 4 + ln > limit:
            raise _Unsupported("malformed dictionary entry")
        srcs[i] = pos + 4
        lens[i] = ln
        pos += 4 + ln
    offs = np.zeros(n + 1, dtype=np.int32)
    np.cumsum(lens, out=offs[1:])
    total = int(offs[-1])
    dict_bytes = np.empty(max(total, 1), dtype=np.uint8)
    raw = np.frombuffer(chunk, dtype=np.uint8)
    for i in range(n):
        dict_bytes[offs[i]:offs[i + 1]] = raw[srcs[i]:srcs[i] + lens[i]]
    return dict_bytes, offs, lens


def _host_count_ones(chunk_np: np.ndarray, rt: RunTable, n: int) -> int:
    """Number of 1-bits among the first n values of a bit-width-1 hybrid
    stream, computed ON HOST from the run table + raw bytes. This is what
    lets the whole-chunk flat decode know every page's present-value count
    without the per-page device round trip that cost the device tier 12x
    vs host decode (BENCH_DECODE_r04.json: one ~66 ms sync per page)."""
    total = 0
    n_runs = len(rt.out_start)
    for i in range(n_runs):
        start = int(rt.out_start[i])
        end = int(rt.out_start[i + 1]) if i + 1 < n_runs else rt.total
        cnt = min(end, n) - start
        if cnt <= 0:
            continue
        if rt.is_rle[i]:
            total += (int(rt.value[i]) & 1) * cnt
        else:
            b0 = int(rt.bit_off[i]) >> 3  # byte-aligned for bit-packed runs
            nb = (cnt + 7) >> 3
            bits = np.unpackbits(chunk_np[b0:b0 + nb], bitorder="little")
            total += int(bits[:cnt].sum())
    return total


def _shifted_tab(rt: RunTable, row_shift: int, n: int):
    """Run table adjusted to a chunk-global output offset (numpy)."""
    return (rt.out_start.astype(np.int32) + np.int32(row_shift),
            rt.is_rle.astype(bool), rt.value.astype(np.int32),
            rt.bit_off.astype(np.int64))


def _synth_rle_tab(row_shift: int, value: int):
    return (np.asarray([row_shift], np.int32), np.asarray([True], bool),
            np.asarray([value], np.int32), np.asarray([0], np.int64))


def _pack_flat_tabs(tabs):
    """Concatenate shifted run tables and pad the run count to a pow2
    bucket (pads carry out_start = INT32_MAX so searchsorted never selects
    them) — run-count variation between chunks must not retrace."""
    out_start = np.concatenate([t[0] for t in tabs])
    is_rle = np.concatenate([t[1] for t in tabs])
    value = np.concatenate([t[2] for t in tabs])
    bit_off = np.concatenate([t[3] for t in tabs])
    n = len(out_start)
    padded = max(8, 1 << (n - 1).bit_length()) if n else 8
    if padded > n:
        pad = padded - n
        out_start = np.pad(out_start, (0, pad),
                           constant_values=np.iinfo(np.int32).max)
        is_rle = np.pad(is_rle, (0, pad), constant_values=True)
        value = np.pad(value, (0, pad))
        bit_off = np.pad(bit_off, (0, pad))
    return (out_start, is_rle, value, bit_off)


@functools.partial(jax.jit, static_argnums=(4, 5, 6, 7, 8))
def _flat_dict_kernel(src, def_tab, val_tab, dict_vals, bw: int,
                      cap: int, cap_p: int, def_form: str, val_form: str):
    """Whole-chunk dictionary decode in one program: validity expansion,
    index expansion, dictionary gather. Each stream expands in the form
    the host read off its run table (`_expand_stream`)."""
    validity = _expand_stream(src, def_tab, 1, cap, def_form).astype(bool)
    idx = _expand_stream(src, val_tab, bw, cap_p, val_form)
    dense = dict_vals[jnp.clip(idx, 0, dict_vals.shape[0] - 1)]
    return dense, validity


@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6, 7))
def _flat_dict_codes_kernel(src, def_tab, val_tab, bw: int,
                            cap: int, cap_p: int, def_form: str,
                            val_form: str):
    """_flat_dict_kernel WITHOUT the dictionary gather: the expanded
    index stream IS the encoded column's code array
    (columnar/encoded.py — fixed-value dictionary chunks)."""
    validity = _expand_stream(src, def_tab, 1, cap, def_form).astype(bool)
    idx = _expand_stream(src, val_tab, bw, cap_p, val_form)
    return idx.astype(jnp.int32), validity


def _rle_run_table(val_tabs, num_rows: int):
    """Host RunTable (columnar/runs.py) from a chunk's PURE-RLE value run
    tables, or None when any bit-packed group is present (its values are
    not host-known) or the stream is empty. Only meaningful for all-
    present chunks (no def levels): run output offsets are then row
    offsets."""
    from spark_rapids_tpu.columnar.runs import RunTable as _RT

    starts_parts = []
    values_parts = []
    for out_start, is_rle, value, _bit_off in val_tabs:
        if not bool(np.all(is_rle)):
            return None
        starts_parts.append(out_start.astype(np.int64))
        values_parts.append(value)
    if not starts_parts:
        return None
    starts = np.concatenate(starts_parts)
    values = np.concatenate(values_parts)
    keep = starts < num_rows
    starts, values = starts[keep], values[keep]
    if len(starts) == 0 or starts[0] != 0 or \
            bool(np.any(np.diff(starts) <= 0)):
        return None
    return _RT(starts, values, num_rows)


@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6))
def _flat_plain_kernel(chunk_u8, def_tab, page_meta, np_dtype_name: str,
                       cap: int, cap_p: int, def_form: str):
    """Whole-chunk PLAIN decode: per-lane page lookup (searchsorted over
    dense offsets), byte gather, bitcast. page_meta: int32/int64 [2, m] =
    (dense_end, byte_pos)."""
    validity = _expand_stream(chunk_u8, def_tab, 1, cap,
                              def_form).astype(bool)
    dt = np.dtype(np_dtype_name)
    w = dt.itemsize
    i = jnp.arange(cap_p, dtype=jnp.int32)
    dense_end = page_meta[0]
    page = jnp.searchsorted(dense_end, i, side="right").astype(jnp.int32)
    page = jnp.minimum(page, dense_end.shape[0] - 1)
    dense_start = jnp.concatenate([jnp.zeros((1,), dense_end.dtype),
                                   dense_end[:-1]])
    local = i - dense_start[page]
    base = page_meta[1][page] + local.astype(page_meta.dtype) * w
    idx = base[:, None] + jnp.arange(w, dtype=page_meta.dtype)[None, :]
    seg = chunk_u8[jnp.clip(idx, 0, chunk_u8.shape[0] - 1)]
    dense = jax.lax.bitcast_convert_type(seg.reshape(cap_p, w),
                                         jnp.dtype(dt))
    return dense, validity


@functools.partial(jax.jit, static_argnums=(3, 4))
def _flat_finish(dense, validity, nums, cap: int, dense_is_rows: bool):
    """Mask validity to the row count and spread dense values to rows.
    Where every row is present (`dense_is_rows`: the host counted the
    definition levels) the dense position IS the row position and
    nothing is spread."""
    validity = validity & (jnp.arange(cap) < nums[0])
    if dense_is_rows:
        dense = _pad_to(dense, cap, 0)
        data = jnp.where(validity, dense, jnp.zeros((), dense.dtype))
    else:
        data = _assemble(validity, dense, cap)
    return data, validity


_FIXED_ENC_DTYPES = (DataType.INT64, DataType.DATE, DataType.TIMESTAMP)


@dataclass
class _FlatPlan:
    """What the host reads off a fixed-width chunk's pages for the
    whole-chunk decode (`_plan_flat_fixed`): host arrays only, so the scan
    may make it ahead of the admission permit (io/scan.py). Every table is
    padded and packed as the programs take it; `_issue_flat_fixed` only
    uploads and dispatches."""

    cap_p: int                 # capacity bucket of the present values
    nums: np.ndarray           # int32 [num_rows, present]
    dense_is_rows: bool        # every row present: nothing to spread
    def_form: str              # _ONES | _RUNS
    def_tab: Optional[tuple]   # packed run table (numpy), _RUNS only
    dict_page: Optional[PageInfo] = None   # dictionary chunks
    bw: int = 1
    val_form: str = _RUNS      # _PACKED | _RUNS
    planes: Optional[np.ndarray] = None    # _PACKED: the payload's planes
    val_tab: Optional[tuple] = None        # _RUNS: packed run table
    runs: object = None        # host RunTable of an all-RLE value stream
    plain_meta: Optional[np.ndarray] = None  # PLAIN chunks: page table


_UNPLANNED = object()  # decode_chunk_device(flat=...): plan it here


def _try_flat_fixed(chunk: bytes, upload, pages, dtype: DataType,
                    num_rows: int, max_def: int, cap: int, npdt,
                    encoded_ok: bool = False,
                    max_dict_fraction: float = 1.0, plan=_UNPLANNED):
    """Whole-chunk fixed-width decode with ZERO per-page device work:
    host computes every page's present count (bit-popcount over def-level
    bytes), all pages' run tables concatenate into one flat table (output
    offsets made chunk-global; bit offsets are already chunk-absolute),
    and 2-3 jitted dispatches decode the entire chunk. Returns a
    ColumnVector, or None when the chunk's shape needs the general
    per-page path (mixed/exotic encodings, strings, bools, FLBA).

    Two steps: `_plan_flat_fixed` is the host's (pages walked, run tables
    built, the packed payload copied: no jax call) and may have been run
    ahead by the caller (`plan`, from `stage_chunk`); `_issue_flat_fixed`
    uploads what the plan holds and dispatches the programs.

    What the run tables state is not recomputed on the device: definition
    levels the host counted as all present are not expanded and nothing
    is spread to rows (`_ONES`, `dense_is_rows`), and a dictionary-index
    stream that is bit-packed throughout goes up as its packed payload
    alone and is unpacked with static shapes (`_PACKED`: the program's
    key is (bit width, capacity), not the chunk's bytes or runs). Any
    other stream takes `_expand_hybrid` on the uploaded chunk. `upload`
    gives the chunk on the device, sent on first use: the packed form
    never asks for it. The caller's `scan.decode` span reads `expand` =
    `packed` where no stream of the chunk needed the per-lane lookup,
    `runs` where one did, `plain` for a PLAIN chunk without one (its
    values are gathered a lane all the same).

    With `encoded_ok`, an INT64/DATE/TIMESTAMP dictionary chunk clearing
    the ndv/rows heuristic emits a DictionaryColumn instead: codes ARE
    the expanded index stream (no dictionary gather) and the host-parsed
    PLAIN dictionary page interns into one shared fixed-value
    DeviceDictionary (ROADMAP item 5: INT64 dictionary chunks). Either
    way, an all-present pure-RLE value stream additionally attaches a
    host RunTable for the run-granular aggregate path
    (columnar/runs.py).

    Reference bar: on-accelerator decode is the FAST path
    (GpuParquetScan.scala:536-556); round 4's per-page loop paid one
    sync + ~9 eager dispatches per page
    (tools/decode_census.py: 648 syncs + 6015 eager ops per iteration)."""
    if plan is _UNPLANNED:
        plan = _plan_flat_fixed(chunk, pages, dtype, num_rows, max_def,
                                npdt)
    if plan is None:
        return None
    return _issue_flat_fixed(plan, chunk, upload, dtype, num_rows, cap, npdt,
                             encoded_ok, max_dict_fraction)


def _plan_flat_fixed(chunk: bytes, pages, dtype: DataType, num_rows: int,
                     max_def: int, npdt) -> Optional[_FlatPlan]:
    """HOST step of `_try_flat_fixed`; None where the chunk needs the
    per-page path."""
    from spark_rapids_tpu.columnar.dtypes import is_decimal

    if dtype in (DataType.STRING, DataType.BOOL):
        return None
    if is_decimal(dtype) and np.dtype(npdt) not in (np.dtype(np.int32),
                                                    np.dtype(np.int64)):
        return None
    data_pages = [p for p in pages if p.kind in (PAGE_DATA_V1,
                                                 PAGE_DATA_V2)]
    dict_pages = [p for p in pages if p.kind == PAGE_DICT]
    if not data_pages or len(dict_pages) > 1:
        return None
    if any(p.rep_len for p in data_pages):
        return None
    encs = {p.encoding for p in data_pages}
    dict_mode = bool(dict_pages) and encs <= {ENC_PLAIN_DICT, ENC_RLE_DICT}
    plain_mode = not dict_pages and encs == {ENC_PLAIN}
    if not (dict_mode or plain_mode):
        return None
    chunk_np = np.frombuffer(chunk, dtype=np.uint8)
    def_tabs = []
    val_tabs = []
    val_pages = []  # (RunTable, present) a page, None for a bw == 0 page
    plain_dense_end = []
    plain_pos = []
    rows = 0
    present = 0
    bw = None
    for p in data_pages:
        pos = p.data_start
        end = p.data_start + p.data_len
        if p.kind == PAGE_DATA_V2:
            if max_def > 0 and p.def_len > 0:
                rt = parse_runs(chunk, pos, pos + p.def_len, 1,
                                p.num_values)
                n_present = _host_count_ones(chunk_np, rt, p.num_values)
                def_tabs.append(_shifted_tab(rt, rows, p.num_values))
            else:
                n_present = p.num_values
                def_tabs.append(_synth_rle_tab(rows, 1))
            pos += p.def_len
        elif max_def > 0:
            dl_len = int.from_bytes(chunk[pos:pos + 4], "little")
            rt = parse_runs(chunk, pos + 4, pos + 4 + dl_len, 1,
                            p.num_values)
            n_present = _host_count_ones(chunk_np, rt, p.num_values)
            def_tabs.append(_shifted_tab(rt, rows, p.num_values))
            pos += 4 + dl_len
        else:
            n_present = p.num_values
            def_tabs.append(_synth_rle_tab(rows, 1))
        if dict_mode:
            pbw = chunk[pos]
            pos += 1
            if pbw > 24:
                return None
            if pbw == 0:
                val_tabs.append(_synth_rle_tab(present, 0))
                val_pages.append(None)
            else:
                if bw is None:
                    bw = pbw
                elif bw != pbw:
                    return None
                rt = parse_runs(chunk, pos, end, pbw, n_present)
                val_tabs.append(_shifted_tab(rt, present, n_present))
                val_pages.append((rt, n_present))
        else:
            plain_dense_end.append(present + n_present)
            plain_pos.append(pos)
        rows += p.num_values
        present += n_present
    # every row present (a required column, or a nullable one whose def
    # levels the host counted as all 1): no validity to expand, and the
    # dense position is the row position
    dense_is_rows = present == rows
    def_form = _ONES if dense_is_rows else _RUNS
    plan = _FlatPlan(
        cap_p=bucket_capacity(max(present, 1)),
        nums=np.asarray([num_rows, present], np.int32),
        dense_is_rows=dense_is_rows, def_form=def_form,
        def_tab=_pack_flat_tabs(def_tabs) if def_form == _RUNS else None)
    if not dict_mode:
        meta = np.zeros((2, len(plain_pos)), np.int64)
        meta[0] = plain_dense_end
        meta[1] = plain_pos
        if int(meta.max()) * np.dtype(npdt).itemsize < (1 << 31):
            meta = meta.astype(np.int32)
        plan.plain_meta = meta
        return plan
    dp = plan.dict_page = dict_pages[0]
    plan.bw = int(bw or 1)
    # the packed form reads nothing of the uploaded chunk (the
    # dictionary page is read on the host too, so it has to lie
    # inside the chunk, where the device's clipped read would not
    # mind), so it is taken where the def levels need no chunk
    # either: none goes up twice
    dict_whole = dp.data_start + dp.num_values * \
        np.dtype(npdt).itemsize <= len(chunk)
    if def_form == _ONES and dict_whole:
        plan.planes = _pack_value_stream(chunk, val_pages, plan.bw,
                                         plan.cap_p)
    if plan.planes is not None:
        plan.val_form = _PACKED
    else:
        plan.val_tab = _pack_flat_tabs(val_tabs)
    # host run table: only when the whole chunk is present (run
    # output offsets == row offsets — a nullable schema still
    # qualifies as long as no NULL actually occurs) and every value
    # run is RLE
    if dense_is_rows:
        plan.runs = _rle_run_table(val_tabs, num_rows)
    return plan


def _issue_flat_fixed(plan: _FlatPlan, chunk: bytes, upload,
                      dtype: DataType, num_rows: int, cap: int, npdt,
                      encoded_ok: bool, max_dict_fraction: float):
    """DEVICE step of `_try_flat_fixed`: the plan's tables and payload
    uploaded, the chunk's programs dispatched."""
    from spark_rapids_tpu.columnar.batch import ColumnVector

    cap_p, nums, dense_is_rows = plan.cap_p, plan.nums, plan.dense_is_rows
    def_form, val_form, bw = plan.def_form, plan.val_form, plan.bw
    def_tab = tuple(jnp.asarray(a) for a in plan.def_tab) \
        if def_form == _RUNS else _EMPTY_TAB()
    if plan.dict_page is not None:
        dp = plan.dict_page
        if val_form == _PACKED:
            val_tab = _EMPTY_TAB()
            with OBS.span("scan.upload", bytes=plan.planes.nbytes):
                src = jnp.asarray(plan.planes)
        else:
            val_tab = tuple(jnp.asarray(a) for a in plan.val_tab)
            src = upload()
        OBS.annotate(expand=val_form)
        runs = plan.runs
        if encoded_ok and dtype in _FIXED_ENC_DTYPES:
            from spark_rapids_tpu.columnar.encoded import (
                DeviceDictionary,
                DictionaryColumn,
                scan_encoded_ok,
            )

            if scan_encoded_ok(dp.num_values, num_rows,
                               max_dict_fraction):
                host_vals = np.frombuffer(
                    chunk, dtype=np.dtype(npdt), count=dp.num_values,
                    offset=dp.data_start).astype(dtype.to_np())
                d = DeviceDictionary.from_fixed_values(host_vals, dtype)
                codes, validity = _flat_dict_codes_kernel(
                    src, def_tab, val_tab, bw, cap, cap_p, def_form,
                    val_form)
                codes, validity = _flat_finish(codes, validity, nums, cap,
                                               dense_is_rows)
                out = DictionaryColumn(dtype, codes, validity, d)
                out.runs = runs  # run values ARE codes for encoded cols
                return out
        if val_form == _PACKED:
            # the dictionary from the host's bytes too, padded to a
            # bucket so that its length keys no program
            host_dict = np.frombuffer(
                chunk, dtype=np.dtype(npdt), count=dp.num_values,
                offset=dp.data_start)
            dict_vals = jnp.asarray(np.pad(
                host_dict,
                (0, bucket_capacity(dp.num_values) - dp.num_values)))
        else:
            dict_vals = _bitcast_values(src, np.int32(dp.data_start),
                                        dp.num_values, np.dtype(npdt).name)
        dense, validity = _flat_dict_kernel(
            src, def_tab, val_tab, dict_vals, bw, cap, cap_p, def_form,
            val_form)
        runs_out = None
        if runs is not None and dp.num_values:
            # decoded emission still benefits from runs: values via one
            # host take through the dictionary page's raw values
            from spark_rapids_tpu.columnar.runs import RunTable as _RT

            host_vals = np.frombuffer(
                chunk, dtype=np.dtype(npdt), count=dp.num_values,
                offset=dp.data_start)
            sel = np.clip(runs.values, 0, dp.num_values - 1)
            runs_out = _RT(runs.starts,
                           host_vals[sel].astype(dtype.to_np()), num_rows)
        data, validity = _flat_finish(dense, validity, nums, cap,
                                      dense_is_rows)
        out = ColumnVector(dtype, data, validity)
        out.runs = runs_out
        return out
    # PLAIN values are still gathered a lane (page lookup, byte
    # gather): never `packed`, which is the gather-free form's name
    OBS.annotate(expand="plain" if def_form == _ONES else _RUNS)
    dense, validity = _flat_plain_kernel(
        upload(), def_tab, plan.plain_meta, np.dtype(npdt).name, cap, cap_p,
        def_form)
    data, validity = _flat_finish(dense, validity, nums, cap, dense_is_rows)
    return ColumnVector(dtype, data, validity)


_EMPTY_TAB_CACHE = None


def _EMPTY_TAB():
    # cached: rebuilding would pay 4 host->device uploads per chunk of
    # every required column (device_const-style interning, local form)
    global _EMPTY_TAB_CACHE
    if _EMPTY_TAB_CACHE is None:
        _EMPTY_TAB_CACHE = (
            jnp.asarray(np.full((1,), np.iinfo(np.int32).max, np.int32)),
            jnp.asarray(np.ones((1,), bool)),
            jnp.asarray(np.zeros((1,), np.int32)),
            jnp.asarray(np.zeros((1,), np.int64)))
    return _EMPTY_TAB_CACHE


def stage_chunk(chunk: bytes, codec: str, dtype: Optional[DataType] = None,
                num_rows: int = 0, max_def: int = 0, flba_len: int = 0):
    """Host half of `decode_chunk_device`: a raw column chunk's pages
    decompressed and their headers walked, and, where the caller says
    what the column is (`dtype`, `num_rows`, `max_def`, `flba_len`), the
    whole-chunk decode planned (`_plan_flat_fixed`: run tables, present
    counts, the packed payload). Returns (normalised chunk bytes, pages
    with offsets into them, the plan: None where the chunk needs the
    per-page loop, `_UNPLANNED` without a `dtype`). Pure host work on
    host data: the scan runs it ahead of the admission permit
    (io/scan.py: `_stage_split`) and hands the three to
    `decode_chunk_device(pages=..., flat=...)`. Raises _Unsupported for a
    codec or page type outside scope."""
    from spark_rapids_tpu.columnar.dtypes import is_decimal

    with OBS.span("scan.parse") as sp:
        if codec != "UNCOMPRESSED":
            chunk, pages = normalize_chunk(chunk, codec)
        else:
            pages = parse_pages(chunk)
        if sp is not None:
            sp.attrs["bytes_out"] = len(chunk)
        if dtype is None:
            flat = _UNPLANNED
        elif is_decimal(dtype) and flba_len > 0:
            flat = None  # FLBA decimals fold on the per-page loop
        else:
            flat = _plan_flat_fixed(chunk, pages, dtype, num_rows, max_def,
                                    physical_np_dtype(dtype))
    return chunk, pages, flat


def decode_chunk_device(chunk: bytes, dtype: DataType, num_rows: int,
                        max_def: int, cap: Optional[int] = None,
                        codec: str = "UNCOMPRESSED", flba_len: int = 0,
                        encoded_ok: bool = False,
                        max_dict_fraction: float = 1.0,
                        pages: Optional[List[PageInfo]] = None,
                        flat=_UNPLANNED):
    """Decode one raw column chunk into a device ColumnVector.

    Fixed-width columns: PLAIN / dictionary pages, v1 or v2. STRING
    columns: dictionary pages (host parses the (offset, length) dictionary
    table, values gather through it) or PLAIN byte-array pages (host walks
    per-value (start, len) tables — native single pass — and the device
    gathers the bytes); a chunk mixing both falls back. Either way the
    output column is one jitted gather through build_from_plan (reference
    decodes strings on the accelerator via cudf the same way,
    GpuParquetScan.scala:536-556).
    Compressed chunks (snappy/gzip/zstd/brotli) decompress page-by-page on
    the host first (normalize_chunk); the device data plane is identical.

    max_def: 1 for nullable columns (def levels present), 0 for required.
    `pages`, `flat`: `stage_chunk`'s, where the caller ran it ahead;
    `chunk` is then the normalised bytes they index, and `codec` only
    names what the file held. Raises _Unsupported for shapes outside
    scope (caller falls back to the Arrow host path)."""
    from spark_rapids_tpu.columnar.batch import ColumnVector

    if pages is None:
        chunk, pages, flat = stage_chunk(chunk, codec)
    OBS.annotate(pages=len(pages))  # on the caller's scan.decode
    from spark_rapids_tpu.columnar.dtypes import is_decimal

    cap = cap or bucket_capacity(max(num_rows, 1))
    is_string = dtype is DataType.STRING
    # flba_len == 0 with a decimal dtype means the column is physical
    # INT64 (column_eligible rejects other widths): the generic
    # fixed-width paths below read it correctly since npdt is int64
    is_dec_flba = is_decimal(dtype) and flba_len > 0
    if is_dec_flba and not 1 <= flba_len <= 16:
        raise _Unsupported(f"FLBA decimal byte length {flba_len}")
    npdt = np.dtype(np.int32) if is_string else physical_np_dtype(dtype)

    @functools.cache
    def upload():
        """The chunk on the device, sent once, on first use."""
        with OBS.span("scan.upload", bytes=len(chunk)):
            return jnp.asarray(np.frombuffer(chunk, dtype=np.uint8))

    if not is_string and not is_dec_flba:
        flat = _try_flat_fixed(chunk, upload, pages, dtype, num_rows,
                               max_def, cap, npdt,
                               encoded_ok=encoded_ok,
                               max_dict_fraction=max_dict_fraction,
                               plan=flat)
        if flat is not None:
            return flat
    chunk_dev = upload()

    dict_vals = None          # fixed-width dictionary values (device)
    str_dict = None           # (bytes_dev, offs_dev, lens_dev) for strings
    str_dict_host = None      # host (bytes_np, offs_np) dictionary table
    str_run_tabs = []         # per-page value run tables (no-null chunks)
    row_base = 0              # rows decoded so far (run-table shifting)
    str_plain = []            # per-page (starts_np, lens_np) for strings
    str_delta = []            # per-page DEVICE (starts, lens, n) for
                              # DELTA_LENGTH_BYTE_ARRAY strings
    str_delta_bytes = 0       # host-known total value bytes across pages
    str_dba = []              # per-page (bytes_dev, starts, lens, n, total)
    dense_parts = []
    valid_parts = []
    for p in pages:
        if p.kind == PAGE_DICT:
            if is_string:
                db, do, dl = _parse_dict_strings(chunk, p.data_start,
                                                 p.num_values)
                str_dict_host = (db, do)
                str_dict = (jnp.asarray(db), jnp.asarray(do),
                            jnp.asarray(dl))
            elif is_dec_flba:
                dict_vals = _fold_flba_be(chunk_dev,
                                          jnp.int32(p.data_start),
                                          p.num_values, flba_len)
            else:
                dict_vals = _bitcast_values(
                    chunk_dev, jnp.int32(p.data_start), p.num_values,
                    npdt.name)
            continue
        is_bool = dtype is DataType.BOOL
        ok_encs = (ENC_PLAIN, ENC_PLAIN_DICT, ENC_RLE_DICT) + \
            ((ENC_RLE,) if is_bool else ()) + \
            (() if (is_bool or is_string)
             else (ENC_DELTA_BINARY, ENC_BYTE_STREAM_SPLIT)) + \
            ((ENC_DELTA_LENGTH, ENC_DELTA_BYTE_ARRAY)
             if is_string else ())
        if p.encoding not in ok_encs:
            raise _Unsupported(f"data page encoding {p.encoding}")
        pos = p.data_start
        end = p.data_start + p.data_len
        page_cap = bucket_capacity(max(p.num_values, 1))
        if p.kind == PAGE_DATA_V2:
            # v2: rep/def level bytes sit unprefixed (and uncompressed)
            # ahead of the data section, lengths from the page header
            if p.rep_len:
                raise _Unsupported("repetition levels (nested) in v2 page")
            if max_def > 0 and p.def_len > 0:
                rt = parse_runs(chunk, pos, pos + p.def_len, 1,
                                p.num_values)
                page_valid = _expand_hybrid(
                    chunk_dev, jnp.asarray(rt.out_start),
                    jnp.asarray(rt.is_rle), jnp.asarray(rt.value),
                    jnp.asarray(rt.bit_off), 1, page_cap).astype(bool)
            else:
                page_valid = jnp.ones((page_cap,), dtype=bool)
            pos += p.def_len
        elif max_def > 0:
            # v1 def levels: u32 length prefix + RLE hybrid, bit width 1
            dl_len = int.from_bytes(chunk[pos:pos + 4], "little")
            rt = parse_runs(chunk, pos + 4, pos + 4 + dl_len, 1,
                            p.num_values)
            page_valid = _expand_hybrid(
                chunk_dev, jnp.asarray(rt.out_start), jnp.asarray(rt.is_rle),
                jnp.asarray(rt.value), jnp.asarray(rt.bit_off), 1,
                page_cap).astype(bool)
            pos += 4 + dl_len
        else:
            page_valid = jnp.ones((page_cap,), dtype=bool)
        page_valid = page_valid & (jnp.arange(page_cap) < p.num_values)
        n_present = int(jax.device_get(jnp.sum(page_valid)))
        if p.encoding in (ENC_PLAIN_DICT, ENC_RLE_DICT):
            if dict_vals is None and str_dict is None:
                raise _Unsupported("dictionary-encoded page before dict")
            bit_width = chunk[pos]
            if bit_width > 24:
                raise _Unsupported(f"dict index bit width {bit_width}")
            pos += 1
            all_present = n_present == p.num_values
            if bit_width == 0:
                idx = jnp.zeros((page_cap,), dtype=jnp.int32)
                if all_present:
                    str_run_tabs.append(_synth_rle_tab(row_base, 0))
            else:
                rt = parse_runs(chunk, pos, end, bit_width, n_present)
                idx = _expand_hybrid(
                    chunk_dev, jnp.asarray(rt.out_start),
                    jnp.asarray(rt.is_rle), jnp.asarray(rt.value),
                    jnp.asarray(rt.bit_off), bit_width, page_cap)
                if all_present:
                    str_run_tabs.append(
                        _shifted_tab(rt, row_base, n_present))
            if is_string:
                page_dense = idx  # gather through the dict AFTER assembly
            else:
                page_dense = dict_vals[jnp.clip(idx, 0,
                                                dict_vals.shape[0] - 1)]
        elif is_bool and p.encoding == ENC_RLE:
            # v2 boolean values: length-prefixed RLE hybrid, bit width 1
            rl_len = int.from_bytes(chunk[pos:pos + 4], "little")
            if pos + 4 + rl_len > end:
                # corrupt/truncated length prefix: decoding would walk into
                # the next page's bytes — fall back rather than misread
                raise _Unsupported(
                    f"boolean RLE length {rl_len} exceeds page data section")
            brt = parse_runs(chunk, pos + 4, pos + 4 + rl_len, 1,
                             n_present)
            page_dense = _expand_hybrid(
                chunk_dev, jnp.asarray(brt.out_start),
                jnp.asarray(brt.is_rle), jnp.asarray(brt.value),
                jnp.asarray(brt.bit_off), 1, page_cap).astype(bool)
        elif is_bool:  # PLAIN booleans: LSB-first bit-packed
            page_dense = _extract_bits_lsb(chunk_dev, jnp.int32(pos),
                                           page_cap)
        elif p.encoding == ENC_DELTA_BINARY:
            if not np.issubdtype(npdt, np.integer):
                raise _Unsupported("DELTA_BINARY_PACKED on non-integral")
            first_value, vpm, mb_off, mb_w, mb_md, _base = \
                _parse_delta_header(chunk, pos, end, n_present)
            prefix = _expand_delta(chunk_dev, jnp.asarray(mb_off),
                                   jnp.asarray(mb_w), jnp.asarray(mb_md),
                                   vpm, page_cap)
            # int64 arithmetic wraps mod 2^64; the final astype wraps a
            # 32-bit column the way the encoding's modular deltas require
            page_dense = (jnp.int64(first_value) + prefix).astype(npdt)
        elif p.encoding == ENC_DELTA_LENGTH and is_string:
            # DELTA_LENGTH_BYTE_ARRAY: delta-packed lengths, then the
            # value bytes concatenated — lengths expand through the SAME
            # delta cumsum kernel and exclusive-summed into byte starts,
            # all on device; total byte size is host-known from the page
            # layout (no sync)
            first_value, vpm, mb_off, mb_w, mb_md, data_base = \
                _parse_delta_header(chunk, pos, end, n_present)
            prefix = _expand_delta(chunk_dev, jnp.asarray(mb_off),
                                   jnp.asarray(mb_w), jnp.asarray(mb_md),
                                   vpm, page_cap)
            in_page = jnp.arange(page_cap) < n_present
            lens_dev = jnp.where(in_page, jnp.int64(first_value) + prefix, 0)
            cl = jnp.cumsum(lens_dev)
            starts_dev = jnp.int64(data_base) + cl - lens_dev
            str_delta.append((starts_dev.astype(jnp.int32),
                              lens_dev.astype(jnp.int32), n_present))
            str_delta_bytes += max(0, end - data_base)
            page_dense = None
        elif p.encoding == ENC_DELTA_BYTE_ARRAY and is_string:
            # two delta streams (prefix lengths, suffix lengths) then the
            # concatenated suffix bytes
            fv1, vpm1, o1, w1, m1, base1 = \
                _parse_delta_header(chunk, pos, end, n_present)
            pp = _expand_delta(chunk_dev, jnp.asarray(o1), jnp.asarray(w1),
                               jnp.asarray(m1), vpm1, page_cap)
            in_page = jnp.arange(page_cap) < n_present
            plen_dev = jnp.where(in_page, jnp.int64(fv1) + pp,
                                 0).astype(jnp.int32)
            fv2, vpm2, o2, w2, m2, base2 = \
                _parse_delta_header(chunk, base1, end, n_present)
            sp = _expand_delta(chunk_dev, jnp.asarray(o2), jnp.asarray(w2),
                               jnp.asarray(m2), vpm2, page_cap)
            slen_dev = jnp.where(in_page, jnp.int64(fv2) + sp,
                                 0).astype(jnp.int32)
            # one host sync sizes the provider matrix + byte buffer
            maxlen, total = (int(x) for x in jax.device_get(
                (jnp.max(plen_dev + slen_dev), jnp.sum(plen_dev + slen_dev))))
            mlen_cap = bucket_capacity(max(maxlen, 1))
            if page_cap * mlen_cap > _DBA_MATRIX_BUDGET:
                raise _Unsupported(
                    "DELTA_BYTE_ARRAY provider matrix over budget")
            rec, out_off = _expand_dba(chunk_dev, plen_dev, slen_dev,
                                       jnp.int32(base2), mlen_cap,
                                       bucket_capacity(max(total, 8)))
            str_dba.append((rec, out_off[:-1], plen_dev + slen_dev,
                            n_present, total))
            page_dense = None
        elif p.encoding == ENC_BYTE_STREAM_SPLIT:
            # npdt.itemsize == the file's physical width here: eligibility
            # rejects FLOAT64 columns unless the device stores real f64
            # (same assumption the PLAIN bitcast path makes)
            page_dense = _decode_bss(chunk_dev, jnp.int32(pos),
                                     jnp.int32(n_present), page_cap,
                                     npdt.name)
        elif is_string:  # PLAIN byte-array: host (start, len) walk
            ps, pl = _parse_plain_strings(chunk, pos, end, n_present)
            str_plain.append((ps, pl))
            page_dense = None  # plain-string chunks skip dense assembly
        elif is_dec_flba:  # PLAIN FLBA decimal: big-endian fold
            page_dense = _fold_flba_be(chunk_dev, jnp.int32(pos),
                                       page_cap, flba_len)
        else:  # PLAIN fixed-width
            page_dense = _bitcast_values(chunk_dev, jnp.int32(pos),
                                         page_cap, npdt.name)
            # only the first n_present values are real; tail reads past the
            # page but is masked by validity at assemble time
        if page_dense is not None:
            dense_parts.append((page_dense, n_present))
        valid_parts.append((page_valid, p.num_values))
        row_base += p.num_values

    # stitch pages (single-page chunks — the common case with row-group
    # splits — take the fast path)
    if len(valid_parts) == 1:
        validity = _pad_to(valid_parts[0][0], cap, False)
    else:
        validity = _concat_logical(
            [(v, n) for v, n in valid_parts], cap, False)
    if not str_plain and not str_delta and not str_dba:
        # plain/delta-length string chunks skip the dense assembly — their
        # values come from the (start, len) tables below
        if len(dense_parts) == 1:
            dense = _pad_to(dense_parts[0][0], cap, 0)
        else:
            dense = _concat_logical(
                [(d, n) for d, n in dense_parts], cap, 0)
        data = _assemble(validity, dense, cap)
    if not is_string:
        return ColumnVector(dtype, data, validity)
    from spark_rapids_tpu.columnar.strings import build_from_plan

    if str_dba:
        if str_dict is not None or str_plain or str_delta:
            raise _Unsupported("mixed DELTA_BYTE_ARRAY/other string pages")
        # values live in per-page reconstructed buffers; build_from_plan's
        # multi-source gather stitches them (source = page index)
        starts_dev = _concat_logical(
            [(s, n) for _b, s, _l, n, _t in str_dba], cap, 0)
        lens_dev = _concat_logical(
            [(l, n) for _b, _s, l, n, _t in str_dba], cap, 0)
        page_ids = _concat_logical(
            [(jnp.full((n,), pi, jnp.int32), n)
             for pi, (_b, _s, _l, n, _t) in enumerate(str_dba)], cap, 0)
        row_starts = _assemble(validity, starts_dev, cap)
        row_lens = _assemble(validity, lens_dev, cap)
        row_choice = _assemble(validity, page_ids, cap)
        byte_cap = bucket_capacity(
            max(sum(t for *_x, t in str_dba), 8))
        out_bytes, offsets = build_from_plan(
            [b for b, *_x in str_dba], row_choice, row_starts,
            jnp.where(validity, row_lens, 0), byte_cap)
        return ColumnVector(dtype, out_bytes, validity, offsets)
    if str_delta:
        if str_dict is not None or str_plain:
            raise _Unsupported("mixed delta-length/other string pages")
        # per-page DEVICE (start, len) tables from the delta expansion;
        # total byte size came from the page layout — no sync
        starts_dev = _concat_logical([(s, n) for s, _l, n in str_delta],
                                     cap, 0)
        lens_dev = _concat_logical([(l, n) for _s, l, n in str_delta],
                                   cap, 0)
        row_starts = _assemble(validity, starts_dev, cap)
        row_lens = _assemble(validity, lens_dev, cap)
        byte_cap = bucket_capacity(max(str_delta_bytes, 8))
        out_bytes, offsets = build_from_plan(
            [chunk_dev], jnp.zeros((cap,), jnp.int32),
            row_starts, jnp.where(validity, row_lens, 0), byte_cap)
        return ColumnVector(dtype, out_bytes, validity, offsets)
    if str_plain and str_dict is None:
        # PLAIN byte-array pages: per-present (start, len) from the host
        # walk; the device gathers the value bytes in one pass. Total byte
        # size is host-known — no device sync.
        starts_np = np.concatenate([s for s, _l in str_plain])
        lens_np = np.concatenate([l for _s, l in str_plain])
        total = int(lens_np.sum())
        pad = max(0, cap - starts_np.shape[0])
        dstarts = jnp.asarray(np.pad(starts_np, (0, pad))[:cap])
        dlens = jnp.asarray(np.pad(lens_np, (0, pad))[:cap])
        row_starts = _assemble(validity, dstarts, cap)
        row_lens = _assemble(validity, dlens, cap)
        byte_cap = bucket_capacity(max(total, 8))
        out_bytes, offsets = build_from_plan(
            [chunk_dev], jnp.zeros((cap,), jnp.int32),
            row_starts, row_lens, byte_cap)
        return ColumnVector(dtype, out_bytes, validity, offsets)
    if str_dict is None:
        raise _Unsupported("string chunk without a dictionary page")
    if str_plain:
        raise _Unsupported("mixed dictionary/plain string pages")
    dict_bytes, dict_offs, dict_lens = str_dict
    if encoded_ok and str_dict_host is not None:
        # keep the column ENCODED: the codes ARE the decoded index stream
        # (`data`), and the host-parsed dictionary table interns into one
        # shared DeviceDictionary — no dictionary gather, no byte-total
        # sync, and several-x less HBM (columnar/encoded.py; conf
        # rapids.tpu.sql.encoded.*)
        from spark_rapids_tpu.columnar.encoded import (
            DeviceDictionary,
            DictionaryColumn,
            scan_encoded_ok,
        )

        db, do = str_dict_host
        if scan_encoded_ok(int(len(do)) - 1, num_rows, max_dict_fraction):
            d = DeviceDictionary.from_byte_table(db, do)
            out = DictionaryColumn(dtype, data.astype(jnp.int32),
                                   validity, d)
            if len(str_run_tabs) == len(
                    [p for p in pages if p.kind != PAGE_DICT]):
                # all-present pure-RLE index stream: attach the host run
                # table for run-granular compute (values are CODES)
                out.runs = _rle_run_table(str_run_tabs, num_rows)
            return out
    row_idx = jnp.clip(data, 0, dict_lens.shape[0] - 1)
    row_lens = jnp.where(validity, dict_lens[row_idx], 0)
    total = int(jax.device_get(jnp.sum(row_lens)))
    byte_cap = bucket_capacity(max(total, 8))
    out_bytes, offsets = build_from_plan(
        [dict_bytes], jnp.zeros((cap,), jnp.int32),
        dict_offs[row_idx], row_lens, byte_cap)
    return ColumnVector(dtype, out_bytes, validity, offsets)


def _pad_to(arr, cap: int, fill):
    if arr.shape[0] == cap:
        return arr
    if arr.shape[0] > cap:
        return arr[:cap]
    pad = jnp.full((cap - arr.shape[0],), fill, dtype=arr.dtype)
    return jnp.concatenate([arr, pad])


def _concat_logical(parts, cap: int, fill):
    """Concatenate the first n logical elements of each part."""
    segs = [p[:n] for p, n in parts]
    out = jnp.concatenate(segs)
    return _pad_to(out, cap, fill)


def chunk_dict_ndv(path: str, col_meta) -> Optional[int]:
    """num_values of a chunk's dictionary page from a header-only read
    (a few hundred bytes at the dictionary page offset), or None when
    the chunk has no dictionary page / the header is unreadable. The
    plan-time half of the encoded-scan heuristic: the resource analyzer
    must apply the SAME ndv/rows test the runtime decode applies, or its
    encoded-column byte model would diverge from what executes."""
    start = getattr(col_meta, "dictionary_page_offset", None)
    if start is None or start <= 0:
        return None
    try:
        with open(path, "rb") as f:
            f.seek(start)
            head = f.read(512)
        r = _Compact(head, 0)
        hdr = r.struct()
        if hdr.get(_PH_TYPE) != PAGE_DICT:
            return None
        return int(hdr[_PH_DICT][_DI_NUM_VALUES])
    except Exception:
        return None


def chunk_dict_only(path: str, col_meta) -> Optional[bool]:
    """True when EVERY data page of the chunk is dictionary-encoded,
    proven by walking the page HEADERS only (one small read per page;
    payloads are skipped by their header-declared size). False when a
    PLAIN fallback page exists — the footer's `encodings` list cannot
    distinguish the two (a pure-dict chunk and a mid-chunk dictionary
    fallback both report {PLAIN, RLE, RLE_DICTIONARY}), and the resource
    analyzer must not reduce its peak-HBM ceiling on an unprovable
    claim. None when the headers are unreadable (treated as unproven)."""
    start = getattr(col_meta, "dictionary_page_offset", None)
    if start is None or start <= 0:
        return None
    try:
        end = start + col_meta.total_compressed_size
        with open(path, "rb") as f:
            pos = start
            while pos < end:
                f.seek(pos)
                head = f.read(min(8192, end - pos))
                if not head:
                    break
                r = _Compact(head, 0)
                hdr = r.struct()
                size = hdr[_PH_COMPRESSED]
                kind = hdr[_PH_TYPE]
                if kind == PAGE_DATA_V1:
                    if hdr[_PH_DATA_V1][_DP_ENCODING] not in \
                            (ENC_PLAIN_DICT, ENC_RLE_DICT):
                        return False
                elif kind == PAGE_DATA_V2:
                    if hdr[_PH_DATA_V2][_D2_ENCODING] not in \
                            (ENC_PLAIN_DICT, ENC_RLE_DICT):
                        return False
                elif kind != PAGE_DICT:
                    return False
                pos += r.pos + size
    except Exception:
        return None
    return True


def read_chunk_bytes(path: str, col_meta) -> bytes:
    start = col_meta.dictionary_page_offset
    if start is None or start <= 0:
        start = col_meta.data_page_offset
    with open(path, "rb") as f:
        f.seek(start)
        return f.read(col_meta.total_compressed_size)
