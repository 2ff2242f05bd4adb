"""Device-side parquet decode of BYTE_ARRAY string columns.

Reference parity: the reference decodes parquet ON the accelerator —
it reassembles a minimal in-memory file from raw column chunks on the host
and hands the bytes to the GPU decoder (`GpuParquetScan.scala:316-458`
host reassembly, `:536-556` device `Table.readParquet`). The TPU-native
split keeps the same shape for the one input whose device form Arrow's
read does not hand over, a string column as dictionary codes + dictionary
(columnar/encoded.py):

- HOST (control plane, tiny): parse thrift-compact page headers and the
  RLE/bit-packed *run tables* (a few dozen entries per page — runs, not
  values), and locate the dictionary. No value is decoded on the host.
- DEVICE (data plane): jitted programs per shape bucket expand
  definition-level runs into the validity mask, expand dictionary-index
  runs (RLE repeats + bit-packed groups extracted straight from the raw
  chunk bytes), and gather the dictionary, or keep the indices as the
  encoded column's codes.

Scope: flat STRING columns (`column_eligible`); v1 AND v2 data pages
encoded PLAIN, RLE_DICTIONARY/PLAIN_DICTIONARY, DELTA_LENGTH_BYTE_ARRAY
(lengths ride one device cumsum over miniblock-unpacked deltas, byte
starts are a device exclusive-sum) or DELTA_BYTE_ARRAY (prefix-sharing
resolves through a provider running-max scan, then one gather per output
byte; pages whose values x max-length matrix exceeds the budget fall
back). UNCOMPRESSED, SNAPPY, GZIP, ZSTD and BROTLI codecs. Compressed
pages decompress on the HOST (block decompression is control-plane:
inherently serial bit-stream work) and the decompressed chunk feeds the
identical device expansion.

Every fixed-width column (ints, floats, bools, dates, timestamps,
decimals) is decoded by Arrow in the split's one threaded read and
uploaded (io/scan.py `_read_host`): a device decode of those columns ran
1.8x (Q6) and 1.4x (a parquet write) behind that read on the chip, twice
over, and went in PR 30 (PERF.md section 6). Arrow remains the oracle
and the fallback for everything else.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import List, Optional

import numpy as np

from spark_rapids_tpu import _jax_setup  # noqa: F401
import jax
import jax.numpy as jnp

from spark_rapids_tpu.columnar.batch import bucket_capacity
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
def _bitcast_values(chunk_u8, byte_start, count: int, np_dtype_name: str):
    """PLAIN-encoded fixed-width values: gather + bitcast from raw bytes
    (the ORC decoder's, io/orc_device.py)."""
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
def column_eligible(col_meta, dtype: DataType) -> bool:
    """Does this column chunk decode on the device? The one place that
    says so: a BYTE_ARRAY chunk read as STRING, in a codec and in
    encodings the decoder walks (dictionary gather, plain (start, len)
    walk, delta-length expansion, the DELTA_BYTE_ARRAY provider scan;
    oversized pages raise _Unsupported at decode and fall back). Every
    fixed-width column is Arrow's (the module docstring says why)."""
    return (dtype is DataType.STRING
            and col_meta.physical_type == "BYTE_ARRAY"
            and codec_supported(col_meta.compression)
            and set(col_meta.encodings) <= {
                "PLAIN", "RLE", "PLAIN_DICTIONARY", "RLE_DICTIONARY",
                "DELTA_LENGTH_BYTE_ARRAY", "DELTA_BYTE_ARRAY"})


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


def _shifted_tab(rt: RunTable, row_shift: int, n: int):
    """Run table adjusted to a chunk-global output offset (numpy)."""
    return (rt.out_start.astype(np.int32) + np.int32(row_shift),
            rt.is_rle.astype(bool), rt.value.astype(np.int32),
            rt.bit_off.astype(np.int64))


def _synth_rle_tab(row_shift: int, value: int):
    return (np.asarray([row_shift], np.int32), np.asarray([True], bool),
            np.asarray([value], np.int32), np.asarray([0], np.int64))


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


def stage_chunk(chunk: bytes, codec: str):
    """Host half of `decode_chunk_device`: a raw column chunk's pages
    decompressed and their headers walked. Returns (normalised chunk
    bytes, pages with offsets into them). Pure host work on host data:
    the scan runs it ahead of the admission permit (io/scan.py:
    `_stage_split`) and hands both to `decode_chunk_device(pages=...)`.
    Raises _Unsupported for a codec or page type outside scope."""
    with OBS.span("scan.parse") as sp:
        if codec != "UNCOMPRESSED":
            chunk, pages = normalize_chunk(chunk, codec)
        else:
            pages = parse_pages(chunk)
        if sp is not None:
            sp.attrs["bytes_out"] = len(chunk)
    return chunk, pages


def decode_chunk_device(chunk: bytes, dtype: DataType, num_rows: int,
                        max_def: int, cap: Optional[int] = None,
                        codec: str = "UNCOMPRESSED",
                        encoded_ok: bool = False,
                        max_dict_fraction: float = 1.0,
                        pages: Optional[List[PageInfo]] = None):
    """Decode one raw BYTE_ARRAY column chunk into a device STRING column.

    Dictionary pages (host parses the (offset, length) dictionary table,
    values gather through it), PLAIN byte-array pages (host walks
    per-value (start, len) tables — native single pass — and the device
    gathers the bytes), DELTA_LENGTH_BYTE_ARRAY and DELTA_BYTE_ARRAY
    pages, v1 or v2; a chunk mixing them falls back. Either way the
    output column is one jitted gather through build_from_plan (reference
    decodes strings on the accelerator via cudf the same way,
    GpuParquetScan.scala:536-556), or, with `encoded_ok`, a dictionary
    chunk that clears the ndv/rows heuristic stays ENCODED
    (columnar/encoded.py).
    Compressed chunks (snappy/gzip/zstd/brotli) decompress page-by-page on
    the host first (normalize_chunk); the device data plane is identical.

    max_def: 1 for nullable columns (def levels present), 0 for required.
    `pages`: `stage_chunk`'s, where the caller ran it ahead; `chunk` is
    then the normalised bytes they index, and `codec` only names what the
    file held. Raises _Unsupported for shapes outside scope (caller falls
    back to the Arrow host path)."""
    from spark_rapids_tpu.columnar.batch import ColumnVector
    from spark_rapids_tpu.columnar.strings import build_from_plan

    if dtype is not DataType.STRING:
        raise _Unsupported(f"{dtype.name} chunk: fixed-width columns are "
                           "Arrow's (column_eligible)")
    if pages is None:
        chunk, pages = stage_chunk(chunk, codec)
    OBS.annotate(pages=len(pages))  # on the caller's scan.decode
    cap = cap or bucket_capacity(max(num_rows, 1))
    with OBS.span("scan.upload", bytes=len(chunk)):
        chunk_dev = jnp.asarray(np.frombuffer(chunk, dtype=np.uint8))

    str_dict = None           # (bytes_dev, offs_dev, lens_dev)
    str_dict_host = None      # host (bytes_np, offs_np) dictionary table
    str_run_tabs = []         # per-page value run tables (no-null chunks)
    row_base = 0              # rows decoded so far (run-table shifting)
    str_plain = []            # per-page (starts_np, lens_np)
    str_delta = []            # per-page DEVICE (starts, lens, n) for
                              # DELTA_LENGTH_BYTE_ARRAY
    str_delta_bytes = 0       # host-known total value bytes across pages
    str_dba = []              # per-page (bytes_dev, starts, lens, n, total)
    dense_parts = []
    valid_parts = []
    for p in pages:
        if p.kind == PAGE_DICT:
            db, do, dl = _parse_dict_strings(chunk, p.data_start,
                                             p.num_values)
            str_dict_host = (db, do)
            str_dict = (jnp.asarray(db), jnp.asarray(do), jnp.asarray(dl))
            continue
        if p.encoding not in (ENC_PLAIN, ENC_PLAIN_DICT, ENC_RLE_DICT,
                              ENC_DELTA_LENGTH, ENC_DELTA_BYTE_ARRAY):
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
        page_dense = None  # only dictionary pages assemble a dense stream
        if p.encoding in (ENC_PLAIN_DICT, ENC_RLE_DICT):
            if str_dict is None:
                raise _Unsupported("dictionary-encoded page before dict")
            bit_width = chunk[pos]
            if bit_width > 24:
                raise _Unsupported(f"dict index bit width {bit_width}")
            pos += 1
            all_present = n_present == p.num_values
            if bit_width == 0:
                page_dense = jnp.zeros((page_cap,), dtype=jnp.int32)
                if all_present:
                    str_run_tabs.append(_synth_rle_tab(row_base, 0))
            else:
                rt = parse_runs(chunk, pos, end, bit_width, n_present)
                # the indices: gathered through the dict AFTER assembly
                page_dense = _expand_hybrid(
                    chunk_dev, jnp.asarray(rt.out_start),
                    jnp.asarray(rt.is_rle), jnp.asarray(rt.value),
                    jnp.asarray(rt.bit_off), bit_width, page_cap)
                if all_present:
                    str_run_tabs.append(
                        _shifted_tab(rt, row_base, n_present))
        elif p.encoding == ENC_DELTA_LENGTH:
            # DELTA_LENGTH_BYTE_ARRAY: delta-packed lengths, then the
            # value bytes concatenated — lengths expand through the
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
        elif p.encoding == ENC_DELTA_BYTE_ARRAY:
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
        else:  # PLAIN byte-array: host (start, len) walk
            str_plain.append(_parse_plain_strings(chunk, pos, end,
                                                  n_present))
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
