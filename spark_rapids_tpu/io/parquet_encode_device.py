"""Device-side parquet encode (write path).

Reference parity: the reference encodes parquet ON the accelerator into a
host buffer and only streams bytes afterwards (`ColumnarOutputWriter.scala:
62-177` — cudf `Table.writeParquet` under the semaphore,
`GpuParquetFileFormat.scala:34-192`). The TPU-native split mirrors the
device decoder (io/parquet_device.py) in reverse:

- DEVICE (data plane): per column, one jitted kernel compacts the non-null
  values into a dense stream (the PLAIN page payload) and bit-packs the
  validity into v1 definition levels. What downloads is the *encoded* page
  payload — dense values + packed bits — not padded arrays.
- HOST (control plane, tiny): wraps payloads in thrift-compact page
  headers and writes the footer (schema / row group / column chunk
  metadata). No value is touched on the host.

Scope: PLAIN v1 pages for fixed-width columns (INT32/INT64/FLOAT/DOUBLE +
DATE/TIMESTAMP logical annotations; DECIMAL over INT64), STRING
(BYTE_ARRAY with device-built length prefixes), and BOOLEAN (dense
values bit-packed LSB-first). Pages optionally host-compressed per block
(snappy/gzip/zstd via the same pyarrow codecs the decoder uses — the
exact mirror of the decode split: device data plane, host block codec).
Files read back with pyarrow/Spark. Nested types use the host Arrow
writer.
"""

from __future__ import annotations

import functools
import os
import struct
from typing import List, Optional, Tuple

import numpy as np

from spark_rapids_tpu import _jax_setup  # noqa: F401
import jax
import jax.numpy as jnp

from spark_rapids_tpu.columnar.batch import (
    ColumnarBatch,
    device_float64_supported,
)
from spark_rapids_tpu.columnar.dtypes import DataType, DecimalType
from spark_rapids_tpu.obs.trace import span as obs_span

MAGIC = b"PAR1"

# parquet physical type ids (parquet.thrift Type)
_T_BOOLEAN = 0
_T_INT32 = 1
_T_INT64 = 2
_T_FLOAT = 4
_T_DOUBLE = 5
_T_BYTE_ARRAY = 6

# ConvertedType ids for logical annotation
_CT_UTF8 = 0
_CT_DATE = 6
_CT_TIMESTAMP_MICROS = 10
_CT_DECIMAL = 5

# parquet CompressionCodec ids and the pyarrow codec names behind them
_CODECS = {"UNCOMPRESSED": (0, None), "SNAPPY": (1, "snappy"),
           "GZIP": (2, "gzip"), "ZSTD": (6, "zstd")}


def _phys_type(dt) -> Optional[Tuple[int, int, Optional[int]]]:
    """(parquet physical type, byte width, converted type) or None when the
    dtype can't device-encode."""
    if isinstance(dt, DecimalType):
        return _T_INT64, 8, _CT_DECIMAL
    return {
        DataType.INT32: (_T_INT32, 4, None),
        DataType.INT64: (_T_INT64, 8, None),
        DataType.FLOAT32: (_T_FLOAT, 4, None),
        DataType.FLOAT64: (_T_DOUBLE, 8, None),
        DataType.DATE: (_T_INT32, 4, _CT_DATE),
        DataType.TIMESTAMP: (_T_INT64, 8, _CT_TIMESTAMP_MICROS),
        DataType.STRING: (_T_BYTE_ARRAY, 0, _CT_UTF8),
        DataType.BOOL: (_T_BOOLEAN, 0, None),
    }.get(dt)


def schema_encodable(attrs) -> bool:
    for a in attrs:
        if _phys_type(a.data_type) is None:
            return False
        if a.data_type is DataType.FLOAT64 and not device_float64_supported():
            return False
    return True


def codec_supported(compression: str) -> bool:
    """Can the device encoder produce this parquet compression? (Mirrors
    the decoder's host block-codec support, parquet_device.py.)"""
    name = compression.upper()
    if name in ("NONE",):
        name = "UNCOMPRESSED"
    if name not in _CODECS:
        return False
    cid, pa_name = _CODECS[name]
    if pa_name is None:
        return True
    try:
        import pyarrow as pa

        pa.Codec(pa_name)
        return True
    except Exception:
        return False


# ---------------------------------------------------------------------------
# Device kernels
# ---------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnums=())
def _encode_fixed(data, validity, num_rows):
    """Compact non-null values to the front (PLAIN payload order) and pack
    validity bits little-endian (v1 def levels). Returns
    (dense_values[cap], packed_bits[cap//8], n_present)."""
    cap = data.shape[0]
    live = validity & (jnp.arange(cap) < num_rows)
    # stable compaction: present rows keep their order
    order = jnp.argsort(~live, stable=True).astype(jnp.int32)
    dense = data[order]
    n_present = jnp.sum(live.astype(jnp.int32))
    bits = live.reshape(cap // 8, 8).astype(jnp.uint8)
    weights = (jnp.uint8(1) << jnp.arange(8, dtype=jnp.uint8))
    packed = jnp.sum(bits * weights[None, :], axis=1).astype(jnp.uint8)
    return dense, packed, n_present


@functools.partial(jax.jit, static_argnums=(4, 5))
def _encode_string_plan(data, offsets, validity, num_rows, cap: int,
                        prefix: int = 4):
    """Plan a dense string byte stream: per present row the output is
    [prefix length bytes][bytes] (prefix=4 -> parquet BYTE_ARRAY PLAIN;
    prefix=0 -> ORC DATA stream). Returns (sel_rows, out_lens,
    out_offsets, n_present, total_bytes) with sel = dense non-null row
    ids in order."""
    live = validity & (jnp.arange(cap) < num_rows)
    order = jnp.argsort(~live, stable=True).astype(jnp.int32)
    n_present = jnp.sum(live.astype(jnp.int32))
    sel = order
    lens = (offsets[1:] - offsets[:-1])[sel]
    in_sel = jnp.arange(cap) < n_present
    piece = jnp.where(in_sel, lens + prefix, 0)
    out_offsets = jnp.concatenate([
        jnp.zeros((1,), jnp.int32), jnp.cumsum(piece, dtype=jnp.int32)])
    return sel, lens, out_offsets, n_present, out_offsets[-1]


@functools.partial(jax.jit, static_argnums=(5, 6))
def _encode_string_bytes(data, offsets, sel, lens, out_offsets,
                         byte_cap: int, prefix: int = 4):
    """Materialize the (optionally length-prefixed) dense byte stream in
    ONE kernel: each output byte is either a little-endian length byte
    (first `prefix` of its value) or a gathered source byte."""
    cap = sel.shape[0]
    pos = jnp.arange(byte_cap, dtype=jnp.int32)
    row = jnp.clip(jnp.searchsorted(out_offsets[1:], pos, side="right"),
                   0, cap - 1).astype(jnp.int32)
    within = pos - out_offsets[row]
    src_start = offsets[:-1][sel]
    src_pos = jnp.clip(src_start[row] + within - prefix, 0,
                       data.shape[0] - 1)
    valid = pos < out_offsets[-1]
    if prefix:
        is_len = within < prefix
        ln = lens[row].astype(jnp.uint32)
        len_byte = (ln >> (within.astype(jnp.uint32) * 8)) & \
            jnp.uint32(0xFF)
        out = jnp.where(is_len, len_byte.astype(jnp.uint8), data[src_pos])
    else:
        out = data[src_pos]
    return jnp.where(valid, out, 0).astype(jnp.uint8)


def encode_column_page(col, num_rows: int):
    """Device-encode one column of one batch into host page-payload pieces:
    (def_level_bytes, value_bytes, n_present). DOUBLE columns are eligible
    only where the device computes real f64 (schema_encodable gates TPU)."""
    from spark_rapids_tpu.columnar.batch import bucket_capacity
    from spark_rapids_tpu.columnar.dtypes import DataType as _DT

    if col.dtype is _DT.STRING:
        cap = col.validity.shape[0]
        sel, lens, out_offsets, n_present, total = _encode_string_plan(
            col.data, col.offsets, col.validity, jnp.int32(num_rows), cap)
        n_present = int(jax.device_get(n_present))
        total = int(jax.device_get(total))
        byte_cap = bucket_capacity(max(total, 1))
        stream = _encode_string_bytes(col.data, col.offsets, sel, lens,
                                      out_offsets, byte_cap)
        val_host = np.asarray(jax.device_get(stream[:total]))
        packed = _pack_validity_bits(col.validity, jnp.int32(num_rows))
        nbytes_bits = (num_rows + 7) // 8
        bits_host = np.asarray(jax.device_get(packed[:nbytes_bits]))
        groups = (num_rows + 7) // 8
        header = _uvarint((groups << 1) | 1)
        dl = header + bits_host.tobytes()
        return (struct.pack("<I", len(dl)) + dl, val_host.tobytes(),
                n_present)
    dense, packed, n_present = _encode_fixed(col.data, col.validity,
                                             jnp.int32(num_rows))
    n_present = int(jax.device_get(n_present))
    if col.dtype is _DT.BOOL:
        # PLAIN booleans: dense values bit-packed LSB-first
        vbits = _pack_validity_bits(dense.astype(bool),
                                    jnp.int32(n_present))
        val_host = np.asarray(
            jax.device_get(vbits[:(n_present + 7) // 8]))
        dense_host = None
    else:
        # slice ON device before download: only the encoded payload
        # transfers
        dense_host = np.asarray(jax.device_get(dense[:n_present]))
    nbytes_bits = (num_rows + 7) // 8
    bits_host = np.asarray(jax.device_get(packed[:nbytes_bits]))
    # v1 def levels: u32 length prefix + RLE-hybrid; ONE bit-packed run of
    # ceil(n/8) groups is always legal
    groups = (num_rows + 7) // 8
    header = _uvarint((groups << 1) | 1)
    dl = header + bits_host.tobytes()
    vals = (val_host if dense_host is None else dense_host).tobytes()
    return struct.pack("<I", len(dl)) + dl, vals, n_present


@jax.jit
def _pack_validity_bits(validity, num_rows):
    cap = validity.shape[0]
    live = validity & (jnp.arange(cap) < num_rows)
    bits = live.reshape(cap // 8, 8).astype(jnp.uint8)
    weights = (jnp.uint8(1) << jnp.arange(8, dtype=jnp.uint8))
    return jnp.sum(bits * weights[None, :], axis=1).astype(jnp.uint8)


# ---------------------------------------------------------------------------
# Thrift compact writer (just enough for parquet metadata)
# ---------------------------------------------------------------------------
def _uvarint(v: int) -> bytes:
    out = bytearray()
    while True:
        b = v & 0x7F
        v >>= 7
        if v:
            out.append(b | 0x80)
        else:
            out.append(b)
            return bytes(out)


def _zigzag(v: int) -> bytes:
    return _uvarint((v << 1) ^ (v >> 63))


class _CompactWriter:
    def __init__(self):
        self.buf = bytearray()
        self._fid_stack: List[int] = []
        self.last_fid = 0

    def _field_header(self, fid: int, ftype: int):
        delta = fid - self.last_fid
        if 0 < delta <= 15:
            self.buf.append((delta << 4) | ftype)
        else:
            self.buf.append(ftype)
            self.buf += _zigzag(fid)
        self.last_fid = fid

    def i32(self, fid: int, v: int):
        self._field_header(fid, 5)
        self.buf += _zigzag(v)

    def i64(self, fid: int, v: int):
        self._field_header(fid, 6)
        self.buf += _zigzag(v)

    def string(self, fid: int, s: str):
        self._field_header(fid, 8)
        b = s.encode("utf-8")
        self.buf += _uvarint(len(b)) + b

    def begin_struct(self, fid: int):
        self._field_header(fid, 12)
        self._fid_stack.append(self.last_fid)
        self.last_fid = 0

    def begin_element_struct(self):
        """A struct that is a LIST ELEMENT: no field header byte — compact
        protocol list elements are bare values."""
        self._fid_stack.append(self.last_fid)
        self.last_fid = 0

    def end_struct(self):
        self.buf.append(0)
        self.last_fid = self._fid_stack.pop()

    def list_header(self, fid: int, etype: int, n: int):
        self._field_header(fid, 9)
        if n < 15:
            self.buf.append((n << 4) | etype)
        else:
            self.buf.append(0xF0 | etype)
            self.buf += _uvarint(n)

    def stop(self) -> bytes:
        self.buf.append(0)
        return bytes(self.buf)


def _page_header(n_values: int, payload_len: int,
                 compressed_len: int) -> bytes:
    w = _CompactWriter()
    w.i32(1, 0)                    # type = DATA_PAGE
    w.i32(2, payload_len)          # uncompressed_size
    w.i32(3, compressed_len)       # compressed_size
    w.begin_struct(5)              # data_page_header
    w.i32(1, n_values)
    w.i32(2, 0)                    # encoding = PLAIN
    w.i32(3, 3)                    # definition_level_encoding = RLE
    w.i32(4, 3)                    # repetition_level_encoding = RLE
    w.end_struct()
    return w.stop()


def _schema_element(w: _CompactWriter, a) -> None:
    phys, _width, conv = _phys_type(a.data_type)
    w.begin_element_struct()
    w.i32(1, phys)
    w.i32(3, 1)        # repetition = OPTIONAL
    w.string(4, a.name)
    if conv is not None:
        w.i32(6, conv)
    if isinstance(a.data_type, DecimalType):
        w.i32(7, a.data_type.scale)
        w.i32(8, a.data_type.precision)
    w.end_struct()


def write_file(path: str, attrs, batches: List[ColumnarBatch],
               compression: str = "UNCOMPRESSED") -> int:
    """Assemble one parquet file from device-encoded pages; page payloads
    are host-block-compressed when a codec is requested (the exact mirror
    of the decode split — device data plane, host block codec). Returns
    rows written."""
    cname = compression.upper()
    if cname == "NONE":
        cname = "UNCOMPRESSED"
    codec_id, pa_name = _CODECS[cname]
    pa_codec = None
    if pa_name is not None:
        import pyarrow as pa

        pa_codec = pa.Codec(pa_name)
    from spark_rapids_tpu.columnar.batch import ensure_compact

    # encode: pages[column][batch] -> (def_bytes, val_bytes, n_present, n)
    pages: List[List[Tuple[bytes, bytes, int, int]]] = [[] for _ in attrs]
    total_rows = 0
    with obs_span("write.encode"):
        for b in batches:
            # live-masked batches (exchange outputs) compact first:
            # validity and offsets must be positional over the rows
            # actually written
            b = ensure_compact(b)
            for ci, a in enumerate(attrs):
                defb, valb, npres = encode_column_page(b.columns[ci],
                                                       b.num_rows)
                pages[ci].append((defb, valb, npres, b.num_rows))
            total_rows += b.num_rows
    with obs_span("write.file", encoder="device", path=path,
                  rows=total_rows) as sp:
        _write_pages(path, attrs, pages, total_rows, codec_id, pa_codec)
        if sp is not None:
            sp.attrs["bytes"] = os.path.getsize(path)
    return total_rows


def _write_pages(path: str, attrs, pages, total_rows: int, codec_id: int,
                 pa_codec) -> None:
    """Host control plane of write_file: compress each page payload where
    a codec is set, frame it, and close the file with the footer."""
    with open(path, "wb") as f:
        f.write(MAGIC)
        offset = 4
        col_meta = []
        for ci, a in enumerate(attrs):
            first_off = offset
            n_vals = 0
            chunk_bytes = 0
            chunk_raw_bytes = 0
            for defb, valb, npres, nrows in pages[ci]:
                payload = defb + valb
                if pa_codec is not None:
                    wire = bytes(pa_codec.compress(payload))
                else:
                    wire = payload
                hdr = _page_header(nrows, len(payload), len(wire))
                f.write(hdr)
                f.write(wire)
                offset += len(hdr) + len(wire)
                chunk_bytes += len(hdr) + len(wire)
                chunk_raw_bytes += len(hdr) + len(payload)
                n_vals += nrows
            col_meta.append((a, first_off, n_vals, chunk_bytes,
                             chunk_raw_bytes))
        # footer: FileMetaData
        w = _CompactWriter()
        w.i32(1, 1)                          # version
        w.list_header(2, 12, len(attrs) + 1)  # schema
        # root schema element
        w.begin_element_struct()
        w.string(4, "schema")
        w.i32(5, len(attrs))                 # num_children
        w.end_struct()
        for a in attrs:
            _schema_element(w, a)
        w.i64(3, total_rows)                 # num_rows
        w.list_header(4, 12, 1)              # row_groups
        w.begin_element_struct()             # RowGroup
        w.list_header(1, 12, len(attrs))     # columns
        for a, first_off, n_vals, chunk_bytes, chunk_raw in col_meta:
            w.begin_element_struct()         # ColumnChunk
            w.i64(2, first_off)              # file_offset
            w.begin_struct(3)                # ColumnMetaData
            w.i32(1, _phys_type(a.data_type)[0])
            w.list_header(2, 5, 2)           # encodings [PLAIN, RLE]
            w.buf += _zigzag(0) + _zigzag(3)
            w.list_header(3, 8, 1)           # path_in_schema
            nb = a.name.encode("utf-8")
            w.buf += _uvarint(len(nb)) + nb
            w.i32(4, codec_id)               # codec
            w.i64(5, n_vals)
            w.i64(6, chunk_raw)              # total_uncompressed_size
            w.i64(7, chunk_bytes)            # total_compressed_size
            w.i64(9, first_off)              # data_page_offset
            w.end_struct()
            w.end_struct()
        w.i64(2, sum(m[3] for m in col_meta))  # total_byte_size
        w.i64(3, total_rows)                   # num_rows
        w.end_struct()
        w.string(6, "spark-rapids-tpu device encoder")
        footer = w.stop()
        f.write(footer)
        f.write(struct.pack("<I", len(footer)))
        f.write(MAGIC)
