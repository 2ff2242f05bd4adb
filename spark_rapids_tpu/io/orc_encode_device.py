"""Device-side ORC encode (write path).

Reference parity: the reference encodes ORC ON the accelerator into a host
buffer and only streams bytes afterwards (`ColumnarOutputWriter.scala:
62-177` — cudf `Table.writeORC` under the semaphore,
`GpuOrcFileFormat.scala`). Mirrors the parquet device encoder
(io/parquet_encode_device.py) with ORC's stream model:

- DEVICE (data plane): per column, jitted kernels compact the non-null
  values, zigzag-encode, and big-endian bit-pack them into the RLEv2
  DIRECT payload; the validity bitmap bit-packs into the PRESENT bytes.
  What downloads is the *encoded* stream payload, not padded columns.
- HOST (control plane, tiny): interleaves the per-512-value DIRECT run
  headers and per-128-byte PRESENT literal headers, and writes the
  protobuf metadata (StripeFooter / Footer / PostScript). No value is
  touched on the host.

Scope: flat SHORT/INT/LONG/DATE columns (DIRECT_V2 with a single
column-wide bit width), STRING (DIRECT_V2: device byte-gather DATA +
RLEv2 LENGTH), FLOAT/DOUBLE (raw IEEE LE streams; DOUBLE needs an
f64-capable backend); one stripe per input batch. Streams and metadata
sections optionally host-compressed in ORC's 3-byte-header block framing
(zlib/snappy — the same codecs the device decoder's host control plane
uses). Files read back with pyarrow.orc and this repo's own device ORC
decoder. Everything else uses the host Arrow writer.
"""

from __future__ import annotations

import functools
import struct
from typing import List, Optional, Tuple

import numpy as np

from spark_rapids_tpu import _jax_setup  # noqa: F401
import jax
import jax.numpy as jnp

from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.columnar.dtypes import DataType
from spark_rapids_tpu.obs.trace import span as obs_span

# ORC type kinds (orc_proto Type.Kind)
_KIND = {
    DataType.BOOL: 0,    # BOOLEAN
    DataType.INT16: 2,   # SHORT
    DataType.INT32: 3,   # INT
    DataType.INT64: 4,   # LONG
    DataType.DATE: 15,   # DATE
    DataType.FLOAT32: 5,   # FLOAT
    DataType.FLOAT64: 6,   # DOUBLE
    DataType.STRING: 7,    # STRING
}
_INT_DTS = (DataType.INT16, DataType.INT32, DataType.INT64, DataType.DATE)
_K_STRUCT = 12

# PostScript CompressionKind
_COMP = {"none": 0, "uncompressed": 0, "zlib": 1, "snappy": 2}
_COMP_BLOCK = 64 * 1024

# RLEv2 DIRECT width -> 5-bit width code (subset: the widths we emit)
_DIRECT_WIDTHS = [1, 2, 4, 8, 16, 24, 32, 40, 48, 56, 64]
_WIDTH_CODE = {1: 0, 2: 1, 4: 3, 8: 7, 16: 15, 24: 23, 32: 27, 40: 28,
               48: 29, 56: 30, 64: 31}

_RUN = 512           # values per DIRECT run (max RLEv2 run length)
_LIT = 128           # bytes per PRESENT literal run


def schema_encodable(attrs) -> bool:
    from spark_rapids_tpu.columnar.batch import device_float64_supported

    for a in attrs:
        if a.data_type not in _KIND:
            return False
        if a.data_type is DataType.FLOAT64 and \
                not device_float64_supported():
            return False
    return True


def codec_supported(compression: str) -> bool:
    name = compression.lower()
    if name not in _COMP:
        return False
    if _COMP[name] == 2:  # snappy via the same pyarrow codec the decoder uses
        try:
            import pyarrow as pa

            pa.Codec("snappy")
        except Exception:
            return False
    return True


def _compress_stream(payload: bytes, kind: int) -> bytes:
    """Wrap a stream/metadata payload in ORC's compressed-block framing:
    3-byte little-endian header (len << 1 | is_original) per <=64KB block.
    HOST control plane — the mirror of decompress_blocks in the device
    decoder (orc_device.py)."""
    if kind == 0:
        return payload
    out = bytearray()
    for i in range(0, len(payload), _COMP_BLOCK):
        chunk = payload[i:i + _COMP_BLOCK]
        if kind == 1:
            import zlib

            c = zlib.compressobj(6, zlib.DEFLATED, -15)
            comp = c.compress(chunk) + c.flush()
        else:
            import pyarrow as pa

            comp = bytes(pa.Codec("snappy").compress(chunk))
        if len(comp) < len(chunk):
            h = len(comp) << 1
            out += bytes((h & 0xFF, (h >> 8) & 0xFF, (h >> 16) & 0xFF))
            out += comp
        else:
            h = (len(chunk) << 1) | 1
            out += bytes((h & 0xFF, (h >> 8) & 0xFF, (h >> 16) & 0xFF))
            out += chunk
    return bytes(out)


# ---------------------------------------------------------------------------
# Device kernels
# ---------------------------------------------------------------------------
@jax.jit
def _compact_zigzag(data, validity, num_rows):
    """Dense non-null values in row order, zigzag-encoded to uint64, plus
    the present count and the max encoded value (for the width pick).
    Validity is row-masked first — padding lanes must never contribute
    (same guard as the parquet encoder, parquet_encode_device.py)."""
    validity = validity & (jnp.arange(validity.shape[0]) < num_rows)
    order = jnp.argsort(~validity, stable=True)
    dense = data.astype(jnp.int64)[order]
    u = ((dense << 1) ^ (dense >> 63)).astype(jnp.uint64)
    n = jnp.sum(validity.astype(jnp.int32))
    in_range = jnp.arange(u.shape[0]) < n
    u = jnp.where(in_range, u, 0)
    return u, n, jnp.max(u)


@functools.partial(jax.jit, static_argnums=(1, 2))
def _bitpack_be(u, width: int, out_bytes: int):
    """Big-endian bit-pack: value i occupies bits [i*width, (i+1)*width),
    MSB first — the RLEv2 DIRECT payload layout."""
    nvals = u.shape[0]
    byte_i = jnp.arange(out_bytes, dtype=jnp.int64)
    gb = byte_i[:, None] * 8 + jnp.arange(8, dtype=jnp.int64)[None, :]
    val_idx = gb // width
    shift = (width - 1 - (gb % width)).astype(jnp.uint64)
    vals = u[jnp.clip(val_idx, 0, nvals - 1)]
    vals = jnp.where(val_idx < nvals, vals, 0)
    bits = ((vals >> shift) & jnp.uint64(1)).astype(jnp.uint32)
    weights = (jnp.uint32(1) << (7 - jnp.arange(8, dtype=jnp.uint32)))
    return jnp.sum(bits * weights[None, :], axis=1).astype(jnp.uint8)


@jax.jit
def _pack_present(validity, num_rows):
    """PRESENT bitmap bytes: MSB-first, 1 = value present; bits beyond
    num_rows are zero-padded."""
    cap = validity.shape[0]
    nbytes = (cap + 7) // 8
    idx = jnp.arange(nbytes)[:, None] * 8 + jnp.arange(8)[None, :]
    ok = (idx < num_rows) & validity[jnp.clip(idx, 0, cap - 1)]
    weights = (jnp.uint32(1) << (7 - jnp.arange(8, dtype=jnp.uint32)))
    return jnp.sum(ok.astype(jnp.uint32) * weights[None, :],
                   axis=1).astype(jnp.uint8)


# ---------------------------------------------------------------------------
# Host control plane: headers + protobuf
# ---------------------------------------------------------------------------
def _pick_width(max_u: int) -> int:
    need = max(int(max_u).bit_length(), 1)
    for w in _DIRECT_WIDTHS:
        if w >= need:
            return w
    return 64


def _direct_stream(packed: bytes, n: int, width: int) -> bytes:
    """Interleave the 2-byte DIRECT run headers between the contiguous
    512-value byte-aligned payload chunks the device produced."""
    out = bytearray()
    run_bytes = _RUN * width // 8
    for r in range((n + _RUN - 1) // _RUN):
        length = min(_RUN, n - r * _RUN)
        h1 = 0x40 | (_WIDTH_CODE[width] << 1) | ((length - 1) >> 8)
        h2 = (length - 1) & 0xFF
        out.append(h1)
        out.append(h2)
        chunk = packed[r * run_bytes:
                       r * run_bytes + (length * width + 7) // 8]
        out += chunk
    return bytes(out)


def _present_stream(bitmap: bytes) -> bytes:
    """Byte-RLE literal runs over the bitmap bytes (header = -count)."""
    out = bytearray()
    for i in range(0, len(bitmap), _LIT):
        chunk = bitmap[i:i + _LIT]
        out.append(256 - len(chunk))
        out += chunk
    return bytes(out)


# varint shared with the parquet thrift writer (same LEB128 wire format)
from spark_rapids_tpu.io.parquet_encode_device import _uvarint  # noqa: E402


def _fv(fnum: int, v: int) -> bytes:
    return _uvarint((fnum << 3) | 0) + _uvarint(v)


def _fb(fnum: int, b: bytes) -> bytes:
    return _uvarint((fnum << 3) | 2) + _uvarint(len(b)) + b


@jax.jit
def _compact_fixed(data, validity, num_rows):
    """Dense non-null values in row order (no transform — FLOAT/DOUBLE
    raw IEEE streams)."""
    validity = validity & (jnp.arange(validity.shape[0]) < num_rows)
    order = jnp.argsort(~validity, stable=True)
    return data[order], jnp.sum(validity.astype(jnp.int32))


@functools.partial(jax.jit, static_argnums=(2,))
def _lens_u64(lens, n_present, cap: int):
    """Unsigned length stream values for RLEv2 (no zigzag — LENGTH is
    unsigned per the ORC spec)."""
    in_sel = jnp.arange(cap) < n_present
    u = jnp.where(in_sel, lens, 0).astype(jnp.uint64)
    return u, jnp.max(u)


def _rle_direct(u, n: int, max_u: int) -> bytes:
    width = _pick_width(max_u)
    if n <= 0:
        return b""
    out_bytes = ((n + _RUN - 1) // _RUN) * (_RUN * width // 8)
    packed = bytes(np.asarray(jax.device_get(
        _bitpack_be(u, width, out_bytes))))
    return _direct_stream(packed, n, width)


def _encode_stripe(attrs, batch: ColumnarBatch,
                   comp_kind: int) -> Tuple[bytes, bytes, int]:
    """One input batch -> (stripe data bytes, stripe footer bytes, rows).
    Stream payloads are device-encoded then host-compressed per block."""
    from spark_rapids_tpu.columnar.batch import (
        bucket_capacity,
        ensure_compact,
    )
    from spark_rapids_tpu.io.parquet_encode_device import (
        _encode_string_bytes,
        _encode_string_plan,
    )

    # live-masked batches (exchange outputs) compact first: the PRESENT
    # bitmap is positional over the stripe's rows, so lanes 0..n_rows-1
    # must BE the rows
    batch = ensure_compact(batch)
    n_rows = int(batch.host_rows())
    streams: List[Tuple[int, int, bytes]] = []   # (kind, column, payload)
    for ci, a in enumerate(attrs):
        cv = batch.columns[ci]
        validity = cv.validity
        dt = a.data_type
        if dt is DataType.STRING:
            cap = validity.shape[0]
            sel, lens, out_offsets, n, total = _encode_string_plan(
                cv.data, cv.offsets, validity, jnp.int32(n_rows), cap, 0)
            n = int(jax.device_get(n))
            total = int(jax.device_get(total))
            if n != n_rows:
                bitmap = bytes(np.asarray(jax.device_get(
                    _pack_present(validity, jnp.int32(n_rows)))))
                streams.append((0, ci + 1,
                                _present_stream(bitmap[:(n_rows + 7) // 8])))
            byte_cap = bucket_capacity(max(total, 1))
            sbytes = _encode_string_bytes(cv.data, cv.offsets, sel, lens,
                                          out_offsets, byte_cap, 0)
            data = bytes(np.asarray(jax.device_get(sbytes[:total])))
            streams.append((1, ci + 1, data))
            u, max_u = _lens_u64(lens, jnp.int32(n), cap)
            max_u = int(jax.device_get(max_u))
            streams.append((2, ci + 1, _rle_direct(u, n, max_u)))
            continue
        if dt is DataType.BOOL:
            # BOOLEAN DATA: dense values bit-packed MSB-first in the same
            # byte-RLE literal framing as PRESENT
            dense, n = _compact_fixed(cv.data, validity, jnp.int32(n_rows))
            n = int(jax.device_get(n))
            if n != n_rows:
                bitmap = bytes(np.asarray(jax.device_get(
                    _pack_present(validity, jnp.int32(n_rows)))))
                streams.append((0, ci + 1,
                                _present_stream(bitmap[:(n_rows + 7) // 8])))
            vbits = bytes(np.asarray(jax.device_get(
                _pack_present(dense.astype(bool), jnp.int32(n)))))
            streams.append((1, ci + 1,
                            _present_stream(vbits[:(n + 7) // 8])))
            continue
        if dt in (DataType.FLOAT32, DataType.FLOAT64):
            dense, n = _compact_fixed(cv.data, validity, jnp.int32(n_rows))
            n = int(jax.device_get(n))
            if n != n_rows:
                bitmap = bytes(np.asarray(jax.device_get(
                    _pack_present(validity, jnp.int32(n_rows)))))
                streams.append((0, ci + 1,
                                _present_stream(bitmap[:(n_rows + 7) // 8])))
            host = np.asarray(jax.device_get(dense[:n]))
            want = np.float32 if dt is DataType.FLOAT32 else np.float64
            streams.append((1, ci + 1,
                            host.astype(want, copy=False).tobytes()))
            continue
        u, n, max_u = _compact_zigzag(cv.data, validity,
                                      jnp.int32(n_rows))
        n, max_u = int(jax.device_get(n)), int(jax.device_get(max_u))
        if n != n_rows:
            bitmap = bytes(np.asarray(
                jax.device_get(_pack_present(validity,
                                             jnp.int32(n_rows)))))
            bitmap = bitmap[:(n_rows + 7) // 8]
            streams.append((0, ci + 1, _present_stream(bitmap)))
        streams.append((1, ci + 1, _rle_direct(u, n, max_u)))

    data_area = bytearray()
    footer = bytearray()
    for kind, col, payload in streams:
        wire = _compress_stream(payload, comp_kind)
        data_area += wire
        footer += _fb(1, _fv(1, kind) + _fv(2, col) + _fv(3, len(wire)))
    # column encodings: root struct DIRECT; ints/strings DIRECT_V2,
    # floats DIRECT
    footer += _fb(2, _fv(1, 0))
    for a in attrs:
        enc = 0 if a.data_type in (DataType.FLOAT32, DataType.FLOAT64,
                                   DataType.BOOL) else 2
        footer += _fb(2, _fv(1, enc))
    return bytes(data_area), bytes(footer), n_rows


def write_file(path: str, attrs, batches: List[ColumnarBatch],
               compression: str = "uncompressed") -> int:
    """Assemble one ORC file from device-encoded stripes (one stripe per
    batch); streams and metadata sections are host-block-compressed when
    a codec is requested. Returns rows written."""
    comp_kind = _COMP[compression.lower()]
    header = b"ORC"
    body = bytearray(header)
    stripe_infos: List[Tuple[int, int, int, int]] = []
    total_rows = 0
    with obs_span("write.encode"):
        for b in batches:
            if b.host_rows() == 0:
                continue
            offset = len(body)
            data, sfooter, rows = _encode_stripe(attrs, b, comp_kind)
            sfooter = _compress_stream(sfooter, comp_kind)
            body += data
            body += sfooter
            stripe_infos.append((offset, len(data), len(sfooter), rows))
            total_rows += rows

    # Footer
    footer = bytearray()
    footer += _fv(1, len(header))          # headerLength
    footer += _fv(2, len(body))            # contentLength
    for off, dlen, flen, rows in stripe_infos:
        footer += _fb(3, _fv(1, off) + _fv(2, 0) + _fv(3, dlen)
                      + _fv(4, flen) + _fv(5, rows))
    # types: root struct + one per column
    root = _fv(1, _K_STRUCT)
    for ci, a in enumerate(attrs):
        root += _fv(2, ci + 1)
    for a in attrs:
        root += _fb(3, a.name.encode("utf-8"))
    footer += _fb(4, root)
    for a in attrs:
        footer += _fb(4, _fv(1, _KIND[a.data_type]))
    footer += _fv(6, total_rows)           # numberOfRows
    footer += _fv(8, 0)                    # rowIndexStride: no row index
    footer = bytearray(_compress_stream(bytes(footer), comp_kind))

    ps = bytearray()
    ps += _fv(1, len(footer))              # footerLength
    ps += _fv(2, comp_kind)                # compression kind
    ps += _fv(3, _COMP_BLOCK)              # compressionBlockSize
    ps += _uvarint((4 << 3) | 0) + _uvarint(0)    # version: 0
    ps += _uvarint((4 << 3) | 0) + _uvarint(12)   # version: 12
    ps += _fv(5, 0)                        # metadataLength
    ps += _fv(6, 1)                        # writerVersion
    ps += _fb(8000, b"ORC")                # magic
    assert len(ps) < 256

    with obs_span("write.file", encoder="device", path=path,
                  rows=total_rows, bytes=len(body) + len(footer)
                  + len(ps) + 1):
        with open(path, "wb") as f:
            f.write(bytes(body))
            f.write(bytes(footer))
            f.write(bytes(ps))
            f.write(struct.pack("B", len(ps)))
    return total_rows
