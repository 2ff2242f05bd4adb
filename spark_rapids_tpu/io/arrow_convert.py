"""Arrow <-> columnar batch conversion.

The host staging format is Arrow (pyarrow) — its C++ readers play the role
cuDF's native parquet/ORC/CSV decoders play in the reference (GpuParquetScan
/ GpuOrcScan / GpuCSVScan). Conversion is column-at-a-time and zero-copy
where Arrow's layout allows (primitive columns without nulls).
"""

from __future__ import annotations

from typing import List, Optional, Tuple

import numpy as np
import pyarrow as pa

from spark_rapids_tpu.columnar.batch import (
    HostColumnarBatch,
    HostColumnVector,
)
from spark_rapids_tpu.columnar.dtypes import DataType, DecimalType
from spark_rapids_tpu.ops.base import AttributeReference

_ARROW_TO_DT = {
    pa.bool_(): DataType.BOOL,
    pa.int8(): DataType.INT8,
    pa.int16(): DataType.INT16,
    pa.int32(): DataType.INT32,
    pa.int64(): DataType.INT64,
    pa.float32(): DataType.FLOAT32,
    pa.float64(): DataType.FLOAT64,
    pa.string(): DataType.STRING,
    pa.large_string(): DataType.STRING,
    pa.date32(): DataType.DATE,
}


def arrow_type_to_dt(t: pa.DataType) -> DataType:
    if t in _ARROW_TO_DT:
        return _ARROW_TO_DT[t]
    if pa.types.is_timestamp(t):
        return DataType.TIMESTAMP
    if pa.types.is_decimal(t):
        if t.precision > DecimalType.MAX_PRECISION:
            raise TypeError(
                f"decimal precision {t.precision} exceeds the 64-bit cap "
                f"({DecimalType.MAX_PRECISION})")
        return DecimalType(t.precision, t.scale)
    if pa.types.is_dictionary(t):
        return arrow_type_to_dt(t.value_type)
    raise TypeError(f"unsupported arrow type {t} (flat types only, "
                    "reference: GpuOverrides.isSupportedType)")


def dt_to_arrow_type(dt: DataType) -> pa.DataType:
    if isinstance(dt, DecimalType):
        return pa.decimal128(dt.precision, dt.scale)
    for at, d in _ARROW_TO_DT.items():
        if d is dt and at != pa.large_string():
            return at
    if dt is DataType.TIMESTAMP:
        return pa.timestamp("us", tz="UTC")
    raise TypeError(f"no arrow type for {dt}")


def schema_attrs(schema: pa.Schema) -> List[AttributeReference]:
    return [
        AttributeReference(f.name, arrow_type_to_dt(f.type), f.nullable)
        for f in schema
    ]


def _chunked_to_np(col: pa.ChunkedArray) -> pa.Array:
    return col.combine_chunks() if col.num_chunks != 1 else col.chunk(0)


def _dictionary_column(col: pa.ChunkedArray, max_fraction: float):
    """A STRING column Arrow read as dictionary arrays (one a row group,
    each with its own dictionary, in the order its values first appear)
    as codes + ONE dictionary, and no value decoded. The dictionary is
    the row groups' values in ascending byte order: splits that hold the
    same values then share one interned `DeviceDictionary`, so their
    partial aggregates merge and sort on the codes as they are, with no
    remap between them (columnar/encoded.py `align_encoded`,
    `to_rank_space`). Each row group's indices are taken through a table
    of its dictionary's positions in that order, in Arrow, as Arrow's own
    `unify_dictionaries` would transpose them. None where the dictionary
    fails the encoded-scan heuristic (`scan_encoded_ok`): the caller
    decodes the column."""
    import pyarrow.compute as pc

    from spark_rapids_tpu.columnar.encoded import (
        DeviceDictionary,
        HostDictionaryColumn,
        scan_encoded_ok,
    )

    n = len(col)
    chunks = [c for c in col.chunks if len(c)]
    dicts = [c.dictionary.cast(pa.string()) for c in chunks]
    if any(d.null_count for d in dicts):
        return None
    values = pa.concat_arrays(dicts).unique() if dicts \
        else pa.array([], pa.string())
    if not scan_encoded_ok(len(values), n, max_fraction) and n:
        return None
    # binary, so that the order is the bytes' (the engine's string order)
    values = values.take(pc.sort_indices(values.cast(pa.binary())))
    codes = np.zeros(n, dtype=np.int32)
    validity = np.ones(n, dtype=bool)
    at = 0
    for c, d in zip(chunks, dicts):
        idx = c.indices
        if not d.equals(values):
            idx = pc.index_in(d, value_set=values).take(idx)
        if idx.null_count:
            validity[at:at + len(c)] = np.asarray(idx.is_valid())
            idx = idx.fill_null(0)
        codes[at:at + len(c)] = idx.to_numpy(zero_copy_only=False)
        at += len(c)
    if len(values):
        _, offsets_buf, data_buf = values.buffers()
        offsets = np.frombuffer(offsets_buf, dtype=np.int32)[
            values.offset:values.offset + len(values) + 1]
        byts = np.frombuffer(data_buf, dtype=np.uint8)[
            offsets[0]:offsets[-1]] if data_buf is not None \
            else np.zeros(0, np.uint8)
        offsets = offsets - offsets[0]
    else:
        offsets, byts = np.zeros(1, np.int32), np.zeros(0, np.uint8)
    return HostDictionaryColumn(
        DataType.STRING, codes, validity,
        DeviceDictionary.from_byte_table(byts, offsets))


def arrow_to_host_batch(table: pa.Table, attrs: List[AttributeReference],
                        dict_fraction: Optional[float] = None
                        ) -> HostColumnarBatch:
    """`dict_fraction` (the device scan: `rapids.tpu.sql.encoded.
    maxDictFraction`) keeps a STRING column that Arrow hands over as
    dictionary arrays as codes + dictionary (`HostDictionaryColumn`);
    without it every dictionary array is decoded, as the host operators
    need."""
    cols = []
    for attr in attrs:
        # look up by NAME: pyarrow ORC returns selected columns in file
        # order, not requested order
        col = table.column(attr.name)
        if dict_fraction is not None and attr.data_type is DataType.STRING \
                and pa.types.is_dictionary(col.type):
            coded = _dictionary_column(col, dict_fraction)
            if coded is not None:
                cols.append(coded)
                continue
        arr = _chunked_to_np(col)
        if pa.types.is_dictionary(arr.type):
            arr = arr.dictionary_decode()
        dt = attr.data_type
        n = len(arr)
        validity = np.ones(n, dtype=bool) if arr.null_count == 0 else \
            np.asarray(arr.is_valid())
        if dt is DataType.STRING:
            data = np.empty(n, dtype=object)
            py = arr.to_pylist()
            for i, v in enumerate(py):
                data[i] = v if v is not None else ""
        elif dt is DataType.TIMESTAMP:
            # fill nulls BEFORE to_numpy: arrow otherwise converts through
            # float64/NaT and corrupts large 64-bit values
            a = arr.cast(pa.timestamp("us")).cast(pa.int64()).fill_null(0)
            data = a.to_numpy(zero_copy_only=False).astype(np.int64)
        elif dt is DataType.DATE:
            data = arr.cast(pa.int32()).fill_null(0) \
                .to_numpy(zero_copy_only=False).astype(np.int32)
        elif isinstance(dt, DecimalType):
            data = _decimal_unscaled(arr, dt, validity)
        else:
            npdt = dt.to_np()
            if arr.null_count:
                # (fill_null copies the column even where none is null)
                arr = arr.fill_null(False if dt is DataType.BOOL
                                    else npdt.type(0).item())
            data = arr.to_numpy(zero_copy_only=False)
            if data.dtype != npdt:
                data = data.astype(npdt)
        cols.append(HostColumnVector(dt, data, validity))
    return HostColumnarBatch(cols, table.num_rows)


def _decimal_unscaled(arr: pa.Array, dt: DecimalType,
                      validity: np.ndarray) -> np.ndarray:
    """decimal128 arrow array -> unscaled int64 (the batch physical form).

    Fast path reads the low 64 bits of each 128-bit little-endian value
    straight from the arrow buffer — exact whenever |unscaled| < 2^63, which
    the p <= 18 gate guarantees."""
    n = len(arr)
    want = pa.decimal128(dt.precision, dt.scale)
    if arr.type != want:
        # the cast raises loudly on values that don't fit dt — never
        # silently truncate a wider column (decimal256, higher precision,
        # other scale) to 64 bits
        arr = arr.cast(want)
    bufs = arr.buffers()
    if len(bufs) > 1 and bufs[1] is not None and np.little_endian:
        raw = np.frombuffer(bufs[1], dtype=np.int64)
        lo = raw[arr.offset * 2:(arr.offset + n) * 2:2].copy()
        return np.where(validity, lo, np.int64(0))
    from spark_rapids_tpu.ops.decimal_util import to_unscaled

    py = arr.to_pylist()
    return np.array(
        [to_unscaled(v, dt.scale) if v is not None else 0 for v in py],
        dtype=np.int64)


def _unscaled_to_decimal128(col, dt: DecimalType) -> pa.Array:
    """Vectorized unscaled int64 -> decimal128 array: widen each value to
    two little-endian 64-bit limbs (lo, sign-extended hi) and hand arrow the
    raw buffer — no per-row Decimal objects."""
    n = len(col.data)
    data = np.ascontiguousarray(col.data[:n], dtype=np.int64)
    validity = np.ascontiguousarray(col.validity[:n], dtype=bool)
    if not np.little_endian:
        from spark_rapids_tpu.ops.decimal_util import from_unscaled

        vals = [from_unscaled(int(v), dt.scale) if ok else None
                for v, ok in zip(data, validity)]
        return pa.array(vals, type=pa.decimal128(dt.precision, dt.scale))
    limbs = np.empty((n, 2), dtype=np.int64)
    limbs[:, 0] = np.where(validity, data, 0)
    limbs[:, 1] = limbs[:, 0] >> 63  # sign extension
    if validity.all():
        vbuf = None
        null_count = 0
    else:
        vbuf = pa.py_buffer(
            np.packbits(validity, bitorder="little").tobytes())
        null_count = int((~validity).sum())
    return pa.Array.from_buffers(
        pa.decimal128(dt.precision, dt.scale), n,
        [vbuf, pa.py_buffer(limbs.tobytes())], null_count=null_count)


def _dictionary_array(col) -> pa.DictionaryArray:
    """A STRING `HostDictionaryColumn` as Arrow's dictionary array: the
    codes as they are, nulls from the validity, the dictionary's byte
    table handed over by its buffers. No value is decoded and nothing
    runs a row at a time."""
    d = col.dictionary
    values = pa.Array.from_buffers(
        pa.string(), d.size,
        [None,
         pa.py_buffer(np.ascontiguousarray(d.host_offsets, dtype=np.int32)),
         pa.py_buffer(np.ascontiguousarray(d.host_bytes, dtype=np.uint8))])
    mask = None if col.validity.all() else ~col.validity
    return pa.DictionaryArray.from_arrays(
        pa.array(col.data, type=pa.int32(), mask=mask), values)


def host_batch_to_arrow(batch: HostColumnarBatch,
                        attrs: List[AttributeReference]) -> pa.Table:
    """A dictionary-coded STRING column (`HostDictionaryColumn`, what a
    sink with `keep_encoded` downloads) becomes a `pa.DictionaryArray`;
    the caller decides what its format makes of one (io/writer.py)."""
    from spark_rapids_tpu.columnar.encoded import HostDictionaryColumn

    arrays = []
    names = []
    for attr, col in zip(attrs, batch.columns):
        dt = attr.data_type
        names.append(attr.name)
        if isinstance(col, HostDictionaryColumn):
            if dt is DataType.STRING and not col.dictionary.is_fixed:
                arrays.append(_dictionary_array(col))
                continue
            col = col.decoded()     # a fixed-width dictionary: one take
        mask = ~col.validity  # arrow mask semantics: True = null
        if dt is DataType.STRING:
            # one Arrow call over the object array and the mask
            arrays.append(pa.array(col.data, type=pa.string(), mask=mask))
        elif dt is DataType.TIMESTAMP:
            arrays.append(pa.array(col.data.astype(np.int64), mask=mask)
                          .cast(pa.timestamp("us", tz="UTC")))
        elif dt is DataType.DATE:
            arrays.append(pa.array(col.data.astype(np.int32), mask=mask)
                          .cast(pa.date32()))
        elif isinstance(dt, DecimalType):
            arrays.append(_unscaled_to_decimal128(col, dt))
        else:
            arrays.append(pa.array(col.data, mask=mask,
                                   type=dt_to_arrow_type(dt)))
    # positional construction: duplicate column names must round-trip to the
    # writer (which then raises), not silently drop columns
    return pa.table(arrays, names=names)
