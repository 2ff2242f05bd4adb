"""Device-side CSV numeric parsing.

Reference parity: the reference parses CSV ON the accelerator — the host
reads line-aligned chunks and cudf tokenizes + converts on device
(GpuBatchScanExec.scala:322-520, device parse under the semaphore at
:474-502). The TPU-native split keeps the same control/data-plane shape as
the parquet device decoder (io/parquet_device.py):

- HOST (control plane, vectorized numpy): one pass over the raw bytes to
  find field boundaries (separator/newline positions -> a (rows, cols)
  offset table). No value is converted on the host.
- DEVICE (data plane): raw bytes + per-field (start, len) upload once; a
  jitted kernel gathers up to MAXW bytes per field and folds digits into
  int64 — the conversion FLOPs happen on the accelerator.

Scope: integral columns (INT8..INT64); DATE (strict ISO YYYY-MM-DD) and
TIMESTAMP (ISO date[ T]HH:MM:SS[.f{1,6}]<zone>, zone required — the host
oracle reads timestamp[us, tz=UTC]) columns; and —
where the backend has f64 — FLOAT32/FLOAT64 columns with plain decimal
literals (sign, digits, one dot; <= 15 significant digits and <= 22
fractional digits, so the single f64 division is correctly rounded and
bit-identical to the host parser; exponents/inf/nan take the host path).
Quoted fields are handled
structurally (quote-aware boundary scan + quote stripping; escaped ""
unescapes via a host control-plane rewrite before upload). Regular
column count per line. Empty fields are NULL
(pyarrow's strings_can_be_null oracle behavior); malformed digits abandon
the device path for the split so both engines behave identically.
"""

from __future__ import annotations

import functools
from typing import List, Optional, Tuple

import numpy as np

from spark_rapids_tpu import _jax_setup  # noqa: F401
import jax
import jax.numpy as jnp

from spark_rapids_tpu.columnar.dtypes import DataType

MAXW = 20   # int64: up to 19 digits + sign
MAXW_F = 24  # float: sign + 15 digits + dot (+ slack)

_NL = 0x0A
_CR = 0x0D
_QUOTE = 0x22
_MINUS = 0x2D
_PLUS = 0x2B
_ZERO = 0x30

INTEGRAL = (DataType.INT8, DataType.INT16, DataType.INT32, DataType.INT64)
FLOATS = (DataType.FLOAT32, DataType.FLOAT64)
_DOT = 0x2E


class FieldTable:
    """Host-side field offset table for one CSV file."""

    __slots__ = ("raw", "starts", "lens", "num_rows", "header_names",
                 "_dev_raw")

    def __init__(self, raw, starts, lens, num_rows, header_names):
        self.raw = raw              # np.uint8 [nbytes]
        self.starts = starts        # np.int32 [rows, cols]
        self.lens = lens            # np.int32 [rows, cols]
        self.num_rows = num_rows
        self.header_names = header_names  # list[str] | None
        self._dev_raw = None

    def device_raw(self):
        """The raw bytes on device — uploaded once per file, shared by
        every column decode."""
        if self._dev_raw is None:
            self._dev_raw = jnp.asarray(self.raw)
        return self._dev_raw


def plan_fields(data: bytes, ncols: int, header: bool,
                sep: str = ",") -> Optional[FieldTable]:
    """Field-boundary scan (native single-pass when built, numpy multi-pass
    fallback). None -> structure too complex for the device path (quotes,
    ragged rows): caller host-falls-back."""
    if not data or len(data) > 2 ** 31 - 2:
        return None
    sep_b = ord(sep)
    if sep_b in (_NL, _CR, _QUOTE):
        return None
    if b'"' in data:
        # quote-aware boundary scan lives only in the numpy path
        res = _plan_fields_quoted(data, ncols, sep_b)
    else:
        res = _plan_fields_native(data, ncols, sep_b)
        if res is NotImplemented:
            res = _plan_fields_py(data, ncols, sep_b)
    if res is None:
        return None
    arr, starts, lens, n_lines = res
    return _finish_plan(data, arr, starts, lens, n_lines, ncols, header)


def _plan_fields_native(data: bytes, ncols: int, sep_b: int):
    """Single native sweep (srt_csv_plan). NotImplemented -> no library."""
    import ctypes

    from spark_rapids_tpu.native import get_lib

    lib = get_lib()
    if lib is None:
        return NotImplemented
    est = data.count(b"\n") + (0 if data.endswith(b"\n") else 1)
    if est <= 0:
        est = 1
    starts = np.empty(est * ncols, dtype=np.int32)
    lens = np.empty(est * ncols, dtype=np.int32)
    rc = lib.srt_csv_plan(
        data, len(data), sep_b, ncols,
        starts.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        lens.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)), est)
    if rc < 0:
        return None
    n_lines = int(rc)
    arr = np.frombuffer(data, dtype=np.uint8)
    return (arr, starts[:n_lines * ncols].reshape(n_lines, ncols),
            lens[:n_lines * ncols].reshape(n_lines, ncols), n_lines)


def _plan_fields_quoted(data: bytes, ncols: int, sep_b: int):
    """Quote-aware boundary scan (reference: cudf's quoted-field tokenizer
    behind GpuBatchScanExec.scala:322-520). Separators/newlines inside
    quotes are not boundaries; fully-quoted fields strip their quotes;
    escaped "" pairs inside quoted fields unescape via a host rewrite
    (second quote of each pair deleted, spans remapped to the rewritten
    buffer). Stray unpaired quotes -> None (host fallback)."""
    arr = np.frombuffer(data, dtype=np.uint8)
    is_q = arr == _QUOTE
    # inside[i]: byte i lies inside a quoted section (after an odd number
    # of quotes). A quote toggles state AFTER itself.
    inside = (np.cumsum(is_q) - is_q) % 2 == 1
    is_bound = ((arr == sep_b) | (arr == _NL)) & ~inside & ~is_q
    bpos = np.flatnonzero(is_bound).astype(np.int64)
    if arr[-1] != _NL:
        bpos = np.append(bpos, len(arr))
    n_fields = len(bpos)
    if n_fields % ncols != 0:
        return None
    n_lines = n_fields // ncols
    ends = bpos.reshape(n_lines, ncols)
    interior = ends[:, :-1].ravel()
    if interior.size and (arr[interior] == _NL).any():
        return None
    line_final = ends[:, -1]
    real = line_final[line_final < len(arr)]
    if real.size and (arr[real] != _NL).any():
        return None
    starts = np.empty_like(ends)
    starts[:, 0] = np.concatenate(([0], ends[:-1, -1] + 1))
    starts[:, 1:] = ends[:, :-1] + 1
    lens = ends - starts
    last_ends = ends[:, -1]
    has_cr = np.zeros(n_lines, dtype=bool)
    nonempty = lens[:, -1] > 0
    prev = np.clip(last_ends - 1, 0, len(arr) - 1)
    has_cr[nonempty] = arr[prev[nonempty]] == _CR
    lens[:, -1] -= has_cr.astype(np.int32)
    # strip full surrounding quotes; any other quote layout -> fallback
    fs = starts.ravel()
    fl = lens.ravel()
    first_q = np.zeros(fs.shape, dtype=bool)
    last_q = np.zeros(fs.shape, dtype=bool)
    nz = fl >= 2
    first_q[nz] = arr[fs[nz]] == _QUOTE
    last_q[nz] = arr[np.clip(fs[nz] + fl[nz] - 1, 0,
                             len(arr) - 1)] == _QUOTE
    quoted = first_q & last_q
    # escaped "" pairs inside quoted fields: the first quote of a pair is
    # seen while the pre-state is INSIDE (the toggle math already kept
    # boundaries correct across the zero-width out-in flip)
    pre_inside = inside
    nxt_q = np.zeros_like(is_q)
    nxt_q[:-1] = is_q[1:]
    pair_first = is_q & nxt_q & pre_inside
    # per-field quote / escape-pair counts via cum-count differences
    qcum = np.concatenate(([0], np.cumsum(is_q)))
    ecum = np.concatenate(([0], np.cumsum(pair_first)))
    lo = np.clip(fs, 0, len(arr))
    hi = np.clip(fs + fl, 0, len(arr))
    qcnt = qcum[hi] - qcum[lo]
    ecnt = ecum[hi] - ecum[lo]
    # quoted fields: outer pair + every interior quote in an escape pair;
    # bare fields: no quotes at all. Anything else -> host fallback.
    if not np.all((quoted & (qcnt == 2 + 2 * ecnt))
                  | (~quoted & (qcnt == 0))):
        return None
    fs = fs + quoted.astype(np.int64)
    fl = fl - 2 * quoted.astype(np.int64)
    if pair_first.any():
        # unescape: delete the SECOND quote of each pair and remap spans
        # (host control-plane rewrite, mirroring cudf's unescape pass)
        second = np.zeros_like(pair_first)
        second[1:] = pair_first[:-1]
        delcum = np.concatenate(([0], np.cumsum(second)))
        fl = fl - (delcum[np.clip(fs + fl, 0, len(arr))]
                   - delcum[np.clip(fs, 0, len(arr))])
        fs = fs - delcum[np.clip(fs, 0, len(arr))]
        arr = arr[~second]
    return (arr, fs.reshape(n_lines, ncols).astype(np.int64),
            fl.reshape(n_lines, ncols).astype(np.int64), n_lines)


def _plan_fields_py(data: bytes, ncols: int, sep_b: int):
    """Vectorized numpy fallback for srt_csv_plan."""
    arr = np.frombuffer(data, dtype=np.uint8)
    if (arr == _QUOTE).any():
        return None
    is_bound = (arr == sep_b) | (arr == _NL)
    bpos = np.flatnonzero(is_bound).astype(np.int64)
    # virtual trailing newline when the file doesn't end with one
    if arr[-1] != _NL:
        bpos = np.append(bpos, len(arr))
    n_fields = len(bpos)
    if n_fields % ncols != 0:
        return None
    n_lines = n_fields // ncols
    ends = bpos.reshape(n_lines, ncols)
    # every line's last boundary must be a newline (or the virtual EOF one),
    # and no interior boundary may be a newline — else the reshape is wrong
    interior = ends[:, :-1].ravel()
    if interior.size and (arr[interior] == _NL).any():
        return None
    # ...and every line-final boundary must be a newline (the last may be
    # the virtual EOF boundary)
    line_final = ends[:, -1]
    real = line_final[line_final < len(arr)]
    if real.size and (arr[real] != _NL).any():
        return None
    starts = np.empty_like(ends)
    starts[:, 0] = np.concatenate(([0], ends[:-1, -1] + 1))
    starts[:, 1:] = ends[:, :-1] + 1
    lens = ends - starts
    # tolerate CRLF: trim a trailing \r from the last field of each line
    last_ends = ends[:, -1]
    has_cr = np.zeros(n_lines, dtype=bool)
    nonempty = lens[:, -1] > 0
    prev = np.clip(last_ends - 1, 0, len(arr) - 1)
    has_cr[nonempty] = arr[prev[nonempty]] == _CR
    lens[:, -1] -= has_cr.astype(np.int32)
    return arr, starts, lens, n_lines


def _finish_plan(data: bytes, arr, starts, lens, n_lines: int, ncols: int,
                 header: bool) -> Optional[FieldTable]:
    if ncols == 1:
        # blank lines are SKIPPED lines, not NULL rows (pyarrow's
        # ignore_empty_lines oracle behavior); only reachable for
        # single-column files — a blank line is ragged otherwise
        keep = lens[:, 0] > 0
        if header and n_lines >= 1:
            keep[0] = True  # never drop the header row
        if not keep.all():
            starts = starts[keep]
            lens = lens[keep]
            n_lines = int(keep.sum())
    header_names = None
    if header:
        if n_lines < 1:
            return None
        # slice from `arr`, not `data`: the quoted planner's unescape pass
        # may have rewritten the buffer and remapped starts/lens to it
        header_names = [
            bytes(arr[starts[0, j]:starts[0, j] + lens[0, j]]).decode(
                "utf-8", errors="replace").strip()
            for j in range(ncols)]
        starts = starts[1:]
        lens = lens[1:]
        n_lines -= 1
    return FieldTable(arr, np.ascontiguousarray(starts, dtype=np.int32),
                      np.ascontiguousarray(lens, dtype=np.int32),
                      n_lines, header_names)


@functools.partial(jax.jit, static_argnums=(3,))
def _parse_int_kernel(raw, starts, lens, maxw: int):
    """Fold up to `maxw` gathered bytes per field into int64. Returns
    (values, validity, malformed): empty fields are NULL; anything else the
    strict grammar ('-' then digits, in int64 range — what the pyarrow host
    oracle accepts) does not cover is MALFORMED, and the caller abandons the
    device path for the whole split so both engines raise identically."""
    idx = starts[:, None].astype(jnp.int32) + \
        jnp.arange(maxw, dtype=jnp.int32)[None, :]
    ch = raw[jnp.clip(idx, 0, raw.shape[0] - 1)]
    inb = jnp.arange(maxw, dtype=jnp.int32)[None, :] < lens[:, None]
    ch = jnp.where(inb, ch, 0)
    first = ch[:, 0]
    neg = first == _MINUS
    skip = neg.astype(jnp.int32)  # '+' is malformed, matching pyarrow
    digits = ch.astype(jnp.int32) - _ZERO
    isdig = (digits >= 0) & (digits <= 9)
    pos = jnp.arange(maxw, dtype=jnp.int32)[None, :]
    digpos = (pos >= skip[:, None]) & inb
    all_digits = jnp.all(jnp.where(digpos, isdig, True), axis=1)
    ndig = lens - skip
    ok = all_digits & (ndig > 0) & (lens <= maxw)
    val = jnp.zeros(starts.shape[0], dtype=jnp.int64)
    imax = jnp.int64(np.iinfo(np.int64).max)
    overflow = jnp.zeros(starts.shape[0], dtype=bool)
    for i in range(maxw):
        d = jnp.where(isdig[:, i], digits[:, i], 0).astype(jnp.int64)
        # detect BEFORE the fold can wrap: val*10 + d > int64max
        overflow = overflow | (digpos[:, i] & (val > (imax - d) // 10))
        val = jnp.where(digpos[:, i], val * 10 + d, val)
    val = jnp.where(neg, -val, val)
    nonempty = lens > 0
    validity = ok & nonempty & ~overflow
    malformed = nonempty & ~validity
    return jnp.where(validity, val, 0), validity, malformed


@functools.partial(jax.jit, static_argnums=(3,))
def _parse_float_kernel(raw, starts, lens, maxw: int):
    """Plain decimal floats: [-] digits [. digits], <= 15 significant
    digits and <= 22 fractional digits. The value is mantissa / 10^scale in
    ONE f64 division — both operands are exact, so the result is the
    correctly-rounded double of the literal, bit-identical to the host
    parser. Exponents / inf / nan / longer literals are MALFORMED (the
    caller host-falls-back for the split; the host parses them fine)."""
    idx = starts[:, None].astype(jnp.int32) + \
        jnp.arange(maxw, dtype=jnp.int32)[None, :]
    ch = raw[jnp.clip(idx, 0, raw.shape[0] - 1)]
    inb = jnp.arange(maxw, dtype=jnp.int32)[None, :] < lens[:, None]
    ch = jnp.where(inb, ch, 0)
    neg = ch[:, 0] == _MINUS
    skip = neg.astype(jnp.int32)
    digits = ch.astype(jnp.int32) - _ZERO
    isdig = (digits >= 0) & (digits <= 9)
    isdot = ch == _DOT
    pos = jnp.arange(maxw, dtype=jnp.int32)[None, :]
    body = (pos >= skip[:, None]) & inb
    # exactly 0 or 1 dots; everything else in the body must be a digit
    ndots = jnp.sum((body & isdot).astype(jnp.int32), axis=1)
    ok_chars = jnp.all(jnp.where(body, isdig | isdot, True), axis=1)
    dotpos = jnp.argmax(body & isdot, axis=1).astype(jnp.int32)
    has_dot = ndots == 1
    # fractional digit count; mantissa = all digits folded in order
    frac = jnp.where(has_dot, lens - 1 - dotpos, 0)
    ndig = lens - skip - has_dot.astype(jnp.int32)
    m = jnp.zeros(starts.shape[0], dtype=jnp.int64)
    for i in range(maxw):
        d = jnp.where(isdig[:, i], digits[:, i], 0).astype(jnp.int64)
        m = jnp.where(body[:, i] & isdig[:, i], m * 10 + d, m)
    ok = ok_chars & (ndots <= 1) & (ndig > 0) & (ndig <= 15) & \
        (frac >= 0) & (frac <= 22) & (lens <= maxw)
    p10 = jnp.asarray([10.0 ** k for k in range(23)], dtype=jnp.float64)
    val = m.astype(jnp.float64) / p10[jnp.clip(frac, 0, 22)]
    val = jnp.where(neg, -val, val)
    nonempty = lens > 0
    validity = ok & nonempty
    malformed = nonempty & ~validity
    return jnp.where(validity, val, 0.0), validity, malformed


def decode_float_column(table: FieldTable, col_idx: int, dtype: DataType,
                        cap: int):
    """Parse one float column on device, padded to `cap` rows (same
    contract as decode_int_column)."""
    from spark_rapids_tpu.columnar.batch import physical_np_dtype

    n = table.num_rows
    starts = np.zeros(cap, dtype=np.int32)
    lens = np.zeros(cap, dtype=np.int32)
    starts[:n] = table.starts[:, col_idx]
    lens[:n] = table.lens[:, col_idx]
    row_mask = jnp.arange(cap) < n
    val, validity, malformed = _parse_float_kernel(table.device_raw(),
                                                   jnp.asarray(starts),
                                                   jnp.asarray(lens),
                                                   MAXW_F)
    malformed = malformed & row_mask
    npdt = physical_np_dtype(dtype)
    if npdt != np.dtype(np.float64):
        val = val.astype(npdt)
    return val, validity & row_mask, jnp.any(malformed)


def decode_int_column(table: FieldTable, col_idx: int, dtype: DataType,
                      cap: int):
    """Parse one integral column on device, padded to `cap` rows. Returns
    (data, validity, any_malformed) where any_malformed is a DEVICE bool
    scalar — the caller batches the malformed checks of every column into
    ONE host sync (each sync is a host round trip) and falls back to the host parser if any is set, so both
    engines raise the same error on bad fields."""
    from spark_rapids_tpu.columnar.batch import physical_np_dtype

    n = table.num_rows
    starts = np.zeros(cap, dtype=np.int32)
    lens = np.zeros(cap, dtype=np.int32)
    starts[:n] = table.starts[:, col_idx]
    lens[:n] = table.lens[:, col_idx]
    row_mask = jnp.arange(cap) < n
    val, validity, malformed = _parse_int_kernel(table.device_raw(),
                                                 jnp.asarray(starts),
                                                 jnp.asarray(lens), MAXW)
    malformed = malformed & row_mask
    npdt = physical_np_dtype(dtype)
    if npdt != np.dtype(np.int64):
        info = np.iinfo(npdt)
        in_range = (val >= info.min) & (val <= info.max)
        malformed = malformed | (validity & ~in_range & row_mask)
        val = jnp.where(in_range, val, 0).astype(npdt)
    return val, validity & row_mask, jnp.any(malformed)


@functools.partial(jax.jit, static_argnums=(3,))
def _parse_date_kernel(raw, starts, lens, maxw: int):
    """Strict ISO 'YYYY-MM-DD' (what the pyarrow host oracle accepts for
    date32) -> epoch days on device. Invalid layouts AND invalid civil
    dates (2023-02-30) are MALFORMED -> the caller abandons the device path
    so the host parser raises the identical error."""
    from spark_rapids_tpu.ops import datetimeops as DT

    idx = starts[:, None].astype(jnp.int32) + \
        jnp.arange(maxw, dtype=jnp.int32)[None, :]
    ch = raw[jnp.clip(idx, 0, raw.shape[0] - 1)]
    inb = jnp.arange(maxw, dtype=jnp.int32)[None, :] < lens[:, None]
    ch = jnp.where(inb, ch, 0)
    digits = ch.astype(jnp.int32) - _ZERO
    isdig = (digits >= 0) & (digits <= 9)
    layout = jnp.ones(starts.shape[0], dtype=bool)
    for i in (0, 1, 2, 3, 5, 6, 8, 9):
        layout = layout & isdig[:, i]
    layout = layout & (ch[:, 4] == _MINUS) & (ch[:, 7] == _MINUS)
    layout = layout & (lens == 10)
    y = (digits[:, 0] * 1000 + digits[:, 1] * 100
         + digits[:, 2] * 10 + digits[:, 3])
    m = digits[:, 5] * 10 + digits[:, 6]
    d = digits[:, 8] * 10 + digits[:, 9]
    days = DT.days_from_civil(jnp, y, m, d)
    ry, rm, rd = DT.civil_from_days(jnp, days)
    civil_ok = (ry == y) & (rm == m) & (rd == d)
    nonempty = lens > 0
    validity = layout & civil_ok & nonempty
    malformed = nonempty & ~validity
    return (jnp.where(validity, days, 0).astype(jnp.int32), validity,
            malformed)


@functools.partial(jax.jit, static_argnums=(3,))
def _parse_timestamp_kernel(raw, starts, lens, maxw: int):
    """ISO zoned timestamps on device:
    'YYYY-MM-DD[ T]HH:MM:SS[.f{1,6}]<zone>' with zone = 'Z' | ±HH |
    ±HHMM | ±HH:MM -> epoch micros. The host oracle reads TIMESTAMP CSV
    columns as arrow timestamp[us, tz=UTC], which REQUIRES a zone offset
    in the text — naive timestamps are a conversion error there, so here
    they are MALFORMED (whole split -> host, which raises identically)."""
    from spark_rapids_tpu.ops import datetimeops as DT

    n = starts.shape[0]
    idx = starts[:, None].astype(jnp.int32) + \
        jnp.arange(maxw, dtype=jnp.int32)[None, :]
    ch = raw[jnp.clip(idx, 0, raw.shape[0] - 1)]
    inb = jnp.arange(maxw, dtype=jnp.int32)[None, :] < lens[:, None]
    ch = jnp.where(inb, ch, 0)
    digits = ch.astype(jnp.int32) - _ZERO
    isdig = (digits >= 0) & (digits <= 9)
    date_ok = lens >= 19
    for i in (0, 1, 2, 3, 5, 6, 8, 9):
        date_ok = date_ok & isdig[:, i]
    date_ok = date_ok & (ch[:, 4] == _MINUS) & (ch[:, 7] == _MINUS)
    y = (digits[:, 0] * 1000 + digits[:, 1] * 100
         + digits[:, 2] * 10 + digits[:, 3])
    m = digits[:, 5] * 10 + digits[:, 6]
    d = digits[:, 8] * 10 + digits[:, 9]
    days = DT.days_from_civil(jnp, y, m, d)
    ry, rm, rd = DT.civil_from_days(jnp, days)
    civil_ok = (ry == y) & (rm == m) & (rd == d)

    time_ok = jnp.ones(n, dtype=bool)
    for i in (11, 12, 14, 15, 17, 18):
        time_ok = time_ok & isdig[:, i]
    sep = ch[:, 10]
    time_ok = time_ok & ((sep == 0x20) | (sep == 0x54))  # ' ' | 'T'
    time_ok = time_ok & (ch[:, 13] == 0x3A) & (ch[:, 16] == 0x3A)
    hh = digits[:, 11] * 10 + digits[:, 12]
    mi = digits[:, 14] * 10 + digits[:, 15]
    ss = digits[:, 17] * 10 + digits[:, 18]
    time_ok = time_ok & (hh < 24) & (mi < 60) & (ss < 60)

    # fraction: optional '.' at 19 followed by a 1..6-digit run
    has_dot = (lens > 19) & (ch[:, 19] == _DOT)
    fd = jnp.zeros(n, jnp.int32)
    going = has_dot
    frac = jnp.zeros(n, dtype=jnp.int64)
    for i in range(6):
        p = 20 + i
        going = going & (jnp.int32(p) < lens) & isdig[:, p]
        fd = fd + going.astype(jnp.int32)
        frac = jnp.where(going, frac * 10 + digits[:, p], frac)
    frac_ok = ~has_dot | (fd >= 1)
    p10 = jnp.asarray([10 ** k for k in range(7)], dtype=jnp.int64)
    frac = frac * p10[jnp.clip(6 - fd, 0, 6)]

    # zone suffix starts right after seconds or fraction
    zstart = jnp.where(has_dot, 20 + fd, 19)
    zlen = lens - zstart

    def at(k):
        pos = jnp.clip(zstart + k, 0, maxw - 1)
        v = jnp.take_along_axis(ch, pos[:, None], axis=1)[:, 0]
        return jnp.where(zstart + k < lens, v, 0).astype(jnp.int32)

    def dg(k):
        return at(k) - _ZERO

    def isd(k):
        v = dg(k)
        return (v >= 0) & (v <= 9)

    sign_ch = at(0)
    signed = (sign_ch == _PLUS) | (sign_ch == _MINUS)
    z_utc = (zlen == 1) & (at(0) == 0x5A)  # 'Z'
    z_hh = (zlen == 3) & signed & isd(1) & isd(2)
    z_hhmm = (zlen == 5) & signed & isd(1) & isd(2) & isd(3) & isd(4)
    z_colon = (zlen == 6) & signed & isd(1) & isd(2) & (at(3) == 0x3A) \
        & isd(4) & isd(5)
    off_h = dg(1) * 10 + dg(2)
    off_m = jnp.where(z_hhmm, dg(3) * 10 + dg(4),
                      jnp.where(z_colon, dg(4) * 10 + dg(5), 0))
    zone_ok = z_utc | ((z_hh | z_hhmm | z_colon)
                       & (off_h < 24) & (off_m < 60))
    off_us = jnp.where(z_utc, 0,
                       (off_h * 3600 + off_m * 60).astype(jnp.int64)
                       * 1_000_000)
    off_us = jnp.where(sign_ch == _MINUS, -off_us, off_us)

    ok = date_ok & civil_ok & time_ok & frac_ok & zone_ok
    us = (days.astype(jnp.int64) * 86_400_000_000
          + (hh * 3600 + mi * 60 + ss).astype(jnp.int64) * 1_000_000
          + frac - off_us)
    nonempty = lens > 0
    validity = ok & nonempty
    malformed = nonempty & ~validity
    return jnp.where(validity, us, 0), validity, malformed


MAXW_TS = 32  # 19 + .ffffff (7) + ±HH:MM (6)


def _decode_with_kernel(kernel, maxw: int, table: FieldTable, col_idx: int,
                        cap: int):
    """Shared (starts, lens) padding + row/malformed masking around a
    field-parse kernel (same contract as decode_int_column)."""
    n = table.num_rows
    starts = np.zeros(cap, dtype=np.int32)
    lens = np.zeros(cap, dtype=np.int32)
    starts[:n] = table.starts[:, col_idx]
    lens[:n] = table.lens[:, col_idx]
    row_mask = jnp.arange(cap) < n
    val, validity, malformed = kernel(table.device_raw(),
                                      jnp.asarray(starts),
                                      jnp.asarray(lens), maxw)
    return val, validity & row_mask, jnp.any(malformed & row_mask)


def decode_date_column(table: FieldTable, col_idx: int, cap: int):
    return _decode_with_kernel(_parse_date_kernel, 10, table, col_idx, cap)


def decode_timestamp_column(table: FieldTable, col_idx: int, cap: int):
    return _decode_with_kernel(_parse_timestamp_kernel, MAXW_TS, table,
                               col_idx, cap)


def _null_sentinels() -> List[bytes]:
    """pyarrow's default CSV null spellings, read at runtime so the device
    path can never drift from the host oracle's list (the boundary scan
    strips quotes, and quoted sentinels are null too —
    quoted_strings_can_be_null defaults True)."""
    global _NULL_SENTINELS
    if _NULL_SENTINELS is None:
        import pyarrow.csv as pc

        _NULL_SENTINELS = [s.encode() for s in
                           pc.ConvertOptions().null_values if s]
    return _NULL_SENTINELS


_NULL_SENTINELS: Optional[List[bytes]] = None


@functools.partial(jax.jit, static_argnums=(3,))
def _match_sentinels_kernel(raw, starts, lens, sentinels: Tuple[bytes, ...]):
    """Per field: does it equal any null sentinel? (Empty fields are handled
    by the caller — lens == 0.)"""
    smax = max(len(s) for s in sentinels)
    idx = starts[:, None].astype(jnp.int32) + \
        jnp.arange(smax, dtype=jnp.int32)[None, :]
    ch = raw[jnp.clip(idx, 0, raw.shape[0] - 1)]
    inb = jnp.arange(smax, dtype=jnp.int32)[None, :] < lens[:, None]
    ch = jnp.where(inb, ch, 0)
    is_null = jnp.zeros(starts.shape[0], dtype=bool)
    for s in sentinels:
        pat = jnp.asarray(np.frombuffer(s.ljust(smax, b"\0"), np.uint8))
        is_null = is_null | ((lens == len(s)) &
                             jnp.all(ch == pat[None, :], axis=1))
    return is_null


def decode_string_column(table: FieldTable, col_idx: int, cap: int):
    """Build a device STRING column straight from the boundary plan: the
    (start, len) tables plus the already-uploaded raw bytes ARE the column —
    one fused gather packs the field bytes contiguously (reference: cudf
    parses the full CSV type matrix on device, GpuBatchScanExec.scala:
    322-520). Null semantics match the host oracle's strings_can_be_null
    list via an on-device sentinel match. Returns a ColumnVector; total
    byte size is host-known, so there is no device sync."""
    from spark_rapids_tpu.columnar.batch import (
        ColumnVector,
        bucket_capacity,
    )
    from spark_rapids_tpu.columnar.strings import build_from_plan

    n = table.num_rows
    starts = np.zeros(cap, dtype=np.int32)
    lens = np.zeros(cap, dtype=np.int32)
    starts[:n] = table.starts[:, col_idx]
    lens[:n] = table.lens[:, col_idx]
    total = int(lens.astype(np.int64).sum())
    raw = table.device_raw()
    dstarts = jnp.asarray(starts)
    dlens = jnp.asarray(lens)
    row_mask = jnp.arange(cap) < n
    is_null = _match_sentinels_kernel(raw, dstarts, dlens,
                                      tuple(_null_sentinels()))
    validity = row_mask & (dlens > 0) & ~is_null
    out_len = jnp.where(validity, dlens, 0)
    byte_cap = bucket_capacity(max(total, 8))
    out_bytes, offsets = build_from_plan(
        [raw], jnp.zeros((cap,), jnp.int32), dstarts, out_len, byte_cap)
    return ColumnVector(DataType.STRING, out_bytes, validity, offsets)


def device_parseable(dtype: DataType) -> bool:
    if dtype in INTEGRAL:
        return True
    if dtype is DataType.STRING:
        return True
    if dtype in (DataType.DATE, DataType.TIMESTAMP):
        return True
    if dtype is DataType.FLOAT64:
        # the exact-rounding argument needs a real f64 division on device.
        # FLOAT32 stays on the host: parse-f64-then-narrow double-rounds,
        # which can differ from Arrow's direct decimal->float32 conversion
        # on midpoint-adjacent literals.
        from spark_rapids_tpu.columnar.batch import device_float64_supported

        return device_float64_supported()
    return False


def decode_column(table: FieldTable, col_idx: int, dtype: DataType,
                  cap: int):
    if dtype in FLOATS:
        return decode_float_column(table, col_idx, dtype, cap)
    if dtype is DataType.DATE:
        return decode_date_column(table, col_idx, cap)
    if dtype is DataType.TIMESTAMP:
        return decode_timestamp_column(table, col_idx, cap)
    return decode_int_column(table, col_idx, dtype, cap)


def eligible_attrs(attrs, header_names: Optional[List[str]],
                   attr_names_in_file_order: List[str]) -> dict:
    """Map attr name -> file column index for device-parseable columns."""
    order = header_names if header_names is not None \
        else attr_names_in_file_order
    out = {}
    for a in attrs:
        if device_parseable(a.data_type) and a.name in order:
            out[a.name] = order.index(a.name)
    return out
