"""Cast (reference: GpuCast.scala, 867 LoC — per-direction compat flags,
date/timestamp special cases; conf gates RapidsConf.scala:393-425).

Device-supported directions (round 1): numeric<->numeric, bool<->numeric,
date<->timestamp, timestamp<->long, int->string, date->string. String->numeric
and float->string run on the CPU path (gated by the same conf keys the
reference uses); the meta layer tags them for fallback on device.
"""

from __future__ import annotations

import numpy as np

from spark_rapids_tpu.columnar.dtypes import DataType, DecimalType, is_decimal
from spark_rapids_tpu.ops import decimal_util as DU
from spark_rapids_tpu.ops.base import UnaryExpression
from spark_rapids_tpu.ops.values import ColV

MICROS_PER_DAY = 86_400_000_000
MICROS_PER_SEC = 1_000_000


class Cast(UnaryExpression):
    def __init__(self, child, to_type: DataType, ansi: bool = False):
        super().__init__(child)
        self.to_type = to_type
        self.ansi = ansi

    def with_children(self, new_children):
        return Cast(new_children[0], self.to_type, self.ansi)

    @property
    def data_type(self):
        return self.to_type

    def _fingerprint_extra(self):
        # ansi changes compiled behavior (deferred error flags), so it must
        # key the jit cache
        return f"->{self.to_type.name};ansi={int(self.ansi)};"

    def result_vrange(self, v):
        """Integral widening/identity casts preserve the child's value
        bounds; an int->int cast to a *narrower* type may wrap, so only
        claim a range when the child provably fits the target."""
        frm, to = self.child.data_type, self.to_type
        if not (frm.is_integral and to.is_integral):
            return None
        from spark_rapids_tpu.ops.base import val_interval

        iv = val_interval(v)
        if iv is None:
            info = np.iinfo(frm.to_np())
            iv = (int(info.min), int(info.max))
        tinfo = np.iinfo(to.to_np())
        if iv[0] >= int(tinfo.min) and iv[1] <= int(tinfo.max):
            return iv
        return None

    # which (from, to) directions the device kernel handles
    @staticmethod
    def device_supported(frm, to) -> bool:
        if frm == to:
            return True
        numeric_ish = {DataType.BOOL, DataType.INT8, DataType.INT16,
                       DataType.INT32, DataType.INT64, DataType.FLOAT32,
                       DataType.FLOAT64}
        if is_decimal(frm):
            # decimal -> numeric/decimal is pure int64 math on device
            return is_decimal(to) or to in numeric_ish
        if is_decimal(to):
            # float -> decimal stays on the host oracle: Spark rounds via the
            # double's shortest decimal repr (BigDecimal.valueOf), which has
            # no jittable equivalent (cf. the reference gating float casts,
            # RapidsConf.scala:393-425)
            return frm in numeric_ish and not frm.is_floating
        if frm in numeric_ish and to in numeric_ish:
            return True
        if frm is DataType.DATE and to in (DataType.TIMESTAMP, DataType.STRING,
                                           DataType.INT32):
            return True
        if frm is DataType.TIMESTAMP and to in (DataType.DATE, DataType.INT64,
                                                DataType.STRING):
            return True
        if frm in (DataType.BOOL, DataType.INT8, DataType.INT16,
                   DataType.INT32, DataType.INT64) and to is DataType.STRING:
            return True
        if frm is DataType.INT64 and to is DataType.TIMESTAMP:
            return True
        return False

    def do_columnar(self, ctx, v):
        frm, to = self.child.data_type, self.to_type
        if frm == to:
            return v.data if to is not DataType.STRING else v
        if to is DataType.STRING:
            return self._to_string(ctx, v, frm)
        if frm is DataType.STRING:
            return self._from_string(ctx, v, to)
        return self._numeric_datetime(ctx, v, frm, to)

    # -- decimal --------------------------------------------------------------
    def _decimal(self, ctx, v, frm, to):
        """Casts with a decimal endpoint; overflow -> SQL NULL (non-ANSI) or
        raises (ANSI), matching Spark's Decimal.changePrecision."""
        xp = ctx.xp
        data = v.data
        if is_decimal(frm) and is_decimal(to):
            out, ok1 = DU.rescale(xp, data, frm.scale, to.scale)
            out, ok2 = DU.fit_precision(xp, out, to.precision)
            return self._dec_result(ctx, v, to, out, ok1 & ok2)
        if is_decimal(frm):
            if to is DataType.BOOL:
                return data != 0
            if to.is_floating:
                npdt = ctx.np_dtype(to)
                return data.astype(npdt) / npdt.type(float(DU.POW10[frm.scale]))
            if to.is_integral:
                # truncate toward zero, overflow -> null
                q = xp.abs(data) // DU.POW10[frm.scale]
                q = xp.where(data < 0, -q, q)
                info = np.iinfo(to.to_np())
                ok = (q >= info.min) & (q <= info.max)
                out = xp.where(ok, q, 0).astype(ctx.np_dtype(to))
                return self._dec_result(ctx, v, to, out, ok)
            raise NotImplementedError(f"cast {frm} -> {to}")
        # numeric -> decimal
        if frm is DataType.BOOL:
            out = data.astype(np.int64) * DU.POW10[to.scale]
            return self._dec_result(ctx, v, to, out,
                                    xp.ones_like(out, dtype=bool))
        if frm.is_integral:
            out, ok1 = DU.checked_mul_pow10(xp, data.astype(np.int64),
                                            to.scale)
            out, ok2 = DU.fit_precision(xp, out, to.precision)
            return self._dec_result(ctx, v, to, out, ok1 & ok2)
        if frm.is_floating:
            if ctx.is_device:
                # approximate path (direct kernel use only; the plan layer
                # keeps this direction on the host oracle): binary-float
                # HALF_UP at target scale; NaN/Inf/overflow -> null
                scaled = data * float(DU.POW10[to.scale])
                finite = xp.isfinite(scaled)
                limit = float(DU.bound(to.precision))
                ok = finite & (xp.abs(scaled) <= limit)
                half = xp.where(scaled >= 0, 0.5, -0.5)
                out = xp.where(ok, scaled + half, 0.0).astype(np.int64)
                out, ok2 = DU.fit_precision(xp, out, to.precision)
                return self._dec_result(ctx, v, to, out, ok & ok2)
            # host: Spark-exact — round the double's shortest decimal repr
            # (BigDecimal.valueOf semantics), HALF_UP at target scale
            out = np.zeros(len(data), dtype=np.int64)
            ok = np.zeros(len(data), dtype=bool)
            limit = int(DU.bound(to.precision))
            for i, x in enumerate(data):
                x = float(x)
                if not np.isfinite(x):
                    continue
                try:
                    u = DU.to_unscaled(x, to.scale)
                except OverflowError:
                    continue
                if abs(u) <= limit:
                    out[i] = u
                    ok[i] = True
            return self._dec_result(ctx, v, to, out, ok)
        raise NotImplementedError(f"cast {frm} -> {to}")

    def _dec_result(self, ctx, v, to, out, ok):
        if self.ansi:
            overflow = v.validity & ~ok
            if not ctx.is_device and bool(np.asarray(overflow).any()):
                raise ArithmeticError(
                    f"cast to {getattr(to, 'value', to)} overflowed (ANSI)")
        return ColV(to, out, ok)

    # -- numeric / datetime --------------------------------------------------
    def _numeric_datetime(self, ctx, v, frm, to):
        xp = ctx.xp
        if is_decimal(frm) or is_decimal(to):
            return self._decimal(ctx, v, frm, to)
        data = v.data
        npdt = ctx.np_dtype(to)
        if frm is DataType.DATE and to is DataType.TIMESTAMP:
            return data.astype(np.int64) * MICROS_PER_DAY
        if frm is DataType.TIMESTAMP and to is DataType.DATE:
            return (data // MICROS_PER_DAY).astype(np.int32)
        if frm is DataType.TIMESTAMP and to is DataType.INT64:
            # spark: epoch seconds, floored
            return data // MICROS_PER_SEC
        if frm is DataType.INT64 and to is DataType.TIMESTAMP:
            # explicit widen: an int32-narrowed LONG would wrap at *1e6
            return data.astype(np.int64) * MICROS_PER_SEC
        if to is DataType.BOOL:
            return data != 0
        if frm.is_floating and to.is_integral:
            # spark truncates toward zero; NaN -> 0, out-of-range saturates
            # (non-ansi). float(int64.max) rounds up to 2^63, so saturate via
            # comparisons instead of clip-then-astype (which would wrap).
            clean = xp.where(xp.isnan(data), 0.0, data)
            t = xp.trunc(clean)
            info = np.iinfo(npdt)
            res = t.astype(npdt)
            res = xp.where(t >= float(info.max), info.max, res)
            res = xp.where(t <= float(info.min), info.min, res)
            return res
        return data.astype(npdt)

    # -- to string -----------------------------------------------------------
    def _to_string(self, ctx, v, frm):
        if not ctx.is_device:
            return self._to_string_host(ctx, v, frm)
        from spark_rapids_tpu.columnar import format as F

        if frm.is_integral or frm is DataType.BOOL:
            return F.int_to_string(ctx, v)
        if frm is DataType.DATE:
            return F.date_to_string(ctx, v)
        if frm is DataType.TIMESTAMP:
            return F.timestamp_to_string(ctx, v)
        if frm.is_floating:
            # planner admits this direction only when
            # rapids.tpu.sql.castFloatToString.enabled is set AND the
            # backend carries real f64 lanes (the shared shortest-decimal
            # search runs in f64; overrides.py:_tag_cast)
            return F.float_to_string(ctx, v)
        raise NotImplementedError(f"device cast {frm} -> STRING")

    def _to_string_host(self, ctx, v, frm):
        if frm.is_floating:
            return format_float_array(np.asarray(v.data),
                                      frm is DataType.FLOAT32)

        def fmt(x):
            if is_decimal(frm):
                return str(DU.from_unscaled(int(x), frm.scale))
            if frm is DataType.BOOL:
                return "true" if x else "false"
            if frm.is_integral:
                return str(int(x))
            if frm is DataType.DATE:
                return _date_str(int(x))
            if frm is DataType.TIMESTAMP:
                return _ts_str(int(x))
            raise NotImplementedError(f"cast {frm} -> STRING")

        return np.array([fmt(x) for x in v.data], dtype=object)

    # -- from string ---------------------------------------------------------
    def _from_string(self, ctx, v, to):
        if ctx.is_device:
            from spark_rapids_tpu.columnar import parse as PRS

            if to.is_floating:
                out, malformed = PRS.parse_float_col(ctx, v, to)
            elif to is DataType.TIMESTAMP:
                out, malformed = PRS.parse_timestamp_col(ctx, v)
            else:
                raise NotImplementedError(f"device cast STRING -> {to}")
            if self.ansi:
                import jax.numpy as jnp

                # deferred ANSI error: can't raise mid-trace; the evaluator
                # entry point checks the flag after the jitted call
                ctx.ansi_errors.append((
                    jnp.any(malformed),
                    f"ANSI cast STRING -> {to.name}: malformed input"))
            return out
        out = np.zeros(len(v.data), dtype=to.to_np())
        validity = v.validity.copy()
        for i, s in enumerate(v.data):
            if not validity[i]:
                continue
            # ASCII whitespace only: the device trim (columnar/parse.py)
            # cannot see Unicode spaces, and host/device must agree on
            # exactly which inputs parse (advisor round 4)
            s = s.strip(" \t\n\r\f\x0b")
            try:
                if is_decimal(to):
                    u = DU.to_unscaled(s, to.scale)
                    if abs(u) > int(DU.bound(to.precision)):
                        raise OverflowError(s)
                    out[i] = u
                elif to.is_integral:
                    out[i] = int(float(s)) if "." in s or "e" in s.lower() else int(s)
                elif to.is_floating:
                    out[i] = _parse_float_text(s)
                elif to is DataType.BOOL:
                    low = s.lower()
                    if low in ("t", "true", "y", "yes", "1"):
                        out[i] = True
                    elif low in ("f", "false", "n", "no", "0"):
                        out[i] = False
                    else:
                        raise ValueError(s)
                elif to is DataType.DATE:
                    out[i] = _parse_date(s)
                elif to is DataType.TIMESTAMP:
                    out[i] = _parse_ts_strict(s)
                else:
                    raise NotImplementedError(f"cast STRING -> {to}")
            except (ValueError, OverflowError, ArithmeticError):
                if self.ansi:
                    raise
                validity[i] = False
                out[i] = 0
        if to is DataType.FLOAT32:
            # shared convention with the device parse kernel: sub-normal
            # f32 results flush to signed zero (columnar/parse.py)
            tiny = np.isfinite(out) & (np.abs(out) < 2.0 ** -126)
            out[tiny] = np.copysign(np.float32(0.0), out[tiny])
        return ColV(to, out, validity & v.validity)

def _date_str(days: int) -> str:
    # integer civil math, not datetime.date (which caps years at 9999 and
    # raises beyond; DATE is the full int32 days domain). Byte-identical
    # to the device kernel (columnar/format.py:date_to_string).
    from spark_rapids_tpu.ops import datetimeops as DT

    y, m, d = DT.civil_from_days(np, np.asarray([days], dtype=np.int64))
    return f"{_year_str(int(y[0]))}-{int(m[0]):02d}-{int(d[0]):02d}"


def _year_str(y: int) -> str:
    """Year formatting shared by date/timestamp casts: 4-digit zero-padded
    inside [0, 9999], explicit sign + >= 4 digits outside (Java
    DateTimeFormatter SignStyle.EXCEEDS_PAD, which Spark's uuuu pattern
    uses: 10000 -> '+10000', -5 -> '-0005')."""
    if 0 <= y <= 9999:
        return f"{y:04d}"
    sign = "-" if y < 0 else "+"
    return f"{sign}{abs(y):04d}"


def _ts_str(micros: int) -> str:
    # pure integer civil-calendar math, NOT datetime/strftime: datetime
    # caps years at [1, 9999] (raising beyond) and glibc's %Y does not
    # zero-pad — while SQL timestamps span the full int64 micros domain
    # (years +-294k). Must stay byte-identical to the device kernel
    # (columnar/format.py:timestamp_to_string).
    from spark_rapids_tpu.ops import datetimeops as DT

    days, rem = divmod(micros, MICROS_PER_DAY)
    y, m, d = DT.civil_from_days(np, np.asarray([days], dtype=np.int64))
    secs, frac = divmod(rem, MICROS_PER_SEC)
    base = (f"{_year_str(int(y[0]))}-{int(m[0]):02d}-{int(d[0]):02d} "
            f"{secs // 3600:02d}:{secs % 3600 // 60:02d}:{secs % 60:02d}")
    if frac:
        return f"{base}.{frac:06d}".rstrip("0")
    return base


def _parse_date(s: str) -> int:
    import datetime

    return (datetime.date.fromisoformat(s) - datetime.date(1970, 1, 1)).days


import re as _re

_FLOAT_RE = _re.compile(
    r"^[+-]?(?:(?:\d+(?:\.\d*)?|\.\d+)(?:[eE][+-]?\d{1,3})?|"
    r"(?i:inf|infinity|nan))$")
_TS_RE = _re.compile(
    r"^(\d{4})-(\d{2})-(\d{2})"
    r"(?:[ T](\d{2}):(\d{2}):(\d{2})(?:\.(\d{1,6}))?"
    r"(Z|[+-]\d{2}:\d{2})?)?$")


def _parse_float_text(s: str) -> float:
    """Host mirror of the device STRING->float kernel
    (columnar/parse.py:_parse_float_kernel): same grammar, same 17-digit
    mantissa fold, same shared-table scaling — values agree bitwise with
    the device (raises ValueError on grammar violations)."""
    from spark_rapids_tpu.columnar import format as F

    if len(s) > 48 or not _FLOAT_RE.match(s):
        raise ValueError(s)
    low = s.lstrip("+-").lower()
    negv = s.startswith("-")
    if low in ("inf", "infinity"):
        return -np.inf if negv else np.inf
    if low == "nan":
        return np.nan
    mant, _, ex = low.partition("e")
    ipart, _, fpart = mant.partition(".")
    m = 0
    nsig = 0
    dropped_int = 0
    scale = 0
    for d in ipart:
        if nsig < 17:
            m = m * 10 + int(d)
            if m > 0:
                nsig += 1
        else:
            dropped_int += 1
    for d in fpart:
        if nsig < 17:
            m = m * 10 + int(d)
            scale += 1
            if m > 0:
                nsig += 1
    q = (int(ex) if ex else 0) - scale + dropped_int
    val = float(F.f64_scale_int(np, np.int64(m),
                                np.int64(max(-400, min(400, q)))))
    return -val if negv else val


def _parse_ts_strict(s: str) -> int:
    """Host mirror of the device STRING->TIMESTAMP kernel
    (columnar/parse.py:_parse_timestamp_kernel): strict 'YYYY-MM-DD' /
    'YYYY-MM-DD[ T]HH:MM:SS[.f{1,6}][Z|+-HH:MM]' grammar, naive = UTC,
    integer epoch math (raises ValueError on violations)."""
    mt = _TS_RE.match(s)
    if not mt:
        raise ValueError(s)
    from spark_rapids_tpu.ops import datetimeops as DT

    y, mo, d = int(mt.group(1)), int(mt.group(2)), int(mt.group(3))
    days = int(DT.days_from_civil(np, np.int64(y), np.int64(mo),
                                  np.int64(d)))
    ry, rm, rd = DT.civil_from_days(np, np.int64(days))
    if (int(ry), int(rm), int(rd)) != (y, mo, d):
        raise ValueError(s)
    micros = days * 86_400_000_000
    if mt.group(4) is not None:
        hh, mi, ss = int(mt.group(4)), int(mt.group(5)), int(mt.group(6))
        if hh >= 24 or mi >= 60 or ss >= 60:
            raise ValueError(s)
        frac = (mt.group(7) or "").ljust(6, "0")
        micros += (hh * 3600 + mi * 60 + ss) * MICROS_PER_SEC + int(frac)
        z = mt.group(8)
        if z and z != "Z":
            zh, zm = int(z[1:3]), int(z[4:6])
            if zh >= 24 or zm >= 60:
                raise ValueError(s)
            off = zh * 60 + zm
            if z[0] == "-":
                off = -off
            micros -= off * 60_000_000
    return micros


def _parse_ts(s: str) -> int:
    import datetime

    dt = datetime.datetime.fromisoformat(s)
    if dt.tzinfo is not None:
        dt = dt.astimezone(datetime.timezone.utc).replace(tzinfo=None)
    delta = dt - datetime.datetime(1970, 1, 1)
    return (delta.days * 86_400 + delta.seconds) * MICROS_PER_SEC + delta.microseconds


def _emit_float_digits(m: int, p: int, e10: int, neg: bool) -> str:
    """Render a (mantissa, precision, exponent) decomposition Java-style:
    plain decimal for -3 <= e10 < 7, else 'd.dddE[-]ee'. Pure integer
    logic — the device emitter (columnar/format.py float_to_string)
    implements the identical placement rules, so given identical
    decompositions the bytes are identical."""
    digs = str(m).rjust(p, "0")
    sign = "-" if neg else ""
    if -3 <= e10 < 7:
        if e10 >= p - 1:
            body = digs + "0" * (e10 - p + 1) + ".0"
        elif e10 >= 0:
            body = digs[:e10 + 1] + "." + digs[e10 + 1:]
        else:
            body = "0." + "0" * (-e10 - 1) + digs
        return sign + body
    frac = digs[1:] if p > 1 else "0"
    return f"{sign}{digs[0]}.{frac}E{e10}"


def format_float_array(vals: np.ndarray, is32: bool) -> np.ndarray:
    """Host float->string with the SAME shortest-round-trip algorithm as
    the device kernel (shared core shortest_float_decomposition run with
    xp=numpy): the framework's float formatting convention. Replaces the
    earlier repr()-based formatter so host and device agree bytewise."""
    from spark_rapids_tpu.columnar import format as F

    x = np.ascontiguousarray(vals,
                             dtype=np.float32 if is32 else np.float64)
    f64 = x.astype(np.float64)
    a = np.abs(f64)
    nan = np.isnan(f64)
    inf = np.isinf(f64)
    zero = a == 0.0
    neg = np.signbit(f64)
    finite = ~(nan | inf | zero)
    with np.errstate(over="ignore", invalid="ignore"):
        m, p, e10 = F.shortest_float_decomposition(
            np, np.where(finite, a, 1.0), 9 if is32 else 17, is32=is32)
    out = np.empty(len(x), dtype=object)
    for i in range(len(x)):
        if nan[i]:
            out[i] = "NaN"
        elif inf[i]:
            out[i] = "-Infinity" if neg[i] else "Infinity"
        elif zero[i]:
            out[i] = "-0.0" if neg[i] else "0.0"
        else:
            out[i] = _emit_float_digits(int(m[i]), int(p[i]), int(e10[i]),
                                        bool(neg[i]))
    return out


