"""Arithmetic expressions (reference:
org/apache/spark/sql/rapids/arithmetic.scala — +,-,*,/,div,pmod,remainder,
abs,signum,unary +/-; 227 LoC)."""

from __future__ import annotations

import numpy as np

from spark_rapids_tpu.columnar.dtypes import (
    DataType,
    DecimalType,
    common_type,
    is_decimal,
)
from spark_rapids_tpu.ops import decimal_util as DU
from spark_rapids_tpu.ops.base import (
    BinaryExpression,
    UnaryExpression,
    _d,
    val_interval,
)
from spark_rapids_tpu.ops.values import ColV

_I64_MIN, _I64_MAX = -(1 << 63), (1 << 63) - 1


class BinaryArithmetic(BinaryExpression):
    # per-op decimal precision rule (None -> decimal operands unsupported)
    _decimal_result = None

    def _decimal_types(self):
        """(left, right, result) DecimalTypes when this op runs in decimal
        space (at least one decimal operand, the other decimal-coercible)."""
        lt, rt = self.left.data_type, self.right.data_type
        if not (is_decimal(lt) or is_decimal(rt)):
            return None
        ld, rd = DU.as_decimal_type(lt), DU.as_decimal_type(rt)
        if ld is None or rd is None:
            return None  # decimal op float resolves via common_type -> double
        if type(self)._decimal_result is None:
            raise TypeError(
                f"{type(self).__name__} does not support decimal operands")
        return ld, rd, type(self)._decimal_result(ld, rd)

    @property
    def data_type(self):
        dts = self._decimal_types()
        if dts is not None:
            return dts[2]
        ct = common_type(self.left.data_type, self.right.data_type)
        if ct is None:
            raise TypeError(
                f"{type(self).__name__}: incompatible types "
                f"{self.left.data_type} / {self.right.data_type}"
            )
        return ct

    @property
    def nullable(self):
        # decimal arithmetic can overflow to NULL (Spark non-ANSI semantics)
        if self._decimal_types() is not None:
            return True
        return super().nullable

    # -- static interval rules (int32-narrowing proof; see columnar.batch) ---
    def _math_interval(self, li, ri):
        """Exact mathematical result interval from operand intervals (python
        ints, no wrap), or None. Per-op; conservative default."""
        return None

    def result_vrange(self, lv, rv):
        if not self.data_type.is_integral or self._decimal_types() is not None:
            return None
        iv = self._math_interval(val_interval(lv), val_interval(rv))
        if iv is None:
            return None
        # only claim a bound when no wrap can have occurred at the result type
        info = np.iinfo(self.data_type.to_np())
        if iv[0] >= int(info.min) and iv[1] <= int(info.max):
            return iv
        return None

    def _narrow_npdt(self, ctx, lv, rv):
        """np.int32 when int32 compute is provably exact for this op's
        int64 result (math interval and both operand values fit int32),
        else None. Remainder's pure mod chain is ring-exact whenever its
        FINAL value fits int32 (its _math_interval bounds that); Pmod's
        sign fix-up DIVIDES after an add that can wrap, so its kernel
        widens that one step to int64 (see Pmod.do_columnar)."""
        from spark_rapids_tpu.columnar.batch import (
            fits_int32,
            int64_narrowing_enabled,
        )

        if (not ctx.is_device or not getattr(ctx, "narrow", True)
                or not int64_narrowing_enabled()
                or self.data_type is not DataType.INT64):
            return None
        li, ri = val_interval(lv), val_interval(rv)
        if not (fits_int32(li) and fits_int32(ri)):
            return None
        mi = self._math_interval(li, ri)
        if fits_int32(mi):
            return np.dtype(np.int32)
        return None

    def _cast_operands(self, ctx, lv, rv):
        npdt = self._narrow_npdt(ctx, lv, rv) or ctx.np_dtype(self.data_type)
        types = (self.left.data_type, self.right.data_type)

        def cast(x, dt):
            # decimal operand entering a float op: unscale to its real value
            if is_decimal(dt) and npdt.kind == "f":
                x = x / float(DU.POW10[dt.scale]) if hasattr(x, "astype") \
                    else float(x) / float(DU.POW10[dt.scale])
            if hasattr(x, "astype"):
                return x.astype(npdt) if x.dtype != npdt else x
            return npdt.type(x)

        return cast(_d(lv), types[0]), cast(_d(rv), types[1])

    # -- shared decimal mod driver -------------------------------------------
    def _decimal_mod(self, ctx, lv, rv, positive: bool):
        """Truncated (or positive, for pmod) modulus at the common scale.
        Result scale is max(s1, s2), which the remainder precision rule
        always preserves (p <= 18 by construction, so no adjust)."""
        xp = ctx.xp
        ld, rd, res = self._decimal_types()
        s = max(ld.scale, rd.scale)
        l, ok1 = DU.rescale(xp, DU._i64(xp, _d(lv)), ld.scale, s)
        r, ok2 = DU.rescale(xp, DU._i64(xp, _d(rv)), rd.scale, s)
        safe_r = xp.where(r == 0, 1, r)

        def trunc_mod(a, n):
            q = a // n
            rem = a - q * n
            adj = (rem != 0) & ((a < 0) ^ (n < 0))
            return a - (q + adj.astype(np.int64)) * n

        m = trunc_mod(l, safe_r)
        if positive:
            m = xp.where(m < 0, trunc_mod(m + safe_r, safe_r), m)
        ok = ok1 & ok2  # r == 0 -> null is applied by eval_kernel
        return ColV(res, xp.where(ok, m, 0), ok)

    # -- shared decimal addsub/mul driver ------------------------------------
    def _decimal_addsub(self, ctx, lv, rv, sign: int):
        """Add/sub at the max operand scale, then round once to the result
        scale.  When precision adjustment shrinks the result scale below
        max(s1, s2), rescaling each operand independently before adding
        would round twice and can differ from Spark's exact-add-then-round
        by one ulp.  The upscaled operands are only bounded by int64, so the
        add carries an explicit wrap check; a wrapped intermediate is the
        documented intermediate-overflow NULL, never a wrong value."""
        xp = ctx.xp
        ld, rd, res = self._decimal_types()
        s = max(ld.scale, rd.scale)
        l, ok1 = DU.rescale(xp, DU._i64(xp, _d(lv)), ld.scale, s)
        r, ok2 = DU.rescale(xp, DU._i64(xp, _d(rv)), rd.scale, s)
        r = r if sign > 0 else -r
        out = l + r
        # the upscaled operands can each reach ~9.2e18, so the add itself can
        # wrap int64: same-sign inputs whose sum flips sign -> overflow NULL
        no_wrap = ~(((l >= 0) == (r >= 0)) & ((out >= 0) != (l >= 0)))
        ok = ok1 & ok2 & no_wrap
        if s != res.scale:
            out, ok4 = DU.rescale(xp, out, s, res.scale)
            ok = ok & ok4
        out, ok3 = DU.fit_precision(xp, out, res.precision)
        ok = ok & ok3
        return ColV(res, xp.where(ok, out, 0), ok)


class Add(BinaryArithmetic):
    _decimal_result = staticmethod(DU.add_result_type)

    def _math_interval(self, li, ri):
        if li is None or ri is None:
            return None
        return (li[0] + ri[0], li[1] + ri[1])

    def do_columnar(self, ctx, lv, rv):
        if self._decimal_types() is not None:
            return self._decimal_addsub(ctx, lv, rv, +1)
        l, r = self._cast_operands(ctx, lv, rv)
        return l + r


class Subtract(BinaryArithmetic):
    _decimal_result = staticmethod(DU.add_result_type)

    def _math_interval(self, li, ri):
        if li is None or ri is None:
            return None
        return (li[0] - ri[1], li[1] - ri[0])

    def do_columnar(self, ctx, lv, rv):
        if self._decimal_types() is not None:
            return self._decimal_addsub(ctx, lv, rv, -1)
        l, r = self._cast_operands(ctx, lv, rv)
        return l - r


class Multiply(BinaryArithmetic):
    _decimal_result = staticmethod(DU.multiply_result_type)

    def _math_interval(self, li, ri):
        if li is None or ri is None:
            return None
        corners = [a * b for a in li for b in ri]
        return (min(corners), max(corners))

    def do_columnar(self, ctx, lv, rv):
        dts = self._decimal_types()
        if dts is not None:
            xp = ctx.xp
            ld, rd, res = dts
            prod, ok1 = DU.checked_mul(xp, _d(lv), _d(rv))
            # natural scale is ld.scale + rd.scale; adjust may have shrunk it
            prod, ok2 = DU.rescale(xp, prod, ld.scale + rd.scale, res.scale)
            prod, ok3 = DU.fit_precision(xp, prod, res.precision)
            ok = ok1 & ok2 & ok3
            return ColV(res, xp.where(ok, prod, 0), ok)
        l, r = self._cast_operands(ctx, lv, rv)
        return l * r


class Divide(BinaryArithmetic):
    """SQL / — floating (Spark Divide), or decimal division with Spark's
    DecimalPrecision result type when both operands are decimal-coercible and
    at least one is decimal. x/0 -> null on both paths."""

    _decimal_result = staticmethod(DU.divide_result_type)

    @property
    def data_type(self):
        dts = self._decimal_types()
        if dts is not None:
            return dts[2]
        return DataType.FLOAT64

    @property
    def nullable(self):
        return True

    def eval_kernel(self, ctx, lv, rv):
        out = super().eval_kernel(ctx, lv, rv)
        if isinstance(out, ColV):
            # division by zero yields SQL NULL
            xp = ctx.xp
            r = _d(rv)
            zero_div = (r == 0) if not isinstance(rv, ColV) else (rv.data == 0)
            validity = out.validity & ctx.xp.logical_not(zero_div)
            zero = np.zeros((), dtype=out.data.dtype)
            data = xp.where(validity, out.data, zero)
            return ColV(out.dtype, data, validity)
        if out.value is not None and _scalar_zero(rv):
            out.value = None
        return out

    def do_columnar(self, ctx, lv, rv):
        xp = ctx.xp
        dts = self._decimal_types()
        if dts is not None:
            ld, rd, res = dts
            l = DU._i64(xp, _d(lv))
            r = DU._i64(xp, _d(rv))
            # bring the numerator to result scale: num = l * 10^k with
            # k = res.scale - ld.scale + rd.scale, then HALF_UP divide
            k = res.scale - ld.scale + rd.scale
            if k >= 0:
                num, ok1 = DU.checked_mul_pow10(xp, l, k)
                q, ok2 = DU.div_half_up(xp, num, r)
            else:
                # extreme-scale corner: divide first, then scale down
                q0, ok1 = DU.div_half_up(xp, l, r)
                q, ok2 = DU.rescale(xp, q0, ld.scale - rd.scale, res.scale)
            q, ok3 = DU.fit_precision(xp, q, res.precision)
            ok = ok1 & ok2 & ok3
            return ColV(res, xp.where(ok, q, 0), ok)
        npdt = ctx.np_dtype(self.data_type)
        l, r = _d(lv), _d(rv)
        l = l.astype(npdt) if hasattr(l, "astype") else float(l)
        r_arr = r.astype(npdt) if hasattr(r, "astype") else float(r)
        # a typed one: a bare 1.0 is traced as an f64 constant under x64
        safe_r = xp.where(r_arr == 0, npdt.type(1), r_arr) \
            if hasattr(r_arr, "dtype") else \
            (1.0 if r_arr == 0 else r_arr)
        return l / safe_r


def _scalar_zero(v):
    from spark_rapids_tpu.ops.values import ScalarV

    return isinstance(v, ScalarV) and v.value == 0


class IntegralDivide(BinaryExpression):
    """SQL div — integer division returning LONG (Spark IntegralDivide)."""

    @property
    def data_type(self):
        return DataType.INT64

    def result_vrange(self, lv, rv):
        # |a div n| <= |a| except the INT64_MIN/-1 wrap corner; the result
        # sign follows sign(a)*sign(n), so without a known divisor sign the
        # bound must be symmetric (10 div -3 = -3)
        li, ri = val_interval(lv), val_interval(rv)
        if li is None or li[0] <= _I64_MIN:
            return None
        m = max(abs(li[0]), abs(li[1]))
        if li[0] >= 0 and ri is not None and ri[0] >= 0:
            return (0, m)
        return (-m, m)

    @property
    def nullable(self):
        return True

    def eval_kernel(self, ctx, lv, rv):
        out = super().eval_kernel(ctx, lv, rv)
        if isinstance(out, ColV):
            xp = ctx.xp
            zero_div = (rv.data == 0) if isinstance(rv, ColV) else (_d(rv) == 0)
            validity = out.validity & ctx.xp.logical_not(zero_div)
            return ColV(out.dtype, xp.where(validity, out.data, 0), validity,
                        vrange=out.vrange)
        if out.value is not None and _scalar_zero(rv):
            out.value = None
        return out

    def do_columnar(self, ctx, lv, rv):
        xp = ctx.xp
        l = _d(lv)
        l = l.astype(np.int64) if hasattr(l, "astype") else np.int64(l)
        r = _d(rv)
        r = r.astype(np.int64) if hasattr(r, "astype") else int(r)
        lt = DU.as_decimal_type(self.left.data_type) \
            if is_decimal(self.left.data_type) else None
        rt = DU.as_decimal_type(self.right.data_type) \
            if is_decimal(self.right.data_type) else None
        if lt is not None or rt is not None:
            # a div b over decimals = trunc(a/b) on the *logical* values:
            # scale the numerator (or denominator) so both sides share one
            # scale; overflow -> NULL
            s1 = lt.scale if lt is not None else 0
            s2 = rt.scale if rt is not None else 0
            l = DU._i64(xp, l)
            r = DU._i64(xp, r)
            ok = xp.ones_like(l, dtype=bool)
            if s2 > s1:
                l, ok = DU.checked_mul_pow10(xp, l, s2 - s1)
            elif s1 > s2:
                r, ok = DU.checked_mul_pow10(xp, r, s1 - s2)
            safe_r = xp.where(r == 0, 1, r)
            q = l // safe_r
            rem = l - q * safe_r
            adj = (rem != 0) & ((l < 0) ^ (safe_r < 0))
            q = q + adj.astype(np.int64)
            return ColV(DataType.INT64, xp.where(ok, q, 0), ok)
        safe_r = xp.where(r == 0, 1, r) if hasattr(r, "dtype") else (1 if r == 0 else r)
        # SQL div truncates toward zero; // floors — fix up
        q = l // safe_r
        rem = l - q * safe_r
        adj = (rem != 0) & ((l < 0) ^ (safe_r < 0))
        return q + adj.astype(np.int64)


class Remainder(BinaryArithmetic):
    """SQL % — sign follows the dividend (C semantics, like Spark)."""

    _decimal_result = staticmethod(DU.remainder_result_type)

    def _math_interval(self, li, ri):
        # |a % n| <= min(|a|, |n| - 1); sign follows the dividend. The
        # wrapped int32 chain is ring-exact because this final bound always
        # fits (divisor-zero lanes become NULL, value irrelevant).
        if li is None or ri is None:
            return None
        mn = max(abs(ri[0]), abs(ri[1]))
        m = min(max(abs(li[0]), abs(li[1])), max(mn - 1, 0))
        return (0 if li[0] >= 0 else -m, 0 if li[1] <= 0 else m)

    @property
    def nullable(self):
        return True

    def eval_kernel(self, ctx, lv, rv):
        out = super().eval_kernel(ctx, lv, rv)
        if isinstance(out, ColV):
            xp = ctx.xp
            zero_div = (rv.data == 0) if isinstance(rv, ColV) else (_d(rv) == 0)
            validity = out.validity & ctx.xp.logical_not(zero_div)
            return ColV(out.dtype, xp.where(validity, out.data, 0), validity,
                        vrange=out.vrange)
        if out.value is not None and _scalar_zero(rv):
            out.value = None
        return out

    def do_columnar(self, ctx, lv, rv):
        if self._decimal_types() is not None:
            return self._decimal_mod(ctx, lv, rv, positive=False)
        xp = ctx.xp
        npdt = self._narrow_npdt(ctx, lv, rv) or ctx.np_dtype(self.data_type)
        l, r = _d(lv), _d(rv)
        l = l.astype(npdt) if hasattr(l, "astype") else l
        r = r.astype(npdt) if hasattr(r, "astype") else r
        safe_r = xp.where(r == 0, 1, r) if hasattr(r, "dtype") else (1 if r == 0 else r)
        if npdt.kind == "f":
            return xp.fmod(l, safe_r)
        # truncated (toward-zero) remainder for ints: l - trunc_div(l,r)*r
        q = l // safe_r
        rem = l - q * safe_r
        adj = (rem != 0) & ((l < 0) ^ (safe_r < 0))
        return l - (q + adj) * safe_r


class Pmod(BinaryArithmetic):
    """pmod(a, b): positive modulus (reference: GpuPmod)."""

    _decimal_result = staticmethod(DU.remainder_result_type)

    def _math_interval(self, li, ri):
        # pmod's sign follows the DIVISOR (Spark/Hive): pmod(-5, 3) = 1 but
        # pmod(-5, -3) = -2. |result| <= |divisor| - 1 always; a
        # non-negative dividend with a non-negative divisor also bounds by
        # the dividend. (divisor-zero lanes become NULL, value irrelevant)
        if li is None or ri is None:
            return None
        m = max(max(abs(ri[0]), abs(ri[1])) - 1, 0)
        if li[0] >= 0 and ri[0] >= 0:
            return (0, min(m, max(abs(li[0]), abs(li[1]))))
        lo = 0 if ri[0] >= 0 else -m
        hi = 0 if ri[1] <= 0 else m
        return (lo, hi)

    @property
    def nullable(self):
        return True

    def eval_kernel(self, ctx, lv, rv):
        out = super().eval_kernel(ctx, lv, rv)
        if isinstance(out, ColV):
            xp = ctx.xp
            zero_div = (rv.data == 0) if isinstance(rv, ColV) else (_d(rv) == 0)
            validity = out.validity & ctx.xp.logical_not(zero_div)
            return ColV(out.dtype, xp.where(validity, out.data, 0), validity,
                        vrange=out.vrange)
        if out.value is not None and _scalar_zero(rv):
            out.value = None
        return out

    def do_columnar(self, ctx, lv, rv):
        if self._decimal_types() is not None:
            return self._decimal_mod(ctx, lv, rv, positive=True)
        xp = ctx.xp
        npdt = self._narrow_npdt(ctx, lv, rv) or ctx.np_dtype(self.data_type)
        l, r = _d(lv), _d(rv)
        l = l.astype(npdt) if hasattr(l, "astype") else l
        r = r.astype(npdt) if hasattr(r, "astype") else r
        safe_r = xp.where(r == 0, 1, r) if hasattr(r, "dtype") else (1 if r == 0 else r)
        if npdt.kind == "f":
            m = xp.fmod(l, safe_r)
            return xp.where(m < 0, xp.fmod(m + safe_r, safe_r), m)

        # java semantics: r = truncated a % n; if r < 0 then trunc_mod(r+n, n)
        def trunc_mod(a, n):
            q = a // n
            rem = a - q * n
            adj = (rem != 0) & ((a < 0) ^ (n < 0))
            return a - (q + adj) * n

        m = trunc_mod(l, safe_r)
        if np.dtype(npdt).itemsize < 8 and hasattr(m, "astype"):
            # the sign fix-up intermediate m + r spans up to 2|r| - 1, which
            # overflows int32 when |r| > 2^30 — and the trunc_mod that
            # follows DIVIDES, so the wrap is not ring-exact (unlike
            # Remainder's pure mod chain). Widen just the fix-up; the final
            # pmod value always fits the narrow lane (|v| <= |r| - 1).
            mw = m.astype(np.int64)
            rw = safe_r.astype(np.int64) if hasattr(safe_r, "astype") \
                else np.int64(safe_r)
            fix = trunc_mod(mw + rw, rw).astype(npdt)
            return xp.where(m < 0, fix, m)
        return xp.where(m < 0, trunc_mod(m + safe_r, safe_r), m)


class UnaryMinus(UnaryExpression):
    @property
    def data_type(self):
        return self.child.data_type

    def result_vrange(self, v):
        iv = val_interval(v)
        if iv is None or not self.data_type.is_integral:
            return None
        info = np.iinfo(self.data_type.to_np())
        # claim only when no wrap at the RESULT type (e.g. INT negate of
        # INT32_MIN wraps and the math interval would be a lie)
        if -iv[1] >= int(info.min) and -iv[0] <= int(info.max):
            return (-iv[1], -iv[0])
        return None

    def do_columnar(self, ctx, v):
        data = v.data
        iv = val_interval(v)
        # only a logically-INT64 column narrowed to int32 lanes may widen:
        # -INT32_MIN wraps in the narrowed lane but not in int64. A plain
        # SQL INT keeps Java wrap semantics (-INT32_MIN == INT32_MIN).
        if (self.data_type is DataType.INT64
                and hasattr(data, "astype") and data.dtype == np.int32
                and (iv is None or -iv[0] > (1 << 31) - 1)):
            data = data.astype(np.int64)
        return -data


class UnaryPositive(UnaryExpression):
    @property
    def data_type(self):
        return self.child.data_type

    def result_vrange(self, v):
        return val_interval(v)

    def do_columnar(self, ctx, v):
        return v.data


class Abs(UnaryExpression):
    @property
    def data_type(self):
        return self.child.data_type

    def result_vrange(self, v):
        iv = val_interval(v)
        if iv is None or not self.data_type.is_integral:
            return None
        info = np.iinfo(self.data_type.to_np())
        hi = max(abs(iv[0]), abs(iv[1]))
        if hi > int(info.max):  # abs(MIN) wraps at the result type
            return None
        lo = 0 if iv[0] <= 0 <= iv[1] else min(abs(iv[0]), abs(iv[1]))
        return (lo, hi)

    def do_columnar(self, ctx, v):
        data = v.data
        iv = val_interval(v)
        # see UnaryMinus: widen only int32-narrowed LONG lanes; SQL INT
        # keeps Java wrap semantics (abs(INT32_MIN) == INT32_MIN)
        if (self.data_type is DataType.INT64
                and hasattr(data, "astype") and data.dtype == np.int32
                and (iv is None or -iv[0] > (1 << 31) - 1)):
            data = data.astype(np.int64)
        return ctx.xp.abs(data)


class Signum(UnaryExpression):
    @property
    def data_type(self):
        return DataType.FLOAT64

    def do_columnar(self, ctx, v):
        return ctx.xp.sign(v.data).astype(ctx.np_dtype(self.data_type))
