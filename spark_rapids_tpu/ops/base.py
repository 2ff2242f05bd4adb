"""Expression base classes.

Reference parity: GpuExpressions.scala —
- `GpuExpression.columnarEval(batch): Any` contract (:74-99) -> `Expression.eval`
- arity templates with scalar/vector dispatch and null propagation
  (GpuUnaryExpression :115-149, GpuBinaryExpression :158-199, ternary)
- GpuBoundReference / GpuBindReferences (GpuBoundAttribute.scala)
- GpuAlias / named expressions (namedExpressions.scala)
- GpuSortOrder (SortOrder used by GpuSortExec)
"""

from __future__ import annotations

import itertools
from typing import Any, List, Optional, Sequence, Tuple

import numpy as np

from spark_rapids_tpu.columnar.dtypes import DataType
from spark_rapids_tpu.ops.values import (
    ColV,
    EvalContext,
    ScalarV,
    and_validity,
    zero_nulls,
)

_expr_id_counter = itertools.count(1)


def next_expr_id() -> int:
    return next(_expr_id_counter)


def val_interval(v) -> Optional[Tuple[int, int]]:
    """Static (lo, hi) bound of an evaluated integral value, or None.
    Exact python-int arithmetic feeds the int32-narrowing proof
    (columnar.batch module docstring)."""
    if isinstance(v, ScalarV):
        if v.dtype.is_integral and not v.is_null:
            return (int(v.value), int(v.value))
        return None
    if isinstance(v, ColV) and v.dtype.is_integral:
        return v.vrange
    return None


class Expression:
    """Immutable expression-tree node."""

    def children(self) -> Tuple["Expression", ...]:
        return ()

    @property
    def data_type(self) -> DataType:
        raise NotImplementedError

    @property
    def nullable(self) -> bool:
        return any(c.nullable for c in self.children())

    @property
    def foldable(self) -> bool:
        ch = self.children()
        return bool(ch) and all(c.foldable for c in ch)

    # deterministic unless overridden (reference: nondeterministic exprs like
    # GpuRand disable certain rewrites)
    @property
    def deterministic(self) -> bool:
        return all(c.deterministic for c in self.children())

    def with_children(self, new_children: Sequence["Expression"]) -> "Expression":
        """Rebuild this node with new children (used by bind/transform)."""
        raise NotImplementedError(type(self).__name__)

    def transform_up(self, fn) -> "Expression":
        new_children = [c.transform_up(fn) for c in self.children()]
        node = self.with_children(new_children) if new_children else self
        return fn(node)

    def collect(self, pred) -> List["Expression"]:
        out = [self] if pred(self) else []
        for c in self.children():
            out.extend(c.collect(pred))
        return out

    # -- evaluation ----------------------------------------------------------
    def eval(self, ctx: EvalContext):
        """Evaluate to a ColV or ScalarV. One implementation serves both the
        device and cpu paths via ctx.xp; expressions whose device kernel
        differs structurally (strings) override `eval_kernel` per path."""
        child_vals = [c.eval(ctx) for c in self.children()]
        return self.eval_kernel(ctx, *child_vals)

    def eval_kernel(self, ctx: EvalContext, *child_vals):
        raise NotImplementedError(type(self).__name__)

    def result_vrange(self, *child_vals) -> Optional[Tuple[int, int]]:
        """Static (lo, hi) bound of this expression's integral result given
        the child values' bounds, or None (unknown). Conservative default;
        arithmetic/conditional ops override with exact interval rules."""
        return None

    # -- identity (used for jit-cache keys and explain output) ---------------
    def fingerprint(self) -> str:
        parts = ",".join(c.fingerprint() for c in self.children())
        return f"{type(self).__name__}({self._fingerprint_extra()}{parts})"

    def _fingerprint_extra(self) -> str:
        return ""

    def sql_name(self) -> str:
        return type(self).__name__

    def __repr__(self):
        ch = ", ".join(repr(c) for c in self.children())
        return f"{type(self).__name__}({ch})"


class LeafExpression(Expression):
    def with_children(self, new_children):
        assert not new_children
        return self


class UnaryExpression(Expression):
    """Null-propagating unary template (reference: GpuUnaryExpression,
    GpuExpressions.scala:115-149)."""

    def __init__(self, child: Expression):
        self.child = child

    def children(self):
        return (self.child,)

    def with_children(self, new_children):
        return type(self)(*new_children)

    def eval_kernel(self, ctx, v):
        if isinstance(v, ScalarV):
            if v.is_null:
                return ScalarV(self.data_type, None)
            return self.eval_scalar(v)
        data = self.do_columnar(ctx, v)
        validity = v.validity
        if isinstance(data, ColV):  # string kernels return full ColV
            return ColV(data.dtype, data.data,
                        and_validity(ctx.xp, data.validity, validity),
                        data.offsets, vrange=data.vrange)
        return ColV(self.data_type, zero_nulls(ctx.xp, data, validity), validity,
                    vrange=self.result_vrange(v))

    def do_columnar(self, ctx, v: ColV):
        raise NotImplementedError(type(self).__name__)

    def eval_scalar(self, v: ScalarV) -> ScalarV:
        # fold via a 1-element numpy vector on the cpu kernel
        ctx = _scalar_fold_ctx()
        col = ColV(v.dtype, np.array([v.value], dtype=v.dtype.to_np())
                   if v.dtype is not DataType.STRING else np.array([v.value], dtype=object),
                   np.array([True]))
        out = self.do_columnar(ctx, col)
        return _fold_result(self.data_type, out)


class BinaryExpression(Expression):
    """Null-propagating binary template (reference: GpuBinaryExpression,
    GpuExpressions.scala:158-199)."""

    def __init__(self, left: Expression, right: Expression):
        self.left = left
        self.right = right

    def children(self):
        return (self.left, self.right)

    def with_children(self, new_children):
        return type(self)(*new_children)

    def eval_kernel(self, ctx, lv, rv):
        if isinstance(lv, ScalarV) and isinstance(rv, ScalarV):
            if lv.is_null or rv.is_null:
                return ScalarV(self.data_type, None)
            return self.eval_scalars(lv, rv)
        if isinstance(lv, ScalarV) and lv.is_null or \
           isinstance(rv, ScalarV) and rv.is_null:
            cap = ctx.capacity
            npdt = ctx.np_dtype(self.data_type)
            data = ctx.xp.zeros((cap,), dtype=npdt if npdt != object else None) \
                if self.data_type is not DataType.STRING else None
            validity = ctx.xp.zeros((cap,), dtype=bool)
            if self.data_type is DataType.STRING:
                return _null_string_col(ctx)
            return ColV(self.data_type, data, validity)
        data = self.do_columnar(ctx, lv, rv)
        validity = and_validity(
            ctx.xp,
            lv.validity if isinstance(lv, ColV) else None,
            rv.validity if isinstance(rv, ColV) else None,
        )
        if validity is None:
            validity = ctx.xp.ones((ctx.capacity,), dtype=bool)
            if ctx.is_device:
                validity = validity & ctx.row_mask()
        if isinstance(data, ColV):  # string kernels return full ColV
            return ColV(data.dtype, data.data,
                        and_validity(ctx.xp, data.validity, validity), data.offsets,
                        vrange=data.vrange)
        return ColV(self.data_type, zero_nulls(ctx.xp, data, validity), validity,
                    vrange=self.result_vrange(lv, rv))

    def do_columnar(self, ctx, lv, rv):
        """lv/rv are ColV or non-null ScalarV; kernels use `_d(v)` to get the
        broadcastable raw value."""
        raise NotImplementedError(type(self).__name__)

    def eval_scalars(self, lv: ScalarV, rv: ScalarV) -> ScalarV:
        ctx = _scalar_fold_ctx()

        def lift(s):
            if s.dtype is DataType.STRING:
                return ColV(s.dtype, np.array([s.value], dtype=object), np.array([True]))
            return ColV(s.dtype, np.array([s.value], dtype=s.dtype.to_np()),
                        np.array([True]))

        out = self.do_columnar(ctx, lift(lv), lift(rv))
        return _fold_result(self.data_type, out)


class TernaryExpression(Expression):
    def __init__(self, a: Expression, b: Expression, c: Expression):
        self.a, self.b, self.c = a, b, c

    def children(self):
        return (self.a, self.b, self.c)

    def with_children(self, new_children):
        return type(self)(*new_children)

    def eval_kernel(self, ctx, *vals):
        if all(isinstance(v, ScalarV) for v in vals) and \
           not any(v.is_null for v in vals):
            # constant fold via a 1-row cpu context
            fctx = _scalar_fold_ctx()

            def lift(s):
                if s.dtype is DataType.STRING:
                    return ColV(s.dtype, np.array([s.value], dtype=object),
                                np.array([True]))
                return ColV(s.dtype, np.array([s.value], dtype=s.dtype.to_np()),
                            np.array([True]))

            return _fold_result(self.data_type,
                                self.do_columnar(fctx, *[lift(v) for v in vals]))
        # lift string scalars to columns so string kernels see real operands
        vals = tuple(
            _lift_string_scalar(ctx, v)
            if isinstance(v, ScalarV) and not v.is_null and
            v.dtype is DataType.STRING else v
            for v in vals
        )
        if any(isinstance(v, ScalarV) and v.is_null for v in vals):
            if self.data_type is DataType.STRING:
                return _null_string_col(ctx)
            return ColV(self.data_type,
                        ctx.xp.zeros((ctx.capacity,),
                                     dtype=ctx.np_dtype(self.data_type)),
                        ctx.xp.zeros((ctx.capacity,), dtype=bool))
        data = self.do_columnar(ctx, *vals)
        validity = and_validity(
            ctx.xp, *[v.validity for v in vals if isinstance(v, ColV)]
        )
        if validity is None:
            validity = ctx.xp.ones((ctx.capacity,), dtype=bool)
            if ctx.is_device:
                validity = validity & ctx.row_mask()
        if isinstance(data, ColV):
            return ColV(data.dtype, data.data,
                        and_validity(ctx.xp, data.validity, validity), data.offsets,
                        vrange=data.vrange)
        return ColV(self.data_type, zero_nulls(ctx.xp, data, validity), validity,
                    vrange=self.result_vrange(*vals))

    def do_columnar(self, ctx, *vals):
        raise NotImplementedError(type(self).__name__)


def _null_string_col(ctx):
    xp = ctx.xp
    if ctx.is_device:
        return ColV(
            DataType.STRING,
            xp.zeros((8,), dtype=xp.uint8),
            xp.zeros((ctx.capacity,), dtype=bool),
            xp.zeros((ctx.capacity + 1,), dtype=xp.int32),
        )
    return ColV(DataType.STRING,
                np.full((ctx.capacity,), "", dtype=object),
                np.zeros((ctx.capacity,), dtype=bool))


def _scalar_fold_ctx() -> EvalContext:
    return EvalContext(np, False, [], 1, 1)


def _fold_result(dtype: DataType, out) -> ScalarV:
    """Convert a 1-row kernel result back to a scalar (handles kernels that
    return a full ColV, e.g. string producers and validity-computing casts)."""
    if isinstance(out, ColV):
        valid = bool(np.asarray(out.validity)[0])
        if not valid:
            return ScalarV(dtype, None)
        v = out.data[0]
        if isinstance(v, np.generic):
            v = v.item()
        return ScalarV(dtype, v)
    v = np.asarray(out)[0]
    if isinstance(v, np.generic):
        v = v.item()
    return ScalarV(dtype, v)


def _lift_string_scalar(ctx: EvalContext, s: ScalarV) -> ColV:
    """Materialize a string scalar as a real column on either path."""
    if ctx.is_device:
        from spark_rapids_tpu.columnar import strings as S
        import jax.numpy as jnp

        v = S.as_view(ctx, s)
        n = len(s.value.encode("utf-8"))
        byte_cap = max(8, ctx.capacity * max(n, 1))
        validity = v.validity & ctx.row_mask()
        data, offsets = S.build_from_plan(
            [v.data], jnp.zeros((ctx.capacity,), jnp.int32),
            jnp.zeros((ctx.capacity,), jnp.int32),
            jnp.where(validity, n, 0), byte_cap)
        return ColV(DataType.STRING, data, validity, offsets)
    return ColV(DataType.STRING,
                np.full((ctx.capacity,), s.value, dtype=object),
                np.ones((ctx.capacity,), dtype=bool))


def _d(v):
    """Raw broadcastable data of a ColV or non-null ScalarV operand."""
    if isinstance(v, ColV):
        return v.data
    return v.value


# ---------------------------------------------------------------------------
# References / named expressions
# ---------------------------------------------------------------------------
class AttributeReference(LeafExpression):
    """A named column of the input relation. Resolved to a BoundReference
    before execution (reference: GpuBoundAttribute.scala)."""

    def __init__(self, name: str, dtype: DataType, nullable: bool = True,
                 expr_id: Optional[int] = None):
        self.name = name
        self._dtype = dtype
        self._nullable = nullable
        self.expr_id = expr_id if expr_id is not None else next_expr_id()

    @property
    def data_type(self):
        return self._dtype

    @property
    def nullable(self):
        return self._nullable

    @property
    def foldable(self):
        return False

    def eval_kernel(self, ctx):
        raise RuntimeError(
            f"unbound attribute {self.name}#{self.expr_id}; run bind_references first"
        )

    def _fingerprint_extra(self):
        return f"{self.name}#{self.expr_id}:{self._dtype.name};"

    def __repr__(self):
        return f"{self.name}#{self.expr_id}"


class BoundReference(LeafExpression):
    """Ordinal reference into the input batch (reference: GpuBoundReference)."""

    def __init__(self, ordinal: int, dtype: DataType, nullable: bool = True):
        self.ordinal = ordinal
        self._dtype = dtype
        self._nullable = nullable

    @property
    def data_type(self):
        return self._dtype

    @property
    def nullable(self):
        return self._nullable

    @property
    def foldable(self):
        return False

    def eval(self, ctx: EvalContext):
        return ctx.columns[self.ordinal]

    def _fingerprint_extra(self):
        return f"{self.ordinal}:{self._dtype.name};"

    def __repr__(self):
        return f"input[{self.ordinal}:{self._dtype.name}]"


class Alias(UnaryExpression):
    """Named result (reference: GpuAlias, namedExpressions.scala)."""

    def __init__(self, child: Expression, name: str, expr_id: Optional[int] = None):
        super().__init__(child)
        self.name = name
        self.expr_id = expr_id if expr_id is not None else next_expr_id()

    def with_children(self, new_children):
        return Alias(new_children[0], self.name, self.expr_id)

    @property
    def data_type(self):
        return self.child.data_type

    @property
    def nullable(self):
        return self.child.nullable

    def eval_kernel(self, ctx, v):
        return v

    def to_attribute(self) -> AttributeReference:
        return AttributeReference(self.name, self.data_type, self.nullable,
                                  self.expr_id)

    def _fingerprint_extra(self):
        return f"{self.name};"

    def __repr__(self):
        return f"{self.child!r} AS {self.name}#{self.expr_id}"


def to_attribute(e: Expression) -> AttributeReference:
    if isinstance(e, AttributeReference):
        return e
    if isinstance(e, Alias):
        return e.to_attribute()
    raise TypeError(f"not a named expression: {e!r}")


class SortOrder:
    """Sort key descriptor (reference: GpuSortOrder)."""

    __slots__ = ("child", "ascending", "nulls_first")

    def __init__(self, child: Expression, ascending: bool = True,
                 nulls_first: Optional[bool] = None):
        self.child = child
        self.ascending = ascending
        # Spark default: NULLS FIRST for ASC, NULLS LAST for DESC
        self.nulls_first = ascending if nulls_first is None else nulls_first

    def fingerprint(self):
        return f"SortOrder({self.child.fingerprint()},{self.ascending},{self.nulls_first})"

    def __repr__(self):
        d = "ASC" if self.ascending else "DESC"
        n = "NULLS FIRST" if self.nulls_first else "NULLS LAST"
        return f"{self.child!r} {d} {n}"
