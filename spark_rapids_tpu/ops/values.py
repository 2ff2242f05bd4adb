"""Evaluation value model shared by the device (jnp) and cpu (numpy) paths.

Reference parity: GpuExpression.columnarEval returns either a GpuColumnVector
or a scalar (GpuExpressions.scala:74-99); GpuScalar wraps host values into
cudf Scalars (literals.scala:33). Here `ColV` is the column result and
`ScalarV` the scalar result; kernels receive either and rely on numpy/jnp
broadcasting.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Optional

import numpy as np

from spark_rapids_tpu.columnar.dtypes import DataType


@dataclass
class ColV:
    """A column value during evaluation.

    device path: data/validity (and offsets for strings) are traced jax arrays
    padded to the batch capacity.
    cpu path: numpy arrays of exactly num_rows; strings are object arrays and
    offsets is None.

    `vrange` (static (lo, hi) python ints, or None = unknown) bounds the
    valid values of an integral column; kernels use it to prove that int32
    compute is exact for a logically-int64 expression (columnar.batch
    module docstring). It is aux data in the jit pytree, so narrowability
    participates in program cache identity.
    """

    dtype: DataType
    data: Any
    validity: Any
    offsets: Optional[Any] = None
    vrange: Optional[tuple] = None
    # static pow2 bound on any single string's byte length (STRING only;
    # None = unknown) — see ColumnVector.max_len
    max_len: Optional[int] = None

    @property
    def is_string(self) -> bool:
        return self.dtype is DataType.STRING


@dataclass
class ScalarV:
    dtype: DataType
    value: Any  # python scalar; None iff is_null
    @property
    def is_null(self) -> bool:
        return self.value is None


class EvalContext:
    """Carries the batch being evaluated plus engine context.

    device path: xp = jax.numpy, capacity static, num_rows traced scalar.
    cpu path: xp = numpy, capacity == num_rows (no padding), num_rows int.
    """

    __slots__ = (
        "xp", "is_device", "columns", "num_rows", "capacity",
        "partition_id", "rng_seed", "row_start", "narrow", "ansi_errors",
    )

    def __init__(self, xp, is_device, columns, num_rows, capacity,
                 partition_id=0, rng_seed=0, row_start=0, narrow=True):
        # deferred ANSI error channel: device ops can't raise mid-trace, so
        # they append (device bool scalar, message) here and the evaluator
        # entry point (DeviceProjector/DeviceFilter) checks the flags after
        # the jitted call returns — one batched host read, zero cost when
        # no ANSI op is present
        self.ansi_errors = []
        self.xp = xp
        self.is_device = is_device
        # narrow=False turns int32 narrowing off for the WHOLE kernel:
        # inputs stay at physical width AND expression ops skip their
        # in-kernel narrowing (checked via ctx.narrow in _narrow_npdt)
        self.narrow = narrow
        if is_device and narrow:
            columns = [narrow_colv(cv) for cv in columns]
        self.columns = columns  # list[ColV]
        self.num_rows = num_rows
        self.capacity = capacity
        self.partition_id = partition_id
        self.rng_seed = rng_seed
        # global row offset of this batch within the partition (for
        # monotonically_increasing_id)
        self.row_start = row_start

    def row_mask(self):
        return self.xp.arange(self.capacity) < self.num_rows

    def np_dtype(self, dt) -> np.dtype:
        """The dtype an op computes `dt` in: on the device the physical one
        (DOUBLE is f32 where the backend has no f64 — an f64 traced into a
        TPU program is not refused but emulated, at minutes of compile), on
        the numpy engine the exact one."""
        if self.is_device:
            from spark_rapids_tpu.columnar.batch import physical_np_dtype

            return physical_np_dtype(dt)
        return dt.to_np()


def narrow_colv(cv: ColV) -> ColV:
    """int32 view of a logically-int64 column whose value range fits int32
    (exact: value-preserving; null/pad lanes hold zeros by convention and
    survive the cast unchanged). The astype fuses into the consuming kernel
    — XLA reads the int64 pair once and computes 32-bit thereafter."""
    from spark_rapids_tpu.columnar.batch import (
        fits_int32,
        int64_narrowing_enabled,
    )

    if (isinstance(cv, ColV) and cv.data is not None
            and cv.dtype is DataType.INT64 and fits_int32(cv.vrange)
            and int64_narrowing_enabled()
            and hasattr(cv.data, "astype")
            and np.dtype(cv.data.dtype).itemsize > 4):
        return ColV(cv.dtype, cv.data.astype(np.int32), cv.validity,
                    cv.offsets, cv.vrange, cv.max_len)
    return cv


def and_validity(xp, *validities):
    """Null propagation: result is null if any input is null."""
    out = None
    for v in validities:
        if v is None:
            continue
        out = v if out is None else (out & v)
    return out


def broadcast_scalar(ctx: EvalContext, s: ScalarV):
    """Materialize a scalar as a column (used when a kernel needs arrays)."""
    xp = ctx.xp
    if s.dtype is DataType.STRING:
        raise NotImplementedError("string scalar broadcast is kernel-specific")
    npdt = s.dtype.to_np()
    vrange = None
    if ctx.is_device:
        from spark_rapids_tpu.columnar.batch import (
            fits_int32,
            int64_narrowing_enabled,
            physical_np_dtype,
        )

        npdt = physical_np_dtype(s.dtype)
        if s.dtype is DataType.INT64 and not s.is_null:
            vrange = (int(s.value), int(s.value))
            if (fits_int32(vrange) and int64_narrowing_enabled()
                    and getattr(ctx, "narrow", True)):
                npdt = np.dtype(np.int32)
    fill = s.value if not s.is_null else 0
    data = xp.full((ctx.capacity,), npdt.type(fill) if not ctx.is_device else fill,
                   dtype=npdt)
    validity = xp.full((ctx.capacity,), not s.is_null, dtype=bool)
    if ctx.is_device:
        validity = validity & ctx.row_mask()
    return ColV(s.dtype, data, validity, vrange=vrange)


def zero_nulls(xp, data, validity):
    """Re-establish the 'data is 0 at null slots' convention after a kernel
    (keeps padded/null lanes deterministic for hashing and sorting)."""
    if validity is None:
        return data
    return xp.where(validity, data, np.zeros((), dtype=data.dtype))
