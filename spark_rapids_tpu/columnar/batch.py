"""Device and host columnar batches.

Reference parity:
- GpuColumnVector.java (device column vector wrapping cudf; Spark<->cudf dtype
  map :134-207, batch<->Table conversion :244-268, device memory accounting
  :460-483) -> `ColumnVector` wrapping padded jax arrays.
- RapidsHostColumnVector.java (host mirror with row accessors) ->
  `HostColumnVector` over numpy arrays + validity mask.
- GpuColumnarBatchBuilder (host-build-then-upload, GpuColumnVector.java:43-132)
  -> `HostColumnarBatch.to_device()`.

Shape discipline (the "dynamic shapes vs XLA static shapes" decision,
SURVEY.md section 7 hard part #3): every device array is padded to a bucketed
capacity (next power of two, >= 8). The logical row count is a host-side int.
Kernels that care about the valid region take `num_rows` as a *traced scalar*
argument and mask with `iota < num_rows`, so one compiled program serves every
batch in the same capacity bucket.

Padding convention: rows >= num_rows have validity False and zeroed data, so
reductions/hashes over the padded tail are deterministic.
"""

from __future__ import annotations

import contextlib
import functools
import itertools
import threading
from typing import (
    Any,
    Callable,
    Iterator,
    List,
    NamedTuple,
    Optional,
    Sequence,
    Tuple,
)

import numpy as np

from spark_rapids_tpu import _jax_setup  # noqa: F401  (enables x64 before jax use)
import jax
import jax.numpy as jnp

from spark_rapids_tpu.columnar.dtypes import DataType, from_np
from spark_rapids_tpu.obs import trace as OBS
from spark_rapids_tpu.utils import metrics as M

MIN_CAPACITY = 8


def bucket_capacity(n: int) -> int:
    """Round up to the next power of two (min MIN_CAPACITY) so jit caches are
    reused across batches of similar size."""
    if n <= MIN_CAPACITY:
        return MIN_CAPACITY
    return 1 << (int(n - 1).bit_length())


def device_float64_supported() -> bool:
    """TPU has no f64 hardware; DOUBLE columns are computed in f32 there and
    the affected ops are tagged incompat (approximate-float compare in tests)."""
    return jax.default_backend() == "cpu"


def physical_np_dtype(dt: DataType) -> np.dtype:
    if dt is DataType.FLOAT64 and not device_float64_supported():
        return np.dtype(np.float32)
    if dt is DataType.STRING:
        return np.dtype(np.uint8)
    return dt.to_np()


# ---------------------------------------------------------------------------
# Range-aware int64 narrowing (rapids.tpu.sql.int64.narrowing.enabled)
# ---------------------------------------------------------------------------
# XLA emulates int64 on TPU as 32-bit pairs; measured on the real chip the
# flagship filter+project+segment-sum kernel runs 9.75x slower on int64 than
# int32 physical columns (round 4; BENCH_I64_r04.json keeps the later
# 9.18x reading). SQL LONG semantics stay int64, but
# when a column's actual VALUE RANGE provably fits int32, expression kernels
# may compute on an int32 view without changing any result. `vrange` is the
# static (lo, hi) bound of a column's valid values that makes that proof
# possible; it is attached at host->device build time (and from parquet
# footer statistics) and propagated through filters/gathers/projections.
_NARROW_I64 = True
_NARROW_PIN = threading.local()
I32_MIN, I32_MAX = -(1 << 31), (1 << 31) - 1


def set_int64_narrowing(enabled: bool) -> None:
    global _NARROW_I64
    _NARROW_I64 = bool(enabled)


def int64_narrowing_enabled() -> bool:
    # a per-thread pin (set by the jit-cache while invoking a cached kernel)
    # outranks the process global: the flag is re-read at TRACE time, which
    # happens on a cached callable's FIRST call — without the pin a
    # concurrent conf flip between key lookup and first trace would cache a
    # wrong-flavor program under the salted key forever
    pinned = getattr(_NARROW_PIN, "v", None)
    return _NARROW_I64 if pinned is None else pinned


@contextlib.contextmanager
def pin_int64_narrowing(value: bool):
    """Pin the narrowing flag for the current thread (nestable)."""
    prev = getattr(_NARROW_PIN, "v", None)
    _NARROW_PIN.v = bool(value)
    try:
        yield
    finally:
        _NARROW_PIN.v = prev


def fits_int32(vrange) -> bool:
    return (vrange is not None and vrange[0] >= I32_MIN
            and vrange[1] <= I32_MAX)


def union_vrange(*vranges):
    """Conservative union: None if any input range is unknown."""
    vranges = [v for v in vranges]
    if not vranges or any(v is None for v in vranges):
        return None
    return (min(v[0] for v in vranges), max(v[1] for v in vranges))


def quantize_vrange(vr):
    """Widen (lo, hi) to power-of-two ladder bounds: lo down to -(2^k) (or
    0), hi up to 2^k - 1 (or 0). vrange rides jit pytree AUX DATA, i.e. the
    program cache key — exact per-batch min/max would retrace every kernel
    for every batch a streaming scan yields. The ladder caps the distinct
    programs per column at a handful while keeping every bound
    conservative (the narrowing proof only needs containment)."""
    if vr is None:
        return None
    lo, hi = int(vr[0]), int(vr[1])
    lo_q = 0 if lo >= 0 else -(1 << (-lo - 1).bit_length())
    hi_q = 0 if hi <= 0 else (1 << hi.bit_length()) - 1
    return (lo_q, hi_q)


def host_value_range(dt: DataType, host_data):
    """Quantized (lo, hi) of an INT64 host array (nulls already zeroed), or
    None. One cheap host pass at upload time buys every downstream kernel
    the int32-compute proof. TIMESTAMP stays int64 (microseconds since
    epoch never fit int32); narrower ints gain nothing on 32-bit TPU
    lanes."""
    if not _NARROW_I64 or dt is not DataType.INT64 or len(host_data) == 0:
        return None
    return quantize_vrange((int(host_data.min()), int(host_data.max())))


# ---------------------------------------------------------------------------
# Device column vector
# ---------------------------------------------------------------------------
class ColumnVector:
    """A device-resident column (reference: GpuColumnVector.java).

    data:     numeric/bool/date/timestamp -> [capacity] array
              string -> uint8 [byte_capacity] array
    offsets:  string only -> int32 [capacity + 1]
    validity: bool [capacity]; False beyond num_rows and for SQL NULLs.

    Registered as a jax pytree so whole batches can flow through jit.

    `vrange` (optional static (lo, hi) python ints) bounds the VALID values
    of an integral column; it rides the pytree aux data, so a change in
    narrowability retraces dependent jit programs. Storage stays at
    physical_np_dtype regardless — vrange only licenses in-kernel int32
    compute (see module docstring above).

    `max_len` (optional static python int, STRING only) is a power-of-two
    upper bound on any single value's UTF-8 byte length. A host-known
    bound lets string consumers derive static shapes without a device
    round trip: sort/agg chunk counts (string_chunks_needed) and string
    gather output byte capacities both come from it, which removes the
    per-batch count fences (costly where a fence is, as
    utils/devprobe measures). Like vrange it
    rides pytree aux data (pow2-bucketed so it rarely retraces).

    `runs` (optional columnar.runs.RunTable, scan-attached) is HOST run
    metadata for run-granular compute; it deliberately does NOT ride the
    pytree, so any kernel that rebuilds the column drops it — exactly
    the invalidation a row-reordering op needs.
    """

    __slots__ = ("dtype", "data", "validity", "offsets", "vrange",
                 "max_len", "runs")

    def __init__(self, dtype: DataType, data, validity, offsets=None,
                 vrange=None, max_len=None):
        self.dtype = dtype
        self.data = data
        self.validity = validity
        self.offsets = offsets
        self.vrange = vrange
        self.max_len = max_len
        self.runs = None

    @property
    def capacity(self) -> int:
        if self.dtype is DataType.STRING:
            return int(self.offsets.shape[0]) - 1
        return int(self.data.shape[0])

    def device_memory_size(self) -> int:
        """Bytes of device memory referenced (reference:
        GpuColumnVector.java:460-483 device-memory accounting)."""
        size = self.data.size * self.data.dtype.itemsize
        size += self.validity.size  # bool = 1 byte
        if self.offsets is not None:
            size += self.offsets.size * 4
        return int(size)

    def __repr__(self):
        return f"ColumnVector({self.dtype.name}, cap={self.capacity})"


def _cv_flatten(cv: ColumnVector):
    if cv.offsets is None:
        return (cv.data, cv.validity), (cv.dtype, False, cv.vrange, None)
    return (cv.data, cv.validity, cv.offsets), (cv.dtype, True, cv.vrange,
                                                cv.max_len)


def _cv_unflatten(aux, children):
    dtype, has_offsets, vrange, max_len = aux
    if has_offsets:
        data, validity, offsets = children
        return ColumnVector(dtype, data, validity, offsets, vrange,
                            max_len)
    data, validity = children
    return ColumnVector(dtype, data, validity, vrange=vrange)


def len_bucket(n: int) -> int:
    """Pow2 bucket for a string max-byte-length bound (min 1): keeps the
    set of distinct max_len aux values (and thus retraces) logarithmic."""
    n = max(int(n), 1)
    return 1 << (n - 1).bit_length()


jax.tree_util.register_pytree_node(ColumnVector, _cv_flatten, _cv_unflatten)


# ---------------------------------------------------------------------------
# Host column vector (CPU oracle / fallback representation)
# ---------------------------------------------------------------------------
class HostColumnVector:
    """Host column: numpy data + validity (reference: RapidsHostColumnVector).

    Strings are held as a numpy object array of Python str (None-free; nulls
    are expressed only via the validity mask)."""

    __slots__ = ("dtype", "data", "validity")

    def __init__(self, dtype: DataType, data: np.ndarray, validity: np.ndarray):
        assert len(data) == len(validity)
        self.dtype = dtype
        self.data = data
        self.validity = validity

    def __len__(self):
        return len(self.data)

    def rows(self, key) -> "HostColumnVector":
        """The rows a numpy index picks (a slice, a boolean mask), as a
        column of the same kind."""
        return HostColumnVector(self.dtype, self.data[key],
                                self.validity[key])

    @staticmethod
    def from_pylist(values: Sequence[Any], dtype: DataType) -> "HostColumnVector":
        n = len(values)
        validity = np.array([v is not None for v in values], dtype=bool)
        if dtype is DataType.STRING:
            data = np.array([v if v is not None else "" for v in values], dtype=object)
        elif getattr(dtype, "is_decimal", False):
            # logical values (Decimal/int/float/str) -> unscaled int64
            from spark_rapids_tpu.ops.decimal_util import to_unscaled

            data = np.array(
                [to_unscaled(v, dtype.scale, dtype.precision)
                 if v is not None else 0
                 for v in values], dtype=np.int64)
        else:
            npdt = dtype.to_np()
            zero = npdt.type(0)
            data = np.array([v if v is not None else zero for v in values], dtype=npdt)
        return HostColumnVector(dtype, data, validity)

    @staticmethod
    def from_numpy(arr: np.ndarray, validity: Optional[np.ndarray] = None,
                   dtype: Optional[DataType] = None) -> "HostColumnVector":
        arr = np.asarray(arr)
        dt = dtype or from_np(arr.dtype)
        if arr.dtype.kind == "M":
            # normalize datetime64 to the documented physical units:
            # DATE = days, TIMESTAMP = microseconds since epoch
            unit = "D" if dt is DataType.DATE else "us"
            nat = np.isnat(arr)
            arr = arr.astype(f"datetime64[{unit}]").astype(dt.to_np())
            if nat.any():
                base = np.ones(len(arr), dtype=bool) if validity is None else \
                    np.asarray(validity, dtype=bool)
                validity = base & ~nat
                arr = np.where(nat, 0, arr)
        if dt is DataType.STRING:
            if arr.dtype != object:
                arr = arr.astype(object)
            none_mask = np.fromiter((v is None for v in arr), dtype=bool,
                                    count=len(arr))
            if none_mask.any():
                base = np.ones(len(arr), dtype=bool) if validity is None else \
                    np.asarray(validity, dtype=bool)
                validity = base & ~none_mask
                arr = np.where(none_mask, "", arr)
        elif arr.dtype != dt.to_np():
            arr = arr.astype(dt.to_np())
        if validity is None:
            validity = np.ones(len(arr), dtype=bool)
        return HostColumnVector(dt, np.asarray(arr), np.asarray(validity, dtype=bool))

    def to_pylist(self) -> List[Any]:
        dec_scale = self.dtype.scale if getattr(self.dtype, "is_decimal",
                                                False) else None
        if dec_scale is not None:
            from spark_rapids_tpu.ops.decimal_util import from_unscaled
        out = []
        for i in range(len(self.data)):
            if not self.validity[i]:
                out.append(None)
            else:
                v = self.data[i]
                if isinstance(v, np.generic):
                    v = v.item()
                if dec_scale is not None:
                    v = from_unscaled(v, dec_scale)
                out.append(v)
        return out


# ---------------------------------------------------------------------------
# Batches
# ---------------------------------------------------------------------------
class HostColumnarBatch:
    """Host-side columnar batch (reference: Spark ColumnarBatch over host
    vectors; the CPU oracle engine operates directly on these)."""

    __slots__ = ("columns", "num_rows")

    def __init__(self, columns: List[HostColumnVector], num_rows: Optional[int] = None):
        self.columns = columns
        self.num_rows = num_rows if num_rows is not None else (
            len(columns[0]) if columns else 0
        )

    @property
    def num_columns(self):
        return len(self.columns)

    def dtypes(self) -> List[DataType]:
        return [c.dtype for c in self.columns]

    @staticmethod
    def from_pydict(data, dtypes: Sequence[DataType]) -> "HostColumnarBatch":
        cols = [
            HostColumnVector.from_pylist(vals, dt)
            for vals, dt in zip(data.values(), dtypes)
        ]
        return HostColumnarBatch(cols)

    def to_pylist_rows(self) -> List[tuple]:
        col_lists = [c.to_pylist() for c in self.columns]
        return [tuple(vals) for vals in zip(*col_lists)] if col_lists else []

    def slice(self, start: int, length: int) -> "HostColumnarBatch":
        cols = [c.rows(slice(start, start + length)) for c in self.columns]
        return HostColumnarBatch(cols, min(length, max(0, self.num_rows - start)))

    def estimated_size_bytes(self) -> int:
        total = 0
        seen_dicts = set()
        for c in self.columns:
            if getattr(c, "dictionary", None) is not None:
                # host codes + the dictionary bytes once per distinct
                # dictionary in THIS batch (cross-batch sharing of an
                # interned dictionary is deliberately overcounted — the
                # spill store's accounting must never underestimate)
                total += c.data.nbytes + len(c.validity)
                if c.dictionary.did not in seen_dicts:
                    seen_dicts.add(c.dictionary.did)
                    total += int(c.dictionary.host_offsets[-1])
            elif c.dtype is DataType.STRING:
                total += sum(len(s) for s in c.data) + 5 * len(c.data)
            else:
                total += c.data.nbytes + len(c.validity)
        return total

    # -- upload (reference: GpuColumnarBatchBuilder host-build-then-upload) --
    def to_device(self) -> "ColumnarBatch":
        """Batched upload: every column's data/validity/offsets are packed
        into ONE host buffer PER DTYPE, moved to the device in a handful of
        copies, and sliced apart by one jitted program. With the
        accelerator behind a network link, per-column transfers dominate
        otherwise (the pinned-staging-pool lesson of
        GpuDeviceManager.scala:200-206). Per-dtype rather than one uint8
        buffer because a device-side u8[n, itemsize] bitcast pads the
        minor dim to the 128-lane tile on TPU — a 32x HBM blowup that
        OOMed real-chip uploads at 64M rows.

        Two steps, for a caller that can run the first ahead of its
        admission permit (the device parquet scan's host half,
        io/scan.py): `stage_upload()` is host work on host data,
        `StagedUpload.upload()` puts it on the device."""
        return self.stage_upload().upload()

    def stage_upload(self) -> "StagedUpload":
        """The host half of `to_device`: nulls zeroed, columns padded to
        the capacity bucket and packed into one host buffer a dtype. No
        jax call, no device state.

        One pass: the segments are sized first, each dtype group's buffer
        is allocated once (zeroed: the padding, the null lanes) and every
        column is written once, cast on the copy, into its place. The
        buffers are fresh and nothing writes them afterwards: `jnp.asarray`
        may return before a transfer has finished, and on the CPU backend
        the device array may alias the host one, so a staging buffer that
        is pooled or patched after `upload()` is a race on the chip."""
        from spark_rapids_tpu.columnar.encoded import HostDictionaryColumn

        n = self.num_rows
        cap = bucket_capacity(n)
        # a column: (its segments, in layout order, each (group, count,
        # want_bool), and `fill(*segments) -> spec` that writes them)
        plan = []
        for hc in self.columns:
            # a dictionary column's codes upload as fixed int32; the
            # dictionary is interned and uploads (at most) once per
            # process, not per batch
            coded = isinstance(hc, HostDictionaryColumn)
            if hc.dtype is DataType.STRING and not coded:
                plan.append(_plan_string(hc, n, cap))
                continue
            npdt = np.dtype(np.int32) if coded \
                else physical_np_dtype(hc.dtype)
            # a BOOL column rides the uint8 group, as every validity does
            data_seg = ("uint8", cap, True) if npdt == np.bool_ \
                else (npdt.name, cap, False)
            plan.append(([data_seg, ("uint8", cap, True)],
                         functools.partial(_fill_fixed, hc, n, npdt)))
        keys = tuple(sorted({g for segs, _ in plan for g, _, _ in segs}))
        sizes = dict.fromkeys(keys, 0)
        layout = []
        for segs, _ in plan:
            for group, count, want_bool in segs:
                layout.append((keys.index(group), sizes[group], count,
                               want_bool))
                sizes[group] += count
        bufs = tuple(np.zeros(sizes[k], dtype=k) for k in keys)
        # per column: ("fixed", dtype, vrange) | ("string", max_len) |
        # ("dict", dtype, dictionary)
        segments = (bufs[bi][start:start + count]
                    for bi, start, count, _ in layout)
        specs = [fill(*itertools.islice(segments, len(segs)))
                 for segs, fill in plan]
        return StagedUpload(n, specs, bufs, tuple(layout))


class StagedUpload:
    """A HostColumnarBatch packed for its upload (`stage_upload`): one
    host buffer a dtype, where each column's segments lie in them, and
    what kind of column each is. Host data only until `upload()`."""

    __slots__ = ("num_rows", "specs", "bufs", "layout")

    def __init__(self, num_rows: int, specs: list, bufs: tuple,
                 layout: tuple):
        self.num_rows = num_rows
        self.specs = specs
        self.bufs = bufs
        self.layout = layout

    def upload(self) -> "ColumnarBatch":
        """The device half of `to_device`: one transfer a dtype group,
        then the segments sliced back out in one jitted program. No
        device-side bitcasts: u8[n, itemsize] bitcasting pads the minor
        dim to the 128-lane tile on TPU (32x HBM)."""
        n = self.num_rows
        if not self.layout:
            return ColumnarBatch([], n, owned=True)
        arrays = _slice_grouped(tuple(jnp.asarray(b) for b in self.bufs),
                                self.layout)
        cols = []
        ai = 0
        for spec in self.specs:
            if spec[0] == "string":
                offsets, buf, validity = arrays[ai], arrays[ai + 1], \
                    arrays[ai + 2]
                ai += 3
                cols.append(ColumnVector(DataType.STRING, buf, validity,
                                         offsets, max_len=spec[1]))
            elif spec[0] == "dict":
                from spark_rapids_tpu.columnar.encoded import (
                    DictionaryColumn,
                )

                data, validity = arrays[ai], arrays[ai + 1]
                ai += 2
                cols.append(DictionaryColumn(spec[1], data, validity,
                                             spec[2]))
            else:
                data, validity = arrays[ai], arrays[ai + 1]
                ai += 2
                cols.append(ColumnVector(spec[1], data, validity,
                                         vrange=spec[2]))
        # a fresh upload is consume-once by construction (donation-eligible
        # until some path stores it for re-read and clears the flag)
        return ColumnarBatch(cols, n, owned=True)


class ColumnarBatch:
    """Device-resident columnar batch (reference: ColumnarBatch of
    GpuColumnVectors / cudf Table).

    `num_rows` is normally a host int, but operators on the hot
    agg->exchange->agg path carry it as a DEVICE scalar to avoid paying a
    device->host round trip per batch (the row-count sync is the single
    most expensive operation when the chip sits behind a network link).
    Use `host_rows()` where a python int is genuinely required.

    `live` (optional device bool [capacity]) marks which lanes hold real
    rows. A live-masked batch is a zero-copy VIEW used by the in-process
    shuffle: a partition slice is just (shared columns, pid==target mask) —
    no gather, no count sync, no data movement. Consumers compact via
    `ensure_compact` / `concat_batches` (a single traced scatter).

    `owned` marks a batch whose column buffers were FRESHLY materialized
    for it (an upload, a gather/concat output) and that no other holder
    can re-read — the consume-once proof buffer DONATION requires
    (docs/async-execution.md). Producers of fresh buffers set it; any
    path that stores a batch for potential multi-read (the shuffle's
    reduce buckets, the spill store's cached device batches) clears it.
    Donation sites (fused stage, agg update, sort gather) only donate
    owned batches."""

    __slots__ = ("columns", "num_rows", "live", "owned")

    def __init__(self, columns: List[ColumnVector], num_rows, live=None,
                 owned: bool = False):
        self.columns = columns
        self.num_rows = int(num_rows) if isinstance(
            num_rows, (int, np.integer)) else num_rows
        self.live = live
        self.owned = owned

    @property
    def rows_on_host(self) -> bool:
        return isinstance(self.num_rows, int)

    def host_rows(self) -> int:
        if not self.rows_on_host:
            self.num_rows = int(jax.device_get(self.num_rows))
        return self.num_rows

    def live_mask(self):
        """Traced mask of real rows (works for compact and masked batches)."""
        if self.live is not None:
            return self.live
        return jnp.arange(self.capacity) < jnp.asarray(self.num_rows)

    @property
    def num_columns(self):
        return len(self.columns)

    @property
    def capacity(self) -> int:
        return self.columns[0].capacity if self.columns else bucket_capacity(self.num_rows)

    def dtypes(self) -> List[DataType]:
        return [c.dtype for c in self.columns]

    def device_memory_size(self) -> int:
        total = 0
        seen_dicts = set()
        for c in self.columns:
            total += c.device_memory_size()
            d = getattr(c, "dictionary", None)
            if d is not None and d.did not in seen_dicts:
                # each distinct dictionary's uploaded device footprint
                # once per batch (cross-batch sharing of an interned
                # dictionary is deliberately overcounted — spill/HBM
                # accounting must never underestimate residency)
                seen_dicts.add(d.did)
                total += d.device_memory_size()
        return total

    # -- download (reference: GpuColumnarToRowExec copyToRowHost) ------------
    def _download_plan(self):
        """(device arrays to fetch, n_or_None, trim, columns) for this
        batch — the first phase of to_host, shared with the batched
        to_host_many. Encoded (dictionary) columns download their CODES
        only — the dictionary's values already live on the host.
        `columns` is what `_download_finish` rebuilds each host column
        from (a `_ColumnPlan`): it holds no device array, so the batch
        can be dropped between the fetch and the finish."""
        from spark_rapids_tpu.columnar.encoded import is_encoded

        if self.rows_on_host:
            n = self.num_rows
            trim = min(self.capacity, bucket_capacity(max(n, 1)))
        elif sum(c.device_memory_size()
                 for c in self.columns) <= (1 << 20):
            # device count + small batch (DOWNLOAD bytes — dictionaries
            # never download, so the residency-with-dictionaries figure
            # would wrongly disqualify small encoded batches): ride the
            # count inside the ONE packed transfer instead of paying a
            # separate scalar round trip
            n = None
            trim = self.capacity
        else:
            n = self.host_rows()
            trim = min(self.capacity, bucket_capacity(max(n, 1)))
        arrays, columns = [], []
        for cv in self.columns:
            if cv.dtype is DataType.STRING and not is_encoded(cv):
                arrays.extend([cv.offsets[:trim + 1], cv.data,
                               cv.validity[:trim]])
            else:
                arrays.extend([cv.data[:trim], cv.validity[:trim]])
            columns.append(_ColumnPlan(
                cv.dtype, np.dtype(cv.data.dtype), int(cv.data.shape[0]),
                cv.dictionary if is_encoded(cv) else None))
        if n is None:
            arrays.append(jnp.asarray(self.num_rows,
                                      dtype=jnp.int32).reshape(1))
        return arrays, n, trim, columns

    def to_host(self) -> HostColumnarBatch:
        """Single-transfer download: one jitted device pack into per-dtype
        buffers, one copy to host, numpy views to reconstruct columns."""
        return to_host_many([self])[0]

    def __repr__(self):
        return (f"ColumnarBatch(rows={self.num_rows}, cap={self.capacity}, "
                f"cols={[c.dtype.name for c in self.columns]})")


def _batch_device_key(b: "ColumnarBatch"):
    """Identity of the single device holding a batch's arrays (None when
    indeterminate). The grouped download program requires co-located
    inputs, so to_host_many groups per device — the query-level sink may
    see batches committed to different chips (ICI exchange outputs)."""
    if not b.columns:
        return None
    devs = getattr(b.columns[0].data, "devices", None)
    if devs is None:
        return None
    try:
        ds = devs() if callable(devs) else devs
    except Exception:
        return None
    return next(iter(ds)) if len(ds) == 1 else None


# device bytes per grouped download transfer; the session's lifted sink
# accumulates to the SAME budget before flushing (session._SINK_FLUSH_BYTES
# aliases this), so residency bounds and fence counts stay in step
DOWNLOAD_BYTE_BUDGET = 256 << 20


class _ColumnPlan(NamedTuple):
    """What `_download_finish` needs of one device column, none of it on
    the device: the logical dtype, the dtype and length of `data` as it
    was fetched (a DOUBLE narrowed by the upload; a STRING's bytes), and
    the dictionary of an encoded column (None for any other)."""
    dtype: DataType
    data_dtype: np.dtype
    data_len: int
    dictionary: Any


def _download_finish(columns: Sequence[_ColumnPlan], host, offs, n, trim,
                     keep_encoded: bool = False) -> HostColumnarBatch:
    """Reconstruct one batch's host columns from the grouped download
    buffers, consuming segments at the shared per-dtype cursors `offs`.
    Pure host work: it reads `columns` (`ColumnarBatch._download_plan`)
    and the fetched bytes, never the device batch.
    Encoded columns arrive as codes: keep_encoded=True (the serialized
    shuffle, the spill store, a file writer's sink) keeps them as
    HostDictionaryColumn; otherwise they expand here through the host
    dictionary — the result-sink form of late materialization (the
    values never crossed the fence)."""
    from spark_rapids_tpu.columnar.encoded import (
        HostDictionaryColumn,
        materialize_host_values,
    )

    def take(count, np_dtype):
        np_dtype = np.dtype(np_dtype)
        key = "uint8" if np_dtype == np.bool_ else np_dtype.name
        seg = host[key][offs[key]:offs[key] + count]
        offs[key] += count
        if np_dtype == np.bool_:
            return seg.astype(bool)
        return seg

    # consume raw segments in the exact _download_plan append order
    # first (the count, when device-resident, rides LAST), then build
    raw = []
    for col in columns:
        if col.dtype is DataType.STRING and col.dictionary is None:
            raw.append((take(trim + 1, np.int32),
                        take(col.data_len, np.uint8),
                        take(trim, np.bool_)))
        else:
            raw.append((take(trim, col.data_dtype),
                        take(trim, np.bool_)))
    if n is None:
        n = int(take(1, np.int32)[0])
    out = []
    for col, seg in zip(columns, raw):
        if col.dictionary is not None:
            codes = seg[0][:n].astype(np.int32)
            validity = seg[1][:n]
            codes = np.where(validity, codes, 0)
            if keep_encoded:
                out.append(HostDictionaryColumn(
                    col.dtype, codes, validity, col.dictionary))
            else:
                strs = materialize_host_values(codes, validity,
                                               col.dictionary)
                out.append(HostColumnVector(col.dtype, strs, validity))
        elif col.dtype is DataType.STRING:
            offsets, data, validity = seg
            validity = validity[:n]
            strs = np.empty(n, dtype=object)
            for i in range(n):
                if validity[i]:
                    strs[i] = bytes(
                        data[offsets[i]:offsets[i + 1]]
                    ).decode("utf-8", errors="replace")
                else:
                    strs[i] = ""
            out.append(HostColumnVector(DataType.STRING, strs, validity))
        else:
            data, validity = seg[0][:n], seg[1][:n]
            npdt = col.dtype.to_np()
            if data.dtype != npdt:
                data = data.astype(npdt)
            data = np.where(validity, data, npdt.type(0))
            out.append(HostColumnVector(col.dtype, data, validity))
    return HostColumnarBatch(out, n)


def _permit_held() -> bool:
    """Whether the calling task holds the admission permit (a traced
    `sink.finish` records it: host work that needs none)."""
    from spark_rapids_tpu.exec.transitions import current_task_id
    from spark_rapids_tpu.memory.semaphore import TpuSemaphore

    return TpuSemaphore.get().held_by(current_task_id())


def _finish_group(fetched, out: list, keep_encoded: bool) -> None:
    """The fourth step of a fence, `sink.finish`: the host batches of one
    fetched group rebuilt into `out`. `fetched` is what `_fetch_groups`
    yields: the fence's bytes on the host (a numpy array a dtype) and, a
    batch, (its place in `out`, columns, n, trim). No device array."""
    host, entries = fetched
    with OBS.span("sink.finish") as sp:
        if sp is not None:
            sp.attrs["permit_held"] = _permit_held()
        offs = {k: 0 for k in host}
        for bi, columns, n, trim in entries:
            out[bi] = _download_finish(columns, host, offs, n, trim,
                                       keep_encoded=keep_encoded)


def _fetch_groups(batches: Sequence["ColumnarBatch"], byte_budget: int,
                  out: list) -> Iterator[tuple]:
    """The device half of a download: groups `batches` a device and a
    `byte_budget`, and yields each group fetched, one fence each, in the
    three steps a traced query sees as spans: `sink.pack` (the pack
    program issued), `sink.wait` (the device finishing, traced queries
    only), `sink.transfer` (the copy and its numpy views).
    `_finish_group` is the fourth. A batch without columns is its row
    count, put into `out` here."""
    if any(b.live is not None for b in batches):
        with OBS.span("sink.pack"):
            batches = [ensure_compact(b) for b in batches]
    # per-device open group: dev_key -> (entries, bytes)
    groups: dict = {}

    def flush(dev_key):
        group, _bytes = groups.pop(dev_key)
        arrays = tuple(a for _, segs, _, _, _ in group for a in segs)
        with OBS.span("sink.pack"):
            packed = _download_grouped(arrays)
        if OBS.current_tracer() is not None:
            # the one place tracing calls into jax: it tells the device
            # still working from the copy. No dispatch, and no fence the
            # next line would not make: nothing counts it
            with OBS.span("sink.wait"):
                jax.block_until_ready(packed)
        with OBS.span("sink.transfer") as sp:
            host = {k: np.asarray(v)
                    for k, v in jax.device_get(packed).items()}
            if sp is not None:
                sp.attrs["bytes"] = sum(v.nbytes for v in host.values())
        # a device-resident row count came with the batch's int32
        # segments, as their last lane: the batch keeps it as a host int
        counts = 0
        for bi, segs, n, _trim, _columns in group:
            counts += sum(a.shape[0] for a in segs if a.dtype == jnp.int32)
            if n is None:
                batches[bi].num_rows = int(host["int32"][counts - 1])
        return host, [(bi, columns, n, trim)
                      for bi, _, n, trim, columns in group]

    for bi, b in enumerate(batches):
        if not b.columns:
            out[bi] = HostColumnarBatch([], b.host_rows())
            continue
        arrays, n, trim, columns = b._download_plan()
        sz = b.device_memory_size()
        dev = _batch_device_key(b)
        group, group_bytes = groups.get(dev, ([], 0))
        if group and group_bytes + sz > byte_budget:
            yield flush(dev)
            group, group_bytes = [], 0
        group.append((bi, arrays, n, trim, columns))
        groups[dev] = (group, group_bytes + sz)
    for dev in list(groups):
        yield flush(dev)


def to_host_many(batches: Sequence["ColumnarBatch"],
                 byte_budget: int = DOWNLOAD_BYTE_BUDGET,
                 keep_encoded: bool = False) -> List[HostColumnarBatch]:
    """Download MANY device batches with one grouped transfer (one fence)
    per `byte_budget` worth of data — the collect/transition path would
    otherwise pay one host round trip per batch.
    Batches on different devices download in per-device groups (the
    grouped pack program needs co-located inputs). keep_encoded=True (the
    serialized shuffle) keeps dictionary columns as host CODES instead of
    expanding them at the fence. A group is finished (`sink.finish`)
    before the next is fetched."""
    out: List[Optional[HostColumnarBatch]] = [None] * len(batches)
    for fetched in _fetch_groups(batches, byte_budget, out):
        _finish_group(fetched, out, keep_encoded)
    return out  # type: ignore[return-value]


def fetch_many(batches: Sequence["ColumnarBatch"],
               byte_budget: int = DOWNLOAD_BYTE_BUDGET,
               keep_encoded: bool = False
               ) -> Callable[[], List[HostColumnarBatch]]:
    """`to_host_many` in two calls, for a caller that gives the chip back
    in between (exec/transitions.DeviceToHostExec): every group is
    fetched here, which is all of the download that touches the device,
    and the call returned rebuilds the host batches, `to_host_many`'s to
    the byte. What is returned holds no device array: the caller may
    drop `batches` before it calls it."""
    out: List[Optional[HostColumnarBatch]] = [None] * len(batches)
    groups = list(_fetch_groups(batches, byte_budget, out))

    def finish() -> List[HostColumnarBatch]:
        while groups:  # a group's bytes go as its batches are rebuilt
            _finish_group(groups.pop(0), out, keep_encoded)
        return out  # type: ignore[return-value]

    return finish


# ---------------------------------------------------------------------------
# Packed transfer helpers (one host<->device copy per batch)
# ---------------------------------------------------------------------------
def _fill_validity(dst, validity, n) -> np.ndarray:
    """`validity[:n]` into the head of a zeroed uint8 segment (the padding
    stays False); the bool view of what was written."""
    valid = dst[:n].view(np.bool_)
    np.copyto(valid, validity[:n], casting="unsafe")
    return valid


def _fill_data(dst, src, valid) -> None:
    """`src` into a zeroed segment of the upload's dtype, cast on the
    copy, null lanes left zero. The masked copy runs only where the
    column has a null. (`unsafe` is what the assignment it replaces
    did: a BOOL column held as uint8, an INT32 one held as int64.)"""
    if valid.all():
        np.copyto(dst, src, casting="unsafe")
    else:
        np.copyto(dst, src, casting="unsafe", where=valid)


def _fill_fixed(hc, n, npdt, data, validity):
    """A fixed-width column (a dictionary column: its codes) into its
    data and validity segments; its spec."""
    valid = _fill_validity(validity, hc.validity, n)
    data = data[:n].view(npdt)
    _fill_data(data, hc.data[:n], valid)
    dictionary = getattr(hc, "dictionary", None)
    if dictionary is not None:
        return ("dict", hc.dtype, dictionary)
    return ("fixed", hc.dtype, host_value_range(hc.dtype, data))


def _plan_string(hc, n, cap):
    """A STRING column's segments (offsets, bytes, validity) and their
    fill. The byte segment's size is the data's, so the encoding runs
    here, ahead of the allocation."""
    valid = np.array(hc.validity[:n], dtype=bool)
    encoded = [
        (s.encode("utf-8") if isinstance(s, str) else bytes(s))
        if valid[i] else b""
        for i, s in enumerate(hc.data[:n])
    ]
    lengths = np.fromiter(map(len, encoded), dtype=np.int32, count=n)
    ends = np.cumsum(lengths, dtype=np.int32)
    nbytes = int(ends[-1]) if n else 0

    def fill(offsets, buf, validity):
        offsets[1:n + 1] = ends
        offsets[n + 1:] = nbytes
        if nbytes:
            buf[:nbytes] = np.frombuffer(b"".join(encoded), dtype=np.uint8)
        _fill_validity(validity, valid, n)
        return ("string", len_bucket(int(lengths.max()) if n else 1))

    return [("int32", cap + 1, False),
            ("uint8", bucket_capacity(max(nbytes, 1)), False),
            ("uint8", cap, True)], fill


@functools.partial(jax.jit, static_argnums=(1,))
def _slice_grouped(bufs, layout):
    out = []
    for bi, start, count, want_bool in layout:
        seg = bufs[bi][start:start + count]
        out.append(seg.astype(bool) if want_bool else seg)
    return out


@jax.jit
def _download_grouped(arrays):
    """Concatenate arrays into one buffer per dtype for the host transfer
    (the download mirror of `stage_upload` + `StagedUpload.upload`; bools
    ride as uint8)."""
    order: dict = {}
    for i, a in enumerate(arrays):
        a = a.astype(jnp.uint8) if a.dtype == jnp.bool_ else a
        order.setdefault(a.dtype.name, []).append(a)
    keys = tuple(sorted(order))
    return {k: jnp.concatenate(order[k]) for k in keys}


# ---------------------------------------------------------------------------
# Device batch ops used by many execs
# ---------------------------------------------------------------------------
def row_mask(num_rows, capacity: int):
    """Traced mask of logically-present rows."""
    return jnp.arange(capacity) < num_rows


@functools.partial(jax.jit, static_argnums=(2,))
def _pad_array(arr, fill, new_cap: int):
    pad = new_cap - arr.shape[0]
    return jnp.concatenate([arr, jnp.full((pad,), fill, dtype=arr.dtype)])


def repad_column(cv: ColumnVector, new_cap: int) -> ColumnVector:
    """Grow a column to a larger capacity bucket."""
    from spark_rapids_tpu.columnar.encoded import is_encoded

    if cv.capacity == new_cap:
        return cv
    assert new_cap > cv.capacity
    if is_encoded(cv):
        return cv.with_codes(
            _pad_array(cv.data, jnp.int32(0), new_cap),
            _pad_array(cv.validity, False, new_cap))
    if cv.dtype is DataType.STRING:
        new_offsets = jnp.concatenate([
            cv.offsets,
            jnp.full((new_cap - cv.capacity,), cv.offsets[-1], dtype=jnp.int32),
        ])
        return ColumnVector(
            cv.dtype,
            cv.data,
            _pad_array(cv.validity, False, new_cap),
            new_offsets,
            max_len=cv.max_len,
        )
    zero = jnp.zeros((), dtype=cv.data.dtype)
    return ColumnVector(
        cv.dtype,
        _pad_array(cv.data, zero, new_cap),
        _pad_array(cv.validity, False, new_cap),
        vrange=cv.vrange,
    )


def batch_to_device(b: "ColumnarBatch", dev) -> "ColumnarBatch":
    """Move a batch's arrays onto one device. Encoded columns decode
    first (visible materialize): the shared dictionary's device arrays
    are committed to the default device, and a cross-device code gather
    would mix committed devices inside one program."""
    from spark_rapids_tpu.columnar.encoded import decode_batch

    b = decode_batch(b)
    cols = [ColumnVector(c.dtype, jax.device_put(c.data, dev),
                         jax.device_put(c.validity, dev),
                         None if c.offsets is None
                         else jax.device_put(c.offsets, dev),
                         vrange=c.vrange, max_len=c.max_len)
            for c in b.columns]
    live = None if b.live is None else jax.device_put(b.live, dev)
    num = b.num_rows
    if hasattr(num, "devices"):
        num = jax.device_put(num, dev)
    return ColumnarBatch(cols, num, live=live)


def _same_device(batches: Sequence["ColumnarBatch"]):
    """Bring batches committed to different chips onto one device before a
    fused concat (exchange outputs chained by adaptive partition coalescing
    live on the chip that received them — the reference's cross-device
    concat goes through cudf the same way)."""
    def dev_of(b):
        if not b.columns:
            return None  # zero-column batches carry no device arrays
        devs = getattr(b.columns[0].data, "devices", None)
        if devs is None:
            return None
        ds = devs() if callable(devs) else devs
        return next(iter(ds)) if len(ds) == 1 else None

    devs = [dev_of(b) for b in batches]
    uniq = {d for d in devs if d is not None}
    if len(uniq) <= 1:
        return list(batches)
    target = devs[0] or next(iter(uniq))
    return [b if d is target else batch_to_device(b, target)
            for b, d in zip(batches, devs)]


def concat_batches(batches: Sequence[ColumnarBatch]) -> ColumnarBatch:
    """Concatenate batches with the same schema (reference: cudf
    Table.concatenate used by GpuCoalesceBatches.scala:38-63). The whole
    fixed-width part is ONE fused device call. Batches carrying device-
    scalar row counts concatenate without any host sync (capacity is then
    bounded by the sum of input capacities)."""
    assert batches, "cannot concat zero batches"
    if len(batches) == 1:
        return ensure_compact(batches[0])
    batches = _same_device(batches)
    # encoded positions first align onto ONE shared dictionary (interned
    # dictionaries make identity the common case); their codes then
    # concatenate as ordinary fixed-width columns and re-wrap below
    from spark_rapids_tpu.columnar.encoded import is_encoded

    batches, enc_dicts = _align_encoded_positions(batches)
    has_string = any(c.dtype is DataType.STRING and not is_encoded(c)
                     for c in batches[0].columns)
    if has_string:
        # string concat is host-coordinated (byte totals); force host counts
        # and compact any live-masked views first
        batches = [ensure_compact(b) for b in batches]
        for b in batches:
            b.host_rows()
    all_plain = all(b.rows_on_host and b.live is None for b in batches)
    ncols = batches[0].num_columns
    fixed_idx = [ci for ci in range(ncols)
                 if ci in enc_dicts
                 or batches[0].columns[ci].dtype is not DataType.STRING]
    out_cols: List[Optional[ColumnVector]] = [None] * ncols
    if all_plain:
        total = sum(b.num_rows for b in batches)
        cap = bucket_capacity(total)
        if fixed_idx:
            piece_cols, buckets = _trimmed_piece_cols(batches, fixed_idx)
            groups = _group_pieces(buckets)
            row_starts = np.concatenate(
                [[0], np.cumsum([b.num_rows for b in batches])]
            ).astype(np.int32)
            g_datas, g_valids, subcols = _assemble_groups(
                piece_cols, groups)
            meta_parts = []
            for _bkt, m_pad, idxs in groups:
                m = len(idxs)
                part = np.zeros((2, m_pad), np.int32)
                part[0, :] = cap
                part[0, :m] = row_starts[idxs]
                part[1, :m] = [batches[i].num_rows for i in idxs]
                meta_parts.append(part)
            meta = device_const(np.concatenate(meta_parts, axis=1))
            outs = _pack_kernel(
                "pack_fixed", _pack_fixed_traced, (0, 1, 2, 3),
                cap, tuple((b, m) for b, m, _ in groups), subcols,
                len(fixed_idx), meta, g_datas, g_valids)
            _fill_out_cols(out_cols, fixed_idx, outs, batches)
    else:
        # masked/device-count path: grouped scatter-compaction, no syncs
        assert not has_string
        cap = bucket_capacity(sum(b.capacity for b in batches))
        lives = [b.live_mask() for b in batches]
        piece_cols = [tuple((b.columns[ci].data, b.columns[ci].validity)
                            for ci in fixed_idx) for b in batches]
        groups = _group_pieces([lv.shape[0] for lv in lives])
        p_pad = 1 << (len(batches) - 1).bit_length()
        g_datas, g_valids, subcols = _assemble_groups(piece_cols, groups)
        g_lives, meta_parts = [], []
        for bkt, m_pad, idxs in groups:
            m = len(idxs)
            g_lives.append(_pack3d([[lives[i] for i in idxs]], m_pad,
                                   bkt)[0])
            part = np.full((1, m_pad), p_pad, np.int32)
            part[0, :m] = idxs
            meta_parts.append(part)
        meta = device_const(np.concatenate(meta_parts, axis=1))
        outs, total = _pack_kernel(
            "pack_live", _pack_live_traced, (0, 1, 2, 3, 4),
            cap, p_pad, tuple((b, m) for b, m, _ in groups),
            subcols, len(fixed_idx), meta, g_datas, g_valids,
            tuple(g_lives))
        _fill_out_cols(out_cols, fixed_idx, outs, batches)
    for ci in range(ncols):
        if ci in enc_dicts:
            c = out_cols[ci]
            from spark_rapids_tpu.columnar.encoded import DictionaryColumn

            out_cols[ci] = DictionaryColumn(
                batches[0].columns[ci].dtype, c.data, c.validity,
                enc_dicts[ci])
        elif batches[0].columns[ci].dtype is DataType.STRING:
            out_cols[ci] = _concat_string_cols(
                [b.columns[ci] for b in batches],
                [b.num_rows for b in batches], cap)
    if all_plain:
        # scan run tables survive a plain concat: pieces stack in order,
        # so per-piece run starts shift by the piece's row offset. (The
        # encoded alignment above already remapped run VALUES into the
        # union dictionary's code space — _align_encoded_positions.)
        _concat_run_tables(out_cols, batches)
    return ColumnarBatch(out_cols, total, owned=True)


# the most operands `concat_in_order`'s one program takes (a piece brings
# two a column): tracing a jit over 832 took 2.2 s on the chip's host
_IN_ORDER_OPERANDS = 1024


def concat_in_order(batches: Sequence[ColumnarBatch]) -> ColumnarBatch:
    """`concat_batches` for a few large pieces that will be read many
    times (a resident relation's batch, exec/cache.py): ONE program that
    copies each piece to its place, so no pack matrix stands beside the
    pieces and the output (the transient is the output alone) and the
    program holds no scatter. The row counts are operands, so a program
    is keyed by the pieces' capacities in order. Pieces it cannot take
    (a live mask, a count on the device, a plain STRING column, more
    operands than a jit should trace) go to `concat_batches`."""
    from spark_rapids_tpu.columnar.encoded import DictionaryColumn

    assert batches, "cannot concat zero batches"
    ncols = batches[0].num_columns
    if len(batches) > 1 and ncols:
        batches, enc_dicts = _align_encoded_positions(_same_device(batches))
    if (len(batches) == 1 or not ncols
            or 2 * ncols * len(batches) > _IN_ORDER_OPERANDS
            or not all(b.rows_on_host and b.live is None for b in batches)
            or any(c.dtype is DataType.STRING and ci not in enc_dicts
                   for ci, c in enumerate(batches[0].columns))):
        return concat_batches(batches)
    starts = np.concatenate(
        [[0], np.cumsum([b.num_rows for b in batches])]).astype(np.int32)
    total = int(starts[-1])
    cap = bucket_capacity(total)
    outs = _pack_kernel(
        "concat_in_order", _concat_in_order_traced, (0,), cap,
        device_const(starts),
        tuple(tuple(c.data for c in b.columns) for b in batches),
        tuple(tuple(c.validity for c in b.columns) for b in batches))
    out_cols: List[Optional[ColumnVector]] = [None] * ncols
    _fill_out_cols(out_cols, list(range(ncols)), outs, batches)
    for ci, shared in enc_dicts.items():
        out_cols[ci] = DictionaryColumn(
            batches[0].columns[ci].dtype, out_cols[ci].data,
            out_cols[ci].validity, shared)
    _concat_run_tables(out_cols, batches)
    return ColumnarBatch(out_cols, total, owned=True)


def _concat_in_order_traced(cap, starts, datas, valids):
    """Piece p's whole capacity is written at `starts[p]`, in order, so
    the lanes behind its rows are overwritten by the piece after it; what
    the last one leaves behind the total is cleared. The buffer written
    is the output and the largest piece over, so that no write is
    clamped back onto rows."""
    room = cap + max(d[0].shape[0] for d in datas)
    keep = jnp.arange(cap, dtype=jnp.int32) < starts[len(datas)]
    outs = []
    for ci in range(len(datas[0])):
        data = jnp.zeros((room,), datas[0][ci].dtype)
        valid = jnp.zeros((room,), bool)
        for p in range(len(datas)):
            at = (starts[p],)
            data = jax.lax.dynamic_update_slice(data, datas[p][ci], at)
            valid = jax.lax.dynamic_update_slice(valid, valids[p][ci], at)
        outs.append((jnp.where(keep, data[:cap], jnp.zeros((), data.dtype)),
                     keep & valid[:cap]))
    return outs


def _concat_run_tables(out_cols, batches) -> None:
    from spark_rapids_tpu.columnar.runs import RunTable

    for ci, out in enumerate(out_cols):
        tabs = [b.columns[ci].runs for b in batches]
        if any(t is None for t in tabs):
            continue
        if any(t.num_rows != b.num_rows for t, b in zip(tabs, batches)):
            continue
        starts = []
        values = []
        base = 0
        for t in tabs:
            starts.append(t.starts + base)
            values.append(np.asarray(t.values))
            base += t.num_rows
        out.runs = RunTable(np.concatenate(starts),
                            np.concatenate(values), base)


def _align_encoded_positions(batches):
    """Pre-pass for concat: per column position, either every batch is
    encoded there (align dictionaries, possibly remapping codes into a
    union) or none is (a mixed position materializes its encoded members
    through the visible decode path). Returns (batches, {position:
    shared DeviceDictionary})."""
    from spark_rapids_tpu.columnar import encoded as ENC

    ncols = batches[0].num_columns
    flags = [[ENC.is_encoded(b.columns[ci]) for b in batches]
             for ci in range(ncols)]
    if not any(any(f) for f in flags):
        return list(batches), {}
    new_cols = [list(b.columns) for b in batches]
    enc_dicts = {}
    for ci in range(ncols):
        if not any(flags[ci]):
            continue
        if not all(flags[ci]):
            for bi, b in enumerate(batches):
                if flags[ci][bi]:
                    new_cols[bi][ci] = ENC.materialize(new_cols[bi][ci])
            continue
        originals = [new_cols[bi][ci] for bi in range(len(batches))]
        shared, aligned = ENC.align_encoded(originals)
        for bi in range(len(batches)):
            orig = originals[bi]
            if orig.runs is not None and aligned[bi] is not orig:
                # the column's codes were remapped into the union
                # dictionary: remap (or keep) the run-table CODES the
                # same way, so a stale pre-union run value can never
                # describe post-union codes
                from spark_rapids_tpu.columnar.runs import RunTable

                remap = orig.dictionary.remap_to(shared)
                vals = np.asarray(orig.runs.values)
                if remap is not None and len(vals):
                    vals = remap[np.clip(vals, 0, len(remap) - 1)]
                aligned[bi].runs = RunTable(orig.runs.starts, vals,
                                            orig.runs.num_rows)
            elif orig.runs is not None:
                aligned[bi].runs = orig.runs
            new_cols[bi][ci] = aligned[bi]
        enc_dicts[ci] = shared
    out = [ColumnarBatch(cols, b.num_rows, live=b.live, owned=b.owned)
           for cols, b in zip(new_cols, batches)]
    return out, enc_dicts


def ensure_compact(batch: ColumnarBatch) -> ColumnarBatch:
    """Compact a live-masked shuffle view into a dense batch (single traced
    scatter; row count stays a device scalar — still no sync). Encoded
    columns compact their codes as fixed-width lanes."""
    from spark_rapids_tpu.columnar.encoded import is_encoded

    if batch.live is None:
        return batch
    if any(c.dtype is DataType.STRING and not is_encoded(c)
           for c in batch.columns):
        # string view compaction: sync the mask and gather
        mask = np.asarray(jax.device_get(batch.live))
        rows = np.nonzero(mask)[0]
        n = len(rows)
        idx_cap = bucket_capacity(max(n, 1))
        idx = np.zeros(idx_cap, dtype=np.int32)
        idx[:n] = rows
        return gather_batch(
            ColumnarBatch(batch.columns, batch.capacity), jnp.asarray(idx), n,
            unique_indices=True)
    cap = bucket_capacity(batch.capacity)
    live = batch.live_mask()
    bkt = live.shape[0]
    ncols = batch.num_columns
    piece_cols = [tuple((c.data, c.validity) for c in batch.columns)]
    g_datas, g_valids, subcols = _assemble_groups(
        piece_cols, [(bkt, 1, [0])])
    outs, total = _pack_kernel(
        "pack_live", _pack_live_traced, (0, 1, 2, 3, 4),
        cap, 1, ((bkt, 1),), subcols, ncols,
        jnp.zeros((1, 1), jnp.int32), g_datas, g_valids,
        (live[None, :],))
    cols = [c.with_codes(d, v) if is_encoded(c)
            else ColumnVector(c.dtype, d, v, vrange=c.vrange)
            for c, (d, v) in zip(batch.columns, outs)]
    return ColumnarBatch(cols, total, owned=True)


def _group_pieces(buckets: Sequence) -> List[Tuple[Any, int, List[int]]]:
    """Group piece indices by shape bucket, padding each group's piece count
    to a power of two. The pack kernels below stack each group into one
    (M, B) matrix and scatter with vectorized positions, so compiled-graph
    size is O(groups x columns) REGARDLESS of piece count — a naive
    per-piece trace put thousands of scatters in one graph and drove LLVM
    out of memory on wide coalesces (TPC-H q8 at suite scale). Pow-2
    padding keeps the program-key space log-bounded."""
    by: dict = {}
    for i, b in enumerate(buckets):
        by.setdefault(b, []).append(i)
    return [(b, 1 << (len(idxs) - 1).bit_length(), idxs)
            for b, idxs in sorted(by.items())]


_DEVICE_CONST_MAX = 2048
_DEVICE_CONST_LOCK = threading.Lock()
_DEVICE_CONST: "dict" = {}


def device_const(arr: np.ndarray):
    """Device copy of a small host array through a content-keyed LRU: the
    pack/slice metadata vectors repeat across iterations of a cached
    query, and a fresh host->device upload is a transfer the device waits
    on while a jitted launch pipelines. Entries are immutable jax arrays. A DEDICATED LRU, not the
    kernel jit-cache: row-count-bearing meta keys churn much faster than
    kernels, and sharing one bound would let meta entries evict compiled
    executables (a recompile costs seconds to save a 17 ms upload).
    Insertion-order (FIFO) eviction — cheap and good enough for a cache
    whose entries cost ~nothing to rebuild."""
    key = (arr.dtype.str, arr.shape, arr.tobytes())
    with _DEVICE_CONST_LOCK:
        got = _DEVICE_CONST.get(key)
        if got is not None:
            return got
    val = jnp.asarray(arr)
    with _DEVICE_CONST_LOCK:
        got = _DEVICE_CONST.setdefault(key, val)
        while len(_DEVICE_CONST) > _DEVICE_CONST_MAX:
            _DEVICE_CONST.pop(next(iter(_DEVICE_CONST)))
        return got


# the most operands one pack program takes: tracing a jit over hundreds of
# operands costs seconds
_PACK_OPERANDS = 64


class PackTally:
    """What the pack programs of one concat did, for the span that asked
    (`exec/transitions._coalesce_iter`): `operands` is the largest operand
    count one `_pack3d` call saw, `programs` the jitted pack programs
    dispatched (the runs and joins of `_pack3d`, the pack kernels)."""

    __slots__ = ("operands", "programs")

    def __init__(self):
        self.operands = 0
        self.programs = 0


_TALLY = threading.local()


@contextlib.contextmanager
def pack_tally():
    """Tally the calling thread's pack programs inside the block."""
    outer = getattr(_TALLY, "open", None)
    tally = _TALLY.open = PackTally()
    try:
        yield tally
    finally:
        _TALLY.open = outer


def _note_pack_program(operands: int = 0) -> None:
    """One pack program dispatched, to the calling thread's open tally."""
    tally = getattr(_TALLY, "open", None)
    if tally is not None:
        tally.programs += 1
        tally.operands = max(tally.operands, operands)


def _pack3d(piece_lists: Sequence[Sequence], m_pad: int, bkt: int):
    """Pack C columns x M same-bucket pieces into one (C, m_pad, bkt)
    matrix with ONE jitted concatenate + reshape (+ pad) program. jnp.stack
    costs an expand_dims dispatch per operand, and even the fused eager
    concatenate pays a per-op dispatch that a jitted launch pipelines
    away. Past `_PACK_OPERANDS` operands the pieces are first concatenated
    in runs of that many (`_concat_runs`), so no program takes more and
    none is issued eagerly; the operand order is the same."""
    from spark_rapids_tpu.engine.jit_cache import get_or_build

    c = len(piece_lists)
    m = len(piece_lists[0])
    flat = [p for pieces in piece_lists for p in pieces]
    _note_pack_program(operands=len(flat))
    if len(flat) > _PACK_OPERANDS:
        flat = _concat_runs(flat)
        key = ("pack3d_join", c, m, m_pad, bkt, flat[0].dtype.name)
    else:
        key = ("pack3d", c, m, m_pad, bkt,
               tuple(p.dtype.name for p in flat))

    def build():
        def fn(flat_arrs):
            mat = jnp.concatenate(flat_arrs).reshape(c, m, bkt)
            if m_pad > m:
                mat = jnp.pad(mat, [(0, 0), (0, m_pad - m), (0, 0)])
            return mat

        return jax.jit(fn)

    return get_or_build(key, build)(flat)


def _concat_runs(flat: List) -> List:
    """Bring more than `_PACK_OPERANDS` same-dtype flat arrays down to at
    most that many, in order: each run of `_PACK_OPERANDS` goes through
    one cached jitted concatenate, level by level. At every level all
    arrays but the last are one length, so a program's key is the run's
    operand count and those two lengths: a full run, a remainder run, and
    nothing that names the piece count."""
    from spark_rapids_tpu.engine.jit_cache import get_or_build

    def build():
        def pack3d_run(run):
            return jnp.concatenate(run)

        return jax.jit(pack3d_run)

    dtype = flat[0].dtype.name
    while len(flat) > _PACK_OPERANDS:
        level = []
        for i in range(0, len(flat), _PACK_OPERANDS):
            run = flat[i:i + _PACK_OPERANDS]
            if len(run) == 1:
                level.append(run[0])
                continue
            key = ("pack3d_run", dtype, len(run), run[0].shape[0],
                   run[-1].shape[0])
            _note_pack_program()
            level.append(get_or_build(key, build)(run))
        flat = level
    return flat


def _dtype_subgroups(cols_of_first_piece) -> List[Tuple[str, Tuple[int, ...]]]:
    """Partition local column indices by physical dtype so each subgroup
    packs with one concatenate (mixed dtypes would silently promote)."""
    by: dict = {}
    for local, arr in enumerate(cols_of_first_piece):
        by.setdefault(arr.dtype.name, []).append(local)
    return [(dt, tuple(cis)) for dt, cis in sorted(by.items())]


def _pack_fixed_traced(cap, shapes, subcols, ncols, meta, g_datas, g_valids):
    """Pack grouped piece matrices into dense output columns. Position of
    source lane (p, i) = start_p + i when i < nrows_p, else dropped; one
    shared position grid per group, one scatter per column per group (all
    inside this single compiled program — graph size is O(groups x
    columns) regardless of piece count)."""
    outs_d: List[Any] = [None] * ncols
    outs_v: List[Any] = [None] * ncols
    off = 0
    for gi, (bkt, m_pad) in enumerate(shapes):
        st = meta[0, off:off + m_pad]
        nr = meta[1, off:off + m_pad]
        off += m_pad
        idx = jnp.arange(bkt, dtype=jnp.int32)
        mask = idx[None, :] < nr[:, None]
        pos = jnp.where(mask, st[:, None] + idx[None, :], cap).ravel()
        for mat, cis in zip(g_datas[gi], subcols[gi]):
            for k, ci in enumerate(cis):
                od = (jnp.zeros((cap,), mat.dtype)
                      if outs_d[ci] is None else outs_d[ci])
                outs_d[ci] = od.at[pos].set(mat[k].ravel(), mode="drop")
        vmat = g_valids[gi]
        for ci in range(ncols):
            ov = (jnp.zeros((cap,), bool)
                  if outs_v[ci] is None else outs_v[ci])
            outs_v[ci] = ov.at[pos].set(
                (vmat[ci] & mask).ravel(), mode="drop")
    return list(zip(outs_d, outs_v))


def _pack_live_traced(cap, p_pad, shapes, subcols, ncols, meta, g_datas,
                      g_valids, g_lives):
    """Scatter-compact grouped live-masked views without any host sync.
    Global position of live row i of piece p = (live rows of pieces earlier
    in the ORIGINAL order) + (live cumsum within p) - 1; the original-order
    piece index rides in meta row 0 so grouping never reorders rows."""
    l_all = jnp.zeros((p_pad,), jnp.int32)
    off = 0
    for gi, (_bkt, m_pad) in enumerate(shapes):
        orig = meta[0, off:off + m_pad]
        off += m_pad
        l_all = l_all.at[orig].set(
            jnp.sum(g_lives[gi], axis=1, dtype=jnp.int32), mode="drop")
    offs_all = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                                jnp.cumsum(l_all, dtype=jnp.int32)])
    outs_d: List[Any] = [None] * ncols
    outs_v: List[Any] = [None] * ncols
    off = 0
    for gi, (_bkt, m_pad) in enumerate(shapes):
        orig = meta[0, off:off + m_pad]
        off += m_pad
        live = g_lives[gi]
        within = jnp.cumsum(live, axis=1, dtype=jnp.int32) - 1
        pos = jnp.where(live, offs_all[orig][:, None] + within, cap).ravel()
        for mat, cis in zip(g_datas[gi], subcols[gi]):
            for k, ci in enumerate(cis):
                od = (jnp.zeros((cap,), mat.dtype)
                      if outs_d[ci] is None else outs_d[ci])
                outs_d[ci] = od.at[pos].set(mat[k].ravel(), mode="drop")
        vmat = g_valids[gi]
        for ci in range(ncols):
            ov = (jnp.zeros((cap,), bool)
                  if outs_v[ci] is None else outs_v[ci])
            outs_v[ci] = ov.at[pos].set(
                (vmat[ci] & live).ravel(), mode="drop")
    return list(zip(outs_d, outs_v)), offs_all[-1]


def _pack_string_traced(cap, byte_cap, shapes, meta, g_sd, g_so, g_sv,
                        totals):
    """Pack grouped stacked string pieces: data bytes, rebased offsets and
    validity each scatter once per group."""
    out_data = jnp.zeros((byte_cap,), jnp.uint8)
    out_offsets = jnp.zeros((cap + 1,), jnp.int32)
    out_valid = jnp.zeros((cap,), bool)
    off = 0
    for gi, (_db, _b1, m_pad) in enumerate(shapes):
        rs = meta[0, off:off + m_pad]
        nr = meta[1, off:off + m_pad]
        bs = meta[2, off:off + m_pad]
        bb = meta[3, off:off + m_pad]
        off += m_pad
        sd = g_sd[gi][0]
        so = g_so[gi][0]
        sv = g_sv[gi][0]
        db = sd.shape[1]
        bidx = jnp.arange(db, dtype=jnp.int32)
        bmask = bidx[None, :] < bb[:, None]
        bpos = jnp.where(bmask, bs[:, None] + bidx[None, :], byte_cap).ravel()
        out_data = out_data.at[bpos].set(sd.ravel(), mode="drop")
        k = so.shape[1] - 1
        ridx = jnp.arange(k, dtype=jnp.int32)
        rmask = ridx[None, :] < nr[:, None]
        rpos = jnp.where(rmask, rs[:, None] + ridx[None, :], cap + 1)
        out_offsets = out_offsets.at[rpos.ravel()].set(
            (so[:, :k] + bs[:, None]).ravel(), mode="drop")
        vpos = jnp.where(rmask, rs[:, None] + ridx[None, :], cap).ravel()
        out_valid = out_valid.at[vpos].set((sv & rmask).ravel(), mode="drop")
    pos = jnp.arange(cap + 1, dtype=jnp.int32)
    out_offsets = jnp.where(pos >= totals[0], totals[1], out_offsets)
    return out_data, out_offsets, out_valid


def _string_sizes_traced(offs3d, nr):
    """Per-piece byte totals for one group: offsets[p, nrows_p]."""
    return offs3d[0][jnp.arange(offs3d.shape[1]), nr]


def _trimmed_piece_cols(batches, fixed_idx):
    """Per piece, slice columns down to bucket_capacity(num_rows) when that
    shrinks the array (post-filter batches can be nearly empty inside a
    huge bucket) — otherwise pass arrays through untouched so the common
    compact case stays O(1) dispatches per column group. All columns of a
    batch share one capacity (ColumnarBatch invariant); _pack3d's reshape
    fails loudly if that is ever violated."""
    piece_cols, buckets = [], []
    for b in batches:
        bkt = b.columns[fixed_idx[0]].data.shape[0]
        eff = bucket_capacity(max(b.num_rows, 1))
        if eff < bkt:
            piece_cols.append(tuple(
                (b.columns[ci].data[:eff], b.columns[ci].validity[:eff])
                for ci in fixed_idx))
            buckets.append(eff)
        else:
            piece_cols.append(tuple(
                (b.columns[ci].data, b.columns[ci].validity)
                for ci in fixed_idx))
            buckets.append(bkt)
    return piece_cols, buckets


def _assemble_groups(piece_cols, groups):
    """Shared group assembly for the pack kernels: dtype-subgrouped data
    matrices, one validity matrix per group, and the static subgroup ->
    local-column map. piece_cols: per piece, a tuple of (data, validity)
    pairs in local column order."""
    g_datas, g_valids, subcols = [], [], []
    ncols = len(piece_cols[0]) if piece_cols else 0
    for bkt, m_pad, idxs in groups:
        subs = _dtype_subgroups(
            [piece_cols[idxs[0]][lc][0] for lc in range(ncols)])
        g_datas.append(tuple(
            _pack3d([[piece_cols[i][lc][0] for i in idxs] for lc in cis],
                    m_pad, bkt) for _dt, cis in subs))
        g_valids.append(_pack3d(
            [[piece_cols[i][lc][1] for i in idxs] for lc in range(ncols)],
            m_pad, bkt) if ncols else jnp.zeros((0, m_pad, bkt), bool))
        subcols.append(tuple(cis for _dt, cis in subs))
    return tuple(g_datas), tuple(g_valids), tuple(subcols)


def _fill_out_cols(out_cols, fixed_idx, outs, batches):
    for lc, (data, validity) in enumerate(outs):
        ci = fixed_idx[lc]
        out_cols[ci] = ColumnVector(
            batches[0].columns[ci].dtype, data, validity,
            vrange=union_vrange(*[b.columns[ci].vrange for b in batches]))


def _pack_kernel(name: str, traced, statics: tuple, *args):
    """Dispatch a pack kernel through the LRU-bounded process jit cache
    (NOT module-level @jax.jit: the key space — group buckets x counts x
    caps — still grows on a long-running stream; LRU eviction drops cold
    executables)."""
    from spark_rapids_tpu.engine.jit_cache import get_or_build

    key = (name,) + tuple(args[i] for i in statics)
    fn = get_or_build(key, lambda: jax.jit(traced, static_argnums=statics))
    _note_pack_program()
    return fn(*args)


def _concat_string_cols(cols: List[ColumnVector], nrows: List[int],
                        cap: int) -> ColumnVector:
    # Host-coordinated string concat: byte_cap must be static, so piece
    # byte totals come to the host in ONE jitted gather + transfer per
    # group (never one eager op per piece).
    groups = _group_pieces(
        [(c.data.shape[0], c.offsets.shape[0]) for c in cols])
    g_sd, g_so, g_sv = [], [], []
    size_parts = []
    for (db, b1), m_pad, idxs in groups:
        m = len(idxs)
        so = _pack3d([[cols[i].offsets for i in idxs]], m_pad, b1)
        nr_real = device_const(np.asarray(
            [nrows[i] for i in idxs] + [0] * (m_pad - m), np.int32))
        size_parts.append(_pack_kernel(
            "string_sizes", _string_sizes_traced, (), so, nr_real))
        g_so.append(so)
        g_sd.append(_pack3d([[cols[i].data for i in idxs]], m_pad, db))
        g_sv.append(_pack3d([[cols[i].validity for i in idxs]], m_pad,
                            cols[idxs[0]].validity.shape[0]))
    sizes_by_group = [np.asarray(s) for s in jax.device_get(size_parts)]
    byte_sizes = [0] * len(cols)
    for ((_b, _m, idxs), sizes) in zip(groups, sizes_by_group):
        for i, s in zip(idxs, sizes):
            byte_sizes[i] = int(s)
    row_starts = np.concatenate(
        [[0], np.cumsum(nrows)]).astype(np.int32)
    byte_starts = np.concatenate(
        [[0], np.cumsum(byte_sizes)]).astype(np.int32)
    total_rows = int(row_starts[-1])
    total_bytes = int(byte_starts[-1])
    byte_cap = bucket_capacity(max(total_bytes, 1))
    meta_parts = []
    for (_b, m_pad, idxs) in groups:
        m = len(idxs)
        part = np.zeros((4, m_pad), np.int32)
        part[0, :] = cap
        part[0, :m] = row_starts[idxs]
        part[1, :m] = [nrows[i] for i in idxs]
        part[2, :] = byte_cap
        part[2, :m] = byte_starts[idxs]
        part[3, :m] = [byte_sizes[i] for i in idxs]
        meta_parts.append(part)
    meta = device_const(np.concatenate(meta_parts, axis=1))
    shapes = tuple((db, b1, m) for (db, b1), m, _ in groups)
    out_data, out_offsets, out_valid = _pack_kernel(
        "pack_string", _pack_string_traced, (0, 1, 2),
        cap, byte_cap, shapes, meta, tuple(g_sd), tuple(g_so), tuple(g_sv),
        device_const(np.asarray([total_rows, total_bytes], np.int32)))
    lens = [c.max_len for c in cols]
    out_ml = max(lens) if all(m is not None for m in lens) else None
    return ColumnVector(DataType.STRING, out_data, out_valid, out_offsets,
                        max_len=out_ml)


def _gather_fixed_cols_donated(cap: int, datas, valids, indices,
                               indices_valid, out_rows):
    """Donated flavor of _gather_fixed_cols: the source column buffers
    (`datas`/`valids`) are donated into the kernel, so the gathered output
    reuses their HBM instead of doubling the batch footprint
    (docs/async-execution.md). Cached via get_or_build so the donation
    flag is part of the program key; callers must hold the consume-once
    proof (ColumnarBatch.owned) and route the dispatch through
    with_retry(donated=True)."""
    from spark_rapids_tpu.engine.jit_cache import get_or_build

    key = ("gather_fixed", cap,
           tuple((d.dtype.name, int(d.shape[0])) for d in datas),
           indices_valid is None)

    def build(donate_argnums=()):
        def fn(datas, valids, indices, indices_valid, out_rows):
            return _gather_fixed_body(cap, datas, valids, indices,
                                      indices_valid, out_rows)

        return jax.jit(fn, donate_argnums=donate_argnums)

    return get_or_build(key, build, donate_argnums=(0, 1))(
        datas, valids, indices, indices_valid, out_rows)


@functools.partial(jax.jit, static_argnums=(0,))
def _gather_fixed_cols(cap: int, datas, valids, indices, indices_valid,
                       out_rows):
    """One fused gather for every fixed-width column of a batch (a single
    device dispatch instead of one eager op per column)."""
    return _gather_fixed_body(cap, datas, valids, indices, indices_valid,
                              out_rows)


def _gather_fixed_body(cap: int, datas, valids, indices, indices_valid,
                       out_rows):
    idx = indices[:cap]
    sel_mask = jnp.arange(cap) < out_rows
    src_cap = valids[0].shape[0] if valids else 0
    in_bounds = sel_mask & (idx >= 0) & (idx < src_cap)
    if indices_valid is not None:
        in_bounds = in_bounds & indices_valid[:cap]
    safe_idx = jnp.where(in_bounds, idx, 0)
    out = []
    for d, v in zip(datas, valids):
        data = jnp.where(in_bounds, d[safe_idx], jnp.zeros((), d.dtype))
        validity = jnp.where(in_bounds, v[safe_idx], False)
        out.append((data, validity))
    return out


def _string_byte_bound(cv: ColumnVector, out_cap: int,
                       unique_indices: bool) -> Optional[int]:
    """Static output byte capacity for gathering `out_cap` rows out of
    string column `cv` without a device round trip, or None when the
    sync-priced exact total is the better deal. Bounds: out_cap * max_len
    always; the source byte buffer additionally when no index repeats
    (permutations, group reps, contiguous slices).

    Balloon guard for repeating gathers (join probes): the hazard is ONE
    long outlier row repeated out_cap times — max_len then oversizes every
    lane of the byte kernel. That is a per-row SKEW property, not an
    output/source ratio: a dimension table's short uniform strings (nation
    names) gathered to fact-table size overshoot the source buffer
    enormously yet bound tightly. Accept the max_len bound when max_len is
    close to the source's mean length (or absolutely small); decline only
    genuinely skewed sources, whose exact-total sync is cheaper than the
    ballooned kernel."""
    src_bytes = int(cv.data.shape[0])
    bounds = []
    if cv.max_len is not None:
        ml_bound = out_cap * cv.max_len
        # src_bytes is the pow2-bucketed byte CAPACITY (up to ~2x the live
        # byte count) over capacity lanes (dead lanes count 0), so this
        # mean can run up to ~2x the live-row mean; the 2x gate below
        # keeps the effective live-mean bound at <= 4x even in that worst
        # case
        n_lanes = max(int(cv.offsets.shape[0]) - 1, 1) \
            if cv.offsets is not None else 1
        mean_len = src_bytes / n_lanes
        low_skew = cv.max_len <= 2 * mean_len + 8
        if unique_indices or ml_bound <= 4 * src_bytes or low_skew:
            bounds.append(ml_bound)
    if unique_indices:
        bounds.append(src_bytes)
    if not bounds:
        return None
    return bucket_capacity(max(min(bounds), 1))


# a bounded (sync-free) string gather is only worth oversizing the output
# buffer for when a fence is expensive; below this it stays exact-sized
_SYNC_FREE_FENCE_MS = 5.0


def _sync_free_strings() -> bool:
    from spark_rapids_tpu.utils.devprobe import fence_cost_ms

    return fence_cost_ms() >= _SYNC_FREE_FENCE_MS


def _fixed_and_string_ordinals(batch: ColumnarBatch):
    """-> ([(ordinal, column)] of the columns that move as fixed-width
    lanes, [ordinal] of the plain STRING ones). An encoded (dictionary)
    column moves its int32 CODES like any fixed-width column: the
    dictionary rides along untouched."""
    from spark_rapids_tpu.columnar.encoded import is_encoded

    fixed = [(i, cv) for i, cv in enumerate(batch.columns)
             if is_encoded(cv) or cv.dtype is not DataType.STRING]
    sidx = [i for i, cv in enumerate(batch.columns)
            if cv.dtype is DataType.STRING and not is_encoded(cv)]
    return fixed, sidx


def _fill_fixed_cols(cols, fixed, outs) -> None:
    from spark_rapids_tpu.columnar.encoded import is_encoded

    for (i, cv), (data, validity) in zip(fixed, outs):
        # moved values are a subset of the source (null lanes hold 0),
        # so the source range bound still holds
        cols[i] = cv.with_codes(data, validity) if is_encoded(cv) \
            else ColumnVector(cv.dtype, data, validity, vrange=cv.vrange)


def gather_batch(batch: ColumnarBatch, indices, out_rows: int,
                 indices_valid=None,
                 unique_indices: bool = False,
                 donate: bool = False) -> ColumnarBatch:
    """Gather rows by index into a new batch of `out_rows` logical rows.
    `indices` is a device int32 array of length >= bucket_capacity(out_rows);
    entries >= capacity are treated as 'emit null row' (used by outer joins).

    unique_indices=True promises no source row index repeats (sort
    permutations, group representatives, contiguous partition slices):
    string output bytes are then bounded by the source buffer, which — on
    high-fence backends — removes the per-gather byte-count round trip.

    donate=True donates the fixed-width source buffers into the gather
    (the sort-scatter hot path): the caller must own the batch
    (ColumnarBatch.owned) and wrap the dispatch in
    with_retry(donated=True) — the sources are consumed, so re-dispatch
    is impossible. String columns never donate (their source bytes are
    re-read after the plan phase below).
    """
    cap = bucket_capacity(max(out_rows, 1))
    M.record_dispatch()
    fixed, sidx = _fixed_and_string_ordinals(batch)
    cols: List[Optional[ColumnVector]] = [None] * batch.num_columns
    if fixed:
        datas = tuple(cv.data for _, cv in fixed)
        valids = tuple(cv.validity for _, cv in fixed)
        if donate:
            outs = _gather_fixed_cols_donated(
                cap, datas, valids, indices, indices_valid,
                np.int32(out_rows))
        else:
            outs = _gather_fixed_cols(cap, datas, valids, indices,
                                      indices_valid, np.int32(out_rows))
        _fill_fixed_cols(cols, fixed, outs)
    if sidx:
        _gather_string_cols(cols, batch, sidx, indices, indices_valid, cap,
                            out_rows, unique_indices)
    return ColumnarBatch(cols, out_rows, owned=True)


def _gather_string_cols(cols, batch: ColumnarBatch, sidx, indices,
                        indices_valid, cap: int, out_rows: int,
                        unique_indices: bool) -> None:
    """The plain STRING columns `sidx` of `batch` gathered through
    `indices` into `cols`, the host holding `out_rows`."""
    # plan every string column first so any byte totals still needed
    # come back in a single host transfer (one sync per gather at most)
    plans = [_gather_string_plan_cap(batch.columns[i].offsets,
                                     batch.columns[i].validity,
                                     indices, indices_valid, cap,
                                     np.int32(out_rows))
             for i in sidx]
    byte_caps: List[Optional[int]] = [None] * len(sidx)
    if _sync_free_strings():
        for j, i in enumerate(sidx):
            byte_caps[j] = _string_byte_bound(batch.columns[i], cap,
                                              unique_indices)
    need = [j for j, bc in enumerate(byte_caps) if bc is None]
    if need:
        totals = jax.device_get([plans[j][2][-1] for j in need])
        for j, total in zip(need, totals):
            byte_caps[j] = bucket_capacity(max(int(total), 1))
    for j, i in enumerate(sidx):
        starts, lengths, new_offsets, validity = plans[j]
        out = _gather_string_bytes(batch.columns[i].data, starts,
                                   new_offsets, lengths, byte_caps[j])
        cols[i] = ColumnVector(DataType.STRING, out, validity,
                               new_offsets,
                               max_len=batch.columns[i].max_len)


def _gather_string_cols_traced(cols, batch: ColumnarBatch, sidx, indices,
                               out_rows) -> None:
    """`_gather_string_cols` with a TRACED row count: the byte capacity
    is the input byte buffer's (the bytes of a subset of the rows can
    never exceed it), so no host sync anywhere."""
    for i in sidx:
        cv = batch.columns[i]
        starts, lengths, new_offsets, validity = _gather_string_plan_traced(
            cv.offsets, cv.validity, indices, out_rows)
        out = _gather_string_bytes(cv.data, starts, new_offsets, lengths,
                                   int(cv.data.shape[0]))
        cols[i] = ColumnVector(DataType.STRING, out, validity, new_offsets,
                               max_len=cv.max_len)


def _string_plan_body(offsets, validity, idx, in_bounds, sel_mask):
    """Shared string-gather prelude: source starts, output offsets, and
    gathered validity (called from both jitted plan entry points)."""
    safe_idx = jnp.where(in_bounds, idx, 0)
    starts = offsets[safe_idx]
    ends = offsets[safe_idx + 1]
    lengths = jnp.where(in_bounds, ends - starts, 0)
    new_offsets = jnp.concatenate([
        jnp.zeros((1,), jnp.int32), jnp.cumsum(lengths, dtype=jnp.int32)
    ])
    out_valid = jnp.where(in_bounds, validity[safe_idx], False) & sel_mask
    return starts, lengths, new_offsets, out_valid


@functools.partial(jax.jit, static_argnums=(4,))
def _gather_string_plan_cap(offsets, validity, indices, indices_valid,
                            cap: int, out_rows):
    """Fused prelude of a string gather in ONE dispatch, masks computed
    in-trace (each eager mask op would be a dispatch of its own).
    indices_valid=None (an empty pytree at the jit boundary) selects the
    unmasked variant at trace time."""
    idx = indices[:cap]
    sel_mask = jnp.arange(cap) < out_rows
    in_bounds = sel_mask & (idx >= 0) & (idx < offsets.shape[0] - 1)
    if indices_valid is not None:
        in_bounds = in_bounds & indices_valid[:cap]
    return _string_plan_body(offsets, validity, idx, in_bounds, sel_mask)


@functools.partial(jax.jit, static_argnums=(4,))
def _gather_string_bytes(src, starts, new_offsets, lengths, byte_cap: int):
    """Scatter-free string gather: for each output byte position find its
    source row via searchsorted over the output offsets, then index the
    source bytes."""
    pos = jnp.arange(byte_cap, dtype=jnp.int32)
    row = jnp.searchsorted(new_offsets[1:], pos, side="right").astype(jnp.int32)
    nrows = starts.shape[0]
    row = jnp.clip(row, 0, nrows - 1)
    within = pos - new_offsets[row]
    src_pos = starts[row] + within
    valid = pos < new_offsets[-1]
    src_pos = jnp.clip(jnp.where(valid, src_pos, 0), 0, src.shape[0] - 1)
    return jnp.where(valid, src[src_pos], 0).astype(jnp.uint8)


# -- the stream compaction ----------------------------------------------------
# A filter's survivors keep their order, so row i moves left by shift[i] =
# the dropped rows in front of it, a number that never falls from one kept
# row to the next. Such a move needs no gather (XLA's on a TPU v5e fetches
# an element at a time: 21-27 ns a lane, ledger PR 40): bit b of the shift
# says whether the row moves 2^b lanes in step b, lowest bit first, and a
# step is one dense select between an array and itself shifted by a static
# 2^b lanes. No two kept rows meet: after bits 0..b-1 row i stands at
# i - (shift[i] mod 2^b), and for kept i < j, j - i >= 1 + shift[j] -
# shift[i].

# The network passes over the INPUT's lanes log2(capacity) times whatever
# survives. Seven columns at 2^20 lanes, the reader's largest batch, cost
# a v5e 0.72-0.79 ms of device time at any share kept (PR 41, `PERF.md`
# section 6); fetching the survivors through their order instead wins
# only from 2^22 lanes on and 1/64 kept or less, which no plan produces
# yet, so there is no second path.


def _shift_left(x, k: int):
    """x[i + k] at lane i, zeros (False) in the last k lanes."""
    return jnp.pad(x[k:], [(0, k)])


def _dropped_through(drop):
    """Inclusive count of True lanes, int32. Two levels (rows of 1024
    lanes, then the rows' totals) where the capacity allows: a flat
    `cumsum` over 2^20 lanes costs a v5e's compiler 32 s, this one 0.4."""
    cap = drop.shape[0]
    if cap <= 1024 or cap % 1024:
        return jnp.cumsum(drop, dtype=jnp.int32)
    in_row = jnp.cumsum(drop.reshape(cap // 1024, 1024), axis=1,
                        dtype=jnp.int32)
    total = in_row[:, -1]
    return (in_row + (jnp.cumsum(total) - total)[:, None]).reshape(cap)


def compact_steps(capacity: int) -> int:
    """Steps of the shift network over `capacity` lanes."""
    return (capacity - 1).bit_length()


@jax.jit
def _compact_plan(keep_mask, num_rows):
    """The survivors' count: what the eager compaction syncs before it
    moves a column, and sizes its output by."""
    keep = keep_mask & row_mask(num_rows, keep_mask.shape[0])
    return jnp.sum(keep, dtype=jnp.int32)


@functools.partial(jax.jit, static_argnums=(0, 5))
def _compact_shift_fixed_cols(out_cap: int, datas, valids, keep_mask,
                              num_rows, with_order: bool):
    """The kept rows of every fixed-width column brought to the front in
    their order by the log-step shift network: no gather, no scatter, no
    sort. -> ([(data, validity)] at `out_cap` lanes, lanes past the count
    zero and invalid: byte for byte what `_gather_fixed_body` gives
    through `argsort(~keep, stable=True)`; the survivors' order (their
    source lanes, int32; whatever past the count) or None; the count).

    The validities travel as bits of uint32 words, 32 to a word, and a
    dropped or vacated lane's shift is 0, so that it never moves and no
    `live` mask needs carrying: what stands past the count is garbage
    until the last select clears it."""
    cap = keep_mask.shape[0]
    keep = keep_mask & row_mask(num_rows, cap)
    n = jnp.sum(keep, dtype=jnp.int32)
    shift = jnp.where(keep, _dropped_through(~keep), 0)
    words = []
    for w in range(0, len(valids), 32):
        word = jnp.zeros((cap,), jnp.uint32)
        for j, v in enumerate(valids[w:w + 32]):
            word = word | (v.astype(jnp.uint32) << j)
        words.append(word)
    carried = list(datas) + words
    if with_order:
        carried.append(jnp.arange(cap, dtype=jnp.int32))
    for b in range(compact_steps(cap)):
        k = 1 << b
        arriving = _shift_left(shift, k)
        incoming = ((arriving >> b) & 1) != 0
        carried = [jnp.where(incoming, _shift_left(x, k), x)
                   for x in carried]
        shift = jnp.where(incoming, arriving,
                          jnp.where(((shift >> b) & 1) != 0, 0, shift))
    sel = row_mask(n, out_cap)
    carried = [x[:out_cap] for x in carried]
    outs = []
    for j, d in enumerate(carried[:len(datas)]):
        bit = (carried[len(datas) + j // 32] >> (j % 32)) & 1
        outs.append((jnp.where(sel, d, jnp.zeros((), d.dtype)),
                     sel & (bit != 0)))
    return outs, carried[-1] if with_order else None, n


@contextlib.contextmanager
def compact_span(rows_in, capacity: int, columns: int, lazy: bool):
    """The `filter.compact` span around one batch's compaction, its count
    and its move (exec/fused.py's stage exit, `compact_batch`), and the
    process-wide `compactedBatches`. `rows_in` where the host holds the
    count; `compact_rows` sets `rows_out` where it learns it (an eager
    compaction syncs the count, a lazy one never does) and `steps`.
    Yields the span, None with tracing off."""
    M.record_compacted_batch()
    attrs = {"rows_in": rows_in} if isinstance(rows_in, int) else {}
    with OBS.span("filter.compact", capacity=capacity, columns=columns,
                  lazy=lazy, **attrs) as sp:
        yield sp


def compact_batch(batch: ColumnarBatch, keep_mask,
                  lazy: bool = False) -> ColumnarBatch:
    """Compact rows where keep_mask is True to the front (the filter kernel;
    reference: cudf Table.filter used by GpuFilterExec,
    basicPhysicalOperators.scala:96-177).

    lazy=True skips the row-count host sync: the move runs at the
    INPUT's capacity and the result carries a traced num_rows (the batch
    invariant — rows 0..n-1 live, suffix padded — still holds, so every
    consumer works unchanged; anything needing a host int syncs lazily
    via host_rows()). On a backend whose fence is expensive, as
    utils/devprobe measures, this folds the filter's fence into whatever downstream sync
    happens anyway; the cost is padded-lane compute at the unshrunk
    capacity."""
    with compact_span(batch.num_rows, int(keep_mask.shape[0]),
                      batch.num_columns, lazy) as sp:
        num_rows = jnp.int32(batch.num_rows)
        n_keep = None
        if not lazy:
            M.record_dispatch()
            n_keep = int(jax.device_get(_compact_plan(keep_mask, num_rows)))
        return compact_rows(batch, keep_mask, num_rows, n_keep, sp)


def compact_rows(batch: ColumnarBatch, keep_mask, num_rows,
                 n_keep: Optional[int], sp=None) -> ColumnarBatch:
    """The move of one compaction, inside its `compact_span` `sp`: the rows
    of `batch` whose `keep_mask` lane is True and lies under `num_rows`,
    dense and in their order. `n_keep` is `_compact_plan`'s count on the
    host (the eager compaction: the output takes that count's capacity
    bucket) or None (the lazy one: the input's capacity, a traced count,
    no sync). A plain STRING column is fetched through the survivors'
    order, which the network carries only then."""
    cap = int(keep_mask.shape[0])
    lazy = n_keep is None
    out_cap = cap if lazy else bucket_capacity(max(n_keep, 1))
    if sp is not None:
        sp.attrs["steps"] = compact_steps(cap)
        if not lazy:
            sp.attrs["rows_out"] = n_keep
    M.record_dispatch()
    fixed, sidx = _fixed_and_string_ordinals(batch)
    outs, order, n = _compact_shift_fixed_cols(
        out_cap, tuple(cv.data for _, cv in fixed),
        tuple(cv.validity for _, cv in fixed), keep_mask, num_rows,
        bool(sidx))
    cols: List[Optional[ColumnVector]] = [None] * batch.num_columns
    _fill_fixed_cols(cols, fixed, outs)
    if sidx and lazy:
        _gather_string_cols_traced(cols, batch, sidx, order, n)
    elif sidx:
        _gather_string_cols(cols, batch, sidx, order, None, out_cap, n_keep,
                            False)
    return ColumnarBatch(cols, n if lazy else n_keep, owned=True)


@jax.jit
def _gather_string_plan_traced(offsets, validity, idx, out_rows):
    """_gather_string_plan with the masks derived from a TRACED row count
    (shared body; one extra fused mask computation, still one dispatch)."""
    sel_mask = jnp.arange(idx.shape[0]) < out_rows
    in_bounds = sel_mask & (idx >= 0) & (idx < (offsets.shape[0] - 1))
    return _string_plan_body(offsets, validity, idx, in_bounds, sel_mask)


def slice_batch_host(batch: ColumnarBatch, start: int, length: int) -> ColumnarBatch:
    """Row-range slice via gather (used by limit; reference: limit.scala:39-123)."""
    length = max(0, min(length, batch.host_rows() - start))
    idx = jnp.arange(bucket_capacity(max(length, 1)), dtype=jnp.int32) + start
    return gather_batch(batch, idx, length)
