"""Encoded columnar subsystem: dictionary columns that stay CODES in HBM.

The device parquet reader (io/parquet_device.py) already extracts RLE run
tables and the dictionary without decoding a value on the host — and until
this module existed it immediately gathered the dictionary into a dense
string column, throwing the compression away before the first operator ran.
"GPU Acceleration of SQL Analytics on Compressed Data" (PAPERS.md) shows
the larger win is to keep the codes: a `DictionaryColumn` holds int32 codes
in HBM plus ONE shared `DeviceDictionary`, and operators compute on the
codes end-to-end —

- equality / IN / IS NULL filters translate their literals into code space
  once per (condition, dictionary) (`rewrite_condition`),
- hash aggregates group directly on the codes and gather the dictionary
  only at finalize (exec/aggregate.py),
- hash joins on dictionary keys align the two sides through a build-time
  code-remap table (`join_remap`),
- hash partitioning hashes per-DICTIONARY word tables gathered by code
  (`DeviceDictionary.hash_words`) so pieces with different dictionaries —
  or plain string pieces — still co-partition,
- the serialized shuffle ships codes + one dictionary copy per piece
  (columnar/serde.py).

Everything else decodes at its operator boundary through `materialize()` /
`decode_batch()` — the ONLY paths from codes back to values, each counted
in the `lateMaterializations` metric and guarded by the `eager-materialize`
tpulint rule so a decode is never silent. The device materialize is a
dispatch site (`with_retry` + faultinject site `encoded.materialize`).

Null convention: invalid lanes carry code 0 with validity False (the
engine-wide zeros-under-null rule); validity is authoritative, so no
distinct null code value is reserved.
"""

from __future__ import annotations

import functools
import hashlib
import threading
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from spark_rapids_tpu import _jax_setup  # noqa: F401
import jax
import jax.numpy as jnp

from spark_rapids_tpu.columnar.batch import (
    ColumnarBatch,
    ColumnVector,
    bucket_capacity,
    len_bucket,
)
from spark_rapids_tpu.columnar.dtypes import DataType
from spark_rapids_tpu.utils import metrics as M

# per-row device bytes of an encoded column (int32 code + validity byte);
# the decoded side of the savings formula is the engine-wide STRING
# estimate (DataType.STRING.itemsize) — both the measured
# encodedBytesSaved metric and the analyzer's prediction use exactly
# rows x (STR_BYTES_PER_ROW - CODE_BYTES_PER_ROW)
CODE_BYTES_PER_ROW = 5
STR_BYTES_PER_ROW = DataType.STRING.itemsize


# ---------------------------------------------------------------------------
# DeviceDictionary (content-interned: identical row-group dictionaries
# share one object, which makes identity-alignment the common case and
# "one dictionary copy per piece" free)
# ---------------------------------------------------------------------------
_DICT_CACHE_MAX = 256
_DICT_CACHE_LOCK = threading.Lock()
_DICT_CACHE: "Dict[str, DeviceDictionary]" = {}
_NEXT_DID_LOCK = threading.Lock()
_NEXT_DID = [0]


def _next_did() -> int:
    with _NEXT_DID_LOCK:
        _NEXT_DID[0] += 1
        return _NEXT_DID[0]


class DeviceDictionary:
    """One shared dictionary: `size` distinct values held as a flat host
    byte table (control plane: literal lookup, remaps, serde) and a
    lazily-uploaded device value table (data plane: the materialize gather
    and the hash word tables). Immutable.

    `value_dtype` is the logical value type. STRING dictionaries hold
    utf-8 byte values; FIXED dictionaries (INT64/DATE/TIMESTAMP parquet
    dictionary chunks, ROADMAP item 5) hold the raw little-endian value
    bytes at a uniform width — byte equality IS value equality either
    way, so interning, code_of, remaps, and unions are representation-
    agnostic. Only ordering, materialization, and hashing branch on the
    value dtype.

    Order-preserving machinery (docs/compressed-execution.md): every
    dictionary can answer `sorted_dict()` — the interned dictionary
    holding the SAME values in ascending value order, whose codes are
    therefore RANKS (code order == value order). `rank_remap()` is the
    cached code->rank permutation into it (None when this dictionary is
    already sorted), built once per interned dictionary; consumers
    re-encode a column through `to_rank_space` and then sorts, range
    bounds, min/max reductions, and comparison predicates all compute on
    int32 codes directly."""

    __slots__ = ("size", "did", "fingerprint", "host_bytes", "host_offsets",
                 "host_lens", "max_len", "value_dtype", "_lock", "_dev",
                 "_code_of", "_host_strs", "_hash_words", "_remaps",
                 "_order", "_sorted", "_fixed_dev")

    def __init__(self, host_bytes: np.ndarray, host_offsets: np.ndarray,
                 fingerprint: str,
                 value_dtype: DataType = DataType.STRING):
        self.size = int(len(host_offsets) - 1)
        self.did = _next_did()
        self.fingerprint = fingerprint
        self.host_bytes = host_bytes          # uint8 [total_bytes]
        self.host_offsets = host_offsets      # int32 [size + 1]
        self.host_lens = (host_offsets[1:] - host_offsets[:-1]).astype(
            np.int32)
        self.max_len = len_bucket(int(self.host_lens.max())
                                  if self.size else 1)
        self.value_dtype = value_dtype
        self._lock = threading.Lock()
        self._dev = None          # (bytes_dev, offsets_dev, lens_dev)
        self._code_of = None      # {value bytes: code}
        self._host_strs = None    # np array of decoded values
        self._hash_words = None   # uint32 device arrays [cap]
        self._remaps: Dict[int, np.ndarray] = {}  # other.did -> remap table
        self._order = None        # (order np, rank np, is_sorted)
        self._sorted = None       # the sorted-value sibling dictionary
        self._fixed_dev = None    # padded device value table (fixed dicts)

    @property
    def is_fixed(self) -> bool:
        return self.value_dtype is not DataType.STRING

    # -- constructors --------------------------------------------------------
    @staticmethod
    def from_byte_table(host_bytes: np.ndarray, host_offsets: np.ndarray,
                        value_dtype: DataType = DataType.STRING
                        ) -> "DeviceDictionary":
        """Intern a dictionary given its flat byte table (the exact layout
        the parquet dictionary-page parser produces)."""
        host_bytes = np.ascontiguousarray(host_bytes, dtype=np.uint8)
        host_offsets = np.ascontiguousarray(host_offsets, dtype=np.int32)
        h = hashlib.sha1()
        h.update(value_dtype.name.encode())
        h.update(host_offsets.tobytes())
        h.update(host_bytes[:int(host_offsets[-1])].tobytes())
        fp = h.hexdigest()
        with _DICT_CACHE_LOCK:
            got = _DICT_CACHE.get(fp)
            if got is not None:
                return got
        d = DeviceDictionary(host_bytes, host_offsets, fp, value_dtype)
        with _DICT_CACHE_LOCK:
            got = _DICT_CACHE.setdefault(fp, d)
            while len(_DICT_CACHE) > _DICT_CACHE_MAX:
                _DICT_CACHE.pop(next(iter(_DICT_CACHE)))
            return got

    @staticmethod
    def from_fixed_values(values: np.ndarray,
                          value_dtype: DataType) -> "DeviceDictionary":
        """Intern a FIXED-width dictionary (INT64/DATE/TIMESTAMP parquet
        dictionary chunks): the byte table is the raw little-endian value
        bytes at the dtype's uniform width."""
        npdt = value_dtype.to_np()
        values = np.ascontiguousarray(values, dtype=npdt)
        w = npdt.itemsize
        offsets = (np.arange(len(values) + 1, dtype=np.int64) * w)
        if int(offsets[-1]) > np.iinfo(np.int32).max:
            raise ValueError("fixed dictionary byte table exceeds int32")
        return DeviceDictionary.from_byte_table(
            values.view(np.uint8), offsets.astype(np.int32), value_dtype)

    @staticmethod
    def from_values(values: Sequence) -> "DeviceDictionary":
        """Intern a dictionary from python/numpy string values (serde
        decode, union builds, tests)."""
        encoded = [v.encode("utf-8") if isinstance(v, str) else bytes(v)
                   for v in values]
        offsets = np.zeros(len(encoded) + 1, dtype=np.int32)
        if encoded:
            np.cumsum([len(b) for b in encoded], out=offsets[1:])
        total = int(offsets[-1])
        buf = np.frombuffer(b"".join(encoded), dtype=np.uint8).copy() \
            if total else np.zeros(0, dtype=np.uint8)
        return DeviceDictionary.from_byte_table(buf, offsets)

    # -- host views ----------------------------------------------------------
    def value_bytes(self, code: int) -> bytes:
        o = self.host_offsets
        return self.host_bytes[o[code]:o[code + 1]].tobytes()

    def host_values(self) -> np.ndarray:
        """np array of decoded values (object str array for STRING, the
        value-dtype array for fixed; cached). The sink expansion and
        serde read through this."""
        with self._lock:
            if self._host_strs is None:
                if self.is_fixed:
                    self._host_strs = self.host_bytes[
                        :int(self.host_offsets[-1])].view(
                            self.value_dtype.to_np()).copy()
                else:
                    out = np.empty(self.size, dtype=object)
                    o = self.host_offsets
                    raw = self.host_bytes.tobytes()
                    for i in range(self.size):
                        out[i] = raw[o[i]:o[i + 1]].decode(
                            "utf-8", errors="replace")
                    self._host_strs = out
            return self._host_strs

    def _value_key(self, value) -> bytes:
        """Canonical byte key of one literal value (the representation
        `code_of` and the union builders compare on)."""
        if isinstance(value, str):
            return value.encode("utf-8")
        if self.is_fixed and isinstance(value, (int, np.integer)):
            return self.value_dtype.to_np().type(value).tobytes()
        return bytes(value)

    def code_of(self, value) -> int:
        """Code of a literal value, or -1 when absent (a code that can
        never match — the code-space translation of 'no row equals this
        literal')."""
        with self._lock:
            if self._code_of is None:
                o = self.host_offsets
                raw = self.host_bytes.tobytes()
                self._code_of = {raw[o[i]:o[i + 1]]: i
                                 for i in range(self.size)}
        return self._code_of.get(self._value_key(value), -1)

    # -- order-preserving views ----------------------------------------------
    def _order_rank(self):
        """(order rank->code, rank code->rank, is_sorted), cached once per
        interned dictionary. STRING values order by utf-8 BYTES — identical
        to code-point order and to the engine's device byte-matrix
        comparators (rowkeys.string_order_proxy); fixed values order
        numerically."""
        with self._lock:
            got = self._order
        if got is not None:
            return got
        if self.size == 0:
            built = (np.zeros(0, np.int32), np.zeros(0, np.int32), True)
        else:
            if self.is_fixed:
                vals = self.host_values()
            else:
                o = self.host_offsets
                raw = self.host_bytes.tobytes()
                vals = np.array([raw[o[i]:o[i + 1]]
                                 for i in range(self.size)], dtype=object)
            order = np.argsort(vals, kind="stable").astype(np.int32)
            rank = np.empty(self.size, np.int32)
            rank[order] = np.arange(self.size, dtype=np.int32)
            built = (order, rank,
                     bool((order == np.arange(self.size)).all()))
        with self._lock:
            if self._order is None:
                self._order = built
            return self._order

    @property
    def is_sorted(self) -> bool:
        """Code order == value order (the order-preserving property)."""
        return self._order_rank()[2]

    def rank_codes(self) -> np.ndarray:
        """int32 code->rank table (identity when already sorted). Always
        materialized — the SPMD absorbed-sort LUT and host rank transforms
        read through this."""
        order, rank, is_sorted = self._order_rank()
        if is_sorted:
            return np.arange(self.size, dtype=np.int32)
        return rank

    def rank_remap(self) -> Optional[np.ndarray]:
        """code -> rank permutation into `sorted_dict()`'s code space, in
        the exact shape `apply_remap` consumes (None = identity: this
        dictionary is already order-preserving)."""
        order, rank, is_sorted = self._order_rank()
        return None if is_sorted else rank

    def sorted_dict(self) -> "DeviceDictionary":
        """The interned dictionary holding the SAME values in ascending
        value order — its codes are ranks, so every downstream consumer
        (equality, hashing, joins, serde, materialize) works unchanged
        while code comparisons become value comparisons. Identity when
        already sorted; built + interned once per dictionary."""
        order, rank, is_sorted = self._order_rank()
        if is_sorted:
            return self
        with self._lock:
            if self._sorted is not None:
                return self._sorted
        o = self.host_offsets
        lens = self.host_lens[order]
        offsets = np.zeros(self.size + 1, dtype=np.int64)
        np.cumsum(lens, out=offsets[1:])
        buf = np.empty(int(offsets[-1]), dtype=np.uint8)
        for r, c in enumerate(order):
            buf[offsets[r]:offsets[r + 1]] = self.host_bytes[o[c]:o[c + 1]]
        sd = DeviceDictionary.from_byte_table(
            buf, offsets.astype(np.int32), self.value_dtype)
        with self._lock:
            if self._sorted is None:
                self._sorted = sd
            return self._sorted

    def count_lt_le(self, value) -> Tuple[int, int]:
        """(# values < literal, # values <= literal) in VALUE order — the
        rank thresholds a comparison predicate rewrites its literal to
        (docs/compressed-execution.md). Works on any dictionary via its
        sorted order; on a sorted dictionary the counts ARE code-space
        split points."""
        order, _rank, _s = self._order_rank()
        if self.size == 0:
            return 0, 0
        if self.is_fixed:
            svals = self.host_values()[order]
            v = self.value_dtype.to_np().type(value)
            return (int(np.searchsorted(svals, v, side="left")),
                    int(np.searchsorted(svals, v, side="right")))
        key = self._value_key(value)
        o = self.host_offsets
        raw = self.host_bytes.tobytes()
        lo = hi = 0
        import bisect

        svals = [raw[o[c]:o[c + 1]] for c in order]
        lo = bisect.bisect_left(svals, key)
        hi = bisect.bisect_right(svals, key)
        return lo, hi

    # -- device views --------------------------------------------------------
    def device_values(self):
        """(bytes_dev, offsets_dev, lens_dev) padded to pow2 buckets; one
        upload per dictionary per process (interned)."""
        with self._lock:
            if self._dev is None:
                cap = bucket_capacity(max(self.size, 1))
                total = int(self.host_offsets[-1])
                byte_cap = bucket_capacity(max(total, 8))
                buf = np.zeros(byte_cap, dtype=np.uint8)
                buf[:total] = self.host_bytes[:total]
                offs = np.full(cap + 1, total, dtype=np.int32)
                offs[:self.size + 1] = self.host_offsets
                lens = np.zeros(cap, dtype=np.int32)
                lens[:self.size] = self.host_lens
                self._dev = (jnp.asarray(buf), jnp.asarray(offs),
                             jnp.asarray(lens))
            return self._dev

    def device_fixed_values(self):
        """Padded device value table of a FIXED dictionary (one upload per
        interned dictionary) — the materialize gather's source."""
        assert self.is_fixed
        with self._lock:
            got = self._fixed_dev
        if got is not None:
            return got
        cap = bucket_capacity(max(self.size, 1))
        npdt = self.value_dtype.to_np()
        buf = np.zeros(cap, dtype=npdt)
        buf[:self.size] = self.host_values()
        built = jnp.asarray(buf)
        with self._lock:
            if self._fixed_dev is None:
                self._fixed_dev = built
            return self._fixed_dev

    def device_memory_size(self) -> int:
        total = 0
        if self._dev is not None:
            b, o, l = self._dev
            total += int(b.size + o.size * 4 + l.size * 4)
        if self._fixed_dev is not None:
            total += int(self._fixed_dev.size
                         * self._fixed_dev.dtype.itemsize)
        if self._hash_words is not None:
            total += sum(int(w.size) * 4 for w in self._hash_words)
        return total

    def hash_words(self):
        """Per-entry hash words (for STRING the exact (h1, h2, len) triple
        hashing.string_words derives from the expanded column; for fixed
        dictionaries the column_words of the value table), one jitted
        computation per dictionary: a row's hash words are then one gather
        by code, so hashing an encoded column is bit-identical to hashing
        its expansion — pieces with DIFFERENT dictionaries (or plain
        pieces) still co-partition."""
        with self._lock:
            if self._hash_words is not None:
                return self._hash_words
        if self.is_fixed:
            words = _dict_fixed_hash_words_kernel(
                self.device_fixed_values(), self.value_dtype,
                np.int32(self.size))
        else:
            byts, offs, _lens = self.device_values()
            words = _dict_hash_words_kernel(byts, offs, np.int32(self.size))
        with self._lock:
            if self._hash_words is None:
                self._hash_words = tuple(words)
            return self._hash_words

    # -- alignment -----------------------------------------------------------
    def remap_to(self, other: "DeviceDictionary") -> Optional[np.ndarray]:
        """int32 table mapping MY codes into `other`'s code space (-1 for
        values `other` lacks), or None when self is other (identity).
        Cached per target dictionary — the join's build-time remap."""
        if other is self:
            return None
        with self._lock:
            got = self._remaps.get(other.did)
            if got is not None:
                return got
        table = np.full(max(self.size, 1), -1, dtype=np.int32)
        for i in range(self.size):
            table[i] = other.code_of(self.value_bytes(i))
        with self._lock:
            self._remaps[other.did] = table
            return table

    def __repr__(self):
        return f"DeviceDictionary(size={self.size}, did={self.did})"


def _dict_fixed_hash_words_kernel(vals, value_dtype, size):
    from spark_rapids_tpu.engine.jit_cache import get_or_build

    key = ("dict_fixed_hash_words", value_dtype, int(vals.shape[0]))

    def build():
        def fn(v, n):
            from spark_rapids_tpu.ops import hashing as H
            from spark_rapids_tpu.ops.values import ColV

            cap = v.shape[0]
            validity = jnp.arange(cap) < n
            col = ColV(value_dtype, v, validity)
            return H.column_words(jnp, col)

        return jax.jit(fn)

    def _attempt():
        M.record_dispatch()
        return get_or_build(key, build)(vals, jnp.int32(size))

    from spark_rapids_tpu.engine.retry import with_retry

    return with_retry(_attempt, site="encoded.materialize")


def _dict_hash_words_kernel(byts, offs, size):
    from spark_rapids_tpu.engine.jit_cache import get_or_build

    key = ("dict_hash_words", int(byts.shape[0]), int(offs.shape[0]))

    def build():
        def fn(b, o, n):
            from spark_rapids_tpu.ops.hashing import _string_words_device
            from spark_rapids_tpu.ops.values import ColV

            cap = o.shape[0] - 1
            validity = jnp.arange(cap) < n
            col = ColV(DataType.STRING, b, validity, o)
            return _string_words_device(col)

        return jax.jit(fn)

    def _attempt():
        M.record_dispatch()
        return get_or_build(key, build)(byts, offs, jnp.int32(size))

    from spark_rapids_tpu.engine.retry import with_retry

    return with_retry(_attempt, site="encoded.materialize")


# ---------------------------------------------------------------------------
# DictionaryColumn
# ---------------------------------------------------------------------------
class DictionaryColumn(ColumnVector):
    """A first-class encoded column inside ColumnarBatch: logical dtype
    stays the value type (STRING), `data` holds int32 CODES into the
    shared `dictionary`, `validity` is the ordinary null mask (invalid
    lanes carry code 0). `materialize()` / `decode_batch()` are the only
    paths back to values."""

    __slots__ = ("dictionary",)

    def __init__(self, dtype: DataType, codes, validity,
                 dictionary: DeviceDictionary):
        super().__init__(dtype, codes, validity, None,
                         max_len=dictionary.max_len)
        self.dictionary = dictionary

    @property
    def capacity(self) -> int:
        return int(self.data.shape[0])

    def device_memory_size(self) -> int:
        # codes + validity; the shared dictionary is accounted once per
        # BATCH by ColumnarBatch.device_memory_size, not per column
        return int(self.data.size * 4 + self.validity.size)

    def with_codes(self, codes, validity,
                   dictionary: Optional[DeviceDictionary] = None
                   ) -> "DictionaryColumn":
        return DictionaryColumn(self.dtype, codes, validity,
                                dictionary or self.dictionary)

    def __repr__(self):
        return (f"DictionaryColumn({self.dtype.name}, cap={self.capacity}, "
                f"ndv={self.dictionary.size})")


def is_encoded(cv) -> bool:
    return isinstance(cv, DictionaryColumn)


def encoded_ordinals(batch: ColumnarBatch) -> Tuple[int, ...]:
    return tuple(i for i, c in enumerate(batch.columns) if is_encoded(c))


def codes_colv(cv: DictionaryColumn):
    """ColV view of the CODES (int32) — what code-space kernels consume."""
    from spark_rapids_tpu.ops.values import ColV

    return ColV(DataType.INT32, cv.data, cv.validity)


# ---------------------------------------------------------------------------
# Materialization (the ONLY decode paths; metric + retry/faultinject site)
# ---------------------------------------------------------------------------
# byte budget above which the sync-free (max_len-bounded) materialize
# buffer is declined in favor of one exact-total sync
_MATERIALIZE_BOUND_BUDGET = 64 << 20


def materialize(cv: DictionaryColumn,
                site: str = "encoded.materialize") -> ColumnVector:
    """Decode an encoded column to a dense device string column: one
    jitted gather of the dictionary bytes by code. A dispatch site — the
    gather runs under with_retry at the `encoded.materialize` fault-
    injection site; every call counts in lateMaterializations."""
    from spark_rapids_tpu.engine.retry import with_retry

    assert is_encoded(cv)
    M.record_late_materialization()
    d = cv.dictionary
    if d.is_fixed:
        # fixed-value dictionary: one jitted value-table gather
        vals = d.device_fixed_values()

        def _attempt_fixed():
            M.record_dispatch()
            return _materialize_fixed_kernel(vals, cv.data, cv.validity)

        data = with_retry(_attempt_fixed, site=site)
        vr = None
        from spark_rapids_tpu.columnar.batch import host_value_range

        if d.size:
            vr = host_value_range(d.value_dtype, d.host_values())
        return ColumnVector(cv.dtype, data, cv.validity, vrange=vr)
    byts, offs, lens = d.device_values()
    cap = cv.capacity
    bound = cap * d.max_len
    if bound <= max(4 * int(byts.shape[0]), _MATERIALIZE_BOUND_BUDGET):
        byte_cap = bucket_capacity(max(bound, 8))
    else:
        # skewed dictionary at a huge capacity: size exactly with one sync
        def _total():
            M.record_dispatch()
            return _materialize_total(byts.shape[0], lens, cv.data,
                                      cv.validity)

        total = int(jax.device_get(with_retry(_total, site=site)))
        byte_cap = bucket_capacity(max(total, 8))

    def _attempt():
        M.record_dispatch()
        return _materialize_kernel(byte_cap, byts, offs, lens, cv.data,
                                   cv.validity)

    out_bytes, out_offs = with_retry(_attempt, site=site)
    return ColumnVector(cv.dtype, out_bytes, cv.validity, out_offs,
                        max_len=d.max_len)


@jax.jit
def _materialize_fixed_kernel(vals, codes, validity):
    safe = jnp.clip(codes, 0, vals.shape[0] - 1)
    return jnp.where(validity, vals[safe], jnp.zeros((), vals.dtype))


@jax.jit
def _materialize_total(_nbytes, lens, codes, validity):
    safe = jnp.clip(codes, 0, lens.shape[0] - 1)
    return jnp.sum(jnp.where(validity, lens[safe], 0))


@functools.partial(jax.jit, static_argnums=(0,))
def _materialize_kernel(byte_cap: int, byts, offs, lens, codes, validity):
    from spark_rapids_tpu.columnar.strings import build_from_plan

    cap = codes.shape[0]
    safe = jnp.clip(codes, 0, lens.shape[0] - 1)
    starts = offs[safe]
    out_len = jnp.where(validity, lens[safe], 0)
    return build_from_plan([byts], jnp.zeros((cap,), jnp.int32), starts,
                           out_len, byte_cap)


def decode_batch(batch: ColumnarBatch,
                 site: str = "encoded.materialize") -> ColumnarBatch:
    """Materialize every encoded column of a batch (the operator-boundary
    decode). No-op (and zero-cost) when nothing is encoded."""
    if not any(is_encoded(c) for c in batch.columns):
        return batch
    cols = [materialize(c, site=site) if is_encoded(c) else c
            for c in batch.columns]
    return ColumnarBatch(cols, batch.num_rows, live=batch.live,
                         owned=batch.owned)


def materialize_host_values(codes: np.ndarray, validity: np.ndarray,
                            dictionary: DeviceDictionary) -> np.ndarray:
    """Host-side expansion at the result sink / serde boundary: one numpy
    take through the dictionary's host values — the cheap form of late
    materialization (codes crossed the fence, values never did)."""
    M.record_late_materialization()
    if dictionary.is_fixed:
        npdt = dictionary.value_dtype.to_np()
        if dictionary.size == 0:
            return np.zeros(len(codes), dtype=npdt)
        vals = dictionary.host_values()
        out = vals[np.clip(codes, 0, dictionary.size - 1)]
        return np.where(validity, out, npdt.type(0))
    if dictionary.size == 0:
        return np.full(len(codes), "", dtype=object)
    vals = dictionary.host_values()
    out = vals[np.clip(codes, 0, dictionary.size - 1)]
    if not validity.all():
        out = np.where(validity, out, "")
    return out.astype(object)


# ---------------------------------------------------------------------------
# Host-side encoded column (the serialized-shuffle / serde representation)
# ---------------------------------------------------------------------------
from spark_rapids_tpu.columnar.batch import HostColumnVector  # noqa: E402


class HostDictionaryColumn(HostColumnVector):
    """Host mirror of DictionaryColumn: `data` holds int32 codes, the
    shared dictionary holds the values. Exists transiently on the
    serialized-shuffle / spill path (to_host_many(keep_encoded=True) ->
    serde -> to_device) and between a write's sink and Arrow
    (io/arrow_convert.py hands it over as a dictionary array); any
    value access decodes through the host dictionary."""

    __slots__ = ("dictionary",)

    def __init__(self, dtype: DataType, codes: np.ndarray,
                 validity: np.ndarray, dictionary: DeviceDictionary):
        super().__init__(dtype, np.asarray(codes, dtype=np.int32),
                         np.asarray(validity, dtype=bool))
        self.dictionary = dictionary

    def rows(self, key) -> "HostDictionaryColumn":
        # a dictionary column's codes mean nothing without it
        return HostDictionaryColumn(self.dtype, self.data[key],
                                    self.validity[key], self.dictionary)

    def decoded(self) -> HostColumnVector:
        values = materialize_host_values(self.data, self.validity,
                                         self.dictionary)
        return HostColumnVector(self.dtype, values, self.validity)

    def to_pylist(self):
        return self.decoded().to_pylist()


# ---------------------------------------------------------------------------
# Code remaps / alignment
# ---------------------------------------------------------------------------
def apply_remap(cv: DictionaryColumn, remap: Optional[np.ndarray],
                target: DeviceDictionary) -> DictionaryColumn:
    """Rewrite a column's codes into `target`'s code space through a host
    remap table (None = identity). One jitted gather."""
    if remap is None:
        return cv if cv.dictionary is target else \
            DictionaryColumn(cv.dtype, cv.data, cv.validity, target)
    from spark_rapids_tpu.columnar.batch import device_const

    M.record_dispatch()
    new_codes = _remap_kernel(device_const(remap), cv.data, cv.validity)
    return DictionaryColumn(cv.dtype, new_codes, cv.validity, target)


@jax.jit
def _remap_kernel(remap, codes, validity):
    safe = jnp.clip(codes, 0, remap.shape[0] - 1)
    # invalid lanes keep code 0 (zeros-under-null convention)
    return jnp.where(validity, remap[safe], 0).astype(jnp.int32)


def to_rank_space(cv: DictionaryColumn) -> DictionaryColumn:
    """Re-encode a column through its dictionary's SORTED sibling so code
    order == value order (one jitted permutation gather; identity — zero
    dispatches — when the dictionary is already order-preserving). The
    result is an ordinary encoded column over an interned dictionary:
    every downstream consumer works unchanged, and sorts / range bounds /
    min-max / comparisons now compute on the codes directly. NOT a decode
    — lateMaterializations is untouched."""
    d = cv.dictionary
    return apply_remap(cv, d.rank_remap(), d.sorted_dict())


def batch_to_rank_space(batch: ColumnarBatch, ords) -> ColumnarBatch:
    """`to_rank_space` over a subset of a batch's encoded columns."""
    if not ords:
        return batch
    cols = list(batch.columns)
    changed = False
    for i in ords:
        if is_encoded(cols[i]) and not cols[i].dictionary.is_sorted:
            cols[i] = to_rank_space(cols[i])
            changed = True
    if not changed:
        return batch
    return ColumnarBatch(cols, batch.num_rows, live=batch.live,
                         owned=batch.owned)


def align_encoded(cols: Sequence[DictionaryColumn]
                  ) -> Tuple[DeviceDictionary, List[DictionaryColumn]]:
    """Bring same-position encoded columns of several batches onto ONE
    shared dictionary (union of values), remapping codes where needed —
    the concat/merge alignment. Identity-interned dictionaries make the
    no-op path the common case."""
    base = cols[0].dictionary
    dicts = [c.dictionary for c in cols]
    if all(d is base for d in dicts):
        return base, list(cols)
    # single pass over all distinct dictionaries: base's entries keep
    # their codes, each value some later dictionary adds appends ONCE —
    # one intern of the final union instead of a chained pairwise fold
    # (which re-hashed the growing union per piece: O(pieces * ndv))
    o = base.host_offsets
    raw = base.host_bytes.tobytes()
    mapping = {raw[o[i]:o[i + 1]]: i for i in range(base.size)}
    pieces = [base.host_bytes[:int(o[-1])]]
    lens = list(base.host_lens)
    seen = {id(base)}
    for d in dicts[1:]:
        if id(d) in seen:
            continue
        seen.add(id(d))
        od = d.host_offsets
        rd = d.host_bytes.tobytes()
        for i in range(d.size):
            b = rd[od[i]:od[i + 1]]
            if b not in mapping:
                mapping[b] = len(mapping)
                pieces.append(d.host_bytes[od[i]:od[i + 1]])
                lens.append(int(od[i + 1] - od[i]))
    if len(mapping) == base.size:
        union = base
    else:
        offsets = np.zeros(len(lens) + 1, dtype=np.int32)
        np.cumsum(lens, out=offsets[1:])
        union = DeviceDictionary.from_byte_table(
            np.concatenate(pieces), offsets, base.value_dtype)
    out = [apply_remap(c, c.dictionary.remap_to(union), union)
           for c in cols]
    return union, out


def union_rank_tables(dicts: Sequence[DeviceDictionary]
                      ) -> Dict[int, np.ndarray]:
    """{did: int32 code -> GLOBAL rank} over the VALUE UNION of several
    dictionaries — the host-side transform that makes range-partition
    bounds comparable across pieces carrying different dictionaries
    (codes download, values never do). Ranks are dense over the union's
    distinct values, so ties across dictionaries collapse to one rank
    and the quantile split points are exact."""
    if len(dicts) == 1:
        d = dicts[0]
        return {d.did: d.rank_codes()}
    fixed = dicts[0].is_fixed
    per_dict = []
    for d in dicts:
        if fixed:
            per_dict.append(np.asarray(d.host_values()))
        else:
            o = d.host_offsets
            raw = d.host_bytes.tobytes()
            per_dict.append([raw[o[i]:o[i + 1]] for i in range(d.size)])
    if fixed:
        union = np.unique(np.concatenate(
            [v for v in per_dict if len(v)])) if any(
            len(v) for v in per_dict) else np.zeros(0)
        return {d.did: np.searchsorted(union, vals).astype(np.int32)
                for d, vals in zip(dicts, per_dict)}
    union = sorted(set(b for vals in per_dict for b in vals))
    pos = {b: i for i, b in enumerate(union)}
    return {d.did: np.asarray([pos[b] for b in vals], dtype=np.int32)
            if vals else np.zeros(0, np.int32)
            for d, vals in zip(dicts, per_dict)}


def join_remap(stream_dict: DeviceDictionary,
               build_dict: DeviceDictionary) -> Optional[np.ndarray]:
    """Build-time code-remap table for a dictionary-keyed hash join:
    stream codes -> build codes (-1 = value absent from the build side,
    which can never match a build row — exactly the join semantics of an
    absent key). None = the sides already share a dictionary."""
    return stream_dict.remap_to(build_dict)


def remapped_codes_colv(cv: DictionaryColumn, remap: Optional[np.ndarray]):
    """ColV of codes remapped into another dictionary's space (identity
    when remap is None) — the join key substitution."""
    if remap is None:
        return codes_colv(cv)
    from spark_rapids_tpu.columnar.batch import device_const
    from spark_rapids_tpu.ops.values import ColV

    M.record_dispatch()
    codes = _remap_join_kernel(device_const(remap), cv.data, cv.validity)
    return ColV(DataType.INT32, codes, cv.validity)


@jax.jit
def _remap_join_kernel(remap, codes, validity):
    safe = jnp.clip(codes, 0, remap.shape[0] - 1)
    # absent values keep -1 (never equal to a real build code); invalid
    # lanes are excluded by validity at the key-proxy layer anyway
    return jnp.where(validity, remap[safe],
                     jnp.int32(-1)).astype(jnp.int32)


# ---------------------------------------------------------------------------
# Code-space predicate rewrite
# ---------------------------------------------------------------------------
def _is_str_literal(e) -> bool:
    from spark_rapids_tpu.ops.literals import Literal

    return isinstance(e, Literal) and (
        e.data_type is DataType.STRING or e.value is None)


_FIXED_DICT_DTYPES = (DataType.INT64, DataType.DATE, DataType.TIMESTAMP)


def _is_enc_literal(e, ref) -> bool:
    """Is `e` a literal translatable into the code space of a reference's
    value type? STRING columns take string literals; fixed dictionary
    columns take integral literals of a matching kind (an INT32 literal
    against an INT64 column is fine — the value embeds exactly)."""
    from spark_rapids_tpu.ops.literals import Literal

    if not isinstance(e, Literal):
        return False
    if e.value is None:
        return True
    rdt = ref.data_type
    if rdt is DataType.STRING:
        return e.data_type is DataType.STRING
    if rdt is DataType.INT64:
        return e.data_type in (DataType.INT32, DataType.INT64)
    return e.data_type is rdt


def classify_code_refs(exprs: Sequence, enc_ids, ref_pred, ref_id):
    """(code_ids, rank_ids): the subset of `enc_ids` whose EVERY reference
    across `exprs` sits in a code-space-computable position — equality /
    null-safe equality against a literal, IN over literals, IS [NOT]
    NULL, and ORDER comparisons (<, <=, >, >=, i.e. BETWEEN after
    lowering) against a literal. Ids with at least one ORDER-comparison
    use land in `rank_ids` (a subset of code_ids): their column must
    re-encode through the order-preserving sorted dictionary
    (`to_rank_space`) before the rewritten predicate runs, because code
    order is not value order on an arbitrary dictionary. Any other use
    (LIKE, concat, arithmetic, ...) needs the values — the column
    materializes instead.

    Parameterized over the reference node kind so the same walk serves
    bound trees (BoundReference.ordinal — the exec layer) and unbound
    trees (AttributeReference.expr_id — the plan-time analyzer)."""
    from spark_rapids_tpu.ops.literals import Literal
    from spark_rapids_tpu.ops.nulls import IsNotNull, IsNull
    from spark_rapids_tpu.ops.predicates import (
        EqualNullSafe,
        EqualTo,
        GreaterThan,
        GreaterThanOrEqual,
        In,
        LessThan,
        LessThanOrEqual,
    )

    ok = set(enc_ids)
    rank = set()

    def is_enc_ref(e) -> bool:
        return ref_pred(e) and ref_id(e) in enc_ids

    def walk(e) -> None:
        if isinstance(e, (EqualTo, EqualNullSafe)):
            l, r = e.left, e.right
            if is_enc_ref(l) and _is_enc_literal(r, l):
                return
            if is_enc_ref(r) and _is_enc_literal(l, r):
                return
        elif isinstance(e, (LessThan, LessThanOrEqual, GreaterThan,
                            GreaterThanOrEqual)):
            l, r = e.left, e.right
            if is_enc_ref(l) and _is_enc_literal(r, l):
                rank.add(ref_id(l))
                return
            if is_enc_ref(r) and _is_enc_literal(l, r):
                rank.add(ref_id(r))
                return
        elif isinstance(e, In):
            if is_enc_ref(e.value) and \
                    all(isinstance(c, Literal) for c in e.candidates) and \
                    all(_is_enc_literal(c, e.value) for c in e.candidates):
                return
        elif isinstance(e, (IsNull, IsNotNull)) and is_enc_ref(e.child):
            return
        if is_enc_ref(e):
            ok.discard(ref_id(e))
            return
        for c in e.children():
            walk(c)

    for e in exprs:
        walk(e)
    return ok, rank & ok


def supported_code_refs(exprs: Sequence, enc_ids, ref_pred, ref_id):
    """classify_code_refs restricted to pure code space (no ORDER
    comparisons admitted) — for callers that cannot re-encode through the
    sorted dictionary (the SPMD stage's in-trace rewrite)."""
    ok, rank = classify_code_refs(exprs, enc_ids, ref_pred, ref_id)
    return ok - rank


def bound_supported_refs(exprs: Sequence, enc_ords):
    from spark_rapids_tpu.ops.base import BoundReference

    return supported_code_refs(
        exprs, set(enc_ords),
        lambda e: isinstance(e, BoundReference),
        lambda e: e.ordinal)


def unbound_supported_refs(exprs: Sequence, enc_expr_ids):
    from spark_rapids_tpu.ops.base import AttributeReference

    return supported_code_refs(
        exprs, set(enc_expr_ids),
        lambda e: isinstance(e, AttributeReference),
        lambda e: e.expr_id)


def classify_bound_refs(exprs: Sequence, enc_ords):
    from spark_rapids_tpu.ops.base import BoundReference

    return classify_code_refs(
        exprs, set(enc_ords),
        lambda e: isinstance(e, BoundReference),
        lambda e: e.ordinal)


def classify_unbound_refs(exprs: Sequence, enc_expr_ids):
    from spark_rapids_tpu.ops.base import AttributeReference

    return classify_code_refs(
        exprs, set(enc_expr_ids),
        lambda e: isinstance(e, AttributeReference),
        lambda e: e.expr_id)


def rewrite_condition(expr, dict_by_id, ref_pred, ref_id, make_ref):
    """Rewrite a predicate into code space for the references in
    `dict_by_id` (id -> DeviceDictionary): literals translate to their
    dictionary code ONCE here (absent values become -1, a code no row
    carries), references retype to INT32, and the numeric comparison
    kernels do the rest.

    ORDER comparisons (<, <=, >, >=) rewrite their literal to a RANK
    THRESHOLD: the caller must have re-encoded the column through the
    order-preserving sorted dictionary (to_rank_space) and pass THAT
    dictionary here, so its codes are ranks and `count_lt_le` yields the
    exact code-space split points (value < x  <=>  code < #{v: v < x}).
    Callers must have proven supportedness with classify_code_refs
    first."""
    from spark_rapids_tpu.ops.literals import Literal
    from spark_rapids_tpu.ops.nulls import IsNotNull, IsNull
    from spark_rapids_tpu.ops.predicates import (
        EqualNullSafe,
        EqualTo,
        GreaterThan,
        GreaterThanOrEqual,
        In,
        LessThan,
        LessThanOrEqual,
    )

    def lit_code(d, lit) -> "Literal":
        if lit.value is None:
            return Literal(None, DataType.INT32)
        return Literal(int(d.code_of(lit.value)), DataType.INT32)

    def rank_lit(d, lit, ref_side: str, cls) -> "Literal":
        """Rank threshold for one comparison: lt = #{v < x}, le = #{v <=
        x}. With ranks r in [0, size): v < x <=> r < lt; v <= x <=> r <=
        le-1; v > x <=> r > le-1; v >= x <=> r >= lt. Mirrored when the
        reference sits on the RIGHT (lit OP col reads col OP' lit)."""
        if lit.value is None:
            return Literal(None, DataType.INT32)
        lt, le = d.count_lt_le(lit.value)
        if ref_side == "left":
            want_lt = cls in (LessThan, GreaterThanOrEqual)
        else:
            want_lt = cls in (LessThanOrEqual, GreaterThan)
        return Literal(int(lt if want_lt else le - 1), DataType.INT32)

    def rw(e):
        if isinstance(e, (EqualTo, EqualNullSafe)):
            l, r = e.left, e.right
            if ref_pred(l) and ref_id(l) in dict_by_id and \
                    _is_enc_literal(r, l):
                d = dict_by_id[ref_id(l)]
                return type(e)(make_ref(l), lit_code(d, r))
            if ref_pred(r) and ref_id(r) in dict_by_id and \
                    _is_enc_literal(l, r):
                d = dict_by_id[ref_id(r)]
                return type(e)(lit_code(d, l), make_ref(r))
        elif isinstance(e, (LessThan, LessThanOrEqual, GreaterThan,
                            GreaterThanOrEqual)):
            l, r = e.left, e.right
            if ref_pred(l) and ref_id(l) in dict_by_id and \
                    _is_enc_literal(r, l):
                d = dict_by_id[ref_id(l)]
                return type(e)(make_ref(l),
                               rank_lit(d, r, "left", type(e)))
            if ref_pred(r) and ref_id(r) in dict_by_id and \
                    _is_enc_literal(l, r):
                d = dict_by_id[ref_id(r)]
                return type(e)(rank_lit(d, l, "right", type(e)),
                               make_ref(r))
        elif isinstance(e, In):
            v = e.value
            if ref_pred(v) and ref_id(v) in dict_by_id:
                d = dict_by_id[ref_id(v)]
                return In(make_ref(v),
                          [lit_code(d, c) for c in e.candidates])
        elif isinstance(e, (IsNull, IsNotNull)):
            c = e.child
            if ref_pred(c) and ref_id(c) in dict_by_id:
                return type(e)(make_ref(c))
        return e.with_children([rw(c) for c in e.children()]) \
            if e.children() else e

    return rw(expr)


def rewrite_bound_condition(expr, dict_by_ord: Dict[int, DeviceDictionary]):
    from spark_rapids_tpu.ops.base import BoundReference

    return rewrite_condition(
        expr, dict_by_ord,
        lambda e: isinstance(e, BoundReference),
        lambda e: e.ordinal,
        lambda e: BoundReference(e.ordinal, DataType.INT32, e.nullable))


def rewrite_unbound_condition(expr, dict_by_eid, attr_by_eid):
    from spark_rapids_tpu.ops.base import AttributeReference

    return rewrite_condition(
        expr, dict_by_eid,
        lambda e: isinstance(e, AttributeReference),
        lambda e: e.expr_id,
        lambda e: attr_by_eid[e.expr_id])


# ---------------------------------------------------------------------------
# Filter planning (exec/basic.py TpuFilterExec via ops/eval.DeviceFilter)
# ---------------------------------------------------------------------------
class FilterPlan:
    """Per-(condition, dictionary-set) filter rewrite: which ordinals stay
    codes, which of those must first re-encode through the sorted
    dictionary (`rank_ords` — ORDER comparisons over them), the rewritten
    condition, and which must materialize."""

    __slots__ = ("condition", "code_ords", "rank_ords", "mat_ords", "sig")

    def __init__(self, condition, code_ords, rank_ords, mat_ords, sig):
        self.condition = condition
        self.code_ords = code_ords
        self.rank_ords = rank_ords
        self.mat_ords = mat_ords
        self.sig = sig


def plan_filter(bound_condition, batch: ColumnarBatch) -> Optional[FilterPlan]:
    """None when the batch carries no encoded columns; otherwise the
    code-space rewrite of the condition for the supported ordinals plus
    the (visible) materialize set for the rest. Ordinals with ORDER
    comparisons rewrite against the SORTED dictionary — the caller
    converts those columns with batch_to_rank_space before evaluating."""
    enc = {i: c for i, c in enumerate(batch.columns) if is_encoded(c)}
    if not enc:
        return None
    ok, rank = classify_bound_refs([bound_condition], enc.keys())
    referenced = _bound_ref_ords(bound_condition)
    mat = sorted((set(enc) - ok) & referenced)
    dict_by_ord = {i: (enc[i].dictionary.sorted_dict() if i in rank
                       else enc[i].dictionary) for i in ok}
    cond = rewrite_bound_condition(bound_condition, dict_by_ord) \
        if dict_by_ord else bound_condition
    sig = tuple(sorted((i, enc[i].dictionary.did) for i in ok)) + \
        ("rank",) + tuple(sorted(rank)) + ("mat",) + tuple(mat)
    return FilterPlan(cond, frozenset(ok), frozenset(rank), tuple(mat), sig)


def enc_sig(batch: ColumnarBatch) -> tuple:
    """(ordinal, dictionary id) signature of a batch's encoded columns —
    dictionaries are interned, so this fully determines every code-space
    plan (rewritten literals, remaps, retyped attrs) for fixed
    expressions: the memo key for per-batch planning."""
    return tuple(sorted((i, c.dictionary.did)
                        for i, c in enumerate(batch.columns)
                        if is_encoded(c)))


def _bound_ref_ords(expr) -> set:
    from spark_rapids_tpu.ops.base import BoundReference

    return {r.ordinal
            for r in expr.collect(lambda x: isinstance(x, BoundReference))}


def batch_with_materialized(batch: ColumnarBatch, ords,
                            site: str = "encoded.materialize"
                            ) -> ColumnarBatch:
    """Materialize a subset of a batch's encoded columns (the boundary
    decode for a consumer that needs those values)."""
    if not ords:
        return batch
    cols = list(batch.columns)
    for i in ords:
        if is_encoded(cols[i]):
            cols[i] = materialize(cols[i], site=site)
    return ColumnarBatch(cols, batch.num_rows, live=batch.live,
                         owned=batch.owned)


def eval_cols(batch: ColumnarBatch, code_ords=()):
    """ColV list for kernel evaluation: codes for the ordinals kept in
    code space; every other encoded ordinal must have been materialized
    already (ops/eval._col_to_colv raises on a stray DictionaryColumn)."""
    from spark_rapids_tpu.ops.eval import _col_to_colv

    out = []
    for i, c in enumerate(batch.columns):
        if is_encoded(c) and i in code_ords:
            out.append(codes_colv(c))
        else:
            out.append(_col_to_colv(c))
    return out


# ---------------------------------------------------------------------------
# Aggregate planning (exec/aggregate.py): group directly on codes
# ---------------------------------------------------------------------------
class AggEncPlan:
    """Per-(batch dictionaries) update-kernel plan: which input ordinals
    stay codes (and which of those re-encode through the SORTED
    dictionary first — `rank_ords`: min/max inputs and order-comparison
    filters), the retyped attrs/keys and code-space filters to bind the
    kernel with, and which OUTPUT positions wrap back into
    DictionaryColumn: grouping keys AND min/max buffers (the dictionary
    is gathered only at the sink — the finalize decode point is
    closed)."""

    __slots__ = ("attrs", "key_exprs", "filters", "code_ords", "rank_ords",
                 "mat_ords", "key_dicts", "buf_dicts", "out_dicts", "sig")

    def __init__(self, attrs, key_exprs, filters, code_ords, rank_ords,
                 mat_ords, key_dicts, buf_dicts, out_dicts, sig):
        self.attrs = attrs
        self.key_exprs = key_exprs
        self.filters = filters
        self.code_ords = code_ords
        self.rank_ords = rank_ords     # batch ordinals -> to_rank_space
        self.mat_ords = mat_ords
        self.key_dicts = key_dicts     # key position -> DeviceDictionary
        self.buf_dicts = buf_dicts     # buffer slot -> DeviceDictionary
        self.out_dicts = out_dicts     # inter position -> DeviceDictionary
        self.sig = sig


def plan_agg_update(batch: ColumnarBatch, child_attrs, key_exprs,
                    input_exprs, filters, op_names=()) -> Optional[AggEncPlan]:
    """None when the batch has no encoded columns. An encoded column stays
    CODES through the update kernel when its only uses are (a) a bare
    grouping-key reference — grouping on codes partitions rows exactly
    like grouping on values, since codes are injective per dictionary —
    (b) code-space-supported filter predicates, and (c) a bare MIN/MAX
    aggregate input: the column re-encodes through the order-preserving
    sorted dictionary (rank_ords) and the reduction runs over int32 ranks,
    emitting the winning CODE per group — the value gathers only at the
    sink. Any other aggregate-input use needs the values and decodes at
    the boundary instead."""
    from spark_rapids_tpu.ops.base import Alias, AttributeReference

    enc = {i: c for i, c in enumerate(batch.columns) if is_encoded(c)}
    if not enc:
        return None
    enc_by_eid = {child_attrs[i].expr_id: (i, c) for i, c in enc.items()
                  if i < len(child_attrs)}

    def bare_eid(e):
        inner = e.child if isinstance(e, Alias) else e
        if isinstance(inner, AttributeReference):
            return inner.expr_id
        return None

    def refs(e):
        return {r.expr_id for r in e.collect(
            lambda x: isinstance(x, AttributeReference))}

    # aggregate inputs: bare min/max references reduce over ranks; every
    # other input use needs values
    minmax_eids = set()
    other_input_refs = set()
    for xi, e in enumerate(input_exprs):
        op = op_names[xi] if xi < len(op_names) else None
        b = e.expr_id if isinstance(e, AttributeReference) else None
        if op in ("min", "max") and b is not None and b in enc_by_eid:
            minmax_eids.add(b)
        else:
            other_input_refs |= refs(e)
    minmax_eids -= other_input_refs
    nonbare_key_refs = set()
    for e in key_exprs:
        b = bare_eid(e)
        r = refs(e)
        if b is not None:
            r = r - {b}
        nonbare_key_refs |= r
    if filters:
        filter_ok, filter_rank = classify_unbound_refs(
            filters, enc_by_eid.keys())
    else:
        filter_ok, filter_rank = set(enc_by_eid), set()
    kept_eids = {eid for eid in enc_by_eid
                 if eid not in other_input_refs
                 and eid not in nonbare_key_refs
                 and eid in filter_ok}
    minmax_eids &= kept_eids
    rank_eids = (minmax_eids | filter_rank) & kept_eids
    code_ords = frozenset(enc_by_eid[eid][0] for eid in kept_eids)
    rank_ords = frozenset(enc_by_eid[eid][0] for eid in rank_eids)
    referenced = other_input_refs | nonbare_key_refs | minmax_eids
    for e in input_exprs:
        referenced |= refs(e)
    for e in key_exprs:
        b = bare_eid(e)
        if b is not None:
            referenced.add(b)
    for f in filters:
        referenced |= refs(f)
    mat_ords = tuple(sorted(
        enc_by_eid[eid][0] for eid in enc_by_eid
        if eid not in kept_eids and eid in referenced))

    def eff_dict(eid) -> DeviceDictionary:
        d = enc_by_eid[eid][1].dictionary
        return d.sorted_dict() if eid in rank_eids else d

    attr2_by_eid = {}
    attrs2 = list(child_attrs)
    for eid in kept_eids:
        i, c = enc_by_eid[eid]
        a = child_attrs[i]
        a2 = AttributeReference(a.name, DataType.INT32, a.nullable,
                                a.expr_id)
        attr2_by_eid[eid] = a2
        attrs2[i] = a2
    key_exprs2 = []
    key_dicts = {}
    for k, e in enumerate(key_exprs):
        b = bare_eid(e)
        if b is not None and b in kept_eids:
            a2 = attr2_by_eid[b]
            key_exprs2.append(Alias(a2, e.name, e.expr_id)
                              if isinstance(e, Alias) else a2)
            key_dicts[k] = eff_dict(b)
        else:
            key_exprs2.append(e)
    buf_dicts = {}
    for xi, e in enumerate(input_exprs):
        b = e.expr_id if isinstance(e, AttributeReference) else None
        if b is not None and b in minmax_eids:
            buf_dicts[xi] = eff_dict(b)
    dict_by_eid = {eid: eff_dict(eid) for eid in kept_eids}
    filters2 = [rewrite_unbound_condition(f, dict_by_eid, attr2_by_eid)
                for f in filters] if dict_by_eid else list(filters)
    out_dicts = dict(key_dicts)
    for bi, d in buf_dicts.items():
        out_dicts[len(key_exprs) + bi] = d
    sig = tuple(sorted((i, c.dictionary.did) for i, c in enc.items()))
    return AggEncPlan(attrs2, key_exprs2, filters2, code_ords, rank_ords,
                      mat_ords, key_dicts, buf_dicts, out_dicts, sig)


def wrap_batch_cols(batch: ColumnarBatch,
                    dicts: Dict[int, DeviceDictionary]) -> ColumnarBatch:
    """Re-wrap code-valued output columns as DictionaryColumn (the
    aggregate's assembled key columns, a fused stage's passthroughs)."""
    if not dicts:
        return batch
    cols = list(batch.columns)
    for i, d in dicts.items():
        c = cols[i]
        cols[i] = DictionaryColumn(d.value_dtype, c.data, c.validity, d)
    return ColumnarBatch(cols, batch.num_rows, live=batch.live,
                         owned=batch.owned)


# ---------------------------------------------------------------------------
# Scan heuristics + emission accounting (io/parquet_device.py, io/scan.py)
# ---------------------------------------------------------------------------
def scan_encoded_ok(ndv: int, rows: int, max_fraction: float) -> bool:
    """Per-column opt-in: a dictionary-encoded chunk stays encoded only
    when ndv/rows clears the heuristic (near-unique columns gain nothing
    from codes and pay the dictionary twice)."""
    if rows <= 0 or ndv <= 0:
        return False
    return (ndv / rows) <= max_fraction


def decoded_bytes_per_row(value_dtype: DataType) -> int:
    """Per-row device bytes of the DECODED representation an encoded
    column avoided: the engine-wide string estimate for STRING values,
    physical width + validity for fixed values. Shared by the measured
    encodedBytesSaved metric and the analyzer's prediction — the two must
    stay one formula."""
    if value_dtype is DataType.STRING:
        return STR_BYTES_PER_ROW
    from spark_rapids_tpu.columnar.batch import physical_np_dtype

    return int(physical_np_dtype(value_dtype).itemsize) + 1


def record_scan_emission(cv: DictionaryColumn, rows: int) -> None:
    """Metrics at the scan boundary: one encoded column emitted, and the
    HBM it avoided versus the decoded estimate (the deterministic formula
    the analyzer predicts an interval for)."""
    M.record_encoded_column()
    M.record_encoded_bytes_saved(
        max(0, rows) * max(0, decoded_bytes_per_row(
            cv.dictionary.value_dtype) - CODE_BYTES_PER_ROW))
