"""Shuffle exchange execs + partitioners (tier A).

Reference parity:
- GpuShuffleExchangeExec.scala:122-243 — compute partition indices on the
  device, slice the batch into per-partition batches, hand (partId, batch)
  pairs to the shuffle -> `TpuShuffleExchangeExec` computes per-row partition
  ids in one jit (hash/range/round-robin), sorts rows by partition id and
  slices contiguously (the `sliceInternalOnGpu` contiguous-split analog,
  GpuPartitioning.scala:29-120).
- Partitioners (GpuHashPartitioning / GpuRangePartitioner with driver-side
  sample + bounds / GpuRoundRobinPartitioning / GpuSinglePartitioning)
  -> the Partitioning hierarchy below. Hashing is the framework's own
  murmur-style mix (ops/hashing.py) — consistent across both engines.
- In-process map outputs stay device-resident, which is the reference's
  OPT-IN RapidsShuffleManager behavior (shuffle partitions cached in the
  device store, RapidsShuffleInternalManager.scala:92-141) promoted to the
  default here; host serialization only happens at explicit boundaries.

The exchange materializes eagerly at execute() (a stage boundary, like
Spark): a map job runs over child partitions via the task scheduler, each
map task returns its per-target slices, and the reduce-side iterator streams
the pieces for its partition in map order (deterministic).
"""

from __future__ import annotations

from typing import Any, List, Optional, Sequence, Tuple

import numpy as np

from spark_rapids_tpu import _jax_setup  # noqa: F401
import jax
import jax.numpy as jnp

from spark_rapids_tpu import conf as C
from spark_rapids_tpu.columnar.batch import (
    ColumnarBatch,
    ColumnVector,
    HostColumnarBatch,
    HostColumnVector,
    bucket_capacity,
    gather_batch,
)
from spark_rapids_tpu.columnar.dtypes import DataType
from spark_rapids_tpu.exec import rowkeys as RK
from spark_rapids_tpu.exec.base import (
    CpuExec,
    ExecContext,
    PartitionedBatches,
    PhysicalExec,
    TpuExec,
    count_output,
)
from spark_rapids_tpu.ops import hashing as H
from spark_rapids_tpu.ops.base import (
    AttributeReference,
    Expression,
    SortOrder,
)
from spark_rapids_tpu.ops.bind import bind_all, bind_sort_orders
from spark_rapids_tpu.ops.eval import (
    _col_to_colv,
    _host_to_colv,
    cpu_project,
)
from spark_rapids_tpu.ops.values import EvalContext, ScalarV
from spark_rapids_tpu.utils import metrics as M

# Max device bytes for a batch to be split into lazy zero-copy piece views
# instead of the count-synced contiguous split. Shared with the aggregate
# exec's lazy-update decision: an un-compacted partial-agg output bigger
# than this would hit the count sync here anyway, defeating the point.
LAZY_PIECE_CAP_BYTES = 4 << 20

# In-place re-executions of an upstream map partition per failed piece
# before the FetchFailedError surfaces to the task-level retry loop (each
# re-execution is a full recompute of the map task — cheap in-process, so
# the bound is generous; beyond it the task retry and then the query-level
# CPU fallback take over).
_FETCH_REMAP_ATTEMPTS = 6


# ===========================================================================
# Partitioning descriptors
# ===========================================================================
class Partitioning:
    num_partitions: int

    def describe(self) -> str:
        return type(self).__name__


class SinglePartitioning(Partitioning):
    def __init__(self):
        self.num_partitions = 1


class RoundRobinPartitioning(Partitioning):
    def __init__(self, num_partitions: int):
        self.num_partitions = num_partitions


class HashPartitioning(Partitioning):
    def __init__(self, exprs: Sequence[Expression], num_partitions: int):
        self.exprs = list(exprs)
        self.num_partitions = num_partitions

    def describe(self):
        return f"HashPartitioning({self.exprs!r}, {self.num_partitions})"

    def key_ids(self) -> Tuple[int, ...]:
        return tuple(
            e.expr_id for e in self.exprs
            if isinstance(e, AttributeReference))


class RangePartitioning(Partitioning):
    def __init__(self, orders: Sequence[SortOrder], num_partitions: int):
        self.orders = list(orders)
        self.num_partitions = num_partitions

    def describe(self):
        return f"RangePartitioning({self.orders!r}, {self.num_partitions})"


# ===========================================================================
# Shared exchange machinery
# ===========================================================================
class _ExchangeBase(PhysicalExec):
    def __init__(self, partitioning: Partitioning, child: PhysicalExec,
                 allow_adaptive: bool = True):
        super().__init__(child)
        self.partitioning = partitioning
        # False for user-specified repartition(n) and for exchanges feeding
        # a shuffled join (set at plan time / by the transition pass);
        # carried through every rebuild so the pin can never be lost
        self.allow_adaptive = allow_adaptive

    @property
    def output(self) -> List[AttributeReference]:
        return self.children[0].output

    def with_children(self, new_children):
        return type(self)(self.partitioning, new_children[0],
                          self.allow_adaptive)

    def output_partitioning(self):
        return self.partitioning

    @property
    def coalesce_after(self) -> bool:
        # reduce-side pieces are small; coalesce them back up
        # (reference: GpuShuffleExchangeExec coalesceAfter=true, :68)
        return True

    def node_name(self):
        return f"{type(self).__name__}({self.partitioning.describe()})"

    # -- shared runner -------------------------------------------------------
    # set by a runtime-broadcast probe that already executed (and
    # materialized) this exchange's child; consumed exactly once
    _pre_pb = None

    def set_pre_executed(self, pb: PartitionedBatches) -> None:
        self._pre_pb = pb

    def _child_pb(self, ctx: ExecContext) -> PartitionedBatches:
        """The input to exchange: a runtime-broadcast probe may have
        already executed (and materialized) the child — consume that
        exactly once so the child never runs twice. EVERY execute path
        (in-process, ICI, range) must come through here."""
        if self._pre_pb is not None:
            pb, self._pre_pb = self._pre_pb, None
            return pb
        return self.children[0].execute(ctx)

    def _materialize(self, ctx: ExecContext, map_fn) -> PartitionedBatches:
        """Run the map job; regroup slices into reduce buckets."""
        child_pb = self._child_pb(ctx)
        n_out = self.partitioning.num_partitions
        n_maps = child_pb.num_partitions
        serialize = ctx.conf.get(C.SHUFFLE_SERIALIZE)

        def run_map(pidx: int) -> List[List[Any]]:
            buckets: List[List[Any]] = [[] for _ in range(n_out)]

            def emit(routed) -> None:
                if serialize:
                    # ONE grouped device->host transfer for ALL of this
                    # batch's pieces (was one ~66 ms fence per piece —
                    # the PR 2 range-exchange grouped-transfer fix applied
                    # to the serialized map output; grouping per input
                    # batch bounds peak HBM at one batch's pieces)
                    routed = _encode_pieces_grouped(routed)
                for target, piece in routed:
                    buckets[target].append(piece)

            # issue-ahead pipelining (serialized tier only — without
            # serialization emit is a pure host append with nothing to
            # overlap): batch k's blocking encode/download runs AFTER
            # batch k+1's routing dispatches are issued, so the wire
            # time overlaps the device work already in flight (the
            # per-partition barrier the issue-ahead executor removes;
            # docs/async-execution.md)
            prev = None
            for batch in child_pb.iterator(pidx):
                if getattr(batch, "rows_on_host", True) and \
                        batch.num_rows == 0:
                    continue
                routed = [(target, piece)
                          for target, piece in map_fn(pidx, batch)
                          if not getattr(piece, "rows_on_host", True)
                          or piece.num_rows > 0]
                if not serialize:
                    emit(routed)
                    continue
                if prev is not None:
                    emit(prev)
                prev = routed
            if prev is not None:
                emit(prev)
            return buckets

        from spark_rapids_tpu.engine.scheduler import run_job_or_serial
        from spark_rapids_tpu.obs.trace import span as obs_span

        # the exchange map job IS a stage boundary: a traced query gets a
        # stage span covering its partition tasks (the task spans nest
        # under it via the scheduler's context propagation)
        with obs_span(f"stage:map:{self.node_name()}", kind="stage",
                      maps=n_maps, reducers=n_out):
            map_results = run_job_or_serial(ctx.scheduler, n_maps, run_map)
        reduce_buckets: List[List[Any]] = [[] for _ in range(n_out)]
        # piece provenance (map partition, index within its (map, target)
        # slice list): the lineage needed to RE-EXECUTE the upstream map
        # partition when a serialized piece cannot be fetched back — the
        # in-process analog of Spark's stage retry after FetchFailed
        piece_src: List[List[Tuple[int, int]]] = [[] for _ in range(n_out)]
        bytes_m = self.metrics["dataSize"]
        for m_idx, mb in enumerate(map_results):
            for t in range(n_out):
                for k, piece in enumerate(mb[t]):
                    if isinstance(piece, ColumnarBatch):
                        # bucket-held pieces may be re-read (task retry,
                        # fetch remap): they lose the consume-once
                        # donation proof here
                        piece.owned = False
                    reduce_buckets[t].append(piece)
                    piece_src[t].append((m_idx, k))
                    bytes_m.add(_piece_bytes(piece))

        to_device = self.placement == "tpu"

        # Map-output statistics (aqe/stats.py): per-bucket bytes, rows,
        # and piece costs from HOST-KNOWN metadata only — the measured
        # sizes the adaptive rule passes (and the coordinated join
        # coalescing) consume. Zero extra device syncs by construction:
        # a lazy piece whose count is device-resident reports rows
        # unknown instead of forcing one.
        from spark_rapids_tpu.aqe.stats import bucket_stats

        stats = bucket_stats(reduce_buckets,
                             lambda p: _piece_cost(p, n_out))
        costs = stats.bytes_per_bucket

        def decode_with_remap(piece: "_SerializedPiece", t: int, j: int):
            """Decode a serialized piece; on fetch failure re-execute its
            upstream map partition and decode the regenerated piece
            (bounded attempts — beyond them the failure surfaces and the
            task-level retry takes over)."""
            from spark_rapids_tpu.engine.cancel import check_cancel
            from spark_rapids_tpu.engine.scheduler import FetchFailedError

            attempts = 0
            while True:
                # a cancelled query must not burn fetch-remap attempts
                # re-running upstream maps it will never consume
                check_cancel("shuffle.remap")
                try:
                    return piece.decode(to_device)
                except FetchFailedError:
                    if attempts >= _FETCH_REMAP_ATTEMPTS:
                        raise
                    attempts += 1
                    M.record_fetch_retry()
                    m_idx, k = piece_src[t][j]
                    fresh = run_map(m_idx)[t]
                    if k >= len(fresh):
                        raise
                    piece = fresh[k]

        def piece_gen(pidx: int, lo: int = 0, hi: Optional[int] = None):
            # fuse runs of routed slices into one batch per <=16 slices
            # (the assemble kernel unrolls per slice; 16 bounds compile
            # size while one fused gather replaces piece-wise
            # gather+concat). [lo, hi) bounds serve the adaptive runtime's
            # skew-split sub-partition reads (aqe/stages.py): piece
            # indices stay ABSOLUTE so fetch-remap lineage holds.
            stop = len(reduce_buckets[pidx]) if hi is None else hi
            routed: List[_RoutedSlice] = []
            for j, piece in enumerate(reduce_buckets[pidx]):
                if j < lo or j >= stop:
                    continue
                if isinstance(piece, _RoutedSlice):
                    routed.append(piece)
                    if len(routed) >= 16:
                        yield _assemble_routed(routed)
                        routed = []
                    continue
                if routed:
                    yield _assemble_routed(routed)
                    routed = []
                if isinstance(piece, _SerializedPiece):
                    piece = decode_with_remap(piece, pidx, j)
                yield piece
            if routed:
                yield _assemble_routed(routed)

        def factory(pidx: int):
            return count_output(self.metrics, piece_gen(pidx))

        pb = PartitionedBatches(n_out, factory, bucket_costs=costs)
        pb.map_stats = stats
        pb.piece_range = lambda t, lo, hi: count_output(
            self.metrics, piece_gen(t, lo, hi))
        # adaptive partition coalescing (reference role: Spark AQE's
        # CoalesceShufflePartitions, which the plugin runs under in
        # TpchLikeAdaptiveSparkSuite): group small contiguous reduce
        # buckets so downstream tasks amortize their fixed dispatch cost.
        # The grouping math, the never-coalesce pins, and the adaptive
        # rule pass that replaces this runtime side effect all live in
        # aqe/coalesce.py — one enforcement point.
        from spark_rapids_tpu.aqe.coalesce import maybe_coalesce_runtime

        return maybe_coalesce_runtime(self, pb, ctx.conf)


def _piece_cost(piece, n_out: int) -> int:
    """Estimated bytes of one piece for coalescing decisions. Lazy device
    views share full source buffers, so their per-target expected share is
    used instead of 0 (unlike the dataSize metric, which must not
    over-count shared buffers)."""
    if isinstance(piece, ColumnarBatch) and piece.live is not None:
        return piece.device_memory_size() // max(n_out, 1)
    return _piece_bytes(piece)


def _piece_bytes(piece) -> int:
    if isinstance(piece, _SerializedPiece):
        return piece.size
    if isinstance(piece, _RoutedSlice):
        return piece.device_memory_size()  # pro-rata share of the source
    if isinstance(piece, ColumnarBatch):
        if piece.live is not None:
            # zero-copy view sharing the source batch: counting the full
            # shared buffers once per target would overreport n_partitions-x
            return 0
        return piece.device_memory_size()
    return piece.estimated_size_bytes()


class _SerializedPiece:
    """One shuffle piece held as serialized bytes (reference: the
    length-prefixed host stream of GpuColumnarBatchSerializer.scala:37-245).
    When the spill framework is up, the bytes live in the host spill store
    (and can demote to disk); the piece frees its buffer when dropped."""

    def __init__(self, data=None, buf=None, fw=None, num_rows=None):
        self._data = data
        self._buf = buf
        self._fw = fw
        self.size = len(data) if data is not None else buf.size
        # row count from the serialized header (known at encode time):
        # the adaptive runtime's MapOutputStats read it host-side
        # (aqe/stats.piece_rows) without decoding the piece
        self.num_rows = num_rows

    def decode(self, to_device: bool):
        from spark_rapids_tpu.columnar.serde import deserialize_batch
        from spark_rapids_tpu.engine.scheduler import FetchFailedError
        from spark_rapids_tpu.utils import faultinject as FI

        FI.maybe_inject("shuffle.fetch")
        try:
            data = self._data if self._data is not None else \
                self._fw.read_bytes(self._buf)
        except (OSError, KeyError, RuntimeError) as e:
            # a spilled shuffle piece could not be read back — surface as a
            # retryable fetch failure (reference:
            # RapidsShuffleFetchFailedException -> Spark stage retry)
            raise FetchFailedError(f"shuffle piece unavailable: {e}") from e
        host = deserialize_batch(data)
        if not to_device:
            return host
        fw = self._fw
        if fw is not None:
            fw.watermark.ensure_headroom(len(data))
        return host.to_device()

    def __del__(self):
        if self._buf is not None and self._fw is not None:
            try:
                self._fw.free(self._buf)
            # tpulint: swallowed-cancellation -- a __del__ must never
            # raise (the interpreter would just print and drop it), and
            # finalizer timing is unrelated to the owning query's state
            except Exception:
                pass


def _encode_piece(piece) -> _SerializedPiece:
    from spark_rapids_tpu.columnar.batch import ensure_compact, to_host_many
    from spark_rapids_tpu.memory.spill import SpillFramework

    if isinstance(piece, _RoutedSlice):
        piece = piece.to_batch()
    if isinstance(piece, ColumnarBatch):
        # keep_encoded: dictionary columns cross the exchange as CODES +
        # one dictionary copy per piece, not expanded strings
        host = to_host_many([ensure_compact(piece)], keep_encoded=True)[0]
    else:
        host = piece
    return _serialize_host_piece(host, SpillFramework.get())


def _serialize_host_piece(host, fw) -> _SerializedPiece:
    from spark_rapids_tpu.columnar.serde import serialize_batch
    from spark_rapids_tpu.memory.spill import SpillPriorities

    data = serialize_batch(host)
    rows = host.num_rows
    if fw is not None:
        return _SerializedPiece(
            buf=fw.add_host_bytes(data, SpillPriorities.OUTPUT_FOR_READ),
            fw=fw, num_rows=rows)
    return _SerializedPiece(data=data, num_rows=rows)


def _encode_pieces_grouped(routed):
    """Serialize one map batch's (target, piece) list with ONE grouped
    device->host transfer for every device piece (to_host_many packs all
    columns of all pieces into per-dtype buffers: one fence per byte
    budget instead of one per piece). run_map calls this one batch
    BEHIND the routing dispatches, so the blocking download overlaps the
    next batch's in-flight device work."""
    from spark_rapids_tpu.columnar.batch import (
        ensure_compact,
        to_host_many,
    )
    from spark_rapids_tpu.engine.retry import with_retry
    from spark_rapids_tpu.memory.spill import SpillFramework

    fw = SpillFramework.get()
    dev_idx: List[int] = []
    dev_batches: List[ColumnarBatch] = []
    for j, (_target, piece) in enumerate(routed):
        if isinstance(piece, _RoutedSlice):
            piece = piece.to_batch()
        if isinstance(piece, ColumnarBatch):
            piece = ensure_compact(piece)
            dev_idx.append(j)
            dev_batches.append(piece)
    if dev_batches:
        # THE grouped map-output download: one planned fence per input
        # batch replaces one per piece (counted by the fencesPerQuery
        # instrumentation inside with_retry)
        # keep_encoded: dictionary columns ship codes + one dictionary
        # copy per piece instead of expanded strings
        hosts = with_retry(
            lambda: to_host_many(dev_batches, keep_encoded=True),
            site="transfer.download")
    out = []
    hi = 0
    for j, (target, piece) in enumerate(routed):
        if hi < len(dev_idx) and dev_idx[hi] == j:
            # device piece: its grouped-download host batch
            host = hosts[hi]
            hi += 1
        else:
            host = piece  # already host-side
        out.append((target, _serialize_host_piece(host, fw)))
    return out


def _sample_bounds_host(key_cols: List[np.ndarray], orders: List[SortOrder],
                        n_parts: int):
    """Compute range-partition bounds from sampled key rows (host side;
    reference: GpuRangePartitioner.scala driver-side reservoir sample).
    Returns rows of raw key values at the n_parts-1 split points."""
    if not key_cols or len(key_cols[0]) == 0:
        return None
    n = len(key_cols[0])
    decorated = [
        (tuple(_order_key(c[i], o) for c, o in zip(key_cols, orders)), i)
        for i in range(n)
    ]
    decorated.sort(key=lambda t: t[0])
    order_idx = [i for _, i in decorated]
    bounds_rows = [order_idx[min(n - 1, (b * n) // n_parts)]
                   for b in range(1, n_parts)]
    return [tuple(c[i] for c in key_cols) for i in bounds_rows]


def _order_key(v, o: SortOrder):
    """Sortable python key matching SQL null/NaN ordering for one column:
    (null_rank, nan_rank, value). Nulls rank 0 (first) or 2 (last); NaN is
    strictly greater than every number including +inf (Spark ordering)."""
    if isinstance(v, np.generic):
        # tpulint: host-sync -- np.generic -> python scalar; host value
        v = v.item()
    if v is None:
        return (0 if o.nulls_first else 2, 0, 0)
    if isinstance(v, float) and v != v:
        return (1, 1 if o.ascending else -1, 0)
    if isinstance(v, str):
        return (1, 0, _InvertedStr(v) if not o.ascending else v)
    if isinstance(v, bool):
        v = int(v)
    return (1, 0, -v if not o.ascending else v)


class _InvertedStr:
    __slots__ = ("s",)

    def __init__(self, s):
        self.s = s

    def __lt__(self, other):
        return other.s < self.s

    def __eq__(self, other):
        return self.s == other.s

    def __le__(self, other):
        return other.s <= self.s


# ---------------------------------------------------------------------------
# Vectorized composite range keys
# ---------------------------------------------------------------------------
# Every sort key reduces to LEVELS whose unsigned elementwise comparison,
# taken lexicographically, equals the SQL composite order: a null-rank level
# (0/1/2 per nulls_first) and a value level (order bits as uint64 with the
# sign bit flipped; descending keys complement the word, so every level is
# plain ascending uint64). Packing all levels big-endian into one bytes
# column makes numpy's 'S' comparison THE composite comparator — bounds and
# per-row bucket ids come from vectorized sort/searchsorted instead of a
# per-row python bisect loop (which dominated global-sort exchanges at SF1).


def _fixed_key_levels_np(ob: np.ndarray, nf: np.ndarray, order: SortOrder):
    """(null_rank u8[rows], value u64[rows]) for one fixed-width key from
    downloaded order bits + null flags."""
    null_rank = np.where(nf, np.uint8(0 if order.nulls_first else 2),
                         np.uint8(1))
    u = ob.astype(np.int64).view(np.uint64) ^ np.uint64(1 << 63)
    if not order.ascending:
        u = ~u
    u = np.where(nf, np.uint64(0), u)
    return null_rank, u


def _string_key_levels_np(values: List, order: SortOrder, width: int):
    """(null_rank u8[rows], bytes u8[rows, width]) for one string key.
    numpy 'S' arrays zero-pad, so ascending compares bytewise like SQL;
    descending complements (pad becomes 0xFF, reversing the order)."""
    bs = [b"" if v is None else v.encode("utf-8") for v in values]
    width = max(width, 1)
    arr = np.array(bs, dtype=f"S{width}")
    mat = arr.view(np.uint8).reshape(len(bs), width).copy()
    if not order.ascending:
        mat = ~mat
    nulls = np.array([v is None for v in values])
    null_rank = np.where(nulls, np.uint8(0 if order.nulls_first else 2),
                         np.uint8(1))
    mat[nulls] = 0
    return null_rank, mat


def _pack_key_rows(levels: List[np.ndarray]) -> np.ndarray:
    """Concatenate per-key levels into one 'S{w}' column whose bytewise
    comparison is the composite lexicographic order."""
    parts = []
    for lv in levels:
        if lv.dtype == np.uint64:
            parts.append(lv.astype(">u8").view(np.uint8).reshape(-1, 8))
        elif lv.ndim == 1:
            parts.append(lv[:, None])
        else:
            parts.append(lv)
    m = np.ascontiguousarray(np.concatenate(parts, axis=1))
    return m.view(f"S{m.shape[1]}").ravel()


def _range_bounds_levels_np(per_map, bound, orders, n: int):
    """[n-1, 2K] uint64 bounds matrix for the ICI range exchange: evaluate
    ORDER keys per materialized batch (device kernel), download, transform
    to uint64 levels via _fixed_key_levels_np (the kernel-side _range_pid
    mirrors the same transform), then pick quantile rows by lexsort."""
    kernel = _build_order_keys_kernel(list(bound))
    nlevels = 2 * len(orders)
    # dispatch the order-keys kernel for EVERY batch first, then download
    # all results in one host transfer (one sync per exchange, not one per
    # map batch)
    pending = []
    for batches in per_map:
        for batch in batches:
            batch = _compacted(batch)  # live-masked exchange outputs hold
            hr = batch.host_rows()     # dead lanes that must not seed bounds
            if hr == 0:
                continue
            cols = [_col_to_colv(c) for c in batch.columns]
            pending.append((hr, kernel(cols, jnp.int32(hr))))
    gots = jax.device_get([outs for _, outs in pending])
    level_parts: List[List[np.ndarray]] = []
    for (hr, _), got in zip(pending, gots):
        levels: List[np.ndarray] = []
        for (ob, nf), o in zip(got, orders):
            nr, u = _fixed_key_levels_np(np.asarray(ob)[:hr],
                                         np.asarray(nf)[:hr], o)
            levels.extend([nr.astype(np.uint64), u])
        level_parts.append(levels)
    if not level_parts:
        return np.zeros((max(n - 1, 1), nlevels), np.uint64)
    merged = [np.concatenate([lp[i] for lp in level_parts])
              for i in range(nlevels)]
    order_idx = np.lexsort(tuple(reversed(merged)))
    cnt = order_idx.shape[0]
    sel = [order_idx[min(cnt - 1, (b * cnt) // n)] for b in range(1, n)]
    return np.stack([[merged[li][i] for li in range(nlevels)]
                     for i in sel]).astype(np.uint64) if sel else \
        np.zeros((max(n - 1, 1), nlevels), np.uint64)


def _packed_bounds(packed_all: np.ndarray, n: int) -> Optional[np.ndarray]:
    """n-1 sorted split points over all packed rows (the reference computes
    bounds from a driver-side sample, GpuRangePartitioner.scala:42-230; the
    full sort here is vectorized and exact)."""
    cnt = packed_all.shape[0]
    if cnt == 0:
        return None
    s = np.sort(packed_all)
    return s[[min(cnt - 1, (b * cnt) // n) for b in range(1, n)]]


# ===========================================================================
# CPU exchange
# ===========================================================================
class CpuShuffleExchangeExec(_ExchangeBase, CpuExec):
    placement = "cpu"

    def execute(self, ctx: ExecContext) -> PartitionedBatches:
        p = self.partitioning
        n = p.num_partitions
        child_attrs = self.children[0].output

        if isinstance(p, SinglePartitioning):
            return self._materialize(ctx, lambda pidx, b: [(0, b)])

        if isinstance(p, RoundRobinPartitioning):
            def rr_map(pidx: int, batch: HostColumnarBatch):
                ids = (np.arange(batch.num_rows) + pidx) % n
                return _host_slices(batch, ids, n)
            return self._materialize(ctx, rr_map)

        if isinstance(p, HashPartitioning):
            bound = bind_all(p.exprs, child_attrs)

            def hash_map(pidx: int, batch: HostColumnarBatch):
                ev = cpu_project(bound, batch, partition_id=pidx)
                cols = [_host_to_colv(c) for c in ev.columns]
                ids = np.asarray(H.partition_ids(np, cols, n))
                return _host_slices(batch, ids, n)
            return self._materialize(ctx, hash_map)

        if isinstance(p, RangePartitioning):
            return self._execute_range(ctx, p)
        raise NotImplementedError(p.describe())

    def _execute_range(self, ctx: ExecContext,
                       p: RangePartitioning) -> PartitionedBatches:
        child_pb = self._child_pb(ctx)
        child_attrs = self.children[0].output
        bound = bind_all([o.child for o in p.orders], child_attrs)
        n = p.num_partitions

        # phase 1: materialize child batches + evaluated keys per partition
        def mat(pidx: int):
            out = []
            for batch in child_pb.iterator(pidx):
                if batch.num_rows == 0:
                    continue
                ev = cpu_project(bound, batch, partition_id=pidx)
                keys = [c.to_pylist() for c in ev.columns]
                out.append((batch, keys))
            return out

        from spark_rapids_tpu.engine.scheduler import run_job_or_serial
        from spark_rapids_tpu.obs.trace import span as obs_span

        with obs_span(f"stage:map:{self.node_name()}", kind="stage",
                      maps=child_pb.num_partitions):
            per_part = run_job_or_serial(ctx.scheduler,
                                         child_pb.num_partitions, mat)
        all_keys: List[List[Any]] = [[] for _ in p.orders]
        for part in per_part:
            for _, keys in part:
                for i, k in enumerate(keys):
                    all_keys[i].extend(k)
        bounds = _sample_bounds_host(
            [np.array(k, dtype=object) for k in all_keys], p.orders, n)

        reduce_buckets: List[List[HostColumnarBatch]] = [[] for _ in range(n)]
        for part in per_part:
            for batch, keys in part:
                ids = _range_ids_host(keys, bounds, p.orders)
                for t, piece in _host_slices(batch, ids, n):
                    if piece.num_rows:
                        reduce_buckets[t].append(piece)

        def factory(pidx: int):
            return count_output(self.metrics, iter(reduce_buckets[pidx]))

        pb = PartitionedBatches(n, factory)
        from spark_rapids_tpu.aqe.stats import bucket_stats

        pb.map_stats = bucket_stats(reduce_buckets,
                                    lambda piece: _piece_bytes(piece))
        return pb


def _range_ids_host(key_cols: List[List[Any]], bounds, orders) -> np.ndarray:
    nrows = len(key_cols[0]) if key_cols else 0
    if bounds is None:
        return np.zeros(nrows, dtype=np.int32)
    ids = np.zeros(nrows, dtype=np.int32)
    bound_keys = [tuple(_order_key(v, o) for v, o in zip(b, orders))
                  for b in bounds]
    for i in range(nrows):
        row = tuple(_order_key(kc[i], o) for kc, o in zip(key_cols, orders))
        import bisect

        ids[i] = bisect.bisect_right(bound_keys, row)
    return ids


def _host_slices(batch: HostColumnarBatch, ids: np.ndarray, n: int):
    out = []
    for t in range(n):
        mask = ids == t
        if not mask.any():
            continue
        cols = [HostColumnVector(c.dtype, c.data[mask], c.validity[mask])
                for c in batch.columns]
        out.append((t, HostColumnarBatch(cols, int(mask.sum()))))
    return out


# ===========================================================================
# TPU exchange
# ===========================================================================
class TpuShuffleExchangeExec(_ExchangeBase, TpuExec):
    placement = "tpu"

    def execute(self, ctx: ExecContext) -> PartitionedBatches:
        p = self.partitioning
        n = p.num_partitions
        child_attrs = self.children[0].output

        if isinstance(p, SinglePartitioning):
            return self._materialize(ctx, lambda pidx, b: [(0, b)])

        # ICI collective tier (reference: the opt-in RapidsShuffleManager
        # data plane, RapidsShuffleInternalManager.scala:74-178, replaced by
        # one all_to_all epoch over the mesh — shuffle/ici.py)
        if ctx.conf.get(C.SHUFFLE_MODE) == "ici" and \
                not ctx.conf.get(C.SHUFFLE_SERIALIZE):
            from spark_rapids_tpu.shuffle import ici

            if ici.supports_ici(p, child_attrs, n):
                return self._execute_ici(ctx, p, n)

        no_strings = all(a.data_type is not DataType.STRING
                         for a in child_attrs)
        serialize = ctx.conf.get(C.SHUFFLE_SERIALIZE)

        def slicer(batch, ids, n_):
            # lazy zero-copy views keep FULL source capacity per piece, so
            # the reduce side would run kernels over sum-of-capacities
            # lanes. Worth it only for small batches (e.g. partial-agg
            # output); big scans use routed range views (one routing
            # dispatch + one counts sync per batch, fused reduce-side
            # assembly). The serialized tier needs materialized pieces, so
            # it keeps the per-target contiguous split.
            # (Measured on a single-chip backend in round 4: raising the
            # lazy cap to cover scan-sized batches multiplies reduce-side
            # lane counts 8-16x and regressed the flagship query 13x — the
            # per-lane cost is NOT free even where host fences dominate.)
            from spark_rapids_tpu.columnar.encoded import is_encoded

            enc = any(is_encoded(c) for c in batch.columns)
            # encoded columns slice as fixed-width CODES: the lazy
            # zero-copy view works for them, and the contiguous split's
            # gather carries the dictionary along
            fixed_only = no_strings or (enc and all(
                is_encoded(c) or c.dtype is not DataType.STRING
                for c in batch.columns))
            if fixed_only and \
                    batch.device_memory_size() <= LAZY_PIECE_CAP_BYTES:
                return _device_slices_lazy(batch, ids, n_)
            if serialize or enc:
                return _device_slices(batch, ids, n_)
            return _device_slices_routed(batch, ids, n_)

        if isinstance(p, RoundRobinPartitioning):
            jitted = _jit_rr_ids(n)

            def rr_map(pidx: int, batch: ColumnarBatch):
                batch = _compacted(batch)
                ids = jitted(jnp.int32(pidx),
                             jnp.asarray(batch.num_rows, dtype=jnp.int32),
                             batch.capacity)
                return slicer(batch, ids, n)
            return self._materialize(ctx, rr_map)

        if isinstance(p, HashPartitioning):
            bound = bind_all(p.exprs, child_attrs)
            jitted = [None]

            def hash_map(pidx: int, batch: ColumnarBatch):
                from spark_rapids_tpu.columnar import encoded as ENC

                batch = _compacted(batch)
                if ENC.encoded_ordinals(batch):
                    ids, batch = _hash_ids_encoded(bound, n, batch)
                    return slicer(batch, ids, n)
                if jitted[0] is None:
                    jitted[0] = _build_hash_ids(bound, n)
                cols = [_col_to_colv(c) for c in batch.columns]
                ids = jitted[0](cols,
                                jnp.asarray(batch.num_rows, dtype=jnp.int32))
                return slicer(batch, ids, n)
            return self._materialize(ctx, hash_map)

        if isinstance(p, RangePartitioning):
            return self._execute_range(ctx, p)
        raise NotImplementedError(p.describe())

    def _execute_ici(self, ctx: ExecContext, p: Partitioning,
                     n: int) -> PartitionedBatches:
        """Lower the exchange onto one collective epoch over the mesh:
        materialize map outputs, then shard_map + lax.all_to_all moves every
        row to its target chip in a single XLA program (shuffle/ici.py).
        Hash routes by key hash, round-robin by live-row modulo, and range
        by host-computed bounds (reference: the partitioning-agnostic
        transport, RapidsShuffleInternalManager.scala:74-178)."""
        from spark_rapids_tpu.shuffle import ici

        child_pb = self._child_pb(ctx)
        child_attrs = self.children[0].output

        def mat(pidx: int):
            from spark_rapids_tpu.columnar.encoded import decode_batch

            # tpulint: eager-materialize -- the ICI collective assembles
            # raw fixed/string matrices: sanctioned boundary decode
            return [decode_batch(b) for b in child_pb.iterator(pidx)
                    if not getattr(b, "rows_on_host", True) or b.num_rows > 0]

        from spark_rapids_tpu.engine.scheduler import run_job_or_serial
        from spark_rapids_tpu.obs.trace import span as obs_span

        with obs_span(f"stage:map:{self.node_name()}", kind="stage",
                      maps=child_pb.num_partitions):
            per_map = run_job_or_serial(ctx.scheduler,
                                        child_pb.num_partitions, mat)
        bounds_np = None
        if isinstance(p, HashPartitioning):
            spec = ("hash", tuple(bind_all(p.exprs, child_attrs)), ())
        elif isinstance(p, RoundRobinPartitioning):
            spec = ("rr", (), ())
        else:
            bound = bind_all([o.child for o in p.orders], child_attrs)
            flags = tuple((o.ascending, o.nulls_first) for o in p.orders)
            bounds_np = _range_bounds_levels_np(per_map, bound, p.orders, n)
            spec = ("range", tuple(bound), flags)
        with M.trace_range("IciExchange", self.metrics[M.TOTAL_TIME]):
            out = ici.ici_exchange(per_map, spec, child_attrs, n,
                                   bounds_np=bounds_np)
        bytes_m = self.metrics["dataSize"]
        for b in out:
            b.owned = False  # held for potential re-iteration (task retry)
            bytes_m.add(b.device_memory_size())

        def factory(pidx: int):
            return count_output(self.metrics, iter([out[pidx]]))

        pb = PartitionedBatches(n, factory)
        # ICI piece shapes are host-known (the collective's static
        # per-target buckets): stats come free (aqe/stats.py)
        from spark_rapids_tpu.aqe.stats import MapOutputStats, piece_rows

        sizes = [b.device_memory_size() for b in out]
        pb.map_stats = MapOutputStats(sizes, [piece_rows(b) for b in out],
                                      [[s] for s in sizes])
        return pb

    def _execute_range(self, ctx: ExecContext,
                       p: RangePartitioning) -> PartitionedBatches:
        """Device range exchange: order bits for fixed-width keys are
        computed on device; STRING keys download their values so bounds are
        computed host-side (the reference's driver-side reservoir sample,
        GpuRangePartitioner.scala:42-230, does the same). Bucket assignment
        is fully vectorized — composite keys pack into one bytes column and
        bounds/ids come from numpy sort/searchsorted. Routing/slicing stays
        on device.

        ENCODED bare-ref keys never decode: their int32 CODES download in
        the same grouped transfer, the host maps them through a union RANK
        table (columnar/encoded.union_rank_tables — comparable across
        pieces with different dictionaries), and bounds are sampled as
        ranks. The batches route and slice still carrying codes — the
        range-bounds decode point is closed. Only a mixed key position
        (encoded pieces meeting plain pieces) falls back to host values
        through the dictionary."""
        from spark_rapids_tpu.columnar import encoded as ENC
        from spark_rapids_tpu.ops.base import BoundReference

        child_pb = self._child_pb(ctx)
        child_attrs = self.children[0].output
        bound = bind_all([o.child for o in p.orders], child_attrs)
        n = p.num_partitions
        str_key = [b.data_type is DataType.STRING for b in bound]
        bare_ord = [b.ordinal if isinstance(b, BoundReference) else None
                    for b in bound]
        computed_refs = set()
        for b in bound:
            if not isinstance(b, BoundReference):
                computed_refs |= ENC._bound_ref_ords(b)
        kernel_memo: dict = {}

        def kernel_for(skip_kis: frozenset):
            """Order-keys kernel over the fixed keys NOT handled in code
            space for this batch signature (encoded bare refs download
            codes instead of evaluating)."""
            got = kernel_memo.get(skip_kis)
            if got is None:
                fb = [b for ki, (b, s) in enumerate(zip(bound, str_key))
                      if not s and ki not in skip_kis]
                got = (_build_order_keys_kernel(fb) if fb else None,
                       len(fb))
                kernel_memo[skip_kis] = got
            return got[0]

        def mat(pidx: int):
            """Stage batches + DISPATCH the order-key kernel per batch,
            then download the partition's fixed-width order bits AND
            encoded-key codes in ONE grouped transfer (the per-batch
            device_get pair this replaces cost 2*n_keys fences per batch;
            grouping per PARTITION rather than per
            exchange keeps peak HBM for key arrays bounded by one
            partition's batches — the device refs drop as each partition
            completes)."""
            staged = []
            for batch in child_pb.iterator(pidx):
                if batch.num_rows == 0:
                    continue
                enc = set(ENC.encoded_ordinals(batch))
                if enc & computed_refs:
                    # tpulint: eager-materialize -- COMPUTED range-key
                    # expressions need values; bare keys stay codes and
                    # bound in rank space
                    batch = ENC.batch_with_materialized(
                        batch, tuple(sorted(enc & computed_refs)))
                    enc = set(ENC.encoded_ordinals(batch))
                enc_kis = frozenset(
                    ki for ki, o in enumerate(bare_ord)
                    if o is not None and o in enc)
                kern = kernel_for(enc_kis)
                cols = ENC.eval_cols(batch, frozenset(enc)) if enc \
                    else [_col_to_colv(c) for c in batch.columns]
                dev_keys = kern(cols, jnp.int32(batch.num_rows)) \
                    if kern is not None else []
                enc_cols = [(ki, batch.columns[bare_ord[ki]])
                            for ki in sorted(enc_kis)]
                if enc_kis:
                    M.record_order_preserving_sort()
                    # per-node attribution for EXPLAIN ANALYZE's inline
                    # counter column
                    self.metrics[M.ORDER_PRESERVING_SORTS].add(1)
                staged.append((batch, dev_keys, enc_cols))
            to_get = []
            for _b, dev, encs in staged:
                for ob, nf in dev:
                    to_get.extend([ob, nf])
                for _ki, c in encs:
                    to_get.extend([c.data, c.validity])
            # tpulint: host-sync -- one grouped key download per partition
            flat = jax.device_get(to_get)
            got = iter(flat)
            out = []
            for batch, dev, encs in staged:
                # tpulint: host-sync -- already host: grouped download above
                fixed_keys = [
                    (np.asarray(next(got))[:batch.num_rows],
                     np.asarray(next(got))[:batch.num_rows])
                    for _ in dev]
                enc_keys = {}
                for ki, c in encs:
                    # tpulint: host-sync -- already host: grouped download
                    codes = np.asarray(next(got))[:batch.num_rows]
                    # tpulint: host-sync -- already host: grouped download
                    valid = np.asarray(next(got))[:batch.num_rows]
                    enc_keys[ki] = ("enc", codes, valid, c.dictionary)
                host_keys = []
                fi = 0
                for ki, (b, is_str) in enumerate(zip(bound, str_key)):
                    if ki in enc_keys:
                        host_keys.append(enc_keys[ki])
                    elif is_str:
                        host_keys.append(
                            ("str", _host_string_values(batch, b.ordinal)))
                    else:
                        host_keys.append(("bits", fixed_keys[fi]))
                        fi += 1
                out.append((batch, host_keys))
            return out

        from spark_rapids_tpu.engine.scheduler import run_job_or_serial
        from spark_rapids_tpu.obs.trace import span as obs_span

        with obs_span(f"stage:map:{self.node_name()}", kind="stage",
                      maps=child_pb.num_partitions):
            per_part = run_job_or_serial(ctx.scheduler,
                                         child_pb.num_partitions, mat)

        # encoded keys: global rank tables over the union of every piece's
        # dictionary; a MIXED position (encoded pieces + plain pieces)
        # repairs to host values through the dictionary instead
        enc_tables: dict = {}
        for ki in range(len(bound)):
            entries = [hks[ki] for part in per_part for _b, hks in part]
            kinds = {e[0] for e in entries}
            if "enc" not in kinds:
                continue
            if kinds == {"enc"}:
                dicts = {e[3].did: e[3] for e in entries}
                enc_tables[ki] = ENC.union_rank_tables(
                    list(dicts.values()))
                continue
            for part in per_part:
                for _b, hks in part:
                    if hks[ki][0] != "enc":
                        continue
                    _k, codes, valid, d = hks[ki]
                    vals = ENC.materialize_host_values(codes, valid, d)
                    if str_key[ki]:
                        hks[ki] = ("str", [v if ok else None for v, ok
                                           in zip(vals, valid)])
                    else:
                        # tpulint: host-sync -- numpy bools from the
                        # grouped download, not device values
                        hks[ki] = ("bits", (vals.astype(np.int64),
                                            ~np.asarray(valid, bool)))

        # one fixed byte width per string key across all batches so every
        # packed row compares in the same space
        widths = [0] * len(bound)
        for ki, is_str in enumerate(str_key):
            if is_str and ki not in enc_tables:
                w = 1
                for part in per_part:
                    for _, host_keys in part:
                        if host_keys[ki][0] != "str":
                            continue
                        vals = host_keys[ki][1]
                        w = max(w, max((len(v.encode("utf-8"))
                                        for v in vals if v is not None),
                                       default=1))
                widths[ki] = w

        def pack_batch(host_keys) -> np.ndarray:
            levels: List[np.ndarray] = []
            for ki, ((kind, *payload), o, w) in enumerate(
                    zip(host_keys, p.orders, widths)):
                if kind == "enc":
                    codes, valid, d = payload
                    table = enc_tables[ki][d.did]
                    size = max(len(table), 1)
                    ranks = table[np.clip(codes, 0, size - 1)] \
                        if len(table) else np.zeros(len(codes), np.int64)
                    # tpulint: host-sync -- numpy bools from the grouped
                    # download, not device values
                    nr, mat_b = _fixed_key_levels_np(
                        ranks.astype(np.int64),
                        ~np.asarray(valid, bool), o)
                elif kind == "str":
                    nr, mat_b = _string_key_levels_np(payload[0], o, w)
                else:
                    nr, u = _fixed_key_levels_np(payload[0][0],
                                                 payload[0][1], o)
                    mat_b = u
                levels.append(nr)
                levels.append(mat_b)
            return _pack_key_rows(levels)

        packed_parts = [pack_batch(host_keys)
                        for part in per_part for _, host_keys in part]
        bounds = _packed_bounds(
            np.concatenate(packed_parts) if packed_parts
            else np.empty((0,), dtype="S1"), n)

        reduce_buckets: List[List[ColumnarBatch]] = [[] for _ in range(n)]
        pi = 0
        for part in per_part:
            for batch, _host_keys in part:
                cap = batch.capacity
                ids = np.full(cap, n, dtype=np.int32)
                if bounds is not None:
                    ids[:batch.num_rows] = np.searchsorted(
                        bounds, packed_parts[pi], side="right")
                else:
                    ids[:batch.num_rows] = 0
                pi += 1
                for t, piece in _device_slices(batch, jnp.asarray(ids), n):
                    if piece.num_rows:
                        piece.owned = False  # bucket-held: multi-read
                        reduce_buckets[t].append(piece)

        def factory(pidx: int):
            return count_output(self.metrics, iter(reduce_buckets[pidx]))

        pb = PartitionedBatches(n, factory)
        from spark_rapids_tpu.aqe.stats import bucket_stats

        pb.map_stats = bucket_stats(reduce_buckets,
                                    lambda piece: _piece_bytes(piece))
        return pb


def _jit_rr_ids(n: int):
    import functools

    from spark_rapids_tpu.engine.jit_cache import get_or_build

    def build():
        @functools.partial(jax.jit, static_argnums=(2,))
        def f(pidx, num_rows, capacity: int):
            ids = (jnp.arange(capacity, dtype=jnp.int32) + pidx) % n
            return jnp.where(jnp.arange(capacity) < num_rows, ids, n)

        return f

    return get_or_build(("rr_ids", n), build)


def _build_hash_ids(bound_exprs, n: int):
    from spark_rapids_tpu.engine.jit_cache import get_or_build
    from spark_rapids_tpu.ops.eval import _scalar_to_colv

    key = ("hash_ids", tuple(e.fingerprint() for e in bound_exprs), n)

    def build():
        def f(cols, num_rows):
            capacity = cols[0].validity.shape[0]
            ctx = EvalContext(jnp, True, cols, num_rows, capacity)
            key_cols = []
            for e in bound_exprs:
                r = e.eval(ctx)
                if isinstance(r, ScalarV):
                    r = _scalar_to_colv(ctx, r, e.data_type)
                key_cols.append(r)
            ids = H.partition_ids(jnp, key_cols, n)
            return jnp.where(jnp.arange(capacity) < num_rows, ids, n)

        return jax.jit(f)

    return get_or_build(key, build)


def _hash_ids_encoded(bound_exprs, n: int, batch):
    """Partition ids for a batch carrying encoded columns: a bare-ref key
    over an encoded column hashes through its DICTIONARY's per-entry word
    table (one gather by code) — bit-identical to hashing the expanded
    strings, so pieces with different dictionaries (or plain string
    pieces from other maps) still co-partition. Non-bare uses of encoded
    columns decode at this boundary. Returns (ids, effective batch)."""
    from spark_rapids_tpu.columnar import encoded as ENC
    from spark_rapids_tpu.ops.base import Alias, BoundReference

    enc = set(ENC.encoded_ordinals(batch))

    def bare_ord(e):
        inner = e.child if isinstance(e, Alias) else e
        if isinstance(inner, BoundReference) and inner.ordinal in enc:
            return inner.ordinal
        return None

    cand = []       # (expr index, ordinal) for bare-ref encoded keys
    mat = set()
    for xi, e in enumerate(bound_exprs):
        o = bare_ord(e)
        if o is not None:
            cand.append((xi, o))
            continue
        mat |= ENC._bound_ref_ords(e) & enc
    # an ordinal ALSO referenced inside a computed expression is about
    # to materialize — its bare keys hash the values (bit-identical)
    enc_info = [(xi, o) for xi, o in cand if o not in mat]
    # tpulint: eager-materialize -- non-bare partition-key expressions
    # need values; bare keys hash through the dictionary word tables
    batch = ENC.batch_with_materialized(batch, tuple(sorted(mat)))
    still_enc = frozenset(set(ENC.encoded_ordinals(batch)))
    cols = ENC.eval_cols(batch, still_enc)
    tables = tuple(batch.columns[o].dictionary.hash_words()
                   for _xi, o in enc_info)
    kern = _build_hash_ids_enc(bound_exprs, n, tuple(enc_info))
    ids = kern(cols, tables, jnp.asarray(batch.num_rows, dtype=jnp.int32))
    return ids, batch


def _build_hash_ids_enc(bound_exprs, n: int, enc_info):
    from spark_rapids_tpu.engine.jit_cache import get_or_build
    from spark_rapids_tpu.ops.eval import _scalar_to_colv

    key = ("hash_ids_enc", tuple(e.fingerprint() for e in bound_exprs),
           enc_info, n)
    enc_by_xi = dict(enc_info)

    def build():
        def f(cols, tables, num_rows):
            capacity = cols[0].validity.shape[0]
            ctx = EvalContext(jnp, True, cols, num_rows, capacity)
            entries = []
            ti = 0
            for xi, e in enumerate(bound_exprs):
                if xi in enc_by_xi:
                    cv = cols[enc_by_xi[xi]]
                    table = tables[ti]
                    ti += 1
                    safe = jnp.clip(cv.data, 0, table[0].shape[0] - 1)
                    words = [t[safe] for t in table]
                    entries.append((words, cv.validity))
                    continue
                r = e.eval(ctx)
                if isinstance(r, ScalarV):
                    r = _scalar_to_colv(ctx, r, e.data_type)
                words = H.string_words(jnp, r) \
                    if r.dtype is DataType.STRING else \
                    H.column_words(jnp, r)
                entries.append((words, r.validity))
            ids = H.partition_ids_from_entries(jnp, entries, n)
            return jnp.where(jnp.arange(capacity) < num_rows, ids, n)

        return jax.jit(f)

    return get_or_build(key, build)


def _build_order_keys_kernel(bound_exprs):
    """One jitted range-key evaluator reused for every batch of the exchange
    (process-wide cache); returns [(order_bits_int64, null_flag)] per key."""
    from spark_rapids_tpu.engine.jit_cache import get_or_build

    key = ("order_keys", tuple(e.fingerprint() for e in bound_exprs))

    def build():
        @jax.jit
        def f(cols, num_rows):
            capacity = cols[0].validity.shape[0]
            ctx = EvalContext(jnp, True, cols, num_rows, capacity)
            out = []
            for e in bound_exprs:
                r = e.eval(ctx)
                if isinstance(r, ScalarV):
                    from spark_rapids_tpu.ops.eval import _scalar_to_colv

                    r = _scalar_to_colv(ctx, r, e.data_type)
                proxy = RK.key_proxy(r)
                assert proxy.orderable and len(proxy.arrays) == 1
                arr = proxy.arrays[0]
                if arr.dtype == jnp.uint64:
                    # f64 order bits are monotone in UNSIGNED space; the
                    # host/device binning transform treats every emitted
                    # key as a SIGNED int64 (sign-flip to uint64). A bare
                    # astype would wrap values >= 2^63 negative and invert
                    # the negative/positive float order; pre-flipping the
                    # top bit makes the bitcast signed-monotone.
                    arr = jax.lax.bitcast_convert_type(
                        arr ^ jnp.uint64(1 << 63), jnp.int64)
                else:
                    arr = arr.astype(jnp.int64)
                out.append((arr, proxy.null_flag))
            return out

        return f

    return get_or_build(key, build)


def _host_string_values(batch: ColumnarBatch, ordinal: int):
    """Download one string key column as python values (None for NULL) for
    host-side range bounds."""
    cv = batch.columns[ordinal]
    host = ColumnarBatch([cv], batch.host_rows()).to_host()
    hv = host.columns[0]
    return [hv.data[i] if hv.validity[i] else None
            for i in range(host.num_rows)]


import functools


@functools.partial(jax.jit, static_argnums=(1,))
def _route_plan(ids, n: int):
    cap = ids.shape[0]
    order = jnp.argsort(ids, stable=True).astype(jnp.int32)
    counts = jax.ops.segment_sum(jnp.ones((cap,), jnp.int32),
                                 jnp.clip(ids, 0, n), num_segments=n + 1)
    return order, counts


@functools.partial(jax.jit, static_argnums=(2,))
def _slice_indices(order, start, idx_cap: int):
    pos = jnp.arange(idx_cap) + start
    safe = jnp.clip(pos, 0, order.shape[0] - 1)
    return order[safe]


@functools.partial(jax.jit, static_argnums=(1,))
def _lazy_masks(ids, n: int):
    counts = jax.ops.segment_sum(jnp.ones((ids.shape[0],), jnp.int32),
                                 jnp.clip(ids, 0, n), num_segments=n + 1)
    return [ids == t for t in range(n)], [counts[t] for t in range(n)]


def _device_slices_lazy(batch: ColumnarBatch, ids, n: int):
    """Zero-copy split: each piece is the SAME batch with a pid==target live
    mask — no gather, no row-count sync, no data movement. The reduce-side
    concat performs the one scatter-compaction. This is the in-process
    promotion of the reference's device-resident cached shuffle
    (RapidsShuffleInternalManager.scala:92-141): partitions never leave HBM
    and never round-trip a count to the host."""
    masks, counts = _lazy_masks(ids[:batch.capacity], n)
    return [(t, ColumnarBatch(batch.columns, counts[t], live=masks[t]))
            for t in range(n)]


def _compacted(batch: ColumnarBatch) -> ColumnarBatch:
    from spark_rapids_tpu.columnar.batch import ensure_compact

    return ensure_compact(batch)


def _device_slices(batch: ColumnarBatch, ids, n: int):
    """Contiguous split by partition id: stable sort rows by id, then gather
    each target's contiguous range (reference: GpuPartitioning
    sliceInternalOnGpu, GpuPartitioning.scala:29-120). One routing dispatch +
    one fused gather per non-empty target."""
    cap = batch.capacity
    order, counts_dev = _route_plan(ids[:cap], n)
    # tpulint: host-sync -- one n-int counts sync per batch: the
    # contiguous split's gather capacities are static shape arguments
    counts = np.asarray(jax.device_get(counts_dev))
    out = []
    offset = 0
    for t in range(n):
        c = int(counts[t])
        if c == 0:
            continue
        idx = _slice_indices(order, np.int32(offset),
                             bucket_capacity(max(c, 1)))
        piece = gather_batch(batch, idx, c, unique_indices=True)
        out.append((t, piece))
        offset += c
    return out


class _RoutedSlice:
    """One target's rows of a route-sorted map batch, held as a ZERO-KERNEL
    view: `order[start : start+count]` indexes the (still-shared) source
    batch. The map side pays ONE routing dispatch + ONE counts sync per
    batch and no per-target kernels; the reduce side assembles all of a
    bucket's slices — across map batches — with ONE fused gather
    (_assemble_routed). This in-process promotion of the reference's
    device-resident shuffle (RapidsShuffleInternalManager.scala:92-141)
    replaces the per-piece gather+concat pipeline that cost ~1000 kernel
    launches per exchange epoch (tools/shuffle_census.py, round 5)."""

    __slots__ = ("batch", "order", "start", "count")

    def __init__(self, batch: ColumnarBatch, order, start: int, count: int):
        self.batch = batch
        self.order = order
        self.start = start
        self.count = count

    @property
    def rows_on_host(self) -> bool:
        return True

    @property
    def num_rows(self) -> int:
        return self.count

    def device_memory_size(self) -> int:
        # pro-rata share of the shared source (for coalesce cost models)
        cap = max(self.batch.capacity, 1)
        return self.batch.device_memory_size() * self.count // cap

    def to_batch(self) -> ColumnarBatch:
        return _assemble_routed([self])


def _device_slices_routed(batch: ColumnarBatch, ids, n: int):
    """Route once, sync the 16-int counts vector once, emit zero-kernel
    range views (see _RoutedSlice)."""
    cap = batch.capacity
    order, counts_dev = _route_plan(ids[:cap], n)
    # tpulint: host-sync -- the ONE counts sync per routed batch (the
    # design point of _RoutedSlice: no per-target kernels or syncs)
    counts = np.asarray(jax.device_get(counts_dev))
    out = []
    offset = 0
    for t in range(n):
        c = int(counts[t])
        if c:
            out.append((t, _RoutedSlice(batch, order, offset, c)))
        offset += c
    return out


def _assemble_routed(slices: Sequence[_RoutedSlice]) -> ColumnarBatch:
    """Concatenate routed slices (possibly from different map batches) into
    one compact batch with ONE fused kernel. Static shape key: per-slice
    source capacities + dtypes + output bucket — starts/counts ride as a
    device argument, so batch-to-batch count variation never recompiles.
    String byte capacity is host-known without a sync: routing uses each
    source row at most once, so a bucket's bytes are bounded by the sum of
    its sources' byte buffers (tightened by out_cap * max_len when known)."""
    from spark_rapids_tpu.engine.jit_cache import get_or_build

    from spark_rapids_tpu.columnar.batch import _sync_free_strings

    total = sum(s.count for s in slices)
    cap_out = bucket_capacity(max(total, 1))
    first = slices[0].batch
    dtypes = tuple(c.dtype for c in first.columns)
    src_caps = tuple(s.batch.capacity for s in slices)
    # string byte capacities: a high-fence backend uses the host-known
    # bound (sum of source buffers, tightened by cap_out * max_len); a
    # cheap-fence backend syncs the EXACT totals and gathers at exact
    # capacity — a bucket holds ~1/n_out of its sources' rows, so the
    # bound over-sizes the byte kernel by ~n_out
    sync_free = _sync_free_strings()
    byte_caps = []
    for ci, dt in enumerate(dtypes):
        if dt is not DataType.STRING:
            byte_caps.append(0)
            continue
        if not sync_free:
            byte_caps.append(-1)  # resolved after the plan pass
            continue
        bound = sum(int(s.batch.columns[ci].data.shape[0]) for s in slices)
        mls = [s.batch.columns[ci].max_len for s in slices]
        if all(m is not None for m in mls):
            bound = min(bound, cap_out * max(mls))
        byte_caps.append(bucket_capacity(max(bound, 1)))
    key = ("routed_assemble", len(slices), src_caps, dtypes,
           tuple(byte_caps), cap_out)

    def build():
        m = len(slices)

        def kernel(cols_by_slice, orders, meta):
            # meta: int32 [3, m] rows = (start, count, cum_start_out)
            j = jnp.arange(cap_out, dtype=jnp.int32)
            ends = meta[2] + meta[1]  # cumulative output ends per slice
            pid = jnp.searchsorted(ends, j, side="right").astype(jnp.int32)
            pid = jnp.minimum(pid, m - 1)
            local = j - meta[2][pid]
            live = j < ends[m - 1]
            # source row per output lane, resolved per slice then selected
            src_rows = []
            for p in range(m):
                pos = jnp.clip(meta[0, p] + local, 0,
                               orders[p].shape[0] - 1)
                src_rows.append(orders[p][pos])
            outs = []
            for ci, dt in enumerate(dtypes):
                if dt is DataType.STRING:
                    col_slices = [cs[ci] for cs in cols_by_slice]
                    starts, new_offsets, valid = _routed_string_plan(
                        col_slices, src_rows, pid, live)
                    if byte_caps[ci] > 0:
                        out = _routed_string_bytes(
                            [cv.data for cv in col_slices], starts,
                            new_offsets, pid, byte_caps[ci], cap_out)
                        outs.append([out, valid, new_offsets])
                    else:
                        # exact-cap path (4-list): bytes gather runs
                        # after a host read of the totals (cheap-fence
                        # backends)
                        outs.append([starts, new_offsets, valid, pid])
                    continue
                acc_d = None
                acc_v = None
                for p in range(m):
                    cv = cols_by_slice[p][ci]
                    d = cv.data[src_rows[p]]
                    v = cv.validity[src_rows[p]]
                    if acc_d is None:
                        acc_d, acc_v = d, v
                    else:
                        here = pid == p
                        acc_d = jnp.where(here, d, acc_d)
                        acc_v = jnp.where(here, v, acc_v)
                acc_v = acc_v & live
                acc_d = jnp.where(acc_v, acc_d, jnp.zeros((), acc_d.dtype))
                outs.append([acc_d, acc_v, None])
            return outs

        return jax.jit(kernel)

    kern = get_or_build(key, build)
    meta = np.zeros((3, len(slices)), np.int32)
    cum = 0
    for p, s in enumerate(slices):
        meta[0, p] = s.start
        meta[1, p] = s.count
        meta[2, p] = cum
        cum += s.count
    cols_by_slice = [[_col_to_colv(c) for c in s.batch.columns]
                     for s in slices]
    orders = [s.order for s in slices]
    outs = kern(cols_by_slice, orders, meta)  # np meta: no eager convert
    # exact-cap string columns: one host read of all totals, then one
    # byte-gather kernel each at the exact bucket
    plan_cis = [ci for ci, o in enumerate(outs) if len(o) == 4]
    if plan_cis:
        # tpulint: host-sync -- one batched byte-totals read (cheap-fence
        # backends only) buys exact-capacity string gathers
        totals = jax.device_get([outs[ci][1][-1] for ci in plan_cis])
        for ci, tot in zip(plan_cis, totals):
            starts, new_offsets, valid, pid = outs[ci]
            byte_cap = bucket_capacity(max(int(tot), 1))
            datas = [s.batch.columns[ci].data for s in slices]
            out = _routed_bytes_kernel(
                tuple(int(d.shape[0]) for d in datas), byte_cap, cap_out,
                len(slices))(datas, starts, new_offsets, pid)
            outs[ci] = (out, valid, new_offsets)
    cols = []
    for ci, (dt, (d, v, off)) in enumerate(zip(dtypes, outs)):
        if dt is DataType.STRING:
            mls = [s.batch.columns[ci].max_len for s in slices]
            ml = max(mls) if all(x is not None for x in mls) else None
            cols.append(ColumnVector(dt, d, v, off, max_len=ml))
        else:
            vrs = [s.batch.columns[ci].vrange for s in slices]
            from spark_rapids_tpu.columnar.batch import union_vrange

            cols.append(ColumnVector(dt, d, v,
                                     vrange=union_vrange(*vrs)))
    return ColumnarBatch(cols, total)


def _routed_string_plan(col_slices, src_rows, pid, live):
    """String plan inside the routed kernel: per-lane source starts and
    output offsets selected across slices (no byte work)."""
    starts = None
    lengths = None
    valid = None
    for p, cv in enumerate(col_slices):
        sr = src_rows[p]
        st = cv.offsets[sr]
        ln = cv.offsets[sr + 1] - st
        va = cv.validity[sr]
        if starts is None:
            starts, lengths, valid = st, ln, va
        else:
            here = pid == p
            starts = jnp.where(here, st, starts)
            lengths = jnp.where(here, ln, lengths)
            valid = jnp.where(here, va, valid)
    lengths = jnp.where(live, lengths, 0)
    valid = valid & live
    new_offsets = jnp.concatenate([
        jnp.zeros((1,), jnp.int32),
        jnp.cumsum(lengths, dtype=jnp.int32)])
    return starts, new_offsets, valid


def _routed_string_bytes(datas, starts, new_offsets, pid, byte_cap: int,
                         cap_out: int):
    """Byte gather of a routed string plan: searchsorted byte->row, then
    per-slice source selection (shared by the fused in-kernel path and
    the exact-cap post-sync path)."""
    pos = jnp.arange(byte_cap, dtype=jnp.int32)
    row = jnp.searchsorted(new_offsets[1:], pos,
                           side="right").astype(jnp.int32)
    row = jnp.clip(row, 0, cap_out - 1)
    within = pos - new_offsets[row]
    in_use = pos < new_offsets[-1]
    out = None
    src_pos_base = jnp.where(in_use, starts[row] + within, 0)
    for p, d in enumerate(datas):
        sp = jnp.clip(src_pos_base, 0, d.shape[0] - 1)
        b = d[sp]
        if out is None:
            out = b
        else:
            out = jnp.where(pid[row] == p, b, out)
    out = jnp.where(in_use, out, 0).astype(jnp.uint8)
    return out


def _routed_bytes_kernel(byte_shapes, byte_cap: int, cap_out: int,
                         m: int):
    """Jitted exact-cap byte gather (cheap-fence backends), cached per
    (source byte buffer shapes, output byte bucket)."""
    from spark_rapids_tpu.engine.jit_cache import get_or_build

    key = ("routed_bytes", tuple(byte_shapes), byte_cap, cap_out, m)

    def build():
        def fn(datas, starts, new_offsets, pid):
            return _routed_string_bytes(datas, starts, new_offsets, pid,
                                        byte_cap, cap_out)
        return jax.jit(fn)

    return get_or_build(key, build)


# ===========================================================================
# planner hook for Repartition (imported by plan/planner.py)
# ===========================================================================
def plan_repartition_exchange(plan, child: PhysicalExec, conf) -> PhysicalExec:
    n = plan.num_partitions or conf.shuffle_partitions
    if plan.partition_exprs:
        part = HashPartitioning(plan.partition_exprs, n)
    else:
        part = RoundRobinPartitioning(n)
    ex = CpuShuffleExchangeExec(part, child)
    if plan.num_partitions is not None:
        # an explicit repartition(n) states the user's intended fan-out —
        # never adaptively merge it (Spark AQE likewise exempts
        # REPARTITION_BY_NUM shuffles)
        ex.allow_adaptive = False
    return ex
