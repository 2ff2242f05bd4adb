"""Hash aggregate execs (reference: aggregate.scala, 897 LoC).

Reference parity:
- `GpuHashAggregateExec` streaming per-batch aggregation: aggregate each
  incoming batch, concatenate with the running aggregation and re-merge
  (aggregate.scala:338-396) -> the same incremental merge loop here.
- 4-phase bound expressions (input refs / update+merge cudf aggs / final
  projection / result projection, aggregate.scala:307-336) -> key_exprs /
  AggSpec update+merge ops / evaluate_expression / result projection.
- reduction default row for empty ungrouped input (aggregate.scala:406-419)
  -> `_default_row_batch`.
- partial/final mode split composed across a hash exchange
  (call stack SURVEY.md section 3.5).

TPU design: groupby = group-id assignment (sort + neighbor-diff prefix sum)
followed by `jax.ops.segment_*` reductions — the XLA-native composition —
instead of cudf's hash-based groupby. One jitted program per (expression
fingerprint, capacity bucket) covers eval + grouping + every reduction. Host
syncs per batch: the group count; with a string min/max aggregate, also a
max-string-length read (sizes the static chunk count) and the string
gather's byte-total read in _assemble. An aggregate with NO grouping key
has no grouping to do and no count to ask for: its update is one program
whose output is the partial's one row (`_build_ungrouped_update_kernel`),
no sync at all.
"""

from __future__ import annotations

import math
from typing import Any, Dict, List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

from spark_rapids_tpu import _jax_setup  # noqa: F401
import jax
import jax.numpy as jnp

from spark_rapids_tpu.columnar.batch import (
    ColumnarBatch,
    ColumnVector,
    HostColumnarBatch,
    HostColumnVector,
    bucket_capacity,
    concat_batches,
    gather_batch,
    physical_np_dtype,
)
from spark_rapids_tpu.columnar.dtypes import DataType
from spark_rapids_tpu.engine.retry import with_retry
from spark_rapids_tpu.exec import dense_agg as DA
from spark_rapids_tpu.exec import rowkeys as RK
from spark_rapids_tpu.exec.base import (
    CpuExec,
    ExecContext,
    PartitionedBatches,
    PhysicalExec,
    TpuExec,
    count_output,
)
from spark_rapids_tpu import conf as C
from spark_rapids_tpu.ops.aggregates import AggregateFunction
from spark_rapids_tpu.ops.base import (
    Alias,
    AttributeReference,
    Expression,
    to_attribute,
)
from spark_rapids_tpu.ops.bind import bind_all
from spark_rapids_tpu.ops.eval import (
    DeviceProjector,
    _col_to_colv,
    cpu_project,
)
from spark_rapids_tpu.obs import trace as OBS
from spark_rapids_tpu.utils import metrics as M

PARTIAL = "partial"
FINAL = "final"
COMPLETE = "complete"

# 'auto' aggCompactSync goes lazy when one host fence costs at least this
# many ms — a locally attached chip (0.8-1.1 ms on a v5e, chip_smoke.py
# 2026-09-26) stays below it, a remote backend (tens of ms) clears it. A fixed threshold, not a modeled
# compute-saved comparison; conf 'always'/'never' override it either way.
LAZY_FENCE_THRESHOLD_MS = 5.0

# The reduce ops the ungrouped update program takes, and the merge ops it
# can hand its one row to. What has to hold of each: a batch with no live
# row leaves the buffer's empty state (a sum / min / max lane invalid, a
# count lane 0), and the merge op treats that state as its identity, so a
# row of empty states merges exactly as no row does and an input of
# nothing but such rows still ends in `_emit`'s default row (sum NULL,
# count 0). first / last / pct / unmergeable are not here: they stay on
# the grouped path.
UNGROUPED_UPDATE_OPS = frozenset({"sum", "count", "min", "max"})
UNGROUPED_MERGE_OPS = frozenset({"sum", "min", "max"})


class AggSpec(NamedTuple):
    """One distinct aggregate function instance and its buffer slots."""

    func: AggregateFunction
    buffers: List[AttributeReference]


def build_agg_specs(agg_exprs: Sequence[Expression]) -> List[AggSpec]:
    """Collect distinct AggregateFunction nodes (deduped by fingerprint) and
    allocate buffer attributes for each."""
    specs: List[AggSpec] = []
    seen: Dict[str, AggSpec] = {}
    for e in agg_exprs:
        for f in e.collect(lambda n: isinstance(n, AggregateFunction)):
            fp = f.fingerprint()
            if fp not in seen:
                spec = AggSpec(f, list(f.buffer_attrs()))
                seen[fp] = spec
                specs.append(spec)
    return specs


def rewrite_result_exprs(agg_exprs: Sequence[Expression],
                         specs: List[AggSpec]) -> List[Expression]:
    """Replace AggregateFunction nodes with their evaluate_expression over
    the buffer attributes (the reference's final projection)."""
    by_fp = {s.func.fingerprint(): s for s in specs}

    def rewrite(node: Expression) -> Expression:
        if isinstance(node, AggregateFunction):
            spec = by_fp[node.fingerprint()]
            return node.evaluate_expression(spec.buffers)
        return node

    return [e.transform_up(rewrite) for e in agg_exprs]


def _key_exprs_for(grouping: Sequence[AttributeReference],
                   agg_exprs: Sequence[Expression]) -> List[Expression]:
    """The expression computing each grouping key (the Alias carrying the
    key computation lives in agg_exprs; fall back to the attr itself)."""
    out: List[Expression] = []
    for g in grouping:
        found: Expression = g
        for e in agg_exprs:
            if isinstance(e, (Alias, AttributeReference)) and \
                    to_attribute(e).expr_id == g.expr_id:
                found = e
                break
        out.append(found)
    return out


class _HashAggregateBase(PhysicalExec):
    """Shared schema/structure for the CPU and TPU hash aggregate."""

    def __init__(self, grouping: List[AttributeReference],
                 agg_exprs: List[Expression], mode: str,
                 child: PhysicalExec,
                 specs: Optional[List[AggSpec]] = None):
        super().__init__(child)
        self.grouping = list(grouping)
        self.agg_exprs = list(agg_exprs)
        self.mode = mode
        self.specs = specs if specs is not None else build_agg_specs(agg_exprs)
        self.key_exprs = _key_exprs_for(self.grouping, self.agg_exprs)

    @property
    def buffer_attrs(self) -> List[AttributeReference]:
        return [b for s in self.specs for b in s.buffers]

    @property
    def output(self) -> List[AttributeReference]:
        if self.mode == PARTIAL:
            return list(self.grouping) + self.buffer_attrs
        return [to_attribute(e) for e in self.agg_exprs]

    def with_children(self, new_children):
        return type(self)(self.grouping, self.agg_exprs, self.mode,
                          new_children[0], self.specs)

    def node_name(self):
        return f"{type(self).__name__}({self.mode})"

    # intermediate schema during update/merge: keys then buffers
    @property
    def _inter_attrs(self) -> List[AttributeReference]:
        return list(self.grouping) + self.buffer_attrs

    def _update_ops(self) -> List[Tuple[str, Expression, DataType]]:
        """(reduce op, input expr, buffer dtype) per buffer, in buffer order."""
        out = []
        for spec in self.specs:
            for (bname, op, expr), battr in zip(spec.func.update_aggs(),
                                                spec.buffers):
                out.append((op, expr, battr.data_type))
        return out

    def _merge_ops(self) -> List[Tuple[str, DataType]]:
        out = []
        for spec in self.specs:
            for (bname, op), battr in zip(spec.func.merge_aggs(), spec.buffers):
                out.append((op, battr.data_type))
        return out


def _default_row_values(specs: List[AggSpec]) -> List[Any]:
    """Buffer values representing the empty ungrouped reduction
    (reference: aggregate.scala:406-419)."""
    vals: List[Any] = []
    for spec in specs:
        vals.extend(spec.func.initial_buffer_values())
    return vals


# ===========================================================================
# TPU exec
# ===========================================================================
def _collapse_scan_chain(child: PhysicalExec, exprs: List[Expression],
                         max_nodes: Optional[int] = None):
    """Fuse a TpuFilter/TpuProject/TpuCoalesceBatches chain below the
    aggregate into its update kernel: project lists substitute into the
    aggregate's expressions, filter conditions become row masks evaluated
    inside the SAME jit. This removes the filter's compact (a device->host
    row-count sync + gather) from the hot path entirely — the XLA analog of
    cuDF's pre-projection into the groupby (aggregate.scala:307-336).

    `max_nodes` bounds the walk to the same chain length the fusion pass
    claimed (fusion.maxOps), keeping the executed program consistent with
    the plan's stage accounting.

    Returns (scan child, rewritten exprs, filter conditions)."""
    from spark_rapids_tpu.exec import basic as B
    from spark_rapids_tpu.exec.transitions import TpuCoalesceBatchesExec

    filters: List[Expression] = []
    exprs = list(exprs)
    node = child
    walked = 0
    while max_nodes is None or walked < max_nodes:
        walked += 1
        if isinstance(node, B.TpuProjectExec):
            mapping: Dict[int, Expression] = {}
            for e in node.project_list:
                attr = to_attribute(e)
                mapping[attr.expr_id] = e.child if isinstance(e, Alias) else e

            def sub(x: Expression) -> Expression:
                if isinstance(x, AttributeReference) and \
                        x.expr_id in mapping:
                    return mapping[x.expr_id]
                return x

            exprs = [e.transform_up(sub) for e in exprs]
            filters = [f.transform_up(sub) for f in filters]
            node = node.children[0]
        elif isinstance(node, B.TpuFilterExec):
            filters.append(node.condition)
            node = node.children[0]
        elif isinstance(node, TpuCoalesceBatchesExec):
            if node.goal.target_bytes() is None:
                # RequireSingleBatch is SEMANTIC (holistic aggregates need
                # exactly one update pass per partition) — only
                # best-effort TargetSize coalesces are perf no-ops here
                break
            node = node.children[0]
        else:
            break
    if any(not e.deterministic for e in exprs + filters):
        return child, list(exprs), []  # cannot push past a filter safely
    return node, exprs, filters


def collapse_update_chain(child: PhysicalExec, exprs: List[Expression]):
    """`_collapse_scan_chain` extended to see through non-agg-form fused
    stage wrappers (TpuFusedStageExec keeps the ORIGINAL chain as its
    child, so collapsing through it is sound — the wrapper is pure
    packaging). The traced SPMD stage builder (plan/spmd.py) uses this to
    absorb chains that the fusion pass already claimed, e.g. a fused
    Filter/Project stage feeding a lowered join's build side."""
    from spark_rapids_tpu.exec.fused import TpuFusedStageExec

    node = child
    cur_exprs = list(exprs)
    filters: List[Expression] = []
    while True:
        node2, cur_exprs, f2 = _collapse_scan_chain(node, cur_exprs)
        filters.extend(f2)
        if isinstance(node2, TpuFusedStageExec) and not node2.agg_form:
            node = node2.children[0]
            continue
        if node2 is node:
            break
        node = node2
    return node, cur_exprs, filters


class TpuHashAggregateExec(_HashAggregateBase, TpuExec):
    placement = "tpu"

    @property
    def children_coalesce_goal(self):
        if self.mode == COMPLETE and \
                any(getattr(s.func, "holistic", False) for s in self.specs):
            # holistic aggs can't merge partials: the whole partition must
            # arrive as ONE batch so exactly one update pass runs. A
            # TPU-kernel property only — the CPU exec streams rows into
            # per-group accumulators and needs no coalesce
            from spark_rapids_tpu.exec.transitions import RequireSingleBatch

            return [RequireSingleBatch()]
        return [None]

    # -- jitted kernels (cached process-wide by semantic identity) -----------
    def _build_update_kernel(self, input_attrs, key_exprs, input_exprs,
                             op_names, filters, lazy: bool,
                             n_chunks: int = 0, donate: bool = False):
        from spark_rapids_tpu.engine.jit_cache import get_or_build

        bound_keys = bind_all(key_exprs, input_attrs)
        bound_inputs = bind_all(input_exprs, input_attrs)
        bound_filters = bind_all(filters, input_attrs)
        key = ("agg_update", lazy, n_chunks,
               tuple(e.fingerprint() for e in bound_keys),
               tuple(zip(op_names,
                         (e.fingerprint() for e in bound_inputs))),
               tuple(f.fingerprint() for f in bound_filters))
        buffer_npdts = tuple(physical_np_dtype(a.data_type)
                             for a in self.buffer_attrs)

        def build(donate_argnums=()):
            def agg_update(cols, num_rows):
                key_cols, in_cols, live, capacity = _update_inputs(
                    cols, num_rows, bound_keys, bound_inputs, bound_filters)
                gi = _group_info_masked(key_cols, live, capacity)
                buf_outs = []
                for op, cv in zip(op_names, in_cols):
                    if cv.dtype.is_string and op in ("min", "max"):
                        sel = RK.segment_arg_extreme_string(
                            cv, cv.validity & live, gi.gid, capacity,
                            n_chunks, want_min=(op == "min"))
                        buf_outs.append(
                            (sel, cv))
                    else:
                        data, validity = RK.segment_reduce(
                            op, cv.data, cv.validity & live, gi,
                            num_rows, capacity)
                        buf_outs.append((data, validity))
                if lazy:
                    return (_assemble_traced(key_cols, buf_outs, gi,
                                             capacity, buffer_npdts),
                            gi.num_groups)
                return key_cols, buf_outs, gi

            # donate_argnums=(0,) donates the input batch's columns into
            # the update program (lazy form only: in-kernel assembly reads
            # nothing from the inputs afterwards; docs/async-execution.md)
            return jax.jit(agg_update, donate_argnums=donate_argnums)

        return get_or_build(key, build,
                            donate_argnums=(0,) if donate else ())

    def _ungrouped_ok(self) -> bool:
        """Whether an update of this aggregate is the ungrouped program:
        no grouping key, every buffer fixed-width, every op one whose
        empty state its merge takes as the identity. Read off the plan
        alone: `aggCompactSync` trades a sync against padded lanes, and
        this path has neither."""
        return (not self.grouping and self._lazy_ok()
                and all(op in UNGROUPED_UPDATE_OPS
                        for op, _e, _dt in self._update_ops())
                and all(op in UNGROUPED_MERGE_OPS
                        for op, _dt in self._merge_ops()))

    def _build_ungrouped_update_kernel(self, input_attrs, input_exprs,
                                       op_names, filters):
        """The whole partial of a batch of an aggregate with no grouping
        key: the collapsed filters and projections, each buffer reduced
        over the live lanes as `RK.segment_reduce` reduces one group
        (`RK.reduce_all`), written into lane 0 of a one-row column. No
        group ids, no assembly, and a row count the host knows: 1."""
        from spark_rapids_tpu.engine.jit_cache import get_or_build

        bound_inputs = bind_all(input_exprs, input_attrs)
        bound_filters = bind_all(filters, input_attrs)
        buffer_npdts = tuple(physical_np_dtype(a.data_type)
                             for a in self.buffer_attrs)
        # a buffer that cannot be NULL (a count) holds its op's value over
        # no lane, 0, where a nullable one is invalid; said of the buffer
        # and not of the op because the run-aware collapse counts by
        # summing run lengths
        never_null = tuple(not a.nullable for a in self.buffer_attrs)
        key = ("agg_ungrouped_update", buffer_npdts, never_null,
               tuple(zip(op_names,
                         (e.fingerprint() for e in bound_inputs))),
               tuple(f.fingerprint() for f in bound_filters))

        def build():
            def agg_ungrouped_update(cols, num_rows):
                _, in_cols, live, _ = _update_inputs(
                    cols, num_rows, (), bound_inputs, bound_filters)
                lane0 = jnp.arange(bucket_capacity(1)) == 0
                outs = []
                for op, cv, npdt, nn in zip(op_names, in_cols,
                                            buffer_npdts, never_null):
                    r, has = RK.reduce_all(op, cv.data, cv.validity & live)
                    v = lane0 if nn else lane0 & has
                    outs.append((jnp.where(v, r.astype(npdt),
                                           jnp.zeros((), npdt)), v))
                return outs

            return jax.jit(agg_ungrouped_update)

        return get_or_build(key, build)

    def _build_dense_update_kernel(self, input_attrs, key_exprs,
                                   input_exprs, op_names, filters,
                                   radices: tuple, buffer_npdts: tuple):
        """The update over a table of dictionary codes (exec/dense_agg.py):
        the filters, the keys' codes and the inputs evaluate as in the
        sort-based kernel; the grouping and the reductions are the
        table's. Returns what the lazy kernels return: the intermediate
        batch's columns, compact, and the group count as a device
        scalar."""
        from spark_rapids_tpu.engine.jit_cache import get_or_build

        bound_keys = bind_all(key_exprs, input_attrs)
        bound_inputs = bind_all(input_exprs, input_attrs)
        bound_filters = bind_all(filters, input_attrs)
        key = ("agg_dense_update", radices, buffer_npdts,
               tuple(e.fingerprint() for e in bound_keys),
               tuple(zip(op_names,
                         (e.fingerprint() for e in bound_inputs))),
               tuple(f.fingerprint() for f in bound_filters))

        def build():
            def agg_dense_update(cols, num_rows):
                key_cols, in_cols, live, _ = _update_inputs(
                    cols, num_rows, bound_keys, bound_inputs, bound_filters)
                return DA.group_reduce(key_cols, radices, live, op_names,
                                       in_cols, buffer_npdts)

            return jax.jit(agg_dense_update)

        return get_or_build(key, build)

    def _build_dense_merge_kernel(self, n_keys: int, radices: tuple,
                                  buffer_npdts: tuple):
        from spark_rapids_tpu.engine.jit_cache import get_or_build

        ops = tuple(op for op, _ in self._merge_ops())
        key = ("agg_dense_merge", n_keys, radices, ops, buffer_npdts)

        def build():
            def agg_dense_merge(cols, num_rows):
                capacity = cols[0].validity.shape[0]
                return DA.group_reduce(
                    cols[:n_keys], radices, jnp.arange(capacity) < num_rows,
                    ops, cols[n_keys:], buffer_npdts)

            return jax.jit(agg_dense_merge)

        return get_or_build(key, build)

    def _dense_batch(self, outs, num_groups, key_dicts,
                     buf_dicts) -> ColumnarBatch:
        """The intermediate batch of a dense kernel's output: a key is a
        DictionaryColumn over its dictionary again, as is a min/max
        buffer that was reduced over ranks; the row count stays on the
        device."""
        from spark_rapids_tpu.columnar.encoded import DictionaryColumn

        n_keys = len(self.grouping)
        cols = []
        for i, ((data, validity), attr) in enumerate(
                zip(outs, self._inter_attrs)):
            d = key_dicts[i] if i < n_keys else \
                (buf_dicts or {}).get(i - n_keys)
            cols.append(DictionaryColumn(d.value_dtype, data, validity, d)
                        if d is not None
                        else ColumnVector(attr.data_type, data, validity))
        return ColumnarBatch(cols, num_groups)

    def _dense_radices(self, ops, key_dicts, buf_dicts, in_dtypes=None):
        """The table's radices where this aggregate over these
        dictionaries takes exec/dense_agg.py (every grouping key a
        dictionary column, `key_dicts`: position -> dictionary), else
        None: the sort-based path. `in_dtypes`: the update's input
        types (default: the buffers', the merge's inputs)."""
        n_keys = len(self.grouping)
        if not n_keys or len(key_dicts) != n_keys:
            return None
        dts = in_dtypes if in_dtypes is not None else \
            [a.data_type for a in self.buffer_attrs]
        dts = [DataType.INT32 if bi in (buf_dicts or {}) else dt
               for bi, dt in enumerate(dts)]
        return DA.applies(ops, dts,
                          [key_dicts[k].size for k in range(n_keys)])

    def _dense_npdts(self, buf_dicts) -> tuple:
        """Storage dtypes of the buffers in a dense kernel: a min/max
        buffer reduced over a dictionary's ranks holds int32 codes."""
        return tuple(np.dtype(np.int32) if bi in (buf_dicts or {})
                     else physical_np_dtype(a.data_type)
                     for bi, a in enumerate(self.buffer_attrs))

    def _lazy_ok(self) -> bool:
        """In-kernel assembly (device-scalar row counts, zero per-batch
        syncs) works for fixed-width schemas; string output columns need a
        host-coordinated byte-count gather."""
        return all(a.data_type is not DataType.STRING
                   for a in self._inter_attrs)

    def _lazy_batch(self, outs, num_groups,
                    key_vranges=None) -> ColumnarBatch:
        cols = []
        for i, ((data, validity), attr) in enumerate(
                zip(outs, self._inter_attrs)):
            vr = (key_vranges[i]
                  if key_vranges and i < len(key_vranges) else None)
            cols.append(ColumnVector(attr.data_type, data, validity,
                                     vrange=vr))
        return ColumnarBatch(cols, num_groups)

    def _build_merge_kernel(self, n_keys: int, lazy: bool,
                            n_chunks: int = 0, enc_sig: tuple = ()):
        from spark_rapids_tpu.engine.jit_cache import get_or_build

        ops = [op for op, _ in self._merge_ops()]
        # enc_sig: ordinals of ENCODED key columns — those lanes arrive as
        # int32 codes (columnar/encoded.py), a different traced program
        # than the expanded-string flavor under the same inter schema
        key = ("agg_merge", lazy, n_keys, n_chunks, tuple(ops), enc_sig,
               tuple(a.data_type for a in self._inter_attrs))
        buffer_npdts = tuple(physical_np_dtype(a.data_type)
                             for a in self.buffer_attrs)

        def build():
            def agg_merge(cols, num_rows):
                from spark_rapids_tpu.ops.values import narrow_colv

                capacity = cols[0].validity.shape[0] if cols else 8
                key_cols = [narrow_colv(c) for c in cols[:n_keys]]
                buf_cols = cols[n_keys:]
                gi = _group_info(key_cols, num_rows, capacity)
                buf_outs = []
                for op, cv in zip(ops, buf_cols):
                    if cv.dtype.is_string and op in ("min", "max"):
                        sel = RK.segment_arg_extreme_string(
                            cv, cv.validity, gi.gid, capacity,
                            n_chunks, want_min=(op == "min"))
                        buf_outs.append(
                            (sel, cv))
                        continue
                    data, validity = RK.segment_reduce(
                        op, cv.data, cv.validity, gi, num_rows, capacity)
                    buf_outs.append((data, validity))
                if lazy:
                    return (_assemble_traced(key_cols, buf_outs, gi,
                                             capacity, buffer_npdts),
                            gi.num_groups)
                return key_cols, buf_outs, gi

            return jax.jit(agg_merge)

        return get_or_build(key, build)

    # -- assembling an intermediate [keys+buffers] device batch --------------
    def _assemble(self, key_cols, buf_outs, gi, capacity,
                  key_vranges=None, buf_dicts=None) -> ColumnarBatch:
        """buf_dicts: buffer slot -> DeviceDictionary for min/max buffers
        reduced over RANKS — those slots hold int32 CODES of the (sorted)
        dictionary and wrap back into DictionaryColumn; the winning value
        gathers only at the sink."""
        # tpulint: host-sync -- merge-side group count at the blocking
        # aggregate boundary; sizes the assembled intermediate batch
        n_groups = int(jax.device_get(gi.num_groups))
        key_batch = ColumnarBatch(
            [ColumnVector(
                cv.dtype,
                cv.data if (cv.dtype is DataType.STRING
                            or cv.data.dtype == physical_np_dtype(cv.dtype))
                else cv.data.astype(physical_np_dtype(cv.dtype)),
                cv.validity, cv.offsets, vrange=cv.vrange,
                max_len=cv.max_len)
             for cv in key_cols], capacity)
        gathered = gather_batch(key_batch, gi.rep_rows, n_groups,
                                unique_indices=True)
        out_cap = gathered.capacity if gathered.columns else \
            bucket_capacity(max(n_groups, 1))
        cols = list(gathered.columns)
        if key_vranges:
            for i, vr in enumerate(key_vranges[:len(cols)]):
                if vr is not None and cols[i].vrange is None:
                    cols[i].vrange = vr
        fixed: List[Tuple[int, Tuple[Any, Any], Any]] = []
        slots: List[Optional[ColumnVector]] = []
        enc_slots: Dict[int, Any] = {}
        for bi, (out, battr) in enumerate(zip(buf_outs,
                                              self.buffer_attrs)):
            if len(out) == 2 and getattr(out[1], "is_string", False):
                # string min/max: (arg-row per group, source string ColV) —
                # gather the winning row's string per group (the ColV rides
                # the jit pytree so its max_len bound survives the kernel)
                sel, scv = out
                src = ColumnarBatch(
                    [ColumnVector(DataType.STRING, scv.data, scv.validity,
                                  scv.offsets, max_len=scv.max_len)],
                    capacity)
                g = gather_batch(src, sel, n_groups, unique_indices=True)
                slots.append(g.columns[0])
                continue
            if buf_dicts and bi in buf_dicts:
                # rank-reduced min/max: the per-group winner is an int32
                # CODE of the sorted dictionary — stays encoded
                enc_slots[len(slots)] = buf_dicts[bi]
                fixed.append((len(slots), out, DataType.INT32))
                slots.append(None)
                continue
            fixed.append((len(slots), out, battr.data_type))
            slots.append(None)
        if fixed:
            # ONE dispatch finalizes every fixed-width buffer column
            # (eager per-column slice+mask glue is one dispatch per op)
            npdts = tuple(physical_np_dtype(dt) for _, _, dt in fixed)
            kern = _finalize_kernel(out_cap, npdts)

            def _attempt():
                M.record_dispatch()
                return kern([o for _, o, _ in fixed], np.int32(n_groups))

            with M.trace_range("TpuHashAggregate.finalize",
                               self.metrics[M.TOTAL_TIME]):
                OBS.annotate(groups=n_groups)
                outs = with_retry(_attempt, site="agg.finalize")
            for (si, _o, dt), (d, v) in zip(fixed, outs):
                if si in enc_slots:
                    from spark_rapids_tpu.columnar.encoded import (
                        DictionaryColumn,
                    )

                    dct = enc_slots[si]
                    slots[si] = DictionaryColumn(dct.value_dtype, d, v,
                                                 dct)
                else:
                    slots[si] = ColumnVector(dt, d, v)
        assert all(c is not None for c in slots)
        cols.extend(slots)
        return ColumnarBatch(cols, n_groups)

    def execute(self, ctx: ExecContext) -> PartitionedBatches:
        do_update = self.mode in (PARTIAL, COMPLETE)
        child = self.children[0]
        key_exprs = self.key_exprs
        ops = self._update_ops()
        input_exprs = [e for _, e, _ in ops]
        op_names = [op for op, _, _ in ops]
        filters: List[Expression] = []
        str_agg_idx = [i for i, (op, _e, dt) in enumerate(ops)
                       if dt is DataType.STRING and op in ("min", "max")]
        # chain collapse is the aggregate half of whole-stage fusion; it
        # follows the SAME eligibility predicate and chain-length budget as
        # the plan pass (plan/fusion._agg_stage_len wraps the chain in a
        # TpuFusedStageExec for accounting), so what executes always matches
        # the claimed stage — and fusion off really runs one program per
        # operator
        stage_len = 0
        if do_update and ctx.conf.get(C.FUSION_ENABLED):
            from spark_rapids_tpu.plan.fusion import agg_stage_len

            stage_len = agg_stage_len(self, ctx.conf.get(C.FUSION_MAX_OPS))
        if stage_len > 1:
            n_in = len(key_exprs)
            scan, rewritten, new_filters = _collapse_scan_chain(
                child, list(key_exprs) + list(input_exprs),
                max_nodes=stage_len - 1)
            collapsed_inputs = rewritten[n_in:]
            # string min/max needs a statically-bounded max length, which is
            # only derivable for plain column inputs — skip the collapse if
            # it substituted a computed expression there. This abandons the
            # fusion for the whole chain (filters + other aggs included);
            # a finer guard could stop the walk at the offending project,
            # but computed-string agg inputs over collapsible chains are
            # rare enough that the simple rule wins on maintainability.
            if scan is not child and all(
                    isinstance(collapsed_inputs[i], AttributeReference)
                    for i in str_agg_idx):
                child = scan
                key_exprs = rewritten[:n_in]
                input_exprs = collapsed_inputs
                filters = new_filters
        child_pb = child.execute(ctx)
        child_attrs = child.output
        update_kernel = [None]
        merge_kernel = [None]
        n_keys = len(self.grouping)
        from spark_rapids_tpu.ops import bind as SV
        bound_key_static = bind_all(key_exprs, child_attrs)
        # input/buffer column positions feeding string min/max (for the
        # per-batch chunk-count bound)
        str_update_ords = []
        for i in str_agg_idx:
            e = input_exprs[i]
            if isinstance(e, AttributeReference):
                for ci, a in enumerate(child_attrs):
                    if a.expr_id == e.expr_id:
                        str_update_ords.append(ci)
                        break
        str_merge_ords = [n_keys + i for i in str_agg_idx]

        def str_chunks(batch: ColumnarBatch, ordinals) -> int:
            if not ordinals:
                return 0
            return max(RK.string_chunks_needed(batch.columns[ci])
                       for ci in ordinals)
        # The update (partial) stage can either compact its output with a
        # row-count sync (shrinking capacities 100x+ so shuffle concat,
        # merge sorts, and result download get proportionally cheaper) or
        # stay lazy with zero per-partition host round trips.  Which wins is
        # a property of the backend: a fence is about a millisecond on a
        # local chip but tens of ms on a remote PJRT backend, where
        # per-partition syncs dominate the whole query.  'auto' measures once and decides; the
        # merge stage stays sync-free either way — its inputs are small.
        lazy = self._lazy_ok()
        update_lazy = False
        if do_update and lazy and self.placement == "tpu":
            policy = ctx.conf.get(C.AGG_COMPACT_SYNC)
            if policy == "never":
                update_lazy = True
            elif policy == "auto" and \
                    child_pb.num_partitions <= ctx.conf.get(
                        C.AGG_LAZY_MAX_PARTS):
                from spark_rapids_tpu.utils.devprobe import fence_cost_ms
                update_lazy = fence_cost_ms() >= LAZY_FENCE_THRESHOLD_MS

        # An aggregate with no grouping key has neither a sync to save nor
        # lanes to pad: its update is one program whose output is one row
        # (`_build_ungrouped_update_kernel`), whatever the policy above.
        ungrouped = do_update and self.placement == "tpu" and \
            self._ungrouped_ok()
        ungrouped_kernel = [None]

        def count_arg(b: ColumnarBatch):
            n = b.num_rows
            if isinstance(n, (int, np.integer)):
                return np.int32(n)  # host count: no eager device convert
            return jnp.asarray(n, dtype=jnp.int32)

        merge_op_names = [op for op, _ in self._merge_ops()]

        def rows_attr(b: ColumnarBatch) -> dict:
            return {"rows": b.num_rows} if b.rows_on_host else {}

        def dense_merge(batch, rs, key_dicts, buf_dicts, code_ords):
            """Partials with every key a dictionary column, brought to
            one dictionary a column by the concat: the table's reduction
            with the merge ops (exec/dense_agg.py)."""
            from spark_rapids_tpu.columnar import encoded as ENC

            npdts = self._dense_npdts(buf_dicts)
            kern = self._build_dense_merge_kernel(n_keys, rs, npdts)
            cols = ENC.eval_cols(batch, code_ords)

            def _attempt():
                M.record_dispatch()
                return kern(cols, count_arg(batch))

            with M.trace_range("TpuHashAggregate.merge",
                               self.metrics[M.TOTAL_TIME]):
                OBS.annotate(path="dense", groups=math.prod(rs),
                             **rows_attr(batch))
                outs, num_groups = with_retry(_attempt, site="agg.merge")
            return self._dense_batch(outs, num_groups, key_dicts, buf_dicts)

        def merge(batch: ColumnarBatch) -> ColumnarBatch:
            from spark_rapids_tpu.columnar import encoded as ENC

            # encoded KEY columns merge on their codes (concat already
            # aligned every piece onto one dictionary per position);
            # encoded MIN/MAX buffers merge over RANKS — the column
            # re-encodes through the sorted dictionary (identity when the
            # update side already emitted sorted-dict codes) and the
            # reduction is a plain int32 segment min/max; any other
            # encoded buffer decodes at this boundary
            enc_buf_pos = []
            stray = []
            for i in range(n_keys, batch.num_columns):
                if not ENC.is_encoded(batch.columns[i]):
                    continue
                bi = i - n_keys
                if bi < len(merge_op_names) and \
                        merge_op_names[bi] in ("min", "max"):
                    enc_buf_pos.append(i)
                else:
                    stray.append(i)
            if stray:
                # tpulint: eager-materialize -- merge-side BUFFER
                # columns outside min/max have no code-space reduction;
                # keys and min/max buffers stay codes
                batch = ENC.batch_with_materialized(batch, tuple(stray))
            if enc_buf_pos:
                batch = ENC.batch_to_rank_space(batch, enc_buf_pos)
            enc_keys = {i: batch.columns[i].dictionary
                        for i in range(min(n_keys, batch.num_columns))
                        if ENC.is_encoded(batch.columns[i])}
            buf_dicts = {i - n_keys: batch.columns[i].dictionary
                         for i in enc_buf_pos}
            enc_sig = tuple(sorted(enc_keys)) + ("buf",) + \
                tuple(sorted(buf_dicts))
            dense_rs = self._dense_radices(merge_op_names, enc_keys,
                                           buf_dicts)
            if dense_rs is not None:
                return dense_merge(batch, dense_rs, enc_keys, buf_dicts,
                                   frozenset(enc_keys)
                                   | frozenset(enc_buf_pos))
            m_lazy = lazy and not enc_keys and not buf_dicts
            nc = str_chunks(batch, str_merge_ords)
            # capture the kernel in a local: the memo slot is shared by
            # concurrent partition tasks, and _attempt must dispatch the
            # kernel THIS batch's key selected, not whatever a racing
            # task installed meanwhile
            memo = merge_kernel[0]
            if memo is None or memo[0] != (nc, enc_sig):
                memo = ((nc, enc_sig),
                        self._build_merge_kernel(n_keys, m_lazy, nc,
                                                 enc_sig))
                merge_kernel[0] = memo
            kern = memo[1]
            code_ords = frozenset(enc_keys) | frozenset(enc_buf_pos)
            cols = ENC.eval_cols(batch, code_ords) if code_ords \
                else [_col_to_colv(c) for c in batch.columns]
            kvr = [c.vrange for c in batch.columns[:n_keys]]

            def _attempt():
                M.record_dispatch()
                return kern(cols, count_arg(batch))

            with M.trace_range("TpuHashAggregate.merge",
                               self.metrics[M.TOTAL_TIME]):
                OBS.annotate(path="sort", **rows_attr(batch))
                out = with_retry(_attempt, site="agg.merge")
            if m_lazy:
                outs, num_groups = out
                merged = self._lazy_batch(outs, num_groups, kvr)
            else:
                k, b, gi = out
                merged = self._assemble(k, b, gi, batch.capacity, kvr,
                                        buf_dicts=buf_dicts)
            return ENC.wrap_batch_cols(merged, enc_keys)

        # un-compacted (lazy) update output keeps the INPUT batch capacity;
        # past the exchange's zero-copy piece cap that re-introduces the
        # very count fence the lazy path exists to avoid (the slicer falls
        # back to the count-synced contiguous split) AND inflates every
        # downstream kernel to input-capacity lanes. Lazy is only a win for
        # outputs that stay under the cap, so the choice is per batch.
        from spark_rapids_tpu.shuffle.exchange import LAZY_PIECE_CAP_BYTES
        inter_width = sum(
            (physical_np_dtype(a.data_type).itemsize + 1)
            for a in self._inter_attrs) or 1
        lazy_out_cap_bytes = LAZY_PIECE_CAP_BYTES

        run_aware = do_update and self.placement == "tpu" and \
            ctx.conf.get(C.RUN_AWARE_ENABLED)
        run_fraction = ctx.conf.get(C.RUN_AWARE_MAX_RUN_FRACTION)

        def agg_partition(pidx: int):
            from spark_rapids_tpu.columnar.batch import ensure_compact
            from spark_rapids_tpu.engine import async_exec as AX
            from spark_rapids_tpu.memory.device_manager import (
                TpuDeviceManager,
            )

            kvr_cache: Dict[tuple, list] = {}
            enc_plan_memo: Dict[tuple, object] = {}
            running: Optional[ColumnarBatch] = None
            for batch in child_pb.iterator(pidx):
                if batch.rows_on_host and batch.num_rows == 0:
                    continue
                batch = ensure_compact(batch)
                # run-granular collapse (columnar/runs.py): when every
                # referenced column carries a scan run table, aggregate
                # one row per merged run (sum -> value x run_length),
                # through the SAME update kernel machinery
                eff_inputs, eff_ops, run_key = input_exprs, op_names, False
                eff_child_attrs = child_attrs
                if run_aware and do_update:
                    from spark_rapids_tpu.columnar import runs as RUNS

                    cu = RUNS.collapse_update(
                        batch, child_attrs, key_exprs, input_exprs,
                        op_names, filters, run_fraction)
                    if cu is not None:
                        batch = cu.batch
                        eff_inputs = cu.input_exprs
                        eff_ops = cu.op_names
                        eff_child_attrs = cu.attrs
                        run_key = True
                        # per-node attribution: EXPLAIN ANALYZE renders
                        # the collapse inline on this aggregate's row
                        self.metrics[M.RUN_COLLAPSED_ROWS].add(
                            cu.collapsed)
                if do_update:
                    from spark_rapids_tpu.columnar import encoded as ENC

                    # encoded columns group directly on their CODES when
                    # their only uses are bare grouping keys + code-space
                    # filters, and min/max aggregate inputs reduce over
                    # RANKS through the sorted dictionary
                    # (columnar/encoded.py); any other aggregate-input
                    # use decodes here, visibly
                    ekey = (run_key,) + ENC.enc_sig(batch)
                    if ekey in enc_plan_memo:
                        enc_plan = enc_plan_memo[ekey]
                    else:
                        # memoized per encoded signature — the sig fully
                        # determines the retyped attrs/keys/filters
                        # (dictionaries are interned)
                        enc_plan = enc_plan_memo[ekey] = \
                            ENC.plan_agg_update(
                                batch, eff_child_attrs, key_exprs,
                                eff_inputs, filters, eff_ops)
                    if enc_plan is not None:
                        # tpulint: eager-materialize -- aggregate
                        # INPUT expressions outside bare min/max
                        # need values; keys + min/max inputs stay codes
                        batch = ENC.batch_with_materialized(
                            batch, enc_plan.mat_ords)
                        batch = ENC.batch_to_rank_space(
                            batch, enc_plan.rank_ords)
                        eff_attrs = enc_plan.attrs
                        eff_keys = enc_plan.key_exprs
                        eff_filters = enc_plan.filters
                        enc_sig = enc_plan.sig
                    else:
                        eff_attrs, eff_keys, eff_filters = \
                            eff_child_attrs, key_exprs, filters
                        enc_sig = ()
                    dense_rs = self._dense_radices(
                        eff_ops, enc_plan.key_dicts, enc_plan.buf_dicts,
                        [e.data_type for e in eff_inputs]) \
                        if enc_plan is not None else None
                    if n_keys:
                        M.record_agg_batch(dense_rs is not None)
                    if dense_rs is not None:
                        # every key a dictionary column and a table that
                        # fits: no sort, no group-count fence
                        npdts = self._dense_npdts(enc_plan.buf_dicts)
                        kern = self._build_dense_update_kernel(
                            eff_attrs, eff_keys, eff_inputs,
                            tuple(eff_ops), eff_filters, dense_rs, npdts)
                        cols = ENC.eval_cols(batch, enc_plan.code_ords)

                        def _attempt():
                            M.record_dispatch()
                            return kern(cols, count_arg(batch))

                        with M.trace_range("TpuHashAggregate.update",
                                           self.metrics[M.TOTAL_TIME]):
                            OBS.annotate(path="dense",
                                         groups=math.prod(dense_rs),
                                         **rows_attr(batch))
                            outs, num_groups = with_retry(
                                _attempt, site="agg.update")
                        local = self._dense_batch(
                            outs, num_groups, enc_plan.key_dicts,
                            enc_plan.buf_dicts)
                        running = local if running is None else \
                            merge(concat_batches([running, local]))
                        continue
                    if ungrouped and \
                            (enc_plan is None or not enc_plan.buf_dicts):
                        M.record_ungrouped_agg_batch()
                        memo = ungrouped_kernel[0]
                        if memo is None or memo[0] != (enc_sig, run_key):
                            memo = ((enc_sig, run_key),
                                    self._build_ungrouped_update_kernel(
                                        eff_attrs, eff_inputs,
                                        tuple(eff_ops), eff_filters))
                            ungrouped_kernel[0] = memo
                        kern = memo[1]
                        cols = ENC.eval_cols(batch, enc_plan.code_ords) \
                            if enc_plan is not None \
                            else [_col_to_colv(c) for c in batch.columns]
                        if not cols:
                            cols = [_synth_col(batch)]

                        def _attempt():
                            M.record_dispatch()
                            return kern(cols, count_arg(batch))

                        with M.trace_range("TpuHashAggregate.update",
                                           self.metrics[M.TOTAL_TIME]):
                            OBS.annotate(path="ungrouped",
                                         **rows_attr(batch))
                            outs = with_retry(_attempt, site="agg.update")
                        # one row whatever the device found: a batch with
                        # no live row leaves each buffer's empty state
                        local = self._lazy_batch(outs, 1)
                        running = local if running is None else \
                            merge(concat_batches([running, local]))
                        continue
                    nc = str_chunks(batch, str_update_ords)
                    b_lazy = update_lazy and \
                        (enc_plan is None or not enc_plan.code_ords) and \
                        batch.capacity * inter_width <= lazy_out_cap_bytes
                    # update-side donation (docs/async-execution.md): the
                    # lazy kernel assembles its output in-trace and reads
                    # nothing from the inputs afterwards, so an OWNED
                    # input batch donates its buffers into the update
                    b_donate = b_lazy and batch.owned and \
                        AX.donation_active()
                    # capture the kernel in a local: concurrent partition
                    # tasks share the memo slot, and a stale read across
                    # the donation dimension would run a DONATED program
                    # on a batch whose owner never consented — silent
                    # buffer consumption, not just a shape error
                    memo = update_kernel[0]
                    if memo is None or \
                            memo[0] != (nc, b_lazy, b_donate, enc_sig,
                                        run_key):
                        memo = ((nc, b_lazy, b_donate, enc_sig, run_key),
                                self._build_update_kernel(
                            eff_attrs, eff_keys, eff_inputs, eff_ops,
                            eff_filters, b_lazy, nc, donate=b_donate))
                        update_kernel[0] = memo
                    kern = memo[1]
                    cols = ENC.eval_cols(
                        batch, enc_plan.code_ords) if enc_plan is not None \
                        else [_col_to_colv(c) for c in batch.columns]
                    if not cols:
                        cols = [_synth_col(batch)]
                    if b_donate:
                        TpuDeviceManager.get().note_donation(
                            batch.device_memory_size())

                    def _attempt():
                        M.record_dispatch()
                        return kern(cols, count_arg(batch))

                    with M.trace_range("TpuHashAggregate.update",
                                       self.metrics[M.TOTAL_TIME]):
                        OBS.annotate(path="sort", **rows_attr(batch))
                        out = with_retry(_attempt, site="agg.update",
                                         donated=b_donate)
                    # keyed by the batch's (quantized) column vranges so the
                    # symbolic walk runs once per distinct range profile,
                    # not once per batch
                    in_vrs = tuple(c.vrange for c in batch.columns)
                    kvr = kvr_cache.get(in_vrs)
                    if kvr is None:
                        kvr = [SV.static_vrange(e, in_vrs)
                               for e in bound_key_static]
                        kvr_cache[in_vrs] = kvr
                    if b_lazy:
                        outs, num_groups = out
                        local = self._lazy_batch(outs, num_groups, kvr)
                    else:
                        k, b, gi = out
                        local = self._assemble(
                            k, b, gi, batch.capacity, kvr,
                            buf_dicts=(enc_plan.buf_dicts
                                       if enc_plan is not None else None))
                    if enc_plan is not None and enc_plan.key_dicts:
                        # code-grouped keys wrap back into encoded columns
                        # (min/max buffers were wrapped by _assemble; the
                        # dictionary gathers only at the sink)
                        local = ENC.wrap_batch_cols(local,
                                                    enc_plan.key_dicts)
                    # a fresh update output has unique keys already
                    if running is None:
                        running = local
                    else:
                        running = merge(concat_batches([running, local]))
                else:
                    # merge mode: even a single input batch may hold duplicate
                    # keys (upstream coalesce concatenates exchange pieces)
                    merged = batch if running is None else \
                        concat_batches([running, batch])
                    running = merge(merged)
            yield from self._emit(running, pidx)

        def factory(pidx: int):
            return count_output(self.metrics, agg_partition(pidx))

        return PartitionedBatches(child_pb.num_partitions, factory)

    def _emit(self, running: Optional[ColumnarBatch], pidx: int):
        if self.mode == PARTIAL:
            if running is not None:
                yield running
            return
        if running is not None and not self.grouping:
            # the empty ungrouped reduction must emit the default row; a
            # device-count batch needs one scalar sync to know
            if running.host_rows() == 0:
                running = None
        if running is None:
            if not self.grouping and pidx == 0:
                yield _default_row_batch_device(self.specs, self._inter_attrs,
                                                self.agg_exprs)
            return
        rewritten = rewrite_result_exprs(self.agg_exprs, self.specs)
        projector = DeviceProjector(bind_all(rewritten, self._inter_attrs))
        yield projector.project(running)


def _synth_col(batch: ColumnarBatch):
    from spark_rapids_tpu.ops.values import ColV

    cap = bucket_capacity(max(batch.num_rows, 1))
    # tpulint: eager-jnp, untracked-alloc -- zero-column COUNT(*)
    # placeholder col: one tiny bool lane, not batch data
    return ColV(DataType.BOOL, jnp.zeros((cap,), bool),
                jnp.arange(cap) < batch.num_rows)


def _finalize_kernel(out_cap: int, npdts: tuple):
    """Jitted finalizer for _assemble's fixed-width buffer columns: slice
    to the output capacity, mask dead slots, restore storage dtypes — all
    columns in ONE device dispatch."""
    from spark_rapids_tpu.engine.jit_cache import get_or_build

    def build():
        @jax.jit
        def agg_finalize(outs, n_groups):
            slot = jnp.arange(out_cap) < n_groups
            res = []
            for (data, validity), npdt in zip(outs, npdts):
                d = data[:out_cap]
                v = validity[:out_cap] & slot
                if d.dtype != jnp.dtype(npdt):
                    d = d.astype(npdt)
                d = jnp.where(v, d, jnp.zeros((), d.dtype))
                res.append((d, v))
            return res
        return agg_finalize

    return get_or_build(("agg_finalize", out_cap, npdts), build)


def _assemble_traced(key_cols, buf_outs, gi, capacity: int, buffer_npdts):
    """In-kernel compaction to group slots: one (data, validity) pair per
    output column, all lanes >= num_groups masked dead. Runs inside the
    update/merge jit — no host round trip. Module-level on purpose: jit
    closures are cached process-wide, so they must not capture the exec
    (which would pin the whole plan + source data in memory)."""
    slot = jnp.arange(capacity) < gi.num_groups
    rep = jnp.clip(gi.rep_rows, 0, capacity - 1)
    outs = []
    for cv in key_cols:
        data = jnp.where(slot, cv.data[rep], jnp.zeros((), cv.data.dtype))
        npdt = physical_np_dtype(cv.dtype)
        if cv.dtype is not DataType.STRING and data.dtype != jnp.dtype(npdt):
            data = data.astype(npdt)  # restore storage width after narrowing
        validity = jnp.where(slot, cv.validity[rep], False)
        outs.append((data, validity))
    for (data, validity), npdt in zip(buf_outs, buffer_npdts):
        d = data.astype(npdt) if data.dtype != jnp.dtype(npdt) else data
        v = validity & slot
        d = jnp.where(v, d, jnp.zeros((), d.dtype))
        outs.append((d, v))
    return outs


def _update_inputs(cols, num_rows, bound_keys, bound_inputs, bound_filters):
    """Traced: what an update kernel starts from, the sort-based and the
    dense one alike: (key columns, input columns, the rows that count,
    the batch's capacity). A row counts when it lies under `num_rows`
    and passes every filter the fused stage folded into the aggregate."""
    from spark_rapids_tpu.ops.eval import _scalar_to_colv
    from spark_rapids_tpu.ops.values import EvalContext, ScalarV

    capacity = cols[0].validity.shape[0] if cols else 8
    ctx = EvalContext(jnp, True, cols, num_rows, capacity)

    def as_col(e):
        r = e.eval(ctx)
        if isinstance(r, ScalarV):
            r = _scalar_to_colv(ctx, r, e.data_type)
        return r

    live = ctx.row_mask()
    for f in bound_filters:
        r = f.eval(ctx)
        if isinstance(r, ScalarV):
            live = live & ((not r.is_null) and bool(r.value))
        else:
            live = live & r.data.astype(bool) & r.validity
    return ([as_col(e) for e in bound_keys],
            [as_col(e) for e in bound_inputs], live, capacity)


def _group_info(key_cols, num_rows, capacity: int) -> RK.GroupInfo:
    return _group_info_masked(key_cols, jnp.arange(capacity) < num_rows,
                              capacity)


def _group_info_masked(key_cols, live, capacity: int) -> RK.GroupInfo:
    if not key_cols:
        gid = jnp.where(live, 0, capacity).astype(jnp.int32)
        num_groups = jnp.minimum(jnp.sum(live.astype(jnp.int32)), 1)
        rep = jnp.zeros((capacity,), jnp.int32)
        return RK.GroupInfo(gid, num_groups.astype(jnp.int32), rep)
    proxies = [RK.key_proxy(cv) for cv in key_cols]
    return RK.group_ids_masked(proxies, live, capacity)


def _default_row_batch_device(specs, inter_attrs, agg_exprs) -> ColumnarBatch:
    host = _default_row_batch_host(specs, inter_attrs, agg_exprs)
    return _project_default(host, specs, inter_attrs, agg_exprs, True)


# ===========================================================================
# CPU oracle exec
# ===========================================================================
def _canonical_key(dtype: DataType, value, valid: bool):
    if not valid:
        return None
    if dtype in (DataType.FLOAT32, DataType.FLOAT64):
        f = float(value)
        if f != f:
            return ("NaN",)
        if f == 0.0:
            return 0.0
        return f
    if dtype is DataType.STRING:
        return str(value)
    if dtype is DataType.BOOL:
        return bool(value)
    return int(value)


class _HostAcc:
    """Per-group per-buffer accumulator with SQL null semantics."""

    __slots__ = ("op", "value", "valid", "seen")

    def __init__(self, op: str):
        self.op = op
        self.value = None
        self.valid = False
        self.seen = False  # for first/last including nulls

    def add(self, v, valid: bool):
        op = self.op
        if op.startswith("pct:"):
            if valid:
                if self.value is None:
                    self.value = []
                self.value.append(float(v))
            return
        if op == "unmergeable":
            raise AssertionError(
                "holistic aggregate reached a merge stage — the planner "
                "must run it complete-mode")
        if op == "count":
            if self.value is None:
                self.value = 0
            if valid:
                self.value += 1
            self.valid = True
            return
        if op in ("first", "last"):
            if op == "first" and self.seen:
                return
            self.value, self.valid, self.seen = v, valid, True
            return
        if op in ("first_ignore_nulls", "last_ignore_nulls"):
            if not valid:
                return
            if op.startswith("first") and self.seen:
                return
            self.value, self.valid, self.seen = v, True, True
            return
        if not valid:
            return
        if not self.valid:
            self.value, self.valid = v, True
            return
        if op == "sum":
            s = self.value + v
            if isinstance(s, int):
                # wrap to signed 64-bit like the device's int64 arithmetic
                # (and Java long addition in the reference)
                s = ((s + (1 << 63)) % (1 << 64)) - (1 << 63)
            self.value = s
        elif op == "min":
            self.value = _min_sql(self.value, v)
        elif op == "max":
            self.value = _max_sql(self.value, v)
        elif op == "any":
            self.value = bool(self.value) or bool(v)
        else:
            raise ValueError(f"unknown op {op}")

    def result(self):
        if self.op == "count":
            return (self.value or 0), True
        if self.op.startswith("pct:"):
            if not self.value:
                return None, False
            p = float(self.op[4:])
            vals = np.sort(np.asarray(self.value, dtype=np.float64))
            q = p * (len(vals) - 1)
            k = int(np.floor(q))
            frac = q - k
            hi = min(k + 1, len(vals) - 1) if frac > 0 else k
            return float(vals[k] * (1 - frac) + vals[hi] * frac), True
        return self.value, self.valid


def _is_nan(v) -> bool:
    try:
        return v != v
    except TypeError:
        return False


def _min_sql(a, b):
    # NaN is greater than any value (Spark float ordering)
    if _is_nan(a):
        return b
    if _is_nan(b):
        return a
    return a if a <= b else b


def _max_sql(a, b):
    if _is_nan(a):
        return a
    if _is_nan(b):
        return b
    return a if a >= b else b


_FAST_OPS = frozenset(("sum", "count", "min", "max"))


def _fast_groups(evs, n_keys, key_dtypes, ops):
    """Vectorized group-by for the oracle's hot shape, or None.

    Returns (key_cols, buf_data, buf_valid) group-major arrays — fed to
    _fast_inter_batch instead of the per-row loop's acc dicts — when
    every semantic subtlety is provably absent: integer/bool all-valid
    keys (no _canonical_key float/string/null cases), ops limited to
    sum/count/min/max, and no NaN among valid float values (the
    _min_sql/_max_sql NaN ordering). Anything else falls back to the
    loop. int64 sums wrap per-addition exactly like _HostAcc (modular
    arithmetic is associative), float sums accumulate in row order via
    the unbuffered np.*.at ufuncs, and an all-null group stays invalid
    for sum/min/max (its buf_data slot holds an unused sentinel) while
    count stays valid.
    """
    if not evs or not ops or any(op not in _FAST_OPS for op in ops):
        return None
    if len(evs[0].columns) != n_keys + len(ops):
        return None
    for dt in key_dtypes:
        if dt in (DataType.FLOAT32, DataType.FLOAT64, DataType.STRING):
            return None

    def _cat(cidx, what):
        # tpulint: host-sync -- CPU-oracle columns; HostColumnVector data
        # and validity are already numpy, asarray is a no-op view
        return np.concatenate(
            [np.asarray(getattr(ev.columns[cidx], what)) for ev in evs]) \
            if len(evs) > 1 else np.asarray(getattr(evs[0].columns[cidx],
                                                    what))

    kdata = []
    for c in range(n_keys):
        if not _cat(c, "validity").all():
            return None  # null key rows take the _canonical_key path
        kd = _cat(c, "data")
        if kd.dtype.kind not in "iub":
            return None
        kdata.append(kd)
    vdata, vvalid = [], []
    for j, op in enumerate(ops):
        d = _cat(n_keys + j, "data")
        v = _cat(n_keys + j, "validity").astype(bool, copy=False)
        if op != "count":  # count never reads the value column
            if d.dtype.kind == "f":
                if np.isnan(d[v]).any():
                    return None
            elif d.dtype.kind not in "iu":
                return None
        vdata.append(d)
        vvalid.append(v)

    total = evs[0].num_rows if len(evs) == 1 else \
        sum(ev.num_rows for ev in evs)
    if n_keys == 0:
        grp_count = 1
        inv = np.zeros(total, dtype=np.intp)
        key_cols = []
    elif n_keys == 1:
        uniq, inv = np.unique(kdata[0], return_inverse=True)
        grp_count = len(uniq)
        key_cols = [uniq]
    else:
        mat = np.stack(
            [k.astype(np.int64, copy=False) for k in kdata], axis=1)
        uniq, inv = np.unique(mat, axis=0, return_inverse=True)
        inv = inv.ravel()
        grp_count = len(uniq)
        key_cols = [uniq[:, c] for c in range(n_keys)]

    buf_data, buf_valid = [], []
    for op, d, v in zip(ops, vdata, vvalid):
        nvalid = np.bincount(
            inv, weights=v.astype(np.float64),
            minlength=grp_count).astype(np.int64)
        if op == "count":
            buf_data.append(nvalid)
            buf_valid.append(np.ones(grp_count, dtype=bool))
            continue
        is_float = d.dtype.kind == "f"
        dv = d[v].astype(np.float64 if is_float else np.int64, copy=False)
        iv = inv[v]
        if op == "sum":
            out = np.zeros(grp_count, dtype=dv.dtype)
            np.add.at(out, iv, dv)
        elif op == "min":
            out = np.full(grp_count, np.inf) if is_float else \
                np.full(grp_count, np.iinfo(np.int64).max, dtype=np.int64)
            np.minimum.at(out, iv, dv)
        else:  # max
            out = np.full(grp_count, -np.inf) if is_float else \
                np.full(grp_count, np.iinfo(np.int64).min, dtype=np.int64)
            np.maximum.at(out, iv, dv)
        buf_data.append(out)
        buf_valid.append(nvalid > 0)
    return key_cols, buf_data, buf_valid


class CpuHashAggregateExec(_HashAggregateBase, CpuExec):
    placement = "cpu"

    def execute(self, ctx: ExecContext) -> PartitionedBatches:
        child_pb = self.children[0].execute(ctx)
        child_attrs = self.children[0].output

        def agg_partition(pidx: int):
            groups: Dict[tuple, List[_HostAcc]] = {}
            key_rows: Dict[tuple, tuple] = {}
            order: List[tuple] = []
            do_update = self.mode in (PARTIAL, COMPLETE)
            ops = [op for op, _, _ in self._update_ops()] if do_update else \
                [op for op, _ in self._merge_ops()]
            n_keys = len(self.grouping)
            key_dtypes = [g.data_type for g in self.grouping]
            bound_update = bind_all(
                self.key_exprs + [e for _, e, _ in self._update_ops()],
                child_attrs) if do_update else None
            saw_input = False

            evs = []
            for batch in child_pb.iterator(pidx):
                if batch.num_rows == 0:
                    continue
                saw_input = True
                if do_update:
                    ev = cpu_project(bound_update, batch, partition_id=pidx)
                else:
                    ev = batch
                evs.append(ev)

            fast = _fast_groups(evs, n_keys, key_dtypes, ops)
            if fast is not None:
                evs = []
            for ev in evs:
                kcols = ev.columns[:n_keys]
                vcols = ev.columns[n_keys:]
                for i in range(ev.num_rows):
                    key = tuple(
                        _canonical_key(key_dtypes[c], kcols[c].data[i],
                                       bool(kcols[c].validity[i]))
                        for c in range(n_keys))
                    accs = groups.get(key)
                    if accs is None:
                        accs = [_HostAcc(op) for op in ops]
                        groups[key] = accs
                        order.append(key)
                        key_rows[key] = tuple(
                            (kcols[c].data[i], bool(kcols[c].validity[i]))
                            for c in range(n_keys))
                    for acc, col in zip(accs, vcols):
                        v = col.data[i]
                        if isinstance(v, np.generic):
                            v = v.item()
                        acc.add(v, bool(col.validity[i]))

            if fast is not None:
                inter = self._fast_inter_batch(*fast)
            else:
                inter = self._build_inter_batch(order, key_rows, groups,
                                                saw_input, pidx)
            if inter is None:
                return
            if self.mode == PARTIAL:
                yield inter
                return
            rewritten = rewrite_result_exprs(self.agg_exprs, self.specs)
            yield cpu_project(bind_all(rewritten, self._inter_attrs), inter,
                              partition_id=pidx)

        def factory(pidx: int):
            return count_output(self.metrics, agg_partition(pidx))

        return PartitionedBatches(child_pb.num_partitions, factory)

    def _fast_inter_batch(self, key_cols, buf_data, buf_valid):
        """_build_inter_batch for _fast_groups' group-major arrays: the
        same inter batch, built column-at-a-time. Invalid buffer slots
        carry a sentinel in buf_data — zero them BEFORE the dtype cast
        (inf through an int cast is undefined)."""
        n = len(key_cols[0]) if key_cols else len(buf_data[0])
        cols: List[HostColumnVector] = []
        for c, attr in enumerate(self.grouping):
            npdt = attr.data_type.to_np()
            cols.append(HostColumnVector(
                attr.data_type, key_cols[c].astype(npdt, copy=False),
                np.ones(n, dtype=bool)))
        for b, battr in enumerate(self.buffer_attrs):
            npdt = battr.data_type.to_np()
            valid = buf_valid[b]
            data = np.where(valid, buf_data[b], 0).astype(npdt, copy=False)
            cols.append(HostColumnVector(battr.data_type, data, valid))
        return HostColumnarBatch(cols, n)

    def _build_inter_batch(self, order, key_rows, groups, saw_input, pidx):
        n_keys = len(self.grouping)
        if not order:
            if self.mode == PARTIAL or self.grouping or pidx != 0:
                return None
            return _default_row_batch_host(self.specs, self._inter_attrs,
                                           self.agg_exprs)
        n = len(order)
        cols: List[HostColumnVector] = []
        for c, attr in enumerate(self.grouping):
            npdt = attr.data_type.to_np()
            data = np.zeros(n, dtype=npdt)
            validity = np.zeros(n, dtype=bool)
            for i, key in enumerate(order):
                v, valid = key_rows[key][c]
                validity[i] = valid
                if valid:
                    data[i] = v
                elif attr.data_type is DataType.STRING:
                    data[i] = ""
            cols.append(HostColumnVector(attr.data_type, data, validity))
        for b, battr in enumerate(self.buffer_attrs):
            npdt = battr.data_type.to_np()
            data = np.zeros(n, dtype=npdt)
            if battr.data_type is DataType.STRING:
                data[:] = ""
            validity = np.zeros(n, dtype=bool)
            for i, key in enumerate(order):
                v, valid = groups[key][b].result()
                validity[i] = valid
                if valid and v is not None:
                    data[i] = v
            cols.append(HostColumnVector(battr.data_type, data, validity))
        return HostColumnarBatch(cols, n)


def _default_row_batch_host(specs, inter_attrs, agg_exprs) -> HostColumnarBatch:
    """One row of initial buffer values (no grouping columns by definition)."""
    vals = _default_row_values(specs)
    cols = []
    for battr, v in zip(inter_attrs, vals):
        npdt = battr.data_type.to_np()
        data = np.zeros(1, dtype=npdt)
        validity = np.array([v is not None])
        if v is not None and battr.data_type is not DataType.STRING:
            data[0] = v
        cols.append(HostColumnVector(battr.data_type, data, validity))
    return HostColumnarBatch(cols, 1)


def _project_default(host_batch, specs, inter_attrs, agg_exprs, device: bool):
    rewritten = rewrite_result_exprs(agg_exprs, specs)
    if device:
        dev = host_batch.to_device()
        return DeviceProjector(bind_all(rewritten, inter_attrs)).project(dev)
    return cpu_project(bind_all(rewritten, inter_attrs), host_batch)
