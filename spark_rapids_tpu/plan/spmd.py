"""Single-program SPMD stage compiler (plan side).

The scale-out unlock of ROADMAP open item 1, extended by open item 2 into
whole-query single-program compilation: where the host-loop executor runs
a stage as O(partitions x ops) per-partition dispatches with the exchange
mediated through host-visible buffers, this pass identifies maximal
SPMD-eligible stage pipelines in the FINAL physical plan and lowers each
into ONE jitted `shard_map` program over the session device mesh
(engine/spmd_exec.py builds and runs it):

    [TpuSortExec                       <- optional absorbed global-sort tail
      [TpuShuffleExchangeExec(Range)]]
        TpuHashAggregateExec(final)    <- in-program merge + finalize
          TpuShuffleExchangeExec(Hash) <- in-program lax.all_to_all epoch
            TpuHashAggregateExec(partial) + Filter/Project chain
                                       <- in-program update side
              [inner equi-join]*       <- in-program: build side broadcast
                                          via lax.all_gather, probe rows
                                          stream on through the stage
              <stage input>            <- host batches (scan) or device
                                          batches (join output, previous
                                          SPMD stage)

Two composition axes beyond the single pipeline:

- **join lowering**: shuffled/broadcast INNER equi-joins below the partial
  aggregate lower into the stage program — the build side assembles like a
  second stage input and an in-program `lax.all_gather` replicates it to
  every shard (the planned join exchanges are elided in-program; the
  host-loop subtree keeps them). The probe side streams on through the
  existing in-program all_to_all hash exchange of the aggregate.
- **stage chaining**: when the stage input is itself an SPMD-eligible
  pipeline (a double group-by), the two stages CHAIN inside one program —
  the post-exchange merged buckets of stage k are the in-trace inputs of
  stage k+1, never re-assembled into [m, cap] slots through the host.

Best-effort TpuCoalesceBatches nodes between the pattern members are
transparent (they are perf no-ops once the whole pipeline is one program).
Theseus (PAPERS.md) is the blueprint: the distributed plan is designed
around data movement — the exchange is a collective INSIDE the stage
program, not a host-driven boundary between task loops.

Like `TpuFusedStageExec`, the wrapper node keeps the ORIGINAL operator
subtree as its child: EXPLAIN, the plan verifier, and the resource
analyzer keep seeing the member nodes, and the host-loop executor is
always one `children[0].execute()` away — ineligible-at-runtime stages,
checked replays, and CPU fallbacks all take that path, so the PR 4/PR 6
retry and re-attribution contracts hold unchanged (docs/spmd-stages.md).

Conf: rapids.tpu.sql.spmd.enabled (default ON), spmd.meshDevices,
spmd.bucketRows, spmd.maxSortLanes, spmd.joinLowering.enabled,
spmd.chainStages.enabled, spmd.joinRows, spmd.measuredCapacity.enabled.
"""

from __future__ import annotations

import itertools
import logging
from typing import List, Optional, Tuple

from spark_rapids_tpu import conf as C
from spark_rapids_tpu.columnar.dtypes import DataType
from spark_rapids_tpu.exec.base import (
    ExecContext,
    PartitionedBatches,
    PhysicalExec,
    TpuExec,
    count_output,
)
from spark_rapids_tpu.ops.base import Alias, AttributeReference, Expression

log = logging.getLogger(__name__)

# merge-safe reduce ops the in-program aggregate supports; everything else
# (holistic percentiles, order-dependent first/last, string min/max with
# their chunked arg-extreme machinery) keeps the host-loop executor
_SPMD_OPS = ("sum", "count", "min", "max")

# compile-time guard: joins absorbed per stage segment
_SPMD_MAX_JOINS = 8


class SpmdJoinSpec:
    """One INNER equi-join lowered into the stage program. The build side
    is a second stage input (its own collapsed Filter/Project chain over a
    host upload or device producer), broadcast in-program via all_gather;
    the probe side is the stage's streaming frontier. Expressions are
    UNBOUND; the executor binds them against the pruned schemas."""

    __slots__ = (
        "join", "n_keys",
        # build side: collapsed chain below the build child
        "build_input_node", "build_host_input", "build_attrs",
        "build_ordinals", "build_filters", "build_keys", "build_out_exprs",
        "build_out_attrs",
        # join output frontier
        "out_attrs", "out_sources", "post_filters",
        # production for the join ABOVE this one (None for the topmost
        # join — the stage's key/input exprs consume out_attrs directly)
        "prod_exprs",
        # exchanges this lowering absorbs (shuffled-join inputs)
        "covered_exchanges",
        # filled by plan/resources._spmd_stage: sound upper bound on the
        # join's output rows — sizes the static expansion capacity
        "rows_hint",
    )

    def __init__(self, **kw):
        for k in self.__slots__:
            setattr(self, k, kw.get(k))


class SpmdStageInfo:
    """Everything the stage program builder needs for ONE pipeline
    segment, extracted once at plan time. Expressions are UNBOUND (over
    attr references); the executor binds them against the pruned stage
    input / frontier schemas."""

    __slots__ = (
        "head", "sort", "sort_keys", "final", "exchange", "partial",
        "input_node", "host_input", "input_attrs", "needed_ordinals",
        "key_exprs", "input_exprs", "filters", "op_names", "merge_ops",
        "result_exprs", "result_key_idx", "hash_key_idx", "n_keys",
        # in-program joins (execution order: joins[0] innermost) and the
        # production expressions feeding the innermost join
        "joins", "bottom_exprs", "bottom_filters",
    )

    def __init__(self, **kw):
        for k in self.__slots__:
            setattr(self, k, kw.get(k))
        if self.joins is None:
            self.joins = ()

    @property
    def top_attrs(self) -> List[AttributeReference]:
        """Schema the update-side key/input/filter expressions bind
        against: the topmost join's output frontier, or the stage input."""
        if self.joins:
            return list(self.joins[-1].out_attrs)
        return list(self.input_attrs)

    def covered_exchanges(self) -> List[PhysicalExec]:
        """Exchange nodes this segment absorbs in-program (its hash
        exchange, the absorbed range exchange, and any shuffled-join
        exchanges) — the resource analyzer's stage-coverage accounting."""
        out = [self.exchange]
        if self.sort is not None:
            out.append(_skip_coalesce(self.sort.children[0]))
        for j in self.joins:
            out.extend(j.covered_exchanges or ())
        return out


def _skip_coalesce(node: PhysicalExec) -> PhysicalExec:
    """Walk through batch coalesces between pattern members. TargetSize
    coalesces are pure perf; a RequireSingleBatch below a sort only exists
    so the host-loop sort sees one batch per partition — inside the single
    stage program both are moot."""
    from spark_rapids_tpu.exec.transitions import TpuCoalesceBatchesExec

    while isinstance(node, TpuCoalesceBatchesExec):
        node = node.children[0]
    return node


def _string_refs(e: Expression) -> List[AttributeReference]:
    return [a for a in e.collect(
        lambda n: isinstance(n, AttributeReference))
        if a.data_type is DataType.STRING]


def _string_filters_ok(filters: List[Expression]) -> bool:
    """String references inside filter conditions are admissible when
    every use sits in an equality-class position (EqualTo / EqualNullSafe
    / In over literals, IS [NOT] NULL) — exactly the code-space
    supportedness rule of columnar/encoded.py, reused here because the
    traced stage evaluates those predicates either on int32 dictionary
    CODES (encoded inputs) or on the fixed-width byte-matrix
    representation (raw strings)."""
    from spark_rapids_tpu.columnar.encoded import unbound_supported_refs

    str_ids = {a.expr_id for f in filters for a in _string_refs(f)}
    if not str_ids:
        return True
    return unbound_supported_refs(filters, str_ids) == str_ids


def _prod_exprs_ok(exprs: List[Expression]) -> bool:
    """Matrix discipline for frontier-production expressions: a STRING
    result must be a direct column reference (it travels as a byte matrix
    / code column), and computed expressions must not read strings."""
    for e in exprs:
        if e.data_type is DataType.STRING:
            if not isinstance(e, AttributeReference):
                return False
        elif _string_refs(e):
            return False
    return True


def _collapse_through(cur: PhysicalExec, exprs: List[Expression]):
    """exec/aggregate.collapse_update_chain: _collapse_scan_chain extended
    to see through non-agg-form fused stage wrappers."""
    from spark_rapids_tpu.exec.aggregate import collapse_update_chain

    return collapse_update_chain(cur, exprs)


def _eligible_join(node: PhysicalExec) -> bool:
    from spark_rapids_tpu.exec.join import (
        TpuBroadcastHashJoinExec,
        TpuShuffledHashJoinExec,
    )
    from spark_rapids_tpu.plan.logical import JoinType

    return (isinstance(node, (TpuShuffledHashJoinExec,
                              TpuBroadcastHashJoinExec))
            and node.join_type is JoinType.INNER
            and not node.build_left)


def _unwrap_join_input(node: PhysicalExec):
    """Descend through coalesce wrappers and (for shuffled joins) the
    planned exchange feeding a join input. Returns (subtree, covered
    exchange nodes): in-program the build broadcast makes both planned
    join shuffles moot, exactly like runtime broadcast demotion — the
    host-loop subtree keeps them."""
    from spark_rapids_tpu.shuffle.exchange import (
        HashPartitioning,
        TpuShuffleExchangeExec,
    )

    covered = []
    cur = _skip_coalesce(node)
    if isinstance(cur, TpuShuffleExchangeExec) and \
            isinstance(cur.partitioning, HashPartitioning):
        covered.append(cur)
        cur = _skip_coalesce(cur.children[0])
    return cur, covered


def _match_build_side(join, needed_build_attrs) -> Optional[SpmdJoinSpec]:
    """Collapse a join's build child into (input node, key exprs, output
    exprs, filters) — the second stage input this join broadcasts. Returns
    a PARTIAL SpmdJoinSpec (build fields only) or None."""
    from spark_rapids_tpu.exec.fused import exprs_fusable
    from spark_rapids_tpu.exec.transitions import HostToDeviceExec

    build_keys_raw = join.right_keys
    build_sub, covered = _unwrap_join_input(join.children[1])
    bexprs = list(build_keys_raw) + \
        [AttributeReference(a.name, a.data_type, a.nullable, a.expr_id)
         for a in needed_build_attrs]
    binput, brew, bfilters = _collapse_through(build_sub, bexprs)
    n_jk = len(build_keys_raw)
    build_keys = brew[:n_jk]
    build_out_exprs = brew[n_jk:]
    if not exprs_fusable(build_keys + build_out_exprs + bfilters):
        return None
    for e in build_keys:
        if e.data_type is DataType.STRING and \
                not isinstance(e, AttributeReference):
            return None
        if e.data_type is not DataType.STRING and _string_refs(e):
            return None
    if not _prod_exprs_ok(build_out_exprs):
        return None
    if not _string_filters_ok(bfilters):
        return None

    host_input = isinstance(binput, HostToDeviceExec)
    if not host_input and binput.placement != "tpu":
        return None
    battrs = list(binput.output)
    needed_ids = set()
    for e in list(build_keys) + list(build_out_exprs) + list(bfilters):
        for a in e.collect(lambda n: isinstance(n, AttributeReference)):
            needed_ids.add(a.expr_id)
    bords = [i for i, a in enumerate(battrs) if a.expr_id in needed_ids]
    pruned = [battrs[i] for i in bords]
    if needed_ids - {a.expr_id for a in pruned}:
        return None
    return SpmdJoinSpec(
        join=join, n_keys=n_jk, build_input_node=binput,
        build_host_input=host_input, build_attrs=pruned,
        build_ordinals=bords, build_filters=bfilters,
        build_keys=build_keys, build_out_exprs=build_out_exprs,
        build_out_attrs=list(needed_build_attrs),
        covered_exchanges=covered)


def _match_update_pipeline(partial_child: PhysicalExec,
                           raw_exprs: List[Expression],
                           join_lowering: bool):
    """Walk the chain below the partial aggregate, absorbing eligible
    INNER equi-joins. Returns (input_node, top_exprs, top_filters, joins,
    bottom_exprs, bottom_filters) where `joins` is in EXECUTION order
    (innermost first) or None on a hard ineligibility. An ineligible join
    simply becomes the stage input (device producer) — per stage, the
    lowering is maximal-but-graceful."""
    from spark_rapids_tpu.exec.fused import exprs_fusable

    levels = []  # top-down: [join node, exprs above, filters above]
    cur, exprs = partial_child, raw_exprs
    while True:
        node, rewritten, filters = _collapse_through(cur, exprs)
        if not (join_lowering and _eligible_join(node)
                and len(levels) < _SPMD_MAX_JOINS):
            bottom = (node, rewritten, filters)
            break
        join = node
        needed_exprs = list(rewritten) + list(filters)
        post_filters = list(filters)
        if join.condition is not None:
            needed_exprs.append(join.condition)
            post_filters.append(join.condition)
        if not exprs_fusable(post_filters) or \
                not _string_filters_ok(post_filters):
            bottom = (node, rewritten, filters)
            break
        needed_ids = set()
        for e in needed_exprs:
            for a in e.collect(lambda n: isinstance(n, AttributeReference)):
                needed_ids.add(a.expr_id)
        stream_ids = {a.expr_id for a in join.children[0].output}
        build_ids = {a.expr_id for a in join.children[1].output}
        if needed_ids - (stream_ids | build_ids):
            bottom = (node, rewritten, filters)
            break
        out_attrs = [a for a in join.output if a.expr_id in needed_ids]
        stream_out = [a for a in out_attrs if a.expr_id in stream_ids]
        build_out = [a for a in out_attrs if a.expr_id not in stream_ids]
        stream_keys = join.left_keys
        if any(sk.data_type != bk.data_type
               for sk, bk in zip(stream_keys, join.right_keys)):
            bottom = (node, rewritten, filters)
            break
        jspec = _match_build_side(join, build_out)
        if jspec is None:
            bottom = (node, rewritten, filters)
            break
        sout_pos = {a.expr_id: i for i, a in enumerate(stream_out)}
        bout_pos = {a.expr_id: i for i, a in enumerate(build_out)}
        jspec.out_attrs = out_attrs
        jspec.out_sources = [
            ("s", sout_pos[a.expr_id]) if a.expr_id in stream_ids
            else ("b", bout_pos[a.expr_id]) for a in out_attrs]
        jspec.post_filters = post_filters
        levels.append([jspec, rewritten])
        stream_sub, s_covered = _unwrap_join_input(join.children[0])
        jspec.covered_exchanges = list(jspec.covered_exchanges) + s_covered
        cur = stream_sub
        exprs = list(stream_keys) + [
            AttributeReference(a.name, a.data_type, a.nullable, a.expr_id)
            for a in stream_out]

    input_node, bottom_rewritten, bottom_filters = bottom
    if not _string_filters_ok(bottom_filters):
        return None
    if not levels:
        return (input_node, bottom_rewritten, bottom_filters, (), (), ())

    # execution order: innermost join first. levels[t][1] is the expr
    # list evaluated ON join t's output frontier: the top agg exprs for
    # t == 0, or the production (stream keys + pass-throughs) for the
    # join ABOVE (t - 1) otherwise.
    joins_exec = [levels[t][0] for t in range(len(levels) - 1, -1, -1)]
    for k, jspec in enumerate(joins_exec):
        t = len(levels) - 1 - k  # top-down index of this join
        if t == 0:
            jspec.prod_exprs = None  # top agg exprs consume directly
        else:
            jspec.prod_exprs = list(levels[t][1])
            if not exprs_fusable(jspec.prod_exprs) or \
                    not _prod_exprs_ok(jspec.prod_exprs):
                return None
    top_exprs = levels[0][1]
    # bottom production (feeds the innermost join): the last descend's
    # collapsed expressions over the stage input
    if not exprs_fusable(list(bottom_rewritten)) or \
            not _prod_exprs_ok(list(bottom_rewritten)):
        return None
    return (input_node, top_exprs, [], tuple(joins_exec),
            tuple(bottom_rewritten), tuple(bottom_filters))


def match_spmd_stage(node: PhysicalExec,
                     join_lowering: bool = True) -> Optional[SpmdStageInfo]:
    """The SPMD stage pattern rooted at `node`, or None. See the module
    docstring for the shape; docs/spmd-stages.md for the eligibility
    rules in prose."""
    from spark_rapids_tpu.exec.aggregate import (
        FINAL,
        PARTIAL,
        TpuHashAggregateExec,
        rewrite_result_exprs,
    )
    from spark_rapids_tpu.exec.fused import TpuFusedStageExec, exprs_fusable
    from spark_rapids_tpu.exec.sort import TpuSortExec
    from spark_rapids_tpu.shuffle.exchange import (
        HashPartitioning,
        RangePartitioning,
        TpuShuffleExchangeExec,
    )

    # -- optional global-sort tail -------------------------------------------
    sort = None
    cur = node
    if isinstance(cur, TpuSortExec):
        below = _skip_coalesce(cur.children[0])
        if not (isinstance(below, TpuShuffleExchangeExec)
                and isinstance(below.partitioning, RangePartitioning)):
            return None
        rp = below.partitioning
        if len(rp.orders) != len(cur.orders) or any(
                not (isinstance(a.child, AttributeReference)
                     and isinstance(b.child, AttributeReference)
                     and a.child.expr_id == b.child.expr_id
                     and a.ascending == b.ascending
                     and a.nulls_first == b.nulls_first)
                for a, b in zip(rp.orders, cur.orders)):
            return None  # the exchange must implement exactly this sort
        sort = cur
        cur = _skip_coalesce(below.children[0])

    # -- final aggregate ------------------------------------------------------
    if not (isinstance(cur, TpuHashAggregateExec) and cur.mode == FINAL
            and cur.grouping):
        return None
    final = cur

    # -- hash exchange --------------------------------------------------------
    ex = _skip_coalesce(final.children[0])
    if not (isinstance(ex, TpuShuffleExchangeExec)
            and isinstance(ex.partitioning, HashPartitioning)
            and ex.partitioning.exprs):
        return None
    exchange = ex

    # -- partial aggregate (possibly inside an agg-form fused stage) ---------
    pa = _skip_coalesce(exchange.children[0])
    if isinstance(pa, TpuFusedStageExec) and pa.agg_form:
        pa = pa.children[0]
    if not (isinstance(pa, TpuHashAggregateExec) and pa.mode == PARTIAL):
        return None
    partial = pa

    n_keys = len(final.grouping)
    inter = exchange.children[0].output  # partial output: keys + buffers
    if len(partial.grouping) != n_keys or \
            len(inter) != n_keys + len(final.buffer_attrs):
        return None
    # positional dtype agreement between the partial's emitted buffers and
    # the final's declared ones (the exchange passes them through verbatim)
    for a, b in zip(inter, list(final.grouping) + final.buffer_attrs):
        if a.data_type != b.data_type:
            return None
    if any(a.data_type is DataType.STRING for a in final.buffer_attrs):
        return None  # string min/max buffers stay host-loop

    # the exchange must route by (a subset of) the grouping keys so equal
    # key tuples meet on one shard
    hash_key_idx: List[int] = []
    key_ids = [a.expr_id for a in inter[:n_keys]]
    for e in exchange.partitioning.exprs:
        if not isinstance(e, AttributeReference) or e.expr_id not in key_ids:
            return None
        hash_key_idx.append(key_ids.index(e.expr_id))

    # -- update side: collapse the chain (and joins) below the partial -------
    ops = partial._update_ops()
    op_names = [op for op, _, _ in ops]
    if any(op not in _SPMD_OPS for op in op_names):
        return None
    merge_ops = final._merge_ops()
    if any(op not in _SPMD_OPS for op, _ in merge_ops):
        return None
    raw_exprs = list(partial.key_exprs) + [e for _, e, _ in ops]
    matched = _match_update_pipeline(partial.children[0], raw_exprs,
                                     join_lowering)
    if matched is None:
        return None
    (input_node, rewritten, filters, joins, bottom_exprs,
     bottom_filters) = matched
    key_exprs = rewritten[:n_keys]
    input_exprs = rewritten[n_keys:]
    if not exprs_fusable(list(key_exprs) + list(input_exprs)
                         + list(filters)):
        return None
    if not _string_filters_ok(list(filters)):
        return None

    # -- string discipline ----------------------------------------------------
    # string stage-input columns travel as fixed-width byte matrices (or
    # int32 dictionary codes when the input arrives encoded), so they may
    # only be consumed as DIRECT key references (hashed/grouped straight
    # from that representation, shuffle/ici.py); computed expressions must
    # not read them. Filter predicates over strings follow the code-space
    # supportedness rule (checked in _match_update_pipeline).
    for e in key_exprs:
        if e.data_type is DataType.STRING:
            if not isinstance(e, AttributeReference):
                return None
        elif _string_refs(e):
            return None
    for e in list(input_exprs):
        if e.data_type is DataType.STRING or _string_refs(e):
            return None

    # -- finalize side --------------------------------------------------------
    result_exprs = rewrite_result_exprs(final.agg_exprs, final.specs)
    grouping_ids = [a.expr_id for a in final.grouping]
    result_key_idx: List[Optional[int]] = []
    for e in result_exprs:
        if e.data_type is DataType.STRING:
            if not (isinstance(e, AttributeReference)
                    and e.expr_id in grouping_ids):
                return None
            result_key_idx.append(grouping_ids.index(e.expr_id))
        else:
            if _string_refs(e):
                return None
            result_key_idx.append(None)
    if not exprs_fusable(result_exprs):
        return None

    # -- absorbed sort keys ---------------------------------------------------
    sort_keys: Optional[List[Tuple[int, bool, bool]]] = None
    if sort is not None:
        out_ids = [a.expr_id for a in final.output]
        sort_keys = []
        for o in sort.orders:
            if not (isinstance(o.child, AttributeReference)
                    and o.child.expr_id in out_ids):
                return None
            sort_keys.append((out_ids.index(o.child.expr_id),
                              o.ascending, o.nulls_first))

    # -- stage input ----------------------------------------------------------
    from spark_rapids_tpu.exec.transitions import HostToDeviceExec

    host_input = isinstance(input_node, HostToDeviceExec)
    if not host_input and input_node.placement != "tpu":
        return None

    # prune the stage input to the columns the program actually reads
    consumed = (list(bottom_exprs) + list(bottom_filters)) if joins else \
        (list(key_exprs) + list(input_exprs) + list(filters))
    input_attrs = list(input_node.output)
    needed_ids = set()
    for e in consumed:
        for a in e.collect(lambda n: isinstance(n, AttributeReference)):
            needed_ids.add(a.expr_id)
    needed_ordinals = [i for i, a in enumerate(input_attrs)
                       if a.expr_id in needed_ids]
    pruned = [input_attrs[i] for i in needed_ordinals]
    if needed_ids - {a.expr_id for a in pruned}:
        return None  # an expression reads a column the input never emits

    return SpmdStageInfo(
        head=node, sort=sort, sort_keys=sort_keys, final=final,
        exchange=exchange, partial=partial, input_node=input_node,
        host_input=host_input, input_attrs=pruned,
        needed_ordinals=needed_ordinals, key_exprs=list(key_exprs),
        input_exprs=list(input_exprs), filters=list(filters),
        op_names=op_names, merge_ops=merge_ops, result_exprs=result_exprs,
        result_key_idx=result_key_idx, hash_key_idx=hash_key_idx,
        n_keys=n_keys, joins=joins, bottom_exprs=list(bottom_exprs),
        bottom_filters=list(bottom_filters))


def match_spmd_chain(node: PhysicalExec, join_lowering: bool = True,
                     chaining: bool = True
                     ) -> Optional[List[SpmdStageInfo]]:
    """A CHAIN of SPMD stage segments rooted at `node`: the outermost
    pipeline, plus every nested pipeline reachable through the stage
    input (a double group-by), innermost FIRST. Chained segments execute
    inside ONE shard_map program — the post-exchange merged buckets of
    segment k are segment k+1's in-trace input, with no [m, cap] host
    re-assembly between them. Only sortless segments chain below another
    (a mid-pipeline sort has no in-trace consumer shape)."""
    info = match_spmd_stage(node, join_lowering=join_lowering)
    if info is None:
        return None
    infos = [info]
    while chaining:
        inner = match_spmd_stage(infos[0].input_node,
                                 join_lowering=join_lowering)
        if inner is None or inner.sort is not None:
            break
        infos.insert(0, inner)
    return infos


class TpuSpmdStageExec(TpuExec):
    """One SPMD stage pipeline — possibly a CHAIN of segments — compiled
    to a single shard_map program over the mesh (engine/spmd_exec.py).
    children[0] is the ORIGINAL subtree — the host-loop executor for this
    stage, taken whenever the program is ineligible at runtime, a fault
    exhausts its retries, or the session is replaying in checked mode."""

    def __init__(self, stage_id: int, head: PhysicalExec,
                 infos: List[SpmdStageInfo], join_lowering: bool = True,
                 chaining: bool = True):
        super().__init__(head)
        self.stage_id = stage_id
        self.infos = list(infos)
        # the conf the stage was LOWERED under: a with_children rebuild
        # (an AQE stage replacement below the input) must re-match with
        # the same flags, not the defaults
        self._join_lowering = join_lowering
        self._chaining = chaining
        # filled by the resource analyzer (plan/resources._spmd_stage):
        # per segment, a sound upper bound on the partial-aggregate output
        # rows, sizing the per-target exchange buckets inside the program
        self.bucket_rows_hints: List[Optional[int]] = [None] * len(infos)

    # -- single-segment compatibility ----------------------------------------
    @property
    def info(self) -> SpmdStageInfo:
        """The OUTERMOST segment (the one whose head is children[0])."""
        return self.infos[-1]

    @property
    def bucket_rows_hint(self) -> Optional[int]:
        return self.bucket_rows_hints[-1]

    @bucket_rows_hint.setter
    def bucket_rows_hint(self, v) -> None:
        self.bucket_rows_hints[-1] = v

    @property
    def output(self):
        return self.children[0].output

    def with_children(self, new_children):
        infos = match_spmd_chain(new_children[0],
                                 join_lowering=self._join_lowering,
                                 chaining=self._chaining)
        if infos is None:
            # the rebuilt subtree no longer matches the pattern — hand the
            # bare subtree back rather than wrap an unrunnable stage
            return new_children[0]
        node = TpuSpmdStageExec(self.stage_id, new_children[0], infos,
                                join_lowering=self._join_lowering,
                                chaining=self._chaining)
        if len(infos) == len(self.infos):
            # keep the analyzer's capacity hints across the rebuild (they
            # are advisory — the overflow probes backstop a stale one)
            node.bucket_rows_hints = list(self.bucket_rows_hints)
        return node

    def node_name(self):
        segs = []
        for info in self.infos:
            inner = []
            if info.joins:
                inner.append(f"Join*{len(info.joins)}")
            inner.extend(["PartialAgg", "AllToAll", "FinalAgg"])
            if info.sort is not None:
                inner.append("Sort")
            segs.append("->".join(inner))
        return f"TpuSpmdStage({self.stage_id})[{'=>'.join(segs)}]"

    def execute(self, ctx: ExecContext) -> PartitionedBatches:
        from spark_rapids_tpu.engine import async_exec as AX
        from spark_rapids_tpu.engine import spmd_exec
        from spark_rapids_tpu.engine.retry import (
            TpuAsyncSinkError,
            failure_is_device_rooted,
        )

        if AX.in_checked_mode() or not ctx.conf.get(C.SPMD_ENABLED):
            # the checked replay must re-attribute errors to HOST-LOOP
            # dispatch sites (docs/async-execution.md); a conf flip between
            # plan and execute degrades the same way
            return self._host_loop(ctx)
        # the fallback runs AFTER the except blocks, and execute_stage
        # explicitly drops its assembled [m, cap] stage-input arrays
        # before raising a fallback: the host-loop re-run happens exactly
        # when device memory is tightest, so nothing from the abandoned
        # program may stay referenced from the in-flight exception's
        # traceback frames
        try:
            return spmd_exec.execute_stage(self, ctx)
        except spmd_exec.SpmdStageFallback as e:
            log.warning("SPMD stage %d ineligible at runtime (%s); "
                        "degrading to the host-loop executor",
                        self.stage_id, e)
        except Exception as e:  # noqa: BLE001 — degradation boundary
            if isinstance(e, TpuAsyncSinkError) or not \
                    failure_is_device_rooted(e):
                # sink-attributed errors belong to the session's checked
                # replay; non-device errors are real bugs — neither may be
                # absorbed by the stage fallback
                raise
            log.warning("SPMD stage %d failed on-device (%r); degrading "
                        "to the host-loop executor", self.stage_id, e)
        return self._host_loop(ctx)

    def _host_loop(self, ctx: ExecContext) -> PartitionedBatches:
        pb = self.children[0].execute(ctx)
        return PartitionedBatches(
            pb.num_partitions,
            lambda p: count_output(self.metrics, pb.iterator(p)),
            bucket_costs=pb.bucket_costs)


def mesh_size(conf: C.TpuConf) -> int:
    """Devices a stage program of this session would span
    (shuffle/ici.stage_mesh's count, without building the mesh)."""
    import jax

    m = len(jax.devices())
    want = int(conf.get(C.SPMD_MESH_DEVICES) or 0)
    return min(m, want) if want else m


def _streams_dense(infos: List[SpmdStageInfo], conf: C.TpuConf) -> bool:
    """Is this pipeline better left to the streaming operators? On a mesh
    of ONE device, where every grouping key is a dictionary-coded STRING
    column of a parquet scan and the table over the dictionaries the
    footers report fits `exec/dense_agg.py`: the partial aggregate then
    reduces each batch as its task uploads it into a table of a few
    slots, with no sort and no fence, and what the exchange, the merge
    and the sort see is a handful of rows. The single program has nothing
    to exchange across chips there, and would first re-pack the whole
    stage input into one [1, capacity] table (a row-count fence and a
    copy of every column) to aggregate it into the same few slots; sized
    from its input rows it did not fit `spmd.maxSortLanes` at all (TPC-H
    Q1 at SF1: ROADMAP S6, M3). On a larger mesh the program is what
    spreads the work, and the analyzer sizes its buckets from the same
    dictionaries (plan/resources._file_scan)."""
    from spark_rapids_tpu.exec import dense_agg as DA
    from spark_rapids_tpu.io.scan import TpuFileScanExec

    if len(infos) != 1 or infos[0].joins:
        return False
    info = infos[0]
    if not info.key_exprs:
        return False
    scans = info.input_node.collect_nodes(
        lambda n: isinstance(n, TpuFileScanExec))
    if len(scans) != 1:
        return False
    if mesh_size(conf) != 1:
        return False
    plan = scans[0].dict_columns_plan(conf)
    names = {a.expr_id: a.name for a in scans[0].output}
    sizes = []
    for e in info.key_exprs:
        inner = e.child if isinstance(e, Alias) else e
        if not isinstance(inner, AttributeReference) or \
                names.get(inner.expr_id) not in plan:
            return False
        sizes.append(plan[names[inner.expr_id]][0])
    return DA.applies(info.op_names,
                      [e.data_type for e in info.input_exprs],
                      sizes) is not None


def lower_spmd_stages(plan: PhysicalExec, conf: C.TpuConf) -> PhysicalExec:
    """Wrap every maximal SPMD-eligible pipeline (chains included) in a
    TpuSpmdStageExec. Runs LAST in the plan pipeline (after fusion), so
    the wrapped subtree is exactly what the host-loop executor would
    run."""
    from spark_rapids_tpu.engine import async_exec as AX

    if not conf.get(C.SPMD_ENABLED) or AX.in_checked_mode():
        return plan
    join_lowering = bool(conf.get(C.SPMD_JOIN_LOWERING))
    chaining = bool(conf.get(C.SPMD_CHAIN_STAGES))
    counter = itertools.count(1)

    def walk(node: PhysicalExec) -> PhysicalExec:
        infos = match_spmd_chain(node, join_lowering=join_lowering,
                                 chaining=chaining)
        if infos is not None and _streams_dense(infos, conf):
            infos = None
        if infos is not None:
            # recurse only at/below the CHAIN's innermost stage input (a
            # deeper ineligible producer may still contain eligible
            # pipelines); the pattern members themselves — and every
            # chained segment — are consumed by this one program
            inp = infos[0].input_node
            new_inp = walk(inp)
            if new_inp is not inp:
                node = node.transform_up(
                    lambda n: new_inp if n is inp else n)
                infos = match_spmd_chain(node, join_lowering=join_lowering,
                                         chaining=chaining)
                if infos is None:  # pragma: no cover - rebuild kept shape
                    return node
            return TpuSpmdStageExec(next(counter), node, infos,
                                    join_lowering=join_lowering,
                                    chaining=chaining)
        new_children = [walk(c) for c in node.children]
        if new_children and any(
                a is not b for a, b in zip(new_children, node.children)):
            node = node.with_children(new_children)
        return node

    return walk(plan)


def count_spmd_stages(plan: PhysicalExec) -> int:
    """Total SPMD segments in the plan (a chained program counts each of
    its pipeline segments — the dispatch count, not this, reflects that
    they share one program)."""
    return sum(len(n.infos) for n in plan.collect_nodes(
        lambda n: isinstance(n, TpuSpmdStageExec)))
