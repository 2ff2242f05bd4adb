"""Whole-stage fused executor (the WholeStageCodegen analog).

One `TpuFusedStageExec` owns a maximal chain of pipelined device operators
(the plan/fusion.py pass builds it) and traces the WHOLE chain as one
composed device function: child batch in, final stage batch out. Filters
become live-row masks carried through the trace (no per-operator compaction),
projections rewrite the column set in-trace, Expand selects its projection
list as a static program variant, and a LocalLimit becomes a prefix mask over
the live rows — so XLA fuses across operator boundaries and the
intermediates between exec nodes never materialize as HBM batches. One
compaction at stage exit (skipped entirely for row-preserving chains)
replaces the per-filter compact+sync of the unfused path.

The stage keeps the ORIGINAL operator subtree as its child for plan
introspection (EXPLAIN renders the members with Spark-style `*(N)` markers,
plan-capture tests keep seeing the member nodes); execute() bypasses the
members and runs the composed program against the chain's input directly.

Two forms:
- scan form: Filter/Project/Expand/LocalLimit chain -> own composed program.
- aggregate form: the chain terminates at the update side of a hash
  aggregate; the aggregate's update kernel already traces projections and
  filter masks below it into its single program
  (exec/aggregate._collapse_scan_chain — gated on the same fusion conf), so
  the stage node wraps it for stage accounting and delegates execution.

Program cache: engine/jit_cache.py keyed by the stage's composite expression
fingerprint (+ expand variant); capacity bucketing rides jax.jit's
shape-keyed retrace as everywhere else in the engine.
"""

from __future__ import annotations

from typing import Iterator, List, Optional, Sequence

from spark_rapids_tpu import _jax_setup  # noqa: F401
import jax
import jax.numpy as jnp

from spark_rapids_tpu import conf as C
from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.columnar.dtypes import DataType
from spark_rapids_tpu.exec import basic as B
from spark_rapids_tpu.exec.base import (
    ExecContext,
    PartitionedBatches,
    PhysicalExec,
    TpuExec,
    count_output,
)
from spark_rapids_tpu.ops.base import Expression
from spark_rapids_tpu.ops.bind import bind_all, bind_references
from spark_rapids_tpu.ops.eval import (
    _col_to_colv,
    _colv_to_col,
    _scalar_to_colv,
    _widen_physical,
    keep_mask_from_result,
    raise_deferred_ansi,
)
from spark_rapids_tpu.ops.values import ColV, EvalContext, ScalarV
from spark_rapids_tpu.utils import metrics as M


def is_fusable_scan_node(node: PhysicalExec) -> bool:
    """Stage-member predicate shared with the fusion pass: pipelined device
    operators whose semantics survive mask-deferred evaluation."""
    from spark_rapids_tpu.exec.expand import TpuExpandExec

    return isinstance(node, (B.TpuFilterExec, B.TpuProjectExec,
                             TpuExpandExec, B.TpuLocalLimitExec))


def exprs_fusable(exprs: Sequence[Expression]) -> bool:
    """Expressions a fused stage may defer behind a live-row mask:
    deterministic (a filtered-then-projected nondeterministic stream must
    not see dropped rows — rand/monotonic ids consume positions), no
    deferred-ANSI ops (an ANSI error on a row a preceding filter dropped
    must not surface), no input-file context expressions."""
    def bad(x) -> bool:
        return (getattr(x, "ansi", False)
                or getattr(x, "disable_coalesce_until_input", False))

    for e in exprs:
        if not e.deterministic or e.collect(bad):
            return False
    return True


class _StageOp:
    """One fused operator: kind + expressions bound to the running schema."""

    __slots__ = ("kind", "bound", "limit")

    def __init__(self, kind: str, bound=None, limit: Optional[int] = None):
        self.kind = kind       # 'filter' | 'project' | 'expand' | 'limit'
        self.bound = bound     # filter: Expression; project: [Expression];
        #                        expand: [[Expression]] (one list per variant)
        self.limit = limit

    def fingerprint(self) -> tuple:
        if self.kind == "filter":
            return ("filter", self.bound.fingerprint())
        if self.kind == "project":
            return ("project", tuple(e.fingerprint() for e in self.bound))
        if self.kind == "expand":
            return ("expand", tuple(tuple(e.fingerprint() for e in p)
                                    for p in self.bound))
        return ("limit",)


class TpuFusedStageExec(TpuExec):
    """Executes `n_ops` chained operators (rooted at children[0]) as one
    composed XLA program per batch (aggregate form: delegates to the
    aggregate's own fused update kernel)."""

    def __init__(self, stage_id: int, top: PhysicalExec, n_ops: int):
        super().__init__(top)
        self.stage_id = stage_id
        self.n_ops = n_ops
        # walk the member chain top-down; the node below the chain is the
        # stage input
        self.members: List[PhysicalExec] = []
        node = top
        for _ in range(n_ops):
            self.members.append(node)
            node = node.children[0]
        self.input_node = node
        from spark_rapids_tpu.exec.aggregate import TpuHashAggregateExec

        self.agg_form = isinstance(top, TpuHashAggregateExec)
        if not self.agg_form:
            self._build_scan_ops()

    # -- structure -----------------------------------------------------------
    @property
    def output(self):
        return self.children[0].output

    def with_children(self, new_children):
        return TpuFusedStageExec(self.stage_id, new_children[0], self.n_ops)

    def node_name(self):
        inner = "->".join(type(m).__name__.replace("Tpu", "").replace(
            "Exec", "") for m in reversed(self.members))
        return f"TpuFusedStage({self.stage_id})[{inner}]"

    # -- scan-form program ----------------------------------------------------
    def _build_scan_ops(self) -> None:
        """Bottom-up: rebind each member's expressions against the running
        schema so the composed trace consumes the previous op's outputs."""
        from spark_rapids_tpu.exec.expand import TpuExpandExec

        ops: List[_StageOp] = []
        attrs = list(self.input_node.output)
        n_variants = 1
        for node in reversed(self.members):
            if isinstance(node, B.TpuFilterExec):
                ops.append(_StageOp(
                    "filter", bind_references(node.condition, attrs)))
            elif isinstance(node, B.TpuProjectExec):
                ops.append(_StageOp(
                    "project", bind_all(node.project_list, attrs)))
                attrs = node.output
            elif isinstance(node, TpuExpandExec):
                ops.append(_StageOp(
                    "expand", [bind_all(p, attrs) for p in node.projections]))
                attrs = list(node.output_attrs)
                n_variants = len(node.projections)
            elif isinstance(node, B.TpuLocalLimitExec):
                ops.append(_StageOp("limit", limit=node.limit))
            else:  # pragma: no cover - the fusion pass only builds the above
                raise AssertionError(f"unfusable {type(node).__name__}")
        self._ops = ops
        self._n_variants = n_variants
        self._limit = next((op.limit for op in ops if op.kind == "limit"),
                           None)
        # does the (single) limit sit below the (single) expand? then all
        # expand variants of one input batch share the SAME remaining budget
        kinds = [op.kind for op in ops]
        self._limit_below_expand = (
            "limit" in kinds and "expand" in kinds
            and kinds.index("limit") < kinds.index("expand"))
        self._row_changing = any(k in ("filter", "limit") for k in kinds)
        # every row-changing op below the expand => all expand variants of
        # one input batch share the SAME live mask, so the stage computes
        # one survivors' count per batch instead of one per variant
        self._live_shared = "expand" not in kinds or all(
            k not in ("filter", "limit")
            for k in kinds[kinds.index("expand") + 1:])
        self._programs = {}
        # encoded-input stage plans keyed by (ordinal, dictionary) sig
        self._enc_cache: dict = {}

    # -- encoded-input planning (columnar/encoded.py) -------------------------
    def _ord_stays_encoded(self, o: int) -> Optional[str]:
        """Can input ordinal `o` flow through the whole member chain as
        CODES? Its running positions must only be passed through bare by
        projects or consumed by code-space-supported predicates. Returns
        None (no — decode at the boundary), 'code' (yes), or 'rank' (yes,
        but an ORDER comparison consumes it — the column re-encodes
        through the sorted dictionary first and literals rewrite to rank
        thresholds)."""
        from spark_rapids_tpu.columnar import encoded as ENC
        from spark_rapids_tpu.ops.base import Alias, BoundReference

        pos = {o}
        need_rank = False
        for op in self._ops:
            if op.kind == "filter":
                ok, rank = ENC.classify_bound_refs([op.bound], pos)
                if ok != pos:
                    return None
                need_rank = need_rank or bool(rank)
            elif op.kind == "project":
                newpos = set()
                others = []
                for i, e in enumerate(op.bound):
                    inner = e.child if isinstance(e, Alias) else e
                    if isinstance(inner, BoundReference) and \
                            inner.ordinal in pos:
                        newpos.add(i)
                        continue
                    others.append(e)
                ok, rank = ENC.classify_bound_refs(others, pos)
                if ok != pos:
                    return None
                need_rank = need_rank or bool(rank)
                pos = newpos
                if not pos:
                    # column dropped: nothing left to misuse
                    return "rank" if need_rank else "code"
            elif op.kind == "expand":
                # expand variants would need per-variant encoded schemas;
                # decode at the stage boundary instead
                return None
        return "rank" if need_rank else "code"

    def _enc_ops_for(self, batch: ColumnarBatch):
        """(rewritten ops, enc_sig, code ordinals, rank ordinals,
        materialize ordinals, output position -> dictionary) for a batch
        with encoded columns, cached per (ordinal, dictionary)
        signature."""
        from spark_rapids_tpu.columnar import encoded as ENC
        from spark_rapids_tpu.columnar.dtypes import DataType as DT
        from spark_rapids_tpu.ops.base import Alias, BoundReference

        enc = {i: c for i, c in enumerate(batch.columns)
               if ENC.is_encoded(c)}
        sig = tuple(sorted((i, c.dictionary.did) for i, c in enc.items()))
        cached = self._enc_cache.get(sig)
        if cached is not None:
            return cached
        kind_by_ord = {o: self._ord_stays_encoded(o) for o in enc}
        kept = {o for o, k in kind_by_ord.items() if k is not None}
        rank_ords = frozenset(o for o, k in kind_by_ord.items()
                              if k == "rank")
        mat = tuple(sorted(set(enc) - kept))

        def eff_dict(o):
            d = enc[o].dictionary
            return d.sorted_dict() if o in rank_ords else d

        pos2ord = {o: o for o in kept}
        ops2: List[_StageOp] = []
        for op in self._ops:
            dicts = {p: eff_dict(pos2ord[p]) for p in pos2ord}
            if op.kind == "filter":
                ops2.append(_StageOp("filter", ENC.rewrite_bound_condition(
                    op.bound, dicts) if dicts else op.bound))
            elif op.kind == "project":
                newmap = {}
                exprs2 = []
                for i, e in enumerate(op.bound):
                    inner = e.child if isinstance(e, Alias) else e
                    if isinstance(inner, BoundReference) and \
                            inner.ordinal in pos2ord:
                        ref2 = BoundReference(inner.ordinal, DT.INT32,
                                              inner.nullable)
                        exprs2.append(
                            Alias(ref2, e.name, e.expr_id)
                            if isinstance(e, Alias) else ref2)
                        newmap[i] = pos2ord[inner.ordinal]
                        continue
                    exprs2.append(ENC.rewrite_bound_condition(e, dicts)
                                  if dicts else e)
                ops2.append(_StageOp("project", exprs2))
                pos2ord = newmap
            else:
                ops2.append(op)
        out_enc = {p: eff_dict(o) for p, o in pos2ord.items()}
        plan = (ops2, sig, frozenset(kept), rank_ords, mat, out_enc)
        self._enc_cache[sig] = plan
        while len(self._enc_cache) > 64:
            self._enc_cache.pop(next(iter(self._enc_cache)))
        return plan

    def _program(self, variant: int, donated: bool = False, ops=None,
                 enc_sig: tuple = ()):
        from spark_rapids_tpu.engine.jit_cache import get_or_build

        cached = self._programs.get((variant, donated, enc_sig))
        if cached is not None:
            return cached
        ops = self._ops if ops is None else ops
        key = ("fused_stage", tuple(op.fingerprint() for op in ops), variant)

        def build(donate_argnums=()):
            msgs: List[str] = []

            def fn(cols: List[ColV], num_rows, partition_id, row_start,
                   remaining):
                capacity = cols[0].validity.shape[0] if cols else 8
                live = jnp.arange(capacity) < num_rows
                limit_passed = jnp.int32(0)
                ansi = []
                cur = cols
                for op in ops:
                    if op.kind == "limit":
                        n_live = jnp.sum(live.astype(jnp.int32))
                        limit_passed = jnp.minimum(n_live, remaining)
                        live = live & (jnp.cumsum(live.astype(jnp.int32))
                                       <= remaining)
                        continue
                    ctx = EvalContext(jnp, True, cur, num_rows, capacity,
                                      partition_id=partition_id,
                                      row_start=row_start)
                    if op.kind == "filter":
                        live = live & keep_mask_from_result(
                            op.bound.eval(ctx), capacity)
                    else:  # project / expand
                        exprs = op.bound if op.kind == "project" \
                            else op.bound[variant]
                        outs = []
                        for e in exprs:
                            r = e.eval(ctx)
                            if isinstance(r, ScalarV):
                                r = _scalar_to_colv(ctx, r, e.data_type)
                            outs.append(r)
                        cur = outs
                    ansi.extend(ctx.ansi_errors)
                del msgs[:]
                msgs.extend(m for _, m in ansi)
                return ([_widen_physical(c) for c in cur], live,
                        limit_passed, [f for f, _ in ansi])

            # donate_argnums=(0,) donates the input batch's columns into
            # the stage program when donation is armed (the cache key
            # carries the effective donation, so donated/undonated
            # variants coexist; docs/async-execution.md)
            return jax.jit(fn, donate_argnums=donate_argnums), msgs

        built = get_or_build(key, build,
                             donate_argnums=(0,) if donated else ())
        self._programs[(variant, donated, enc_sig)] = built
        while len(self._programs) > 128:
            self._programs.pop(next(iter(self._programs)))
        return built

    # -- execution ------------------------------------------------------------
    def execute(self, ctx: ExecContext) -> PartitionedBatches:
        if self.agg_form:
            # the aggregate's update kernel IS the stage program (it folds
            # the projections/filter masks below it into its own trace)
            agg_pb = self.children[0].execute(ctx)
            return PartitionedBatches(
                agg_pb.num_partitions,
                lambda p: count_output(self.metrics, agg_pb.iterator(p)),
                bucket_costs=agg_pb.bucket_costs)
        child_pb = self.input_node.execute(ctx)
        total_time = self.metrics[M.TOTAL_TIME]
        # stage-exit compaction sync policy: same shape as the standalone
        # filter's (exec/basic.TpuFilterExec); a limit in the stage always
        # syncs — its cross-batch budget needs the host count anyway
        lazy = False
        if self._row_changing and self._limit is None:
            policy = ctx.conf.get(C.FILTER_COMPACT_SYNC)
            if policy == "never":
                lazy = True
            elif policy == "auto":
                from spark_rapids_tpu.exec.aggregate import (
                    LAZY_FENCE_THRESHOLD_MS,
                )
                from spark_rapids_tpu.utils.devprobe import fence_cost_ms

                lazy = fence_cost_ms() >= LAZY_FENCE_THRESHOLD_MS

        # per-batch CPU replay (runtime graceful degradation) is possible
        # exactly when the stage is one variant with no limit and every
        # member is a plain filter/project: the member chain re-executes on
        # the host oracle engine with identical semantics (fused exprs are
        # deterministic by eligibility, so immediate compaction on the CPU
        # path cannot diverge from the fused deferred-mask evaluation)
        cpu_replayable = (
            self._n_variants == 1 and self._limit is None and
            all(isinstance(m, (B.TpuFilterExec, B.TpuProjectExec))
                for m in self.members))

        def factory(pidx: int) -> Iterator[ColumnarBatch]:
            from spark_rapids_tpu.columnar.batch import (
                _compact_plan,
                bucket_capacity,
                compact_rows,
                compact_span,
            )
            from spark_rapids_tpu.engine.retry import (
                device_op_with_fallback,
                with_retry,
            )
            from spark_rapids_tpu.ops.eval import cpu_filter, cpu_project

            def prep(b: ColumnarBatch):
                """(batch, eval cols, rewritten ops or None, enc sig,
                output-position -> dictionary). Encoded inputs keep their
                codes through the composed program wherever the chain
                allows; anything else decodes at the stage boundary."""
                from spark_rapids_tpu.columnar import encoded as ENC

                ops2, sig, out_enc = None, (), {}
                if ENC.encoded_ordinals(b):
                    ops2, sig, code_ords, rank_ords, mat, out_enc = \
                        self._enc_ops_for(b)
                    # tpulint: eager-materialize -- stage-boundary
                    # decode for members that need values (non-
                    # code-space predicates, computed projections)
                    b = ENC.batch_with_materialized(b, mat)
                    b = ENC.batch_to_rank_space(b, rank_ords)
                    cols = ENC.eval_cols(b, code_ords)
                else:
                    cols = [_col_to_colv(c) for c in b.columns]
                if not cols:
                    cap = bucket_capacity(max(b.host_rows(), 1))
                    # tpulint: eager-jnp, untracked-alloc -- zero-column
                    # COUNT(*) placeholder: one tiny bool lane
                    cols = [ColV(DataType.BOOL,
                                 jnp.zeros((cap,), dtype=bool),
                                 jnp.arange(cap) < b.num_rows)]
                return b, cols, ops2, sig, out_enc

            def wrap_out(outs, rows, owned, out_enc):
                from spark_rapids_tpu.columnar.encoded import (
                    DictionaryColumn,
                )

                cols = []
                for i, o in enumerate(outs):
                    c = _colv_to_col(o)
                    d = out_enc.get(i)
                    if d is not None:
                        c = DictionaryColumn(d.value_dtype, c.data,
                                             c.validity, d)
                    cols.append(c)
                return ColumnarBatch(cols, rows, owned=owned)

            def dispatch_variant(variant, cols, n, pidx, row_start,
                                 remaining, donated=False, ops=None,
                                 enc_sig=()):
                jitted, msgs = self._program(variant, donated, ops=ops,
                                             enc_sig=enc_sig)

                def _attempt():
                    M.record_dispatch()
                    outs, live, limit_passed, flags = jitted(
                        cols, n, jnp.int32(pidx), jnp.int64(row_start),
                        jnp.int32(remaining or 0))
                    raise_deferred_ansi(flags, msgs)
                    return outs, live, limit_passed

                return with_retry(_attempt, site="fused", donated=donated)

            def compact_count(live, n):
                def _attempt():
                    M.record_dispatch()
                    return _compact_plan(live, n)

                return with_retry(_attempt, site="fused")

            def compact(out: ColumnarBatch, live, n, n_keep=None):
                """The stage exit's compaction of one output batch: the
                survivors' count (synced here on the eager path unless
                `n_keep` hands over a sibling variant's; never on the
                lazy one) and the move, in one `filter.compact` span.
                -> (dense batch, count or None)."""
                with compact_span(out.num_rows, int(live.shape[0]),
                                  out.num_columns, lazy) as sp:
                    if not lazy and n_keep is None:
                        # tpulint: host-sync -- policy-gated stage-exit
                        n_keep = int(jax.device_get(compact_count(live, n)))
                    return with_retry(
                        lambda: compact_rows(out, live, n, n_keep, sp),
                        site="fused"), n_keep

            def run_simple(b: ColumnarBatch, off: int) -> ColumnarBatch:
                """One-variant no-limit batch: the split-and-retry /
                CPU-fallback unit."""
                from spark_rapids_tpu.engine import async_exec as AX
                from spark_rapids_tpu.memory.device_manager import (
                    TpuDeviceManager,
                )

                b2, cols, ops2, enc_sig, out_enc = prep(b)
                n = jnp.asarray(b2.num_rows, dtype=jnp.int32)
                # the stage consumes its input exactly once, so an OWNED
                # input batch donates its buffers into the stage program
                # (docs/async-execution.md); failures then escalate to the
                # checked replay instead of re-dispatching in place
                donated = AX.donation_active() and b2.owned
                if donated:
                    TpuDeviceManager.get().note_donation(
                        b2.device_memory_size())
                outs, live, _lp = dispatch_variant(
                    0, cols, n, pidx, row_start + off, None,
                    donated=donated, ops=ops2, enc_sig=enc_sig)

                def finish():
                    # ownership propagates: outputs are fresh kernel
                    # buffers (identity pass-throughs alias the consumed
                    # input, which only an owned input may hand on)
                    out = wrap_out(outs, b2.num_rows, b2.owned, out_enc)
                    if self._row_changing:
                        return compact(out, live, n)[0]
                    return out

                if not donated:
                    return finish()
                try:
                    return finish()
                except Exception as e:  # noqa: BLE001 - escalation gate
                    from spark_rapids_tpu.engine.retry import (
                        TpuAsyncSinkError,
                        as_typed_error,
                    )

                    typed = as_typed_error(e)
                    if typed is None or \
                            isinstance(typed, TpuAsyncSinkError):
                        raise
                    # the input batch was donated into the stage program:
                    # split-retry and the per-batch CPU replay would
                    # re-read consumed buffers — escalate to the checked
                    # replay (which runs with donation off)
                    raise TpuAsyncSinkError(
                        f"fused: failure after a donated dispatch "
                        f"({typed}); inputs were consumed — checked "
                        "replay required", origin_site="fused") from e

            def cpu_replay(hb, off: int):
                """Re-run the member chain bottom-up on the host oracle."""
                for m in reversed(self.members):
                    if isinstance(m, B.TpuFilterExec):
                        hb = cpu_filter(m._bound, hb, partition_id=pidx,
                                        row_start=row_start + off)
                    else:
                        hb = cpu_project(m._bound, hb, partition_id=pidx,
                                         row_start=row_start + off)
                return hb

            row_start = 0
            remaining = self._limit
            for batch in child_pb.iterator(pidx):
                if remaining is not None and remaining <= 0:
                    break
                if cpu_replayable:
                    with M.trace_range("TpuFusedStage", total_time):
                        outs = device_op_with_fallback(
                            run_simple, batch, cpu_replay, site="fused")
                    row_start += batch.num_rows
                    yield from outs
                    continue
                # variant/limit form: dispatches retry in place (spill +
                # transient backoff); exhaustion propagates for task-level
                # retry / query-level CPU fallback — mid-variant splits
                # would corrupt the cross-batch LIMIT budget
                batch, cols, ops2, enc_sig, out_enc = prep(batch)
                n = jnp.asarray(batch.num_rows, dtype=jnp.int32)
                n_keep = None
                for variant in range(self._n_variants):
                    if remaining is not None and remaining <= 0:
                        break
                    with M.trace_range("TpuFusedStage", total_time):
                        outs, live, limit_passed = dispatch_variant(
                            variant, cols, n, pidx, row_start, remaining,
                            ops=ops2, enc_sig=enc_sig)
                    out = wrap_out(outs, batch.num_rows, False, out_enc)
                    if self._row_changing:
                        out, n_keep = compact(
                            out, live, n,
                            n_keep if self._live_shared else None)
                    if remaining is not None and \
                            not self._limit_below_expand:
                        # tpulint: host-sync -- cross-batch LIMIT budget
                        remaining -= int(jax.device_get(limit_passed))
                    yield out
                if remaining is not None and self._limit_below_expand:
                    # tpulint: host-sync -- cross-batch LIMIT budget
                    remaining -= int(jax.device_get(limit_passed))
                row_start += batch.num_rows

        return PartitionedBatches(
            child_pb.num_partitions,
            lambda p: count_output(self.metrics, factory(p)))
