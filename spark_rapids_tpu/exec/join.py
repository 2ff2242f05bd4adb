"""Join execs (reference: GpuHashJoin.scala, GpuShuffledHashJoinExec.scala,
GpuBroadcastHashJoinExec.scala, GpuCartesianProductExec.scala).

Reference parity:
- shared join core: one built table, stream-side iteration with per-batch
  join + optional post-join condition filter (GpuHashJoin.scala:27-230) ->
  `_HashJoinBase` with a single build batch (RequireSingleBatch on the build
  child) streaming probe batches.
- shuffled hash join (both sides hash-exchanged, GpuShuffledHashJoinExec
  :86-120) and broadcast hash join (build side collected once and reused by
  every stream partition, GpuBroadcastHashJoinExec) -> the two exec
  subclasses; sort-merge joins are *replaced* by shuffled hash join exactly
  like the reference (GpuSortMergeJoinMeta, conf
  rapids.tpu.sql.replaceSortMergeJoin.enabled).
- cartesian/cross product (GpuCartesianProductExec.scala:59-257) ->
  `TpuNestedLoopJoinExec` (tile/repeat composition + condition filter).

TPU equi-join design (no hash table, XLA-native): dense-rank the BUILD and
STREAM key tuples TOGETHER via union grouping (exec/rowkeys.group_ids_masked)
so equality becomes an int32 group-id match; sort build rows by group id once
per (stream-batch, build) pair inside the same jit; then each stream row's
matches are the contiguous range [start[gid], start[gid]+cnt[gid]) of the
sorted build order — an interval probe, expanded with a searchsorted-based
output-row -> (stream row, k-th match) map. Null keys never match (SQL
equi-join semantics); outer rows surface with count 0.
"""

from __future__ import annotations

from typing import Any, Callable, List, Optional, Tuple

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
    concat_batches,
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
from spark_rapids_tpu.exec.transitions import RequireSingleBatch
from spark_rapids_tpu.ops.base import AttributeReference, Expression
from spark_rapids_tpu.ops.bind import bind_all, bind_references
from spark_rapids_tpu.ops.eval import (
    DeviceFilter,
    _col_to_colv,
    cpu_filter,
    cpu_project,
)
from spark_rapids_tpu.ops.values import EvalContext, ScalarV
from spark_rapids_tpu.plan.logical import JoinType
from spark_rapids_tpu.utils import metrics as M


def _nullable(attrs: List[AttributeReference]) -> List[AttributeReference]:
    return [AttributeReference(a.name, a.data_type, True, a.expr_id)
            for a in attrs]


def join_output(join_type: JoinType, left: List[AttributeReference],
                right: List[AttributeReference]) -> List[AttributeReference]:
    if join_type in (JoinType.LEFT_SEMI, JoinType.LEFT_ANTI):
        return list(left)
    if join_type is JoinType.LEFT_OUTER:
        return list(left) + _nullable(right)
    if join_type is JoinType.RIGHT_OUTER:
        return _nullable(left) + list(right)
    if join_type is JoinType.FULL_OUTER:
        return _nullable(left) + _nullable(right)
    return list(left) + list(right)


class _JoinBase(PhysicalExec):
    """Equi-join base. Build side is the right child except RIGHT_OUTER
    (which builds left and streams right, preserving the stream side)."""

    def __init__(self, left_keys: List[Expression],
                 right_keys: List[Expression], join_type: JoinType,
                 condition: Optional[Expression],
                 left: PhysicalExec, right: PhysicalExec):
        super().__init__(left, right)
        self.left_keys = list(left_keys)
        self.right_keys = list(right_keys)
        self.join_type = join_type
        self.condition = condition
        # set by runtime_broadcast_probe when an INNER join swaps its build
        # side because the planned one exceeded the broadcast threshold
        self._runtime_build_left: Optional[bool] = None

    @property
    def build_left(self) -> bool:
        if self._runtime_build_left is not None:
            return self._runtime_build_left
        return self.join_type is JoinType.RIGHT_OUTER

    @property
    def output(self) -> List[AttributeReference]:
        return join_output(self.join_type, self.children[0].output,
                           self.children[1].output)

    def with_children(self, new_children):
        return type(self)(self.left_keys, self.right_keys, self.join_type,
                          self.condition, *new_children)

    def node_name(self):
        return (f"{type(self).__name__}({self.join_type.value}, "
                f"keys={len(self.left_keys)})")

    # stream semantics: OUTER = preserve unmatched stream rows
    @property
    def _stream_mode(self) -> str:
        jt = self.join_type
        if jt is JoinType.INNER:
            return "inner"
        if jt in (JoinType.LEFT_OUTER, JoinType.RIGHT_OUTER,
                  JoinType.FULL_OUTER):
            return "outer"
        if jt is JoinType.LEFT_SEMI:
            return "semi"
        return "anti"


# ===========================================================================
# TPU equi-join kernel
# ===========================================================================
def _cat_promote(a, b):
    if a.dtype == b.dtype:
        return jnp.concatenate([a, b])
    dt = jnp.promote_types(a.dtype, b.dtype)
    return jnp.concatenate([a.astype(dt), b.astype(dt)])


def union_key_proxies(s_proxies, b_proxies):
    """Union the per-side key proxies so equality becomes one dense-rank
    grouping problem: stream rows at [0, s_cap), build rows at
    [s_cap, cap). Traced helper, shared between the per-batch joiner
    kernel below and the single-program SPMD stage (engine/spmd_exec.py
    lowers joins with exactly this core). Returns (union proxies,
    any-null flags per side — null keys never match)."""
    s_cap = s_proxies[0].null_flag.shape[0]
    b_cap = b_proxies[0].null_flag.shape[0]
    proxies = []
    any_null_s = jnp.zeros((s_cap,), bool)
    any_null_b = jnp.zeros((b_cap,), bool)
    for sp, bp in zip(s_proxies, b_proxies):
        arrays = tuple(_cat_promote(a, b)
                       for a, b in zip(sp.arrays, bp.arrays))
        null_flag = jnp.concatenate([sp.null_flag, bp.null_flag])
        proxies.append(RK.KeyProxy(arrays, null_flag, sp.orderable))
        any_null_s = any_null_s | sp.null_flag
        any_null_b = any_null_b | bp.null_flag
    return proxies, any_null_s, any_null_b


def traced_join_plan(proxies, any_null_s, any_null_b, s_live, b_live,
                     mode: str):
    """The interval-probe join plan over unioned key proxies (see the
    module docstring): dense-rank both sides together, sort build rows by
    group id, and express each stream row's matches as a contiguous range
    of the sorted build order. Runs inside a jit (the per-batch joiner's
    kernel or an SPMD stage program). Returns (offsets, total, b_order,
    b_start, s_safe_gid, match_cnt, b_matched)."""
    s_cap = any_null_s.shape[0]
    b_cap = any_null_b.shape[0]
    cap = s_cap + b_cap
    s_grp = s_live & ~any_null_s
    b_grp = b_live & ~any_null_b
    valid = jnp.concatenate([s_grp, b_grp])
    gi = RK.group_ids_masked(proxies, valid, cap)
    s_gid = gi.gid[:s_cap]
    b_gid = gi.gid[s_cap:]

    # sort build rows by gid; per-gid contiguous ranges
    b_order = jnp.argsort(jnp.where(b_grp, b_gid, cap),
                          stable=True).astype(jnp.int32)
    b_cnt = jax.ops.segment_sum(
        jnp.ones((b_cap,), jnp.int32),
        jnp.where(b_grp, b_gid, cap), num_segments=cap)
    b_start = jnp.concatenate([
        jnp.zeros((1,), jnp.int32),
        jnp.cumsum(b_cnt, dtype=jnp.int32)[:-1]])

    s_safe_gid = jnp.where(s_grp, s_gid, cap - 1)
    match_cnt = jnp.where(s_grp, b_cnt[s_safe_gid], 0)
    if mode == "inner":
        out_cnt = jnp.where(s_live, match_cnt, 0)
    elif mode == "outer":
        out_cnt = jnp.where(s_live, jnp.maximum(match_cnt, 1), 0)
    elif mode == "semi":
        out_cnt = jnp.where(s_live & (match_cnt > 0), 1, 0)
    else:  # anti
        out_cnt = jnp.where(s_live & (match_cnt == 0), 1, 0)

    offsets = jnp.concatenate([
        jnp.zeros((1,), jnp.int32),
        jnp.cumsum(out_cnt, dtype=jnp.int32)])
    total = offsets[-1]
    # build-side matched flags (for full-outer tail emission)
    s_cnt_per_gid = jax.ops.segment_sum(
        jnp.ones((s_cap,), jnp.int32),
        jnp.where(s_grp, s_gid, cap), num_segments=cap)
    b_matched = b_grp & \
        (s_cnt_per_gid[jnp.where(b_grp, b_gid, cap - 1)] > 0)
    return (offsets, total, b_order, b_start, s_safe_gid, match_cnt,
            b_matched)


class _DeviceJoiner:
    """Per-(stream schema, build schema) jitted equi-join planner."""

    def __init__(self, stream_keys, build_keys, stream_attrs, build_attrs,
                 mode: str):
        self.bound_stream = bind_all(stream_keys, stream_attrs)
        self.bound_build = bind_all(build_keys, build_attrs)
        self.mode = mode
        self._jitted = None

    def _build(self):
        from spark_rapids_tpu.engine.jit_cache import get_or_build

        cache_key = ("join", self.mode,
                     tuple(e.fingerprint() for e in self.bound_stream),
                     tuple(e.fingerprint() for e in self.bound_build))
        return get_or_build(cache_key, self._build_uncached)

    def _build_uncached(self):
        bound_stream, bound_build = self.bound_stream, self.bound_build
        mode = self.mode
        from spark_rapids_tpu.ops.eval import _scalar_to_colv

        def kernel(s_cols, s_rows, b_cols, b_rows):
            s_cap = s_cols[0].validity.shape[0]
            b_cap = b_cols[0].validity.shape[0]
            s_ctx = EvalContext(jnp, True, s_cols, s_rows, s_cap)
            b_ctx = EvalContext(jnp, True, b_cols, b_rows, b_cap)

            def keys_of(ctx, bound):
                out = []
                for e in bound:
                    r = e.eval(ctx)
                    if isinstance(r, ScalarV):
                        r = _scalar_to_colv(ctx, r, e.data_type)
                    out.append(r)
                return out

            s_keys = keys_of(s_ctx, bound_stream)
            b_keys = keys_of(b_ctx, bound_build)

            # union proxies: stream rows at [0,s_cap), build at [s_cap,cap)
            proxies, any_null_s, any_null_b = union_key_proxies(
                [RK.key_proxy(sk) for sk in s_keys],
                [RK.key_proxy(bk) for bk in b_keys])
            s_live = (jnp.arange(s_cap) < s_rows)
            b_live = (jnp.arange(b_cap) < b_rows)
            # null keys never match: traced_join_plan excludes them from
            # the union grouping entirely
            return traced_join_plan(proxies, any_null_s, any_null_b,
                                    s_live, b_live, mode)

        return jax.jit(kernel)

    def plan(self, stream: ColumnarBatch, build: ColumnarBatch,
             s_cols=None, b_cols=None):
        if self._jitted is None:
            self._jitted = self._build()
        if s_cols is None:
            s_cols = [_col_to_colv(c) for c in stream.columns]
        if b_cols is None:
            b_cols = [_col_to_colv(c) for c in build.columns]
        s_cols = s_cols or [_synth(stream)]
        b_cols = b_cols or [_synth(build)]

        def cnt(b):
            n = b.num_rows
            if isinstance(n, (int, np.integer)):
                return np.int32(n)  # host count: no eager device convert
            return jnp.asarray(n, dtype=jnp.int32)

        return self._jitted(s_cols, cnt(stream), b_cols, cnt(build))


def _synth(batch: ColumnarBatch):
    from spark_rapids_tpu.ops.values import ColV

    cap = bucket_capacity(max(batch.num_rows, 1))
    # tpulint: eager-jnp, untracked-alloc -- zero-column COUNT(*)
    # placeholder col: one tiny bool lane, not batch data
    return ColV(DataType.BOOL, jnp.zeros((cap,), bool),
                jnp.arange(cap) < batch.num_rows)


class _TpuJoinMixin:
    """Shared device join driver for shuffled + broadcast variants."""

    def _join_stream(self, stream_iter, build: ColumnarBatch,
                     emit_build_tail: bool):
        st = self  # typing: _JoinBase subclass
        build_left = st.build_left
        stream_child = 1 if build_left else 0
        build_child = 0 if build_left else 1
        stream_attrs = st.children[stream_child].output
        build_attrs = st.children[build_child].output
        stream_keys = st.right_keys if build_left else st.left_keys
        build_keys = st.left_keys if build_left else st.right_keys
        mode = st._stream_mode
        joiner = _DeviceJoiner(stream_keys, build_keys, stream_attrs,
                               build_attrs, mode)
        # encoded-key joining (columnar/encoded.py): key positions where
        # BOTH sides reference an encoded column join on CODES — the
        # stream side's codes rewrite into the build dictionary's space
        # through a build-time remap table (values absent from the build
        # side map to -1, which can never match). Mixed/unsupported uses
        # decode at this boundary; emit gathers from the ORIGINAL batches
        # so pass-through encoded columns stay encoded in the output.
        from spark_rapids_tpu.columnar import encoded as ENC
        from spark_rapids_tpu.ops.base import (
            AttributeReference as _Attr,
        )

        def _bare_ord(e, attrs):
            if isinstance(e, _Attr):
                for i, a in enumerate(attrs):
                    if a.expr_id == e.expr_id:
                        return i
            return None

        def _ref_ords(exprs, attrs):
            eids = {r.expr_id for e in exprs
                    for r in e.collect(lambda x: isinstance(x, _Attr))}
            return {i for i, a in enumerate(attrs) if a.expr_id in eids}

        _cands = [(kp, _bare_ord(sk, stream_attrs),
                   _bare_ord(bk, build_attrs))
                  for kp, (sk, bk) in enumerate(zip(stream_keys,
                                                    build_keys))]
        _s_key_refs = _ref_ords(stream_keys, stream_attrs)
        _b_key_refs = _ref_ords(build_keys, build_attrs)
        # ordinals referenced inside a NON-bare key expression need the
        # VALUES there — a column used both as a bare key and inside a
        # computed key must materialize, not code-join
        _s_nonbare = _ref_ords(
            [sk for sk in stream_keys
             if _bare_ord(sk, stream_attrs) is None], stream_attrs)
        _b_nonbare = _ref_ords(
            [bk for bk in build_keys
             if _bare_ord(bk, build_attrs) is None], build_attrs)
        _b_enc = set(ENC.encoded_ordinals(build))
        _enc_joiners: dict = {}
        _build_forms: dict = {}

        def _retyped(attrs, ords):
            out = list(attrs)
            for i in ords:
                a = attrs[i]
                out[i] = AttributeReference(a.name, DataType.INT32,
                                            a.nullable, a.expr_id)
            return out

        def _retype_keys(keys, attrs2, attrs):
            out = []
            for e in keys:
                o = _bare_ord(e, attrs)
                out.append(attrs2[o] if o is not None else e)
            return out

        def _prep_pair(stream_batch):
            """(joiner, stream batch, s_cols, b_cols) with encoded keys in
            code space and unsupported encoded key uses decoded."""
            s_enc = set(ENC.encoded_ordinals(stream_batch))
            if not s_enc and not _b_enc:
                return joiner, stream_batch, None, None
            subs = [(kp, so, bo) for kp, so, bo in _cands
                    if so is not None and bo is not None
                    and so in s_enc and bo in _b_enc
                    and so not in _s_nonbare and bo not in _b_nonbare]
            # one stream ordinal joined against build columns with
            # DIFFERENT dictionaries cannot share one remap: those
            # positions fall back to value comparison
            by_so: dict = {}
            for _kp, so, bo in subs:
                by_so.setdefault(so, set()).add(
                    build.columns[bo].dictionary.did)
            subs = [t for t in subs if len(by_so[t[1]]) == 1]
            sub_s = {so: bo for _kp, so, bo in subs}
            sub_b = {bo for _kp, _so, bo in subs}
            s_mat = tuple(sorted((_s_key_refs & s_enc) - set(sub_s)))
            b_mat = frozenset((_b_key_refs & _b_enc) - sub_b)
            # tpulint: eager-materialize -- a key encoded on ONE side
            # only (or used non-bare) must compare as values
            stream_batch = ENC.batch_with_materialized(stream_batch, s_mat)
            form = _build_forms.get(b_mat)
            if form is None:
                # tpulint: eager-materialize -- build-side key encoded
                # on one side only: compare as values (cached per form)
                beval = ENC.batch_with_materialized(build, b_mat)
                b_cols = []
                for i, c in enumerate(beval.columns):
                    b_cols.append(ENC.codes_colv(c) if ENC.is_encoded(c)
                                  else _col_to_colv(c))
                form = _build_forms[b_mat] = b_cols
            b_cols = form
            s_cols = []
            for i, c in enumerate(stream_batch.columns):
                if ENC.is_encoded(c):
                    if i in sub_s:
                        bd = build.columns[sub_s[i]].dictionary
                        remap = ENC.join_remap(c.dictionary, bd)
                        s_cols.append(ENC.remapped_codes_colv(c, remap))
                    else:
                        s_cols.append(ENC.codes_colv(c))
                else:
                    s_cols.append(_col_to_colv(c))
            jkey = tuple(sorted(kp for kp, _s, _b in subs))
            jv = _enc_joiners.get(jkey)
            if jv is None:
                sa2 = _retyped(stream_attrs, {so for _k, so, _b in subs})
                ba2 = _retyped(build_attrs, {bo for _k, _s, bo in subs})
                jv = _DeviceJoiner(
                    _retype_keys(stream_keys, sa2, stream_attrs),
                    _retype_keys(build_keys, ba2, build_attrs),
                    sa2, ba2, mode)
                _enc_joiners[jkey] = jv
            return jv, stream_batch, s_cols, b_cols

        emit_build_cols = mode in ("inner", "outer")
        cond_filter = None
        if st.condition is not None:
            bound_cond = bind_references(st.condition,
                                         st._joined_attrs())
            cond_filter = DeviceFilter(bound_cond)

        b_matched_acc = None

        def emit(stream_batch, plan_out):
            nonlocal b_matched_acc
            (offsets, total, b_order, b_start, s_safe_gid, match_cnt,
             _b_matched) = plan_out
            # tpulint: host-sync -- join output size determines the gather
            # bucket; one count sync per (stream batch, build) pair
            n_out = int(jax.device_get(total))
            if n_out == 0:
                return None
            out_cap = bucket_capacity(n_out)
            s_idx, b_idx, live = _expand_full(offsets, b_order, b_start,
                                              s_safe_gid, match_cnt, out_cap)
            s_out = gather_batch(stream_batch, s_idx, n_out)
            if emit_build_cols:
                # negative (unmatched) indices already emit null rows in
                # gather_batch's in-bounds mask — no eager pre-masking
                b_out = gather_batch(build, b_idx, n_out)
                cols = (b_out.columns + s_out.columns) if build_left \
                    else (s_out.columns + b_out.columns)
                joined = ColumnarBatch(cols, n_out)
            else:
                joined = s_out
            if cond_filter is not None:
                joined = cond_filter.apply(joined)
            return joined

        # depth-1 software pipeline: batch i's output-count fence (one
        # host round trip) overlaps batch i+1's
        # plan dispatch — the count's host copy is requested as soon as
        # the plan kernel is enqueued
        from spark_rapids_tpu.engine.retry import with_retry

        pending = None
        for stream_batch in stream_iter:
            if stream_batch.host_rows() == 0:
                continue
            jv, stream_batch, s_cols, b_cols = _prep_pair(stream_batch)
            # OOM/transient resilience: the plan and emit dispatches are
            # pure over (stream batch, build), so a spill+re-dispatch is
            # safe; exhaustion propagates for task retry / query-level
            # CPU fallback (the build table is device-resident state —
            # batch bisection cannot recover it)
            with M.trace_range("TpuHashJoin.plan",
                               self.metrics[M.TOTAL_TIME]):
                plan_out = with_retry(
                    lambda: jv.plan(stream_batch, build, s_cols, b_cols),
                    site="join")
            b_matched = plan_out[6]
            if b_matched_acc is None:
                b_matched_acc = b_matched
            else:
                b_matched_acc = b_matched_acc | b_matched
            try:
                plan_out[1].copy_to_host_async()
            except AttributeError:
                pass  # non-jax scalar (host count path)
            if pending is not None:
                with M.trace_range("TpuHashJoin.emit",
                                   self.metrics[M.TOTAL_TIME]):
                    joined = with_retry(lambda: emit(*pending),
                                        site="join")
                if joined is not None:
                    yield joined
            pending = (stream_batch, plan_out)
        if pending is not None:
            with M.trace_range("TpuHashJoin.emit",
                               self.metrics[M.TOTAL_TIME]):
                joined = with_retry(lambda: emit(*pending), site="join")
            if joined is not None:
                yield joined

        if emit_build_tail and build.num_rows > 0:
            # full outer: unmatched build rows with null stream columns
            if b_matched_acc is None:
                # tpulint: eager-jnp, untracked-alloc -- empty-stream full
                # outer: one bool mask at build capacity
                b_matched_acc = jnp.zeros((build.capacity,), bool)
            # tpulint: host-sync -- once per partition at stream end: the
            # unmatched-build tail of a full outer join needs host rows
            unmatched = (~np.asarray(jax.device_get(b_matched_acc))) & \
                (np.arange(build.capacity) < build.num_rows)
            rows = np.nonzero(unmatched)[0]
            if len(rows) == 0:
                return
            n_out = len(rows)
            idx_cap = bucket_capacity(n_out)
            idx = np.zeros(idx_cap, dtype=np.int32)
            idx[:n_out] = rows
            b_out = gather_batch(build, jnp.asarray(idx), n_out)
            # full outer always builds right / streams left: output is
            # null left columns ++ the unmatched build rows
            cols = (_null_batch(self.children[0].output, n_out).columns +
                    b_out.columns)
            yield ColumnarBatch(cols, n_out)

    def _joined_attrs(self) -> List[AttributeReference]:
        return self.children[0].output + self.children[1].output


import functools


@functools.partial(jax.jit, static_argnums=(5,))
def _expand_full(offsets, b_order, b_start, s_safe_gid, match_cnt,
                 out_cap: int):
    pos = jnp.arange(out_cap, dtype=jnp.int32)
    s_row = jnp.searchsorted(offsets[1:], pos, side="right").astype(jnp.int32)
    s_cap = s_safe_gid.shape[0]
    s_row = jnp.clip(s_row, 0, s_cap - 1)
    k = pos - offsets[s_row]
    has_match = match_cnt[s_row] > 0
    b_pos = b_start[s_safe_gid[s_row]] + k
    b_cap = b_order.shape[0]
    b_row = jnp.where(has_match, b_order[jnp.clip(b_pos, 0, b_cap - 1)],
                      jnp.int32(-1))
    live = pos < offsets[-1]
    return jnp.where(live, s_row, 0), jnp.where(live, b_row, -1), live


def _null_batch(attrs: List[AttributeReference], n_rows: int) -> ColumnarBatch:
    from spark_rapids_tpu.columnar.batch import physical_np_dtype

    cap = bucket_capacity(max(n_rows, 1))
    cols = []
    for a in attrs:
        # tpulint: eager-jnp, untracked-alloc -- all-null column
        # build, outer-join tail only (once per partition)
        validity = jnp.zeros((cap,), bool)
        if a.data_type is DataType.STRING:
            # tpulint: eager-jnp, untracked-alloc -- all-null string
            # column, same tail
            cols.append(ColumnVector(
                a.data_type, jnp.zeros((8,), jnp.uint8), validity,
                jnp.zeros((cap + 1,), jnp.int32)))
        else:
            npdt = physical_np_dtype(a.data_type)
            # tpulint: eager-jnp, untracked-alloc -- all-null column
            # build, same tail
            cols.append(ColumnVector(a.data_type, jnp.zeros((cap,), npdt),
                                     validity))
    return ColumnarBatch(cols, n_rows)


def _unwrap_to_exchange(node):
    """Descend through batch-coalesce wrappers to the planned shuffle
    exchange feeding a join input; None when the shape is anything else."""
    from spark_rapids_tpu.exec.transitions import (
        CpuCoalesceBatchesExec,
        TpuCoalesceBatchesExec,
    )
    from spark_rapids_tpu.shuffle.exchange import _ExchangeBase

    cur = node
    while isinstance(cur, (TpuCoalesceBatchesExec, CpuCoalesceBatchesExec)):
        cur = cur.children[0]
    return cur if isinstance(cur, _ExchangeBase) else None


def runtime_broadcast_probe(node, ctx):
    """AQE-style runtime join re-planning (the role Spark AQE's join
    strategy switch plays for the reference plugin — its adaptive suite
    TpchLikeAdaptiveSparkSuite exercises shuffled->broadcast demotion the
    same way). The planner statically broadcasts only when the logical
    plan bounds the build size; a build side behind an aggregate, another
    join, or a file scan estimates unknown and would always pay two
    shuffles. Here the join materializes the build input BEFORE its
    exchange; when the actual bytes fit under autoBroadcastJoinThreshold
    both exchanges are skipped and the join streams the other input
    as-is. Safe because every downstream distribution requirement has its
    own explicitly planned exchange (this planner never elides one based
    on advertised output partitioning).

    Returns None to proceed with the planned shuffle (any materialized
    build input is handed back to its exchange via set_pre_executed), or
    (build_batches, stream_pb) for the broadcast path."""
    if node.join_type is JoinType.FULL_OUTER:
        return None
    if not ctx.conf.get(C.RUNTIME_BROADCAST):
        return None
    from spark_rapids_tpu.shuffle.exchange import _piece_bytes

    bidx = 0 if node.build_left else 1
    bex = _unwrap_to_exchange(node.children[bidx])
    sex = _unwrap_to_exchange(node.children[1 - bidx])
    if bex is None or sex is None:
        return None

    def _materialize(pb):
        def collect(pidx: int):
            return list(pb.iterator(pidx))

        from spark_rapids_tpu.engine.scheduler import run_job_or_serial

        parts = run_job_or_serial(ctx.scheduler, pb.num_partitions, collect)
        batches = [b for part in parts for b in part
                   if (b.host_rows() if hasattr(b, "host_rows")
                       else b.num_rows) > 0]
        return parts, batches, sum(_piece_bytes(b) for b in batches)

    threshold = ctx.conf.get(C.BROADCAST_THRESHOLD)
    bpb = bex.children[0].execute(ctx)
    parts, batches, total = _materialize(bpb)
    if total <= threshold:
        node.metrics["runtimeBroadcastJoins"].add(1)
        stream_pb = sex.children[0].execute(ctx)
        return batches, stream_pb
    if node.join_type is JoinType.INNER:
        # the planned build side is too big, but an INNER join can build
        # on either side (the preserved/filtering-side role constraints of
        # outer/semi/anti joins don't apply): probe the other input before
        # falling back to the two planned shuffles. Spark AQE reaches the
        # same plan via statistics; here the actual materialized bytes
        # decide (both inputs sit above their exchanges, so both must be
        # materialized anyway for the shuffle fallback).
        spb = sex.children[0].execute(ctx)
        sparts, sbatches, stotal = _materialize(spb)
        if stotal <= threshold:
            node.metrics["runtimeBroadcastJoins"].add(1)
            node._runtime_build_left = (1 - bidx) == 0
            return sbatches, PartitionedBatches(
                bpb.num_partitions, lambda p: iter(parts[p]))
        sex.set_pre_executed(PartitionedBatches(
            spb.num_partitions, lambda p: iter(sparts[p])))
    # too big: replay the already-materialized input through the
    # planned exchange (it must not re-execute the child)
    bex.set_pre_executed(PartitionedBatches(
        bpb.num_partitions, lambda p: iter(parts[p])))
    return None


def coalesce_join_inputs(ctx, left_pb, right_pb):
    """Coordinated AQE partition coalescing for a shuffled join: group BOTH
    inputs with the SAME contiguous bucket grouping, chosen from their
    combined per-bucket costs (the exchanges below publish bucket_costs and
    stay unfused; Spark AQE's coordinated CoalesceShufflePartitions)."""
    from spark_rapids_tpu import conf as C

    if (left_pb.bucket_costs is None or right_pb.bucket_costs is None
            or left_pb.num_partitions != right_pb.num_partitions
            or left_pb.num_partitions <= 1
            or not ctx.conf.get(C.ADAPTIVE_COALESCE)):
        return left_pb, right_pb
    from spark_rapids_tpu.aqe.coalesce import coordinated_groups

    groups = coordinated_groups(left_pb.bucket_costs,
                                right_pb.bucket_costs,
                                ctx.conf.get(C.ADAPTIVE_TARGET_BYTES))
    if len(groups) == left_pb.num_partitions:
        return left_pb, right_pb
    # groups are sized under the advisory target, so concatenating each
    # group's device batches is memory-safe and turns a grouped partition
    # into ONE joiner dispatch instead of one per original bucket
    return (left_pb.grouped(groups, concat_device=True),
            right_pb.grouped(groups, concat_device=True))


class TpuShuffledHashJoinExec(_JoinBase, _TpuJoinMixin, TpuExec):
    placement = "tpu"

    @property
    def children_coalesce_goal(self):
        if self.build_left:
            return [RequireSingleBatch(), None]
        return [None, RequireSingleBatch()]

    def execute(self, ctx: ExecContext) -> PartitionedBatches:
        rb = runtime_broadcast_probe(self, ctx)
        if rb is not None:
            build_batches, stream_pb = rb
            if build_batches:
                bc = build_batches[0] if len(build_batches) == 1 else \
                    concat_batches(build_batches)
            else:
                bc = _null_batch(
                    self.children[0 if self.build_left else 1].output, 0)

            def bfactory(pidx: int):
                it = self._join_stream(stream_pb.iterator(pidx), bc, False)
                return count_output(self.metrics, it)

            return PartitionedBatches(stream_pb.num_partitions, bfactory)
        left_pb = self.children[0].execute(ctx)
        right_pb = self.children[1].execute(ctx)
        left_pb, right_pb = coalesce_join_inputs(ctx, left_pb, right_pb)
        build_pb = left_pb if self.build_left else right_pb
        stream_pb = right_pb if self.build_left else left_pb
        emit_tail = self.join_type is JoinType.FULL_OUTER

        def factory(pidx: int):
            builds = [b for b in build_pb.iterator(pidx)
                      if b.host_rows() > 0]
            if builds:
                build = builds[0] if len(builds) == 1 else \
                    concat_batches(builds)
            else:
                build = _null_batch(
                    self.children[0 if self.build_left else 1].output, 0)
            it = self._join_stream(stream_pb.iterator(pidx), build, emit_tail)
            return count_output(self.metrics, it)

        return PartitionedBatches(stream_pb.num_partitions, factory)


class TpuBroadcastHashJoinExec(_JoinBase, _TpuJoinMixin, TpuExec):
    """Build side materialized ONCE (all partitions concatenated) and reused
    by every stream partition (reference: GpuBroadcastHashJoinExec +
    GpuBroadcastExchangeExec collect/broadcast)."""

    placement = "tpu"

    def execute(self, ctx: ExecContext) -> PartitionedBatches:
        if self.join_type is JoinType.FULL_OUTER:
            # the unmatched-build tail would be emitted once per stream
            # partition; the planner never broadcasts full outer joins
            raise NotImplementedError(
                "full outer join cannot use the broadcast path")
        build_child = 0 if self.build_left else 1
        stream_child = 1 - build_child
        build_pb = self.children[build_child].execute(ctx)
        stream_pb = self.children[stream_child].execute(ctx)

        def collect_build(pidx: int):
            return [b for b in build_pb.iterator(pidx) if b.host_rows() > 0]

        from spark_rapids_tpu.engine.scheduler import run_job_or_serial

        parts = run_job_or_serial(ctx.scheduler, build_pb.num_partitions,
                                  collect_build)
        batches = [b for part in parts for b in part]
        if batches:
            build = batches[0] if len(batches) == 1 else \
                concat_batches(batches)
        else:
            build = _null_batch(self.children[build_child].output, 0)
        if ctx.conf.get(C.SHUFFLE_SERIALIZE):
            # materialize the broadcast relation through the serialized
            # batch format — the host-serialized broadcast of
            # GpuBroadcastExchangeExec.scala:47-200 (TorrentBroadcast
            # payload); proves the build side survives a bytes round trip
            # and registers it with the host spill store
            from spark_rapids_tpu.shuffle.exchange import _encode_piece

            build = _encode_piece(build).decode(to_device=True)
        emit_tail = self.join_type is JoinType.FULL_OUTER

        def factory(pidx: int):
            it = self._join_stream(stream_pb.iterator(pidx), build, emit_tail)
            return count_output(self.metrics, it)

        return PartitionedBatches(stream_pb.num_partitions, factory)


class TpuNestedLoopJoinExec(_JoinBase, TpuExec):
    """Cross/cartesian product with optional condition (reference:
    GpuCartesianProductExec / GpuBroadcastNestedLoopJoinExec). The right
    side is materialized once; per stream batch the product expands via a
    repeat/tile index composition."""

    placement = "tpu"

    def execute(self, ctx: ExecContext) -> PartitionedBatches:
        left_pb = self.children[0].execute(ctx)
        right_pb = self.children[1].execute(ctx)

        def collect_right(pidx: int):
            return [b for b in right_pb.iterator(pidx)
                    if b.host_rows() > 0]

        from spark_rapids_tpu.engine.scheduler import run_job_or_serial

        parts = run_job_or_serial(ctx.scheduler, right_pb.num_partitions,
                                  collect_right)
        batches = [b for part in parts for b in part]
        build = concat_batches(batches) if batches else \
            _null_batch(self.children[1].output, 0)
        cond_filter = None
        if self.condition is not None:
            cond_filter = DeviceFilter(
                bind_references(self.condition, self._joined_attrs()))

        def factory(pidx: int):
            def gen():
                for sb in left_pb.iterator(pidx):
                    if sb.host_rows() == 0 or build.host_rows() == 0:
                        continue
                    n_out = sb.num_rows * build.num_rows
                    cap = bucket_capacity(n_out)
                    # tpulint: eager-jnp -- cross-product index build; the
                    # two fused gathers below dominate this tiny iota
                    pos = jnp.arange(cap, dtype=jnp.int32)
                    s_idx = pos // build.num_rows
                    b_idx = pos % build.num_rows
                    s_out = gather_batch(sb, s_idx, n_out)
                    b_out = gather_batch(build, b_idx, n_out)
                    joined = ColumnarBatch(s_out.columns + b_out.columns,
                                           n_out)
                    if cond_filter is not None:
                        joined = cond_filter.apply(joined)
                    yield joined

            return count_output(self.metrics, gen())

        return PartitionedBatches(left_pb.num_partitions, factory)

    def _joined_attrs(self):
        return self.children[0].output + self.children[1].output


# ===========================================================================
# CPU oracle joins
# ===========================================================================
def _host_key(dtype: DataType, v, valid: bool):
    if not valid:
        return None  # sentinel; null keys never match
    if dtype in (DataType.FLOAT32, DataType.FLOAT64):
        f = float(v)
        if f != f:
            return ("NaN",)
        return 0.0 if f == 0.0 else f
    if dtype is DataType.STRING:
        return str(v)
    if dtype is DataType.BOOL:
        return bool(v)
    return int(v)


class CpuShuffledHashJoinExec(_JoinBase, CpuExec):
    placement = "cpu"

    broadcast = False

    def execute(self, ctx: ExecContext) -> PartitionedBatches:
        if self.broadcast and self.join_type is JoinType.FULL_OUTER:
            raise NotImplementedError(
                "full outer join cannot use the broadcast path")
        if not self.broadcast:
            rb = runtime_broadcast_probe(self, ctx)
            if rb is not None:
                build_batches, stream_pb = rb

                def bfactory(pidx: int):
                    return count_output(
                        self.metrics,
                        self._join_partition(pidx, stream_pb.iterator(pidx),
                                             build_batches))

                return PartitionedBatches(stream_pb.num_partitions, bfactory)
        left_pb = self.children[0].execute(ctx)
        right_pb = self.children[1].execute(ctx)
        if not self.broadcast:
            left_pb, right_pb = coalesce_join_inputs(ctx, left_pb, right_pb)
        build_left = self.build_left
        build_pb = left_pb if build_left else right_pb
        stream_pb = right_pb if build_left else left_pb

        if self.broadcast:
            def collect(pidx: int):
                return list(build_pb.iterator(pidx))

            from spark_rapids_tpu.engine.scheduler import run_job_or_serial

            parts = run_job_or_serial(ctx.scheduler, build_pb.num_partitions, collect)
            all_build = [b for part in parts for b in part if b.num_rows > 0]

        def factory(pidx: int):
            if self.broadcast:
                builds = all_build
            else:
                builds = [b for b in build_pb.iterator(pidx)
                          if b.num_rows > 0]
            return count_output(
                self.metrics,
                self._join_partition(pidx, stream_pb.iterator(pidx), builds))

        return PartitionedBatches(stream_pb.num_partitions, factory)

    def _join_partition(self, pidx, stream_iter, builds):
        build_left = self.build_left
        stream_child = 1 if build_left else 0
        build_child = 0 if build_left else 1
        stream_attrs = self.children[stream_child].output
        build_attrs = self.children[build_child].output
        stream_keys = self.right_keys if build_left else self.left_keys
        build_keys = self.left_keys if build_left else self.right_keys
        mode = self._stream_mode
        emit_build = mode in ("inner", "outer")
        full_outer = self.join_type is JoinType.FULL_OUTER

        build_batch = _concat_host(builds, build_attrs)
        bkeys = cpu_project(bind_all(build_keys, build_attrs), build_batch,
                            partition_id=pidx)
        table: dict = {}
        for i in range(build_batch.num_rows):
            key = tuple(
                _host_key(build_keys[c].data_type, bkeys.columns[c].data[i],
                          bool(bkeys.columns[c].validity[i]))
                for c in range(len(build_keys)))
            if any(k is None for k in key):
                continue
            table.setdefault(key, []).append(i)
        b_matched = np.zeros(build_batch.num_rows, dtype=bool)

        bound_skeys = bind_all(stream_keys, stream_attrs)
        for sb in stream_iter:
            if sb.num_rows == 0:
                continue
            skeys = cpu_project(bound_skeys, sb, partition_id=pidx)
            s_idx: List[int] = []
            b_idx: List[int] = []
            for i in range(sb.num_rows):
                key = tuple(
                    _host_key(stream_keys[c].data_type,
                              skeys.columns[c].data[i],
                              bool(skeys.columns[c].validity[i]))
                    for c in range(len(stream_keys)))
                matches = [] if any(k is None for k in key) else \
                    table.get(key, [])
                if matches:
                    for m in matches:
                        b_matched[m] = True
                    if mode == "semi":
                        s_idx.append(i)
                        b_idx.append(-1)
                    elif mode == "anti":
                        pass
                    else:
                        for m in matches:
                            s_idx.append(i)
                            b_idx.append(m)
                else:
                    if mode == "outer" or mode == "anti":
                        s_idx.append(i)
                        b_idx.append(-1)
            if not s_idx:
                continue
            out = self._emit_host(sb, build_batch, s_idx, b_idx, emit_build,
                                  build_left, stream_attrs, build_attrs)
            if self.condition is not None and mode == "inner":
                out = cpu_filter(
                    bind_references(self.condition,
                                    self.children[0].output +
                                    self.children[1].output), out)
            yield out

        if full_outer:
            rows = [i for i in range(build_batch.num_rows) if not b_matched[i]]
            if rows:
                out = self._emit_host(None, build_batch,
                                      [-1] * len(rows), rows, True,
                                      build_left, stream_attrs, build_attrs)
                yield out

    def _emit_host(self, sb, build_batch, s_idx, b_idx, emit_build,
                   build_left, stream_attrs, build_attrs):
        s_cols = _host_gather(sb, stream_attrs, s_idx)
        if not emit_build:
            return HostColumnarBatch(s_cols, len(s_idx))
        b_cols = _host_gather(build_batch, build_attrs, b_idx)
        cols = (b_cols + s_cols) if build_left else (s_cols + b_cols)
        return HostColumnarBatch(cols, len(s_idx))


class CpuBroadcastHashJoinExec(CpuShuffledHashJoinExec):
    broadcast = True


class CpuNestedLoopJoinExec(_JoinBase, CpuExec):
    placement = "cpu"

    def execute(self, ctx: ExecContext) -> PartitionedBatches:
        left_pb = self.children[0].execute(ctx)
        right_pb = self.children[1].execute(ctx)

        def collect(pidx: int):
            return list(right_pb.iterator(pidx))

        from spark_rapids_tpu.engine.scheduler import run_job_or_serial

        parts = run_job_or_serial(ctx.scheduler, right_pb.num_partitions, collect)
        batches = [b for part in parts for b in part if b.num_rows > 0]
        build = _concat_host(batches, self.children[1].output)

        def factory(pidx: int):
            def gen():
                for sb in left_pb.iterator(pidx):
                    if sb.num_rows == 0 or build.num_rows == 0:
                        continue
                    s_idx = [i for i in range(sb.num_rows)
                             for _ in range(build.num_rows)]
                    b_idx = list(range(build.num_rows)) * sb.num_rows
                    cols = _host_gather(sb, self.children[0].output, s_idx) + \
                        _host_gather(build, self.children[1].output, b_idx)
                    out = HostColumnarBatch(cols, len(s_idx))
                    if self.condition is not None:
                        out = cpu_filter(
                            bind_references(
                                self.condition,
                                self.children[0].output +
                                self.children[1].output), out)
                    yield out

            return count_output(self.metrics, gen())

        return PartitionedBatches(left_pb.num_partitions, factory)


def _concat_host(batches: List[HostColumnarBatch],
                 attrs: List[AttributeReference]) -> HostColumnarBatch:
    if not batches:
        cols = [
            HostColumnVector(
                a.data_type,
                np.zeros(0, dtype=a.data_type.to_np()),
                np.zeros(0, dtype=bool))
            for a in attrs
        ]
        return HostColumnarBatch(cols, 0)
    if len(batches) == 1:
        return batches[0]
    cols = []
    for c in range(batches[0].num_columns):
        data = np.concatenate([b.columns[c].data for b in batches])
        validity = np.concatenate([b.columns[c].validity for b in batches])
        cols.append(HostColumnVector(batches[0].columns[c].dtype, data,
                                     validity))
    return HostColumnarBatch(cols, sum(b.num_rows for b in batches))


def _host_gather(batch: Optional[HostColumnarBatch],
                 attrs: List[AttributeReference],
                 idx: List[int]) -> List[HostColumnVector]:
    n = len(idx)
    out = []
    for c, a in enumerate(attrs):
        npdt = a.data_type.to_np()
        data = np.zeros(n, dtype=npdt)
        validity = np.zeros(n, dtype=bool)
        if a.data_type is DataType.STRING:
            data[:] = ""
        if batch is not None:
            src = batch.columns[c]
            for j, i in enumerate(idx):
                if i >= 0:
                    data[j] = src.data[i]
                    validity[j] = src.validity[i]
        return_col = HostColumnVector(a.data_type, data, validity)
        out.append(return_col)
    return out
