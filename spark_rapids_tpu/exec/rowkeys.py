"""Device row-key kernels: sort permutations and group-id assignment.

These are the TPU-native replacements for the cudf primitives the reference
leans on everywhere (`Table.orderBy` for GpuSortExec.scala:100-235,
`Table.groupBy` for aggregate.scala:728, `Table.onColumns(keys).innerJoin`
for GpuHashJoin.scala:27-230). On TPU the idiomatic composition is:

- sort: iterated stable `argsort` passes (least-significant key first), which
  XLA lowers to its sort HLO — no hand-written comparator needed;
- groupby: sort rows by key, mark segment boundaries by neighbor inequality,
  dense group ids via prefix-sum, then `jax.ops.segment_*` reductions;
- join: dense-rank both sides' keys TOGETHER (union grouping), then the join
  becomes an int32-key searchsorted interval probe (exec/join.py).

Key *proxies*: every key column is reduced to one or more numeric arrays on
which equality (and, for orderable types, order) agrees with SQL semantics:

- integral/bool/date/timestamp: the data itself (nulls zeroed by convention,
  null flag carried separately);
- floats: total-order uint32 bit trick (-0.0 == 0.0, all NaNs equal, NaN
  sorts greater than all numbers, matching Spark's NaN ordering);
- strings, for grouping/joining: double 32-bit polynomial hash + byte
  length — EQUALITY-ONLY proxies (exact up to a ~2^-60 collision
  probability);
- strings, for ORDERING: `string_order_proxy` — chunked big-endian uint64
  byte keys + length tie-break, exact whenever the static chunk count
  covers the batch's longest string (callers size it via
  `string_chunks_needed`).

Every function here is a kernel HELPER invoked inside jit traces built by
the exec drivers (aggregate/sort/join/mesh kernels):
# tpulint: traced-helpers

All functions here take padded device arrays + a traced `num_rows` and are
jit-safe. Padded rows always sort to the end and get group id = capacity
(dropped by segment reductions with num_segments=capacity).
"""

from __future__ import annotations

from typing import Any, List, NamedTuple, Sequence, Tuple

import numpy as np

from spark_rapids_tpu import _jax_setup  # noqa: F401
import jax
import jax.numpy as jnp

from spark_rapids_tpu.columnar.dtypes import DataType
from spark_rapids_tpu.ops import hashing as H
from spark_rapids_tpu.ops.values import ColV


class KeyProxy(NamedTuple):
    """Numeric stand-ins for one key column."""

    arrays: Tuple[Any, ...]   # uint32/int arrays; order-significant first
    null_flag: Any            # bool array, True where SQL NULL
    orderable: bool           # arrays reflect sort order, not just equality


def _float_order_bits(data) -> Any:
    """Map a float array to unsigned bits preserving total order: -NaN <
    -inf < ... < -0.0 == 0.0 < ... < inf < NaN, with all NaNs canonicalized
    (Spark sorts NaN greater than any value). float64 inputs (the CPU-backed
    oracle-parity environment stores DOUBLE as real f64) use the 64-bit
    transform — narrowing them to f32 would merge distinct keys."""
    if jnp.dtype(data.dtype) == jnp.dtype(jnp.float64):
        f = jnp.where(data == 0.0, jnp.zeros((), jnp.float64), data)
        f = jnp.where(jnp.isnan(f), jnp.full((), jnp.nan, jnp.float64), f)
        bits = f.view(jnp.uint64)
        sign = (bits >> jnp.uint64(63)).astype(bool)
        return jnp.where(sign, ~bits, bits | jnp.uint64(1 << 63))
    f32 = data.astype(jnp.float32)
    f32 = jnp.where(f32 == 0.0, jnp.zeros((), jnp.float32), f32)
    f32 = jnp.where(jnp.isnan(f32), jnp.full((), jnp.nan, jnp.float32), f32)
    bits = f32.view(jnp.uint32)
    sign = (bits >> jnp.uint32(31)).astype(bool)
    flipped = jnp.where(sign, ~bits, bits | jnp.uint32(0x80000000))
    return flipped.astype(jnp.uint32)


def key_proxy(col: ColV) -> KeyProxy:
    """Null lanes are canonicalized to zero so all SQL NULLs compare equal
    regardless of whatever data the producing kernel left behind."""
    dt = col.dtype
    if dt in (DataType.FLOAT32, DataType.FLOAT64):
        bits = _float_order_bits(col.data)
        bits = jnp.where(col.validity, bits, jnp.uint32(0))
        return KeyProxy((bits,), ~col.validity, True)
    if dt is DataType.STRING:
        h1, h2, ln = H._string_words_device(col)
        return KeyProxy((h1, h2, ln), ~col.validity, False)
    if dt is DataType.BOOL:
        data = jnp.where(col.validity, col.data, False).astype(jnp.int32)
        return KeyProxy((data,), ~col.validity, True)
    # integral / date / timestamp. A logically-int64 column whose vrange
    # fits int32 sorts/groups on an int32 proxy (value-preserving, so order
    # and equality are unchanged) — argsort over emulated-int64 pairs is the
    # hottest lane in sort-based groupby on TPU (BENCH_I64_r04.json).
    from spark_rapids_tpu.ops.values import narrow_colv

    col = narrow_colv(col)
    data = jnp.where(col.validity, col.data, jnp.zeros((), col.data.dtype))
    return KeyProxy((data,), ~col.validity, True)


def string_order_proxy(col: ColV, n_chunks: int) -> KeyProxy:
    """ORDERABLE string proxy: big-endian byte-chunk keys plus a length
    tie-break (shorter sorts first when one string is a prefix of the
    other, matching UTF-8 byte order == code point order). EXACT whenever
    the chunks cover the batch's longest string — callers compute that
    bound outside jit and pass it as a static arg (the cudf device string
    comparator this replaces: reference GpuSortExec via Table.orderBy,
    GpuSortExec.scala:100-235).

    Columns with a host-known max_len <= 8 use uint32 chunks instead of
    uint64 ones: sort comparators over emulated 64-bit pairs are the
    hottest TPU lane, and short keys (flags, status codes) don't need
    them."""
    lens = col.offsets[1:] - col.offsets[:-1]
    ml = col.max_len
    if ml is not None and ml <= 8:
        from spark_rapids_tpu.columnar import strings as STR

        starts = col.offsets[:-1]
        widths = [4] if ml <= 4 else [4, 4]
        arrays = []
        off = 0
        for _w in widths:
            c = STR._chunk_u32(col.data, starts + off,
                               jnp.maximum(lens - off, 0))
            arrays.append(jnp.where(col.validity, c, jnp.uint32(0)))
            off += 4
    else:
        arrays = [jnp.where(col.validity, c, jnp.uint64(0))
                  for c in _string_chunk_keys(col, n_chunks)]
    arrays.append(jnp.where(col.validity, lens, 0))
    return KeyProxy(tuple(arrays), ~col.validity, True)


def _string_chunk_keys(col: ColV, n_chunks: int):
    """The shared big-endian uint64 byte-chunk extraction used by both the
    sort proxy and the aggregate arg-extreme reduction."""
    from spark_rapids_tpu.columnar import strings as STR

    starts = col.offsets[:-1]
    lens = col.offsets[1:] - col.offsets[:-1]
    for c in range(n_chunks):
        off = 8 * c
        yield STR._chunk_u64(col.data, starts + off,
                             jnp.maximum(lens - off, 0))


def string_chunks_needed(col_or_lens) -> int:
    """Bucketed chunk count for a batch's longest string (the static-shape
    discipline of SURVEY.md section 7 hard part #3). A column carrying a
    host-known max_len bound answers without a device round trip — and
    because both the bound and the chunk count are pow2-bucketed, the
    bucket is IDENTICAL to the synced exact answer (pow2(ceil(x/8)) ==
    pow2(x)/8 for x > 8), so kernels keyed on it never over-widen."""
    ml = getattr(col_or_lens, "max_len", None)
    if ml is not None:
        chunks = max(1, -(-int(ml) // 8))
        return 1 << (chunks - 1).bit_length()
    if hasattr(col_or_lens, "offsets"):
        lens = col_or_lens.offsets[1:] - col_or_lens.offsets[:-1]
    else:
        lens = col_or_lens
    # tpulint: host-sync -- one max-length probe per string sort column;
    # the pow2 bucket below bounds how often the answer can change
    max_len = int(jax.device_get(jnp.max(jnp.maximum(lens, 0))))
    chunks = max(1, -(-max_len // 8))
    return 1 << (chunks - 1).bit_length()  # pow2 bucket bounds recompiles


def segment_arg_extreme_string(col: ColV, validity, gid, capacity: int,
                               n_chunks: int, want_min: bool):
    """Per-group ROW INDEX of the lexicographically min/max string
    (null-skipping, SQL min/max semantics). Iterative refinement: keep the
    rows extreme on chunk 0, then among those chunk 1, ..., then the length
    tie-break — n_chunks+1 segment reductions total, all fused by XLA.
    Returns sel_pos int32 [capacity], clamped to == capacity when the group
    has no non-null row, for a string gather by the caller (the cudf groupby
    min/max-on-strings this replaces; reference AggregateFunctions.scala)."""
    mask = validity & (gid < capacity)
    lens = col.offsets[1:] - col.offsets[:-1]
    U64MAX = jnp.uint64(0xFFFFFFFFFFFFFFFF)

    def refine(mask, key, top, bot):
        seg = jnp.where(mask, gid, capacity)
        if want_min:
            best = jax.ops.segment_min(jnp.where(mask, key, top), seg,
                                       num_segments=capacity)
        else:
            best = jax.ops.segment_max(jnp.where(mask, key, bot), seg,
                                       num_segments=capacity)
        safe_g = jnp.clip(gid, 0, capacity - 1)
        return mask & (key == best[safe_g])

    for chunk in _string_chunk_keys(col, n_chunks):
        mask = refine(mask, chunk, U64MAX, jnp.uint64(0))
    mask = refine(mask, lens.astype(jnp.int32), jnp.int32(1 << 30),
                  jnp.int32(-1))
    pos = jnp.arange(capacity, dtype=jnp.int32)
    seg = jnp.where(mask, gid, capacity)
    sel = jax.ops.segment_min(jnp.where(mask, pos, capacity), seg,
                              num_segments=capacity)
    # empty segments get segment_min's int32-max identity; normalize to the
    # documented `capacity` sentinel
    return jnp.minimum(sel, capacity)


def _invert_order(arr):
    """Monotonically order-reversing transform (for descending keys):
    bitwise NOT reverses order for signed, unsigned, and bool alike."""
    return ~arr


def _multi_key_sort(operands, capacity: int):
    """ONE lax.sort HLO over all key operands (lexicographic, stable) with
    a row-index payload — instead of a chain of argsort passes. XLA fuses
    the comparator; on TPU this is several times faster than iterated
    argsorts of 64-bit keys."""
    payload = jnp.arange(capacity, dtype=jnp.int32)
    result = jax.lax.sort(tuple(operands) + (payload,),
                          is_stable=True, num_keys=len(operands))
    return result[-1]


def sort_permutation(proxies: Sequence[KeyProxy],
                     directions: Sequence[Tuple[bool, bool]],
                     num_rows, capacity: int):
    """Stable lexicographic sort permutation (int32 [capacity]).

    directions[i] = (ascending, nulls_first) for proxies[i]. Requires every
    proxy to be orderable. Padded rows land at the end.
    """
    pad = jnp.arange(capacity) >= num_rows
    operands = [pad]  # most significant: pads last
    for proxy, (ascending, nulls_first) in zip(proxies, directions):
        assert proxy.orderable, "sort on equality-only key proxy"
        nf = proxy.null_flag
        operands.append(~nf if nulls_first else nf)
        for arr in proxy.arrays:
            operands.append(arr if ascending else _invert_order(arr))
    return _multi_key_sort(operands, capacity)


def group_sort_permutation(proxies: Sequence[KeyProxy], num_rows,
                           capacity: int):
    """Permutation clustering equal keys together (any consistent order;
    equality-only proxies allowed). Nulls group together (SQL GROUP BY)."""
    return group_sort_permutation_masked(
        proxies, jnp.arange(capacity) < num_rows, capacity)


def group_sort_permutation_masked(proxies: Sequence[KeyProxy], valid_mask,
                                  capacity: int):
    """Like group_sort_permutation but with an arbitrary row-validity mask
    (used by the join's union grouping where live rows are interleaved)."""
    operands = [~valid_mask]  # pads last
    for proxy in proxies:
        operands.append(proxy.null_flag)
        operands.extend(proxy.arrays)
    return _multi_key_sort(operands, capacity)


def _neighbor_differs(proxies: Sequence[KeyProxy], order) -> Any:
    """sorted-position i>0: does row order[i] differ from row order[i-1] in
    any key (value or null flag)?"""
    cap = order.shape[0]
    prev = jnp.concatenate([order[:1], order[:-1]])
    diff = jnp.zeros((cap,), dtype=bool)
    for proxy in proxies:
        for arr in proxy.arrays:
            diff = diff | (arr[order] != arr[prev])
        diff = diff | (proxy.null_flag[order] != proxy.null_flag[prev])
    return diff.at[0].set(True)


class GroupInfo(NamedTuple):
    """Result of group_ids: everything a segment reduction needs.

    The sorted-order fields power the fast segment reductions (see
    `segment_reduce`): measured on the real chip, an exact cumulative-sum
    difference over group-sorted data runs int64 sums 2.5x faster than
    XLA's unsorted scatter-add (docs/tuning-guide.md "int64 on TPU").
    They are None when the caller assembled gids by hand (e.g. the
    keyless global-aggregate path), which keeps the scatter fallback.
    """

    gid: Any         # int32 [capacity]; group id per original row; pads -> capacity
    num_groups: Any  # traced int32 scalar
    rep_rows: Any    # int32 [capacity]; original row index of each group's
                     # first (in sorted order) member; slots >= num_groups = 0
    order: Any = None       # int32 [capacity]; group-sort permutation
                            # (stable: within a group, original row order)
    gid_sorted: Any = None  # int32 [capacity]; monotone group id per sorted
                            # position; pads -> capacity
    seg_ends: Any = None    # int32 [capacity]; sorted position of group g's
                            # LAST member; slots >= num_groups = 0


def group_ids(proxies: Sequence[KeyProxy], num_rows, capacity: int) -> GroupInfo:
    return group_ids_masked(proxies, jnp.arange(capacity) < num_rows, capacity)


def group_ids_masked(proxies: Sequence[KeyProxy], valid_mask,
                     capacity: int) -> GroupInfo:
    order = group_sort_permutation_masked(proxies, valid_mask, capacity)
    valid_sorted = valid_mask[order]
    boundary = _neighbor_differs(proxies, order) & valid_sorted
    # the first valid row always starts a group even if it equals a pad row
    first_valid = valid_sorted & (jnp.cumsum(valid_sorted.astype(jnp.int32)) == 1)
    boundary = boundary | first_valid
    gid_sorted = jnp.cumsum(boundary.astype(jnp.int32)) - 1
    gid_sorted = jnp.where(valid_sorted, gid_sorted, capacity)
    gid = jnp.zeros((capacity,), jnp.int32).at[order].set(gid_sorted)
    gid = jnp.where(valid_mask, gid, capacity)
    num_groups = jnp.sum(boundary.astype(jnp.int32))
    rep_rows = jnp.zeros((capacity,), jnp.int32).at[
        jnp.where(boundary, gid_sorted, capacity)
    ].set(order, mode="drop")
    pos = jnp.arange(capacity, dtype=jnp.int32)
    nxt = jnp.concatenate([gid_sorted[1:],
                           jnp.full((1,), capacity, jnp.int32)])
    is_end = (gid_sorted != nxt) & (gid_sorted < capacity)
    seg_ends = jnp.zeros((capacity,), jnp.int32).at[
        jnp.where(is_end, gid_sorted, capacity)
    ].set(pos, mode="drop")
    return GroupInfo(gid, num_groups, rep_rows, order, gid_sorted, seg_ends)


# ---------------------------------------------------------------------------
# Segment reductions (the cudf groupby-aggregate analog)
# ---------------------------------------------------------------------------
def _seg_ids(gid, validity, capacity: int):
    """Segment ids restricted to non-null input rows (SQL aggs skip nulls)."""
    return jnp.where(validity, gid, capacity)


def _cumsum_wrap(x):
    """Cumulative sum with modular-wrap semantics. 64-bit integer input on
    an accelerator rides two uint32 lanes with carry reconstruction (exact
    mod 2^64: lo-lane wrap at step i shows as clo[i] < clo[i-1], and the
    running wrap count is the hi-lane carry) instead of XLA's 32-bit-pair
    int64 emulation, whose log2(n) scan levels each pay the measured 9.18x
    emulation tax (BENCH_I64_r04.json). CPU XLA has native int64 — keep the plain
    cumsum there (the 2-lane form measured ~2.5x slower on CPU)."""
    dt = jnp.dtype(x.dtype)
    if dt.kind not in "iu" or dt.itemsize < 8 \
            or jax.default_backend() == "cpu":
        return jnp.cumsum(x)
    return _cumsum_wrap_lanes(x)


def _cumsum_wrap_lanes(x):
    u = x.astype(jnp.uint64)
    lo = (u & jnp.uint64(0xFFFFFFFF)).astype(jnp.uint32)
    hi = (u >> jnp.uint64(32)).astype(jnp.uint32)
    clo = jnp.cumsum(lo)
    prev = jnp.concatenate([jnp.zeros((1,), jnp.uint32), clo[:-1]])
    carries = jnp.cumsum((clo < prev).astype(jnp.uint32))
    chi = jnp.cumsum(hi) + carries
    out = (chi.astype(jnp.uint64) << jnp.uint64(32)) | clo.astype(jnp.uint64)
    return out.astype(x.dtype)


def _sorted_group_totals(per_row_sorted, gi: GroupInfo, capacity: int):
    """Per-group total of an already-sorted per-row array via ONE cumulative
    sum + boundary gathers — the TPU-fast replacement for an unsorted
    scatter-add (2.5x on emulated int64, measured on chip; tuning guide).
    Exact for integers: a difference of wrapped cumulative values equals the
    wrapped per-group sum in modular arithmetic, the same wrap the scatter
    path has. Requires dense groups (every gid < num_groups has >= 1 member
    row — group_ids guarantees this); slots >= num_groups return 0."""
    cs = _cumsum_wrap(per_row_sorted)
    ends = jnp.clip(gi.seg_ends, 0, capacity - 1)
    tot = cs[ends]
    prev = jnp.concatenate([jnp.zeros((1,), tot.dtype), tot[:-1]])
    slot_ok = jnp.arange(capacity, dtype=jnp.int32) < gi.num_groups
    return jnp.where(slot_ok, tot - prev, jnp.zeros((), tot.dtype))


def _sorted_counts(validity, gi: GroupInfo, capacity: int):
    """Per-group count of rows whose `validity` (original order) is True.
    i32 cumsum is exact: counts are bounded by capacity < 2^31."""
    vs = validity[gi.order] & (gi.gid_sorted < capacity)
    return _sorted_group_totals(vs.astype(jnp.int32), gi, capacity)


def _segment_starts(gi: GroupInfo):
    """Boundary flags in sorted order: True at each group's first member.
    Derived from the monotone gid_sorted — pads (gid == capacity) form one
    trailing pseudo-segment whose scan result is never gathered."""
    g = gi.gid_sorted
    return jnp.concatenate([jnp.ones((1,), bool), g[1:] != g[:-1]])


def _segmented_scan(per_row_sorted, starts, combine):
    """Inclusive segmented scan (Blelloch flag-carry form): within each run
    of rows sharing a group, accumulate with `combine`; reset at every
    `starts` flag. One associative_scan — log2(capacity) fused elementwise
    levels, NO scatter. This is the TPU answer to the scatter cliff round 4
    measured on a v5e (scatter segment reductions 0.63 GB/s vs 3+ GB/s for
    everything else at 16M rows): the per-group reduction
    becomes scan + boundary gather, same as the int-sum cumsum trick but
    valid for ANY associative op and numerically safe for float sums
    (accumulation restarts at each group, so no cross-group magnitude
    absorption the way a global-cumsum difference would)."""
    def comb(a, b):
        fa, va = a
        fb, vb = b
        return fa | fb, jnp.where(fb, vb, combine(va, vb))

    _, vals = jax.lax.associative_scan(comb, (starts, per_row_sorted))
    return vals


def _sorted_segment_reduce(per_row_sorted, gi: GroupInfo, capacity: int,
                           combine):
    """Per-group reduction of an already-group-sorted array via segmented
    scan + gather at each group's last sorted position. Input must already
    hold the op's identity in masked-out (null/pad) lanes. Slots >=
    num_groups return the scan value at position 0 (callers mask by their
    own per-group validity)."""
    scanned = _segmented_scan(per_row_sorted, _segment_starts(gi), combine)
    ends = jnp.clip(gi.seg_ends, 0, capacity - 1)
    return scanned[ends]


def reduce_all(op: str, data, vmask):
    """Traced: one group's reduction of `data` over the lanes of `vmask`
    with SQL null semantics, as scalars: (value, whether any lane
    counted). The keyless case of `segment_reduce`, and what the
    ungrouped update program (exec/aggregate.py) writes into its one
    row; `count` always counts (0 over no lane)."""
    if op == "count":
        return jnp.sum(vmask.astype(jnp.int64)), jnp.array(True)
    has = jnp.sum(vmask.astype(jnp.int32)) > 0
    if op == "sum":
        if jnp.dtype(data.dtype).kind in "iu" \
                and jnp.dtype(data.dtype).itemsize < 8:
            data = data.astype(jnp.int64)  # SQL sum over integrals is LONG
        r = jnp.sum(jnp.where(vmask, data, jnp.zeros((), data.dtype)))
    elif op == "any":
        r = jnp.any(vmask & data.astype(bool))
    elif jnp.dtype(data.dtype).kind == "f":
        # total-order bits: NaN sorts greater than every number
        bits = _float_order_bits(data)
        if op == "min":
            r = _float_from_order_bits(jnp.min(jnp.where(
                vmask, bits, jnp.array(jnp.iinfo(bits.dtype).max,
                                       bits.dtype)))
            ).astype(data.dtype)
        else:
            r = _float_from_order_bits(jnp.max(jnp.where(
                vmask, bits, jnp.array(0, bits.dtype)))
            ).astype(data.dtype)
    elif op == "min":
        r = jnp.min(jnp.where(vmask, data, _type_max(data.dtype)))
    elif op == "max":
        r = jnp.max(jnp.where(vmask, data, _type_min(data.dtype)))
    else:
        raise ValueError(f"no whole-batch reduction for op {op!r}")
    return r, has


def segment_reduce(op: str, data, validity, gid, num_rows, capacity: int):
    """Reduce `data` per group with SQL null semantics.

    `gid` may be a raw int32 per-row group-id array or a full `GroupInfo`;
    with a GroupInfo carrying sort-order fields, sum/count (integral) and
    first/last ride the group-sorted fast paths instead of unsorted
    scatters. Float sums stay on the scatter path on purpose: a cumulative
    difference would absorb other groups' magnitudes (catastrophic
    cancellation), while f32 scatter-adds are native-speed anyway.

    Returns (out_data [capacity], out_validity [capacity]) where slot g holds
    group g's result. All-null (or empty) groups -> null, except count -> 0.
    first/last follow encounter order in the ORIGINAL row order, matching the
    reference's First/Last aggregates (stable group sort keeps original
    order within each group).
    """
    gi = gid if isinstance(gid, GroupInfo) else None
    if gi is not None:
        gid = gi.gid
    sorted_ok = gi is not None and gi.order is not None
    # a GroupInfo without sort-order fields is the keyless global
    # aggregate (the only hand-assembled construction,
    # exec/aggregate.py:_group_info_masked): ONE group -> plain masked
    # tree reductions into slot 0, no scatter at all
    keyless = gi is not None and not sorted_ok
    pos = jnp.arange(capacity, dtype=jnp.int32)
    in_group = gid < capacity  # real (non-pad) rows
    slot0 = pos == 0

    def at_slot0(x, dtype=None):
        z = jnp.zeros((capacity,), dtype or x.dtype)
        return jnp.where(slot0, x.astype(z.dtype), z)

    if op == "count":
        if sorted_ok:
            cnt = _sorted_counts(validity & in_group, gi,
                                 capacity).astype(jnp.int64)
            return cnt, jnp.ones((capacity,), bool)
        if keyless:
            cnt, _ = reduce_all("count", data, validity & in_group)
            return at_slot0(cnt), jnp.ones((capacity,), bool)
        seg = _seg_ids(gid, validity & in_group, capacity)
        ones = jnp.ones((capacity,), jnp.int64)
        cnt = jax.ops.segment_sum(jnp.where(seg < capacity, ones, 0), seg,
                                  num_segments=capacity)
        return cnt, jnp.ones((capacity,), bool)
    if op.startswith("pct:"):
        # exact percentile by one fresh (gid, nulls-last, value) sort +
        # boundary gathers + linear interpolation — independent of the
        # group-sort order (values must be ASCENDING within each group).
        # Update expr pre-casts to DOUBLE, so data is always float here.
        p = float(op[4:])
        vmask = validity & in_group
        vkey = _float_order_bits(jnp.where(vmask, data,
                                           jnp.zeros((), data.dtype)))
        order2 = jax.lax.sort(
            (jnp.where(in_group, gid, capacity), ~vmask, vkey, pos),
            is_stable=True, num_keys=3)[-1]
        gid2 = jnp.where(in_group, gid, capacity)[order2]
        seg2 = jnp.where(vmask[order2], gid2, capacity)
        cnt = jax.ops.segment_sum((seg2 < capacity).astype(jnp.int32), seg2,
                                  num_segments=capacity)
        starts = jax.ops.segment_min(pos, seg2, num_segments=capacity)
        outv = cnt > 0
        starts = jnp.where(outv, starts, 0)
        # rank p*(cnt-1) split into exact int base + in-[0,1) fraction —
        # float row indices lose integer precision past the mantissa
        c1 = jnp.maximum(cnt - 1, 0)
        from spark_rapids_tpu.columnar.batch import device_float64_supported
        if device_float64_supported():
            q = p * c1.astype(jnp.float64)
            k = jnp.floor(q).astype(jnp.int32)
            frac = (q - jnp.floor(q)).astype(data.dtype)
        else:
            # no f64 lanes (TPU hardware): int64 fixed-point at 31
            # fractional bits. P*(c-1) <= 2^31 * 2^31 fits int64; rank
            # error <= c1 * 2^-32 (< 0.004 at 16M rows) — within this
            # backend's documented f32-ulp deviation policy, while a plain
            # f32 product would corrupt the INTEGER part past 2^24 rows
            P = int(round(p * (1 << 31)))
            prod = P * c1.astype(jnp.int64)
            k = (prod >> 31).astype(jnp.int32)
            frac = ((prod & ((1 << 31) - 1)).astype(data.dtype)
                    / data.dtype.type(1 << 31))
        lo = jnp.clip(starts + k, 0, capacity - 1)
        hi = jnp.clip(lo + (frac > 0), 0, capacity - 1)
        sv = data[order2]
        out = sv[lo] * (1 - frac) + sv[hi] * frac
        out = jnp.where(outv, out, jnp.zeros((), out.dtype))
        return out, outv
    if op == "unmergeable":
        raise AssertionError(
            "holistic aggregate reached a merge stage — the planner must "
            "run it complete-mode over a single batch")
    if op in ("sum", "min", "max", "any"):
        if op == "sum" and jnp.dtype(data.dtype).kind in "iu" \
                and jnp.dtype(data.dtype).itemsize < 8:
            # SQL sum over any integral type is LONG: an int32-narrowed (or
            # plain INT) input must accumulate 64-bit — per-group totals are
            # unbounded even when every element fits int32
            data = data.astype(jnp.int64)
        if sorted_ok:
            # scatter-free lane: every reduction is scan + boundary gather
            # over the group-sorted order (scatter segment reductions are the
            # one slow TPU kernel)
            nonnull = _sorted_counts(validity & in_group, gi, capacity)
            outv = nonnull > 0
            vmask = (validity & in_group)[gi.order]
            if op == "sum" and jnp.dtype(data.dtype).kind in "iu":
                # integer sums: a single global cumsum + difference is even
                # cheaper than the segmented scan (exact under modular wrap)
                vs = jnp.where(vmask, data[gi.order],
                               jnp.zeros((), data.dtype))
                out = _sorted_group_totals(vs, gi, capacity)
            elif op == "sum":
                vs = jnp.where(vmask, data[gi.order],
                               jnp.zeros((), data.dtype))
                out = _sorted_segment_reduce(vs, gi, capacity, jnp.add)
            elif op == "any":
                vs = vmask & data[gi.order].astype(bool)
                out = _sorted_segment_reduce(vs, gi, capacity,
                                             jnp.logical_or)
            else:  # min / max
                if jnp.dtype(data.dtype).kind == "f":
                    # scan on total-order bits so NaN sorts greater than
                    # every number (Spark: min skips NaN unless all-NaN)
                    bits = _float_order_bits(data)[gi.order]
                    if op == "min":
                        ident = jnp.array(jnp.iinfo(bits.dtype).max,
                                          bits.dtype)
                        comb = jnp.minimum
                    else:
                        ident = jnp.array(0, bits.dtype)
                        comb = jnp.maximum
                    vs = jnp.where(vmask, bits, ident)
                    r = _sorted_segment_reduce(vs, gi, capacity, comb)
                    out = _float_from_order_bits(r).astype(data.dtype)
                else:
                    ident = (_type_max(data.dtype) if op == "min"
                             else _type_min(data.dtype))
                    comb = jnp.minimum if op == "min" else jnp.maximum
                    vs = jnp.where(vmask, data[gi.order], ident)
                    out = _sorted_segment_reduce(vs, gi, capacity, comb)
            out = jnp.where(outv, out, jnp.zeros((), out.dtype))
            return out, outv
        if keyless:
            r, has = reduce_all(op, data, validity & in_group)
            outv = at_slot0(has, bool)
            out = jnp.where(outv, at_slot0(r), jnp.zeros((), r.dtype))
            return out, outv
        seg = _seg_ids(gid, validity & in_group, capacity)
        nonnull = jax.ops.segment_sum(
            (seg < capacity).astype(jnp.int32), seg,
            num_segments=capacity)
        outv = nonnull > 0
        if op == "sum":
            out = jax.ops.segment_sum(jnp.where(seg < capacity, data, 0), seg,
                                      num_segments=capacity)
        elif op == "any":
            out = jax.ops.segment_max(
                jnp.where(seg < capacity, data.astype(jnp.int32), 0), seg,
                num_segments=capacity).astype(bool)
        elif op in ("min", "max"):
            if jnp.dtype(data.dtype).kind == "f":
                # reduce on total-order bits so NaN sorts greater than every
                # number (Spark semantics: min skips NaN unless all-NaN)
                bits = _float_order_bits(data)
                top = jnp.array(jnp.iinfo(bits.dtype).max, bits.dtype)
                bot = jnp.array(0, bits.dtype)
                if op == "min":
                    r = jax.ops.segment_min(
                        jnp.where(seg < capacity, bits, top), seg,
                        num_segments=capacity)
                else:
                    r = jax.ops.segment_max(
                        jnp.where(seg < capacity, bits, bot), seg,
                        num_segments=capacity)
                out = _float_from_order_bits(r).astype(data.dtype)
            elif op == "min":
                out = jax.ops.segment_min(_mask_for_min(data, seg, capacity),
                                          seg, num_segments=capacity)
            else:
                out = jax.ops.segment_max(_mask_for_max(data, seg, capacity),
                                          seg, num_segments=capacity)
        out = jnp.where(outv, out, jnp.zeros((), out.dtype))
        return out, outv
    if op in ("first", "last", "first_ignore_nulls", "last_ignore_nulls"):
        if sorted_ok and not op.endswith("ignore_nulls"):
            # stable group sort => group g's members occupy sorted positions
            # [start_g, end_g] in original row order: first/last are pure
            # boundary gathers, no scatter-reduce needed. first is exactly
            # rep_rows (each group's first sorted member, already in
            # GroupInfo); last gathers through seg_ends.
            if op.startswith("first"):
                sel_row = jnp.clip(gi.rep_rows, 0, capacity - 1)
            else:
                ends = jnp.clip(gi.seg_ends, 0, capacity - 1)
                sel_row = gi.order[ends]
            has = pos < gi.num_groups  # dense groups: every slot has a row
            out = jnp.where(has, data[sel_row], jnp.zeros((), data.dtype))
            outv = jnp.where(has, validity[sel_row], False)
            return out, outv
        consider = in_group
        if op.endswith("ignore_nulls"):
            consider = consider & validity
        seg = jnp.where(consider, gid, capacity)
        if op.startswith("first"):
            sel_pos = jax.ops.segment_min(
                jnp.where(consider, pos, capacity), seg, num_segments=capacity)
        else:
            sel_pos = jax.ops.segment_max(
                jnp.where(consider, pos, -1), seg, num_segments=capacity)
        has = (sel_pos >= 0) & (sel_pos < capacity)
        safe = jnp.clip(sel_pos, 0, capacity - 1)
        out = jnp.where(has, data[safe], jnp.zeros((), data.dtype))
        outv = jnp.where(has, validity[safe], False)
        return out, outv
    raise ValueError(f"unknown reduce op {op!r}")


def _mask_for_min(data, seg, capacity: int):
    big = _type_max(data.dtype)
    return jnp.where(seg < capacity, data, big)


def _mask_for_max(data, seg, capacity: int):
    small = _type_min(data.dtype)
    return jnp.where(seg < capacity, data, small)


def _float_from_order_bits(flipped):
    """Inverse of _float_order_bits (modulo -0.0/NaN canonicalization)."""
    if jnp.dtype(flipped.dtype) == jnp.dtype(jnp.uint64):
        top = (flipped & jnp.uint64(1 << 63)) != 0
        bits = jnp.where(top, flipped ^ jnp.uint64(1 << 63), ~flipped)
        return bits.view(jnp.float64)
    top = (flipped & jnp.uint32(0x80000000)) != 0
    bits = jnp.where(top, flipped ^ jnp.uint32(0x80000000), ~flipped)
    return bits.view(jnp.float32)


def _type_max(dtype):
    dtype = jnp.dtype(dtype)
    if dtype.kind == "f":
        return jnp.array(jnp.inf, dtype)
    if dtype.kind == "b":
        return jnp.array(True)
    return jnp.array(jnp.iinfo(dtype).max, dtype)


def _type_min(dtype):
    dtype = jnp.dtype(dtype)
    if dtype.kind == "f":
        return jnp.array(-jnp.inf, dtype)
    if dtype.kind == "b":
        return jnp.array(False)
    return jnp.array(jnp.iinfo(dtype).min, dtype)
