"""A grouped aggregate over dictionary codes that neither sorts nor
scatters (exec/aggregate.py takes it where it applies; ROADMAP M2).

Where every grouping key of an aggregate is a dictionary-coded column
(columnar/encoded.py) the group of a row is known without looking at any
other row: the mixed-radix number of its codes, one digit a key, one
value of the digit kept for a null key. The groups are then the slots of
a table whose size is the product of the radices, fixed when the program
is traced, and every buffer is reduced into that table by one masked
reduction a slot: no sort, no scatter, no `rowkeys.GroupInfo`. The
sort-based aggregate pays a multi-operand sort of the batch's capacity
for the same answer (Q1 at SF1 on a v5e: 14.8 s warm, minutes of compile
a sort-bearing kernel; PERF.md section 6, PR 37), and stays the path of
every aggregate this one does not take: a key that is not a bare
dictionary column, a table over `MAX_GROUPS`, an op outside `OPS`.

A radix is the next power of two over the dictionary's size + 1, so that
a dictionary that gains or loses a value from one file to the next traces
the same program. The occupied slots are compacted to the front of the
output, in slot order, by a one-hot selection over the table (a table is
a few lanes): what leaves the kernel is an ordinary compact
[keys + buffers] batch of at most `MAX_GROUPS` rows, which the exchange,
the merge and the sort take as they take any other. The merge of partials
is the same reduction over their concatenation with the merge ops (sum of
sums, min of mins), once `concat_batches` has brought the codes of one
column to one dictionary.

Precision: a DOUBLE is f32 on the chip, and a group of Q1 sums a million
terms. Float sums are blocked: `SUM_BLOCK` lanes a block, the blocks'
totals summed after, so the rounding error grows with the square root of
the block, not of the batch. Nothing here goes through the MXU.
"""

# tpulint: traced-helpers

from __future__ import annotations

import math
from typing import Optional, Sequence, Tuple

from spark_rapids_tpu import _jax_setup  # noqa: F401
import jax.numpy as jnp

from spark_rapids_tpu.columnar.batch import MIN_CAPACITY, bucket_capacity
from spark_rapids_tpu.exec import rowkeys as RK

# the most slots a table may have. The reductions cost (slots x buffers)
# masked passes over the batch on the vector unit, where the sort they
# replace costs the same whatever the cardinality: at 64 slots and Q1's
# 11 buffers a 2^21-lane batch is about 3e9 lane operations, single
# milliseconds on a v5e, still tens of times under the sort
MAX_GROUPS = 64
# the ops a table slot can hold: each is a plain reduction of the rows of
# one group (first/last, percentiles and string min/max need an order or
# a gather and stay with the sort)
OPS = frozenset({"sum", "count", "min", "max", "any"})
SUM_BLOCK = 2048


def radices(dict_sizes: Sequence[int]) -> Optional[Tuple[int, ...]]:
    """The table's radices for keys with these dictionary sizes, or None
    where the table would pass `MAX_GROUPS` (or there is no key: an
    ungrouped aggregate has its own path)."""
    if not dict_sizes:
        return None
    rs = tuple(max(2, 1 << int(s).bit_length()) for s in dict_sizes)
    return rs if math.prod(rs) <= MAX_GROUPS else None


def table_capacity(rs: Sequence[int]) -> int:
    return bucket_capacity(max(math.prod(rs), MIN_CAPACITY))


def _blocked_sum(x):
    """Sum over the last axis in blocks of SUM_BLOCK lanes."""
    n = x.shape[-1]
    if n <= SUM_BLOCK or n % SUM_BLOCK:
        return jnp.sum(x, axis=-1)
    return jnp.sum(jnp.sum(
        x.reshape(x.shape[:-1] + (n // SUM_BLOCK, SUM_BLOCK)), axis=-1),
        axis=-1)


def _reduce(op: str, data, member):
    """One buffer into the table: (values [slots], valid [slots]) of the
    rows `member` [slots, lanes] marks (a group's live rows whose input is
    not null), with SQL's null semantics (rowkeys.segment_reduce's)."""
    if op == "count":
        cnt = jnp.sum(member, axis=1, dtype=jnp.int32)
        return cnt.astype(jnp.int64), jnp.ones(cnt.shape, bool)
    outv = jnp.any(member, axis=1)
    kind = jnp.dtype(data.dtype).kind
    if op == "sum":
        if kind in "iu":
            # SQL sum over any integral type is LONG
            data = data.astype(jnp.int64)
        vals = jnp.where(member, data[None, :], jnp.zeros((), data.dtype))
        out = _blocked_sum(vals) if kind == "f" else jnp.sum(vals, axis=1)
    elif op == "any":
        out = jnp.any(member & data.astype(bool)[None, :], axis=1)
    elif kind == "f":
        # on total-order bits, so that NaN sorts over every number (Spark:
        # min skips NaN unless all are NaN)
        bits = RK._float_order_bits(data)
        if op == "min":
            r = jnp.min(jnp.where(member, bits[None, :], jnp.array(
                jnp.iinfo(bits.dtype).max, bits.dtype)), axis=1)
        else:
            r = jnp.max(jnp.where(member, bits[None, :],
                                  jnp.array(0, bits.dtype)), axis=1)
        out = RK._float_from_order_bits(r).astype(data.dtype)
    elif op == "min":
        out = jnp.min(jnp.where(member, data[None, :],
                                RK._type_max(data.dtype)), axis=1)
    else:
        out = jnp.max(jnp.where(member, data[None, :],
                                RK._type_min(data.dtype)), axis=1)
    return jnp.where(outv, out, jnp.zeros((), out.dtype)), outv


def group_reduce(key_cols, rs: Sequence[int], live, ops: Sequence[str],
                 in_cols, out_npdts):
    """Traced. `key_cols`: one int32 code ColV a grouping key (codes below
    the key's radix - 1; a null key is its own group, as GROUP BY has it);
    `live` [lanes]: the rows that count; `in_cols`: one ColV a buffer,
    reduced with `ops`. Returns (outs, n_groups): one (data, validity)
    pair a key and a buffer, `table_capacity(rs)` lanes, the occupied
    groups first in slot order and every lane past `n_groups` dead: the
    aggregate's intermediate batch."""
    slots = math.prod(rs)
    cap_out = table_capacity(rs)
    gid = jnp.zeros(live.shape, jnp.int32)
    for cv, r in zip(key_cols, rs):
        digit = jnp.where(cv.validity,
                          jnp.clip(cv.data.astype(jnp.int32), 0, r - 2),
                          r - 1)
        gid = gid * r + digit
    gid = jnp.where(live, gid, slots)
    slot_ids = jnp.arange(slots, dtype=jnp.int32)
    member = gid[None, :] == slot_ids[:, None]
    present = jnp.any(member, axis=1)
    n_groups = jnp.sum(present, dtype=jnp.int32)
    # output lane j holds the j-th occupied slot
    pos = jnp.cumsum(present.astype(jnp.int32)) - 1
    lanes = jnp.arange(cap_out, dtype=jnp.int32)
    pick = present[None, :] & (pos[None, :] == lanes[:, None])
    src = jnp.sum(jnp.where(pick, slot_ids[None, :], 0), axis=1)
    alive = lanes < n_groups

    def to_lanes(data, valid):
        v = valid[src] & alive
        return jnp.where(v, data[src], jnp.zeros((), data.dtype)), v

    outs = []
    stride = slots
    for r in rs:
        stride //= r
        digit = (slot_ids // stride) % r
        outs.append(to_lanes(digit, digit != r - 1))
    for op, cv, npdt in zip(ops, in_cols, out_npdts):
        data, valid = _reduce(op, cv.data, member & cv.validity[None, :])
        if data.dtype != jnp.dtype(npdt):
            data = data.astype(npdt)
        outs.append(to_lanes(data, valid))
    return outs, n_groups


def applies(ops: Sequence[str], in_dtypes, key_dict_sizes
            ) -> Optional[Tuple[int, ...]]:
    """The radices where this aggregate can take the table: every op a
    slot can hold, no string input, and a table within MAX_GROUPS."""
    if not all(op in OPS for op in ops):
        return None
    if any(getattr(dt, "is_string", False) for dt in in_dtypes):
        return None
    return radices(key_dict_sizes)
