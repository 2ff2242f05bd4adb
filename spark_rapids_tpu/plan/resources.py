"""Plan-time resource analyzer: abstract interpretation of memory, shapes,
and dispatches over the FINAL physical plan.

The reference accelerator's worst operational failures — device OOM on
build-side joins and sorts, spill thrash, jit recompile churn — all
manifest at runtime but are decidable (or tightly boundable) at PLAN time
from the physical plan plus the stats the plan already carries (local
relations know their exact partition row counts, file scans know their
split bytes and reader caps, exchanges know their partition counts).
This module walks the plan bottom-up propagating an abstract state per
operator:

- row-count bounds as integer intervals [lo, hi] (hi may be unbounded),
- per-column byte widths from columnar/dtypes physical mapping,
- the padded/bucketed SHAPE SET of batches feeding each kernel
  (columnar.batch.bucket_capacity is the engine's jit shape key),
- a peak-HBM watermark including the transient doubles each operator
  creates (sort key proxies + gather, hash-join build tables, shuffle
  exchange staging, partial-agg buffer lanes),
- a device-dispatch count interval, derived from the engine's actual
  instrumentation sites (utils.metrics.record_dispatch callers), with an
  exactness flag that survives only through operators whose batch flow
  is statically determined.

The result is a `PlanResourceReport`: per-stage peak-bytes estimates,
predicted jit shape-bucket cache keys (recompile-churn count against
engine/jit_cache's LRU capacity), predicted device dispatches, and typed
violations:

- OOM_HAZARD        the peak LOWER bound exceeds the HBM budget: the plan
                    cannot run without blowing the budget (cross joins,
                    oversized single-batch build sides / sorts).
- SPILL_LIKELY      the peak upper bound exceeds the budget while the
                    lower bound fits: the spill framework will likely
                    engage (degraded, not fatal — never raises).
- RECOMPILE_CHURN   predicted (kernel, shape-bucket) compile keys exceed
                    the jit cache capacity: the query would thrash XLA
                    compilation.
- UNBOUNDED_GENERATE a row-multiplying Generate whose input row bound is
                    unbounded: output size cannot be boxed at all.

Wired into session._physical_plan behind
`rapids.tpu.sql.resourceAnalysis.enabled` (+ `.failOnViolation`,
`.hbmBudgetBytes`), rendered by EXPLAIN (`== Resource analysis ==`), and
fed forward as admission weight hints to memory/semaphore and spill
pressure hints to memory/spill (docs/static-analysis.md).
"""

from __future__ import annotations

import math
from collections import OrderedDict
from typing import Dict, List, Optional, Set, Tuple

from spark_rapids_tpu import conf as C
from spark_rapids_tpu.columnar.dtypes import DataType
from spark_rapids_tpu.exec.base import PhysicalExec
from spark_rapids_tpu.plan.verify import PlanViolation

INF = math.inf

# violation kinds (the taxonomy; docs/static-analysis.md)
OOM_HAZARD = "OOM_HAZARD"
SPILL_LIKELY = "SPILL_LIKELY"
RECOMPILE_CHURN = "RECOMPILE_CHURN"
UNBOUNDED_GENERATE = "UNBOUNDED_GENERATE"

# kinds that abort the query under failOnViolation (SPILL_LIKELY is
# advisory: the runtime spill framework exists precisely to absorb it)
FATAL_KINDS = frozenset({OOM_HAZARD, RECOMPILE_CHURN, UNBOUNDED_GENERATE})

# rough per-row payload estimate for STRING columns (matches
# DataType.STRING.itemsize, the batch-sizing estimate used engine-wide)
_STR_BYTES_PER_ROW = DataType.STRING.itemsize

# encoded (dictionary) columns: per-row bytes the byte model drops when a
# STRING column stays CODES (the decoded model charges offsets + validity
# + the string estimate; the encoded layout is int32 code + validity).
# The SAVINGS interval reported against the measured encodedBytesSaved
# metric uses the metric's own formula (columnar.encoded.STR/CODE
# constants) so containment is a like-for-like comparison.
from spark_rapids_tpu.columnar.encoded import (  # noqa: E402
    CODE_BYTES_PER_ROW as _ENC_ROW_BYTES,
)

_ENC_ROW_MODEL_SAVING = (4 + 1 + _STR_BYTES_PER_ROW) - _ENC_ROW_BYTES


class ResourceAnalysisError(ValueError):
    """A physical plan failed resource admission (failOnViolation)."""

    def __init__(self, violations: List[PlanViolation]):
        self.violations = list(violations)
        super().__init__(
            "physical plan failed resource analysis:\n  - "
            + "\n  - ".join(self.violations))


# ---------------------------------------------------------------------------
# Interval arithmetic ([lo, hi] over non-negative ints; hi may be INF)
# ---------------------------------------------------------------------------
class Interval:
    __slots__ = ("lo", "hi")

    def __init__(self, lo, hi=None):
        self.lo = lo
        self.hi = lo if hi is None else hi

    @staticmethod
    def exact(v) -> "Interval":
        return Interval(v, v)

    @staticmethod
    def top() -> "Interval":
        return Interval(0, INF)

    @property
    def is_exact(self) -> bool:
        return self.lo == self.hi and self.hi != INF

    def add(self, o: "Interval") -> "Interval":
        return Interval(self.lo + o.lo, self.hi + o.hi)

    def mul(self, o: "Interval") -> "Interval":
        return Interval(_mul0(self.lo, o.lo), _mul0(self.hi, o.hi))

    def scale(self, k) -> "Interval":
        return Interval(_mul0(self.lo, k), _mul0(self.hi, k))

    def clamp_hi(self, cap) -> "Interval":
        return Interval(min(self.lo, cap), min(self.hi, cap))

    def with_lo(self, lo) -> "Interval":
        return Interval(lo, self.hi)

    def union(self, o: "Interval") -> "Interval":
        return Interval(min(self.lo, o.lo), max(self.hi, o.hi))

    def __repr__(self):
        return f"[{_fmt_n(self.lo)}, {_fmt_n(self.hi)}]"


def _mul0(a, b):
    """Row-count product: 0 * inf is 0 (an exactly-empty side makes the
    output empty no matter how unbounded the other side is), never the
    float NaN that would poison every comparison downstream."""
    if a == 0 or b == 0:
        return 0
    return a * b


def _fmt_n(v) -> str:
    if v == INF:
        return "inf"
    return str(int(v))


def _fmt_ms(v) -> str:
    if v == INF:
        return "inf"
    return f"{v / 1e6:.2f}ms"


def _fmt_bytes(v) -> str:
    if v == INF:
        return "inf"
    v = int(v)
    for unit, shift in (("GiB", 30), ("MiB", 20), ("KiB", 10)):
        if v >= (1 << shift):
            return f"{v / (1 << shift):.1f}{unit}"
    return f"{v}B"


def _bucket(n) -> int:
    """bucket_capacity without importing jax machinery at module load."""
    n = int(min(max(n, 1), 1 << 62)) if n != INF else (1 << 62)
    if n <= 8:
        return 8
    return 1 << (int(n - 1).bit_length())


# ---------------------------------------------------------------------------
# Abstract state
# ---------------------------------------------------------------------------
class AbsState:
    """Per-operator abstract output description.

    rows        total output rows across all partitions
    parts       output partition count (exact; plans carry it statically)
    nonempty    number of partitions that will yield >= 1 batch
    batches     total batches across all partitions
    batch_rows  rows of the largest single batch
    buckets     padded capacities (jit shape keys) of output batches;
                empty set = unknown (estimate as one bucket of batch_rows)
    row_bytes   padded bytes per row of the output schema
    lazy_tail   output batches may carry live masks / device row counts
                (consumers that compact them add data-dependent work)
    col_ndv     per-column distinct-count upper bounds keyed by expr_id
                (the catalog-stats half of the analysis: small host-
                resident leaves are scanned at plan time, and the bounds
                survive filters/limits/exchanges/projections of plain
                column references — they bound GROUP counts above)
    """

    __slots__ = ("rows", "parts", "nonempty", "batches", "batch_rows",
                 "buckets", "row_bytes", "lazy_tail", "placement",
                 "col_ndv", "col_range", "chain_bytes")

    def __init__(self, rows: Interval, parts: int, nonempty: Interval,
                 batches: Interval, batch_rows: Interval,
                 buckets: Set[int], row_bytes: int,
                 lazy_tail: bool = False, placement: str = "tpu",
                 col_ndv: Optional[Dict[int, int]] = None,
                 col_range: Optional[Dict[int, Tuple[float, float]]] = None,
                 chain_bytes=None):
        self.rows = rows
        self.parts = parts
        self.nonempty = nonempty
        self.batches = batches
        self.batch_rows = batch_rows
        self.buckets = buckets
        self.row_bytes = row_bytes
        self.lazy_tail = lazy_tail
        self.placement = placement
        self.col_ndv = dict(col_ndv or {})
        self.col_range = dict(col_range or {})
        # bytes live PER TASK while the next operator processes one batch:
        # pipelined operators extend their input's chain (input batch and
        # every intermediate stay referenced across the generator chain);
        # materialization barriers (exchange, coalesce, aggregate) reset it
        self.chain_bytes = chain_bytes

    # -- derived byte figures -------------------------------------------------
    @property
    def batch_bytes(self) -> float:
        """Padded bytes of the largest single batch."""
        if self.batch_rows.hi == INF:
            return INF
        return _bucket(self.batch_rows.hi) * self.row_bytes

    @property
    def total_bytes(self) -> Interval:
        """Materialized size of the whole output (padded estimate)."""
        if self.buckets and self.batches.is_exact and \
                self.batches.hi == len(self.buckets_list()):
            tot = sum(b * self.row_bytes for b in self.buckets_list())
            return Interval.exact(tot)
        lo = self.rows.lo * self.row_bytes
        if self.rows.hi == INF:
            return Interval(lo, INF)
        # padding can at most double a rows-based bound; a finite batch
        # count may bound tighter still
        hi = self.rows.hi * self.row_bytes * 2
        if self.batches.hi != INF and self.batch_bytes != INF:
            hi = min(hi, self.batches.hi * self.batch_bytes)
        if hi < lo:
            hi = lo
        return Interval(lo, hi)

    def buckets_list(self) -> List[int]:
        return sorted(self.buckets)

    def kernel_buckets(self) -> List[int]:
        """Shape buckets feeding a kernel that consumes this output."""
        if self.buckets:
            return self.buckets_list()
        return [_bucket(self.batch_rows.hi if self.batch_rows.hi != INF
                        else 1 << 20)]

    def chain(self):
        """Per-task live bytes while a consumer processes one batch."""
        return self.chain_bytes if self.chain_bytes is not None \
            else self.batch_bytes


def _row_bytes(attrs, physical) -> int:
    total = 0
    for a in attrs:
        dt = a.data_type
        if getattr(dt, "is_string", False):
            total += 4 + 1 + _STR_BYTES_PER_ROW  # offsets + validity + data
        else:
            total += physical(dt).itemsize + 1
    return max(total, 1)


def _expr_ndv(e, col_ndv: Dict[int, int]):
    """Distinct-count upper bound of one deterministic expression: at most
    the product of its referenced columns' bounds (a literal contributes
    1 — it has one value). INF when any referenced column is unbounded or
    the expression is nondeterministic."""
    from spark_rapids_tpu.plan.verify import _refs

    try:
        if not e.deterministic:
            return INF
    except Exception:
        return INF
    prod = 1
    for ref in {r.expr_id for r in _refs(e)}:
        n = col_ndv.get(ref)
        if n is None:
            return INF
        prod *= max(int(n), 1)
        if prod > (1 << 62):
            return INF
    return prod


def _keys_ndv(exprs, col_ndv: Dict[int, int]):
    """Combined distinct bound of a grouping-key tuple (product of the
    per-key bounds; INF when any key is unbounded)."""
    prod = 1
    for e in exprs:
        n = _expr_ndv(e, col_ndv)
        if n == INF:
            return INF
        prod *= max(int(n), 1)
        if prod > (1 << 62):
            return INF
    return prod


# bounded memo for _scan_col_stats: every plan build re-visits the same
# host-resident leaves (and EXPLAIN analyzes the plan again), but the
# relation's batches and attr expr_ids are stable objects — keying on
# their identities makes the O(rows log rows * cols) scan once-per-
# relation instead of once-per-query. Stats only refine the ESTIMATE
# side (never the OOM floor), so even a pathological stale hit degrades
# an estimate, not soundness.
_STATS_MEMO: "OrderedDict[tuple, tuple]" = OrderedDict()
_STATS_MEMO_CAP = 64


def _scan_col_stats(attrs, host_batches,
                    max_rows: int) -> Tuple[Dict[int, int],
                                            Dict[int, Tuple[float, float]]]:
    """Per-column (distinct counts, numeric min/max) of a host-resident
    leaf, computed at plan time. `host_batches` is a flat list of
    HostColumnarBatch sharing the `attrs` schema; relations above
    `max_rows` skip the scan (cost guard) and return no stats."""
    import numpy as np

    total = sum(b.num_rows for b in host_batches)
    if total == 0 or total > max_rows:
        return {}, {}
    key = (tuple(id(b) for b in host_batches),
           tuple(a.expr_id for a in attrs),
           tuple(b.num_rows for b in host_batches), max_rows)
    hit = _STATS_MEMO.get(key)
    if hit is not None:
        _STATS_MEMO.move_to_end(key)
        return dict(hit[0]), dict(hit[1])
    ndv: Dict[int, int] = {}
    rng: Dict[int, Tuple[float, float]] = {}
    for ci, a in enumerate(attrs):
        seen: Set = set()
        has_null = False
        lo = hi = None
        try:
            for b in host_batches:
                cv = b.columns[ci]
                data = np.asarray(cv.data[:b.num_rows])
                valid = np.asarray(cv.validity[:b.num_rows]).astype(bool)
                if not valid.all():
                    has_null = True
                vals = data[valid]
                if vals.dtype == object:
                    seen.update(vals.tolist())
                else:
                    uniq = np.unique(vals)
                    seen.update(uniq.tolist())
                    if uniq.size and np.issubdtype(uniq.dtype, np.number):
                        vlo, vhi = float(uniq[0]), float(uniq[-1])
                        lo = vlo if lo is None else min(lo, vlo)
                        hi = vhi if hi is None else max(hi, vhi)
        except Exception:  # noqa: BLE001 - stats are best-effort
            continue
        ndv[a.expr_id] = len(seen) + (1 if has_null else 0)
        if lo is not None and hi == hi and lo == lo:  # NaN-free
            rng[a.expr_id] = (lo, hi)
    _STATS_MEMO[key] = (dict(ndv), dict(rng))
    while len(_STATS_MEMO) > _STATS_MEMO_CAP:
        _STATS_MEMO.popitem(last=False)
    return ndv, rng


def _filter_selectivity(cond, col_ndv: Dict[int, int],
                        col_range: Dict[int, Tuple[float, float]]) -> float:
    """Uniformity-based selectivity estimate of a filter condition, in
    (0, 1]; 1.0 when nothing is known. Equality against a literal keeps
    1/ndv of the column; range comparisons keep the overlap fraction of
    the column's value range; AND multiplies, OR adds (capped), NOT
    complements. Estimates only the hi side of row bounds — the certain
    lo is always 0 after a filter."""
    from spark_rapids_tpu.ops.base import AttributeReference
    from spark_rapids_tpu.ops.literals import Literal
    from spark_rapids_tpu.ops.predicates import (
        And,
        EqualTo,
        GreaterThan,
        GreaterThanOrEqual,
        LessThan,
        LessThanOrEqual,
        Not,
        Or,
    )

    def col_lit(e):
        l, r = e.children()
        if isinstance(l, AttributeReference) and isinstance(r, Literal):
            return l, r.value, False
        if isinstance(r, AttributeReference) and isinstance(l, Literal):
            return r, l.value, True
        return None, None, False

    def sel(e) -> float:
        if isinstance(e, And):
            l, r = e.children()
            return sel(l) * sel(r)
        if isinstance(e, Or):
            l, r = e.children()
            return min(1.0, sel(l) + sel(r))
        if isinstance(e, Not):
            return max(0.0, 1.0 - sel(e.children()[0]))
        if isinstance(e, EqualTo):
            col, _v, _sw = col_lit(e)
            if col is not None:
                n = col_ndv.get(col.expr_id)
                if n:
                    return 1.0 / max(n, 1)
            return 1.0
        if isinstance(e, (LessThan, LessThanOrEqual,
                          GreaterThan, GreaterThanOrEqual)):
            col, v, swapped = col_lit(e)
            if col is None:
                return 1.0
            rng = col_range.get(col.expr_id)
            try:
                v = float(v)
            except (TypeError, ValueError):
                return 1.0
            if rng is None or rng[1] <= rng[0]:
                return 1.0
            lo, hi = rng
            frac = (min(max(v, lo), hi) - lo) / (hi - lo)
            keeps_below = isinstance(e, (LessThan, LessThanOrEqual))
            if swapped:  # lit < col reads as col > lit
                keeps_below = not keeps_below
            s = frac if keeps_below else 1.0 - frac
            return min(1.0, max(s, 0.0))
        return 1.0

    try:
        return min(1.0, max(sel(cond), 1e-6))
    except Exception:  # noqa: BLE001 - estimates are best-effort
        return 1.0


# ---------------------------------------------------------------------------
# Report
# ---------------------------------------------------------------------------
class NodeEstimate:
    """One line of the per-stage breakdown."""

    __slots__ = ("name", "depth", "rows", "resident_bytes", "dispatches",
                 "node_id", "placement")

    def __init__(self, name: str, depth: int, rows: Interval,
                 resident_bytes, dispatches: Interval,
                 node_id: int = 0, placement: str = "tpu"):
        self.name = name
        self.depth = depth
        self.rows = rows
        self.resident_bytes = resident_bytes
        self.dispatches = dispatches
        # identity + side of the plan node this line prices — the
        # placement analyzer keys its DP table on node_id and the
        # mixed-plan cost split on placement
        self.node_id = node_id
        self.placement = placement


class PlanResourceReport:
    """The analyzer's verdict for one final physical plan."""

    def __init__(self, budget: int, concurrency: int):
        self.budget = budget
        self.concurrency = concurrency
        self.peak_bytes = Interval.exact(0)
        self.dispatches = Interval.exact(0)
        self.dispatches_exact = True
        self.compile_keys = 0
        # predicted device->host transfer events (the fencesPerQuery
        # metric's unit): sink downloads + serialized-shuffle encodes.
        # The issue-ahead executor's whole point is driving this to ~1
        # (docs/async-execution.md)
        self.fences = Interval.exact(0)
        # single-program SPMD stages (plan/spmd.py): pipelines predicted to
        # run as ONE shard_map dispatch, and the bytes their in-program
        # collectives (all_to_all epoch, sort all_gather) are expected to
        # move across the mesh. The prediction covers SPMD stage epochs
        # only — the standalone ICI shuffle tier records into the SAME
        # measured metric but is not modeled here
        self.spmd_stages = 0
        self.collective_bytes = Interval.exact(0)
        # stage coverage for the EXPLAIN line `spmd stages: N of M
        # stages`: M = SPMD segments + materializing exchanges that
        # stayed host-loop stage boundaries — partial lowering is visible
        # instead of a bare count
        self.host_exchange_stages = 0
        self.total_stages = 0
        # encoded columnar execution (columnar/encoded.py): how many scan
        # columns are predicted to emit ENCODED, the HBM-savings interval
        # in the measured metric's own formula (containment-testable
        # against encodedBytesSaved), the encoded-vs-decoded byte model
        # for those columns, and WHERE the plan decodes them (the
        # late-materialization points — 'sink' when codes survive to the
        # result download)
        self.encoded_cols = 0
        self.encoded_saved = Interval.exact(0)
        self.encoded_code_bytes = Interval.exact(0)
        self.encoded_decoded_bytes = Interval.exact(0)
        # HOST bytes of scan-attached RLE run tables (run-aware kernels,
        # columnar/runs.py) — bounded by maxRunFraction; reported so the
        # collapse's host residency is visible, never charged to HBM
        self.run_table_bytes = Interval.exact(0)
        self.decode_points: List[str] = []
        self.nodes: List[NodeEstimate] = []
        self.violations: List[PlanViolation] = []
        # calibrated wall-time prediction (obs/calibrate.py): the fitted
        # cost model's [lo, hi] ns interval for this plan, attached by
        # analyze_plan when a model is active at PLAN time (None
        # otherwise — the render line is conditional, so plans analyzed
        # without calibration keep the golden EXPLAIN layout).
        # wall_calibrated/wall_fallback name the classes priced at
        # fitted vs cold-start-fallback coefficients.
        self.predicted_wall_ns: Optional[Interval] = None
        self.wall_calibrated: List[str] = []
        self.wall_fallback: List[str] = []

    # -- hints consumed by session wiring ------------------------------------
    @property
    def per_task_peak_bytes(self):
        """Peak bytes one concurrent task contributes (admission weight)."""
        if self.concurrency <= 0:
            return self.peak_bytes.hi
        if self.peak_bytes.hi == INF:
            return INF
        return self.peak_bytes.hi // self.concurrency

    @property
    def spill_pressure(self) -> float:
        """Predicted peak over budget; > 1.0 means spill is expected."""
        if self.budget <= 0:
            return 0.0
        if self.peak_bytes.hi == INF:
            return INF
        return self.peak_bytes.hi / self.budget

    def admission_weight(self, max_concurrent: int) -> int:
        """Semaphore permits one task of this query should hold: heavier
        plans admit fewer concurrent tasks (the static half of admission
        control)."""
        if max_concurrent <= 1 or self.budget <= 0:
            return 1
        per_task = self.per_task_peak_bytes
        if per_task == INF:
            return max_concurrent
        share = self.budget / max_concurrent
        if share <= 0:
            return 1
        need = int(math.ceil(per_task / share))
        return max(1, min(max_concurrent, need))

    def render(self) -> str:
        """The EXPLAIN `== Resource analysis ==` body (deterministic)."""
        lines = [
            f"peak HBM: {_fmt_bytes(self.peak_bytes.lo)}"
            f"..{_fmt_bytes(self.peak_bytes.hi)}"
            f" (budget {_fmt_bytes(self.budget)},"
            f" concurrency {self.concurrency})",
            f"device dispatches: {_fmt_n(self.dispatches.lo)}"
            f"..{_fmt_n(self.dispatches.hi)}"
            + (" (exact)" if self.dispatches_exact else ""),
            f"host fences (device->host transfers): "
            f"{_fmt_n(self.fences.lo)}..{_fmt_n(self.fences.hi)}",
            f"jit shape-bucket cache keys: {self.compile_keys}",
        ]
        if self.predicted_wall_ns is not None:
            cal = ",".join(self.wall_calibrated) or "none"
            lines.append(
                f"predicted wall time: "
                f"{_fmt_ms(self.predicted_wall_ns.lo)}"
                f"..{_fmt_ms(self.predicted_wall_ns.hi)} "
                f"(calibrated: {cal}"
                + (f"; flat fallback: {','.join(self.wall_fallback)}"
                   if self.wall_fallback else "") + ")")
        if self.spmd_stages:
            total = max(self.total_stages, self.spmd_stages)
            lines.append(
                f"spmd stages: {self.spmd_stages} of {total} stages "
                f"(collective bytes "
                f"{_fmt_bytes(self.collective_bytes.lo)}"
                f"..{_fmt_bytes(self.collective_bytes.hi)})")
        if self.encoded_cols:
            pts = ", ".join(self.decode_points) or "none"
            lines.append(
                f"encoded columns: {self.encoded_cols} (bytes saved "
                f"{_fmt_bytes(self.encoded_saved.lo)}"
                f"..{_fmt_bytes(self.encoded_saved.hi)}; decode at: "
                f"{pts})")
            if self.run_table_bytes.hi:
                lines.append(
                    f"run tables (host): "
                    f"{_fmt_bytes(self.run_table_bytes.lo)}"
                    f"..{_fmt_bytes(self.run_table_bytes.hi)}")
        for n in self.nodes:
            lines.append(
                "  " * (n.depth + 1)
                + f"{n.name}: rows={n.rows!r} "
                f"resident~{_fmt_bytes(n.resident_bytes)} "
                f"dispatches={n.dispatches!r}")
        if self.violations:
            lines.extend(f"! [{v.kind}] {v}" for v in self.violations)
        else:
            lines.append("violations: none")
        return "\n".join(lines)


# ---------------------------------------------------------------------------
# Encoded-column flow (columnar/encoded.py): a structural pre-pass
# mirroring the runtime's code-space eligibility, so the byte model can
# charge code-bytes where codes will actually flow and predict WHERE each
# encoded column decodes (the late-materialization point)
# ---------------------------------------------------------------------------
def _encoded_flow(plan: PhysicalExec, conf: "C.TpuConf"):
    """(enc_at: {id(node): {output expr_id: 'certain'|'possible'}},
    decode_points: ordered unique node labels where encoded columns
    materialize — 'sink' when codes survive to the result download)."""
    from spark_rapids_tpu.columnar import encoded as ENCX
    from spark_rapids_tpu.exec import basic as B
    from spark_rapids_tpu.exec.aggregate import (
        COMPLETE,
        PARTIAL,
        _HashAggregateBase,
    )
    from spark_rapids_tpu.exec.fused import TpuFusedStageExec
    from spark_rapids_tpu.exec.join import _JoinBase
    from spark_rapids_tpu.exec.transitions import (
        CpuCoalesceBatchesExec,
        DeviceToHostExec,
        HostToDeviceExec,
        TpuCoalesceBatchesExec,
    )
    from spark_rapids_tpu.io.scan import TpuFileScanExec
    from spark_rapids_tpu.ops.base import (
        Alias,
        AttributeReference,
        to_attribute,
    )
    from spark_rapids_tpu.shuffle.exchange import (
        HashPartitioning,
        RangePartitioning,
        _ExchangeBase,
    )

    enc_at: Dict[int, Dict[int, str]] = {}
    decode_points: List[str] = []

    def _is_spmd_stage(node) -> bool:
        from spark_rapids_tpu.plan.spmd import TpuSpmdStageExec

        return isinstance(node, TpuSpmdStageExec)

    def _is_sort(node) -> bool:
        from spark_rapids_tpu.exec.sort import _SortBase

        return isinstance(node, _SortBase)

    def _is_window(node) -> bool:
        from spark_rapids_tpu.exec.window import _WindowBase

        return isinstance(node, _WindowBase)

    def _unwrap_window(e):
        from spark_rapids_tpu.exec.window import _unwrap

        return _unwrap(e)

    def note_decode(label: str) -> None:
        if label not in decode_points:
            decode_points.append(label)

    def refs(e):
        return {r.expr_id for r in e.collect(
            lambda x: isinstance(x, AttributeReference))}

    def bare(e):
        inner = e.child if isinstance(e, Alias) else e
        return inner.expr_id if isinstance(inner, AttributeReference) \
            else None

    def walk(node) -> Dict[int, str]:
        kids = [walk(c) for c in node.children]
        cin = kids[0] if kids else {}
        enc: Dict[int, str] = {}
        if isinstance(node, TpuFileScanExec):
            try:
                ep = node.encoded_plan(conf)
            except Exception:
                ep = {}
            by_name = {a.name: a.expr_id for a in node.output}
            enc = {by_name[n]: st for n, st in ep.items() if n in by_name}
        elif isinstance(node, TpuFusedStageExec):
            # children[0] is the member chain's top: its state IS the
            # stage output's (members were walked on the recursion)
            enc = dict(cin)
        elif isinstance(node, (B.TpuFilterExec, B.CpuFilterExec)):
            enc = dict(cin)
            ok = ENCX.unbound_supported_refs([node.condition], enc.keys())
            bad = (set(enc) - ok) & refs(node.condition)
            if bad:
                note_decode(node.node_name())
                for i in bad:
                    enc.pop(i, None)
        elif isinstance(node, (B.TpuProjectExec, B.CpuProjectExec)):
            srcs = {}
            others = []
            for a, e in zip(node.output, node.project_list):
                b = bare(e)
                if b is not None and b in cin:
                    enc[a.expr_id] = cin[b]
                    srcs[a.expr_id] = b
                else:
                    others.append(e)
            ok = ENCX.unbound_supported_refs(others, cin.keys())
            oref = set()
            for e in others:
                oref |= refs(e)
            bad = (set(cin) - ok) & oref
            if bad:
                note_decode(node.node_name())
                enc = {oe: st for oe, st in enc.items()
                       if srcs[oe] not in bad}
        elif isinstance(node, _HashAggregateBase):
            if cin:
                from spark_rapids_tpu.ops.aggregates import (
                    AggregateFunction,
                    Max,
                    Min,
                )

                key_eids = {g.expr_id for g in node.grouping}
                minmax_kept = set()   # buffer eids of rank-space min/max
                if node.mode in (PARTIAL, COMPLETE):
                    # bare MIN/MAX inputs reduce over RANKS (the sorted
                    # dictionary) and stay encoded; any other input use
                    # decodes — mirror exec/aggregate.plan_agg_update
                    minmax_in = set()
                    input_refs = set()
                    for op, e, _dt in node._update_ops():
                        b = bare(e)
                        if op in ("min", "max") and b is not None \
                                and b in cin:
                            minmax_in.add(b)
                        else:
                            input_refs |= refs(e)
                    minmax_in -= input_refs
                    nonbare = set()
                    for e in node.key_exprs:
                        b = bare(e)
                        r = refs(e)
                        if b is not None:
                            r = r - {b}
                        nonbare |= r
                    kept = {i for i in cin
                            if (i in key_eids or i in minmax_in)
                            and i not in input_refs and i not in nonbare}
                    for spec in node.specs:
                        for (_bn, op, e), battr in zip(
                                spec.func.update_aggs(), spec.buffers):
                            b = bare(e)
                            if op in ("min", "max") and b is not None \
                                    and b in kept and b in minmax_in:
                                minmax_kept.add(battr.expr_id)
                else:
                    # FINAL: encoded grouping keys and min/max BUFFER
                    # columns (cin carries the partial schema) merge in
                    # code space
                    buf_eids = {b.expr_id for s in node.specs
                                for (_bn, op), b in zip(s.func.merge_aggs(),
                                                        s.buffers)
                                if op in ("min", "max")}
                    kept = {i for i in cin
                            if i in key_eids or i in buf_eids}
                    minmax_kept = kept - key_eids
                if set(cin) - kept:
                    note_decode(node.node_name())
                if node.mode == PARTIAL:
                    enc = {i: cin[i] for i in kept}
                    for spec in node.specs:
                        for (_bn, op, e), battr in zip(
                                spec.func.update_aggs(), spec.buffers):
                            if battr.expr_id in minmax_kept:
                                enc[battr.expr_id] = cin[bare(e)]
                else:
                    for e in node.agg_exprs:
                        b = bare(e)
                        if b is not None and b in kept:
                            enc[to_attribute(e).expr_id] = cin[b]
                            continue
                        # Alias(Min/Max(kept ref/buffer)) emits the
                        # winning CODE — encoded through to the sink
                        fs = e.collect(
                            lambda x: isinstance(x, AggregateFunction))
                        if len(fs) != 1 or not isinstance(fs[0],
                                                          (Min, Max)):
                            continue
                        if node.mode == COMPLETE:
                            inner = bare(fs[0].children()[0]) \
                                if fs[0].children() else None
                            if inner is not None and inner in kept:
                                enc[to_attribute(e).expr_id] = cin[inner]
                        else:  # FINAL: map through the buffer attr
                            bufs = [s for s in node.specs
                                    if s.func.fingerprint()
                                    == fs[0].fingerprint()]
                            if bufs and bufs[0].buffers[0].expr_id \
                                    in kept:
                                enc[to_attribute(e).expr_id] = \
                                    cin[bufs[0].buffers[0].expr_id]
        elif isinstance(node, _ExchangeBase):
            p = node.partitioning
            if isinstance(p, RangePartitioning):
                # bare-ref encoded keys route in RANK space (bounds
                # sampled as union ranks — shuffle/exchange.py); only
                # computed key expressions over an encoded column decode
                enc = dict(cin)
                bad = set()
                for o in p.orders:
                    if bare(o.child) in enc:
                        continue
                    bad |= refs(o.child) & set(enc)
                if bad:
                    note_decode(node.node_name())
                    for i in bad:
                        enc.pop(i, None)
            else:
                enc = dict(cin)
                if isinstance(p, HashPartitioning):
                    bad = set()
                    for e in p.exprs:
                        if bare(e) in enc:
                            continue  # dictionary-hashed key
                        bad |= refs(e) & set(enc)
                    if bad:
                        note_decode(node.node_name())
                        for i in bad:
                            enc.pop(i, None)
        elif isinstance(node, _JoinBase):
            left = kids[0] if kids else {}
            right = kids[1] if len(kids) > 1 else {}
            enc = {}
            enc.update(left)
            enc.update(right)
            bad = set()
            # one ordinal equi-joined against SEVERAL columns on the
            # other side may face differing dictionaries at runtime (one
            # remap cannot serve two code spaces — exec/join falls back
            # to value comparison), so the ceiling must assume a decode
            pair_l: dict = {}
            pair_r: dict = {}
            for lk, rk in zip(node.left_keys, node.right_keys):
                lb, rb = bare(lk), bare(rk)
                if lb is not None and rb is not None and \
                        lb in left and rb in right:
                    pair_l.setdefault(lb, set()).add(rb)
                    pair_r.setdefault(rb, set()).add(lb)
            for lk, rk in zip(node.left_keys, node.right_keys):
                lb, rb = bare(lk), bare(rk)
                if lb is not None and rb is not None and \
                        lb in left and rb in right:
                    if len(pair_l[lb]) == 1 and len(pair_r[rb]) == 1:
                        continue  # both sides encoded: code-remap join
                    bad.add(lb)
                    bad.add(rb)
                    continue
                for e, side in ((lk, left), (rk, right)):
                    b = bare(e)
                    if b is not None and b in side:
                        bad.add(b)
                    bad |= refs(e) & set(side)
            if node.condition is not None:
                ok = ENCX.unbound_supported_refs([node.condition],
                                                 enc.keys())
                bad |= (set(enc) - ok) & refs(node.condition)
            if bad:
                note_decode(node.node_name())
                for i in bad:
                    enc.pop(i, None)
        elif isinstance(node, (HostToDeviceExec, TpuCoalesceBatchesExec,
                               CpuCoalesceBatchesExec,
                               B.CoalescePartitionsExec,
                               B.TpuLocalLimitExec, B.CpuLocalLimitExec,
                               B._GlobalLimitBase)):
            enc = dict(cin)
        elif isinstance(node, DeviceToHostExec):
            if cin:
                note_decode("sink")
        elif _is_spmd_stage(node):
            # the stage program preserves the member chain's code flow:
            # encoded group keys pass through the in-program exchange as
            # int32 lanes and emit encoded (engine/spmd_exec.py); the
            # members themselves were walked on the recursion. An
            # absorbed sort tail sorts codes through a rank LUT, so a
            # sort-topped subtree keeps its input's flow rather than the
            # sort node's conservative boundary decode
            enc = dict(cin)
            if not cin and len(node.infos) == 1 \
                    and node.infos[0].sort is not None:
                below = enc_at.get(id(node.infos[0].final), {})
                enc = dict(below)
        elif _is_sort(node):
            # order-preserving sort: bare encoded keys sort on RANKS
            # (exec/sort.py) — no decode; computed key expressions over
            # an encoded column decode
            enc = dict(cin)
            bad = set()
            for o in node.orders:
                if bare(o.child) in enc:
                    continue
                bad |= refs(o.child) & set(enc)
            if bad:
                note_decode(node.node_name())
                for i in bad:
                    enc.pop(i, None)
        elif _is_window(node):
            # bare encoded partition/order refs stay RANK codes; window
            # function inputs, computed spec expressions, and finite
            # RANGE offsets decode (mirror exec/window._encoded_plan)
            from spark_rapids_tpu.ops.window import UNBOUNDED

            enc = dict(cin)
            spec = node._spec()
            wexprs = [w for e in node.window_exprs
                      for w in [_unwrap_window(e)]]
            finite_range = any(
                w.spec.frame.frame_type == "range"
                and (w.spec.frame.lower not in (UNBOUNDED, 0)
                     or w.spec.frame.upper not in (UNBOUNDED, 0))
                for w in wexprs)
            bad = set()
            for e in spec.partition_by:
                if bare(e) in enc:
                    continue
                bad |= refs(e) & set(enc)
            for so in spec.order_by:
                b = bare(so.child)
                if b in enc and not finite_range:
                    continue
                if b in enc:
                    bad.add(b)
                bad |= refs(so.child) & set(enc)
            for w in wexprs:
                for c in w.function.children():
                    bad |= refs(c) & set(enc)
            if bad:
                note_decode(node.node_name())
                for i in bad:
                    enc.pop(i, None)
        else:
            # expand/generate/union/cache/write/unknown:
            # the operator boundary decode
            if any(k for k in kids):
                note_decode(node.node_name())
        enc_at[id(node)] = enc
        return enc

    walk(plan)
    return enc_at, decode_points


# ---------------------------------------------------------------------------
# The analyzer
# ---------------------------------------------------------------------------
class _Analyzer:
    def __init__(self, conf: "C.TpuConf", budget: int,
                 donation: bool = False, measured_stats=None):
        from spark_rapids_tpu.columnar.batch import physical_np_dtype

        self.conf = conf
        self.budget = budget
        # measured-stats input channel (aqe/loop.py): MapOutputStats per
        # materialized query-stage node id. A TpuQueryStageExec leaf is
        # charged from these MEASURED sizes instead of plan-time priors;
        # a stage also carries its own stats, so the channel only needs
        # to override when the caller wants different numbers
        self.measured_stats = dict(measured_stats or {})
        self.physical = physical_np_dtype
        self.concurrency = max(1, min(conf.concurrent_tpu_tasks,
                                      conf.task_threads))
        # issue-ahead knobs the model must mirror (docs/async-execution.md):
        # prefetch holds (1 + depth) scan batches in flight per task, and
        # donation lets a consume-once kernel's output reuse its input's
        # HBM (subtracting the input from the pipeline chain estimate)
        self.prefetch_depth = max(0, int(conf.get(C.IO_PREFETCH_BATCHES)))
        self.donation = bool(donation)
        self.report = PlanResourceReport(budget, self.concurrency)
        self._compile_keys: Set[tuple] = set()
        self._depth = 0
        # SPMD-stage capture: while visiting a TpuSpmdStageExec's subtree,
        # the abstract states of its member exchanges (per-target bucket
        # sizing) and lowered join nodes (expansion sizing) are stashed
        # here, keyed by node id (see visit())
        self._spmd_capture_map: Optional[Dict[int, Optional[AbsState]]] = \
            None
        # exchanges absorbed IN-PROGRAM by an SPMD stage (its hash
        # exchange, the absorbed range exchange, elided join shuffles):
        # not host-loop stage boundaries for the coverage accounting
        self._covered_exchanges: Set[int] = set()
        # lazy-compaction policies mirror the exec layer's (devprobe fence
        # measurement + conf); they change capacities, not semantics
        self._filter_lazy = self._policy(C.FILTER_COMPACT_SYNC)
        self._agg_lazy = self._policy(C.AGG_COMPACT_SYNC)
        # encoded-column flow (populated by run()'s pre-pass): per node,
        # which output columns are predicted to stay dictionary CODES
        self._enc_at: Dict[int, Dict[int, str]] = {}

    def _policy(self, entry) -> bool:
        policy = self.conf.get(entry)
        if policy == "never":
            return True
        if policy == "always":
            return False
        try:
            from spark_rapids_tpu.exec.aggregate import (
                LAZY_FENCE_THRESHOLD_MS,
            )
            from spark_rapids_tpu.utils.devprobe import fence_cost_ms

            return fence_cost_ms() >= LAZY_FENCE_THRESHOLD_MS
        except Exception:  # pragma: no cover - probe needs a live backend
            return False

    # -- accounting helpers ---------------------------------------------------
    def _spend(self, d: Interval, exact: bool = True) -> Interval:
        self.report.dispatches = self.report.dispatches.add(d)
        if not exact:
            self.report.dispatches_exact = False
        return d

    def _inexact(self) -> None:
        self.report.dispatches_exact = False

    def _compiles(self, kind: str, ident, buckets) -> None:
        for b in buckets:
            self._compile_keys.add((kind, ident, b))

    def _resident(self, node: PhysicalExec, nbytes, state: AbsState,
                  dispatches: Interval, record: bool = True) -> None:
        """Record an UPPER-bound residency estimate for one operator. Only
        the peak's hi moves: estimates are pessimistic, and a pessimistic
        value must never feed the lower bound (the OOM_HAZARD trigger) —
        certain floors go through _resident_floor instead."""
        hi = INF if nbytes == INF else int(nbytes)
        cur = self.report.peak_bytes.hi
        self.report.peak_bytes = Interval(
            self.report.peak_bytes.lo,
            INF if (hi == INF or cur == INF) else max(cur, hi))
        if record:
            self.report.nodes.append(NodeEstimate(
                node.node_name(), self._depth, state.rows, nbytes,
                dispatches, node_id=id(node),
                placement=getattr(node, "placement", "tpu")))

    def _resident_floor(self, nbytes) -> None:
        """Raise the peak's CERTAIN lower bound: only for residency the
        plan cannot avoid (a hash-join build table of exactly-known size,
        a cross join's exact output, a RequireSingleBatch coalesce of an
        exactly-known partition)."""
        if nbytes == INF:
            return
        self.report.peak_bytes = Interval(
            max(self.report.peak_bytes.lo, int(nbytes)),
            max(_hi_or(self.report.peak_bytes.hi, 0), int(nbytes))
            if self.report.peak_bytes.hi != INF else INF)

    def _violate(self, kind: str, msg: str) -> None:
        self.report.violations.append(PlanViolation(msg, kind=kind))

    # -- entry ---------------------------------------------------------------
    def run(self, plan: PhysicalExec) -> PlanResourceReport:
        try:
            self._enc_at, decode_points = _encoded_flow(plan, self.conf)
        except Exception:
            self._enc_at, decode_points = {}, []
        final = self.visit(plan)
        r = self.report
        r.decode_points = decode_points
        r.compile_keys = len(self._compile_keys)
        r.total_stages = r.spmd_stages + r.host_exchange_stages
        # plan-level violations ---------------------------------------------
        from spark_rapids_tpu.engine import jit_cache

        if r.compile_keys > jit_cache._MAX_ENTRIES:
            self._violate(
                RECOMPILE_CHURN,
                f"predicted jit compile keys ({r.compile_keys}) exceed the "
                f"process jit cache capacity ({jit_cache._MAX_ENTRIES}): "
                "the query would thrash XLA compilation "
                "(parameterize literals or coalesce batch shapes)")
        if self.budget > 0:
            if r.peak_bytes.lo > self.budget:
                self._violate(
                    OOM_HAZARD,
                    "predicted peak HBM lower bound "
                    f"{_fmt_bytes(r.peak_bytes.lo)} exceeds the budget "
                    f"{_fmt_bytes(self.budget)}: the plan cannot execute "
                    "inside the device budget (reduce the build side, "
                    "raise hbmBudgetBytes, or re-plan)")
            elif r.peak_bytes.hi > self.budget:
                self._violate(
                    SPILL_LIKELY,
                    "predicted peak HBM upper bound "
                    f"{_fmt_bytes(r.peak_bytes.hi)} exceeds the budget "
                    f"{_fmt_bytes(self.budget)} (lower bound "
                    f"{_fmt_bytes(r.peak_bytes.lo)} fits): expect the "
                    "spill framework to engage")
        # deterministic ordering: hard hazards first, then advisory
        r.violations.sort(key=lambda v: (v.kind not in FATAL_KINDS, v.kind,
                                         str(v)))
        return r

    # -- dispatch table -------------------------------------------------------
    def visit(self, node: PhysicalExec) -> AbsState:
        self._depth += 1
        try:
            st = self._dispatch(node)
            cm = self._spmd_capture_map
            if cm is not None and id(node) in cm:
                # SPMD-stage capture (_spmd_stage): abstract states of the
                # member exchanges / lowered joins, sizing the program's
                # per-target buckets and join expansion capacities
                cm[id(node)] = st
            return st
        finally:
            self._depth -= 1

    def _dispatch(self, node: PhysicalExec) -> AbsState:
        from spark_rapids_tpu.exec import basic as B
        from spark_rapids_tpu.exec.aggregate import _HashAggregateBase
        from spark_rapids_tpu.exec.cache import _CachedScanBase
        from spark_rapids_tpu.exec.expand import _ExpandBase, _GenerateBase
        from spark_rapids_tpu.exec.fused import TpuFusedStageExec
        from spark_rapids_tpu.exec.join import _JoinBase
        from spark_rapids_tpu.exec.sort import _SortBase
        from spark_rapids_tpu.exec.transitions import (
            CpuCoalesceBatchesExec,
            DeviceToHostExec,
            HostToDeviceExec,
            TpuCoalesceBatchesExec,
        )
        from spark_rapids_tpu.exec.window import _WindowBase
        from spark_rapids_tpu.io.scan import _FileScanBase
        from spark_rapids_tpu.shuffle.exchange import _ExchangeBase

        from spark_rapids_tpu.aqe.loop import TpuAdaptiveExec
        from spark_rapids_tpu.aqe.stages import (
            TpuQueryStageExec,
            TpuStageReaderExec,
        )
        from spark_rapids_tpu.plan.spmd import TpuSpmdStageExec

        if isinstance(node, TpuAdaptiveExec):
            # transparent: the wrapper only drives stage-by-stage
            # execution of the subtree it declares
            return self.visit(node.children[0])
        if isinstance(node, TpuQueryStageExec):
            return self._query_stage(node)
        if isinstance(node, TpuStageReaderExec):
            return self._stage_reader(node)
        if isinstance(node, TpuSpmdStageExec):
            return self._spmd_stage(node)
        if isinstance(node, TpuFusedStageExec):
            return self._fused_stage(node)
        if isinstance(node, B.HostScanExec):
            return self._host_scan(node)
        if isinstance(node, B.RangeExec):
            return self._range(node)
        if isinstance(node, _FileScanBase):
            return self._file_scan(node)
        if isinstance(node, _CachedScanBase):
            return self._cached_scan(node)
        if isinstance(node, HostToDeviceExec):
            return self._host_to_device(node)
        if isinstance(node, DeviceToHostExec):
            return self._device_to_host(node)
        if isinstance(node, (TpuCoalesceBatchesExec,
                             CpuCoalesceBatchesExec)):
            return self._coalesce(node)
        if isinstance(node, B.CoalescePartitionsExec):
            return self._coalesce_parts(node)
        if isinstance(node, (B.TpuProjectExec, B.CpuProjectExec)):
            return self._project(node)
        if isinstance(node, (B.TpuFilterExec, B.CpuFilterExec)):
            return self._filter(node)
        if isinstance(node, (B.TpuLocalLimitExec, B.CpuLocalLimitExec)):
            return self._local_limit(node)
        if isinstance(node, B._GlobalLimitBase):
            return self._global_limit(node)
        if isinstance(node, B._UnionBase):
            return self._union(node)
        if isinstance(node, _GenerateBase):
            return self._generate(node)
        if isinstance(node, _ExpandBase):
            return self._expand(node)
        if isinstance(node, _SortBase):
            return self._sort(node)
        if isinstance(node, _ExchangeBase):
            return self._exchange(node)
        if isinstance(node, _JoinBase):
            return self._join(node)
        if isinstance(node, _HashAggregateBase):
            return self._aggregate(node, node.children[0],
                                   collapsed=False)
        if isinstance(node, _WindowBase):
            return self._window(node)
        return self._unknown(node)

    # -- leaves ---------------------------------------------------------------
    def _mk(self, node, rows, parts, nonempty, batches, batch_rows,
            buckets, lazy_tail=False, ndv=None, rng=None,
            chain=None) -> AbsState:
        rb = _row_bytes(node.output, self.physical)
        enc = self._enc_at.get(id(node))
        if enc:
            # columns CERTAIN to flow as dictionary codes charge the
            # encoded layout (int32 code + validity) instead of the
            # expanded-string estimate; 'possible' columns keep the
            # decoded charge so the pessimistic ceiling stays sound
            for a in node.output:
                if enc.get(a.expr_id) == "certain":
                    rb = max(1, rb - _ENC_ROW_MODEL_SAVING)
        return AbsState(rows, parts, nonempty, batches, batch_rows,
                        set(buckets), rb,
                        lazy_tail=lazy_tail, placement=node.placement,
                        col_ndv=ndv, col_range=rng, chain_bytes=chain)

    def _host_scan(self, node) -> AbsState:
        part_rows = [sum(b.num_rows for b in p) for p in node._partitions]
        n_batches = sum(len(p) for p in node._partitions)
        nonempty = sum(1 for p in node._partitions if p)
        batch_rows = [b.num_rows for p in node._partitions for b in p]
        buckets = {_bucket(r) for r in batch_rows}
        total = sum(part_rows)
        ndv, rng = _scan_col_stats(node.output,
                                   [b for p in node._partitions for b in p],
                                   self.conf.get(C.RESOURCE_STATS_MAX_ROWS))
        return self._mk(node, Interval.exact(total), len(part_rows),
                        Interval.exact(nonempty),
                        Interval.exact(n_batches),
                        Interval.exact(max(batch_rows, default=0)), buckets,
                        ndv=ndv, rng=rng)

    def _range(self, node) -> AbsState:
        total = max(0, -(-(node.end - node.start) // node.step))
        parts = node.num_parts
        per = -(-total // parts) if total else 0
        part_rows = [max(0, min(total, (i + 1) * per) - i * per)
                     for i in range(parts)]
        nonempty = sum(1 for r in part_rows if r)
        buckets = {_bucket(r) for r in part_rows if r}
        return self._mk(node, Interval.exact(total), parts,
                        Interval.exact(nonempty),
                        Interval.exact(nonempty),
                        Interval.exact(max(part_rows, default=0)), buckets,
                        ndv={node.output[0].expr_id: max(total, 1)})

    def _file_scan(self, node) -> AbsState:
        import os

        from spark_rapids_tpu.io.prefetch import prefetch_depth

        # the runtime honors a per-read .option("prefetchBatches", k)
        # override carried on the splits — the model must see the SAME
        # depth or the ceiling under-predicts exactly the deep-prefetch
        # reads most likely to OOM
        depth = self.prefetch_depth
        if node.splits:
            depth = prefetch_depth(self.conf, node.splits[0])
        parts = len(node.splits)
        total_bytes = 0
        for s in node.splits:
            try:
                total_bytes += os.path.getsize(s.path)
            except OSError:
                pass
        row_bytes = _row_bytes(node.output, self.physical)
        # encoded bytes bound decoded rows very loosely (>= 1 byte/row);
        # the reader caps rows per BATCH, so per-batch shape stays bounded
        # even when totals are unknown
        rows_hi = INF if total_bytes <= 0 else total_bytes * 8
        cap_rows = self.conf.get(C.MAX_READ_BATCH_SIZE_ROWS)
        batch_rows = Interval(0, cap_rows if rows_hi == INF
                              else min(cap_rows, rows_hi))
        self._inexact()
        # a column every chunk of which is dictionary-encoded has at most
        # the sum of its chunks' dictionaries of distinct values, and a
        # null: what sizes a group-by over it from its groups, not from
        # its rows (ROADMAP M3)
        dict_plan = node.dict_columns_plan(self.conf) \
            if hasattr(node, "dict_columns_plan") else {}
        ndv = {a.expr_id: dict_plan[a.name][1] + 1
               for a in node.output if a.name in dict_plan}
        st = self._mk(node, Interval(0, rows_hi), parts,
                      Interval(0, parts), Interval(0, INF), batch_rows,
                      set(), ndv=ndv or None)
        # decode staging: raw split bytes + the in-flight decoded batches.
        # Prefetch double-buffering multiplies the latter: with depth k
        # the consumer's batch, the worker's in-hand batch, and k queued
        # batches are live per task (2 + k; io/prefetch.py queue sizing)
        # — the peak-HBM ceiling for scan leaves scales with the
        # configured depth (rapids.tpu.io.prefetchBatches)
        staged = 1 if depth == 0 else (2 + depth)
        self._resident(node,
                       self.concurrency * (total_bytes / max(parts, 1)
                                           + st.batch_bytes * staged)
                       if st.batch_bytes != INF else INF,
                       st, Interval.exact(0))
        if node.placement == "tpu":
            # device decode kernels: unknown page/chunk mix
            self._spend(Interval(0, INF), exact=False)
        enc = self._enc_at.get(id(node))
        if enc:
            # predicted encoded emission: savings in the measured metric's
            # own formula (rows x (STR - CODE) per encoded column), lo
            # only for certain columns (the heuristic/decode may still
            # fall back on 'possible' ones, and file row totals are loose
            # so rows.lo is typically 0 anyway)
            from spark_rapids_tpu.columnar.encoded import (
                CODE_BYTES_PER_ROW,
                decoded_bytes_per_row,
            )

            # per-claim decoded estimate: a scan emits STRING columns
            # encoded and no others (parquet_device.column_eligible), so
            # the string estimate — the measured encodedBytesSaved
            # metric's own formula (columnar/encoded.record_scan_emission)
            per_row = max(0, decoded_bytes_per_row(DataType.STRING)
                          - CODE_BYTES_PER_ROW)
            n_cert = sum(1 for s in enc.values() if s == "certain")
            cert_saved = per_row * n_cert
            all_saved = per_row * len(enc)
            r = self.report
            r.encoded_cols += len(enc)
            r.encoded_saved = r.encoded_saved.add(
                Interval(_mul0(st.rows.lo, cert_saved),
                         _mul0(st.rows.hi, all_saved)))
            r.encoded_code_bytes = r.encoded_code_bytes.add(
                Interval(_mul0(st.rows.lo, _ENC_ROW_BYTES * n_cert),
                         _mul0(st.rows.hi, _ENC_ROW_BYTES * len(enc))))
            r.encoded_decoded_bytes = r.encoded_decoded_bytes.add(
                Interval(_mul0(st.rows.lo,
                               (4 + 1 + _STR_BYTES_PER_ROW) * n_cert),
                         _mul0(st.rows.hi,
                               (4 + 1 + _STR_BYTES_PER_ROW) * len(enc))))
            if self.conf.get(C.RUN_AWARE_ENABLED):
                # host run-table residency bound: <= maxRunFraction x
                # rows x (8 B start + 8 B value) per covered column —
                # HOST bytes (never uploaded), reported, not charged to
                # the HBM ceiling
                frac = self.conf.get(C.RUN_AWARE_MAX_RUN_FRACTION)
                r.run_table_bytes = r.run_table_bytes.add(Interval(
                    0, _mul0(st.rows.hi, int(16 * frac) * len(enc))))
        return st

    def _cached_scan(self, node) -> AbsState:
        from spark_rapids_tpu.exec.cache import (
            cached_device_bytes,
            cached_device_partition_rows,
            cached_host_partitions,
        )

        host_parts = cached_host_partitions(node.logical_node)
        rng = None
        if host_parts is not None:
            part_rows = [[b.num_rows for b in p] for p in host_parts]
            ndv, rng = _scan_col_stats(
                node.output, [b for p in host_parts for b in p],
                self.conf.get(C.RESOURCE_STATS_MAX_ROWS))
        else:
            part_rows = cached_device_partition_rows(node.logical_node)
            ndv = None
        if part_rows is not None:
            batch_rows = [r for p in part_rows for r in p]
            st = self._mk(node, Interval.exact(sum(batch_rows)),
                          len(part_rows),
                          Interval.exact(sum(1 for p in part_rows if p)),
                          Interval.exact(len(batch_rows)),
                          Interval.exact(max(batch_rows, default=0)),
                          {_bucket(r) for r in batch_rows}, ndv=ndv,
                          rng=rng)
        else:
            # cache not yet populated: the first execution runs the child
            # in full and materializes it — the child's own state (incl.
            # its stats) IS the cached relation's
            st = self.visit(node.children[0])
        if node.placement == "tpu":
            # the materialized relation is device-resident (spillable):
            # once it is, at the bytes its buffers were registered with;
            # before, at the child's estimate of what it will hold
            registered = cached_device_bytes(node.logical_node)
            self._resident(node, st.total_bytes.hi if registered is None
                           else registered, st, Interval.exact(0))
        return st

    def _unknown(self, node) -> AbsState:
        """Operator outside the transfer-function registry: sound but
        maximally imprecise."""
        for c in node.children:
            self.visit(c)
        self._inexact()
        self._spend(Interval(0, INF), exact=False)
        st = self._mk(node, Interval(0, INF), 1, Interval(0, 1),
                      Interval(0, INF), Interval(0, INF), set())
        self._resident(node, INF, st, Interval(0, INF))
        return st

    # -- identity / plumbing --------------------------------------------------
    def _host_to_device(self, node) -> AbsState:
        cin = self.visit(node.children[0])
        st = self._mk(node, cin.rows, cin.parts, cin.nonempty, cin.batches,
                      cin.batch_rows, cin.buckets, ndv=cin.col_ndv,
                      rng=cin.col_range)
        # uploaded batches live on device per concurrent task
        self._resident(node, _mulsafe(self.concurrency, st.batch_bytes),
                       st, Interval.exact(0), record=False)
        return st

    def _device_to_host(self, node) -> AbsState:
        cin = self.visit(node.children[0])
        # the download run buffers up to 32 batches before one grouped
        # transfer; they stay device-live until the run flushes
        self._resident(node,
                       _mulsafe(self.concurrency,
                                _mulsafe(min(32, _hi_or(cin.batches.hi, 32)),
                                         cin.batch_bytes)),
                       cin, Interval.exact(0), record=False)
        # sink fences: under issue-ahead execution the session lifts a
        # root sink to ONE grouped query-level download (floor 1); the
        # checked/sync path flushes per nonempty partition, and the
        # 1->32 run ramp bounds the worst case by one transfer per batch
        lo = 0
        if cin.batches.lo > 0:
            lo = 1 if self.conf.get(C.ASYNC_DISPATCH) \
                else max(1, cin.nonempty.lo)
        self.report.fences = self.report.fences.add(
            Interval(lo, cin.batches.hi))
        return AbsState(cin.rows, cin.parts, cin.nonempty, cin.batches,
                        cin.batch_rows, set(cin.buckets), cin.row_bytes,
                        placement="cpu", col_ndv=cin.col_ndv,
                        col_range=cin.col_range)

    def _coalesce_parts(self, node) -> AbsState:
        cin = self.visit(node.children[0])
        n_out = min(node.num_partitions, max(1, cin.parts))
        return AbsState(cin.rows, n_out, cin.nonempty.clamp_hi(n_out),
                        cin.batches, cin.batch_rows, set(cin.buckets),
                        cin.row_bytes, cin.lazy_tail, node.placement,
                        col_ndv=cin.col_ndv, col_range=cin.col_range,
                        chain_bytes=cin.chain_bytes)

    def _coalesce(self, node) -> AbsState:
        from spark_rapids_tpu.exec.transitions import RequireSingleBatch

        cin = self.visit(node.children[0])
        single = isinstance(node.goal, RequireSingleBatch)
        if single:
            part_rows_hi = cin.rows.hi  # whole partition in one batch
            batches = cin.nonempty
            batch_rows = Interval(cin.batch_rows.lo, part_rows_hi)
            if node.placement == "tpu" and cin.rows.lo > 0:
                # the largest partition holds >= ceil(rows/parts) rows and
                # MUST materialize as one padded batch — a certain floor
                self._resident_floor(
                    _bucket(-(-cin.rows.lo // max(cin.parts, 1)))
                    * cin.row_bytes)
        else:
            target = node.goal.target_bytes() or (512 << 20)
            rows_per = max(1, target // max(cin.row_bytes, 1))
            batch_rows = Interval(cin.batch_rows.lo,
                                  cin.rows.hi if cin.rows.hi != INF
                                  else INF).clamp_hi(
                                      max(rows_per, cin.batch_rows.hi)
                                      if cin.batch_rows.hi != INF
                                      else INF)
            if cin.batches.is_exact and cin.nonempty.is_exact and \
                    cin.total_bytes.hi != INF and \
                    cin.total_bytes.hi <= target:
                batches = cin.nonempty  # everything concats per partition
            else:
                batches = Interval(min(cin.batches.lo, cin.nonempty.lo),
                                   cin.batches.hi)
                if not batches.is_exact:
                    self._inexact()
        buckets = {_bucket(batch_rows.hi)} if batch_rows.hi != INF \
            else set()
        st = AbsState(cin.rows, cin.parts, cin.nonempty, batches,
                      batch_rows, buckets, cin.row_bytes,
                      lazy_tail=False, placement=node.placement,
                      col_ndv=cin.col_ndv, col_range=cin.col_range)
        if node.placement == "tpu":
            # concat transient: inputs + packed output live together
            self._resident(node,
                           _mulsafe(self.concurrency,
                                    _mulsafe(2, st.batch_bytes)),
                           st, Interval.exact(0), record=False)
        return st

    # -- pipelined row operators ----------------------------------------------
    def _project(self, node) -> AbsState:
        from spark_rapids_tpu.ops.base import AttributeReference as _AR

        cin = self.visit(node.children[0])
        ndv = {}
        rng = {}
        for a, e in zip(node.output, node.project_list):
            n = _expr_ndv(e, cin.col_ndv)
            if n != INF:
                ndv[a.expr_id] = n
            if isinstance(e, _AR) and e.expr_id in cin.col_range:
                rng[a.expr_id] = cin.col_range[e.expr_id]
        st = self._mk(node, cin.rows, cin.parts, cin.nonempty, cin.batches,
                      cin.batch_rows, cin.buckets,
                      lazy_tail=cin.lazy_tail, ndv=ndv, rng=rng,
                      chain=_addsafe(cin.chain(), 0))
        if node.placement == "tpu":
            d = self._spend(cin.batches, exact=cin.batches.is_exact)
            self._compiles(
                "project",
                tuple(e.fingerprint() for e in node.project_list),
                cin.kernel_buckets())
            st.chain_bytes = _addsafe(cin.chain(), st.batch_bytes)
            self._resident(node,
                           _mulsafe(self.concurrency, st.chain_bytes),
                           st, d, record=False)
        return st

    def _filter(self, node) -> AbsState:
        cin = self.visit(node.children[0])
        sel = _filter_selectivity(node.condition, cin.col_ndv,
                                  cin.col_range)
        rows = Interval(0, cin.rows.hi if cin.rows.hi == INF
                        else int(-(-cin.rows.hi * sel // 1)))
        lazy = self._filter_lazy and node.placement == "tpu"
        # compacted output re-buckets by surviving rows (estimated via the
        # selectivity); lazy keeps the input capacity
        buckets = set(cin.buckets) if lazy else set()
        batch_rows = cin.batch_rows.with_lo(0)
        if not lazy and batch_rows.hi != INF:
            batch_rows = Interval(0, int(-(-batch_rows.hi * sel // 1)))
        st = self._mk(node, rows, cin.parts, cin.nonempty.with_lo(0),
                      cin.batches, batch_rows, buckets,
                      lazy_tail=lazy or cin.lazy_tail, ndv=cin.col_ndv,
                      rng=cin.col_range)
        if node.placement == "tpu":
            # filter kernel + compact plan + gather: 3 per batch
            d = self._spend(cin.batches.scale(3),
                            exact=cin.batches.is_exact)
            self._compiles("filter", node.condition.fingerprint(),
                           cin.kernel_buckets())
            st.chain_bytes = _addsafe(cin.chain(), st.batch_bytes)
            self._resident(node,
                           _mulsafe(self.concurrency,
                                    _addsafe(cin.chain(), cin.batch_bytes)),
                           st, d, record=False)
        return st

    def _local_limit(self, node) -> AbsState:
        cin = self.visit(node.children[0])
        rows = cin.rows.clamp_hi(node.limit * max(cin.parts, 1))
        batches = cin.batches
        if not (cin.batches.is_exact and cin.batches.hi <= cin.parts):
            # early-exit can drop later batches
            batches = Interval(min(cin.nonempty.lo, cin.batches.lo),
                               cin.batches.hi)
            self._inexact()
        if node.placement == "tpu":
            # the batch crossing the limit boundary is cut with one gather
            # per partition — whether any batch crosses is data-dependent
            self._spend(Interval(0, min(cin.parts,
                                        _hi_or(cin.batches.hi, cin.parts))),
                        exact=False)
        return self._mk(node, rows, cin.parts, cin.nonempty, batches,
                        cin.batch_rows.clamp_hi(node.limit)
                        if not cin.lazy_tail else cin.batch_rows,
                        set(), lazy_tail=cin.lazy_tail, ndv=cin.col_ndv,
                        rng=cin.col_range, chain=cin.chain_bytes)

    def _global_limit(self, node) -> AbsState:
        cin = self.visit(node.children[0])
        rows = cin.rows.clamp_hi(node.limit)
        batches = cin.batches
        if not (cin.batches.is_exact and cin.batches.hi <= 1):
            # the cut can drop trailing batches entirely
            batches = Interval(min(1, cin.batches.lo), cin.batches.hi)
            self._inexact()
        if node.placement == "tpu":
            # at most one boundary-crossing slice gather (single partition)
            self._spend(Interval(0, 1), exact=False)
        return self._mk(node, rows, 1, cin.nonempty.clamp_hi(1),
                        batches, cin.batch_rows.clamp_hi(node.limit),
                        set(), lazy_tail=cin.lazy_tail, ndv=cin.col_ndv,
                        rng=cin.col_range, chain=cin.chain_bytes)

    def _union(self, node) -> AbsState:
        states = [self.visit(c) for c in node.children]
        rows = states[0].rows
        batches = states[0].batches
        nonempty = states[0].nonempty
        parts = states[0].parts
        batch_rows = states[0].batch_rows
        buckets = set(states[0].buckets)
        lazy = states[0].lazy_tail
        for s in states[1:]:
            rows = rows.add(s.rows)
            batches = batches.add(s.batches)
            nonempty = nonempty.add(s.nonempty)
            parts += s.parts
            batch_rows = batch_rows.union(s.batch_rows)
            buckets |= s.buckets
            lazy = lazy or s.lazy_tail
        if any(not s.buckets for s in states):
            buckets = set()
        # positional sum: output column i holds the union of every input's
        # column i, so its distinct bound is the sum of theirs
        ndv = {}
        for oi, a in enumerate(node.output):
            tot = 0
            for s, c in zip(states, node.children):
                n = s.col_ndv.get(c.output[oi].expr_id)
                if n is None:
                    tot = None
                    break
                tot += n
            if tot is not None:
                ndv[a.expr_id] = tot
        return self._mk(node, rows, parts, nonempty, batches, batch_rows,
                        buckets, lazy_tail=lazy, ndv=ndv)

    def _expand(self, node) -> AbsState:
        cin = self.visit(node.children[0])
        k = len(node.projections)
        ndv = {}
        for oi, a in enumerate(node.output_attrs):
            tot = 0
            for proj in node.projections:
                n = _expr_ndv(proj[oi], cin.col_ndv)
                if n == INF:
                    tot = None
                    break
                tot += n
            if tot is not None:
                ndv[a.expr_id] = tot
        st = self._mk(node, cin.rows.scale(k), cin.parts, cin.nonempty,
                      cin.batches.scale(k), cin.batch_rows, cin.buckets,
                      ndv=ndv)
        if node.placement == "tpu":
            d = self._spend(cin.batches.scale(k),
                            exact=cin.batches.is_exact and not cin.lazy_tail)
            for pi, proj in enumerate(node.projections):
                self._compiles(
                    "project",
                    tuple(e.fingerprint() for e in proj),
                    cin.kernel_buckets())
            st.chain_bytes = _addsafe(cin.chain(), st.batch_bytes)
            self._resident(node,
                           _mulsafe(self.concurrency, st.chain_bytes),
                           st, d, record=False)
        return st

    def _generate(self, node) -> AbsState:
        cin = self.visit(node.children[0])
        k = len(node.elem_exprs)
        if cin.rows.hi == INF:
            self._violate(
                UNBOUNDED_GENERATE,
                f"{node.node_name()}: generate multiplies an input whose "
                "row bound is unbounded (no stats reach this scan); the "
                "output size cannot be boxed at plan time")
        st = self._mk(node, cin.rows.scale(k), cin.parts, cin.nonempty,
                      cin.batches, cin.batch_rows.scale(k), set(),
                      ndv=cin.col_ndv)
        if node.placement == "tpu":
            d = self._spend(cin.batches.scale(2),
                            exact=cin.batches.is_exact and not cin.lazy_tail)
            self._compiles(
                "project",
                tuple(e.fingerprint() for e in node.elem_exprs),
                cin.kernel_buckets())
            st.chain_bytes = _addsafe(cin.chain(), st.batch_bytes)
            self._resident(node,
                           _mulsafe(self.concurrency, st.chain_bytes),
                           st, d)
        return st

    def _sort(self, node) -> AbsState:
        cin = self.visit(node.children[0])
        st = self._mk(node, cin.rows, cin.parts, cin.nonempty, cin.batches,
                      cin.batch_rows, cin.buckets, ndv=cin.col_ndv,
                      rng=cin.col_range)
        if node.placement == "tpu":
            # sort permutation kernel is uninstrumented; the row gather is
            # the one counted dispatch per non-empty batch
            d = self._spend(cin.batches, exact=cin.batches.is_exact)
            self._compiles(
                "sort",
                tuple(o.fingerprint() for o in node.orders),
                cin.kernel_buckets())
            # transient double: key proxies + permutation + gathered copy
            key_bytes = _mulsafe(
                _bucket(cin.batch_rows.hi) if cin.batch_rows.hi != INF
                else INF,
                8 * max(1, len(node.orders)))
            st.chain_bytes = _addsafe(cin.chain(), st.batch_bytes)
            self._resident(
                node,
                _mulsafe(self.concurrency,
                         _addsafe(_addsafe(cin.chain(), st.batch_bytes),
                                  key_bytes)),
                st, d)
        return st

    def _window(self, node) -> AbsState:
        cin = self.visit(node.children[0])
        st = self._mk(node, cin.rows, cin.parts, cin.nonempty, cin.batches,
                      cin.batch_rows, set(), ndv=cin.col_ndv,
                      rng=cin.col_range)
        if node.placement == "tpu":
            d = self._spend(
                Interval(0, _mulsafe(4, cin.batches.hi)), exact=False)
            self._resident(
                node,
                _mulsafe(self.concurrency,
                         _mulsafe(3, cin.batch_bytes)),
                st, d)
        else:
            d = Interval.exact(0)
            self._resident(node, 0, st, d)
        return st

    # -- adaptive query stages (spark_rapids_tpu/aqe/) -----------------------
    def _query_stage(self, node) -> AbsState:
        """A materialized exchange boundary: MEASURED MapOutputStats
        replace every plan-time prior for the subtree below it — the
        data already sits in its reduce buckets, so rows/bytes/partition
        counts are facts, not estimates."""
        stats = self.measured_stats.get(id(node))
        if stats is None:
            stats = node.stats
        rb = _row_bytes(node.output, self.physical)
        parts = node.pb.num_partitions
        if stats is None:
            # no stats collected (a range exchange on the CPU oracle
            # path, say): the stage is opaque but finite
            self._inexact()
            st = AbsState(Interval(0, INF), parts, Interval(0, parts),
                          Interval(0, INF), Interval(0, INF), set(), rb,
                          placement=node.placement)
            self._resident(node, 0, st, Interval.exact(0))
            return st
        total_rows = stats.total_rows
        if total_rows is not None:
            rows = Interval.exact(total_rows)
            batch_rows = Interval(
                0, max([r for r in stats.rows_per_bucket], default=0))
        else:
            # a lazy piece's count is device-resident: bytes are still
            # measured, rows stay an interval
            self._inexact()
            rows = Interval(0, INF)
            batch_rows = Interval(0, INF)
        nonempty = Interval.exact(stats.nonempty_buckets())
        batches = Interval(nonempty.lo, max(stats.total_pieces(),
                                            nonempty.lo))
        st = AbsState(rows, parts, nonempty, batches, batch_rows, set(),
                      rb, placement=node.placement)
        # the whole materialized stage is resident until consumed
        self._resident(node,
                       stats.total_bytes if node.placement == "tpu" else 0,
                       st, Interval.exact(0))
        return st

    def _stage_reader(self, node) -> AbsState:
        """Partition-spec reader: row-preserving; only the partition
        count (and per-task grouping) changes."""
        cin = self.visit(node.children[0])
        parts = max(1, len(node.spec))
        self._inexact()
        return AbsState(cin.rows, parts,
                        Interval(min(cin.nonempty.lo, parts), parts),
                        cin.batches, cin.batch_rows, set(cin.buckets),
                        cin.row_bytes, lazy_tail=cin.lazy_tail,
                        placement=node.placement, col_ndv=cin.col_ndv,
                        col_range=cin.col_range)

    # -- single-program SPMD stages ------------------------------------------
    def _spmd_stage(self, node) -> AbsState:
        """Model one TpuSpmdStageExec — possibly a CHAIN of segments with
        lowered joins: the wrapped subtree is analyzed as the host-loop
        executor would run it (its estimates stay sound for the runtime
        fallback path), then the subtree's dispatch interval widens DOWN
        to the SPMD floor — ONE program dispatch for the whole chain,
        with host-input assembly issuing none — so the combined interval
        contains the measured count in BOTH modes. Per segment, the
        exchange's row bound is stashed on the node (per-target bucket
        sizing) and each lowered join's output row bound on its join spec
        (expansion sizing); the member exchanges are marked COVERED for
        the `spmd stages: N of M stages` coverage accounting."""
        before_d = self.report.dispatches
        # save/restore: a NESTED SPMD stage (double group-by) must not
        # clobber the outer stage's capture map
        prev_map = self._spmd_capture_map
        cm: Dict[int, Optional[AbsState]] = {}
        for info in node.infos:
            cm[id(info.exchange)] = None
            for jp in info.joins:
                cm[id(jp.join)] = None
            self._covered_exchanges.update(
                id(x) for x in info.covered_exchanges())
        self._spmd_capture_map = cm
        cin = self.visit(node.children[0])
        self._spmd_capture_map = prev_map
        after_d = self.report.dispatches
        inner_lo = after_d.lo - before_d.lo
        self.report.dispatches = Interval(
            before_d.lo + min(1, inner_lo), after_d.hi)
        self._inexact()

        any_joins = False
        node.bucket_rows_hints = [None] * len(node.infos)
        for s, info in enumerate(node.infos):
            st = cm.get(id(info.exchange))
            if st is not None and st.rows.hi != INF:
                node.bucket_rows_hints[s] = int(st.rows.hi)
            for jp in info.joins:
                any_joins = True
                jst = cm.get(id(jp.join))
                jp.rows_hint = int(jst.rows.hi) \
                    if jst is not None and jst.rows.hi != INF else None
        hint = node.bucket_rows_hints[-1]

        try:
            from spark_rapids_tpu.plan.spmd import mesh_size

            m = mesh_size(self.conf)
        except Exception:  # pragma: no cover - no backend at plan time
            m = 1
        m_out = 1 if node.info.sort is not None else m
        est_total = 0
        unbounded = any_joins  # join all_gather volume is data-dependent
        for s, info in enumerate(node.infos):
            inter_attrs = info.exchange.children[0].output
            inter_bytes = _row_bytes(inter_attrs, self.physical)
            has_strings = any(
                getattr(a.data_type, "is_string", False)
                for a in list(inter_attrs) + list(info.final.output))
            h = node.bucket_rows_hints[s]
            if h is None or has_strings:
                # string keys travel as padded byte matrices whose width
                # the plan cannot bound (the runtime pow2-buckets the
                # actual max length) — only an unbounded METRIC ceiling
                # is sound. The residency estimate below stays on the
                # finite per-row-bytes figure: _resident only raises the
                # pessimistic peak hi, so a width underestimate can at
                # worst under-warn SPILL_LIKELY
                unbounded = True
                continue
            # per-(shard, target) buckets of bucket_cap rows: data +
            # validity lanes + the live mask; the absorbed sort
            # all_gathers the merged output (m * received-lanes) to every
            # shard
            bucket = _bucket(max(h, 8))
            est_total = _addsafe(est_total, _mulsafe(
                m * m * bucket, inter_bytes + 2 * len(inter_attrs) + 8))
            if s == len(node.infos) - 1 and m_out == 1:
                out_bytes = _row_bytes(node.output, self.physical)
                est_total = _addsafe(est_total, _mulsafe(
                    m * m * m * bucket,
                    out_bytes + 2 * len(node.output) + 8))
        # `unbounded` widens only the collective-bytes METRIC ceiling
        # (string matrix widths and join all_gather volume are
        # data-dependent); the residency estimate below stays on the
        # finite per-segment sum — _resident only raises the pessimistic
        # peak hi, so an underestimate can at worst under-warn
        coll = Interval(0, INF if unbounded else est_total)
        self.report.spmd_stages += len(node.infos)
        self.report.collective_bytes = self.report.collective_bytes.add(
            coll)
        self._compiles("spmd_stage", node.stage_id, (0,))

        # output: m live-masked partitions (ONE globally sorted partition
        # when the sort tail is absorbed); union with the host-loop flow so
        # downstream models stay containment-correct under fallback
        parts = max(cin.parts, m_out)
        batches = Interval(0, max(_hi_or(cin.batches.hi, parts), parts))
        # output batches are live-masked at the program's received-lane
        # capacity: m * bucket_cap lanes (x m again when the absorbed sort
        # all_gathers), bucket_cap bounded by the captured partial rows
        if hint is not None:
            lane_hi = _mulsafe(m * m if m_out == 1 else m,
                               _bucket(max(hint, 1)))
            batch_rows = Interval(0, max(lane_hi,
                                         _hi_or(cin.batch_rows.hi, 0)))
        else:
            batch_rows = Interval(0, INF)
        st = self._mk(node, cin.rows, parts, Interval(0, parts), batches,
                      batch_rows, set(), lazy_tail=True,
                      ndv=cin.col_ndv, rng=cin.col_range)
        # the executor materializes EVERY stage input — the innermost
        # segment's probe input and each lowered join's build side — as
        # [m, cap] mesh-global arrays before the one dispatch; the
        # host-loop streaming model above never charges that. 2x covers
        # the pow2 slot padding; a build side additionally replicates to
        # every shard through the in-program all_gather (x m); strings
        # ride the analyzer-wide per-row estimate (_row_bytes), same as
        # every other string residency figure
        def _table_bytes(input_node, attrs, replicate: int) -> int:
            try:
                sub = _Analyzer(self.conf, self.budget,
                                donation=self.donation)
                in_rows = sub.visit(input_node).rows.hi
            except Exception:  # pragma: no cover - best-effort estimator
                in_rows = INF
            if in_rows != INF:
                in_rows = _bucket(max(int(in_rows), 1))
            return _mulsafe(2 * replicate, _mulsafe(
                in_rows, _row_bytes(attrs, self.physical)))

        in_bytes = _table_bytes(node.infos[0].input_node,
                                node.infos[0].input_attrs, 1)
        for info in node.infos:
            for jp in info.joins:
                in_bytes = _addsafe(in_bytes, _table_bytes(
                    jp.build_input_node, jp.build_attrs, m))
        self._resident(node, _addsafe(est_total, in_bytes), st,
                       Interval(1, 1))
        return st

    # -- exchanges ------------------------------------------------------------
    def _exchange(self, node) -> AbsState:
        from spark_rapids_tpu.shuffle.exchange import (
            LAZY_PIECE_CAP_BYTES,
            RangePartitioning,
            SinglePartitioning,
        )

        cin = self.visit(node.children[0])
        if id(node) not in self._covered_exchanges:
            # a materializing exchange that stays OUTSIDE every SPMD
            # program is a host-loop stage boundary (coverage line)
            self.report.host_exchange_stages += 1
        p = node.partitioning
        n_out = p.num_partitions
        row_bytes = cin.row_bytes
        has_strings = any(getattr(a.data_type, "is_string", False)
                          for a in node.output)
        serialize = self.conf.get(C.SHUFFLE_SERIALIZE)
        is_tpu = node.placement == "tpu"
        if serialize and is_tpu:
            # serialized map outputs download host-side: one grouped
            # transfer per input batch (exchange._encode_pieces_grouped)
            self.report.fences = self.report.fences.add(
                Interval(0, cin.batches.hi))
        d = Interval.exact(0)
        if is_tpu:
            if isinstance(p, SinglePartitioning):
                pass  # pieces pass through unsliced
            elif isinstance(p, RangePartitioning):
                # one gather per non-empty (batch, target) piece
                d = self._spend(
                    Interval(cin.nonempty.lo,
                             _mulsafe(cin.batches.hi, n_out)),
                    exact=False)
            elif serialize or has_strings:
                # serialized pieces and string-bearing pieces cannot pass
                # as lazy views: slicing gathers per (batch, target)
                d = self._spend(
                    Interval(0, _mulsafe(cin.batches.hi, n_out)),
                    exact=False)
            elif cin.lazy_tail:
                # _compacted may have to gather lazy string views
                d = self._spend(Interval(0, cin.batches.hi), exact=False)
            lazy_pieces = (not has_strings and not serialize
                           and cin.batch_bytes != INF
                           and cin.batch_bytes <= LAZY_PIECE_CAP_BYTES)
        else:
            lazy_pieces = False

        if isinstance(p, SinglePartitioning):
            out_parts = 1
            nonempty = Interval(1 if cin.rows.lo > 0 else 0,
                                min(1, _hi_or(cin.nonempty.hi, 1)))
            batches = cin.batches
            batch_rows = cin.batch_rows
            exact_flow = cin.batches.is_exact
        else:
            out_parts = n_out
            # adaptive coalescing regroups reduce buckets under the
            # advisory target; model the group count from total bytes.
            # Range exchanges NEVER regroup: _execute_range returns its
            # n raw buckets without the _materialize grouping pass
            target = self.conf.get(C.ADAPTIVE_TARGET_BYTES)
            adaptive = (self.conf.get(C.ADAPTIVE_COALESCE)
                        and node.allow_adaptive and n_out > 1
                        and not isinstance(p, RangePartitioning))
            if adaptive and cin.total_bytes.hi != INF and \
                    cin.total_bytes.hi <= target:
                out_parts = 1
                nonempty = Interval(1 if cin.rows.lo > 0 else 0, 1)
                exact_flow = cin.batches.is_exact
            else:
                nonempty = Interval(min(1, cin.rows.lo), out_parts)
                exact_flow = False
                self._inexact()
            if lazy_pieces and not isinstance(p, RangePartitioning):
                # every (batch, target) lazy view survives piece filtering
                batches = cin.batches.scale(n_out)
                batch_rows = cin.batch_rows  # views keep source capacity
            else:
                batches = Interval(nonempty.lo,
                                   _mulsafe(cin.batches.hi, n_out))
                batch_rows = Interval(0, cin.rows.hi)
                if exact_flow:
                    exact_flow = False
                    self._inexact()
        st = AbsState(cin.rows, out_parts, nonempty, batches, batch_rows,
                      set(), row_bytes,
                      lazy_tail=is_tpu and lazy_pieces,
                      placement=node.placement, col_ndv=cin.col_ndv,
                      col_range=cin.col_range)
        if is_tpu:
            # staging: the in-process exchange materializes EVERY map
            # output before the reduce side runs — the whole child output
            # is device-resident at once (plus slicing transients)
            self._resident(
                node,
                _addsafe(cin.total_bytes.hi,
                         _mulsafe(self.concurrency,
                                  _mulsafe(2, cin.batch_bytes))),
                st, d)
        else:
            self._resident(node, 0, st, d)
        return st

    # -- joins ----------------------------------------------------------------
    def _join(self, node) -> AbsState:
        from spark_rapids_tpu.exec.join import TpuNestedLoopJoinExec
        from spark_rapids_tpu.plan.logical import JoinType

        left = self.visit(node.children[0])
        right = self.visit(node.children[1])
        jt = node.join_type
        build_left = node.build_left
        build, stream = (left, right) if build_left else (right, left)
        row_bytes = _row_bytes(node.output, self.physical)
        nested = isinstance(node, TpuNestedLoopJoinExec) or \
            type(node).__name__ == "CpuNestedLoopJoinExec"

        # output row bounds ---------------------------------------------------
        cross = left.rows.mul(right.rows)
        # equi-join match multiplicity: with key distinct stats on either
        # side, the classic uniformity estimate |L . R| = |L|*|R| /
        # max(ndv_L, ndv_R) gives the expected matches PER STREAM ROW as
        # build_rows / max(ndv) (can be < 1: a selective build side drops
        # stream rows); without stats the worst case (all build rows under
        # one key) stands. This refines the ESTIMATE side only — the
        # certain OOM floor below never uses it.
        build_keys = (node.left_keys if build_left else node.right_keys) \
            if not nested else []
        stream_keys = (node.right_keys if build_left else node.left_keys) \
            if not nested else []
        match = INF
        if build_keys:
            bndv = _keys_ndv(build_keys, build.col_ndv)
            sndv = _keys_ndv(stream_keys, stream.col_ndv)
            if bndv != INF and build.rows.hi != INF:
                bndv = min(bndv, build.rows.hi)  # distinct <= rows
                if sndv != INF and stream.rows.hi != INF:
                    sndv = min(sndv, stream.rows.hi)
                denom = max(bndv, 0 if sndv == INF else sndv, 1)
                match = build.rows.hi / denom
        eq_hi = cross.hi if match == INF else \
            min(cross.hi,
                _ceilsafe(_mulsafe(stream.rows.hi, match)))
        if nested and node.condition is None:
            rows = cross  # exact cartesian product
        elif jt in (JoinType.LEFT_SEMI, JoinType.LEFT_ANTI):
            rows = Interval(0, left.rows.hi)
        elif jt is JoinType.INNER:
            rows = Interval(0, eq_hi)
        elif jt is JoinType.LEFT_OUTER:
            rows = Interval(left.rows.lo, _addsafe(eq_hi, left.rows.hi))
        elif jt is JoinType.RIGHT_OUTER:
            rows = Interval(right.rows.lo, _addsafe(eq_hi, right.rows.hi))
        else:  # FULL_OUTER
            rows = Interval(max(left.rows.lo, right.rows.lo),
                            _addsafe(eq_hi,
                                     _addsafe(left.rows.hi, right.rows.hi)))

        parts = stream.parts
        self._inexact()
        d = self._spend(
            Interval(0, _mulsafe(stream.batches.hi, 5)), exact=False)
        # per-output-batch rows: one output batch per STREAM batch, so the
        # match-multiplicity estimate bounds it tighter than total rows
        if rows.hi == INF:
            batch_rows = Interval(0, INF)
        else:
            batch_rows = Interval(0, min(
                rows.hi,
                _ceilsafe(_mulsafe(_hi_or(stream.batch_rows.hi, rows.hi),
                                   match if match != INF
                                   else _hi_or(build.rows.hi, 1)))))
        ndv = dict(left.col_ndv)
        ndv.update(right.col_ndv)
        rngs = dict(left.col_range)
        rngs.update(right.col_range)
        st = AbsState(rows, parts, Interval(0, parts),
                      Interval(0, stream.batches.hi), batch_rows, set(),
                      row_bytes, placement=node.placement, col_ndv=ndv,
                      col_range=rngs)
        if node.placement != "tpu":
            self._resident(node, 0, st, d)
            return st

        # memory: the build side is ONE single batch per partition
        # (RequireSingleBatch), resident for the whole stream side; a
        # shuffled build is bounded by its total too — skew could land
        # every row in one partition, same as the broadcast case
        if build.parts > 0 and build.rows.hi != INF:
            build_batch_bytes = _bucket(build.rows.hi) * build.row_bytes
        else:
            build_batch_bytes = INF
        out_batch_hi = INF if rows.hi == INF else \
            _bucket(batch_rows.hi) * row_bytes
        # lower bound: the build table ALONE must fit (a cross join's
        # exact output too) — this is the OOM_HAZARD trigger
        lo_bytes = 0
        if build.rows.lo > 0:
            lo_bytes = _bucket(-(-build.rows.lo // max(build.parts, 1))
                               ) * build.row_bytes
        if nested and node.condition is None and cross.lo > 0:
            per_part_out = -(-cross.lo // max(parts, 1))
            lo_bytes = max(lo_bytes, _bucket(per_part_out) * row_bytes)
        st.chain_bytes = _addsafe(stream.chain(), out_batch_hi)
        # the build side counts ONCE, not per task: a broadcast build is
        # one shared table, and for a shuffled build the total bounds the
        # sum of the per-partition tables the concurrent tasks hold
        hi_bytes = _addsafe(
            build_batch_bytes,
            _mulsafe(self.concurrency,
                     _addsafe(stream.chain(), out_batch_hi)))
        self._resident_floor(lo_bytes)
        self._resident(node, hi_bytes, st, d)
        return st

    # -- aggregates -----------------------------------------------------------
    def _aggregate(self, node, input_node, collapsed: bool,
                   chain_filters: int = 0) -> AbsState:
        from spark_rapids_tpu.exec.aggregate import COMPLETE, PARTIAL

        cin = self.visit(input_node)
        do_update = node.mode in (PARTIAL, COMPLETE)
        grouped = bool(node.grouping)
        n_keys = len(node.grouping)
        inter_attrs = node._inter_attrs
        inter_bytes = _row_bytes(inter_attrs, self.physical)
        lazy_ok = all(a.data_type is not DataType.STRING
                      for a in inter_attrs)
        n_str_aggs = sum(
            1 for op, _e, dt in node._update_ops()
            if dt is DataType.STRING and op in ("min", "max"))
        is_tpu = node.placement == "tpu"

        # group-count bound from the key tuple's distinct stats (INF when
        # any key column lacks stats); bounds rows, batch shapes, and —
        # through them — every downstream estimate
        G = _keys_ndv(node.key_exprs, cin.col_ndv) if grouped else 1

        # output rows: <= input rows (groups), >= 1 per non-empty partition
        # when grouped; exactly one default row for the ungrouped final
        if grouped:
            if do_update:
                # each partition emits its local groups: <= G per partition
                hi = min(_hi_or(cin.rows.hi, INF),
                         _mulsafe(_hi_or(cin.nonempty.hi, cin.parts or 1),
                                  G))
                rows = Interval(min(cin.nonempty.lo, cin.rows.lo),
                                hi if hi != INF else cin.rows.hi)
            else:
                # merge/final: hash-partitioned groups are globally disjoint
                hi = min(_hi_or(cin.rows.hi, INF), G)
                rows = Interval(min(1, cin.rows.lo),
                                hi if hi != INF else cin.rows.hi)
        # the ungrouped update program (exec/aggregate.py
        # `_ungrouped_ok`): one dispatch a batch and one row a partition
        # that holds a batch, whatever the device finds in it
        ungrouped = is_tpu and do_update and node._ungrouped_ok()
        if not grouped:
            rows = Interval.exact(1) if node.mode != PARTIAL else \
                Interval(min(cin.nonempty.lo, cin.rows.lo) if ungrouped
                         else 0, cin.nonempty.hi)
        batches = cin.nonempty if node.mode == PARTIAL else \
            Interval(1 if (not grouped and node.mode != PARTIAL)
                     else cin.nonempty.lo, _hi_or(cin.nonempty.hi, 1))
        if not grouped and node.mode in (COMPLETE,) or \
                (not grouped and not do_update):
            batches = Interval.exact(1)
        # stats for consumers: pass-through ids survive; with a finite row
        # bound every output column holds at most that many distinct values
        out_ndv = {a.expr_id: cin.col_ndv[a.expr_id]
                   for a in node.output if a.expr_id in cin.col_ndv}
        if rows.hi != INF:
            for a in node.output:
                out_ndv[a.expr_id] = min(
                    out_ndv.get(a.expr_id, 1 << 62), int(rows.hi))
        st = AbsState(rows, cin.parts, batches.clamp_hi(cin.parts or 1),
                      batches, Interval(0, _hi_or(cin.batch_rows.hi,
                                                  cin.rows.hi)),
                      set(), _row_bytes(node.output, self.physical),
                      placement=node.placement, col_ndv=out_ndv)
        if not is_tpu:
            self._resident(node, 0, st, Interval.exact(0))
            return st

        # dispatch model (mirrors exec/aggregate.TpuHashAggregateExec) ----
        from spark_rapids_tpu.shuffle.exchange import LAZY_PIECE_CAP_BYTES

        inter_width = sum(
            (self.physical(a.data_type).itemsize + 1)
            for a in inter_attrs) or 1
        upd_lazy = (self._agg_lazy and lazy_ok and do_update
                    and cin.parts <= self.conf.get(C.AGG_LAZY_MAX_PARTS)
                    and cin.batch_bytes != INF
                    and _bucket(cin.batch_rows.hi) * inter_width
                    <= LAZY_PIECE_CAP_BYTES)
        exact = (cin.batches.is_exact and cin.nonempty.is_exact
                 and not cin.lazy_tail)
        asm = 0 if (upd_lazy or ungrouped) else (2 + n_str_aggs)
        merge_asm = 0 if lazy_ok else (2 + n_str_aggs)
        # a compacted output re-buckets to its group count; a lazy output
        # keeps the INPUT capacity (padded lanes), so only the compacted
        # case may shrink the modeled batch shape
        compacts = not upd_lazy if do_update else not lazy_ok
        if grouped and G != INF and compacts:
            st.batch_rows = st.batch_rows.clamp_hi(int(G))
        if ungrouped:
            st.batch_rows = st.batch_rows.clamp_hi(1)
        if do_update:
            per_batch = 1 + asm
            d = cin.batches.scale(per_batch)
            # one merge per extra batch within a partition
            extra = Interval(
                max(0, cin.batches.lo - max(cin.nonempty.hi, 1))
                if cin.nonempty.hi != INF else 0,
                max(0, _hi_or(cin.batches.hi, 0)
                    - (cin.nonempty.lo or 0)))
            if cin.batches.is_exact and cin.nonempty.is_exact:
                extra = Interval.exact(cin.batches.lo - cin.nonempty.lo)
            d = d.add(extra.scale(1 + merge_asm))
        else:
            d = cin.batches.scale(1 + merge_asm)
        emit = Interval.exact(0)
        if node.mode != PARTIAL:
            # final projection once per partition holding groups
            if grouped:
                emit = cin.nonempty
                if not cin.nonempty.is_exact:
                    exact = False
            else:
                emit = Interval.exact(1)
        d = d.add(emit)
        d = self._spend(d, exact=exact)
        ident = (tuple(e.fingerprint() for e in node.key_exprs),
                 tuple(op for op, _e, _dt in node._update_ops()))
        self._compiles("agg_update" if do_update else "agg_merge", ident,
                       cin.kernel_buckets())
        if node.mode != PARTIAL:
            self._compiles("agg_final_project", ident, [0])
        # memory: the live input chain + buffer lanes at input capacity +
        # the emitted output
        lanes = _mulsafe(_bucket(cin.batch_rows.hi)
                         if cin.batch_rows.hi != INF else INF,
                         inter_width)
        self._resident(
            node,
            _mulsafe(self.concurrency,
                     _addsafe(cin.chain(),
                              _addsafe(lanes, st.batch_bytes))),
            st, d)
        return st

    # -- fused stages ----------------------------------------------------------
    @staticmethod
    def _stage_donates(node, n_variants: int, has_limit: bool) -> bool:
        """Whether the fused stage is GUARANTEED to donate at runtime, so
        subtracting the consumed input keeps the pessimistic peak ceiling
        sound: only the simple (one-variant, no-limit) form dispatches the
        donated program, and only on OWNED input batches — which an
        upload/scan input always produces (exchange-fed inputs may be
        shared bucket pieces that never donate, so they get no credit)."""
        from spark_rapids_tpu.exec.transitions import HostToDeviceExec
        from spark_rapids_tpu.io.scan import TpuFileScanExec

        if n_variants != 1 or has_limit:
            return False
        return isinstance(node.input_node,
                          (HostToDeviceExec, TpuFileScanExec))

    def _fused_stage(self, node) -> AbsState:
        from spark_rapids_tpu.exec import basic as B
        from spark_rapids_tpu.exec.expand import TpuExpandExec

        if node.agg_form:
            # the aggregate's update kernel IS the stage program; the
            # chain members below it fold into that one trace
            agg = node.members[0]
            st = self._aggregate(agg, node.input_node, collapsed=True)
            self.report.nodes.append(NodeEstimate(
                node.node_name(), self._depth, st.rows,
                st.batch_bytes, Interval.exact(0), node_id=id(node),
                placement=getattr(node, "placement", "tpu")))
            return st

        cin = self.visit(node.input_node)
        n_variants = getattr(node, "_n_variants", 1)
        row_changing = getattr(node, "_row_changing", False)
        live_shared = getattr(node, "_live_shared", True)
        has_limit = getattr(node, "_limit", None) is not None

        # row/batch + stats transfer through the member chain (bottom-up):
        # filters scale the row estimate by their selectivity, projections
        # and expands re-map the column stats the way the schema moves
        from spark_rapids_tpu.ops.base import AttributeReference as _AR

        rows = cin.rows
        ndv = dict(cin.col_ndv)
        rngs = dict(cin.col_range)
        for m in reversed(node.members):
            if isinstance(m, B.TpuFilterExec):
                sel = _filter_selectivity(m.condition, ndv, rngs)
                rows = Interval(0, rows.hi if rows.hi == INF
                                else int(-(-rows.hi * sel // 1)))
            elif isinstance(m, TpuExpandExec):
                rows = rows.scale(len(m.projections))
                nxt = {}
                for oi, a in enumerate(m.output_attrs):
                    tot = 0
                    for proj in m.projections:
                        n = _expr_ndv(proj[oi], ndv)
                        if n == INF:
                            tot = None
                            break
                        tot += n
                    if tot is not None:
                        nxt[a.expr_id] = tot
                ndv = nxt
                rngs = {}
            elif isinstance(m, B.TpuLocalLimitExec):
                rows = rows.clamp_hi(m.limit * max(cin.parts, 1))
            elif isinstance(m, B.TpuProjectExec):
                nxt = {}
                nxt_rng = {}
                for a, e in zip(m.output, m.project_list):
                    n = _expr_ndv(e, ndv)
                    if n != INF:
                        nxt[a.expr_id] = n
                    if isinstance(e, _AR) and e.expr_id in rngs:
                        nxt_rng[a.expr_id] = rngs[e.expr_id]
                ndv = nxt
                rngs = nxt_rng
        batches = cin.batches.scale(n_variants)
        lazy = False
        if row_changing and not has_limit:
            lazy = self._filter_lazy
        per_batch = n_variants
        if row_changing:
            per_batch += (1 if live_shared else n_variants)  # compact plan
            per_batch += n_variants                          # gather
        exact = cin.batches.is_exact and not cin.lazy_tail
        spend_iv = cin.batches.scale(per_batch)
        if has_limit:
            # a limit can stop the stage early only when a partition feeds
            # it multiple batches
            if not (cin.batches.is_exact and cin.nonempty.is_exact
                    and cin.batches.hi <= max(cin.nonempty.hi, 0)):
                exact = False
                spend_iv = Interval(
                    min(cin.nonempty.lo * per_batch, spend_iv.lo),
                    spend_iv.hi)
                batches = Interval(min(cin.nonempty.lo, batches.lo),
                                   batches.hi)
        d = self._spend(spend_iv, exact=exact)
        # one XLA program per (variant, bucket): exec/fused.py builds a
        # distinct _program(variant) per live-column variant
        for v in range(n_variants):
            self._compiles(
                "fused_stage",
                (tuple(type(m).__name__ for m in node.members), v),
                cin.kernel_buckets())
        row_bytes = _row_bytes(node.output, self.physical)
        batch_rows = cin.batch_rows if not row_changing or lazy \
            else cin.batch_rows.with_lo(0)
        if row_changing and not lazy and batch_rows.hi != INF and \
                rows.hi != INF and cin.rows.hi not in (0, INF):
            # compacted stage output re-buckets by surviving rows; carry
            # the member filters' combined selectivity onto the batch shape
            batch_rows = Interval(
                batch_rows.lo,
                max(1, int(-(-batch_rows.hi * rows.hi // cin.rows.hi))))
        st = AbsState(rows, cin.parts, cin.nonempty.with_lo(
            0 if row_changing else cin.nonempty.lo),
            batches, batch_rows,
            set(cin.buckets) if (lazy or not row_changing) else set(),
            row_bytes, lazy_tail=lazy, placement="tpu", col_ndv=ndv,
            col_range=rngs)
        chain_in = cin.chain()
        if self.donation and chain_in != INF and \
                cin.batch_bytes != INF and \
                self._stage_donates(node, n_variants, has_limit):
            # buffer donation: the stage consumes its input batch into its
            # output (donate_argnums on the stage program), so the input's
            # bytes never coexist with the output's — subtract them from
            # the pipeline chain estimate
            chain_in = max(0, chain_in - cin.batch_bytes)
        st.chain_bytes = _addsafe(chain_in, st.batch_bytes)
        self._resident(
            node,
            _mulsafe(self.concurrency,
                     _addsafe(chain_in,
                              _mulsafe(2 if row_changing else 1,
                                       st.batch_bytes))),
            st, d)
        return st


def _addsafe(a, b):
    if a == INF or b == INF:
        return INF
    return a + b


def _ceilsafe(v):
    if v == INF:
        return INF
    return int(math.ceil(v))


def _mulsafe(a, b):
    if a == INF or b == INF:
        return INF
    return a * b


def _hi_or(v, default):
    return default if v == INF else v


# ---------------------------------------------------------------------------
# Entry points
# ---------------------------------------------------------------------------
def resolve_budget(conf: "C.TpuConf",
                   device_manager=None) -> int:
    """hbmBudgetBytes conf override, else the device manager's budget."""
    override = conf.get(C.RESOURCE_HBM_BUDGET)
    if override:
        return override
    if device_manager is not None:
        return device_manager.hbm_budget
    from spark_rapids_tpu.memory.device_manager import TpuDeviceManager

    mgr = TpuDeviceManager._instance
    return mgr.hbm_budget if mgr is not None and mgr._initialized else 0


def analyze_plan(plan: PhysicalExec, conf: "C.TpuConf",
                 budget: Optional[int] = None,
                 device_manager=None,
                 measured_stats=None) -> PlanResourceReport:
    """Bottom-up abstract interpretation; never raises on violations.

    measured_stats: optional {id(TpuQueryStageExec): MapOutputStats} —
    the adaptive loop's runtime channel (aqe/loop.py): materialized
    stages are charged from MEASURED sizes, replacing the leaf priors
    of everything already executed below them."""
    if budget is None:
        budget = resolve_budget(conf, device_manager)
    from spark_rapids_tpu.engine.async_exec import in_checked_mode

    # no donation credit inside a checked replay: the replay runs with
    # donation OFF (engine/async_exec), so its re-analysis must predict
    # the undonated peak — exactly the run happening because memory is
    # already tight
    donation = bool(conf.get(C.BUFFER_DONATION)) and (
        bool(device_manager is not None and device_manager.is_tpu)
        or bool(conf.get(C.BUFFER_DONATION_ASSUME_SUPPORTED))) and \
        not in_checked_mode()
    report = _Analyzer(conf, budget, donation=donation,
                       measured_stats=measured_stats).run(plan)
    _attach_wall_prediction(report, conf)
    return report


def _attach_wall_prediction(report: PlanResourceReport,
                            conf: "C.TpuConf") -> None:
    """Price the plan's predicted wall time with the fitted cost model
    (obs/calibrate.py) when one is active: classes with enough samples
    at their calibrated coefficients, the rest at the flat
    deadline.costPerDispatchMs cold-start fallback. A plan analyzed
    before any calibration keeps predicted_wall_ns=None (and the render
    line absent) — the estimator is additive, never load-bearing."""
    try:
        if not conf.get(C.OBS_CALIBRATION_ENABLED):
            return
        from spark_rapids_tpu.obs import calibrate as CAL

        model = CAL.active_model()
        if model is None:
            return
        lo, hi, calibrated, fallback = model.predict_report(
            report,
            flat_cost_ms=conf.get(C.DEADLINE_COST_PER_DISPATCH_MS),
            min_samples=conf.get(C.OBS_CALIBRATION_MIN_SAMPLES),
            host_model=CAL.active_host_model())
        if not calibrated:
            return
        report.predicted_wall_ns = Interval(
            int(lo), INF if hi == INF else int(hi))
        report.wall_calibrated = list(calibrated)
        report.wall_fallback = list(fallback)
    except Exception:  # noqa: BLE001 - calibration is best-effort
        report.predicted_wall_ns = None


def check_resources(plan: PhysicalExec, conf: "C.TpuConf",
                    budget: Optional[int] = None,
                    device_manager=None) -> PlanResourceReport:
    """Analyze and, per conf, raise on fatal violations. The report is
    attached to the raised error's `report` attribute either way."""
    report = analyze_plan(plan, conf, budget, device_manager)
    fatal = [v for v in report.violations if v.kind in FATAL_KINDS]
    if fatal and conf.get(C.RESOURCE_ANALYSIS_FAIL):
        err = ResourceAnalysisError(fatal)
        err.report = report
        raise err
    return report
