"""Range-aware int64->int32 narrowing tests.

XLA emulates int64 on TPU as 32-bit pairs (9.18x measured cost,
BENCH_I64_r04.json). `rapids.tpu.sql.int64.narrowing.enabled` lets device
kernels compute logically-int64 expressions in int32 lanes when static
value-range metadata (`vrange`) proves the result identical. These tests
pin the PROOF OBLIGATIONS: narrowing must never change a result, at any
boundary, for any expression shape — the CPU oracle never narrows
(EvalContext narrowing is device-only), so equivalence checks are
independent.

Reference analog: the reference keeps cuDF columns at their logical width
(no narrowing pass exists in CUDA where int64 is native,
GpuColumnVector.java); this subsystem is TPU-specific by design.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from spark_rapids_tpu.columnar.dtypes import DataType
from spark_rapids_tpu.plan import functions as F

from tests.harness import (
    IntGen,
    assert_tpu_and_cpu_are_equal_collect,
    gen_df,
)

I32_MAX = (1 << 31) - 1
I32_MIN = -(1 << 31)


@pytest.fixture(autouse=True)
def _narrowing_on():
    """The process-wide flag follows the LAST executed query's conf
    (TpuConf.sync_int64_narrowing) — pin it on for these unit tests so an
    earlier narrowing-off query elsewhere in the session can't leak in."""
    from spark_rapids_tpu.columnar.batch import set_int64_narrowing

    set_int64_narrowing(True)
    yield
    set_int64_narrowing(True)


# ---------------------------------------------------------------------------
# unit: narrow_colv / vrange plumbing
# ---------------------------------------------------------------------------


def test_narrow_colv_narrowing_and_gates():
    from spark_rapids_tpu.ops.values import ColV, narrow_colv

    data = jnp.array([1, -5, I32_MAX, 0], dtype=jnp.int64)
    valid = jnp.array([True, True, True, False])
    # in-range vrange -> int32 view, values preserved
    cv = narrow_colv(ColV(DataType.INT64, data, valid,
                          vrange=(-5, I32_MAX)))
    assert cv.data.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(cv.data),
                                  np.asarray(data).astype(np.int32))
    # out-of-range / unknown vrange -> untouched
    for vr in [None, (0, I32_MAX + 1), (I32_MIN - 1, 0)]:
        cv = narrow_colv(ColV(DataType.INT64, data, valid, vrange=vr))
        assert cv.data.dtype == jnp.int64
    # non-INT64 untouched even with a range
    d32 = jnp.array([1, 2], dtype=jnp.int32)
    cv = narrow_colv(ColV(DataType.INT32, d32, valid[:2], vrange=(1, 2)))
    assert cv.data.dtype == jnp.int32


def test_narrow_conf_gate():
    from spark_rapids_tpu.columnar.batch import (
        int64_narrowing_enabled,
        set_int64_narrowing,
    )
    from spark_rapids_tpu.ops.values import ColV, narrow_colv

    data = jnp.array([1, 2], dtype=jnp.int64)
    valid = jnp.array([True, True])
    set_int64_narrowing(False)
    try:
        assert not int64_narrowing_enabled()
        cv = narrow_colv(ColV(DataType.INT64, data, valid, vrange=(1, 2)))
        assert cv.data.dtype == jnp.int64
    finally:
        set_int64_narrowing(True)


def test_host_upload_attaches_vrange():
    from spark_rapids_tpu.columnar.batch import HostColumnarBatch, \
        HostColumnVector

    hb = HostColumnarBatch(
        [HostColumnVector(DataType.INT64,
                          np.array([3, -7, 11], dtype=np.int64),
                          np.array([True, True, True]))], 3)
    dev = hb.to_device()
    # quantized to ladder bounds (power-of-two; see quantize_vrange)
    assert dev.columns[0].vrange == (-8, 15)


def test_serde_roundtrip_recovers_vrange():
    """TPB1 bytes carry no vrange (spill/shuffle/broadcast); the re-upload
    min/max pass must recover one, so a spilled-and-restored batch narrows
    again downstream."""
    from spark_rapids_tpu.columnar.batch import HostColumnarBatch, \
        HostColumnVector
    from spark_rapids_tpu.columnar.serde import (
        deserialize_batch,
        serialize_batch,
    )

    hb = HostColumnarBatch(
        [HostColumnVector(DataType.INT64,
                          np.array([100, -3, 77], dtype=np.int64),
                          np.array([True, True, True]))], 3)
    back = deserialize_batch(serialize_batch(hb))
    dev = back.to_device()
    assert dev.columns[0].vrange == (-4, 127)


def test_conf_flip_selects_kernel_flavor():
    """The narrowing flag is read at kernel TRACE time, so it salts every
    jit-cache key: flipping rapids.tpu.sql.int64.narrowing.enabled selects
    a different compiled program WITHOUT flushing the other flavor — two
    sessions with different settings can interleave without thrashing."""
    import spark_rapids_tpu as srt
    from spark_rapids_tpu.columnar.batch import int64_narrowing_enabled
    from spark_rapids_tpu.engine import jit_cache

    s = srt.new_session()
    try:
        assert int64_narrowing_enabled()
        on = jit_cache.get_or_build(("probe", 1), lambda: object())
        assert jit_cache.get_or_build(("probe", 1), lambda: object()) is on
        before = jit_cache.stats()["entries"]
        s.conf.set("rapids.tpu.sql.int64.narrowing.enabled", True)  # no-op
        assert jit_cache.stats()["entries"] == before
        s.conf.set("rapids.tpu.sql.int64.narrowing.enabled", False)
        assert not int64_narrowing_enabled()
        # same logical key now resolves to the narrowing-off flavor...
        off = jit_cache.get_or_build(("probe", 1), lambda: object())
        assert off is not on
        # ...and the narrowing-on flavor survived the flip
        s.conf.set("rapids.tpu.sql.int64.narrowing.enabled", True)
        assert int64_narrowing_enabled()
        assert jit_cache.get_or_build(("probe", 1), lambda: object()) is on
    finally:
        s.conf.set("rapids.tpu.sql.int64.narrowing.enabled", True)
        s.stop()


def test_quantize_vrange_ladder():
    """vrange is jit-cache aux data: exact per-batch min/max would retrace
    every kernel per batch, so bounds quantize to a power-of-two ladder.
    Quantization must only WIDEN (containment preserves the proof)."""
    from spark_rapids_tpu.columnar.batch import quantize_vrange

    assert quantize_vrange(None) is None
    assert quantize_vrange((0, 0)) == (0, 0)
    assert quantize_vrange((5, 100)) == (0, 127)
    assert quantize_vrange((-1, 1)) == (-1, 1)
    assert quantize_vrange((-7, 11)) == (-8, 15)
    assert quantize_vrange((-8, 15)) == (-8, 15)  # idempotent on ladder
    assert quantize_vrange((-9, 16)) == (-16, 31)
    rng = np.random.default_rng(1)
    for _ in range(200):
        lo = int(rng.integers(-2**40, 2**40))
        hi = int(rng.integers(lo, 2**40))
        qlo, qhi = quantize_vrange((lo, hi))
        assert qlo <= lo and hi <= qhi
        assert quantize_vrange((qlo, qhi)) == (qlo, qhi)


def test_interval_rules_exact():
    """Static interval arithmetic must over-approximate, never under."""
    from spark_rapids_tpu.ops.arithmetic import (
        Add,
        Multiply,
        Pmod,
        Remainder,
        Subtract,
    )
    from spark_rapids_tpu.ops.base import BoundReference

    a = BoundReference(0, DataType.INT64, True)
    b = BoundReference(1, DataType.INT64, True)
    cases = [
        (Add(a, b), (0, 10), (-3, 4), (-3, 14)),
        (Subtract(a, b), (0, 10), (-3, 4), (-4, 13)),
        (Multiply(a, b), (-2, 3), (-5, 7), (-15, 21)),
        (Remainder(a, b), (-100, 50), (2, 10), (-9, 9)),
        (Pmod(a, b), (-100, 50), (2, 10), (0, 9)),
        # pmod sign follows the DIVISOR: negative divisors give negatives
        (Pmod(a, b), (-100, 50), (-10, -2), (-9, 0)),
        (Pmod(a, b), (-100, 50), (-10, 10), (-9, 9)),
        (Pmod(a, b), (5, 50), (3, 10), (0, 9)),
    ]
    for expr, li, ri, want in cases:
        got = expr._math_interval(li, ri)
        assert got == want, (type(expr).__name__, got, want)
        # brute-force containment over the lattice corners + interior
        rng = np.random.default_rng(0)
        xs = np.unique(np.concatenate(
            [np.array(li), rng.integers(li[0], li[1] + 1, 50)]))
        ys = np.unique(np.concatenate(
            [np.array(ri), rng.integers(ri[0], ri[1] + 1, 50)]))
        for x in xs:
            for y in ys:
                x, y = int(x), int(y)
                if isinstance(expr, (Remainder, Pmod)) and y == 0:
                    continue
                if isinstance(expr, Add):
                    v = x + y
                elif isinstance(expr, Subtract):
                    v = x - y
                elif isinstance(expr, Multiply):
                    v = x * y
                elif isinstance(expr, Pmod):
                    v = ((x % y) + y) % y if y != 0 else 0
                else:
                    v = int(np.fmod(x, y))
                assert want[0] <= v <= want[1], (
                    type(expr).__name__, x, y, v, want)


def test_interval_rules_fuzz_containment():
    """Randomized sweep: for random operand intervals and random in-range
    values, every op's claimed interval must contain the exact math result
    — the narrowing proof is only as sound as these bounds."""
    from spark_rapids_tpu.ops.arithmetic import (
        Add,
        Multiply,
        Pmod,
        Remainder,
        Subtract,
    )
    from spark_rapids_tpu.ops.base import BoundReference

    a = BoundReference(0, DataType.INT64, True)
    b = BoundReference(1, DataType.INT64, True)
    ops = {
        Add(a, b): lambda x, y: x + y,
        Subtract(a, b): lambda x, y: x - y,
        Multiply(a, b): lambda x, y: x * y,
        Remainder(a, b): lambda x, y: int(np.fmod(x, y)) if y else None,
        Pmod(a, b): lambda x, y: ((x % y) + y) % y if y else None,
    }
    rng = np.random.default_rng(17)
    for _ in range(300):
        lo1 = int(rng.integers(-2**33, 2**33))
        hi1 = lo1 + int(rng.integers(0, 2**20))
        lo2 = int(rng.integers(-2**33, 2**33))
        hi2 = lo2 + int(rng.integers(0, 2**20))
        xs = [lo1, hi1] + [int(v) for v in rng.integers(lo1, hi1 + 1, 4)]
        ys = [lo2, hi2] + [int(v) for v in rng.integers(lo2, hi2 + 1, 4)]
        for expr, fn in ops.items():
            iv = expr._math_interval((lo1, hi1), (lo2, hi2))
            if iv is None:
                continue
            for x in xs:
                for y in ys:
                    v = fn(x, y)
                    if v is None:
                        continue
                    assert iv[0] <= v <= iv[1], (
                        type(expr).__name__, (lo1, hi1), (lo2, hi2),
                        x, y, v, iv)


def test_static_vrange_through_expressions():
    from spark_rapids_tpu.ops.arithmetic import Add, Multiply
    from spark_rapids_tpu.ops.base import BoundReference
    from spark_rapids_tpu.ops.bind import static_vrange
    from spark_rapids_tpu.ops.literals import Literal

    a = BoundReference(0, DataType.INT64, True)
    e = Add(Multiply(a, Literal(3, DataType.INT64)),
            Literal(10, DataType.INT64))
    # outputs quantize to the ladder (they become batch-level aux data)
    assert static_vrange(e, [(0, 100)]) == (0, 511)
    assert static_vrange(e, [None]) is None
    assert static_vrange(a, [(5, 6)]) == (0, 7)


# ---------------------------------------------------------------------------
# end-to-end: boundary correctness (CPU oracle never narrows)
# ---------------------------------------------------------------------------


def _df_vals(s, vals, extra_cols=None):
    data = {"a": vals}
    schema = [("a", DataType.INT64)]
    for name, v in (extra_cols or {}).items():
        data[name] = v
        schema.append((name, DataType.INT64))
    return s.createDataFrame(data, schema)


def test_add_overflowing_int32_is_exact(session):
    # operands fit int32; their sum does not -> the interval rule must
    # refuse the narrow compute and the result must be int64-exact
    vals = [I32_MAX, I32_MAX - 1, 5, -3]
    assert_tpu_and_cpu_are_equal_collect(
        session,
        lambda s: _df_vals(s, vals).select(
            (F.col("a") + F.col("a")).alias("s"),
            (F.col("a") * F.lit(3)).alias("m"),
            (F.col("a") - F.lit(I32_MIN)).alias("d")))


def test_unary_minus_abs_at_int32_min(session):
    # -INT32_MIN and abs(INT32_MIN) wrap in an int32 lane but not int64
    vals = [I32_MIN, I32_MIN + 1, -1, 7]
    assert_tpu_and_cpu_are_equal_collect(
        session,
        lambda s: _df_vals(s, vals).select(
            (-F.col("a")).alias("n"),
            F.abs_(F.col("a")).alias("ab")))


def test_shift_on_narrowed_column_uses_logical_width(session):
    # shiftleft(a, 40) on an int32-narrowed LONG must shift as 64-bit
    vals = [1, 3, -2, 100]
    assert_tpu_and_cpu_are_equal_collect(
        session,
        lambda s: _df_vals(s, vals).select(
            F.shiftleft(F.col("a"), 40).alias("sl"),
            F.shiftright(F.col("a"), 1).alias("sr")))


def test_long_to_timestamp_cast_widens(session):
    # epoch-seconds * 1e6 exceeds int32 for any recent date
    vals = [1_700_000_000, 0, -5]
    assert_tpu_and_cpu_are_equal_collect(
        session,
        lambda s: _df_vals(s, vals).select(
            F.col("a").cast(DataType.TIMESTAMP).alias("ts")))


def test_groupby_sum_exceeding_int32_is_exact(session):
    # every element fits int32, per-group totals do not: segment_reduce
    # must accumulate 64-bit
    n = 600
    vals = [I32_MAX // 100] * n
    keys = [i % 3 for i in range(n)]
    assert_tpu_and_cpu_are_equal_collect(
        session,
        lambda s: s.createDataFrame(
            {"k": keys, "v": vals},
            [("k", DataType.INT64), ("v", DataType.INT64)])
        .groupBy("k").agg(F.sum("v").alias("s"), F.min("v").alias("mn"),
                          F.max("v").alias("mx")),
        ignore_order=True)


def test_window_running_sum_exceeding_int32_is_exact(session):
    from spark_rapids_tpu.plan.window_api import Window

    n = 400
    assert_tpu_and_cpu_are_equal_collect(
        session,
        lambda s: s.createDataFrame(
            {"k": [i % 2 for i in range(n)],
             "o": list(range(n)),
             "v": [I32_MAX // 50] * n},
            [("k", DataType.INT64), ("o", DataType.INT64),
             ("v", DataType.INT64)])
        .select(F.col("k"), F.col("o"),
                F.sum("v").over(
                    Window.partitionBy("k").orderBy("o")).alias("rs")),
        ignore_order=True)


def test_remainder_pmod_ring_exact(session):
    # mod results always fit the divisor bound -> narrowed chain is
    # ring-exact even when intermediate products would not fit
    vals = [I32_MAX, I32_MIN + 1, 123456789, -987654321, 17]
    assert_tpu_and_cpu_are_equal_collect(
        session,
        lambda s: _df_vals(s, vals).select(
            (F.col("a") % F.lit(97)).alias("m"),
            F.pmod(F.col("a"), F.lit(97)).alias("pm"),
            (F.col("a") % F.lit(-97)).alias("mn"),
            F.pmod(F.col("a"), F.lit(-97)).alias("pmn")))


def test_pmod_huge_divisor_fixup_is_exact(session):
    # pmod's sign fix-up computes m + r, which overflows an int32 lane when
    # |r| > 2^30 — and the division that follows makes the wrap non-exact.
    # The kernel must widen that step (pmod(-2147483646, -2147483647) was
    # 3 instead of -2147483646 before the fix).
    vals = [-(I32_MAX - 1), -5, I32_MAX, 7]
    assert_tpu_and_cpu_are_equal_collect(
        session,
        lambda s: _df_vals(s, vals).select(
            F.pmod(F.col("a"), F.lit(-I32_MAX)).alias("p1"),
            F.pmod(F.col("a"), F.lit(I32_MAX)).alias("p2"),
            (F.col("a") % F.lit(-I32_MAX)).alias("r1")))


def test_conditional_vrange_union(session):
    vals = [5, -3, 2, 9]
    assert_tpu_and_cpu_are_equal_collect(
        session,
        lambda s: _df_vals(s, vals).select(
            F.when(F.col("a") > F.lit(0), F.col("a"))
            .otherwise(F.lit(-1)).alias("c"),
            F.coalesce(F.col("a"), F.lit(0)).alias("co")))


def test_narrowing_off_matches_on(session):
    """The conf gate flips compute width only — results must be identical
    (run the same plan under both settings against the oracle)."""
    gens = [("k", IntGen(DataType.INT64, lo=0, hi=50)),
            ("v", IntGen(DataType.INT64, lo=-1000, hi=1000))]

    def q(s):
        return gen_df(s, gens, n=500).filter(F.col("v") > F.lit(-500)) \
            .groupBy("k").agg(F.sum("v").alias("s"), F.count("*").alias("c"))

    assert_tpu_and_cpu_are_equal_collect(
        session, q, ignore_order=True,
        extra_conf={"rapids.tpu.sql.int64.narrowing.enabled": True})
    assert_tpu_and_cpu_are_equal_collect(
        session, q, ignore_order=True,
        extra_conf={"rapids.tpu.sql.int64.narrowing.enabled": False})


# ---------------------------------------------------------------------------
# parquet footer statistics -> vrange
# ---------------------------------------------------------------------------


class TestParquetStatsVrange:
    def _write(self, tmp_path, vals, stats=True):
        import pyarrow as pa
        import pyarrow.parquet as pq

        path = str(tmp_path / "t.parquet")
        pq.write_table(
            pa.table({"a": pa.array(vals, type=pa.int64())}), path,
            compression="NONE", data_page_version="1.0",
            write_statistics=stats)
        return path

    def _scanned_vrange(self, path, dt):
        """The column as the parquet scan hands it to the device: Arrow's
        read of the split, `arrow_to_host_batch`, `to_device` (io/scan.py
        `_read_host`). The range is proven from the VALUES at upload
        (columnar.batch.host_value_range), whatever the footer says."""
        from spark_rapids_tpu.io.arrow_convert import arrow_to_host_batch
        from spark_rapids_tpu.io.scan import FileSplit, read_split
        from spark_rapids_tpu.ops.base import AttributeReference

        attrs = [AttributeReference("a", dt)]
        table = read_split(FileSplit(path, "parquet"), attrs)
        return arrow_to_host_batch(table, attrs).to_device().columns[0].vrange

    def test_scan_attaches_vrange(self, tmp_path):
        path = self._write(tmp_path, [5, -2, 100])
        assert self._scanned_vrange(path, DataType.INT64) == (-2, 127)

    def test_no_stats_same_vrange(self, tmp_path):
        path = self._write(tmp_path, [5, -2, 100], stats=False)
        assert self._scanned_vrange(path, DataType.INT64) == (-2, 127)

    def test_orc_footer_stats_vrange(self, tmp_path):
        import pyarrow as pa
        from pyarrow import orc as po

        from spark_rapids_tpu.io import orc_device as OD
        from spark_rapids_tpu.io.scan import _orc_stats_vrange
        from spark_rapids_tpu.ops.base import AttributeReference

        path = str(tmp_path / "t.orc")
        po.write_table(
            pa.table({"a": pa.array([7, -3, 1000], type=pa.int64())}),
            path, compression="uncompressed")
        with open(path, "rb") as f:
            meta = OD.parse_file_meta(f.read())
        a = AttributeReference("a", DataType.INT64)
        assert _orc_stats_vrange(a, meta) == (-4, 1023)
        a32 = AttributeReference("a", DataType.INT32)
        assert _orc_stats_vrange(a32, meta) is None

    def test_device_scan_carries_vrange_and_is_exact(self, session,
                                                     tmp_path):
        # end-to-end: scanned column + its value range + agg, vs oracle
        vals = [int(x) for x in
                np.random.default_rng(7).integers(-10**6, 10**6, 2000)]
        path = self._write(tmp_path, vals)
        assert_tpu_and_cpu_are_equal_collect(
            session,
            lambda s: s.read.parquet(path).select(
                (F.col("a") + F.lit(1)).alias("a1")),
            ignore_order=True)


def test_footer_vrange_verification_drops_corrupt_stats():
    """ADVICE r3: footer min/max stats are a value-correctness proof for
    narrowing, and writers have shipped corrupt stats. verify_footer_vranges
    must drop a claim the decoded data contradicts (losing the optimization,
    never correctness) and keep a claim the data satisfies."""
    import jax.numpy as jnp

    from spark_rapids_tpu.columnar.batch import ColumnVector
    from spark_rapids_tpu.columnar.dtypes import DataType
    from spark_rapids_tpu.io.scan import verify_footer_vranges

    data = jnp.asarray([100, -3, 77_000, 0], dtype=jnp.int64)
    valid = jnp.asarray([True, True, True, False])
    honest = ColumnVector(DataType.INT64, data, valid, vrange=(-4, 131071))
    # claims (-4, 127) but the data holds 77_000 in a valid lane
    corrupt = ColumnVector(DataType.INT64, data, valid, vrange=(-4, 127))
    # claim on a fully-null column is unverifiable -> kept
    allnull = ColumnVector(DataType.INT64, data,
                           jnp.zeros((4,), bool), vrange=(0, 1))
    cols = {"h": honest, "c": corrupt, "n": allnull}
    verify_footer_vranges(cols)
    assert cols["h"].vrange == (-4, 131071)
    assert cols["c"].vrange is None
    assert cols["n"].vrange == (0, 1)
