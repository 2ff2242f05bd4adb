"""Hash aggregate equivalence tests (reference: HashAggregatesSuite.scala,
hash_aggregate_test.py)."""

import pytest

from spark_rapids_tpu.columnar.dtypes import DataType
from spark_rapids_tpu.plan import functions as F

from tests.harness import (
    BoolGen,
    FloatGen,
    IntGen,
    StringGen,
    assert_tpu_and_cpu_are_equal_collect,
    gen_df,
)

FLOAT_CONF = {"rapids.tpu.sql.variableFloatAgg.enabled": True}


def test_groupby_sum_count(session):
    assert_tpu_and_cpu_are_equal_collect(
        session,
        lambda s: gen_df(s, [("k", IntGen(DataType.INT32)),
                             ("v", IntGen(DataType.INT64))], n=300)
        .groupBy("k").agg(F.sum("v").alias("s"), F.count("v").alias("c")),
        ignore_order=True)


@pytest.mark.parametrize("policy", ["always", "never"])
def test_groupby_compact_sync_policies(session, policy):
    """The partial-aggregate stage must produce identical results whether it
    compacts with a row-count sync ('always') or stays fully lazy with
    device-scalar row counts through the exchange ('never') — the policy is
    a backend-latency tradeoff, never a semantics change."""
    assert_tpu_and_cpu_are_equal_collect(
        session,
        lambda s: gen_df(s, [("k", IntGen(DataType.INT64, lo=0, hi=50)),
                             ("v", IntGen(DataType.INT64)),
                             ("f", FloatGen())], n=500)
        .groupBy("k").agg(F.sum("v").alias("s"), F.count("*").alias("c"),
                          F.max("f").alias("m")),
        ignore_order=True,
        extra_conf={"rapids.tpu.engine.aggCompactSync": policy,
                    **FLOAT_CONF})


def test_devprobe_override(monkeypatch):
    from spark_rapids_tpu.utils import devprobe

    devprobe.reset()
    monkeypatch.setenv("SRT_FENCE_MS", "42.5")
    assert devprobe.fence_cost_ms() == 42.5
    devprobe.reset()


@pytest.mark.parametrize("fence_ms,expect_lazy", [("50", True), ("0.1", False)])
def test_auto_policy_follows_fence_cost(session, monkeypatch, fence_ms,
                                        expect_lazy):
    """'auto' must pick the sync-free lazy update kernel exactly when the
    measured fence cost crosses the threshold (and the batch is small
    enough for the exchange's zero-copy piece cap)."""
    from spark_rapids_tpu.utils import devprobe
    import spark_rapids_tpu.engine.jit_cache as jc

    devprobe.reset()
    monkeypatch.setenv("SRT_FENCE_MS", fence_ms)
    seen = []
    orig = jc.get_or_build

    def spy(key, builder, **kwargs):
        if isinstance(key, tuple) and key and key[0] == "agg_update":
            seen.append(key[1])  # the lazy flag
        return orig(key, builder, **kwargs)

    monkeypatch.setattr(jc, "get_or_build", spy)
    try:
        assert_tpu_and_cpu_are_equal_collect(
            session,
            lambda s: gen_df(s, [("k", IntGen(DataType.INT64, lo=0, hi=20)),
                                 ("v", IntGen(DataType.INT64))], n=400)
            .groupBy("k").agg(F.sum("v").alias("s")),
            ignore_order=True,
            # the HOST-LOOP update kernel's policy is under test: keep the
            # SPMD stage compiler (default on since r14) out of the way
            extra_conf={"rapids.tpu.engine.aggCompactSync": "auto",
                        "rapids.tpu.sql.spmd.enabled": False})
    finally:
        devprobe.reset()
    assert seen and all(flag is expect_lazy for flag in seen), seen


def test_auto_policy_big_batch_stays_compact(session, monkeypatch):
    """Even on a high-fence backend, an update output too big for the
    exchange's zero-copy cap must compact (lazy would just move the sync
    into the shuffle slicer and inflate downstream lanes)."""
    from spark_rapids_tpu.utils import devprobe
    import spark_rapids_tpu.engine.jit_cache as jc

    devprobe.reset()
    monkeypatch.setenv("SRT_FENCE_MS", "50")
    seen = []
    orig = jc.get_or_build

    def spy(key, builder, **kwargs):
        if isinstance(key, tuple) and key and key[0] == "agg_update":
            seen.append(key[1])
        return orig(key, builder, **kwargs)

    monkeypatch.setattr(jc, "get_or_build", spy)
    try:
        # 300k rows x (8+1)x2 bytes of inter buffers > the 4 MiB lazy cap
        assert_tpu_and_cpu_are_equal_collect(
            session,
            lambda s: gen_df(s, [("k", IntGen(DataType.INT64, lo=0, hi=20)),
                                 ("v", IntGen(DataType.INT64))], n=300_000)
            .groupBy("k").agg(F.sum("v").alias("s")),
            ignore_order=True,
            # host-loop policy pin (see test_auto_policy_follows_fence_cost)
            extra_conf={"rapids.tpu.engine.aggCompactSync": "auto",
                        "rapids.tpu.sql.spmd.enabled": False})
    finally:
        devprobe.reset()
    assert seen and all(flag is False for flag in seen), seen


def test_agg_compact_sync_conf_checker():
    import spark_rapids_tpu.conf as C

    with pytest.raises(ValueError):
        C.TpuConf({"rapids.tpu.engine.aggCompactSync": "bogus"}).get(
            C.AGG_COMPACT_SYNC)
    assert C.TpuConf().get(C.AGG_COMPACT_SYNC) == "auto"


def test_groupby_min_max(session):
    assert_tpu_and_cpu_are_equal_collect(
        session,
        lambda s: gen_df(s, [("k", IntGen(DataType.INT16)),
                             ("v", IntGen(DataType.INT32))], n=200)
        .groupBy("k").agg(F.min("v").alias("lo"), F.max("v").alias("hi")),
        ignore_order=True)


def test_groupby_avg_float(session):
    assert_tpu_and_cpu_are_equal_collect(
        session,
        lambda s: gen_df(s, [("k", IntGen(DataType.INT32)),
                             ("v", FloatGen(DataType.FLOAT32))], n=200)
        .groupBy("k").agg(F.avg("v").alias("a")),
        ignore_order=True, approx_float=1e-5, extra_conf=FLOAT_CONF)


def test_groupby_string_key(session):
    assert_tpu_and_cpu_are_equal_collect(
        session,
        lambda s: gen_df(s, [("k", StringGen(max_len=6)),
                             ("v", IntGen(DataType.INT64))], n=250)
        .groupBy("k").agg(F.sum("v").alias("s"), F.count("*").alias("c")),
        ignore_order=True)


def test_groupby_multi_key(session):
    assert_tpu_and_cpu_are_equal_collect(
        session,
        lambda s: gen_df(s, [("a", IntGen(DataType.INT32)),
                             ("b", BoolGen()),
                             ("v", IntGen(DataType.INT64))], n=300)
        .groupBy("a", "b").agg(F.sum("v").alias("s")),
        ignore_order=True)


def test_ungrouped_reduction(session):
    assert_tpu_and_cpu_are_equal_collect(
        session,
        lambda s: gen_df(s, [("v", IntGen(DataType.INT64))], n=128)
        .agg(F.sum("v").alias("s"), F.count("v").alias("c"),
             F.min("v").alias("lo"), F.max("v").alias("hi")))


def test_ungrouped_empty_input_default_row(session):
    assert_tpu_and_cpu_are_equal_collect(
        session,
        lambda s: s.createDataFrame({"v": []}, [("v", "long")])
        .agg(F.sum("v").alias("s"), F.count("v").alias("c")))


def test_count_star(session):
    assert_tpu_and_cpu_are_equal_collect(
        session,
        lambda s: gen_df(s, [("k", IntGen(DataType.INT32)),
                             ("v", IntGen(DataType.INT64))], n=100)
        .groupBy("k").agg(F.count("*").alias("c")),
        ignore_order=True)


def test_first_last(session):
    # first/last depend on encounter order; restrict to one partition
    assert_tpu_and_cpu_are_equal_collect(
        session,
        lambda s: gen_df(s, [("k", IntGen(DataType.INT32, lo=0, hi=5)),
                             ("v", IntGen(DataType.INT64))], n=64,
                         num_partitions=1)
        .groupBy("k").agg(F.first("v").alias("f"), F.last("v").alias("l")),
        ignore_order=True)


def test_distinct(session):
    assert_tpu_and_cpu_are_equal_collect(
        session,
        lambda s: gen_df(s, [("a", IntGen(DataType.INT32, lo=0, hi=8)),
                             ("b", BoolGen())], n=200).distinct(),
        ignore_order=True)


def test_all_null_group_sum_is_null(session):
    assert_tpu_and_cpu_are_equal_collect(
        session,
        lambda s: s.createDataFrame(
            {"k": [1, 1, 2], "v": [None, None, 5]},
            [("k", "int"), ("v", "long")])
        .groupBy("k").agg(F.sum("v").alias("s"), F.count("v").alias("c")),
        ignore_order=True)


def test_dataframe_count_action(session):
    from tests.harness import run_on_cpu, run_on_tpu

    data = {"v": list(range(57))}

    def build(s):
        return s.createDataFrame(data, [("v", "long")]).filter(F.col("v") > 10)

    cpu = run_on_cpu(session, lambda s: build(s).agg(F.count("*").alias("c")))
    tpu = run_on_tpu(session, lambda s: build(s).agg(F.count("*").alias("c")))
    assert cpu == tpu == [(46,)]


def test_string_min_max_on_device(session):
    # string min/max now runs ON DEVICE (arg-extreme over chunked u64 keys)
    assert_tpu_and_cpu_are_equal_collect(
        session,
        lambda s: gen_df(s, [("k", IntGen(DataType.INT32, lo=0, hi=6)),
                             ("v", StringGen(max_len=5))], n=120)
        .groupBy("k").agg(F.min("v").alias("lo"), F.max("v").alias("hi"),
                          F.count("v").alias("c")),
        ignore_order=True)


def test_groupby_double_key_exact(session):
    # f64 keys must group exactly on the oracle-parity backend (no f32
    # narrowing merging distinct keys)
    assert_tpu_and_cpu_are_equal_collect(
        session,
        lambda s: s.createDataFrame(
            {"k": [1.0, 1.0 + 1e-12, 1.0, -0.0, 0.0, float("nan"),
                   float("nan")],
             "v": [1, 2, 3, 4, 5, 6, 7]},
            [("k", "double"), ("v", "long")])
        .groupBy("k").agg(F.count("v").alias("c")),
        ignore_order=True)


class TestStringMinMax:
    """Device string min/max via chunked-u64 arg-extreme reduction
    (rowkeys.segment_arg_extreme_string; reference: cudf groupby min/max on
    strings, AggregateFunctions.scala)."""

    def test_grouped_string_min_max(self, session):
        assert_tpu_and_cpu_are_equal_collect(
            session,
            lambda s: gen_df(s, [("k", IntGen(DataType.INT64, lo=0, hi=8)),
                                 ("t", StringGen(max_len=10))],
                             n=400, num_partitions=3)
            .groupBy("k").agg(F.min("t").alias("mn"),
                              F.max("t").alias("mx"),
                              F.count("t").alias("c")),
            ignore_order=True)

    def test_ungrouped_string_min_max(self, session):
        assert_tpu_and_cpu_are_equal_collect(
            session,
            lambda s: gen_df(s, [("t", StringGen(max_len=20))], n=150)
            .agg(F.min("t").alias("mn"), F.max("t").alias("mx")))

    def test_string_min_max_prefix_ties_and_nulls(self, session):
        def q(s):
            return s.createDataFrame(
                {"k": [1, 1, 1, 2, 2, 3],
                 "t": ["abcdefghij", "abcdefghi", "abcdefghija",
                       None, "z", None]},
                [("k", DataType.INT64), ("t", DataType.STRING)]) \
                .groupBy("k").agg(F.min("t").alias("mn"),
                                  F.max("t").alias("mx"))

        from tests.harness import run_on_cpu

        cpu = sorted(run_on_cpu(session, q))
        assert cpu == [(1, "abcdefghi", "abcdefghija"),
                       (2, "z", "z"), (3, None, None)]
        assert_tpu_and_cpu_are_equal_collect(session, q, ignore_order=True)

    def test_computed_string_input_falls_back(self, session):
        from tests.harness import assert_tpu_fallback_collect

        assert_tpu_fallback_collect(
            session,
            lambda s: gen_df(s, [("k", IntGen(DataType.INT64, lo=0, hi=4)),
                                 ("t", StringGen(max_len=6))], n=100)
            .groupBy("k").agg(F.min(F.concat(F.col("t"),
                                             F.col("t"))).alias("m")),
            fallback_exec="CpuHashAggregateExec",
            ignore_order=True,
            extra_conf={"rapids.tpu.sql.test.allowedNonTpu":
                        "CpuHashAggregateExec,CpuShuffleExchangeExec,"
                        "CpuCoalesceBatchesExec"})

    def test_string_min_through_projected_scan(self, session):
        # scan-chain collapse must not substitute a computed string into
        # the min input (the collapse guard)
        def q(s):
            df = gen_df(s, [("k", IntGen(DataType.INT64, lo=0, hi=4)),
                            ("a", StringGen(max_len=4)),
                            ("b", StringGen(max_len=4))], n=120)
            df2 = df.select("k", F.concat(F.col("a"),
                                          F.col("b")).alias("c"))
            return df2.groupBy("k").agg(F.min("c").alias("m"))

        assert_tpu_and_cpu_are_equal_collect(session, q, ignore_order=True)


# ---------------------------------------------------------------------------
# The ungrouped update program: one program a batch, one row out, nothing
# fetched from the device (exec/aggregate.py `_build_ungrouped_update_kernel`)
# ---------------------------------------------------------------------------
HOST_LOOP = {"rapids.tpu.sql.spmd.enabled": False, **FLOAT_CONF}


def _six_aggs(df):
    """sum, count, count(*), min, max and avg together, behind a filter."""
    return df.filter(F.col("k") > 3).agg(
        F.sum("v").alias("s"), F.count("v").alias("c"),
        F.count("*").alias("n"), F.min("f").alias("lo"),
        F.max("f").alias("hi"), F.avg("v").alias("a"))


def _kvf(s, n, parts, k_hi=10):
    return gen_df(s, [("k", IntGen(DataType.INT32, lo=0, hi=k_hi)),
                      ("v", IntGen(DataType.INT64, lo=-1000, hi=1000)),
                      ("f", FloatGen(DataType.FLOAT64, no_nans=True))],
                  n=n, num_partitions=parts)


def _ungrouped_sources():
    """name -> (df_fn, update batches the ungrouped program should take)."""
    def several_partitions(s):
        return _six_aggs(_kvf(s, 1000, 4))

    def one_partition_filtered_out(s):
        # partition 0 holds k = 0 only: every row of it fails k > 3
        k = [0] * 100 + [7] * 100
        return _six_aggs(s.createDataFrame(
            {"k": k, "v": list(range(200)),
             "f": [float(i) / 7 for i in range(200)]},
            [("k", "int"), ("v", "long"), ("f", "double")],
            num_partitions=2))

    def all_filtered_out(s):
        return _six_aggs(_kvf(s, 300, 3, k_hi=3))

    def all_null_column(s):
        return _six_aggs(s.createDataFrame(
            {"k": [5] * 64, "v": [None] * 64, "f": [None] * 64},
            [("k", "int"), ("v", "long"), ("f", "double")],
            num_partitions=2))

    return {"several_partitions": (several_partitions, 4),
            "one_partition_filtered_out": (one_partition_filtered_out, 2),
            "all_filtered_out": (all_filtered_out, 3),
            "all_null_column": (all_null_column, 2)}


def _agg_batch_counts():
    from spark_rapids_tpu.utils import metrics as M

    return (M.ungrouped_agg_batch_count(), M.dense_agg_batch_count(),
            M.sort_agg_batch_count())


def _moved(before):
    return tuple(a - b for a, b in zip(_agg_batch_counts(), before))


@pytest.mark.parametrize("case", sorted(_ungrouped_sources()))
def test_ungrouped_partial_equals_the_oracle(session, case):
    from tests.harness import assert_rows_equal, run_on_cpu, run_on_tpu

    df_fn, batches = _ungrouped_sources()[case]
    before = _agg_batch_counts()
    got = run_on_tpu(session, df_fn, extra_conf=HOST_LOOP)
    assert _moved(before) == (batches, 0, 0)
    assert session.last_query_metrics["ungroupedAggBatches"] == batches
    assert_rows_equal(run_on_cpu(session, df_fn), got, approx_float=1e-9)
    assert len(got) == 1


def test_ungrouped_all_partitions_empty_gives_the_default_row(session):
    """No batch at all: no partial runs, `_emit`'s default row answers."""
    from tests.harness import run_on_tpu

    def df_fn(s):
        return _six_aggs(s.createDataFrame(
            {"k": [], "v": [], "f": []},
            [("k", "int"), ("v", "long"), ("f", "double")]))

    before = _agg_batch_counts()
    assert run_on_tpu(session, df_fn, extra_conf=HOST_LOOP) == \
        [(None, 0, 0, None, None, None)]
    assert _moved(before) == (0, 0, 0)


@pytest.mark.parametrize("policy", ["auto", "always", "never"])
def test_ungrouped_partial_ignores_the_compact_sync_policy(session, policy):
    """The policy trades a sync against padded lanes; this path has
    neither, so every value of the key runs the same program."""
    from tests.harness import run_on_tpu

    df_fn, batches = _ungrouped_sources()["several_partitions"]
    before = _agg_batch_counts()
    got = run_on_tpu(session, df_fn, extra_conf={
        "rapids.tpu.engine.aggCompactSync": policy, **HOST_LOOP})
    assert _moved(before) == (batches, 0, 0)
    assert got == run_on_tpu(session, df_fn, extra_conf=HOST_LOOP)


def _write_kvf(tmp_path, rows=900):
    import numpy as np
    import pyarrow as pa
    import pyarrow.parquet as pq

    rng = np.random.default_rng(3)
    pq.write_table(pa.table({
        "k": rng.integers(0, 10, rows).astype(np.int32),
        "v": rng.integers(-1000, 1000, rows).astype(np.int64),
        "f": rng.random(rows)}), str(tmp_path / "t.parquet"),
        row_group_size=rows)
    return str(tmp_path)


@pytest.mark.parametrize("batch_rows, batches", [(10_000, 1), (300, 3)])
def test_ungrouped_partial_over_batches_of_one_partition(session, tmp_path,
                                                         batch_rows, batches):
    """Several batches a partition: each is one ungrouped program, and the
    running merge folds them as before."""
    from tests.harness import assert_rows_equal, run_on_cpu, run_on_tpu

    path = _write_kvf(tmp_path)

    def df_fn(s):
        return _six_aggs(s.read.parquet(path))

    conf = {"rapids.tpu.sql.reader.batchSizeRows": batch_rows, **HOST_LOOP}
    before = _agg_batch_counts()
    got = run_on_tpu(session, df_fn, extra_conf=conf)
    assert _moved(before) == (batches, 0, 0)
    assert_rows_equal(run_on_cpu(session, df_fn), got, approx_float=1e-9)


@pytest.mark.parametrize("aggs", [
    lambda df: df.agg(F.min("t").alias("mn"), F.count("t").alias("c")),
    lambda df: df.agg(F.first("v").alias("fv"), F.sum("v").alias("s")),
], ids=["string_min", "first"])
def test_an_op_outside_the_ungrouped_program_keeps_todays_path(session, aggs):
    """A STRING min/max (a buffer that is not fixed-width) and first (an
    op whose empty state the merge does not take as its identity) stay on
    the keyless form of the sort path: right, and counted in none."""
    from tests.harness import assert_rows_equal, run_on_cpu, run_on_tpu

    def df_fn(s):
        return aggs(gen_df(s, [("t", StringGen(max_len=12)),
                               ("v", IntGen(DataType.INT64))],
                           n=150, num_partitions=1))

    before = _agg_batch_counts()
    got = run_on_tpu(session, df_fn, extra_conf=HOST_LOOP)
    assert _moved(before) == (0, 0, 0)
    assert_rows_equal(run_on_cpu(session, df_fn), got)


def test_ungrouped_ok_is_read_off_the_op_lists():
    from spark_rapids_tpu.exec import aggregate as A
    from spark_rapids_tpu.ops import aggregates as AG
    from spark_rapids_tpu.ops.base import Alias, AttributeReference

    v = AttributeReference("v", DataType.INT64, True)
    t = AttributeReference("t", DataType.STRING, True)

    def exec_of(funcs, grouping=()):
        return A.TpuHashAggregateExec(
            list(grouping), [Alias(f, f"a{i}") for i, f in enumerate(funcs)],
            A.PARTIAL, None)

    assert exec_of([AG.Sum(v), AG.Count(v), AG.Min(v), AG.Max(v),
                    AG.Average(v)])._ungrouped_ok()
    assert not exec_of([AG.Sum(v)], grouping=[v])._ungrouped_ok()
    assert not exec_of([AG.Min(t)])._ungrouped_ok()
    assert not exec_of([AG.Sum(v), AG.First(v)])._ungrouped_ok()
    assert not exec_of([AG.Last(v)])._ungrouped_ok()
    # every op of the program has its empty state among the merge's
    # identities: the two lists are kept together
    assert {op for f in (AG.Sum(v), AG.Count(v), AG.Min(v), AG.Max(v),
                         AG.Average(v))
            for _n, op, _e in f.update_aggs()} == A.UNGROUPED_UPDATE_OPS
    assert {op for f in (AG.Sum(v), AG.Count(v), AG.Min(v), AG.Max(v),
                         AG.Average(v))
            for _n, op in f.merge_aggs()} == A.UNGROUPED_MERGE_OPS


@pytest.mark.parametrize("op, empty_valid", [
    ("sum", False), ("min", False), ("max", False), ("count", True)])
def test_a_row_of_empty_states_merges_as_no_row(op, empty_valid):
    """What the ungrouped program leaves of a batch with no live row, and
    that `agg_merge`'s op over rows of it alone gives the default row's
    buffer again (sum / min / max NULL, count 0), over it and a real
    partial the real partial."""
    import jax.numpy as jnp
    import numpy as np

    from spark_rapids_tpu.exec import rowkeys as RK

    data = jnp.asarray(np.arange(8, dtype=np.int64))
    nothing = jnp.zeros((8,), bool)
    r, has = RK.reduce_all(op, data, nothing)
    assert bool(has) is empty_valid
    if empty_valid:
        assert int(r) == 0
    merge_op = "sum" if op == "count" else op
    # two partials of empty batches, then one real partial (value 5) too
    lanes = jnp.asarray(np.array([0, 0, 5, 0, 0, 0, 0, 0], np.int64))
    for live, want in ((2, (0, empty_valid)), (3, (5, True))):
        valid = jnp.asarray(np.array(
            [empty_valid, empty_valid, True] + [False] * 5)) \
            & (jnp.arange(8) < live)
        r, has = RK.reduce_all(merge_op, lanes, valid)
        assert bool(has) is want[1]
        if want[1]:
            assert int(r) == want[0]


def test_q6_shaped_partial_is_one_dispatch_and_fetches_nothing(
        session, monkeypatch):
    """Under a map task of the ungrouped partial nothing asks the device
    for a value: `jax.device_get` and a `host_rows()` that would have to
    fetch both raise there. One dispatch a partial batch, then the merge
    and the final projection."""
    from tests.harness import forbid_device_fetch_in_map_tasks, run_on_tpu

    tasks = forbid_device_fetch_in_map_tasks(monkeypatch)

    def q6(s):
        df = _kvf(s, 2000, 4)
        return df.filter((F.col("k") >= 2) & (F.col("k") < 8)
                         & (F.col("f") < 0.5)) \
            .agg(F.sum(F.col("f") * F.col("v")).alias("revenue"))

    got = run_on_tpu(session, q6, extra_conf=HOST_LOOP)
    assert len(got) == 1 and sorted(tasks) == [0, 1, 2, 3]
    m = session.last_query_metrics
    assert m["ungroupedAggBatches"] == 4
    # 4 uploads are no dispatch; 4 partials, the merge, the projection
    assert m["deviceDispatches"] == 4 + 2
    rep = session.last_resource_report
    assert rep.dispatches_exact
    assert rep.dispatches.lo == rep.dispatches.hi == m["deviceDispatches"]
