"""The grouped aggregate over dictionary codes (exec/dense_agg.py), the
scan that feeds it (Arrow hands a dictionary-encoded STRING column over
as codes: io/scan.py, io/arrow_convert.py), the planner's rule for it
(plan/spmd.py `_streams_dense`) and TPC-H Q1 through all three against the
benchmark's plain reference (benchmark/actions/q1.py: the one reference of
Q1 in the repo). PR 37."""

import os
import sys

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from spark_rapids_tpu.exec import dense_agg as DA
from spark_rapids_tpu.io import scan as SCAN
from spark_rapids_tpu.plan import functions as F
from spark_rapids_tpu.utils import metrics as M

from tests.harness import assert_rows_equal, run_on_cpu, run_on_tpu

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")
FLOAT_CONF = {"rapids.tpu.sql.variableFloatAgg.enabled": True,
              "rapids.tpu.sql.incompatibleOps.enabled": True}


def _keys(rng, values, n, null_every=0):
    out = rng.choice(np.array(values, dtype=object), size=n).astype(object)
    if null_every:
        out[::null_every] = None
    return pa.array(out.tolist(), pa.string())


def _table(seed, n, k1, k2, k3=None, null_keys=0):
    rng = np.random.default_rng(seed)
    v = rng.integers(-1000, 1000, n).astype(np.int64)
    d = rng.uniform(-5.0, 5.0, n)
    cols = {"k1": _keys(rng, k1, n, null_keys),
            "k2": _keys(rng, k2, n, null_keys and null_keys + 2),
            "v": pa.array(v, mask=rng.random(n) < 0.1),
            "d": pa.array(d, mask=rng.random(n) < 0.1),
            "s": _keys(rng, ["x", "yy", "zzz", "w"], n, 5)}
    if k3 is not None:
        cols["k3"] = _keys(rng, k3, n)
    return pa.table(cols)


def _aggs(df, keys):
    return df.groupBy(*keys).agg(
        F.sum("v").alias("sv"), F.count("v").alias("cv"),
        F.count("*").alias("n"), F.avg("d").alias("ad"),
        F.sum("d").alias("sd"), F.min("v").alias("mnv"),
        F.max("d").alias("mxd"), F.min("s").alias("mns"),
        F.max("s").alias("mxs"))


def _both_paths(session, monkeypatch, df_fn):
    """The query by the dense table, by the sort (the table's limit taken
    away, in the test), and by the CPU operators; returns the counters'
    movement (dense, sort) of the first and of the second."""
    def counted():
        before = (M.dense_agg_batch_count(), M.sort_agg_batch_count())
        rows = run_on_tpu(session, df_fn, extra_conf=FLOAT_CONF)
        return rows, (M.dense_agg_batch_count() - before[0],
                      M.sort_agg_batch_count() - before[1])

    dense, moved_dense = counted()
    with monkeypatch.context() as mp:
        mp.setattr(DA, "MAX_GROUPS", 0)
        by_sort, moved_sort = counted()
    cpu = run_on_cpu(session, df_fn)
    assert_rows_equal(cpu, dense, ignore_order=True, approx_float=1e-9)
    assert_rows_equal(by_sort, dense, ignore_order=True, approx_float=1e-9)
    return moved_dense, moved_sort


CASES = {
    # name: (tables of one directory, keys, filter on d or None)
    "two_keys_null_keys_null_inputs": (
        [_table(1, 3000, ["A", "N", "R"], ["F", "O"], null_keys=7)],
        ("k1", "k2"), None),
    "filter_keeps_nothing": (
        [_table(2, 500, ["A", "N"], ["F", "O"])], ("k1", "k2"), 1e9),
    "filter_keeps_some": (
        [_table(3, 2000, ["A", "N", "R"], ["F"])], ("k1", "k2"), 0.0),
    "an_empty_file_beside_a_full_one": (
        [_table(4, 0, ["A"], ["F"]), _table(5, 800, ["A", "N"], ["F", "O"])],
        ("k1", "k2"), None),
    "two_files_with_different_dictionaries": (
        [_table(6, 900, ["a", "b"], ["F", "O"]),
         _table(7, 700, ["b", "c", "d"], ["O", "P"], null_keys=9)],
        ("k1", "k2"), None),
    "three_keys": (
        [_table(8, 2500, ["A", "N", "R"], ["F", "O"], ["x", "y", "z"])],
        ("k1", "k2", "k3"), None),
    "one_key": ([_table(9, 1000, list("abcdefg"), ["F"])], ("k1",), None),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_dense_and_sort_agree(session, monkeypatch, tmp_path, case):
    tables, keys, bound = CASES[case]
    for i, t in enumerate(tables):
        pq.write_table(t, str(tmp_path / f"part-{i}.parquet"),
                       row_group_size=400)

    def df_fn(s):
        df = s.read.parquet(str(tmp_path))
        if bound is not None:
            df = df.filter(F.col("d") > F.lit(bound))
        return _aggs(df, keys)

    moved_dense, moved_sort = _both_paths(session, monkeypatch, df_fn)
    assert moved_dense[0] >= 1 and moved_dense[1] == 0, moved_dense
    assert moved_sort[0] == 0 and moved_sort[1] >= 1, moved_sort


def test_a_table_over_the_constant_takes_the_sort(session, tmp_path):
    """9 x 9 values: radices 16 x 16 = 256 slots, over MAX_GROUPS. The
    sort-based aggregate runs, and `sortAggBatches` says so."""
    values = [f"v{i}" for i in range(9)]
    pq.write_table(_table(10, 3000, values, values),
                   str(tmp_path / "t.parquet"))
    assert DA.radices([9, 9]) is None and DA.radices([3, 2]) == (4, 4)

    def df_fn(s):
        return _aggs(s.read.parquet(str(tmp_path)), ("k1", "k2"))

    before = (M.dense_agg_batch_count(), M.sort_agg_batch_count())
    rows = run_on_tpu(session, df_fn, extra_conf=FLOAT_CONF)
    assert M.dense_agg_batch_count() == before[0]
    assert M.sort_agg_batch_count() > before[1]
    assert_rows_equal(run_on_cpu(session, df_fn), rows, ignore_order=True,
                      approx_float=1e-9)


def test_an_ungrouped_aggregate_counts_in_neither(session, tmp_path):
    """It has no grouping to take either way: it counts in a third, the
    ungrouped update program's `ungroupedAggBatches`."""
    pq.write_table(_table(11, 500, ["A"], ["F"]), str(tmp_path / "t.parquet"))
    before = (M.dense_agg_batch_count(), M.sort_agg_batch_count())
    ungrouped = M.ungrouped_agg_batch_count()
    rows = run_on_tpu(session, lambda s: s.read.parquet(str(tmp_path))
                      .agg(F.sum("v").alias("sv")))
    assert len(rows) == 1
    assert (M.dense_agg_batch_count(), M.sort_agg_batch_count()) == before
    assert M.ungrouped_agg_batch_count() == ungrouped + 1


def test_group_reduce_blocks_a_long_float_sum():
    """2^16 lanes of one group in f32: the blocked sum stays within a few
    ulps of the float64 total where a running f32 sum would drift."""
    import jax
    import jax.numpy as jnp

    from spark_rapids_tpu.columnar.dtypes import DataType
    from spark_rapids_tpu.ops.values import ColV

    n = 1 << 16
    rng = np.random.default_rng(0)
    x = rng.uniform(900.0, 105000.0, n).astype(np.float32)
    key = ColV(DataType.INT32, jnp.zeros(n, jnp.int32), jnp.ones(n, bool))
    val = ColV(DataType.FLOAT32, jnp.asarray(x), jnp.ones(n, bool))
    # tpulint: jit-cache -- one-shot program of a unit test
    outs, groups = jax.jit(lambda: DA.group_reduce(
        [key], (2,), jnp.ones(n, bool), ("sum", "count"), [val, val],
        (np.dtype(np.float32), np.dtype(np.int64))))()
    assert int(groups) == 1
    want = float(x.astype(np.float64).sum())
    assert abs(float(outs[1][0][0]) - want) / want < 5e-7
    assert int(outs[2][0][0]) == n
    running = np.float32(0.0)
    for block in x.reshape(-1, 64):      # what it guards against
        for v in block:
            running = np.float32(running + v)
    assert abs(float(running) - want) / want > 5e-7


# ---------------------------------------------------------------------------
# the scan: dictionary codes from Arrow
# ---------------------------------------------------------------------------
def _strings_file(path):
    n = 4000
    rng = np.random.default_rng(3)
    # sorted, so that each row group of 1000 rows holds its own values
    flag = np.sort(rng.choice(np.array(list("ABCDEFGH"), dtype=object), n))
    flag = flag.astype(object)
    flag[::11] = None
    plain = np.array([f"text-{i:06d}" for i in range(n)], dtype=object)
    table = pa.table({"flag": pa.array(flag.tolist(), pa.string()),
                      "plain": pa.array(plain.tolist(), pa.string()),
                      "v": np.arange(n, dtype=np.int64)})
    pq.write_table(table, path, row_group_size=1000,
                   use_dictionary=["flag"])
    return table


def test_dictionary_column_through_the_host_scan_equals_arrows(session,
                                                               tmp_path):
    path = str(tmp_path / "s.parquet")
    table = _strings_file(path)
    md = pq.ParquetFile(path).metadata
    dicts = {tuple(sorted(set(v for v in pq.ParquetFile(path).read_row_group(
        g, columns=["flag"]).column(0).to_pylist() if v is not None)))
        for g in range(md.num_row_groups)}
    assert len(dicts) > 1, "the row groups were to differ in their values"
    before = M.dispatch_count()
    rows = run_on_tpu(session, lambda s: s.read.parquet(path)
                      .select("v", "flag"))
    assert sorted(rows) == sorted(zip(table.column("v").to_pylist(),
                                      table.column("flag").to_pylist()))
    assert M.dispatch_count() > before
    assert session.last_query_metrics["encodedColumns"] >= 1


def test_only_a_dictionary_encoded_string_is_asked_for_as_codes(session,
                                                                tmp_path):
    from spark_rapids_tpu.io.arrow_convert import schema_attrs

    path = str(tmp_path / "s.parquet")
    _strings_file(path)
    attrs = schema_attrs(pq.read_schema(path))
    splits = SCAN.plan_splits("parquet", [path], {}, session.conf)
    assert [list(SCAN.dict_chunk_ndvs(sp, attrs, session.conf))
            for sp in splits] == [["flag"]] * len(splits)
    ndvs = SCAN.dict_chunk_ndvs(splits[0], attrs, session.conf)
    assert all(1 <= n <= 8 for n in ndvs["flag"])
    session.conf.set("rapids.tpu.sql.encoded.enabled", False)
    assert SCAN.dict_chunk_ndvs(splits[0], attrs, session.conf) == {}


def test_splits_with_the_same_values_share_one_sorted_dictionary():
    """Row groups list their values in the order they first appear; what
    the scan hands on is one dictionary in byte order, whatever the
    order was, so two splits' partials merge with no remap."""
    from spark_rapids_tpu.io.arrow_convert import _dictionary_column

    def column(chunks):
        return pa.chunked_array([pa.array(c, pa.string()).dictionary_encode()
                                 for c in chunks])

    a = _dictionary_column(column([["R", "A", None, "N"], ["N", "N", "A"]]),
                           0.5)
    b = _dictionary_column(column([["N", "R", "A", "A", None, None, "R"]]),
                           0.5)
    assert a.dictionary is b.dictionary and a.dictionary.is_sorted
    assert list(a.dictionary.host_values()) == ["A", "N", "R"]
    assert a.data.tolist() == [2, 0, 0, 1, 1, 1, 0]
    assert a.validity.tolist() == [True, True, False, True, True, True, True]
    assert a.decoded().to_pylist() == ["R", "A", None, "N", "N", "N", "A"]
    # near-unique: the heuristic sends it back to be decoded
    assert _dictionary_column(column([["p", "q", "r"]]), 0.5) is None


# ---------------------------------------------------------------------------
# TPC-H Q1 from generated parquet, against the benchmark's reference
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def q1_bench():
    """benchmark/actions/q1.py and its generator, as the harness loads
    them (benchmark/ on the path: the action imports `lib`)."""
    sys.path.insert(0, BENCH)
    try:
        from lib import harness, tpch_gen

        yield (harness.load_module("actions", "q1"), tpch_gen,
               harness.load_config(os.path.join(
                   BENCH, "configs", "tpch_sf1_parquet_q1.json")))
    finally:
        sys.path.remove(BENCH)


def _q1_on(session, q1_bench, seed, root, **layout):
    action, gen, config = q1_bench
    arrays = gen.gen_tables(0.004, seed, ["lineitem"])
    paths = gen.write_parquet(arrays, str(root),
                              dict(config["layout"], **layout))
    for key, value in config["conf"].items():
        session.conf.set(key, value)
    df = action.build({"lineitem": session.read.parquet(paths["lineitem"])})
    return action, arrays, df


@pytest.mark.parametrize("seed", (5, 3141592653))
def test_q1_matches_the_plain_reference(session, q1_bench, tmp_path, seed):
    action, arrays, df = _q1_on(session, q1_bench, seed, tmp_path)
    got = action.run(df, None)
    assert got.agg_batches[0] >= 1 and got.agg_batches[1] == 0
    numbers = action.compare(action.reference(arrays), [got])[0]
    assert all(n["value"] <= n["limit"] for n in numbers), numbers
    assert [r[:2] for r in got] == sorted(r[:2] for r in got)
    assert len(got) == 6
    m = session.last_query_metrics
    assert m["cpuFallbackEvents"] == 0 and m["spmdStages"] == 0
    assert m["denseAggBatches"] == got.agg_batches[0]
    assert m["sortAggBatches"] == 0


def test_q1_at_a_second_seed_compiles_no_program(session, q1_bench,
                                                 tmp_path):
    """Set-up compiles Q1's programs once; another seed's files (other
    values in another order in every dictionary page) find every one of
    them built: jax reports no backend compile (engine/compile_clock.py)."""
    from spark_rapids_tpu.engine import compile_clock

    action, _, df = _q1_on(session, q1_bench, 11, tmp_path / "a")
    first = action.run(df, None)
    warm = action.run(df, None)
    assert list(first) == list(warm)
    built = compile_clock.step_seconds()["compile_or_load"]
    action, arrays, df = _q1_on(session, q1_bench, 2718281828,
                                tmp_path / "b")
    got = action.run(df, None)
    assert compile_clock.step_seconds()["compile_or_load"] == built
    assert all(n["value"] <= n["limit"] for n in
               action.compare(action.reference(arrays), [got])[0])


def test_the_planner_streams_a_dense_group_by_on_one_device(session,
                                                            q1_bench,
                                                            tmp_path):
    """One device in the mesh: no SPMD stage is planned for Q1 (it was
    planned and degraded at SF1: ROADMAP S6), and the analyzer bounds the
    aggregate's rows from the dictionaries, not from the input rows. On
    the full mesh the stage is planned as before."""
    _, _, df = _q1_on(session, q1_bench, 7, tmp_path)
    text = df.explain()
    assert "TpuSpmdStage" not in text and "TpuHashAggregateExec" in text
    session.conf.set("rapids.tpu.sql.spmd.meshDevices", 0)
    assert "TpuSpmdStage" in df.explain()


def test_q1s_final_aggregate_input_is_packed_in_bounded_programs(
        session, q1_bench, tmp_path):
    """The cell's shape on the CPU: eight splits, so the one coalesced
    reduce task concatenates 8 map outputs x 8 hash partitions = 64 lazy
    slices of 13 columns. Its `coalesce-concat` span says what that cost:
    832 validity operands in one `_pack3d` call, packed in runs (13 + 1,
    7 + 1, 4 + 1, 2 + 1 programs, the live masks' one, the pack kernel)
    where 114 eager concatenates were."""
    action, arrays, df = _q1_on(session, q1_bench, 13, tmp_path,
                                files_per_table=8)
    session.conf.set("rapids.tpu.obs.tracing.enabled", True)
    got = action.run(df, None)
    assert all(n["value"] <= n["limit"] for n in
               action.compare(action.reference(arrays), [got])[0])
    concats = [sp.attrs for sp in
               session.last_query_trace.find("coalesce-concat")]
    assert all(set(a) >= {"pieces", "operands", "programs"}
               for a in concats)
    final = [a for a in concats if a["pieces"] > 1]
    assert len(final) == 1, concats
    assert final[0]["pieces"] == 64 and final[0]["operands"] == 832
    assert final[0]["programs"] == 32 < 40
