"""Single-program SPMD stage tests (plan/spmd.py + engine/spmd_exec.py).

The load-bearing claims, each pinned here:
- oracle equality: TPC-H q1/q5 over the SPMD path equal the CPU oracle on
  a 1-device mesh AND on the full 8-virtual-device mesh (same program,
  different mesh — ROADMAP open item 1's core promise);
- whole-query compilation (ROADMAP open item 2): q5's five INNER joins
  lower INTO the stage program (build broadcast via in-program
  all_gather), chained group-bys share ONE program, and both flagships
  run `deviceDispatches <= 3` at 4 AND 16 partitions (the tier-1 CI pin);
- encoded stage inputs: dictionary codes flow into the program (no
  stage-input boundary decode) with `lateMaterializations` no higher than
  the host-loop path;
- measured capacities: with AQE on, a stage whose input materialized
  takes the MEASURED row count instead of the analyzer's interval;
- graceful degradation: ineligible shapes, undersized exchange buckets /
  join expansions (the in-program overflow probes), mid-chain faults at
  the `spmd.stage` site, and checked replays all take the host-loop
  subtree with unchanged results — and a degrading stage DROPS its
  assembled [m, cap] input arrays before the host loop re-runs;
- static analysis: the resource analyzer's dispatch prediction contains
  the measured count in BOTH modes, and EXPLAIN surfaces the stage plus
  its coverage (`spmd stages: N of M stages`).
"""

import gc

import pytest

from spark_rapids_tpu.benchmarks import tpch
from spark_rapids_tpu.plan import functions as F

from tests.harness import (
    assert_rows_equal,
    assert_tpu_and_cpu_are_equal_collect,
    run_on_cpu,
    run_on_tpu,
)

SPMD_1DEV = {
    "rapids.tpu.sql.spmd.enabled": True,
    "rapids.tpu.sql.spmd.meshDevices": 1,
}
SPMD_FULL = {
    "rapids.tpu.sql.spmd.enabled": True,
    "rapids.tpu.sql.spmd.meshDevices": 0,
}
SPMD_OFF = {"rapids.tpu.sql.spmd.enabled": False}


def _tpch_q(qname, num_partitions=3):
    def f(s):
        tables = tpch.gen_tables(s, sf=0.0005,
                                 num_partitions=num_partitions)
        return tpch.QUERIES[qname](tables)

    return f


def _metrics_of(session, df_fn, extra_conf):
    got = run_on_tpu(session, df_fn, extra_conf=extra_conf)
    return got, dict(session.last_query_metrics)


# ---------------------------------------------------------------------------
# Oracle equality: the q1/q5 flagship shapes
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("qname", ["q1", "q5"])
def test_tpch_oracle_equality_one_device_mesh(session, qname):
    """q1 (string-keyed agg + absorbed sort) and q5 (join-fed agg with a
    string group key + float sort — the five INNER joins lower into the
    program) on a 1-chip mesh: the SPMD program must actually run
    (spmdStages == 1) and match the oracle."""
    df_fn = _tpch_q(qname)
    cpu = run_on_cpu(session, df_fn)
    got, m = _metrics_of(session, df_fn, SPMD_1DEV)
    assert m["spmdStages"] == 1, m
    assert m["collectiveBytes"] > 0, m
    if qname == "q5":
        assert m["spmdJoins"] == 5, m
    assert_rows_equal(cpu, got, ignore_order=True, approx_float=1e-9)


@pytest.mark.slow  # 8-device stage programs compile slowly on 1-core CI
@pytest.mark.parametrize("qname", ["q1", "q5"])
def test_tpch_oracle_equality_full_mesh(session, qname):
    """The SAME stage program over the full 8-virtual-device mesh — the
    in-program all_to_all (and q5's build-broadcast all_gather) actually
    cross shards."""
    df_fn = _tpch_q(qname)
    cpu = run_on_cpu(session, df_fn)
    got, m = _metrics_of(session, df_fn, SPMD_FULL)
    assert m["spmdStages"] == 1, m
    assert_rows_equal(cpu, got, ignore_order=True, approx_float=1e-9)


def test_plain_groupby_spmd(session):
    """A bare groupBy().agg() (no sort tail, no fused chain wrapper) also
    lowers — the output is m live-masked partitions, downloaded by the
    ordinary sink."""
    def df_fn(s):
        df = s.createDataFrame(
            {"k": [i % 7 for i in range(200)],
             "v": [float(i) for i in range(200)],
             "w": list(range(200))},
            schema=[("k", "long"), ("v", "double"), ("w", "long")],
            num_partitions=5)
        return df.groupBy("k").agg(
            F.sum("v").alias("sv"), F.avg("w").alias("aw"),
            F.count("*").alias("c"), F.max("v").alias("mv"))

    assert_tpu_and_cpu_are_equal_collect(
        session, df_fn, ignore_order=True, approx_float=1e-9,
        extra_conf=SPMD_1DEV)
    assert session.last_query_metrics["spmdStages"] == 1


def test_nullable_keys_and_values(session):
    """NULL group keys form their own group; all-null value groups emit
    NULL sums — the in-program key proxies and segment reductions must
    keep SQL null semantics through the exchange."""
    def df_fn(s):
        ks = [None if i % 5 == 0 else f"k{i % 3}" for i in range(60)]
        vs = [None if i % 4 == 0 else float(i) for i in range(60)]
        df = s.createDataFrame(
            {"k": ks, "v": vs},
            schema=[("k", "string"), ("v", "double")], num_partitions=4)
        return df.groupBy("k").agg(F.sum("v").alias("s"),
                                   F.count("v").alias("c"))

    assert_tpu_and_cpu_are_equal_collect(
        session, df_fn, ignore_order=True, approx_float=1e-9,
        extra_conf=SPMD_1DEV)
    assert session.last_query_metrics["spmdStages"] == 1


# ---------------------------------------------------------------------------
# In-program joins: oracle equality across seeds and partition counts
# ---------------------------------------------------------------------------
def _join_agg_query(seed, num_partitions):
    import numpy as np

    def f(s):
        rng = np.random.default_rng(seed)
        n, nb = 600, 40
        facts = s.createDataFrame(
            {"fk": rng.integers(0, nb, n).astype("int64"),
             "v": (rng.random(n) * 100).round(3),
             "tag": [["x", "y", "z"][i] for i in
                     rng.integers(0, 3, n)]},
            schema=[("fk", "long"), ("v", "double"), ("tag", "string")],
            num_partitions=num_partitions)
        dims = s.createDataFrame(
            {"dk": list(range(nb)),
             "grp": [f"g{i % 5}" for i in range(nb)],
             "w": [float(i % 7) for i in range(nb)]},
            schema=[("dk", "long"), ("grp", "string"), ("w", "double")],
            num_partitions=2)
        return (facts.filter(facts["tag"] == F.lit("x"))
                .join(dims, on=(facts["fk"] == dims["dk"]), how="inner")
                .filter(F.col("w") > F.lit(1.0))
                .groupBy("grp")
                .agg(F.sum("v").alias("sv"), F.count("*").alias("c")))

    return f


@pytest.mark.parametrize("parts", [4, 16])
@pytest.mark.parametrize("seed", [0, pytest.param(7, marks=pytest.mark.slow)])
def test_in_program_join_oracle_equality(session, seed, parts):
    """An INNER equi-join below the aggregate lowers into the program
    (build broadcast via all_gather, probe rows streaming on through the
    in-program exchange): oracle-equal across seeds and partition counts,
    with the join actually lowered (spmdJoins pinned)."""
    df_fn = _join_agg_query(seed, parts)
    cpu = run_on_cpu(session, df_fn)
    got, m = _metrics_of(session, df_fn, SPMD_1DEV)
    assert m["spmdStages"] == 1, m
    assert m["spmdJoins"] == 1, m
    assert m["deviceDispatches"] <= 3, m
    assert_rows_equal(cpu, got, ignore_order=True, approx_float=1e-9)


def test_join_lowering_disabled_still_matches(session):
    """spmd.joinLowering.enabled=false keeps the aggregate pipeline
    lowered but the join on the host loop — same results."""
    df_fn = _join_agg_query(3, 4)
    cpu = run_on_cpu(session, df_fn)
    conf = dict(SPMD_1DEV)
    conf["rapids.tpu.sql.spmd.joinLowering.enabled"] = False
    got, m = _metrics_of(session, df_fn, conf)
    assert m["spmdJoins"] == 0, m
    assert m["spmdStages"] == 1, m
    assert_rows_equal(cpu, got, ignore_order=True, approx_float=1e-9)


def test_join_expansion_overflow_degrades(session):
    """An undersized join expansion capacity trips the in-program
    overflow probe — the stage degrades to the host loop (never dropping
    a row) and still matches the oracle."""
    df_fn = _join_agg_query(1, 4)
    cpu = run_on_cpu(session, df_fn)
    conf = dict(SPMD_1DEV)
    conf["rapids.tpu.sql.spmd.joinRows"] = 1  # out_cap floor = 8
    got, m = _metrics_of(session, df_fn, conf)
    assert m["spmdStages"] == 0, m  # the degraded stage must not count
    assert_rows_equal(cpu, got, ignore_order=True, approx_float=1e-9)


# ---------------------------------------------------------------------------
# Stage chaining: one program for consecutive eligible stages
# ---------------------------------------------------------------------------
def _double_groupby(s, num_partitions=4):
    df = s.createDataFrame(
        {"k": [i % 17 for i in range(300)],
         "v": [i % 4 for i in range(300)]},
        schema=[("k", "long"), ("v", "long")],
        num_partitions=num_partitions)
    inner = df.groupBy("k").agg(F.count("*").alias("c"))
    return inner.groupBy("c").agg(F.count("*").alias("dist"))


@pytest.mark.parametrize("parts", [4, 16])
def test_chained_stages_one_program(session, parts):
    """q13-style double aggregation CHAINS inside one shard_map program:
    the inner stage's post-exchange merged buckets feed the outer stage
    in-trace — both segments count in spmdStages, but the whole chain is
    ONE device dispatch at any partition count."""
    cpu = run_on_cpu(session, lambda s: _double_groupby(s, parts))
    got, m = _metrics_of(session, lambda s: _double_groupby(s, parts),
                         SPMD_1DEV)
    assert m["spmdStages"] == 2, m
    assert m["deviceDispatches"] <= 3, m
    assert_rows_equal(cpu, got, ignore_order=True)


def test_chaining_disabled_still_matches(session):
    """spmd.chainStages.enabled=false falls back to two separate stage
    programs with a host re-assembly between — same results, more
    dispatches."""
    conf = dict(SPMD_1DEV)
    conf["rapids.tpu.sql.spmd.chainStages.enabled"] = False
    cpu = run_on_cpu(session, _double_groupby)
    got, m = _metrics_of(session, _double_groupby, conf)
    assert m["spmdStages"] == 2, m
    assert_rows_equal(cpu, got, ignore_order=True)


@pytest.mark.slow  # 8-device chained program: compile-heavy
def test_double_groupby_chained_full_mesh(session):
    """The chained program over the full 8-virtual-device mesh."""
    cpu = run_on_cpu(session, _double_groupby)
    got, m = _metrics_of(session, _double_groupby, SPMD_FULL)
    assert m["spmdStages"] == 2, m
    assert_rows_equal(cpu, got, ignore_order=True)


def test_chained_stage_fault_degrades_mid_query(session):
    """Fault injection at the `spmd.stage` site with a CHAINED stage:
    every program dispatch OOMs, the retry ladder exhausts, and the whole
    chain degrades to the host-loop subtree mid-query — results equal."""
    cpu = run_on_cpu(session, _double_groupby)
    conf = dict(SPMD_1DEV)
    conf.update({
        "rapids.tpu.test.faultInjection.enabled": True,
        "rapids.tpu.test.faultInjection.seed": 7,
        "rapids.tpu.test.faultInjection.sites": "spmd.stage",
        "rapids.tpu.test.faultInjection.rate": 1.0,
    })
    got, m = _metrics_of(session, _double_groupby, conf)
    assert m["spmdStages"] == 0, m  # the degraded chain must not count
    assert m["retries"] >= 1, m
    assert_rows_equal(cpu, got, ignore_order=True)


# ---------------------------------------------------------------------------
# The dispatch-count acceptance: one dispatch per stage chain,
# independent of the partition count (the tier-1 CI pin)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("qname", ["q1", "q5"])
def test_flagship_dispatches_independent_of_partition_count(session, qname):
    disp = {}
    host_loop_16 = None
    for parts in (4, 16):
        df_fn = _tpch_q(qname)
        conf = dict(SPMD_1DEV)
        conf["rapids.tpu.sql.shuffle.partitions"] = parts
        _, m = _metrics_of(session, df_fn, conf)
        assert m["spmdStages"] == 1, m
        disp[parts] = m["deviceDispatches"]
        if parts == 16:
            conf_off = dict(SPMD_OFF)
            conf_off["rapids.tpu.sql.shuffle.partitions"] = parts
            _, mh = _metrics_of(session, df_fn, conf_off)
            host_loop_16 = mh["deviceDispatches"]
    # the whole eligible pipeline — q5's joins included — is ONE program
    # dispatch; only the constant sink-side compaction of the live-masked
    # output adds to it
    assert disp[4] == disp[16], disp
    assert disp[16] <= 3
    assert disp[16] * 3 <= host_loop_16, (disp, host_loop_16)


def test_resource_prediction_contains_measured_in_both_modes(session):
    for conf in (SPMD_1DEV, SPMD_OFF):
        df_fn = _tpch_q("q1")
        _, m = _metrics_of(session, df_fn, conf)
        rep = session.last_resource_report
        assert rep is not None
        assert rep.dispatches.lo <= m["deviceDispatches"] \
            <= rep.dispatches.hi, (conf, m, rep.dispatches)
        if conf is SPMD_1DEV:
            assert rep.spmd_stages == 1
            assert rep.collective_bytes.lo <= m["collectiveBytes"] \
                <= rep.collective_bytes.hi, (m, rep.collective_bytes)
        else:
            assert rep.spmd_stages == 0


def test_q5_join_prediction_containment(session):
    """q5 with joins lowered: ONE program inside the host-loop subtree's
    dispatch interval, all five member joins covered (coverage line shows
    full lowering)."""
    df_fn = _tpch_q("q5")
    _, m = _metrics_of(session, df_fn, SPMD_1DEV)
    rep = session.last_resource_report
    assert m["spmdJoins"] == 5, m
    assert rep.dispatches.lo <= m["deviceDispatches"] \
        <= rep.dispatches.hi, (m, rep.dispatches)
    assert rep.spmd_stages == 1
    assert rep.total_stages == 1, rep.total_stages


def test_explain_surfaces_spmd_stage(session):
    tables = tpch.gen_tables(session, sf=0.0005, num_partitions=3)
    df = tpch.QUERIES["q1"](tables)
    session.conf.set("rapids.tpu.sql.spmd.enabled", True)
    session.conf.set("rapids.tpu.sql.spmd.meshDevices", 1)
    out = df.explain()
    assert "TpuSpmdStage(1)[PartialAgg->AllToAll->FinalAgg->Sort]" in out
    # coverage: N of M stages, so partial lowering is visible
    assert "spmd stages: 1 of 1 stages (collective bytes " in out
    # the wrapped members stay visible for plan introspection
    assert "TpuHashAggregateExec(partial)" in out
    assert "== Plan verification ==\nOK" in out


def test_explain_surfaces_join_lowering(session):
    tables = tpch.gen_tables(session, sf=0.0005, num_partitions=3)
    df = tpch.QUERIES["q5"](tables)
    session.conf.set("rapids.tpu.sql.spmd.enabled", True)
    session.conf.set("rapids.tpu.sql.spmd.meshDevices", 1)
    out = df.explain()
    assert "TpuSpmdStage(1)[Join*5->PartialAgg->AllToAll->FinalAgg->Sort]" \
        in out
    assert "spmd stages: 1 of 1 stages (collective bytes " in out


# ---------------------------------------------------------------------------
# Encoded stage inputs: codes flow into the program
# ---------------------------------------------------------------------------
@pytest.mark.usefixtures("device_string_decoder")
def test_encoded_stage_inputs_stay_codes(session, tmp_path):
    """Dictionary-encoded parquet strings enter the stage program as
    int32 CODES (filter rewritten to code space, group key grouped on
    codes, sort tail ordered through a code->rank LUT, output emitted
    encoded): lateMaterializations must be NO HIGHER than the host-loop
    path — the PR 9 stage-input boundary decode is closed."""
    import numpy as np
    import pyarrow as pa
    import pyarrow.parquet as pq

    rng = np.random.default_rng(0)
    n = 4000
    tbl = pa.table({
        "flag": rng.choice(["A", "B", "C", "N", "R"],
                           size=n).astype(object),
        "status": rng.choice(["open", "closed", "pending"],
                             size=n).astype(object),
        "v": rng.integers(0, 10_000, size=n)})
    path = str(tmp_path / "enc.parquet")
    pq.write_table(tbl, path, use_dictionary=True, row_group_size=1000)

    def df_fn(s):
        return (s.read.parquet(path)
                .filter(F.col("flag") == F.lit("A"))
                .groupBy("status").agg(F.count("*").alias("c"),
                                       F.sum("v").alias("t"))
                .orderBy("status"))

    _, mh = _metrics_of(session, df_fn, SPMD_OFF)
    got, m = _metrics_of(session, df_fn, SPMD_1DEV)
    cpu = run_on_cpu(session, df_fn)
    assert m["spmdStages"] == 1, m
    assert m["encodedColumns"] > 0, m
    assert m["lateMaterializations"] <= mh["lateMaterializations"], \
        (m["lateMaterializations"], mh["lateMaterializations"])
    assert_rows_equal(cpu, got, approx_float=1e-9)


# ---------------------------------------------------------------------------
# Measured capacities (AQE channel)
# ---------------------------------------------------------------------------
def test_measured_capacity_from_materialized_stage(session):
    """With AQE on, a stage whose input exchange already materialized
    takes the MEASURED MapOutputStats row count as its bucket bound
    (spmdMeasuredCaps pinned) — results equal either way."""
    def df_fn(s):
        df = s.createDataFrame(
            {"k": [i % 9 for i in range(400)],
             "g": [i % 3 for i in range(400)],
             "v": [float(i) for i in range(400)]},
            schema=[("k", "long"), ("g", "long"), ("v", "double")],
            num_partitions=4)
        # repartition materializes an exchange BELOW the aggregate
        # pipeline: with AQE on it becomes a measured query stage feeding
        # the SPMD program
        return (df.repartition(4, "k")
                .groupBy("g").agg(F.sum("v").alias("sv"),
                                  F.count("*").alias("c")))

    conf = dict(SPMD_1DEV)
    conf["rapids.tpu.sql.adaptive.enabled"] = True
    # serialized shuffle pieces carry exact row counts in their headers —
    # the MapOutputStats rows_known precondition of measured sizing
    conf["rapids.tpu.shuffle.serialize.enabled"] = True
    cpu = run_on_cpu(session, df_fn)
    got, m = _metrics_of(session, df_fn, conf)
    assert m["spmdStages"] == 1, m
    assert m["spmdMeasuredCaps"] >= 1, m
    assert_rows_equal(cpu, got, ignore_order=True, approx_float=1e-9)


# ---------------------------------------------------------------------------
# Degradation: ineligible shapes and runtime fallbacks stay oracle-equal
# ---------------------------------------------------------------------------
def test_ineligible_single_partition_agg_falls_back(session):
    """q6's global aggregate exchanges through SinglePartitioning — not an
    SPMD shape; with the flag on it must still run (host loop) and match."""
    df_fn = _tpch_q("q6")
    cpu = run_on_cpu(session, df_fn)
    got, m = _metrics_of(session, df_fn, SPMD_FULL)
    assert m["spmdStages"] == 0, m
    assert_rows_equal(cpu, got, ignore_order=True, approx_float=1e-9)


def test_bucket_overflow_degrades_to_host_loop(session):
    """An undersized per-target bucket trips the in-program overflow probe
    — the stage must degrade to the host-loop executor (never dropping a
    row) and still match the oracle."""
    def df_fn(s):
        df = s.createDataFrame(
            {"k": list(range(100)), "v": [float(i) for i in range(100)]},
            schema=[("k", "long"), ("v", "double")], num_partitions=3)
        return df.groupBy("k").agg(F.sum("v").alias("s"))

    conf = dict(SPMD_1DEV)
    conf["rapids.tpu.sql.spmd.bucketRows"] = 1  # bucket_cap floor = 8
    cpu = run_on_cpu(session, df_fn)
    got, m = _metrics_of(session, df_fn, conf)
    assert m["spmdStages"] == 0, m  # the degraded stage must not count
    assert_rows_equal(cpu, got, ignore_order=True, approx_float=1e-9)


def test_degraded_stage_drops_assembled_inputs(session):
    """Live-bytes regression: a DEGRADED stage must drop its assembled
    [m, cap] stage-input arrays BEFORE re-running the host loop — the
    weakref watch list published by the fallback path must be fully dead
    WITHOUT an intervening GC (the re-run happens exactly when device
    memory is tightest)."""
    from spark_rapids_tpu.engine import spmd_exec

    def df_fn(s):
        df = s.createDataFrame(
            {"k": list(range(100)), "v": [float(i) for i in range(100)]},
            schema=[("k", "long"), ("v", "double")], num_partitions=3)
        return df.groupBy("k").agg(F.sum("v").alias("s"))

    conf = dict(SPMD_1DEV)
    conf["rapids.tpu.sql.spmd.bucketRows"] = 1  # force the degrade
    gc.disable()
    try:
        _, m = _metrics_of(session, df_fn, conf)
        assert m["spmdStages"] == 0, m
        refs = spmd_exec.last_degraded_input_refs()
        assert refs, "degraded stage published no watch refs"
        alive = [r for r in refs if r() is not None]
        assert not alive, (
            f"{len(alive)}/{len(refs)} assembled stage-input arrays "
            "still referenced after degradation (host-loop re-run would "
            "pay their HBM)")
    finally:
        gc.enable()


def test_spmd_enabled_is_default(session):
    """spmd.enabled flipped ON by default (r14): a bare q1 runs the
    stage program with zero extra conf."""
    _, m = _metrics_of(session, _tpch_q("q1"), {})
    assert m["spmdStages"] == 1, m
    assert session.last_resource_report.spmd_stages == 1


def test_mesh_reset_on_session_stop():
    """The collective meshes must not leak across sessions in one process
    (the PR 3 device-manager singleton leak class)."""
    import spark_rapids_tpu as srt
    from spark_rapids_tpu.shuffle import ici

    s = srt.new_session()
    try:
        ici.stage_mesh(1)
        ici.stage_mesh(0)
        assert ici._STAGE_MESHES
    finally:
        s.stop()
    assert not ici._STAGE_MESHES
    assert ici._MESH is None
