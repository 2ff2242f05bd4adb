"""Cached-relation tests (reference: cache_test.py — accelerated
InMemoryTableScan).

The second half holds the device cache to what the cell `q6_cached`
measures (PR 45): TPC-H Q6 over `select(<seven columns>).cache()` of a
parquet lineitem equals a numpy float64 reference and the uncached query;
what the relation materializes is what was selected, not the file; every
later action is served from the device (same dispatches, no scan, no
upload, every cached batch once, none restored) under the admission
permit; a spilled batch is counted as restored and still answers right;
`unpersist` empties the gauge; the analyzer books a materialized relation
at its registered bytes, and a plan made before the relation was held is
not reused after."""

import numpy as np
import pytest

from spark_rapids_tpu.columnar.dtypes import DataType
from spark_rapids_tpu.benchmarks import tpch
from spark_rapids_tpu.exec import cache as cache_mod
from spark_rapids_tpu.plan import functions as F
from spark_rapids_tpu.utils import metrics as M

from tests.harness import (
    IntGen,
    StringGen,
    assert_tpu_and_cpu_are_equal_collect,
    gen_df,
    run_on_tpu,
)


def test_cache_equivalence(session):
    assert_tpu_and_cpu_are_equal_collect(
        session,
        lambda s: gen_df(s, [("k", IntGen(DataType.INT32, lo=0, hi=10)),
                             ("v", IntGen(DataType.INT64)),
                             ("t", StringGen(max_len=4))], n=200).cache()
        .groupBy("k").agg(F.sum("v").alias("s"), F.count("t").alias("c")),
        ignore_order=True)


def test_cache_reused_across_queries(session):
    df_holder = {}

    def fn(s):
        if "df" not in df_holder:
            df_holder["df"] = gen_df(
                s, [("v", IntGen(DataType.INT64))], n=100).cache()
        return df_holder["df"].agg(F.count("*").alias("c"))

    r1 = run_on_tpu(session, fn)
    r2 = run_on_tpu(session, fn)
    assert r1 == r2 == [(100,)]
    # unpersist returns the uncached frame and still computes correctly
    un = df_holder["df"].unpersist()
    r3 = run_on_tpu(session, lambda s: un.agg(F.count("*").alias("c")))
    assert r3 == [(100,)]


# ---------------------------------------------------------------------------
# Q6 over the seven report columns of a parquet lineitem, cached
# ---------------------------------------------------------------------------
CACHED = ("l_quantity", "l_extendedprice", "l_discount", "l_tax",
          "l_shipdate", "l_returnflag", "l_linestatus")
EPOCH = np.datetime64("1970-01-01", "D")
TRACING = "rapids.tpu.obs.tracing.enabled"


def days(s):
    return int((np.datetime64(s, "D") - EPOCH).astype(int))


def lineitem(rows, seed):
    """The seven columns and two the report does not read, one of them a
    text no dictionary page holds."""
    rng = np.random.default_rng(seed)
    quantity = rng.integers(1, 51, rows).astype(np.float64)
    return {
        "l_orderkey": np.arange(rows, dtype=np.int64),
        "l_quantity": quantity,
        "l_extendedprice": (quantity * rng.integers(90000, 209900, rows)
                            / 100.0).round(2),
        "l_discount": rng.integers(0, 11, rows) / 100.0,
        "l_tax": rng.integers(0, 9, rows) / 100.0,
        "l_shipdate": rng.integers(days("1992-01-01"), days("1998-12-01"),
                                   rows).astype(np.int32),
        "l_returnflag": np.array(["A", "N", "R"])[rng.integers(0, 3, rows)],
        "l_linestatus": np.array(["F", "O"])[rng.integers(0, 2, rows)],
        "l_comment": np.array([f"comment {i} {i * 7919 % 1000}"
                               for i in range(rows)]),
    }


def write_lineitem(cols, directory, files):
    import pyarrow as pa
    import pyarrow.parquet as pq

    rows = len(cols["l_orderkey"])
    per = -(-rows // files)
    for i in range(files):
        part = {k: v[i * per:(i + 1) * per] for k, v in cols.items()}
        arrays = [pa.array(v, pa.int32()).cast(pa.date32())
                  if k == "l_shipdate" else pa.array(v)
                  for k, v in part.items()]
        pq.write_table(pa.table(arrays, names=list(part)),
                       str(directory / f"part-{i:05d}.parquet"),
                       row_group_size=1 << 12)
    return str(directory)


def q6(li):
    """The program's own TPC-H Q6 (1994, 0.06 +- 0.01, 24) over `li`."""
    return tpch.q6({"lineitem": li})


def q6_reference(cols):
    """numpy in float64 over the generated arrays: the predicates on the
    exact values, one sum. Nothing of the engine."""
    keep = ((cols["l_shipdate"] >= days("1994-01-01"))
            & (cols["l_shipdate"] < days("1995-01-01"))
            & (cols["l_discount"] >= 0.05) & (cols["l_discount"] <= 0.07)
            & (cols["l_quantity"] < 24.0))
    return float((cols["l_extendedprice"][keep].astype(np.float64)
                  * cols["l_discount"][keep].astype(np.float64)).sum())


@pytest.fixture
def device_session(session):
    """The session as chip_smoke.DEVICE_CONF runs it: DOUBLE arithmetic
    on the device, nothing allowed off it."""
    for key, value in (
            ("rapids.tpu.sql.incompatibleOps.enabled", True),
            ("rapids.tpu.sql.variableFloatAgg.enabled", True),
            ("rapids.tpu.sql.test.enabled", True),
            ("rapids.tpu.sql.test.allowedNonTpu", ""),
            ("rapids.tpu.execution.cpuFallback.enabled", False)):
        session.conf.set(key, value)
    return session


class Relation:
    """A parquet lineitem, its seven columns cached, Q6 over them."""

    def __init__(self, session, tmp_path, rows=20_000, files=3, seed=5):
        self.session = session
        self.cols = lineitem(rows, seed)
        self.files = files
        self.table = session.read.parquet(
            write_lineitem(self.cols, tmp_path, files))
        self.cached = self.table.select(*CACHED).cache()
        self.query = q6(self.cached)

    def action(self):
        """(rows, this action's query metrics, its span tree or None)."""
        self.session.last_query_trace = None
        rows = self.query.collect()
        return (rows, dict(self.session.last_query_metrics),
                self.session.last_query_trace)

    def buffers(self):
        parts = cache_mod._DEVICE_CACHE[self.cached._plan]
        return [b for part in parts for b in part]


@pytest.fixture
def relation(device_session, tmp_path):
    rel = Relation(device_session, tmp_path)
    yield rel
    rel.cached.unpersist()


@pytest.mark.parametrize("rows,files", [(20_000, 3), (5_000, 1)])
def test_cached_q6_equals_the_reference_and_the_uncached_query(
        device_session, tmp_path, rows, files):
    rel = Relation(device_session, tmp_path, rows, files)
    try:
        want = q6_reference(rel.cols)
        uncached = q6(rel.table).collect()
        first, second = rel.action()[0], rel.action()[0]
        for got in (uncached, first, second):
            assert len(got) == 1
            assert got[0][0] == pytest.approx(want, rel=1e-9)
        assert first == second
    finally:
        rel.cached.unpersist()


def test_select_cache_scans_the_selected_columns_only(relation):
    """`optimizer._cache` never prunes below a cache, so `cache()` prunes
    what it will hold: the scan under the relation reads seven columns,
    not the file's nine (and no PLAIN text through the device decoder)."""
    from spark_rapids_tpu.plan import logical as L

    def scans(p):
        return ([p] if isinstance(p, L.FileScan) else []) + [
            s for c in p.children for s in scans(c)]

    (scan,) = scans(relation.cached._plan)
    assert sorted(a.name for a in scan.output) == sorted(CACHED)
    relation.session.conf.set(TRACING, True)
    _, _, tree = relation.action()
    decoded = [sp.attrs["columns"] for sp in tree.find("scan.host_decode")]
    assert decoded == [len(CACHED)] * relation.files
    assert not tree.find("scan.decode")   # the device decoder's span


def test_materialize_and_serve_leave_their_spans(relation):
    relation.session.conf.set(TRACING, True)
    _, metrics, first = relation.action()
    made = first.find("cache.materialize")
    assert len(made) == relation.files
    assert sum(sp.attrs["rows"] for sp in made) == 20_000
    assert sum(sp.attrs["batches"] for sp in made) == len(relation.buffers())
    assert sum(sp.attrs["bytes"] for sp in made) == sum(
        b.size for b in relation.buffers())
    assert {sp.attrs["columns"] for sp in made} == {7}
    assert {sp.attrs["dict_columns"] for sp in made} == {2}
    # the scan is the materialization's child, in the task that ran it
    assert all(sp.children and sp.children[0].name.startswith("scan.")
               for sp in made)
    _, _, later = relation.action()
    assert not later.find("cache.materialize")
    served = later.find("cache.serve")
    assert len(served) == len(relation.buffers())
    assert sorted(sp.attrs["bytes"] for sp in served) == sorted(
        b.size for b in relation.buffers())
    assert all(sp.attrs["restored"] == 0 for sp in served)


@pytest.mark.parametrize("action", [2, 3])
def test_a_later_action_is_served_from_the_device(relation, action):
    relation.session.conf.set(TRACING, True)
    want = q6_reference(relation.cols)
    runs = [relation.action() for _ in range(action)]
    rows, metrics, tree = runs[-1]
    cached = len(relation.buffers())
    assert cached == relation.files
    assert rows[0][0] == pytest.approx(want, rel=1e-9)
    assert metrics[M.CACHED_BATCHES_SERVED] == cached
    assert metrics[M.CACHE_RESTORED_BATCHES] == 0
    assert metrics[M.CACHE_RESIDENT_BYTES] == sum(
        b.size for b in relation.buffers())
    assert metrics[M.CPU_FALLBACK_EVENTS] == 0
    # the same programs as the action before (the first one also scans)
    if action > 2:
        assert metrics[M.DEVICE_DISPATCHES] == runs[-2][1][M.DEVICE_DISPATCHES]
    assert metrics[M.DEVICE_DISPATCHES] < runs[0][1][M.DEVICE_DISPATCHES]
    # no file opened, nothing uploaded: the tree has no span of a scan
    names = {sp.name for sp in tree.spans()}
    assert not [n for n in names if n.startswith(("scan.", "HostToDevice",
                                                  "prefetch:"))]
    # and every task that was handed a batch held the admission permit
    tasks = [sp for sp in tree.spans()
             if sp.kind == "task" and any(c.name == "cache.serve"
                                          for c in sp.children)]
    assert len(tasks) == cached
    for task in tasks:
        kids = [c.name for c in task.children]
        assert kids.index("Acquire TPU Semaphore") < kids.index("cache.serve")


def test_the_process_wide_readers_move_with_the_actions(relation):
    relation.action()
    before = (M.cached_batches_served_count(), M.cache_restored_batch_count())
    relation.action()
    assert M.cached_batches_served_count() - before[0] == relation.files
    assert M.cache_restored_batch_count() == before[1]
    assert M.cache_resident_bytes() == sum(b.size for b in relation.buffers())


def test_a_spilled_batch_is_counted_as_restored_and_answers_right(relation):
    from spark_rapids_tpu.memory.spill import SpillFramework, StorageTier

    want = q6_reference(relation.cols)
    relation.action()
    held = M.cache_resident_bytes()
    victim = relation.buffers()[0]
    fw = SpillFramework.get()
    assert fw.device_store.spill_buffer(victim) == victim.size
    assert victim.tier is StorageTier.HOST and victim.device_batch is None
    assert M.cache_resident_bytes() == held - victim.size
    rows, metrics, _ = relation.action()
    assert rows[0][0] == pytest.approx(want, rel=1e-9)
    assert metrics[M.CACHED_BATCHES_SERVED] == relation.files
    assert metrics[M.CACHE_RESTORED_BATCHES] == 1
    # it was promoted back: the next action restores nothing
    assert victim.tier is StorageTier.DEVICE
    assert metrics[M.CACHE_RESIDENT_BYTES] == held
    assert relation.action()[1][M.CACHE_RESTORED_BATCHES] == 0


def test_unpersist_frees_the_gauge(device_session, tmp_path):
    rel = Relation(device_session, tmp_path)
    assert M.cache_resident_bytes() == 0
    rel.action()
    bufs = rel.buffers()
    assert M.cache_resident_bytes() == sum(b.size for b in bufs) > 0
    uncached = rel.cached.unpersist()
    assert M.cache_resident_bytes() == 0
    assert all(b.tier is None and b.device_batch is None for b in bufs)
    assert not cache_mod.is_materialized(rel.cached._plan)
    assert q6(uncached).collect()[0][0] == pytest.approx(
        q6_reference(rel.cols), rel=1e-9)
    assert device_session.last_query_metrics[M.CACHE_RESIDENT_BYTES] == 0


def test_resources_books_a_materialized_relation_at_its_registered_bytes(
        relation):
    def booked():
        (node,) = [n for n in relation.session.last_resource_report.nodes
                   if n.name.startswith("TpuCachedScanExec")]
        return node

    relation.action()
    # before it is held: the child scan's estimate, an upper bound from
    # the files' bytes that knows no row count
    estimate = booked()
    assert estimate.rows.lo == 0 and estimate.rows.hi > 20_000
    relation.action()
    registered = sum(b.size for b in relation.buffers())
    assert cache_mod.cached_device_bytes(relation.cached._plan) == registered
    held = booked()
    assert held.resident_bytes == registered < estimate.resident_bytes
    assert (held.rows.lo, held.rows.hi) == (20_000, 20_000)


def test_a_plan_made_before_the_relation_was_held_is_not_reused(relation):
    """The plan cache keys a cached relation by whether it is
    materialized: the first action's analysis (the scan's estimate, its
    admission weight and spill reserve) is not the window's."""
    first, second, third = (relation.action()[1] for _ in range(3))
    assert (first[M.PLAN_CACHE_MISSES], first[M.PLAN_CACHE_HITS]) == (1, 0)
    assert (second[M.PLAN_CACHE_MISSES], second[M.PLAN_CACHE_HITS]) == (1, 0)
    assert (third[M.PLAN_CACHE_MISSES], third[M.PLAN_CACHE_HITS]) == (0, 1)
    report = relation.session.last_resource_report
    assert report.peak_bytes.hi < 64 << 20


# ---------------------------------------------------------------------------
# The ungrouped partial over a cached batch: one program, nothing fetched
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("source", ["files", "cache"])
def test_q6_partial_is_one_program_a_batch_and_the_analyzer_says_so(
        relation, source):
    """Q6 has no grouping key: every batch's partial is the ungrouped
    update program, the action's dispatches are one a batch and the
    merge's two, and the analyzer predicts that count: exactly over a
    cached relation (it knows the batches), as an interval that holds it
    over files (a scan's batches are not known before it runs)."""
    relation.action()                      # materialize
    if source == "cache":
        _, metrics, _ = relation.action()
    else:
        q6(relation.table).collect()
        metrics = dict(relation.session.last_query_metrics)
    batches = relation.files
    assert metrics[M.UNGROUPED_AGG_BATCHES] == batches
    assert metrics[M.DENSE_AGG_BATCHES] == metrics[M.SORT_AGG_BATCHES] == 0
    assert metrics[M.DEVICE_DISPATCHES] == batches + 2
    predicted = relation.session.last_resource_report.dispatches
    assert predicted.lo <= metrics[M.DEVICE_DISPATCHES] <= predicted.hi
    if source == "cache":
        assert relation.session.last_resource_report.dispatches_exact
        assert predicted.lo == predicted.hi


def test_a_cached_partial_task_fetches_nothing_from_the_device(
        relation, monkeypatch):
    """A map task over a cached batch: the permit, the batch, one program.
    No `jax.device_get` and no `host_rows()` that would have to fetch a
    count runs on its thread, and its span tree holds one update (no
    finalize) with `ungroupedAggBatches` counted on the task."""
    from tests.harness import forbid_device_fetch_in_map_tasks

    relation.action()                      # materialize, unguarded
    guarded = forbid_device_fetch_in_map_tasks(monkeypatch)
    relation.session.conf.set(TRACING, True)
    rows, metrics, tree = relation.action()
    assert rows[0][0] == pytest.approx(q6_reference(relation.cols), rel=1e-9)
    tasks = [sp for sp in tree.spans()
             if sp.kind == "task" and any(c.name == "cache.serve"
                                          for c in sp.children)]
    assert len(tasks) == relation.files == len(guarded)
    for task in tasks:
        assert task.counts[M.UNGROUPED_AGG_BATCHES] == 1
        updates = [c for c in task.children
                   if c.name == "TpuHashAggregate.update"]
        assert len(updates) == 1 and updates[0].attrs["path"] == "ungrouped"
        assert updates[0].counts[M.DEVICE_DISPATCHES] == 1
        # the gather over a key batch with no columns counted one here
        assert M.DEVICE_DISPATCHES not in task.counts
    assert not tree.find("TpuHashAggregate.finalize")
