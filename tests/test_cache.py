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
not reused after.

The third half (PR 51) holds the materialisation to its rule: the pieces
the cached plan hands over are gathered, in order, into batches of the
engine's target size (`rapids.tpu.sql.batchSizeBytes`), rows against the
capacity bucket; a file scan's partitions are merged, a promised
partitioning's are kept; what does not end in the relation is freed."""

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
BATCH_BYTES = "rapids.tpu.sql.batchSizeBytes"
# seven columns are 51 B a lane on this backend (DOUBLE stays f64) and
# the two dictionaries a little over: a target of 64 B x 2^13 lanes is
# batches of 2^13 lanes
LANES_8K = 64 << 13


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

    def __init__(self, session, tmp_path, rows=20_000, files=3, seed=5,
                 batch_bytes=None, order_by=None):
        self.session = session
        if batch_bytes is not None:
            session.conf.set(BATCH_BYTES, batch_bytes)
        self.cols = lineitem(rows, seed)
        if order_by is not None:
            order = np.argsort(self.cols[order_by], kind="stable")
            self.cols = {k: v[order] for k, v in self.cols.items()}
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

    def partitions(self):
        return cache_mod._DEVICE_CACHE[self.cached._plan]

    def buffers(self):
        return [b for part in self.partitions() for b in part]


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
    assert {sp.attrs["batches"] for sp in made} == {1}
    assert {sp.attrs["columns"] for sp in made} == {7}
    assert {sp.attrs["dict_columns"] for sp in made} == {2}
    # the scan is the materialization's child, in the task that ran it
    assert all(sp.children and sp.children[0].name.startswith("scan.")
               for sp in made)
    # a resident batch: what came in, what stands, and how full it is
    (whole,) = first.find("cache.coalesce")
    (buf,) = relation.buffers()
    assert whole.attrs["pieces"] == relation.files
    assert whole.attrs["rows"] == 20_000
    assert whole.attrs["lanes"] == 1 << 15 == buf.device_batch.capacity
    assert whole.attrs["bytes"] == buf.size
    assert sum(sp.attrs["bytes"] for sp in made) > buf.size * 20_000 // (
        1 << 15)   # three buckets of 8192 lanes held 24576
    assert metrics[M.CACHE_COALESCED_PIECES] == relation.files
    _, metrics, later = relation.action()
    assert not later.find("cache.materialize")
    assert not later.find("cache.coalesce")
    assert metrics[M.CACHE_COALESCED_PIECES] == 0
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
    assert cached == 1          # three files' pieces, one resident batch
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
    assert M.cached_batches_served_count() - before[0] == len(
        relation.buffers())
    assert M.cache_restored_batch_count() == before[1]
    assert M.cache_resident_bytes() == sum(b.size for b in relation.buffers())


def test_a_spilled_batch_is_counted_as_restored_and_answers_right(relation):
    from spark_rapids_tpu.memory.spill import SpillFramework, StorageTier

    want = q6_reference(relation.cols)
    relation.action()
    held = M.cache_resident_bytes()
    (victim,) = relation.buffers()     # a batch of three pieces
    fw = SpillFramework.get()
    assert fw.device_store.spill_buffer(victim) == victim.size
    assert victim.tier is StorageTier.HOST and victim.device_batch is None
    assert M.cache_resident_bytes() == held - victim.size
    rows, metrics, _ = relation.action()
    assert rows[0][0] == pytest.approx(want, rel=1e-9)
    assert metrics[M.CACHED_BATCHES_SERVED] == 1
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
    batches = len(relation.buffers()) if source == "cache" \
        else relation.files
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
    assert len(tasks) == len(relation.buffers()) == len(guarded)
    for task in tasks:
        assert task.counts[M.UNGROUPED_AGG_BATCHES] == 1
        updates = [c for c in task.children
                   if c.name == "TpuHashAggregate.update"]
        assert len(updates) == 1 and updates[0].attrs["path"] == "ungrouped"
        assert updates[0].counts[M.DEVICE_DISPATCHES] == 1
        # the gather over a key batch with no columns counted one here
        assert M.DEVICE_DISPATCHES not in task.counts
    assert not tree.find("TpuHashAggregate.finalize")


# ---------------------------------------------------------------------------
# The relation is held in batches of the engine's target size (PR 51)
# ---------------------------------------------------------------------------
Q6_CACHED = [1_048_576, 451_424] * 40     # the cell's 80 pieces, in order


@pytest.mark.parametrize("lane_bytes,target,lanes", [
    (35.0, 512 << 20, 1 << 23),     # q6_cached: seven columns, f32 and codes
    (35.0, 35 << 23, 1 << 23),      # the target met to the byte
    (35.0, (35 << 23) - 1, 1 << 22),
    (51.2, LANES_8K, 1 << 13),
    (1000.0, 1, 8),                 # never under the smallest bucket
])
def test_target_lanes_is_the_largest_bucket_inside_the_target(
        lane_bytes, target, lanes):
    assert cache_mod.target_lanes(lane_bytes, target) == lanes


@pytest.mark.parametrize("rows,lanes,sizes", [
    (Q6_CACHED, 1 << 23, [10] * 8),
    ([8, 8, 8, 8], 16, [2, 2]),
    ([8, 8, 8, 8], 8, [1, 1, 1, 1]),
    ([5, 5, 5, 5], 16, [3, 1]),
    ([3, 20, 3, 3], 8, [1, 1, 2]),          # a piece is never split
    ([4] * 7, 1 << 20, [7]),
    ([], 8, []),
])
def test_fill_groups_fills_the_bucket_and_never_passes_it(rows, lanes, sizes):
    from spark_rapids_tpu.columnar.batch import bucket_capacity

    groups = cache_mod.fill_groups(rows, lanes)
    assert [len(g) for g in groups] == sizes
    assert [i for g in groups for i in g] == list(range(len(rows)))
    for g in groups:
        filled = sum(rows[i] for i in g)
        assert filled <= lanes or len(g) == 1
        assert bucket_capacity(filled) <= lanes or len(g) == 1
    if rows is Q6_CACHED:
        # by bytes against the target (`_coalesce_iter`'s rule) nineteen
        # pieces fit 512 MiB: 14.5 M rows in a 2^24-lane batch, twice the
        # lanes these ten take and 13% of them padding
        by_bytes, held = 0, 0
        for n in rows:
            if held + bucket_capacity(n) * 35 > 512 << 20:
                break
            by_bytes, held = by_bytes + 1, held + bucket_capacity(n) * 35
        assert by_bytes == 19
        assert bucket_capacity(sum(rows[:by_bytes])) == 2 * lanes
        assert sum(rows[:10]) / lanes == pytest.approx(0.894, abs=1e-3)


@pytest.mark.parametrize("files,batches", [(5, 3), (2, 1), (8, 4)])
def test_several_files_become_batches_of_the_target_size(
        device_session, tmp_path, files, batches):
    """4,000 rows a file in 4,096 lanes, a target of 8,192 lanes: two
    pieces a batch, each batch a partition. Every action, the first too,
    serves those batches, the rows are the reference's, and the analyzer
    counts a program a batch and the merge's two."""
    rel = Relation(device_session, tmp_path, rows=4_000 * files, files=files,
                   batch_bytes=LANES_8K)
    try:
        want = q6_reference(rel.cols)
        runs = [rel.action() for _ in range(3)]
        parts = rel.partitions()
        assert [len(p) for p in parts] == [1] * batches
        held = [b.device_batch for b in rel.buffers()]
        assert [b.num_rows for b in held] == [
            min(8_000, 4_000 * files - 8_000 * i) for i in range(batches)]
        assert all(b.capacity == 1 << (b.num_rows - 1).bit_length()
                   <= 1 << 13 for b in held)
        for rows, metrics, _ in runs:
            assert rows[0][0] == pytest.approx(want, rel=1e-9)
            assert metrics[M.CACHED_BATCHES_SERVED] == batches
            assert metrics[M.CACHE_RESTORED_BATCHES] == 0
        assert [m[M.CACHE_COALESCED_PIECES] for _, m, _ in runs] == [
            files - files % 2, 0, 0]
        assert runs[2][1][M.DEVICE_DISPATCHES] == batches + 2
        report = rel.session.last_resource_report
        assert report.dispatches_exact
        assert report.dispatches.lo == report.dispatches.hi == batches + 2
        assert cache_mod.cached_row_count(rel.cached._plan) == 4_000 * files
    finally:
        rel.cached.unpersist()


def test_a_cached_scan_of_several_batches_equals_the_cpu_oracle(
        session, tmp_path):
    path = write_lineitem(lineitem(18_000, seed=9), tmp_path, files=6)
    assert_tpu_and_cpu_are_equal_collect(
        session,
        lambda s: s.read.parquet(path).select(*CACHED).cache(),
        ignore_order=True, extra_conf={BATCH_BYTES: LANES_8K})


@pytest.mark.parametrize("order_by", [None, "l_returnflag"])
def test_two_dictionary_columns_across_merged_pieces(
        device_session, tmp_path, order_by):
    """The two flags stay codes through the concat. Sorted by the flag,
    a file holds one or two of its three values, so the pieces'
    dictionaries differ and are brought to one."""
    from spark_rapids_tpu.columnar.encoded import is_encoded

    rel = Relation(device_session, tmp_path, files=4, order_by=order_by)
    try:
        report = rel.cached.groupBy("l_returnflag", "l_linestatus").agg(
            F.count("*").alias("n"), F.sum("l_quantity").alias("q"))
        got = sorted(report.collect())
        (buf,) = rel.buffers()
        assert [is_encoded(c) for c in buf.device_batch.columns] == [
            False] * 5 + [True] * 2
        assert device_session.last_query_metrics[
            M.CACHE_COALESCED_PIECES] == 4
        keys = np.char.add(rel.cols["l_returnflag"], rel.cols["l_linestatus"])
        want = sorted(
            (k[0], k[1], int((keys == k).sum()),
             float(rel.cols["l_quantity"][keys == k].sum()))
            for k in np.unique(keys))
        assert [g[:3] for g in got] == [w[:3] for w in want]
        assert [g[3] for g in got] == pytest.approx([w[3] for w in want])
        assert sorted(report.collect()) == got      # served, not rebuilt
    finally:
        rel.cached.unpersist()


class _Pieces(cache_mod.TpuExec):
    """A cached plan that hands over the given batches, a list a
    partition, and promises `partitioning` (None: nothing, a file scan)."""

    def __init__(self, parts, partitioning):
        super().__init__()
        self.parts, self.partitioning = parts, partitioning

    @property
    def output(self):
        return []

    def output_partitioning(self):
        return self.partitioning

    def execute(self, ctx):
        from spark_rapids_tpu.exec.base import PartitionedBatches

        return PartitionedBatches(len(self.parts),
                                  lambda p: iter(self.parts[p]))


class _Key:
    """A logical node's stand-in: the cache's weak key."""


@pytest.mark.parametrize("promise,want", [
    (None, [[0, 1, 2, 3, 4, 10, 11, 12, 13, 14, 20, 21, 22, 23, 24],
            [30, 31, 32, 33, 34, 40, 41, 42, 43, 44, 50, 51, 52, 53, 54]]),
    ("hash(k, 3)", [[0, 1, 2, 3, 4, 10, 11, 12, 13, 14],
                    [20, 21, 22, 23, 24, 30, 31, 32, 33, 34],
                    [40, 41, 42, 43, 44, 50, 51, 52, 53, 54]]),
])
def test_a_promised_partitioning_keeps_its_partitions_a_scan_s_are_merged(
        session, promise, want):
    """Three partitions of two pieces of five rows, a target of sixteen
    lanes. A plan that promises nothing (a file scan) is gathered across
    its partitions, three pieces a batch, and each batch is a partition.
    One that promises a partitioning (an exchange under the cache) keeps
    its partitions, each holding the rows it held and in their order,
    and only the batches inside one are gathered: a join or an aggregate
    above may have planned on the promise."""
    from spark_rapids_tpu.columnar.batch import (
        HostColumnarBatch,
        HostColumnVector,
    )
    from spark_rapids_tpu.exec.base import ExecContext

    def piece(first):
        return HostColumnarBatch([HostColumnVector.from_pylist(
            list(range(first, first + 5)), DataType.INT64)]).to_device()

    session.conf.set(BATCH_BYTES, 16 * 9)     # 8 B and a validity a lane
    child = _Pieces([[piece(0), piece(10)], [piece(20), piece(30)],
                     [piece(40), piece(50)]], promise)
    key = _Key()
    scan = cache_mod.TpuCachedScanExec(key, child)
    assert scan.output_partitioning() is promise
    try:
        served = scan.execute(ExecContext(session.conf))
        got = [[v for b in served.iterator(p)
                for v in b.to_host().columns[0].to_pylist()]
               for p in range(served.num_partitions)]
        assert got == want
        parts = cache_mod._DEVICE_CACHE[key]
        assert [len(part) for part in parts] == [1] * len(want)
        assert all(part[0].device_batch.capacity == 16 for part in parts)
        # the next execution is served what stands, the child is not run
        child.parts = None
        again = scan.execute(ExecContext(session.conf))
        assert again.num_partitions == len(want)
    finally:
        cache_mod.invalidate(key)


def test_a_repartitioned_relation_promises_what_its_exchange_does(
        device_session, tmp_path):
    """`repartition(4, key).cache()` through the planner: the cached scan
    passes the exchange's partitioning on, the relation has the
    exchange's four partitions with every key in one of them, and an
    aggregate over it answers right. The same files cached without the
    exchange promise nothing and become one batch in one partition."""
    rel = Relation(device_session, tmp_path, files=3)
    plain = rel.table.select("l_orderkey", "l_quantity")
    keyed = plain.repartition(4, "l_orderkey").cache()
    merged = plain.cache()
    try:
        device_session.plan_capture.start()
        got = sorted(keyed.groupBy("l_orderkey").agg(
            F.sum("l_quantity").alias("q")).collect())
        (scan,) = [n for p in device_session.plan_capture.stop()
                   for n in p.collect_nodes(
                       lambda n: isinstance(n, cache_mod.TpuCachedScanExec))]
        assert scan.output_partitioning() is not None
        assert scan.output_partitioning() is \
            scan.children[0].output_partitioning()
        assert got == sorted(zip(rel.cols["l_orderkey"].tolist(),
                                 rel.cols["l_quantity"].tolist()))
        parts = cache_mod._DEVICE_CACHE[keyed._plan]
        assert len(parts) == 4
        keys = [set(v for buf in part
                    for v in buf.device_batch.to_host().columns[0].to_pylist())
                for part in parts]
        assert sum(map(len, keys)) == 20_000 == len(set().union(*keys))
        assert merged.agg(F.count("*").alias("n")).collect() == [(20_000,)]
        assert [len(p) for p in cache_mod._DEVICE_CACHE[merged._plan]] == [1]
    finally:
        keyed.unpersist()
        merged.unpersist()
        rel.cached.unpersist()


@pytest.mark.parametrize("fails_in", ["scan", "assemble"])
def test_a_materialisation_that_raises_leaves_nothing_behind(
        device_session, tmp_path, monkeypatch, fails_in):
    """No query owns a cache entry's buffers, so a failed attempt frees
    its own: the pieces other tasks had registered, the batches already
    assembled, the pieces still waiting. The next action materialises."""
    from spark_rapids_tpu.memory.spill import SpillFramework

    rel = Relation(device_session, tmp_path, rows=16_000, files=4,
                   batch_bytes=LANES_8K)
    store = SpillFramework.get().device_store
    before = (store.current_size, store.buffer_count())
    calls = []

    def failing(real):
        def wrapper(*a, **k):
            calls.append(1)
            if len(calls) >= 2:     # every retry too
                raise RuntimeError("injected")
            return real(*a, **k)
        return wrapper

    if fails_in == "scan":
        monkeypatch.setattr(cache_mod._Registered, "add",
                            failing(cache_mod._Registered.add))
    else:
        monkeypatch.setattr(cache_mod, "concat_in_order",
                            failing(cache_mod.concat_in_order))
    try:
        with pytest.raises(Exception, match="injected"):
            rel.action()
        assert len(calls) >= 2
        assert not cache_mod.is_materialized(rel.cached._plan)
        assert M.cache_resident_bytes() == 0
        assert (store.current_size, store.buffer_count()) == before
        monkeypatch.undo()
        rows, metrics, _ = rel.action()
        assert rows[0][0] == pytest.approx(q6_reference(rel.cols), rel=1e-9)
        assert metrics[M.CACHED_BATCHES_SERVED] == 2 == len(rel.buffers())
        assert store.current_size - before[0] == M.cache_resident_bytes()
    finally:
        rel.cached.unpersist()
    assert (store.current_size, store.buffer_count()) == before


def test_the_pieces_go_as_their_batch_stands(device_session, tmp_path,
                                             monkeypatch):
    """Over the relation's own bytes the device holds one batch being
    assembled: when the second is built the first one's pieces are
    already freed."""
    from spark_rapids_tpu.memory.spill import SpillFramework

    rel = Relation(device_session, tmp_path, rows=16_000, files=4,
                   batch_bytes=LANES_8K)
    store = SpillFramework.get().device_store
    base, seen = store.buffer_count(), []
    real = cache_mod.concat_in_order

    def watching(pieces):
        seen.append(store.buffer_count() - base)
        return real(pieces)

    monkeypatch.setattr(cache_mod, "concat_in_order", watching)
    try:
        rel.action()
        assert seen == [4, 3]      # four pieces; a batch and two pieces
        assert store.buffer_count() - base == 2
    finally:
        rel.cached.unpersist()


def test_what_a_materialisation_registers_is_kept_or_freed_whoever_is_late():
    """`_Registered` under more threads than cores and a short switch
    interval: buffers added before the close are freed by it unless
    kept, one added after it (a straggling duplicate of a task) frees
    itself, and none is left that is neither kept nor freed."""
    import sys
    import threading

    class Buf:
        def __init__(self, i):
            self.id, self.freed = i, 0

    class Store:
        def __init__(self):
            self.ids = iter(range(1 << 30))
            self.lock = threading.Lock()

        def add_device_batch(self, batch, scope_to_query):
            assert scope_to_query is False
            with self.lock:
                return Buf(next(self.ids))

    made = cache_mod._Registered(Store())
    added, lock = [], threading.Lock()
    stop = threading.Event()

    def worker():
        while not stop.is_set():
            buf = made.add(object())
            with lock:
                added.append(buf)
            if buf.id % 7 == 0:
                made.free([buf])

    freed = []
    real = cache_mod._free_buffers

    def counting(bufs):
        for b in bufs:
            b.freed += 1
            freed.append(b)

    cache_mod._free_buffers = counting
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    threads = [threading.Thread(target=worker) for _ in range(32)]
    try:
        for t in threads:
            t.start()
        while len(added) < 2_000:
            pass
        with lock:
            keep = [b for b in added if b.id % 7 and b.id % 5 == 0]
        made.close(keep=keep)
        while len(added) < 4_000:
            pass
        stop.set()
        for t in threads:
            t.join(timeout=30)
        assert not any(t.is_alive() for t in threads)
    finally:
        stop.set()
        sys.setswitchinterval(interval)
        cache_mod._free_buffers = real
    kept = {b.id for b in keep}
    assert len(added) >= 4_000
    for b in added:
        assert (b.freed == 0) == (b.id in kept), (b.id, b.freed)
