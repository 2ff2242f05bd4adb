"""Encoded columnar subsystem (columnar/encoded.py): dictionary columns
stay CODES in HBM and operators compute on the codes with late
materialization — oracle equality, metric pins, serde round trips,
analyzer containment, and fault injection at the materialize site."""

from __future__ import annotations

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from spark_rapids_tpu import conf as C
from spark_rapids_tpu.columnar import encoded as ENC
from spark_rapids_tpu.columnar.dtypes import DataType
from spark_rapids_tpu.plan import functions as F
from tests.harness import (
    assert_tpu_and_cpu_are_equal_collect,
    run_on_cpu,
    run_on_tpu,
)

# extra seeds ride outside the tier-1 window (the dots budget
# is shared by the whole suite); seed 0 stays in tier-1
SEEDS = [0, pytest.param(7, marks=pytest.mark.slow),
         pytest.param(1234, marks=pytest.mark.slow)]


def _write_dict_heavy(tmp_path, seed=0, n=4000, nulls=True,
                      name="enc.parquet", row_group_size=2500):
    """Dictionary-heavy parquet: low-ndv string columns + numerics."""
    rng = np.random.default_rng(seed)
    flag = rng.choice(["A", "B", "C", "N", "R"], size=n).astype(object)
    status = rng.choice(["open", "closed", "pending"], size=n).astype(object)
    v = rng.integers(0, 10_000, size=n)
    k = rng.integers(0, 50, size=n)
    if nulls:
        null_at = rng.random(n) < 0.05
        flag = np.where(null_at, None, flag)
    tbl = pa.table({"flag": flag, "status": status, "v": v, "k": k})
    path = str(tmp_path / name)
    pq.write_table(tbl, path, use_dictionary=True,
                   row_group_size=row_group_size)
    return path


def _scan_emits_encoded(session, path) -> bool:
    run_on_tpu(session, lambda s: s.read.parquet(path))
    return session.last_query_metrics.get("encodedColumns", 0) > 0


# ---------------------------------------------------------------------------
# Oracle equality across operators and seeds
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("seed", SEEDS)
def test_filter_groupby_oracle_equal(session, tmp_path, seed):
    path = _write_dict_heavy(tmp_path, seed=seed)
    assert_tpu_and_cpu_are_equal_collect(
        session,
        lambda s: s.read.parquet(path)
        .filter(F.col("flag") == F.lit("A"))
        .groupBy("status").agg(F.count("*").alias("c"),
                               F.sum("v").alias("t")),
        ignore_order=True)
    assert session.last_query_metrics["encodedColumns"] > 0


@pytest.mark.parametrize("seed", SEEDS[:2])
def test_in_isnull_predicates_oracle_equal(session, tmp_path, seed):
    path = _write_dict_heavy(tmp_path, seed=seed)
    assert_tpu_and_cpu_are_equal_collect(
        session,
        lambda s: s.read.parquet(path)
        .filter(F.col("flag").isin("A", "B", "Z") |
                F.col("flag").isNull())
        .groupBy("flag").agg(F.count("*").alias("c")),
        ignore_order=True)
    assert session.last_query_metrics["encodedColumns"] > 0


def test_absent_literal_matches_nothing(session, tmp_path):
    path = _write_dict_heavy(tmp_path, seed=1)
    assert_tpu_and_cpu_are_equal_collect(
        session,
        lambda s: s.read.parquet(path)
        .filter(F.col("flag") == F.lit("NOT_IN_DICT"))
        .groupBy("status").agg(F.count("*").alias("c")),
        ignore_order=True)


@pytest.mark.parametrize("seed", SEEDS[:2])
def test_sort_over_encoded_oracle_equal(session, tmp_path, seed):
    """Sort over encoded keys runs in RANK space (the order-preserving
    sorted dictionary) — no boundary decode; results oracle-equal."""
    path = _write_dict_heavy(tmp_path, seed=seed)
    assert_tpu_and_cpu_are_equal_collect(
        session,
        lambda s: s.read.parquet(path)
        .groupBy("flag", "status").agg(F.sum("v").alias("t"))
        .orderBy("flag", "status"))
    assert session.last_query_metrics["encodedColumns"] > 0


@pytest.mark.parametrize("seed", SEEDS[:2])
def test_join_on_encoded_keys_oracle_equal(session, tmp_path, seed):
    """Hash join on dictionary keys: the two sides' dictionaries align
    through a build-time code-remap table."""
    left = _write_dict_heavy(tmp_path, seed=seed, name="l.parquet")
    right = _write_dict_heavy(tmp_path, seed=seed + 100, n=800,
                              nulls=False, name="r.parquet",
                              row_group_size=800)

    def q(s):
        l = s.read.parquet(left)
        r = s.read.parquet(right).groupBy("status").agg(
            F.sum("k").alias("rk"))
        return l.join(r, l["status"] == r["status"], "inner") \
            .groupBy("flag").agg(F.count("*").alias("c"),
                                 F.sum("rk").alias("t"))

    assert_tpu_and_cpu_are_equal_collect(session, q, ignore_order=True)
    assert session.last_query_metrics["encodedColumns"] > 0


def test_join_key_used_bare_and_computed_oracle_equal(session, tmp_path):
    """A column used BOTH as a bare key and inside a computed key needs
    VALUES at the computed position: the whole ordinal materializes
    instead of code-joining (the computed expression would otherwise
    evaluate over int32 codes)."""
    rng = np.random.default_rng(21)
    vals = ["open", "closed", "pending"]
    lpath = str(tmp_path / "l.parquet")
    pq.write_table(pa.table({
        "status": rng.choice(vals, size=4000).astype(object),
        "v": rng.integers(0, 100, size=4000)}), lpath,
        use_dictionary=True, row_group_size=2500)
    rs = np.array(vals + ["archived"], dtype=object)
    rpath = str(tmp_path / "r.parquet")
    pq.write_table(pa.table({
        "rstatus": rs,
        "slen": np.array([len(x) for x in rs]),
        "rk": np.arange(len(rs)) * 10}), rpath, use_dictionary=True)

    def q(s):
        left = s.read.parquet(lpath)
        right = s.read.parquet(rpath)
        return left.join(
            right, (left["status"] == right["rstatus"]) &
            (F.length(left["status"]) == right["slen"]), "inner") \
            .groupBy("status").agg(F.count("*").alias("c"),
                                   F.sum("rk").alias("t"))

    assert_tpu_and_cpu_are_equal_collect(session, q, ignore_order=True)


def test_join_one_stream_col_against_two_build_dictionaries(
        session, tmp_path):
    """One stream ordinal equi-joined against two build columns whose
    dictionaries DIFFER cannot share one code remap: those key positions
    must fall back to value comparison (a single remap into either
    build dictionary's code space silently mismatches the other)."""
    rng = np.random.default_rng(22)
    vals = ["open", "closed", "pending"]
    lpath = str(tmp_path / "l.parquet")
    pq.write_table(pa.table({
        "status": rng.choice(vals, size=4000).astype(object),
        "v": rng.integers(0, 100, size=4000)}), lpath,
        use_dictionary=True, row_group_size=2500)
    rpath = str(tmp_path / "r.parquet")
    pq.write_table(pa.table({
        "a": rng.choice(vals, size=400).astype(object),
        "b": rng.choice(vals + ["archived", "stale"],
                        size=400).astype(object),
        "rw": rng.integers(0, 9, size=400)}), rpath, use_dictionary=True)

    def q(s):
        left = s.read.parquet(lpath)
        right = s.read.parquet(rpath)
        return left.join(
            right, (left["status"] == right["a"]) &
            (left["status"] == right["b"]), "inner") \
            .groupBy("status").agg(F.count("*").alias("c"),
                                   F.sum("rw").alias("t"))

    assert_tpu_and_cpu_are_equal_collect(session, q, ignore_order=True)


def test_chunk_dict_only_page_walk(session, tmp_path):
    """`chunk_dict_only` proves dict-only-ness from page HEADERS: a
    mid-chunk PLAIN fallback chunk carries the SAME footer encodings as
    a pure-dict chunk, so the footer alone must never yield 'certain' —
    the analyzer's ceiling reduction rides on this proof."""
    from spark_rapids_tpu.io import parquet_device as PD
    from spark_rapids_tpu.io.scan import TpuFileScanExec

    pure = str(tmp_path / "pure.parquet")
    rng = np.random.default_rng(23)
    pq.write_table(pa.table({
        "s": rng.choice(["open", "closed", "pending"],
                        size=4000).astype(object)}), pure,
        use_dictionary=True)
    # high ndv + tiny dictionary page limit forces a mid-chunk PLAIN
    # fallback; the footer still reports {PLAIN, RLE, RLE_DICTIONARY}
    fb = str(tmp_path / "fb.parquet")
    pq.write_table(pa.table({
        "s": np.array([f"val_{i % 1500:05d}_{'x' * 20}"
                       for i in range(4000)], dtype=object)}), fb,
        use_dictionary=True, dictionary_pagesize_limit=2048,
        data_page_size=4096)
    md_p = pq.ParquetFile(pure).metadata.row_group(0).column(0)
    md_f = pq.ParquetFile(fb).metadata.row_group(0).column(0)
    assert set(md_p.encodings) == set(md_f.encodings)  # indistinguishable
    assert PD.chunk_dict_only(pure, md_p) is True
    assert PD.chunk_dict_only(fb, md_f) is False

    def find_scan(node):
        if isinstance(node, TpuFileScanExec):
            return node
        for c in node.children:
            got = find_scan(c)
            if got is not None:
                return got
        return None

    # plan-time mirror: the pure chunk may claim 'certain', the
    # fallback chunk must not (ndv here fails the heuristic anyway,
    # so it simply never reaches 'certain')
    scan = find_scan(session._physical_plan(
        session.read.parquet(pure)._plan))
    if scan is not None:
        assert scan.encoded_plan(session.conf).get("s") == "certain"


@pytest.mark.slow
def test_unsupported_predicate_materializes_visibly(session, tmp_path):
    """A non-equality use (LIKE-style compare) cannot run on codes: the
    column decodes through materialize() — counted, never silent."""
    path = _write_dict_heavy(tmp_path, seed=3)
    if not _scan_emits_encoded(session, path):
        pytest.skip("scan did not emit encoded columns")
    got = run_on_tpu(
        session,
        lambda s: s.read.parquet(path)
        .filter(F.col("status") > F.lit("m"))   # ordering needs values
        .groupBy("status").agg(F.count("*").alias("c")))
    assert session.last_query_metrics["lateMaterializations"] >= 1
    cpu = run_on_cpu(
        session,
        lambda s: s.read.parquet(path)
        .filter(F.col("status") > F.lit("m"))
        .groupBy("status").agg(F.count("*").alias("c")))
    assert sorted(got) == sorted(cpu)


# ---------------------------------------------------------------------------
# The flagship contract: filter + group-by entirely in code space
# ---------------------------------------------------------------------------
def test_flagship_zero_materializations_before_sink(session, tmp_path):
    """Dictionary-heavy filter + group-by runs end-to-end on codes: the
    ONLY late materializations are the sink's host expansions of the
    encoded output key column (one per output batch), pinned by the
    lateMaterializations metric. The tpulint eager-materialize gate
    (tests/test_lint_clean.py) pins the static half: no unsanctioned
    decode call sites exist in exec/engine code."""
    path = _write_dict_heavy(tmp_path, seed=5, n=8000)
    if not _scan_emits_encoded(session, path):
        pytest.skip("scan did not emit encoded columns")
    got = run_on_tpu(
        session,
        lambda s: s.read.parquet(path)
        .filter(F.col("flag") == F.lit("A"))
        .groupBy("status").agg(F.count("*").alias("c"),
                               F.sum("v").alias("t")))
    m = session.last_query_metrics
    assert m["encodedColumns"] > 0
    assert m["encodedBytesSaved"] > 0
    # the final-agg output is ONE batch with ONE encoded column (status):
    # exactly one sink-side expansion, nothing before finalize
    assert m["lateMaterializations"] == 1
    cpu = run_on_cpu(
        session,
        lambda s: s.read.parquet(path)
        .filter(F.col("flag") == F.lit("A"))
        .groupBy("status").agg(F.count("*").alias("c"),
                               F.sum("v").alias("t")))
    assert sorted(got) == sorted(cpu)


def test_encoded_through_fused_stage(session, tmp_path):
    """A scan-form fused stage (filter+project, no aggregate) keeps the
    passthrough column encoded through the composed program."""
    path = _write_dict_heavy(tmp_path, seed=6)
    assert_tpu_and_cpu_are_equal_collect(
        session,
        lambda s: s.read.parquet(path)
        .filter(F.col("flag") == F.lit("B"))
        .select("status", "v"),
        ignore_order=True,
        extra_conf={"rapids.tpu.sql.fusion.enabled": True})
    assert session.last_query_metrics["encodedColumns"] > 0


@pytest.mark.slow
def test_encoded_off_matches_on(session, tmp_path):
    """Conf off really disables the subsystem; both modes oracle-equal."""
    path = _write_dict_heavy(tmp_path, seed=8)

    def q(s):
        return s.read.parquet(path) \
            .filter(F.col("flag") == F.lit("A")) \
            .groupBy("status").agg(F.sum("v").alias("t"))

    on = run_on_tpu(session, q)
    m_on = dict(session.last_query_metrics)
    off = run_on_tpu(session, q, extra_conf={
        "rapids.tpu.sql.encoded.enabled": False})
    m_off = dict(session.last_query_metrics)
    assert sorted(on) == sorted(off)
    assert m_off["encodedColumns"] == 0
    if m_on["encodedColumns"] == 0:
        pytest.skip("scan did not emit encoded columns (heuristic)")


def test_max_dict_fraction_gates_encoding(session, tmp_path):
    """A near-unique column (ndv ~ rows) must NOT stay encoded under the
    default heuristic."""
    rng = np.random.default_rng(0)
    n = 2000
    uniq = np.array([f"u{i:06d}" for i in range(n)], dtype=object)
    rng.shuffle(uniq)
    tbl = pa.table({"u": uniq, "v": rng.integers(0, 10, size=n)})
    path = str(tmp_path / "uniq.parquet")
    pq.write_table(tbl, path, use_dictionary=True)
    # (the low-cardinality INT column beside it is Arrow's: a scan emits
    # string columns encoded and no others)
    run_on_tpu(session, lambda s: s.read.parquet(path)
               .filter(F.col("v") >= F.lit(0)))
    assert session.last_query_metrics["encodedColumns"] == 0


# ---------------------------------------------------------------------------
# Shuffle bytes: serialized pieces ship codes + one dictionary copy
# ---------------------------------------------------------------------------
def test_serialized_shuffle_ships_codes(session, tmp_path):
    from spark_rapids_tpu.columnar.serde import serialize_batch

    path = _write_dict_heavy(tmp_path, seed=9, n=4000)
    if not _scan_emits_encoded(session, path):
        pytest.skip("scan did not emit encoded columns")

    def q(s):
        return s.read.parquet(path).groupBy("status", "flag").agg(
            F.sum("v").alias("t"))

    from tests.harness import assert_rows_equal

    base = {"rapids.tpu.shuffle.serialize.enabled": True}
    on = run_on_tpu(session, q, extra_conf=base)
    off = run_on_tpu(session, q, extra_conf={
        **base, "rapids.tpu.sql.encoded.enabled": False})
    assert_rows_equal(off, on, ignore_order=True)


def test_serde_roundtrip_encoded_host_column(session):
    from spark_rapids_tpu.columnar.batch import HostColumnarBatch
    from spark_rapids_tpu.columnar.serde import (
        deserialize_batch,
        serialize_batch,
        serialized_size,
    )

    d = ENC.DeviceDictionary.from_values(["x", "yy", "zzz"])
    codes = np.array([0, 2, 1, 0, 2, 0], dtype=np.int32)
    validity = np.array([True, True, True, True, True, False])
    hc = ENC.HostDictionaryColumn(DataType.STRING, codes, validity, d)
    hb = HostColumnarBatch([hc], 6)
    blob = serialize_batch(hb)
    assert len(blob) == serialized_size(hb)
    back = deserialize_batch(blob)
    col = back.columns[0]
    assert isinstance(col, ENC.HostDictionaryColumn)
    # every entry referenced -> the pruned table equals the original, and
    # interning maps identical content onto the SAME object
    assert col.dictionary is d
    assert col.to_pylist() == ["x", "zzz", "yy", "x", "zzz", None]
    # round trip through the device: stays encoded
    dev = back.to_device()
    assert ENC.is_encoded(dev.columns[0])
    assert dev.columns[0].dictionary is d
    host = dev.to_host()
    assert host.columns[0].to_pylist() == \
        ["x", "zzz", "yy", "x", "zzz", None]


def test_serde_prunes_dictionary_per_piece():
    """A piece referencing a subset of the dictionary ships only the
    entries it uses (per-piece dictionary pruning), and round-trips."""
    from spark_rapids_tpu.columnar.batch import HostColumnarBatch
    from spark_rapids_tpu.columnar.serde import (
        deserialize_batch,
        serialize_batch,
        serialized_size,
    )

    big = ENC.DeviceDictionary.from_values(
        [f"value_{i:04d}" for i in range(1000)])
    codes = np.array([7, 7, 42, 7, 42], dtype=np.int32)
    validity = np.ones(5, dtype=bool)
    hb = HostColumnarBatch(
        [ENC.HostDictionaryColumn(DataType.STRING, codes, validity, big)],
        5)
    blob = serialize_batch(hb)
    assert len(blob) == serialized_size(hb)
    # pruned: far smaller than shipping all 1000 entries (~10KB)
    assert len(blob) < 200
    back = deserialize_batch(blob)
    assert back.columns[0].to_pylist() == \
        ["value_0007", "value_0007", "value_0042", "value_0007",
         "value_0042"]
    assert back.columns[0].dictionary.size == 2


def test_serialized_size_smaller_than_expanded():
    """Codes + one dictionary copy beat expanded strings by >= 2x on
    dictionary-heavy data (the shuffle-bytes win, measured exactly)."""
    from spark_rapids_tpu.columnar.batch import (
        HostColumnVector,
        HostColumnarBatch,
    )
    from spark_rapids_tpu.columnar.serde import serialized_size

    n = 4000
    values = ["alpha", "bravo", "charlie", "delta"]
    d = ENC.DeviceDictionary.from_values(values)
    codes = np.arange(n, dtype=np.int32) % 4
    validity = np.ones(n, dtype=bool)
    enc_b = HostColumnarBatch(
        [ENC.HostDictionaryColumn(DataType.STRING, codes, validity, d)], n)
    expanded = np.array([values[c] for c in codes], dtype=object)
    dec_b = HostColumnarBatch(
        [HostColumnVector(DataType.STRING, expanded, validity)], n)
    assert serialized_size(dec_b) >= 2 * serialized_size(enc_b)


# ---------------------------------------------------------------------------
# Analyzer: encoded byte model, savings containment, decode point
# ---------------------------------------------------------------------------
def test_analyzer_predicts_encoded_savings_and_decode_point(
        session, tmp_path):
    path = _write_dict_heavy(tmp_path, seed=11, n=10000)

    def q(s):
        return s.read.parquet(path) \
            .filter(F.col("flag") == F.lit("A")) \
            .groupBy("status").agg(F.sum("v").alias("t"))

    got = run_on_tpu(session, q)
    assert got is not None
    m = dict(session.last_query_metrics)
    if m["encodedColumns"] == 0:
        pytest.skip("scan did not emit encoded columns")
    report = session.last_resource_report
    assert report is not None and report.encoded_cols > 0
    # containment: measured savings inside the predicted interval
    saved = m["encodedBytesSaved"]
    assert report.encoded_saved.lo <= saved <= report.encoded_saved.hi
    # the decode point: codes survive to the result sink
    assert "sink" in report.decode_points
    # the encoded byte model is >= 2x smaller than the decoded equivalent
    assert report.encoded_decoded_bytes.hi >= \
        2 * report.encoded_code_bytes.hi > 0


def test_analyzer_peak_not_higher_with_encoding(session, tmp_path):
    path = _write_dict_heavy(tmp_path, seed=12, n=10000)

    def q(s):
        return s.read.parquet(path) \
            .filter(F.col("flag") == F.lit("A")) \
            .groupBy("status").agg(F.sum("v").alias("t"))

    run_on_tpu(session, q)
    rep_on = session.last_resource_report
    run_on_tpu(session, q, extra_conf={
        "rapids.tpu.sql.encoded.enabled": False})
    rep_off = session.last_resource_report
    if rep_on is None or rep_off is None or rep_on.encoded_cols == 0:
        pytest.skip("no encoded prediction")
    assert rep_on.peak_bytes.hi <= rep_off.peak_bytes.hi


def test_verifier_rejects_bogus_encoded_claim(session, tmp_path):
    from spark_rapids_tpu.plan.verify import verify_plan

    path = _write_dict_heavy(tmp_path, seed=13, n=500)
    df = session.read.parquet(path)
    physical = session._physical_plan(df._plan)

    def find_scan(node):
        from spark_rapids_tpu.io.scan import TpuFileScanExec

        if isinstance(node, TpuFileScanExec):
            return node
        for c in node.children:
            got = find_scan(c)
            if got is not None:
                return got
        return None

    scan = find_scan(physical)
    if scan is None:
        pytest.skip("no device scan in plan")
    # corrupt the cached claim: a column the scan does not output
    scan._encoded_plan_cache = ((True, 0.5), {"no_such_col": "certain"})
    violations = verify_plan(physical)
    assert any("encoded-column claim" in str(v) for v in violations)


# ---------------------------------------------------------------------------
# DictionaryColumn unit behavior
# ---------------------------------------------------------------------------
def test_dictionary_interning_and_remap():
    d1 = ENC.DeviceDictionary.from_values(["a", "b", "c"])
    d2 = ENC.DeviceDictionary.from_values(["a", "b", "c"])
    assert d1 is d2  # content-interned
    d3 = ENC.DeviceDictionary.from_values(["b", "x", "a"])
    remap = d3.remap_to(d1)
    assert list(remap) == [1, -1, 0]
    assert d1.code_of("b") == 1
    assert d1.code_of("absent") == -1


def test_materialize_counts_and_roundtrips(session):
    import jax.numpy as jnp

    d = ENC.DeviceDictionary.from_values(["aa", "b", "cccc"])
    codes = jnp.asarray(np.array([2, 0, 1, 0, 0, 0, 0, 0], np.int32))
    validity = jnp.asarray(
        np.array([True, True, True, False] + [False] * 4))
    cv = ENC.DictionaryColumn(DataType.STRING, codes, validity, d)
    from spark_rapids_tpu.utils import metrics as M

    before = M.late_materialization_count()
    out = ENC.materialize(cv)
    assert M.late_materialization_count() == before + 1
    from spark_rapids_tpu.columnar.batch import ColumnarBatch

    host = ColumnarBatch([out], 4).to_host()
    assert host.columns[0].to_pylist() == ["cccc", "aa", "b", None]


def test_concat_aligns_different_dictionaries(session):
    import jax.numpy as jnp

    from spark_rapids_tpu.columnar.batch import ColumnarBatch, concat_batches

    d1 = ENC.DeviceDictionary.from_values(["a", "b"])
    d2 = ENC.DeviceDictionary.from_values(["b", "z"])
    mk = lambda d, codes, n: ColumnarBatch(  # noqa: E731
        [ENC.DictionaryColumn(
            DataType.STRING, jnp.asarray(np.asarray(codes, np.int32)),
            jnp.asarray(np.array([True] * n + [False] *
                                 (len(codes) - n))), d)], n)
    b1 = mk(d1, [0, 1, 1, 0, 0, 0, 0, 0], 4)      # a b b a
    b2 = mk(d2, [1, 0, 0, 0, 0, 0, 0, 0], 3)      # z b b
    out = concat_batches([b1, b2])
    assert ENC.is_encoded(out.columns[0])
    host = out.to_host()
    assert host.columns[0].to_pylist() == \
        ["a", "b", "b", "a", "z", "b", "b"]


def test_align_encoded_many_pieces_single_union(session):
    """align_encoded merges ALL distinct dictionaries in one pass: codes
    stay correct across 3+ overlapping dictionaries, and when the base
    already covers every value the base dictionary itself is reused."""
    import jax.numpy as jnp

    mk = lambda d, codes: ENC.DictionaryColumn(  # noqa: E731
        DataType.STRING, jnp.asarray(np.asarray(codes, np.int32)),
        jnp.asarray(np.ones(len(codes), dtype=bool)), d)
    d1 = ENC.DeviceDictionary.from_values(["a", "b", "c"])
    d2 = ENC.DeviceDictionary.from_values(["c", "d"])
    d3 = ENC.DeviceDictionary.from_values(["d", "a", "e"])
    union, cols = ENC.align_encoded(
        [mk(d1, [0, 2]), mk(d2, [1, 0]), mk(d3, [2, 1])])
    assert union.size == 5       # a b c d e, each interned once
    vals = union.host_values()
    got = [[vals[int(c)] for c in np.asarray(col.data)] for col in cols]
    assert got == [["a", "c"], ["d", "c"], ["e", "a"]]
    # base codes are union codes unchanged
    assert [vals[i] for i in range(3)] == ["a", "b", "c"]
    # base covering every value: no new dictionary is interned
    sub = ENC.DeviceDictionary.from_values(["b", "c"])
    union2, _ = ENC.align_encoded([mk(d1, [0]), mk(sub, [1])])
    assert union2 is d1


def test_mixed_bare_and_computed_partition_keys(session, tmp_path):
    """Hash partitioning where an encoded column is BOTH a bare key and
    referenced inside a computed key expression: the ordinal
    materializes and its bare key hashes the values (bit-identical) —
    previously this crashed the exchange map task."""
    path = _write_dict_heavy(tmp_path, seed=17, row_group_size=1000)

    def q(s):
        return s.read.parquet(path) \
            .repartition(4, F.col("status"), F.length(F.col("status"))) \
            .groupBy("status").agg(F.count("*").alias("c"),
                                   F.sum("v").alias("t"))

    assert_tpu_and_cpu_are_equal_collect(session, q, ignore_order=True)


# ---------------------------------------------------------------------------
# Fault injection at the materialize site
# ---------------------------------------------------------------------------
@pytest.mark.usefixtures("device_string_decoder")
def test_fault_injection_at_materialize_site(session, tmp_path):
    """Injected OOM at encoded.materialize: spill+retry owns it, the
    query completes oracle-equal."""
    path = _write_dict_heavy(tmp_path, seed=21, n=3000)

    def q(s):
        # the ORDER BY forces a sort-boundary materialize
        return s.read.parquet(path) \
            .groupBy("status").agg(F.sum("v").alias("t")) \
            .orderBy("status")

    cpu = run_on_cpu(session, q)
    got = run_on_tpu(session, q, extra_conf={
        # the sort-boundary materialize exists only on the host loop (the
        # SPMD program keeps codes end-to-end and sorts via a rank LUT)
        "rapids.tpu.sql.spmd.enabled": False,
        "rapids.tpu.test.faultInjection.enabled": True,
        "rapids.tpu.test.faultInjection.sites": "encoded.materialize",
        "rapids.tpu.test.faultInjection.rate": 1.0,
        "rapids.tpu.test.faultInjection.seed": 3,
    })
    assert got == cpu
    m = session.last_query_metrics
    if m["encodedColumns"]:
        assert m["retries"] + m["cpuFallbackEvents"] >= 1


def test_spmd_stage_fallback_with_encoded(session, tmp_path):
    """SPMD enabled over an encoded scan: the stage either lowers (after
    the boundary decode) or falls back to the host loop — both paths
    oracle-equal."""
    path = _write_dict_heavy(tmp_path, seed=22, n=4000)
    assert_tpu_and_cpu_are_equal_collect(
        session,
        lambda s: s.read.parquet(path)
        .filter(F.col("flag") == F.lit("A"))
        .groupBy("status").agg(F.count("*").alias("c"),
                               F.sum("v").alias("t")),
        ignore_order=True,
        extra_conf={"rapids.tpu.sql.spmd.enabled": True})


# ===========================================================================
# Order-preserving codes (rank space): sort / range / min-max / window /
# comparison predicates compute on codes of the SORTED dictionary
# ===========================================================================
HOST_LOOP = {"rapids.tpu.sql.spmd.enabled": False}


def _write_sorted_lowcard(tmp_path, seed=0, n=4000, name="rr.parquet",
                          nulls=False, tier=False):
    """Sorted / low-cardinality columns: RLE-friendly (run tables attach
    to the string ones, which the device decodes) AND dictionary-encoded
    — the run-aware + rank-space flagship shape. `tier`: `grp` as a
    string beside it, a second key that carries runs."""
    rng = np.random.default_rng(seed)
    status = np.sort(rng.choice(["open", "closed", "pending"],
                                size=n)).astype(object)
    grp = np.sort(rng.integers(0, 8, size=n)).astype(np.int64)
    flag = rng.choice(["A", "B", "C", "N", "R"], size=n).astype(object)
    if nulls:
        flag = np.where(rng.random(n) < 0.05, None, flag)
    v = rng.integers(0, 10_000, size=n)
    cols = {"status": status, "grp": grp, "flag": flag, "v": v}
    if tier:
        cols["tier"] = np.array([f"t{g}" for g in grp], dtype=object)
    path = str(tmp_path / name)
    pq.write_table(pa.table(cols), path, use_dictionary=True,
                   row_group_size=2500)
    return path


@pytest.mark.parametrize("seed", SEEDS[:2])
@pytest.mark.parametrize("asc,nulls_first", [
    (True, True), (False, False),
    pytest.param(True, False, marks=pytest.mark.slow),
    pytest.param(False, True, marks=pytest.mark.slow)])
def test_encoded_orderby_rank_space(session, tmp_path, seed, asc,
                                    nulls_first):
    """ORDER BY over encoded columns sorts on RANK codes — zero decodes
    before the sink — across directions and null placement."""
    path = _write_dict_heavy(tmp_path, seed=seed)
    col = F.col("flag").asc() if (asc and nulls_first) else \
        F.col("flag").asc_nulls_last() if asc else \
        F.col("flag").desc_nulls_first() if nulls_first else \
        F.col("flag").desc()
    assert_tpu_and_cpu_are_equal_collect(
        session,
        lambda s: s.read.parquet(path)
        .groupBy("flag").agg(F.sum("v").alias("t")).orderBy(col),
        extra_conf=HOST_LOOP)
    m = session.last_query_metrics
    if m["encodedColumns"]:
        assert m["orderPreservingSorts"] > 0


def test_encoded_range_repartition_bounds_in_rank_space(session, tmp_path):
    """The global-sort RANGE exchange samples bounds as union RANKS from
    downloaded CODES: the batches route still encoded, and the only
    decodes are the sink expansions (one per non-empty output
    partition)."""
    path = _write_sorted_lowcard(tmp_path, seed=3)
    got = run_on_tpu(
        session,
        lambda s: s.read.parquet(path).select("flag", "v")
        .orderBy("flag"), extra_conf=HOST_LOOP)
    m = session.last_query_metrics
    assert m["encodedColumns"] > 0
    assert m["orderPreservingSorts"] > 0
    # sink-only decodes: one expansion of the encoded column per
    # non-empty sorted output partition, nothing at the range bounds
    n_out = len({r[0] for r in got})
    assert 0 < m["lateMaterializations"] <= n_out + 1
    cpu = run_on_cpu(session,
                     lambda s: s.read.parquet(path).select("flag", "v")
                     .orderBy("flag"))
    assert got == cpu


@pytest.mark.parametrize("seed", SEEDS[:2])
def test_encoded_minmax_rank_space(session, tmp_path, seed):
    """MIN/MAX over an encoded column reduces int32 RANKS per group and
    carries the winning CODE through partial -> exchange -> final: the
    finalize decode point is closed (sink-only expansions)."""
    path = _write_dict_heavy(tmp_path, seed=seed)
    got = run_on_tpu(
        session,
        lambda s: s.read.parquet(path)
        .groupBy("status").agg(F.min("flag").alias("mn"),
                               F.max("flag").alias("mx")),
        extra_conf=HOST_LOOP)
    m = session.last_query_metrics
    cpu = run_on_cpu(
        session,
        lambda s: s.read.parquet(path)
        .groupBy("status").agg(F.min("flag").alias("mn"),
                               F.max("flag").alias("mx")))
    assert sorted(got) == sorted(cpu)
    if m["encodedColumns"]:
        # ONE output batch with three encoded columns (status, mn, mx):
        # exactly the sink expansions, nothing at update/merge/finalize
        assert m["lateMaterializations"] == 3


@pytest.mark.parametrize("op,lit", [("lt", "closed"), ("le", "open"),
                                    ("gt", "closed"), ("ge", "x_absent"),
                                    ("between", None)])
def test_comparison_predicates_rank_thresholds(session, tmp_path, op, lit):
    """<, <=, >, >= (and BETWEEN, which lowers onto them) against string
    literals rewrite to RANK thresholds — including literals ABSENT from
    the dictionary — with no decode before the sink."""
    path = _write_dict_heavy(tmp_path, seed=11, nulls=True)

    def q(s):
        c = F.col("status")
        cond = {"lt": c < F.lit(lit), "le": c <= F.lit(lit),
                "gt": c > F.lit(lit), "ge": c >= F.lit(lit),
                "between": (c >= F.lit("closed")) & (c <= F.lit("open"))
                }[op]
        return s.read.parquet(path).filter(cond) \
            .groupBy("status").agg(F.count("*").alias("c"))

    assert_tpu_and_cpu_are_equal_collect(session, q, ignore_order=True)


def test_window_rank_space(session, tmp_path):
    """Window partition-by/order-by over encoded columns stays encoded as
    RANK codes; only window-function inputs decode."""
    from spark_rapids_tpu.plan.window_api import Window

    path = _write_dict_heavy(tmp_path, seed=12, nulls=False)
    w = Window.partitionBy("status").orderBy("flag")
    assert_tpu_and_cpu_are_equal_collect(
        session,
        lambda s: s.read.parquet(path)
        .select("status", "flag", "v",
                F.row_number().over(w).alias("rn")),
        ignore_order=True, extra_conf=HOST_LOOP)
    m = session.last_query_metrics
    if m["encodedColumns"]:
        assert m["orderPreservingSorts"] > 0


def test_sort_and_range_bounds_decode_pragmas_gone():
    """The decode points are CLOSED, not bypassed: the sanctioned
    eager-materialize pragmas that marked the sort and range-bounds
    boundary decodes no longer exist (sorts run on ranks; range bounds
    sample ranks from downloaded codes)."""
    import pathlib

    root = pathlib.Path(__file__).resolve().parent.parent
    sort_src = (root / "spark_rapids_tpu" / "exec" / "sort.py").read_text()
    assert "code order is NOT value order" not in sort_src
    assert "sanctioned decode site" not in sort_src
    ex_src = (root / "spark_rapids_tpu" / "shuffle" /
              "exchange.py").read_text()
    assert "range bounds need VALUES" not in ex_src
    assert "codes order is not value order" not in ex_src


def test_orc_dictionary_emission(session, tmp_path):
    """ORC DICTIONARY_V2 string columns join the code-space pipeline
    under the same eligibility as parquet."""
    import pyarrow.orc as po

    rng = np.random.default_rng(5)
    n = 4000
    tbl = pa.table({
        "flag": rng.choice(["A", "B", "C", "N", "R"],
                           size=n).astype(object),
        "v": rng.integers(0, 100, size=n)})
    path = str(tmp_path / "t.orc")
    po.write_table(tbl, path, dictionary_key_size_threshold=1.0)

    def q(s):
        return s.read.orc(path).filter(F.col("flag") <= F.lit("C")) \
            .groupBy("flag").agg(F.count("*").alias("c"),
                                 F.sum("v").alias("t")).orderBy("flag")

    assert_tpu_and_cpu_are_equal_collect(session, q, extra_conf=HOST_LOOP)
    m = session.last_query_metrics
    if m["encodedColumns"] == 0:
        pytest.skip("ORC writer did not dictionary-encode")
    assert m["orderPreservingSorts"] > 0


# ---------------------------------------------------------------------------
# Rank-table units: construction, caching per interned dictionary,
# union-remap consistency (incl. the concat regression)
# ---------------------------------------------------------------------------
def test_rank_table_construction_and_caching():
    d = ENC.DeviceDictionary.from_values(["cherry", "apple", "banana"])
    assert not d.is_sorted
    assert list(d.rank_codes()) == [2, 0, 1]
    sd = d.sorted_dict()
    assert sd.is_sorted and list(sd.host_values()) == [
        "apple", "banana", "cherry"]
    # cached per interned dictionary: same objects back
    assert d.sorted_dict() is sd
    assert d.rank_remap() is d.rank_remap()
    d2 = ENC.DeviceDictionary.from_values(["cherry", "apple", "banana"])
    assert d2 is d and d2.sorted_dict() is sd
    # an already-sorted dictionary is its own rank space (zero-cost)
    assert sd.sorted_dict() is sd and sd.rank_remap() is None
    # rank thresholds: count_lt_le over present and absent literals
    assert d.count_lt_le("banana") == (1, 2)
    assert d.count_lt_le("aardvark") == (0, 0)
    assert d.count_lt_le("zebra") == (3, 3)


def test_fixed_rank_table_and_materialize():
    import jax.numpy as jnp

    d = ENC.DeviceDictionary.from_fixed_values(
        np.array([30, 10, 20]), DataType.INT64)
    assert d.is_fixed and list(d.rank_codes()) == [2, 0, 1]
    assert d.code_of(20) == 2 and d.code_of(15) == -1
    assert d.count_lt_le(15) == (1, 1)
    col = ENC.DictionaryColumn(
        DataType.INT64, jnp.asarray(np.array([0, 1, 2, 0], np.int32)),
        jnp.asarray(np.array([True, True, True, False])), d)
    m = ENC.materialize(col)
    assert m.dtype is DataType.INT64
    assert list(np.asarray(m.data)[:3]) == [30, 10, 20]
    r = ENC.to_rank_space(col)
    assert r.dictionary is d.sorted_dict()
    assert list(np.asarray(r.data)) == [2, 0, 1, 0]


def test_union_remap_rank_consistency(session):
    """REGRESSION (concat union remap x rank tables): after concat
    aligns two batches onto a UNION dictionary, ordering the combined
    codes through the union's rank table must equal value order — a
    stale pre-union rank permutation can never order post-union codes,
    because rank tables cache on the immutable interned dictionary and
    the union is a DIFFERENT dictionary object."""
    from spark_rapids_tpu.columnar.batch import concat_batches
    import jax.numpy as jnp

    def enc_batch(values, dict_values):
        d = ENC.DeviceDictionary.from_values(dict_values)
        codes = np.array([dict_values.index(v) for v in values], np.int32)
        cap = 8
        codes = np.pad(codes, (0, cap - len(codes)))
        valid = np.zeros(cap, bool)
        valid[:len(values)] = True
        from spark_rapids_tpu.columnar.batch import ColumnarBatch

        col = ENC.DictionaryColumn(DataType.STRING, jnp.asarray(codes),
                                   jnp.asarray(valid), d)
        return ColumnarBatch([col], len(values)), d

    b1, d1 = enc_batch(["mango", "apple"], ["mango", "apple"])
    b2, d2 = enc_batch(["kiwi", "apple"], ["kiwi", "apple"])
    rank1_before = d1.rank_codes().copy()
    merged = concat_batches([b1, b2])
    u = merged.columns[0].dictionary
    assert u is not d1 and u is not d2
    # order the merged codes through the UNION's rank table
    codes = np.asarray(merged.columns[0].data)[:merged.num_rows]
    ranks = u.rank_codes()[codes]
    vals = [u.host_values()[c] for c in codes]
    assert [v for _, v in sorted(zip(ranks, vals))] == sorted(vals)
    # the pre-union dictionary's cached table is untouched (immutable)
    assert list(d1.rank_codes()) == list(rank1_before)


def test_serde_roundtrip_fixed_dictionary():
    from spark_rapids_tpu.columnar.serde import (
        deserialize_batch,
        serialize_batch,
    )

    d = ENC.DeviceDictionary.from_fixed_values(
        np.array([100, 7, 42]), DataType.INT64)
    col = ENC.HostDictionaryColumn(
        DataType.INT64, np.array([2, 0, 1, 2], np.int32),
        np.array([True, True, False, True]), d)
    from spark_rapids_tpu.columnar.batch import HostColumnarBatch

    buf = serialize_batch(HostColumnarBatch([col], 4))
    back = deserialize_batch(buf)
    c = back.columns[0]
    assert isinstance(c, ENC.HostDictionaryColumn)
    assert c.dictionary.value_dtype is DataType.INT64
    assert c.to_pylist() == [42, 100, None, 42]


# ---------------------------------------------------------------------------
# Run-aware kernels: aggregate per RUN, not per row
# ---------------------------------------------------------------------------
def test_run_tables_attach_and_survive_concat(session, tmp_path):
    from spark_rapids_tpu.io import parquet_device as PD
    import pyarrow.parquet as pq2

    path = _write_sorted_lowcard(tmp_path, seed=6)
    md = pq2.ParquetFile(path).metadata
    idx = {md.row_group(0).column(i).path_in_schema: i
           for i in range(md.num_columns)}
    col = md.row_group(0).column(idx["status"])
    cv = PD.decode_chunk_device(
        PD.read_chunk_bytes(path, col), DataType.STRING,
        md.row_group(0).num_rows, max_def=1, codec=col.compression,
        encoded_ok=True, max_dict_fraction=0.5)
    assert cv.runs is not None
    assert cv.runs.num_runs < md.row_group(0).num_rows // 4


@pytest.mark.usefixtures("device_string_decoder")
@pytest.mark.parametrize("seed", SEEDS[:2])
def test_run_collapsed_aggregate_oracle_equal(session, tmp_path, seed):
    """Sorted/low-cardinality scan -> the update batch collapses to one
    row per merged run: counts become run-length sums, min/max/filters
    evaluate per run — oracle-equal with runCollapsedRows > 0. Both keys
    are strings: the columns the scan leaves as codes with a run table."""
    path = _write_sorted_lowcard(tmp_path, seed=seed, tier=True)

    def q(s):
        return s.read.parquet(path) \
            .filter(F.col("status") != F.lit("zzz")) \
            .groupBy("status", "tier").agg(
                F.count("*").alias("c"), F.count("tier").alias("t"),
                F.min("tier").alias("mn"), F.max("status").alias("mx"))

    assert_tpu_and_cpu_are_equal_collect(session, q, ignore_order=True,
                                         extra_conf=HOST_LOOP)
    m = session.last_query_metrics
    if m["encodedColumns"]:
        assert m["runCollapsedRows"] > 0


def test_run_aware_off_matches_on(session, tmp_path):
    path = _write_sorted_lowcard(tmp_path, seed=7)

    def q(s):
        return s.read.parquet(path).groupBy("status").agg(
            F.count("*").alias("c"), F.sum("v").alias("t"))

    on = run_on_tpu(session, q, extra_conf=HOST_LOOP)
    m_on = dict(session.last_query_metrics)
    off = run_on_tpu(session, q, extra_conf={
        **HOST_LOOP, "rapids.tpu.sql.runAware.enabled": False})
    m_off = dict(session.last_query_metrics)
    assert sorted(on) == sorted(off)
    assert m_off["runCollapsedRows"] == 0
    # v (near-unique) is an aggregate input: its column has no run table
    # only when the scan couldn't prove pure-RLE — the collapse falls
    # back silently either way; when it engaged, rows really collapsed
    if m_on["runCollapsedRows"]:
        assert m_on["runCollapsedRows"] > 0


def test_run_fraction_gates_collapse(session, tmp_path):
    """A run fraction of ~0 disables the collapse (merged runs never
    clear it)."""
    path = _write_sorted_lowcard(tmp_path, seed=8)
    run_on_tpu(session,
               lambda s: s.read.parquet(path).groupBy("status").agg(
                   F.count("*").alias("c")),
               extra_conf={**HOST_LOOP,
                           "rapids.tpu.sql.runAware.maxRunFraction":
                           0.0001})
    assert session.last_query_metrics["runCollapsedRows"] == 0
