"""A filtered extract with its strings, written as parquet (PR 40): the
engine against a plain reference kept here (pyarrow's `filter` and
`select` over the table the files were made from), on the chip's sink
path (the device encoder off: there a DOUBLE makes it refuse the schema,
so the sink downloads and Arrow writes).

Held here: the rows, order-free, column by column; the written files'
parquet and Arrow schemas equal to what Arrow writes for the same table
of plain strings (which is what the sink wrote before PR 40) and the flag
chunks dictionary-encoded; the same with nulls in a flag column, with
batches of one file whose dictionaries differ, with a filter that keeps
nothing and one that keeps all, with a PLAIN string column beside the
dictionary ones, partitioned by a dictionary column, and as ORC and CSV;
that no dictionary column reaches `host_batch_to_arrow` expanded
(`lateMaterializations` 0 for the action); that a `collect()` of the same
plan still returns strings; the compaction's span, counter and program
names; and `host_batch_to_arrow`'s branch for a STRING column that is not
dictionary-coded: one Arrow call, the same array as the loop it
replaced."""

import os

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc
import pyarrow.parquet as pq
import pytest

import spark_rapids_tpu as srt
from spark_rapids_tpu import conf as C
from spark_rapids_tpu.columnar import batch as B
from spark_rapids_tpu.columnar.batch import HostColumnarBatch, HostColumnVector
from spark_rapids_tpu.columnar.dtypes import DataType
from spark_rapids_tpu.columnar.encoded import (
    DeviceDictionary,
    HostDictionaryColumn,
)
from spark_rapids_tpu.io import arrow_convert, writer
from spark_rapids_tpu.io.arrow_convert import host_batch_to_arrow, schema_attrs
from spark_rapids_tpu.plan import logical as L
from spark_rapids_tpu.utils import metrics as M

COLUMNS = ("qty", "price", "disc", "tax", "shipdate", "flag", "status")
CUTOFF = 10471                      # 1998-09-02 as days since 1970-01-01
ROWS = 3000                         # a file; two row groups


def _date(days: int):
    import datetime

    return datetime.date(1970, 1, 1) + datetime.timedelta(days=days)


def make_table(seed: int, rows: int = ROWS, flags=("A", "N", "R"),
               null_flags: bool = False, dates=(8036, 10592)) -> pa.Table:
    """lineitem's seven columns of Q1, seeded, and nine more a scan has to
    leave behind folded into one (`other`)."""
    rng = np.random.default_rng(seed)
    flag = pa.array(rng.choice(list(flags), rows),
                    mask=(rng.random(rows) < 0.1) if null_flags else None)
    return pa.table({
        "qty": rng.integers(1, 51, rows).astype(np.float64),
        "price": rng.integers(90000, 10500000, rows) / 100.0,
        "disc": rng.integers(0, 11, rows) / 100.0,
        "tax": rng.integers(0, 9, rows) / 100.0,
        "other": rng.integers(0, 1 << 40, rows),
        "flag": flag,
        "status": pa.array(rng.choice(["F", "O"], rows)),
        "shipdate": pa.array(rng.integers(dates[0], dates[1], rows)
                             .astype(np.int32)).cast(pa.date32())})


def write_files(root, tables, **kw) -> str:
    os.makedirs(root)
    for i, t in enumerate(tables):
        pq.write_table(t, os.path.join(root, f"f{i}.parquet"),
                       row_group_size=ROWS // 2, **kw)
    return str(root)


def reference(tables, cutoff: int = CUTOFF, columns=COLUMNS) -> pa.Table:
    """The plain reference: the extract by pyarrow alone."""
    whole = pa.concat_tables(tables)
    keep = pc.less_equal(whole.column("shipdate"),
                         pa.scalar(_date(cutoff), pa.date32()))
    return whole.filter(keep).select(list(columns))


def sorted_rows(table: pa.Table) -> pa.Table:
    table = table.combine_chunks()
    return table.sort_by([(name, "ascending") for name in table.column_names])


def assert_same_rows(got: pa.Table, want: pa.Table):
    assert got.column_names == want.column_names
    assert got.num_rows == want.num_rows
    got, want = sorted_rows(got), sorted_rows(want)
    for name in want.column_names:
        assert got.column(name).equals(want.column(name)), name


@pytest.fixture
def session():
    s = srt.new_session()
    # the chip's sink: the device encoder refuses a DOUBLE there
    s.conf.set(C.PARQUET_DEVICE_ENCODE.key, False)
    s.conf.set(C.ORC_DEVICE_ENCODE.key, False)
    # these tests count process-wide counters: on a loaded machine a slow
    # task's speculative duplicate would compact its batch a second time
    s.conf.set(C.SPECULATION_ENABLED.key, False)
    yield s
    s.stop()


def extract(session, src: str, cutoff: int = CUTOFF, columns=COLUMNS):
    from spark_rapids_tpu.columnar.dtypes import DataType as DT
    from spark_rapids_tpu.ops.literals import Literal
    from spark_rapids_tpu.plan.column import Column

    li = session.read.parquet(src)
    return li.filter(li["shipdate"] <= Column(Literal(cutoff, DT.DATE))) \
        .select(*columns)


def part_files(out: str):
    found = []
    for top, _dirs, files in os.walk(out):
        found += [os.path.join(top, f) for f in files
                  if f.endswith(".parquet")]
    return sorted(found)


def check_flag_chunks(files, want: pa.Table, tmp_path,
                      names=("flag", "status")):
    """The written files against what Arrow writes for the same table of
    plain strings: the parquet schema, the Arrow schema a reader sees and
    the footer's key-value metadata; each flag chunk dictionary-encoded."""
    ref_path = str(tmp_path / "as_plain_strings.parquet")
    pq.write_table(want, ref_path)
    ref = pq.ParquetFile(ref_path)
    for f in files:
        pf = pq.ParquetFile(f)
        assert pf.schema.equals(ref.schema)
        assert pf.schema_arrow.equals(ref.schema_arrow, check_metadata=True)
        assert pf.metadata.metadata == ref.metadata.metadata
        for name in names:
            at = pf.schema_arrow.get_field_index(name)
            assert pf.schema.column(at).physical_type == "BYTE_ARRAY"
            assert str(pf.schema.column(at).logical_type) == "String"
            assert pf.schema_arrow.field(at).type == pa.string()
            for g in range(pf.metadata.num_row_groups):
                chunk = pf.metadata.row_group(g).column(at)
                assert chunk.has_dictionary_page
                assert "RLE_DICTIONARY" in chunk.encodings


CASES = {
    # name: (tables' kwargs a file, cutoff)
    "plain": ([dict(seed=1), dict(seed=2)], CUTOFF),
    "nulls_in_a_flag": ([dict(seed=3, null_flags=True),
                         dict(seed=4, null_flags=True)], CUTOFF),
    "a_file_lacks_a_value": ([dict(seed=5), dict(seed=6, flags=("A", "R"))],
                             CUTOFF),
    "keeps_nothing": ([dict(seed=7), dict(seed=8)], 8000),
    "keeps_all": ([dict(seed=9), dict(seed=10)], 11000),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_extract_equals_the_reference(session, tmp_path, case):
    specs, cutoff = CASES[case]
    tables = [make_table(**kw) for kw in specs]
    src = write_files(tmp_path / "src", tables)
    out = str(tmp_path / "out")
    late = M.late_materialization_count()
    extract(session, src, cutoff).write.parquet(out)
    assert M.late_materialization_count() == late
    want = reference(tables, cutoff)
    files = part_files(out)
    if want.num_rows == 0:
        assert sum(pq.ParquetFile(f).metadata.num_rows for f in files) == 0
        return
    assert_same_rows(pq.read_table(files), want)
    check_flag_chunks(files, want, tmp_path)
    if case == "keeps_all":
        assert want.num_rows == len(specs) * ROWS


def test_batches_of_one_file_with_differing_dictionaries(session, tmp_path,
                                                         monkeypatch):
    """Two files whose flag dictionaries differ, brought into ONE written
    file (`coalesce(1)`): the sink downloads two batches under two
    dictionaries and the writer brings them to one."""
    tables = [make_table(seed=11), make_table(seed=12, flags=("R",))]
    src = write_files(tmp_path / "src", tables)
    out = str(tmp_path / "out")
    seen = []
    real = writer.host_batch_to_arrow

    def spy(batch, attrs):
        seen.append(batch.columns[COLUMNS.index("flag")].dictionary.size)
        return real(batch, attrs)

    monkeypatch.setattr(writer, "host_batch_to_arrow", spy)
    extract(session, src).coalesce(1).write.parquet(out)
    files = part_files(out)
    assert len(files) == 1 and sorted(set(seen)) == [1, 3]
    want = reference(tables)
    assert_same_rows(pq.read_table(files), want)
    check_flag_chunks(files, want, tmp_path)


def test_a_column_coded_in_one_batch_and_plain_in_another(session, tmp_path,
                                                          monkeypatch):
    """One written file (`coalesce(1)`) over a file whose flags are
    dictionary-encoded and one written without a dictionary: the scan
    hands the first split's flags over as codes and leaves the second's
    to the device decoder, so the sink downloads the same column as codes
    in one batch and as values in the other, and the writer brings the
    file's batches to values, the table the sink wrote before PR 40."""
    tables = [make_table(seed=31, null_flags=True),
              make_table(seed=32, null_flags=True)]
    src = str(tmp_path / "src")
    write_files(src, tables[:1])
    pq.write_table(tables[1], os.path.join(src, "f1.parquet"),
                   row_group_size=ROWS // 2, use_dictionary=False)
    out = str(tmp_path / "out")
    seen = []
    real = writer.host_batch_to_arrow

    def spy(batch, attrs):
        seen.append(tuple(type(batch.columns[COLUMNS.index(name)]).__name__
                          for name in ("flag", "status")))
        return real(batch, attrs)

    monkeypatch.setattr(writer, "host_batch_to_arrow", spy)
    extract(session, src).coalesce(1).write.parquet(out)
    files = part_files(out)
    assert len(files) == 1
    assert sorted(set(seen)) == [("HostColumnVector",) * 2,
                                 ("HostDictionaryColumn",) * 2]
    want = reference(tables)
    assert_same_rows(pq.read_table(files), want)
    ref_path = str(tmp_path / "as_plain_strings.parquet")
    pq.write_table(want, ref_path)
    pf, ref = pq.ParquetFile(files[0]), pq.ParquetFile(ref_path)
    assert pf.schema.equals(ref.schema)
    assert pf.schema_arrow.equals(ref.schema_arrow, check_metadata=True)


def test_concat_arrow_brings_differing_dictionaries_to_one():
    """The writer's own step, on hand-built batches: codes under two
    dictionaries, a null, and a value only the second batch has."""
    def coded(values, codes, valid):
        return HostColumnarBatch([HostDictionaryColumn(
            DataType.STRING, np.asarray(codes, np.int32),
            np.asarray(valid, bool), DeviceDictionary.from_values(values))])

    attrs = schema_attrs(pa.schema([("s", pa.string())]))
    table = writer._concat_arrow(
        [coded(["A", "N"], [0, 1, 0, 1], [1, 1, 0, 1]),
         coded(["N", "R"], [1, 0, 1], [1, 1, 1])], attrs)
    col = table.column("s")
    assert pa.types.is_dictionary(col.type)
    assert len({tuple(c.dictionary.to_pylist()) for c in col.chunks}) == 1
    assert col.cast(pa.string()).to_pylist() == ["A", "N", None, "N",
                                                 "R", "N", "R"]


def test_concat_arrow_brings_a_column_coded_in_some_batches_to_values():
    """`s` is codes in two batches and values in one; `t` is codes in all
    three, under differing dictionaries, and stays so."""
    def coded(values, codes):
        return HostDictionaryColumn(
            DataType.STRING, np.asarray(codes, np.int32),
            np.ones(len(codes), bool), DeviceDictionary.from_values(values))

    plain = HostColumnVector(DataType.STRING,
                             np.array(["R", "x"], object),
                             np.array([True, False]))
    attrs = schema_attrs(pa.schema([("s", pa.string()), ("t", pa.string())]))
    table = writer._concat_arrow(
        [HostColumnarBatch([coded(["A", "N"], [1, 0]),
                            coded(["F"], [0, 0])]),
         HostColumnarBatch([plain, coded(["F", "O"], [1, 0])]),
         HostColumnarBatch([coded(["N"], [0]), coded(["O"], [0])])], attrs)
    assert table.schema.field("s").type == pa.string()
    assert table.column("s").to_pylist() == ["N", "A", "R", None, "N"]
    t = table.column("t")
    assert pa.types.is_dictionary(t.type)
    assert len({tuple(c.dictionary.to_pylist()) for c in t.chunks}) == 1
    assert t.cast(pa.string()).to_pylist() == ["F", "F", "O", "F", "O"]


def test_a_plain_string_column_beside_the_dictionary_ones(session, tmp_path):
    """`note` is PLAIN-encoded (its split's strings are the device
    decoder's): it reaches Arrow as values through the one-call branch,
    the flags beside it as codes."""
    tables = []
    for seed in (13, 14):
        t = make_table(seed=seed)
        rng = np.random.default_rng(seed)
        note = pa.array([f"note {v} é" for v in rng.integers(0, 1 << 30,
                                                                  ROWS)],
                        mask=rng.random(ROWS) < 0.05)
        tables.append(t.append_column("note", note))
    src = write_files(tmp_path / "src", tables,
                      use_dictionary=["flag", "status"])
    out = str(tmp_path / "out")
    columns = COLUMNS + ("note",)
    extract(session, src, columns=columns).write.parquet(out)
    files = part_files(out)
    assert_same_rows(pq.read_table(files), reference(tables, columns=columns))
    for f in files:
        assert pq.ParquetFile(f).schema_arrow.field("note").type == pa.string()


def test_partitioned_by_a_dictionary_column(session, tmp_path):
    tables = [make_table(seed=15, null_flags=True), make_table(seed=16)]
    src = write_files(tmp_path / "src", tables)
    out = str(tmp_path / "out")
    extract(session, src).write.partitionBy("flag").parquet(out)
    want = reference(tables)
    dirs = sorted(d for d in os.listdir(out) if d.startswith("flag="))
    assert dirs == ["flag=A", "flag=N", "flag=R",
                    "flag=__HIVE_DEFAULT_PARTITION__"]
    rest = [c for c in COLUMNS if c != "flag"]
    for d in dirs:
        value = d.split("=", 1)[1]
        in_dir = pc.is_null(want.column("flag")) if value.startswith("__") \
            else pc.fill_null(pc.equal(want.column("flag"), value), False)
        files = part_files(os.path.join(out, d))
        got = pq.read_table(files, partitioning=None)
        assert_same_rows(got, want.filter(in_dir).select(rest))
        # the other dictionary column keeps its dictionary through the masks
        check_flag_chunks(files, want.select(rest), tmp_path,
                          names=("status",))


@pytest.mark.parametrize("fmt", ["orc", "csv"])
def test_a_format_that_takes_values_gets_them_decoded_in_arrow(
        session, tmp_path, fmt):
    tables = [make_table(seed=17, null_flags=True)]
    src = write_files(tmp_path / "src", tables)
    out = str(tmp_path / "out")
    late = M.late_materialization_count()
    getattr(extract(session, src).write, fmt)(out)
    assert M.late_materialization_count() == late
    files = sorted(os.path.join(out, f) for f in os.listdir(out)
                   if f.endswith("." + fmt))
    if fmt == "orc":
        import pyarrow.orc as po

        got = pa.concat_tables([po.read_table(f) for f in files])
    else:
        import pyarrow.csv as pcsv

        want_schema = reference(tables).schema
        got = pa.concat_tables([pcsv.read_csv(
            f, convert_options=pcsv.ConvertOptions(
                column_types=want_schema, strings_can_be_null=True))
            for f in files])
    assert_same_rows(got, reference(tables))


def test_no_dictionary_column_reaches_arrow_expanded(session, tmp_path,
                                                     monkeypatch):
    """The guard: the sink keeps the codes (`DeviceToHost` `keep_encoded`),
    the writer is handed `HostDictionaryColumn`s, `write.arrow` counts
    them, and nothing is materialized on the way
    (`lateMaterializations` 0 for the action)."""
    tables = [make_table(seed=18), make_table(seed=19)]
    src = write_files(tmp_path / "src", tables)
    handed = []
    real = writer.host_batch_to_arrow

    def spy(batch, attrs):
        handed.append([type(c) for c in batch.columns])
        return real(batch, attrs)

    monkeypatch.setattr(writer, "host_batch_to_arrow", spy)

    def no_expansion(*a, **k):
        raise AssertionError("a dictionary column was expanded at the sink")

    from spark_rapids_tpu.columnar import encoded as ENC

    monkeypatch.setattr(ENC, "materialize_host_values", no_expansion)
    monkeypatch.setattr(ENC, "materialize", no_expansion)
    session.conf.set("rapids.tpu.obs.tracing.enabled", True)
    late = M.late_materialization_count()
    extract(session, src).write.parquet(str(tmp_path / "out"))
    assert M.late_materialization_count() == late
    assert len(handed) == 2
    for types in handed:
        assert types[-2:] == [HostDictionaryColumn, HostDictionaryColumn]
        assert types[:-2] == [HostColumnVector] * 5
    tree = session.last_query_trace
    fences = [sp for sp in tree.spans() if sp.name == "DeviceToHost"]
    assert fences and all(sp.attrs["keep_encoded"] is True for sp in fences)
    arrows = [sp for sp in tree.spans() if sp.name == "write.arrow"]
    assert [sp.attrs["dict_columns"] for sp in arrows] == [2, 2]
    # a file's codes (4 B a row a column) and its dictionaries' bytes
    rows = [sp.attrs["rows"] for sp in tree.spans()
            if sp.name == "write.file"]
    assert sorted(sp.attrs["dict_bytes"] for sp in arrows) == sorted(
        2 * 4 * n + 3 + 2 for n in rows)


def test_a_collect_of_the_written_plan_still_returns_strings(session,
                                                             tmp_path):
    """The sink's `keep_encoded` is the write's own node: the plan cache
    hands the same physical plan to a collect(), whose rows are values."""
    tables = [make_table(seed=20)]
    src = write_files(tmp_path / "src", tables)
    df = extract(session, src)
    df.write.parquet(str(tmp_path / "out1"))
    rows = df.collect()
    df.write.parquet(str(tmp_path / "out2"))
    want = reference(tables)
    assert len(rows) == want.num_rows
    assert {type(r[5]) for r in rows} == {str}
    assert sorted(r[5:] for r in rows) == sorted(
        zip(want.column("flag").to_pylist(),
            want.column("status").to_pylist()))
    assert_same_rows(pq.read_table(part_files(str(tmp_path / "out2"))), want)


def test_the_compaction_has_a_span_a_counter_and_names(session, tmp_path):
    """What the device trace and the span tree find a compaction by: a
    `filter.compact` span a batch under the task with the network's
    steps, `compactedBatches`, the count and the move in programs whose
    names hold `compact`; the gather a sort or a slice runs keeps its
    name and is not run."""
    tables = [make_table(seed=21), make_table(seed=22)]
    src = write_files(tmp_path / "src", tables)
    session.conf.set("rapids.tpu.obs.tracing.enabled", True)
    df = extract(session, src)
    df.write.parquet(str(tmp_path / "warm"))        # compiles
    before = M.compacted_batch_count()
    sizes = (B._compact_shift_fixed_cols._cache_size(),
             B._gather_fixed_cols._cache_size())
    dispatches = M.dispatch_count()
    df.write.parquet(str(tmp_path / "out"))
    assert M.compacted_batch_count() - before == 2
    # a fused stage, the survivors' count and their move a split
    assert M.dispatch_count() - dispatches == 6
    assert (B._compact_shift_fixed_cols._cache_size(),
            B._gather_fixed_cols._cache_size()) == sizes
    assert sizes[0] >= 1
    for fn in (B._compact_plan, B._compact_shift_fixed_cols):
        assert "compact" in fn.__name__
    assert "compact" not in B._gather_fixed_cols.__name__
    tree = session.last_query_trace
    spans = [sp for sp in tree.spans() if sp.name == "filter.compact"]
    assert len(spans) == 2
    want = reference(tables)
    assert sum(sp.attrs["rows_out"] for sp in spans) == want.num_rows
    for sp in spans:
        assert sp.attrs["rows_in"] == ROWS and sp.attrs["columns"] == 7
        assert sp.attrs["capacity"] == B.bucket_capacity(ROWS)
        assert sp.attrs["lazy"] is False
        assert sp.attrs["steps"] == B.compact_steps(sp.attrs["capacity"])
    tasks = [sp for sp in tree.spans() if sp.kind == "task"]
    assert sum(sp.name == "filter.compact" for t in tasks
               for sp in _below(t)) == 2
    assert tree.counts_total()[M.COMPACTED_BATCHES] == 2
    assert session.last_query_metrics[M.COMPACTED_BATCHES] == 2


def _below(span):
    for c in span.children:
        yield c
        yield from _below(c)


def test_a_lazy_compaction_says_so_and_moves_under_the_same_name(
        session, tmp_path):
    tables = [make_table(seed=23)]
    src = write_files(tmp_path / "src", tables)
    session.conf.set(C.FILTER_COMPACT_SYNC.key, "never")
    session.conf.set("rapids.tpu.obs.tracing.enabled", True)
    untouched = B._gather_fixed_cols._cache_size()
    out = str(tmp_path / "out")
    dispatches = M.dispatch_count()
    extract(session, src).write.parquet(out)
    # a fused stage and the network, which counts for itself
    assert M.dispatch_count() - dispatches == 2
    assert B._gather_fixed_cols._cache_size() == untouched
    spans = [sp for sp in session.last_query_trace.spans()
             if sp.name == "filter.compact"]
    assert len(spans) == 1 and spans[0].attrs["lazy"] is True
    assert "rows_out" not in spans[0].attrs
    assert spans[0].attrs["steps"] == B.compact_steps(
        spans[0].attrs["capacity"])
    assert session.last_query_metrics[M.COMPACTED_BATCHES] == 1
    assert_same_rows(pq.read_table(part_files(out)), reference(tables))


# ---------------------------------------------------------------------------
# host_batch_to_arrow, by itself
# ---------------------------------------------------------------------------
def _loop_array(col) -> pa.Array:
    """The STRING branch as it was before PR 40: a Python step a row."""
    return pa.array([v if ok else None
                     for v, ok in zip(col.data, col.validity)],
                    type=pa.string())


@pytest.mark.parametrize("rows", [0, 1, 257])
def test_a_plain_string_column_is_one_arrow_call_and_the_same_array(
        rows, monkeypatch):
    rng = np.random.default_rng(rows)
    pool = np.array(["", "a", "été", "\U0001f600", "x" * 40, "N"],
                    dtype=object)
    data = pool[rng.integers(0, len(pool), rows)] if rows \
        else np.empty(0, dtype=object)
    validity = rng.random(rows) > 0.3
    col = HostColumnVector(DataType.STRING, data, validity)
    attrs = schema_attrs(pa.schema([("s", pa.string())]))
    calls = []
    real = pa.array

    def counting(obj, *a, **k):
        # a list of Python values is what the loop handed over
        assert not isinstance(obj, list)
        calls.append(type(obj))
        return real(obj, *a, **k)

    monkeypatch.setattr(arrow_convert.pa, "array", counting)
    got = host_batch_to_arrow(HostColumnarBatch([col], rows), attrs)
    monkeypatch.undo()
    assert calls == [np.ndarray]
    got = got.column("s").combine_chunks()
    want = _loop_array(col)
    assert got.type == pa.string() and got.equals(want)
    assert got.null_count == int((~validity).sum())
    # the same bytes: offsets and data
    for a, b in zip(got.buffers()[1:], want.buffers()[1:]):
        assert (a.to_pybytes() if a else b"") == (b.to_pybytes() if b else b"")


def test_a_dictionary_column_becomes_a_dictionary_array():
    d = DeviceDictionary.from_values(["A", "N", "R"])
    col = HostDictionaryColumn(DataType.STRING, np.array([2, 0, 0, 1], np.int32),
                               np.array([1, 1, 0, 1], bool), d)
    attrs = schema_attrs(pa.schema([("s", pa.string())]))
    late = M.late_materialization_count()
    arr = host_batch_to_arrow(HostColumnarBatch([col], 4), attrs) \
        .column("s").chunk(0)
    assert M.late_materialization_count() == late
    assert isinstance(arr, pa.DictionaryArray)
    assert arr.type == pa.dictionary(pa.int32(), pa.string())
    assert arr.dictionary.to_pylist() == ["A", "N", "R"]
    assert arr.indices.to_pylist() == [2, 0, None, 1]
    assert arr.to_pylist() == ["R", "A", None, "N"]
    # an empty dictionary under nothing but nulls
    empty = HostDictionaryColumn(DataType.STRING, np.zeros(2, np.int32),
                                 np.zeros(2, bool),
                                 DeviceDictionary.from_values([]))
    arr = host_batch_to_arrow(HostColumnarBatch([empty], 2), attrs) \
        .column("s").chunk(0)
    assert arr.to_pylist() == [None, None]


def test_a_fixed_width_dictionary_is_decoded_by_one_take():
    d = DeviceDictionary.from_fixed_values(np.array([10, 20, 30], np.int64),
                                           DataType.INT64)
    col = HostDictionaryColumn(DataType.INT64, np.array([2, 0, 1], np.int32),
                               np.array([1, 0, 1], bool), d)
    attrs = schema_attrs(pa.schema([("v", pa.int64())]))
    arr = host_batch_to_arrow(HostColumnarBatch([col], 3), attrs).column("v")
    assert arr.type == pa.int64() and arr.to_pylist() == [30, None, 20]


def test_write_table_stores_the_values_schema(tmp_path):
    """`_write_parquet_coded` by itself: the footer's `ARROW:schema` is
    the value type's, whatever metadata the table's schema carries."""
    codes = pa.array([0, 1, None, 1], pa.int32())
    coded = pa.table({"x": [1.0, 2.0, 3.0, 4.0],
                      "s": pa.DictionaryArray.from_arrays(
                          codes, pa.array(["F", "O"]))})
    plain = pa.table({"x": coded.column("x"),
                      "s": coded.column("s").cast(pa.string())})
    plan = L.WriteFile.__new__(L.WriteFile)
    plan.fmt, plan.options = "parquet", {}
    writer._write_table(coded, str(tmp_path / "coded.parquet"), plan)
    writer._write_table(plain, str(tmp_path / "plain.parquet"), plan)
    a = pq.ParquetFile(str(tmp_path / "coded.parquet"))
    b = pq.ParquetFile(str(tmp_path / "plain.parquet"))
    assert a.schema.equals(b.schema)
    assert a.schema_arrow.equals(b.schema_arrow, check_metadata=True)
    assert a.metadata.metadata == b.metadata.metadata
    assert a.read().equals(b.read())
    assert "RLE_DICTIONARY" in a.metadata.row_group(0).column(1).encodings
