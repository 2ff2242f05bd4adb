"""File I/O tests (reference: parquet_test.py, orc_test.py, csv_test.py,
ParquetWriterSuite)."""

import os

import numpy as np
import pytest

from spark_rapids_tpu.columnar.dtypes import DataType
from spark_rapids_tpu.plan import functions as F

from tests.harness import (
    DateGen,
    FloatGen,
    IntGen,
    StringGen,
    TimestampGen,
    assert_rows_equal,
    assert_tpu_and_cpu_are_equal_collect,
    gen_df,
    run_on_cpu,
    run_on_tpu,
)


def _write_sample(session, path, n=300, fmt="parquet"):
    df = gen_df(session, [("i", IntGen(DataType.INT32)),
                          ("l", IntGen(DataType.INT64)),
                          ("f", FloatGen(DataType.FLOAT32)),
                          ("s", StringGen(max_len=8)),
                          ("d", DateGen()),
                          ("t", TimestampGen())], n=n)
    getattr(df.write.mode("overwrite"), fmt)(path)
    return df


def test_parquet_roundtrip(session, tmp_path):
    path = str(tmp_path / "t.parquet")
    session.conf.set("rapids.tpu.sql.enabled", False)
    df = _write_sample(session, path)
    expected = df.collect()
    got_cpu = run_on_cpu(session, lambda s: s.read.parquet(path))
    got_tpu = run_on_tpu(session, lambda s: s.read.parquet(path))
    assert_rows_equal(expected, got_cpu, ignore_order=True)
    assert_rows_equal(expected, got_tpu, ignore_order=True)


def test_orc_roundtrip(session, tmp_path):
    path = str(tmp_path / "t.orc")
    session.conf.set("rapids.tpu.sql.enabled", False)
    df = _write_sample(session, path, fmt="orc")
    expected = df.collect()
    got = run_on_tpu(session, lambda s: s.read.orc(path))
    assert_rows_equal(expected, got, ignore_order=True)


def test_csv_roundtrip(session, tmp_path):
    path = str(tmp_path / "t.csv")
    session.conf.set("rapids.tpu.sql.enabled", False)
    df = session.createDataFrame(
        {"a": [1, 2, 3, None], "b": ["x", "", "z w", None]},
        [("a", "int"), ("b", "string")])
    df.write.mode("overwrite").option("header", True).csv(path)
    got = sorted(run_on_tpu(
        session,
        lambda s: s.read.schema([("a", "int"), ("b", "string")])
        .option("header", True).csv(path)), key=str)
    # CSV cannot distinguish null string from empty string
    expected = sorted([(1, "x"), (2, None), (3, "z w"), (None, None)],
                      key=str)
    assert got == expected


def test_parquet_query_equivalence(session, tmp_path):
    path = str(tmp_path / "q.parquet")
    session.conf.set("rapids.tpu.sql.enabled", False)
    _write_sample(session, path, n=500)
    assert_tpu_and_cpu_are_equal_collect(
        session,
        lambda s: s.read.parquet(path)
        .filter(F.col("i") > 0)
        .groupBy("s").agg(F.count("*").alias("c"), F.sum("l").alias("t")),
        ignore_order=True)


def test_parquet_row_group_splits(session, tmp_path):
    """Small maxReadBatchSizeRows must still read everything exactly once."""
    path = str(tmp_path / "rg.parquet")
    session.conf.set("rapids.tpu.sql.enabled", False)
    df = gen_df(session, [("v", IntGen(DataType.INT64))], n=1000,
                num_partitions=1)
    df.write.mode("overwrite").parquet(path)
    expected = df.collect()
    got = run_on_tpu(
        session, lambda s: s.read.parquet(path),
        extra_conf={"rapids.tpu.sql.reader.batchSizeRows": 100})
    assert_rows_equal(expected, got, ignore_order=True)


def test_write_modes(session, tmp_path):
    path = str(tmp_path / "m.parquet")
    session.conf.set("rapids.tpu.sql.enabled", False)
    df = session.createDataFrame({"v": [1, 2]}, [("v", "int")])
    df.write.parquet(path)
    with pytest.raises(Exception):
        df.write.parquet(path)  # default error mode
    df.write.mode("ignore").parquet(path)
    df.write.mode("overwrite").parquet(path)
    assert os.path.exists(os.path.join(path, "_SUCCESS"))
    assert sorted(session.read.parquet(path).collect()) == [(1,), (2,)]


def test_partitioned_write(session, tmp_path):
    path = str(tmp_path / "p.parquet")
    session.conf.set("rapids.tpu.sql.enabled", False)
    df = session.createDataFrame(
        {"k": [1, 1, 2, 2, 3], "v": [10, 20, 30, 40, 50]},
        [("k", "int"), ("v", "long")])
    df.write.mode("overwrite").partitionBy("k").parquet(path)
    assert os.path.isdir(os.path.join(path, "k=1"))
    assert os.path.isdir(os.path.join(path, "k=3"))
    # partition columns are rediscovered from the directory layout and
    # appended to the schema (Spark semantics)
    back = session.read.parquet(path)
    assert [a.name for a in back.schema] == ["v", "k"]
    assert sorted(back.collect()) == [
        (10, 1), (20, 1), (30, 2), (40, 2), (50, 3)]


def test_scan_disabled_falls_back(session, tmp_path):
    from tests.harness import assert_tpu_fallback_collect

    path = str(tmp_path / "d.parquet")
    session.conf.set("rapids.tpu.sql.enabled", False)
    session.createDataFrame({"v": [1, 2, 3]}, [("v", "int")]) \
        .write.mode("overwrite").parquet(path)
    assert_tpu_fallback_collect(
        session,
        lambda s: s.read.parquet(path),
        fallback_exec="CpuFileScanExec",
        ignore_order=True,
        extra_conf={"rapids.tpu.sql.format.parquet.read.enabled": False})


class TestPartitionedReads:
    """Hive-style partition discovery + partition-value columns per batch
    (reference: ColumnarPartitionReaderWithPartitionValues)."""

    def test_round_trip_partitioned_write_read(self, session, tmp_path):
        import numpy as np

        from spark_rapids_tpu.plan import functions as F

        path = str(tmp_path / "pt")
        df = session.createDataFrame(
            {"k": [1, 1, 2, 2, 3], "v": [10, 20, 30, 40, 50],
             "t": ["a", "b", "c", "d", "e"]},
            [("k", "long"), ("v", "long"), ("t", "string")])
        df.write.partitionBy("k").parquet(path)
        back = session.read.parquet(path)
        names = [a.name for a in back.schema]
        assert "k" in names  # partition column re-appears from directories
        rows = sorted(back.select("v", "t", "k").collect())
        assert rows == sorted(df.select("v", "t", "k").collect())

    def test_partition_column_types_and_filter(self, session, tmp_path):
        from tests.harness import assert_tpu_and_cpu_are_equal_collect

        path = str(tmp_path / "pt2")
        df = session.createDataFrame(
            {"k": [1, 2, 2], "s": ["x", "y", "z"], "v": [1.5, 2.5, 3.5]},
            [("k", "long"), ("s", "string"), ("v", "double")])
        df.write.partitionBy("k", "s").parquet(path)
        back = session.read.parquet(path)
        k_attr = [a for a in back.schema if a.name == "k"][0]
        from spark_rapids_tpu.columnar.dtypes import DataType

        assert k_attr.data_type is DataType.INT64  # inferred integral
        s_attr = [a for a in back.schema if a.name == "s"][0]
        assert s_attr.data_type is DataType.STRING
        # filtering on a partition column works on both engines
        from spark_rapids_tpu.plan import functions as F

        assert_tpu_and_cpu_are_equal_collect(
            session,
            lambda s: s.read.parquet(path).filter(F.col("k") == F.lit(2)),
            ignore_order=True)


@pytest.fixture
def device_chunks(monkeypatch):
    """The (dtype, codec) of every column chunk that reaches the device
    parquet decoder in the test. Only STRING chunks may (PR 30: a
    fixed-width column is Arrow's, whatever its pages look like)."""
    from spark_rapids_tpu.columnar.dtypes import DataType
    from spark_rapids_tpu.io import parquet_device as PD

    seen = []
    orig = PD.decode_chunk_device

    def spy(chunk, dtype, *a, **k):
        seen.append((dtype, k.get("codec", "UNCOMPRESSED")))
        return orig(chunk, dtype, *a, **k)

    monkeypatch.setattr(PD, "decode_chunk_device", spy)
    yield seen
    assert all(dt is DataType.STRING for dt, _ in seen), seen


@pytest.mark.usefixtures("device_string_decoder")
class TestDeviceParquetDecode:
    """The parquet scan vs the Arrow oracle: fixed-width columns through
    Arrow's read, the string column through the device decoder
    (io/parquet_device.py; reference: GpuParquetScan decodes on the
    accelerator, GpuParquetScan.scala:536-556)."""

    def _write(self, tmp_path, name="d.parquet", compression="NONE",
               n=3000, row_group_size=None):
        import numpy as np
        import pyarrow as pa
        import pyarrow.parquet as pq

        rng = np.random.default_rng(3)
        t = pa.table({
            "i64": pa.array(rng.integers(0, 30, n).astype(np.int64)),
            "i32n": pa.array([int(x) if x % 5 else None for x in range(n)],
                             type=pa.int32()),
            "wide": pa.array(rng.integers(-2**45, 2**45, n)
                             .astype(np.int64)),
            "s": pa.array([f"s{i%9}" for i in range(n)]),
        })
        path = str(tmp_path / name)
        pq.write_table(t, path, compression=compression,
                       use_dictionary=True, data_page_version="1.0",
                       row_group_size=row_group_size or n)
        return path

    def test_device_decode_equivalence(self, session, tmp_path,
                                       device_chunks):
        from tests.harness import assert_tpu_and_cpu_are_equal_collect

        path = self._write(tmp_path)
        assert_tpu_and_cpu_are_equal_collect(
            session, lambda s: s.read.parquet(path), ignore_order=True)

    def test_device_decode_multi_row_groups(self, session, tmp_path,
                                            device_chunks):
        from tests.harness import assert_tpu_and_cpu_are_equal_collect

        path = self._write(tmp_path, row_group_size=700)
        assert_tpu_and_cpu_are_equal_collect(
            session, lambda s: s.read.parquet(path), ignore_order=True)

    def test_snappy_decodes_on_device(self, session, tmp_path,
                                      device_chunks):
        # real-world parquet is snappy: the string column's device decode
        # must engage (host page decompression feeding the same device
        # expansion), not silently fall back to Arrow; the fixed-width
        # columns beside it never reach the decoder
        from tests.harness import assert_tpu_and_cpu_are_equal_collect
        from spark_rapids_tpu.columnar.dtypes import DataType

        path = self._write(tmp_path, name="snappy.parquet",
                           compression="SNAPPY")
        assert_tpu_and_cpu_are_equal_collect(
            session, lambda s: s.read.parquet(path), ignore_order=True)
        assert device_chunks == [(DataType.STRING, "SNAPPY")]

    def test_gzip_decodes_on_device(self, session, tmp_path, device_chunks):
        from tests.harness import assert_tpu_and_cpu_are_equal_collect
        from spark_rapids_tpu.columnar.dtypes import DataType

        path = self._write(tmp_path, name="gz.parquet", compression="GZIP")
        assert_tpu_and_cpu_are_equal_collect(
            session, lambda s: s.read.parquet(path), ignore_order=True)
        assert device_chunks == [(DataType.STRING, "GZIP")]

    def test_v2_pages_decode_on_device(self, session, tmp_path,
                                       device_chunks):
        # v2 data pages: unprefixed def levels ahead of the data section
        import numpy as np
        import pyarrow as pa
        import pyarrow.parquet as pq

        from tests.harness import assert_tpu_and_cpu_are_equal_collect

        n = 3000
        rng = np.random.default_rng(5)
        t = pa.table({
            "i64": pa.array(rng.integers(0, 30, n).astype(np.int64)),
            "i32n": pa.array([int(x) if x % 5 else None for x in range(n)],
                             type=pa.int32()),
            "s": pa.array([f"w{i % 11}" for i in range(n)]),
        })
        for comp in ("NONE", "SNAPPY"):
            path = str(tmp_path / f"v2_{comp}.parquet")
            pq.write_table(t, path, compression=comp, use_dictionary=True,
                           data_page_version="2.0")
            device_chunks.clear()
            assert_tpu_and_cpu_are_equal_collect(
                session, lambda s: s.read.parquet(path), ignore_order=True)
            assert device_chunks, comp

    def test_unsupported_codec_falls_back_correctly(self, session, tmp_path):
        # parquet LZ4's framing differs from Arrow's lz4 codec: stays on the
        # host Arrow path, results still correct
        from tests.harness import assert_tpu_and_cpu_are_equal_collect
        from spark_rapids_tpu.io import parquet_device as PD

        assert not PD.codec_supported("LZ4")
        path = self._write(tmp_path, name="lz4.parquet", compression="LZ4")
        assert_tpu_and_cpu_are_equal_collect(
            session, lambda s: s.read.parquet(path), ignore_order=True)

    def test_fixed_width_chunk_matches_arrow_and_is_not_the_decoders(
            self, session, tmp_path):
        import pyarrow.parquet as pq

        from spark_rapids_tpu.columnar.dtypes import DataType
        from spark_rapids_tpu.io import parquet_device as PD

        path = self._write(tmp_path, n=4000)
        pf = pq.ParquetFile(path)
        md = pf.metadata
        want = pf.read().column("i32n").to_pylist()
        col = md.row_group(0).column(1)
        # the rule (column_eligible) and the decoder's own refusal
        assert not PD.column_eligible(col, DataType.INT32)
        with pytest.raises(PD._Unsupported):
            PD.decode_chunk_device(
                PD.read_chunk_bytes(path, col), DataType.INT32,
                md.row_group(0).num_rows, max_def=1)
        got = [r[0] for r in
               session.read.parquet(path).select("i32n").collect()]
        assert got == want

    def test_string_dictionary_decodes_on_device(self, tmp_path):
        # BYTE_ARRAY dictionary chunk -> device string column: host parses
        # only the (offset,len) dict table; values gather on device
        import numpy as np
        import pyarrow as pa
        import pyarrow.parquet as pq

        from spark_rapids_tpu.columnar.dtypes import DataType
        from spark_rapids_tpu.io import parquet_device as PD

        n = 3000
        rng = np.random.default_rng(9)
        words = ["alpha", "beta", "", "gamma-delta", "日本語", "x" * 40]
        vals = [words[i] if i < len(words) else None
                for i in rng.integers(0, len(words) + 1, n)]
        t = pa.table({"s": pa.array(vals, type=pa.string())})
        path = str(tmp_path / "strs.parquet")
        pq.write_table(t, path, compression="NONE", use_dictionary=True,
                       data_page_version="1.0")
        md = pq.ParquetFile(path).metadata
        col = md.row_group(0).column(0)
        assert PD.column_eligible(col, DataType.STRING)
        chunk = PD.read_chunk_bytes(path, col)
        cv = PD.decode_chunk_device(chunk, DataType.STRING,
                                    md.row_group(0).num_rows, max_def=1)
        assert cv.offsets is not None
        import jax

        data = np.asarray(jax.device_get(cv.data))
        offs = np.asarray(jax.device_get(cv.offsets))
        valid = np.asarray(jax.device_get(cv.validity))
        for i, w in enumerate(vals):
            if w is None:
                assert not valid[i]
            else:
                got = data[offs[i]:offs[i + 1]].tobytes().decode("utf-8")
                assert valid[i] and got == w, (i, w, got)

    def test_string_scan_equivalence_device_decode(self, session, tmp_path):
        import numpy as np
        import pyarrow as pa
        import pyarrow.parquet as pq

        from tests.harness import assert_tpu_and_cpu_are_equal_collect
        from spark_rapids_tpu.plan import functions as F

        n = 2500
        rng = np.random.default_rng(10)
        cats = ["red", "green", "blue", "violet", ""]
        t = pa.table({
            "k": pa.array(rng.integers(0, 40, n).astype(np.int64)),
            "c": pa.array([cats[i] if i < len(cats) else None
                           for i in rng.integers(0, len(cats) + 1, n)],
                          type=pa.string()),
        })
        path = str(tmp_path / "mix.parquet")
        pq.write_table(t, path, compression="NONE", use_dictionary=True,
                       data_page_version="1.0")
        assert_tpu_and_cpu_are_equal_collect(
            session,
            lambda s: s.read.parquet(path)
            .filter(F.col("c") != "red")
            .groupBy("c").agg(F.sum("k").alias("sk"),
                              F.count("*").alias("n")),
            ignore_order=True)

    def test_device_encode_write_roundtrip(self, session, tmp_path):
        # TPU engine writes via the device encoder; both engines read the
        # file back identically (and pyarrow can read it: the reader IS
        # pyarrow on the oracle path)
        from decimal import Decimal

        import numpy as np

        from tests.harness import assert_tpu_and_cpu_are_equal_collect
        from spark_rapids_tpu.plan import functions as F

        n = 3000
        rng = np.random.default_rng(12)
        df_path = str(tmp_path / "devw.parquet")

        session.conf.set("rapids.tpu.sql.enabled", True)
        df = session.createDataFrame({
            "k": rng.integers(0, 50, n).astype(np.int64),
            "v": [int(x) if i % 9 else None
                  for i, x in enumerate(rng.integers(-10**9, 10**9, n))],
            "p": [Decimal(int(c)).scaleb(-2) if i % 4 else None
                  for i, c in enumerate(rng.integers(-10**5, 10**5, n))],
        }, [("k", "long"), ("v", "long"), ("p", "decimal(9,2)")],
            num_partitions=3)
        df.write.option("compression", "none").parquet(df_path)

        import os

        parts = [f for f in os.listdir(df_path) if f.endswith(".parquet")]
        assert len(parts) == 3  # one device-encoded file per partition

        assert_tpu_and_cpu_are_equal_collect(
            session,
            lambda s: s.read.parquet(df_path).groupBy("k").agg(
                F.sum("v").alias("sv"), F.sum("p").alias("sp"),
                F.count("*").alias("n")),
            ignore_order=True)

    def test_device_encode_respects_compression_opt(self, session, tmp_path):
        # explicit snappy produces a SNAPPY-tagged file (the device encoder
        # covers compressed writes via host block codecs; a host-only child
        # plan like this one uses the Arrow writer) and stays readable
        import numpy as np
        import pyarrow.parquet as pq

        session.conf.set("rapids.tpu.sql.enabled", True)
        p = str(tmp_path / "snap.parquet")
        session.createDataFrame(
            {"a": np.arange(100, dtype=np.int64)},
            [("a", "long")]).write.option("compression", "snappy").parquet(p)
        import os

        f = [x for x in os.listdir(p) if x.endswith(".parquet")][0]
        md = pq.ParquetFile(os.path.join(p, f)).metadata
        assert md.row_group(0).column(0).compression == "SNAPPY"

    def test_orc_device_decode_kernels_match_oracle(self, tmp_path):
        # every RLEv2 sub-encoding the device path supports, with nulls
        import numpy as np
        import pyarrow as pa
        import pyarrow.orc as po
        import jax
        import jax.numpy as jnp

        from spark_rapids_tpu.columnar.batch import bucket_capacity
        from spark_rapids_tpu.columnar.dtypes import DataType
        from spark_rapids_tpu.io import orc_device as OD

        rng = np.random.default_rng(4)
        n = 8000
        cases = {
            "seq": np.arange(n, dtype=np.int64),              # DELTA fixed
            "rand": rng.integers(-10**9, 10**9, n),           # DIRECT wide
            "small": rng.integers(0, 7, n).astype(np.int32),  # DIRECT narrow
            "rep": np.full(n, 42, dtype=np.int64),            # repeats
            "mono": np.cumsum(rng.integers(0, 100, n)),       # DELTA +
            "neg": -np.cumsum(rng.integers(0, 50, n)),        # DELTA -
        }
        nulls = rng.random(n) < 0.1
        tbl = pa.table({
            k: pa.array(np.where(nulls, None, v) if k == "rand" else v,
                        type=pa.int64() if v.dtype == np.int64
                        else pa.int32())
            for k, v in cases.items()})
        path = str(tmp_path / "od.orc")
        po.write_table(tbl, path, compression="uncompressed")
        raw = open(path, "rb").read()
        meta = OD.parse_file_meta(raw)
        oracle = po.ORCFile(path).read()
        row0 = 0
        for si in meta.stripes:
            streams, encs, _tz = OD.parse_stripe_footer(raw, si)
            cap = bucket_capacity(si.num_rows)
            region = raw[si.offset:si.offset + si.index_length +
                         si.data_length]
            stripe_dev = jnp.asarray(np.frombuffer(region, np.uint8))
            for name, arr in cases.items():
                cid = meta.names.index(name)
                dt = DataType.INT64 if arr.dtype == np.int64 \
                    else DataType.INT32
                assert OD.column_eligible(meta, cid, dt), name
                plan = OD.plan_column(raw, streams, encs, cid,
                                      si.num_rows, si.offset)
                d, v = OD.expand_column(stripe_dev, plan, dt,
                                        si.num_rows, cap)
                got = np.asarray(jax.device_get(d))[:si.num_rows]
                gv = np.asarray(jax.device_get(v))[:si.num_rows]
                want = oracle.column(name).to_pylist()[
                    row0:row0 + si.num_rows]
                for i, w in enumerate(want):
                    if w is None:
                        assert not gv[i], (name, i)
                    else:
                        assert gv[i] and got[i] == w, (name, i, w, got[i])
            row0 += si.num_rows

    def test_orc_device_scan_equivalence(self, session, tmp_path):
        import numpy as np
        import pyarrow as pa
        import pyarrow.orc as po

        from tests.harness import assert_tpu_and_cpu_are_equal_collect
        from spark_rapids_tpu.plan import functions as F

        n = 4000
        rng = np.random.default_rng(21)
        tbl = pa.table({
            "k": pa.array(rng.integers(0, 50, n).astype(np.int64)),
            "v": pa.array([int(x) if i % 11 else None for i, x in
                           enumerate(rng.integers(-10**6, 10**6, n))],
                          type=pa.int64()),
            "s": pa.array([f"tag{i % 5}" for i in range(n)]),
        })
        path = str(tmp_path / "mix.orc")
        po.write_table(tbl, path, compression="uncompressed")
        assert_tpu_and_cpu_are_equal_collect(
            session,
            lambda s: s.read.orc(path).filter(F.col("k") > 10)
            .groupBy("s").agg(F.sum("v").alias("sv"),
                              F.count("*").alias("n")),
            ignore_order=True)

    def test_orc_all_null_column(self, session, tmp_path):
        # an entirely-null int column has an EMPTY RLEv2 run table; the
        # device path must decode it as all-NULL, not crash
        import numpy as np
        import pyarrow as pa
        import pyarrow.orc as po

        from tests.harness import assert_tpu_and_cpu_are_equal_collect

        tbl = pa.table({"a": pa.array([None] * 1000, type=pa.int64()),
                        "b": pa.array(np.arange(1000, dtype=np.int64))})
        path = str(tmp_path / "nulls.orc")
        po.write_table(tbl, path, compression="uncompressed")
        assert_tpu_and_cpu_are_equal_collect(
            session, lambda s: s.read.orc(path), ignore_order=True)

    def test_orc_compressed_decodes_on_device(self, session, tmp_path,
                                              monkeypatch):
        # zlib/snappy ORC: host block decompression feeds the same device
        # expansion — the device path must ENGAGE, not silently fall back
        import numpy as np
        import pyarrow as pa
        import pyarrow.orc as po

        from tests.harness import assert_tpu_and_cpu_are_equal_collect
        from spark_rapids_tpu.io import orc_device as OD

        calls = []
        orig = OD.normalize_stripe

        def spy(*a, **k):
            calls.append(1)
            return orig(*a, **k)

        monkeypatch.setattr(OD, "normalize_stripe", spy)
        rng = np.random.default_rng(6)
        tbl = pa.table({
            "a": pa.array(np.arange(3000, dtype=np.int64)),
            "b": pa.array(rng.integers(-2000, 2000, 3000)
                          .astype(np.int32)),
            "n": pa.array([int(x) if x % 6 else None for x in range(3000)],
                          type=pa.int64()),
        })
        for comp in ("zlib", "snappy"):
            path = str(tmp_path / f"{comp}.orc")
            po.write_table(tbl, path, compression=comp)
            calls.clear()
            assert_tpu_and_cpu_are_equal_collect(
                session, lambda s: s.read.orc(path), ignore_order=True)
            assert calls, f"{comp}: device decode did not engage"

    def test_orc_unsupported_codec_falls_back(self, session, tmp_path):
        import numpy as np
        import pyarrow as pa
        import pyarrow.orc as po

        from tests.harness import assert_tpu_and_cpu_are_equal_collect

        tbl = pa.table({"a": pa.array(np.arange(500, dtype=np.int64))})
        path = str(tmp_path / "zs.orc")
        po.write_table(tbl, path, compression="zstd")
        assert_tpu_and_cpu_are_equal_collect(
            session, lambda s: s.read.orc(path), ignore_order=True)

    def test_required_columns_decode(self, session, tmp_path,
                                     device_chunks):
        # required (non-nullable) columns carry no def levels (max_def=0)
        import numpy as np
        import pyarrow as pa
        import pyarrow.parquet as pq

        from tests.harness import assert_tpu_and_cpu_are_equal_collect

        n = 2000
        rng = np.random.default_rng(5)
        schema = pa.schema([pa.field("r", pa.int64(), nullable=False),
                            pa.field("o", pa.int64(), nullable=True)])
        t = pa.table({"r": rng.integers(0, 9, n).astype(np.int64),
                      "o": rng.integers(0, 9, n).astype(np.int64)},
                     schema=schema)
        path = str(tmp_path / "req.parquet")
        pq.write_table(t, path, compression="NONE", use_dictionary=True,
                       data_page_version="1.0")
        assert_tpu_and_cpu_are_equal_collect(
            session, lambda s: s.read.parquet(path), ignore_order=True)

    def test_device_decode_respects_batch_size_rows(self, session, tmp_path,
                                                    device_chunks):
        from tests.harness import assert_tpu_and_cpu_are_equal_collect

        path = self._write(tmp_path, name="big.parquet", n=2000)
        assert_tpu_and_cpu_are_equal_collect(
            session, lambda s: s.read.parquet(path), ignore_order=True,
            extra_conf={"rapids.tpu.sql.reader.batchSizeRows": 300})


class TestDeviceOrcEncode:
    """Device-side ORC encode (io/orc_encode_device.py): the analog of the
    parquet device encoder for ORC writes (reference encodes ORC on the
    accelerator, GpuOrcFileFormat.scala / ColumnarOutputWriter.scala:62-177).
    """

    def _df(self, session, n=3000):
        # the projection makes the write input DEVICE-resident (device
        # encoders serve device plans; a bare host frame writes via Arrow)
        df = gen_df(session,
                    [("a", IntGen(DataType.INT64, lo=-1000, hi=1000)),
                     ("b", IntGen(DataType.INT64, nullable=True)),
                     ("c", IntGen(DataType.INT32, lo=0, hi=30))],
                    n=n, num_partitions=2, seed=11)
        return df.withColumn("a", F.col("a") + F.lit(0))

    def test_device_encode_roundtrip(self, session, tmp_path, monkeypatch):
        import pyarrow.orc as po

        from spark_rapids_tpu.io import orc_encode_device as OE

        calls = []
        orig = OE.write_file

        def spy(*a, **k):
            calls.append(1)
            return orig(*a, **k)

        monkeypatch.setattr(OE, "write_file", spy)
        session.set_conf("rapids.tpu.sql.enabled", True)
        df = self._df(session)
        out = str(tmp_path / "orc_dev")
        df.write.orc(out)
        assert calls, "device ORC encoder did not engage"

        # pyarrow reads the device-encoded files bit-correctly
        import os

        files = sorted(f for f in os.listdir(out) if f.endswith(".orc"))
        assert files
        got = {}
        for f in files:
            t = po.read_table(os.path.join(out, f))
            for a, b, c in zip(*(t.column(i).to_pylist() for i in range(3))):
                got.setdefault((a, b, c), 0)
                got[(a, b, c)] += 1
        want = {}
        for r in df.collect():
            want.setdefault(tuple(r), 0)
            want[tuple(r)] += 1
        assert got == want

    def test_device_encoded_file_reads_back_both_engines(self, session,
                                                         tmp_path):
        from tests.harness import assert_tpu_and_cpu_are_equal_collect

        session.set_conf("rapids.tpu.sql.enabled", True)
        df = self._df(session, n=1200)
        out = str(tmp_path / "orc_rt")
        df.write.orc(out)
        assert_tpu_and_cpu_are_equal_collect(
            session, lambda s: s.read.orc(out), ignore_order=True)

    def test_float_schema_uses_host_writer(self, session, tmp_path,
                                           monkeypatch):
        import numpy as np

        from spark_rapids_tpu.io import orc_encode_device as OE

        calls = []
        monkeypatch.setattr(OE, "write_file",
                            lambda *a, **k: calls.append(1) or 0)
        session.set_conf("rapids.tpu.sql.enabled", True)
        df = session.createDataFrame(
            {"x": np.random.default_rng(0).random(100)},
            [("x", "double")], num_partitions=1)
        out = str(tmp_path / "orc_host")
        df.write.orc(out)
        assert not calls  # float: host Arrow writer
        import pyarrow.orc as po
        import os

        files = [f for f in os.listdir(out) if f.endswith(".orc")]
        assert sum(po.read_table(os.path.join(out, f)).num_rows
                   for f in files) == 100


class TestDeviceOrcStrings:
    """ORC STRING columns decode on device (DIRECT_V2 length+bytes and
    DICTIONARY_V2 index+dict gather; reference: cudf's device ORC string
    decode behind GpuOrcScan.scala)."""

    def _write(self, tmp_path, comp="uncompressed", n=6000,
               stripe_size=None):
        import numpy as np
        import pyarrow as pa
        import pyarrow.orc as po

        rng = np.random.default_rng(14)
        words = ["alpha", "beta", "", "gamma-delta", "日本語x", "w" * 30]
        vals = [words[i] if i < len(words) else None
                for i in rng.integers(0, len(words) + 1, n)]
        t = pa.table({
            "k": pa.array(rng.integers(0, 25, n).astype(np.int64)),
            "s": pa.array(vals, type=pa.string()),
        })
        path = str(tmp_path / f"str_{comp}.orc")
        kw = {"stripe_size": stripe_size} if stripe_size else {}
        po.write_table(t, path, compression=comp, **kw)
        return path

    @pytest.mark.parametrize("comp", ["uncompressed", "zlib", "snappy"])
    def test_string_scan_equivalence(self, session, tmp_path, comp):
        path = self._write(tmp_path, comp)
        assert_tpu_and_cpu_are_equal_collect(
            session,
            lambda s: s.read.orc(path)
            .filter(F.col("s") != "alpha")
            .groupBy("s").agg(F.sum("k").alias("sk"),
                              F.count("*").alias("n")),
            ignore_order=True)

    def test_string_multi_stripe(self, session, tmp_path):
        path = self._write(tmp_path, "zlib", n=20000, stripe_size=64 * 1024)
        assert_tpu_and_cpu_are_equal_collect(
            session, lambda s: s.read.orc(path), ignore_order=True)

    def test_string_decode_engages(self, session, tmp_path, monkeypatch):
        from spark_rapids_tpu.io import orc_device as OD

        calls = []
        orig = OD.expand_string_column

        def spy(*a, **k):
            calls.append(1)
            return orig(*a, **k)

        monkeypatch.setattr(OD, "expand_string_column", spy)
        path = self._write(tmp_path)
        assert_tpu_and_cpu_are_equal_collect(
            session, lambda s: s.read.orc(path), ignore_order=True)
        assert calls, "device ORC string decode did not engage"


class TestDeviceOrcFloats:
    """ORC FLOAT/DOUBLE columns decode on device: the DATA stream is raw
    IEEE754 LE values — one gather+bitcast (reference decodes all types on
    the accelerator, GpuOrcScan.scala)."""

    @pytest.mark.parametrize("comp", ["uncompressed", "snappy"])
    def test_float_scan_equivalence(self, session, tmp_path, comp):
        import numpy as np
        import pyarrow as pa
        import pyarrow.orc as po

        rng = np.random.default_rng(15)
        n = 4000
        t = pa.table({
            "k": pa.array(rng.integers(0, 15, n).astype(np.int64)),
            "f": pa.array(rng.random(n).astype(np.float32)),
            "d": pa.array([float(x) if i % 6 else None
                           for i, x in enumerate(rng.random(n) * 1e6)],
                          type=pa.float64()),
        })
        path = str(tmp_path / f"flt_{comp}.orc")
        po.write_table(t, path, compression=comp)
        assert_tpu_and_cpu_are_equal_collect(
            session,
            lambda s: s.read.orc(path)
            .filter(F.col("f") < F.lit(0.9))
            .groupBy("k").agg(F.sum("d").alias("sd"),
                              F.count("*").alias("n")),
            ignore_order=True, approx_float=1e-9)

    def test_float_decode_engages(self, session, tmp_path, monkeypatch):
        import numpy as np
        import pyarrow as pa
        import pyarrow.orc as po

        from spark_rapids_tpu.io import orc_device as OD

        calls = []
        orig = OD.expand_float_column

        def spy(*a, **k):
            calls.append(1)
            return orig(*a, **k)

        monkeypatch.setattr(OD, "expand_float_column", spy)
        t = pa.table({"d": pa.array(
            np.random.default_rng(1).random(500))})
        path = str(tmp_path / "fd.orc")
        po.write_table(t, path)
        assert_tpu_and_cpu_are_equal_collect(
            session, lambda s: s.read.orc(path), ignore_order=True)
        assert calls, "device ORC float decode did not engage"


class TestDeviceParquetPlainStrings:
    """PLAIN byte-array string pages decode on device: the host walks the
    (length, bytes) stream into per-value tables (native single pass) and
    the device gathers the value bytes (reference decodes plain strings on
    the accelerator via cudf, GpuParquetScan.scala:536-556)."""

    def _write(self, tmp_path, name, n=4000, **kw):
        import numpy as np
        import pyarrow as pa
        import pyarrow.parquet as pq

        rng = np.random.default_rng(16)
        vals = [f"val-{i}-{rng.integers(0, 10**9)}" if i % 7 else None
                for i in range(n)]
        t = pa.table({
            "k": pa.array(rng.integers(0, 25, n).astype(np.int64)),
            "s": pa.array(vals, type=pa.string()),
        })
        path = str(tmp_path / name)
        pq.write_table(t, path, use_dictionary=False, **kw)
        return path

    @pytest.mark.parametrize("kw", [
        {"compression": "NONE"},
        {"compression": "SNAPPY"},
        {"compression": "SNAPPY", "data_page_version": "2.0"},
    ])
    def test_plain_string_scan_equivalence(self, session, tmp_path, kw):
        path = self._write(tmp_path, "ps.parquet", **kw)
        assert_tpu_and_cpu_are_equal_collect(
            session,
            lambda s: s.read.parquet(path)
            .groupBy("k").agg(F.count("s").alias("c"),
                              F.min("s").alias("mn")),
            ignore_order=True)

    def test_plain_string_decode_engages(self, session, tmp_path,
                                         monkeypatch):
        from spark_rapids_tpu.io import parquet_device as PD

        calls = []
        orig = PD._parse_plain_strings

        def spy(*a, **k):
            calls.append(1)
            return orig(*a, **k)

        monkeypatch.setattr(PD, "_parse_plain_strings", spy)
        path = self._write(tmp_path, "pse.parquet", compression="SNAPPY")
        assert_tpu_and_cpu_are_equal_collect(
            session, lambda s: s.read.parquet(path), ignore_order=True)
        assert calls, "plain-string device decode did not engage"


def test_orc_patched_base_decodes_on_device(session, tmp_path):
    """PATCHED_BASE RLEv2 runs (outlier-heavy int columns): packed values
    expand on device and the host-parsed patch list applies as one
    scatter-add. Verified against real orc-core-written files."""
    import numpy as np
    import pyarrow as pa
    import pyarrow.orc as po

    from spark_rapids_tpu.columnar.dtypes import DataType as DT
    from spark_rapids_tpu.io import orc_device as OD

    rng = np.random.default_rng(21)
    n = 15000
    vals = rng.integers(0, 100, n).astype(np.int64)
    vals[rng.choice(n, 40, replace=False)] = \
        rng.integers(10**11, 10**12, 40)
    neg = vals.copy()
    neg[::3] -= 10**6
    path = str(tmp_path / "patched.orc")
    po.write_table(pa.table({"a": pa.array(vals), "b": pa.array(neg)}),
                   path, compression="zlib")

    # the writer really used PATCHED_BASE (else this test is vacuous)
    raw = open(path, "rb").read()
    meta = OD.parse_file_meta(raw)
    si = meta.stripes[0]
    region = raw[si.offset:si.offset + si.index_length + si.data_length
                 + si.footer_length]
    norm, streams, encs, _tz = OD.normalize_stripe(region, si, meta.compression)
    plan = OD.plan_column(norm, streams, encs, 1, si.num_rows, 0,
                          dtype=DT.INT64)
    assert plan.rt.patch_pos.size > 0

    assert_tpu_and_cpu_are_equal_collect(
        session,
        lambda s: s.read.orc(path).groupBy().agg(
            F.sum("a").alias("sa"), F.sum("b").alias("sb"),
            F.max("a").alias("ma"), F.min("b").alias("mb")),
        ignore_order=True)


class TestDeviceOrcMoreTypes:
    """BOOLEAN (byte-RLE bitmap), TIMESTAMP (seconds + packed nanos), and
    wide (>32-bit) RLEv2 widths decode on device."""

    def test_bool_scan_equivalence(self, session, tmp_path):
        import numpy as np
        import pyarrow as pa
        import pyarrow.orc as po

        rng = np.random.default_rng(22)
        n = 5000
        bools = [bool(x) if i % 9 else None
                 for i, x in enumerate(rng.random(n) < 0.4)]
        t = pa.table({
            "b": pa.array(bools, type=pa.bool_()),
            "k": pa.array(rng.integers(0, 9, n).astype(np.int64)),
        })
        path = str(tmp_path / "b.orc")
        po.write_table(t, path, compression="zlib")
        assert_tpu_and_cpu_are_equal_collect(
            session,
            lambda s: s.read.orc(path)
            .groupBy("b").agg(F.count("*").alias("n"),
                              F.sum("k").alias("sk")),
            ignore_order=True)

    def test_timestamp_scan_equivalence(self, session, tmp_path,
                                        monkeypatch):
        import numpy as np
        import pyarrow as pa
        import pyarrow.orc as po

        from spark_rapids_tpu.io import orc_device as OD

        calls = []
        orig = OD.expand_timestamp_column

        def spy(*a, **k):
            calls.append(1)
            return orig(*a, **k)

        monkeypatch.setattr(OD, "expand_timestamp_column", spy)
        rng = np.random.default_rng(23)
        n = 5000
        # post-2000 seconds keep the epoch-relative stream narrow enough
        # for the device path (width <= 56); mixed sub-second precisions
        # exercise every trailing-zero scale code
        secs = rng.integers(946_684_800, 2_000_000_000, n)
        sub = rng.integers(0, 1_000_000, n)
        sub[::3] = (sub[::3] // 1000) * 1000      # ms precision
        sub[::5] = 0                              # whole seconds
        us = secs * 1_000_000 + sub
        ts = [int(x) if i % 8 else None for i, x in enumerate(us)]
        t = pa.table({
            "t": pa.array(ts, type=pa.timestamp("us")),
            "k": pa.array(rng.integers(0, 7, n).astype(np.int64)),
        })
        path = str(tmp_path / "ts.orc")
        po.write_table(t, path, compression="snappy")
        assert_tpu_and_cpu_are_equal_collect(
            session,
            lambda s: s.read.orc(path)
            .groupBy("k").agg(F.count("t").alias("n"),
                              F.min("t").alias("mn"),
                              F.max("t").alias("mx")),
            ignore_order=True)
        assert calls, "device ORC timestamp decode did not engage"

    def test_wide_direct_widths(self, session, tmp_path):
        import numpy as np
        import pyarrow as pa
        import pyarrow.orc as po

        rng = np.random.default_rng(24)
        vals = rng.integers(-2**54, 2**54, 4000).astype(np.int64)
        path = str(tmp_path / "w.orc")
        po.write_table(pa.table({"a": pa.array(vals)}), path,
                       compression="uncompressed")
        assert_tpu_and_cpu_are_equal_collect(
            session,
            lambda s: s.read.orc(path).agg(F.sum("a").alias("s"),
                                           F.min("a").alias("mn"),
                                           F.max("a").alias("mx")),
            ignore_order=True)


def test_parquet_bool_decodes_on_device(session, tmp_path, device_chunks):
    """BOOLEAN columns, PLAIN LSB-first bit-packing (v1) and
    length-prefixed RLE (v2): Arrow's, like every fixed-width column; the
    device decoder is not entered."""
    import numpy as np
    import pyarrow as pa
    import pyarrow.parquet as pq

    rng = np.random.default_rng(25)
    n = 5000
    bools = [bool(x) if i % 9 else None
             for i, x in enumerate(rng.random(n) < 0.35)]
    t = pa.table({
        "b": pa.array(bools, type=pa.bool_()),
        "k": pa.array(rng.integers(0, 8, n).astype(np.int64)),
    })
    for ver in ("1.0", "2.0"):
        path = str(tmp_path / f"pb_{ver}.parquet")
        pq.write_table(t, path, compression="SNAPPY",
                       data_page_version=ver)
        assert_tpu_and_cpu_are_equal_collect(
            session,
            lambda s: s.read.parquet(path)
            .groupBy("b").agg(F.count("*").alias("n"),
                              F.sum("k").alias("sk")),
            ignore_order=True)
        assert not device_chunks, ver


class TestParquetDeltaBinaryPacked:
    """DELTA_BINARY_PACKED integral pages: Arrow's, like every
    fixed-width column (the delta kernel serves DELTA_LENGTH_BYTE_ARRAY
    strings' lengths)."""

    def _write(self, tmp_path, name, n=5000, nulls=False, comp="NONE"):
        import numpy as np
        import pyarrow as pa
        import pyarrow.parquet as pq

        rng = np.random.default_rng(13)
        big = rng.integers(-2**40, 2**40, n).astype(np.int64)
        seq = np.cumsum(rng.integers(0, 9, n)).astype(np.int64)
        i32 = rng.integers(-2**30, 2**30, n).astype(np.int32)
        cols = {
            "seq": pa.array(seq),           # tiny widths
            "big": pa.array(big),           # wide deltas
            "i32": pa.array(i32),
        }
        if nulls:
            cols["ni"] = pa.array(
                [int(x) if x % 7 else None for x in range(n)],
                type=pa.int64())
        t = pa.table(cols)
        path = str(tmp_path / name)
        pq.write_table(
            t, path, compression=comp, use_dictionary=False,
            column_encoding={c: "DELTA_BINARY_PACKED" for c in cols},
            data_page_version="2.0", version="2.6")
        return path

    def test_delta_decodes_on_device(self, session, tmp_path,
                                     device_chunks):
        from tests.harness import assert_tpu_and_cpu_are_equal_collect

        for comp, nulls in (("NONE", False), ("SNAPPY", True)):
            path = self._write(tmp_path, f"delta_{comp}.parquet",
                               nulls=nulls, comp=comp)
            assert_tpu_and_cpu_are_equal_collect(
                session, lambda s: s.read.parquet(path), ignore_order=True)
            assert not device_chunks, comp

    def test_delta_agg_equivalence(self, session, tmp_path):
        from tests.harness import assert_tpu_and_cpu_are_equal_collect
        from spark_rapids_tpu.plan import functions as F

        path = self._write(tmp_path, "delta_agg.parquet", nulls=True)

        def q(s):
            df = s.read.parquet(path)
            return (df.filter(F.col("i32") % 3 != 0)
                    .withColumn("k", F.col("seq") % 10)
                    .groupBy("k")
                    .agg(F.sum("big").alias("sb"),
                         F.count("ni").alias("cn")))

        assert_tpu_and_cpu_are_equal_collect(session, q, ignore_order=True)


class TestParquetDeltaLengthAndBSS:
    """DELTA_LENGTH_BYTE_ARRAY strings decode on device (lengths ride the
    delta cumsum kernel, starts are a device exclusive-sum); the
    BYTE_STREAM_SPLIT fixed-width columns beside them are Arrow's."""

    def _write(self, tmp_path, name, comp="NONE", n=4000):
        import numpy as np
        import pyarrow as pa
        import pyarrow.parquet as pq

        rng = np.random.default_rng(23)
        words = ["", "a", "bee", "seven77", "unicode-日本語",
                 "longer-value-" + "x" * 40]
        t = pa.table({
            "s": pa.array([words[i % len(words)] if i % 11 else None
                           for i in range(n)], type=pa.string()),
            "f": pa.array(rng.random(n).astype(np.float32)),
            "i": pa.array(rng.integers(-2**60, 2**60, n).astype(np.int64)),
        })
        path = str(tmp_path / name)
        pq.write_table(
            t, path, compression=comp, use_dictionary=False,
            column_encoding={"s": "DELTA_LENGTH_BYTE_ARRAY",
                             "f": "BYTE_STREAM_SPLIT",
                             "i": "BYTE_STREAM_SPLIT"},
            data_page_version="2.0", version="2.6")
        return path

    def test_decodes_on_device(self, session, tmp_path, monkeypatch,
                               device_chunks):
        from tests.harness import assert_tpu_and_cpu_are_equal_collect
        from spark_rapids_tpu.columnar.dtypes import DataType
        from spark_rapids_tpu.io import parquet_device as PD

        calls = []
        orig = PD._expand_delta

        def spy(*a, **k):
            calls.append(1)
            return orig(*a, **k)

        monkeypatch.setattr(PD, "_expand_delta", spy)
        for comp, codec in (("NONE", "UNCOMPRESSED"), ("SNAPPY", "SNAPPY")):
            path = self._write(tmp_path, f"dlba_{comp}.parquet", comp=comp)
            calls.clear()
            device_chunks.clear()
            assert_tpu_and_cpu_are_equal_collect(
                session, lambda s: s.read.parquet(path), ignore_order=True,
                approx_float=1e-6)
            assert calls, f"{comp}: delta-length strings"
            # the byte-stream-split columns did not reach the decoder
            assert device_chunks == [(DataType.STRING, codec)]

    def test_string_ops_after_delta_length_scan(self, session, tmp_path):
        from tests.harness import assert_tpu_and_cpu_are_equal_collect
        from spark_rapids_tpu.plan import functions as F

        path = self._write(tmp_path, "dlba_ops.parquet")

        def q(s):
            df = s.read.parquet(path)
            return (df.filter(F.length(F.col("s")) > F.lit(2))
                    .groupBy("s").agg(F.count("*").alias("c"),
                                      F.max("i").alias("m")))

        assert_tpu_and_cpu_are_equal_collect(session, q, ignore_order=True)


class TestParquetDecimalDeviceDecode:
    """FLBA-physical decimal columns (plain + dictionary pages; precision
    <= 18, so the value fits int64): Arrow's, like every fixed-width
    column."""

    def _write(self, tmp_path, name, comp="NONE", use_dict=True, n=2500):
        from decimal import Decimal

        import numpy as np
        import pyarrow as pa
        import pyarrow.parquet as pq

        rng = np.random.default_rng(31)
        cents = rng.integers(-10**7, 10**7, n)
        vals = [Decimal(int(c)).scaleb(-2) if i % 13 else None
                for i, c in enumerate(cents)]
        wide = [Decimal(int(c)) * 10**9 for c in cents]  # needs > 4 bytes
        t = pa.table({
            "d": pa.array(vals, type=pa.decimal128(9, 2)),
            "w": pa.array(wide, type=pa.decimal128(18, 0)),
            "k": pa.array((np.arange(n) % 7).astype(np.int64)),
        })
        path = str(tmp_path / name)
        pq.write_table(t, path, compression=comp, use_dictionary=use_dict,
                       data_page_version="1.0")
        return path

    @pytest.mark.parametrize("use_dict,comp", [
        (True, "NONE"), (False, "NONE"), (True, "SNAPPY")])
    def test_decimal_decodes_on_device(self, session, tmp_path,
                                       device_chunks, use_dict, comp):
        from tests.harness import assert_tpu_and_cpu_are_equal_collect

        path = self._write(tmp_path, f"dec_{use_dict}_{comp}.parquet",
                           comp=comp, use_dict=use_dict)
        assert_tpu_and_cpu_are_equal_collect(
            session, lambda s: s.read.parquet(path), ignore_order=True)
        assert not device_chunks

    def test_decimal_agg_after_device_scan(self, session, tmp_path):
        from tests.harness import assert_tpu_and_cpu_are_equal_collect
        from spark_rapids_tpu.plan import functions as F

        path = self._write(tmp_path, "dec_agg.parquet")

        def q(s):
            return (s.read.parquet(path)
                    .groupBy("k")
                    .agg(F.sum("d").alias("sd"), F.max("w").alias("mw"),
                         F.count("d").alias("cd")))

        assert_tpu_and_cpu_are_equal_collect(session, q, ignore_order=True)


class TestPartitionedDeviceEncode:
    """Round-5: dynamic-partition writes device-encode (reference:
    GpuFileFormatDataWriter dynamic writer encodes on the accelerator) —
    keys route on device, only key columns visit the host."""

    def test_partitioned_device_encode_roundtrip(self, session, tmp_path,
                                                 monkeypatch):
        import numpy as np

        from spark_rapids_tpu.io import parquet_encode_device as PE
        from spark_rapids_tpu.io import writer as W
        from tests.harness import assert_tpu_and_cpu_are_equal_collect
        from spark_rapids_tpu.plan import functions as F

        calls = []
        orig = PE.write_file

        def counting_write(path, attrs, batches, compression):
            calls.append(path)
            return orig(path, attrs, batches, compression=compression)

        monkeypatch.setattr(PE, "write_file", counting_write)

        n = 2500
        rng = np.random.default_rng(31)
        session.conf.set("rapids.tpu.sql.enabled", True)
        df = session.createDataFrame({
            "k": rng.integers(0, 4, n).astype(np.int64),
            "v": [int(x) if i % 7 else None
                  for i, x in enumerate(rng.integers(-10**6, 10**6, n))],
            "s": [f"s{int(x)}" if i % 5 else None
                  for i, x in enumerate(rng.integers(0, 100, n))],
        }, [("k", "long"), ("v", "long"), ("s", "string")],
            num_partitions=3)
        # a device filter puts a DeviceToHost transition at the plan root,
        # which is what the writer peels to hand device batches to the
        # encoder (a bare host scan never visits the device)
        df = df.filter(F.col("v").isNotNull() | F.col("v").isNull())
        path = str(tmp_path / "pdev")
        df.write.partitionBy("k").parquet(path)

        # the DEVICE encoder wrote every partition directory's files
        assert calls, "partitioned write did not take the device encoder"
        import os

        dirs = sorted(d for d in os.listdir(path) if d.startswith("k="))
        assert dirs == ["k=0", "k=1", "k=2", "k=3"]

        assert_tpu_and_cpu_are_equal_collect(
            session,
            lambda s: s.read.parquet(path).groupBy("k").agg(
                F.sum("v").alias("sv"), F.count("*").alias("n"),
                F.min("s").alias("ms")),
            ignore_order=True)

        # row-level identity against the source (None-safe sort key)
        key = (lambda r: tuple((x is None, x) for x in r))
        back = sorted(session.read.parquet(path)
                      .select("v", "s", "k").collect(), key=key)
        src = sorted(df.select("v", "s", "k").collect(), key=key)
        assert back == src
