"""Main-path programs compiled for a described TPU v5e, at SF1 capacities.

The CPU suite cannot take the chip's branches: `device_float64_supported()`
is a test on the backend, so DOUBLE stays f64 and 64-bit cumulative sums
stay native here. These tests force the device flavour (jax.default_backend
patched to say "tpu" — in the test, not through an option of the program),
capture the programs the engine really builds for chip_smoke's q6/q1 from
parquet, and hand them to the TPU compiler that is installed here without a
chip (on-chip-measurement guide, section 2). Each asserts that the lowered
text holds no f64: the chip's compiler would not refuse one, it would
emulate it, at minutes of compile per program (the first chip run of PR 22
spent 33 s on q6 for that reason).

Sort-bearing programs compile for minutes at every size (q1's sort-based
aggregate update kernel: 255 s cold on the v5e, PR 22). Since PR 37 q1's
update is the dense table of exec/dense_agg.py, which holds no sort, and
is compiled here at an SF1 partition's capacity.
"""

import os
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, \
    SingleDeviceSharding

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

ROW_GROUP_CAP = 1 << 19   # an SF1 lineitem row group (chip_smoke.py)
PARTITION_CAP = 1 << 21   # a 1.5M-row SF1 partition


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep the cache off around them."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    cc.reset_cache()


class _Recorded:
    """A jitted function that notes each call made with concrete arguments
    (a call from inside another trace is that program's business)."""

    def __init__(self, fun, jitted, calls):
        self._fun, self._jitted, self._calls = fun, jitted, calls

    def __call__(self, *a, **k):
        leaves = jax.tree.leaves((a, k))
        if not any(isinstance(x, jax.core.Tracer) for x in leaves):
            self._calls.append((self._fun, self._jitted, a, k))
        return self._jitted(*a, **k)

    def __getattr__(self, name):
        return getattr(self._jitted, name)


class _Recorder:
    """jax.jit stand-in: what it builds is recorded."""

    def __init__(self):
        self.calls = []
        self._jit = jax.jit

    def __call__(self, fun=None, **kw):
        if fun is None:
            return lambda f: self(f, **kw)
        return _Recorded(fun, self._jit(fun, **kw), self.calls)

    def wrap_module(self, mp, module):
        """Programs a module jitted when it was imported — before this
        recorder stood in for jax.jit — are wrapped where they live."""
        for name, obj in list(vars(module).items()):
            if not isinstance(obj, _Recorded) and hasattr(obj, "lower") \
                    and hasattr(obj, "__wrapped__"):
                mp.setattr(module, name,
                           _Recorded(obj.__wrapped__, obj, self.calls))

    def programs(self, qualname_part: str, filename: str):
        return [c for c in self.calls
                if qualname_part in getattr(c[0], "__qualname__", "")
                and c[0].__code__.co_filename.endswith(filename)]


@pytest.fixture(scope="module")
def device_flavour():
    """The engine as it traces on the chip: DOUBLE is f32, 64-bit cumsum
    rides two lanes. Captures what it builds; nothing runs on a TPU."""
    from spark_rapids_tpu.engine import jit_cache

    from spark_rapids_tpu.io import parquet_device

    mp = pytest.MonkeyPatch()
    rec = _Recorder()
    mp.setattr(jax, "default_backend", lambda: "tpu")
    mp.setattr(jax, "jit", rec)
    rec.wrap_module(mp, parquet_device)
    jit_cache.clear()
    try:
        yield rec
    finally:
        mp.undo()
        jit_cache.clear()


@pytest.fixture(scope="module")
def smoke_programs(device_flavour, tmp_path_factory):
    """q6 and q1 of chip_smoke.py at sf=0.002 from parquet, q1 on the
    host-loop executor (at SF1 its SPMD stage exceeds spmd.maxSortLanes and
    degrades to it: what the chip really ran in PR 22)."""
    import chip_smoke
    from spark_rapids_tpu.benchmarks import tpch

    dev, ref = chip_smoke.open_sessions(
        {"rapids.tpu.sql.spmd.enabled": False})
    try:
        _, paths, _ = chip_smoke.generate_and_write(
            ref, 0.002, 0, str(tmp_path_factory.mktemp("compile_data")))
        tables = chip_smoke.read_tables(dev, paths)
        rows = {q: tpch.QUERIES[q](tables).collect() for q in ("q6", "q1")}
    finally:
        dev.stop()
        ref.stop()
    assert len(rows["q6"]) == 1 and len(rows["q1"]) > 1
    return types.SimpleNamespace(rec=device_flavour, rows=rows)


def _at_capacity(args, tiny: int, cap: int, sharding):
    """The captured call's arguments as shapes on the described chip, every
    axis of the tiny run's capacity widened to the SF1 one."""
    def widen(x):
        if isinstance(x, (jax.Array, np.ndarray)):
            shape = tuple(cap if d == tiny else d for d in x.shape)
            return jax.ShapeDtypeStruct(shape, x.dtype, sharding=sharding)
        return x

    return jax.tree.map(widen, args)


def _capacity_of(args) -> int:
    return max(x.shape[0] for x in jax.tree.leaves(args)
               if isinstance(x, (jax.Array, np.ndarray)) and x.ndim == 1)


def _lower(jitted, args, kwargs):
    lowered = jitted.lower(*args, **kwargs)
    text = lowered.as_text()
    assert "f64" not in text, \
        [ln for ln in text.splitlines() if "f64" in ln][:5]
    return lowered, text


def test_cumsum_wrap_lanes(one_chip, no_persistent_cache):
    from spark_rapids_tpu.exec import rowkeys as RK

    # tpulint: jit-cache -- one-shot compile of the device-only branch
    lowered, _ = _lower(jax.jit(RK._cumsum_wrap_lanes), (
        jax.ShapeDtypeStruct((PARTITION_CAP,), jnp.int64,
                             sharding=one_chip),), {})
    ma = lowered.compile().memory_analysis()
    assert ma.temp_size_in_bytes < 1 << 30


def test_q6_fused_aggregate_in_f32(smoke_programs, one_chip,
                                   no_persistent_cache):
    """q6's fused stage: the filter and l_extendedprice * l_discount folded
    into the partial aggregate's update kernel, DOUBLE narrowed to f32.
    Since PR 50 that kernel is the ungrouped update program: no sort, no
    scatter, and an output of `bucket_capacity(1)` lanes."""
    from spark_rapids_tpu.columnar.batch import bucket_capacity

    calls = smoke_programs.rec.programs("_build_ungrouped_update_kernel",
                                        "exec/aggregate.py")
    assert calls, "q6 built no ungrouped update kernel"
    fun, jitted, args, kwargs = calls[0]
    tiny = _capacity_of(args)
    lowered, text = _lower(jitted, _at_capacity(
        args, tiny, ROW_GROUP_CAP, one_chip), kwargs)
    assert f"tensor<{ROW_GROUP_CAP}xf32>" in text
    assert "stablehlo.sort" not in text and "stablehlo.scatter" not in text
    out = jax.tree.leaves(lowered.out_info)
    assert out and all(o.shape == (bucket_capacity(1),) for o in out)
    lowered.compile()


RESIDENT_CAP = 1 << 23    # a resident relation's batch: 512 MB / 35 B


def _assert_streams(text):
    """Nothing that costs the chip's compiler minutes at 2^23 lanes, or
    the chip 20 ns a lane: no sort, no scatter, no flat cumulative sum
    (a `reduce_window` over the lanes: 31.8 s of compile at 2^20, PR 41)."""
    for op in ("sort", "scatter", "reduce_window"):
        assert f"stablehlo.{op}" not in text, op


def test_a_resident_batch_is_assembled_and_summed_at_its_capacity(
        smoke_programs, one_chip, no_persistent_cache):
    """`DataFrame.cache()` holds its relation in batches of the engine's
    target size (PR 51): `q6_cached`'s are ten pieces of 2^20 and 2^19
    lanes, seven columns, in 2^23 lanes. The concat that assembles one
    (`columnar/batch.concat_in_order`: a copy a piece, the row counts as
    operands) and Q6's ungrouped update over it are compiled here for the
    v5e at that capacity, both inside a bound on the seconds this test
    measures; the concat's temporaries are one column's room (the output
    and the largest piece over), not a second copy of the pieces."""
    import time

    from spark_rapids_tpu.columnar import batch as B

    began = time.perf_counter()
    caps = (1 << 20, 1 << 19) * 5
    dtypes = (jnp.float32,) * 4 + (jnp.int32,) * 3

    def lanes(n, dtype):
        return jax.ShapeDtypeStruct((n,), dtype, sharding=one_chip)

    # tpulint: jit-cache -- one-shot compile of the device-only branch
    lowered, text = _lower(
        jax.jit(B._concat_in_order_traced, static_argnums=(0,)),
        (RESIDENT_CAP, lanes(len(caps) + 1, jnp.int32),
         tuple(tuple(lanes(c, d) for d in dtypes) for c in caps),
         tuple(tuple(lanes(c, jnp.bool_) for _ in dtypes) for c in caps)),
        {})
    _assert_streams(text)
    assert "stablehlo.gather" not in text
    out = jax.tree.leaves(lowered.out_info)
    assert [o.shape for o in out] == [(RESIDENT_CAP,)] * 14
    ma = lowered.compile().memory_analysis()
    assert 0 <= ma.output_size_in_bytes - 35 * RESIDENT_CAP < 4096
    assert ma.temp_size_in_bytes < 64 << 20    # the pieces are 275 MB

    calls = smoke_programs.rec.programs("_build_ungrouped_update_kernel",
                                        "exec/aggregate.py")
    assert calls, "q6 built no ungrouped update kernel"
    fun, jitted, args, kwargs = calls[0]
    lowered, text = _lower(jitted, _at_capacity(
        args, _capacity_of(args), RESIDENT_CAP, one_chip), kwargs)
    assert f"tensor<{RESIDENT_CAP}xf32>" in text
    _assert_streams(text)
    assert lowered.compile().memory_analysis().temp_size_in_bytes < 1 << 28
    assert time.perf_counter() - began < 120


def test_q1_aggregate_update_kernel_is_dense_in_f32(
        smoke_programs, one_chip, no_persistent_cache):
    """q1's update since PR 37: two dictionary-coded string keys from
    Arrow, eight aggregates, the table of exec/dense_agg.py: no sort and
    no scatter in the program, DOUBLE in f32, and a compile of seconds at
    an SF1 partition's capacity (the sort-based kernel it replaces took
    255 s on the v5e and was only lowered here)."""
    calls = smoke_programs.rec.programs("_build_dense_update_kernel",
                                        "exec/aggregate.py")
    assert calls, "q1 built no dense update kernel"
    assert not [c for c in smoke_programs.rec.programs(
        "_build_update_kernel", "exec/aggregate.py")
        if "stablehlo.sort" in c[1].lower(*c[2], **c[3]).as_text()], \
        "q1 built a sort-based update kernel beside it"
    fun, jitted, args, kwargs = calls[0]
    tiny = _capacity_of(args)
    lowered, text = _lower(jitted, _at_capacity(
        args, tiny, PARTITION_CAP, one_chip), kwargs)
    assert f"tensor<{PARTITION_CAP}xf32>" in text
    assert "stablehlo.sort" not in text and "stablehlo.scatter" not in text
    ma = lowered.compile().memory_analysis()
    assert ma.temp_size_in_bytes < 1 << 30


def test_compaction_programs_at_an_sf1_split(one_chip, no_persistent_cache):
    """A filter's survivors in front of a sink (`lineitem_write7`) at the
    2^20 lanes of a split of two row groups; DOUBLE in f32, codes and the
    date in int32. Since PR 41 the count is a reduction and the move the
    shift network over seven columns and their validities: no sort (the
    argsort cost a cold run 25 s a capacity), no gather, no scatter in
    either, a compiled name the device trace finds, and no temporaries
    worth naming. The order alone through the network, what a plain
    STRING column is fetched through, is the same program with an iota
    for its columns."""
    from spark_rapids_tpu.columnar import batch as B

    cap = 2 * ROW_GROUP_CAP

    def lanes(dtype):
        return jax.ShapeDtypeStruct((cap,), dtype, sharding=one_chip)

    def assert_dense(text):
        for op in ("sort", "gather", "scatter"):
            assert f"stablehlo.{op}" not in text
            assert f"stablehlo.dynamic_{op}" not in text

    count = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    lowered, text = _lower(B._compact_plan, (lanes(jnp.bool_), count), {})
    assert_dense(text)
    compiled = lowered.compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 26
    assert "jit__compact_plan" in compiled.as_text()
    datas = tuple(lanes(d) for d in (jnp.float32,) * 4 + (jnp.int32,) * 3)
    valids = tuple(lanes(jnp.bool_) for _ in datas)
    for columns, with_order in (((datas, valids), False), (((), ()), True)):
        lowered, text = _lower(
            B._compact_shift_fixed_cols,
            (cap, *columns, lanes(jnp.bool_), count, with_order), {})
        assert_dense(text)
        compiled = lowered.compile()
        assert compiled.memory_analysis().temp_size_in_bytes < 1 << 26
        assert "jit__compact_shift_fixed_cols" in compiled.as_text()


@pytest.mark.parametrize("builder, filename, sorts", [
    ("_build_dense_merge_kernel", "exec/aggregate.py", False),
    ("TpuSortExec._build_kernel", "exec/sort.py", True),
])
def test_q1_behind_the_update_sees_a_table_not_a_partition(
        smoke_programs, one_chip, no_persistent_cache, builder, filename,
        sorts):
    """What runs behind q1's update sees the groups, never the rows: the
    merge of the partials is the table's reduction again, and the ORDER
    BY sorts a handful of lanes. Their shapes do not grow with the scale
    factor, so they compile here at the size the chip runs them (a sort
    over a partition's lanes was minutes)."""
    calls = smoke_programs.rec.programs(builder, filename)
    assert calls, f"q1 built no {builder} program"
    for fun, jitted, args, kwargs in calls:
        assert _capacity_of(args) <= 4096
        shapes = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=one_chip)
            if isinstance(x, (jax.Array, np.ndarray)) else x, args)
        lowered, text = _lower(jitted, shapes, kwargs)
        assert ("stablehlo.sort" in text) == sorts
        lowered.compile()


def test_parquet_decode_programs(device_flavour, one_chip,
                                 no_persistent_cache, tmp_path):
    """Device decode of one SF1-sized row group of what the device
    decoder takes, dictionary strings: a flag column kept as codes
    (l_returnflag's shape) and a mode column gathered through its
    dictionary. (Fixed-width columns are Arrow's: no decode program.)"""
    import pyarrow as pa
    import pyarrow.parquet as pq

    from spark_rapids_tpu.columnar import encoded as ENC
    from spark_rapids_tpu.columnar.dtypes import DataType
    from spark_rapids_tpu.io import parquet_device as PD

    rows = ROW_GROUP_CAP - 1234
    rng = np.random.default_rng(0)
    path = str(tmp_path / "rg.parquet")
    modes = np.array(["AIR", "FOB", "MAIL", "RAIL", "REG AIR", "SHIP",
                      "TRUCK"], dtype=object)
    pq.write_table(pa.table({
        "returnflag": np.array(["A", "N", "R"],
                               dtype=object)[rng.integers(0, 3, rows)],
        "shipmode": pa.array(modes[rng.integers(0, 7, rows)],
                             mask=rng.random(rows) < 0.01),
    }), path, compression="snappy", row_group_size=ROW_GROUP_CAP)
    pf = pq.ParquetFile(path)
    rg = pf.metadata.row_group(0)
    start = len(device_flavour.calls)
    for ci, encoded_ok in ((0, True), (1, False)):
        col = rg.column(ci)
        assert PD.column_eligible(col, DataType.STRING)
        cv = PD.decode_chunk_device(
            PD.read_chunk_bytes(path, col), DataType.STRING, rows,
            max_def=pf.schema.column(ci).max_definition_level,
            cap=ROW_GROUP_CAP, codec=col.compression,
            encoded_ok=encoded_ok)
        assert ENC.is_encoded(cv) == encoded_ok
        assert cv.validity.shape == (ROW_GROUP_CAP,)
        if encoded_ok:
            assert cv.data.shape == (ROW_GROUP_CAP,)
            assert cv.data.dtype == np.int32
    decode = [c for c in device_flavour.calls[start:]
              if c[0].__code__.co_filename.endswith("io/parquet_device.py")]
    assert len(decode) >= 2
    for fun, jitted, args, kwargs in decode:
        lowered, _ = _lower(jitted, _at_capacity(
            args, -1, -1, one_chip), kwargs)
        lowered.compile()


def test_ici_exchange_on_four_chip_mesh(topo, device_flavour,
                                        no_persistent_cache):
    """The hash exchange of the ICI shuffle tier (shard_map + all_to_all)
    over a mesh of the four described chips: q3's join exchange on
    o_orderkey, eight partitions, with a DOUBLE payload."""
    from spark_rapids_tpu.columnar.dtypes import DataType
    from spark_rapids_tpu.ops.base import BoundReference
    from spark_rapids_tpu.parallel.mesh import DATA_AXIS
    from spark_rapids_tpu.shuffle import ici

    cap = 1 << 16
    mesh = Mesh(np.array(topo.devices[:4]), (DATA_AXIS,))
    dtypes = (DataType.INT64, DataType.FLOAT64, DataType.DATE)
    kernel = ici._build_exchange_kernel(
        mesh, tuple(d.value for d in dtypes),
        ("hash", [BoundReference(0, DataType.INT64, True)], ()),
        8, cap, (0, 0, 0))
    sh = NamedSharding(mesh, P(DATA_AXIS))

    def arg(dtype):
        return jax.ShapeDtypeStruct((4, cap), dtype, sharding=sh)

    args = [arg(np.bool_), arg(np.int64), arg(np.float32), arg(np.int32)] \
        + [arg(np.bool_)] * 3
    lowered, text = _lower(kernel, args, {})
    assert "all_to_all" in text
    compiled = lowered.compile()
    assert "all-to-all" in compiled.as_text()
    ma = compiled.memory_analysis()
    assert ma.temp_size_in_bytes + ma.argument_size_in_bytes < 16 << 30
