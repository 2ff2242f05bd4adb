"""CPU rehearsal of chip_smoke.py and the process set-up rules it leans on.

The script itself only runs on a TPU and has no switch that says otherwise:
these tests import it and call its phase functions at a tiny scale factor on
the CPU backend, with the fallback keys exactly as the script sets them.
"""

import os
import subprocess
import sys
import types

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402


@pytest.fixture(scope="module")
def smoke(tmp_path_factory):
    """Sessions as the script opens them, tables generated from the seed at
    sf=0.002, written as parquet and read back by the device session. The
    one-device stage mesh keeps the SPMD compile inside the suite's budget
    (tests/conftest.py does the same for its `session` fixture)."""
    data_dir = str(tmp_path_factory.mktemp("chip_smoke_data"))
    dev, ref = chip_smoke.open_sessions(
        {"rapids.tpu.sql.spmd.meshDevices": 1})
    ref_tables, paths, rows = chip_smoke.generate_and_write(
        ref, 0.002, 0, data_dir)
    ns = types.SimpleNamespace(
        dev=dev, ref=ref, ref_tables=ref_tables, paths=paths, rows=rows,
        dev_tables=chip_smoke.read_tables(dev, paths), data_dir=data_dir)
    yield ns
    dev.stop()
    ref.stop()


def test_data_is_parquet_on_disk(smoke):
    import pyarrow.parquet as pq

    assert smoke.rows["lineitem"] == 12000 and smoke.rows["orders"] == 3000
    files = sorted(os.listdir(smoke.paths["lineitem"]))
    assert len(files) == chip_smoke.FILES_PER_TABLE
    md = pq.ParquetFile(
        os.path.join(smoke.paths["lineitem"], files[0])).metadata
    assert md.num_row_groups >= 2
    assert md.row_group(0).column(0).compression == "SNAPPY"
    # the device session reads files, not the generator's arrays
    assert "FileScan" in type(smoke.dev_tables["lineitem"]._plan).__name__


# q3 is rehearsed here although the script does not run it on the chip yet
# (its cold compile does not fit the script's 1200 s)
@pytest.mark.parametrize("name", ("q6", "q1", "q3"))
def test_rehearsal_query_matches_reference(smoke, name):
    for key, want in chip_smoke.DEVICE_CONF.items():
        assert smoke.dev.conf.settings[key] == want
    line = chip_smoke.run_query(name, smoke.dev, smoke.dev_tables,
                                smoke.ref_tables)
    assert line["match"] and line["rows"] > 0
    assert line["cpuFallbackEvents"] == 0 and line["deviceDispatches"] > 0
    assert line["watchdogKills"] == 0 and line["speculativeTasks"] == 0
    assert line["cold"]["watchdogKills"] == 0
    if name == "q1":
        # one device in the mesh and two dictionary-coded string keys:
        # the planner leaves q1 to the streaming operators and the dense
        # aggregate (plan/spmd.py `_streams_dense`, PR 37); before, the
        # stage was planned here and degraded at SF1 on the chip
        assert not line["spmd_planned"] and not line["spmd_degraded"]
    if name == "q3":
        # the join stage overflows the SPMD lane budget and the executor
        # reroutes it to the host loop without failing: reported, not fatal
        assert line["spmd_planned"] and line["spmd_degraded"]


def test_rehearsal_write_reads_back(smoke):
    line = chip_smoke.run_write(
        smoke.dev, smoke.dev_tables, smoke.ref_tables,
        os.path.join(smoke.data_dir, "_written"))
    assert line["match"] and line["files"] == chip_smoke.FILES_PER_TABLE
    assert 0 < line["rows"] <= smoke.rows["lineitem"]
    assert line["cpuFallbackEvents"] == 0 and line["deviceDispatches"] > 0


def test_direct_reference_agrees_with_numpy_engine(smoke):
    """q1's reference at SF1 is pandas over the generated arrays (the numpy
    engine takes too long there); here both are cheap and must agree."""
    from spark_rapids_tpu.benchmarks import tpch

    want = tpch.q1(smoke.ref_tables).collect()
    got = chip_smoke.DIRECT_REFERENCES["q1"](smoke.ref_tables)
    assert len(got) > 1
    chip_smoke._harness().assert_rows_equal(want, got, approx_float=1e-12)


def test_metric_check_bites_on_hidden_fallback(smoke):
    """With the two fallback keys back at their defaults an injected device
    fault is replayed on the numpy engine and collect() returns the right
    rows all the same; the script's check on the metrics is what fails."""
    from spark_rapids_tpu.benchmarks import tpch

    conf = {"rapids.tpu.execution.cpuFallback.enabled": True,
            "rapids.tpu.execution.circuitBreaker.enabled": True,
            "rapids.tpu.sql.test.enabled": False,
            "rapids.tpu.test.faultInjection.enabled": True,
            "rapids.tpu.test.faultInjection.sites": "*",
            "rapids.tpu.test.faultInjection.rate": 1.0}
    restore = chip_smoke._harness()._with_conf(smoke.dev, conf)
    try:
        rows = tpch.q6(smoke.dev_tables).collect()
        metrics = dict(smoke.dev.last_query_metrics)
    finally:
        restore()
        from spark_rapids_tpu.engine.retry import CircuitBreaker

        CircuitBreaker.reset()
    want = tpch.q6(smoke.ref_tables).collect()
    chip_smoke._harness().assert_rows_equal(want, rows, approx_float=1e-9)
    assert metrics["cpuFallbackEvents"] > 0
    with pytest.raises(chip_smoke.SmokeFailure, match="cpuFallbackEvents"):
        chip_smoke.check_device_metrics("q6", metrics)
    with pytest.raises(chip_smoke.SmokeFailure, match="no device dispatch"):
        chip_smoke.check_device_metrics("q6", {"deviceDispatches": 0})


@pytest.mark.parametrize("key", ("watchdogKills", "speculativeTasks"))
def test_metric_check_bites_on_self_healing(key):
    """A watchdog kill or a speculative duplicate on a healthy run fails
    the smoke: compiling was counted as silence or as straggling."""
    chip_smoke.check_device_metrics("q1", {"deviceDispatches": 9, key: 0})
    with pytest.raises(chip_smoke.SmokeFailure, match=key):
        chip_smoke.check_device_metrics("q1", {"deviceDispatches": 9, key: 1})


def _failing_decoder(monkeypatch, exc):
    from spark_rapids_tpu.io import parquet_device as PD

    def decode(*_a, **_kw):
        raise exc

    monkeypatch.setattr(PD, "decode_chunk_device", decode)


@pytest.mark.usefixtures("device_string_decoder")
def test_device_decode_error_reaches_the_script(smoke, monkeypatch):
    """A device decoder that fails (as a compiler or runtime error on the
    chip would make it) is not turned into a host decode of the split:
    with the fallback keys as the script sets them the error ends the
    query. q1 reads the decoder's columns, its two strings (q6 reads
    none: fixed-width columns are Arrow's)."""
    _failing_decoder(monkeypatch, RuntimeError("injected decode fault"))
    with pytest.raises(Exception, match="injected decode fault"):
        chip_smoke.run_query("q1", smoke.dev, smoke.dev_tables,
                             smoke.ref_tables)


@pytest.mark.usefixtures("device_string_decoder")
def test_refused_page_shape_is_counted_and_fails_the_smoke(
        smoke, monkeypatch):
    """A page shape the device decoder refuses still decodes on the host
    and the rows are right, but the query's cpuFallbackEvents counts every
    such split and the script's check fails on it — for a query and for
    the write, which reads the process-wide counter."""
    from spark_rapids_tpu.benchmarks import tpch
    from spark_rapids_tpu.io import parquet_device as PD

    _failing_decoder(monkeypatch, PD._Unsupported("injected page shape"))
    rows = tpch.q1(smoke.dev_tables).collect()
    metrics = dict(smoke.dev.last_query_metrics)
    want = tpch.q1(smoke.ref_tables).collect()
    chip_smoke._harness().assert_rows_equal(
        want, rows, approx_float=chip_smoke.FLOAT_TOLERANCE)
    assert metrics["cpuFallbackEvents"] >= chip_smoke.FILES_PER_TABLE
    with pytest.raises(chip_smoke.SmokeFailure, match="cpuFallbackEvents"):
        chip_smoke.run_query("q1", smoke.dev, smoke.dev_tables,
                             smoke.ref_tables)
    with pytest.raises(chip_smoke.SmokeFailure, match="cpuFallbackEvents"):
        chip_smoke.run_write(smoke.dev, smoke.dev_tables, smoke.ref_tables,
                             os.path.join(smoke.data_dir, "_refused"))


def test_cpu_backend_is_refused():
    """Run as a command without a TPU the script says why and exits
    non-zero; no verdict line is printed."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    got = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"), "--sf", "0.01"],
        capture_output=True, text=True, env=env, timeout=120, cwd=REPO)
    assert got.returncode != 0
    assert '"ok": true' not in got.stdout
    assert "platform 'cpu'" in got.stderr


def test_rehearsal_mesh_conf_crosses_four_devices(smoke):
    """--chips 4 rehearsed: MESH_CONF on a stage mesh of four of the
    virtual CPU devices (the one steer: 0 would take all eight). q1's SPMD
    stage runs as one program, is not rerouted to the host loop, and bytes
    cross the mesh."""
    from spark_rapids_tpu import conf as C
    from spark_rapids_tpu.shuffle import ici

    dev, ref = chip_smoke.open_sessions(
        {**chip_smoke.MESH_CONF, "rapids.tpu.sql.spmd.meshDevices": 4})
    try:
        assert ici.stage_mesh(
            dev.conf.get(C.SPMD_MESH_DEVICES)).devices.size == 4
        (name,) = chip_smoke.MESH_QUERIES
        line = chip_smoke.run_query(
            name, dev, chip_smoke.read_tables(dev, smoke.paths),
            smoke.ref_tables)
    finally:
        dev.stop()
        ref.stop()
    assert line["match"] and line["spmdStages"] >= 1
    assert line["spmd_planned"] and not line["spmd_degraded"]
    assert line["collectiveBytes"] > 0


def test_mesh_check_needs_every_device(smoke):
    """check_mesh on the 8 virtual CPU devices: the cpu allocator reports
    no bytes, so the per-device check must refuse rather than pass."""
    with pytest.raises(chip_smoke.SmokeFailure):
        chip_smoke.check_mesh(smoke.dev, [{"collectiveBytes": 1}])


def test_rebuild_native_builds_from_source(tmp_path, monkeypatch):
    from spark_rapids_tpu import native

    stale = tmp_path / "_srt_native.so"
    stale.write_bytes(b"not a library")
    monkeypatch.setattr(native, "_SO", str(stale))
    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.setattr(native, "_tried", False)
    assert chip_smoke.rebuild_native() == "native"
    assert stale.stat().st_size > 1000
    assert not list(tmp_path.glob("*.tmp"))


# ---------------------------------------------------------------------------
# the compile cache rule (spark_rapids_tpu/_jax_setup.place_compile_cache)
# ---------------------------------------------------------------------------
@pytest.fixture()
def config_updates(monkeypatch):
    from spark_rapids_tpu import _jax_setup

    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: calls.append((k, v)))
    monkeypatch.setattr(_jax_setup, "compile_cache_dir", None)
    return calls


def _cache_dir_updates(calls):
    return [v for k, v in calls if k.endswith("compilation_cache_dir")]


def test_cache_dir_from_environment_is_not_set_in_code(
        config_updates, monkeypatch, tmp_path):
    from spark_rapids_tpu import _jax_setup

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    # a cache placed from outside keeps its place and its policy: the
    # code sets nothing at all
    assert _jax_setup.place_compile_cache("tpu") == str(tmp_path)
    assert _jax_setup.place_compile_cache("cpu") == str(tmp_path)
    assert config_updates == []


def test_no_cache_on_cpu_backend(config_updates, monkeypatch):
    from spark_rapids_tpu import _jax_setup

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert _jax_setup.place_compile_cache("cpu") is None
    assert config_updates == []


def test_cache_dir_defaults_to_checkout_on_accelerator(
        config_updates, monkeypatch):
    from spark_rapids_tpu import _jax_setup

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(REPO, ".jax_cache")
    assert _jax_setup.place_compile_cache("tpu") == want
    assert _cache_dir_updates(config_updates) == [want]
    assert os.path.isdir(want)


def test_detect_hbm_refuses_accelerator_without_limit():
    from spark_rapids_tpu.memory.device_manager import TpuDeviceManager

    class Dev:
        def __init__(self, platform, stats):
            self.platform, self._stats = platform, stats

        def memory_stats(self):
            return self._stats

    assert TpuDeviceManager._detect_hbm(
        Dev("tpu", {"bytes_limit": 123})) == 123
    assert TpuDeviceManager._detect_hbm(Dev("cpu", None)) == 16 << 30
    for stats in (None, {}, {"bytes_in_use": 5}):
        with pytest.raises(RuntimeError, match="no memory limit"):
            TpuDeviceManager._detect_hbm(Dev("tpu", stats))


# ---------------------------------------------------------------------------
# compiling is neither a wedge nor straggling (engine/compile_clock.py)
# ---------------------------------------------------------------------------
def test_compile_clock_sees_backend_compile():
    import jax.numpy as jnp

    from spark_rapids_tpu.engine import compile_clock
    from spark_rapids_tpu.obs.trace import wall_ns

    before = compile_clock.compiling_ns(wall_ns())
    steps = compile_clock.step_seconds()
    # tpulint: jit-cache -- a fresh program on purpose: it must compile
    jax.jit(lambda x: x * 3 + 1)(jnp.arange(7)).block_until_ready()
    assert compile_clock.compiling_ns(wall_ns()) > before
    assert compile_clock._in_flight == 0
    after = compile_clock.step_seconds()
    for step in ("trace", "lower", "compile_or_load"):
        assert after[step] > steps[step], step


def test_watchdog_and_scheduler_subtract_compile_time(monkeypatch):
    from spark_rapids_tpu.engine import compile_clock, pause_clock
    from spark_rapids_tpu.engine.scheduler import _Attempt
    from spark_rapids_tpu.engine.watchdog import DispatchEntry

    s = 1_000_000_000
    pause_clock.shutdown()  # no heartbeat: the pause clock reads its total
    monkeypatch.setattr(pause_clock, "_total_ns", 0)
    monkeypatch.setattr(compile_clock, "_total_ns", 0)
    monkeypatch.setattr(compile_clock, "_in_flight", 0)
    entry = DispatchEntry("t", None, None, 10 * s, 30000.0)
    attempt = _Attempt(None, None, False)
    attempt.mark_started(10 * s)
    # a compile (on any thread) that began 1 s after the dispatch and is
    # still running
    monkeypatch.setattr(compile_clock, "_in_flight", 1)
    monkeypatch.setattr(compile_clock, "_since_ns", 11 * s)
    assert entry.silent_ms(100 * s) == pytest.approx(1000.0)
    assert attempt.runtime_ns(100 * s) == 1 * s
    # ... and ended at 95 s: the 5 s since then count again
    monkeypatch.setattr(compile_clock, "_in_flight", 0)
    monkeypatch.setattr(compile_clock, "_total_ns", 84 * s)
    assert entry.silent_ms(100 * s) == pytest.approx(6000.0)
    assert attempt.runtime_ns(100 * s) == 6 * s
    # ... and the whole process stood still for 4 of those
    # (engine/pause_clock.py; tests/test_pause_clock.py has the clock)
    monkeypatch.setattr(pause_clock, "_total_ns", 4 * s)
    assert entry.silent_ms(100 * s) == pytest.approx(2000.0)
    assert attempt.runtime_ns(100 * s) == 2 * s
