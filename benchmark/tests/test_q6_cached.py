"""The cell `q6_cached` (PR 45) on the CPU backend at sf 0.01: its
configuration through `extends`, its entries found by NAME (a later PR
appends behind them: PERF.md section 7 (e), (j)), its phases through
measure() as run.py drives it, untraced and traced, its four per-layer
readers on the recorded tiny trace and on hand-built runs, the control of
its comparison, the cache's guarantee held per action, and what its action
does on a program whose cached scan counts nothing (the parent commit: it
refuses at once)."""

import os
import time
from types import SimpleNamespace

import numpy as np
import pytest
from ml_dtypes import bfloat16

from conftest import CPU_DEVICE, SF
from lib import compare as C
from lib import harness, loop, xplane
from test_run import TINY, check_line
from test_span_readers import action, run_of, span

CELL = "q6_cached"
CONFIG = "tpch_parquet_cached7"
NEW = ("cache.resident_GB", "cache.serve_ms", "cache.restored_batches",
       "kernels.cached_roofline")
JOINED = ("device.permit_wait_ms", "sink.download_ms", "host.cpu_ms",
          "scheduler.gap_ms")


@pytest.fixture
def rehearse_cached(bench, monkeypatch, tmp_path):
    """conftest's `rehearse` at SF with the cell's 40 files cut to 4 (a
    file of 15,000 rows, not 1,500) and one device, as the chip has."""
    monkeypatch.setattr(harness, "require_tpu", lambda chips: CPU_DEVICE)

    def run(traced=False, seconds=0.5, seed=2147483659):
        entry, config, cell = harness.load_cell(bench, CELL)
        config = dict(
            config, scale_factor=SF,
            layout=dict(config["layout"], files_per_table=4),
            conf=dict(config["conf"],
                      **{"rapids.tpu.sql.spmd.meshDevices": 1}))
        return harness.measure(bench, entry, config, cell, seed, seconds,
                               traced, time.perf_counter(),
                               data_root=str(tmp_path / "data"))

    return run


def test_the_configuration_loads_through_extends(bench):
    entry, config, cell = harness.load_cell(bench, CELL)
    assert entry["config"] == CONFIG and entry["chips"] == 1
    assert entry["traffic"] == "q6_cached_closed_1client"
    assert config["name"] == CONFIG and config["scale_factor"] == 10.0
    assert config["reduced"] == ["scale_factor"]
    assert config["layout"] == {"files_per_table": 40,
                                "row_groups_per_file": 3,
                                "min_row_group_rows": 8,
                                "compression": "snappy"}
    base = harness.load_config(
        os.path.join(harness.HERE, "configs", "tpch_sf1_parquet.json"))
    # the base's tables, schema, conf and precision, both its guarantees
    # and the cache's beside them
    for key in ("schema", "rows_at_sf1", "conf", "precision"):
        assert config[key] == base[key]
    assert set(base["guarantees"]) < set(config["guarantees"])
    assert any("cacheRestoredBatches 0" in g for g in config["guarantees"])
    act = harness.load_module("actions", cell["action"])
    assert config["cached"]["columns"] == list(act.CACHED)
    assert set(act.COLUMNS["lineitem"]) < set(act.CACHED)
    declared = harness.named(bench["configs"], CONFIG, "configuration")
    assert declared["source"] == config["source"]
    assert len(config["source"]) <= 200
    assert declared["reduced"] == config["reduced"]


def test_the_new_entries_are_found_by_name(bench):
    per_layer = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW:
        assert per_layer[name]["workloads"] == [CELL]
        assert per_layer[name]["moves"] == "query_s"
    assert per_layer["kernels.cached_roofline"]["unit"] == "%"
    assert per_layer["kernels.cached_roofline"]["layer"] == "kernels"
    reported = {m["name"] for m in harness.metrics_of(bench, "per_layer",
                                                      CELL)}
    assert set(NEW) | set(JOINED) | {
        "operators.dispatches", "operators.device_ms", "sink.fences",
        "kernels.hbm_roofline", "device.idle_share", "device.peak_hbm_GB",
        "window.build_s", "planner.plan_ms"} <= reported
    # there is no scan in the window, and no write
    assert not [n for n in reported if n.startswith("scan.")]
    assert not [n for n in reported if n.endswith(".write")]
    for name in JOINED:
        assert per_layer[name]["workloads"][:2] == ["q6_scan", "q1_agg"]
    for cell in ("q6_scan", "q1_agg", "lineitem_write_slim",
                 "lineitem_write7"):
        theirs = {m["name"] for m in harness.metrics_of(bench, "per_layer",
                                                        cell)}
        assert not set(NEW) & theirs
    end_to_end = {m["name"] for m in harness.metrics_of(bench, "end_to_end",
                                                        CELL)}
    assert end_to_end == {"query_s", "query_p90_s", "rows_per_s", "setup_s"}


def test_q6_cached(rehearse_cached, bench):
    result = rehearse_cached(seconds=1.0)
    declared = bench
    if result["attempted"] < 10:
        declared = dict(bench, end_to_end=[
            m for m in bench["end_to_end"] if m["name"] != "query_p90_s"])
    check_line(result, declared, CELL, "end_to_end")
    compared = result["compared"]
    assert compared["q6.rows_differ"] == [0, 0]
    assert compared["q6.max_rel_err"][1] == C.FLOAT_RTOL
    for name in ("cache.batches_not_served", "cache.nothing_cached",
                 "cache.restored_batches", "cache.scan_spans"):
        assert compared[name] == [0, 0]
    for counter in C.MUST_BE_ZERO:
        assert compared[f"{CELL}.{counter}"] == [0, 0]


def test_q6_cached_traced_reports_its_layer_metrics(rehearse_cached, bench,
                                                    monkeypatch):
    """The CPU backend has no device plane: the reduction is handed the
    trace recorded on the chip with a millisecond of device time put into
    each action (test_run.py does the same for q6_scan); the spans and
    the counters are the program's own."""
    reduced = dict(xplane.reduce(TINY), action_busy_s=[0.001] * 3)
    monkeypatch.setattr(harness.xplane, "reduce", lambda path: reduced)
    result = rehearse_cached(traced=True, seconds=0.3)
    check_line(result, bench, CELL, "per_layer")
    m = {k: v["value"] for k, v in result["metrics"].items()}
    assert set(NEW) | set(JOINED) <= set(m)
    assert m["cache.restored_batches"] == 0
    # 0.01 x 6M rows x seven columns: f64 on this backend, so over 36.7 B
    # a row; under a GB either way
    assert 60_000 * 36 / 1e9 < m["cache.resident_GB"] < 0.1
    assert 0 < m["cache.serve_ms"] < 50
    # 16 B x 60,000 rows over 819 GB/s over the millisecond put in
    assert m["kernels.cached_roofline"] == pytest.approx(
        100 * 16 * 60_000 / 819e9 / 0.001)
    assert m["sink.fences"] == 1 and m["window.build_s"] == 0
    assert m["device.permit_wait_ms"] >= 0
    # materialisation is set-up: no action of the window scans
    assert not [g for g in result["breakdown"]["idle_gaps"]
                if "scan." in g[0]]
    assert result["compared"]["cache.scan_spans"] == [0, 0]


# ---------------------------------------------------------------------------
# the four readers, on the recorded tiny trace and on hand-built runs
# ---------------------------------------------------------------------------
def counted(restored=0, served=4, cached=4, scan_spans=0):
    rows = harness.load_module("actions", CELL).Rows([(1.0,)])
    rows.served, rows.restored = served, restored
    rows.cached, rows.scan_spans = cached, scan_spans
    return rows


def sample(result, error=""):
    return loop.Sample(0.0, 0.1, SimpleNamespace(result=result, spans=None),
                       error)


def traced_run(busy, rows=60_000_000):
    return SimpleNamespace(
        trace=dict(xplane.reduce(TINY), action_busy_s=busy),
        cell={"action": CELL}, rows_per_action=rows,
        peaks={"hbm_bytes_per_s": 819e9})


def test_least_bytes_is_16_a_row():
    mod = harness.load_module("layer_metrics", "kernels.cached_roofline")
    run = traced_run([0.008, 0.009, 0.008])
    assert mod.least_bytes(run) == 16 * 60_000_000
    assert mod.least_bytes(traced_run([0.008], rows=1000)) == 16_000
    # 960 MB at 819 GB/s is 1.172 ms; over 8 ms of device time, 14.65%
    assert mod.read(run) == pytest.approx(100 * 960e6 / 819e9 / 0.008)
    assert mod.read(run) < 100
    # no device time in the traced actions, or no trace: nothing to read
    assert mod.read(traced_run([0.0, 0.0, 0.0])) is None
    assert mod.read(traced_run([])) is None
    assert mod.read(SimpleNamespace(trace=None)) is None


def test_restored_batches_adds_up_the_window():
    read = harness.load_reader("layer_metrics", "cache.restored_batches")
    assert read(run_of([sample(counted())] * 3)) == 0
    assert read(run_of([sample(counted(2)), sample(counted(1)),
                        sample(counted(5), error="boom")])) == 3
    # an action that carries no counter (another cell's rows)
    assert read(run_of([sample([(1.0,)])])) is None


def test_serve_ms_adds_the_tasks_spans():
    read = harness.load_reader("layer_metrics", "cache.serve_ms")

    def tasks(*ms):
        return [span(f"task:p{i}", 0, 50, [
            span("Acquire TPU Semaphore", 0, 1, kind="op"),
            span("cache.serve", 1, 1 + d, bytes=10, restored=0)],
            kind="task") for i, d in enumerate(ms)]

    # side by side, so added up and not united; median over actions
    assert read(run_of([action(tasks(1, 1, 2)), action(tasks(1, 1, 1)),
                        action(tasks(2, 2, 2))])) == pytest.approx(4)
    bare = [span("task:p0", 0, 50, [span("scan.upload", 1, 2)], kind="task")]
    assert read(run_of([action(bare)])) is None
    assert read(run_of([action(None)])) is None


def test_resident_GB_reads_the_programs_gauge(monkeypatch):
    from spark_rapids_tpu.utils import metrics as M

    read = harness.load_reader("layer_metrics", "cache.resident_GB")
    monkeypatch.setattr(M, "cache_resident_bytes", lambda: 2_200_000_000)
    assert read(SimpleNamespace()) == pytest.approx(2.2)
    # a program without the gauge (the parent) leaves nothing to read
    monkeypatch.delattr(M, "cache_resident_bytes")
    assert read(SimpleNamespace()) is None


# ---------------------------------------------------------------------------
# the comparison
# ---------------------------------------------------------------------------
def test_q6_cached_in_bf16_fails_and_in_f32_passes(arrays):
    act = harness.load_module("actions", CELL)
    want = act.reference(arrays)
    assert want == harness.load_module("actions", "q6").reference(arrays)
    assert C.holds(act.compare(want, [act.reference(arrays, np.float32)])[0])
    low = act.compare(want, [act.reference(arrays, bfloat16)])[0]
    assert not C.holds(low)
    worst = {n["name"]: n["value"] for n in low}["q6.max_rel_err"]
    assert worst > 10 * C.FLOAT_RTOL


@pytest.mark.parametrize("broken,name", [
    (dict(restored=1), "cache.restored_batches"),
    (dict(served=3), "cache.batches_not_served"),
    (dict(served=8), "cache.batches_not_served"),
    (dict(served=0, cached=0), "cache.nothing_cached"),
    (dict(scan_spans=2), "cache.scan_spans")])
def test_an_action_that_breaks_the_caches_guarantee_is_not_correct(
        broken, name):
    act = harness.load_module("actions", CELL)
    want = [(1.0,)]
    sound, bad = act.compare(want, [counted(), counted(**broken)])
    assert C.holds(sound)
    assert not C.holds(bad)
    assert [n["name"] for n in bad if n["value"] > n["limit"]] == [name]


def test_q6_cached_refuses_a_program_whose_cached_scan_counts_nothing(
        monkeypatch, tmp_path):
    """What the parent commit does with the cell: the action's file fails
    to load in a checkout whose exec/cache.py has no `cache.serve`, run.py
    prints why and exits 1, and no chip is touched."""
    monkeypatch.setattr(harness, "ROOT", str(tmp_path))
    with pytest.raises(harness.BenchFailure, match="cache.serve"):
        harness.load_module("actions", CELL)
    old = tmp_path / "spark_rapids_tpu" / "exec"
    old.mkdir(parents=True)
    (old / "cache.py").write_text("class TpuCachedScanExec: pass\n")
    with pytest.raises(harness.BenchFailure, match="PR 45"):
        harness.load_module("actions", CELL)


def test_the_control_tool_reads_q6_cached_at_a_small_size(monkeypatch,
                                                          capsys):
    import json

    from lib import tpch_gen

    control = harness.load_module("tools", "control")
    gen = tpch_gen.gen_tables
    monkeypatch.setattr(tpch_gen, "gen_tables",
                        lambda sf, seed, tables: gen(SF, seed, tables))
    assert control.main(["--workload", CELL, "--seeds", "3,4"]) == 0
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()]
    for ln in lines:
        assert ln["float32_correct"] and not ln["bfloat16_correct"]
