"""The cell `q1_agg` (PR 37) on the CPU backend at sf 0.01: its phases
through measure() as run.py drives it, untraced and traced; the control of
its comparison (the reference in bfloat16 fails, in float32 passes); its
four per-layer readers on hand-built runs; and what its action does on a
program that lacks the dense aggregate (the parent commit: it refuses at
once)."""

import os
import time
from types import SimpleNamespace

import numpy as np
import pytest
from ml_dtypes import bfloat16

from lib import compare as C
from conftest import CPU_DEVICE, SF
from lib import harness, loop, xplane
from test_run import TINY, check_line
from test_span_readers import action, run_of, span

CELL = "q1_agg"
NEW = ("agg.device_ms", "agg.dense_share", "kernels.agg_roofline",
       "scan.dict_columns")


@pytest.fixture
def rehearse_one_chip(bench, monkeypatch, tmp_path):
    """conftest's `rehearse` with a mesh of one device, as the chip the
    cell runs on has: the tests' CPU backend shows eight, and on a mesh
    the planner gives Q1 to the SPMD stage program, not to the streaming
    operators the cell measures (plan/spmd.py `_streams_dense`)."""
    monkeypatch.setattr(harness, "require_tpu", lambda chips: CPU_DEVICE)

    def run(traced=False, seconds=0.5, seed=7):
        entry, config, cell = harness.load_cell(bench, CELL)
        config = dict(config, scale_factor=SF, conf=dict(
            config["conf"], **{"rapids.tpu.sql.spmd.meshDevices": 1}))
        return harness.measure(bench, entry, config, cell, seed, seconds,
                               traced, time.perf_counter(),
                               data_root=str(tmp_path / "data"))

    return run


def test_q1_agg(rehearse_one_chip, bench):
    result = rehearse_one_chip(seconds=2.0)
    declared = bench
    if result["attempted"] < 10:
        # a busy sandbox: under ten samples there is no tail to report
        # (end_to_end/query_p90_s.py), and the line leaves it out
        declared = dict(bench, end_to_end=[
            m for m in bench["end_to_end"] if m["name"] != "query_p90_s"])
    check_line(result, declared, CELL, "end_to_end")
    m = result["metrics"]
    assert {"query_s", "rows_per_s", "setup_s"} <= set(m)
    assert m["query_s"]["value"] > 0 and m["rows_per_s"]["value"] > 0
    compared = result["compared"]
    assert compared["q1.rows_differ"] == [0, 0]
    assert compared["q1.max_rel_err"][1] == C.FLOAT_RTOL
    for counter in C.MUST_BE_ZERO:
        assert compared[f"q1.{counter}"] == [0, 0]


def test_q1_agg_traced_reports_its_layer_metrics(rehearse_one_chip, bench,
                                                 monkeypatch):
    """The CPU backend has no device plane: the reduction is handed the
    trace recorded on the chip with the aggregate's programs put into it
    (test_run.py does the same for q6_scan); the spans and the counters
    are the program's own."""
    programs = [["jit_agg_dense_update", 0.0024, 24],
                ["jit_agg_dense_merge", 0.0003, 3],
                ["jit_agg_finalize", 0.0003, 3],
                ["jit__slice_grouped", 0.0030, 24]]
    reduced = dict(xplane.reduce(TINY), action_busy_s=[0.002] * 3,
                   device_programs=programs)
    monkeypatch.setattr(harness.xplane, "reduce", lambda path: reduced)
    result = rehearse_one_chip(traced=True, seconds=0.3)
    check_line(result, bench, CELL, "per_layer")
    m = {k: v["value"] for k, v in result["metrics"].items()}
    assert set(NEW) <= set(m)
    assert m["agg.dense_share"] == 100
    # 0.01 x 6M rows in 4 files of one row group: 2 columns x 4 splits
    assert m["scan.dict_columns"] == 8
    assert m["agg.device_ms"] == pytest.approx(1.0)
    assert 0 < m["kernels.agg_roofline"] <= 100
    assert m["sink.fences"] == 1 and m["window.build_s"] == 0
    assert m["scan.upload_MB"] > 0 and m["scan.host_ms"] > 0


def test_q1_in_bf16_fails_and_in_f32_passes(arrays):
    act = harness.load_module("actions", "q1")
    want = act.reference(arrays)
    assert len(want) == 6
    assert [r[:2] for r in want] == sorted(r[:2] for r in want)
    f32 = act.compare(want, [act.reference(arrays, np.float32)])[0]
    assert C.holds(f32), f32
    low = act.compare(want, [act.reference(arrays, bfloat16)])[0]
    assert not C.holds(low)
    worst = {n["name"]: n["value"] for n in low}["q1.max_rel_err"]
    assert worst > 10 * C.FLOAT_RTOL
    assert {n["name"]: n["value"] for n in low}["q1.rows_differ"] == 0


def test_q1_rows_out_of_order_or_miscounted_are_not_correct(arrays):
    act = harness.load_module("actions", "q1")
    want = act.reference(arrays)
    swapped = [want[1], want[0]] + want[2:]
    assert not C.holds(act.compare(want, [swapped])[0])
    off = [want[0][:-1] + (want[0][-1] + 1,)] + want[1:]
    numbers = {n["name"]: n["value"] for n in act.compare(want, [off])[0]}
    assert numbers["q1.rows_differ"] == 1


def test_q1_refuses_a_program_without_the_dense_aggregate(monkeypatch,
                                                          tmp_path):
    """What the parent commit does with the cell: the action's file fails
    to load in a checkout that lacks exec/dense_agg.py, run.py prints why
    and exits 1, and no chip is touched."""
    monkeypatch.setattr(harness, "ROOT", str(tmp_path))
    with pytest.raises(harness.BenchFailure, match="dense_agg"):
        harness.load_module("actions", "q1")


def test_the_control_tool_reads_q1_at_a_small_size(monkeypatch, capsys):
    """tools/control.py with the cell's name: it needs the action's
    reference and nothing of the program."""
    import json

    from lib import tpch_gen

    control = harness.load_module("tools", "control")
    gen = tpch_gen.gen_tables
    monkeypatch.setattr(tpch_gen, "gen_tables",
                        lambda sf, seed, tables: gen(SF, seed, tables))
    assert control.main(["--workload", CELL, "--seeds", "3,4"]) == 0
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()]
    assert [ln["seed"] for ln in lines] == [3, 4]
    for ln in lines:
        assert ln["float32_correct"] and not ln["bfloat16_correct"]
        assert ln["bfloat16"]["bfloat16.max_rel_err"] > 10 * ln["limit"]


# ---------------------------------------------------------------------------
# the four readers, on hand-built runs
# ---------------------------------------------------------------------------
def sample(result, error=""):
    return loop.Sample(0.0, 0.1, SimpleNamespace(result=result, spans=None),
                       error)


def rows_with(dense, sort):
    rows = harness.load_module("actions", "q1").Rows()
    rows.agg_batches = (dense, sort)
    return rows


def traced(programs, actions=3, rows=6_000_000):
    return SimpleNamespace(
        trace={"device_programs": programs, "action_s": [0.1] * actions},
        cell={"action": "q1"}, rows_per_action=rows,
        peaks={"hbm_bytes_per_s": 819e9})


def test_dense_share_counts_batches_over_the_window():
    read = harness.load_reader("layer_metrics", "agg.dense_share")
    assert read(run_of([sample(rows_with(8, 0))] * 3)) == 100
    assert read(run_of([sample(rows_with(8, 0)), sample(rows_with(0, 8)),
                        sample(rows_with(9, 9), error="boom")])) == 50
    # an action that updated no grouped aggregate, or carries no counters
    assert read(run_of([sample(rows_with(0, 0))])) is None
    assert read(run_of([sample([(1.0,)])])) is None


def test_agg_device_ms_adds_the_aggregates_programs():
    read = harness.load_reader("layer_metrics", "agg.device_ms")
    run = traced([["jit_agg_dense_update", 0.0030, 24],
                  ["jit_agg_dense_merge", 0.0006, 3],
                  ["jit_agg_finalize", 0.0003, 3],
                  ["jit__slice_grouped", 0.0100, 24]])
    assert read(run) == pytest.approx(1.3)
    # the sort-based names are the aggregate's too
    assert read(traced([["jit_agg_update", 0.3, 24]])) == pytest.approx(100)
    # an older program names them all `kernel`; an untraced run has none
    assert read(traced([["jit_kernel", 0.3, 24]])) is None
    assert read(SimpleNamespace(trace=None)) is None


def test_agg_roofline_is_least_bytes_over_the_peak_over_busy():
    mod = harness.load_module("layer_metrics", "kernels.agg_roofline")
    run = traced([["jit_agg_dense_update", 0.003, 24]])
    # 7 columns x 4 B x 6M rows = 168 MB an action: 0.2051 ms at 819 GB/s
    assert mod.least_bytes(run) == 168_000_000
    assert mod.read(run) == pytest.approx(100 * 168e6 / 819e9 / 0.001)
    assert mod.read(traced([["jit_kernel", 0.3, 24]])) is None
    assert mod.read(SimpleNamespace(trace=None)) is None


def test_dict_columns_adds_the_attr_over_an_actions_scan_spans():
    read = harness.load_reader("layer_metrics", "scan.dict_columns")

    def tasks(*counts):
        return [span(f"task:p{i}", 0, 50, [
            span("scan.host_decode", 1, 40, columns=7, rows=10,
                 dict_columns=n, dict_bytes=40)], kind="task")
            for i, n in enumerate(counts)]

    assert read(run_of([action(tasks(2, 2, 2, 2))] * 2)) == 8
    # strings that came decoded leave the attr at 0; a program without
    # the attr (q6's scan, the parent) leaves nothing to read
    assert read(run_of([action(tasks(0, 0))])) == 0
    bare = [span("task:p0", 0, 50, [
        span("scan.host_decode", 1, 40, columns=4, rows=10)], kind="task")]
    assert read(run_of([action(bare)])) is None
    assert read(run_of([action(None)])) is None


def test_new_entries_are_declared_as_the_issue_says(bench):
    per_layer = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW:
        assert per_layer[name]["workloads"] == [CELL]
        assert per_layer[name]["moves"] == "query_s"
    assert per_layer["kernels.agg_roofline"]["unit"] == "%"
    reported = {m["name"] for m in harness.metrics_of(bench, "per_layer", CELL)}
    assert set(NEW) | {"scan.host_ms", "scan.upload_MB", "sink.fences",
                       "device.permit_wait_ms", "sink.download_ms",
                       "operators.dispatches", "operators.device_ms",
                       "kernels.hbm_roofline", "device.idle_share",
                       "window.build_s", "planner.plan_ms"} <= reported
    assert not any(n.endswith(".write") for n in reported)
    for cell in ("q6_scan", "lineitem_write_slim"):
        theirs = {m["name"] for m in harness.metrics_of(bench, "per_layer",
                                                        cell)}
        assert not set(NEW) & theirs
    end_to_end = {m["name"] for m in harness.metrics_of(bench, "end_to_end",
                                                        CELL)}
    # the tail too: a window holds 142-166 actions (PERF.md section 2)
    assert end_to_end == {"query_s", "query_p90_s", "rows_per_s", "setup_s"}
    entry, config, cell = harness.load_cell(bench, CELL)
    assert config["name"] == "tpch_sf1_parquet_q1"
    assert config["scale_factor"] == 1.0 and config["reduced"] == [
        "scale_factor"]
    # the base's conf, layout and guarantees, and its own beside them
    base = harness.load_config(
        os.path.join(harness.HERE, "configs", "tpch_sf1_parquet.json"))
    assert config["conf"] == base["conf"] and config["layout"] == base["layout"]
    assert set(base["guarantees"]) < set(config["guarantees"])
