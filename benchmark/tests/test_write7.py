"""The cell `lineitem_write7` (PR 40) on the CPU backend at sf 0.01: its
phases through measure() as run.py drives it, untraced and traced; the
control of its comparison (the reference in bfloat16 fails, in float32
passes); its three new per-layer readers on hand-built runs, with the
roofline's `least_bytes` checked by hand for one size; and three files
written wrong (a row too many, a row past the date, two flags swapped),
each of which has to read `correct` false."""

import os
import time
from types import SimpleNamespace

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest
from ml_dtypes import bfloat16

from lib import compare as C
from conftest import CPU_DEVICE, SF
from lib import harness, loop, xplane
from lib.tpch_gen import days
from test_run import TINY, check_line
from test_span_readers import action, run_of, span

CELL = "lineitem_write7"
NEW = ("sink.dict_columns.write", "compact.device_ms.write",
       "kernels.compact_roofline.write", "scan.dict_columns.write")
JOINED = ("scan.host_ms.write", "scan.upload_MB.write",
          "device.permit_wait_ms.write", "sink.download_ms.write",
          "sink.download_MB.write", "sink.host_ms.write",
          "planner.plan_ms.write")
# the chip's path: there a DOUBLE makes the device encoder refuse the
# schema, so the sink downloads and Arrow writes (ROADMAP M9)
CHIP_SINK = {"rapids.tpu.sql.format.parquet.deviceEncode.enabled": False}


@pytest.fixture
def rehearse_chip_sink(bench, monkeypatch, tmp_path):
    """conftest's `rehearse` with the device encoder off, as on the chip."""
    monkeypatch.setattr(harness, "require_tpu", lambda chips: CPU_DEVICE)

    def run(traced=False, seconds=0.5, seed=7):
        entry, config, cell = harness.load_cell(bench, CELL)
        config = dict(config, scale_factor=SF,
                      conf=dict(config["conf"], **CHIP_SINK))
        return harness.measure(bench, entry, config, cell, seed, seconds,
                               traced, time.perf_counter(),
                               data_root=str(tmp_path / "data"))

    return run


def test_lineitem_write7(rehearse_chip_sink, bench, tmp_path):
    result = rehearse_chip_sink(seed=2_900_000_011)
    check_line(result, bench, CELL, "end_to_end")
    assert {"rows_per_s.write", "setup_s"} <= set(result["metrics"])
    assert result["metrics"]["rows_per_s.write"]["value"] > 0
    compared = result["compared"]
    assert compared["write.row_count_off"] == [0, 0]
    assert compared["write.digest.rows_differ"] == [0, 0]
    assert compared["write.digest.max_rel_err"][1] == C.FLOAT_RTOL
    for counter in C.MUST_BE_ZERO:
        assert compared[f"write_lineitem7.{counter}"] == [0, 0]
    assert not os.path.exists(tmp_path / "data" / CELL)


def test_lineitem_write7_traced_reports_its_layer_metrics(
        rehearse_chip_sink, bench, monkeypatch):
    """The CPU backend has no device plane: the reduction is handed the
    trace recorded on the chip with the compaction's programs put into it
    (test_run.py does the same for q6_scan); the spans and the counters
    are the program's own."""
    programs = [["jit__compact_gather_fixed_cols", 0.0270, 24],
                ["jit__compact_plan", 0.0030, 24],
                ["jit__gather_fixed_cols", 0.0500, 24],
                ["jit__slice_grouped", 0.0030, 24]]
    reduced = dict(xplane.reduce(TINY), action_busy_s=[0.011] * 3,
                   device_programs=programs)
    monkeypatch.setattr(harness.xplane, "reduce", lambda path: reduced)
    result = rehearse_chip_sink(traced=True, seconds=0.3)
    check_line(result, bench, CELL, "per_layer")
    m = {k: v["value"] for k, v in result["metrics"].items()}
    assert set(NEW) | set(JOINED) <= set(m)
    # 0.01 x 6M rows in 4 files of one row group: 2 columns x 4 splits in,
    # 2 columns x 4 files out
    assert m["scan.dict_columns.write"] == 8
    assert m["sink.dict_columns.write"] == 8
    # the two programs named `compact`, not the sort's gather beside them
    assert m["compact.device_ms.write"] == pytest.approx(10.0)
    assert 0 < m["kernels.compact_roofline.write"] <= 100
    assert m["sink.fences.write"] == 4 and m["window.build_s.write"] == 0
    # a fused stage, a plan and a gather a split
    assert m["operators.dispatches.write"] == 12
    assert m["sink.download_MB.write"] > 0 and m["sink.host_ms.write"] > 0


def test_extract_in_bf16_fails_and_in_f32_passes(arrays):
    act = harness.load_module("actions", "write_lineitem7")
    want = act.reference(arrays)
    li, _ = arrays["lineitem"]
    kept = int((li["l_shipdate"] <= days(act.SHIPPED_BY)).sum())
    assert want["rows"] == kept and 0.96 < kept / len(li["l_shipdate"]) < 0.99
    # (flag, status, weekday): 3 x 2 x 7 groups, in key order
    keys = [r[:3] for r in want["digest"]]
    assert len(keys) == 42 and keys == sorted(keys)
    assert sum(r[3] for r in want["digest"]) == kept
    f32 = C.rows(want["digest"],
                 act.reference(arrays, np.float32)["digest"], "w")
    low = C.rows(want["digest"],
                 act.reference(arrays, bfloat16)["digest"], "w")
    assert C.holds(f32), f32
    assert not C.holds(low)
    numbers = {n["name"]: n["value"] for n in low}
    assert numbers["w.rows_differ"] == 0
    assert numbers["w.max_rel_err"] > 10 * C.FLOAT_RTOL


def test_the_control_tool_reads_the_cell_at_a_small_size(monkeypatch, capsys):
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
# files written wrong
# ---------------------------------------------------------------------------
def extract(arrays, act) -> pa.Table:
    """What a sound action writes, by pyarrow from the generated arrays."""
    li, _ = arrays["lineitem"]
    keep = li["l_shipdate"] <= days(act.SHIPPED_BY)
    cols = {}
    for c in act.COLUMNS["lineitem"]:
        v = li[c]
        if isinstance(v, np.ndarray):
            v = pa.array(v[keep])
            if c == "l_shipdate":
                v = v.cast(pa.int32()).cast(pa.date32())
        else:
            v = v.filter(pa.array(keep))
        cols[c] = v
    return pa.table(cols)


def write_dir(table: pa.Table, path) -> str:
    os.makedirs(path)
    half = table.num_rows // 2
    pq.write_table(table.slice(0, half), os.path.join(path, "p0.parquet"))
    pq.write_table(table.slice(half), os.path.join(path, "p1.parquet"))
    return str(path)


def with_row(table: pa.Table, at: int, **values) -> pa.Table:
    """`table` with the named columns of row `at` replaced."""
    for name, value in values.items():
        i = table.schema.get_field_index(name)
        col = table.column(i).combine_chunks()
        one = pa.array([value], col.type)
        col = pa.concat_arrays([col.slice(0, at), one, col.slice(at + 1)])
        table = table.set_column(i, name, col)
    return table


def numbers_of(act, want, out_dir):
    return {n["name"]: n["value"] for n in act.compare(want, [out_dir])[0]}


def test_files_written_wrong_are_not_correct(arrays, tmp_path):
    import datetime

    act = harness.load_module("actions", "write_lineitem7")
    want = act.reference(arrays)
    sound = extract(arrays, act)
    numbers = act.compare(want, [write_dir(sound, tmp_path / "sound")])[0]
    assert C.holds(numbers), numbers

    # a row too many: the first row twice
    twice = pa.concat_tables([sound, sound.slice(0, 1)])
    got = numbers_of(act, want, write_dir(twice, tmp_path / "twice"))
    assert got["write.row_count_off"] == 1
    assert got["write.digest.rows_differ"] >= 1

    # a row past the date in a kept row's place: the count is right
    late = datetime.date(1970, 1, 1) + datetime.timedelta(
        days=days(act.SHIPPED_BY) + 7)      # the same weekday group
    past = with_row(sound, 5, l_shipdate=late)
    got = numbers_of(act, want, write_dir(past, tmp_path / "past"))
    assert got["write.row_count_off"] == 0
    assert got["write.digest.rows_differ"] >= 1

    # two flags swapped between two rows: every column's multiset is right
    flags = sound.column("l_returnflag").to_pylist()
    j = next(i for i, f in enumerate(flags) if f != flags[0])
    swapped = with_row(with_row(sound, 0, l_returnflag=flags[j]),
                       j, l_returnflag=flags[0])
    assert sorted(swapped.column("l_returnflag").to_pylist()) == sorted(flags)
    got = numbers_of(act, want, write_dir(swapped, tmp_path / "swapped"))
    assert got["write.row_count_off"] == 0
    assert got["write.digest.rows_differ"] >= 1


def test_a_file_whose_arrow_schema_says_dictionary_reads_the_same(arrays,
                                                                  tmp_path):
    """The comparison is of values: flags stored under a dictionary type
    (what pq.write_table makes of a DictionaryArray) compare like plain
    strings."""
    act = harness.load_module("actions", "write_lineitem7")
    sound = extract(arrays, act)
    for name in ("l_returnflag", "l_linestatus"):
        i = sound.schema.get_field_index(name)
        sound = sound.set_column(
            i, name, sound.column(i).combine_chunks().dictionary_encode())
    numbers = act.compare(act.reference(arrays),
                          [write_dir(sound, tmp_path / "coded")])[0]
    assert C.holds(numbers), numbers


# ---------------------------------------------------------------------------
# the three new readers, on hand-built runs
# ---------------------------------------------------------------------------
def traced(programs, actions=3, rows=6_000_000, result=None, config=None):
    return SimpleNamespace(
        trace={"device_programs": programs, "action_s": [0.1] * actions},
        cell={"action": "write_lineitem7"}, rows_per_action=rows,
        config=config or {"schema": {"lineitem": dict(
            l_quantity="double", l_extendedprice="double",
            l_discount="double", l_tax="double", l_shipdate="date",
            l_returnflag="string", l_linestatus="string")}},
        peaks={"hbm_bytes_per_s": 819e9},
        samples=[loop.Sample(0.0, 0.1, SimpleNamespace(result=result), "")])


def test_sink_dict_columns_adds_the_attr_over_an_actions_write_spans():
    read = harness.load_reader("layer_metrics", "sink.dict_columns.write")

    def tasks(*counts):
        return [span(f"task:p{i}", 0, 50, [
            span("write.arrow", 1, 2, dict_columns=n, dict_bytes=40),
            span("write.file", 2, 40, encoder="arrow", rows=10, bytes=9)],
            kind="task") for i, n in enumerate(counts)]

    assert read(run_of([action(tasks(2, 2, 2, 2))] * 2)) == 8
    # strings that came expanded leave the attr at 0; a program without
    # the attr (the parent) leaves nothing to read
    assert read(run_of([action(tasks(0, 0))])) == 0
    bare = [span("task:p0", 0, 50, [span("write.arrow", 1, 2)], kind="task")]
    assert read(run_of([action(bare)])) is None
    assert read(run_of([action(None)])) is None


def test_compact_device_ms_adds_the_programs_named_compact():
    read = harness.load_reader("layer_metrics", "compact.device_ms.write")
    run = traced([["jit__compact_gather_fixed_cols", 0.0270, 24],
                  ["jit__compact_plan", 0.0030, 24],
                  ["jit__gather_fixed_cols", 0.5, 36],
                  ["jit__pack_live_traced", 0.1, 3]])
    assert read(run) == pytest.approx(10.0)
    # the parent gathers under a name a sort's permutation shares, and
    # plans under `_compact_plan`: the plan alone is read there
    assert read(traced([["jit__gather_fixed_cols", 0.3, 24]])) is None
    assert read(SimpleNamespace(trace=None)) is None


def test_compact_roofline_is_least_bytes_over_the_peak_over_busy(tmp_path):
    mod = harness.load_module("layer_metrics", "kernels.compact_roofline")
    out = tmp_path / "a00000"
    os.makedirs(out)
    for i, n in enumerate((1000, 500)):
        pq.write_table(pa.table({"x": list(range(n))}),
                       str(out / f"p{i}.parquet"))
    run = traced([["jit__compact_plan", 0.003, 24]], rows=2000,
                 result=str(out))
    # five 4-byte values and two 1-byte codes
    assert mod.row_bytes(run) == 22
    assert mod.rows_out(run) == 1500
    # (2000 in + 1500 out) x 22 B = 77,000 B; at SF1 6,000,000 in and
    # 5,850,000 out: 260.7 MB, 0.318 ms at 819 GB/s
    assert mod.least_bytes(run, 1500) == 77_000
    assert mod.least_bytes(traced([], rows=6_000_000), 5_850_000) \
        == 260_700_000
    assert mod.read(run) == pytest.approx(100 * 77_000 / 819e9 / 0.001)
    # no compaction program, no written directory, no trace
    assert mod.read(traced([["jit_fn", 0.3, 24]], result=str(out))) is None
    assert mod.read(traced([["jit__compact_plan", 0.003, 24]])) is None
    assert mod.read(SimpleNamespace(trace=None)) is None


def test_new_entries_are_declared_as_the_issue_says(bench):
    per_layer = {m["name"]: m for m in bench["per_layer"]}
    # a later cell may join these lists behind this one: what is held is
    # that this cell is on them, not that it is the last
    for name in NEW:
        assert per_layer[name]["workloads"][0] == CELL
        assert per_layer[name]["moves"] == "rows_per_s.write"
    for name in JOINED:
        assert per_layer[name]["workloads"][:2] == ["lineitem_write_slim",
                                                    CELL]
    assert per_layer["kernels.compact_roofline.write"]["unit"] == "%"
    # entries are found by name: the harness reads no order. The driver's
    # check of the benchmark does: a PR that changes the program may add
    # entries at the END of a list and nowhere else (it refused this PR's
    # first hand-in, whose four entries stood in front of
    # `operators.concat_ms`, as a change to that entry). So they stand
    # last, and PR 38's test_concat_ms.py, which pins `operators.concat_ms`
    # as `per_layer[-1]` and which no PR but a `benchmark` PR may edit,
    # fails by that position alone (PERF.md section 7, "For the next
    # `benchmark` PR", (e): look the entry up by name). What that test
    # holds of the entry itself is held here, by name
    assert [m["name"] for m in bench["per_layer"][-len(NEW):]] == list(NEW)
    assert per_layer["operators.concat_ms"] == {
        "name": "operators.concat_ms", "unit": "ms", "better": "lower",
        "source": "program_span", "layer": "operator programs",
        "moves": "query_s", "workloads": ["q1_agg"]}
    assert CELL in {w["name"] for w in bench["workloads"]}
    assert "tpch_sf1_parquet_extract7" in {c["name"]
                                           for c in bench["configs"]}
    reported = {m["name"] for m in harness.metrics_of(bench, "per_layer", CELL)}
    assert set(NEW) | set(JOINED) | {
        "operators.dispatches.write", "operators.device_ms.write",
        "sink.fences.write", "kernels.hbm_roofline.write",
        "device.idle_share.write", "device.peak_hbm_GB.write",
        "window.build_s.write", "window.steady_rows_per_s.write",
        "setup.build_s", "setup.first_query_s"} <= reported
    for cell in ("q6_scan", "lineitem_write_slim", "q1_agg"):
        theirs = {m["name"] for m in harness.metrics_of(bench, "per_layer",
                                                        cell)}
        assert not set(NEW) & theirs
    end_to_end = {m["name"] for m in harness.metrics_of(bench, "end_to_end",
                                                        CELL)}
    assert {"rows_per_s.write", "setup_s"} <= end_to_end
    assert not {"query_s", "query_p90_s", "rows_per_s"} & end_to_end
    entry, config, cell = harness.load_cell(bench, CELL)
    assert config["name"] == "tpch_sf1_parquet_extract7"
    assert config["scale_factor"] == 1.0 and config["reduced"] == [
        "scale_factor"]
    assert cell["traffic"]["fresh_output_dir"] is True
    # `extends` through two files: the base's conf and layout, the sink's
    # guarantees and the base's, and its own beside them
    base = harness.load_config(
        os.path.join(harness.HERE, "configs", "tpch_sf1_parquet.json"))
    sink = harness.load_config(
        os.path.join(harness.HERE, "configs", "tpch_sf1_parquet_sink.json"))
    assert config["conf"] == base["conf"] and config["layout"] == base["layout"]
    assert config["schema"] == base["schema"]
    assert set(sink["guarantees"]) < set(config["guarantees"])
    assert len(config["guarantees"]) == len(sink["guarantees"]) + 2
    act = harness.load_module("actions", "write_lineitem7")
    assert act.COLUMNS == harness.load_module("actions", "q1").COLUMNS
