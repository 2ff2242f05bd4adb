"""A run end to end on the CPU backend at sf 0.01, through measure() as
run.py drives it: each action, the traced path, and a timed path broken
underneath, which has to come out as not correct."""

import os
import subprocess
import sys

import pytest

from conftest import BENCH, ROOT
from lib import harness, xplane

TINY = os.path.join(os.path.dirname(__file__), "data",
                    "tiny_tpu_v5e.xplane.pb")


def check_line(result, bench, cell, group):
    assert set(result) >= {"correct", "attempted", "failed", "metrics",
                           "device"}
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 1
    want = {m["name"] for m in harness.metrics_of(bench, group, cell)}
    assert set(result["metrics"]) == want
    units = {m["name"]: m["unit"] for m in bench[group]}
    for name, m in result["metrics"].items():
        assert m["unit"] == units[name] and isinstance(m["value"], (int, float))
    assert set(result["device"]) >= {"platform", "kind", "count",
                                     "memory_peak_bytes"}
    # each number compared beside its limit, under the line's last key
    assert list(result)[-1] == "compared" and result["compared"]
    assert all(value <= limit for value, limit in result["compared"].values())


def test_q6_scan(rehearse, bench):
    result = rehearse("q6_scan", seconds=1.5)
    check_line(result, bench, "q6_scan", "end_to_end")
    assert result["attempted"] >= 10              # so the tail is reported
    m = result["metrics"]
    assert m["query_p90_s"]["value"] >= m["query_s"]["value"] > 0
    assert m["rows_per_s"]["value"] > 0 and m["setup_s"]["value"] > 0


def test_lineitem_write_slim(rehearse, bench, tmp_path):
    result = rehearse("lineitem_write_slim")
    check_line(result, bench, "lineitem_write_slim", "end_to_end")
    assert set(result["metrics"]) == {"rows_per_s.write", "setup_s"}
    assert not os.path.exists(tmp_path / "data" / "lineitem_write_slim")


def test_traced_run_reports_the_layer_metrics(rehearse, bench, monkeypatch):
    """The CPU backend has no device plane, so the reduction is handed the
    trace recorded on the chip (with a millisecond of device time put into
    each action: its programs ran between them); everything else is the
    traced path."""
    reduced = dict(xplane.reduce(TINY), action_busy_s=[0.001] * 3)
    monkeypatch.setattr(harness.xplane, "reduce", lambda path: reduced)
    result = rehearse("q6_scan", traced=True, seconds=0.3)
    check_line(result, bench, "q6_scan", "per_layer")
    m = {k: v["value"] for k, v in result["metrics"].items()}
    assert m["operators.dispatches"] > 0 and m["sink.fences"] == 1
    assert m["planner.plan_ms"] > 0
    assert m["window.build_s"] == 0
    assert m["window.steady_rows_per_s"] > 0
    assert 0 < m["device.idle_share"] < 100
    assert result["device"]["busy_s"] > 0
    assert result["device"]["window_s"] > result["device"]["busy_s"]
    assert len(result["breakdown"]["device_ops"]) >= 1
    assert all(len(g) == 2 for g in result["breakdown"]["idle_gaps"])
    # a write leaves no span tree: the span metric is not listed there
    write = {m["name"] for m in harness.metrics_of(bench, "per_layer",
                                                   "lineitem_write_slim")}
    assert "planner.plan_ms" not in write and "sink.fences" not in write
    assert "sink.fences.write" in write and "setup.build_s" in write


def test_an_altered_answer_is_not_correct(rehearse, monkeypatch):
    """The timed path broken where it produces its answer: every second
    action's revenue is off by one part in ten thousand."""
    real = harness.ActionRunner.__call__

    def broken(self, marked=False):
        record = real(self, marked)
        if self.made % 2 == 0:
            record.result = [(record.result[0][0] * (1 + 1e-4),)]
        return record

    monkeypatch.setattr(harness.ActionRunner, "__call__", broken)
    result = rehearse("q6_scan", seconds=0.5)
    assert result["correct"] is False
    assert 0 < result["failed"] < result["attempted"]
    value, limit = result["compared"]["q6.max_rel_err"]
    assert value > 5 * limit
    assert result["compared"]["actions_failed"] == [result["failed"], 0]


def test_a_write_that_drops_rows_is_not_correct(rehearse, monkeypatch):
    """The write's timed path broken underneath: the last file of every
    directory is deleted after write.parquet returns."""
    real = harness.ActionRunner.__call__

    def broken(self, marked=False):
        record = real(self, marked)
        files = sorted(os.listdir(record.result))
        os.remove(os.path.join(record.result,
                               [f for f in files if f.endswith(".parquet")][-1]))
        return record

    monkeypatch.setattr(harness.ActionRunner, "__call__", broken)
    result = rehearse("lineitem_write_slim", seconds=0.0)
    assert result["correct"] is False and result["failed"] == result["attempted"]


def test_a_host_fallback_is_a_failed_action(rehearse, monkeypatch):
    real = harness.ActionRunner.__call__

    def fell_back(self, marked=False):
        record = real(self, marked)
        record.counters["cpuFallbackEvents"] = 1
        return record

    monkeypatch.setattr(harness.ActionRunner, "__call__", fell_back)
    assert rehearse("q6_scan", seconds=0.0)["correct"] is False


def run_py(args, cwd=ROOT, env=None):
    return subprocess.run(
        [sys.executable, os.path.join(cwd, "benchmark", "run.py"), *args],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


CELL_ARGS = ["--workload", "q6_scan", "--seed", "1", "--seconds", "1",
             "--trace", "0"]


def test_run_py_ends_stderr_with_the_numbers_compared(monkeypatch, capfd):
    import run

    line = {"correct": False, "attempted": 2, "failed": 1, "metrics": {},
            "device": {}, "compared": {"q6.max_rel_err": [1e-4, 1e-5],
                                       "actions_failed": [1, 0]}}
    monkeypatch.setattr(harness, "run_cell", lambda *a, **kw: line)
    assert run.main(CELL_ARGS) == 0
    out, err = capfd.readouterr()
    assert err.splitlines()[-3:] == [
        "compared q6.max_rel_err: 0.0001 (limit 1e-05)",
        "compared actions_failed: 1 (limit 0)", "correct: False"]
    assert out.splitlines()[-1].startswith('{"correct": false')


def test_run_py_exits_non_zero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = run_py(CELL_ARGS, env=env)
    assert done.returncode != 0
    assert "not a TPU" in done.stderr
    assert '"metrics"' not in done.stdout and '"correct"' not in done.stdout


def test_run_py_exits_non_zero_with_only_the_benchmarks_files(tmp_path):
    """A directory that holds BENCHMARK.json and the files under `paths`
    and nothing of the program gives no result."""
    import shutil

    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "benchmark", ignore=shutil.ignore_patterns(
        ".data", "__pycache__", ".pytest_cache"))
    # past the look for a chip, which this sandbox would fail first
    probe = ("import sys, runpy; sys.argv = ['run.py'] + %r; "
             "sys.path.insert(0, %r); from lib import harness; "
             "harness.require_tpu = lambda chips: "
             "{'platform': 'tpu', 'kind': 'TPU v5 lite', 'count': 1}; "
             "runpy.run_path(%r, run_name='__main__')") % (
        CELL_ARGS, str(tmp_path / "benchmark"),
        str(tmp_path / "benchmark" / "run.py"))
    done = subprocess.run([sys.executable, "-c", probe], cwd=tmp_path,
                          env=dict(os.environ, JAX_PLATFORMS="cpu"),
                          capture_output=True, text=True, timeout=120)
    assert done.returncode != 0
    assert '"metrics"' not in done.stdout and '"correct"' not in done.stdout
    assert "spark_rapids_tpu" in done.stderr
