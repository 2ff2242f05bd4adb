"""Every name in BENCHMARK.json resolves to a file, and uses only the
characters the driver takes."""

import os
import re

import pytest

from conftest import BENCH, ROOT
from lib import harness

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def test_names_and_units_use_the_drivers_characters(bench):
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in bench[group]:
            assert NAME.match(e["name"]), e["name"]
            names.append((group in ("end_to_end", "per_layer")
                          and "metric" or group, e["name"]))
    assert len(names) == len(set(names))
    for e in bench["workloads"]:
        assert NAME.match(e["config"]) and NAME.match(e["traffic"])
        assert e["chips"] in (1, 4) and len(e["why"]) <= 200
    for e in bench["configs"]:
        assert all(NAME.match(k) for k in e["reduced"])
        assert len(e["source"]) <= 200 and len(e["why"]) <= 200
    for e in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(e["unit"]), e
        assert e["better"] in ("lower", "higher") and e["source"] in SOURCES
    for e in bench["end_to_end"]:
        assert e["source"] in ("host_clock", "device_trace")
        assert 0.01 <= e["bound"] <= 0.25
    assert {"setup_s"} <= {e["name"] for e in bench["end_to_end"]}
    assert bench["paths"] == ["benchmark"]
    assert 1 <= bench["run_seconds"] <= 51


def test_every_name_resolves_to_a_file(bench):
    cells = {e["name"] for e in bench["workloads"]}
    end_to_end = {e["name"] for e in bench["end_to_end"]}
    for c in bench["configs"]:
        assert c["file"].startswith("benchmark/")
        conf = harness.load_json(os.path.join(ROOT, c["file"]))
        assert conf["name"] == c["name"]
        assert sorted(conf["reduced"]) == sorted(c["reduced"])
        assert any(w["config"] == c["name"] for w in bench["workloads"])
    for w in bench["workloads"]:
        entry, config, cell = harness.load_cell(bench, w["name"])
        assert config["chips"] == entry["chips"]
        assert os.path.isfile(os.path.join(BENCH, "actions",
                                           cell["action"] + ".py"))
        assert harness.metrics_of(bench, "per_layer", w["name"])
        assert len(harness.metrics_of(bench, "end_to_end", w["name"])) >= 2
    for group, directory in (("end_to_end", "end_to_end"),
                             ("per_layer", "layer_metrics")):
        for m in bench[group]:
            assert callable(harness.load_reader(directory, m["name"]))
            assert set(m.get("workloads", cells)) <= cells
    for m in bench["per_layer"]:
        assert m["moves"] in end_to_end


def test_a_file_under_paths_is_named_from_a_names_characters():
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for top, dirs, files in os.walk(BENCH):
        dirs[:] = [d for d in dirs if d not in (".data", "__pycache__",
                                                ".pytest_cache")]
        for f in files:
            rel = os.path.relpath(os.path.join(top, f), ROOT)
            assert ok.match(rel), rel


def test_an_unknown_cell_is_refused(bench):
    with pytest.raises(harness.BenchFailure, match="no workload"):
        harness.load_cell(bench, "no_such_cell")
