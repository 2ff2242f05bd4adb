"""The harness's own tests run on the CPU backend, like the repo's suite
(tests/conftest.py): the backend is chosen before jax is first imported.
The chip requirement is patched out here, in the tests, never by an option
of run.py."""

import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (ROOT, BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

from spark_rapids_tpu.utils.hostenv import ensure_cpu_env  # noqa: E402

ensure_cpu_env(default_devices=8)

import pytest  # noqa: E402

SF = 0.01  # 60,000 lineitem rows
# what the patched look for a chip answers: the CPU backend, under the
# chip's device_kind so that lib/peaks.json resolves
CPU_DEVICE = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}


@pytest.fixture(scope="session")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.fixture
def rehearse(bench, monkeypatch, tmp_path):
    """measure() as run.py drives it, at scale factor SF on the CPU
    backend: rehearse(cell, traced=False, seconds=0.5)."""
    from lib import harness

    monkeypatch.setattr(harness, "require_tpu", lambda chips: CPU_DEVICE)

    def run(cell_name, traced=False, seconds=0.5, seed=7):
        entry, config, cell = harness.load_cell(bench, cell_name)
        config = dict(config, scale_factor=SF)
        return harness.measure(bench, entry, config, cell, seed, seconds,
                               traced, time.perf_counter(),
                               data_root=str(tmp_path / "data"))

    return run


@pytest.fixture(scope="session")
def arrays():
    from lib import tpch_gen

    return tpch_gen.gen_tables(SF, 11, ["lineitem"])
