"""Test configuration: force an 8-virtual-device CPU mesh.

Tests run CPU-only (no TPU dependency) with 8 virtual XLA devices so that
multi-chip sharding/collective paths compile and execute, per the driver's
dryrun contract. Must run before jax initializes a backend.
"""

import os
import sys

# Must be set before jax import / backend init.  Shared scrub rules live in
# spark_rapids_tpu.utils.hostenv (imports no jax).
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
from spark_rapids_tpu.utils.hostenv import ensure_cpu_env  # noqa: E402

ensure_cpu_env(default_devices=8)

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "hotpath: run under jax transfer_guard_device_to_host('disallow') "
        "— any IMPLICIT device->host transfer (np.asarray/bool()/float() "
        "on a device value) raises, dynamically enforcing what tpulint's "
        "host-sync rule proves statically; explicit jax.device_get at "
        "planned sync points stays allowed")
    config.addinivalue_line(
        "markers", "slow: excluded from the tier-1 verify run")


@pytest.fixture(autouse=True)
def _transfer_guard_sanitizer(request):
    """Sanitizer for tests marked @pytest.mark.hotpath: the linter claims
    the hot paths never sync implicitly; the transfer guard makes the
    claim enforce itself at runtime (PAPERS.md: Theseus attributes most
    regressions to exactly these unplanned device->host transfers)."""
    if request.node.get_closest_marker("hotpath") is None:
        yield
        return
    with jax.transfer_guard_device_to_host("disallow"):
        yield


@pytest.fixture
def device_string_decoder(monkeypatch):
    """Every parquet STRING column to the device decoder
    (io/parquet_device.py), as a scan routed them until PR 37 gave the
    dictionary-encoded ones to Arrow (io/scan.py `dict_chunk_ndvs`; here
    no split of any scan names a column Arrow could hand over as codes,
    to the planner and to the tasks alike). The decoder keeps the splits
    that hold a PLAIN string column, and its tests keep reading the small
    dictionary files they always read: they steer here, in the test, not
    through an option of the program."""
    from spark_rapids_tpu.io.scan import TpuFileScanExec

    monkeypatch.setattr(TpuFileScanExec, "_dict_ndvs_by_split",
                        lambda self, conf: [{} for _ in self.splits])


@pytest.fixture(autouse=True, scope="module")
def _clear_jit_caches_per_module():
    """Release compiled executables between test modules. A full-suite run
    accumulates thousands of live XLA:CPU executables in one process and
    past a threshold the runtime segfaults mid-execution (reproduced only
    with ~the whole suite's cache resident; any half of the suite passes).
    Clearing per module keeps the live-executable count bounded at the cost
    of some recompilation."""
    yield
    jax.clear_caches()


@pytest.fixture(scope="session")
def eight_devices():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 virtual cpu devices, got {devs}"
    return devs


@pytest.fixture()
def session():
    """A fresh TpuSession per test. SPMD stage programs (on by default
    since r14) compile over a 1-device mesh here: an 8-virtual-device
    shard_map program costs multi-second XLA compiles per distinct
    schema on 1-core CI, which the tier-1 dots budget cannot afford for
    every incidental aggregate. The full-mesh shapes are exercised
    explicitly (tests/test_spmd.py sets spmd.meshDevices=0), and tests
    pinning the host-loop executor's metrics disable spmd themselves."""
    import spark_rapids_tpu as srt

    s = srt.new_session()
    s.conf.set("rapids.tpu.sql.spmd.meshDevices", 1)
    yield s
    s.stop()
