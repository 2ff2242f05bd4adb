"""The reduction from .xplane.pb to busy intervals, on a small trace
recorded on a TPU v5 lite by tools/record_tiny_trace.py (PR 24): three
marked actions of one jitted program each."""

import os

import pytest

from lib import xplane

TINY = os.path.join(os.path.dirname(__file__), "data",
                    "tiny_tpu_v5e.xplane.pb")


def test_interval_arithmetic():
    merged = xplane.union([(5, 7), (0, 2), (1, 3), (6, 6.5)])
    assert merged == [(0, 3), (5, 7)]
    assert xplane.covered(merged, 2, 6) == 2
    assert xplane.gaps(merged, -1, 9) == [(-1, 0), (3, 5), (7, 9)]
    assert xplane.gaps(merged, 1, 2) == []


def test_short_op_drops_layouts():
    name = ("%fusion.57 = s32[524288]{0:T(1024)S(1)} fusion(s32[2048]"
            "{0:T(1024)S(1)} %get-tuple-element.542), kind=kCustom")
    assert xplane.short_op(name) == ("%fusion.57 = s32[524288] fusion("
                                     "s32[2048] %get-tuple-element.542), "
                                     "kind=kCustom")


def test_tiny_trace_reduces():
    lines = xplane.describe(TINY)
    assert "PLANE /device:TPU:0" in lines and "PLANE /host:CPU" in lines
    devices, programs, actions = xplane.read_planes(TINY)
    assert list(devices) == ["/device:TPU:0"] and len(actions) == 3
    runs = programs["/device:TPU:0"]
    assert [name for name, _, _ in runs] == [runs[0][0]] * 3
    # the device's events sit about a millisecond EARLY on the profiler's
    # timeline: each 0.86 ms program ends before the host event of the
    # action that dispatched it begins. Cells' actions last seconds, and
    # the reduction does not correct for it; this file shows it.
    leads = [a[0] - run[1] for a, run in zip(actions, runs)]
    assert all(0.5e6 < lead < 2e6 for lead in leads)
    r = xplane.reduce(TINY)
    assert r["chips"] == 1 and len(r["action_s"]) == 3
    assert all(0.02 < s < 0.03 for s in r["action_s"])   # 20 ms of sleep each
    assert r["window_s"] == pytest.approx(
        sum(r["action_s"]) + 2 * 0.01, rel=0.05)         # 10 ms between
    # so the first program lies before the window and the other two in it,
    # between the marked actions
    assert r["action_busy_s"] == [0.0, 0.0, 0.0]
    assert r["device_programs"] == [["jit_step", pytest.approx(
        sum(b - a for _, a, b in runs[1:]) / 1e9), 2]]
    assert r["busy_s"] == pytest.approx(r["device_programs"][0][1], rel=1e-3)
    assert r["device_ops"][0][0].startswith("%while = (s32[], f32[16777216]")
    assert r["device_ops"][0][1] < r["busy_s"]
    gaps = r["idle_gaps_ns"]
    assert gaps == sorted(gaps, key=lambda g: g[0] - g[1])
    assert sum(b - a for a, b in gaps) / 1e9 == pytest.approx(
        r["window_s"] - r["busy_s"], rel=1e-6)


def test_a_trace_without_a_device_is_refused(tmp_path):
    """What the CPU backend records has no device plane: a traced run in
    which no operation ran on a device gives no metric."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: x + 1)
    jax.profiler.start_trace(str(tmp_path), profiler_options=xplane.options())
    with jax.profiler.TraceAnnotation(xplane.MARKER):
        f(jnp.ones(8)).block_until_ready()
    jax.profiler.stop_trace()
    with pytest.raises(ValueError, match="no operation ran on a device"):
        xplane.reduce(xplane.find_trace(str(tmp_path)))
