"""One-time accelerator dispatch-latency probe.

The engine's sync-vs-stay-lazy tradeoffs (e.g. compacting partial-aggregate
output with a row-count round trip) depend on how expensive a host<->device
synchronization actually is.  On a locally attached chip a fence is about
a millisecond (0.8-1.1 ms on a TPU v5 lite, chip_smoke.py 2026-09-26) and
early compaction wins; on a remote PJRT backend a fence can cost tens of
milliseconds, dwarfing any compute it saves.  The
reference hardcodes the cheap-sync assumption (CUDA streams on a local GPU);
a TPU-native engine instead measures once and lets policies adapt.

The probe runs two fenced round trips of a trivial jitted program on the
default backend and caches the minimum.  It must only be called from code
paths where the backend is already initialized (exec-layer policy hooks);
it never forces backend selection on its own.
"""

from __future__ import annotations

import os
import time
from typing import Optional

_fence_ms: Optional[float] = None


def fence_cost_ms() -> float:
    """Measured cost (ms) of one dispatch + blocking scalar readback on the
    default jax backend.  Cached for the process.  Override with
    ``SRT_FENCE_MS`` (float) for tests and benchmarks."""
    global _fence_ms
    if _fence_ms is not None:
        return _fence_ms
    env = os.environ.get("SRT_FENCE_MS")
    if env is not None:
        _fence_ms = float(env)
        return _fence_ms
    import jax
    import jax.numpy as jnp
    import numpy as np

    # tpulint: jit-cache -- one-shot probe; result memoized in _fence_ms
    f = jax.jit(lambda x: x + 1)
    x = jnp.zeros((8,), jnp.int32)
    np.asarray(f(x))  # warm (compile)
    best = float("inf")
    for _ in range(2):
        t0 = time.perf_counter()
        np.asarray(f(x))
        best = min(best, time.perf_counter() - t0)
    _fence_ms = best * 1e3
    return _fence_ms


def reset() -> None:
    """Test hook: forget the cached measurement."""
    global _fence_ms
    _fence_ms = None
