"""Process-wide clock of time during which a program was being built.

The first dispatch of a program includes its build — trace, lowering and
the XLA compile (or its load from the persistent cache) — and on the chip
one stable sort costs minutes of compile, while eight task threads tracing
at once share one GIL. The self-healing layer reads silence
and slowness off wall clocks — the dispatch watchdog (engine/watchdog.py)
and the scheduler's straggler speculation (engine/scheduler.py) — so both
subtract what this clock saw: building is neither a wedge nor straggling.

The clock is process-wide, not per thread, on purpose: jax lets ONE thread
compile a given program while every other thread that dispatches it waits
inside jax's cache for the result, and tasks queue on permits a compiling
task holds. While any build is in flight, no thread's wait is evidence of
a fault.

jax itself marks the windows: under each of its three build events it
records a scalar when the step begins and the duration when it ends.
Windows nest (an inner jit traces inside an outer trace) and overlap
across threads; the clock counts their union.
"""

from __future__ import annotations

import threading

import jax.monitoring

from spark_rapids_tpu.obs.trace import wall_ns

# jax's duration events -> the step each one times. The first three are
# the build windows; the fourth is the part of a backend compile that was a
# load from the persistent cache.
_STEP_OF = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile_or_load",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_load",
}
_BUILD_EVENTS = frozenset(
    e for e, step in _STEP_OF.items() if step != "cache_load")

_lock = threading.Lock()
_in_flight = 0   # build steps running now
_since_ns = 0    # when _in_flight last left zero
_total_ns = 0    # closed windows with at least one build step in flight
_step_seconds = dict.fromkeys(_STEP_OF.values(), 0.0)


def compiling_ns(now_ns: int) -> int:
    """Nanoseconds since process start during which a program was being
    built; callers subtract two readings."""
    with _lock:
        return _total_ns + (now_ns - _since_ns if _in_flight else 0)


def step_seconds() -> dict:
    """Seconds jax reported for each step so far, summed over threads and
    over nested windows (an inner jit's trace counts in the outer one's
    too): they attribute compiling_ns, they do not add up to it."""
    with _lock:
        return dict(_step_seconds)


def _on_begin(event: str, _value, **_kw) -> None:
    global _in_flight, _since_ns
    if event in _BUILD_EVENTS:
        with _lock:
            if _in_flight == 0:
                # tpulint: shared-state-mutation -- under _lock; the clock
                # is process-wide by design (module docstring)
                _since_ns = wall_ns()
            # tpulint: shared-state-mutation -- under _lock (counter)
            _in_flight += 1


def _on_end(event: str, duration_s, **_kw) -> None:
    global _in_flight, _total_ns
    step = _STEP_OF.get(event)
    if step is None:
        return
    with _lock:
        # tpulint: shared-state-mutation -- under _lock (counter)
        _step_seconds[step] += duration_s
        if event in _BUILD_EVENTS and _in_flight > 0:
            # tpulint: shared-state-mutation -- under _lock (counter)
            _in_flight -= 1
            if _in_flight == 0:
                # tpulint: shared-state-mutation -- under _lock
                _total_ns += wall_ns() - _since_ns


jax.monitoring.register_scalar_listener(_on_begin)
jax.monitoring.register_event_duration_secs_listener(_on_end)
