"""Process-wide clock of time during which the process stood still, and the
one reading of "how long has this been at work" built on it.

The self-healing layer reads silence and slowness off wall clocks — the
dispatch watchdog (engine/watchdog.py) and the scheduler's straggler
speculation (engine/scheduler.py). Wall time is not work: while a program
is being built (engine/compile_clock.py), and while the whole process is
stopped — the machine took its cores away, a debugger or a SIGSTOP held
it, a foreign call kept the interpreter's lock — no thread's wait is
evidence of a fault. A stopped process ages every task in flight at once;
read as slowness, one pause of half a second duplicates the pool's width
of tasks, which no straggler does.

ONE heartbeat feeds the clock: a daemon thread of its own that asks to
wake every _BEAT_NS and books how late it woke, past _SLACK_NS of
scheduling noise. A thread that sleeps 50 ms and wakes 2 s later was not
scheduled for 1.95 s; neither was anything else that needs the
interpreter. It is the clock's own thread, not the watchdog's loop or the
scheduler's harvest loop, because each of those runs only while its own
half is on and in use, and the clock must beat while either is: one
feeder, so no union of two feeders' windows. A beat is one timed wait and
a dozen bytecodes under a lock, twenty a second.

A reader does not wait for the late heartbeat to wake: `paused_ns(now)`
counts the overdue part of the beat in flight too, so whichever thread
the kernel runs first after a pause reads the same clock.

What is NOT a pause: a task blocked on a permit, a lock or a queue while
other threads work. The heartbeat beats on time through that, and the
wait stays in the task's runtime.

A pause while a program is being built is the compile clock's already:
this clock books a late wake less what the compile clock saw since the
last beat, so `AtWork` subtracts the two clocks and takes the overlap
once.
"""

from __future__ import annotations

import threading
from typing import Optional

from spark_rapids_tpu.engine import compile_clock
from spark_rapids_tpu.obs.trace import wall_ns

_BEAT_NS = 50_000_000
# a wake less late than this is scheduling noise (eight busy threads hand
# the interpreter's lock round in 5 ms turns) and books nothing
_SLACK_NS = 2 * _BEAT_NS

_lock = threading.Lock()
_total_ns = 0      # booked pauses
_due_ns: Optional[int] = None  # when the heartbeat asked to wake; None = no heartbeat
_compile_ns0 = 0   # the compile clock at the last beat
_thread: Optional[threading.Thread] = None
_stop = threading.Event()


def _overdue_ns(now_ns: int) -> int:
    """How far past its slack the beat in flight is at now_ns, less the
    building since the last beat (caller holds _lock)."""
    if _due_ns is None:
        return 0
    late = now_ns - _due_ns - _SLACK_NS
    if late <= 0:
        return 0
    return max(0, late - (compile_clock.compiling_ns(now_ns) - _compile_ns0))


def paused_ns(now_ns: int) -> int:
    """Nanoseconds since process start during which the process stood
    still and no program was being built; callers subtract two readings."""
    with _lock:
        return _total_ns + _overdue_ns(now_ns)


def _beat(now_ns: int) -> None:
    """The heartbeat woke at now_ns: book how late, ask for the next wake."""
    global _total_ns, _due_ns, _compile_ns0
    with _lock:
        if _due_ns is None:
            return  # shut down between the wake and here
        # tpulint: shared-state-mutation -- under _lock; the clock is
        # process-wide by design (module docstring)
        _total_ns += _overdue_ns(now_ns)
        # tpulint: shared-state-mutation -- under _lock
        _due_ns = now_ns + _BEAT_NS
        # tpulint: shared-state-mutation -- under _lock
        _compile_ns0 = compile_clock.compiling_ns(now_ns)


def _run(stop: threading.Event) -> None:
    while not stop.wait(_BEAT_NS / 1e9):
        _beat(wall_ns())


def start() -> None:
    """Start the heartbeat if it is not beating (speculation and the
    watchdog each call this when they first have something in flight)."""
    global _thread, _stop, _due_ns, _compile_ns0
    if _thread is not None:
        return
    with _lock:
        if _thread is not None:
            return
        now_ns = wall_ns()
        # tpulint: shared-state-mutation -- under _lock
        _due_ns = now_ns + _BEAT_NS
        # tpulint: shared-state-mutation -- under _lock
        _compile_ns0 = compile_clock.compiling_ns(now_ns)
        # tpulint: shared-state-mutation -- under _lock
        _stop = threading.Event()
        # tpulint: naked-thread -- context-free daemon by design: it reads
        # two clocks and acts on no query's state
        # tpulint: shared-state-mutation -- under _lock
        _thread = threading.Thread(target=_run, args=(_stop,), daemon=True,
                                   name="srt-pause-clock")
        _thread.start()


def shutdown() -> None:
    """Stop the heartbeat (with the shared session runtime). What was
    booked stays, so readings taken before still subtract."""
    global _thread, _due_ns
    with _lock:
        th = _thread
        _thread = None
        _due_ns = None
        _stop.set()
    if th is not None:
        th.join(timeout=2.0)


def _excused_ns(now_ns: int) -> int:
    return compile_clock.compiling_ns(now_ns) + paused_ns(now_ns)


class AtWork:
    """How long something has been at work since start_ns with nothing to
    excuse it: wall time, less the time programs were being built, less
    the time the process stood still; the two clocks are read once here
    and once at each asking. Never under 0."""

    __slots__ = ("start_ns", "_excused_ns0")

    def __init__(self, start_ns: int):
        self.start_ns = start_ns
        self._excused_ns0 = _excused_ns(start_ns)

    def ns(self, now_ns: int) -> int:
        excused = _excused_ns(now_ns) - self._excused_ns0
        return max(0, now_ns - self.start_ns - excused)

