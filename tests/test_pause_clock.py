"""A pause of the whole process is neither a straggler nor a wedge
(engine/pause_clock.py): the clock, the one reading of "at work" on it, and
the two judges that read it, the scheduler's speculation and the dispatch
watchdog. Clocks are faked wherever the order of threads would otherwise
decide; the only real waits are a few heartbeats long."""

import threading
import time

import pytest

from spark_rapids_tpu.engine import cancel as CX
from spark_rapids_tpu.engine import compile_clock, pause_clock
from spark_rapids_tpu.engine import watchdog as WD
from spark_rapids_tpu.engine.scheduler import TaskScheduler
from spark_rapids_tpu.engine.watchdog import DispatchEntry, DispatchWatchdog
from spark_rapids_tpu.utils import metrics as M

S = 1_000_000_000
MS = 1_000_000
BEAT = pause_clock._BEAT_NS
SLACK = pause_clock._SLACK_NS
T0 = 100 * S


@pytest.fixture
def by_hand(monkeypatch):
    """No heartbeat thread: the test beats the clock itself, at times of
    its own, from a first wake asked for at T0. Returns a function that
    opens or closes the compile clock's window."""
    pause_clock.shutdown()
    monkeypatch.setattr(pause_clock, "_total_ns", 0)
    monkeypatch.setattr(pause_clock, "_due_ns", T0)
    monkeypatch.setattr(pause_clock, "_compile_ns0", 0)
    monkeypatch.setattr(compile_clock, "_total_ns", 0)
    monkeypatch.setattr(compile_clock, "_in_flight", 0)

    def building(since_ns=None, total_ns=None):
        monkeypatch.setattr(compile_clock, "_in_flight",
                            0 if since_ns is None else 1)
        if since_ns is not None:
            monkeypatch.setattr(compile_clock, "_since_ns", since_ns)
        if total_ns is not None:
            monkeypatch.setattr(compile_clock, "_total_ns", total_ns)

    yield building
    pause_clock.shutdown()


def beat_on_time(until_ns):
    """Every beat due up to until_ns, each woken exactly when it asked."""
    while pause_clock._due_ns <= until_ns:
        pause_clock._beat(pause_clock._due_ns)


@pytest.mark.parametrize("late_ns, booked_ns", [
    (0, 0),                          # on time
    (BEAT, 0),                       # a beat late: scheduling noise
    (SLACK, 0),                      # at the slack exactly
    (SLACK + MS, MS),                # past it: the part past it
    (2 * S, 2 * S - SLACK),          # a stopped process
])
def test_clock_books_a_late_wake_and_not_one_on_time(by_hand, late_ns,
                                                     booked_ns):
    beat_on_time(T0 + S)
    assert pause_clock.paused_ns(T0 + S) == 0
    woke = pause_clock._due_ns + late_ns
    # a reader that runs before the late heartbeat does reads the same
    assert pause_clock.paused_ns(woke) == booked_ns
    pause_clock._beat(woke)
    assert pause_clock.paused_ns(woke) == booked_ns
    assert pause_clock._due_ns == woke + BEAT
    # ... and once booked it stays, with the beats on time again
    beat_on_time(woke + S)
    assert pause_clock.paused_ns(woke + S) == booked_ns


def test_clock_without_a_heartbeat_reads_no_pause(by_hand):
    pause_clock._beat(T0 + 3 * S)
    booked = pause_clock.paused_ns(T0 + 3 * S)
    assert booked == 3 * S - SLACK
    pause_clock.shutdown()
    # what was booked stays; nothing is overdue with no beat asked for,
    # and a beat that lost the race with the shutdown asks for none
    assert pause_clock.paused_ns(T0 + 60 * S) == booked
    pause_clock._beat(T0 + 61 * S)
    assert pause_clock._due_ns is None
    assert pause_clock.paused_ns(T0 + 120 * S) == booked


def test_work_never_reads_under_zero(by_hand):
    beat_on_time(T0 + 10 * S)
    work = pause_clock.AtWork(T0 + 10 * S)
    # the harvest loop's `now` may be older than a task's start
    assert work.ns(T0 + 9 * S) == 0
    assert work.ns(T0 + 10 * S) == 0
    beat_on_time(T0 + 11 * S)
    assert work.ns(T0 + 11 * S) == 1 * S


@pytest.mark.parametrize("build_ends_s, pause_ends_s, union_s", [
    (15, 14, 4),     # the pause inside the build's window
    (13, 16, 5),     # the pause outlasts it
    (None, 14, 2),   # no build at all
])
def test_work_subtracts_a_build_and_a_pause_that_overlap_once(
        by_hand, build_ends_s, pause_ends_s, union_s):
    """Started at 10 s; a build from 11 s; the process stopped from 12 s.
    At 20 s the work is the wall less the UNION of the two windows, to
    within the beat and slack the pause clock cannot see under."""
    building = by_hand
    beat_on_time(T0 + 10 * S)
    work = pause_clock.AtWork(T0 + 10 * S)
    beat_on_time(T0 + 11 * S)
    if build_ends_s is not None:
        building(since_ns=T0 + 11 * S)
    beat_on_time(T0 + 12 * S - 1)
    if build_ends_s is not None and build_ends_s < pause_ends_s:
        # the build's end is booked by jax's listener after the process
        # woke, its duration from jax's own clock
        building(total_ns=(build_ends_s - 11) * S)
    pause_clock._beat(T0 + pause_ends_s * S)
    beat_on_time(T0 + 15 * S)
    if build_ends_s is not None and build_ends_s >= pause_ends_s:
        building(total_ns=(build_ends_s - 11) * S)
    beat_on_time(T0 + 20 * S)
    exact = (10 - union_s) * S
    assert exact <= work.ns(T0 + 20 * S) <= exact + BEAT + SLACK


# -- the two judges -----------------------------------------------------------
@pytest.fixture
def jumping_clock(monkeypatch):
    """The engine's wall clock with an offset the test can bump: every
    thread sees the jump at once, as after a SIGSTOP / SIGCONT. The real
    heartbeat thread beats on it."""
    from spark_rapids_tpu.obs import trace

    pause_clock.shutdown()
    offset = [0]
    real = time.perf_counter_ns

    def wall_ns():
        return real() + offset[0]

    for mod in (trace, pause_clock, compile_clock, WD):
        monkeypatch.setattr(mod, "wall_ns", wall_ns)

    def jump(ns):
        offset[0] += ns

    yield jump
    pause_clock.shutdown()


@pytest.mark.parametrize("jump_s, slow_task, speculated, quick_s", [
    (3, None, 0, 0.02),    # a pause alone: nobody is a straggler
    (0, 11, 1, 0.02),      # a straggler alone: as ever
    (3, 11, 1, 0.02),      # a straggler during a pause: still caught, alone
    # siblings of a millisecond (an ungrouped partial over a cached batch):
    # 4 x p95 is 4 ms, so the floor of 500 ms is the threshold, and the
    # pause is no more a straggler for them than for tasks of 20 ms
    (3, None, 0, 0.001),
])
def test_speculation_tells_a_pause_from_a_straggler(jumping_clock, jump_s,
                                                    slow_task, speculated,
                                                    quick_s):
    """16 tasks on the 8-thread pool. The first eight finish, the second
    eight are all in flight when the clock jumps: read off the wall every
    one of them is 3 s old, the pool's width of stragglers."""
    sched = TaskScheduler(num_threads=8)
    sched.spec_enabled = True  # the conf's defaults: 500 ms, 4 x p95, 0.5
    second_wave = threading.Barrier(8)
    calls = {}
    mu = threading.Lock()

    def fn(p):
        with mu:
            calls[p] = calls.get(p, 0) + 1
            first_try = calls[p] == 1
        if p < 8 or not first_try:
            time.sleep(quick_s)
            return p * 10
        if second_wave.wait(timeout=30.0) == 0 and jump_s:
            jumping_clock(jump_s * S)
        CX.cancel_aware_sleep(10.0 if p == slow_task else 0.1,
                              site="unit-pause")
        return p * 10

    before = M.speculative_task_count()
    t0 = time.monotonic()
    try:
        res = sched.run_job(16, fn)
        wall = time.monotonic() - t0
    finally:
        sched.shutdown()
    assert res == [p * 10 for p in range(16)]
    assert M.speculative_task_count() - before == speculated
    assert sum(calls.values()) == 16 + speculated
    assert wall < 5.0  # the 10 s nap never gates the job
    CX.assert_reclaimed()


@pytest.mark.parametrize("stopped", [
    True,     # 3 s in flight, all of it a pause: not a wedge
    False,    # 3 s of silence with the heart beating: one
])
def test_watchdog_tells_a_pause_from_a_wedge(by_hand, stopped):
    wd = DispatchWatchdog(timeout_ms=1000.0, poll_ms=10.0)
    entry = DispatchEntry("unit.silent", None, None, T0, 1000.0)
    wd._entries[1] = entry
    beat_on_time(T0 + S // 2)
    wd._scan(T0 + S // 2)
    assert not entry.released.is_set()
    end = T0 + S // 2 + 3 * S
    if stopped:
        # the watchdog's thread may run before the heartbeat's does
        wd._scan(end)
        assert not entry.released.is_set()
        pause_clock._beat(end)
        wd._scan(end)
    else:
        beat_on_time(T0 + S - MS)
        wd._scan(T0 + S - MS)
        assert not entry.released.is_set()
        beat_on_time(end)
        wd._scan(end)
    assert entry.released.is_set() == (not stopped)
    assert wd.wedged_sites().get("unit.silent", 0) == int(not stopped)
    if stopped:
        # a dispatch that stays silent after the pause is caught once its
        # silence less the pause passes the timeout
        beat_on_time(end + S)
        wd._scan(end + S)
        assert entry.released.is_set()
        assert entry.silent_ms(end + S) == pytest.approx(
            1500.0 + (BEAT + SLACK) / MS)


@pytest.mark.parametrize("judge", ["speculation", "watchdog"])
def test_either_judge_alone_starts_the_heartbeat(judge):
    pause_clock.shutdown()
    assert pause_clock._thread is None
    if judge == "speculation":
        sched = TaskScheduler(num_threads=2)
        sched.spec_enabled = True
        try:
            assert sched.run_job(2, lambda p: p) == [0, 1]
        finally:
            sched.shutdown()
    else:
        wd = DispatchWatchdog(timeout_ms=30000.0, poll_ms=10.0)
        old = DispatchWatchdog._instance
        DispatchWatchdog._instance = wd
        try:
            WD.deregister(WD.register("unit.beat"))
        finally:
            DispatchWatchdog._instance = old
            wd._stop.set()
    try:
        assert pause_clock._thread.is_alive()
        assert pause_clock._thread.daemon
        due = pause_clock._due_ns
        deadline = time.monotonic() + 3.0
        while pause_clock._due_ns == due and time.monotonic() < deadline:
            time.sleep(0.01)
        assert pause_clock._due_ns > due  # it beats
    finally:
        pause_clock.shutdown()
    assert pause_clock._thread is None and pause_clock._due_ns is None
