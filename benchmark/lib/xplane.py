"""From the profiler's `.xplane.pb` to busy intervals, device seconds per
action, the operations that took most time and the longest idle gaps.

What a trace of this system on a TPU v5 lite looks like (looked at by
hand, PR 24): one plane per chip named `/device:TPU:<n>`, whose line
`XLA Ops` holds one event per operation the chip ran (start and duration
in the device's time, laid on the profiler's common timeline), and the
plane `/host:CPU` with one line per host thread, where a
`jax.profiler.TraceAnnotation` of the harness shows as an event of its
name. The harness wraps every traced action in an annotation named MARKER,
so the actions' boundaries are read from the same file and on the same
clock as the device's events.

The device's events sit about a millisecond early on that timeline (a
program's event ends before the host event that dispatched it begins:
tests/test_xplane.py shows it on the recorded file). A cell's actions last
seconds, so the reduction does not correct for it.

Busy is the union of the intervals in which an operation ran, so that
nested or overlapping events count once.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Tuple

MARKER = "bench_action"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"

Interval = Tuple[float, float]


def options():
    """The profiler as the harness runs it: no Python function tracer (it
    slows eight task threads and swells the file), host events down to the
    level of a TraceAnnotation."""
    from jax.profiler import ProfileOptions

    opts = ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    return opts


def describe(path: str, events: int = 0) -> List[str]:
    """One line per plane and per line of the trace, with the event count
    and the first `events` events: for looking at a trace by hand."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        out.append(f"PLANE {plane.name}")
        for line in plane.lines:
            evs = list(line.events)
            out.append(f"  LINE {line.name!r} events={len(evs)}")
            for e in evs[:events]:
                out.append(f"    {e.name} start_ns={e.start_ns} "
                           f"duration_ns={e.duration_ns}")
    return out


def find_trace(trace_dir: str) -> str:
    """The one .xplane.pb the profiler wrote under trace_dir."""
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def union(intervals: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            if hi > out[-1][1]:
                out[-1] = (out[-1][0], hi)
        else:
            out.append((lo, hi))
    return out


def covered(merged: List[Interval], lo: float, hi: float) -> float:
    """Length of the part of [lo, hi] that the merged intervals cover."""
    return sum(max(0.0, min(b, hi) - max(a, lo)) for a, b in merged)


def gaps(merged: List[Interval], lo: float, hi: float) -> List[Interval]:
    """The parts of [lo, hi] that no interval covers."""
    out, at = [], lo
    for a, b in merged:
        if b <= lo or a >= hi:
            continue
        if a > at:
            out.append((at, a))
        at = max(at, b)
    if hi > at:
        out.append((at, hi))
    return out


_LAYOUT = re.compile(r"\{[^{}]*\}")
_MODULE_ID = re.compile(r"\(\d+\)$")


def short_op(name: str, width: int = 100) -> str:
    """An operation's name as the trace gives it is its whole HLO line;
    without the layouts and cut to `width` it still says which it is."""
    return _LAYOUT.sub("", name)[:width]


def read_planes(path: str) -> Tuple[Dict[str, list], Dict[str, list],
                                    List[Interval]]:
    """({device plane: [(op, start_ns, end_ns)]}, the same for the
    programs (XLA modules, named after the jitted function), the marked
    actions' intervals in the order they ran)."""
    from jax.profiler import ProfileData

    devices: Dict[str, list] = {}
    modules: Dict[str, list] = {}
    actions: List[Interval] = []
    for plane in ProfileData.from_file(path).planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                into = {OPS_LINE: devices, MODULES_LINE: modules}.get(
                    line.name)
                if into is not None:
                    into.setdefault(plane.name, []).extend(
                        (e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                actions.extend((e.start_ns, e.start_ns + e.duration_ns)
                               for e in line.events if e.name == MARKER)
    return devices, modules, sorted(actions)


def reduce(path: str, top: int = 10) -> dict:
    """The reduced trace the layer metrics read:

    window_s           first marked action's start to the last one's end
    busy_s             seconds in which an operation ran in that window,
                       averaged over the chips
    action_busy_s      the same for each marked action
    action_s           each marked action's length
    device_ops         [[name, seconds]], the `top` operations by device
                       seconds summed over the window, averaged over chips;
                       an operation that holds others (a while loop and its
                       body) counts their time too, so these do not add up
    device_programs    [[jitted function, seconds, runs]], the same for
                       whole programs, which do not nest and do add up
    idle_gaps_ns       [(start_ns, end_ns)], the `top` longest gaps on the
                       first chip, on the profiler's clock
    action_start_ns    the first marked action's start on that clock
    """
    devices, modules, actions = read_planes(path)
    if not devices:
        raise ValueError(f"{path}: no plane {DEVICE_PLANE.pattern} with a "
                         f"line {OPS_LINE!r}: no operation ran on a device")
    if not actions:
        raise ValueError(f"{path}: no {MARKER!r} annotation on {HOST_PLANE}")
    lo, hi = actions[0][0], actions[-1][1]
    n = len(devices)
    merged = {name: union([(a, b) for _, a, b in events])
              for name, events in devices.items()}
    by_op: Dict[str, float] = {}
    for events in devices.values():
        for name, a, b in events:
            if b > lo and a < hi:
                by_op[name] = by_op.get(name, 0.0) + (b - a) / 1e9 / n
    by_program: Dict[str, list] = {}
    for events in modules.values():
        for name, a, b in events:
            if b > lo and a < hi:
                rec = by_program.setdefault(_MODULE_ID.sub("", name), [0.0, 0])
                rec[0] += (b - a) / 1e9 / n
                rec[1] += 1
    first = merged[sorted(merged)[0]]
    longest = sorted(gaps(first, lo, hi), key=lambda g: g[0] - g[1])[:top]
    return {
        "chips": n,
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(covered(m, lo, hi) for m in merged.values()) / 1e9 / n,
        "action_s": [(b - a) / 1e9 for a, b in actions],
        "action_busy_s": [sum(covered(m, a, b) for m in merged.values())
                          / 1e9 / n for a, b in actions],
        "device_ops": [[short_op(name), s] for name, s in sorted(
            by_op.items(), key=lambda kv: -kv[1])[:top]],
        "device_programs": [[name, s, runs] for name, (s, runs) in sorted(
            by_program.items(), key=lambda kv: -kv[1][0])[:top]],
        "idle_gaps_ns": longest,
        "action_start_ns": lo,
    }
