"""Arithmetic on the program's span trees, shared by the per-layer readers
that read them (`source`: `program_span`).

A traced run keeps one span tree an action (`record.spans`, the program's
`QueryTrace`: `spans()` walks it, a span has `name`, `start_ns`, `end_ns`,
`tid`, `attrs`). Task threads run side by side, so time is never summed
over spans that may overlap: `union_ms` is the wall time in which at least
one of them was open. A reader gives None where no action of the window
has what it reads, so a program without the span (an older commit) leaves
the metric out of the line instead of reporting 0.
"""

from __future__ import annotations

from typing import Callable, Iterable, List, Optional

from . import loop


def trees(run) -> list:
    """The span trees of the window's actions that ran to their end and
    left one."""
    return [s.record.spans for s in run.samples
            if not s.error and s.record.spans is not None]


def union_ms(spans: Iterable) -> float:
    """Milliseconds covered by at least one of the spans' intervals."""
    covered, end = 0, None
    for lo, hi in sorted((sp.start_ns, sp.end_ns) for sp in spans):
        if end is None or lo > end:
            covered += hi - lo
            end = hi
        elif hi > end:
            covered += hi - end
            end = hi
    return covered / 1e6


def total_ms(spans: Iterable) -> float:
    """The spans' durations added up: thread time, not wall time."""
    return sum(sp.end_ns - sp.start_ns for sp in spans) / 1e6


def attr_total(key: str, scale: float = 1.0) -> Callable[[List], float]:
    """A reduction: the sum of attr `key` over the spans that carry it."""
    return lambda spans: sum(sp.attrs[key] for sp in spans) * scale


def median_an_action(run, names: Iterable[str],
                     reduce: Callable[[List], float],
                     attr: Optional[str] = None) -> Optional[float]:
    """Median over the window's actions of `reduce` over an action's
    closed spans called one of `names` (and carrying `attr`, where one
    is asked for); None where no action has such a span."""
    names = set(names)
    values, found = [], False
    for tree in trees(run):
        spans = [sp for sp in tree.spans()
                 if sp.name in names and sp.end_ns is not None
                 and (attr is None or attr in sp.attrs)]
        found = found or bool(spans)
        values.append(reduce(spans))
    return loop.median(values) if found else None
