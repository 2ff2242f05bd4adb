"""The measured window and the arithmetic on its samples.

A closed loop of one client: a batch engine's caller waits for its rows,
so the next action starts when the last one has returned. Actions start
while less than `seconds` have passed, an action that has started always
finishes, and at least one finishes.
"""

from __future__ import annotations

import math
import time
from typing import Callable, List, NamedTuple

# an action that raises is a failed action, not the end of the run; this
# many in a row is a broken cell, and the window is closed
MAX_FAILURES_IN_A_ROW = 3


class Sample(NamedTuple):
    start_s: float   # from the window's start
    end_s: float
    record: object   # whatever do_action returned (None if it raised)
    error: str       # "" or the exception, as text


def closed_loop(do_action: Callable[[int], object], seconds: float,
                clock: Callable[[], float] = time.perf_counter
                ) -> List[Sample]:
    samples: List[Sample] = []
    in_a_row = 0
    start = clock()
    while True:
        t0 = clock()
        if samples and (t0 - start >= seconds
                        or in_a_row >= MAX_FAILURES_IN_A_ROW):
            return samples
        record, error = None, ""
        try:
            record = do_action(len(samples))
        except Exception as e:  # noqa: BLE001 -- counted in `failed`
            error = f"{type(e).__name__}: {e}"
        in_a_row = in_a_row + 1 if error else 0
        samples.append(Sample(t0 - start, clock() - start, record, error))


def durations(samples: List[Sample]) -> List[float]:
    return [s.end_s - s.start_s for s in samples]


def median(values: List[float]) -> float:
    v = sorted(values)
    mid = len(v) // 2
    return v[mid] if len(v) % 2 else (v[mid - 1] + v[mid]) / 2.0


def nearest_rank(values: List[float], q: float) -> float:
    """The q-quantile by nearest rank: the smallest sample with at least
    q of the samples at or below it."""
    v = sorted(values)
    return v[max(0, math.ceil(q * len(v)) - 1)]


def rate(units_per_action: float, samples: List[Sample]) -> float:
    """Units of every action of the window over the seconds from the
    window's start to the end of the last one: the mean, over all the
    work and all the time."""
    return units_per_action * len(samples) / samples[-1].end_s


def rate_less_longest(units_per_action: float, samples: List[Sample]) -> float:
    """The same rate with the window's single longest action set aside:
    the units of every other action over the window's seconds less that
    action's; with one sample, the plain rate. One action that stands
    still for seconds (PERF.md, section 7: the machine's, 2 to 9 runs in
    74) moves `rate` by 7-20% and this by nothing; in a traced run the
    action set aside is the one that pays the profiler's stop. It stands
    beside `rate`, as a per-layer reading: the end-to-end rate stays the
    one over all the work and all the time."""
    if len(samples) < 2:
        return rate(units_per_action, samples)
    longest = max(durations(samples))
    return (units_per_action * (len(samples) - 1)
            / (samples[-1].end_s - longest))
