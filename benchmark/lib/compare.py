"""The comparison that decides `correct`.

Keys, strings and counts are compared exactly. Floats are compared to a
relative FLOAT_RTOL. The reason for the number: both configurations
compute SQL DOUBLE in float32 on the chip (a v5e has no f64 unit), and a
sound run differs from the float64 reference by 1e-7 or less (PERF.md,
section 2, gives the readings over the seeds), while the reference
computed in bfloat16, the next precision down and the one that would tempt
a later PR, differs by 1.5e-4 or more. 1e-5 leaves two orders of magnitude
below it and one above: room for a 6M-row sum in another order, none for a
lower precision. (chip_smoke.py's 1e-4 is not copied.)
"""

from __future__ import annotations

import math
from typing import List, Sequence

FLOAT_RTOL = 1e-5

# counters of one action that must read 0: rows that came from the host,
# or a self-healing layer that stepped in, are not a result of this system
MUST_BE_ZERO = ("cpuFallbackEvents", "watchdogKills", "speculativeTasks")


def compared(name: str, value: float, limit: float) -> dict:
    return {"name": name, "value": value, "limit": limit}


def rel_err(want: float, got: float) -> float:
    if want == got:
        return 0.0
    if not (math.isfinite(want) and math.isfinite(got)):
        return math.inf
    return abs(want - got) / max(abs(want), abs(got))


def rows(want: Sequence[tuple], got: Sequence[tuple], what: str) -> List[dict]:
    """Two numbers for two lists of rows in the same order: how many rows
    are missing, surplus or differ in a value that is not a float (limit
    0), and the widest relative gap between two floats (FLOAT_RTOL)."""
    differ = abs(len(want) - len(got))
    worst = 0.0
    for rw, rg in zip(want, got):
        if len(rw) != len(rg):
            differ += 1
            continue
        bad = False
        for a, b in zip(rw, rg):
            if isinstance(a, float) and isinstance(b, (float, int)) \
                    and not isinstance(b, bool):
                worst = max(worst, rel_err(a, float(b)))
            elif a != b:
                bad = True
        differ += bad
    return [compared(f"{what}.rows_differ", differ, 0),
            compared(f"{what}.max_rel_err", worst, FLOAT_RTOL)]


def counters(counts: dict, what: str) -> List[dict]:
    """The device did the work: something was dispatched, and nothing fell
    back to the host or was killed or duplicated by the self-healing layer
    (the checks of chip_smoke.check_device_metrics)."""
    out = [compared(f"{what}.{key}", counts.get(key, 0), 0)
           for key in MUST_BE_ZERO]
    out.append(compared(f"{what}.no_dispatch",
                        int(counts.get("deviceDispatches", 0) <= 0), 0))
    return out


def holds(numbers: List[dict]) -> bool:
    return all(n["value"] <= n["limit"] for n in numbers)
