"""Busy against waiting on the host: arithmetic shared by the per-layer
readers of what PR 43 put on the program's span trees (`source`:
`program_span`).

A span the program reads the CPU clock for (`obs.trace.CPU_CLOCKED`)
carries `cpu_ns`, its thread's CPU time between open and close (None for
every other span, and where no one thread's clock could be read), beside
its wall time; an action's root span carries `proc_cpu_ms`, the CPU of
all the process's threads. A program without them (an older commit: its
`Span` has no `cpu_ns` at all) gives every reader here None, so the line
leaves the metric out.
"""

from __future__ import annotations

from types import SimpleNamespace
from typing import Callable, Iterable, Optional

from . import loop, spans

# the spans in which some thread works for the action, or waits inside
# its task; what is left of the root is the session's and the
# scheduler's own time. The query's wait for admission is a `site` span
# and the session's own time all the same
WORK_KINDS = ("task", "op", "site")
PLAN = "plan"
ADMISSION = "admission.wait"


def wall_ms(sp) -> float:
    return (sp.end_ns - sp.start_ns) / 1e6


def cpu_ns(sp) -> Optional[int]:
    return getattr(sp, "cpu_ns", None)


def an_action(run, per_tree: Callable[[object], Optional[float]]
              ) -> Optional[float]:
    """The median over the window's trees of `per_tree`, leaving out the
    trees for which it gives None; None where it gives None for every
    tree."""
    values = [v for v in map(per_tree, spans.trees(run)) if v is not None]
    return loop.median(values) if values else None


def named(tree, names: Iterable[str]) -> list:
    names = set(names)
    return [sp for sp in tree.spans()
            if sp.name in names and sp.end_ns is not None]


def gap_ms(tree) -> float:
    """The root's wall less the part of it in which a working span was
    open on some thread."""
    root = tree.root
    work = [sp for sp in tree.spans() if sp.end_ns is not None
            and (sp.kind in WORK_KINDS or sp.name == PLAN)
            and sp.name != ADMISSION]
    # cut to the root's own interval, so that nothing a late reporter
    # noted outside it can make the gap negative
    clipped = [SimpleNamespace(start_ns=max(sp.start_ns, root.start_ns),
                               end_ns=min(sp.end_ns, root.end_ns))
               for sp in work]
    return wall_ms(root) - spans.union_ms(
        iv for iv in clipped if iv.end_ns > iv.start_ns)


def oncpu_share(tree, names: Iterable[str]) -> Optional[float]:
    """100 x CPU over wall, added up over the action's spans called one
    of `names` that have a `cpu_ns`; None where it has none."""
    timed = [sp for sp in named(tree, names) if cpu_ns(sp) is not None]
    wall = sum(sp.end_ns - sp.start_ns for sp in timed)
    if not wall:
        return None
    return 100.0 * sum(cpu_ns(sp) for sp in timed) / wall
