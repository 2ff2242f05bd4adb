"""Wall milliseconds at the end of a write in which the chip has nothing
left of the action and the host finishes files: the end of `stage:write`
less the end of the action's last `DeviceToHost`. Nothing for an action
without either span. Median over the window."""

from lib import hostclock


def tail_ms(tree):
    stages = hostclock.named(tree, ("stage:write",))
    fences = hostclock.named(tree, ("DeviceToHost",))
    if not stages or not fences:
        return None
    return (max(sp.end_ns for sp in stages)
            - max(sp.end_ns for sp in fences)) / 1e6


def read(run):
    return hostclock.an_action(run, tail_ms)
