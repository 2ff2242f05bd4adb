"""Milliseconds in which an operation ran on the device during one traced
action: the union of the device trace's intervals, median over the traced
actions, mean over the chips."""

from lib import loop


def read(run):
    if run.trace is None:
        return None
    return loop.median(run.trace["action_busy_s"]) * 1e3
