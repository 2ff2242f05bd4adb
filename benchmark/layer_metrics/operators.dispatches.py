"""Device dispatches of one action (the program's deviceDispatches counter
read around it), median over the window. It repeats exactly."""

from lib import loop


def read(run):
    return loop.median([s.record.counters["deviceDispatches"]
                        for s in run.samples if not s.error])
