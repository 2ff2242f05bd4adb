"""Host fences of one action (the program's fencesPerQuery counter read
around it: every point where the host waits for the device), median over
the window."""

from lib import loop


def read(run):
    return loop.median([s.record.counters["fencesPerQuery"]
                        for s in run.samples if not s.error])
