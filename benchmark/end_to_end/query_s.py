"""Median wall seconds of one action of the window, from collect() or
write.parquet() called to rows returned or every file closed."""

from lib import loop


def read(run):
    return loop.median(loop.durations(run.samples))
