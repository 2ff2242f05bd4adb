"""Rows of the scanned tables (counted from the generated arrays) times
the actions of the window, over the seconds from the window's start to the
end of the last action: the mean, where query_s is the median."""

from lib import loop


def read(run):
    return loop.rate(run.rows_per_action, run.samples)
