"""The rate of end_to_end/rows_per_s.py with the window's single longest
action set aside (lib/loop.rate_less_longest): what the action loop
sustains when one action that stood still, or in a traced run the one
that pays the profiler's stop, is not counted against it. Where it and
the end-to-end rate part by more than 1 / (actions a window), the
difference is what one stalled action took."""

from lib import loop


def read(run):
    return loop.rate_less_longest(run.rows_per_action, run.samples)
