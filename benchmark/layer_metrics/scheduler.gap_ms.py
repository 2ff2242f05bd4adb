"""Wall milliseconds of one action in which no span that is work was
open on any thread: the root span's wall less the union of its spans of
kind `task`, `op` or `site` (but `admission.wait`) and of `plan`. What
is left is the session's and the scheduler's own: admission, the plan
cache, `execute()` building iterators, submitting and harvesting a job,
stage boundaries. Median over the window."""

from lib import hostclock


def read(run):
    return hostclock.an_action(run, hostclock.gap_ms)
