"""Wall milliseconds of one action in which at least one reader was
inside Arrow's read of its split (`scan.arrow_read`, a child of
`scan.host_decode`: `read_split`, one threaded `read_row_groups`): the
union over threads, as `scan.host_ms` is of the scan's steps, so never
more than it. Arrow's share of an action's head; median over the
window."""

from lib import spans


def read(run):
    return spans.median_an_action(run, ("scan.arrow_read",), spans.union_ms)
