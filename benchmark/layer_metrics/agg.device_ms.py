"""Milliseconds of device time one traced action spends in the grouped
aggregate's own programs: update, merge and finalize, found by the names
the program gives its jitted functions (`agg_update`, `agg_merge`,
`agg_finalize`, and `agg_dense_update`, `agg_dense_merge` for the table
over dictionary codes; exec/aggregate.py). Whole programs do not nest or
overlap on a chip, so their seconds add up to the union of their
intervals; the sum over the traced actions over their number. Nothing
where no such program ran (an older program names them all `kernel`)."""

PROGRAMS = "agg_"


def device_seconds(run):
    """Device seconds of the aggregate's programs over the traced
    actions, or None."""
    if run.trace is None:
        return None
    found = [sec for name, sec, _runs in run.trace["device_programs"]
             if PROGRAMS in name]
    return sum(found) if found else None


def read(run):
    s = device_seconds(run)
    if s is None:
        return None
    return 1e3 * s / len(run.trace["action_s"])
