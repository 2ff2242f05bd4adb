"""Milliseconds of device time one traced action spends making a filter's
survivors dense: the programs whose name holds `compact`, the plan (the
order of the kept rows: `_compact_plan`) and the gather a column and a
validity through it (`_compact_gather_fixed_cols`; columnar/batch.py).
Whole programs do not nest or overlap on a chip, so their seconds add up;
the sum over the traced actions over their number. Nothing where no such
program ran (no filter in front of a sink, or an older program, which
gathers under `_gather_fixed_cols`, a name a sort's permutation shares)."""

PROGRAMS = "compact"


def device_seconds(run):
    """Device seconds of the compaction's programs over the traced
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
