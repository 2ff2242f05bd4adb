"""Percent of the window's grouped-aggregate update batches that took the
table over dictionary codes (exec/dense_agg.py) and not the sort: the
program's `denseAggBatches` over `denseAggBatches` + `sortAggBatches`,
each read around every action (actions/q1.py `run`, as
harness.counter_readers reads its five). 100 in `q1_agg`: the guard
against a silent return to the sort. Nothing where no action of the
window carries the counters or none updated a grouped aggregate."""


def read(run):
    dense = sort = 0
    for s in run.samples:
        if s.error:
            continue
        d, t = getattr(s.record.result, "agg_batches", (0, 0))
        dense, sort = dense + d, sort + t
    if not dense + sort:
        return None
    return 100.0 * dense / (dense + sort)
