"""Cached batches that had left the device and were uploaded again to be
served, added up over the window's actions: the program's
`cacheRestoredBatches`, read around every action (actions/q6_cached.py
`run`). 0, or the cell is measuring the spill store and not the cache.
Nothing where no action of the window carries the counter."""


def read(run):
    counts = [s.record.result.restored for s in run.samples
              if not s.error and hasattr(s.record.result, "restored")]
    return float(sum(counts)) if counts else None
