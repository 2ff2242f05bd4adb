"""Milliseconds one action's tasks spent being handed their cached
batches: the durations of its `cache.serve` spans (one a batch, around
the spill framework's `fetch_device_batch`) added up over the tasks;
median over the window. Thread time, not wall time: the tasks run side
by side. A few microseconds a batch while every batch is on the device;
milliseconds where one had to be uploaded again (`restored`). Nothing
where no action has the span."""

from lib import spans


def read(run):
    return spans.median_an_action(run, ("cache.serve",), spans.total_ms)
