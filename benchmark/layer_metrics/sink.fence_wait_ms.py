"""Wall milliseconds of one action in which a fence waited for the
device to finish what the pack program and everything before it had
queued: the union of its `sink.wait` spans (children of `DeviceToHost`;
`block_until_ready` on the packed arrays, which a traced query alone
calls). Median over the window."""

from lib import spans


def read(run):
    return spans.median_an_action(run, ("sink.wait",), spans.union_ms)
