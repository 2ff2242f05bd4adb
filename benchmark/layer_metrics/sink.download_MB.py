"""Megabytes one action brings back through its download fences: the
`bytes` of its `DeviceToHost` spans (the device bytes of the columns
fetched); median over the window."""

from lib import spans


def read(run):
    return spans.median_an_action(run, ("DeviceToHost",),
                                  spans.attr_total("bytes", 1e-6), "bytes")
