"""Wall milliseconds of one action in which the host stood at a download
fence: the union of its `DeviceToHost` spans (the device's pending work
finishes, then the grouped transfer); median over the window."""

from lib import spans


def read(run):
    return spans.median_an_action(run, ("DeviceToHost",), spans.union_ms)
