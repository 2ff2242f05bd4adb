"""Wall milliseconds of one action in which a fence copied its packed
buffers to the host: the union of its `sink.transfer` spans (children of
`DeviceToHost`: `jax.device_get` and the numpy views). With
`sink.download_MB` beside it, the fence's GB/s. Median over the
window."""

from lib import spans


def read(run):
    return spans.median_an_action(run, ("sink.transfer",), spans.union_ms)
