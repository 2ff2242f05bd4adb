"""Megabytes one action uploads for its scan: the `bytes` of its
`scan.upload` spans, which are the decompressed column chunks the decode
programs are given and the columns Arrow decoded on the host, at their
device width (beside `scanned_bytes`, the compressed bytes of the same
chunks from the footers, which kernels.hbm_roofline divides by); median
over the window."""

from lib import spans


def read(run):
    return spans.median_an_action(run, ("scan.upload",),
                                  spans.attr_total("bytes", 1e-6), "bytes")
