"""Wall milliseconds of one action in which at least one task thread was
inside the scan's host work: a `scan.split` (opening the file, the schema
maps, which columns the device decodes), a `scan.read` (a column chunk's
bytes), a `scan.decode` (pages decompressed and parsed, the chunk
uploaded, run tables built, the decode programs issued), a
`scan.host_decode` (the columns Arrow decodes: on a TPU every DOUBLE) or a
`scan.upload` span. The union over threads, never thread time added up
(r1's mistake: 2.6 s for a 1.33 s action); median over the window."""

from lib import spans

STEPS = ("scan.split", "scan.read", "scan.decode", "scan.host_decode",
         "scan.upload")


def read(run):
    return spans.median_an_action(run, STEPS, spans.union_ms)
