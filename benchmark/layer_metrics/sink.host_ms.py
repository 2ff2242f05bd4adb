"""Wall milliseconds of one write action in which at least one task thread
was in the writer's host part: `write.arrow` (host columns to an Arrow
table) or `write.encode` (the device encoder's page loop), `write.file`
(Arrow's encode + compress + file I/O, or the device path's compress and
write) and `write.commit`. The union over threads; median over the
window."""

from lib import spans


def read(run):
    return spans.median_an_action(
        run, ("write.arrow", "write.encode", "write.file", "write.commit"),
        spans.union_ms)
