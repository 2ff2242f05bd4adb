"""Share of the HBM roofline: the least seconds the chip needs for one
action over the seconds it was busy in it (operators.device_ms).

The least seconds are the bytes the action cannot avoid moving through
HBM over the device_kind's peak in lib/peaks.json: the compressed size of
the column chunks it reads, from the parquet footers, plus, for a write,
the bytes of the files it wrote. Decoded columns, intermediates and every
re-read are left out, so the byte count is a lower bound on the traffic
and the share cannot pass 100%."""

from lib import loop


def read(run):
    busy_s = loop.median(run.trace["action_busy_s"]) if run.trace else 0
    if not busy_s:
        return None
    least_s = (run.scanned_bytes + run.written_bytes) \
        / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / busy_s
