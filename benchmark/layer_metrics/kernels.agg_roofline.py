"""Share of the HBM roofline of the grouped aggregate: the least seconds
the chip needs to read what the aggregate of one action cannot avoid
reading, over the seconds its programs were busy (agg.device_ms).

What it cannot avoid reading (`least_bytes`, kept here beside the reader:
it counts the same work whatever implements it): every row of the scanned
tables once, 4 bytes for each fixed-width column the action reads (a
DOUBLE is f32 on the chip, a DATE 4 bytes) and 4 bytes for each STRING
column's dictionary code as it lies on the device; `q1_agg`: five values
and two codes, 28 bytes a row. No validity bytes, no intermediates, no
second pass, no partials: a lower bound on the traffic, so the share
cannot pass 100%. Bound by bytes: the reductions are a few operations a
byte."""

from lib import harness

BYTES_A_VALUE = 4   # f32, date, and an int32 dictionary code alike


def least_bytes(run) -> int:
    columns = harness.load_module("actions", run.cell["action"]).COLUMNS
    return BYTES_A_VALUE * run.rows_per_action * sum(
        len(cols) for cols in columns.values())


def read(run):
    busy_s = harness.load_module("layer_metrics",
                                 "agg.device_ms").device_seconds(run)
    if not busy_s:
        return None
    busy_an_action = busy_s / len(run.trace["action_s"])
    return 100.0 * least_bytes(run) / run.peaks["hbm_bytes_per_s"] \
        / busy_an_action
