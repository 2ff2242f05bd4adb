"""Share of the HBM roofline of an action over a cached relation: the
least seconds the chip needs to read what the action cannot avoid
reading, over the seconds the device was busy in it
(operators.device_ms: every program of the action, since a cached action
has no scan, upload or decode on the device).

What it cannot avoid reading (`least_bytes`, kept here beside the reader:
it counts the same work whatever implements it): every row of the cached
relation once, 4 bytes for each column the action reads as it lies in HBM
(a DOUBLE is f32 on the chip, a DATE 4 bytes); `q6_cached`: four columns,
16 bytes a row, 960 MB an action. No validity bytes, no intermediates, no
partials: a floor on the traffic, so the share cannot pass 100%."""

from lib import harness, loop

BYTES_A_VALUE = 4   # f32 and date alike


def least_bytes(run) -> int:
    columns = harness.load_module("actions", run.cell["action"]).COLUMNS
    return BYTES_A_VALUE * run.rows_per_action * sum(
        len(cols) for cols in columns.values())


def read(run):
    if run.trace is None or not run.trace["action_busy_s"]:
        return None
    busy_an_action = loop.median(run.trace["action_busy_s"])
    if not busy_an_action:
        return None
    return 100.0 * least_bytes(run) / run.peaks["hbm_bytes_per_s"] \
        / busy_an_action
