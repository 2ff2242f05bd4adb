"""Share of the HBM roofline of the stream compaction: the least seconds
the chip needs to move what making one action's survivors dense cannot
avoid moving, over the seconds its programs were busy
(compact.device_ms).

What it cannot avoid (`least_bytes`, kept here beside the reader: it
counts the same work whatever implements it): every input row read once
and every surviving row written once, at the narrowest the
configuration's precision allows a column: 4 bytes for a fixed-width
column (a DOUBLE is f32 on the chip, a DATE 4 bytes) and 1 byte for a
STRING column's dictionary code (what a dictionary of up to 256 values
needs; the flags' have 3 and 2). `lineitem_write7`: five values and two
codes, 22 bytes a row, 6,000,000 rows in and 97-98% out: 261 MB an
action. No validity, no indices, no keep mask, no second pass: a floor on
what any implementation moves, so the share cannot pass 100%. Bound by
bytes: a compaction computes nothing.

The surviving rows are counted from the footers of the last directory
the window wrote (the harness removes it after the readers ran)."""

from lib import harness, written

BYTES = {"string": 1}   # a dictionary code; every other type: 4
BYTES_OTHERWISE = 4


def row_bytes(run) -> int:
    columns = harness.load_module("actions", run.cell["action"]).COLUMNS
    return sum(BYTES.get(run.config["schema"][table][c], BYTES_OTHERWISE)
               for table, cols in columns.items() for c in cols)


def rows_out(run):
    """Rows of the last action's files, from their footers; None where
    no action wrote any."""
    import pyarrow.parquet as pq

    done = [s.record.result for s in run.samples if not s.error]
    found = written.files(done[-1]) if done and isinstance(done[-1], str) \
        else []
    if not found:
        return None
    return sum(pq.ParquetFile(f).metadata.num_rows for f in found)


def least_bytes(run, survivors: int) -> int:
    return row_bytes(run) * (run.rows_per_action + survivors)


def read(run):
    busy_s = harness.load_module("layer_metrics",
                                 "compact.device_ms").device_seconds(run)
    survivors = rows_out(run) if busy_s else None
    if survivors is None:
        return None
    busy_an_action = busy_s / len(run.trace["action_s"])
    return 100.0 * least_bytes(run, survivors) \
        / run.peaks["hbm_bytes_per_s"] / busy_an_action
