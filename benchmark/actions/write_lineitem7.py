"""A filtered extract with its strings: the rows of lineitem that TPC-H
Q1's predicate keeps (DELTA = 90: l_shipdate <= 1998-09-02, 97-98% of
them) and the seven columns Q1 reads, two of them strings, written with
DataFrame.write.parquet into a directory no earlier write has touched.

The filter's survivors go to a sink, not into an aggregate, so the engine
makes them dense on the device (its `filter.compact`), and the two flag
columns come through the scan as dictionary codes and are to reach
Arrow's writer so. The program's parent runs the same DataFrame program
(its sink expands each flag column a row at a time in Python: an action
of seconds, measured in PERF.md section 6, PR 40), so nothing here asks
what the checkout holds.

What is compared: every written directory's row count, from its footers,
and for the first and the last directory of the window a digest of the
files as Arrow's reader gives them back: per (l_returnflag, l_linestatus,
weekday of l_shipdate) the row count, the largest l_shipdate, the sum of
every numeric column and one cross term, against the same digest of the
generated arrays, both by pandas. A row too many or one past the date
moves a count or a maximum; a flag on the wrong row moves two groups."""

import numpy as np

from lib import frames, written
from lib.tpch_gen import days

written_bytes = written.written_bytes  # the harness reads it for the roofline
COLUMNS = {"lineitem": ("l_quantity", "l_extendedprice", "l_discount",
                        "l_tax", "l_shipdate", "l_returnflag",
                        "l_linestatus")}   # actions/q1.py's seven
FLOATS = ("l_quantity", "l_extendedprice", "l_discount", "l_tax")
SHIPPED_BY = "1998-09-02"


def build(tables):
    from lib.dataframe import date_lit

    li = tables["lineitem"]
    return (li.filter(li["l_shipdate"] <= date_lit(SHIPPED_BY))
            .select(*COLUMNS["lineitem"]))


def run(df, out_dir):
    """Returns when write.parquet has: every file is closed by then (the
    engine's flush policy as it stands: closed, not fsynced)."""
    df.write.parquet(out_dir)
    return out_dir


def digest(frame):
    frame = frame.assign(weekday=frame["l_shipdate"] % 7,
                         x=frame["l_quantity"] * frame["l_extendedprice"])
    g = frame.groupby(["l_returnflag", "l_linestatus", "weekday"], sort=True)
    return frames.rows(g.agg(
        n=("l_quantity", "size"), last_date=("l_shipdate", "max"),
        s_date=("l_shipdate", "sum"), s_qty=("l_quantity", "sum"),
        s_price=("l_extendedprice", "sum"), s_disc=("l_discount", "sum"),
        s_tax=("l_tax", "sum"), s_x=("x", "sum")).reset_index())


def reference(arrays, dtype=np.float64):
    """pandas over the generated arrays, nothing of the engine: the
    filter, the seven columns, the digest. float64, or, for a control,
    the four measures held in `dtype` (the extract computes nothing: what
    a lower precision would lose is the values themselves); the digest's
    own sums are float64 either way. The predicate, the dates and the
    strings are exact."""
    li = frames.frame(arrays, "lineitem", COLUMNS["lineitem"])
    li = li[li["l_shipdate"] <= days(SHIPPED_BY)]
    li = li.assign(**{c: li[c].to_numpy().astype(dtype).astype(np.float64)
                      for c in FLOATS})
    li["l_shipdate"] = li["l_shipdate"].astype(np.int64)
    return {"rows": len(li), "digest": digest(li)}


def read_back(files):
    import pyarrow as pa
    import pyarrow.parquet as pq

    table = pq.read_table(files)
    at = table.schema.get_field_index("l_shipdate")
    table = table.set_column(at, "l_shipdate",
                             table.column(at).cast(pa.int32()).cast(pa.int64()))
    # plain Python strings, whatever the file's Arrow schema says
    return table.to_pandas(strings_to_categorical=False).astype(
        {"l_returnflag": object, "l_linestatus": object})


def compare(expected, results):
    return written.compare_dirs(expected, results,
                                lambda files: digest(read_back(files)))
