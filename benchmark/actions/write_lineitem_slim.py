"""Read, transform, write: every row of lineitem, the four columns Q6 reads
and two computed from them, written with DataFrame.write.parquet into a
directory no earlier write has touched.

There is no filter: a filter that keeps nearly every row (Q1's date keeps
96%) puts a gather of the survivors in front of the sink, and on the chip
that gather took the largest share of the action, 1.5 s of 3.5 s (PERF.md,
section 6, PR 24), which is not what a write cell is for. The columns are
Q6's because the decode programs of those four do
not depend on the seed's data (PERF.md, section 5): lineitem's small-
dictionary strings hold RLE runs whose count does, and a run at a new seed
then compiles for 400 s before it can measure anything.

What is compared: every written directory's row count, from its footers,
and for the first and the last directory of the window a digest of the
files as Arrow's reader (not the device decoder) gives them back: per
weekday of l_shipdate the row count, the sum of every written column and
one cross term, against the same digest of the generated arrays, both by
pandas."""

import numpy as np

from lib import frames, written

written_bytes = written.written_bytes  # the harness reads it for the roofline
COLUMNS = {"lineitem": ("l_shipdate", "l_quantity", "l_extendedprice",
                        "l_discount")}
FLOATS = ("l_quantity", "l_extendedprice", "l_discount")


def build(tables):
    from spark_rapids_tpu.plan import functions as F

    price, disc = F.col("l_extendedprice"), F.col("l_discount")
    return tables["lineitem"].select(
        F.col("l_shipdate"), F.col("l_quantity"), price, disc,
        (price * disc).alias("revenue"),
        (price * (F.lit(1.0) - disc)).alias("disc_price"))


def run(df, out_dir):
    """Returns when write.parquet has: every file is closed by then (the
    engine's flush policy as it stands: closed, not fsynced)."""
    df.write.parquet(out_dir)
    return out_dir


def digest(frame):
    frame = frame.assign(weekday=frame["l_shipdate"] % 7,
                         x=frame["l_quantity"] * frame["disc_price"])
    g = frame.groupby("weekday", sort=True)
    return frames.rows(g.agg(
        n=("l_quantity", "size"), s_date=("l_shipdate", "sum"),
        s_qty=("l_quantity", "sum"), s_price=("l_extendedprice", "sum"),
        s_disc=("l_discount", "sum"), s_revenue=("revenue", "sum"),
        s_disc_price=("disc_price", "sum"), s_x=("x", "sum")).reset_index())


def reference(arrays, dtype=np.float64):
    """float64, or, for a control, the float columns and the arithmetic in
    `dtype`; the digest's own sums are float64 either way."""
    li = frames.frame(arrays, "lineitem", COLUMNS["lineitem"])
    low = {c: li[c].to_numpy().astype(dtype) for c in FLOATS}
    low["revenue"] = low["l_extendedprice"] * low["l_discount"]
    low["disc_price"] = low["l_extendedprice"] * (
        np.asarray(1.0, dtype) - low["l_discount"])
    li = li.assign(**{c: v.astype(np.float64) for c, v in low.items()})
    li["l_shipdate"] = li["l_shipdate"].astype(np.int64)
    return {"rows": len(li), "digest": digest(li)}


def read_back(files):
    import pyarrow as pa
    import pyarrow.parquet as pq

    table = pq.read_table(files)
    at = table.schema.get_field_index("l_shipdate")
    table = table.set_column(at, "l_shipdate",
                             table.column(at).cast(pa.int32()).cast(pa.int64()))
    return table.to_pandas()


def compare(expected, results):
    return written.compare_dirs(expected, results,
                                lambda files: digest(read_back(files)))
