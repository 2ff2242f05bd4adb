"""TPC-H Q1, the pricing summary report, over lineitem: one date
predicate that keeps nearly every row, two string grouping keys, four sums
(two over computed columns), three averages and a count, ordered by the
keys. Parameter fixed: DELTA = 90, so l_shipdate <= 1998-09-02. The
DataFrame program is the one the program's own TPC-H-like suite writes
(spark_rapids_tpu/benchmarks/tpch.py `q1`), copied.

The cell needs a program that holds a grouped aggregate a new seed does not
compile for and a scan that does not compile a program a string chunk
(PR 37). On an older program the action refuses at once: run there, one
action is 15 s and a new seed 380 s of compiling (PERF.md section 6), and
a run would be cut, not measured."""

import os

import numpy as np

from lib import compare as C
from lib import harness
from lib.tpch_gen import days

# asked of the checkout's files, not by importing the program: the
# reference and tools/control.py need nothing of it
DENSE_AGG = os.path.join("spark_rapids_tpu", "exec", "dense_agg.py")
if not os.path.isfile(os.path.join(harness.ROOT, DENSE_AGG)):
    raise harness.BenchFailure(
        "actions/q1.py: this program has no dense grouped aggregate "
        f"({DENSE_AGG}, PR 37)")

COLUMNS = {"lineitem": ("l_quantity", "l_extendedprice", "l_discount",
                        "l_tax", "l_shipdate", "l_returnflag",
                        "l_linestatus")}
SHIPPED_BY = "1998-09-02"


def build(tables):
    from spark_rapids_tpu.plan import functions as F

    from lib.dataframe import date_lit

    li = tables["lineitem"]
    return (li.filter(li["l_shipdate"] <= date_lit(SHIPPED_BY))
            .withColumn("disc_price",
                        F.col("l_extendedprice")
                        * (F.lit(1.0) - F.col("l_discount")))
            .withColumn("charge",
                        F.col("l_extendedprice")
                        * (F.lit(1.0) - F.col("l_discount"))
                        * (F.lit(1.0) + F.col("l_tax")))
            .groupBy("l_returnflag", "l_linestatus")
            .agg(F.sum("l_quantity").alias("sum_qty"),
                 F.sum("l_extendedprice").alias("sum_base_price"),
                 F.sum("disc_price").alias("sum_disc_price"),
                 F.sum("charge").alias("sum_charge"),
                 F.avg("l_quantity").alias("avg_qty"),
                 F.avg("l_extendedprice").alias("avg_price"),
                 F.avg("l_discount").alias("avg_disc"),
                 F.count("*").alias("count_order"))
            .orderBy("l_returnflag", "l_linestatus"))


class Rows(list):
    """An action's rows, with the program's two aggregate-path counters
    read around the action (one client: the difference of two readings is
    one action's), for layer_metrics/agg.dense_share.py."""

    agg_batches = (0, 0)   # (dense, sort)


def run(df, out_dir):
    from spark_rapids_tpu.utils import metrics as M

    before = (M.dense_agg_batch_count(), M.sort_agg_batch_count())
    rows = Rows(df.collect())
    rows.agg_batches = (M.dense_agg_batch_count() - before[0],
                        M.sort_agg_batch_count() - before[1])
    return rows


def reference(arrays, dtype=np.float64):
    """numpy over the generated arrays, nothing of the engine. `dtype` is
    what the measures and the two computed columns are held in (the
    control of tests/test_control.py passes bfloat16); the sums are in
    float64, or in float32 under a lower `dtype` (a sum of a million terms
    in bfloat16 stalls, and no engine would do that). The predicate and
    the keys are exact either way."""
    li, _ = arrays["lineitem"]
    keep = li["l_shipdate"] <= days(SHIPPED_BY)
    flag = li["l_returnflag"].dictionary_encode()
    status = li["l_linestatus"].dictionary_encode()
    flags = flag.dictionary.to_pylist()
    statuses = status.dictionary.to_pylist()
    group = (flag.indices.to_numpy().astype(np.int64) * len(statuses)
             + status.indices.to_numpy())[keep]
    wide = np.float64 if dtype == np.float64 else np.float32
    one = np.asarray(1.0, dtype)
    qty = li["l_quantity"][keep].astype(dtype)
    price = li["l_extendedprice"][keep].astype(dtype)
    disc = li["l_discount"][keep].astype(dtype)
    tax = li["l_tax"][keep].astype(dtype)
    disc_price = price * (one - disc)
    charge = disc_price * (one + tax)
    rows = []
    for g in np.unique(group):
        of = group == g
        n = int(of.sum())

        def total(x):
            return float(x[of].astype(wide).sum(dtype=wide))

        rows.append((flags[g // len(statuses)], statuses[g % len(statuses)],
                     total(qty), total(price), total(disc_price),
                     total(charge), total(qty) / n, total(price) / n,
                     total(disc) / n, n))
    return sorted(rows, key=lambda r: (r[0].encode(), r[1].encode()))


def compare(expected, results):
    return [C.rows(expected, got, "q1") for got in results]
