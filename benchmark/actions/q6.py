"""TPC-H Q6 over lineitem: three range predicates on three columns, one sum
of a product. Parameters fixed: 1994, discount 0.06 +- 0.01, quantity < 24.
The DataFrame program is the one the program's own TPC-H-like suite writes
(spark_rapids_tpu/benchmarks/tpch.py `q6`), copied."""

import numpy as np

from lib import compare as C
from lib.tpch_gen import days

COLUMNS = {"lineitem": ("l_shipdate", "l_discount", "l_quantity",
                        "l_extendedprice")}


def build(tables):
    from spark_rapids_tpu.plan import functions as F

    from lib.dataframe import date_lit

    li = tables["lineitem"]
    return (li.filter((li["l_shipdate"] >= date_lit("1994-01-01"))
                      & (li["l_shipdate"] < date_lit("1995-01-01"))
                      & (li["l_discount"] >= F.lit(0.05))
                      & (li["l_discount"] <= F.lit(0.07))
                      & (li["l_quantity"] < F.lit(24.0)))
            .withColumn("revenue",
                        F.col("l_extendedprice") * F.col("l_discount"))
            .agg(F.sum("revenue").alias("revenue")))


def run(df, out_dir):
    return df.collect()


def reference(arrays, dtype=np.float64):
    """numpy over the generated arrays; `dtype` is what the prices are
    computed in (the control of tests/test_control.py passes bfloat16).
    The predicates are on exact values either way, as a lower-precision
    engine would still read the dictionary codes exactly."""
    li, _ = arrays["lineitem"]
    keep = ((li["l_shipdate"] >= days("1994-01-01"))
            & (li["l_shipdate"] < days("1995-01-01"))
            & (li["l_discount"] >= 0.05) & (li["l_discount"] <= 0.07)
            & (li["l_quantity"] < 24.0))
    price = li["l_extendedprice"][keep].astype(dtype)
    disc = li["l_discount"][keep].astype(dtype)
    # the product in `dtype`, the sum in float32 at the least: a sum of
    # 1e5 terms in bfloat16 stalls, and no engine would do that
    wide = np.float64 if dtype == np.float64 else np.float32
    return [(float((price * disc).astype(wide).sum(dtype=wide)),)]


def compare(expected, results):
    return [C.rows(expected, got, "q6") for got in results]
