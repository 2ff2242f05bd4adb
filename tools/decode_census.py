# tpulint: stdout-protocol -- census CLI: stdout is the report
"""Dispatch census of the parquet device-decode bench query (bench.py
--decode shape): 4M rows x 3 int cols, snappy v1 dictionary pages, 8 row
groups. Attributes the device tier's measured 12x loss to host decode
(BENCH_DECODE_r04.json) to eager ops / syncs / uploads / launches.

Usage: python tools/decode_census.py [dev|host]
"""
from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import tools.dispatch_census as DC

DC._patch()

import numpy as np  # noqa: E402

import spark_rapids_tpu as srt  # noqa: E402
from spark_rapids_tpu.plan import functions as F  # noqa: E402

mode = sys.argv[1] if len(sys.argv) > 1 else "dev"
n = 4 << 20
rng = np.random.default_rng(7)
path = "/tmp/srt_decode_bench_snappy.parquet"
if not os.path.exists(path):
    import pyarrow as pa
    import pyarrow.parquet as pq

    t = pa.table({
        "a": pa.array(rng.integers(0, 1000, n).astype(np.int64)),
        "b": pa.array(rng.integers(0, 50, n).astype(np.int64)),
        "c": pa.array(rng.integers(0, 200, n).astype(np.int32)),
    })
    pq.write_table(t, path, compression="SNAPPY", use_dictionary=True,
                   data_page_version="1.0", row_group_size=1 << 19)
session = srt.new_session()
session.conf.set("rapids.tpu.sql.enabled", True)
session.conf.set(
    "rapids.tpu.sql.format.parquet.deviceDecode.enabled", mode == "dev")


def q():
    return session.read.parquet(path).agg(
        F.sum("a").alias("sa"), F.sum("b").alias("sb"),
        F.sum("c").alias("sc")).collect()


q()
q()
DC.ENABLED = True
t0 = time.perf_counter()
q()
wall = time.perf_counter() - t0
DC.ENABLED = False

n_eager = sum(DC.EAGER.values())
n_sync = sum(DC.SYNC.values())
n_up = sum(DC.UPLOAD.values())
n_jit = sum(DC.JITCALL.values())
print(f"\n=== decode[{mode}] steady iter {wall:.3f}s (cpu) ===")
print(f"eager={n_eager} sync={n_sync} upload={n_up} jit_calls={n_jit}")
for name, ctr in (("eager", DC.EAGER), ("sync", DC.SYNC),
                  ("upload", DC.UPLOAD), ("jit", DC.JITCALL)):
    print(f"-- {name} (top 12) --")
    for key, c in ctr.most_common(12):
        print(f"{c:6d}  {key}")
