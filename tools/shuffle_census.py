# tpulint: stdout-protocol -- census CLI: stdout is the report
"""Dispatch census of the shuffle bench query (bench.py --shuffle shape):
hash-repartition 4M rows from 8 map partitions into 16 targets, then
count(*). Reports eager ops / syncs / jit calls per steady-state iteration
plus the number of DISTINCT compiled programs the iteration touches (shape
churn -> recompiles is the prime suspect for the device
tier losing to its serialized fallback, BENCH_SHUFFLE_r04.json).

Usage: python tools/shuffle_census.py [dev|ser]
"""
from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import tools.dispatch_census as DC

DC._patch()

import numpy as np  # noqa: E402
import jax  # noqa: E402

import spark_rapids_tpu as srt  # noqa: E402
from spark_rapids_tpu.plan import functions as F  # noqa: E402

mode = sys.argv[1] if len(sys.argv) > 1 else "dev"
n = 1 << 22
rng = np.random.default_rng(3)
session = srt.new_session()
session.conf.set("rapids.tpu.sql.enabled", True)
if mode == "ser":
    session.conf.set("rapids.tpu.shuffle.serialize.enabled", True)
df = session.createDataFrame(
    {"k": rng.integers(0, 1 << 30, n).astype(np.int64),
     "v": rng.integers(-10_000, 10_000, n).astype(np.int64),
     "f": rng.random(n).astype(np.float32)},
    [("k", "long"), ("v", "long"), ("f", "float")],
    num_partitions=8).cache()


def q():
    return df.repartition(16, F.col("k")).agg(
        F.count("*").alias("n")).collect()


assert q()[0][0] == n
q()

# count distinct executables: every compile logs via jax's compile cache
compiles = [0]
orig = jax._src.interpreters.pxla.MeshExecutable  # probe only

from jax._src import monitoring  # noqa: E402


def _ev(event: str, **kw):
    if "compile" in event:
        compiles[0] += 1


monitoring.register_event_listener(
    lambda event, **kw: _ev(event))

DC.ENABLED = True
t0 = time.perf_counter()
q()
wall = time.perf_counter() - t0
DC.ENABLED = False

n_eager = sum(DC.EAGER.values())
n_sync = sum(DC.SYNC.values())
n_jit = sum(DC.JITCALL.values())
print(f"\n=== shuffle[{mode}] steady iter {wall:.3f}s (cpu) ===")
print(f"eager={n_eager} sync={n_sync} jit_calls={n_jit} "
      f"steady-state-compiles={compiles[0]}")
print("-- eager (top 15) --")
for (site, prim), c in DC.EAGER.most_common(15):
    print(f"{c:6d}  {site}  [{prim}]")
print("-- sync (top 15) --")
for site, c in DC.SYNC.most_common(15):
    print(f"{c:6d}  {site}")
print("-- jit calls (top 10) --")
for site, c in DC.JITCALL.most_common(10):
    print(f"{c:6d}  {site}")
