# tpulint: stdout-protocol -- profiler CLI: stdout is the report
"""Profile one suite query through the engine (CPU backend).

Usage: python tools/profile_query.py [suite] [qname] [sf] [--oracle]
Prints wall-clock for warmup + 2 timed iters, a cProfile top-40 by
cumulative time for the steady-state iteration, and engine dispatch
counters (jit-cache hits/misses, device syncs) when available.
"""
from __future__ import annotations

import cProfile
import io
import os
import pstats
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from spark_rapids_tpu.utils import hostenv

hostenv.apply_cpu_env()

import importlib  # noqa: E402

import spark_rapids_tpu as srt  # noqa: E402


def main():
    args = [a for a in sys.argv[1:] if not a.startswith("--")]
    suite = args[0] if len(args) > 0 else "tpch"
    qname = args[1] if len(args) > 1 else "q8"
    sf = float(args[2]) if len(args) > 2 else 0.02
    oracle = "--oracle" in sys.argv

    qmod = importlib.import_module(f"spark_rapids_tpu.benchmarks.{suite}")
    session = srt.new_session()
    session.conf.set("rapids.tpu.sql.variableFloatAgg.enabled", True)
    session.conf.set("rapids.tpu.sql.enabled", not oracle)
    tables = {k: v.cache() for k, v in
              qmod.gen_tables(session, sf=sf, num_partitions=4).items()}
    qfn = qmod.QUERIES[qname]

    t0 = time.perf_counter()
    qfn(tables).collect()
    print(f"warmup (compile): {time.perf_counter() - t0:.3f}s", flush=True)

    t0 = time.perf_counter()
    qfn(tables).collect()
    print(f"iter 1: {time.perf_counter() - t0:.3f}s", flush=True)

    pr = cProfile.Profile()
    t0 = time.perf_counter()
    pr.enable()
    qfn(tables).collect()
    pr.disable()
    print(f"iter 2 (profiled): {time.perf_counter() - t0:.3f}s", flush=True)

    s = io.StringIO()
    ps = pstats.Stats(pr, stream=s).sort_stats("cumulative")
    ps.print_stats(45)
    print(s.getvalue())
    s = io.StringIO()
    ps = pstats.Stats(pr, stream=s).sort_stats("tottime")
    ps.print_stats(25)
    print(s.getvalue())


if __name__ == "__main__":
    main()
