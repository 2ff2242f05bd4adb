# tpulint: stdout-protocol -- census CLI: stdout is the report
"""Attribute eager (non-jit) jax primitive dispatches and device_get calls
to engine call sites for one suite query on the CPU backend.

Usage: python tools/eager_census.py [suite] [qname] [sf]
Prints the top (primitive, caller-chain) pairs by count for the steady-state
iteration — each one is a dispatch of its own that a jitted program would
pipeline away.
"""
from __future__ import annotations

import collections
import os
import sys
import traceback

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from spark_rapids_tpu.utils import hostenv

hostenv.apply_cpu_env()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import importlib  # noqa: E402
import time  # noqa: E402

import spark_rapids_tpu as srt  # noqa: E402


def _engine_frames(limit=3):
    out = []
    for f in traceback.extract_stack():
        if "/spark_rapids_tpu/" in f.filename:
            out.append(f"{os.path.basename(f.filename)}:{f.lineno}")
    return tuple(out[-limit:])


def main():
    args = [a for a in sys.argv[1:] if not a.startswith("--")]
    suite = args[0] if args else "tpch"
    qname = args[1] if len(args) > 1 else "q7"
    sf = float(args[2]) if len(args) > 2 else 0.02

    qmod = importlib.import_module(f"spark_rapids_tpu.benchmarks.{suite}")
    session = srt.new_session()
    session.conf.set("rapids.tpu.sql.variableFloatAgg.enabled", True)
    tables = {k: v.cache() for k, v in
              qmod.gen_tables(session, sf=sf, num_partitions=4).items()}
    qfn = qmod.QUERIES[qname]
    qfn(tables).collect()  # warmup/compile
    qfn(tables).collect()

    from jax._src import dispatch as _dispatch

    eager = collections.Counter()
    orig_apply = _dispatch.apply_primitive

    def counting_apply(prim, *a, **kw):
        eager[(prim.name, _engine_frames())] += 1
        return orig_apply(prim, *a, **kw)

    _dispatch.apply_primitive = counting_apply

    getter = collections.Counter()
    orig_get = jax._src.api._device_get

    def counting_get(x):
        getter[_engine_frames()] += 1
        return orig_get(x)

    jax._src.api._device_get = counting_get

    t0 = time.perf_counter()
    qfn(tables).collect()
    dt = time.perf_counter() - t0
    _dispatch.apply_primitive = orig_apply
    jax._src.api._device_get = orig_get

    print(f"steady iter: {dt:.3f}s; eager primitives: "
          f"{sum(eager.values())}; device_get leaves: "
          f"{sum(getter.values())}", flush=True)
    print("\n== top eager-dispatch sites ==")
    for (prim, frames), n in eager.most_common(25):
        print(f"{n:6d}  {prim:<22} {' <- '.join(reversed(frames))}")
    print("\n== top device_get sites ==")
    for frames, n in getter.most_common(15):
        print(f"{n:6d}  {' <- '.join(reversed(frames))}")


if __name__ == "__main__":
    main()
