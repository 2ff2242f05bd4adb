# tpulint: stdout-protocol -- census CLI: stdout is the report
"""Attribute eager jax dispatches / host syncs / uploads to repo call sites.

Runs one suite query on the CPU backend (dispatch counts are
backend-invariant), then prints a per-call-site census of the steady-state iteration so the
glue that would dominate on-chip wall-clock can be jitted/batched away.

Usage: python tools/dispatch_census.py [suite] [qname] [sf]
"""
from __future__ import annotations

import collections
import os
import sys
import time
import traceback

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from spark_rapids_tpu.utils import hostenv

hostenv.apply_cpu_env()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import importlib  # noqa: E402

import spark_rapids_tpu as srt  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

EAGER = collections.Counter()
SYNC = collections.Counter()
UPLOAD = collections.Counter()
JITCALL = collections.Counter()
ENABLED = False


DEEP = int(os.environ.get("CENSUS_DEPTH", "1"))


def _site() -> str:
    # topmost frame(s) inside spark_rapids_tpu (skip tools/, jax, stdlib)
    frames = []
    for fr in traceback.extract_stack()[::-1]:
        fn = fr.filename
        if "spark_rapids_tpu" in fn and "/tools/" not in fn:
            frames.append(f"{os.path.relpath(fn, REPO)}:{fr.lineno}")
            if len(frames) >= DEEP:
                break
    return " < ".join(frames) if frames else "<outside-repo>"


def _patch():
    # EvalTrace.process_primitive is the single choke point every EAGER
    # primitive execution funnels through (patching the dispatch module's
    # apply_primitive attribute would miss most of them: each primitive
    # captured a partial-bound reference at def_impl time)
    from jax._src import core as jcore

    orig_pp = jcore.EvalTrace.process_primitive

    def process_primitive(self, primitive, args, params):
        if ENABLED:
            EAGER[(_site(), primitive.name)] += 1
        return orig_pp(self, primitive, args, params)

    jcore.EvalTrace.process_primitive = process_primitive

    from jax._src import array as jarray

    orig_value = jarray.ArrayImpl._value.fget

    def _value(self):
        if ENABLED and self._npy_value is None:
            SYNC[_site()] += 1
        return orig_value(self)

    jarray.ArrayImpl._value = property(_value)

    orig_put = jax.device_put

    def device_put(x, *a, **k):
        if ENABLED:
            UPLOAD[_site()] += 1
        return orig_put(x, *a, **k)

    jax.device_put = device_put

    from spark_rapids_tpu.engine import jit_cache

    orig_call = jit_cache._SaltPinnedKernel.__call__

    def jcall(self, *a, **k):
        if ENABLED:
            JITCALL[_site()] += 1
        return orig_call(self, *a, **k)

    jit_cache._SaltPinnedKernel.__call__ = jcall


def main():
    global ENABLED
    args = [a for a in sys.argv[1:] if not a.startswith("--")]
    suite = args[0] if args else "tpch"
    qname = args[1] if len(args) > 1 else "q1"
    sf = float(args[2]) if len(args) > 2 else 0.1

    _patch()
    qmod = importlib.import_module(f"spark_rapids_tpu.benchmarks.{suite}")
    session = srt.new_session()
    session.conf.set("rapids.tpu.sql.variableFloatAgg.enabled", True)
    tables = {k: v.cache() for k, v in
              qmod.gen_tables(session, sf=sf, num_partitions=4).items()}
    qfn = qmod.QUERIES[qname]

    qfn(tables).collect()   # warmup/compile
    qfn(tables).collect()   # settle caches

    ENABLED = True
    t0 = time.perf_counter()
    qfn(tables).collect()
    wall = time.perf_counter() - t0
    ENABLED = False

    n_eager = sum(EAGER.values())
    n_sync = sum(SYNC.values())
    n_up = sum(UPLOAD.values())
    n_jit = sum(JITCALL.values())
    print(f"\n=== {suite} {qname} sf={sf}: steady-state iter {wall:.3f}s "
          f"(cpu) ===")
    print(f"eager={n_eager} sync={n_sync} upload={n_up} "
          f"jit_calls={n_jit}\n")
    print("-- eager dispatch sites (top 30) --")
    for (site, prim), c in EAGER.most_common(30):
        print(f"{c:6d}  {site}  [{prim}]")
    print("\n-- host-sync sites (top 20) --")
    for site, c in SYNC.most_common(20):
        print(f"{c:6d}  {site}")
    print("\n-- upload sites (top 15) --")
    for site, c in UPLOAD.most_common(15):
        print(f"{c:6d}  {site}")
    print("\n-- jit-cache call sites (top 15) --")
    for site, c in JITCALL.most_common(15):
        print(f"{c:6d}  {site}")


if __name__ == "__main__":
    main()
