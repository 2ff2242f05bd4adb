"""TPC-H Q6 (actions/q6.py's DataFrame program, parameters fixed: 1994,
discount 0.06 +- 0.01, quantity < 24) over a relation held on the device:
the seven report columns of lineitem, `select(*CACHED).cache()`. The first
`run` of a process materialises the relation (it reads the files once);
every later one is served from HBM.

Beside the rows an action carries what the program counted of its cache
around it (one client: the difference of two readings is one action's),
and `compare` holds every action to the configuration's guarantee: each
cached batch served once, none of them brought back from a spill tier,
and in a traced run no span of the scan in the action's tree.

The cell needs a program that scans only the selected columns under a
cache and counts what its cached scan serves (PR 45). On an older program
the action refuses at once: there `select(...).cache()` reads all sixteen
columns, the device decoder refuses l_comment's chunks and every split is
decoded on the host (50 s a split of 80 on the chip's host, PERF.md
section 6), so a run at this scale would be cut, not measured."""

import os

import numpy as np

from lib import compare as C
from lib import harness
from lib.tpch_gen import days

# asked of the checkout's files, not by importing the program: the
# reference and tools/control.py need nothing of it
CACHED_SCAN = os.path.join("spark_rapids_tpu", "exec", "cache.py")
SERVE_SPAN = "cache.serve"


def _program_serves_a_cache() -> bool:
    try:
        with open(os.path.join(harness.ROOT, CACHED_SCAN)) as f:
            return SERVE_SPAN in f.read()
    except OSError:
        return False


if not _program_serves_a_cache():
    raise harness.BenchFailure(
        "actions/q6_cached.py: this program's cached scan counts nothing "
        f"it serves ({CACHED_SCAN} has no {SERVE_SPAN!r}, PR 45)")

COLUMNS = {"lineitem": ("l_shipdate", "l_discount", "l_quantity",
                        "l_extendedprice")}
CACHED = ("l_quantity", "l_extendedprice", "l_discount", "l_tax",
          "l_shipdate", "l_returnflag", "l_linestatus")
SCAN_SPANS = ("scan.", "HostToDevice")


def build(tables):
    q6 = harness.load_module("actions", "q6")
    return q6.build({"lineitem": tables["lineitem"].select(*CACHED).cache()})


class Rows(list):
    """An action's rows, with the program's cache counters read around
    the action, the batches the relation holds (what the process's first
    action, which materialised it, served) and, where the action left a
    span tree, the number of its spans that belong to a scan or an
    upload."""

    served = restored = cached = scan_spans = 0


_cached_batches = None   # what the first action of this process served


def run(df, out_dir):
    from spark_rapids_tpu.utils import metrics as M

    global _cached_batches
    before = (M.cached_batches_served_count(), M.cache_restored_batch_count())
    rows = Rows(df.collect())
    rows.served = M.cached_batches_served_count() - before[0]
    rows.restored = M.cache_restored_batch_count() - before[1]
    if _cached_batches is None:
        _cached_batches = rows.served
    rows.cached = _cached_batches
    tree = df.session.last_query_trace
    if tree is not None:
        rows.scan_spans = sum(sp.name.startswith(SCAN_SPANS)
                              for sp in tree.spans())
    return rows


def reference(arrays, dtype=np.float64):
    """numpy over the generated arrays, nothing of the engine (a copy of
    actions/q6.py's, so that the cell's yardstick is its own file).
    `dtype` is what the prices are computed in (tools/control.py passes
    bfloat16); the predicates are on exact values either way."""
    li, _ = arrays["lineitem"]
    keep = ((li["l_shipdate"] >= days("1994-01-01"))
            & (li["l_shipdate"] < days("1995-01-01"))
            & (li["l_discount"] >= 0.05) & (li["l_discount"] <= 0.07)
            & (li["l_quantity"] < 24.0))
    price = li["l_extendedprice"][keep].astype(dtype)
    disc = li["l_discount"][keep].astype(dtype)
    # the product in `dtype`, the sum in float32 at the least: a sum of
    # 1e6 terms in bfloat16 stalls, and no engine would do that
    wide = np.float64 if dtype == np.float64 else np.float32
    return [(float((price * disc).astype(wide).sum(dtype=wide)),)]


def compare(expected, results):
    """Per action: the rows to lib/compare's tolerance, and the cache's
    guarantee: the action served each batch the relation holds, once,
    none of them was brought back from a spill tier, and (a traced run)
    its tree has no span of a scan or an upload. Results that carry no
    counters (the control's, a reference's) are compared by their rows
    alone."""
    out = []
    for got in results:
        numbers = C.rows(expected, got, "q6")
        if hasattr(got, "served"):
            numbers += [
                C.compared("cache.batches_not_served",
                           abs(got.cached - got.served), 0),
                C.compared("cache.nothing_cached", int(got.cached <= 0), 0),
                C.compared("cache.restored_batches", got.restored, 0),
                C.compared("cache.scan_spans", got.scan_spans, 0)]
        out.append(numbers)
    return out
