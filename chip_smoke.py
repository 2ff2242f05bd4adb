#!/usr/bin/env python3
"""Quickest proof that the engine still starts and answers on the chip.

One process, no subprocess: TPC-H-like tables are generated from --seed,
written once as snappy parquet under the checkout, read back through
``session.read.parquet`` and driven through planner and executor exactly as
a user's program would be (README.md). q6, q1 and one parquet write run
twice each (cold, then warm) on a device session whose conf forbids every
fallback that could hide the device, and each result is compared with a
reference computed from the generated in-memory arrays: the same DataFrame
program on the numpy engine (a second session with
``rapids.tpu.sql.enabled=false``), or pandas where that engine needs more
than a minute at SF1.

    python chip_smoke.py             # one chip, SF1: q6, q1, the write
    python chip_smoke.py --chips 4   # only the cross-chip path: q1 on a mesh

The run needs a TPU: with any other platform, or a device count other than
--chips, it says why and exits non-zero. There is no CPU continuation. The
CPU rehearsal is tests/test_chip_smoke.py, which imports this module and
calls the phase functions below at a tiny scale factor.

Every line printed is one JSON object; the last one is the verdict the
driver reads: {"ok": true, "device": {"platform", "kind", "count"}}.
"""

from __future__ import annotations

import argparse
import faulthandler
import glob
import importlib.util
import json
import math
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
DATA_DIR = os.path.join(HERE, ".chip_smoke_data")  # listed in .gitignore

DEADLINE_S = 1170
FILES_PER_TABLE = 4
# f32-computed DOUBLEs against the f64 numpy engine: the loosest tolerance
# the suite uses for float aggregates (tests/test_window.py, approx 1e-4)
FLOAT_TOLERANCE = 1e-4
# q3 is not run: its cold run alone compiled for more than 770 s on the v5e
# (three join kernels at two minutes each, CHANGES.md PR 22) and the whole
# script has 1200 s. tests/test_chip_smoke.py rehearses it through
# run_query; it comes back here when its compile fits.
QUERIES = ("q6", "q1")
# a cold four-chip q1 took 1134 s of DEADLINE_S (PR 22): nothing fits beside
MESH_QUERIES = ("q1",)

# operators of these queries that rightly stay on the host under test mode
ALLOWED_NON_TPU: tuple = ()

DEVICE_CONF = {
    # the prices are DOUBLE and the chip computes them in f32: without these
    # two the planner puts every price expression on the host, row by row
    "rapids.tpu.sql.incompatibleOps.enabled": True,
    "rapids.tpu.sql.variableFloatAgg.enabled": True,
    # any operator left off the device raises instead of running on the host
    "rapids.tpu.sql.test.enabled": True,
    "rapids.tpu.sql.test.allowedNonTpu": ",".join(ALLOWED_NON_TPU),
    # a device error reaches this script instead of a numpy re-run
    "rapids.tpu.execution.cpuFallback.enabled": False,
    "rapids.tpu.execution.circuitBreaker.enabled": False,
}
MESH_CONF = {
    # 0 = every healthy device (shuffle/ici.session_mesh)
    "rapids.tpu.sql.spmd.meshDevices": 0,
    "rapids.tpu.sql.shuffle.partitions": 8,
    # left to itself the stage sizes its exchange buckets from the measured
    # INPUT rows (6M at SF1), overruns spmd.maxSortLanes and degrades to
    # the host loop, which crosses no chip; q1 has at most six groups
    "rapids.tpu.sql.spmd.bucketRows": 4096,
}

_COUNTERS = ("deviceDispatches", "fencesPerQuery", "checkedReplays",
             "donatedBytes", "watchdogKills", "speculativeTasks",
             "speculativeWins", "spmdStages", "fusedStages",
             "collectiveBytes", "cpuFallbackEvents", "retries",
             "encodedColumns")


class SmokeFailure(AssertionError):
    """A check of this script failed; the run ends non-zero."""


def emit(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


_T0 = time.perf_counter()


def progress(msg: str) -> None:
    """Where the run is, on stderr: what a killed run leaves behind."""
    print(f"[chip_smoke +{time.perf_counter() - _T0:7.1f}s] {msg}",
          file=sys.stderr, flush=True)


def _harness():
    """tests/harness.py imported by path (the comparison the suite uses,
    not a copy of it)."""
    name = "srt_tests_harness"
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(
            name, os.path.join(HERE, "tests", "harness.py"))
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod
        spec.loader.exec_module(mod)
    return sys.modules[name]


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------
def require_tpu(chips: int) -> dict:
    """The device as jax reports it, or SystemExit: no CPU continuation."""
    import jax

    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    if dev["platform"] != "tpu":
        print(f"chip_smoke: jax found platform {dev['platform']!r}, not a "
              "TPU; this script only runs on the chip", file=sys.stderr)
        raise SystemExit(2)
    if dev["count"] != chips:
        print(f"chip_smoke: jax found {dev['count']} device(s) but --chips "
              f"is {chips}", file=sys.stderr)
        raise SystemExit(2)
    return dev


def rebuild_native() -> str:
    """Drop any prebuilt native library and let get_lib() build it from
    srt_native.cpp as committed. 'native' or 'python' (the stand-in, only
    when the machine has no C++ compiler)."""
    from spark_rapids_tpu import native

    if native._tried:
        raise SmokeFailure("the native library was loaded before this "
                           "script could rebuild it from source")
    for so in glob.glob(os.path.join(os.path.dirname(native._SO), "*.so")):
        os.remove(so)
    lib = native.get_lib()
    if lib is not None:
        return "native"
    if shutil.which("g++") or shutil.which("clang++"):
        raise SmokeFailure(
            "a C++ compiler is on this machine but srt_native.cpp did not "
            "build or load")
    return "python"


def open_sessions(extra_conf: dict = None):
    """(device session, numpy reference session)."""
    import spark_rapids_tpu as srt

    dev = srt.new_session()
    for k, v in {**DEVICE_CONF, **(extra_conf or {})}.items():
        dev.conf.set(k, v)
    ref = srt.new_session()
    ref.conf.set("rapids.tpu.sql.enabled", False)
    return dev, ref


def generate_and_write(ref_session, sf: float, seed: int,
                       data_dir: str) -> tuple:
    """gen_tables from the seed on the reference session (its in-memory
    DataFrames are what the reference queries read), each table written
    once as snappy parquet: FILES_PER_TABLE files of about three row groups.
    Returns (reference tables, {table: directory}, {table: rows})."""
    import pyarrow as pa
    import pyarrow.parquet as pq

    from spark_rapids_tpu.benchmarks import tpch
    from spark_rapids_tpu.columnar.batch import bucket_capacity
    from spark_rapids_tpu.io.arrow_convert import host_batch_to_arrow

    shutil.rmtree(data_dir, ignore_errors=True)
    tables = tpch.gen_tables(ref_session, sf=sf,
                             num_partitions=FILES_PER_TABLE, seed=seed)
    paths, rows = {}, {}
    for name, df in tables.items():
        rel = df._plan
        tdir = os.path.join(data_dir, name)
        os.makedirs(tdir)
        rows[name] = 0
        for i, part in enumerate(rel.partitions):
            if not part:
                continue
            table = pa.concat_tables(
                [host_batch_to_arrow(b, rel.schema) for b in part])
            rows[name] += table.num_rows
            pq.write_table(
                table, os.path.join(tdir, f"part-{i:05d}.parquet"),
                compression="snappy",
                row_group_size=bucket_capacity(-(-table.num_rows // 3)))
        paths[name] = tdir
    return tables, paths, rows


def read_tables(session, paths: dict) -> dict:
    return {name: session.read.parquet(p) for name, p in paths.items()}


def check_device_metrics(name: str, metrics: dict) -> None:
    """The device did the work, and the self-healing layer left a healthy
    run alone: nothing fell back to the host (a query replayed on the numpy
    engine, a split the device decoder refused), something dispatched, and
    neither the watchdog nor speculation took compiling for a fault."""
    if metrics.get("cpuFallbackEvents", 0) != 0:
        raise SmokeFailure(
            f"{name}: cpuFallbackEvents={metrics['cpuFallbackEvents']} — "
            "rows came from the host, not the device")
    if metrics.get("deviceDispatches", 0) <= 0:
        raise SmokeFailure(f"{name}: no device dispatch was recorded")
    for key in ("watchdogKills", "speculativeTasks"):
        if metrics.get(key, 0) != 0:
            raise SmokeFailure(
                f"{name}: {key}={metrics[key]} on a healthy run — the "
                "self-healing layer mistook a slow build for a fault")


def max_rel_error(ref_rows, dev_rows) -> float:
    worst = 0.0
    for rr, rd in zip(ref_rows, dev_rows):
        for a, b in zip(rr, rd):
            if isinstance(a, float) and isinstance(b, float) and \
                    math.isfinite(a) and math.isfinite(b) and a != b:
                worst = max(worst, abs(a - b) / max(abs(a), abs(b)))
    return worst


def _counters(metrics: dict) -> dict:
    return {k: metrics.get(k, 0) for k in _COUNTERS}


def _build_seconds() -> float:
    """Seconds so far during which some thread was tracing, lowering or
    compiling a program (or loading it from the compile cache)."""
    from spark_rapids_tpu.engine import compile_clock
    from spark_rapids_tpu.obs.trace import wall_ns

    return compile_clock.compiling_ns(wall_ns()) / 1e9


def run_query(name: str, dev_session, dev_tables: dict,
              ref_tables: dict) -> dict:
    """Cold then warm on the device session, metrics read before the
    reference runs (every query overwrites last_query_metrics), then the
    same program on the numpy engine and the comparison."""
    from spark_rapids_tpu.benchmarks import tpch

    build = tpch.QUERIES[name]
    runs = []
    for run in ("cold", "warm"):
        progress(f"{name}: {run} run")
        t0, b0 = time.perf_counter(), _build_seconds()
        dev_rows = build(dev_tables).collect()
        seconds = time.perf_counter() - t0
        metrics = dict(dev_session.last_query_metrics)
        metrics["build_s"] = _build_seconds() - b0
        check_device_metrics(name, metrics)
        runs.append((seconds, metrics))
    (cold_s, cold_m), (warm_s, warm_m) = runs
    explain = dev_session.explain_plan(build(dev_tables)._plan, "ALL")
    planned_spmd = "TpuSpmdStage(" in explain

    progress(f"{name}: reference")
    t0 = time.perf_counter()
    if name in DIRECT_REFERENCES:
        ref_rows = DIRECT_REFERENCES[name](ref_tables)
        reference = "pandas over the generated arrays"
    else:
        ref_rows = build(ref_tables).collect()
        reference = "numpy engine, in-memory tables"
    ref_s = time.perf_counter() - t0
    _harness().assert_rows_equal(ref_rows, dev_rows,
                                 approx_float=FLOAT_TOLERANCE)
    line = {"query": name, "rows": len(dev_rows),
            "cold_s": cold_s, "warm_s": warm_s,
            "cold_build_s": cold_m["build_s"],
            "warm_build_s": warm_m["build_s"],
            "reference_s": ref_s, "reference": reference}
    line.update(_counters(warm_m))
    line["cold"] = {k: cold_m.get(k, 0) for k in
                    ("watchdogKills", "speculativeTasks", "checkedReplays",
                     "retries", "spmdStages")}
    line["spmd_planned"] = planned_spmd
    # the overflow probe of engine/spmd_exec.py reroutes a planned SPMD
    # stage to the host loop without a word: a finding, not a fault
    line["spmd_degraded"] = planned_spmd and warm_m.get("spmdStages", 0) == 0
    line["max_rel_err"] = max_rel_error(ref_rows, dev_rows)
    line["match"] = True
    return line


def _q1_input(tables: dict):
    """q1's filter + project, the stage in front of its aggregate."""
    from spark_rapids_tpu.benchmarks import tpch
    from spark_rapids_tpu.plan import functions as F

    li = tables["lineitem"]
    disc = F.col("l_extendedprice") * (F.lit(1.0) - F.col("l_discount"))
    return (li.filter(li["l_shipdate"] <= tpch.date_lit("1998-09-02"))
            .select(F.col("l_returnflag"), F.col("l_linestatus"),
                    F.col("l_quantity"), F.col("l_extendedprice"),
                    F.col("l_discount"), F.col("l_tax"),
                    disc.alias("disc_price"),
                    (disc * (F.lit(1.0) + F.col("l_tax"))).alias("charge")))


def _lineitem_frame(ref_tables: dict):
    """The generated lineitem arrays q1 reads, as a pandas frame, with q1's
    filter and projections computed in float64."""
    import numpy as np
    import pandas as pd

    from spark_rapids_tpu.benchmarks import tpch

    rel = ref_tables["lineitem"]._plan
    at = {a.name: i for i, a in enumerate(rel.schema)}
    li = pd.DataFrame({
        c: np.concatenate([b.columns[at[c]].data
                           for part in rel.partitions for b in part])
        for c in ("l_returnflag", "l_linestatus", "l_quantity",
                  "l_extendedprice", "l_discount", "l_tax", "l_shipdate")})
    li = li[li["l_shipdate"] <= tpch._days("1998-09-02")]
    li = li.drop(columns="l_shipdate")
    li["disc_price"] = li["l_extendedprice"] * (1.0 - li["l_discount"])
    li["charge"] = li["disc_price"] * (1.0 + li["l_tax"])
    return li


def _rows(frame) -> list:
    return [tuple(v.item() if hasattr(v, "item") else v for v in row)
            for row in frame.itertuples(index=False, name=None)]


def q1_by_pandas(ref_tables: dict) -> list:
    """q1 computed directly: the numpy engine needs a minute and a half
    for it at SF1 (93 s on the v5e's host, PR 22), pandas two seconds."""
    g = _lineitem_frame(ref_tables).groupby(
        ["l_returnflag", "l_linestatus"], sort=True)
    return _rows(g.agg(
        sum_qty=("l_quantity", "sum"),
        sum_base_price=("l_extendedprice", "sum"),
        sum_disc_price=("disc_price", "sum"),
        sum_charge=("charge", "sum"),
        avg_qty=("l_quantity", "mean"),
        avg_price=("l_extendedprice", "mean"),
        avg_disc=("l_discount", "mean"),
        count_order=("l_quantity", "size")).reset_index())


# queries whose numpy-engine run takes more than about a minute at SF1
DIRECT_REFERENCES = {"q1": q1_by_pandas}


def _digest(frame) -> list:
    """Per-group counts, sums and one cross term of the written columns:
    what is compared instead of millions of rows."""
    frame = frame.assign(x=frame["l_quantity"] * frame["charge"])
    g = frame.groupby(["l_returnflag", "l_linestatus"], sort=True)
    return _rows(g.agg(
        n=("l_quantity", "size"), s_qty=("l_quantity", "sum"),
        s_price=("l_extendedprice", "sum"), s_disc=("l_discount", "sum"),
        s_tax=("l_tax", "sum"), s_disc_price=("disc_price", "sum"),
        s_charge=("charge", "sum"), s_x=("x", "sum")).reset_index())


def _write_counts() -> dict:
    from spark_rapids_tpu.utils import metrics as M

    return {"deviceDispatches": M.dispatch_count,
            "cpuFallbackEvents": M.cpu_fallback_count,
            "fencesPerQuery": M.fence_count,
            "watchdogKills": M.watchdog_kill_count,
            "speculativeTasks": M.speculative_task_count}


def run_write(dev_session, dev_tables: dict, ref_tables: dict,
              out_dir: str) -> dict:
    """q1's input written by the device session with df.write.parquet,
    twice; the files are read back with Arrow's reader (not the device
    decoder) and their digest compared with the same digest of the
    generated arrays, both by pandas."""
    import pyarrow.parquet as pq

    counts = _write_counts()
    runs = []
    for run in ("cold", "warm"):
        progress(f"write: {run} run")
        shutil.rmtree(out_dir, ignore_errors=True)
        before = [count() for count in counts.values()]
        t0, b0 = time.perf_counter(), _build_seconds()
        _q1_input(dev_tables).write.parquet(out_dir)
        seconds = time.perf_counter() - t0
        # a write runs outside execute_partitions: no per-query context, so
        # the process-wide counters are read around it
        metrics = {key: count() - was for (key, count), was
                   in zip(counts.items(), before)}
        metrics["build_s"] = _build_seconds() - b0
        check_device_metrics("write", metrics)
        runs.append((seconds, metrics))
    (cold_s, cold_m), (warm_s, warm_m) = runs
    files = sorted(glob.glob(os.path.join(out_dir, "*.parquet")))
    progress("write: reference")
    t0 = time.perf_counter()
    back = _digest(pq.read_table(files).to_pandas())
    want = _digest(_lineitem_frame(ref_tables))
    ref_s = time.perf_counter() - t0
    _harness().assert_rows_equal(want, back, approx_float=FLOAT_TOLERANCE)
    line = {"query": "write", "rows": sum(r[2] for r in back),
            "files": len(files),
            "bytes": sum(os.path.getsize(f) for f in files),
            "cold_s": cold_s, "warm_s": warm_s,
            "cold_build_s": cold_m.pop("build_s"),
            "warm_build_s": warm_m.pop("build_s"),
            "reference_s": ref_s,
            "reference": "pandas digest of the files read back by Arrow"}
    line.update(warm_m)
    line["max_rel_err"] = max_rel_error(want, back)
    line["match"] = True
    return line


def cache_entries(cache_dir) -> int:
    if not cache_dir or not os.path.isdir(cache_dir):
        return 0
    return sum(1 for f in os.listdir(cache_dir) if f.endswith("-cache"))


def check_mesh(dev_session, lines: list) -> dict:
    """The cross-chip checks of --chips 4: the stage mesh spans every
    device, every device held data, and bytes crossed the interconnect."""
    import jax

    from spark_rapids_tpu import conf as C
    from spark_rapids_tpu.shuffle import ici

    n_mesh = int(ici.stage_mesh(
        dev_session.conf.get(C.SPMD_MESH_DEVICES)).devices.size)
    per_device = []
    for d in jax.devices():
        st = d.memory_stats() or {}
        per_device.append({"id": d.id,
                           "bytes_in_use": st.get("bytes_in_use", 0),
                           "peak_bytes_in_use":
                               st.get("peak_bytes_in_use", 0)})
    collective = sum(ln.get("collectiveBytes", 0) for ln in lines)
    out = {"mesh_devices": n_mesh, "per_device": per_device,
           "collectiveBytes": collective}
    if n_mesh != len(jax.devices()):
        raise SmokeFailure(f"stage mesh has {n_mesh} devices, "
                           f"jax has {len(jax.devices())}: {out}")
    idle = [p["id"] for p in per_device if p["peak_bytes_in_use"] <= 0]
    if idle:
        raise SmokeFailure(f"devices {idle} never held a byte: {out}")
    if collective <= 0:
        raise SmokeFailure(f"no bytes crossed the interconnect: {out}")
    return out


# ---------------------------------------------------------------------------
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--sf", type=float, default=1.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    args = ap.parse_args(argv)

    # the contract is 1200 s, compilation included: a run still going
    # shortly before that says where every thread stands and exits non-zero
    faulthandler.dump_traceback_later(DEADLINE_S, exit=True)
    t_start = time.perf_counter()
    device = require_tpu(args.chips)

    import jax

    from spark_rapids_tpu import _jax_setup
    from spark_rapids_tpu.engine import compile_clock
    from spark_rapids_tpu.utils import devprobe

    mesh_run = args.chips == 4
    queries = MESH_QUERIES if mesh_run else QUERIES
    # before anything can load the library: what runs is built from the
    # source as committed, never a .so that came with the copy
    native = rebuild_native()
    dev_session, ref_session = open_sessions(MESH_CONF if mesh_run else None)
    dm = dev_session.device_manager
    cache_dir = _jax_setup.compile_cache_dir
    entries_before = cache_entries(cache_dir)
    emit({"device_kind": device["kind"], "platform": device["platform"],
          "count": device["count"], "jax": jax.__version__,
          "hbm_total": dm.hbm_total, "hbm_budget": dm.hbm_budget,
          "fence_cost_ms": devprobe.fence_cost_ms(),
          "native": native,
          "compile_cache_dir": cache_dir,
          "compile_cache_entries_before": entries_before,
          "sf": args.sf, "seed": args.seed, "queries": list(queries),
          "conf": {**DEVICE_CONF, **(MESH_CONF if mesh_run else {})},
          "allowed_non_tpu": list(ALLOWED_NON_TPU)})

    progress("generate + write parquet")
    t0 = time.perf_counter()
    ref_tables, paths, rows = generate_and_write(
        ref_session, args.sf, args.seed, DATA_DIR)
    emit({"phase": "generate+write", "seconds": time.perf_counter() - t0,
          "rows": rows, "files_per_table": FILES_PER_TABLE,
          "parquet_bytes": sum(
              os.path.getsize(f) for f in glob.glob(
                  os.path.join(DATA_DIR, "*", "*.parquet")))})
    dev_tables = read_tables(dev_session, paths)

    lines = []
    for name in queries:
        lines.append(run_query(name, dev_session, dev_tables, ref_tables))
        emit(lines[-1])
    if mesh_run:
        emit({"phase": "mesh", **check_mesh(dev_session, lines)})
    else:
        lines.append(run_write(dev_session, dev_tables, ref_tables,
                               os.path.join(DATA_DIR, "_written")))
        emit(lines[-1])

    stats = dm.device.memory_stats() or {}
    emit({"phase": "summary",
          "compile_seconds": sum(ln["cold_s"] - ln["warm_s"]
                                 for ln in lines),
          "compile_cache_entries_after": cache_entries(cache_dir),
          "compile_cache_entries_before": entries_before,
          # jax's own durations per build step, summed over threads: what
          # the cold-minus-warm seconds were spent on
          "build_step_seconds": compile_clock.step_seconds(),
          "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
          "watchdogKills": sum(ln.get("watchdogKills", 0)
                               + ln.get("cold", {}).get("watchdogKills", 0)
                               for ln in lines),
          "speculativeTasks": sum(
              ln.get("speculativeTasks", 0)
              + ln.get("cold", {}).get("speculativeTasks", 0)
              for ln in lines),
          "spmd_degraded": [ln["query"] for ln in lines
                            if ln.get("spmd_degraded")],
          "total_seconds": time.perf_counter() - t_start})
    dev_session.stop()
    ref_session.stop()
    shutil.rmtree(DATA_DIR, ignore_errors=True)
    faulthandler.cancel_dump_traceback_later()
    emit({"ok": True, "device": device})
    return 0


if __name__ == "__main__":
    sys.exit(main())
