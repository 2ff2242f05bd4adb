"""One cell, once, in one process: the phases of a run and what a metric's
reader is given.

The harness knows no cell, configuration, action or metric by name. It is
handed the parsed BENCHMARK.json and a cell's name, and finds the rest in
files named after what BENCHMARK.json lists:

    configs/<config>.json        the deployment (BENCHMARK.json names the file)
    workloads/<cell>.json        the action, the tables, the traffic
    actions/<action>.py          build / run / reference / compare
    end_to_end/<metric>.py       read(run) -> float or None
    layer_metrics/<metric>.py    read(run) -> float or None
                                 (<quantity>.<group> is read by <quantity>.py)

Phase functions `require_tpu` and `native_library` follow chip_smoke.py
(PR 22), copied so that the yardstick does not move with the program.
"""

from __future__ import annotations

import glob
import importlib.util
import json
import os
import shutil
import sys
import time
from types import SimpleNamespace
from typing import Callable, List, Optional

from . import compare, loop, tpch_gen, xplane

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))  # benchmark/
ROOT = os.path.dirname(HERE)
DATA_ROOT = os.path.join(HERE, ".data")          # in benchmark/.gitignore
TRACED_ACTIONS = 3                               # the profiler is on for these
TRACING_CONF = "rapids.tpu.obs.tracing.enabled"


class BenchFailure(Exception):
    """The run cannot give a result; the process ends non-zero."""


def emit(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


def progress(t0: float, msg: str) -> None:
    print(f"[bench +{time.perf_counter() - t0:7.1f}s] {msg}",
          file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# finding things by name
# ---------------------------------------------------------------------------
def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_config(path: str) -> dict:
    """A configuration's file as it is run. One that `extends` another is
    laid over that one, read from the same directory: a list is joined to
    the base's list, any other key replaces the base's."""
    config = load_json(path)
    if "extends" not in config:
        return config
    merged = load_config(os.path.join(os.path.dirname(path),
                                      config["extends"] + ".json"))
    for key, value in config.items():
        if isinstance(value, list) and isinstance(merged.get(key), list):
            merged[key] = merged[key] + [v for v in value
                                         if v not in merged[key]]
        else:
            merged[key] = value
    return merged


def load_module(directory: str, name: str):
    """<benchmark>/<directory>/<name>.py, imported by path (a metric's
    name may hold dots, which a module name may not)."""
    path = os.path.join(HERE, directory, name + ".py")
    if not os.path.isfile(path):
        raise BenchFailure(f"{directory}/{name}.py: no such file")
    spec = importlib.util.spec_from_file_location(
        f"bench_{directory}_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(directory: str, metric: str):
    """The `read` of <directory>/<metric>.py. A quantity that is split by
    the end-to-end metric its cells report (`operators.device_ms` moves
    `query_s`, `operators.device_ms.write` moves `rows_per_s.write`) is
    read by the one reader of the quantity: where <metric>.py is not
    there, the name less its last dotted part is looked for."""
    stem = metric.rpartition(".")[0]
    for name in (metric, stem):
        if name and os.path.isfile(os.path.join(HERE, directory,
                                                name + ".py")):
            return load_module(directory, name).read
    raise BenchFailure(f"{directory}/{metric}.py: no such file, nor one "
                       f"for the quantity {stem!r}")


def named(entries: List[dict], name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise BenchFailure(f"BENCHMARK.json lists no {what} {name!r}; it has "
                       f"{[e['name'] for e in entries]}")


def metrics_of(bench: dict, group: str, cell: str) -> List[dict]:
    """The metrics of `end_to_end` or `per_layer` that this cell reports:
    those that list it under `workloads`, and of those that list no cells,
    every end-to-end metric and every per-layer metric whose `moves` is an
    end-to-end metric the cell reports."""
    def listed(m):
        return "workloads" not in m or cell in m["workloads"]

    end_to_end = {m["name"] for m in bench["end_to_end"] if listed(m)}
    return [m for m in bench[group] if listed(m)
            and ("workloads" in m or m.get("moves", m["name"]) in end_to_end)]


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
        print(f"benchmark: jax found platform {dev['platform']!r}, not a "
              "TPU; a cell is measured only on the chip", file=sys.stderr)
        raise SystemExit(2)
    if dev["count"] != chips:
        print(f"benchmark: jax found {dev['count']} device(s), the cell "
              f"asks for {chips}", file=sys.stderr)
        raise SystemExit(2)
    return dev


def native_library() -> str:
    """'native' once the program's C++ library is built from the source in
    this checkout and loaded, 'python' where the machine has no compiler.
    The program's own rule decides whether to build: no library yet, or one
    older than srt_native.cpp. A checkout holds only what git commits, and
    the library is not committed, so the first run of a checkout builds it
    and the later ones load what that run built."""
    from spark_rapids_tpu import native

    if native.get_lib() is not None:
        return "native"
    if shutil.which("g++") or shutil.which("clang++"):
        raise BenchFailure("a C++ compiler is on this machine but "
                           "srt_native.cpp did not build or load")
    return "python"


def cache_entries(cache_dir: Optional[str]) -> int:
    if not cache_dir or not os.path.isdir(cache_dir):
        return 0
    return sum(1 for f in os.listdir(cache_dir) if f.endswith("-cache"))


def open_session(conf: dict, traced: bool):
    import spark_rapids_tpu as srt

    session = srt.new_session()
    for key, value in conf.items():
        session.conf.set(key, value)
    session.conf.set(TRACING_CONF, bool(traced))
    return session


def build_seconds() -> float:
    """Seconds so far in which some thread traced, lowered, compiled or
    loaded a program (engine/compile_clock.py: jax's own events)."""
    from spark_rapids_tpu.engine import compile_clock
    from spark_rapids_tpu.obs.trace import wall_ns

    return compile_clock.compiling_ns(wall_ns()) / 1e9


def counter_readers() -> dict:
    """The process-wide counters, read around every action: a write runs
    outside a query context, so session.last_query_metrics misses it, and
    with one client the difference of two readings is one action's."""
    from spark_rapids_tpu.utils import metrics as M

    return {"deviceDispatches": M.dispatch_count,
            "fencesPerQuery": M.fence_count,
            "cpuFallbackEvents": M.cpu_fallback_count,
            "watchdogKills": M.watchdog_kill_count,
            "speculativeTasks": M.speculative_task_count}


def scanned_bytes(paths: dict, columns: dict) -> int:
    """Compressed bytes of the column chunks an action reads, from the
    files' footers: the least the scan must move, whatever it does."""
    import pyarrow.parquet as pq

    total = 0
    for table, wanted in columns.items():
        for f in sorted(glob.glob(os.path.join(paths[table], "*.parquet"))):
            md = pq.ParquetFile(f).metadata
            for g in range(md.num_row_groups):
                rg = md.row_group(g)
                for c in range(rg.num_columns):
                    col = rg.column(c)
                    if col.path_in_schema in wanted:
                        total += col.total_compressed_size
    return total


def memory_peak_bytes() -> int:
    import jax

    return max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in jax.devices())


class ActionRunner:
    """Runs the cell's action once: a fresh output directory where the
    traffic asks for one, the counters read around it, the span tree kept
    in a traced run, and the profiler's marker around a traced action."""

    def __init__(self, session, action, df, traffic: dict, out_root: str):
        self.session, self.action, self.df = session, action, df
        self.fresh_dir = bool(traffic.get("fresh_output_dir"))
        self.out_root = out_root
        self.counters = counter_readers()
        self.made = 0

    def __call__(self, marked: bool = False) -> SimpleNamespace:
        from jax.profiler import TraceAnnotation

        out_dir = None
        if self.fresh_dir:
            out_dir = os.path.join(self.out_root, f"a{self.made:05d}")
            if os.path.exists(out_dir):
                raise BenchFailure(f"{out_dir} holds an earlier write")
        self.made += 1
        self.session.last_query_trace = None
        before = {k: read() for k, read in self.counters.items()}
        start_ns = time.perf_counter_ns()
        if marked:
            with TraceAnnotation(xplane.MARKER):
                result = self.action.run(self.df, out_dir)
        else:
            result = self.action.run(self.df, out_dir)
        end_ns = time.perf_counter_ns()
        return SimpleNamespace(
            result=result, start_ns=start_ns, end_ns=end_ns,
            counters={k: read() - before[k]
                      for k, read in self.counters.items()},
            spans=self.session.last_query_trace)


def load_cell(bench: dict, cell_name: str):
    """(BENCHMARK.json's entry, the configuration, workloads/<cell>.json),
    checked against each other."""
    entry = named(bench["workloads"], cell_name, "workload")
    config_entry = named(bench["configs"], entry["config"], "configuration")
    config = load_config(os.path.join(ROOT, config_entry["file"]))
    cell = load_json(os.path.join(HERE, "workloads", cell_name + ".json"))
    for key in ("config", "traffic", "chips"):
        theirs = cell["traffic"]["name"] if key == "traffic" else cell[key]
        if theirs != entry[key]:
            raise BenchFailure(f"workloads/{cell_name}.json says {key} "
                               f"{theirs!r}, BENCHMARK.json {entry[key]!r}")
    return entry, config, cell


def run_cell(bench: dict, cell_name: str, seed: int, seconds: float,
             traced: bool, t_start: float, **kw) -> dict:
    """One run of a cell that BENCHMARK.json lists; returns the result
    object of the contract (run.py prints it as the last line)."""
    entry, config, cell = load_cell(bench, cell_name)
    return measure(bench, entry, config, cell, seed, seconds, traced,
                   t_start, **kw)


def measure(bench: dict, entry: dict, config: dict, cell: dict, seed: int,
            seconds: float, traced: bool, t_start: float,
            data_root: str = DATA_ROOT,
            clock: Callable[[], float] = time.perf_counter) -> dict:
    """Every phase of one run. `t_start` is the process's start on
    `clock`."""
    cell_name = entry["name"]
    action = load_module("actions", cell["action"])
    group = "per_layer" if traced else "end_to_end"
    readers = {m["name"]: load_reader(
        "layer_metrics" if traced else "end_to_end", m["name"])
        for m in metrics_of(bench, group, cell_name)}

    phases = {}

    def phase(name: str, since: float) -> float:
        now = clock()
        phases[name] = now - since
        progress(t_start, f"{name}: {now - since:.1f}s")
        return now

    t = clock()
    device = require_tpu(entry["chips"])
    peaks = load_json(os.path.join(HERE, "lib", "peaks.json"))
    if device["kind"] not in peaks:
        raise BenchFailure(f"lib/peaks.json has no device_kind "
                           f"{device['kind']!r}")
    t = phase("start_and_device", t_start)
    native = native_library()
    session = open_session(config["conf"], traced)
    from spark_rapids_tpu import _jax_setup

    session.device_manager  # bring-up places the compile cache
    cache_dir = _jax_setup.compile_cache_dir
    entries_before = cache_entries(cache_dir)
    emit({"cell": cell_name, "seed": seed, "seconds": seconds,
          "traced": traced, "device": device, "native": native,
          "compile_cache_dir": cache_dir,
          "compile_cache_entries_before": entries_before})
    t = phase("native_and_session", t)

    data_dir = os.path.join(data_root, cell_name)
    shutil.rmtree(data_dir, ignore_errors=True)
    arrays = tpch_gen.gen_tables(config["scale_factor"], seed, cell["tables"])
    t = phase("generate", t)
    paths = tpch_gen.write_parquet(arrays, os.path.join(data_dir, "tables"),
                                   config["layout"])
    t = phase("write_tables", t)

    tables = {name: session.read.parquet(p) for name, p in paths.items()}
    df = action.build(tables)
    runner = ActionRunner(session, action, df, cell["traffic"],
                          os.path.join(data_dir, "out"))
    t0 = clock()
    runner()
    first_query_s = clock() - t0
    t = phase("first_action", t)
    runner()
    t = phase("warm_up", t)
    setup_build_s = build_seconds()
    entries_after_setup = cache_entries(cache_dir)

    import jax

    trace_dir = os.path.join(data_dir, "trace")
    if traced:
        jax.profiler.start_trace(trace_dir, profiler_options=xplane.options())
    profiling = traced
    # the clock every earlier phase read ends the set-up and starts the window
    setup_s = clock() - t_start

    def do_action(i: int):
        nonlocal profiling
        record = runner(marked=profiling)
        if profiling and i + 1 >= TRACED_ACTIONS:
            jax.profiler.stop_trace()
            profiling = False
        return record

    try:
        samples = loop.closed_loop(do_action, seconds, clock)
    finally:
        if profiling:
            jax.profiler.stop_trace()
    phases["window"] = samples[-1].end_s
    t = clock()
    window_build_s = build_seconds() - setup_build_s
    peak = memory_peak_bytes()

    run = SimpleNamespace(
        cell=cell, config=config, device=device, peaks=peaks[device["kind"]],
        seconds=seconds, traced=traced, samples=samples,
        setup_s=setup_s, first_query_s=first_query_s,
        setup_build_s=setup_build_s, window_build_s=window_build_s,
        phases=phases,
        rows_per_action=sum(len(next(iter(arrays[t_][0].values())))
                            for t_ in action.COLUMNS),
        scanned_bytes=scanned_bytes(paths, action.COLUMNS),
        memory_peak_bytes=peak,
        trace=None)
    if traced:
        trace_file = xplane.find_trace(trace_dir)
        emit({"trace_file_bytes": os.path.getsize(trace_file),
              "trace_lines": xplane.describe(trace_file)})
        run.trace = xplane.reduce(trace_file)
        emit({"device_programs": run.trace["device_programs"]})
        t = phase("reduce_trace", t)

    # outside every timed number: the reference, from the generated arrays
    expected = action.reference(arrays)
    t = phase("reference", t)
    done = [s.record for s in samples if not s.error]
    run.written_bytes = action.written_bytes(done[-1].result) \
        if done and hasattr(action, "written_bytes") else 0
    failed, compared = count_failed(action, cell["action"], expected, samples)
    t = phase("compare", t)

    emit({"phases_s": phases, "setup_s": setup_s,
          "first_query_s": first_query_s, "setup_build_s": setup_build_s,
          "window_build_s": window_build_s,
          "actions": len(samples), "action_s": loop.durations(samples),
          "rate": loop.rate(run.rows_per_action, samples),
          "rate_less_longest": loop.rate_less_longest(run.rows_per_action,
                                                      samples),
          "counters_last_action": done[-1].counters if done else None,
          "scanned_bytes": run.scanned_bytes,
          "written_bytes": run.written_bytes,
          "rows_per_action": run.rows_per_action,
          "compile_cache_entries_before": entries_before,
          "compile_cache_entries_after_setup": entries_after_setup,
          "compile_cache_entries_after": cache_entries(cache_dir)})

    declared = {m["name"]: m for m in bench[group]}
    metrics = {}
    for name, read in readers.items():
        value = read(run)
        if value is not None:
            metrics[name] = {"value": value, "unit": declared[name]["unit"]}
    out_device = dict(device, memory_peak_bytes=peak)
    result = {"correct": failed == 0 and len(samples) > 0,
              "attempted": len(samples), "failed": failed,
              "metrics": metrics, "device": out_device}
    if traced:
        out_device["busy_s"] = run.trace["busy_s"]
        out_device["window_s"] = run.trace["window_s"]
        result["breakdown"] = breakdown(run)
    # last in the line: each number compared, at its worst over the
    # window's actions, beside its limit (run.py repeats it on stderr)
    result["compared"] = {n["name"]: [n["value"], n["limit"]]
                          for n in compared}
    if failed:
        result["compared"]["actions_failed"] = [failed, 0]
    session.stop()
    shutil.rmtree(data_dir, ignore_errors=True)
    return result


def count_failed(action, action_name: str, expected, samples: list):
    """How many of the window's actions raised, differ from the reference
    or show a counter that must read 0, and each number compared at its
    worst over the actions (the result line's `compared`). Prints every
    number compared beside its limit for the first and the last action
    and every failed one."""
    done = [s for s in samples if not s.error]
    per_action = iter(action.compare(expected,
                                     [s.record.result for s in done]))
    failed, worst = 0, {}
    for i, s in enumerate(samples):
        if s.error:
            failed += 1
            emit({"action": i, "error": s.error})
            continue
        numbers = next(per_action) + compare.counters(s.record.counters,
                                                      action_name)
        ok = compare.holds(numbers)
        failed += not ok
        if not ok or i in (0, len(samples) - 1):
            emit({"action": i, "ok": ok, "compared": numbers})
        for n in numbers:
            if n["name"] not in worst or n["value"] > worst[n["name"]]["value"]:
                worst[n["name"]] = n
    return failed, list(worst.values())


def breakdown(run) -> dict:
    """The operations that took most device time, and the longest idle
    gaps on the first chip, each labelled with the span of the program's
    span tree that `owner` picks at the gap's middle (or the action, where
    the action leaves no span tree)."""
    tr = run.trace
    marked = [s.record for s in run.samples if not s.error][:len(tr["action_s"])]
    # both clocks are read at a marked action's start: the profiler's in
    # the annotation's event, the host's perf_counter_ns beside it
    offset = tr["action_start_ns"] - marked[0].start_ns if marked else 0
    labelled = []
    for lo, hi in tr["idle_gaps_ns"]:
        at = (lo + hi) / 2 - offset
        labelled.append([owner(marked, at, run.cell["action"]),
                         (hi - lo) / 1e9])
    return {"device_ops": tr["device_ops"], "idle_gaps": labelled}


# spans in which a thread waits for another: the task queued for the
# chip's admission permit, and the prefetcher's own span, which is open
# from the reader thread's start to its end beside the reader's steps
WAITS = ("Acquire TPU Semaphore", "prefetch:")


def owner(records: list, at_ns: float, action_name: str) -> str:
    """The label of a moment: of the spans of the action's tree that are
    open at it and have no open child (what each thread of the action is
    in), one that works before one that waits (WAITS), then the deepest,
    then the one begun last. So the head of a Q6 action reads the reader's
    `scan.host_decode`, not the prefetcher's span beside it under the same
    task, and a queued task's wait for the permit does not hide the step
    of the task that holds it; where every thread waits, the wait is the
    label."""
    def is_open(sp) -> bool:
        return sp.end_ns is not None and sp.start_ns <= at_ns <= sp.end_ns

    for rec in records:
        if not rec.start_ns <= at_ns <= rec.end_ns:
            continue
        best, rank = action_name, None
        stack = [(rec.spans.root, 0)] if rec.spans is not None else []
        while stack:
            sp, d = stack.pop()
            if not is_open(sp):
                continue
            if any(is_open(c) for c in sp.children):
                stack.extend((c, d + 1) for c in sp.children)
                continue
            mine = (not sp.name.startswith(WAITS), d, sp.start_ns)
            if rank is None or mine > rank:
                best, rank = f"{sp.kind}:{sp.name}", mine
        return best
    return "between actions"
