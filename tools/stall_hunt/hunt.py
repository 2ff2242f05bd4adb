# tpulint: stdout-protocol -- hunt CLI: stdout is the report
"""One window of a benchmark cell under the instruments of instruments.py:
which kind of pause is it when an action stands still. On the chip:

    chiprun -- python3 tools/stall_hunt/hunt.py <checkout> <seed> 60 <tag> [<cell>]

Prints the harness's lines, one `"tag"` line with every action slower
than 1.5 medians or with a speculative task and every pause the three
heartbeats saw (seconds from the window's start), and the result. Stacks
taken DURING a pause land in chiprun_out/stall_<tag>.stacks. A sixth
argument rehearses on the CPU at SF 0.01."""

import time

T_START = time.perf_counter()

import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT, SEED, SECONDS, TAG = (sys.argv[1], int(sys.argv[2]),
                            float(sys.argv[3]), sys.argv[4])
CELL = sys.argv[5] if len(sys.argv) > 5 else "lineitem_write_slim"
REHEARSE = len(sys.argv) > 6
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
sys.path[:0] = [ROOT, os.path.join(ROOT, "benchmark"),
                os.path.dirname(os.path.abspath(__file__))]

from instruments import Instruments, since  # noqa: E402
from lib import harness, loop  # noqa: E402

OUT = os.environ.get("STALL_HUNT_OUT") or os.path.join(ROOT, "chiprun_out")
os.makedirs(OUT, exist_ok=True)
closed_loop = loop.closed_loop


def instrumented_loop(do_action, secs, clock=time.perf_counter):
    """The harness's closed loop with the instruments on for its length."""
    log = []  # (action, start on the monotonic clock, seconds, speculated)
    instruments = Instruments(TAG, OUT)

    def timed(i):
        start = time.monotonic()
        rec = do_action(i)
        log.append((i, start, time.monotonic() - start,
                    rec.counters.get("speculativeTasks", 0)))
        return rec

    samples = closed_loop(timed, secs, clock)
    report = since(instruments.close(), log[0][1])
    lengths = sorted(s for _, _, s, _ in log)
    median = lengths[len(lengths) // 2]
    print(json.dumps({
        "tag": TAG, "actions": len(log), "median_s": round(median, 4),
        "max_s": round(lengths[-1], 3),
        "slow_or_speculated(i, at, s, speculativeTasks)": [
            (i, round(start - log[0][1], 2), round(s, 3), spec)
            for i, start, s, spec in log if s > 1.5 * median or spec],
        **report}), flush=True)
    return samples


loop.closed_loop = harness.loop.closed_loop = instrumented_loop
if REHEARSE:
    harness.require_tpu = lambda chips: {
        "platform": "cpu", "kind": "TPU v5 lite", "count": 1}
    load_cell = harness.load_cell

    def small(bench, name):
        entry, config, cell = load_cell(bench, name)
        return entry, dict(config, scale_factor=0.01), cell

    harness.load_cell = small
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)
res = harness.run_cell(BENCH, CELL, SEED, SECONDS, False, T_START)
print(json.dumps({
    "tag": TAG, "correct": res["correct"], "attempted": res["attempted"],
    "failed": res["failed"],
    "metrics": {k: v["value"] for k, v in res["metrics"].items()}}),
    flush=True)
