# tpulint: stdout-protocol -- probe CLI: stdout is the report
"""Does writing files at the write cell's rate, and nothing else, make this
sandbox stand still? No jax, no engine. Phase `arrow`: three threads each
write 375,000-row, 6-column tables (16.5 MB) with
pyarrow.parquet.write_table into fresh directories (what the sink's host
writer does), paced to the cell's 110 MB/s; a fourth allocates and frees
4 MB arrays. Phase `arrow_fast`: the same, unpaced. The instruments record
every pause (seconds from the phase's start).

    chiprun -- python3 tools/stall_hunt/fsprobe.py [seconds] [phase,phase]

PR 26 on one chip machine: 6.76 GB at 112 MB/s and 19.83 GB at 440 MB/s,
longest write 0.205 s, no pause but the sandbox's own 0.1 s ones."""

import json
import os
import shutil
import sys
import threading
import time

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from instruments import Instruments, since  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
OUT = os.path.join(REPO, "chiprun_out")
DATA = os.path.join(REPO, "benchmark", ".data", "fsprobe")
SECONDS = float(sys.argv[1]) if len(sys.argv) > 1 else 60.0
PHASES = sys.argv[2].split(",") if len(sys.argv) > 2 else ["arrow",
                                                           "arrow_fast"]
ROWS = 375_000      # 16.5 MB a file, as the cell's
PACE_S = 0.45       # a file a writer: 110 MB/s from three writers


def run_phase(phase: str, table) -> dict:
    shutil.rmtree(DATA, ignore_errors=True)
    os.makedirs(DATA)
    instruments = Instruments(f"fsprobe_{phase}", OUT)
    started = time.monotonic()
    until = started + SECONDS
    writes = []  # (end, seconds, bytes)
    lock = threading.Lock()
    churn = {"max_s": 0.0, "n": 0}

    def writer(k):
        i = 0
        while time.monotonic() < until:
            directory = os.path.join(DATA, f"w{k}_{i:05d}")
            os.makedirs(directory)
            path = os.path.join(directory, "part.parquet")
            t0 = time.monotonic()
            pq.write_table(table, path, compression="snappy")
            t1 = time.monotonic()
            with lock:
                writes.append((t1, t1 - t0, os.path.getsize(path)))
            i += 1
            if phase == "arrow":
                time.sleep(max(0.0, PACE_S - (t1 - t0)))

    def churner():
        while time.monotonic() < until:
            t0 = time.monotonic()
            block = np.empty(4 << 20, np.uint8)
            block[::4096] = 1
            del block
            churn["max_s"] = max(churn["max_s"], time.monotonic() - t0)
            churn["n"] += 1
            time.sleep(0.002)

    threads = [threading.Thread(target=writer, args=(k,)) for k in range(3)]
    threads.append(threading.Thread(target=churner))
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    elapsed = time.monotonic() - started
    report = since(instruments.close(), started)
    shutil.rmtree(DATA, ignore_errors=True)
    lengths = sorted(w[1] for w in writes)
    median = lengths[len(lengths) // 2]
    total = sum(w[2] for w in writes)
    return {"phase": phase, "seconds": round(elapsed, 1),
            "files": len(writes), "GB": round(total / 1e9, 2),
            "MB_per_s": round(total / 1e6 / elapsed, 1),
            "write_s_median": round(median, 4),
            "write_s_max": round(lengths[-1], 3),
            "writes_over_4x_median(at, s)": [
                (round(end - started, 2), round(s, 3))
                for end, s, _ in writes if s > 4 * median][:40],
            "churn_max_s": round(churn["max_s"], 4), "churn_n": churn["n"],
            **report}


def main():
    os.makedirs(OUT, exist_ok=True)
    rng = np.random.default_rng(7)
    table = pa.table({
        "d": rng.integers(8000, 10500, ROWS).astype(np.int32),
        **{f"f{i}": rng.random(ROWS) for i in range(5)}})
    for phase in PHASES:
        print(json.dumps(run_phase(phase, table)), flush=True)


main()
