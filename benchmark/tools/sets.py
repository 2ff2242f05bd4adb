#!/usr/bin/env python3
"""Run a cell as the driver's check does and say what bound its spread
supports: `--sets` sets of `--runs` runs, the same seeds in every set, each
run a process of its own (this one never touches jax, so the chip is free
for each child in turn).

    python3 benchmark/tools/sets.py --workload <cell> --out <dir> \\
        [--sets 2] [--runs 6] [--seed0 1000] [--seconds <run_seconds>]

For each end-to-end metric and set it prints the median and the spread
(the distance between the first and third quartile, as
statistics.quantiles(values, n=4) gives them, over the median), the widest
spread, and five times that: the rule the bound is set by. Every run's
result line and earlier lines are kept under --out.
"""

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)


def spread(values):
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values) if q3 > q1 else 0.0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--out", required=True)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--runs", type=int, default=6)
    ap.add_argument("--seed0", type=int, default=1000)
    ap.add_argument("--seconds", type=float)
    ap.add_argument("--trace", type=int, default=0)
    args = ap.parse_args(argv)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    seconds = args.seconds or bench["run_seconds"]
    os.makedirs(args.out, exist_ok=True)
    # large and far apart, as the driver's are
    seeds = [args.seed0 + i * 429496897 for i in range(args.runs)]
    sets = []
    for s in range(args.sets):
        values = {}
        for seed in seeds:
            tag = f"{args.workload}.set{s}.seed{seed}.trace{args.trace}"
            t0 = time.time()
            with open(os.path.join(args.out, tag + ".out"), "w") as out, \
                    open(os.path.join(args.out, tag + ".err"), "w") as err:
                rc = subprocess.run(
                    [sys.executable, os.path.join(HERE, "run.py"),
                     "--workload", args.workload, "--seed", str(seed),
                     "--seconds", str(seconds), "--trace", str(args.trace)],
                    cwd=ROOT, stdout=out, stderr=err).returncode
            with open(os.path.join(args.out, tag + ".out")) as f:
                lines = f.read().strip().splitlines()
            result = json.loads(lines[-1]) if rc == 0 and lines else {}
            print(json.dumps({"set": s, "seed": seed, "rc": rc,
                              "wall_s": round(time.time() - t0, 1),
                              "correct": result.get("correct"),
                              "attempted": result.get("attempted"),
                              "failed": result.get("failed"),
                              "metrics": {k: v["value"] for k, v in
                                          result.get("metrics", {}).items()}}),
                  flush=True)
            if rc != 0 or not result.get("correct"):
                print(f"run {tag} did not give a correct result",
                      file=sys.stderr)
                return 1
            for k, v in result["metrics"].items():
                values.setdefault(k, []).append(v["value"])
        sets.append(values)
    if args.runs >= 2:
        for name in sets[0]:
            spreads = [spread(v[name]) for v in sets]
            print(json.dumps({
                "metric": name,
                "medians": [statistics.median(v[name]) for v in sets],
                "spreads": spreads, "widest": max(spreads),
                "five_times_widest": 5 * max(spreads)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
