#!/usr/bin/env python3
"""Run a cell as the driver's check does and say what bound its spread
supports: `--sets` sets of `--runs` runs, the same seeds in every set, each
run a process of its own (this one never touches jax, so the chip is free
for each child in turn).

    python3 benchmark/tools/sets.py --workload <cell> --out <dir> \\
        [--sets 2] [--runs 6] [--seed0 1000] [--seconds <run_seconds>]

The driver's rule, in the words of the refusal it gave PR 28 (ledger):
"in two sets of runs of the same code the spread is 0.00658129 and
0.00753005 s, and the bound is 0.0134167 s, 5% of 0.268334 s. A spread
leaves out the run farthest from its median where that narrows it. For a
workload that is new, or measured anew, the mean of the two spreads may be
at most 50% of the bound." And it refuses a bound as too loose where it is
over eight times the widest spread of all the runs of a set, none left
out, a spread there being the distance between the first and the third
quartile as statistics.quantiles(values, n=4) gives them.

So for each end-to-end metric this prints, per set, the median, `spread`
(the runs less the one farthest from the set's median, largest less
smallest, over the set's median: the widest that a reading of the
driver's words allows, so a bound that holds it holds the rest), `iqr`
(the quartile distance of all the set's runs over the median) and
`iqrs_less_farthest` (the same of the runs less the farthest: the
narrowest reading, and what the driver's notes in the ledger suggest); then
`widest` and `mean` of the sets' spreads, `three_times_widest` (the bound
that keeps the mean of two sets at a third of it or less, against the
driver's half) and `eight_times_widest_iqr` (what a bound may not pass).
Until PR 33 this tool printed five times the widest `iqr`: where one run
of six lies low and one high, the quartile distance is half of `spread`
or less, and a bound of five times it leaves the mean of the spreads at
40% of the bound, a hair under the driver's half. Sets made on another
machine spread wider than that hair: measure in more than one call.

Beside the metrics, for every run: the window's rate by both of
lib/loop.py's formulas on the same samples (`rate`, which is the
end-to-end rows_per_s, and `rate_less_longest`) and the longest action.
Every run's result line and earlier lines are kept under --out. A run
that is not `correct` is printed, left out of its set's spreads, and makes
the exit code 1; a run with no result line ends the tool there.
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


def less_farthest(values):
    """The set's runs, sorted, without the one farthest from their median
    (an end of the sorted list: leaving it out never widens the rest);
    two runs or fewer are kept as they are."""
    kept = sorted(values)
    if len(kept) <= 2:
        return kept
    med = statistics.median(kept)
    return kept[1:] if med - kept[0] > kept[-1] - med else kept[:-1]


def spread(values):
    """The widest reading of how the driver reckons a set's spread when it
    asks whether a bound is too tight: largest less smallest of the runs
    less the farthest, over the median of the whole set."""
    kept = less_farthest(values)
    return (kept[-1] - kept[0]) / statistics.median(values)


def iqr(values, leave_out_farthest=False):
    """The quartile distance of the runs over the median of them all: of
    all the runs, what the driver reads when it asks whether a bound is
    too loose; of the runs less the farthest, the narrowest reading of
    its spread for too tight."""
    kept = less_farthest(values) if leave_out_farthest else values
    q1, _, q3 = statistics.quantiles(kept, n=4)
    return (q3 - q1) / statistics.median(values)


def summary(name, sets):
    """One metric over the sets: `sets` is a list of lists of values."""
    spreads = [spread(v) for v in sets]
    iqrs = [iqr(v) for v in sets]
    return {"metric": name,
            "medians": [statistics.median(v) for v in sets],
            "spreads": spreads, "iqrs": iqrs,
            "iqrs_less_farthest": [iqr(v, True) for v in sets],
            "widest": max(spreads), "mean": statistics.mean(spreads),
            "three_times_widest": 3 * max(spreads),
            "eight_times_widest_iqr": 8 * max(iqrs)}


def window_line(lines):
    """The earlier line of a run's stdout that holds the window's samples
    (lib/harness.py emits it with the phases)."""
    for line in lines:
        if line.startswith('{"phases_s"'):
            return json.loads(line)
    return {}


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
    sets, not_correct = [], 0
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
            window = window_line(lines)
            print(json.dumps({"set": s, "seed": seed, "rc": rc,
                              "wall_s": round(time.time() - t0, 1),
                              "correct": result.get("correct"),
                              "attempted": result.get("attempted"),
                              "failed": result.get("failed"),
                              "metrics": {k: v["value"] for k, v in
                                          result.get("metrics", {}).items()},
                              "rate": window.get("rate"),
                              "rate_less_longest":
                                  window.get("rate_less_longest"),
                              "longest_action_s":
                                  max(window.get("action_s") or [0.0]),
                              "compared": result.get("compared")}),
                  flush=True)
            if rc != 0:
                print(f"run {tag} gave no result", file=sys.stderr)
                return 1
            if not result.get("correct"):
                # the driver refuses a check over one such run; the sets
                # go on without it, so that a call is not lost to it
                print(f"run {tag} is not correct: left out of its set",
                      file=sys.stderr)
                not_correct += 1
                continue
            for k, v in result["metrics"].items():
                values.setdefault(k, []).append(v["value"])
        sets.append(values)
    for name in sets[0]:
        if all(len(v[name]) >= 2 for v in sets):
            print(json.dumps(summary(name, [v[name] for v in sets])),
                  flush=True)
    return 1 if not_correct else 0


if __name__ == "__main__":
    sys.exit(main())
