#!/usr/bin/env python3
"""One cell of BENCHMARK.json, once, in one process, on the chip.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Needs a TPU with as many chips as the cell asks for: with any other
platform or device count it says why on stderr and exits non-zero, with no
result line (there is no CPU continuation; benchmark/tests rehearse the
phases on the CPU). The last line of stdout is the one JSON object of the
contract: correct, attempted, failed, metrics, device, breakdown in a
traced run, and last `compared`, each number compared at its worst over
the window beside its limit (the last lines of stderr say the same).
Progress goes to stderr, everything else worth keeping to
earlier lines of stdout. See benchmark/README.md.
"""

import time

T_START = time.perf_counter()  # before anything heavy is imported

import argparse  # noqa: E402
import faulthandler  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
# the contract allows a checkout's first run 1200 s, compilation included:
# a run still going shortly before that says where every thread stands
DEADLINE_S = 1170


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    faulthandler.dump_traceback_later(DEADLINE_S, exit=True)

    # a cache placed from outside (JAX_COMPILATION_CACHE_DIR) keeps jax's
    # one-second threshold unless told otherwise, and this engine is
    # hundreds of programs that compile faster than that
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    for p in (ROOT, HERE):
        if p not in sys.path:
            sys.path.insert(0, p)
    from lib import harness

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    try:
        result = harness.run_cell(bench, args.workload, args.seed,
                                  args.seconds, bool(args.trace), T_START)
    except harness.BenchFailure as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 1
    faulthandler.cancel_dump_traceback_later()
    # the last lines of stderr: each number compared beside its limit
    for name, (value, limit) in result["compared"].items():
        print(f"compared {name}: {value!r} (limit {limit!r})",
              file=sys.stderr)
    print(f"correct: {result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
