#!/usr/bin/env python3
"""The control of "How correct is decided", at a cell's own size: the
reference put in the program's place and computed in the precision below
the one the configuration states (bfloat16 below float32), on several
seeds. Prints, for each seed, the widest relative gap the comparison would
read, beside the limit. Host only: it touches neither jax nor the program.

    python3 benchmark/tools/control.py --workload <cell> --seeds 1,2,3

tests/test_control.py keeps the same control at a size a test can hold.
"""

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)

import numpy as np  # noqa: E402
from ml_dtypes import bfloat16  # noqa: E402

from lib import compare, harness, tpch_gen  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    bench = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    _entry, config, cell = harness.load_cell(bench, args.workload)
    action = harness.load_module("actions", cell["action"])
    for seed in (int(s) for s in args.seeds.split(",")):
        arrays = tpch_gen.gen_tables(config["scale_factor"], seed,
                                     cell["tables"])
        want = action.reference(arrays)
        line = {"cell": args.workload, "seed": seed,
                "limit": compare.FLOAT_RTOL}
        for label, dtype in (("float32", np.float32), ("bfloat16", bfloat16)):
            got = action.reference(arrays, dtype)
            if isinstance(want, dict):      # a write's digest
                want_rows, got_rows = want["digest"], got["digest"]
            else:
                want_rows, got_rows = want, got
            numbers = compare.rows(want_rows, got_rows, label)
            line[label] = {n["name"]: n["value"] for n in numbers}
            line[label + "_correct"] = compare.holds(numbers)
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
