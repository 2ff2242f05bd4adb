"""The control of "How correct is decided": the reference put in the
program's place and computed in bfloat16, the precision below the float32
that both configurations state, comes out as not correct for every action;
the same reference in float32 comes out correct. At a size a test can
hold; tools/control.py reads the same numbers at the cells' own size."""

import numpy as np
from ml_dtypes import bfloat16

from lib import compare as C
from lib import harness


def worst_rel_err(numbers):
    return max(n["value"] for n in numbers if n["name"].endswith("max_rel_err"))


def test_query_in_bf16_fails_and_in_f32_passes(arrays):
    action = harness.load_module("actions", "q6")
    want = action.reference(arrays)
    f32 = action.compare(want, [action.reference(arrays, np.float32)])[0]
    assert C.holds(f32), f32
    low = action.compare(want, [action.reference(arrays, bfloat16)])[0]
    assert not C.holds(low)
    assert worst_rel_err(low) > 10 * C.FLOAT_RTOL > 100 * worst_rel_err(f32)


def test_written_values_in_bf16_fail(arrays):
    action = harness.load_module("actions", "write_lineitem_slim")
    want = action.reference(arrays)
    f32 = C.rows(want["digest"],
                 action.reference(arrays, np.float32)["digest"], "w")
    low = C.rows(want["digest"],
                 action.reference(arrays, bfloat16)["digest"], "w")
    assert C.holds(f32) and not C.holds(low)
    assert worst_rel_err(low) > 10 * C.FLOAT_RTOL
