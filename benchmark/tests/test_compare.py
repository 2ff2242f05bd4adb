"""The comparison fails on a result of the next precision down and on a
written directory with a row missing, and passes on float32's noise."""

import os

import numpy as np
import pytest
from ml_dtypes import bfloat16

from lib import compare as C
from lib import harness


def test_rows_exact_keys_and_float_tolerance():
    want = [("A", "F", 10.0, 3), ("N", "O", 2.5e7, 4)]
    ok = [("A", "F", 10.0 * (1 + 5e-6), 3), ("N", "O", 2.5e7, 4)]
    assert C.holds(C.rows(want, ok, "t"))
    for bad in ([("A", "F", 10.0 * (1 + 2e-5), 3), want[1]],   # a float off
                [("A", "O", 10.0, 3), want[1]],                # a key
                [("A", "F", 10.0, 4), want[1]],                # a count
                want[:1],                                      # a row missing
                want + want[:1],                               # one too many
                [("A", "F", float("nan"), 3), want[1]]):
        numbers = C.rows(want, bad, "t")
        assert not C.holds(numbers), bad
        assert {n["name"] for n in numbers} == {"t.rows_differ",
                                                "t.max_rel_err"}


def test_a_result_rounded_to_bf16_is_not_correct():
    want = [(123456.789,)]
    assert C.holds(C.rows(want, [(float(np.float32(123456.789)),)], "q6"))
    got = [(float(np.asarray(123456.789).astype(bfloat16)),)]
    worst = C.rows(want, got, "q6")[1]
    assert worst["value"] > 10 * C.FLOAT_RTOL and not C.holds([worst])


def test_counters_of_an_action():
    good = {"deviceDispatches": 54, "cpuFallbackEvents": 0}
    assert C.holds(C.counters(good, "a"))
    for key in C.MUST_BE_ZERO:
        assert not C.holds(C.counters(dict(good, **{key: 1}), "a"))
    assert not C.holds(C.counters({"deviceDispatches": 0}, "a"))


def reference_frame(arrays):
    """The rows a sound write of the action holds, as a pandas frame."""
    import pandas as pd

    from lib import frames

    li = frames.frame(arrays, "lineitem", ("l_shipdate", "l_quantity",
                                           "l_extendedprice", "l_discount"))
    li = li.assign(
        revenue=li["l_extendedprice"] * li["l_discount"],
        disc_price=li["l_extendedprice"] * (1.0 - li["l_discount"]))
    li["l_shipdate"] = pd.to_datetime(li["l_shipdate"], unit="D").dt.date
    return li


@pytest.fixture(scope="module")
def written(arrays, tmp_path_factory):
    """The reference's rows written by pyarrow, whole and with the last
    row missing: what a write action's comparison reads back."""
    import pyarrow as pa
    import pyarrow.parquet as pq

    frame = reference_frame(arrays)
    root = tmp_path_factory.mktemp("written")
    dirs = {"action": "write_lineitem_slim"}
    for name, part in (("whole", frame), ("short", frame.iloc[:-1])):
        dirs[name] = str(root / name)
        os.makedirs(dirs[name])
        half = len(part) // 2
        for i, piece in enumerate((part.iloc[:half], part.iloc[half:])):
            pq.write_table(pa.Table.from_pandas(piece, preserve_index=False),
                           os.path.join(dirs[name], f"part-{i}.parquet"))
    return dirs


def test_a_write_with_a_row_missing_is_not_correct(arrays, written):
    action = harness.load_module("actions", written["action"])
    expected = action.reference(arrays)
    whole, short = action.compare(expected, [written["whole"],
                                             written["short"]])
    assert C.holds(whole)
    assert not C.holds(short)
    off = {n["name"]: n["value"] for n in short}
    assert off["write.row_count_off"] == 1
    assert off["write.digest.rows_differ"] >= 1   # a group's count is exact
    # an empty directory is no write at all
    empty = os.path.join(os.path.dirname(written["whole"]), "empty")
    os.makedirs(empty, exist_ok=True)
    assert not C.holds(action.compare(expected, [empty])[0])
