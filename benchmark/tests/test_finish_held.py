"""`sink.finish_held_ms` (PR 44) on hand-made trees, as
test_host_readers.py tests PR 43's readers: the union over two tasks of
the `sink.finish` spans whose `permit_held` is true or absent (the
parent's program records no attr, and every finish of it held the
permit), nothing of one whose attr is false, None where no action has a
`sink.finish`; the entry behind what `per_layer` held, found by name;
and a traced write cell's line through `measure()`."""

import pytest

from lib import harness
from test_host_readers import (CHIP_SINK, WRITES, action, read,
                               rehearse_traced)  # noqa: F401 (a fixture)
from test_span_readers import span

METRIC = "sink.finish_held_ms"
# what `per_layer` held when PR 44 added to it
ENTRIES_BEFORE = 50


def fence(t0, t1, **finish_attrs):
    """A fence t0 to t1 whose last three ms are its `sink.finish`."""
    return span("DeviceToHost", t0, t1, [
        span("sink.pack", t0, t0 + 1), span("sink.wait", t0 + 1, t0 + 2),
        span("sink.transfer", t0 + 2, t1 - 3, bytes=5),
        span("sink.finish", t1 - 3, t1, **finish_attrs)], kind="op")


def write_action(*finish_attrs, error=""):
    """Three tasks side by side whose fences end at 50, 51 and 60: their
    finishes lie at 47-50, 48-51 and 57-60."""
    ends = (50, 51, 60)
    tasks = [span(f"task:p{i}", 6, 90, [fence(40 + i, end, **attrs)],
                  kind="task")
             for i, (end, attrs) in enumerate(zip(ends, finish_attrs))]
    return action([span("stage:write", 6, 96, tasks, kind="stage")],
                  error=error)


HELD, FREE, OLD = {"permit_held": True}, {"permit_held": False}, {}


@pytest.mark.parametrize("attrs, expected", [
    ((HELD, HELD, HELD), 4 + 3),        # 47-51 (a union, not 3 + 3), 57-60
    ((OLD, OLD, OLD), 4 + 3),           # absent counts as held: the parent
    ((HELD, FREE, OLD), 3 + 3),         # 47-50 and 57-60
    ((FREE, HELD, FREE), 3),
    ((FREE, FREE, FREE), 0.0),          # the change: spans, none held
])
def test_union_of_the_finishes_that_held_the_permit(attrs, expected):
    samples = [write_action(*attrs), write_action(*attrs, error="boom"),
               write_action(*attrs)]
    assert read(METRIC, samples) == pytest.approx(expected)
    assert read(METRIC + ".write", samples) == pytest.approx(expected)


def test_median_over_the_actions_and_unclosed_spans_left_out():
    samples = [write_action(HELD, HELD, HELD), write_action(FREE, FREE, FREE),
               write_action(HELD, FREE, FREE)]
    assert read(METRIC, samples) == pytest.approx(3)
    left_open = write_action(HELD, HELD, HELD)
    for sp in left_open.record.spans.spans():
        if sp.name == "sink.finish":
            sp.end_ns = None
    assert read(METRIC, [left_open]) is None


def test_nothing_where_no_action_has_a_finish():
    """PR 41's trees: a `DeviceToHost` without children; a query with no
    tree; only failed actions."""
    bare = action([span("stage:write", 6, 96, [
        span("task:p0", 6, 90, [span("DeviceToHost", 40, 50, kind="op")],
             kind="task")], kind="stage")])
    no_tree = write_action(HELD, HELD, HELD)
    no_tree.record.spans = None
    for samples in ([bare] * 3, [no_tree],
                    [write_action(HELD, HELD, HELD, error="x")]):
        assert read(METRIC, samples) is None


def test_the_entry_stands_behind_what_was_there(bench):
    """The driver takes new entries at the END of a list alone. Held by
    where it begins, not as "the last": the next PR appends behind it."""
    m = bench["per_layer"][ENTRIES_BEFORE]
    assert m == {"name": METRIC + ".write", "unit": "ms", "better": "lower",
                 "source": "program_span", "layer": "sink and writer",
                 "moves": "rows_per_s.write", "workloads": WRITES}
    assert METRIC + ".write" not in {
        e["name"] for e in bench["per_layer"][:ENTRIES_BEFORE]}
    found = harness.load_reader("layer_metrics", m["name"])
    assert found.__code__.co_filename.endswith(f"layer_metrics/{METRIC}.py")
    for cell in ("q6_scan", "q1_agg") + tuple(WRITES):
        mine = {e["name"] for e in harness.metrics_of(bench, "per_layer",
                                                      cell)}
        assert (m["name"] in mine) == (cell in WRITES)


def test_a_traced_write_cell_reports_it(rehearse_traced):  # noqa: F811
    """The program's own trees: every task's one fence is its last, so
    no finish of the action holds the permit, and the line says 0."""
    m = rehearse_traced("lineitem_write_slim", CHIP_SINK)
    assert m[METRIC + ".write"] == 0.0
    assert m["sink.fences.write"] == 4 and m["sink.download_ms.write"] > 0
