"""`operators.concat_ms` (PR 38) on hand-built trees, as
test_span_readers.py tests its readers: the union of an action's
`coalesce-concat` spans, which stay open while the consumer takes the
batch (the merge and the sorts nest inside them); nothing on a tree
without the span; and the entry declared as ISSUE 38 says."""

import pytest

from lib import harness
from test_span_readers import action, run_of, span

METRIC = "operators.concat_ms"


def q1_action(final_ms, attrs=True):
    """What a Q1 action leaves behind its scan: the final aggregate's one
    task, whose concat of 64 slices holds the merge; then the result
    stage's tasks side by side, a one-piece concat around each sort."""
    said = dict(pieces=64, operands=832, programs=32) if attrs else {}
    one = dict(pieces=1, operands=0, programs=0) if attrs else {}
    final = span("task:p0", 100, 105 + final_ms, [
        span("coalesce-concat", 101, 101 + final_ms, [
            span("TpuHashAggregate.merge", 99 + final_ms, 101 + final_ms,
                 kind="op")], kind="op", **said)], kind="task")
    sorts = [span(f"task:p{i}", 200, 212, [
        span("coalesce-concat", lo, hi, [
            span("TpuSort", lo + 1, hi, kind="op")], kind="op", **one)],
        kind="task") for i, (lo, hi) in enumerate([(200, 208), (202, 211)])]
    return [span("plan", 0, 3, kind="stage"),
            span("stage:map:agg", 100, 110 + final_ms, [final],
                 kind="stage"),
            span("stage:result", 200, 215, sorts, kind="stage")]


def test_concat_ms_is_the_union_of_an_actions_concat_spans():
    read = harness.load_reader("layer_metrics", METRIC)
    # 53 ms in the final task, then 200-211 in the result stage: the two
    # sorts' spans overlap 202-208 and count once
    assert read(run_of([action(q1_action(53))])) == pytest.approx(53 + 11)
    # the median over the actions that left a tree and did not fail
    samples = [action(q1_action(53)), action(q1_action(7)),
               action(q1_action(9)), action(q1_action(99), error="boom"),
               action(None)]
    assert read(run_of(samples)) == pytest.approx(9 + 11)
    # the attrs are for a reader of the tree; the metric is time alone,
    # so the parent's spans, which carry none, read by the same rule
    assert read(run_of([action(q1_action(53, attrs=False))])) \
        == pytest.approx(53 + 11)


def test_concat_ms_finds_nothing_to_read():
    read = harness.load_reader("layer_metrics", METRIC)
    other = [span("stage:result", 0, 90, [
        span("task:p0", 0, 90, [span("TpuFusedStage", 1, 2, kind="op")],
             kind="task")], kind="stage")]
    open_span = span("coalesce-concat", 1, 2, kind="op")
    open_span.end_ns = None
    for samples in ([action(None)], [action(q1_action(5), error="x")],
                    [action(other)], [action([open_span])]):
        assert read(run_of(samples)) is None
    # an action without a concat counts as 0 beside those that have one
    assert read(run_of([action(other), action(q1_action(5)),
                        action(other)])) == 0


def test_the_entry_is_declared_as_the_issue_says(bench):
    entry = bench["per_layer"][-1]
    assert entry == {
        "name": METRIC, "unit": "ms", "better": "lower",
        "source": "program_span", "layer": "operator programs",
        "moves": "query_s", "workloads": ["q1_agg"]}
    for cell in ("q6_scan", "lineitem_write_slim"):
        assert METRIC not in {m["name"] for m in
                              harness.metrics_of(bench, "per_layer", cell)}
    assert METRIC in {m["name"] for m in
                      harness.metrics_of(bench, "per_layer", "q1_agg")}
