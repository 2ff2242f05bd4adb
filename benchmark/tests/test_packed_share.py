"""The reader of `scan.packed_share` (PR 26) on hand-built span trees: no
tree or no `scan.decode` span, spans without the `expand` attr (the parent
commit: nothing, not 0), chunks in both forms, and the two entries of
BENCHMARK.json that the one file reads."""

import pytest

from lib import harness
from test_span_readers import action, run_of, span, two_threads


def decodes(*forms):
    """One action's tree: a `scan.decode` span a chunk, `expand` set where
    a form is given."""
    chunks = [span("scan.decode", 10 * i, 10 * i + 9, column=f"c{i}",
                   codec="SNAPPY", pages=2,
                   **({"expand": f} if f else {}))
              for i, f in enumerate(forms)]
    return [span("stage:result", 0, 99, [
        span("task:p0", 0, 99, [span("scan.rowgroup", 0, 95, chunks)],
             kind="task")], kind="stage")]


@pytest.fixture(scope="module")
def read():
    return harness.load_reader("layer_metrics", "scan.packed_share")


@pytest.mark.parametrize("samples", [
    [action(None)],                                   # no tree
    [action(decodes("packed"), error="boom")],        # only a failed action
    [action([span("plan", 0, 5, kind="stage")])],     # no scan.decode span
    [action(two_threads()), action(two_threads())],   # spans, no attr
    [action(decodes(None, None))],
], ids=["no_tree", "failed_only", "no_span", "parents_spans", "attr_missing"])
def test_nothing_to_read(read, samples):
    assert read(run_of(samples)) is None


@pytest.mark.parametrize("forms, percent", [
    (("packed",) * 12, 100.0),
    (("runs",) * 12, 0.0),
    (("packed", "packed", "runs", "packed"), 75.0),
    # a chunk on the per-page loop names no form and is not counted
    (("packed", None, "runs", None), 50.0),
    # a PLAIN chunk names its form, and it is not the gather-free one
    (("packed", "plain", "packed", "plain"), 50.0),
    (("plain",) * 3, 0.0),
])
def test_share_of_the_chunks_that_name_a_form(read, forms, percent):
    assert read(run_of([action(decodes(*forms))])) == pytest.approx(percent)


def test_median_is_over_the_actions_with_a_tree(read):
    samples = [action(decodes("packed", "packed")),
               action(decodes("packed", "runs")),
               action(decodes("runs", "runs"), error="x"),  # skipped
               action(None),                                 # skipped
               action(decodes("packed", "runs"))]
    assert read(run_of(samples)) == pytest.approx(50.0)
    # an action whose chunks name no form counts as 0 once another does
    samples = [action(decodes(None)), action(decodes("packed")),
               action(decodes(None))]
    assert read(run_of(samples)) == 0.0


def test_the_two_entries_and_their_cells(bench, read):
    entries = {m["name"]: m for m in bench["per_layer"]
               if m["name"].startswith("scan.packed_share")}
    assert sorted(entries) == ["scan.packed_share", "scan.packed_share.write"]
    q6, write = entries["scan.packed_share"], entries["scan.packed_share.write"]
    assert q6["workloads"] == ["q6_scan"] and q6["moves"] == "query_s"
    assert write["workloads"] == ["lineitem_write_slim"]
    assert write["moves"] == "rows_per_s.write"
    for m in (q6, write):
        assert (m["unit"], m["better"], m["source"], m["layer"]) == \
            ("%", "higher", "program_span", "scan and device decode")
    # the twin is read by the quantity's one file
    twin = harness.load_reader("layer_metrics", "scan.packed_share.write")
    assert twin.__code__.co_filename == read.__code__.co_filename
    # and they are the last of the list: appended, nothing moved
    assert [m["name"] for m in bench["per_layer"]][-2:] == \
        ["scan.packed_share", "scan.packed_share.write"]
    for cell, name in (("q6_scan", "scan.packed_share"),
                       ("lineitem_write_slim", "scan.packed_share.write")):
        mine = [m["name"] for m in harness.metrics_of(bench, "per_layer", cell)
                if m["name"].startswith("scan.packed_share")]
        assert mine == [name]
