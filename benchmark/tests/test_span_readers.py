"""The per-layer readers of the program's span tree (PR 25), on hand-built
trees: two task threads that overlap (a union is not a sum), a tree
without the reader's spans (None, so a program without them leaves the
metric out), a failed sample (skipped)."""

from types import SimpleNamespace

import pytest

from lib import harness, loop, spans
from spark_rapids_tpu.obs.trace import QueryTrace, Span

MS = 1_000_000


def span(name, start_ms, end_ms, children=(), kind="site", **attrs):
    sp = Span(name, kind, start_ms * MS, attrs)
    sp.end_ns = end_ms * MS
    sp.children = list(children)
    return sp


def action(children, error=""):
    tree = None if children is None else QueryTrace(
        span("query:x", 0, 100, children, kind="query"), "default")
    return loop.Sample(0.0, 0.1, SimpleNamespace(spans=tree), error)


def two_threads(scale=1):
    """What one action leaves: two tasks side by side. Task p0 holds the
    permit and scans 10-50 ms; p1 waits for it 10-50, then scans 50-70;
    both download and write at the end, overlapping 80-90."""
    s = scale

    def scan(t0, t1, column):
        mid = (t0 + t1) / 2
        return [span("scan.read", t0, t0 + 2 * s, column=column, bytes=1000),
                span("scan.decode", t0 + 2 * s, t1, [
                    span("scan.parse", t0 + 2 * s, mid, bytes_out=4_000_000),
                    span("scan.upload", mid, mid + s, bytes=4_000_000)],
                    column=column, codec="SNAPPY", pages=3)]

    p0 = span("task:p0", 5, 95, [
        span("scan.split", 5, 8, path="f0", row_groups=1),
        span("Acquire TPU Semaphore", 8, 10, kind="op"),
        span("scan.rowgroup", 10, 50, scan(10, 30, "a") + scan(30, 50, "b"),
             path="f0", rg=0, rows=10),
        span("DeviceToHost", 70, 80, kind="op", bytes=3_000_000, batches=1),
        span("write.arrow", 80, 82),
        span("write.file", 82, 90, encoder="arrow", rows=10, bytes=500)],
        kind="task")
    p1 = span("task:p1", 5, 99, [
        span("scan.split", 5, 9, path="f1", row_groups=1),
        span("Acquire TPU Semaphore", 10, 50, kind="op"),
        span("scan.rowgroup", 50, 74, scan(50, 70, "a") + [
            span("scan.host_decode", 70, 72, columns=3),
            span("scan.upload", 72, 74, columns=3, bytes=8_000_000)],
            path="f1", rg=0, rows=10),
        span("DeviceToHost", 75, 85, kind="op", bytes=2_000_000, batches=2),
        span("write.arrow", 85, 86),
        span("write.file", 86, 98, encoder="arrow", rows=10, bytes=700)],
        kind="task")
    return [span("plan", 0, 5, kind="stage"),
            span("stage:write", 5, 99, [p0, p1], kind="stage"),
            span("write.commit", 99, 100)]


# the value one action of two_threads() gives each reader
EXPECTED = {
    # splits 5-9 (union of 5-8 and 5-9); reads, decodes, Arrow's columns
    # and their upload 10-74: never the 3 + 4 + 40 + 24 ms of thread time
    "scan.host_ms": 4 + 64,
    "scan.upload_MB": 20.0,
    "device.permit_wait_ms": 2 + 40,       # thread time, added up
    "sink.download_ms": 15,                # 70-85, not 10 + 10
    "sink.download_MB": 5.0,
    "sink.host_ms": (98 - 80) + 1,         # 80-98 and the commit
    "planner.plan_ms": 5,
}


def run_of(samples):
    return SimpleNamespace(samples=samples)


@pytest.mark.parametrize("metric", sorted(EXPECTED))
def test_reader_on_overlapping_threads(metric):
    read = harness.load_reader("layer_metrics", metric)
    # a failed action is skipped whatever it left; one without a tree too
    samples = [action(two_threads()), action(two_threads(), error="boom"),
               action(None), action(two_threads())]
    assert read(run_of(samples)) == pytest.approx(EXPECTED[metric])
    # the quantity's twin is read by the same file
    twin = harness.load_reader("layer_metrics", metric + ".write")
    assert twin.__code__.co_filename == read.__code__.co_filename


@pytest.mark.parametrize("metric", sorted(EXPECTED))
def test_reader_finds_nothing_to_read(metric):
    read = harness.load_reader("layer_metrics", metric)
    other = [span("stage:result", 0, 90, [
        span("task:p0", 0, 90, [span("TpuFusedStage", 1, 2, kind="op")],
             kind="task")], kind="stage")]
    cases = [[action(None)],                       # no tree (the parent)
             [action(two_threads(), error="x")],  # only failed actions
             [action(other), action(other)]]      # no such span
    for samples in cases:
        assert read(run_of(samples)) is None


def test_plan_ms_without_a_plan_span():
    """A tree without a `plan` span gives nothing, not 0 (PR 24's reader
    gave 0); beside trees that have one it counts as 0, as every span
    reader counts an action that lacks its span."""
    read = harness.load_reader("layer_metrics", "planner.plan_ms")
    no_plan = [span("stage:result", 0, 90, kind="stage")]
    assert read(run_of([action(no_plan)] * 3)) is None
    assert read(run_of([action(two_threads()), action(two_threads()),
                        action(no_plan)])) == pytest.approx(5)
    # a `plan` span that never closed is not read
    open_plan = span("plan", 0, 5, kind="stage")
    open_plan.end_ns = None
    assert read(run_of([action([open_plan])])) is None


def test_attr_readers_need_the_attr():
    """A DeviceToHost span of a program that does not record `bytes` yet
    (the parent commit) is time to sink.download_ms and nothing to
    sink.download_MB."""
    bare = [span("DeviceToHost", 10, 30, kind="op")]
    run = run_of([action(bare)])
    assert harness.load_reader("layer_metrics", "sink.download_ms")(run) \
        == pytest.approx(20)
    assert harness.load_reader("layer_metrics", "sink.download_MB")(run) \
        is None


def test_median_is_over_actions_and_zero_counts():
    """An action with a tree but none of the spans counts as 0 once some
    action has them (a Q6 action whose tasks never waited for a permit)."""
    read = harness.load_reader("layer_metrics", "device.permit_wait_ms")
    none = [span("stage:result", 0, 10, kind="stage")]
    samples = [action(none), action(two_threads()), action(none)]
    assert read(run_of(samples)) == 0
    samples = [action(two_threads()), action(two_threads(2)), action(none)]
    assert read(run_of(samples)) == pytest.approx(42)


def test_union_ms():
    s = [span("a", 0, 10), span("a", 5, 12), span("a", 20, 21),
         span("a", 6, 7)]
    assert spans.union_ms(s) == 13
    assert spans.total_ms(s) == 19
    assert spans.union_ms([]) == 0


def test_new_entries_are_declared_as_the_issue_says(bench):
    new = {m["name"]: m for m in bench["per_layer"]
           if m["source"] == "program_span"}
    for quantity in EXPECTED:
        twin = new[quantity + ".write"]
        assert twin["moves"] == "rows_per_s.write"
        assert twin["unit"] == ("MB" if quantity.endswith("MB") else "ms")
    for plain in ("scan.host_ms", "scan.upload_MB", "device.permit_wait_ms",
                  "sink.download_ms", "planner.plan_ms"):
        assert new[plain]["moves"] == "query_s"
    assert "sink.host_ms" not in new and "sink.download_MB" not in new
    cells = {"q6_scan": set(), "lineitem_write_slim": set()}
    for cell in cells:
        cells[cell] = {m["name"] for m in
                       harness.metrics_of(bench, "per_layer", cell)}
    assert not any(n.endswith(".write") for n in cells["q6_scan"])
    assert {n + ".write" for n in EXPECTED} <= cells["lineitem_write_slim"]
