"""`harness.owner` labels a moment with what a thread of the action is
in (an open span with no open child): one that works before one that
waits, then the deepest, then the one begun last; on hand-built trees
with the shapes the two cells leave (docs/observability.md draws the
scan's)."""

from types import SimpleNamespace

import pytest

from lib import harness
from spark_rapids_tpu.obs.trace import QueryTrace
from test_span_readers import MS, span


def record(children, start_ms=0, end_ms=100):
    tree = None if children is None else QueryTrace(
        span("query:x", start_ms, end_ms, children, kind="query"), "default")
    return SimpleNamespace(start_ns=start_ms * MS, end_ns=end_ms * MS,
                           spans=tree)


def q6_task(n, decode_end, wait_end, upload_end):
    """A task of a Q6 action as the host scan leaves it: the reader's
    `scan.host_decode`, the wait for the permit, the upload, and LAST
    among the children (it is noted when the prefetcher closes) the
    prefetcher's own span, open from the reader's start to its end."""
    return span(f"task:p{n}", 2, upload_end + 1, [
        span("scan.host_decode", 3, decode_end, columns=4, rows=1 << 20),
        span("Acquire TPU Semaphore", decode_end, wait_end, kind="op"),
        span("scan.upload", wait_end, upload_end, columns=4, bytes=1 << 20),
        span("prefetch:scan-prefetch", 2, upload_end, depth=1, items=1)],
        kind="task")


def q6_action():
    """Three tasks, two permits: all readers decode until 50 ms (the
    head); p0 and p1 upload at once, p2 queues for a permit behind them."""
    return [span("plan", 0, 2, kind="stage"),
            span("stage:result", 2, 80, [
                q6_task(0, 50, 50, 58), q6_task(1, 52, 52, 60),
                q6_task(2, 51, 58, 64)], kind="stage")]


@pytest.mark.parametrize("at_ms, label", [
    (1, "stage:plan"),
    # the head: every reader inside scan.host_decode, every prefetcher's
    # span open beside it at the same depth
    (30, "site:scan.host_decode"),
    # p0 uploads, p1 still decodes, p2 waits for the permit
    (51.5, "site:scan.upload"),
    # p0 and p1 upload, p2 queues: the wait does not hide the holders
    (55, "site:scan.upload"),
    # only p2 is left, uploading; its prefetcher's span is open too
    (62, "site:scan.upload"),
    # after the last task: the stage
    (70, "stage:stage:result"),
    (90, "query:query:x"),
])
def test_owner_at_the_moments_of_a_q6_action(at_ms, label):
    assert harness.owner([record(q6_action())], at_ms * MS, "q6") == label


def test_where_every_thread_waits_the_wait_is_the_label():
    waiting = [span("stage:write", 0, 90, [
        span("task:p0", 0, 90, [
            span("Acquire TPU Semaphore", 10, 40, kind="op")], kind="task"),
        span("task:p1", 0, 90, [
            span("prefetch:scan-prefetch", 5, 60)], kind="task")],
        kind="stage")]
    rec = record(waiting)
    # both wait and are as deep: the one begun last
    assert harness.owner([rec], 20 * MS, "w") == "op:Acquire TPU Semaphore"
    # p0 has its permit and is in no step: the task itself works
    assert harness.owner([rec], 50 * MS, "w") == "task:task:p0"


def test_a_wait_that_lies_deeper_hides_no_step():
    """A task that queues for the permit deep inside its upstream does not
    hide another task's file write, which lies shallower."""
    tree = [span("stage:write", 0, 90, [
        span("task:p0", 0, 90, [span("write.file", 10, 80)], kind="task"),
        span("task:p1", 0, 90, [span("write.collect", 5, 85, [
            span("scan.rowgroup", 6, 84, [
                span("Acquire TPU Semaphore", 7, 83, kind="op")])])],
             kind="task")], kind="stage")]
    assert harness.owner([record(tree)], 40 * MS, "w") == "site:write.file"
    # the file is closed: p0 is in no step of its own, and still works
    assert harness.owner([record(tree)], 82 * MS, "w") == "task:task:p0"


def test_a_deeper_step_wins_over_a_shallower_one():
    """The write cell: the permit holder's fence lies deeper than the
    queued tasks' waits, and a span that never closed is passed over."""
    never_closed = span("write.file", 30, 60)
    never_closed.end_ns = None
    write = [span("stage:write", 0, 90, [
        span("task:p0", 0, 90, [span("write.collect", 5, 80, [
            span("DeviceToHost", 20, 70, kind="op", bytes=1, batches=1)]),
            never_closed], kind="task"),
        span("task:p1", 0, 90, [
            span("Acquire TPU Semaphore", 5, 85, kind="op")], kind="task")],
        kind="stage")]
    assert harness.owner([record(write)], 40 * MS, "w") == "op:DeviceToHost"
    assert harness.owner([record(write)], 75 * MS, "w") == "site:write.collect"


def test_outside_the_actions_and_without_a_tree():
    recs = [record(None, 0, 100), record(q6_action(), 200, 300)]
    assert harness.owner(recs, 50 * MS, "q6") == "q6"
    assert harness.owner(recs, 150 * MS, "q6") == "between actions"


def test_breakdown_keeps_its_keys_and_format():
    rec = record(q6_action())
    run = SimpleNamespace(
        samples=[SimpleNamespace(record=rec, error="")],
        cell={"action": "q6"},
        trace={"action_s": [0.1], "action_start_ns": 7_000 * MS,
               "idle_gaps_ns": [(7_010 * MS, 7_050 * MS),
                                (7_053 * MS, 7_057 * MS)],
               "device_ops": [["fusion", 0.0005]]})
    out = harness.breakdown(run)
    assert set(out) == {"device_ops", "idle_gaps"}
    assert out["device_ops"] == [["fusion", 0.0005]]
    assert out["idle_gaps"] == [["site:scan.host_decode", 0.04],
                                ["site:scan.upload", 0.004]]
