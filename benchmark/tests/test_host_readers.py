"""The seven readers of PR 43 (busy against waiting on the host) on
hand-made trees, as test_span_readers.py tests the earlier ones: a union
over two reader threads that overlap, None for a tree without the span
or the attr (the parent's: its `Span` has no `cpu_ns` at all), the
scheduler's gap of a tree whose tasks cover all but a known stretch, a
write's tail with and without a fence; and the eleven entries, behind
what `per_layer` held, each found by name. (ISSUE 43's
`host.throttled_ms` and `host.slow_action_cpu_ratio` are not declared:
the chip's host has no cgroup `cpu.stat`, and charges a thread CPU for
the time the machine takes from it: PERF.md section 7.)"""

import math
import time
from types import SimpleNamespace

import pytest

from conftest import CPU_DEVICE, SF
from lib import harness, hostclock, loop, xplane
from spark_rapids_tpu.obs.trace import QueryTrace
from test_run import TINY
from test_span_readers import MS, run_of, span

READERS = ["host.cpu_ms", "scheduler.gap_ms", "scan.arrow_read_ms",
           "scan.oncpu_share", "sink.fence_wait_ms", "sink.transfer_ms",
           "sink.tail_ms"]
QUERIES = ["q6_scan", "q1_agg"]
WRITES = ["lineitem_write_slim", "lineitem_write7"]
# the entries in the order ISSUE 43 gives them: (name, cells)
ENTRIES = [("host.cpu_ms", QUERIES), ("host.cpu_ms.write", WRITES),
           ("scheduler.gap_ms", QUERIES), ("scheduler.gap_ms.write", WRITES),
           ("scan.arrow_read_ms", QUERIES),
           ("scan.arrow_read_ms.write", WRITES),
           ("scan.oncpu_share", QUERIES), ("scan.oncpu_share.write", WRITES),
           ("sink.fence_wait_ms.write", WRITES),
           ("sink.transfer_ms.write", WRITES),
           ("sink.tail_ms.write", WRITES)]


def cpu(sp, cpu_ms):
    sp.cpu_ns = None if cpu_ms is None else int(cpu_ms * MS)
    return sp


def action(children, wall_ms=100, error="", **account):
    """One action's sample: the root spans 0 to `wall_ms` and carries
    `account` as the process's account."""
    root = span("query:x", 0, wall_ms, children, kind="query", **account)
    return loop.Sample(0.0, wall_ms / 1e3,
                       SimpleNamespace(spans=QueryTrace(root, "default")),
                       error)


def read(metric, samples):
    return harness.load_reader("layer_metrics", metric)(run_of(samples))


def write_action(wall_ms=100, fences=True, **account):
    """A write: the plan 0-4, then two tasks side by side under
    `stage:write` 6-96 whose readers overlap, whose fences (where it has
    any) end at 60 and 70, and whose files close at 96; the commit
    97-99. No working span is open over 4-6, 96-97 or 99-100."""
    def reader(t0, read_ms, convert_ms, pack_ms, oncpu):
        a, b, c = t0 + read_ms, t0 + read_ms + convert_ms, \
            t0 + read_ms + convert_ms + pack_ms
        return span("scan.host_decode", t0, c, [
            cpu(span("scan.arrow_read", t0, a), 0.5),
            cpu(span("scan.convert", a, b), convert_ms * oncpu),
            cpu(span("scan.pack", b, c, packed_bytes=1000),
                pack_ms * oncpu)], columns=4)

    def fence(t0, wait_ms, transfer_ms):
        a, b, c = t0 + 1, t0 + 1 + wait_ms, t0 + 1 + wait_ms + transfer_ms
        return span("DeviceToHost", t0, c + 2, [
            span("sink.pack", t0, a), span("sink.wait", a, b),
            span("sink.transfer", b, c, bytes=5_000_000),
            span("sink.finish", c, c + 2)], kind="op", bytes=6_000_000)

    p0 = span("task:p0", 6, 90, [
        # Arrow 6-26, then 10 ms of convert and 4 of pack, all on a core
        reader(6, 20, 10, 4, 1.0),
        span("scan.upload", 41, 44, bytes=9_000_000)]
        + ([fence(48, 3, 6)] if fences else [])     # 48-60: wait 49-52
        + [span("write.file", 62, 90, encoder="arrow")], kind="task")
    p1 = span("task:p1", 8, 96, [
        # Arrow 16-36 (overlaps p0's 6-26: the union is 6-36), then 20 ms
        # of convert and 6 of pack at half a core
        reader(16, 20, 20, 6, 0.5),
        span("Acquire TPU Semaphore", 62, 63, kind="op")]
        + ([fence(64, 1, 2)] if fences else [])     # 64-70: wait 65-66
        + [span("write.file", 72, 96, encoder="arrow")], kind="task")
    return action([span("plan", 0, 4, kind="stage"),
                   span("stage:write", 6, 96, [p0, p1], kind="stage"),
                   span("write.commit", 97, 99)], wall_ms, **account)


# what one write_action() gives each reader
EXPECTED = {
    "scan.arrow_read_ms": 30,               # 6-36, not 20 + 20
    # (10 + 4 + 0.5 x 26) of CPU over 14 + 26 of wall
    "scan.oncpu_share": 100 * 27 / 40,
    "sink.fence_wait_ms": 3 + 1,
    "sink.transfer_ms": 6 + 2,
    "sink.tail_ms": 96 - 70,
    "scheduler.gap_ms": 2 + 1 + 1,          # 4-6, 96-97, 99-100
}


@pytest.mark.parametrize("metric", sorted(EXPECTED))
def test_reader_on_a_write_of_two_overlapping_tasks(metric):
    samples = [write_action(), write_action(error="boom"), write_action()]
    assert read(metric, samples) == pytest.approx(EXPECTED[metric])
    # a span that never closed is not read
    left_open = write_action()
    unclosed = {"scan.arrow_read": "scan.arrow_read_ms",
                "sink.wait": "sink.fence_wait_ms",
                "sink.transfer": "sink.transfer_ms"}
    for sp in left_open.record.spans.spans():
        if sp.name in unclosed:
            sp.end_ns = None
    if metric in unclosed.values():
        assert read(metric, [left_open]) is None
    else:
        assert read(metric, [left_open]) == pytest.approx(EXPECTED[metric])


def test_arrow_read_is_never_more_than_host_ms():
    """`scan.arrow_read` lies inside `scan.host_decode`, one of
    `scan.host_ms`'s steps, and both are unions over threads."""
    samples = [write_action()]
    # the readers' scan.host_decode 6-40 and 16-62, an upload inside them
    assert read("scan.host_ms", samples) == pytest.approx(62 - 6)
    assert read("scan.arrow_read_ms", samples) == pytest.approx(30)


def test_oncpu_share_reads_only_spans_with_a_cpu_clock():
    one = write_action()
    # a span closed on another thread has no CPU clock: it leaves both
    # sums, so the share is p0's alone
    for sp in one.record.spans.spans():
        if sp.name in ("scan.convert", "scan.pack") and sp.start_ns >= 36 * MS:
            sp.cpu_ns = None
    assert read("scan.oncpu_share", [one]) == pytest.approx(100.0)
    for sp in one.record.spans.spans():
        sp.cpu_ns = None
    assert read("scan.oncpu_share", [one]) is None


def test_tail_needs_a_fence_and_a_write_stage():
    assert read("sink.tail_ms", [write_action(fences=False)]) is None
    # the median over the actions that have both
    assert read("sink.tail_ms", [write_action(fences=False), write_action(),
                                 write_action()]) == pytest.approx(26)
    collect = action([span("stage:result", 0, 50, [
        span("DeviceToHost", 40, 50, kind="op")], kind="stage")])
    assert read("sink.tail_ms", [collect]) is None


def test_gap_is_cut_to_the_root_and_never_negative():
    late = action([span("task:p0", -5, 120, kind="task")], wall_ms=100)
    assert read("scheduler.gap_ms", [late]) == 0.0
    # stages are the envelope, not work: a stage alone leaves all of it
    bare = action([span("stage:result", 0, 100, kind="stage")])
    assert read("scheduler.gap_ms", [bare]) == pytest.approx(100)
    assert hostclock.gap_ms(write_action().record.spans) == pytest.approx(4)


def test_cpu_ms_is_the_median_over_the_actions_that_have_it():
    samples = [write_action(proc_cpu_ms=300.0 + i) for i in range(6)]
    assert read("host.cpu_ms", samples) == pytest.approx(302.5)
    # a failed action is left out, and so is a tree without the attr
    samples += [write_action(error="boom", proc_cpu_ms=9e9), write_action()]
    assert read("host.cpu_ms", samples) == pytest.approx(302.5)


def test_gap_counts_the_wait_for_admission():
    """`admission.wait` is a `site` span, and the session's own time with
    no task running all the same: it stays in the gap."""
    queued = action([span("admission.wait", 0, 30),
                     span("plan", 30, 34, kind="stage"),
                     span("task:p0", 34, 100, kind="task")])
    assert read("scheduler.gap_ms", [queued]) == pytest.approx(30)


@pytest.mark.parametrize("metric", READERS)
def test_reader_finds_nothing_on_the_parents_trees(metric):
    """The parent's program: no account on the root, one `scan.host_decode`
    and one `DeviceToHost` with no children, and a `Span` without the
    `cpu_ns` slot. Every reader but the scheduler's gap, which needs only
    what PR 25's trees have, gives None; none raises."""
    class OldSpan:
        """PR 41's `Span`: the slots it had, and no other attribute."""
        __slots__ = ("name", "kind", "start_ns", "end_ns", "tid", "attrs",
                     "counts", "children", "owner")

        def __init__(self, name, kind, lo, hi, children=(), **attrs):
            self.name, self.kind = name, kind
            self.start_ns, self.end_ns = lo * MS, hi * MS
            self.tid, self.attrs, self.counts = 1, attrs, {}
            self.children, self.owner = list(children), None

    def parent_action(stage):
        root = OldSpan("query:x", "query", 0, 100, [
            OldSpan("plan", "stage", 0, 4),
            OldSpan(stage, "stage", 6, 96, [
                OldSpan("task:p0", "task", 6, 96, [
                    OldSpan("scan.host_decode", "site", 6, 40,
                            pack_ms=4.0, packed_bytes=10),
                    OldSpan("DeviceToHost", "op", 48, 60, bytes=5)])])],
            tenant="default")
        return loop.Sample(0.0, 0.1, SimpleNamespace(
            spans=QueryTrace(root, "default")), "")

    cases = [[parent_action("stage:result")] * 12,
             [loop.Sample(0.0, 0.1, SimpleNamespace(spans=None), "")],
             [write_action(error="x")]]
    for samples in cases:
        got = read(metric, samples)
        if metric == "scheduler.gap_ms" and samples is cases[0]:
            assert got == pytest.approx(2 + 4)
        elif metric == "sink.tail_ms":
            assert got is None      # a query's stage is no `stage:write`
        else:
            assert got is None
    if metric == "sink.tail_ms":
        assert read(metric, [parent_action("stage:write")]) \
            == pytest.approx(96 - 60)


# what `per_layer` held when PR 43 added to it
ENTRIES_BEFORE = 39


def test_the_new_entries_stand_behind_what_was_there(bench):
    """The driver takes new entries at the END of a list alone, so the
    eleven stand behind the thirty-nine that were there, in ISSUE 43's order
    and next to each other. Held by where they begin, not as "the last
    eleven": the next PR appends behind them (test_concat_ms.py and
    test_write7.py pin their entries as the list's last and fail since
    the PR after theirs for that alone)."""
    declared = bench["per_layer"][ENTRIES_BEFORE:ENTRIES_BEFORE
                                  + len(ENTRIES)]
    assert [(m["name"], m["workloads"]) for m in declared] == ENTRIES
    assert not {n for n, _ in ENTRIES} & {
        m["name"] for m in bench["per_layer"][:ENTRIES_BEFORE]}
    for m in declared:
        assert m["source"] == "program_span"
        assert m["moves"] == ("rows_per_s.write" if m["workloads"] == WRITES
                              else "query_s")
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        # found by name: a `.write` twin by its stem's file
        found = harness.load_reader("layer_metrics", m["name"])
        stem = m["name"][:-len(".write")] \
            if m["name"].endswith(".write") else m["name"]
        assert stem in READERS
        assert found.__code__.co_filename.endswith(
            f"layer_metrics/{stem}.py")
    by_name = {m["name"]: m for m in declared}
    assert {n: (m["unit"], m["better"], m["layer"])
            for n, m in by_name.items() if not n.endswith(".write")} == {
        "host.cpu_ms": ("ms", "lower", "session and scheduler"),
        "scheduler.gap_ms": ("ms", "lower", "session and scheduler"),
        "scan.arrow_read_ms": ("ms", "lower", "scan and device decode"),
        "scan.oncpu_share": ("%", "higher", "scan and device decode")}
    for n in ("sink.fence_wait_ms.write", "sink.transfer_ms.write",
              "sink.tail_ms.write"):
        assert (by_name[n]["unit"], by_name[n]["better"],
                by_name[n]["layer"]) == ("ms", "lower", "sink and writer")
    # a twin is declared as its stem is
    for n, m in by_name.items():
        if n.endswith(".write") and n[:-6] in by_name:
            stem = by_name[n[:-6]]
            assert (m["unit"], m["better"], m["layer"]) == \
                (stem["unit"], stem["better"], stem["layer"])
    # each cell reports the ones that list it, and no other of them
    for cell in QUERIES + WRITES:
        mine = {m["name"] for m in harness.metrics_of(bench, "per_layer",
                                                      cell)}
        assert mine & set(by_name) == {n for n, cells in ENTRIES
                                       if cell in cells}


# ---------------------------------------------------------------------------
# the program's own trees, through measure() as run.py drives it
# ---------------------------------------------------------------------------
# the chip's sink: there a DOUBLE makes the device encoder refuse the
# schema, so the fence brings whole columns down and Arrow writes
CHIP_SINK = {"rapids.tpu.sql.format.parquet.deviceEncode.enabled": False}


@pytest.fixture
def rehearse_traced(bench, monkeypatch, tmp_path):
    """A traced run of a cell at scale factor SF on the CPU backend, the
    device plane handed in as test_run.py hands it; the line's metrics
    by name."""
    monkeypatch.setattr(harness, "require_tpu", lambda chips: CPU_DEVICE)
    reduced = dict(xplane.reduce(TINY), action_busy_s=[0.001] * 3)
    monkeypatch.setattr(harness.xplane, "reduce", lambda path: reduced)

    def run(cell_name, conf=None):
        entry, config, cell = harness.load_cell(bench, cell_name)
        config = dict(config, scale_factor=SF,
                      conf=dict(config["conf"], **(conf or {})))
        result = harness.measure(bench, entry, config, cell, 2_900_000_043,
                                 0.3, True, time.perf_counter(),
                                 data_root=str(tmp_path / "data"))
        assert result["correct"] is True
        return {k: v["value"] for k, v in result["metrics"].items()}

    return run


def declared_for(cell):
    return {n for n, cells in ENTRIES if cell in cells}


def test_a_traced_query_cell_reports_the_new_metrics(rehearse_traced):
    m = rehearse_traced("q6_scan")
    mine = declared_for("q6_scan")
    assert mine <= set(m)
    assert all(math.isfinite(m[n]) for n in mine & set(m))
    assert 0 < m["scan.arrow_read_ms"] <= m["scan.host_ms"]
    assert 0 < m["scan.oncpu_share"] <= 100
    assert m["scheduler.gap_ms"] >= 0 and m["host.cpu_ms"] > 0
    # what was there reads as it read: one fence, the same spans
    assert m["sink.fences"] == 1 and m["sink.download_ms"] > 0


def test_a_traced_write_cell_reports_the_new_metrics(rehearse_traced):
    m = rehearse_traced("lineitem_write_slim", CHIP_SINK)
    mine = declared_for("lineitem_write_slim")
    assert mine <= set(m)
    assert all(math.isfinite(m[n]) for n in mine & set(m))
    assert 0 < m["scan.arrow_read_ms.write"] <= m["scan.host_ms.write"]
    assert 0 < m["scan.oncpu_share.write"] <= 100
    assert m["scheduler.gap_ms.write"] >= 0 and m["host.cpu_ms.write"] > 0
    # the wait and the copy are two of a fence's four steps
    assert 0 <= m["sink.fence_wait_ms.write"]
    assert 0 < m["sink.transfer_ms.write"]
    assert m["sink.fence_wait_ms.write"] + m["sink.transfer_ms.write"] \
        <= m["sink.download_ms.write"]
    assert m["sink.tail_ms.write"] > 0
    assert m["sink.fences.write"] == 4
