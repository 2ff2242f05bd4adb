"""Busy against waiting on the host (PR 43, docs/observability.md): the
`cpu_ns` of the spans named in `obs.trace.CPU_CLOCKED` (their thread's
CPU clock beside the wall clock) and `proc_cpu_ms` on a query's root
span (the CPU of all the process's threads).

- a thread that works has a span whose CPU is its wall; one that sleeps
  has almost none; a span with no one thread to read (closed elsewhere,
  noted after the fact) and a span outside the set have None;
- with tracing off no clock is read;
- the root's `proc_cpu_ms` is the exact difference of what the process
  reads at a query's two ends.
"""

import json
import threading
import time

import numpy as np
import pytest

from spark_rapids_tpu import conf as C
from spark_rapids_tpu.obs import trace as T
from spark_rapids_tpu.plan import functions as F


def _traced(fn, name="scan.convert"):
    """`fn()` under one span of a tracer of its own; the closed span."""
    tr = T.QueryTracer("t")
    handle = tr.open_span(name)
    fn()
    tr.close_span(handle)
    tr.finish()
    return handle[0]


def _spin(seconds):
    end = time.perf_counter() + seconds
    while time.perf_counter() < end:
        pass


# ---------------------------------------------------------------------------
# cpu_ns
# ---------------------------------------------------------------------------
def _burn(cpu_s):
    """Work until the calling thread has used `cpu_s` of CPU, however
    long a loaded machine takes to give it."""
    end = time.thread_time() + cpu_s
    while time.thread_time() < end:
        pass


def test_busy_span_cpu_is_its_work():
    """A thread that works: the span's CPU is the 100 ms it burnt (to
    20%: a clock that steps in ticks of 10 ms, the chip's host, reads
    100-110) and no more than its wall, which on a loaded machine is
    longer by what the thread waited for a core."""
    sp = _traced(lambda: _burn(0.1))
    assert 0.1e9 <= sp.cpu_ns <= 0.12e9
    assert sp.cpu_ns <= sp.duration_ns + 0.01e9


def test_sleeping_span_has_almost_no_cpu():
    sp = _traced(lambda: time.sleep(0.2))
    assert sp.duration_ns >= 0.2e9
    assert 0 <= sp.cpu_ns < 0.1 * sp.duration_ns


@pytest.mark.parametrize("name", sorted(T.CPU_CLOCKED))
def test_only_the_named_spans_read_the_cpu_clock(monkeypatch, name):
    reads = []
    monkeypatch.setattr(T, "thread_cpu_ns",
                        lambda: reads.append(1) or 1000 * len(reads))
    assert _traced(lambda: None, "TpuHashAggregate.update").cpu_ns is None
    assert _traced(lambda: None, "scan.arrow_read").cpu_ns is None
    assert not reads
    assert _traced(lambda: None, name).cpu_ns == 1000
    assert len(reads) == 2


def test_span_closed_on_another_thread_has_no_cpu():
    tr = T.QueryTracer("t")
    sp, token, anno = tr.open_span("scan.pack")
    # the contextvar is this thread's to restore; the span itself is
    # closed by whichever thread runs the generator's last step
    T._CURRENT_SPAN.reset(token)
    handle = (sp, None, anno)
    closer = threading.Thread(target=tr.close_span, args=(handle,))
    closer.start()
    closer.join(timeout=10)
    assert not closer.is_alive()
    assert sp.end_ns is not None and sp.cpu_ns is None


def test_noted_and_unclosed_spans_have_no_cpu():
    tr = T.QueryTracer("t")
    noted = tr.note_span("prefetch:q", T.wall_ns() - 1000, T.wall_ns())
    left_open = tr.open_span("write.file")[0]
    trace = tr.finish()
    assert noted.cpu_ns is None
    assert left_open.end_ns is not None and left_open.cpu_ns is None
    # the root has the process's CPU as an attr, and no thread's
    assert trace.root.cpu_ns is None


def test_render_and_perfetto_carry_cpu():
    tr = T.QueryTracer("t")
    handle = tr.open_span("sink.finish")
    _spin(0.002)
    tr.close_span(handle)
    noted = tr.note_span("noted", T.wall_ns() - 1000, T.wall_ns())
    trace = tr.finish()
    lines = {ln.split()[1]: ln for ln in trace.render().splitlines()}
    assert f"cpu={handle[0].cpu_ns / 1e6:.3f}ms" in lines["sink.finish"]
    assert "cpu=" not in lines["noted"]
    events = {e["name"]: e for e in trace.to_perfetto()["traceEvents"]
              if e["ph"] == "X"}
    assert events["sink.finish"]["args"]["cpu_ns"] == handle[0].cpu_ns
    assert "cpu_ns" not in events["noted"]["args"]
    assert noted.cpu_ns is None
    json.dumps(trace.to_perfetto())  # the root's proc_cpu_ms is a number


def test_tracing_off_reads_no_cpu_clock(session, monkeypatch):
    """Off is off: `span()` is the shared no-op, and an untraced query
    reaches neither the thread's CPU clock nor the process's."""
    def never(*_a, **_k):
        raise AssertionError("read with tracing off")

    monkeypatch.setattr(time, "thread_time_ns", never)
    monkeypatch.setattr(time, "process_time_ns", never)
    assert T.span("anything", some_attr=1) is T._NOOP
    df = session.createDataFrame(
        {"k": np.arange(1000, dtype=np.int64) % 7,
         "v": np.arange(1000, dtype=np.int64)},
        [("k", "long"), ("v", "long")], num_partitions=2)
    rows = df.filter(F.col("v") >= 10).groupBy("k").agg(F.sum("v")).collect()
    assert len(rows) == 7 and session.last_query_trace is None
    # and on is on: the same patched clock is what a traced query reads
    session.set_conf(C.OBS_TRACING.key, True)
    with pytest.raises(AssertionError, match="read with tracing off"):
        df.collect()


# ---------------------------------------------------------------------------
# the root's proc_cpu_ms
# ---------------------------------------------------------------------------
def test_root_proc_cpu_is_the_exact_difference(monkeypatch):
    readings = iter([1_000_000_000, 1_250_500_000])
    monkeypatch.setattr(time, "process_time_ns", lambda: next(readings))
    root = T.QueryTracer("WriteFile").finish().root
    assert root.attrs == {"tenant": "default", "proc_cpu_ms": 250.5}


def test_root_proc_cpu_counts_every_thread():
    tr = T.QueryTracer("q")
    workers = [threading.Thread(target=_burn, args=(0.03,))
               for _ in range(2)]
    for w in workers:
        w.start()
    for w in workers:
        w.join()
    # 30 ms of CPU on each of two threads, neither the tracer's own
    assert tr.finish().root.attrs["proc_cpu_ms"] >= 55.0
