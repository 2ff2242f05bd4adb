"""Chrome-trace-event exporter: the query span tree as a Perfetto-loadable
timeline (docs/observability.md).

Format: the Trace Event JSON object form — {"traceEvents": [...]} — with
complete-duration events (ph "X"), microsecond timestamps relative to the
query root, real thread ids (so per-partition tasks land on their worker
thread's track), and metadata events naming the process. Loadable in
ui.perfetto.dev or chrome://tracing.

Retry / spill / replan / admission-wait site spans carry their metric
counts in `args`, so the timeline shows WHY an operator's span is long
(it retried, it spilled, it waited for admission), not just that it was.
"""

from __future__ import annotations

from typing import List

# the pid is cosmetic (one engine process per trace); a stable small int
# keeps the exported JSON deterministic across runs
_PID = 1


def trace_to_chrome_events(trace) -> dict:
    """QueryTrace -> Chrome trace-event JSON object (dict form)."""
    origin = trace.root.start_ns
    events: List[dict] = [{
        "name": "process_name",
        "ph": "M",
        "pid": _PID,
        "tid": 0,
        "args": {"name": f"spark_rapids_tpu tenant={trace.tenant}"},
    }]

    def _prim(v):
        return v if isinstance(v, (bool, int, float, str)) \
            or v is None else str(v)

    def walk(sp) -> None:
        end = sp.end_ns if sp.end_ns is not None else sp.start_ns
        args = {"kind": sp.kind}
        if sp.cpu_ns is not None:
            # the thread's CPU time inside the span: dur less this is
            # time it spent off a core
            args["cpu_ns"] = sp.cpu_ns
        args.update({str(k): _prim(v) for k, v in sp.attrs.items()})
        args.update({str(k): _prim(v) for k, v in sp.counts.items()})
        events.append({
            "name": sp.name,
            "cat": sp.kind,
            "ph": "X",
            "ts": (sp.start_ns - origin) / 1e3,
            "dur": max(0.0, (end - sp.start_ns) / 1e3),
            "pid": _PID,
            "tid": sp.tid,
            "args": args,
        })
        for c in sp.children:
            walk(c)

    walk(trace.root)
    return {"traceEvents": events, "displayTimeUnit": "ms"}
