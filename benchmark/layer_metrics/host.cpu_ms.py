"""CPU milliseconds of ALL the process's threads in one action (the
tasks', the readers', Arrow's and XLA's pools): the root span's
`proc_cpu_ms`, the difference of `time.process_time_ns` read at the
action's two ends. Over the action's wall it is the cores kept busy, of
the `len(os.sched_getaffinity(0))` the process may use: near that
ceiling the action is bound by the work (decode fewer bytes), far under
it the threads wait. Process-wide: one client, so the difference is one
action's. Median over the window."""

from lib import hostclock


def read(run):
    return hostclock.an_action(
        run, lambda tree: tree.root.attrs.get("proc_cpu_ms"))
