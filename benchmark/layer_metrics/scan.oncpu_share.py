"""How much of the reader threads' own steps ran on a core: over one
action's `scan.convert` and `scan.pack` spans (one thread's numpy and
Python each, children of `scan.host_decode`), 100 x the sum of their
`cpu_ns` over the sum of their wall. Under 100 is time the thread was
queued for the interpreter's lock or for a core. (`scan.arrow_read` is
left out: its caller sleeps while Arrow's pool works.) Median over the
window."""

from lib import hostclock

STEPS = ("scan.convert", "scan.pack")


def read(run):
    return hostclock.an_action(
        run, lambda tree: hostclock.oncpu_share(tree, STEPS))
