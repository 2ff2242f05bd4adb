# tpulint: stdout-protocol -- pause CLI: stdout is the child's
"""Makes the pause that hunt.py's instruments watch for: one run of a
benchmark cell as a child process, stopped whole (SIGSTOP, then SIGCONT)
a few times inside its window. What a machine does to a run now and then
(PERF.md section 7, "First"), done on purpose, so that a tree's answer to
it can be read in one run: `speculativeTasks`, `watchdogKills`,
`actions_failed` and `correct` in the result line, and the stopped
actions at their full wall in `action_s`. On the chip:

    chiprun -- python3 tools/stall_hunt/pause.py <checkout> <cell> <seed> \
        [pause seconds=1.5] [pauses=3] [seconds apart=8] [window seconds=45]

The child is `python3 benchmark/run.py --workload <cell> --seed <seed>
--seconds <window>` run from <checkout>; its stdout and exit code pass
through, its stderr too, with a `[pause]` line at each signal. The first
stop comes half a gap after the child's `warm_up` line, which is where
the window starts. This process never imports jax: the chip is the
child's."""

import os
import signal
import subprocess
import sys
import threading
import time


def child_command(cell: str, seed: int, window_s: float) -> list:
    return [sys.executable, os.path.join("benchmark", "run.py"),
            "--workload", cell, "--seed", str(seed),
            "--seconds", str(window_s)]


def main(argv) -> int:
    root, cell, seed = os.path.abspath(argv[0]), argv[1], int(argv[2])
    rest = [float(a) for a in argv[3:]]
    pause_s, pauses, apart_s, window_s = rest + [1.5, 3, 8, 45][len(rest):]
    child = subprocess.Popen(child_command(cell, seed, window_s), cwd=root,
                             stderr=subprocess.PIPE, text=True)
    warm = threading.Event()

    def relay():
        for line in child.stderr:
            sys.stderr.write(line)
            sys.stderr.flush()
            if "warm_up:" in line:
                warm.set()
        warm.set()  # the child ended without one: nothing to stop

    threading.Thread(target=relay, daemon=True).start()
    warm.wait()
    t0 = time.monotonic()

    def say(msg):
        print(f"[pause +{time.monotonic() - t0:6.2f}s of the window] {msg}",
              file=sys.stderr, flush=True)

    for i in range(int(pauses)):
        time.sleep(apart_s / 2 if i == 0 else apart_s - pause_s)
        if child.poll() is not None:
            say("the child has ended")
            break
        child.send_signal(signal.SIGSTOP)
        say(f"SIGSTOP {i + 1} of {int(pauses)}")
        time.sleep(pause_s)
        child.send_signal(signal.SIGCONT)
        say(f"SIGCONT after {pause_s} s")
    return child.wait()


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
