"""What tells a held interpreter lock from a process that stood still from
a sandbox that stood still (PR 26: actions of the write cell stall for
seconds on some chip machines; ROADMAP D6).

 - hb.cc: an in-process C thread that needs no interpreter lock. It times
   its own 5 ms sleep, an mmap + touch + munmap, and a counter that a
   Python thread beats; where the counter stops it notes every thread's
   scheduler state from /proc.
 - a second process that only sleeps and maps memory.
 - faulthandler's watchdog (a C thread too) dumps every thread's Python
   stack WHILE the Python heartbeat is 0.5 s late, not after.

A held interpreter lock stops the Python beat alone and the stacks name
the holder; a process that stands still stops the C thread too; a sandbox
that stands still stops the other process as well. Event kinds: 1 a sleep
came back over 0.1 s late, 2 the mmap took over 0.05 s, 3 the Python beat
has not moved for 0.3 s, 4 it moved again (value: the length). Times are
on CLOCK_MONOTONIC, which all three share."""

import ctypes
import faulthandler
import json
import os
import subprocess
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
SHM = "/dev/shm"
TMP = SHM if os.path.isdir(SHM) and os.access(SHM, os.W_OK) else "/tmp"

OTHER_PROCESS = r'''
import json, mmap, signal, time
events, going, worst, ticks = [], [True], [0.0, 0.0], 0
signal.signal(signal.SIGTERM, lambda *a: going.clear())
while going:
    t0 = time.monotonic()
    time.sleep(0.01)
    t1 = time.monotonic()
    late = t1 - t0 - 0.01
    worst[0] = max(worst[0], late)
    if late > 0.1:
        events.append([1, round(t1, 4), round(late, 4)])
    m = mmap.mmap(-1, 262144)
    m[0] = 1
    m.close()
    t2 = time.monotonic()
    worst[1] = max(worst[1], t2 - t1)
    if t2 - t1 > 0.05:
        events.append([2, round(t2, 4), round(t2 - t1, 4)])
    ticks += 1
print(json.dumps({"ticks": ticks, "max_sleep_late_s": worst[0],
                  "max_mm_s": worst[1], "events": events}), flush=True)
'''


def since(report: dict, origin: float) -> dict:
    """The report with every event's time counted from `origin`."""
    def rel(events):
        return [[kind, round(t - origin, 2), v] for kind, t, v in events]

    report["in_process_c_thread"]["events"] = rel(
        report["in_process_c_thread"]["events"])
    if "events" in report["other_process"]:
        report["other_process"]["events"] = rel(
            report["other_process"]["events"])
    report["python_heartbeat_late"] = [
        [round(t - origin, 2), v] for t, v in report["python_heartbeat_late"]]
    return report


class Instruments:
    """On from construction to close(), which returns the report."""

    def __init__(self, tag: str, out_dir: str):
        self.tag, self.out_dir = tag, out_dir
        # built beside the reports: /dev/shm is noexec on the chip machine
        so = os.path.join(out_dir, f".hb_{os.getpid()}.so")
        subprocess.run(["g++", "-O1", "-shared", "-fPIC", "-o", so,
                        os.path.join(HERE, "hb.cc"), "-lpthread"], check=True)
        self.lib = ctypes.CDLL(so)
        os.unlink(so)
        self.stacks_path = os.path.join(TMP, f"stacks_{tag}.txt")
        self.stacks = open(self.stacks_path, "w")
        self.other = subprocess.Popen([sys.executable, "-c", OTHER_PROCESS],
                                      stdout=subprocess.PIPE, text=True)
        self.lib.hb_start()
        self.late = []
        self.alive = True
        threading.Thread(target=self._beat, daemon=True).start()

    def _beat(self):
        last = time.monotonic()
        while self.alive:
            self.lib.hb_beat()
            faulthandler.cancel_dump_traceback_later()
            faulthandler.dump_traceback_later(0.5, repeat=True,
                                              file=self.stacks)
            time.sleep(0.1)
            now = time.monotonic()
            if now - last - 0.1 > 0.25:
                self.late.append([round(now, 4), round(now - last - 0.1, 4)])
                self.stacks.write(f"\n##### python heartbeat back at "
                                  f"{now:.4f} after {now - last:.4f}s\n")
                self.stacks.flush()
            last = now

    def close(self) -> dict:
        self.alive = False
        time.sleep(0.15)
        faulthandler.cancel_dump_traceback_later()
        self.lib.hb_stop()
        hb_path = os.path.join(TMP, f"hb_{self.tag}.json")
        self.lib.hb_dump(hb_path.encode())
        with open(hb_path) as f:
            in_process = json.load(f)
        self.other.terminate()
        try:
            out = self.other.communicate(timeout=10)[0]
            other = json.loads(out.strip().split("\n")[-1])
        except (subprocess.TimeoutExpired, ValueError, IndexError) as e:
            other = {"error": repr(e)}
        self.stacks.close()
        with open(self.stacks_path) as f:
            stacks = f.read()
        if stacks.strip():
            path = os.path.join(self.out_dir, f"stall_{self.tag}.stacks")
            with open(path, "w") as f:
                f.write(stacks)
        return {"threads(ident: tid, name)": {
                    hex(t.ident): [t.native_id, t.name]
                    for t in threading.enumerate()},
                "in_process_c_thread": in_process, "other_process": other,
                "python_heartbeat_late": self.late}
