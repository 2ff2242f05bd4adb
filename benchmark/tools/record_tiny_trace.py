#!/usr/bin/env python3
"""Record the small trace that tests/test_xplane.py reduces, and print what
a trace of this machine looks like (planes, lines, the first events): the
look by hand that lib/xplane.py's constants were written from.

    python3 benchmark/tools/record_tiny_trace.py <out_dir>

Three marked "actions", each one dispatch of a jitted program `step` (32
dependent elementwise passes over 64 MiB and a sum: some milliseconds of
device time, well above the millisecond or so by which the device's events
sit early on the profiler's timeline), 20 ms of host sleep after it and
10 ms between actions, under the profiler options the harness uses. Run on the chip; on
the CPU backend it shows the host planes only.
"""

import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

from lib import xplane  # noqa: E402


def main(out_dir: str) -> int:
    import jax
    import jax.numpy as jnp

    @jax.jit
    def step(x):
        x = jax.lax.fori_loop(0, 32, lambda _, v: v * 1.0001 + 1.0, x)
        return x.sum()

    x = jnp.ones((1 << 24,), jnp.float32)
    step(x).block_until_ready()
    shutil.rmtree(out_dir, ignore_errors=True)
    jax.profiler.start_trace(out_dir, profiler_options=xplane.options())
    for _ in range(3):
        with jax.profiler.TraceAnnotation(xplane.MARKER):
            step(x).block_until_ready()
            time.sleep(0.02)
        time.sleep(0.01)
    jax.profiler.stop_trace()
    path = xplane.find_trace(out_dir)
    print(path, os.path.getsize(path), "bytes", jax.devices())
    for line in xplane.describe(path, events=8):
        print(line)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
