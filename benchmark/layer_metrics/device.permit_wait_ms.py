"""Task milliseconds of one action spent waiting for the chip's admission
permit: the durations of its `Acquire TPU Semaphore` spans added up over
the task threads (thread time: eight tasks queue for
`concurrentTpuTasks` permits, so it can pass the action's wall time);
median over the window."""

from lib import spans


def read(run):
    return spans.median_an_action(run, ("Acquire TPU Semaphore",),
                                  spans.total_ms)
