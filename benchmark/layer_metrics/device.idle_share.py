"""Share of the traced actions' time in which no operation ran on the
device: 1 - busy / elapsed, over the same intervals as
operators.device_ms."""


def read(run):
    if run.trace is None:
        return None
    return 100.0 * (1.0 - run.trace["busy_s"] / run.trace["window_s"])
