"""Nearest-rank 90th percentile of the same samples as query_s: the tail
is where a re-trace, a GC pause or a spill shows, and a median hides it.
BENCHMARK.json lists it only for cells that finish 30 actions or more in a
window; under 10 samples there is no 90th percentile to speak of."""

from lib import loop

MIN_SAMPLES = 10


def read(run):
    d = loop.durations(run.samples)
    return loop.nearest_rank(d, 0.9) if len(d) >= MIN_SAMPLES else None
