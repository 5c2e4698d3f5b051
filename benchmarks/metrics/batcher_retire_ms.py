"""The per-slot append-and-retire loop of one boundary: the mean of the
program's ``serve.retire`` span over the window (None on a program
from before ``engine.decode.wait``, as its siblings are)."""

from benchmarks.harness.boundary_tape import span_mean_ms


def read(run):
    return span_mean_ms(run, "serve.retire")
