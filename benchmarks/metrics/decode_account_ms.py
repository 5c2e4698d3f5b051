"""What the per-step counters and the numpy state cost the host with the
chip idle: the mean of the program's ``engine.decode.account`` span
over the window."""

from benchmarks.harness.boundary_tape import span_mean_ms


def read(run):
    return span_mean_ms(run, "engine.decode.account")
