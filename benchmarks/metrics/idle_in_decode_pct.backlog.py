"""Share of the traced window the device was idle while the host was in
the program's ``engine.decode.dispatch`` or ``engine.decode.readback``
span (the run prints each).  One metric, not two: the trace's device
plane runs 1 to 2 ms ahead of its host plane on this runtime (PERF.md
section 6), so the split between two adjacent spans is off by that much
while their sum is right."""

from benchmarks.harness.program_tape import idle_pct


def read(run):
    return idle_pct(run, ("engine.decode.dispatch",
                          "engine.decode.readback"))
