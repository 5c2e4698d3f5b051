"""Share of the batcher's decode steps whose tokens were read late: the
step was handed to the device at the boundary before, behind the step
that was then in flight, and read only after its own successor was
handed over — the boundaries at which the device goes from one step
into the next with no host time between.  The program's
``serve_decode_steps_total{readback}`` counters, late over late plus
same_step, over the whole run (ramp and traced tail included: the
counters are read once, after the run; the warm-up's step is the
engine's own and is not counted).  None where the program has no such
series (a program from before the late read-back)."""

from benchmarks.harness.program_tape import registry_value


def read(_run):
    late, same = (registry_value(
        "counters", 'serve_decode_steps_total{readback="%s"}' % how)
        for how in ("late", "same_step"))
    if not (late or 0) + (same or 0):
        return None
    return 100.0 * (late or 0) / ((late or 0) + (same or 0))
