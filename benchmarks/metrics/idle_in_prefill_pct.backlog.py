"""Share of the traced window the device was idle while the host was in
one of the program's ``engine.prefill.pack`` / ``.dispatch`` /
``.readback`` spans (the run prints each)."""

from benchmarks.harness.program_tape import idle_pct


def read(run):
    return idle_pct(run, ("engine.prefill.pack", "engine.prefill.dispatch",
                          "engine.prefill.readback"))
