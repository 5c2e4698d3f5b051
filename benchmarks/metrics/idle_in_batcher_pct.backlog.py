"""Share of the traced window the device was idle while the host was in
``ContinuousBatcher.step()`` outside the engine's spans: ``serve.step``'s
self time, ``serve.admit`` and ``serve.retire``."""

from benchmarks.harness.program_tape import idle_pct


def read(run):
    return idle_pct(run, ("serve.step", "serve.admit", "serve.retire"))
