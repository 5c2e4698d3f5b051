"""Share of the traced window the device was idle while the host was in
no program span at all: the benchmark's own bookkeeping between
``step()`` calls."""

from benchmarks.harness.program_tape import UNATTRIBUTED, idle_pct


def read(run):
    return idle_pct(run, (UNATTRIBUTED,))
