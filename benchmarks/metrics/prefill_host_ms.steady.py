"""Host time a boundary that admitted spends before the prefill's
read-back: per ``serve.step`` that prefilled, the program's
``engine.prefill.pack`` plus ``engine.prefill.dispatch`` spans of all
its bucket groups; the mean over the window."""

from benchmarks.harness.program_tape import prefill_host_ms as read  # noqa: F401
