"""Host time of one boundary that belongs to ``ContinuousBatcher.step()``
itself — 256 slots of bookkeeping a boundary here: the mean self time of
the program's ``serve.step`` span over the window (its duration less
``serve.admit``, ``serve.retire`` and the engine's spans inside it)."""

from benchmarks.harness.program_tape import step_self_ms as read  # noqa: F401
