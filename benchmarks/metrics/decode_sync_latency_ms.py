"""Launch plus completion latency of the decode program: in the traced
tail, the program's ``engine.decode.wait`` span of each boundary that
prefilled nothing less the duration of the ``decode_step`` event of the
trace's ``XLA Modules`` line it waited for; the mean.  Durations only:
the skew between the trace's planes does not enter."""

from benchmarks.harness.boundary_tape import (  # noqa: F401
    decode_sync_latency_ms as read)
