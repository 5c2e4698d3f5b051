"""Host time of the decode program's dispatch: the mean of the program's
``engine.decode.dispatch`` span (the ``_decode_step`` call until it
returns) over the window."""

from benchmarks.harness.program_tape import (  # noqa: F401
    decode_dispatch_ms as read)
