"""The token vector's copy to the host and the counts behind it: the
mean of the program's ``engine.decode.readback`` span less its child
``engine.decode.wait`` over the window."""

from benchmarks.harness.boundary_tape import (  # noqa: F401
    decode_fetch_ms as read)
