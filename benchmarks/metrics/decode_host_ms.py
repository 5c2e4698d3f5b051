"""Host time of one decode boundary in series with the device: over the
window's boundaries that prefilled nothing and whose successor
prefilled nothing, the period from one ``serve.step``'s start to the
next one's less the program's ``engine.decode.wait`` inside it; the
mean.  The benchmark's own bookkeeping between steps is in it.  The run
prints the period piece by piece beside it."""

from benchmarks.harness.boundary_tape import (  # noqa: F401
    decode_host_ms as read)
