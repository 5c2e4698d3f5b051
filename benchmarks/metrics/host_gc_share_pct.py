"""Share of the window inside Python's collector: the program's
``host.gc`` spans (collections of a millisecond or more) over the
window's seconds; the run prints ``host_gc_seconds_total{generation}``
for the whole run beside it."""

from benchmarks.harness.boundary_tape import (  # noqa: F401
    host_gc_share_pct as read)
