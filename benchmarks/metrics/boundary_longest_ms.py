"""The longest boundary of the window, from one ``serve.step``'s start
to the next one's; the run prints what the three longest held (the
three ``engine.prefill.*`` spans, ``engine.decode.wait``, ``host.gc``,
the rest) and whether anything compiled."""

from benchmarks.harness.boundary_tape import (  # noqa: F401
    boundary_longest_ms as read)
