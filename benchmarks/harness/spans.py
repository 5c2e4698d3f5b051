"""The benchmark's own spans around its calls into each layer: a host
clock pair kept in memory, and — so that a device idle gap can be laid
to what the host was doing — a ``jax.profiler.TraceAnnotation`` of the
same name on the profiler's clock."""

from __future__ import annotations

import contextlib
import time

from benchmarks.harness.trace import SPAN_PREFIX


class Spans:
    def __init__(self):
        self.tape: dict = {}          # name -> [(t0, t1)] host seconds

    @contextlib.contextmanager
    def span(self, name: str):
        import jax
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(SPAN_PREFIX + name):
            try:
                yield
            finally:
                self.tape.setdefault(name, []).append(
                    (t0, time.perf_counter()))

    def wrap(self, obj, method: str, name: str) -> None:
        """Put a span around ``obj.method`` (on the instance only)."""
        inner = getattr(obj, method)

        def wrapped(*args, **kwargs):
            with self.span(name):
                return inner(*args, **kwargs)

        setattr(obj, method, wrapped)

    def durations(self, name: str, window=None) -> list:
        return [t1 - t0 for t0, t1 in self.tape.get(name, [])
                if window is None or (t0 >= window[0] and t1 <= window[1])]
