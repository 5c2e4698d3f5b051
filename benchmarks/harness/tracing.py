"""The traced part of a ``--trace 1`` run: a few seconds of the same
work, AFTER the measured window has closed, under jax's profiler.  The
host-clock per-layer metrics come from the untraced window; only what
needs the device's own clock is read here."""

from __future__ import annotations

import shutil
import tempfile

from benchmarks.harness import trace as tr


class TracedTail:
    def __init__(self, run):
        self._run = run
        # Under TMPDIR (the driver gives each side its own); removed
        # again once reduced.
        self._dir = tempfile.mkdtemp(prefix="bench_trace_")

    def __enter__(self):
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0    # the benchmark's spans suffice
        jax.profiler.start_trace(self._dir, profiler_options=opts)
        return self

    def __exit__(self, *exc):
        import jax
        jax.profiler.stop_trace()
        try:
            if exc[0] is None:
                trace = tr.load(tr.newest_xplane(self._dir))
                if trace.device_ops:
                    self._run.trace = trace
                    self._run.trace_window = tr.window_of(trace)
                else:
                    print("[bench] the trace holds no TPU device plane; "
                          "device metrics are left out", flush=True)
        finally:
            shutil.rmtree(self._dir, ignore_errors=True)
        return False
