"""Share of the device's busy time spent in copy operations (HLO
``copy``, ``copy-start``, ``copy-done``) in the traced window: what says
whether a layer's recurrent state, which every step rewrites whole, is
updated in place or copied."""

from benchmarks.harness import trace as tr


def read(run):
    if run.trace is None:
        return None
    busy = tr.busy(run.trace, run.trace_window)
    copies = tr.kind_seconds(run.trace, tr.COPIES, run.trace_window)
    return 100.0 * copies / (sum(busy.values()) / len(busy))
