"""The token step's recurrence kernel (``gated_delta_step``,
``ops/pallas/delta_step.py``: one call a Gated DeltaNet layer a decode
step) against its roofline: the least time of a call — every slot's
state read and written once, the vectors' tile and the output row
(``families/qwen3_next.py: delta_step_bytes``; the FLOPs are far under
it) — over the mean device time of the kernel's events in the traced
tail, found by the kernel's name.  None where no such event ran (a
program whose step is XLA's lowering)."""

from benchmarks.harness.peaks import roofline_seconds

KERNEL = "gated_delta_step"


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    t0, t1 = run.trace_window
    calls = [e.end - e.start for ops in run.trace.device_ops.values()
             for e in ops if e.name == KERNEL and e.end > t0 and e.start < t1]
    if not calls:
        return None
    slots = run.facts["slots"]
    least, bound = roofline_seconds(
        run.family.delta_step_flops(run.config, slots),
        run.family.delta_step_bytes(run.config, slots), run.peaks)
    per_call = sum(calls) / len(calls)
    print(f"[bench] {KERNEL}: {len(calls)} calls, {1e3 * per_call:.3f} ms "
          f"each, least {1e3 * least:.3f} ms ({bound}-bound)", flush=True)
    return 100.0 * least / per_call
