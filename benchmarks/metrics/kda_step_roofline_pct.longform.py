"""The token step's recurrence kernel under a decay a key channel
(``gated_delta_step``, ``ops/pallas/delta_step.py``: one call a Kimi
Delta Attention layer a decode step) against its roofline: the least
time of a call — every slot's state read and written once, the vectors'
tile and the output row (``families/bailing_hybrid.py: kda_step_bytes``;
the FLOPs are far under it) — over the mean device time of the kernel's
events in the traced tail, found by the kernel's name."""

from benchmarks.harness.latent_counts import kernel_roofline_pct


def read(run):
    slots = run.facts.get("slots")
    if slots is None:
        return None
    return kernel_roofline_pct(
        run, "gated_delta_step", run.family.kda_step_flops(run.config, slots),
        run.family.kda_step_bytes(run.config, slots))
