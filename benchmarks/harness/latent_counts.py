"""What a decode step of a model with latent-attention and
recurrent-state layers did, from the program's own counters (read once,
after the run: they are over the whole run's decode steps), and what its
kernels took on the device: shared by the ``.longform`` readers that
hold a step or a kernel to the family's least work.  ``state_counts.py``
is the same for a model whose rows are K/V rows (it reads a key of that
family); this one asks the family which layers are which
(``family.kinds``)."""

from __future__ import annotations

from benchmarks.harness.peaks import roofline_seconds
from benchmarks.harness.program_tape import registry_value
from benchmarks.harness.readers import decode_device_seconds_per_step


def decode_step_counts(run):
    """``{steps, rows, touched, state_bytes, least_bytes, least_flops}``
    a decode step, or None where the program has no such counters.
    ``rows`` are the latent rows the busy slots' queries read in ONE
    latent layer, ``touched`` the held experts a pair reached over all
    layers, ``state_bytes`` what the engine counted of recurrent state
    moved for every slot; the two ``least_*`` are the family's counts
    for that step."""
    slots_total = registry_value("counters", "moe_expert_slots_total")
    state_total = registry_value("counters",
                                 'serve_state_bytes_total{whose="all"}')
    if not slots_total or not state_total:
        return None
    cfg, family = run.config, run.family
    latent, _, expert_layers = family.kinds(cfg)
    steps = slots_total / (cfg["num_experts"] * expert_layers)
    count = lambda series: (registry_value("counters", series) or 0) / steps
    rows = count('serve_cache_rows_read_total{kind="latent"}') / max(1,
                                                                     latent)
    touched = count("moe_experts_touched_total")
    slots = run.facts["slots"]
    return {
        "steps": steps, "rows": rows, "touched": touched,
        "state_bytes": state_total / steps,
        "least_bytes": family.decode_step_bytes(
            cfg, rows, slots=slots, experts_touched=touched),
        "least_flops": family.decode_step_flops(cfg, rows, slots)}


def decode_roofline_pct(run):
    """The least time of one decode step (:func:`decode_step_counts`'
    bytes or FLOPs, whichever takes longer) over the device time of one
    run of the decode program in the traced tail."""
    per_step = decode_device_seconds_per_step(run)
    got = decode_step_counts(run)
    if per_step is None or run.peaks is None or got is None:
        return None
    least, bound = roofline_seconds(got["least_flops"], got["least_bytes"],
                                    run.peaks)
    print(f"[bench] decode roofline: {bound}-bound, least "
          f"{1e3 * least:.3f} ms, device {1e3 * per_step:.3f} ms a step; "
          f"a step touched {got['touched']:.1f} experts, read "
          f"{got['rows']:.0f} rows a latent layer and moved "
          f"{got['state_bytes'] / 1e9:.2f} GB of state", flush=True)
    return 100.0 * least / per_step


def kernel_roofline_pct(run, kernel: str, flops: float, nbytes: float):
    """The least time of ONE call of ``kernel`` (the larger of ``flops``
    over peak FLOP/s and ``nbytes`` over peak bytes/s) over the mean
    device time of the kernel's events in the traced tail, found by the
    kernel's name.  None where no such event ran."""
    if run.trace is None or run.peaks is None:
        return None
    t0, t1 = run.trace_window
    calls = [e.end - e.start for ops in run.trace.device_ops.values()
             for e in ops if e.name == kernel and e.end > t0 and e.start < t1]
    if not calls:
        return None
    least, bound = roofline_seconds(flops, nbytes, run.peaks)
    per_call = sum(calls) / len(calls)
    print(f"[bench] {kernel}: {len(calls)} calls, {1e3 * per_call:.3f} ms "
          f"each, least {1e3 * least:.3f} ms ({bound}-bound)", flush=True)
    return 100.0 * least / per_call
