"""What a decode step of a model with state-space and attention layers
did, from the program's own counters (read once, after the run: they are
over the whole run's decode steps): shared by the ``.assist`` readers
that hold a step to the family's least work.  ``state_counts.py`` and
``latent_counts.py`` are the same for the other two recurrent-state
families, each reading its own family's keys; this one asks the family
which layers are which (``family.kinds``) and how many experts are held
under the key this family's configurations use."""

from __future__ import annotations

from benchmarks.harness.peaks import roofline_seconds
from benchmarks.harness.program_tape import registry_value
from benchmarks.harness.readers import decode_device_seconds_per_step


def decode_step_counts(run):
    """``{steps, rows, touched, state_bytes, least_bytes, least_flops}``
    a decode step, or None where the program has no such counters.
    ``rows`` are the K/V rows the busy slots' queries read in ONE
    attention layer, ``touched`` the held experts a pair reached over all
    layers, ``state_bytes`` what the engine counted of recurrent state
    moved for every slot; the two ``least_*`` are the family's counts for
    that step."""
    slots_total = registry_value("counters", "moe_expert_slots_total")
    state_total = registry_value("counters",
                                 'serve_state_bytes_total{whose="all"}')
    if not slots_total or not state_total:
        return None
    cfg, family = run.config, run.family
    full, _, expert_layers = family.kinds(cfg)
    steps = slots_total / (cfg["num_local_experts"] * expert_layers)
    count = lambda series: (registry_value("counters", series) or 0) / steps
    rows = count('serve_cache_rows_read_total{kind="full"}') / max(1, full)
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
          f"{got['rows']:.0f} rows an attention layer and moved "
          f"{got['state_bytes'] / 1e9:.2f} GB of state", flush=True)
    return 100.0 * least / per_step
