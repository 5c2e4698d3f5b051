"""What a decode step of a model with recurrent-state layers did, from
the program's own counters (read once, after the run: they are over the
whole run's decode steps): shared by the readers that hold a step to the
family's least work (``decode_roofline_pct.chat``,
``decode_state_bytes_pct.chat``)."""

from __future__ import annotations

from benchmarks.harness.program_tape import registry_value


def decode_step_counts(run):
    """``{steps, rows, touched, state_bytes, least_bytes, least_flops}``
    a decode step, or None where the program has no such counters (one
    from before its engine counted recurrent state).  ``rows`` are the
    K/V rows the busy slots' queries read in ONE attention layer,
    ``touched`` the held experts a pair reached over all layers,
    ``state_bytes`` what the engine counted of recurrent state moved for
    every slot; the two ``least_*`` are the family's counts for that
    step."""
    slots_total = registry_value("counters", "moe_expert_slots_total")
    state_total = registry_value("counters",
                                 'serve_state_bytes_total{whose="all"}')
    if not slots_total or not state_total:
        return None
    cfg, family = run.config, run.family
    layers = cfg["num_hidden_layers"]
    steps = slots_total / (cfg["num_experts"] * layers)
    count = lambda series: (registry_value("counters", series) or 0) / steps
    full = layers // cfg["full_attention_interval"]
    rows = count('serve_cache_rows_read_total{kind="full"}') / max(1, full)
    touched = count("moe_experts_touched_total")
    slots = run.facts["slots"]
    return {
        "steps": steps, "rows": rows, "touched": touched,
        "state_bytes": state_total / steps,
        "least_bytes": family.decode_step_bytes(
            cfg, rows, slots=slots, experts_touched=touched),
        "least_flops": family.decode_step_flops(cfg, rows, slots)}
