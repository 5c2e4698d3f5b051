"""What a decode step of a model whose EVERY layer is latent attention
did, from the program's own counters (read once, after the run: they are
over the whole run's decode steps) and from the traced tail's own
boundaries: shared by the ``.reason`` readers that hold a step or the
latent kernel to the family's least work.  ``latent_counts.py`` is the
same for a model that keeps recurrent state beside its rows (it returns
None without ``serve_state_bytes_total`` and reads that family's key for
the experts held); this one has no state to count and asks the family
which layers are which (``family.kinds``)."""

from __future__ import annotations

from benchmarks.harness.peaks import roofline_seconds
from benchmarks.harness.program_tape import registry_value
from benchmarks.harness.readers import decode_device_seconds_per_step


def decode_step_counts(run):
    """``{steps, rows, touched, row_bytes, least_bytes, least_flops}`` a
    decode step, or None where the program has no such counters.
    ``rows`` are the latent rows the busy slots' queries read in ONE
    layer — over the traced tail's boundaries where the run has them
    (the rows grow through a window, and the device times the readers
    divide by are the tail's), else the whole run's mean —, ``touched``
    the held experts a pair reached over all layers, ``row_bytes`` the
    rows' part of ``least_bytes``; the two ``least_*`` are the family's
    counts for that step."""
    slots_total = registry_value("counters", "moe_expert_slots_total")
    read_total = registry_value(
        "counters", 'serve_cache_rows_read_total{kind="latent"}')
    if not slots_total or not read_total:
        return None
    cfg, family = run.config, run.family
    latent, expert_layers = family.kinds(cfg)
    steps = slots_total / (cfg["n_routed_experts"] * expert_layers)
    rows = read_total / steps / latent
    tail = [b for b in run.facts.get("tail_boundaries", []) if b.busy > 0]
    if tail:
        rows = sum(b.live_cache_rows for b in tail) / len(tail)
    touched = (registry_value("counters", "moe_experts_touched_total")
               or 0) / steps
    return {
        "steps": steps, "rows": rows, "touched": touched,
        "row_bytes": latent * family.latent_decode_bytes(cfg, rows),
        "least_bytes": family.decode_step_bytes(cfg, rows,
                                                experts_touched=touched),
        "least_flops": family.decode_step_flops(cfg, rows,
                                                run.facts["slots"])}


def decode_roofline_pct(run):
    """The least time of one decode step (:func:`decode_step_counts`'
    bytes or FLOPs, whichever takes longer) over the device time of one
    run of the decode program in the traced tail."""
    per_step = decode_device_seconds_per_step(run)
    if per_step is None or run.peaks is None:
        return None
    got = decode_step_counts(run)
    if got is None:
        return None
    least, bound = roofline_seconds(got["least_flops"], got["least_bytes"],
                                    run.peaks)
    print(f"[bench] decode roofline: {bound}-bound, least "
          f"{1e3 * least:.3f} ms, device {1e3 * per_step:.3f} ms a step; "
          f"a step touched {got['touched']:.1f} experts and read "
          f"{got['rows']:.0f} rows a layer "
          f"({got['row_bytes'] / 1e9:.2f} of {got['least_bytes'] / 1e9:.2f} "
          f"GB)", flush=True)
    return 100.0 * least / per_step
