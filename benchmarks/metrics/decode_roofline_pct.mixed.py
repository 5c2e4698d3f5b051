"""The decode step's share of its roofline where the step's least work
depends on what was routed: every weight outside the routed experts
once, each routed expert that a pair TOUCHED once (the program's
``moe_experts_touched_total``, not an average of the routing), the cache
rows the queries read by kind of layer (``serve_cache_rows_read_total``),
or the FLOPs, whichever takes longer — over the device time of one run
of the decode program in the traced tail.  The counters are per decode
step over the whole run (read once, after it); the counting functions
are the family's.  None where the program has no such counters."""

from benchmarks.harness.peaks import roofline_seconds
from benchmarks.harness.program_tape import registry_value
from benchmarks.harness.readers import decode_device_seconds_per_step


def read(run):
    per_step = decode_device_seconds_per_step(run)
    slots_total = registry_value("counters", "moe_expert_slots_total")
    if per_step is None or run.peaks is None or not slots_total:
        return None
    cfg, family = run.config, run.family
    layers = cfg["num_hidden_layers"] - cfg["num_dense_layers"]
    steps = slots_total / (cfg["num_experts"] * layers)
    count = lambda series: (registry_value("counters", series) or 0) / steps
    kinds = {t: cfg["layer_types"].count(t)
             for t in ("full_attention", "sliding_attention")}
    full = count('serve_cache_rows_read_total{kind="full"}') \
        / max(1, kinds["full_attention"])
    window = count('serve_cache_rows_read_total{kind="window"}') \
        / max(1, kinds["sliding_attention"])
    touched = count("moe_experts_touched_total")
    least, bound = roofline_seconds(
        family.decode_step_flops(cfg, full, run.facts["slots"],
                                 window_rows=window),
        family.decode_step_bytes(cfg, full, window_rows=window,
                                 experts_touched=touched), run.peaks)
    print(f"[bench] decode roofline: {bound}-bound, least "
          f"{1e3 * least:.3f} ms, device {1e3 * per_step:.3f} ms a step; "
          f"a step touched {touched:.1f} experts and read {full:.0f} rows "
          f"a full layer, {window:.0f} a window layer", flush=True)
    return 100.0 * least / per_step
