"""The decode step's share of its roofline for a model with
recurrent-state layers: the least time of a step — every weight outside
the routed experts once, each routed expert a pair TOUCHED once
(``moe_experts_touched_total``), every slot's recurrent and convolution
state read and written once, the K/V rows the queries read
(``serve_cache_rows_read_total``), or the FLOPs, whichever takes longer
— over the device time of one run of the decode program in the traced
tail.  The roofline share of everything new at once (the recurrence's
token-step kernel has a share of its own beside it,
``gated_delta_step_roofline_pct.chat``).  The counting functions are the
family's.  None where the program has no such counters."""

from benchmarks.harness.peaks import roofline_seconds
from benchmarks.harness.readers import decode_device_seconds_per_step
from benchmarks.harness.state_counts import decode_step_counts


def read(run):
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
