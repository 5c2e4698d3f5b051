"""Host time of one dispatch of the compiled step, ending in
``block_until_ready``, per optimizer step it fused: the mean over the
window of the benchmark's span around the call."""


def read(run):
    d = run.samples.get("train_dispatch_s")
    if not d:
        return None
    return 1e3 * sum(d) / len(d) / run.facts["steps_per_dispatch"]
