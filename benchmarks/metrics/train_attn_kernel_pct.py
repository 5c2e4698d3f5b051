"""Share of the LM blocks the run traced that took the blocked attention
kernels: the program's ``lm_attention_blocks_total`` counter, the
``pallas`` series over both (``pallas`` and ``einsum``).  A share and
not a count: a run traces the step, the model's initialisation and the
probes more than once.  None where the program has no such counter (one
from before PR 25) or traced no block."""

from benchmarks.harness.program_tape import registry_value


def read(_run):
    pallas, einsum = (registry_value(
        "counters", 'lm_attention_blocks_total{impl="%s"}' % impl) or 0
        for impl in ("pallas", "einsum"))
    if not pallas + einsum:
        return None
    return 100.0 * pallas / (pallas + einsum)
