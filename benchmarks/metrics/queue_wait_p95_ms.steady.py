"""From a request's due time to the start of the prefill that admitted
it (the program's tape stamps ``admit_t`` only after that prefill, with
the first token): 95th percentile over the requests due in the window."""

from benchmarks.harness.readers import p95_ms


def read(run):
    return p95_ms(run, "queue_wait_s")
