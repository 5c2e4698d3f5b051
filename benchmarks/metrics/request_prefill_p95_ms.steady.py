"""From the moment a request's batch left the queue to its first token:
95th percentile of the program's ``serve_prefill`` events that end
inside the window."""

from benchmarks.harness.program_tape import request_p95_ms


def read(run):
    return request_p95_ms(run, "serve_prefill")
