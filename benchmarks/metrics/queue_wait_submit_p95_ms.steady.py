"""From a request's submission to the moment its batch left the queue
(``Request.prefill_t``): 95th percentile of the program's ``serve_queue``
events that end inside the window."""

from benchmarks.harness.program_tape import request_p95_ms


def read(run):
    return request_p95_ms(run, "serve_queue")
