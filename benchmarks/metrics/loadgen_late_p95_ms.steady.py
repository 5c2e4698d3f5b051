"""How late the benchmark's generator submitted a request after it was
due: 95th percentile.  A starved generator must not read as a fast
server."""

from benchmarks.harness.readers import p95_ms


def read(run):
    return p95_ms(run, "late_s")
