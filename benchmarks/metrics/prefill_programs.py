"""Distinct ``(bucket, B)`` prefill programs the engine ran, set-up's
warm-up included: the program's ``serve_prefill_programs`` gauge at the
end of the run."""

from benchmarks.harness.program_tape import registry_value


def read(_run):
    return registry_value("gauges", "serve_prefill_programs")
