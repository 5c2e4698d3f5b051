"""Share of the cache rows the decode queries read that lie in window
layers' rings: the program's ``serve_cache_rows_read_total{kind}``,
window over window plus full, over the whole run's decode steps.  With
every layer holding ``cache_len`` rows it would be the window layers'
share of the layers (80 of 100 here); the rings cut it.  None where the
program has no such counter."""

from benchmarks.harness.program_tape import registry_value


def read(_run):
    window, full = (registry_value(
        "counters", 'serve_cache_rows_read_total{kind="%s"}' % kind) or 0
        for kind in ("window", "full"))
    if not window + full:
        return None
    return 100.0 * window / (window + full)
