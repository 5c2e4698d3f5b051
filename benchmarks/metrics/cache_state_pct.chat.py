"""Share of the engine's cache that is recurrent state and not K/V rows:
the program's ``serve_cache_bytes{kind="state"}`` gauge over the sum of
every kind's.  43 at 256 slots x 4,096 rows (3.30 GB of state beside
4.29 GB of rows); it rises as the cache gets shorter, which is what a
model with such layers is deployed for.  None where the program states
no such kind."""

from benchmarks.harness.program_tape import registry_value


def read(_run):
    kinds = {kind: registry_value(
        "gauges", 'serve_cache_bytes{kind="%s"}' % kind) or 0
        for kind in ("state", "full", "window")}
    if not kinds["state"]:
        return None
    return 100.0 * kinds["state"] / sum(kinds.values())
