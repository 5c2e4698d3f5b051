"""Share of the engine's cache that is latent rows (one compressed row a
position) and not recurrent state: the program's
``serve_cache_bytes{kind="latent"}`` gauge over the sum of every kind's.
44 at 256 slots x 8,192 rows (2.68 GB of rows as the program pads them
beside 3.34 GB of state): with K/V rows of 32 heads in their place the
rows alone would be 43 GB.  None where the program states no such kind."""

from benchmarks.harness.program_tape import registry_value


def read(_run):
    kinds = {kind: registry_value(
        "gauges", 'serve_cache_bytes{kind="%s"}' % kind) or 0
        for kind in ("latent", "state", "full", "window")}
    if not kinds["latent"]:
        return None
    return 100.0 * kinds["latent"] / sum(kinds.values())
