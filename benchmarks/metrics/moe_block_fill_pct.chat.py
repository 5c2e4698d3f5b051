"""Share of the sorted rows the expert walks handed to the grouped
products that were pairs on experts this chip holds: the program's
``moe_pairs_total{where="held"}`` over its ``moe_rows_walked_total``
(blocks walked x rows a block), over the whole run (prefill and decode,
warm-up included: the counters are read once, after the run).  100 is a
walk whose every block is full; a token step of 2,560 pairs of which
~320 are held reads 16 in blocks of 2,048 rows and 50 in blocks of 640.
None where the program has no such counter (one from before PR 35)."""

from benchmarks.harness.program_tape import registry_value


def read(_run):
    held = registry_value("counters", 'moe_pairs_total{where="held"}')
    walked = registry_value("counters", "moe_rows_walked_total")
    if not held or not walked:
        return None
    return 100.0 * held / walked
