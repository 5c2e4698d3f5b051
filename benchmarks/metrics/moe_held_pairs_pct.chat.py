"""Share of the (token, expert) pairs that landed on experts this chip
holds: the program's ``moe_pairs_total`` counters, held over held plus
absent, over the whole run (prefill and decode, warm-up included: the
counters are read once, after the run).  12.5 under even routing with 64
of 512 experts held.  None where the program has no such counter."""

from benchmarks.harness.program_tape import registry_value


def read(_run):
    held, absent = (registry_value(
        "counters", 'moe_pairs_total{where="%s"}' % where) or 0
        for where in ("held", "absent"))
    if not held + absent:
        return None
    return 100.0 * held / (held + absent)
