"""Cache rows the decode steps' attention FETCHED over the rows the busy
slots' queries could see: the program's
``serve_cache_rows_fetched_total{kind}`` over its
``serve_cache_rows_read_total{kind}``, both kinds of layer, over the
whole run's decode steps.  100 is the floor (every fetched row is one a
query attends); a length-aware attention reads a few percent over it
(each slot's rows rounded up to a block), one that reads every row a
layer holds and masks the dead ones reads what the slots' emptiness
makes it (~172 at this traffic).  None where the program has no such
counter (one from before PR 29)."""

from benchmarks.harness.program_tape import registry_value


def read(_run):
    fetched, seen = (sum(registry_value(
        "counters", 'serve_cache_rows_%s_total{kind="%s"}' % (what, kind))
        or 0 for kind in ("window", "full")) for what in ("fetched", "read"))
    if not fetched or not seen:
        return None
    return 100.0 * fetched / seen
