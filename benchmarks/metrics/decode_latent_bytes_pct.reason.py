"""Share of a decode step's least bytes that is latent rows: the rows the
busy slots' queries read in every layer at 1,152 bytes a row, over the
family's least bytes of that step (every weight outside the routed
experts, each TOUCHED expert, the rows).  ~30 by the configuration's
arithmetic at 80 slots of ~4,900 live rows.  None where the program has
no such counters."""

from benchmarks.harness.mla_counts import decode_step_counts


def read(run):
    got = decode_step_counts(run)
    if got is None:
        return None
    return 100.0 * got["row_bytes"] / got["least_bytes"]
