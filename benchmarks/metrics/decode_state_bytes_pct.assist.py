"""Share of a decode step's least bytes that is recurrent state: the
program's ``serve_state_bytes_total{whose="all"}`` a step (every slot's
state read and written once) over the family's least bytes of that step
(every weight outside the routed experts, each TOUCHED expert, the
state, the live K/V rows).  ~75 by the configuration's arithmetic at 192
slots: the step's time is the state's, not the weights'.  None where the
program has no such counter."""

from benchmarks.harness.ssm_counts import decode_step_counts


def read(run):
    got = decode_step_counts(run)
    if got is None:
        return None
    return 100.0 * got["state_bytes"] / got["least_bytes"]
