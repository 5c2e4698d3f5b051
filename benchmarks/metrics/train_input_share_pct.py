"""Share of the window the train loop spent fetching the next batch
(the program's ``loop_input_seconds_total`` counter)."""


def read(run):
    if "loop_input_s" not in run.facts:
        return None
    t0, t1 = run.facts["window"]
    return 100.0 * run.facts["loop_input_s"] / (t1 - t0)
