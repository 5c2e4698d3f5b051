"""Share of the window the train loop spent in its after-step hooks
(the program's ``loop_hook_seconds_total`` counter)."""


def read(run):
    if "loop_hook_s" not in run.facts:
        return None
    t0, t1 = run.facts["window"]
    return 100.0 * run.facts["loop_hook_s"] / (t1 - t0)
