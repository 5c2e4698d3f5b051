"""Share of the window the serving loop spent inside
``DecodeEngine.prefill_many`` (host clock, the benchmark's span;
``prefill_share_pct.mixed``'s rule)."""


def read(run):
    s = run.samples.get("prefill_s")
    if s is None:
        return None
    t0, t1 = run.facts["window"]
    return 100.0 * sum(s) / (t1 - t0)
