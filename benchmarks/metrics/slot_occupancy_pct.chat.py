"""Share of the decode slots that held a live request, averaged over
the window's decode boundaries (``ContinuousBatcher.step()``'s count)."""


def read(run):
    b = run.facts.get("boundaries")
    if not b:
        return None
    return 100.0 * sum(x.busy for x in b) / len(b) / run.facts["slots"]
