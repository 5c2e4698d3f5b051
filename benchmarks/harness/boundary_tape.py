"""Reading rules over the decode boundary's host time, piece by piece:
the program's ``engine.decode.wait`` (inside ``engine.decode.readback``),
``engine.decode.account`` and ``host.gc`` spans beside the ones
``program_tape`` already reads.

A *period* runs from one ``serve.step``'s start to the next one's: the
boundary and the benchmark's own bookkeeping after it.  The pieces are
taken over the periods that prefilled nothing and whose successor
prefilled nothing either (a plain decode boundary between two others),
and they add up to the period by construction: what no span covers is
the step's self time.  A program from before these spans (no
``engine.decode.wait`` on its tape) reads None everywhere, as does a
ring that wrapped inside what is read."""

from __future__ import annotations

import bisect
import collections

from benchmarks.harness import program_tape as pt

WAIT = "engine.decode.wait"
GC = "host.gc"
PREFILL = "engine.prefill."
DECODE_PROGRAM = "decode_step"      # in the decode program's module name

#: One boundary: its ``serve.step``, where the next one starts, and the
#: entries that start in between.
Period = collections.namedtuple("Period", "step end held")


def _entries(run, which=pt.window_entries):
    """The window's (or the tail's) entries, or None: no tape, a wrapped
    ring, or a program from before ``engine.decode.wait``."""
    entries = which(run)
    if entries is None or not any(e.name == WAIT for e in entries):
        return None
    return entries


def periods(entries) -> list:
    """Every ``serve.step`` that has a successor, in order."""
    steps = sorted((e for e in entries if e.name == pt.STEP),
                   key=lambda e: e.t0)
    starts = [s.t0 for s in steps]
    held = [[] for _ in steps]
    for e in entries:
        k = bisect.bisect_right(starts, e.t0) - 1
        if e.name != pt.STEP and k >= 0:
            held[k].append(e)
    return [Period(s, nxt.t0, h)
            for s, nxt, h in zip(steps, steps[1:], held)]


def _seconds(p: Period, name: str) -> float:
    return sum(e.t1 - e.t0 for e in p.held if e.name == name)


def _prefilled(p: Period) -> bool:
    return any(e.name.startswith(PREFILL) for e in p.held)


def plain(every: list) -> list:
    """The periods that prefilled nothing and whose successor prefilled
    nothing (the last one's successor is not known: left out)."""
    return [p for p, nxt in zip(every, every[1:])
            if not _prefilled(p) and not _prefilled(nxt)]


def pieces(p: Period) -> dict:
    """Seconds of one period by piece, in the order the host passes
    through them from the dispatch on; they sum to the period."""
    admit, dispatch, wait, readback, account, retire = (
        _seconds(p, name) for name in (
            "serve.admit", "engine.decode.dispatch", WAIT,
            "engine.decode.readback", "engine.decode.account",
            "serve.retire"))
    covered = admit + dispatch + readback + account + retire
    return {"dispatch": dispatch, "wait": wait, "fetch": readback - wait,
            "account": account, "retire": retire, "admit": admit,
            "step self": p.step.t1 - p.step.t0 - covered,
            "between steps": p.end - p.step.t1}


def _means(rows: list) -> dict:
    return {k: sum(r[k] for r in rows) / len(rows) for k in rows[0]}


def _window(run):
    """Mean seconds by piece over the window's plain periods (and how
    many there were), or None."""
    def reduce():
        entries = _entries(run)
        rows = [pieces(p) for p in plain(periods(entries))] if entries \
            else []
        return (_means(rows), len(rows)) if rows else None
    return pt._once(run, "boundary_pieces", reduce)


# ---- host-clock metrics --------------------------------------------------

def decode_host_ms(run):
    """Mean period less the ``engine.decode.wait`` inside it: what the
    host does in series with the device.  Prints the run's one line of
    the boundary's pieces, with the traced tail's device time beside
    them where there is a trace."""
    got = _window(run)
    if got is None:
        return None
    mean, n = got
    line = ("decode boundary: " + " + ".join(
        f"{k} {1e3 * v:.3f}" for k, v in mean.items())
        + f" = period {1e3 * sum(mean.values()):.3f} ms ({n} boundaries "
        f"of the window that prefilled nothing)")
    traced = _tail(run)
    if traced is not None:
        tail, device, n = traced
        line += (f"; device {1e3 * device:.3f} ms, sync latency "
                 f"{1e3 * (tail['wait'] - device):.3f} ms ({n} boundaries "
                 f"of the traced tail: period "
                 f"{1e3 * sum(tail.values()):.3f}, wait "
                 f"{1e3 * tail['wait']:.3f})")
    pt.log(line)
    return 1e3 * (sum(mean.values()) - mean["wait"])


def decode_fetch_ms(run):
    """Mean ``engine.decode.readback`` less its child
    ``engine.decode.wait``: the copy to the host and the counts."""
    if _entries(run) is None:
        return None
    return (pt.span_mean_ms(run, "engine.decode.readback")
            - pt.span_mean_ms(run, WAIT))


def span_mean_ms(run, name: str):
    """``program_tape.span_mean_ms`` on a program that has this PR's
    spans."""
    return None if _entries(run) is None else pt.span_mean_ms(run, name)


def _held_line(p: Period) -> str:
    """One boundary by what it held; the three prefill spans apart (a
    long ``dispatch`` is the host tracing or loading a program, a long
    ``readback`` the device or the runtime)."""
    prefill = {part: sum(e.t1 - e.t0 for e in p.held
                         if e.name == PREFILL + part)
               for part in ("pack", "dispatch", "readback")}
    whole, wait = p.end - p.step.t0, _seconds(p, WAIT)
    return (f"{1e3 * whole:.1f} ms = engine.prefill " + " ".join(
        f".{k} {1e3 * v:.1f}" for k, v in prefill.items())
        + f" + {WAIT} {1e3 * wait:.1f} + the rest "
        f"{1e3 * (whole - sum(prefill.values()) - wait):.1f}, {GC} "
        f"{1e3 * _seconds(p, GC):.1f} wherever it struck")


def boundary_longest_ms(run):
    """The window's longest period; prints what the three longest
    held."""
    entries = _entries(run)
    every = periods(entries) if entries else []
    if not every:
        return None
    longest = sorted(every, key=lambda p: p.step.t0 - p.end)[:3]
    pt.log("longest boundaries: " + "; ".join(map(_held_line, longest))
           + f"; compilations in the window: {run.compiles_in_window}")
    return 1e3 * (longest[0].end - longest[0].step.t0)


def host_gc_share_pct(run):
    """Seconds of the window inside ``host.gc`` spans (collections of a
    millisecond or more), over the window's."""
    entries = _entries(run)
    if entries is None:
        return None
    spans = [e.t1 - e.t0 for e in entries if e.name == GC]
    whole = {g: pt.registry_value(
        "counters", f'host_gc_seconds_total{{generation="{g}"}}') or 0.0
        for g in range(3)}
    pt.log(f"collector: {len(spans)} collections of 1 ms or more in the "
           f"window, {1e3 * sum(spans):.1f} ms (the longest "
           f"{1e3 * max(spans, default=0.0):.1f}); over the whole run by "
           "generation " + ", ".join(
               f"{g}: {v:.3f} s" for g, v in whole.items()))
    t0, t1 = run.facts["window"]
    return 100.0 * sum(spans) / (t1 - t0)


# ---- the traced tail -----------------------------------------------------

def _tail(run):
    """Over the plain periods of the traced tail whose wait lies over a
    run of the decode program on the ``XLA Modules`` line: (mean seconds
    by piece, the program's mean seconds on the device, how many), or
    None.  Durations only: the anchor finds the program's run, the skew
    between the trace's planes (1.4 to 1.8 ms beside a step of 11 to 82)
    does not enter the numbers."""
    def reduce():
        entries = _entries(run, pt.tail_entries)
        offset = pt.anchor(run) if entries else None
        if offset is None or not run.trace.device_modules:
            return None
        programs = [e for e in run.trace.device_modules[
            min(run.trace.device_modules)] if DECODE_PROGRAM in e.name]
        starts = [e.start for e in programs]
        rows, device = [], []
        for p in plain(periods(entries)):
            wait = next((e for e in p.held if e.name == WAIT), None)
            if wait is None:
                continue
            t0, t1 = wait.t0 + offset, wait.t1 + offset
            k = bisect.bisect_left(starts, t1)
            for e in programs[max(0, k - 2):k]:
                # the program's run, not a neighbour's edge the skew let in
                if 2 * (min(e.end, t1) - max(e.start, t0)) > e.end - e.start:
                    rows.append(pieces(p))
                    device.append(e.end - e.start)
                    break
        if not rows:
            return None
        return _means(rows), sum(device) / len(device), len(rows)
    return pt._once(run, "boundary_tail", reduce)


def decode_sync_latency_ms(run):
    """Mean ``engine.decode.wait`` less the device time of the decode
    program it waited for: launch plus completion latency."""
    traced = _tail(run)
    if traced is None:
        return None
    tail, device, _ = traced
    return 1e3 * (tail["wait"] - device)
