"""Reading rules over the PROGRAM's own spans and counters: the tape
``obs/trace.py`` keeps in memory (``serve.step`` and what it holds, the
``engine.*`` spans, a request's ``serve_queue`` / ``serve_prefill`` /
``serve_decode``) and the ``serve_prefill_*`` series of ``obs/metrics``.

Host-clock numbers are over the run's window: the tape is stamped with
``time.monotonic()``, the clock ``run.facts["window"]`` is on.  Device
numbers lay the traced tail's idle time to the innermost program span;
for that the tape has to be put on the trace's clock, and the offset
comes from the benchmark's own spans, which exist on both:
``run.spans.tape`` (host clock) and ``run.trace.host_spans`` (trace
clock).  A reader that finds no tape (a program from before it had
one), a ring that wrapped inside what it reads, or no sound anchor
returns None; it never guesses."""

from __future__ import annotations

import collections
import statistics

from benchmarks.harness import stats
from benchmarks.harness import trace as tr

Entry = collections.namedtuple("Entry", "name t0 t1 parent rid")

STEP = "serve.step"
#: The per-boundary spans, each laid its share of the device's idle time.
PROGRAM_SPANS = (STEP, "serve.admit", "serve.retire",
                 "engine.prefill.pack", "engine.prefill.dispatch",
                 "engine.prefill.readback", "engine.decode.dispatch",
                 "engine.decode.readback")
UNATTRIBUTED = "unattributed"
#: A pair of one span on both clocks is taken only if its two durations
#: agree to this, and kept only if its offset lies this close to the
#: median of all of them.
PAIR_TOLERANCE_S = 50e-6
MIN_PAIRS = 3


def log(msg: str) -> None:
    print(f"[bench] {msg}", flush=True)


def _once(run, key: str, make):
    cache = run.facts.setdefault("program_tape", {})
    if key not in cache:
        cache[key] = make()
    return cache[key]


# ---- the tape ------------------------------------------------------------

def program_tape() -> tuple:
    """(entries, dropped) of the program's tape, or (None, 0) where the
    program has none."""
    from distributedtensorflowexample_tpu.obs import trace as obs_trace
    if not hasattr(obs_trace, "tape"):
        return None, 0
    return [Entry(*e) for e in obs_trace.tape()], obs_trace.tape_dropped()


def select(entries, dropped: int, window: tuple, ending: bool = False):
    """The entries lying wholly inside ``window`` (``ending``: those that
    END inside it, wherever they began); None for no tape, an empty one,
    or a ring that has lost entries and whose oldest is not older than
    the window (some of the window's may be among the lost: the ring is
    in order of closing)."""
    if not entries:
        return None
    if dropped and entries[0].t1 >= window[0]:
        return None
    return [e for e in entries if window[0] <= e.t1 <= window[1]
            and (ending or e.t0 >= window[0])]


def _tape(run) -> tuple:
    return _once(run, "tape", program_tape)


def window_entries(run):
    return _once(run, "window", lambda: select(
        *_tape(run), run.facts["window"]))


def tail_entries(run):
    """Everything after the window closed (the traced tail is there)."""
    return _once(run, "tail", lambda: select(
        *_tape(run), (run.facts["window"][1], float("inf"))))


def inside(entries, outer_name: str, inner) -> list:
    """For each span ``outer_name``, in order: (span, the entries that
    ``inner(entry)`` picks and that start inside it)."""
    outer = sorted((e for e in entries if e.name == outer_name),
                   key=lambda e: e.t0)
    picked = sorted((e for e in entries if inner(e)), key=lambda e: e.t0)
    out, j = [], 0
    for sp in outer:
        while j < len(picked) and picked[j].t0 < sp.t0:
            j += 1
        k = j
        while k < len(picked) and picked[k].t0 < sp.t1:
            k += 1
        out.append((sp, picked[j:k]))
        j = k
    return out


def self_times(entries, name: str) -> list:
    """Seconds of each span ``name`` less what the per-boundary spans
    that name it as parent cover inside it.  A request's events closed
    inside it (``serve_prefill`` runs up to the request's first token)
    are not its children: they are laid over the boundary's spans, and
    what they cover of the step is the step's own bookkeeping."""
    return [(sp.t1 - sp.t0) - tr.total(tr.union(
        (e.t0, min(e.t1, sp.t1)) for e in mine))
        for sp, mine in inside(entries, name, lambda e: (
            e.parent == name and e.name in PROGRAM_SPANS))]


def _mean_ms(values):
    values = list(values)
    return 1e3 * sum(values) / len(values) if values else None


# ---- host-clock metrics --------------------------------------------------

def span_mean_ms(run, name: str):
    """Mean duration of the window's spans ``name``."""
    entries = window_entries(run)
    if entries is None:
        return None
    return _mean_ms(e.t1 - e.t0 for e in entries if e.name == name)


def step_self_ms(run):
    """Mean self time of ``serve.step`` over the window's boundaries."""
    entries = window_entries(run)
    if entries is None:
        return None
    return _mean_ms(self_times(entries, STEP))


def decode_dispatch_ms(run):
    """Mean ``engine.decode.dispatch``; prints it beside the read-back
    and the benchmark's own span round the same calls."""
    dispatch = span_mean_ms(run, "engine.decode.dispatch")
    readback = span_mean_ms(run, "engine.decode.readback")
    if dispatch is not None and readback is not None:
        outer = run.samples.get("decode_step_s")
        log(f"decode: dispatch {dispatch:.3f} ms + read-back "
            f"{readback:.3f} ms = {dispatch + readback:.3f} ms; the "
            f"benchmark's span round decode() "
            + (f"{_mean_ms(outer):.3f} ms" if outer else "has no sample"))
    return dispatch


def prefill_host_ms(run):
    """Per ``serve.step`` that prefilled: ``engine.prefill.pack`` plus
    ``engine.prefill.dispatch`` of all its bucket groups; the mean."""
    entries = window_entries(run)
    if entries is None:
        return None
    sums = [sum(e.t1 - e.t0 for e in parts) for _, parts in inside(
        entries, STEP, lambda e: e.name in (
            "engine.prefill.pack", "engine.prefill.dispatch"))]
    return _mean_ms(x for x in sums if x)


def request_p95_ms(run, event: str):
    """95th percentile of a per-request event's duration, over those
    that end inside the window (the benchmark's percentile rule)."""
    entries = _once(run, "ending", lambda: select(
        *_tape(run), run.facts["window"], ending=True))
    if entries is None:
        return None
    q = stats.percentile([e.t1 - e.t0 for e in entries if e.name == event
                          and e.rid is not None], 0.95)
    return None if q is None else 1e3 * q


# ---- counters ------------------------------------------------------------

def registry_value(kind: str, series: str):
    """A series of the program's registry by its key
    (``name{label="v"}``), or None where it was never touched."""
    from distributedtensorflowexample_tpu.obs import metrics as obs_metrics
    got = obs_metrics.registry().snapshot()[kind].get(series)
    if isinstance(got, dict):
        got = got["value"]
    return got


def prefill_pad_pct(_run):
    """Padding's share of the positions bucketed prefill ran, over the
    WHOLE run (warm-up, ramp and tail included): the counters are read
    once, after the run, and the benchmark takes no reading as the
    window opens."""
    pad = registry_value(
        "counters", 'serve_prefill_positions_total{kind="pad"}')
    prompt = registry_value(
        "counters", 'serve_prefill_positions_total{kind="prompt"}')
    if not prompt:
        return None
    return 100.0 * (pad or 0) / ((pad or 0) + prompt)


# ---- the tape on the trace's clock ---------------------------------------

def _paired_offsets(host: list, traced: list) -> list:
    """``traced`` (trace clock) is a run of consecutive members of
    ``host`` (host clock), both in order: the offsets (trace − host) of
    the pairs whose durations agree, at the alignment where most do."""
    best: list = []
    tie = False
    for shift in range(len(host) - len(traced) + 1):
        got = [e.start - t0 for (t0, t1), e in zip(host[shift:], traced)
               if abs((e.end - e.start) - (t1 - t0)) <= PAIR_TOLERANCE_S]
        if len(got) > len(best):
            best, tie = got, False
        elif got and len(got) == len(best):
            tie = True
    return [] if tie else best


def anchor(run):
    """Seconds to add to a host-clock stamp to put it on the trace's
    clock, from the benchmark's own spans of the traced tail; None where
    fewer than :data:`MIN_PAIRS` pairs agree."""
    def find():
        if run.trace is None or run.spans is None:
            return None
        closed = run.facts["window"][1]
        offsets = []
        for name, pairs in run.spans.tape.items():
            offsets += _paired_offsets(
                sorted(p for p in pairs if p[0] >= closed),
                [e for e in run.trace.host_spans if e.name == name])
        if len(offsets) < MIN_PAIRS:
            log(f"no anchor: {len(offsets)} of the benchmark's spans "
                f"pair up between the host tape and the trace")
            return None
        mid = statistics.median(offsets)
        kept = [o for o in offsets if abs(o - mid) <= PAIR_TOLERANCE_S]
        if len(kept) < max(MIN_PAIRS, len(offsets) // 2):
            log(f"no anchor: of {len(offsets)} pairs only {len(kept)} "
                f"agree on the offset to {1e6 * PAIR_TOLERANCE_S:.0f} us")
            return None
        log(f"program spans onto the trace's clock: offset "
            f"{statistics.median(kept):.6f} s from {len(kept)} pairs of "
            f"{len(offsets)}, {1e6 * (max(kept) - min(kept)):.1f} us apart")
        return statistics.median(kept)
    return _once(run, "anchor", find)


def _agreement(run, shifted: list) -> None:
    """Print how far the program's decode spans lie from the benchmark's
    span round the same ``engine.decode`` call, both on the trace."""
    def farthest(mine: list, theirs: list) -> float:
        return max(min(abs(m - t) for m in mine) for t in theirs)

    outer = [e for e in run.trace.host_spans if e.name == "serve_decode"]
    starts = [e.start for e in shifted
              if e.name == "engine.decode.dispatch"]
    ends = [e.end for e in shifted if e.name == "engine.decode.readback"]
    if outer and starts and ends:
        log(f"engine.decode.* against bench:serve_decode over "
            f"{len(outer)} calls: starts within "
            f"{1e6 * farthest(starts, [e.start for e in outer]):.1f} us, "
            f"ends within "
            f"{1e6 * farthest(ends, [e.end for e in outer]):.1f} us")


def idle_by_span(run):
    """{span name: seconds}: device idle time of the traced window laid
    to the innermost program span the host was in (``serve.step``'s own
    entry is its self time), ``unattributed`` outside any."""
    def reduce():
        entries = tail_entries(run)
        offset = anchor(run) if entries else None
        if offset is None:
            return None
        shifted = sorted(
            (tr.Event(e.name, e.t0 + offset, e.t1 + offset)
             for e in entries if e.name in PROGRAM_SPANS),
            key=lambda e: (e.start, -e.end))
        _agreement(run, shifted)
        gaps = dict(tr.idle_gaps(
            tr.Trace(run.trace.device_ops, shifted), run.trace_window,
            top=len(PROGRAM_SPANS) + 1, unattributed=UNATTRIBUTED))
        window = run.trace_window[1] - run.trace_window[0]
        log("idle by program span: " + ", ".join(
            f"{k} {v:.4f} s ({100 * v / window:.2f}%)"
            for k, v in sorted(gaps.items(), key=lambda kv: -kv[1]))
            + f" of {window:.3f} s")
        return gaps
    return _once(run, "idle", reduce)


def idle_pct(run, names: tuple):
    """Share of the traced window the device was idle inside the program
    spans ``names``."""
    gaps = idle_by_span(run)
    if gaps is None:
        return None
    window = run.trace_window[1] - run.trace_window[0]
    return 100.0 * sum(gaps.get(n, 0.0) for n in names) / window
