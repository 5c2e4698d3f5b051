"""Nestable trace spans with supervisor context, emitted as JSONL events.

One event per closed span::

    {"name": "snapshot", "t0_s": 12.345678, "t0_unix": 1753900000.123456,
     "dur_s": 0.004321, "depth": 1, "parent": "steps", "step": 40,
     "attempt": 1, "phase": "full_bench"}

- ``t0_s``/``dur_s`` are monotonic-clock seconds (same clock as the
  metrics registry, so spans and metric snapshots line up);
  ``t0_unix`` is the SAME instant on the wall clock.  Both are
  deliberate: monotonic is the honest duration/ordering axis inside one
  process, but its epoch is per-boot — two ranks' monotonic stamps are
  incomparable, which made cross-process alignment impossible before
  round 10.  The wall stamp is what obs/timeline.py merges N ranks'
  events on (derived once at close from the shared ``_wall`` seam, so a
  pinned-clock test still gets bitwise-stable dumps).
- ``attempt``/``phase`` are propagated from the environment the
  supervisor exports (``SUPERVISE_ATTEMPT``; ``OBS_PHASE`` is set per
  capture-queue task), read at span close — a child never has to thread
  supervisor identity through its own call stack, which is exactly how
  the capture journal and the telemetry stay in agreement.
- Nesting is a thread-local stack: ``depth``/``parent`` come from the
  enclosing ``span`` on the same thread.

Sinks: every event goes to each registered sink (the flight recorder
registers itself on install) and, when ``OBS_TRACE_FILE`` names a path,
is appended there as one JSON line.  ``span()``/``event()`` close is
NOT a hot path — they wrap phases, snapshot writes, log-boundary
windows and a request's whole life, never the per-step dispatch — so
the per-event env lookups and the append-open are deliberate
simplicity, not an oversight.  Sink exceptions are swallowed: telemetry
must never kill the run it observes.

The hot path is :func:`hot_span`, for what happens at every boundary of
a serving loop (admit, dispatch, read-back, retire).  It does two
things only.  (1) It appends ``(name, t0, t1, parent, rid)`` —
``_metrics._now()`` stamps, the enclosing span's name, the request id
where the span belongs to one request — to :func:`tape`, a bounded
in-memory ring that every ``span()`` and ``event()`` lands on too;
:func:`tape_dropped` counts what fell off its end.  (2) It holds a
``jax.profiler.TraceAnnotation("dtf:" + name)`` open, so that a
profiler session sees the same span on the device trace's clock.  jax
is looked up through ``sys.modules`` — importing obs still never
imports it, and a process that has not imported jax has no device to
annotate.  Always on: there is no switch.  The guard is in
tests/test_obs.py (under 5 us a span; it measures about 1).

:func:`watch_gc` puts Python's collector on the same tape: a collection
of :data:`GC_SPAN_MIN_S` or more is a ``host.gc`` span under whichever
span it struck, and every collection adds its seconds to
``host_gc_seconds_total{generation}``.
"""

from __future__ import annotations

import collections
import contextlib
import gc
import itertools
import json
import os
import sys
import threading

from distributedtensorflowexample_tpu.obs import metrics as _metrics

_tls = threading.local()
_sinks: list = []
_SPAN_SECONDS = _metrics.histogram(
    "span_seconds", "wall seconds per closed trace span")

#: Entries the in-memory tape keeps (about 7 MB when full; a serving
#: loop at a dozen boundaries a second writes ~150 a second).
TAPE_LEN = 65536
_tape: collections.deque = collections.deque(maxlen=TAPE_LEN)
_seq = itertools.count()    # each entry's number: next() is one C call,
#                             so threads never hand out one number twice
_annotation = None      # jax.profiler.TraceAnnotation, once jax is loaded

#: A collection shorter than this adds to the counter only: the ring is
#: for the busy periods, and a young generation is collected in
#: microseconds, hundreds of times a second.
GC_SPAN_MIN_S = 1e-3
_GC_SECONDS = _metrics.counter(
    "host_gc_seconds_total",
    "seconds Python's collector ran, by the generation collected")
_gc_by_generation = None    # the three series, bound by watch_gc()
_gc_t0 = 0.0


def add_sink(sink) -> None:
    """Register ``sink(event: dict)`` for every future event."""
    if sink not in _sinks:
        _sinks.append(sink)


def remove_sink(sink) -> None:
    if sink in _sinks:
        _sinks.remove(sink)


def _stack() -> list:
    stack = getattr(_tls, "stack", None)
    if stack is None:
        stack = _tls.stack = []
    return stack


def record(name: str, t0: float, t1: float, parent: str | None = None,
           rid: str | None = None) -> None:
    """Append one closed span to the tape: the primitive every span of
    this module ends in."""
    _tape.append((name, t0, t1, parent, rid, next(_seq)))


def tape() -> list:
    """The newest :data:`TAPE_LEN` closed spans, oldest first, as
    ``(name, t0, t1, parent, rid)`` — monotonic seconds (``_now``), in
    order of closing."""
    return [e[:5] for e in list(_tape)]


def tape_dropped() -> int:
    """How many entries the ring has lost off its end: the entries ever
    numbered less those it holds (exact once no ``record`` is in
    flight on another thread)."""
    kept = list(_tape)
    return max(e[5] for e in kept) + 1 - len(kept) if kept else 0


def _trace_annotation():
    """``jax.profiler.TraceAnnotation`` if jax is already imported, else
    None (and then there is no profiler session to annotate)."""
    global _annotation
    if _annotation is None:
        jax = sys.modules.get("jax")
        profiler = getattr(jax, "profiler", None)
        _annotation = getattr(profiler, "TraceAnnotation", None)
    return _annotation


class hot_span:
    """``with hot_span("engine.decode.dispatch"): ...`` — the
    per-boundary span: the tape record and the profiler annotation,
    nothing else (no context, no histogram, no sinks, no file)."""

    __slots__ = ("name", "rid", "_stack", "_t0", "_ann", "_keep")

    def __init__(self, name: str, rid: str | None = None):
        self.name = name
        self.rid = rid
        self._keep = True

    def cancel(self) -> None:
        """Leave this span off the tape: for a boundary that turned out
        to hold no work (a serving loop polls an empty queue a hundred
        times a second, and the ring is for its busy periods)."""
        self._keep = False

    def __enter__(self):
        cls = _annotation or _trace_annotation()
        self._ann = None
        if cls is not None:
            self._ann = cls("dtf:" + self.name)
            self._ann.__enter__()
        self._stack = _stack()
        self._stack.append(self.name)
        self._t0 = _metrics._now()
        return self

    def __exit__(self, *exc):
        t1 = _metrics._now()
        stack = self._stack
        stack.pop()
        if self._keep:
            record(self.name, self._t0, t1, stack[-1] if stack else None,
                   self.rid)
        if self._ann is not None:
            self._ann.__exit__(*exc)
        return False


def _on_gc(phase: str, info: dict) -> None:
    """``gc.callbacks`` entry.  Collections do not nest and run under
    the GIL, on the thread that tripped the threshold: one start stamp
    serves, and that thread's innermost open span is the parent."""
    global _gc_t0
    if phase == "start":
        _gc_t0 = _metrics._now()
        return
    t1 = _metrics._now()
    _gc_by_generation[info["generation"]].inc(t1 - _gc_t0)
    if t1 - _gc_t0 >= GC_SPAN_MIN_S:
        stack = _stack()
        record("host.gc", _gc_t0, t1, stack[-1] if stack else None)


def watch_gc() -> None:
    """Put Python's collector on the tape and in the registry, from now
    on; a second call changes nothing."""
    global _gc_by_generation
    if _gc_by_generation is None:
        _gc_by_generation = tuple(_GC_SECONDS.labels(generation=g)
                                  for g in range(3))
        gc.callbacks.append(_on_gc)


def _context() -> dict:
    ctx = {}
    attempt = os.environ.get("SUPERVISE_ATTEMPT")
    if attempt:
        try:
            ctx["attempt"] = int(attempt)
        except ValueError:
            ctx["attempt"] = attempt
    phase = os.environ.get("OBS_PHASE")
    if phase:
        ctx["phase"] = phase
    # Rank context (OBS_RANK: fleet supervisor / distributed trainers):
    # spans from N ranks of one gang land in N flight files, and the
    # per-rank timeline obs_report renders needs each event to say
    # whose it is without joining on pid.
    rank = os.environ.get("OBS_RANK")
    if rank:
        try:
            ctx["rank"] = int(rank)
        except ValueError:
            ctx["rank"] = rank
    return ctx


def event(name: str, dur_s: float, t0_s: float | None = None,
          **attrs) -> dict:
    """Emit one span event without the context manager (hooks that
    measure a boundary-to-boundary window synthesize events this way).
    Returns the event dict (tests and callers may inspect it)."""
    stack = _stack()
    now = _metrics._now()
    if t0_s is None:
        t0_s = now - dur_s
    record(name, t0_s, t0_s + dur_s, stack[-1] if stack else None,
           attrs.get("rid"))
    rec = {"name": name,
           "t0_s": round(t0_s, 6),
           # The same open instant on the wall clock: wall-now minus the
           # monotonic elapsed-since-open.  Computed at CLOSE (not open)
           # so the synthesized-event path (hooks that only know a
           # duration) gets the identical stamp semantics for free.
           "t0_unix": round(_metrics._wall() - (now - t0_s), 6),
           "dur_s": round(dur_s, 6),
           "depth": len(stack),
           "parent": stack[-1] if stack else None,
           **_context(), **attrs}
    _SPAN_SECONDS.labels(name=name).observe(dur_s)
    for sink in list(_sinks):
        try:
            sink(rec)
        except Exception:
            pass
    path = os.environ.get("OBS_TRACE_FILE")
    if path:
        try:
            # default=str: a span attr the caller forgot to convert (a
            # numpy/jax scalar in the yielded attrs dict) serializes as
            # its string form instead of raising TypeError out of
            # span.__exit__ — and the broad except keeps the module
            # contract: telemetry must never kill the run it observes.
            with open(path, "a") as f:
                f.write(json.dumps(_metrics.json_safe(rec), sort_keys=True,
                                   allow_nan=False, default=str) + "\n")
        except Exception:
            pass
    return rec


@contextlib.contextmanager
def span(name: str, **attrs):
    """``with span("dispatch", step=7) as a: ...`` — yields the attr
    dict so the body can add results post-hoc (``a["rc"] = 0``)."""
    stack = _stack()
    stack.append(name)
    t0 = _metrics._now()
    try:
        yield attrs
    finally:
        stack.pop()
        event(name, _metrics._now() - t0, t0_s=t0, **attrs)
