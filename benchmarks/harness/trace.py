"""From a profiler trace (``.xplane.pb``) to numbers: the device's busy
and idle time, the operations that took most of it, each idle gap laid
to what the host was doing, copy time and exposed collective time.

Read with nothing but jax (``jax.profiler.ProfileData``).  A TPU device
plane is named ``/device:TPU:<n>``; its ``XLA Ops`` line holds one event
per executed HLO operation.  The benchmark's own host spans are
``jax.profiler.TraceAnnotation`` events whose names start with
:data:`SPAN_PREFIX`, on the host plane's thread lines, on the same
clock."""

from __future__ import annotations

import dataclasses
import glob
import os
import re

SPAN_PREFIX = "bench:"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute", "collective-broadcast")
COPIES = ("copy", "copy-start", "copy-done")


@dataclasses.dataclass(frozen=True)
class Event:
    name: str           # a span's name; an instruction's without number
    start: float        # seconds on the trace's clock
    end: float
    opcode: str = ""    # HLO opcode of a device operation
    shape: str = ""     # its (first) result shape, ``bf16[1,96,1024]``


@dataclasses.dataclass
class Trace:
    device_ops: dict    # device index -> [Event] sorted by start
    host_spans: list    # [Event] of the benchmark's own annotations
    device_modules: dict = dataclasses.field(default_factory=dict)
    #                     device index -> [Event], one per program run


def newest_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


MODULES_LINE = "XLA Modules"
_INSTRUCTION = re.compile(r"^%?([\w.\-]+)\s*=\s*(.*)$", re.S)
_SHAPE = re.compile(r"([a-z]+\d*\[[\d,]*\])")
_OPCODE = re.compile(r"[\]\}\)]\s+([a-z][a-z0-9\-]*)\(")


def parse_op(text: str) -> tuple:
    """(name without number, opcode, first result shape) of a device
    operation.  XLA:TPU names the event by the instruction's whole text,
    ``%copy.3 = bf16[1,96,1024,12,64]{4,3,2,1,0:T(8,128)} copy(%p)``;
    a bare ``fusion.12`` is its own name and opcode."""
    m = _INSTRUCTION.match(text.strip())
    inst, rest = (m.group(1), m.group(2)) if m else (text.strip("% "), "")
    stem = re.sub(r"[.\d]+$", "", inst)
    shape = _SHAPE.search(rest)
    opcode = _OPCODE.search(rest)
    return (stem, opcode.group(1) if opcode else stem,
            shape.group(1) if shape else "")


def _events(line, parse: bool) -> list:
    out = []
    for e in line.events:
        t0 = e.start_ns * 1e-9
        t1 = (e.start_ns + e.duration_ns) * 1e-9
        if parse:
            stem, opcode, shape = parse_op(e.name)
            out.append(Event(stem, t0, t1, opcode, shape))
        else:
            out.append(Event(e.name, t0, t1))
    return sorted(out, key=lambda e: e.start)


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    return from_profile(ProfileData.from_file(path))


def from_profile(pd) -> Trace:
    device_ops, modules, spans = {}, {}, []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    device_ops[int(m.group(1))] = _events(line, True)
                elif line.name == MODULES_LINE:
                    modules[int(m.group(1))] = _events(line, False)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend(
                    Event(e.name[len(SPAN_PREFIX):], e.start_ns * 1e-9,
                          (e.start_ns + e.duration_ns) * 1e-9)
                    for e in line.events
                    if e.name.startswith(SPAN_PREFIX))
    return Trace(device_ops, sorted(spans, key=lambda e: e.start), modules)


def is_kind(e: Event, kinds) -> bool:
    """Whether a device operation's opcode (or, for an operation XLA
    named after what it fused, its name) is one of ``kinds``."""
    return e.opcode in kinds or e.name in kinds


def union(intervals) -> list:
    """Sorted, merged [(start, end)] of possibly overlapping intervals."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _clip(intervals, window):
    lo, hi = window
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def total(intervals) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a: list, b: list) -> list:
    """The parts of merged intervals ``a`` not covered by merged ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def window_of(trace: Trace) -> tuple:
    """The traced window: first device operation's start to the last
    one's end, over all devices."""
    evs = [e for ops in trace.device_ops.values() for e in ops]
    if not evs:
        raise ValueError("no operation ran on a device in this trace")
    return min(e.start for e in evs), max(e.end for e in evs)


def busy(trace: Trace, window=None) -> dict:
    """Per device: seconds in which some operation ran, within the
    window."""
    window = window or window_of(trace)
    return {d: total(_clip(union((e.start, e.end) for e in ops), window))
            for d, ops in trace.device_ops.items()}


def op_label(e: Event) -> str:
    """A stable printed name for one kind of operation: instruction
    name, opcode and result shape, without the instruction's number."""
    label = "_".join(x for x in (e.name, e.opcode, e.shape) if x)
    return re.sub(r"[^A-Za-z0-9]+", "_", label).strip("_")


def op_sums(trace: Trace, window=None, top: int = 10) -> list:
    """[[label__x<count>, seconds], ...] summed over devices and divided
    by their number, longest first."""
    window = window or window_of(trace)
    sums, counts = {}, {}
    for ops in trace.device_ops.values():
        for e in ops:
            if e.end <= window[0] or e.start >= window[1]:
                continue
            k = op_label(e)
            sums[k] = sums.get(k, 0.0) + (e.end - e.start)
            counts[k] = counts.get(k, 0) + 1
    n = max(1, len(trace.device_ops))
    ranked = sorted(sums.items(), key=lambda kv: -kv[1])[:top]
    return [[f"{k[:56]}__x{counts[k]}", v / n] for k, v in ranked]


def kind_seconds(trace: Trace, kinds, window=None) -> float:
    """Seconds of operations whose HLO kind is in ``kinds``, averaged
    over devices (a plain sum of durations, as 'kernel time' is)."""
    window = window or window_of(trace)
    s = sum(e.end - e.start for ops in trace.device_ops.values()
            for e in ops if is_kind(e, kinds)
            and e.end > window[0] and e.start < window[1])
    return s / max(1, len(trace.device_ops))


def exposed_collective_seconds(trace: Trace, window=None) -> float:
    """Per device, the time a collective runs while no other operation
    does; averaged over devices."""
    window = window or window_of(trace)
    out = 0.0
    for ops in trace.device_ops.values():
        coll = union((e.start, e.end) for e in ops
                     if e.opcode.startswith(COLLECTIVES))
        rest = union((e.start, e.end) for e in ops
                     if not e.opcode.startswith(COLLECTIVES))
        out += total(_clip(subtract(coll, rest), window))
    return out / max(1, len(trace.device_ops))


def idle_gaps(trace: Trace, window=None, top: int = 10,
              unattributed: str = "host_other") -> list:
    """[[span name, seconds], ...]: every idle interval of device 0
    inside the window, each part of it laid to the innermost benchmark
    span the host was in at the time (``unattributed`` outside any),
    summed by name, longest first."""
    window = window or window_of(trace)
    if not trace.device_ops:
        return []
    first = trace.device_ops[min(trace.device_ops)]
    idle = subtract([window], _clip(
        union((e.start, e.end) for e in first), window))
    sums: dict = {}
    spans = trace.host_spans             # sorted by start
    first = 0
    for gap in idle:
        # Spans that ended before this gap ended before every later one.
        while first < len(spans) and spans[first].end <= gap[0]:
            first += 1
        left, j = [gap], first
        over = []
        while j < len(spans) and spans[j].start < gap[1]:
            if spans[j].end > gap[0]:
                over.append(spans[j])
            j += 1
        # Innermost first: a later-starting span nested in an earlier
        # one claims its part before the outer one gets the rest.
        for sp in reversed(over):
            inside = _clip(left, (sp.start, sp.end))
            got = total(inside)
            if got > 0:
                sums[sp.name] = sums.get(sp.name, 0.0) + got
                left = subtract(left, union(inside))
        rest = total(left)
        if rest > 0:
            sums[unattributed] = sums.get(unattributed, 0.0) + rest
    ranked = sorted(sums.items(), key=lambda kv: -kv[1])[:top]
    return [[k[:64], v] for k, v in ranked]
