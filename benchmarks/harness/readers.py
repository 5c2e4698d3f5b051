"""Reading rules shared by per-layer metrics that exist once per kind of
cell (``<metric>.train``, ``.steady``, ``.backlog``): each metric's own
file under ``benchmarks/metrics/`` names the rule it uses."""

from __future__ import annotations

from benchmarks.harness import stats
from benchmarks.harness import trace as tr
from benchmarks.harness.peaks import roofline_seconds


def device_idle_pct(run):
    """Share of the traced window in which no operation ran on the
    device: 1 - (union of device-operation intervals) / window, averaged
    over the chips used."""
    if run.trace is None:
        return None
    busy = tr.busy(run.trace, run.trace_window)
    window = run.trace_window[1] - run.trace_window[0]
    return 100.0 * (1.0 - sum(busy.values()) / len(busy) / window)


def mean_ms(run, sample: str):
    s = run.samples.get(sample)
    return 1e3 * sum(s) / len(s) if s else None


def p95_ms(run, sample: str):
    q = stats.percentile(run.samples.get(sample, []), 0.95)
    return None if q is None else 1e3 * q


def decode_device_seconds_per_step(run):
    """Device time of one decode step in the traced tail: the mean
    duration of the decode program's runs on the trace's ``XLA Modules``
    line (one event per executed program)."""
    if run.trace is None or not run.trace.device_modules:
        return None
    runs = [e.end - e.start for e in run.trace.device_modules[
        min(run.trace.device_modules)] if "decode_step" in e.name]
    return sum(runs) / len(runs) if runs else None


def decode_roofline_pct(run):
    """Least time of one decode step — the larger of bytes (every weight
    once as stored, every live cache row once) over peak bytes/s and
    operations over peak FLOP/s — over the device time of one step."""
    per_step = decode_device_seconds_per_step(run)
    if per_step is None or run.peaks is None:
        return None
    tail = [b for b in run.facts["tail_boundaries"] if b.busy > 0]
    rows = sum(b.live_cache_rows for b in tail) / len(tail)
    least, bound = roofline_seconds(
        run.family.decode_step_flops(run.config, rows, run.facts["slots"]),
        run.family.decode_step_bytes(run.config, rows), run.peaks)
    print(f"[bench] decode roofline: {bound}-bound, least "
          f"{1e3 * least:.3f} ms, device {1e3 * per_step:.3f} ms a step, "
          f"{rows:.0f} live cache rows", flush=True)
    return 100.0 * least / per_step


def memory_peak_bytes(devices):
    """Peak bytes held on the fullest chip: the allocator's peak of live
    arrays plus the peak it RESERVED for programs' scratch.  On this TPU
    runtime ``peak_bytes_in_use`` leaves the compiled programs'
    temporaries out (a program with 1 GiB of ``temp_size_in_bytes``
    moved ``peak_bytes_reserved`` by exactly 1 GiB and
    ``peak_bytes_in_use`` not at all — my chip run, PR 23), and a train
    step's activations are such temporaries.  None where the backend
    reports no memory statistics (the CPU of a rehearsal)."""
    stats_ = [dv.memory_stats() for dv in devices]
    if any(s is None for s in stats_):
        return None
    return max(s["peak_bytes_in_use"] + s.get("peak_bytes_reserved", 0)
               for s in stats_)
