"""One run of one cell of the benchmark.

    python3 benchmarks/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

A new process every time: load, warm up, measure for ``--seconds``,
check what the timed path produced against the plain reference, print
one JSON object as the last line of stdout, exit.  Everything that
belongs to one cell, one configuration, one architecture (the
configuration's family), one traffic mix or one per-layer metric is a
file found by its name (see PERF.md, "Layout"): this file holds none of
it.
"""

from __future__ import annotations

import time

_T_START = time.monotonic()     # set-up is timed from here

import argparse                                         # noqa: E402
import contextlib                                       # noqa: E402
import importlib                                        # noqa: E402
import importlib.util                                   # noqa: E402
import json                                             # noqa: E402
import os                                               # noqa: E402
import sys                                              # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.harness import schema                   # noqa: E402

BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class NoAccelerator(RuntimeError):
    """The machine does not hold the chips the cell asks for."""


class CompileMeter:
    """Seconds jax spent in backend compilation and the persistent
    cache's hits, from jax's own monitoring events (the listener of
    ``chip_smoke.py``).  ``mark()`` splits set-up from the window."""

    def __init__(self):
        self.seconds = 0.0
        self.count = 0
        self.cache_hits = 0
        self.slow: list = []        # (program, seconds) of a second or more
        self._mark = (0.0, 0)

    def install(self) -> None:
        import jax.monitoring
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, seconds: float, **kw) -> None:
        if event == BACKEND_COMPILE_EVENT:
            self.seconds += seconds
            self.count += 1
            if seconds >= 1.0:
                self.slow.append((kw.get("fun_name", "?"), seconds,
                                  time.monotonic() - _T_START))

    def _event(self, event: str, **_) -> None:
        if event == CACHE_HIT_EVENT:
            self.cache_hits += 1

    def mark(self) -> None:
        self._mark = (self.seconds, self.count)

    def since_mark(self) -> tuple:
        return self.seconds - self._mark[0], self.count - self._mark[1]


class Run:
    """What one run knows; the kind's driver fills it and the per-layer
    readers read it."""

    def __init__(self, *, cell_name, cell, config, family, traffic, seed,
                 seconds, traced, chips, peaks, meter, controls=()):
        self.cell_name = cell_name
        self.cell = cell            # benchmarks/workloads/<cell>.json
        self.config = config        # benchmarks/configs/<config>.json
        self.family = family        # benchmarks/families/<family>.py
        self.traffic = traffic      # benchmarks/traffic/<traffic>.json
        self.seed = seed
        self.seconds = seconds
        self.traced = traced
        self.chips = chips
        self.peaks = peaks          # None only in a chip-less rehearsal
        self.meter = meter
        self.controls = tuple(controls)     # precisions to judge as well
        self.control_verdicts: dict = {}    # precision -> [(what, v, lim)]
        self.t_start = _T_START
        self.stages: list = []      # [(name, seconds)] of set-up
        self.untimed_s = 0.0        # reference work done before the window
        self.setup_s = None
        self.end_to_end: dict = {}  # name -> value, by the kind's driver
        self.spans = None           # harness.spans.Spans of the run
        self.samples: dict = {}     # name -> list of floats (host clock)
        self.facts: dict = {}       # name -> number
        self.compared: list = []    # [(what, value, limit)] for `correct`
        self.attempted = 0
        self.failed = 0
        self.memory_peak_bytes = None
        self.trace = None           # harness.trace.Trace of the traced part
        self.trace_window = None    # (start, end) on the trace's clock
        self.compile_before_s = None
        self.compiles_in_window = None

    def param(self, key: str):
        """A parameter of the cell: its own file's ``params`` win over
        the traffic mix's."""
        if key in self.cell.get("params", {}):
            return self.cell["params"][key]
        return self.traffic[key]

    @contextlib.contextmanager
    def stage(self, name: str):
        t0 = time.monotonic()
        yield
        self.stages.append((name, time.monotonic() - t0))

    def open_window(self) -> float:
        """End of set-up: returns the window's opening time."""
        now = time.monotonic()
        self.setup_s = now - self.t_start - self.untimed_s
        self.compile_before_s = self.meter.seconds
        self.meter.mark()
        return now

    def close_window(self) -> None:
        self.compiles_in_window = self.meter.since_mark()[1]

    def compare(self, what: str, value: float, limit: float) -> None:
        self.compared.append((what, float(value), float(limit)))

    @property
    def correct(self) -> bool:
        return bool(self.compared) and self.failed == 0 and all(
            v <= lim and v == v for _, v, lim in self.compared)


def log(msg: str) -> None:
    print(f"[bench] {msg}", flush=True)


def devices_or_fail(chips: int, require_tpu: bool) -> list:
    import jax
    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise NoAccelerator(
            f"jax found platform {devices[0].platform!r} "
            f"({len(devices)} x {devices[0].device_kind}), not a TPU; "
            f"this benchmark never falls back")
    if len(devices) < chips:
        raise NoAccelerator(f"the cell asks for {chips} chips, jax found "
                            f"{len(devices)}")
    return devices[:chips]


def read_per_layer(run: Run, names: list) -> dict:
    """Each per-layer metric is a reader of its own,
    ``benchmarks/metrics/<name>.py: read(run)``; one that finds nothing
    to read returns None and is left out."""
    out = {}
    for name in names:
        path = os.path.join(HERE, "metrics", name + ".py")
        spec = importlib.util.spec_from_file_location(
            "benchmarks.metrics." + name.replace(".", "__"), path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        value = mod.read(run)
        if value is not None:
            out[name] = float(value)
    return out


def run_cell(workload: str, seed: int, seconds: float, traced: bool, *,
             require_tpu: bool = True, overrides: dict | None = None,
             controls: tuple = ()) -> dict:
    """Drive one run and return the result object.  ``require_tpu`` and
    ``overrides`` (of the configuration's sizes and the cell's
    parameters) exist for the tests under benchmarks/tests, which drive
    a tiny copy on the CPU; ``controls`` for benchmarks/control.py,
    which also judges the reference in lower precisions put in the
    program's place.  The command sets none of them."""
    bench = schema.load_and_check(ROOT)
    cell, config, traffic = schema.cell_files(ROOT, bench, workload)
    if overrides:
        config = {**config, **overrides.get("config", {})}
        cell = {**cell, "params": {**cell.get("params", {}),
                                   **overrides.get("params", {})}}

    # The program's own cache rule: <checkout>/.jax_cache, or the
    # directory JAX_COMPILATION_CACHE_DIR names.  A fixed path inside
    # the checkout, so only the first run of a cell there compiles.
    from distributedtensorflowexample_tpu.runtime import (
        enable_compilation_cache)
    cache_dir = enable_compilation_cache()
    meter = CompileMeter()
    meter.install()
    import jax
    # No eviction: a machine that caps the cache (the chip tool's sets
    # JAX_COMPILATION_CACHE_MAX_SIZE to 192 MiB) would otherwise evict
    # one cell's programs for another's — a 774 M step alone is larger
    # than that — and every run would compile again.
    jax.config.update("jax_compilation_cache_max_size", -1)
    devices = devices_or_fail(cell["chips"], require_tpu)
    kind = devices[0].device_kind
    log(f"devices: {len(jax.devices())} x {kind} ({devices[0].platform}); "
        f"cell {workload} uses {len(devices)}; compile cache {cache_dir}")
    peaks = None
    if require_tpu:
        from benchmarks.harness.peaks import peaks_for
        peaks = peaks_for(kind)         # a device not in the table: error

    run = Run(cell_name=workload, cell=cell, config=config,
              family=schema.load_module(config, "family"), traffic=traffic,
              seed=int(seed),
              seconds=float(seconds), traced=bool(traced),
              chips=len(devices), peaks=peaks, meter=meter,
              controls=controls)
    run.stages.append(("import_and_devices", time.monotonic() - _T_START))
    driver = importlib.import_module("benchmarks.kinds." + traffic["kind"])
    with contextlib.redirect_stdout(sys.stderr):
        # The program's own chatter goes to stderr; stdout is the run's.
        driver.run(run, devices)

    log("setup_s %.3f = %s" % (run.setup_s, ", ".join(
        f"{n} {s:.2f}" for n, s in run.stages)))
    log(f"compile: {run.compile_before_s:.2f} s before the window "
        f"({meter.cache_hits} cache hits), {run.compiles_in_window} "
        f"compilations inside it; a second or more each: "
        + (", ".join(f"{n} {s:.1f} (done at {t:.0f} s)"
                     for n, s, t in meter.slow) or "none"))
    for what, value, limit in run.compared:
        log(f"compared {what}: {value:.6g} (limit {limit:.6g}) "
            f"{'ok' if value <= limit else 'FAIL'}")
    for precision, verdicts in run.control_verdicts.items():
        for what, value, limit in verdicts:
            log(f"control {precision} {what}: {value:.6g} (limit "
                f"{limit:.6g}) {'passes' if value <= limit else 'fails'}")
    log(f"attempted {run.attempted}, failed {run.failed}")

    e2e = {m["name"]: m for m in bench["end_to_end"]}
    per = {m["name"]: m for m in bench["per_layer"]}
    if traced:
        names = [n for n, m in per.items()
                 if workload in m.get("workloads", [workload])]
        values, units = read_per_layer(run, names), per
    else:
        run.end_to_end["setup_s"] = run.setup_s
        values, units = run.end_to_end, e2e
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices),
              "memory_peak_bytes": run.memory_peak_bytes}
    result = {"correct": run.correct, "attempted": run.attempted,
              "failed": run.failed,
              "metrics": {n: {"value": v, "unit": units[n]["unit"]}
                          for n, v in values.items() if n in units},
              "device": device}
    if run.control_verdicts:
        result["compared"] = run.compared
        result["controls"] = run.control_verdicts
    if traced and run.trace is not None:
        from benchmarks.harness import trace as tr
        busy = tr.busy(run.trace, run.trace_window)
        device["busy_s"] = sum(busy.values()) / max(1, len(busy))
        device["window_s"] = run.trace_window[1] - run.trace_window[0]
        result["breakdown"] = {
            "device_ops": tr.op_sums(run.trace, run.trace_window),
            "idle_gaps": tr.idle_gaps(run.trace, run.trace_window)}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace))
    except NoAccelerator as e:
        print(f"benchmarks/run.py: {e}", file=sys.stderr)
        return 3
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
