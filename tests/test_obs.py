"""obs/ — unified telemetry (ISSUE 4 tentpole): registry snapshot/delta
and label semantics, span nesting with supervisor-context propagation,
flight-recorder dumps (bitwise-stable canonical JSON; on-SIGTERM via a
real subprocess), exporter golden files, the microbench guards the
tentpole promises (< 2 us per counter increment; metric-hook overhead
< 1% of the CPU bench step), the round-6 fault-library satellites
(disk-full snapshot save, heartbeat_flap, journal_torn), and the
ACCEPTANCE end-to-end: a supervised mnist_cnn run with an injected
preemption leaves flight dumps whose step counter, retry count, and
last span match the supervisor journal and the snapshot manifest, and
tools/obs_report.py renders the lot without error.

Single-device, no collectives.
"""

import gc
import glob
import json
import os
import signal
import subprocess
import sys
import textwrap
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from distributedtensorflowexample_tpu.data.synthetic import make_synthetic
from distributedtensorflowexample_tpu.models import build_model
from distributedtensorflowexample_tpu.obs import anomaly as obs_anomaly
from distributedtensorflowexample_tpu.obs import export as obs_export
from distributedtensorflowexample_tpu.obs import metrics as obs_metrics
from distributedtensorflowexample_tpu.obs import recorder as obs_recorder
from distributedtensorflowexample_tpu.obs import timeline as obs_timeline
from distributedtensorflowexample_tpu.obs import trace as obs_trace
from distributedtensorflowexample_tpu.parallel.sync import make_train_step
from distributedtensorflowexample_tpu.resilience import (
    FaultInjectionHook, FaultPlan, SnapshotHook, SnapshotStore, Supervisor,
    tear_journal)
from distributedtensorflowexample_tpu.resilience.supervisor import (
    Journal, RetryPolicy)
from distributedtensorflowexample_tpu.training.hooks import (AnomalyHook,
                                                             MetricsHook)
from distributedtensorflowexample_tpu.training.loop import TrainLoop
from distributedtensorflowexample_tpu.training.state import TrainState

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

pytestmark = pytest.mark.obs


def _fresh_state(model_name: str = "softmax", batch: int = 8, seed: int = 0):
    return TrainState.create(build_model(model_name),
                             optax.sgd(0.1, momentum=0.9),
                             jnp.zeros((batch, 28, 28, 1), jnp.float32),
                             seed=seed)


def _batches(n: int, batch: int = 8):
    x, y = make_synthetic(batch * n, (28, 28, 1), 10, seed=3)
    return [{"image": jnp.asarray(x[i * batch:(i + 1) * batch]),
             "label": jnp.asarray(y[i * batch:(i + 1) * batch])}
            for i in range(n)]


@pytest.fixture(scope="module")
def sgd_step():
    return make_train_step()


@pytest.fixture()
def sink():
    events = []
    obs_trace.add_sink(events.append)
    yield events
    obs_trace.remove_sink(events.append)


# --- registry --------------------------------------------------------------

def test_registry_snapshot_delta_and_kinds():
    reg = obs_metrics.MetricsRegistry()
    c = reg.counter("steps_total", "steps")
    assert reg.counter("steps_total") is c          # idempotent
    c.inc()
    c.inc(4)
    g = reg.gauge("step")
    g.set(40)
    h = reg.histogram("win_s", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    h.observe(7.0)
    s1 = reg.snapshot()
    assert s1["counters"]["steps_total"] == 5
    assert s1["gauges"]["step"]["value"] == 40
    assert s1["gauges"]["step"]["monotonic_ts"] is not None
    assert s1["histograms"]["win_s"]["count"] == 3
    assert s1["histograms"]["win_s"]["buckets"] == {
        "0.1": 1, "1.0": 2, "+Inf": 3}          # cumulative
    assert s1["histograms"]["win_s"]["sum"] == pytest.approx(7.55)
    c.inc(7)
    g.set(41)
    s2 = reg.snapshot()
    d = obs_metrics.MetricsRegistry.delta(s1, s2)
    assert d["counters"] == {"steps_total": 7}      # only what moved
    assert d["gauges"]["step"] == 41
    assert d["span_s"] >= 0
    # delta from nothing: counters count from zero, no span
    d0 = obs_metrics.MetricsRegistry.delta(None, s1)
    assert d0["counters"]["steps_total"] == 5 and d0["span_s"] is None
    # a name can't change kind
    with pytest.raises(ValueError, match="already registered"):
        reg.gauge("steps_total")


def test_registry_label_semantics():
    reg = obs_metrics.MetricsRegistry()
    fam = reg.counter("kills_total")
    a = fam.labels(why="wall", task="bench")
    assert fam.labels(task="bench", why="wall") is a    # order-canonical
    b = fam.labels(why="heartbeat", task="bench")
    assert b is not a
    a.inc(2)
    b.inc()
    snap = reg.snapshot()["counters"]
    assert snap['kills_total{task="bench",why="wall"}'] == 2
    assert snap['kills_total{task="bench",why="heartbeat"}'] == 1
    # the untouched bare series is elided from a labeled-only family
    assert "kills_total" not in snap
    fam.inc()                                           # now it's real
    assert reg.snapshot()["counters"]["kills_total"] == 1


def test_counter_increment_microbench_guard():
    """Tentpole promise: the lock-free hot path stays under 2 us per
    increment on CPU (best-of-repeats to shrug off host load)."""
    c = obs_metrics.MetricsRegistry().counter("bench_total")
    n, best = 20000, float("inf")
    for _ in range(5):
        t0 = time.perf_counter()
        for _ in range(n):
            c.inc()
        best = min(best, (time.perf_counter() - t0) / n)
    assert c.value == 5 * n
    assert best < 2e-6, f"counter inc {best * 1e9:.0f}ns >= 2us"


# --- trace spans -----------------------------------------------------------

def test_span_nesting_and_env_context(sink, monkeypatch):
    monkeypatch.setenv("SUPERVISE_ATTEMPT", "3")
    monkeypatch.setenv("OBS_PHASE", "full_bench")
    with obs_trace.span("outer", step=7):
        with obs_trace.span("inner"):
            pass
        obs_trace.event("synth", 0.25, n=4)
    inner, synth, outer = sink[-3:]
    assert (inner["name"], inner["parent"], inner["depth"]) == (
        "inner", "outer", 1)
    assert (synth["name"], synth["parent"], synth["depth"]) == (
        "synth", "outer", 1)
    assert synth["dur_s"] == 0.25 and synth["n"] == 4
    assert (outer["parent"], outer["depth"], outer["step"]) == (None, 0, 7)
    for ev in (inner, synth, outer):
        assert ev["attempt"] == 3 and ev["phase"] == "full_bench"
    assert outer["dur_s"] >= inner["dur_s"] >= 0
    # spans feed the registry histogram too
    snap = obs_metrics.registry().snapshot()["histograms"]
    assert snap['span_seconds{name="outer"}']["count"] >= 1


def test_span_attrs_writable_and_exception_safe(sink):
    with pytest.raises(RuntimeError):
        with obs_trace.span("doomed") as attrs:
            attrs["rc"] = 7
            raise RuntimeError("boom")
    assert sink[-1]["name"] == "doomed" and sink[-1]["rc"] == 7
    assert obs_trace._stack() == []         # stack unwound


# --- the hot-path tape -----------------------------------------------------

@pytest.fixture()
def tape(monkeypatch):
    """An empty tape of its own for one test (the module's ring is
    process-wide and other tests write to it).  The collector rests
    meanwhile: once something in this process has called ``watch_gc()``
    a collection reads the clock these tests pin and count."""
    import collections
    import itertools
    ring = collections.deque(maxlen=8)
    monkeypatch.setattr(obs_trace, "_tape", ring)
    monkeypatch.setattr(obs_trace, "_seq", itertools.count())
    gc.disable()
    yield ring
    gc.enable()


def _ticking_clock(monkeypatch, step=1.0):
    """Pin the monotonic seam: every read is ``step`` later."""
    now = [0.0]

    def tick():
        now[0] += step
        return now[0]

    monkeypatch.setattr(obs_metrics, "_now", tick)


def test_tape_keeps_name_stamps_parent_and_rid(tape, monkeypatch):
    _ticking_clock(monkeypatch)
    with obs_trace.hot_span("outer"):
        with obs_trace.hot_span("inner", rid="r7"):
            pass
    assert obs_trace.tape() == [("inner", 2.0, 3.0, "outer", "r7"),
                                ("outer", 1.0, 4.0, None, None)]
    assert obs_trace.tape_dropped() == 0
    assert obs_trace._stack() == []


def test_tape_nested_spans_give_self_time(tape, monkeypatch):
    _ticking_clock(monkeypatch)
    with obs_trace.hot_span("step"):            # 1 .. 8
        with obs_trace.hot_span("a"):           # 2 .. 3
            pass
        with obs_trace.hot_span("b"):           # 4 .. 7
            with obs_trace.hot_span("leaf"):    # 5 .. 6
                pass
    spans = {e[0]: e for e in obs_trace.tape()}

    def self_time(name):
        _, t0, t1, _, _ = spans[name]
        return (t1 - t0) - sum(c[2] - c[1] for c in spans.values()
                               if c[3] == name)

    assert [spans[n][3] for n in ("a", "b", "leaf")] == [
        "step", "step", "b"]
    assert self_time("step") == 7.0 - 1.0 - 3.0     # less a and b only
    assert self_time("b") == 2.0 and self_time("leaf") == 1.0


def test_event_and_span_land_on_the_tape_too(tape, sink):
    with obs_trace.span("phase", step=3):
        obs_trace.event("serve_queue", 0.25, t0_s=10.0, rid="r1", slot=2)
        with obs_trace.hot_span("hot"):
            pass
    queue, hot, phase = obs_trace.tape()
    assert queue == ("serve_queue", 10.0, 10.25, "phase", "r1")
    assert hot[0] == "hot" and hot[3] == "phase" and hot[4] is None
    assert phase[0] == "phase" and phase[3] is None
    assert phase[1] <= hot[1] <= hot[2] <= phase[2]
    # ... and the phase recorder's sinks still get what they got (the
    # hot span goes to the tape only).
    assert [e["name"] for e in sink[-2:]] == ["serve_queue", "phase"]
    assert sink[-2]["rid"] == "r1" and sink[-1]["step"] == 3


def test_tape_ring_wraps_and_counts_what_it_dropped(tape):
    for i in range(11):
        with obs_trace.hot_span(f"s{i}"):
            pass
    assert [e[0] for e in obs_trace.tape()] == [f"s{i}"
                                                for i in range(3, 11)]
    assert obs_trace.tape_dropped() == 3
    assert obs_trace.TAPE_LEN >= 60000      # the real ring: ~64 k


def test_tape_dropped_counts_across_threads(tape):
    """The count comes from the entries' own numbers, handed out by one
    C call each: four threads writing at once lose none of it."""
    def write():
        for _ in range(500):
            with obs_trace.hot_span("t"):
                pass

    threads = [threading.Thread(target=write) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(obs_trace.tape()) == 8
    assert obs_trace.tape_dropped() == 4 * 500 - 8


def test_a_cancelled_hot_span_leaves_no_entry(tape):
    with obs_trace.hot_span("busy"):
        with obs_trace.hot_span("idle") as sp:
            sp.cancel()
    assert [e[0] for e in obs_trace.tape()] == ["busy"]
    assert obs_trace.tape_dropped() == 0 and obs_trace._stack() == []


def test_hot_span_exception_safe_and_annotates_when_jax_is_loaded(tape):
    assert "jax" in sys.modules             # this file imports it
    with pytest.raises(RuntimeError):
        with obs_trace.hot_span("doomed"):
            raise RuntimeError("boom")
    assert obs_trace.tape()[-1][0] == "doomed"
    assert obs_trace._stack() == []
    assert obs_trace._trace_annotation() is jax.profiler.TraceAnnotation


def test_watch_gc_lands_a_long_collection_under_the_open_span(
        tape, monkeypatch):
    obs_trace.watch_gc()
    obs_trace.watch_gc()
    assert gc.callbacks.count(obs_trace._on_gc) == 1
    full = obs_trace._GC_SECONDS.labels(generation=2)
    before = full.value
    _ticking_clock(monkeypatch)         # each read a second later
    with obs_trace.hot_span("outer"):               # opens at 1
        gc.collect()                                # 2 .. 3
    assert obs_trace.tape() == [("host.gc", 2.0, 3.0, "outer", None),
                                ("outer", 1.0, 4.0, None, None)]
    assert full.value - before == 1.0
    assert obs_trace._stack() == []


def test_watch_gc_keeps_a_short_collection_off_the_tape(tape, monkeypatch):
    obs_trace.watch_gc()
    young = obs_trace._GC_SECONDS.labels(generation=0)
    before = young.value
    _ticking_clock(monkeypatch, step=obs_trace.GC_SPAN_MIN_S / 2)
    with obs_trace.hot_span("outer"):
        gc.collect(0)
    assert [e[0] for e in obs_trace.tape()] == ["outer"]
    # ... and the counter has it all the same
    assert young.value - before == pytest.approx(
        obs_trace.GC_SPAN_MIN_S / 2)


def test_hot_span_microbench_guard():
    """Tentpole promise: a per-boundary span (the tape record plus the
    profiler annotation, jax loaded, no profiler session) is about a
    microsecond — the budget is 2 us, the guard 5 us mean over 20 k
    (the suite runs six workers wide)."""
    n, best = 20000, float("inf")
    for _ in range(5):
        t0 = time.perf_counter()
        for _ in range(n):
            with obs_trace.hot_span("bench"):
                pass
        best = min(best, (time.perf_counter() - t0) / n)
    assert best < 5e-6, f"hot span {best * 1e9:.0f}ns >= 5us"


def test_importing_obs_pulls_no_jax():
    """obs/ is stdlib-only by contract (its docstring): the hot span
    finds jax through ``sys.modules`` and never imports it."""
    code = textwrap.dedent("""
        import sys
        import distributedtensorflowexample_tpu.obs as obs
        obs.watch_gc()
        with obs.hot_span("x"):
            pass
        assert obs.tape()[-1][0] == "x"
        heavy = [m for m in ("jax", "jaxlib", "numpy", "flax")
                 if m in sys.modules]
        assert not heavy, heavy
    """)
    subprocess.run([sys.executable, "-c", code], check=True, cwd=REPO)


def test_trace_jsonl_file_sink(tmp_path, monkeypatch):
    path = str(tmp_path / "trace.jsonl")
    monkeypatch.setenv("OBS_TRACE_FILE", path)
    with obs_trace.span("a"):
        pass
    with obs_trace.span("b", step=2):
        pass
    # a caller-forgotten foreign scalar serializes via str, and even a
    # truly unserializable attr must not raise out of span.__exit__ —
    # telemetry must never kill the run it observes
    with obs_trace.span("c", arr=np.float32(1.5)):
        pass
    recs = [json.loads(l) for l in open(path)]
    assert [r["name"] for r in recs] == ["a", "b", "c"]
    assert recs[1]["step"] == 2
    assert recs[2]["arr"] == "1.5"


def test_atomic_write_unlinks_tmp_on_failed_write(tmp_path, monkeypatch):
    """The disk-full-survival path retries every interval; a leaked
    partial tmp per failed attempt would eat the last free bytes."""

    def _enospc(fd):
        raise OSError(28, "No space left on device")

    monkeypatch.setattr(obs_recorder.os, "fsync", _enospc)
    with pytest.raises(OSError):
        obs_recorder.atomic_write(str(tmp_path / "f.json"), b"data")
    monkeypatch.undo()
    assert os.listdir(str(tmp_path)) == []


# --- flight recorder -------------------------------------------------------

def test_flight_dump_bitwise_stable_and_canonical(tmp_path, monkeypatch):
    """Two dumps of an unchanged recorder are bitwise identical, and the
    file is canonical JSON (re-serializing the parsed content reproduces
    the exact bytes) — what makes flights diffable across attempts."""
    monkeypatch.setattr(obs_metrics, "_now", lambda: 123.456789)
    reg = obs_metrics.MetricsRegistry()
    reg.counter("train_steps_total").inc(6)
    reg.gauge("train_step").set(6)
    rec = obs_recorder.FlightRecorder(registry=reg)
    rec.note(model="softmax")
    rec.record_span({"name": "snapshot", "dur_s": 0.004, "step": 6})
    rec.record_loss(6, 1.25)
    rec.record_delta({"counters": {"train_steps_total": 6}})
    p1 = rec.dump("sigterm", path=str(tmp_path / "f1.json"))
    p2 = rec.dump("sigterm", path=str(tmp_path / "f2.json"))
    raw1, raw2 = open(p1, "rb").read(), open(p2, "rb").read()
    assert raw1 == raw2
    flight = json.loads(raw1)
    assert raw1 == (json.dumps(flight, sort_keys=True, indent=1)
                    + "\n").encode()
    assert flight["reason"] == "sigterm"
    assert flight["notes"] == {"model": "softmax"}
    assert flight["loss_tail"] == [[6, 1.25]]
    assert flight["metrics"]["counters"]["train_steps_total"] == 6
    assert flight["spans"][-1]["name"] == "snapshot"


def test_flight_rings_are_bounded():
    rec = obs_recorder.FlightRecorder(max_spans=4, max_loss=3,
                                      registry=obs_metrics.MetricsRegistry())
    for i in range(10):
        rec.record_span({"name": f"s{i}"})
        rec.record_loss(i, float(i))
    payload = rec.payload("exit")
    assert [s["name"] for s in payload["spans"]] == ["s6", "s7", "s8", "s9"]
    assert payload["loss_tail"] == [[7, 7.0], [8, 8.0], [9, 9.0]]


def test_flight_dump_on_sigterm_subprocess(tmp_path):
    """install(sigterm=True) in a process with no handler of its own:
    SIGTERM leaves a flight file with the recorded evidence, then the
    process still dies BY the signal (honest wait-status).  Stdlib-only
    — no jax import in the child, so this is cheap."""
    script = textwrap.dedent("""
        import os, signal, sys
        sys.path.insert(0, %r)
        from distributedtensorflowexample_tpu.obs import (
            metrics, recorder, trace)
        rec = recorder.install(sigterm=True)
        rec.note(drill="sigterm")
        metrics.counter("child_steps_total").inc(5)
        with trace.span("phase_a", step=7):
            pass
        os.kill(os.getpid(), signal.SIGTERM)
    """) % REPO
    env = {**os.environ, "OBS_DIR": str(tmp_path),
           "SUPERVISE_ATTEMPT": "1", "OBS_PHASE": "drill"}
    env.pop("OBS_TRACE_FILE", None)
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, timeout=60)
    assert proc.returncode == -signal.SIGTERM
    dumps = [n for n in os.listdir(str(tmp_path))
             if n.startswith("flight_") and n.endswith(".json")]
    assert len(dumps) == 1
    flight = json.loads(open(os.path.join(str(tmp_path), dumps[0])).read())
    assert flight["reason"] == "sigterm"
    assert flight["attempt"] == 1 and flight["phase"] == "drill"
    assert flight["notes"] == {"drill": "sigterm"}
    assert flight["metrics"]["counters"]["child_steps_total"] == 5
    assert flight["spans"][-1]["name"] == "phase_a"
    assert flight["spans"][-1]["step"] == 7


# --- exporters -------------------------------------------------------------

def test_prometheus_exporter_golden(tmp_path):
    reg = obs_metrics.MetricsRegistry()
    reg.counter("train_steps_total", "completed global steps").inc(12)
    reg.counter("supervisor_kills_total").labels(why="wall").inc()
    reg.gauge("train_step").set(12)
    h = reg.histogram("snap_s", buckets=(0.3, 1.0))
    h.observe(0.25)                 # binary-exact values: the golden
    h.observe(0.5)                  # pins bytes, so no repr drift
    golden = (
        "# TYPE snap_s histogram\n"
        'snap_s_bucket{le="0.3"} 1\n'
        'snap_s_bucket{le="1.0"} 2\n'
        'snap_s_bucket{le="+Inf"} 2\n'
        "snap_s_sum 0.75\n"
        "snap_s_count 2\n"
        "# TYPE supervisor_kills_total counter\n"
        'supervisor_kills_total{why="wall"} 1\n'
        "# TYPE train_step gauge\n"
        "train_step 12\n"
        "# HELP train_steps_total completed global steps\n"
        "# TYPE train_steps_total counter\n"
        "train_steps_total 12\n")
    assert obs_export.prometheus_text(reg) == golden
    path = obs_export.write_prometheus_textfile(
        str(tmp_path / "obs.prom"), reg)
    assert open(path).read() == golden


def test_jsonl_exporter_snapshots_and_deltas(tmp_path):
    reg = obs_metrics.MetricsRegistry()
    c = reg.counter("steps_total")
    exp = obs_export.JsonlExporter(str(tmp_path / "obs.jsonl"))
    c.inc(3)
    exp.export(reg)
    c.inc(2)
    exp.export(reg)
    lines = [json.loads(l) for l in open(str(tmp_path / "obs.jsonl"))]
    assert lines[0]["delta"] is None
    assert lines[0]["snapshot"]["counters"]["steps_total"] == 3
    assert lines[1]["snapshot"]["counters"]["steps_total"] == 5
    assert lines[1]["delta"]["counters"] == {"steps_total": 2}


# --- MetricsHook + overhead guard ------------------------------------------

class _FakeLoop:
    start_step = 0


def test_metrics_hook_feeds_registry_and_recorder(sink):
    reg = obs_metrics.registry()
    before = reg.snapshot()["counters"].get("train_steps_total", 0)
    hook = MetricsHook(every=2)
    hook.begin(_FakeLoop())
    rec = obs_recorder.FlightRecorder(registry=reg)
    # stand in for the installed recorder without installing one
    installed = obs_recorder._GLOBAL
    obs_recorder._GLOBAL = rec
    try:
        for step in range(1, 5):
            hook.after_step(step, None, {"loss": np.float32(step * 0.5)})
    finally:
        obs_recorder._GLOBAL = installed
    snap = reg.snapshot()
    assert snap["counters"]["train_steps_total"] - before == 4
    assert snap["gauges"]["train_step"]["value"] == 4
    assert snap["gauges"]["train_loss"]["value"] == 2.0
    # loss sampled on the every=2 marks only; ring has both marks
    assert list(rec._loss) == [[2, 1.0], [4, 2.0]]
    steps_events = [e for e in sink if e["name"] == "steps"]
    assert [e["step"] for e in steps_events] == [2, 4]
    assert all(e["n"] == 2 for e in steps_events)
    # the delta ring got one entry (second mark vs first)
    assert len(rec._deltas) == 1
    assert rec._deltas[0]["counters"]["train_steps_total"] == 2


def test_metrics_hook_overhead_under_1pct_of_bench_step(sgd_step):
    """ACCEPTANCE guard: per-boundary hook cost vs the measured CPU
    bench step (mnist_cnn — the headline workload) in the SAME process
    under the SAME load.  every=100 is the bench-like cadence (loss
    fetch + registry snapshot amortized across boundaries)."""
    state = _fresh_state("mnist_cnn")
    batch = _batches(1)[0]
    state, metrics = sgd_step(state, batch)      # compile
    jax.block_until_ready(metrics)
    times = []
    for _ in range(5):
        t0 = time.perf_counter()
        state, metrics = sgd_step(state, batch)
        jax.block_until_ready(metrics)
        times.append(time.perf_counter() - t0)
    step_s = min(times)
    # The FULL round-10 production stack at boundary cadence:
    # MetricsHook + AnomalyHook (trainers/common.py installs both) —
    # the <1% budget covers the anomaly detectors' hot-path half too.
    hook = MetricsHook(every=100)
    anom = AnomalyHook(every=100)
    hook.begin(_FakeLoop())
    anom.begin(_FakeLoop())
    fetched = {"loss": np.asarray(metrics["loss"])}
    n = 1000
    t0 = time.perf_counter()
    for i in range(1, n + 1):
        hook.after_step(i, state, fetched)
        anom.after_step(i, state, fetched)
    hook_s = (time.perf_counter() - t0) / n
    assert hook_s < 0.01 * step_s, (
        f"metric+anomaly hooks {hook_s * 1e6:.2f}us/boundary >= 1% of "
        f"the {step_s * 1e3:.1f}ms CPU bench step")


# --- satellite: disk-full snapshot save ------------------------------------

def test_snapshot_hook_survives_disk_full(tmp_path, sgd_step, monkeypatch,
                                          capsys):
    """ROADMAP round-6 by name: a full disk mid-run logs + increments
    snapshot_save_failures instead of killing the run; the newest VALID
    snapshot on disk is untouched and restores."""
    store = SnapshotStore(str(tmp_path / "snaps"))
    state = _fresh_state()
    hook = SnapshotHook(store, every=1, cursor={"seed": 0})
    batches = _batches(3)
    hook.begin(_FakeLoop())
    state, m = sgd_step(state, batches[0])
    hook.after_step(1, state, m)                 # healthy save at step 1
    assert store.latest_valid() == 1

    def _enospc(self, path, data):
        raise OSError(28, "No space left on device", path)

    fails = obs_metrics.registry().counter("snapshot_save_failures")
    before = fails.value
    monkeypatch.setattr(SnapshotStore, "_atomic_write", _enospc)
    for i, b in enumerate(batches[1:], start=2):
        state, m = sgd_step(state, b)
        hook.after_step(i, state, m)             # fails, must not raise
    hook.end(state)                              # final retry also fails
    assert fails.value - before == 3             # steps 2, 3 + end
    err = capsys.readouterr().err
    assert "No space left" in err and "continuing" in err
    monkeypatch.undo()
    assert store.latest_valid() == 1             # prior snapshot intact
    restored = store.restore(_fresh_state(seed=9))
    assert int(restored.step) == 1


# --- satellite: new fault kinds --------------------------------------------

def test_new_fault_kinds_parse_deterministically():
    for text in ("heartbeat_flap", "journal_torn",
                 "heartbeat_flap,journal_torn"):
        a = FaultPlan.parse(text, 10, seed=4)
        b = FaultPlan.parse(text, 10, seed=4)
        assert ([(s.kind, s.step, s.arg) for s in a.specs]
                == [(s.kind, s.step, s.arg) for s in b.specs])
        assert all(1 <= s.step < 10 for s in a.specs)
    # a different seed explores a different schedule
    steps4 = {s.step for s in FaultPlan.parse("heartbeat_flap", 1000, 4).specs}
    steps5 = {s.step for s in FaultPlan.parse("heartbeat_flap", 1000, 5).specs}
    assert steps4 != steps5
    # classification: flap rides the loop, torn journal is post-exit
    plan = FaultPlan.parse("journal_torn,heartbeat_flap@2:0.01", 8, 0)
    assert [s.kind for s in plan.post_exit_specs] == ["journal_torn"]
    assert sorted(s.kind for s in plan.loop_specs) == [
        "heartbeat_flap", "preemption"]


def test_heartbeat_flap_beats_at_the_timeout_edge(tmp_path, sgd_step,
                                                  monkeypatch):
    """The flap blocks for exactly the supervisor-exported timeout, then
    touches the heartbeat — the supervisor's strictly-greater staleness
    check must see a beat ON the edge as alive."""
    hb = str(tmp_path / "hb")
    monkeypatch.setenv("SUPERVISE_HEARTBEAT", hb)
    monkeypatch.setenv("SUPERVISE_HEARTBEAT_TIMEOUT_S", "0.3")
    from distributedtensorflowexample_tpu.resilience.faults import (
        FLAP_EDGE_MARGIN_S)
    plan = FaultPlan.parse("heartbeat_flap@2", 3, 0)
    state = _fresh_state()
    t0 = time.perf_counter()
    loop = TrainLoop(sgd_step, iter(_batches(3)), 3,
                     hooks=[FaultInjectionHook(plan)])
    loop.run(state)
    # blocked to the edge (minus the deterministic-survivability margin)
    assert time.perf_counter() - t0 >= 0.3 - FLAP_EDGE_MARGIN_S
    assert os.path.exists(hb)                    # then beat
    assert time.time() - os.path.getmtime(hb) < 0.3
    injected = obs_metrics.registry().snapshot()["counters"]
    assert injected['faults_injected_total{kind="heartbeat_flap"}'] >= 1


def test_heartbeat_flap_refuses_with_no_edge(sgd_step, monkeypatch):
    """nan_loss-on-uint8 discipline: a flap with no timeout to aim at
    (no arg, no supervisor env) refuses loudly instead of reporting a
    drill that exercised nothing."""
    monkeypatch.delenv("SUPERVISE_HEARTBEAT_TIMEOUT_S", raising=False)
    plan = FaultPlan.parse("heartbeat_flap@1", 2, 0)
    loop = TrainLoop(sgd_step, iter(_batches(2)), 2,
                     hooks=[FaultInjectionHook(plan)])
    with pytest.raises(ValueError, match="no timeout edge"):
        loop.run(_fresh_state())


def test_journal_torn_replay_skips_tail(tmp_path):
    path = str(tmp_path / "journal.jsonl")
    j = Journal(path)
    j.write("attempt_start", task="a", attempt=0)
    j.write("task_done", task="a")
    j.write("task_done", task="b")
    assert tear_journal(path)
    data = open(path, "rb").read()
    assert not data.endswith(b"\n")              # genuinely torn mid-line
    state = Journal(path).replay()
    assert state["done"] == {"a"}                # intact lines survive
    assert not state["wedged"]
    # empty/missing files refuse to tear
    assert not tear_journal(str(tmp_path / "missing"))
    open(str(tmp_path / "empty"), "w").close()
    assert not tear_journal(str(tmp_path / "empty"))


def test_journal_write_heals_a_torn_tail(tmp_path):
    """An append landing AFTER a tear must not merge with the torn
    fragment into one unparseable line (which would eat a LIVE record,
    not just the dead fragment): write() heals the tail with a newline
    first, so replay loses at most the fragment."""
    path = str(tmp_path / "journal.jsonl")
    j = Journal(path)
    j.write("task_done", task="a")
    j.write("attempt_start", task="b", attempt=0)
    assert tear_journal(path)
    j.write("task_done", task="b")               # post-tear append
    state = Journal(path).replay()
    assert state["done"] == {"a", "b"}           # the live record survived
    parseable = 0
    for line in open(path).read().splitlines():
        try:
            json.loads(line)
            parseable += 1
        except ValueError:
            pass                                 # the healed-off fragment
    assert parseable == 2


def test_flight_dump_with_nan_loss_is_strict_json(tmp_path):
    """The NaN-guard postmortem — the one dump whose point is recording
    a NaN — must still be strict JSON (no bare NaN tokens): non-finite
    floats serialize as their string names."""
    reg = obs_metrics.MetricsRegistry()
    reg.gauge("train_loss").set(float("nan"))
    rec = obs_recorder.FlightRecorder(registry=reg)
    rec.record_loss(2, float("nan"))
    rec.record_loss(3, float("inf"))
    path = rec.dump("nan_guard", path=str(tmp_path / "f.json"))
    raw = open(path).read()
    assert "NaN" not in raw and "Infinity" not in raw
    flight = json.loads(raw)                     # strict-parseable
    assert flight["loss_tail"] == [[2, "nan"], [3, "inf"]]
    assert flight["metrics"]["gauges"]["train_loss"]["value"] == "nan"


def test_faultline_journal_torn_plumbing(tmp_path, capsys, monkeypatch):
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import faultline
    sys.path.pop(0)
    path = str(tmp_path / "sup.jsonl")
    Journal(path).write("attempt_start", task="drill", attempt=0)
    intact = open(path, "rb").read()
    monkeypatch.setenv("SUPERVISE_JOURNAL", path)
    rc = faultline.main(["--plan", "journal_torn", "--steps", "4",
                         "--workdir", str(tmp_path / "wd"), "--seed", "1"])
    captured = capsys.readouterr()
    assert rc == 143                             # paired preemption saved
    assert "tore journal" in captured.err
    torn = open(path, "rb").read()
    assert len(torn) < len(intact) and intact.startswith(torn)


# --- ACCEPTANCE: supervised drill leaves a cross-checkable postmortem ------

def test_acceptance_supervised_mnist_cnn_flight_matches_journal_and_manifest(
        tmp_path):
    """Supervised mnist_cnn drill with an injected preemption: every
    attempt leaves a flight dump; the preempted attempt's step gauge and
    last span name the same step the snapshot manifest committed, the
    flight count and attempt ids match the journal, and obs_report
    renders flights + journal without error."""
    wd = str(tmp_path / "drill")
    flights_dir = str(tmp_path / "flight")
    os.makedirs(flights_dir)
    journal_path = str(tmp_path / "journal.jsonl")
    out = str(tmp_path / "out.json")
    sup = Supervisor(policy=RetryPolicy(retries=2, backoff_base_s=0.01),
                     journal=Journal(journal_path), seed=0)
    res = sup.run(
        [sys.executable, os.path.join(REPO, "tools", "faultline.py"),
         "--plan", "preempt", "--steps", "4", "--model", "mnist_cnn",
         "--workdir", wd, "--seed", "0", "--keep", "8"],
        name="drill", stdout_path=out,
        env_extra={"OBS_DIR": flights_dir})
    assert res.status == "ok" and res.attempts == 2      # 143 then 0

    flights = {}
    for name in os.listdir(flights_dir):
        f = json.loads(open(os.path.join(flights_dir, name)).read())
        flights[f["attempt"]] = f
    journal = [json.loads(l) for l in open(journal_path)]
    starts = [r for r in journal if r["event"] == "attempt_start"]
    ends = [r for r in journal if r["event"] == "attempt_end"]
    # retry count: one flight per journaled attempt, ids aligned
    assert sorted(flights) == [r["attempt"] for r in starts] == [0, 1]
    assert [r["rc"] for r in ends] == [143, 0]

    final = json.loads(open(out).read().strip().splitlines()[-1])
    k = final["start_step"]                              # preemption step
    assert 1 <= k < 4
    store = SnapshotStore(os.path.join(wd, "snapshots"))

    preempted = flights[0]
    assert preempted["reason"] == "preempted"
    assert preempted["phase"] == "drill"                 # OBS_PHASE export
    # step counter matches the snapshot manifest the preemption committed
    assert preempted["metrics"]["gauges"]["train_step"]["value"] == k
    assert preempted["metrics"]["counters"]["train_steps_total"] == k
    assert store.manifest(k)["cursor"]["step"] == k
    # last span: the fault marker that caused the 143 the journal
    # recorded, at the same step the snapshot span just committed
    assert preempted["spans"][-1]["name"] == "fault"
    assert preempted["spans"][-1]["kind"] == "preemption"
    assert preempted["spans"][-1]["step"] == k
    snap_spans = [s for s in preempted["spans"] if s["name"] == "snapshot"]
    assert snap_spans[-1]["step"] == k
    assert preempted["loss_tail"][-1][0] == k

    finished = flights[1]
    assert finished["attempt"] == 1
    assert finished["metrics"]["gauges"]["train_step"]["value"] == 4
    assert finished["metrics"]["counters"]["train_steps_total"] == 4 - k
    assert store.latest_valid() == 4                     # manifest agrees
    assert finished["spans"][-1]["name"] == "snapshot"
    assert finished["spans"][-1]["step"] == 4

    # obs_report renders flights + journal without error
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "obs_report.py"),
         "--dir", flights_dir, "--journal", journal_path],
        capture_output=True, text=True, timeout=60)
    assert proc.returncode == 0, proc.stderr
    assert "# Telemetry report" in proc.stdout
    assert "`train_steps_total`" in proc.stdout
    assert "`snapshot`" in proc.stdout
    assert "attempt_end" in proc.stdout
    assert "preempted" in proc.stdout


def test_obs_report_cli_help_runs():
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "obs_report.py"),
         "--help"], capture_output=True, text=True, timeout=60)
    assert proc.returncode == 0 and "--journal" in proc.stdout


# === round 10: timeline merge + online anomaly detection ====================

timeline_mark = pytest.mark.timeline


@timeline_mark
def test_ewma_regression_pins_baseline_and_latches():
    """The boiled-frog defense: the baseline is pinned over the first
    ``warmup`` samples and NEVER updates, so a later sustained slowdown
    scores against the run's own healthy start; ``observe`` returns True
    exactly once (the latch) while ``firing`` tracks the live z."""
    det = obs_anomaly.EwmaRegression(warmup=4, alpha=1.0, z_thresh=4.0,
                                     skip_first=0)
    fired = [det.observe(0.010, step=s) for s in range(1, 5)]
    assert fired == [False] * 4 and det.armed
    mu0, sigma0 = det.mu0, det.sigma0
    assert mu0 == pytest.approx(0.010)
    assert not det.observe(0.010, step=5) and det.z == pytest.approx(0.0)
    assert det.observe(0.050, step=6)            # first crossing fires
    assert det.fired_step == 6 and det.firing
    assert not det.observe(0.060, step=7)        # latched: never re-fires
    assert det.firing and det.fired_step == 6
    assert (det.mu0, det.sigma0) == (mu0, sigma0)  # baseline still pinned
    payload = det.payload()
    assert payload["fired_step"] == 6 and payload["firing"]
    assert payload["baseline_mean_s"] == pytest.approx(0.010)


@timeline_mark
def test_ewma_sigma_floor_and_skip_first():
    """Near-constant warmup samples must not turn scheduler jitter into
    a flag (sigma floored at min_sigma_frac * mean), and the compile-
    dominated first boundary is skipped without feeding the baseline."""
    det = obs_anomaly.EwmaRegression(warmup=3, z_thresh=8.0, skip_first=1,
                                     min_sigma_frac=0.05)
    assert not det.observe(9.0, step=1)          # compile window: skipped
    assert det.n == 0 and det.ewma is None
    for s in (2, 3, 4):
        det.observe(0.020, step=s)
    assert det.sigma0 == pytest.approx(0.05 * 0.020)   # floored, not 0
    det.observe(0.021, step=5)                   # 5% jitter: z ~ 1, quiet
    assert not det.firing


@timeline_mark
def test_detect_skew_laggard_vs_straggler():
    """Lag alone names a laggard, never a straggler: the straggler
    verdict needs slowness evidence (own regression flag, or step time
    over time_ratio x the OTHER ranks' median — self-excluded so a
    2-rank fleet's straggler cannot mask itself)."""
    # fewer than two reporters: skew is a relation, nothing to say
    assert obs_anomaly.detect_skew({0: {"step": 8}})["stragglers"] == []
    # lagging but no evidence (still compiling / unlucky sample)
    out = obs_anomaly.detect_skew(
        {0: {"step": 10, "step_time_s": 0.01},
         1: {"step": 6, "step_time_s": None}}, lag_steps=3)
    assert out["laggards"] == [1] and out["stragglers"] == []
    assert "no slowness evidence" in out["why"][1]
    # lagging with its own regression firing
    out = obs_anomaly.detect_skew(
        {0: {"step": 10, "step_time_s": 0.01},
         1: {"step": 6, "step_time_s": 0.3, "regression_firing": True}},
        lag_steps=3)
    assert out["stragglers"] == [1] and out["max_step"] == 10
    assert out["lag_steps"] == {0: 0, 1: 4}
    assert "regression firing" in out["why"][1]
    # lagging + slow vs the other ranks' median (no flag of its own)
    out = obs_anomaly.detect_skew(
        {0: {"step": 10, "step_time_s": 0.01},
         1: {"step": 5, "step_time_s": 0.25}}, lag_steps=3,
        time_ratio=4.0)
    assert out["stragglers"] == [1]
    assert "fleet median" in out["why"][1]
    # lagging + stale heartbeat (wedged-but-alive: its health report
    # predates the stall so step_time_s looks healthy, but the beat —
    # touched every boundary — has gone stale)
    out = obs_anomaly.detect_skew(
        {0: {"step": 10, "step_time_s": 0.01, "hb_age_s": 0.01},
         1: {"step": 5, "step_time_s": 0.01, "hb_age_s": 7.0}},
        lag_steps=3, time_ratio=4.0)
    assert out["stragglers"] == [1]
    assert "heartbeat" in out["why"][1] and "stale" in out["why"][1]
    # under the lag threshold nothing is even a laggard
    out = obs_anomaly.detect_skew(
        {0: {"step": 10, "step_time_s": 0.01},
         1: {"step": 9, "step_time_s": 9.9, "regression_firing": True}},
        lag_steps=3)
    assert out["laggards"] == [] and out["stragglers"] == []


@timeline_mark
def test_plateau_and_nan_sentinels():
    det = obs_anomaly.PlateauSentinel(window=3, min_delta=1e-3)
    for s, loss in enumerate((1.0, 0.9, 0.8, 0.7), start=1):
        assert not det.observe(loss, step=s)     # still improving
    assert not det.observe(0.7, step=5)
    assert not det.observe(0.7, step=6)          # 0.8 still pre-window best
    assert det.observe(0.7, step=7)              # window best == best_before
    assert det.fired_step == 7
    assert not det.observe(0.7, step=8)          # still firing: edge only
    # NaN is the other sentinel's job and must not poison the window
    assert not det.observe(float("nan"), step=7)
    # improve -> the window re-arms -> a SECOND plateau fires again
    for s, loss in enumerate((0.5, 0.4, 0.3, 0.3), start=9):
        assert not det.observe(loss, step=s)
    assert not det.firing
    assert det.observe(0.3, step=13) or det.observe(0.3, step=14)
    assert det.firing and det.fired_step == 7    # first plateau pinned

    rh = obs_anomaly.RunHealth(rank=3)
    assert rh.observe_loss(4, float("nan")) == ["nan_loss"]
    assert rh.observe_loss(5, float("nan")) == []        # latched
    assert rh.flags["nan_loss"] == {"firing": True, "fired_step": 4}


@timeline_mark
def test_health_json_roundtrip_and_tolerant_read(tmp_path):
    rh = obs_anomaly.RunHealth(rank=1)
    rh.observe_window(5, 1, 0.01)
    path = str(tmp_path / "health.json")
    rh.write(path)
    payload = obs_anomaly.read_health(path)
    assert payload["kind"] == "rank" and payload["rank"] == 1
    assert payload["step"] == 5 and payload["version"] == 1
    assert set(payload["flags"]) == {"step_time_regression", "nan_loss",
                                     "loss_plateau"}
    # tolerant by contract: missing and torn both read as None
    assert obs_anomaly.read_health(str(tmp_path / "absent.json")) is None
    (tmp_path / "torn.json").write_text('{"version": 1, "ste')
    assert obs_anomaly.read_health(str(tmp_path / "torn.json")) is None


@timeline_mark
def test_span_events_carry_both_clocks_pinned_bitwise(sink, tmp_path,
                                                      monkeypatch):
    """The satellite clock fix: every span event carries t0_s (monotonic
    — honest durations) AND t0_unix (wall — the cross-process alignment
    axis), derived through the _now/_wall seams so a pinned-clock test
    still gets bitwise-stable flight dumps."""
    monkeypatch.setattr(obs_metrics, "_now", lambda: 100.0)
    monkeypatch.setattr(obs_metrics, "_wall", lambda: 1700000000.0)
    ev = obs_trace.event("win", 2.5)
    assert ev["t0_s"] == 97.5
    assert ev["t0_unix"] == 1699999997.5         # same instant, wall axis
    with obs_trace.span("s"):
        pass
    assert sink[-1]["t0_unix"] == 1700000000.0
    reg = obs_metrics.MetricsRegistry()
    rec = obs_recorder.FlightRecorder(registry=reg)
    rec.record_span(ev)
    p1 = rec.dump("manual", path=str(tmp_path / "f1.json"))
    p2 = rec.dump("manual", path=str(tmp_path / "f2.json"))
    raw1, raw2 = open(p1, "rb").read(), open(p2, "rb").read()
    assert raw1 == raw2                          # bitwise under pinned clock
    flight = json.loads(raw1)
    assert flight["start_unix"] == 1700000000.0
    assert flight["spans"][0]["t0_unix"] == 1699999997.5


@timeline_mark
def test_fleet_dir_sources_health_discovery_stays_in_bounds(tmp_path):
    """Health discovery covers the fleet layout (<workdir>/health*.json
    next to a <workdir>/flight dir) but must NOT glob the journal
    directory's parent: a default workdir of /tmp/fleet would otherwise
    merge some other process's /tmp/health.json into this report."""
    wd = tmp_path / "fleet"
    (wd / "flight").mkdir(parents=True)
    (wd / "health.json").write_text("{}")
    (wd / "health_rank0.json").write_text("{}")
    foreign = tmp_path / "health.json"           # parent of the workdir
    foreign.write_text("{}")
    src = obs_timeline.fleet_dir_sources(
        flight_dir=str(wd / "flight"), journal=str(wd / "fleet.jsonl"))
    assert str(wd / "health.json") in src["health_paths"]
    assert str(wd / "health_rank0.json") in src["health_paths"]
    assert str(foreign) not in src["health_paths"]
    # an arbitrary --dir (not the <workdir>/flight or <journal>_flight
    # layouts) must not widen the glob to ITS parent either
    src = obs_timeline.fleet_dir_sources(flight_dir=str(wd))
    assert str(wd / "health.json") in src["health_paths"]
    assert str(foreign) not in src["health_paths"]


def _mini_flight(rank: int, pid: int, spans: list, coll: bool = False):
    flight = {"rank": rank, "attempt": 0, "pid": pid, "spans": spans}
    if coll:
        flight["metrics"] = {"gauges": {
            'collective_ops_per_step{op="all-reduce"}': {"value": 3},
            'collective_bytes_per_step{op="all-reduce"}': {"value": 1024}}}
    return flight


@timeline_mark
def test_timeline_merge_calibration_coverage_and_chrome_trace(tmp_path):
    """The tentpole merge: wall-ordered cross-rank events, stamp-less
    events calibrated from a sibling's monotonic->wall offset, torn
    sources costed as coverage entries (never a raised report), and a
    Perfetto/Chrome-trace export with one lane per rank."""
    s0 = [{"name": "steps", "t0_s": 10.0, "t0_unix": 1000.0, "dur_s": 0.5,
           "step": 2, "n": 2, "input_s": 0.1, "compute_s": 0.3,
           "hook_s": 0.05},
          # pre-fix event: no wall stamp — the sibling above calibrates it
          {"name": "snapshot", "t0_s": 10.3, "dur_s": 0.03}]
    s1 = [{"name": "steps", "t0_s": 50.0, "t0_unix": 1000.3, "dur_s": 1.5,
           "step": 2, "n": 2, "input_s": 0.1, "compute_s": 1.3,
           "hook_s": 0.05}]
    (tmp_path / "flight_0_11.json").write_text(
        json.dumps(_mini_flight(0, 11, s0, coll=True)))
    (tmp_path / "flight_1_22.json").write_text(
        json.dumps(_mini_flight(1, 22, s1)))
    (tmp_path / "flight_2_33.json").write_text("{torn")
    journal = tmp_path / "fleet.jsonl"
    journal.write_text(json.dumps(
        {"event": "gang_start", "ts": 999.9, "ranks": [0, 1, 2]}) + "\n"
        + '{"event": "torn_li')
    # An OBS_TRACE_FILE from rank 0's process: the same span closes
    # land in the flight ring AND here (trace events carry rank from
    # OBS_RANK but no pid) — the merge must count each close ONCE or
    # anatomy totals double.
    trace_file = tmp_path / "trace0.jsonl"
    trace_file.write_text("".join(
        json.dumps({**ev, "rank": 0, "attempt": 0}) + "\n" for ev in s0))
    merged = obs_timeline.merge(
        flight_paths=[str(tmp_path / f"flight_{r}_{p}.json")
                      for r, p in ((0, 11), (1, 22), (2, 33))],
        trace_paths=[str(trace_file)],
        journal_paths=[str(journal)])
    assert len(merged["events"]) == len(s0) + len(s1)    # deduped
    assert all(e["pid"] == 11 for e in merged["events"]
               if e["rank"] == 0)           # the flight copy was kept
    cov = merged["coverage"]
    assert cov["ranks_present"] == [0, 1]
    assert cov["ranks_missing"] == [2]           # named, not raised
    assert list(cov["unreadable"]) == [str(tmp_path / "flight_2_33.json")]
    assert cov["torn_lines"] == 1
    assert cov["uncalibrated_events"] == 0
    snap = next(e for e in merged["events"] if e["name"] == "snapshot")
    assert snap["t0_unix"] == pytest.approx(1000.3)      # offset 990.0
    stamps = [e["t0_unix"] for e in merged["events"]]
    assert stamps == sorted(stamps)              # wall-ordered
    assert merged["collectives"][0]["all-reduce"] == {"ops": 3,
                                                      "bytes": 1024}

    trace = obs_timeline.chrome_trace(merged)
    lanes = {e["args"]["name"] for e in trace["traceEvents"]
             if e["ph"] == "M" and e["name"] == "process_name"}
    # one lane per rank + the unranked fleet lane the journal marker uses
    assert lanes == {"rank 0", "rank 1", "fleet / unranked"}
    xs = [e for e in trace["traceEvents"] if e["ph"] == "X"]
    assert xs and all(e["ts"] >= 0 for e in xs)  # relative to base stamp
    marks = [e for e in trace["traceEvents"] if e["ph"] == "i"]
    assert [m["name"] for m in marks] == ["gang_start"]
    json.dumps(trace)                            # loadable = serializable

    rows = obs_timeline.step_anatomy(merged)
    by_rank = {r["rank"]: r for r in rows}
    assert by_rank[1]["window_s"] == 1.5 and by_rank[0]["window_s"] == 0.5
    assert by_rank[1]["compute_s"] > by_rank[0]["compute_s"]  # the skew
    assert by_rank[0]["snapshot_s"] == pytest.approx(0.03)
    assert by_rank[0]["hook_s"] == pytest.approx(0.02)  # snap broken out
    assert by_rank[0]["collective_ops"] == 6     # 3 ops/step x n=2
    tot = obs_timeline.anatomy_totals(rows)
    assert tot["window_s"] == pytest.approx(2.0) and tot["n"] == 4


@timeline_mark
def test_step_anatomy_ties_out_against_loop_counters(sgd_step, sink):
    """ACCEPTANCE tie-out: the per-window anatomy deltas the 'steps'
    events carry sum to the loop_*_seconds_total counters the TrainLoop
    feeds (input/compute exactly; the hook column trails one boundary by
    construction — its counter is still open when the mark reads it)."""
    reg = obs_metrics.registry()
    in_c = reg.counter("loop_input_seconds_total")
    stp_c = reg.counter("loop_step_seconds_total")
    hk_c = reg.counter("loop_hook_seconds_total")
    before = (in_c.value, stp_c.value, hk_c.value)
    state = _fresh_state()
    TrainLoop(sgd_step, iter(_batches(6)), 6,
              hooks=[MetricsHook(every=2)]).run(state)
    d_in = in_c.value - before[0]
    d_stp = stp_c.value - before[1]
    d_hk = hk_c.value - before[2]
    steps_events = [e for e in sink if e["name"] == "steps"]
    assert [e["step"] for e in steps_events] == [2, 4, 6]
    for e in steps_events:
        assert e["t0_unix"] is not None          # mergeable across ranks
        assert e["input_s"] >= 0 and e["compute_s"] > 0

    rows = obs_timeline.step_anatomy(
        {"events": steps_events, "markers": [], "health": [],
         "collectives": {}})
    assert [(r["step_from"], r["step_to"], r["n"]) for r in rows] == [
        (0, 2, 2), (2, 4, 2), (4, 6, 2)]
    tot = obs_timeline.anatomy_totals(rows)
    assert tot["input_s"] == pytest.approx(d_in, abs=1e-4)
    assert tot["compute_s"] == pytest.approx(d_stp, abs=1e-4)
    assert 0.0 <= tot["hook_s"] <= d_hk + 1e-6   # trails one boundary
    for r in rows:
        assert r["other_s"] >= 0.0               # window >= categorized sum
        assert r["window_s"] >= r["input_s"] + r["compute_s"] - 1e-6


@timeline_mark
def test_anomaly_hook_fires_counters_health_and_flight(tmp_path, sink,
                                                       monkeypatch):
    """The hook half of the tentpole: a regression firing bumps
    anomaly_flags_total, emits an 'anomaly' trace event, dumps a flight
    mid-run (the ring must cover the steps AROUND the anomaly), and the
    health.json the fleet polls carries the fired step; the NaN sentinel
    rides the train_loss gauge MetricsHook already set — no second
    device fetch."""
    monkeypatch.setenv("OBS_DIR", str(tmp_path / "flight"))
    ticks = iter([0.0]                           # begin()
                 + [0.010 * s for s in range(1, 7)]       # 6 fast windows
                 + [0.06 + 0.25 * k for k in range(1, 5)])  # then slow
    monkeypatch.setattr(time, "perf_counter", lambda: next(ticks))
    obs_metrics.gauge("train_loss").set(1.0)
    reg = obs_metrics.registry()
    flags_key = 'anomaly_flags_total{kind="step_time_regression"}'
    nan_key = 'anomaly_flags_total{kind="nan_loss"}'
    before = reg.snapshot()["counters"]
    rh = obs_anomaly.RunHealth(
        rank=0, step_time=obs_anomaly.EwmaRegression(
            warmup=4, alpha=1.0, z_thresh=4.0, skip_first=0))
    hook = AnomalyHook(every=2, health_path=str(tmp_path / "health.json"),
                       health=rh)
    installed = obs_recorder._GLOBAL
    obs_recorder._GLOBAL = obs_recorder.FlightRecorder(registry=reg)
    try:
        for step in range(1, 7):                 # healthy: warmup + quiet
            hook.after_step(step, None, None)
        snap = reg.snapshot()["counters"]
        assert snap.get(flags_key, 0) == before.get(flags_key, 0)
        hook.after_step(7, None, None)           # first slow window: fires
        obs_metrics.gauge("train_loss").set(float("nan"))
        hook.after_step(8, None, None)           # due mark: NaN sentinel
        snap = reg.snapshot()["counters"]
        assert snap.get(flags_key, 0) - before.get(flags_key, 0) == 1
        assert snap.get(nan_key, 0) - before.get(nan_key, 0) == 1
    finally:
        obs_recorder._GLOBAL = installed
    kinds = [e["kind"] for e in sink if e["name"] == "anomaly"]
    assert kinds == ["step_time_regression", "nan_loss"]
    assert rh.step_time.fired_step == 7 and rh.nan_step == 8
    flights = glob.glob(str(tmp_path / "flight" / "flight_*.json"))
    assert flights                               # dumped mid-run, pre-death
    assert json.load(open(flights[0]))["reason"].startswith("anomaly_")
    health = obs_anomaly.read_health(str(tmp_path / "health.json"))
    assert health["flags"]["step_time_regression"]["fired_step"] == 7
    assert health["flags"]["nan_loss"] == {"firing": True, "fired_step": 8}
    z = reg.snapshot()["gauges"]["anomaly_step_time_z"]["value"]
    assert z > 4.0


@timeline_mark
def test_obs_report_renders_gaps_and_exports_trace(tmp_path, monkeypatch):
    """The torn-flight satellite end-to-end: a fleet dir with one good
    flight, one torn flight, and a health.json renders the ranks it HAS
    and lists the gaps — and --format trace/json export the same merge
    machine-readably."""
    flight_dir = tmp_path / "flight"
    flight_dir.mkdir()
    monkeypatch.setattr(obs_metrics, "_now", lambda: 50.0)
    monkeypatch.setattr(obs_metrics, "_wall", lambda: 1700000100.0)
    monkeypatch.setenv("OBS_RANK", "0")
    rec = obs_recorder.FlightRecorder(registry=obs_metrics.MetricsRegistry())
    rec.record_span({"name": "steps", "t0_s": 49.0, "t0_unix": 1700000099.0,
                     "dur_s": 1.0, "step": 4, "n": 2, "input_s": 0.2,
                     "compute_s": 0.7, "hook_s": 0.05})
    rec.record_loss(4, 1.5)
    rec.dump("exit", path=str(flight_dir / "flight_0_11.json"))
    (flight_dir / "flight_1_22.json").write_text('{"rank": 1, "spa')
    rh = obs_anomaly.RunHealth(rank=0)
    rh.observe_window(4, 1, 0.01)
    rh.write(str(flight_dir / "health_rank0.json"))
    monkeypatch.delenv("OBS_RANK")

    def _report(*extra):
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "obs_report.py"),
             "--dir", str(flight_dir), *extra],
            capture_output=True, text=True, timeout=60)
        assert proc.returncode == 0, proc.stderr
        return proc.stdout

    md = _report()
    assert "Merged timeline" in md
    assert "ranks present**: [0]" in md
    assert "ranks MISSING" in md and "[1]" in md          # the gap list
    assert "unreadable" in md and "flight_1_22.json" in md
    assert "Step anatomy" in md and "Health" in md
    trace = json.loads(_report("--format", "trace"))
    assert any(e.get("ph") == "X" for e in trace["traceEvents"])
    assert trace["otherData"]["coverage"]["ranks_missing"] == [1]
    merged = json.loads(_report("--format", "json"))
    assert merged["coverage"]["ranks_present"] == [0]
    assert merged["anatomy"][0]["step_to"] == 4
    assert merged["health"][0]["rank"] == 0


@timeline_mark
def test_merge_and_exports_tolerate_string_ranks(tmp_path):
    """OBS_RANK need not be numeric (trace._context and the flight
    writer both keep e.g. "chief" as-is): coverage sorts, the anatomy
    sort, and Perfetto lane assignment must survive mixed int/str ranks
    instead of raising mid-outage."""
    evs = [{"name": "steps", "t0_s": 1.0, "t0_unix": 1000.0, "dur_s": 0.5,
            "step": 2, "n": 2, "rank": 0, "input_s": 0.1,
            "compute_s": 0.3, "hook_s": 0.0},
           {"name": "steps", "t0_s": 2.0, "t0_unix": 1000.6, "dur_s": 0.5,
            "step": 2, "n": 2, "rank": "chief", "input_s": 0.1,
            "compute_s": 0.3, "hook_s": 0.0}]
    tf = tmp_path / "t.jsonl"
    tf.write_text("".join(json.dumps(e) + "\n" for e in evs))
    merged = obs_timeline.merge(trace_paths=[str(tf)])
    assert merged["coverage"]["ranks_present"] == [0, "chief"]
    rows = obs_timeline.step_anatomy(merged)
    assert [r["rank"] for r in rows] == [0, "chief"]
    trace = obs_timeline.chrome_trace(merged)
    xs = {e["pid"] for e in trace["traceEvents"] if e.get("ph") == "X"}
    assert len(xs) == 2 and 0 in xs              # distinct int lanes


@timeline_mark
def test_obs_report_health_only_invocation(tmp_path):
    """Health files alone are renderable input: a postmortem where the
    flights tore away but health.json survived must not exit 2."""
    rh = obs_anomaly.RunHealth(rank=0)
    rh.observe_window(4, 1, 0.01)
    path = tmp_path / "health_rank0.json"
    rh.write(str(path))
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "obs_report.py"),
         "--health", str(path)],
        capture_output=True, text=True, timeout=60)
    assert proc.returncode == 0, proc.stderr
    assert "Health" in proc.stdout and "rank 0" in proc.stdout


@timeline_mark
def test_anomaly_hook_excludes_save_spans_from_step_time(monkeypatch):
    """A periodic checkpoint is seconds against sub-ms steps: without
    excluding checkpoint/snapshot/eval span time from the detector's
    window, the first post-warmup save would score as a guaranteed
    false regression against the warmup-pinned baseline.  A genuinely
    slow window (no span accounting for it) still fires."""
    clock = {"t": 0.0}
    monkeypatch.setattr(time, "perf_counter", lambda: clock["t"])
    hook = AnomalyHook(every=1)
    hook._health.step_time = obs_anomaly.EwmaRegression(
        warmup=4, z_thresh=8.0, skip_first=0)
    hook.begin(_FakeLoop())
    snap = obs_metrics.histogram("span_seconds").labels(name="snapshot")
    for s in range(1, 6):
        clock["t"] += 0.01
        hook.after_step(s, None, {})
    assert hook._health.step_time.armed
    clock["t"] += 5.01                       # 5 s of it inside the save
    snap.observe(5.0)
    hook.after_step(6, None, {})
    assert not hook._health.step_time.firing     # excluded: not a regression
    clock["t"] += 5.0                        # unexplained 5 s window
    hook.after_step(7, None, {})
    assert hook._health.step_time.firing
    assert hook._health.step_time.fired_step == 7
