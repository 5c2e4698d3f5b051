"""The readers of the program's own tape (harness/program_tape.py) on a
made-up tape and a made-up trace, and the CPU rehearsal of a tiny serve
cell, which has to report every host-clock metric of them."""

import types

import pytest

from benchmarks import run as bench_run
from benchmarks.harness import program_tape as pt
from benchmarks.harness import trace as tr
from benchmarks.tests.conftest import TINY_CONFIG, TINY_SERVE

OFFSET = -137.25            # trace clock minus host clock
WINDOW = (100.0, 140.0)     # host clock
PERIOD = 0.1


def _boundary(t: float, k: int) -> tuple:
    """One serving boundary that starts at host time ``t``: the
    program's entries, the benchmark's spans round the same calls, and
    the one device operation it runs.  The decode call grows by 0.1 ms a
    boundary, so only the true alignment pairs durations up."""
    grow = 1e-4 * k
    step = (t, t + 0.090 + grow)
    decode = (t + 0.001, t + 0.085 + grow)
    entries = [
        pt.Entry("serve.admit", t + 0.0001, t + 0.0003, pt.STEP, None),
        pt.Entry("engine.decode.dispatch", decode[0], t + 0.003, pt.STEP,
                 None),
        pt.Entry("engine.decode.readback", t + 0.003, decode[1], pt.STEP,
                 None),
        pt.Entry("serve.retire", t + 0.0851 + grow, t + 0.0855 + grow,
                 pt.STEP, None),
        pt.Entry(pt.STEP, *step, None, None)]
    bench = {"serve_decode_boundary": (step[0] - 1e-6, step[1] + 1e-6),
             "serve_decode": (decode[0] - 1e-6, decode[1] + 1e-6)}
    device = (t + 0.0025, t + 0.084 + grow)
    return entries, bench, device


def _made_up(boundaries: int = 6, untraced: int = 1, stretch: float = 0.0,
             in_window: int = 2, device_early: float = 0.0):
    """A run whose tail holds ``boundaries`` boundaries, the first
    ``untraced`` of them before the profiler started, and whose window
    holds ``in_window``; ``stretch`` is added to every traced span's
    duration (a trace that disagrees with the host tape);
    ``device_early`` moves the device plane that much earlier than the
    host plane (a profiler whose two clocks are skewed)."""
    entries, tape, spans, ops = [], {}, [], []
    for k in range(boundaries):
        e, bench, device = _boundary(WINDOW[1] + 0.05 + PERIOD * k, k)
        entries += e
        for name, (t0, t1) in bench.items():
            tape.setdefault(name, []).append((t0, t1))
            if k >= untraced:
                spans.append(tr.Event(name, t0 + OFFSET,
                                      t1 + OFFSET + stretch))
        if k >= untraced:
            ops.append(tr.Event("fusion", device[0] + OFFSET - device_early,
                                device[1] + OFFSET - device_early,
                                "fusion", ""))
    for k in reversed(range(in_window)):
        entries = _boundary(WINDOW[0] + 1 + PERIOD * k, k)[0] + entries
    programs = [tr.Event("jit__decode_step_fn(7)", e.start, e.end)
                for e in ops]
    trace = tr.Trace({0: ops}, sorted(spans, key=lambda e: e.start),
                     {0: programs})
    return types.SimpleNamespace(
        facts={"window": WINDOW}, samples={}, trace=trace,
        trace_window=tr.window_of(trace),
        spans=types.SimpleNamespace(tape=tape)), entries


@pytest.fixture()
def made_up(monkeypatch):
    def build(dropped=0, empty=False, **kw):
        run, entries = _made_up(**kw)
        monkeypatch.setattr(pt, "program_tape", lambda: (
            [] if empty else entries, dropped))
        return run
    return build


def test_the_anchor_recovers_a_known_offset(made_up):
    run = made_up()
    assert pt.anchor(run) == pytest.approx(OFFSET, abs=2e-6)


def test_idle_laid_to_program_spans_sums_to_the_idle_total(made_up):
    run = made_up()
    gaps = pt.idle_by_span(run)
    window = run.trace_window[1] - run.trace_window[0]
    idle = window - tr.busy(run.trace, run.trace_window)[0]
    assert sum(gaps.values()) == pytest.approx(idle, abs=1e-9)
    # Between two device operations the host: waits out the read-back
    # (1 ms), retires (0.4 ms), is between step() calls (10 ms less the
    # 0.1 ms a boundary the step has grown by), admits (0.2 ms),
    # dispatches (1.5 ms idle of it); the rest is the step's own time.
    # Four such gaps, after traced boundaries 1 to 4.
    assert gaps["engine.decode.dispatch"] == pytest.approx(4 * 0.0015)
    assert gaps["engine.decode.readback"] == pytest.approx(4 * 0.001)
    assert gaps["serve.admit"] == pytest.approx(4 * 0.0002)
    assert gaps["serve.retire"] == pytest.approx(4 * 0.0004)
    assert gaps[pt.UNATTRIBUTED] == pytest.approx(
        sum(0.010 - 1e-4 * k for k in (1, 2, 3, 4)))
    assert pt.idle_pct(run, (pt.UNATTRIBUTED,)) == pytest.approx(
        100 * gaps[pt.UNATTRIBUTED] / window)
    shares = [pt.idle_pct(run, (name,)) for name in gaps]
    assert sum(shares) == pytest.approx(100 * idle / window)


def test_a_skewed_device_plane_moves_idle_between_adjacent_spans_only(
        made_up):
    """A device plane two milliseconds ahead of the host plane (what the
    v5e's profiler gives, PERF.md section 6) lays the dispatch's idle
    time to the read-back before it: the two spans are one metric,
    which the skew moves only by what crosses the pair's outer edges."""
    decode = ("engine.decode.dispatch", "engine.decode.readback")
    sound = pt.idle_by_span(made_up())
    assert sum(sound[n] for n in decode) == pytest.approx(4 * 0.0025)
    skewed = made_up(device_early=2e-3)
    gaps = pt.idle_by_span(skewed)
    assert "engine.decode.dispatch" not in gaps
    # the dispatch's 1.5 ms, the read-back's own 1, and the 0.5 ms that
    # crossed the pair's outer edge (out of the step's own time)
    assert gaps["engine.decode.readback"] == pytest.approx(4 * 0.003)
    window = skewed.trace_window[1] - skewed.trace_window[0]
    assert pt.idle_pct(skewed, decode) == pytest.approx(
        100 * 4 * 0.003 / window)


def test_self_time_is_the_duration_less_what_the_children_cover(made_up):
    run = made_up()
    # 90 ms less admit 0.2, dispatch 2, read-back 82, retire 0.4; the
    # second boundary of the window is 0.1 ms longer, and so is its
    # read-back.
    assert pt.step_self_ms(run) == pytest.approx(5.4)
    assert pt.span_mean_ms(run, "engine.decode.dispatch") == \
        pytest.approx(2.0)
    assert pt.span_mean_ms(run, "engine.decode.readback") == \
        pytest.approx(82.05)
    assert pt.prefill_host_ms(run) is None      # nothing prefilled


def test_a_requests_events_inside_a_step_are_not_its_children(made_up,
                                                              monkeypatch):
    """``serve_prefill`` is emitted inside ``serve.step`` (its parent on
    the tape) and runs from before the prefill to the request's first
    token: over the engine's spans and over the per-request bookkeeping
    after them.  That bookkeeping is the step's self time; the event
    takes nothing from it."""
    run = made_up()
    entries, dropped = pt.program_tape()
    t = WINDOW[0] + 1       # the window's first boundary: admit ends at
    #                         +0.3 ms, the decode dispatch opens at +1 ms
    extra = [pt.Entry("serve_queue", t - 5.0, t + 0.0002, pt.STEP, "r1"),
             pt.Entry("serve_prefill", t + 0.0002, t + 0.0009, pt.STEP,
                      "r1")]
    monkeypatch.setattr(pt, "program_tape",
                        lambda: (extra + entries, dropped))
    assert pt.step_self_ms(run) == pytest.approx(5.4)
    # ... and a request's event is read by its END: this one was
    # submitted five seconds before the window opened
    assert pt.request_p95_ms(run, "serve_queue") is None    # under 10
    assert [e.rid for e in pt.select(extra + entries, 0, WINDOW,
                                     ending=True)
            if e.name == "serve_queue"] == ["r1"]
    assert not [e for e in pt.select(extra + entries, 0, WINDOW)
                if e.name == "serve_queue"]


@pytest.mark.parametrize("why, kw, host_clock_too", [
    ("pairs whose durations disagree", dict(stretch=2e-4), False),
    ("an empty tape", dict(empty=True), True),
    # the ring has lost entries and its oldest lies inside the tail
    ("a wrapped ring", dict(dropped=5, in_window=0), True),
])
def test_no_sound_reading_gives_none_not_a_guess(made_up, why, kw,
                                                 host_clock_too):
    run = made_up(**kw)
    assert pt.idle_by_span(run) is None, why
    assert pt.idle_pct(run, (pt.UNATTRIBUTED,)) is None
    assert (pt.step_self_ms(run) is None) == host_clock_too


def test_a_ring_that_wrapped_before_the_tail_still_reads_the_tail(made_up):
    run = made_up(dropped=5)        # the oldest entry lies in the window
    assert pt.step_self_ms(run) is None
    assert pt.idle_by_span(run) is not None


def test_a_program_without_a_tape_reads_none(made_up, monkeypatch):
    run = made_up()
    monkeypatch.setattr(pt, "program_tape", lambda: (None, 0))
    assert pt.step_self_ms(run) is None and pt.idle_by_span(run) is None
    assert pt.registry_value("gauges", "no_such_series") is None


def test_the_cpu_rehearsal_reports_every_host_clock_metric():
    params = dict(TINY_SERVE, requests_per_s=70.0, burst_window_s=0.2,
                  grace_s=5)
    got = {}
    for cell in ("gpt2_124m.serve_backlog", "gpt2_124m.serve_steady"):
        result = bench_run.run_cell(
            cell, 2 ** 31 + 11, 4.0, True, require_tpu=False,
            overrides={"config": TINY_CONFIG, "params": params})
        assert result["correct"] is True, cell
        got[cell] = result["metrics"]
    backlog, steady = got.values()
    for name in ("batcher_self_ms.backlog", "decode_dispatch_ms.backlog",
                 "prefill_host_ms.backlog", "prefill_pad_pct.backlog",
                 "prefill_programs"):
        assert backlog[name]["value"] > 0, name
    for name in ("batcher_self_ms.steady", "prefill_host_ms.steady",
                 "queue_wait_submit_p95_ms.steady",
                 "request_prefill_p95_ms.steady", "prefill_programs"):
        assert steady[name]["value"] > 0, name
    # the device ones are absent without a device, as device_idle_pct is
    assert not [n for m in got.values() for n in m if n.startswith("idle_")]
    assert 0 < backlog["prefill_pad_pct.backlog"]["value"] < 100
