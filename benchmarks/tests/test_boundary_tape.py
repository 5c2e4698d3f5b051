"""The readers of the decode boundary's pieces (harness/boundary_tape.py)
on the made-up tape and trace of test_program_tape.py, with this PR's
spans laid into every boundary."""

import json
import os

import pytest

from benchmarks import run as bench_run
from benchmarks.harness import boundary_tape as bt
from benchmarks.harness import program_tape as pt
from benchmarks.harness import schema
from benchmarks.tests.conftest import ROOT, TINY_CONFIG, TINY_SERVE
from benchmarks.tests.test_program_tape import WINDOW, _made_up

CELLS = ["gpt2_124m.serve_backlog", "trinity_large_ep8.serve_mixed_backlog",
         "qwen3_next_ep8.serve_chat_backlog",
         "ling3_flash_ep8.serve_longform_backlog"]
NEW = ["decode_host_ms", "decode_sync_latency_ms", "decode_fetch_ms",
       "decode_account_ms", "batcher_retire_ms", "boundary_longest_ms",
       "host_gc_share_pct"]
FETCH, ACCOUNT, LATENCY = 2e-4, 8e-5, 3e-4


def _with_the_new_spans(entries: list) -> list:
    """Into each made-up boundary (a read-back of 82 ms + 0.1 ms a
    boundary, the device done 1 ms before its end, the retire 0.1 ms
    after it): a wait that ends ``LATENCY`` after the device and leaves
    ``FETCH`` of the read-back, and an account behind the read-back."""
    out = []
    for e in entries:
        if e.name == "engine.decode.readback":
            out += [pt.Entry(bt.WAIT, e.t0, e.t1 - FETCH, e.name, None), e,
                    pt.Entry("engine.decode.account", e.t1, e.t1 + ACCOUNT,
                             pt.STEP, None)]
        else:
            out.append(e)
    return out


@pytest.fixture()
def made_up(monkeypatch):
    def build(dropped=0, old_program=False, more=(), **kw):
        run, entries = _made_up(**{"in_window": 5, **kw})
        if not old_program:
            entries = _with_the_new_spans(entries)
        monkeypatch.setattr(pt, "program_tape",
                            lambda: (list(more) + entries, dropped))
        run.compiles_in_window = 0
        return run
    return build


def test_the_pieces_of_a_known_boundary_sum_to_its_period(made_up):
    run = made_up()
    (mean, n), ms = bt._window(run), 1e-3
    # five steps in the window: four have a successor, three of those a
    # successor whose own is known (k = 0, 1, 2, each 0.1 ms longer)
    assert n == 3
    want = {"dispatch": 2.0, "wait": 82.0 + 0.1 - 0.2, "fetch": 0.2,
            "account": 0.08, "retire": 0.4, "admit": 0.2,
            "step self": 90.0 - (0.2 + 2.0 + 82.0 + 0.08 + 0.4),
            "between steps": 10.0 - 0.1}
    assert list(mean) == list(want)
    for name, value in want.items():
        assert mean[name] == pytest.approx(value * ms), name
    assert sum(mean.values()) == pytest.approx(100.0 * ms)
    assert bt.decode_host_ms(run) == pytest.approx(100.0 - 81.9)
    assert bt.decode_fetch_ms(run) == pytest.approx(0.2)
    assert bt.span_mean_ms(run, "engine.decode.account") == \
        pytest.approx(0.08)
    assert bt.span_mean_ms(run, "serve.retire") == pytest.approx(0.4)


def test_a_boundary_that_prefilled_and_the_one_before_it_are_left_out(
        made_up):
    t = WINDOW[0] + 1 + 0.1         # the window's second boundary, k = 1
    run = made_up(more=[pt.Entry("engine.prefill.dispatch", t + 0.0004,
                                 t + 0.0008, pt.STEP, None)])
    (mean, n) = bt._window(run)
    assert n == 1                   # k = 2 alone
    assert mean["wait"] == pytest.approx(1e-3 * (82.0 + 0.2 - 0.2))


def test_the_longest_boundary_and_the_collectors_share(made_up, capsys):
    t = WINDOW[0] + 1 + 0.3         # k = 3: a collection between steps
    run = made_up(more=[pt.Entry(bt.GC, t + 0.092, t + 0.096, None, None)])
    assert bt.boundary_longest_ms(run) == pytest.approx(100.0)
    assert bt.host_gc_share_pct(run) == pytest.approx(
        100 * 0.004 / (WINDOW[1] - WINDOW[0]))
    said = capsys.readouterr().out
    assert "1 collections of 1 ms or more in the window, 4.0 ms" in said
    assert "compilations in the window: 0" in said
    assert bt._held_line(bt.periods(pt.window_entries(run))[3]) == (
        "100.0 ms = engine.prefill .pack 0.0 .dispatch 0.0 .readback 0.0 + "
        "engine.decode.wait 82.1 + the rest 17.9, host.gc 4.0 wherever it "
        "struck")


@pytest.mark.parametrize("early", [0.0, 1.5e-3])
def test_sync_latency_is_durations_only_so_a_skew_does_not_move_it(
        made_up, early, capsys):
    run = made_up(device_early=early)
    assert bt.decode_sync_latency_ms(run) == pytest.approx(1e3 * LATENCY)
    # the line of the run, with the tail's own period and wait beside
    # the device time they are held against
    bt.decode_host_ms(run)
    said = capsys.readouterr().out
    assert "decode boundary: dispatch 2.000 + wait 81.900 + " in said
    assert "sync latency 0.300 ms" in said and "period 100.000" in said


@pytest.mark.parametrize("why, kw", [
    ("a wrapped ring", dict(dropped=5, in_window=0)),
    ("a program from before the spans", dict(old_program=True)),
])
def test_no_sound_reading_gives_none_not_a_guess(made_up, why, kw):
    run = made_up(**kw)
    for name in NEW:
        assert bench_run.read_per_layer(run, [name]) == {}, (why, name)


def test_every_old_metric_reads_what_it_read(made_up):
    """``engine.decode.account`` is the step's own time to the old
    readers, and ``engine.decode.wait`` is not the step's child."""
    def old(run):
        return (pt.step_self_ms(run),
                pt.span_mean_ms(run, "engine.decode.dispatch"),
                pt.span_mean_ms(run, "engine.decode.readback"),
                pt.idle_by_span(run), pt.anchor(run))
    before = old(made_up(old_program=True))
    assert old(made_up()) == before
    assert before[0] == pytest.approx(5.4)


def test_the_committed_benchmark_lists_the_new_metrics_in_four_cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    schema.check(bench, ROOT)
    per = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW:
        assert per[name]["workloads"] == CELLS, name
        assert per[name]["moves"] == "serve_tokens_per_s"
    for kind, cell in zip(("mixed", "chat", "longform"), CELLS[1:]):
        assert per[f"decode_dispatch_ms.{kind}"]["workloads"] == [cell]


def test_the_cpu_rehearsal_reports_the_host_clock_ones():
    result = bench_run.run_cell(
        "gpt2_124m.serve_backlog", 2 ** 31 + 39, 4.0, True,
        require_tpu=False,
        overrides={"config": TINY_CONFIG, "params": TINY_SERVE})
    assert result["correct"] is True
    got = result["metrics"]
    for name in NEW:
        if name != "decode_sync_latency_ms":    # needs a device trace
            assert got[name]["value"] >= 0, name
    assert got["decode_host_ms"]["value"] > got["decode_fetch_ms"]["value"]
