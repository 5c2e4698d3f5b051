"""``decode_ahead_pct`` (metrics/decode_ahead_pct.py): the decode steps
read one boundary late over all the batcher's decode steps, from the
program's ``serve_decode_steps_total{readback}`` counters."""

import types

import pytest

from benchmarks import run as bench_run
from benchmarks.harness import program_tape as pt
from benchmarks.tests.conftest import TINY_CONFIG, TINY_SERVE

NAME = "decode_ahead_pct"


def _read(monkeypatch, series: dict):
    monkeypatch.setattr(pt, "registry_value", lambda kind, key: series.get(
        key) if kind == "counters" else None)
    return bench_run.read_per_layer(types.SimpleNamespace(), [NAME])


def _series(late, same):
    return {'serve_decode_steps_total{readback="late"}': late,
            'serve_decode_steps_total{readback="same_step"}': same}


@pytest.mark.parametrize("late, same, want", [
    (1500, 100, 93.75),                     # a backlog: every slot busy
    (0, 400, 0.0),                          # a free slot at every boundary
    (7, None, 100.0),                       # a series never touched
])
def test_it_is_late_steps_over_all_steps(monkeypatch, late, same, want):
    assert _read(monkeypatch, _series(late, same)) == {
        NAME: pytest.approx(want)}


@pytest.mark.parametrize("series", [
    {},                                     # no registry entry at all
    {"serve_decode_steps_total": 1647},     # the parent: one series, no label
    _series(None, None), _series(0, 0)])
def test_a_program_without_the_counter_leaves_the_metric_out(monkeypatch,
                                                             series):
    assert _read(monkeypatch, series) == {}


def test_the_cpu_rehearsal_of_a_backlog_cell_reads_most_steps_late():
    # Through the whole path, batcher -> registry -> reader -> result
    # line: a closed backlog keeps every slot busy.
    result = bench_run.run_cell(
        "gpt2_124m.serve_backlog", 2 ** 31 + 40, 2.0, True,
        require_tpu=False,
        overrides={"config": TINY_CONFIG, "params": TINY_SERVE})
    assert result["correct"] is True
    got = result["metrics"][NAME]
    assert got["unit"] == "%" and 50.0 < got["value"] <= 100.0
