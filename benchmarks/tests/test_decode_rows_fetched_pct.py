"""``decode_rows_fetched_pct.mixed`` (metrics/decode_rows_fetched_pct.
mixed.py): rows the decode steps' attention fetched over rows the
queries could see, from the program's two counters."""

import types

import pytest

from benchmarks import run as bench_run
from benchmarks.harness import program_tape as pt
from benchmarks.tests.test_afmoe import CELL, TINY_AFMOE, TINY_MIXED

NAME = "decode_rows_fetched_pct.mixed"


def _read(monkeypatch, series: dict):
    monkeypatch.setattr(pt, "registry_value", lambda kind, key: series.get(
        key) if kind == "counters" else None)
    return bench_run.read_per_layer(types.SimpleNamespace(), [NAME])


def _series(what, window, full):
    return {'serve_cache_rows_%s_total{kind="window"}' % what: window,
            'serve_cache_rows_%s_total{kind="full"}' % what: full}


@pytest.mark.parametrize("fetched, seen, want", [
    ((400, 600), (400, 600), 100.0),        # the floor: nothing dead
    ((420, 620), (400, 600), 104.0),        # rounded up to blocks
    ((2 ** 19, 2 ** 19), (425984, 180224), 100.0 * 2 ** 20 / 606208),
    ((0, 512), (None, 500), 102.4),         # a model without rings
])
def test_it_is_fetched_over_seen_both_kinds_together(monkeypatch, fetched,
                                                     seen, want):
    series = {**_series("fetched", *fetched), **_series("read", *seen)}
    assert _read(monkeypatch, series) == {NAME: pytest.approx(want)}


@pytest.mark.parametrize("series", [
    {}, _series("read", 400, 600)])         # the parent of PR 29
def test_a_program_without_the_counter_leaves_the_metric_out(monkeypatch,
                                                             series):
    assert _read(monkeypatch, series) == {}


def test_the_cpu_rehearsal_of_the_cell_reports_every_row_fetched():
    # On the CPU the token step takes the einsum chain, which reads every
    # row every slot holds: the counters say so through the whole path,
    # model -> engine -> registry -> reader -> result line.
    result = bench_run.run_cell(
        CELL, 2 ** 31 + 29, 1.0, True, require_tpu=False,
        overrides={"config": TINY_AFMOE, "params": TINY_MIXED})
    assert result["correct"] is True
    got = result["metrics"][NAME]
    assert got["unit"] == "%" and got["value"] > 100.0
