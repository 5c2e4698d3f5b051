"""``moe_block_fill_pct.chat`` (metrics/moe_block_fill_pct.chat.py):
pairs on held experts over the sorted rows the expert walks handed to
the grouped products, from the program's two counters."""

import types

import pytest

from benchmarks import run as bench_run
from benchmarks.harness import program_tape as pt
from benchmarks.tests.test_qwen3_next import CELL, TINY_CHAT, TINY_QWEN

NAME = "moe_block_fill_pct.chat"


def _read(monkeypatch, series: dict):
    monkeypatch.setattr(pt, "registry_value", lambda kind, key: series.get(
        key) if kind == "counters" else None)
    return bench_run.read_per_layer(types.SimpleNamespace(), [NAME])


def _series(held, walked, absent=None):
    return {'moe_pairs_total{where="held"}': held,
            'moe_pairs_total{where="absent"}': absent,
            "moe_rows_walked_total": walked}


@pytest.mark.parametrize("held, walked, want", [
    (2048, 2048, 100.0),                    # every block full
    (320, 2048, 15.625),                    # a token step, blocks of 2,048
    (320, 640, 50.0),                       # the same step, blocks of 640
    (8 * 320 + 10240, 8 * 640 + 5 * 2048, 100.0 * 12800 / 15360),
])
def test_it_is_held_pairs_over_rows_walked(monkeypatch, held, walked, want):
    series = _series(held, walked, absent=7 * held)
    assert _read(monkeypatch, series) == {NAME: pytest.approx(want)}


@pytest.mark.parametrize("series", [
    {}, _series(320, None, 2240),           # the parent of PR 35
    _series(0, 0)])                         # a model without experts
def test_a_program_without_the_counter_leaves_the_metric_out(monkeypatch,
                                                             series):
    assert _read(monkeypatch, series) == {}


def test_the_cpu_rehearsal_of_the_cell_reports_a_share():
    # The counters come through the whole path, model -> engine ->
    # registry -> reader -> result line: no block holds more pairs than
    # rows, and a walk hands the products some.
    result = bench_run.run_cell(
        CELL, 2 ** 31 + 35, 1.0, True, require_tpu=False,
        overrides={"config": TINY_QWEN, "params": TINY_CHAT})
    assert result["correct"] is True
    got = result["metrics"][NAME]
    assert got["unit"] == "%" and 0.0 < got["value"] <= 100.0
