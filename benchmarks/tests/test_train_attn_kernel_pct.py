"""``train_attn_kernel_pct`` (metrics/train_attn_kernel_pct.py): the
share of traced LM blocks that took the blocked attention kernels, from
the program's ``lm_attention_blocks_total`` counter."""

import types

import pytest

from benchmarks import run as bench_run
from benchmarks.harness import program_tape as pt
from benchmarks.tests.conftest import TINY_CONFIG, TINY_TRAIN

NAME = "train_attn_kernel_pct"


def _read(monkeypatch, series: dict):
    monkeypatch.setattr(pt, "registry_value", lambda kind, key: series.get(
        key) if kind == "counters" else None)
    return bench_run.read_per_layer(types.SimpleNamespace(), [NAME])


@pytest.mark.parametrize("pallas, einsum, want", [
    (24, None, 100.0),          # every block of every trace
    (12, 12, 50.0),             # one trace took the chain
    (None, 36, 0.0),            # a zero is a reading, not a gap
])
def test_it_is_the_pallas_series_over_both(monkeypatch, pallas, einsum,
                                           want):
    series = {'lm_attention_blocks_total{impl="pallas"}': pallas,
              'lm_attention_blocks_total{impl="einsum"}': einsum}
    assert _read(monkeypatch, series) == {NAME: want}


def test_a_program_without_the_counter_leaves_the_metric_out(monkeypatch):
    # The parent of PR 25 has no such series: nothing is reported, and
    # nothing raises.
    assert _read(monkeypatch, {}) == {}


def test_the_cpu_rehearsal_of_a_train_cell_reports_it_as_zero():
    # On the CPU every block takes the einsum chain, and the counter
    # says so through the whole path: model -> registry -> reader ->
    # result line.
    result = bench_run.run_cell(
        "gpt2_124m.train_seq1024", 2 ** 31 + 13, 1.0, True,
        require_tpu=False,
        overrides={"config": TINY_CONFIG, "params": TINY_TRAIN})
    assert result["correct"] is True
    assert result["metrics"][NAME] == {"value": 0.0, "unit": "%"}
