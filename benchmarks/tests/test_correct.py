"""`correct` has to be able to come out false.

1. The control: the reference put in the program's place and computed
   in a precision below the configuration's bfloat16 (int8 linear
   layers in training, fp8 in serving), judged by the cells' own
   comparison and limits, is NOT correct.  (On the chip, at the cells'
   own sizes: benchmarks/control.py, readings in PERF.md.)
2. A whole run with the timed path broken underneath — a train step
   that returns its state unchanged, a decode step whose tokens are
   altered where they are produced — reports ``correct: false``.

The runs skip only the harness's look for a chip; everything else is
the command's path, at toy sizes on the CPU."""

import json
import os

import numpy as np
import pytest

from benchmarks import run as bench_run
from benchmarks.families import gpt2 as family
from benchmarks.harness import tokens
from benchmarks.kinds import train as train_kind
from benchmarks.reference import gpt2
from benchmarks.tests.conftest import (ROOT, TINY_CONFIG, TINY_SERVE,
                                       TINY_TRAIN)


def _cell(name: str) -> dict:
    with open(os.path.join(ROOT, "benchmarks/workloads", name + ".json")) as f:
        return json.load(f)


def _config(**sizes) -> dict:
    with open(os.path.join(ROOT, "benchmarks/configs/gpt2_124m.json")) as f:
        return {**json.load(f), **sizes}


@pytest.mark.parametrize("seed", [3, 4, 5])
def test_training_in_int8_is_not_correct(seed):
    cfg = _config(**TINY_CONFIG)
    limits = _cell("gpt2_124m.train_seq1024")["params"]["limits"]
    rows = tokens.markov_tokens(12, 64, cfg["vocab_size"], seed)
    batches = [rows[0:4], rows[4:8], rows[8:12]]
    kw = dict(learning_rate=0.05, momentum=0.9)
    make = lambda: family.init_params(cfg, seed)     # noqa: E731
    ref = gpt2.train_steps(make, batches, cfg, **kw)
    low = gpt2.train_steps(make, batches, cfg, precision="int8", **kw)
    as_program = (low["losses"], low["first_grad_norms"], low["delta_norms"])
    verdicts = train_kind.judge(as_program, ref, limits)
    assert any(value > limit for _, value, limit in verdicts), verdicts
    # ... and the reference against itself passes every one of them.
    same = (ref["losses"], ref["first_grad_norms"], ref["delta_norms"])
    assert all(v <= lim for _, v, lim in train_kind.judge(same, ref, limits))


@pytest.mark.parametrize("seed", [3, 4, 5])
def test_serving_in_fp8_is_not_correct(seed):
    # The published width and vocabulary (the best logits of 50,257 lie
    # as close together as in the cell), two layers, 256 positions.
    # For THIS number the control is fp8: per-row int8 keeps as many
    # significant bits as bfloat16 and reads no wider than the sound
    # program does (PERF.md, limits).
    cfg = _config(n_layer=2, n_positions=256)
    limit = _cell("gpt2_124m.serve_backlog")["params"]["limits"][
        "served_logit_gap_widest"]
    rng = np.random.default_rng(seed)
    params = family.init_params(cfg, seed)
    prompt = tokens.uniform_prompt(rng, 64, cfg["vocab_size"])
    served = tokens.uniform_prompt(rng, 190, cfg["vocab_size"])
    read = {p: gpt2.served_token_gaps(params, prompt, served, cfg, 256,
                                      control=p)["widest"]
            for p in ("fp8", "bf16")}
    assert read["fp8"] > limit > read["bf16"], read


def _broken_step(monkeypatch):
    from distributedtensorflowexample_tpu.engine import Engine
    real_build = Engine.build

    def build(self, *a, **kw):
        import jax
        import jax.numpy as jnp
        built = real_build(self, *a, **kw)
        real_step = built.step

        def unchanged(state, batch):
            new, metrics = real_step(jax.tree.map(jnp.copy, state), batch)
            return state.replace(step=new.step), metrics

        built.step = unchanged
        return built

    monkeypatch.setattr(Engine, "build", build)


def test_a_step_that_returns_its_state_unchanged_is_not_correct(monkeypatch):
    _broken_step(monkeypatch)
    result = bench_run.run_cell(
        "gpt2_124m.train_seq1024", 2 ** 31 + 5, 1.0, False,
        require_tpu=False,
        overrides={"config": TINY_CONFIG, "params": TINY_TRAIN})
    assert result["correct"] is False
    assert "train_tokens_per_s_per_chip" in result["metrics"]


def test_an_altered_token_is_not_correct(monkeypatch):
    from distributedtensorflowexample_tpu.serving.engine import DecodeEngine
    real_decode = DecodeEngine.decode

    def decode(self, busy=None):
        out = (real_decode(self, busy=busy) + 7) % self.vocab
        live = list(range(self.slots)) if busy is None else list(busy)
        self.last_tokens[live] = out[live]
        return out

    monkeypatch.setattr(DecodeEngine, "decode", decode)
    result = bench_run.run_cell(
        "gpt2_124m.serve_backlog", 77, 1.0, False, require_tpu=False,
        overrides={"config": TINY_CONFIG, "params": TINY_SERVE})
    assert result["correct"] is False
    assert result["attempted"] > 0


@pytest.mark.parametrize("cell, params", [
    ("gpt2_124m.train_seq1024", TINY_TRAIN),
    ("gpt2_124m.serve_backlog", TINY_SERVE),
])
def test_the_sound_path_is_correct(cell, params):
    result = bench_run.run_cell(
        cell, 2 ** 31 + 9, 1.0, False, require_tpu=False,
        overrides={"config": TINY_CONFIG, "params": params})
    assert result["correct"] is True and result["failed"] == 0
    assert set(result) == {"correct", "attempted", "failed", "metrics",
                           "device"}
