"""The ``afmoe`` family through the harness at toy sizes on the CPU: the
cell's own driver (``kinds/serve.py``), comparison and limit, as
``test_correct.py`` drives GPT-2 — `correct` comes out true for the
sound program and false under a control and under an altered token; the
family's weights bind to the program's model; its counts are the
configuration's arithmetic."""

import json
import os

import jax
import jax.numpy as jnp
import pytest

from benchmarks import run as bench_run
from benchmarks.families import afmoe as family
from benchmarks.tests.conftest import ROOT

CELL = "trinity_large_ep8.serve_mixed_backlog"

#: A share of a toy afmoe: rank 1 of 4 holds experts 4-7 of 16; a window
#: of 8 positions, so every context wraps the rings several times.
TINY_AFMOE = dict(
    vocab_size=2048, hidden_size=64, num_attention_heads=4,
    num_key_value_heads=2, head_dim=16, intermediate_size=128,
    moe_intermediate_size=32, num_hidden_layers=4, num_dense_layers=1,
    layer_types=["sliding_attention", "sliding_attention",
                 "full_attention", "sliding_attention"],
    num_experts=4, sliding_window=8, max_position_embeddings=256,
    published={"num_experts": 16}, deployment={"rank": 1},
    # 64 features give the router's scores a tenth of the spread 3072
    # give them: weights ten times as large put the near-ties back to a
    # share of the tokens (the reference judges none at a near-tie).
    assumed={"initializer_range": 0.2})
TINY_MIXED = dict(
    slots=8, cache_len=64, length_pairs=32,
    prompt_tokens=dict(median=16, sigma=0.8, min=4, max=40),
    output_tokens=dict(median=10, sigma=0.6, min=3, max=20),
    in_flight_at_open=8, backlog_requests_per_s=400,
    boundaries_per_s=400, trace_seconds=1,
    prefill_batches={"8": 2, "16": 2, "32": 2})


def _config() -> dict:
    path = os.path.join(ROOT, "benchmarks/configs/trinity_large_ep8.json")
    with open(path) as f:
        return json.load(f)


def _run(seed, **kw):
    return bench_run.run_cell(
        CELL, seed, 1.0, False, require_tpu=False,
        overrides={"config": TINY_AFMOE, "params": TINY_MIXED}, **kw)


def test_the_sound_path_is_correct():
    result = _run(2 ** 31 + 9)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    assert "serve_tokens_per_s" in result["metrics"]


def test_fp8_in_the_programs_place_is_not_correct():
    """The control: the reference in fp8 linear layers, experts and head
    included, judged by the cell's own comparison and limit."""
    result = _run(77, controls=("fp8",))
    assert result["correct"] is True
    (what, low, limit), = result["controls"]["fp8"]
    (_, sound, _), = result["compared"]
    assert what == "served_logit_gap_widest"
    assert low > limit > sound, (low, limit, sound)


def test_an_altered_token_is_not_correct(monkeypatch):
    from distributedtensorflowexample_tpu.serving.engine import DecodeEngine
    real_decode = DecodeEngine.decode

    def decode(self, busy=None):
        out = (real_decode(self, busy=busy) + 7) % self.vocab
        live = list(range(self.slots)) if busy is None else list(busy)
        self.last_tokens[live] = out[live]
        return out

    monkeypatch.setattr(DecodeEngine, "decode", decode)
    result = _run(78)
    assert result["correct"] is False and result["attempted"] > 0


def test_the_familys_tree_is_the_models():
    """``init_params`` lays its leaves out under the program's parameter
    names, shapes and types: the tree binds to DecodeEngine unchanged."""
    cfg = {**_config(), **TINY_AFMOE}
    model = family.build_model(cfg, dtype=jnp.bfloat16)
    want = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    got = jax.eval_shape(family.init_fn(cfg), jnp.uint32(1))
    flat = lambda t: {jax.tree_util.keystr(k): (v.shape, str(v.dtype))
                      for k, v in jax.tree_util.tree_leaves_with_path(t)}
    assert flat(got) == flat(want)
    assert flat(got) == {
        k: (s, flat(got)[k][1]) for k, s in {
            jax.tree_util.keystr(p): v for p, v in
            jax.tree_util.tree_flatten_with_path(
                family.param_shapes(cfg),
                is_leaf=lambda x: isinstance(x, tuple))[0]}.items()}


def test_the_counts_are_the_configurations_arithmetic():
    """ISSUE 28's bytes, to the digit it gives them."""
    cfg = _config()
    outside, one, layers = family._counts(cfg)
    assert one == 28_311_552 and layers == 4
    assert round(2 * outside / 1e9, 2) == 1.24
    assert round(family.weight_bytes(cfg) / 1e9, 2) == 8.64
    # One token of cache in one layer: K and V, 8 heads of 128, bf16.
    step = family.decode_step_bytes(cfg, 1, window_rows=0, experts_touched=0)
    assert step - family.decode_step_bytes(
        cfg, 0, window_rows=0, experts_touched=0) == 4096
    # Every held expert read is all the weights but the embedding.
    everything = family.decode_step_bytes(cfg, 0, window_rows=0)
    assert everything == family.weight_bytes(cfg) - 2 * 25024 * 3072
    # Decode FLOPs: two per parameter a token multiplies by.
    flops = family.decode_step_flops(cfg, 0, 1, pairs_held=2.0)
    assert flops == 2 * (outside + 2 * one)


@pytest.mark.parametrize("key", ["num_hidden_layers", "num_dense_layers",
                                 "layer_types", "num_experts", "vocab_size"])
def test_every_changed_key_is_stated(key):
    """BENCHMARK.json's ``reduced`` and the file's ``changed_from_source``
    name the same five keys, each with its published value beside it."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"]
                     if c["name"] == "trinity_large_ep8")
    cfg = _config()
    assert key in entry["reduced"] and key in cfg["changed_from_source"]
    assert key in cfg["published"]
    assert len(entry["reduced"]) == len(cfg["changed_from_source"]) == 5
