"""The ``qwen3_next`` family through the harness at toy sizes on the
CPU, as ``test_afmoe.py`` drives Trinity's: the cell's own driver,
comparison and limit (`correct` true for the sound program, false under
a control); the family's weights bind to the program's model; its counts
are ISSUE 34's arithmetic; the schema takes the new files; every new
reader answers None on a run without its counters; the reference's
controls order as the precisions do."""

import importlib.util
import json
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import run as bench_run
from benchmarks.families import qwen3_next as family
from benchmarks.harness import schema
from benchmarks.reference import qwen3_next as ref
from benchmarks.tests.conftest import ROOT

CELL = "qwen3_next_ep8.serve_chat_backlog"
NEW_READERS = [
    "decode_step_ms.chat", "prefill_share_pct.chat", "prefill_pad_pct.chat",
    "copy_time_pct.chat", "device_idle_pct.chat", "slot_occupancy_pct.chat",
    "moe_held_pairs_pct.chat", "moe_experts_touched_pct.chat",
    "batcher_self_ms.chat", "cache_state_pct.chat",
    "decode_state_bytes_pct.chat", "decode_roofline_pct.chat",
    "gated_delta_step_roofline_pct.chat"]

#: A share of a toy qwen3_next: rank 1 of 4 holds experts 4-7 of 16; two
#: whole periods of (linear, linear, linear, full).
TINY_QWEN = dict(
    vocab_size=2048, hidden_size=64, num_hidden_layers=8,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    linear_num_key_heads=2, linear_num_value_heads=4,
    linear_key_head_dim=16, linear_value_head_dim=16,
    moe_intermediate_size=32, shared_expert_intermediate_size=32,
    num_experts=4, num_experts_per_tok=3, max_position_embeddings=256,
    published={"num_experts": 16}, deployment={"rank": 1},
    # 64 features give the router's logits a sixth of the spread 2048
    # give them: larger weights put the near-ties back to a share of the
    # tokens (the reference judges none at a near-tie).
    assumed={"initializer_range": 0.1})
TINY_CHAT = dict(
    slots=8, cache_len=128, length_pairs=32,
    prompt_tokens=dict(median=24, sigma=0.8, min=4, max=90),
    output_tokens=dict(median=10, sigma=0.6, min=3, max=20),
    in_flight_at_open=8, backlog_requests_per_s=400,
    boundaries_per_s=400, trace_seconds=1,
    prefill_batches={"8": 2, "16": 2, "32": 2, "64": 1, "128": 1},
    # At 64 features bfloat16 moves a logit by more than it does at 2048
    # (readings here: sound 0.05-0.23, fp8 1.8-2.4); the cell's own limit
    # is set from readings at its own size (PERF.md section 2).
    limits={"served_logit_gap_widest": 0.6})


def _config() -> dict:
    path = os.path.join(ROOT, "benchmarks/configs/qwen3_next_ep8.json")
    with open(path) as f:
        return json.load(f)


def _run(seed, **kw):
    return bench_run.run_cell(
        CELL, seed, 1.0, False, require_tpu=False,
        overrides={"config": TINY_QWEN, "params": TINY_CHAT}, **kw)


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


def test_the_schema_takes_the_new_files():
    bench = schema.load_and_check(ROOT)
    cell, config, traffic = schema.cell_files(ROOT, bench, CELL)
    assert config["family"] == config["reference"] == "qwen3_next"
    assert (traffic["slots"], traffic["cache_len"]) == (256, 4096)
    per = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW_READERS:
        assert per[name]["workloads"] == [CELL], name
        assert per[name]["moves"] == "serve_tokens_per_s"
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert e2e["serve_tokens_per_s"]["workloads"][-1] == CELL


def test_the_familys_tree_is_the_models():
    """``init_params`` lays its leaves out under the program's parameter
    names, shapes and types: the tree binds to DecodeEngine unchanged."""
    cfg = {**_config(), **TINY_QWEN}
    model = family.build_model(cfg, dtype=jnp.bfloat16)
    want = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    got = jax.eval_shape(family.init_fn(cfg), jnp.uint32(1))
    flat = lambda t: {jax.tree_util.keystr(k): (v.shape, str(v.dtype))
                      for k, v in jax.tree_util.tree_leaves_with_path(t)}
    assert flat(got) == flat(want)
    shapes = {jax.tree_util.keystr(p): v for p, v in
              jax.tree_util.tree_flatten_with_path(
                  family.param_shapes(cfg),
                  is_leaf=lambda x: isinstance(x, tuple))[0]}
    assert {k: v[0] for k, v in flat(got).items()} == shapes


def test_the_counts_are_the_configurations_arithmetic():
    """ISSUE 34's parameters and bytes, to the digit it gives them."""
    cfg = _config()
    outside, one = family._counts(cfg)
    assert one == 3_145_728
    assert round(family.param_count(cfg) / 1e9, 2) == 1.98
    assert round(family.weight_bytes(cfg) / 1e9, 2) == 3.96
    # 256 slots: 3.22 GB of recurrent state and 0.08 of convolution's.
    assert round(256 * family.state_bytes_per_slot(cfg) / 1e9, 2) == 3.30
    assert family.kv_row_bytes(cfg) == 2048
    # The step ISSUE 34 reckons: ~1 k live rows a slot in each of the two
    # attention layers, every held expert touched.
    step = family.decode_step_bytes(cfg, 256 * 1024, slots=256)
    assert round(step / 1e9, 1) == 11.5
    parts = (2 * outside, 2 * 64 * 8 * one,
             2 * 256 * family.state_bytes_per_slot(cfg),
             2 * 256 * 1024 * 2048)
    assert sum(parts) == step
    assert [round(p / 1e9, 2) for p in parts] == [0.66, 3.22, 6.59, 1.07]
    # Decode FLOPs: two per parameter a token multiplies by, and the
    # recurrence's three multiply-adds a state element.
    flops = family.decode_step_flops(cfg, 0, 1, pairs_held=2.0)
    assert flops == 2 * (outside + 2 * one + 3 * 6 * 32 * 128 * 128)
    # The recurrence kernel, one call a layer: 256 slots' states in and
    # out (1.07 GB) and 4.6 kB of vectors a head.
    call = family.delta_step_bytes(cfg, 256)
    assert call == 256 * 32 * (2 * 128 * 128 * 4 + 4 * (8 * 128 + 128))
    assert family.delta_step_flops(cfg, 256) == 6 * 256 * 32 * 128 * 128


@pytest.mark.parametrize("key", ["num_hidden_layers", "num_experts",
                                 "vocab_size"])
def test_every_changed_key_is_stated(key):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"]
                     if c["name"] == "qwen3_next_ep8")
    cfg = _config()
    assert key in entry["reduced"] and key in cfg["changed_from_source"]
    assert key in cfg["published"]
    assert len(entry["reduced"]) == len(cfg["changed_from_source"]) == 3


@pytest.mark.parametrize("name", NEW_READERS)
def test_a_reader_finds_nothing_on_a_run_without_its_counters(name,
                                                              monkeypatch):
    """A program from before this PR has no state counters, a run that
    was not traced no trace, a run that served nothing no samples: every
    new reader answers None and does not raise."""
    from benchmarks.harness import program_tape
    monkeypatch.setattr(program_tape, "registry_value", lambda *a: None)
    monkeypatch.setattr(program_tape, "step_self_ms", lambda run: None)
    path = os.path.join(ROOT, "benchmarks", "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("m_" + name[:-5], path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    run = types.SimpleNamespace(
        trace=None, trace_window=None, samples={}, facts={}, peaks=None,
        config=_config(), family=family, spans=None)
    assert mod.read(run) is None


def test_the_controls_order_as_the_precisions_do():
    """At a small size the reference's own logits drift from float32 by
    more the lower the precision of its linear layers."""
    cfg = {**_config(), **TINY_QWEN}
    params = family.init_params(cfg, 5)
    toks = np.random.default_rng(1).integers(0, 2048, (1, 48))
    exact = ref.forward(params, toks, cfg)
    drift = {p: float(jnp.max(jnp.abs(ref.forward(params, toks, cfg, p)
                                      - exact)))
             for p in ("bf16", "int8", "fp8")}
    assert 0 < drift["bf16"] < min(drift["int8"], drift["fp8"]), drift
