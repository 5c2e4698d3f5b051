"""The ``bailing_hybrid`` family through the harness at toy sizes on the
CPU, as ``test_qwen3_next.py`` drives Qwen3-Next's: the cell's own
driver, comparison and limit (`correct` true for the sound program,
false under a control); the family's weights bind to the program's
model; its counts are ISSUE 37's arithmetic; the schema takes the new
files; every new reader answers None on a run without its counters; the
reference's controls order as the precisions do; the near-tie rule
counts a group's edge."""

import importlib.util
import json
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import run as bench_run
from benchmarks.families import bailing_hybrid as family
from benchmarks.harness import schema
from benchmarks.reference import bailing_hybrid as ref
from benchmarks.tests.conftest import ROOT

CELL = "ling3_flash_ep8.serve_longform_backlog"
NEW_READERS = [
    "decode_step_ms.longform", "prefill_share_pct.longform",
    "prefill_pad_pct.longform", "device_idle_pct.longform",
    "slot_occupancy_pct.longform", "moe_held_pairs_pct.longform",
    "moe_experts_touched_pct.longform", "cache_latent_pct.longform",
    "decode_state_bytes_pct.longform", "decode_roofline_pct.longform",
    "kda_step_roofline_pct.longform", "latent_decode_roofline_pct.longform"]
REDUCED = ["num_hidden_layers", "first_k_dense_replace", "num_experts",
           "vocab_size"]

#: A share of a toy bailing_hybrid: rank 1 of 8 holds experts 4-7 of 32,
#: which are routing group 1 of 8; the cut's own pattern (a dense layer,
#: then one whole period: five KDA layers and an MLA layer).
TINY_LING = dict(
    vocab_size=2048, hidden_size=64, num_hidden_layers=7,
    num_attention_heads=4, head_dim=16, kv_lora_rank=32,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    intermediate_size=96, moe_intermediate_size=32,
    moe_shared_expert_intermediate_size=32, num_experts=4,
    num_experts_per_tok=3, max_position_embeddings=256,
    published={"num_experts": 32}, deployment={"rank": 1},
    # 64 features give the router's logits a sixth of the spread 2560
    # give them: larger weights put the near-ties back to a share of the
    # tokens (the reference judges none at a near-tie).
    assumed={"initializer_range": 0.1})
TINY_LONGFORM = dict(
    slots=8, cache_len=128, length_pairs=32,
    prompt_tokens=dict(median=16, sigma=0.6, min=4, max=60),
    output_tokens=dict(median=20, sigma=0.4, min=8, max=40),
    in_flight_at_open=8, backlog_requests_per_s=400,
    boundaries_per_s=400, trace_seconds=1,
    prefill_batches={"8": 2, "16": 2, "32": 2, "64": 1},
    # At 64 features bfloat16 moves a logit by more than it does at 2560
    # (readings here: sound 0.33-0.64 by which requests a one-second
    # window finishes, fp8 1.35-1.69); the cell's own limit is set from
    # readings at its own size (PERF.md section 2).
    limits={"served_logit_gap_widest": 0.9})


def _config() -> dict:
    path = os.path.join(ROOT, "benchmarks/configs/ling3_flash_ep8.json")
    with open(path) as f:
        return json.load(f)


def _run(seed, **kw):
    return bench_run.run_cell(
        CELL, seed, 1.0, False, require_tpu=False,
        overrides={"config": TINY_LING, "params": TINY_LONGFORM}, **kw)


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
    assert config["family"] == config["reference"] == "bailing_hybrid"
    assert (traffic["slots"], traffic["cache_len"]) == (256, 8192)
    per = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW_READERS:
        assert per[name]["workloads"] == [CELL], name
        assert per[name]["moves"] == "serve_tokens_per_s"
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert e2e["serve_tokens_per_s"]["workloads"][-1] == CELL


def test_the_familys_tree_is_the_models():
    """``init_params`` lays its leaves out under the program's parameter
    names, shapes and types: the tree binds to DecodeEngine unchanged."""
    cfg = {**_config(), **TINY_LING}
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
    """ISSUE 37's parameters and bytes, to the digit it gives them."""
    cfg = _config()
    outside, one = family._counts(cfg)
    assert one == 5_898_240
    assert family.kinds(cfg) == (1, 6, 6)
    shapes = family.param_shapes(cfg)
    size = lambda block, names: sum(
        np.prod(shapes[block][n]) for n in names)
    kda = size("block0", ("w_qkvu", "w_f", "w_b", "wo"))
    mla = size("block5", ("wq", "w_kva", "w_kvb", "w_gate", "wo"))
    assert (round(kda / 1e6, 1), round(mla / 1e6, 1)) == (63.0, 32.0)
    assert round(size("block0", ("ffn_gate", "ffn_up", "ffn_down")) / 1e6,
                 1) == 47.2
    assert round(family.param_count(cfg) / 1e6) == 2866
    assert round(family.weight_bytes(cfg) / 1e9, 2) == 5.73
    # 256 slots: 3.22 GB of recurrent state and 0.11 of convolution's.
    assert round(256 * family.state_bytes_per_slot(cfg) / 1e9, 2) == 3.33
    assert family.latent_row_bytes(cfg) == 1152
    # The step ISSUE 37 reckons: ~2 k live rows a slot in the latent
    # layer, 62.8 of 64 experts touched in each of six layers.
    step = family.decode_step_bytes(cfg, 256 * 2048, slots=256,
                                    experts_touched=62.8 * 6)
    parts = (2 * outside, 2 * 62.8 * 6 * one,
             2 * 256 * family.state_bytes_per_slot(cfg),
             256 * 2048 * 1152)
    assert abs(sum(parts) - step) < 1
    assert [round(p / 1e9, 2) for p in parts] == [1.1, 4.44, 6.67, 0.6]
    assert round(step / 1e9, 1) == 12.8
    # Decode FLOPs: two per parameter a token multiplies by, and the
    # recurrence's three multiply-adds a state element.
    flops = family.decode_step_flops(cfg, 0, 1, pairs_held=2.0)
    assert flops == 2 * (outside + 2 * one + 3 * 6 * 32 * 128 * 128)
    # The recurrence kernel, one call a layer: 256 slots' states in and
    # out (1.07 GB) and 4.6 kB of vectors a head.
    call = family.kda_step_bytes(cfg, 256)
    assert call == 256 * 32 * (2 * 128 * 128 * 4 + 4 * (8 * 128 + 128))
    assert family.kda_step_flops(cfg, 256) == 8 * 256 * 32 * 128 * 128
    # The latent kernel: a row is every head's key and value, read once.
    assert family.latent_decode_bytes(cfg, 1000) == 1000 * 1152
    assert family.latent_decode_flops(cfg, 1000) == \
        1000 * 2 * 32 * (512 + 64 + 512)


@pytest.mark.parametrize("key", REDUCED)
def test_every_changed_key_is_stated(key):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"]
                     if c["name"] == "ling3_flash_ep8")
    cfg = _config()
    assert key in entry["reduced"] and key in cfg["changed_from_source"]
    assert key in cfg["published"]
    assert len(entry["reduced"]) == len(cfg["changed_from_source"]) == 4


def test_every_other_number_is_the_catalogs():
    """The file holds every key of the catalog's row as the source has
    it, the four reduced ones apart; no width differs."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Ling-3.0-flash")
    cfg = _config()
    assert cfg["source"] == row["source_url"]
    differs = [k for k, v in row["config"].items() if cfg.get(k) != v]
    assert sorted(differs) == sorted(REDUCED)
    assert cfg["deployment"]["chips_per_layer"] == 8
    assert cfg["deployment"]["rank"] == 0


@pytest.mark.parametrize("name", NEW_READERS)
def test_a_reader_finds_nothing_on_a_run_without_its_counters(name,
                                                              monkeypatch):
    """A program without the counters, a run that was not traced, a run
    that served nothing: every new reader answers None and does not
    raise."""
    from benchmarks.harness import latent_counts, program_tape
    monkeypatch.setattr(program_tape, "registry_value", lambda *a: None)
    monkeypatch.setattr(latent_counts, "registry_value", lambda *a: None)
    path = os.path.join(ROOT, "benchmarks", "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("m_" + name[:-9], path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    run = types.SimpleNamespace(
        trace=None, trace_window=None, samples={}, facts={}, peaks=None,
        config=_config(), family=family, spans=None)
    assert mod.read(run) is None


def test_the_controls_order_as_the_precisions_do():
    """At a small size the reference's own logits drift from float32 by
    more the lower the precision of its linear layers."""
    cfg = {**_config(), **TINY_LING}
    params = family.init_params(cfg, 5)
    toks = np.random.default_rng(1).integers(0, 2048, (1, 48))
    exact = ref.forward(params, toks, cfg)
    drift = {p: float(jnp.max(jnp.abs(ref.forward(params, toks, cfg, p)
                                      - exact)))
             for p in ("bf16", "int8", "fp8")}
    assert 0 < drift["bf16"] < min(drift["int8"], drift["fp8"]), drift


def test_the_near_tie_rule_counts_a_groups_edge():
    """Scores made by hand: 8 experts in 4 groups of 2, 2 groups kept,
    top 2; this share holds group 0.  A held expert far from the
    experts' boundary is still at a near-tie where the last group kept
    and the first left out are close."""
    cfg = {"num_experts_per_tok": 2, "n_group": 4, "topk_group": 2,
           "num_experts": 2, "deployment": {"rank": 0}}
    logit = lambda s: np.log(s / (1 - s))
    scores = np.array([
        # groups' scores 1.3, 0.9, 0.5, 0.3: edge 0.9 - 0.5 = 0.4;
        # held 0.8 selected against 0.5: 0.3; held 0.5 unselected: 0.1
        [0.8, 0.5, 0.6, 0.3, 0.3, 0.2, 0.2, 0.1],
        # groups 1.3, 0.9, 0.88: the groups' edge 0.02 decides
        [0.9, 0.4, 0.7, 0.2, 0.68, 0.2, 0.1, 0.1],
        # group 0 left out (0.3 against 0.9 and 1.0): the held experts
        # are no candidates; only the groups' edge counts, 0.9 - 0.5
        [0.2, 0.1, 0.5, 0.4, 0.6, 0.4, 0.3, 0.2]], np.float32)
    p = {"router": jnp.asarray(np.eye(8, dtype=np.float32)),
         "router_bias": jnp.zeros((8,), jnp.float32)}
    got = np.asarray(ref.held_margin(jnp.asarray(logit(scores)), p, cfg))
    assert np.allclose(got, [0.1, 0.02, 0.4], atol=1e-5), got
    sel, w = ref.route(jnp.asarray(logit(scores)), p,
                       {**cfg, "norm_topk_prob": True,
                        "routed_scaling_factor": 2.5})
    assert [sorted(r) for r in np.asarray(sel).tolist()] == \
        [[0, 2], [0, 2], [2, 4]]
    assert np.allclose(np.asarray(w).sum(-1), 2.5)
