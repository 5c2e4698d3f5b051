"""The ``kimi_k2`` family through the harness at toy sizes on the CPU, as
``test_bailing_hybrid.py`` drives Ling's: the cell's own driver,
comparison and limit (`correct` true for the sound program, false under a
control); the family's weights bind to the program's model and
``SOURCE_NAMES`` names every one of them; its counts are ISSUE 43's
arithmetic; the schema takes the new files; every new reader reads a
recorded run and answers None on a run without its counters; the
reference's controls order as the precisions do."""

import importlib.util
import json
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import run as bench_run
from benchmarks.families import kimi_k2 as family
from benchmarks.harness import schema
from benchmarks.kinds.serve import Boundary
from benchmarks.reference import kimi_k2 as ref
from benchmarks.tests.conftest import ROOT

CELL = "kimi_k2_5_ep32.serve_reasoning_backlog"
NEW_READERS = [
    "prefill_pad_pct.reason", "device_idle_pct.reason",
    "slot_occupancy_pct.reason", "moe_held_pairs_pct.reason",
    "moe_experts_touched_pct.reason", "decode_latent_bytes_pct.reason",
    "decode_roofline_pct.reason", "latent_decode_roofline_pct.reason",
    "decode_dispatch_ms.reason"]
SHARED = ["decode_host_ms", "decode_sync_latency_ms", "decode_fetch_ms",
          "decode_account_ms", "batcher_retire_ms", "boundary_longest_ms",
          "host_gc_share_pct", "decode_ahead_pct"]
REDUCED = ["num_hidden_layers", "n_routed_experts", "vocab_size"]

#: A share of a toy kimi_k2: rank 1 of 8 holds experts 4-7 of 32; the
#: cut's own pattern (a dense layer, then four expert layers).
TINY_KIMI = dict(
    vocab_size=2048, hidden_size=64, num_hidden_layers=5,
    num_attention_heads=4, q_lora_rank=24, kv_lora_rank=32,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    intermediate_size=96, moe_intermediate_size=32, n_routed_experts=4,
    num_experts_per_tok=3, max_position_embeddings=256,
    rope_scaling=dict(type="yarn", factor=8,
                      original_max_position_embeddings=32, beta_fast=32,
                      beta_slow=1, mscale=1, mscale_all_dim=1),
    published={"n_routed_experts": 32}, deployment={"rank": 1},
    # 64 features give the router's logits a tenth of the spread 7168
    # give them: larger weights put the near-ties back to a share of the
    # tokens (the reference judges none at a near-tie).
    assumed={"initializer_range": 0.1})
TINY_REASON = dict(
    slots=8, cache_len=128, length_pairs=8,
    prompt_tokens=dict(median=24, sigma=0.15, min=16, max=40),
    output_tokens=dict(median=24, sigma=0.15, min=16, max=40),
    in_flight_at_open=8, backlog_requests_per_s=400,
    boundaries_per_s=400, trace_seconds=1,
    prefill_batches={"32": 2, "64": 1},
    # At 64 features bfloat16 moves a logit by more than it does at 7168
    # (readings here: sound 0.01, int8 0.11, fp8 0.94); the cell's own
    # limit is set from readings at its own size (PERF.md section 2).
    limits={"served_logit_gap_widest": 0.3})


def _config() -> dict:
    path = os.path.join(ROOT, "benchmarks/configs/kimi_k2_5_ep32.json")
    with open(path) as f:
        return json.load(f)


def _run(seed, **kw):
    return bench_run.run_cell(
        CELL, seed, 1.0, False, require_tpu=False,
        overrides={"config": TINY_KIMI, "params": TINY_REASON}, **kw)


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
    assert config["family"] == config["reference"] == "kimi_k2"
    assert (traffic["slots"], traffic["cache_len"], traffic["length_pairs"],
            cell["chips"]) == (80, 10240, 80, 1)
    assert sorted(map(int, traffic["prefill_batches"])) == [
        3072, 3584, 4096, 4608, 5120]
    per = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW_READERS:
        assert per[name]["workloads"] == [CELL], name
        assert per[name]["moves"] == "serve_tokens_per_s"
    for name in SHARED:     # (a later cell is appended after this one)
        assert CELL in per[name]["workloads"], name
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert CELL in e2e["serve_tokens_per_s"]["workloads"]
    assert sum(c["name"] == "kimi_k2_5_ep32" for c in bench["configs"]) == 1


def test_the_familys_tree_is_the_models_and_every_leaf_has_a_source():
    """``init_params`` lays its leaves out under the program's parameter
    names, shapes and types: the tree binds to DecodeEngine unchanged;
    ``SOURCE_NAMES`` names the published tensor behind each, no more and
    no fewer."""
    cfg = {**_config(), **TINY_KIMI}
    model = family.build_model(cfg, dtype=jnp.bfloat16)
    want = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    got = jax.eval_shape(family.init_fn(cfg), jnp.uint32(1))
    flat = lambda t: {jax.tree_util.keystr(k): (v.shape, str(v.dtype))
                      for k, v in jax.tree_util.tree_leaves_with_path(t)}
    assert flat(got) == flat(want)
    leaves = jax.tree_util.tree_flatten_with_path(
        family.param_shapes(cfg), is_leaf=lambda x: isinstance(x, tuple))[0]
    assert {jax.tree_util.keystr(p): v for p, v in leaves} == {
        k: v[0] for k, v in flat(got).items()}
    assert {p[-1].key for p, _ in leaves} == set(family.SOURCE_NAMES)
    plain = {**cfg, "q_lora_rank": None}    # the uncompressed query's tree
    assert "wq" in family.param_shapes(plain)["block0"]
    assert flat(jax.eval_shape(family.init_fn(plain), jnp.uint32(1))) == flat(
        jax.eval_shape(lambda: family.build_model(plain).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]))


@pytest.mark.parametrize("stated", [None, 0.003])
def test_the_seeded_weights_are_what_the_file_states(stated):
    """``stated`` is ``assumed.router_bias_range``: the cell's own file
    states 0.003 (a seed's bias must not set how many held experts a step
    touches); a configuration that states none is seeded at 0.01."""
    cfg = {**_config(), **TINY_KIMI}
    if stated is not None:
        assert _config()["assumed"]["router_bias_range"] == stated
        cfg["assumed"] = {**cfg["assumed"], "router_bias_range": stated}
    p = family.init_params(cfg, 5)
    std = lambda x: float(jnp.std(x.astype(jnp.float32)))
    assert abs(std(p["block1"]["experts_gate"]) - 0.1) < 0.01
    bias = stated or 0.01
    assert abs(std(p["block1"]["router_bias"]) - bias) < 0.5 * bias
    assert p["block1"]["router_bias"].dtype == jnp.float32
    scale = p["block0"]["norm_q"].astype(jnp.float32)
    assert abs(float(jnp.mean(scale)) - 1.0) < 0.1 and 0.03 < std(scale) < 0.2
    again = family.init_params(cfg, 5)
    assert all(np.array_equal(np.asarray(a), np.asarray(b)) for a, b in zip(
        jax.tree.leaves(p), jax.tree.leaves(again)))
    other = family.init_params(cfg, 6)
    assert not np.array_equal(np.asarray(p["head"]), np.asarray(other["head"]))


def test_the_counts_are_the_configurations_arithmetic():
    """ISSUE 43's parameters and bytes, to the digit it gives them."""
    cfg = _config()
    outside, one = family._counts(cfg)
    assert one == 44_040_192
    assert family.kinds(cfg) == (5, 4)
    shapes = family.param_shapes(cfg)
    size = lambda block, names: sum(
        np.prod(shapes[block][n]) for n in names)
    mla = size("block0", ("wq_a", "wq_b", "w_kva", "w_uk", "w_uv", "wo"))
    assert round(mla / 1e6, 1) == 101.1
    assert [round(np.prod(shapes["block0"][n]) / 1e6, 2) for n in (
        "wq_a", "wq_b", "w_kva", "wo")] == [11.01, 18.87, 4.13, 58.72]
    assert round((np.prod(shapes["block0"]["w_uk"])
                  + np.prod(shapes["block0"]["w_uv"])) / 1e6, 2) == 8.39
    assert round(size("block0", ("ffn_gate", "ffn_up", "ffn_down")) / 1e6,
                 1) == 396.4
    assert round(size("block1", ("shared_gate", "shared_up", "shared_down",
                                 "router")) / 1e6, 1) == 46.8
    assert round(family.param_count(cfg) / 1e6) == 3497
    assert round(family.weight_bytes(cfg) / 1e9, 2) == 6.99
    assert family.latent_row_bytes(cfg) == 1152
    # The step ISSUE 43 reckons: 80 slots of ~4,900 live rows, 39.5 of
    # 48 held experts touched.
    rows = 80 * 4900
    step = family.decode_step_bytes(cfg, rows, experts_touched=39.5)
    parts = (2 * outside, 2 * 39.5 * one, rows * 5 * 1152)
    assert abs(sum(parts) - step) < 1
    assert [round(p / 1e9, 2) for p in parts] == [2.47, 3.48, 2.26]
    # Decode FLOPs: two per parameter a token multiplies by.
    assert family.decode_step_flops(cfg, 0, 1, pairs_held=2.0) == \
        2 * (outside + 2 * one)
    # The latent kernel: a row is every head's key and value, read once;
    # 139 kFLOP a row a layer at 64 heads.
    assert family.latent_decode_bytes(cfg, 1000) == 1000 * 1152
    assert family.latent_decode_flops(cfg, 1000) == \
        1000 * 2 * 64 * (512 + 64 + 512) == 1000 * 139264
    assert family.decode_step_flops(cfg, 1000, 80) - \
        family.decode_step_flops(cfg, 0, 80) == 5 * 1000 * 139264


@pytest.mark.parametrize("key", REDUCED)
def test_every_changed_key_is_stated(key):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"]
                     if c["name"] == "kimi_k2_5_ep32")
    cfg = _config()
    assert key in entry["reduced"] and key in cfg["changed_from_source"]
    assert key in cfg["published"]
    assert len(entry["reduced"]) == len(cfg["changed_from_source"]) == 3
    assert "vision_tower" in cfg["left_out"]


def test_every_other_number_is_the_catalogs():
    """The file holds every key of the catalog's row as the source has
    it, the three reduced ones apart; no width differs."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Kimi-K2.5")
    cfg = _config()
    assert cfg["source"] == row["source_url"]
    differs = [k for k, v in row["config"].items() if cfg.get(k) != v]
    assert sorted(differs) == sorted(REDUCED)
    assert cfg["deployment"]["chips_per_layer"] == 32
    assert cfg["deployment"]["rank"] == 0
    assert {k: cfg["published"][k] for k in REDUCED} == {
        k: row["config"][k] for k in REDUCED}


def _reader(name):
    path = os.path.join(ROOT, "benchmarks", "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("m_" + name[:-7], path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@pytest.mark.parametrize("name", NEW_READERS)
def test_a_reader_finds_nothing_on_a_run_without_its_counters(name,
                                                              monkeypatch):
    """A program without the counters, a run that was not traced, a run
    that served nothing: every new reader answers None and does not
    raise."""
    from benchmarks.harness import mla_counts, program_tape
    monkeypatch.setattr(program_tape, "registry_value", lambda *a: None)
    monkeypatch.setattr(mla_counts, "registry_value", lambda *a: None)
    monkeypatch.setattr(program_tape, "program_tape", lambda: ([], 0))
    run = types.SimpleNamespace(
        trace=None, trace_window=None, samples={},
        facts={"window": (0.0, 1.0)}, peaks=None, config=_config(),
        family=family, spans=types.SimpleNamespace(tape={}))
    assert _reader(name)(run) is None


#: A recorded run: 1,000 decode steps at 80 slots, 12 experts held in
#: each of 4 layers, 40 of 48 touched a step, ~4,500 rows a slot a layer
#: read on average and 5,000 in the traced tail, whose decode program
#: took 20 ms a step and whose latent kernel 1.5 ms a call.
RECORDED = {
    "moe_expert_slots_total": 1000 * 48,
    "moe_experts_touched_total": 1000 * 40,
    'serve_cache_rows_read_total{kind="latent"}': 1000 * 5 * 80 * 4500,
}
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _recorded_run():
    event = lambda name, t0, dur: types.SimpleNamespace(
        name=name, start=t0, end=t0 + dur)
    trace = types.SimpleNamespace(
        device_modules={0: [event("jit__decode_step_fn", i * 0.025, 0.020)
                            for i in range(4)]},
        device_ops={0: [event("latent_decode_attention", i * 0.004, 0.0015)
                        for i in range(20)]})
    tail = [Boundary(0.0, 0, 80, 80 * 5000)] * 4
    return types.SimpleNamespace(
        trace=trace, trace_window=(0.0, 0.1), samples={}, spans=None,
        facts={"slots": 80, "tail_boundaries": tail}, peaks=PEAKS,
        config=_config(), family=family)


@pytest.fixture()
def recorded(monkeypatch):
    from benchmarks.harness import mla_counts
    monkeypatch.setattr(mla_counts, "registry_value",
                        lambda kind, series: RECORDED.get(series))
    return _recorded_run()


def test_the_step_readers_read_a_recorded_run(recorded):
    """The three readers over ``harness/mla_counts.py`` on a recorded
    run, against the arithmetic done by hand: the tail's rows (5,000 a
    slot) and not the run's mean are what the device times are held
    to."""
    from benchmarks.harness import mla_counts
    cfg = _config()
    got = mla_counts.decode_step_counts(recorded)
    assert (got["steps"], got["rows"], got["touched"]) == (
        1000, 80 * 5000, 40)
    outside, one = family._counts(cfg)
    rows_b = 80 * 5000 * 5 * 1152
    least_b = 2 * outside + 2 * 40 * one + rows_b
    assert got["row_bytes"] == rows_b and got["least_bytes"] == least_b
    assert abs(_reader("decode_latent_bytes_pct.reason")(recorded)
               - 100 * rows_b / least_b) < 1e-9
    least_s = max(least_b / 819e9, got["least_flops"] / 197e12)
    assert abs(_reader("decode_roofline_pct.reason")(recorded)
               - 100 * least_s / 0.020) < 1e-6
    kernel_s = max(80 * 5000 * 1152 / 819e9,
                   80 * 5000 * 139264 / 197e12)
    share = _reader("latent_decode_roofline_pct.reason")(recorded)
    assert abs(share - 100 * kernel_s / 0.0015) < 1e-6
    assert 0 < share < 100
    # without the tail's boundaries: the whole run's mean rows
    del recorded.facts["tail_boundaries"]
    assert mla_counts.decode_step_counts(recorded)["rows"] == 80 * 4500


def test_a_traced_run_without_the_kernel_reads_no_kernel_share(recorded):
    """A program whose token step takes another path (the parent's, the
    CPU's einsum chain) has no event of the kernel's name: None."""
    recorded.trace.device_ops = {0: []}
    assert _reader("latent_decode_roofline_pct.reason")(recorded) is None
    assert _reader("decode_roofline_pct.reason")(recorded) is not None


def test_the_controls_order_as_the_precisions_do():
    """At a small size the reference's own logits drift from float32 by
    more the lower the precision of its linear layers."""
    cfg = {**_config(), **TINY_KIMI}
    params = family.init_params(cfg, 5)
    toks = np.random.default_rng(1).integers(0, 2048, (1, 48))
    exact = ref.forward(params, toks, cfg)
    drift = {p: float(jnp.max(jnp.abs(ref.forward(params, toks, cfg, p)
                                      - exact)))
             for p in ("bf16", "int8", "fp8")}
    assert 0 < drift["bf16"] < min(drift["int8"], drift["fp8"]), drift


def test_the_near_tie_rule_is_the_held_experts_edge():
    """Scores made by hand: 8 experts, top 3, this share holds experts
    2 and 3.  A held expert just inside or just outside the selection is
    a near-tie; one far from the boundary is not, however close the
    experts of other shares lie to each other."""
    cfg = {"num_experts_per_tok": 3, "n_routed_experts": 2,
           "deployment": {"rank": 1}}
    logit = lambda s: np.log(s / (1 - s))
    scores = np.array([
        # top 3: 0.9, 0.8 (held), 0.7; next 0.69: held 0.8 is 0.11 in,
        # held 0.2 is 0.5 out: 0.11 (0.7 / 0.69 are other shares')
        [0.9, 0.7, 0.8, 0.2, 0.69, 0.1, 0.1, 0.1],
        # held 0.505 is selected against 0.5: 0.005
        [0.9, 0.8, 0.505, 0.1, 0.5, 0.1, 0.1, 0.1],
        # held 0.595 is just out (the third is 0.6): 0.005
        [0.9, 0.8, 0.595, 0.1, 0.6, 0.1, 0.1, 0.1]], np.float32)
    p = {"router": jnp.asarray(np.eye(8, dtype=np.float32)),
         "router_bias": jnp.zeros((8,), jnp.float32)}
    got = np.asarray(ref.held_margin(jnp.asarray(logit(scores)), p, cfg))
    assert np.allclose(got, [0.11, 0.005, 0.005], atol=1e-5), got
    assert ref.held_experts(cfg) == (2, 2)
