"""The ``granitemoehybrid`` family through the harness at toy sizes on the
CPU, as ``test_bailing_hybrid.py`` drives Ling's: the cell's own driver,
comparison and limit (`correct` true for the sound program, false under a
control); the family's weights bind to the program's model and every one
has a tensor of the published checkpoint; its counts are ISSUE 41's
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
from benchmarks.families import granitemoehybrid as family
from benchmarks.harness import schema
from benchmarks.reference import granitemoehybrid as ref
from benchmarks.tests.conftest import ROOT

CONFIG = "granite4_h_small_ep8"
CELL = CONFIG + ".serve_assist_backlog"
NEW_READERS = [
    "prefill_pad_pct.assist", "device_idle_pct.assist",
    "slot_occupancy_pct.assist", "moe_held_pairs_pct.assist",
    "moe_experts_touched_pct.assist", "cache_state_pct.assist",
    "decode_state_bytes_pct.assist", "decode_roofline_pct.assist",
    "decode_dispatch_ms.assist"]
REDUCED = ["num_hidden_layers", "layer_types", "num_local_experts",
           "vocab_size"]

#: A share of a toy granitemoehybrid: rank 1 of 8 holds experts 2-3 of
#: 16; one whole period of the cut's own pattern in small (three Mamba-2
#: layers, attention, a Mamba-2 layer).
TINY_GRANITE = dict(
    vocab_size=2048, hidden_size=64, num_hidden_layers=5,
    layer_types=["mamba", "mamba", "mamba", "attention", "mamba"],
    num_attention_heads=4, num_key_value_heads=2, attention_multiplier=0.125,
    mamba_n_heads=8, mamba_d_head=16, mamba_d_state=16,
    intermediate_size=32, shared_intermediate_size=48, num_local_experts=2,
    num_experts_per_tok=4, max_position_embeddings=256,
    published={"num_local_experts": 16}, deployment={"rank": 1},
    # 64 features give the router's logits an eighth of the spread 4096
    # give them: larger weights put the near-ties back to a share of the
    # tokens (the reference judges none at a near-tie).
    assumed={"initializer_range": 0.1})
TINY_ASSIST = dict(
    slots=8, cache_len=128, length_pairs=32,
    prompt_tokens=dict(median=16, sigma=0.4, min=4, max=60),
    output_tokens=dict(median=20, sigma=0.3, min=8, max=40),
    in_flight_at_open=8, backlog_requests_per_s=400,
    boundaries_per_s=400, trace_seconds=1,
    prefill_batches={"8": 2, "16": 2, "32": 2, "64": 1},
    # The cell's own limit is set from readings at its own size (PERF.md
    # section 2).  Here the logits are divided by 16 and lie within
    # +-0.01; readings at this size over four seeds: sound 0 to 1.3e-4,
    # the reference in fp8 1.0e-3 to 2.2e-3 (bfloat16 in its place reads
    # as the sound program does, which is what it is).
    limits={"served_logit_gap_widest": 4e-4})


def _config() -> dict:
    path = os.path.join(ROOT, f"benchmarks/configs/{CONFIG}.json")
    with open(path) as f:
        return json.load(f)


def _run(seed, **kw):
    return bench_run.run_cell(
        CELL, seed, 1.0, False, require_tpu=False,
        overrides={"config": TINY_GRANITE, "params": TINY_ASSIST}, **kw)


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
    assert config["family"] == config["reference"] == "granitemoehybrid"
    assert (traffic["slots"], traffic["cache_len"]) == (192, 2048)
    assert cell["chips"] == 1
    per = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW_READERS:
        assert per[name]["workloads"] == [CELL], name
        assert per[name]["moves"] == "serve_tokens_per_s"
    assert CELL in per["decode_ahead_pct"]["workloads"]
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    # Not "the last": the next configuration's cell is appended after it.
    assert CELL in e2e["serve_tokens_per_s"]["workloads"]


def test_the_familys_tree_is_the_models():
    """``init_params`` lays its leaves out under the program's parameter
    names, shapes and types: the tree binds to DecodeEngine unchanged;
    and each leaf is a tensor of the published checkpoint."""
    cfg = {**_config(), **TINY_GRANITE}
    model = family.build_model(cfg, dtype=jnp.bfloat16)
    want = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    got = jax.eval_shape(family.init_fn(cfg), jnp.uint32(1))
    flat = lambda t: {jax.tree_util.keystr(k): (v.shape, str(v.dtype))
                      for k, v in jax.tree_util.tree_leaves_with_path(t)}
    assert flat(got) == flat(want)
    leaves = jax.tree_util.tree_flatten_with_path(
        family.param_shapes(cfg), is_leaf=lambda x: isinstance(x, tuple))[0]
    shapes = {jax.tree_util.keystr(p): v for p, v in leaves}
    assert {k: v[0] for k, v in flat(got).items()} == shapes
    assert {p[-1].key for p, _ in leaves} == set(family.SOURCE_NAMES)


def test_the_seeded_decays_span_what_the_file_says():
    """Mamba-2's own initialisation: a step's decay exp(-dt A) at the
    bias alone runs from ~0.2 to ~0.999 over the heads, D is ones."""
    cfg = _config()
    tiny = {**cfg, **TINY_GRANITE, "mamba_n_heads": 512, "mamba_d_head": 1,
            "hidden_size": 256, "num_attention_heads": 4}
    block = family.init_params(tiny, 3)["block0"]
    decay = np.exp(-np.exp(np.asarray(block["a_log"]))
                   * np.log1p(np.exp(np.asarray(block["dt_bias"]))))
    assert 0.15 < decay.min() < 0.5 and 0.998 < decay.max() < 1.0
    assert (np.asarray(block["d_skip"]) == 1).all()


def test_the_counts_are_the_configurations_arithmetic():
    """ISSUE 41's parameters and bytes, to the digit it gives them."""
    cfg = _config()
    outside, one = family._counts(cfg)
    assert one == 9_437_184
    assert family.kinds(cfg) == (1, 9, 10)
    shapes = family.param_shapes(cfg)
    size = lambda block, names=None: sum(
        np.prod(s) for n, s in shapes[block].items()
        if (n in names if names else not n.startswith("experts_")))
    assert round(size("block0", ("w_in", "w_dt")) / 1e6, 2) == 68.68
    assert round(size("block0", ("w_out",)) / 1e6, 2) == 33.55
    assert int(size("block0") / 1e5) == 1214        # a Mamba-2 layer, 121.4 M
    assert int(size("block5") / 1e5) == 611         # the attention layer
    assert round(np.prod(shapes["embed"]) / 1e6, 1) == 51.4
    # ISSUE 41 adds the layers as it rounded them (1,153.7 M outside the
    # experts, 2,054 M in all); to the parameter it is 0.6 M more.
    assert round((outside - np.prod(shapes["embed"])) / 1e6, 1) == 1154.3
    assert round(family.param_count(cfg) / 1e6) == 2055
    assert round(family.weight_bytes(cfg) / 1e9, 2) == 4.11
    # A slot: 4,194,304 B of state and 50,688 B of convolution a layer.
    assert family.state_bytes_per_slot(cfg) == 9 * (4_194_304 + 50_688)
    assert round(192 * family.state_bytes_per_slot(cfg) / 1e9, 2) == 7.34
    assert family.kv_row_bytes(cfg) == 4096
    assert round(192 * 2048 * family.kv_row_bytes(cfg) / 1e9, 2) == 1.61
    # The step ISSUE 41 reckons: 192 slots, every held expert touched,
    # ~770 live rows a slot in the attention layer.
    rows = 192 * 770
    step = family.decode_step_bytes(cfg, rows, slots=192,
                                    experts_touched=90)
    parts = (2 * outside, 2 * 90 * one,
             2 * 192 * family.state_bytes_per_slot(cfg), rows * 4096)
    assert abs(sum(parts) - step) < 1
    assert [round(p / 1e9, 2) for p in parts] == [2.41, 1.7, 14.67, 0.61]
    assert 0.74 < parts[2] / step < 0.77            # ~75% is the state
    # Decode FLOPs: two per parameter a token multiplies by, and the
    # recurrence's three multiply-adds a state element.
    flops = family.decode_step_flops(cfg, 0, 1, pairs_held=2.0)
    assert flops == 2 * (outside + 2 * one + 3 * 9 * 128 * 64 * 128)
    assert family.decode_step_flops(cfg, 1000, 0, pairs_held=0.0) == \
        1000 * 2 * 2 * 32 * 128


@pytest.mark.parametrize("key", REDUCED)
def test_every_changed_key_is_stated(key):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"]
                     if c["name"] == CONFIG)
    cfg = _config()
    assert key in entry["reduced"] and key in cfg["changed_from_source"]
    assert key in cfg["published"]
    assert len(entry["reduced"]) == len(cfg["changed_from_source"]) == 4


def test_every_other_number_is_the_catalogs():
    """The file holds every key of the catalog's row as the source has
    it, the four reduced ones apart; no width differs; the kept layers
    are the published pattern's first ten."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "granite-4.0-h-small")
    cfg = _config()
    assert cfg["source"] == row["source_url"]
    differs = [k for k, v in row["config"].items() if cfg.get(k) != v]
    assert sorted(differs) == sorted(REDUCED)
    assert cfg["layer_types"] == row["config"]["layer_types"][:10]
    assert cfg["deployment"]["chips_per_layer"] == 8
    assert cfg["deployment"]["rank"] == 0


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
    from benchmarks.harness import program_tape, ssm_counts
    monkeypatch.setattr(program_tape, "registry_value", lambda *a: None)
    monkeypatch.setattr(ssm_counts, "registry_value", lambda *a: None)
    monkeypatch.setattr(program_tape, "program_tape", lambda: ([], 0))
    run = types.SimpleNamespace(
        trace=None, trace_window=None, samples={},
        facts={"window": (0.0, 1.0)}, peaks=None, config=_config(),
        family=family, spans=types.SimpleNamespace(tape={}))
    assert _reader(name)(run) is None


def test_the_readers_read_a_recorded_run(monkeypatch):
    """The counters of ten decode steps made by hand and a trace of one
    decode program: the shares are the family's least work over what the
    record says was taken."""
    from benchmarks.harness import program_tape, ssm_counts
    from benchmarks.harness.peaks import peaks_for
    cfg, slots, steps = _config(), 192, 10
    rows = slots * 700
    state = 2 * slots * family.state_bytes_per_slot(cfg)
    record = {
        "moe_expert_slots_total": steps * 90,
        "moe_experts_touched_total": steps * 88,
        'serve_state_bytes_total{whose="all"}': steps * state,
        'serve_cache_rows_read_total{kind="full"}': steps * rows,
        'moe_pairs_total{where="held"}': 250, 'moe_pairs_total{where="absent"}': 1750,
        'serve_cache_bytes{kind="state"}': 7.33e9,
        'serve_cache_bytes{kind="full"}': 1.61e9}
    lookup = lambda _kind, series: record.get(series)
    monkeypatch.setattr(program_tape, "registry_value", lookup)
    monkeypatch.setattr(ssm_counts, "registry_value", lookup)
    event = lambda name, start, dur: types.SimpleNamespace(
        name=name, start=start, end=start + dur)
    trace = types.SimpleNamespace(
        device_ops={0: [event("fusion.1", 0.3, 0.001)]},
        device_modules={0: [event("jit__decode_step_fn(1)", 0.0, 0.030),
                            event("jit__prefill_bucketed(2)", 0.5, 0.1)]})
    run = types.SimpleNamespace(
        trace=trace, trace_window=(0.0, 1.0), samples={},
        facts={"slots": slots}, peaks=peaks_for("TPU v5 lite"), config=cfg,
        family=family, spans=None)
    least = family.decode_step_bytes(cfg, rows, slots=slots,
                                     experts_touched=88)
    got = _reader("decode_state_bytes_pct.assist")(run)
    assert abs(got - 100 * state / least) < 1e-9 and 74 < got < 77
    pace = run.peaks["hbm_bytes_per_s"]
    roof = _reader("decode_roofline_pct.assist")(run)
    assert abs(roof - 100 * least / pace / 0.030) < 1e-6 and roof < 100
    for name, want in (("moe_held_pairs_pct.assist", 12.5),
                       ("moe_experts_touched_pct.assist", 100 * 88 / 90),
                       ("cache_state_pct.assist",
                        100 * 7.33 / (7.33 + 1.61))):
        assert abs(_reader(name)(run) - want) < 1e-6, name


def test_the_controls_order_as_the_precisions_do():
    """At a small size the reference's own logits drift from float32 by
    more the lower the precision of its linear layers."""
    cfg = {**_config(), **TINY_GRANITE}
    params = family.init_params(cfg, 5)
    toks = np.random.default_rng(1).integers(0, 2048, (1, 48))
    exact = ref.forward(params, toks, cfg)
    drift = {p: float(jnp.max(jnp.abs(ref.forward(params, toks, cfg, p)
                                      - exact)))
             for p in ("bf16", "int8", "fp8")}
    assert 0 < drift["bf16"] < min(drift["int8"], drift["fp8"]), drift


def test_routing_is_top_k_then_softmax_by_hand():
    """Logits made by hand (an identity router): the 3 largest of 8, the
    softmax over those three and nothing else."""
    logits = np.array([[2.0, -1.0, 0.5, 3.0, 0.0, 1.0, -2.0, 0.4],
                       [0.0, 0.1, 0.2, 0.3, 0.4, 0.5, 0.6, 0.7]], np.float32)
    p = {"router": jnp.eye(8, dtype=jnp.float32)}
    sel, w = ref.route(jnp.asarray(logits), p, {"num_experts_per_tok": 3})
    assert np.asarray(sel).tolist() == [[3, 0, 5], [7, 6, 5]]
    want = np.exp(np.take_along_axis(logits, np.asarray(sel), 1))
    want /= want.sum(-1, keepdims=True)
    assert np.allclose(np.asarray(w), want, atol=1e-6)
