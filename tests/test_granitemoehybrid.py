"""The granitemoehybrid model (models/granitemoehybrid.py) — Mamba-2
state-space layers beside attention without position encoding, a
softmax-over-the-selected router, four multipliers, a tied head — and its
path through DecodeEngine and ContinuousBatcher — a cache whose layers
hold K/V rows or a recurrent state — against the plain reference
(benchmarks/reference/granitemoehybrid.py) at tiny widths on the CPU,
float32 compute so that the comparison is of the mathematics: one period
in small (three Mamba-2 layers, attention, two more)."""

import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import served_families as fam

from benchmarks.reference import granitemoehybrid as ref
from distributedtensorflowexample_tpu.models import build_model_from_config
from distributedtensorflowexample_tpu.obs import metrics as obs_metrics
from distributedtensorflowexample_tpu.ops import moe
from distributedtensorflowexample_tpu.serving.engine import DecodeEngine

KINDS = ["state"] * 3 + ["full"] + ["state"] * 2
#: float32 against float32 at HIGHEST: summation order only.  The logits
#: are divided by 16 and lie within +-0.1 here (readings: 1e-8).
TOL = 2e-7


FAMILY = "granitemoehybrid"
TINY = fam.TINY[FAMILY]
_model = functools.partial(fam.model, FAMILY)
_counter = fam.counter


@pytest.fixture(scope="module")
def params():
    return fam.params(FAMILY)


@pytest.fixture(scope="module")
def sequences():
    return fam.sequences(FAMILY)


@pytest.fixture(scope="module")
def ref_logits(params, sequences):
    return np.asarray(ref.forward(params, jnp.asarray(sequences), TINY))


def test_forward_matches_the_reference(params, sequences, ref_logits):
    """300 positions: one whole chunk of the scan and a part of one, the
    reference's recurrence token by token."""
    got = _model().apply({"params": params}, jnp.asarray(sequences))
    assert np.abs(ref_logits).max() > 0.02
    assert np.abs(np.asarray(got) - ref_logits).max() < TOL


@pytest.mark.parametrize("drop", ["conv_bias", "d_skip", "dt_bias", "a_log",
                                  "w_dt", "norm_y", "router"])
def test_the_tolerance_sees_a_dropped_part(params, sequences, ref_logits,
                                           drop):
    """The comparison is tight enough: a convolution without its bias, a
    reading without the skip, a step without its bias or its projection,
    every head's rate e^2 times its own, a gated norm without its scale,
    a router that scores nothing — each moves the logits by ten
    tolerances or more."""
    other = {"norm_y": jnp.ones_like, "a_log": lambda x: x + 2.0}.get(
        drop, jnp.zeros_like)
    flat = jax.tree_util.tree_map_with_path(
        lambda path, x: other(x) if path[-1].key == drop else x, params)
    got = _model().apply({"params": flat}, jnp.asarray(sequences[:1]))
    assert np.abs(np.asarray(got) - ref_logits[:1]).max() > 10 * TOL


@pytest.mark.parametrize("key, value", [
    ("embedding_multiplier", 1), ("residual_multiplier", 1.0),
    ("attention_multiplier", 8.0), ("logits_scaling", 1)])
def test_each_multiplier_is_the_configurations(params, sequences, ref_logits,
                                               key, value):
    """The four multipliers: the model under the configuration's own is
    the reference's (above); under another it is not, and is again the
    reference's under that other one."""
    got = np.asarray(_model(**{key: value}).apply(
        {"params": params}, jnp.asarray(sequences[:1])))
    assert np.abs(got - ref_logits[:1]).max() > 10 * TOL
    want = np.asarray(ref.forward(params, jnp.asarray(sequences[:1]),
                                  {**TINY, key: value}))
    assert np.abs(got - want).max() < 5 * TOL * max(1.0, np.abs(want).max())


def test_the_dense_siblings_layer_is_the_shared_mlp_alone(sequences):
    """``num_local_experts`` 0 (granite-4.0-h-micro's layer): no router,
    no experts, nothing counted; the block is the family's."""
    dense = {**TINY, "num_local_experts": 0}
    dense.pop("published"), dense.pop("deployment")
    model = build_model_from_config(dense, dtype=jnp.float32,
                                    param_dtype=jnp.float32)
    p = model.init(jax.random.PRNGKey(1), jnp.zeros((1, 8), jnp.int32))[
        "params"]
    assert not any(k.startswith(("router", "experts_")) for k in p["block0"])
    assert model.expert_slots == 0
    got = model.apply({"params": p}, jnp.asarray(sequences[:1, :40]))
    want = ref.forward(p, jnp.asarray(sequences[:1, :40]), dense)
    assert np.abs(np.asarray(got - want)).max() < TOL


# ---- prefill, then decode, through the engine ------------------------------

def test_engine_prefill_then_decode_logits_match_the_reference(
        params, sequences, ref_logits):
    """Three slots; prompts of 5, 70 and 67 tokens (two in buckets of 128
    and one of 8); 100 decode steps; a request admitted mid-decode.  Every
    logit the engine gives — prefill chunked, decode recurrent against
    the cache — is the reference's full forward's at that position."""
    engine = DecodeEngine(_model(), params, slots=3, cache_len=256)
    assert [kind for kind, _ in engine.smodel.cache_rows(256)] == KINDS
    worst = 0.0

    def admit(slot, row, length):
        nonlocal worst
        (_, last), = engine.prefill_many(
            [(slot, sequences[row, :length], 1)]).values()
        worst = max(worst, np.abs(last - ref_logits[row, length - 1]).max())
        engine.set_slot(slot, int(sequences[row, length]), length)

    where = {0: 0, 1: 1}                # slot -> row of `sequences`
    admit(0, 0, 5)
    admit(1, 1, 70)
    for step in range(100):
        if step == 11:
            where[2] = 2
            admit(2, 2, 67)
        busy = sorted(where)
        at = {s: int(engine.positions[s]) for s in busy}
        logits = engine.decode_logits(busy=busy)
        for s in busy:
            worst = max(worst, np.abs(
                logits[s] - ref_logits[where[s], at[s]]).max())
            engine.set_slot(s, int(sequences[where[s], at[s] + 1]),
                            at[s] + 1)
    assert int(engine.positions[1]) == 170 and worst < TOL, worst


@pytest.mark.parametrize("bucket, batch", [(8, 1), (16, 2), (32, 2),
                                           (64, 1), (128, 2), (256, 1)])
def test_prefill_in_every_bucket_and_batch_is_the_references(
        params, sequences, ref_logits, bucket, batch):
    """Prompts a little shorter than each bucket of the ladder, one or two
    a program: the last logits are the reference's, and the first decode
    step from what prefill left is too."""
    engine = DecodeEngine(_model(), params, slots=2, cache_len=256)
    lengths = [bucket - 1 - 2 * i for i in range(batch)]
    out = engine.prefill_many([(i, sequences[i, :n], 1)
                               for i, n in enumerate(lengths)])
    assert (bucket, batch) in engine._warm_buckets
    for i, n in enumerate(lengths):
        assert np.abs(out[i][1] - ref_logits[i, n - 1]).max() < TOL
        engine.set_slot(i, int(sequences[i, n]), n)
    logits = engine.decode_logits(busy=list(range(batch)))
    for i, n in enumerate(lengths):
        assert np.abs(logits[i] - ref_logits[i, n]).max() < TOL


def test_a_state_kept_in_bfloat16_fails_the_tolerance(params, sequences,
                                                      ref_logits):
    """The comparison is tight enough to see the recurrent state's type:
    the same engine with its five states rounded to bfloat16 after every
    step is ten tolerances off within 30 steps."""
    engine = DecodeEngine(_model(), params, slots=1, cache_len=256)
    engine.prefill_many([(0, sequences[0, :20], 1)])
    worst = 0.0
    for t in range(20, 50):
        engine._ck = tuple(
            c.astype(jnp.bfloat16).astype(jnp.float32)
            if kind == "state" else c for c, kind in zip(engine._ck, KINDS))
        engine.set_slot(0, int(sequences[0, t]), t)
        worst = max(worst, np.abs(engine.decode_logits(busy=[0])[0]
                                  - ref_logits[0, t]).max())
    assert worst > 10 * TOL, worst


PLAN = [(5, 30), (21, 25), (9, 12), (70, 20), (3, 40), (14, 9), (27, 18)]


@pytest.mark.parametrize("run_ahead", [True, False])
def test_the_batcher_serves_the_references_tokens(params, serve_backlog,
                                                  run_ahead):
    """Seven requests through RequestQueue and ContinuousBatcher on three
    slots (so four are admitted mid-decode, into slots others have
    used), the read-back one step late and at every step: every served
    token is the reference's best at its position."""
    engine = DecodeEngine(_model(), params, slots=3, cache_len=128)
    rng = np.random.default_rng(11)
    plan = [(rng.integers(0, 97, n).astype(np.int32), new)
            for n, new in PLAN]
    served = serve_backlog(engine, plan, run_ahead=run_ahead)
    steps = 'serve_decode_steps_total{readback="%s"}'
    if run_ahead:
        assert served.moved[steps % "late"] > served.moved[
            steps % "same_step"]
    else:
        assert steps % "late" not in served.moved
    for r in served.reqs:
        assert r.outcome == "ok" and len(r.tokens) == r.max_new
        gaps = ref.served_token_gaps(params, r.prompt, np.asarray(r.tokens),
                                     TINY, pad_to=16)
        assert gaps["widest_over_all"] < 1e-5 and gaps["tokens"] == r.max_new


def test_a_late_readback_counts_what_the_synchronous_order_counts(
        params, serve_backlog):
    """Request for request the same tokens, boundary for boundary the
    same ``step()``, and the host's and the model's counters total the
    same over the run."""
    engine = DecodeEngine(_model(), params, slots=3, cache_len=128)
    rng = np.random.default_rng(11)
    plan = [(rng.integers(0, 97, n).astype(np.int32), new)
            for n, new in PLAN]
    late = serve_backlog(engine, plan, run_ahead=True)
    sync = serve_backlog(engine, plan, run_ahead=False)
    assert [r.tokens for r in late.reqs] == [r.tokens for r in sync.reqs]
    assert [row.n for row in late.rows] == [row.n for row in sync.rows]
    counted = ("moe_pairs_total", "moe_rows_walked_total",
               "moe_experts_touched_total", "moe_expert_slots_total",
               "serve_cache_rows_read_total", "serve_state_bytes_total",
               "serve_prefill_positions_total", "serve_tokens_total")
    pick = lambda moved: {k: v for k, v in moved.items()
                          if k.startswith(counted)}
    assert pick(late.moved) == pick(sync.moved) and len(pick(sync.moved)) > 4


# ---- the expert layer ------------------------------------------------------

def _layer_inputs(n=50, seed=2):
    """A tiny expert layer's weights, uncut (24 experts), and n tokens."""
    rng = np.random.default_rng(seed)
    d, f, fs, E = 32, 16, 24, 24
    normal = lambda *s: jnp.asarray(rng.normal(size=s) * 0.2, jnp.float32)
    p = {"router": normal(d, E), "shared_gate": normal(d, fs),
         "shared_up": normal(d, fs), "shared_down": normal(fs, d),
         "experts_gate": normal(E, d, f), "experts_up": normal(E, d, f),
         "experts_down": normal(E, f, d)}
    return p, normal(n, d) * 5


def _route(m, p):
    return moe.route(m, p["router"], None, top_k=4, route_scale=1.0,
                     route_norm=True, score_func="softmax")


def test_routing_is_top_k_then_softmax_written_independently():
    """The program's softmax over all, top k, renormalised over the k is
    the softmax over the k largest LOGITS, written with numpy."""
    p, m = _layer_inputs()
    sel, w = _route(m, p)
    logits = np.asarray(m) @ np.asarray(p["router"])
    want_sel = np.argsort(-logits, axis=-1)[:, :4]
    assert np.array_equal(np.sort(np.asarray(sel), -1), np.sort(want_sel, -1))
    top = np.take_along_axis(logits, np.asarray(sel), axis=-1)
    e = np.exp(top - top.max(-1, keepdims=True))
    assert np.abs(np.asarray(w) - e / e.sum(-1, keepdims=True)).max() < 1e-6
    ref_sel, ref_w = ref.route(m, p, {"num_experts_per_tok": 4})
    assert np.array_equal(np.asarray(sel), np.asarray(ref_sel))
    assert np.abs(np.asarray(w - ref_w)).max() < 1e-6


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """Over all eight shares of a 24-expert layer: the parts the shares
    give (each computed by the program's layer, told which three experts
    it holds), with the shared MLP counted once, are the uncut
    reference's layer."""
    p, m = _layer_inputs()
    uncut = {**TINY, "num_local_experts": 24, "deployment": {"rank": 0}}
    shared, routed = ref.expert_layer(m, p, uncut, ref.make_matmul("f32"))
    sel, w = _route(m, p)
    total = moe.gated_ffn(m, p["shared_gate"], p["shared_up"],
                          p["shared_down"])
    assert np.abs(np.asarray(total - shared)).max() < 2e-5
    pairs = 0
    for rank in range(8):
        held = slice(3 * rank, 3 * rank + 3)
        part, stats = moe.expert_ffn(
            m, sel, w, p["experts_gate"][held], p["experts_up"][held],
            p["experts_down"][held], first_expert=3 * rank,
            experts_known=24)
        _, theirs = ref.expert_layer(       # ... the reference's share
            m, {**p, **{k: p[k][held] for k in (
                "experts_gate", "experts_up", "experts_down")}},
            {**TINY, "num_local_experts": 3, "deployment": {"rank": rank}},
            ref.make_matmul("f32"))
        assert np.abs(np.asarray(part - theirs)).max() < 2e-5
        total, pairs = total + part, pairs + int(stats[0])
        assert int(stats[0]) + int(stats[1]) == 50 * 4
    assert pairs == 50 * 4              # every pair computed exactly once
    assert np.abs(np.asarray(total - (shared + routed))).max() < 5e-5


def test_the_blocks_share_is_the_configurations(params, sequences):
    """The model's own layer under rank 1 of 8 holds experts 3-5 of 24
    and routes over all 24."""
    blk = _model().bind({"params": params}).blocks[0]
    c = blk.dims
    assert (c.experts_held, c.first_expert, c.n_routed, c.top_k) == (
        3, 3, 24, 4)
    assert blk.router.shape == (32, 24) and blk.held[0].shape == (3, 32, 16)


# ---- counters ---------------------------------------------------------------

def test_the_engines_counters_follow_a_hand_count(params, sequences):
    """``serve_cache_bytes`` is the module's own count by kind; rows are
    read and fetched in the one attention layer only, under
    ``kind="full"``; ``serve_state_bytes_total`` is state layers x bytes a
    slot x 2 a decode step; the recurrence's two forms are counted where
    they are traced."""
    names = ['serve_state_bytes_total{whose="all"}',
             'serve_cache_rows_read_total{kind="full"}',
             'serve_cache_rows_fetched_total{kind="full"}',
             'serve_cache_rows_read_total{kind="state"}',
             'moe_pairs_total{where="held"}',
             'moe_pairs_total{where="absent"}', "moe_expert_slots_total",
             'lm_state_space_total{impl="chunked"}',
             'lm_state_space_total{impl="recurrent"}']
    before = [_counter(n) for n in names]
    # a cache length no other test uses: its programs are traced here
    engine = DecodeEngine(_model(), params, slots=3, cache_len=96)
    engine.prefill_many([(0, sequences[0, :5], 1), (2, sequences[1, :19], 1)])
    engine.decode(busy=[0, 2])          # positions 5 and 19
    engine.decode(busy=[2])             # position 20; slot 0 still live
    (every, read, fetched, none, held, absent, slots, chunked,
     recurrent) = (_counter(n) - b for n, b in zip(names, before))
    # a slot's state in one layer: S [8, 8, 16] f32, conv [3, 96] f32 here
    state = 4 * 8 * 8 * 16 + 4 * 3 * (8 * 8 + 2 * 16)
    assert every == 2 * 3 * 5 * state * 2
    assert read == (6 + 20) + 21 and none == 0      # one attention layer
    assert fetched == 2 * 3 * 96        # the CPU's chain reads every row
    # prefill: 24 prompt tokens; two steps of two live slots; top 4; 6
    # expert layers
    assert held + absent == (24 + 2 + 2) * 4 * 6
    assert slots == 2 * 3 * 6
    # two prefill programs (buckets of 8 and of 32) and one decode
    # program traced: five state-space layers each
    assert (chunked, recurrent) == (10, 5)
    gauges = obs_metrics.registry().snapshot()["gauges"]
    assert gauges['serve_cache_bytes{kind="full"}']["value"] == \
        3 * 96 * 2 * 2 * 8 * 4
    assert gauges['serve_cache_bytes{kind="state"}']["value"] == \
        3 * 5 * state
    assert engine.cache_bytes == 3 * (96 * 2 * 2 * 8 * 4 + 5 * state)


# ---- what refuses, and what holds -----------------------------------------

@pytest.mark.parametrize("cache_len, ladder", [
    (2048, (256, 512, 1024, 2048)),     # the benchmark's cell
    (1000, (256, 512, 1000)),
    (256, None),                        # the engine's powers of two
])
def test_the_stated_ladder(cache_len, ladder):
    assert _model().prefill_buckets(cache_len) == ladder


def test_the_cells_configuration_builds_the_cells_model():
    """benchmarks/configs/granite4_h_small_ep8.json through the one
    constructor: one period of ten layers, 9 of 72 experts from id 0,
    the four multipliers, and the cache the cell's arithmetic says: 7.34
    GB of state and 1.61 GB of rows at 192 slots x 2,048."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    model = build_model_from_config(os.path.join(
        root, "benchmarks", "configs", "granite4_h_small_ep8.json"))
    c = model.dims
    assert (model.n_layers, c.experts_held, c.n_routed, c.first_expert,
            c.top_k, c.d_expert, c.d_shared) == (10, 9, 72, 0, 10, 768, 1536)
    assert [k == "attention" for k in c.layer_types] == \
        [False] * 5 + [True] + [False] * 4
    assert (c.n_heads, c.n_kv_heads, c.head_dim, c.ssm_heads, c.ssm_head_dim,
            c.ssm_state, c.d_inner, c.conv_dim, c.conv_kernel) == (
        32, 8, 128, 128, 64, 128, 8192, 8448, 4)
    assert (c.embedding_multiplier, c.residual_multiplier,
            c.attention_multiplier, c.logits_scaling) == (
        12.0, 0.22, 0.0078125, 16.0)
    per_slot = model.cache_slot_bytes(2048)
    assert per_slot[0] == 4_194_304 + 50_688 and per_slot[5] == 2048 * 4096
    held = np.asarray(per_slot) * 192
    kinds = [kind for kind, _ in model.cache_rows(2048)]
    assert round(sum(h for h, k in zip(held, kinds) if k == "full") / 1e9,
                 2) == 1.61
    assert round(sum(h for h, k in zip(held, kinds) if k == "state") / 1e9,
                 2) == 7.34
    shapes = jax.eval_shape(lambda: model.init_cache(192, 2048))
    assert sum(x.size * x.dtype.itemsize
               for x in jax.tree.leaves(shapes)) == held.sum()
    assert model.expert_slots == 9 * 10
    assert model.prefill_positions_max == 2048


@pytest.mark.parametrize("key, value", [
    ("position_embedding_type", "rope"), ("mamba_n_groups", 8),
    ("mamba_proj_bias", True), ("attention_bias", True),
    ("tie_word_embeddings", False), ("normalization_function", "layernorm"),
    ("hidden_act", "gelu")])
def test_a_configuration_the_block_does_not_compute_is_refused(key, value):
    with pytest.raises(ValueError, match=key):
        _model(**{key: value})


def test_layer_types_and_widths_that_disagree_are_refused():
    with pytest.raises(ValueError, match="layer_types"):
        _model(num_hidden_layers=5)
    with pytest.raises(ValueError, match="layer_types"):
        _model(layer_types=["mamba"] * 5 + ["sliding_attention"])
    with pytest.raises(ValueError, match="mamba_expand"):
        _model(mamba_d_head=16)
    with pytest.raises(ValueError, match="granitemoehybrid"):
        build_model_from_config({"model_type": "granitemoe"})


# ---- one constructor, from a configuration file ----------------------------

def test_the_cli_serves_the_model_from_a_configuration_file(tmp_path):
    """``tools/serve_lm.py --model_config`` builds the model by the
    constructor the benchmark's family calls, initialises a snapshot,
    promotes it and drives requests through the batcher; what rolls a
    cache back is refused by name: exit 2."""
    import importlib.util
    path = tmp_path / "tiny_granitemoehybrid.json"
    path.write_text(json.dumps(TINY))
    built = build_model_from_config(str(path), dtype=jnp.float32,
                                    param_dtype=jnp.float32)
    assert built == _model()
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "serve_lm_cli", os.path.join(root, "tools", "serve_lm.py"))
    cli = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(cli)
    results = tmp_path / "results.jsonl"
    rc = cli.main(["--model_config", str(path), "--snapshot",
                   str(tmp_path / "snap"), "--init_if_missing", "--drive",
                   "5", "--drive_max_new", "12", "--max_len", "64",
                   "--slots", "2", "--results", str(results)])
    assert rc == 0
    rows = [json.loads(line) for line in results.read_text().splitlines()]
    assert len(rows) == 5 and all(len(r["tokens"]) == 12 for r in rows)
    assert cli.main(["--model_config", str(path), "--snapshot",
                     str(tmp_path / "snap"), "--prefix_cache", "4",
                     "--drive", "1", "--max_len", "64"]) == 2
