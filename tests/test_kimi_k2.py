"""The kimi_k2 model (models/kimi_k2.py): latent attention in every layer
with a compressed query and YaRN positions, its two forms, sigmoid
routing over a share of the experts, and the model's path through
DecodeEngine and ContinuousBatcher — a cache that is latent rows and
nothing else — against the plain reference (benchmarks/reference/
kimi_k2.py) at tiny widths on the CPU, float32 compute so that the
comparison is of the mathematics: a dense layer, then four expert
layers."""

import functools
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

import served_families as fam

from benchmarks.reference import kimi_k2 as ref
from distributedtensorflowexample_tpu.models import build_model_from_config
from distributedtensorflowexample_tpu.models import kimi_k2
from distributedtensorflowexample_tpu.obs import metrics as obs_metrics
from distributedtensorflowexample_tpu.ops import attention as attention_op
from distributedtensorflowexample_tpu.ops import moe
from distributedtensorflowexample_tpu.ops.pallas import (
    decode_attention as latent_kernel)
from distributedtensorflowexample_tpu.serving.engine import DecodeEngine

TOL = 2e-5      # float32 against float32 at HIGHEST: summation order only

FAMILY = "kimi_k2"
TINY = fam.TINY[FAMILY]
_model = functools.partial(fam.model, FAMILY)
_counter = fam.counter
_seeded = functools.partial(fam.seeded, FAMILY)
YARN = fam.YARN


@pytest.fixture(scope="module")
def params():
    return fam.params(FAMILY)


@pytest.fixture(scope="module")
def sequences():
    return fam.sequences(FAMILY)


@pytest.fixture(scope="module")
def ref_logits(params, sequences):
    return np.asarray(ref.forward(params, jnp.asarray(sequences), TINY))


# ---- positions --------------------------------------------------------------

def _yarn_by_hand(dim, theta, y):
    """The formula in float64, written out: (inv_freq, what cos and sin
    are times, the softmax scale's multiplier)."""
    i = np.arange(dim // 2, dtype=np.float64)
    f = theta ** (-2 * i / dim)
    corr = lambda r: dim * np.log(
        y["original_max_position_embeddings"] / (2 * np.pi * r)) / (
        2 * np.log(theta))
    low = max(int(np.floor(corr(y["beta_fast"]))), 0)
    high = min(int(np.ceil(corr(y["beta_slow"]))), dim - 1)
    ramp = np.clip((i - low) / (high - low), 0, 1)
    m = lambda x: 0.1 * x * np.log(y["factor"]) + 1
    return (f * (1 - ramp) + f / y["factor"] * ramp,
            m(y["mscale"]) / m(y["mscale_all_dim"]),
            m(y["mscale_all_dim"]) ** 2, (low, high))


@pytest.mark.parametrize("dim, theta, scaling, edges", [
    # Kimi-K2.5's own: 64 rotary features, theta 50,000, x 64 over 4,096
    (64, 50000.0, dict(YARN, factor=64, original_max_position_embeddings=4096,
                       beta_fast=32), (8, 20)),
    (16, 10000.0, YARN, (0, 3)),                    # the tiny model's
    (16, 10000.0, dict(YARN, mscale=0.707, mscale_all_dim=1.0), (0, 3)),
])
def test_yarn_is_the_formula(dim, theta, scaling, edges):
    """The program's frequencies, the reference's and the formula written
    out in float64 agree; the corrections are the ones arithmetic gives
    (8 and 20 at the published sizes); the scale is 192^-0.5 times
    m(mscale_all_dim)^2 = 0.144680 there."""
    cfg = {**TINY, "qk_rope_head_dim": dim, "rope_theta": theta,
           "rope_scaling": scaling, "qk_nope_head_dim": 192 - dim}
    c = kimi_k2.dims_from_config(cfg)
    want, mult, scale_mult, got_edges = _yarn_by_hand(dim, theta, scaling)
    inv, got_mult = kimi_k2.rotary_frequencies(c)
    r_inv, r_mult, r_scale = ref.yarn_frequencies(cfg)
    assert got_edges == edges
    assert np.allclose(np.asarray(inv), want, rtol=2e-6, atol=0)
    assert np.allclose(np.asarray(r_inv), want, rtol=2e-6, atol=0)
    assert math.isclose(got_mult, mult, rel_tol=1e-12)
    assert math.isclose(r_mult, mult, rel_tol=1e-12)
    assert math.isclose(c.softmax_scale, 192 ** -0.5 * scale_mult,
                        rel_tol=1e-12)
    assert math.isclose(r_scale, c.softmax_scale, rel_tol=1e-12)
    if scaling["factor"] == 64:
        assert round(c.softmax_scale, 6) == 0.144680
        # the fastest pairs keep their frequency, the slowest are / 64
        assert np.allclose(np.asarray(inv)[:9], want[:9])
        assert np.allclose(np.asarray(inv)[20:] * 64,
                           theta ** (-2 * np.arange(20, 32) / 64), rtol=2e-6)


@pytest.mark.parametrize("scaling", [None, dict(YARN, factor=1)])
def test_a_factor_of_one_is_plain_rope(scaling):
    """No scaling, or YaRN at factor 1: theta^(-2i / Dr), cos and sin as
    they are, the scale 1 / sqrt(Dn + Dr) — in the program and in the
    reference."""
    cfg = {**TINY, "rope_scaling": scaling}
    c = kimi_k2.dims_from_config(cfg)
    plain = 10000.0 ** (-np.arange(8) / 8)
    inv, mult = kimi_k2.rotary_frequencies(c)
    r_inv, r_mult, r_scale = ref.yarn_frequencies(cfg)
    assert np.allclose(np.asarray(inv), plain, rtol=2e-6)
    assert np.allclose(np.asarray(r_inv), plain, rtol=2e-6)
    assert mult == r_mult == 1.0
    assert math.isclose(c.softmax_scale, 24 ** -0.5)
    assert math.isclose(r_scale, 24 ** -0.5)


def test_one_rotary_table_a_program_is_counted():
    """``lm_position_scaling_total{kind}``: one increment a program
    traced (the table is shared by the layers), by how the frequencies
    are scaled."""
    series = 'lm_position_scaling_total{kind="%s"}'
    before = {k: _counter(series % k) for k in ("yarn", "none")}
    toks = jnp.zeros((1, 24), jnp.int32)       # a shape no other test traces
    for scaling, kind in ((YARN, "yarn"), (None, "none")):
        model = _model(rope_scaling=scaling)
        p = model.init(jax.random.PRNGKey(0), toks)["params"]   # one trace
        jax.jit(model.apply)({"params": p}, toks)               # another
        assert _counter(series % kind) - before[kind] == 2


# ---- the model against the reference ----------------------------------------

def test_forward_matches_the_reference(params, sequences, ref_logits):
    """260 positions, four tiles of 64 and a part of one (the tiled
    walk), positions past the 64 the frequencies were stretched from."""
    got = _model().apply({"params": params}, jnp.asarray(sequences))
    assert np.abs(np.asarray(got) - ref_logits).max() < TOL


@pytest.mark.parametrize("sizes", [
    dict(q_lora_rank=None),                             # q = a W_q
    dict(rope_scaling=None),                            # plain rope
    dict(rope_scaling=dict(YARN, mscale=0.5)),          # cos, sin scaled
    dict(first_k_dense_replace=2, deployment={"rank": 7}),
])
def test_the_familys_other_shapes_match_the_reference_too(sizes, sequences):
    """The block is the family's: an uncompressed query, plain rope, a
    magnitude on cos and sin, two leading dense layers and the last
    share — each against the reference under the same configuration."""
    model = _model(**sizes)
    p = _seeded(model)
    toks = jnp.asarray(sequences[:2, :100])
    got = model.apply({"params": p}, toks)
    want = ref.forward(p, toks, {**TINY, **sizes})
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < TOL


@pytest.mark.parametrize("drop", ["norm_q", "norm_c", "wq_a", "w_uv",
                                  "router_bias", "shared_down"])
def test_the_tolerance_sees_a_dropped_part(params, sequences, ref_logits,
                                           drop):
    """The comparison is tight enough: the compressed query's norm or the
    latent norm without its scale, a query or values left out, a router
    without its bias, no shared expert — each moves the logits by ten
    times the tolerance or more."""
    flat = jax.tree_util.tree_map_with_path(
        lambda path, x: (jnp.ones_like(x) if drop.startswith("norm_")
                         else jnp.zeros_like(x))
        if path[-1].key == drop else x, params)
    got = _model().apply({"params": flat}, jnp.asarray(sequences[:1]))
    assert np.abs(np.asarray(got) - ref_logits[:1]).max() > 10 * TOL


def test_a_scale_without_yarns_magnitude_fails_the_tolerance(
        params, sequences, ref_logits):
    """... and so does a softmax at 1 / sqrt(Dn + Dr) where the
    configuration stretches positions (m^2 = 1.46 here)."""
    c = _model().dims
    import dataclasses
    plain = dataclasses.replace(c, yarn=dataclasses.replace(
        c.yarn, mscale=0.0, mscale_all_dim=0.0))
    assert plain.softmax_scale == 24 ** -0.5 < c.softmax_scale
    got = kimi_k2.KimiK2LM(plain, jnp.float32, jnp.float32, 64).apply(
        {"params": params}, jnp.asarray(sequences[:1]))
    assert np.abs(np.asarray(got) - ref_logits[:1]).max() > 10 * TOL


# ---- latent attention's two forms, the compressed query ---------------------

def _block(params, index=1):
    model = _model()
    blk = kimi_k2.KimiBlock(model.dims, index >= 1, jnp.float32, jnp.float32,
                            64)
    return model.dims, blk, {"params": params[f"block{index}"]}


def test_the_absorbed_form_is_the_expanded_form(params):
    """One layer alone: a sequence attended in the expanded form (per-head
    keys of 24 and values of 8 made from the rows), and the same
    positions one token at a time in the absorbed form against the rows
    the sequence left — the same outputs, the same rows, past the 64
    positions the frequencies were stretched from."""
    dims, blk, block = _block(params)
    x = jnp.asarray(np.random.default_rng(2).normal(size=(2, 90, 32)),
                    jnp.float32)
    rot = kimi_k2.rotary(dims, jnp.arange(90)[None])
    want, (rows, none), _ = blk.apply(block, x, None, rot,
                                      method="sequence")
    assert none is None
    assert rows.shape == (2, 90, 128)                  # 16 + 16 -> 128
    assert np.array_equal(np.asarray(rows[..., 32:]), np.zeros((2, 90, 96)))
    ck = jnp.zeros((2, 128, 128))
    for t in range(90):
        pos = jnp.full((2,), t, jnp.int32)
        got, ck, _, _ = blk.apply(block, x[:, t], ck, jnp.zeros((0,)), pos,
                                  kimi_k2.rotary(dims, pos[:, None]),
                                  method="step")
        if t:       # position 0 is a parked slot's: it goes to no expert
            assert np.abs(np.asarray(got - want[:, t])).max() < TOL, t
    assert np.abs(np.asarray(ck[:, :90] - rows)).max() < 1e-6


def test_the_compressed_query_is_its_two_products(params):
    """``q = RMS(a W_qa; g) W_qb`` written out, the rotary part turned
    pair by pair by the stretched frequencies."""
    dims, blk, block = _block(params)
    p = block["params"]
    a = np.random.default_rng(3).normal(size=(1, 70, 32)).astype(np.float32)
    rot = kimi_k2.rotary(dims, jnp.arange(70)[None])
    q_nope, q_pe = blk.apply(block, jnp.asarray(a), rot, method="_mla_q")
    c_q = a[0].astype(np.float64) @ np.asarray(p["wq_a"], np.float64)
    c_q = c_q / np.sqrt((c_q ** 2).mean(-1, keepdims=True) + 1e-5) \
        * np.asarray(p["norm_q"], np.float64)
    q = (c_q @ np.asarray(p["wq_b"], np.float64)).reshape(70, 4, 24)
    inv = _yarn_by_hand(16, 10000.0, YARN)[0]
    ang = np.arange(70)[:, None] * inv[None]
    x1, x2 = q[..., 8::2], q[..., 9::2]
    cos, sin = np.cos(ang)[:, None], np.sin(ang)[:, None]
    want_pe = np.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                       -1).reshape(70, 4, 16)
    assert np.abs(np.asarray(q_nope[0]) - q[..., :8]).max() < TOL
    assert np.abs(np.asarray(q_pe[0]) - want_pe).max() < 5 * TOL
    assert "wq" not in p and p["wq_b"].shape == (12, 4 * 24)


@pytest.mark.parametrize("scale", [None, 0.3])
def test_latent_expanded_attention_takes_a_scale(scale):
    """``scale`` None is the value it always was, 1 / sqrt(Dh) — the
    same numbers as the call without the argument, bit for bit, one tile
    and the tiled walk —, and a stated scale is a hand-made softmax's."""
    rng = np.random.default_rng(4)
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
    q, k, v = f(2, 96, 4, 24), f(2, 96, 4, 24), f(2, 96, 4, 16)
    s = jnp.einsum("bthd,bshd->bhts", q, k) * (scale or 24 ** -0.5)
    s = jnp.where(np.tril(np.ones((96, 96), bool)), s, -jnp.inf)
    want = jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(s, axis=-1), v)
    for block in (128, 32):
        got = attention_op.latent_expanded_attention(q, k, v, block=block,
                                                     scale=scale)
        assert np.abs(np.asarray(got - want)).max() < 1e-5
        if scale is None:
            assert np.array_equal(
                np.asarray(got), np.asarray(
                    attention_op.latent_expanded_attention(q, k, v,
                                                           block=block)))
            assert np.array_equal(
                np.asarray(got), np.asarray(
                    attention_op.grouped_attention(q, k, v, block=block)))


def test_the_latent_kernel_takes_64_heads():
    """``latent_decode_attention`` interpreted at the cell's own query
    block — 64 heads over rows of 640 whose first 512 are the values —
    under the stretched scale: the einsum chain's attention over a slot's
    live rows only."""
    from distributedtensorflowexample_tpu.ops.pallas import (
        decode_attention as ragged)
    rng = np.random.default_rng(3)
    S, R, H, D, V = 2, 256, 64, 640, 512
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
    q, rows = f(S, H, D) * 0.3, f(S, R, D)
    lengths = jnp.asarray([77, 256], jnp.int32)
    assert ragged.latent_fetch_block(10240, 640, 512) == 128
    want = attention_op.latent_decode_attention(
        q, rows, lengths, v_dim=V, scale=0.144680)
    got = ragged.latent_decode_attention(
        q, rows, lengths, v_dim=V, scale=0.144680, block=128, interpret=True)
    assert got.shape == (S, H, V)
    assert np.abs(np.asarray(got - want)).max() < 1e-5


# The two interpreters the walk is held under: Pallas's own, whose copies
# land as they are started, and the TPU's, whose copies land when they
# are WAITED for into buffers that start as NaN — a block read before
# its wait, or a wait on a copy never started, shows.
@pytest.mark.parametrize("interpret", [True, pltpu.InterpretParams()],
                         ids=["pallas", "tpu"])
@pytest.mark.parametrize("block", latent_kernel.LATENT_BLOCKS)
@pytest.mark.parametrize("name, lengths", [
    ("one_row", lambda b, R: [1, 1, 1]),
    ("exactly_one_block", lambda b, R: [b, b, b]),
    ("one_row_over_a_block", lambda b, R: [b + 1, b + 1, b + 1]),
    ("every_row", lambda b, R: [R, R, R]),
    # the fetch ahead across slots: a slot's only block is started by
    # the slot before it and starts the next slot's first itself
    ("one_row_between_full_slots", lambda b, R: [R, 1, R]),
    ("very_different_slots", lambda b, R: [1, R, b + 1, b, 2, R - 1]),
])
def test_the_latent_kernel_walks_live_blocks_only(name, lengths, block,
                                                  interpret):
    """One grid step a slot, the slot's ``ceil(length / block)`` blocks
    fetched two buffers deep in granules of 128 rows, at every block of
    ``LATENT_BLOCKS``: the einsum chain's attention with the dead rows of
    the last live granule large and NaN in every granule past it."""
    R, H, D, V = 2 * max(latent_kernel.LATENT_BLOCKS), 8, 256, 128
    lengths = lengths(block, R)
    rng = np.random.default_rng(5)
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
    q, rows = f(len(lengths), H, D) * 0.3, f(len(lengths), R, D)
    lengths = jnp.asarray(lengths, jnp.int32)
    assert latent_kernel.pick_block(
        R, block, latent_kernel.LATENT_BLOCKS) == block
    want = attention_op.latent_decode_attention(
        q, rows, lengths, v_dim=V, scale=0.125)
    at = jnp.arange(R)[None]
    fetched = -(-lengths // latent_kernel.GRANULE) * latent_kernel.GRANULE
    poisoned = jnp.where((at < lengths[:, None])[..., None], rows,
                         jnp.where((at < fetched[:, None])[..., None],
                                   1e4, jnp.nan))
    got = latent_kernel.latent_decode_attention(
        q, poisoned, lengths, v_dim=V, scale=0.125, block=block,
        interpret=interpret)
    assert np.abs(np.asarray(got - want)).max() < 1e-5


# ---- prefill, then decode, through the engine ------------------------------

LADDER = (64, 128, 192, 256, 320)


def test_the_stated_ladder_is_whole_tiles():
    assert _model().prefill_buckets(320) == LADDER
    assert _model().prefill_buckets(256) is None    # the engine's own
    cell = _model(attn_block=kimi_k2.ATTN_TILE).prefill_buckets(10240)
    assert cell[:3] == (256, 512, 1024) and cell[-1] == 10240
    assert {3072, 3584, 4096, 4608, 5120} <= set(cell)
    assert all(b % 512 == 0 for b in cell[1:])


@pytest.mark.parametrize("run_ahead", [True, False])
def test_engine_prefill_in_every_bucket_then_decode_matches_the_reference(
        params, sequences, ref_logits, serve_backlog, run_ahead):
    """Prompts that land in every bucket of the ladder, alone (B = 1) and
    two to a program (B = 2), each prefill's last logits the reference's;
    then the engine decodes through the rows — every logit of 60 steps
    the reference's full forward's at that position, out to position 259
    (the frequencies were stretched from 64) —, and the greedy path,
    with the late read-back on and off, serves the reference's tokens."""
    engine = DecodeEngine(_model(), params, slots=4, cache_len=320)
    assert engine.buckets == LADDER
    assert [k for k, _ in engine.smodel.cache_rows(320)] == ["latent"] * 5
    worst = 0.0
    lengths = {64: (50, 64), 128: (65, 101), 192: (130, 190),
               256: (200, 256), 320: (257, 259)}
    for bucket, (one, two) in lengths.items():
        assert engine.bucket_for(one, 1) == engine.bucket_for(two, 1) == bucket
        out = engine.prefill_many([(0, sequences[0, :one], 1)])
        worst = max(worst, np.abs(out[0][1] - ref_logits[0, one - 1]).max())
        out = engine.prefill_many([(1, sequences[1, :one], 1),
                                   (2, sequences[2, :two], 1)])
        assert (bucket, 1) in engine._warm_buckets
        assert (bucket, 2) in engine._warm_buckets
        worst = max(worst, np.abs(out[1][1] - ref_logits[1, one - 1]).max(),
                    np.abs(out[2][1] - ref_logits[2, two - 1]).max())
    assert worst < TOL, worst
    # decode: slot 1 from position 200, slot 3 admitted mid-decode
    for s in (0, 2):
        engine.set_slot(s, 0, 0)
    engine.prefill_many([(1, sequences[1, :200], 1)])
    engine.set_slot(1, int(sequences[1, 200]), 200)
    where = {1: 1}
    for step in range(59):
        if step == 7:
            engine.prefill_many([(3, sequences[3, :33], 1)])
            engine.set_slot(3, int(sequences[3, 33]), 33)
            where[3] = 3
        busy = sorted(where)
        at = {s: int(engine.positions[s]) for s in busy}
        logits = engine.decode_logits(busy=busy)
        for s in busy:
            worst = max(worst, np.abs(
                logits[s] - ref_logits[where[s], at[s]]).max())
            engine.set_slot(s, int(sequences[where[s], at[s] + 1]),
                            at[s] + 1)
    assert int(engine.positions[1]) == 259 and worst < TOL, worst
    # the greedy path, read late or at once: the reference's tokens
    for s in range(4):
        engine.set_slot(s, 0, 0)
    rng = np.random.default_rng(11)
    plan = [(rng.integers(0, 97, n).astype(np.int32), new)
            for n, new in [(70, 30), (21, 25), (130, 12), (5, 40), (66, 20),
                           (14, 9)]]
    served = serve_backlog(engine, plan, run_ahead=run_ahead)
    steps = 'serve_decode_steps_total{readback="%s"}'
    assert (steps % "late" in served.moved) == run_ahead
    for r in served.reqs:
        assert r.outcome == "ok" and len(r.tokens) == r.max_new
        gaps = ref.served_token_gaps(params, r.prompt, np.asarray(r.tokens),
                                     TINY, pad_to=16)
        assert gaps["widest_over_all"] < 1e-4 and gaps["tokens"] == r.max_new


def test_a_late_readback_serves_the_synchronous_orders_tokens_and_counts(
        params, serve_backlog):
    """Seven requests on three slots, the next step handed to the device
    before the last one's tokens are read, and then a read-back at every
    step: request for request the same tokens, boundary for boundary the
    same ``step()``, and the host's and the model's counters total the
    same over the run."""
    engine = DecodeEngine(_model(), params, slots=3, cache_len=128)
    rng = np.random.default_rng(11)
    plan = [(rng.integers(0, 97, n).astype(np.int32), new)
            for n, new in [(5, 30), (21, 25), (9, 12), (70, 20), (3, 40),
                           (14, 9), (27, 18)]]
    late = serve_backlog(engine, plan, run_ahead=True)
    sync = serve_backlog(engine, plan, run_ahead=False)
    assert [r.tokens for r in late.reqs] == [r.tokens for r in sync.reqs]
    assert [row.n for row in late.rows] == [row.n for row in sync.rows]
    counted = ("moe_pairs_total", "moe_rows_walked_total",
               "moe_experts_touched_total", "moe_expert_slots_total",
               "serve_cache_rows_read_total", "serve_prefill_positions_total",
               "serve_tokens_total")
    pick = lambda moved: {k: v for k, v in moved.items()
                          if k.startswith(counted)}
    assert pick(late.moved) == pick(sync.moved) and len(pick(sync.moved)) > 4
    assert not any(k.startswith("serve_state_bytes_total")
                   for k in sync.moved)                 # no state to move
    steps = 'serve_decode_steps_total{readback="%s"}'
    assert late.moved[steps % "late"] > late.moved[steps % "same_step"]


def test_latent_rows_kept_in_fp8_fail_the_tolerance(params, sequences,
                                                    ref_logits):
    """The comparison is tight enough to see the rows' type: the same
    engine with its rows rounded to fp8 after the prefill is forty
    tolerances off at once."""
    engine = DecodeEngine(_model(), params, slots=1, cache_len=128)
    engine.prefill_many([(0, sequences[0, :40], 1)])
    engine._ck = tuple(c.astype(jnp.float8_e4m3fn).astype(jnp.float32)
                       for c in engine._ck)
    engine.set_slot(0, int(sequences[0, 40]), 40)
    got = engine.decode_logits(busy=[0])[0]
    assert np.abs(got - ref_logits[0, 40]).max() > 20 * TOL


# ---- the expert layer ------------------------------------------------------

def _layer_inputs(n=50, seed=2, E=64):
    """A tiny expert layer's weights, uncut (64 experts), and n tokens."""
    rng = np.random.default_rng(seed)
    d, f = 32, 16
    normal = lambda *s: jnp.asarray(rng.normal(size=s) * 0.2, jnp.float32)
    p = {"router": normal(d, E), "router_bias": normal(E) * 0.2,
         "shared_gate": normal(d, f), "shared_up": normal(d, f),
         "shared_down": normal(f, d), "experts_gate": normal(E, d, f),
         "experts_up": normal(E, d, f), "experts_down": normal(E, f, d)}
    return p, normal(n, d) * 5


ROUTING = dict(num_experts_per_tok=8, norm_topk_prob=True,
               routed_scaling_factor=2.827)


def test_the_32_shares_add_up_to_the_uncut_layer():
    """Over all 32 shares of a 64-expert layer, top-8: the parts the
    shares give (each computed by the program's layer, told which two
    experts it holds), with the shared expert counted once, are the
    uncut reference's layer, and every pair is computed exactly once."""
    p, m = _layer_inputs()
    uncut = {**ROUTING, "n_routed_experts": 64, "deployment": {"rank": 0}}
    shared, routed = ref.expert_layer(m, p, uncut, ref.make_matmul("f32"))
    sel, w = moe.route(m, p["router"], p["router_bias"], top_k=8,
                       route_scale=2.827)
    total = moe.gated_ffn(m, p["shared_gate"], p["shared_up"],
                          p["shared_down"])
    pairs = 0
    for rank in range(32):
        held = slice(2 * rank, 2 * rank + 2)
        part, stats = moe.expert_ffn(
            m, sel, w, p["experts_gate"][held], p["experts_up"][held],
            p["experts_down"][held], first_expert=2 * rank,
            experts_known=64)
        if rank in (0, 13, 31):     # ... the reference's share, sampled
            _, theirs = ref.expert_layer(
                m, {**p, **{k: p[k][held] for k in (
                    "experts_gate", "experts_up", "experts_down")}},
                {**ROUTING, "n_routed_experts": 2,
                 "deployment": {"rank": rank}}, ref.make_matmul("f32"))
            assert np.abs(np.asarray(part - theirs)).max() < TOL
        total, pairs = total + part, pairs + int(stats[0])
        assert int(stats[0]) + int(stats[1]) == 50 * 8
    assert pairs == 50 * 8              # every pair computed exactly once
    assert np.abs(np.asarray(total - (shared + routed))).max() < 5e-5


def test_the_dense_layer_is_counted_once_too(params):
    """The model's leading dense layer holds no share: the same layer
    under another rank's deployment, bit for bit."""
    x = jnp.asarray(np.random.default_rng(6).normal(size=(1, 12, 32)),
                    jnp.float32)
    outs = []
    for rank in (0, 1, 7):
        model = _model(deployment={"rank": rank})
        rot = kimi_k2.rotary(model.dims, jnp.arange(12)[None])
        blk = model.bind({"params": params}).blocks[0]
        assert not blk.experts
        outs.append(np.asarray(blk.sequence(x, None, rot)[0]))
    assert np.array_equal(outs[0], outs[1]) and np.array_equal(outs[0],
                                                               outs[2])


def test_routing_is_sigmoid_plus_bias_top_8_written_out():
    """The program's and the reference's routing against numpy: scores
    ``sigmoid(m W)``, the 8 largest of ``s + b`` selected, weights the
    selected scores over their sum, times 2.827; the bias selects and
    weighs nothing."""
    p, m = _layer_inputs(n=40, E=384)
    s = 1 / (1 + np.exp(-(np.asarray(m, np.float64)
                          @ np.asarray(p["router"], np.float64))))
    c = s + np.asarray(p["router_bias"], np.float64)
    want_sel = np.sort(np.argsort(-c, axis=-1)[:, :8], axis=-1)
    picked = np.take_along_axis(s, want_sel, axis=-1)
    want_w = 2.827 * picked / picked.sum(-1, keepdims=True)
    assert (np.sort(np.argsort(-s, axis=-1)[:, :8], -1) != want_sel).any()
    got = (moe.route(m, p["router"], p["router_bias"], top_k=8,
                     route_scale=2.827, n_group=1, topk_group=1),
           ref.route(m, p, ROUTING))
    for sel, w in got:
        order = np.argsort(np.asarray(sel), axis=-1)
        assert np.array_equal(np.take_along_axis(np.asarray(sel), order, -1),
                              want_sel)
        assert np.abs(np.take_along_axis(np.asarray(w), order, -1)
                      - want_w).max() < 1e-5


# ---- counters ---------------------------------------------------------------

def test_the_engines_counters_follow_a_hand_count(params, sequences):
    """Five ``latent`` layers and no other kind: ``serve_cache_bytes`` is
    the module's own count (rows of 128 padded features, no V array) and
    the whole cache; rows are read and fetched in every layer under
    ``kind="latent"``; no state is moved; latent attention's two forms
    are counted where they are traced."""
    names = ['serve_cache_rows_read_total{kind="latent"}',
             'serve_cache_rows_fetched_total{kind="latent"}',
             'serve_state_bytes_total{whose="all"}',
             'moe_pairs_total{where="held"}',
             'moe_pairs_total{where="absent"}', "moe_expert_slots_total",
             'lm_latent_attention_total{impl="expanded"}',
             'lm_latent_attention_total{impl="absorbed"}',
             'lm_position_scaling_total{kind="yarn"}']
    before = [_counter(n) for n in names]
    # a cache length no other test uses: its programs are traced here
    engine = DecodeEngine(_model(), params, slots=3, cache_len=96)
    engine.prefill_many([(0, sequences[0, :5], 1), (2, sequences[1, :19], 1)])
    engine.decode(busy=[0, 2])          # positions 5 and 19
    engine.decode(busy=[2])             # position 20; slot 0 still live
    (read, fetched, state, held, absent, slots, expanded, absorbed,
     tables) = (_counter(n) - b for n, b in zip(names, before))
    assert read == 5 * ((6 + 20) + 21)  # five layers
    assert fetched == 5 * 2 * 3 * 96    # the CPU's chain reads every row
    assert state == 0
    # prefill: 24 prompt tokens; two steps of two live slots; top 3; 4
    # expert layers (the dense layer routes nothing)
    assert held + absent == (24 + 2 + 2) * 3 * 4
    assert slots == 2 * 4 * 4
    # two prefill programs (buckets of 8 and of 32) and one decode
    # program traced: five layers each, one rotary table a program
    assert (expanded, absorbed, tables) == (10, 5, 3)
    assert engine.smodel.cache_slot_bytes(96) == (96 * 128 * 4,) * 5
    gauges = obs_metrics.registry().snapshot()["gauges"]
    assert gauges['serve_cache_bytes{kind="latent"}']["value"] == \
        3 * 5 * 96 * 128 * 4 == engine.cache_bytes
    assert all(c.size == 0 for c in engine._cv)
    assert engine.layers_without_rows_by_position == 5


# ---- what refuses, and what holds -----------------------------------------

def test_the_cells_configuration_builds_the_cells_model():
    """benchmarks/configs/kimi_k2_5_ep32.json through the one
    constructor: a dense layer and four expert layers, 12 of 384 experts
    from id 0 with no group limit, 64 heads, a compressed query of 1,536,
    a latent row of 512 + 64 features kept as 640, YaRN x 64 over 4,096,
    and the cache the cell's arithmetic says: 5.24 GB of rows at 80 slots
    x 10,240 and nothing else."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    model = build_model_from_config(os.path.join(
        root, "benchmarks", "configs", "kimi_k2_5_ep32.json"))
    c = model.dims
    assert (c.n_layers, c.n_dense_layers, c.experts_held, c.n_routed,
            c.first_expert, c.top_k, c.n_group, c.topk_group) == (
        5, 1, 12, 384, 0, 8, 1, 1)
    assert (c.n_heads, c.q_rank, c.kv_rank, c.rope_dim, c.nope_dim, c.v_dim,
            c.row_dim, c.d_ff, c.d_expert, c.d_shared) == (
        64, 1536, 512, 64, 128, 128, 640, 18432, 2048, 2048)
    assert c.yarn == kimi_k2.Yarn(64, 4096, 32, 1, 1, 1)
    assert round(c.softmax_scale, 6) == 0.144680 and c.route_scale == 2.827
    assert model.cache_rows(10240) == (("latent", 10240),) * 5
    held = np.asarray(model.cache_slot_bytes(10240)) * 80
    assert round(held.sum() / 1e9, 2) == 5.24
    shapes = jax.eval_shape(lambda: model.init_cache(80, 10240))
    assert sum(x.size * x.dtype.itemsize
               for x in jax.tree.leaves(shapes)) == held.sum()
    assert model.expert_slots == 12 * 4
    assert model.attn_block == 512 and model.prefill_positions_max == 10240


@pytest.mark.parametrize("key, value", [
    ("scoring_func", "softmax"), ("topk_method", "greedy"),
    ("tie_word_embeddings", True), ("attention_bias", True),
    ("hidden_act", "gelu"), ("num_nextn_predict_layers", 1),
    ("moe_layer_freq", 2)])
def test_a_configuration_the_block_does_not_compute_is_refused(key, value):
    with pytest.raises(ValueError, match=key):
        _model(**{key: value})


@pytest.mark.parametrize("scaling", [
    {"type": "linear", "factor": 4}, {"rope_type": "llama3", "factor": 8},
    {"type": "dynamic", "factor": 2}])
def test_a_position_scaling_other_than_yarn_is_refused_by_name(scaling):
    with pytest.raises(ValueError, match="rope_scaling of type"):
        _model(rope_scaling=scaling)


def test_the_registry_names_the_family():
    with pytest.raises(ValueError, match="kimi_k2"):
        build_model_from_config({"model_type": "deepseek_v4"})


# ---- one constructor, from a configuration file ----------------------------

def test_the_cli_serves_the_model_from_a_configuration_file(tmp_path):
    """``tools/serve_lm.py --model_config`` builds the model by the
    constructor the benchmark's family calls, initialises a snapshot,
    promotes it and drives requests through the batcher; what moves the
    stacked K/V pair is refused by name: exit 2."""
    import importlib.util
    path = tmp_path / "tiny_kimi_k2.json"
    path.write_text(json.dumps(TINY))
    built = build_model_from_config(str(path), dtype=jnp.float32,
                                    param_dtype=jnp.float32)
    assert built == _model(attn_block=kimi_k2.ATTN_TILE)
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
