"""The bailing_hybrid model (models/bailing_hybrid.py), the gated delta
rule under a decay a key channel (ops/linear_attention.py), latent
attention's two forms (ops/attention.py), group-limited routing
(ops/moe.py) and the model's path through DecodeEngine and
ContinuousBatcher — a cache whose layers hold latent rows or a recurrent
state — against the plain reference (benchmarks/reference/
bailing_hybrid.py) at tiny widths on the CPU, float32 compute so that
the comparison is of the mathematics: a dense layer, then one whole
period of five KDA layers and an MLA layer."""

import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import served_families as fam

from benchmarks.reference import bailing_hybrid as ref
from distributedtensorflowexample_tpu.models import build_model_from_config
from distributedtensorflowexample_tpu.obs import metrics as obs_metrics
from distributedtensorflowexample_tpu.ops import attention as attention_op
from distributedtensorflowexample_tpu.ops import linear_attention as la
from distributedtensorflowexample_tpu.ops import moe
from distributedtensorflowexample_tpu.serving.engine import DecodeEngine
from distributedtensorflowexample_tpu.serving.queue import (
    ContinuousBatcher, RequestQueue)

KINDS = ["state"] * 5 + ["latent", "state"]
TOL = 2e-5      # float32 against float32 at HIGHEST: summation order only

FAMILY = "bailing_hybrid"
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
    """200 positions: three whole chunks of the scan and a part of one;
    latent attention expanded on both sides."""
    got = _model().apply({"params": params}, jnp.asarray(sequences))
    assert np.abs(np.asarray(got) - ref_logits).max() < TOL


@pytest.mark.parametrize("drop", ["w_gate", "w_qkvu", "dt_bias", "a_log",
                                  "router_bias", "norm_c"])
def test_the_tolerance_sees_a_dropped_part(params, sequences, ref_logits,
                                           drop):
    """The comparison is tight enough: the MLA layer's head-wise gate at
    a half, KDA's output gate at a half, a decay without its bias or
    with every head's rate at one, a router without its bias, a latent
    norm without its scale — each moves the logits by ten times the
    tolerance or more (the weights are small here: the gates sit near a
    half either way)."""
    flat = jax.tree_util.tree_map_with_path(
        lambda path, x: (jnp.ones_like(x) if drop == "norm_c"
                         else jnp.zeros_like(x))
        if path[-1].key == drop else x, params)
    got = _model().apply({"params": flat}, jnp.asarray(sequences[:1]))
    assert np.abs(np.asarray(got) - ref_logits[:1]).max() > 10 * TOL


# ---- the gated delta rule under a decay a channel ---------------------------

def _rule_inputs(B=2, T=150, H=3, Dk=16, Dv=8, seed=0, lower=-5.0):
    """``g [B, T, H, Dk]`` in (lower, 0); channel 0 of every head sits AT
    the lower bound at every position (exp(-5) a step: 320 over a chunk
    of 64, which float32 holds only in blocks), channel 1 hardly decays."""
    rng = np.random.default_rng(seed)
    unit = lambda x: x / np.linalg.norm(x, axis=-1, keepdims=True)
    f = lambda *s: rng.normal(size=s).astype(np.float32)
    q, k = unit(f(B, T, H, Dk)) / 4, unit(f(B, T, H, Dk))
    g = lower / (1 + np.exp(-3 * f(B, T, H, Dk)))
    g[..., 0], g[..., 1] = lower, -1e-3
    beta = 1 / (1 + np.exp(-f(B, T, H)))
    return tuple(jnp.asarray(x) for x in (q, k, f(B, T, H, Dv), g, beta,
                                          f(B, H, Dk, Dv)))


@pytest.mark.parametrize("lengths", [None, (150, 77), (64, 1)])
def test_the_chunked_form_is_the_token_form_under_a_decay_a_channel(lengths):
    """The same outputs at every live position and the same final state,
    from a state that is not zero, with a channel held at the lower
    bound for whole chunks (nothing overflows: every output is finite),
    for lengths that are and are not whole chunks; past a row's length
    (``live`` false) neither form decays or writes."""
    q, k, v, g, beta, S0 = _rule_inputs()
    T = q.shape[1]
    live = None if lengths is None else jnp.asarray(
        np.arange(T)[None] < np.asarray(lengths)[:, None])
    o, S = la.chunked_sequence(q, k, v, g, beta, S0, live)
    assert np.isfinite(np.asarray(o)).all() and np.isfinite(
        np.asarray(S)).all()
    St, outs = S0, []
    for t in range(T):
        o_t, St = la.recurrent_step(
            q[:, t], k[:, t], v[:, t], g[:, t], beta[:, t], St,
            None if live is None else live[:, t])
        outs.append(o_t)
    seen = np.ones((2, T), bool) if live is None else np.asarray(live)
    diff = np.abs(np.asarray(o - jnp.stack(outs, 1)))
    assert diff[seen].max() < TOL and np.abs(np.asarray(S - St)).max() < TOL
    if lengths is not None:     # ... and that state is the one AT the length
        n = lengths[1]
        _, cut = la.chunked_sequence(*(x[1:, :n] for x in (q, k, v, g, beta)),
                                     S0[1:])
        assert np.abs(np.asarray(S[1] - cut[0])).max() < TOL


def test_the_scalar_decay_is_the_broadcast_case():
    """A decay a head, handed over as one number a head or as that
    number repeated over the head's channels: the same outputs and
    states in both forms (ONE definition)."""
    q, k, v, g, beta, S0 = _rule_inputs(T=70)
    g = g[..., 2]                                           # [B, T, H]
    wide = jnp.broadcast_to(g[..., None], q.shape)
    o1, S1 = la.chunked_sequence(q, k, v, g, beta, S0)
    o2, S2 = la.chunked_sequence(q, k, v, wide, beta, S0)
    assert np.abs(np.asarray(o1 - o2)).max() < TOL
    assert np.abs(np.asarray(S1 - S2)).max() < TOL
    a, Sa = la.recurrent_step(q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                              beta[:, 0], S0)
    b, Sb = la.recurrent_step(q[:, 0], k[:, 0], v[:, 0], wide[:, 0],
                              beta[:, 0], S0)
    assert np.abs(np.asarray(a - b)).max() < 1e-6
    assert np.abs(np.asarray(Sa - Sb)).max() < 1e-6


def test_the_token_form_is_the_equations():
    """Against the recurrence written out in float64, row i of a head's
    state decaying by exp(g[i])."""
    q, k, v, g, beta, S0 = (np.asarray(x, np.float64)
                            for x in _rule_inputs(B=1, T=40))
    S, want = S0[0], []
    for t in range(40):
        S = np.exp(g[0, t])[:, :, None] * S
        d = beta[0, t][:, None] * (v[0, t] - np.einsum("hkv,hk->hv", S,
                                                       k[0, t]))
        S = S + k[0, t][:, :, None] * d[:, None, :]
        want.append(np.einsum("hkv,hk->hv", S, q[0, t]))
    o, S_got = la.chunked_sequence(*(jnp.asarray(x, jnp.float32) for x in (
        q, k, v, g, beta, S0)))
    assert np.abs(np.asarray(o[0]) - np.stack(want)).max() < TOL
    assert np.abs(np.asarray(S_got[0]) - S).max() < TOL


def test_the_token_steps_kernel_takes_a_decay_a_channel():
    """``ops/pallas/delta_step.py`` interpreted, at the widths it tiles
    (heads of 128 x 128), in its per-channel body: the outputs and states
    of the lines it stands in for on a TPU, and a slot that is not live
    gets its state back bit for bit."""
    from distributedtensorflowexample_tpu.ops.pallas import delta_step
    rng = np.random.default_rng(0)
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
    q, k, v = f(3, 8, 128) * 0.1, f(3, 8, 128) * 0.1, f(3, 8, 128)
    g = -5 * jax.nn.sigmoid(3 * f(3, 8, 128))
    beta, S0 = jax.nn.sigmoid(f(3, 8)), f(3, 8, 128, 128)
    live = jnp.asarray([True, False, True])
    want_o, want_S = la.recurrent_step(q, k, v, g, beta, S0, live)
    o, S = delta_step.delta_step(q, k, v, g, beta, jnp.array(S0), live,
                                 interpret=True)
    seen = np.asarray(live)
    assert np.abs(np.asarray(o - want_o))[seen].max() < 1e-5
    assert np.abs(np.asarray(S - want_S)).max() < 1e-5
    assert np.array_equal(np.asarray(S[1]), np.asarray(S0[1]))


# ---- latent attention's two forms -------------------------------------------

def test_the_absorbed_form_is_the_expanded_form(params, sequences):
    """The MLA layer alone: a sequence attended in the expanded form
    (per-head keys of 12 and values of 8 made from the rows), and the
    same positions one token at a time in the absorbed form against the
    rows the sequence left — the same outputs, the same rows."""
    model = _model()
    block = {"params": params["block5"]}
    blk = type(model.bind({"params": params}).blocks[5])(
        model.dims, True, True, jnp.float32, jnp.float32)
    x = jnp.asarray(np.random.default_rng(2).normal(size=(2, 40, 32)),
                    jnp.float32)
    want, (rows, none), _ = blk.apply(block, x, method="sequence")
    assert none is None and rows.shape == (2, 40, 128)     # 16 + 4 -> 128
    assert np.array_equal(np.asarray(rows[..., 20:]), np.zeros((2, 40, 108)))
    ck = jnp.zeros((2, 64, 128))
    for t in range(40):
        got, ck, _, _ = blk.apply(block, x[:, t], ck, jnp.zeros((0,)),
                                  jnp.full((2,), t, jnp.int32), method="step")
        if t:       # position 0 is a parked slot's: it goes to no expert
            assert np.abs(np.asarray(got - want[:, t])).max() < TOL, t
    assert np.abs(np.asarray(ck[:, :40] - rows)).max() < 1e-6


def test_the_latent_kernel_reads_one_shared_row(monkeypatch):
    """``latent_decode_attention`` interpreted: 32 heads over rows of 640
    whose first 512 are the values, a slot's live rows only — the einsum
    chain's attention, whatever lies in the rows past them."""
    from distributedtensorflowexample_tpu.ops.pallas import (
        decode_attention as ragged)
    rng = np.random.default_rng(3)
    S, R, H, D, V = 3, 256, 32, 640, 512
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
    q, rows = f(S, H, D) * 0.3, f(S, R, D)
    lengths = jnp.asarray([1, 100, 256], jnp.int32)
    assert ragged.latent_fetch_block(8192, 640, 512) == 128
    assert ragged.latent_fetch_block(8192, 576, 500) == 0
    assert attention_op.latent_fetch_block(8192, 640, 512) == 0     # the CPU
    want = attention_op.latent_decode_attention(
        q, rows, lengths, v_dim=V, scale=192 ** -0.5)
    got = ragged.latent_decode_attention(
        q, rows, lengths, v_dim=V, scale=192 ** -0.5, block=128,
        interpret=True)
    assert got.shape == (S, H, V)
    assert np.abs(np.asarray(got - want)).max() < 1e-5
    # rows past a slot's length weigh nothing (stale rows are finite),
    # and blocks past its last live block are never fetched at all
    poisoned = rows.at[1, 100:128].set(1e4).at[1, 128:].set(jnp.nan)
    again = ragged.latent_decode_attention(
        q, poisoned, lengths, v_dim=V, scale=192 ** -0.5, block=128,
        interpret=True)
    assert np.abs(np.asarray(again[1] - want[1])).max() < 1e-5


def test_grouped_attention_takes_values_narrower_than_keys():
    """Keys of 24 features, values of 16 (the expanded form's shapes):
    one tile, the tiled walk and a hand-made softmax agree."""
    rng = np.random.default_rng(4)
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
    q, k, v = f(2, 96, 4, 24), f(2, 96, 4, 24), f(2, 96, 4, 16)
    one = attention_op.grouped_attention(q, k, v, block=128)
    walked = attention_op.grouped_attention(q, k, v, block=32)
    s = jnp.einsum("bthd,bshd->bhts", q, k) / np.sqrt(24)
    s = jnp.where(np.tril(np.ones((96, 96), bool)), s, -jnp.inf)
    want = jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(s, axis=-1), v)
    assert one.shape == (2, 96, 4, 16)
    assert np.abs(np.asarray(one - want)).max() < 1e-5
    assert np.abs(np.asarray(walked - want)).max() < 1e-5


# ---- prefill, then decode, through the engine ------------------------------

def test_engine_prefill_then_decode_logits_match_the_reference(
        params, sequences, ref_logits):
    """Three slots; prompts of 5, 70 and 67 tokens (none a whole chunk;
    two in buckets of 128 and one of 8); 100 decode steps; a request
    admitted mid-decode.  Every logit the engine gives — prefill
    expanded, decode absorbed against the cache — is the reference's
    full forward's at that position."""
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


def test_a_state_kept_in_bfloat16_fails_the_tolerance(params, sequences,
                                                      ref_logits):
    """The comparison is tight enough to see the recurrent state's type:
    the same engine with its six states rounded to bfloat16 after every
    step is a thousand tolerances off within 30 steps."""
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
    assert worst > 100 * TOL, worst


def test_batcher_serves_the_references_tokens(params):
    """Seven requests through RequestQueue and ContinuousBatcher on three
    slots (so four are admitted mid-decode, into slots others have
    used): every served token is the reference's best at its position."""
    engine = DecodeEngine(_model(), params, slots=3, cache_len=128)
    queue = RequestQueue(engine.vocab)
    batcher = ContinuousBatcher(engine, queue, slo_ms=0, eos_id=None)
    rng = np.random.default_rng(11)
    reqs = [queue.submit(rng.integers(0, 97, n).astype(np.int32), new,
                         rid=f"r{i}")
            for i, (n, new) in enumerate([(5, 30), (21, 25), (9, 12),
                                          (70, 20), (3, 40), (14, 9),
                                          (27, 18)])]
    while not all(r.done.is_set() for r in reqs):
        batcher.step()
    for r in reqs:
        assert r.outcome == "ok" and len(r.tokens) == r.max_new
        gaps = ref.served_token_gaps(params, r.prompt, np.asarray(r.tokens),
                                     TINY, pad_to=16)
        assert gaps["widest_over_all"] < 1e-4 and gaps["tokens"] == r.max_new


def test_a_late_readback_serves_the_synchronous_orders_tokens_and_counts(
        params, serve_backlog):
    """The same seven requests on three slots, the next step handed to
    the device before the last one's tokens are read (the counts ride
    behind the tokens in the one array read late) and then a read-back
    at every step: request for request the same tokens, boundary for
    boundary the same ``step()``, and the host's and the model's
    counters total the same over the run."""
    engine = DecodeEngine(_model(), params, slots=3, cache_len=128)
    rng = np.random.default_rng(11)
    plan = [(rng.integers(0, 97, n).astype(np.int32), new)
            for n, new in [(5, 30), (21, 25), (9, 12), (70, 20), (3, 40), (14, 9), (27, 18)]]
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
    steps = 'serve_decode_steps_total{readback="%s"}'
    assert late.moved[steps % "late"] > late.moved[steps % "same_step"]
    assert steps % "late" not in sync.moved


# ---- the expert layer ------------------------------------------------------

def _layer_inputs(n=50, seed=2):
    """A tiny expert layer's weights, uncut (32 experts in 8 groups), and
    n tokens."""
    rng = np.random.default_rng(seed)
    d, f, E = 32, 16, 32
    normal = lambda *s: jnp.asarray(rng.normal(size=s) * 0.2, jnp.float32)
    p = {"router": normal(d, E), "router_bias": normal(E) * 0.2,
         "shared_gate": normal(d, f), "shared_up": normal(d, f),
         "shared_down": normal(f, d), "experts_gate": normal(E, d, f),
         "experts_up": normal(E, d, f), "experts_down": normal(E, f, d)}
    return p, normal(n, d) * 5


def _route(m, p):
    return moe.route(m, p["router"], p["router_bias"], top_k=3,
                     route_scale=2.5, n_group=8, topk_group=4)


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """Over all eight shares of a 32-expert layer, each share one routing
    group: the parts the shares give (each computed by the program's
    layer, told which four experts it holds), with the shared expert
    counted once, are the uncut reference's layer; and no token reaches
    more than topk_group = 4 of the 8 shares."""
    p, m = _layer_inputs()
    uncut = {**TINY, "num_experts": 32, "deployment": {"rank": 0}}
    shared, routed = ref.expert_layer(m, p, uncut, ref.make_matmul("f32"))
    sel, w = _route(m, p)
    assert (np.asarray([len(set(r // 4)) for r in np.asarray(sel)]) <= 4
            ).all()
    total = moe.gated_ffn(m, p["shared_gate"], p["shared_up"],
                          p["shared_down"])
    pairs = 0
    for rank in range(8):
        held = slice(4 * rank, 4 * rank + 4)
        part, stats = moe.expert_ffn(
            m, sel, w, p["experts_gate"][held], p["experts_up"][held],
            p["experts_down"][held], first_expert=4 * rank,
            experts_known=32)
        _, theirs = ref.expert_layer(       # ... the reference's share
            m, {**p, **{k: p[k][held] for k in (
                "experts_gate", "experts_up", "experts_down")}},
            {**TINY, "num_experts": 4, "deployment": {"rank": rank}},
            ref.make_matmul("f32"))
        assert np.abs(np.asarray(part - theirs)).max() < TOL
        total, pairs = total + part, pairs + int(stats[0])
        assert int(stats[0]) + int(stats[1]) == 50 * 3
    assert pairs == 50 * 3              # every pair computed exactly once
    assert np.abs(np.asarray(total - (shared + routed))).max() < 5e-5


def test_the_dense_layer_is_counted_once_too(params, sequences):
    """The model's leading dense layer holds no share: the same layer
    under another rank's deployment, bit for bit."""
    x = jnp.asarray(np.random.default_rng(6).normal(size=(1, 12, 32)),
                    jnp.float32)
    outs = []
    for rank in (0, 1, 7):
        model = _model(deployment={"rank": rank})
        blk = model.bind({"params": params}).blocks[0]
        assert not blk.experts and not blk.latent
        outs.append(np.asarray(blk.sequence(x)[0]))
    assert np.array_equal(outs[0], outs[1]) and np.array_equal(outs[0],
                                                               outs[2])


def test_group_limited_routing_against_a_hand_made_routing():
    """Scores chosen by hand: 8 experts in 4 groups of 2, 2 groups kept,
    top 2.  The bias selects (groups and experts) and weighs nothing."""
    logit = lambda s: np.log(s / (1 - s))
    scores = np.asarray([[0.8, 0.5, 0.6, 0.3, 0.7, 0.1, 0.2, 0.1],
                         [0.2, 0.1, 0.5, 0.4, 0.6, 0.4, 0.9, 0.05]],
                        np.float32)
    m = jnp.eye(2, dtype=jnp.float32)
    kw = dict(top_k=2, route_scale=2.5, n_group=4, topk_group=2)
    sel, w = moe.route(m, jnp.asarray(logit(scores)), None, **kw)
    # token 0: groups score 1.3, 0.9, 0.8, 0.3 -> groups 0 and 1; 0.7 in
    # group 2 is out though it is the second best expert
    assert sorted(sel[0].tolist()) == [0, 2]
    # token 1: groups 0.3, 0.9, 1.0, 0.95 -> groups 2 and 3
    assert sorted(sel[1].tolist()) == [4, 6]
    want = 2.5 * np.asarray([[0.8, 0.6], [0.9, 0.6]]) / np.asarray(
        [[1.4], [1.5]])
    assert np.abs(np.sort(np.asarray(w))[:, ::-1] - want).max() < 1e-6
    # a bias lifts group 3 over group 1 for token 0 and then selects
    # expert 6 — whose weight is its score, not its biased score
    bias = jnp.asarray([0, 0, 0, 0, 0, 0, 0.5, 0.5], jnp.float32)
    sel, w = moe.route(m, jnp.asarray(logit(scores)), bias, **kw)
    assert sorted(sel[0].tolist()) == [0, 6]
    assert np.abs(np.sort(np.asarray(w[0]))[::-1]
                  - 2.5 * np.asarray([0.8, 0.2])).max() < 1e-6
    got = ref.route(m @ jnp.asarray(logit(scores)), {
        "router": jnp.eye(8), "router_bias": bias},
        {"num_experts_per_tok": 2, "n_group": 4, "topk_group": 2,
         "norm_topk_prob": True, "routed_scaling_factor": 2.5})
    assert np.array_equal(np.sort(np.asarray(got[0])), np.sort(
        np.asarray(sel)))


@pytest.mark.parametrize("experts, top_k, score_func, biased, scale", [
    (256, 4, "sigmoid", True, 2.448),       # trinity_large_ep8's router
    (512, 10, "softmax", False, 1.0),       # qwen3_next_ep8's
])
def test_one_group_is_the_routing_as_it_was(experts, top_k, score_func,
                                            biased, scale):
    """``n_group`` 1 (what the two configurations in the benchmark route
    by): the same program text and the same selection and weights, bit
    for bit, as a copy of the function as it stood before groups came."""
    def before(m, router_kernel, router_bias, *, top_k, route_scale,
               route_norm=True, score_func="sigmoid"):
        with jax.named_scope("moe.route"):
            s = jnp.dot(m, router_kernel, preferred_element_type=jnp.float32)
            s = (jax.nn.sigmoid(s) if score_func == "sigmoid"
                 else jax.nn.softmax(s, axis=-1))
            _, sel = jax.lax.top_k(
                s if router_bias is None
                else s + router_bias.astype(jnp.float32), top_k)
            w = jnp.take_along_axis(s, sel, axis=-1)
            if route_norm:
                w = w / jnp.sum(w, axis=-1, keepdims=True)
            return sel.astype(jnp.int32), route_scale * w

    rng = np.random.default_rng(0)
    args = (jnp.asarray(rng.normal(size=(40, 64)), jnp.bfloat16),
            jnp.asarray(rng.normal(size=(64, experts)) * 0.2, jnp.bfloat16),
            jnp.asarray(rng.normal(size=(experts,)) * 0.01, jnp.float32)
            if biased else None)
    kw = dict(top_k=top_k, route_scale=scale, score_func=score_func)
    text = lambda f: jax.jit(lambda *a: f(*a, **kw)).lower(*args).as_text()
    assert text(moe.route) == text(before)
    for a, b in zip(moe.route(*args, **kw), before(*args, **kw)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


# ---- counters ---------------------------------------------------------------

def test_the_engines_counters_follow_a_hand_count(params, sequences):
    """``serve_cache_bytes`` is the module's own count by kind (latent
    rows of 128 padded features here, no V array); rows are read and
    fetched in the one latent layer only, under ``kind="latent"``;
    ``serve_state_bytes_total`` is state layers x bytes a slot x 2 a
    decode step; the recurrence's and latent attention's two forms are
    counted where they are traced."""
    names = ['serve_state_bytes_total{whose="all"}',
             'serve_cache_rows_read_total{kind="latent"}',
             'serve_cache_rows_fetched_total{kind="latent"}',
             'serve_cache_rows_read_total{kind="state"}',
             'moe_pairs_total{where="held"}',
             'moe_pairs_total{where="absent"}', "moe_expert_slots_total",
             'lm_linear_attention_total{impl="chunked"}',
             'lm_linear_attention_total{impl="recurrent"}',
             'lm_latent_attention_total{impl="expanded"}',
             'lm_latent_attention_total{impl="absorbed"}']
    before = [_counter(n) for n in names]
    # a cache length no other test uses: its programs are traced here
    engine = DecodeEngine(_model(), params, slots=3, cache_len=96)
    engine.prefill_many([(0, sequences[0, :5], 1), (2, sequences[1, :19], 1)])
    engine.decode(busy=[0, 2])          # positions 5 and 19
    engine.decode(busy=[2])             # position 20; slot 0 still live
    (every, read, fetched, none, held, absent, slots, chunked,
     recurrent, expanded, absorbed) = (
        _counter(n) - b for n, b in zip(names, before))
    # a slot's state in one layer: S [4, 8, 8] f32, conv [3, 96] f32 here
    state = 4 * 4 * 8 * 8 + 4 * 3 * (3 * 4 * 8)
    assert every == 2 * 3 * 6 * state * 2
    assert read == (6 + 20) + 21 and none == 0      # one latent layer
    assert fetched == 2 * 3 * 96        # the CPU's chain reads every row
    # prefill: 24 prompt tokens; two steps of two live slots; top 3; 6
    # expert layers (the dense layer routes nothing)
    assert held + absent == (24 + 2 + 2) * 3 * 6
    assert slots == 2 * 4 * 6
    # two prefill programs (buckets of 8 and of 32) and one decode
    # program traced: six KDA layers and one MLA layer each
    assert (chunked, recurrent, expanded, absorbed) == (12, 6, 2, 1)
    gauges = obs_metrics.registry().snapshot()["gauges"]
    assert gauges['serve_cache_bytes{kind="latent"}']["value"] == \
        3 * 96 * 128 * 4
    assert gauges['serve_cache_bytes{kind="state"}']["value"] == \
        3 * 6 * state
    assert engine.cache_bytes == 3 * (96 * 128 * 4 + 6 * state)


# ---- what refuses, and what holds -----------------------------------------

@pytest.mark.parametrize("cache_len, ladder", [
    (8192, (256, 512, 1024, 2048, 3072, 4096, 8192)),   # the benchmark's cell
    (1000, (256, 512, 1000)),
    (256, None),                        # the engine's powers of two
])
def test_the_stated_ladder(cache_len, ladder):
    assert _model().prefill_buckets(cache_len) == ladder


def test_the_cells_configuration_builds_the_cells_model():
    """benchmarks/configs/ling3_flash_ep8.json through the one
    constructor: a dense layer and one period, 64 of 512 experts from id
    0 in 8 groups of which 4 stay, 32 heads, a latent row of 512 + 64
    features kept as 640, and the cache the cell's arithmetic says: 2.68
    GB of rows and 3.33 GB of state at 256 slots x 8,192."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    model = build_model_from_config(os.path.join(
        root, "benchmarks", "configs", "ling3_flash_ep8.json"))
    c = model.dims
    assert (c.n_layers, c.n_dense_layers, c.experts_held, c.n_routed,
            c.first_expert, c.top_k, c.n_group, c.topk_group) == (
        7, 1, 64, 512, 0, 8, 8, 4)
    assert [c.is_latent(i) for i in range(7)] == [False] * 5 + [True, False]
    assert (c.n_heads, c.head_dim, c.kv_rank, c.rope_dim, c.nope_dim,
            c.v_dim, c.row_dim, c.decay_lower) == (
        32, 128, 512, 64, 128, 128, 640, -5.0)
    held = np.asarray(model.cache_slot_bytes(8192)) * 256
    kinds = [kind for kind, _ in model.cache_rows(8192)]
    assert round(sum(h for h, k in zip(held, kinds) if k == "latent") / 1e9,
                 2) == 2.68
    assert round(sum(h for h, k in zip(held, kinds) if k == "state") / 1e9,
                 2) == 3.33
    shapes = jax.eval_shape(lambda: model.init_cache(256, 8192))
    assert sum(x.size * x.dtype.itemsize
               for x in jax.tree.leaves(shapes)) == held.sum()
    assert model.expert_slots == 64 * 6


@pytest.mark.parametrize("key, value", [
    ("q_lora_rank", 1536), ("use_kda_lora", True), ("kda_safe_gate", False),
    ("topk_method", "greedy"), ("rope_interleave", False)])
def test_a_configuration_the_block_does_not_compute_is_refused(key, value):
    with pytest.raises(ValueError, match=key):
        _model(**{key: value})


def test_a_clamped_swiglu_in_a_kept_layer_is_refused():
    limits = [0] * 34 + [4] * 8
    _model(expert_swiglu_limit_list=limits)         # layers 0-6: no clamp
    with pytest.raises(ValueError, match="clamped SwiGLU"):
        _model(expert_swiglu_limit_list=[0, 0, 4] + [0] * 39)
    with pytest.raises(ValueError, match="no model is built"):
        build_model_from_config({"model_type": "bailing_moe_v3"})


# ---- one constructor, from a configuration file ----------------------------

def test_the_cli_serves_the_model_from_a_configuration_file(tmp_path):
    """``tools/serve_lm.py --model_config`` builds the model by the
    constructor the benchmark's family calls, initialises a snapshot,
    promotes it and drives requests through the batcher; what rolls a
    cache back is refused by name: exit 2."""
    import importlib.util
    path = tmp_path / "tiny_bailing_hybrid.json"
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
