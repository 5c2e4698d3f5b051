"""The qwen3_next model (models/qwen3_next.py), the gated delta rule's
two forms (ops/linear_attention.py), the softmax router (ops/moe.py) and
the model's path through DecodeEngine and ContinuousBatcher — a cache
whose layers hold K/V rows or a recurrent state — against the plain
reference (benchmarks/reference/qwen3_next.py) at tiny widths on the CPU,
float32 compute so that the comparison is of the mathematics: two whole
periods of (linear, linear, linear, full)."""

import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import served_families as fam

from benchmarks.reference import qwen3_next as ref
from distributedtensorflowexample_tpu.models import build_model_from_config
from distributedtensorflowexample_tpu.obs import metrics as obs_metrics
from distributedtensorflowexample_tpu.ops import linear_attention as la
from distributedtensorflowexample_tpu.ops import moe
from distributedtensorflowexample_tpu.ops.attention import decode_attention
from distributedtensorflowexample_tpu.serving.engine import DecodeEngine
from distributedtensorflowexample_tpu.serving.queue import (
    ContinuousBatcher, RequestQueue)

TOL = 2e-5      # float32 against float32 at HIGHEST: summation order only

FAMILY = "qwen3_next"
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


# ---- the training-shape forward -------------------------------------------

def test_forward_matches_the_reference(params, sequences, ref_logits):
    """200 positions: three whole chunks of the scan and a part of one."""
    got = _model().apply({"params": params}, jnp.asarray(sequences))
    assert np.abs(np.asarray(got) - ref_logits).max() < TOL


# ---- the gated delta rule's two forms ---------------------------------------

def _rule_inputs(B=2, T=150, H=3, Dk=16, Dv=8, seed=0):
    rng = np.random.default_rng(seed)
    unit = lambda x: x / np.linalg.norm(x, axis=-1, keepdims=True)
    f = lambda *s: rng.normal(size=s).astype(np.float32)
    q, k = unit(f(B, T, H, Dk)) / 4, unit(f(B, T, H, Dk))
    g = -0.3 * np.exp(f(B, T, H))
    beta = 1 / (1 + np.exp(-f(B, T, H)))
    return tuple(jnp.asarray(x) for x in (q, k, f(B, T, H, Dv), g, beta,
                                          f(B, H, Dk, Dv)))


@pytest.mark.parametrize("lengths", [None, (150, 77), (64, 1)])
def test_the_chunked_form_is_the_token_form(lengths):
    """The same outputs at every live position and the same final state,
    from a state that is not zero, for lengths that are and are not whole
    chunks; past a row's length (``live`` false) neither form decays or
    writes, so its state is the one at its length."""
    q, k, v, g, beta, S0 = _rule_inputs()
    T = q.shape[1]
    live = None if lengths is None else jnp.asarray(
        np.arange(T)[None] < np.asarray(lengths)[:, None])
    o, S = la.chunked_sequence(q, k, v, g, beta, S0, live)
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


def test_the_token_form_is_the_equations():
    """Against the recurrence written out in float64."""
    q, k, v, g, beta, S0 = (np.asarray(x, np.float64)
                            for x in _rule_inputs(B=1, T=40))
    S, want = S0[0], []
    for t in range(40):
        S = np.exp(g[0, t])[:, None, None] * S
        d = beta[0, t][:, None] * (v[0, t] - np.einsum("hkv,hk->hv", S,
                                                       k[0, t]))
        S = S + k[0, t][:, :, None] * d[:, None, :]
        want.append(np.einsum("hkv,hk->hv", S, q[0, t]))
    o, S_got = la.chunked_sequence(*(jnp.asarray(x, jnp.float32) for x in (
        q, k, v, g, beta, S0)))
    assert np.abs(np.asarray(o[0]) - np.stack(want)).max() < TOL
    assert np.abs(np.asarray(S_got[0]) - S).max() < TOL


def test_the_token_steps_kernel_is_the_token_form():
    """``ops/pallas/delta_step.py`` interpreted, at the widths it tiles
    (heads of 128 x 128): the outputs and states of the lines it stands
    in for on a TPU, and a slot that is not live gets its state back bit
    for bit."""
    from distributedtensorflowexample_tpu.ops.pallas import delta_step
    assert delta_step.tiles(32, 128, 128) and not delta_step.tiles(4, 8, 8)
    rng = np.random.default_rng(0)
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
    q, k, v = f(3, 8, 128) * 0.1, f(3, 8, 128) * 0.1, f(3, 8, 128)
    g, beta, S0 = -jnp.abs(f(3, 8)), jax.nn.sigmoid(f(3, 8)), f(3, 8, 128,
                                                                128)
    live = jnp.asarray([True, False, True])
    want_o, want_S = la.recurrent_step(q, k, v, g, beta, S0, live)
    o, S = delta_step.delta_step(q, k, v, g, beta, jnp.array(S0), live,
                                 interpret=True)
    seen = np.asarray(live)
    assert np.abs(np.asarray(o - want_o))[seen].max() < 1e-5
    assert np.abs(np.asarray(S - want_S)).max() < 1e-5
    assert np.array_equal(np.asarray(S[1]), np.asarray(S0[1]))


def test_the_convolutions_two_forms_agree_and_keep_the_last_inputs():
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(2, 30, 5)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(4, 5)), jnp.float32)
    lengths = jnp.asarray([30, 2])
    y, state = la.causal_conv_sequence(x, w, lengths)
    s, ys = jnp.zeros((2, 3, 5)), []
    for t in range(30):
        y_t, s = la.causal_conv_step(x[:, t], w, s, live=t < lengths)
        ys.append(y_t)
    live = np.arange(30)[None] < np.asarray(lengths)[:, None]
    assert np.abs(np.asarray(y - jnp.stack(ys, 1)))[live].max() < 1e-6
    assert np.array_equal(np.asarray(state), np.asarray(s))
    # a 2-token prompt's state: one zero (before position 0), then both
    assert np.array_equal(np.asarray(state[1]),
                          np.concatenate([np.zeros((1, 5)), x[1, :2]]))


# ---- prefill, then decode, through the engine ------------------------------

def test_engine_prefill_then_decode_logits_match_the_reference(
        params, sequences, ref_logits):
    """Three slots; prompts of 5, 70 and 67 tokens (none a whole chunk;
    two in buckets of 128 and one of 8); 100 decode steps; a request
    admitted mid-decode.  Every logit the engine gives is the reference's
    full forward's at that position."""
    engine = DecodeEngine(_model(), params, slots=3, cache_len=256)
    assert [kind for kind, _ in engine.smodel.cache_rows(256)] == \
        ["state", "state", "state", "full"] * 2
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


# ---- the flat K/V rows and the ragged kernel --------------------------------

def test_the_ragged_kernel_reads_flat_rows_of_two_heads():
    """Two K/V heads of 128 kept as ``[S, rows * 2, 128]``: the kernel
    (interpreted) gives the einsum chain's attention over each slot's
    live rows, whatever lies in the rows past them."""
    from distributedtensorflowexample_tpu.ops.pallas import (
        decode_attention as ragged)
    rng = np.random.default_rng(3)
    S, R, Hkv, G, Dh = 3, 64, 2, 4, 128
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
    q, ck, cv = f(S, 1, Hkv, G, Dh), f(S, R * Hkv, Dh), f(S, R * Hkv, Dh)
    lengths = jnp.asarray([[1], [37], [64]], jnp.int32)
    assert ragged.fetch_block(R, Hkv, Dh) == 0          # 2 heads: no view
    assert ragged.fetch_block(128, Hkv, Dh, flat=True) == 128
    want = decode_attention(q, ck, cv, lengths)         # CPU: the chain
    got = ragged.ragged_decode_attention(q[:, 0], ck, cv, lengths[:, 0],
                                         block=16, interpret=True)
    assert np.abs(np.asarray(got - want[:, 0])).max() < 1e-5


# ---- the expert layer ------------------------------------------------------

def _layer_inputs(n=50, seed=2):
    """A tiny expert layer's weights, uncut (16 experts), and n tokens."""
    rng = np.random.default_rng(seed)
    d, f, E = 32, 16, 16
    normal = lambda *s: jnp.asarray(rng.normal(size=s) * 0.2, jnp.float32)
    p = {"router": normal(d, E), "shared_gate_w": normal(d, 1),
         "shared_gate": normal(d, f), "shared_up": normal(d, f),
         "shared_down": normal(f, d), "experts_gate": normal(E, d, f),
         "experts_up": normal(E, d, f), "experts_down": normal(E, f, d)}
    return p, normal(n, d) * 5


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """Over all eight shares of a 16-expert layer: the parts the shares
    give (each computed by the program's layer, told which two experts it
    holds), with the gated shared expert counted once, are the uncut
    reference's layer."""
    p, m = _layer_inputs()
    uncut = {**TINY, "num_experts": 16, "deployment": {"rank": 0}}
    shared, routed = ref.expert_layer(m, p, uncut, ref.make_matmul("f32"))
    sel, w = moe.route(m, p["router"], None, top_k=3, route_scale=1.0,
                       score_func="softmax")
    total = jax.nn.sigmoid(m @ p["shared_gate_w"]) * moe.gated_ffn(
        m, p["shared_gate"], p["shared_up"], p["shared_down"])
    pairs = 0
    for rank in range(8):
        held = slice(2 * rank, 2 * rank + 2)
        part, stats = moe.expert_ffn(
            m, sel, w, p["experts_gate"][held], p["experts_up"][held],
            p["experts_down"][held], first_expert=2 * rank,
            experts_known=16)
        _, theirs = ref.expert_layer(       # ... the reference's share
            m, {**p, **{k: p[k][held] for k in (
                "experts_gate", "experts_up", "experts_down")}},
            {**TINY, "num_experts": 2, "deployment": {"rank": rank}},
            ref.make_matmul("f32"))
        assert np.abs(np.asarray(part - theirs)).max() < TOL
        total, pairs = total + part, pairs + int(stats[0])
        assert int(stats[0]) + int(stats[1]) == 50 * 3
    assert pairs == 50 * 3              # every pair computed exactly once
    assert np.abs(np.asarray(total - (shared + routed))).max() < 5e-5


def test_the_softmax_router_against_a_hand_made_routing():
    """Logits chosen by hand: two tokens over five experts, top 2."""
    logits = np.asarray([[2.0, 0.0, 1.0, -1.0, 0.5],
                         [0.0, 3.0, 0.0, 1.0, 2.5]], np.float32)
    m = jnp.eye(2, dtype=jnp.float32)
    sel, w = moe.route(m, jnp.asarray(logits), None, top_k=2,
                       route_scale=1.0, score_func="softmax")
    assert sel.tolist() == [[0, 2], [1, 4]]
    e = np.exp(logits)
    want = np.stack([e[0, [0, 2]] / e[0, [0, 2]].sum(),
                     e[1, [1, 4]] / e[1, [1, 4]].sum()])
    assert np.abs(np.asarray(w) - want).max() < 1e-6
    # not normalised over the selection: the probabilities themselves
    _, raw = moe.route(m, jnp.asarray(logits), None, top_k=2,
                       route_scale=1.0, route_norm=False,
                       score_func="softmax")
    assert np.abs(np.asarray(raw) - np.stack(
        [(e[0] / e[0].sum())[[0, 2]], (e[1] / e[1].sum())[[1, 4]]])
    ).max() < 1e-6
    with pytest.raises(ValueError, match="score_func"):
        moe.route(m, jnp.asarray(logits), None, top_k=2, route_scale=1.0,
                  score_func="tanh")


def test_the_sigmoid_routers_program_did_not_change():
    """The form ``afmoe`` routes by: the same selection and weights as
    written out, and the same program text as a copy of the function as
    it stood before the softmax form came."""
    def before(m, router_kernel, router_bias, *, top_k, route_scale):
        with jax.named_scope("moe.route"):
            s = jax.nn.sigmoid(jnp.dot(m, router_kernel,
                                       preferred_element_type=jnp.float32))
            _, sel = jax.lax.top_k(s + router_bias.astype(jnp.float32), top_k)
            w = jnp.take_along_axis(s, sel, axis=-1)
            w = w / jnp.sum(w, axis=-1, keepdims=True)
            return sel.astype(jnp.int32), route_scale * w

    rng = np.random.default_rng(0)
    args = (jnp.asarray(rng.normal(size=(9, 32)), jnp.float32),
            jnp.asarray(rng.normal(size=(32, 16)), jnp.float32),
            jnp.asarray(rng.normal(size=(16,)) * 0.1, jnp.float32))
    kw = dict(top_k=2, route_scale=2.448)
    text = lambda f: jax.jit(lambda *a: f(*a, **kw)).lower(*args).as_text()
    assert text(moe.route) == text(before)
    for a, b in zip(moe.route(*args, **kw), before(*args, **kw)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


# ---- counters ---------------------------------------------------------------

def test_the_engines_counters_follow_a_hand_count(params, sequences):
    """``serve_state_bytes_total`` is state layers x bytes a slot x 2 a
    decode step, over every slot; ``serve_cache_bytes``
    is the module's own count by kind; rows are read in attention layers
    only; the scan's two forms are counted where they are traced."""
    names = ['serve_state_bytes_total{whose="all"}',
             'serve_cache_rows_read_total{kind="full"}',
             'serve_cache_rows_read_total{kind="state"}',
             'moe_pairs_total{where="held"}',
             'moe_pairs_total{where="absent"}', "moe_expert_slots_total",
             'lm_linear_attention_total{impl="chunked"}',
             'lm_linear_attention_total{impl="recurrent"}']
    before = [_counter(n) for n in names]
    # a cache length no other test uses: its programs are traced here
    engine = DecodeEngine(_model(), params, slots=3, cache_len=96)
    engine.prefill_many([(0, sequences[0, :5], 1), (2, sequences[1, :19], 1)])
    engine.decode(busy=[0, 2])          # positions 5 and 19
    engine.decode(busy=[2])             # position 20; slot 0 still live
    every, full, none, held, absent, slots, chunked, recurrent = (
        _counter(n) - b for n, b in zip(names, before))
    # a slot's state in one layer: S [4, 8, 8] f32, conv [3, 64] f32 here
    state = 4 * 4 * 8 * 8 + 4 * 3 * (2 * 2 * 8 + 4 * 8)
    assert every == 2 * 3 * 6 * state * 2
    assert full == 2 * ((6 + 20) + 21) and none == 0   # two attention layers
    # prefill: 24 prompt tokens; two steps of two live slots; top 3; 8 layers
    assert held + absent == (24 + 2 + 2) * 3 * 8
    assert slots == 2 * 4 * 8
    # two prefill programs (buckets of 8 and of 32) and one decode
    # program traced: six linear layers each
    assert (chunked, recurrent) == (12, 6)
    gauges = obs_metrics.registry().snapshot()["gauges"]
    row = 2 * 2 * 16 * 4                            # K and V, f32 here
    assert gauges['serve_cache_bytes{kind="full"}']["value"] == \
        3 * 2 * 96 * row
    assert gauges['serve_cache_bytes{kind="state"}']["value"] == \
        3 * 6 * state
    assert engine.cache_bytes == 3 * (2 * 96 * row + 6 * state)


# ---- what refuses, and what holds -----------------------------------------

@pytest.mark.parametrize("cache_len, ladder", [
    (4096, (256, 512, 1024, 2048, 3072, 4096)),     # the benchmark's cell
    (16384, (256, 512, 1024, 2048, 3072, 4096, 8192, 16384)),
    (1000, (256, 512, 1000)),
    (256, None),                        # the engine's powers of two
])
def test_the_stated_ladder(cache_len, ladder):
    assert _model().prefill_buckets(cache_len) == ladder


def test_the_cells_configuration_builds_the_cells_model():
    """benchmarks/configs/qwen3_next_ep8.json through the one
    constructor: two periods, 64 of 512 experts from id 0, heads of 256
    of which 64 features rotate, and the cache the cell's arithmetic
    says: 4.29 GB of rows and 3.30 GB of state at 256 slots x 4,096."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    model = build_model_from_config(os.path.join(
        root, "benchmarks", "configs", "qwen3_next_ep8.json"))
    c = model.dims
    assert (c.n_layers, c.experts_held, c.n_routed, c.first_expert,
            c.top_k) == (8, 64, 512, 0, 10)
    assert (c.head_dim, c.rotary_dim, c.n_heads, c.n_kv_heads) == \
        (256, 64, 16, 2)
    held = np.asarray(model.cache_slot_bytes(4096)) * 256
    kinds = [kind for kind, _ in model.cache_rows(4096)]
    assert round(sum(h for h, k in zip(held, kinds) if k == "full") / 1e9,
                 2) == 4.29
    assert round(sum(h for h, k in zip(held, kinds) if k == "state") / 1e9,
                 2) == 3.30
    shapes = jax.eval_shape(lambda: model.init_cache(256, 4096))
    assert sum(x.size * x.dtype.itemsize
               for x in jax.tree.leaves(shapes)) == held.sum()


def test_a_configuration_the_block_does_not_compute_is_refused():
    with pytest.raises(ValueError, match="decoder_sparse_step"):
        _model(decoder_sparse_step=2)


# ---- one constructor, from a configuration file ----------------------------

def test_the_cli_serves_the_model_from_a_configuration_file(tmp_path):
    """``tools/serve_lm.py --model_config`` builds the model by the
    constructor the benchmark's family calls, initialises a snapshot,
    promotes it and drives requests through the batcher; what rolls a
    cache back is refused by name: exit 2."""
    import importlib.util
    path = tmp_path / "tiny_qwen3_next.json"
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
