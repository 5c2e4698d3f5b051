"""The afmoe model (models/afmoe.py), its expert layer (ops/moe.py) and
its path through DecodeEngine and ContinuousBatcher, against the plain
reference (benchmarks/reference/afmoe.py) at tiny widths on the CPU,
float32 compute so that the comparison is of the mathematics: a window of
8 positions, so every context here wraps the rings several times."""

import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import served_families as fam

from benchmarks.reference import afmoe as ref
from distributedtensorflowexample_tpu.models import build_model_from_config
from distributedtensorflowexample_tpu.obs import metrics as obs_metrics
from distributedtensorflowexample_tpu.ops import moe
from distributedtensorflowexample_tpu.ops.attention import (
    grouped_attention, splash_grouped_attention, takes_splash)
from distributedtensorflowexample_tpu.refusal import ModeRefusal
from distributedtensorflowexample_tpu.serving.engine import DecodeEngine
from distributedtensorflowexample_tpu.serving.queue import (
    ContinuousBatcher, RequestQueue)

TOL = 2e-5      # float32 against float32 at HIGHEST: summation order only

FAMILY = "afmoe"
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

@pytest.mark.parametrize("attn_block", [1024, 8, 16])
def test_forward_matches_the_reference(params, sequences, ref_logits,
                                       attn_block):
    """One tile (1024) and the tiled walk (8: the window's own length;
    16: a window inside a tile) give the reference's logits."""
    got = _model(attn_block).apply({"params": params}, jnp.asarray(sequences))
    assert np.abs(np.asarray(got) - ref_logits).max() < TOL


@pytest.mark.parametrize("window, T", [(0, 40), (8, 40), (8, 37), (5, 64)])
def test_tiled_attention_is_the_one_tile_attention(window, T):
    """The walk over query and key tiles (a sequence that a tile does not
    divide is padded) against one tile over the whole sequence."""
    rng = np.random.default_rng(T + window)
    q = jnp.asarray(rng.normal(size=(2, T, 4, 8)), jnp.float32)
    k, v = (jnp.asarray(rng.normal(size=(2, T, 2, 8)), jnp.float32)
            for _ in range(2))
    whole = grouped_attention(q, k, v, window=window, block=1024)
    tiled = grouped_attention(q, k, v, window=window, block=8)
    assert np.abs(np.asarray(whole - tiled)).max() < 1e-5


@pytest.mark.parametrize("window", [0, 128, 200])
def test_the_tpu_kernel_is_the_one_tile_attention(window):
    """What a TPU program takes past one tile (JAX's splash attention,
    interpreted here): fewer K/V heads than query heads, causal, and a
    window that ends on a block's edge and inside a block."""
    rng = np.random.default_rng(window)
    q = jnp.asarray(rng.normal(size=(2, 384, 4, 128)), jnp.float32)
    k, v = (jnp.asarray(rng.normal(size=(2, 384, 2, 128)), jnp.float32)
            for _ in range(2))
    whole = grouped_attention(q, k, v, window=window, block=1024)
    kernel = splash_grouped_attention(q, k, v, window=window, block=128,
                                      interpret=True)
    assert np.abs(np.asarray(whole - kernel)).max() < 1e-5


@pytest.mark.parametrize("shape, block, takes", [
    ((1, 16384, 48, 128), 1024, True), ((2, 2048, 48, 128), 1024, True),
    ((1, 1024, 48, 128), 1024, False),      # one tile: the einsum chain
    ((1, 2040, 48, 128), 1024, False),      # a block does not divide it
    ((1, 4096, 4, 8), 1024, False)])        # heads narrower than a lane group
def test_takes_splash_is_decided_by_backend_and_shape(shape, block, takes,
                                                      monkeypatch):
    assert not takes_splash(shape, block)               # the CPU never
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert takes_splash(shape, block) is takes


# ---- prefill, then decode, through the engine ------------------------------

def test_engine_prefill_then_decode_logits_match_the_reference(
        params, sequences, ref_logits):
    """Three slots; prompts shorter and longer than the window (the long
    one leaves its ring holding the last 8 rows); 36 decode steps, every
    ring wrapping four times; a request admitted mid-decode.  Every logit
    the engine gives is the reference's full forward's at that position."""
    engine = DecodeEngine(_model(), params, slots=3, cache_len=64)
    assert [rows for _, rows in engine.smodel.cache_rows(64)] == [8, 8, 64, 8]
    worst = 0.0

    def admit(slot, row, length):
        nonlocal worst
        (_, last), = engine.prefill_many(
            [(slot, sequences[row, :length], 1)]).values()
        worst = max(worst, np.abs(last - ref_logits[row, length - 1]).max())
        engine.set_slot(slot, int(sequences[row, length]), length)

    where = {0: 0, 1: 1}                # slot -> row of `sequences`
    admit(0, 0, 5)
    admit(1, 1, 20)
    for step in range(36):
        if step == 11:
            where[2] = 2
            admit(2, 2, 13)
        busy = sorted(where)
        at = {s: int(engine.positions[s]) for s in busy}
        logits = engine.decode_logits(busy=busy)
        for s in busy:
            worst = max(worst, np.abs(
                logits[s] - ref_logits[where[s], at[s]]).max())
            # teacher-forced: the next token is the sequence's own
            engine.set_slot(s, int(sequences[where[s], at[s] + 1]),
                            at[s] + 1)
    assert int(engine.positions[1]) == 56 and worst < TOL, worst


def test_a_prompt_longer_than_the_window_leaves_its_last_rows(params,
                                                              sequences):
    """After a 29-token prompt (bucket 32) a window layer's ring holds
    positions 21..28, each at position mod 8, as 29 single steps leave
    it."""
    model = _model()
    long = DecodeEngine(model, params, slots=2, cache_len=64)
    long.prefill_many([(1, sequences[3, :29], 1)])
    steps = DecodeEngine(model, params, slots=2, cache_len=64)
    steps.prefill_many([(1, sequences[3, :1], 1)])
    for t in range(1, 29):
        steps.set_slot(1, int(sequences[3, t]), t)
        steps.decode(busy=[1])
    for layer in (0, 1, 3):
        for a, b in ((long._ck, steps._ck), (long._cv, steps._cv)):
            assert np.abs(np.asarray(a[layer][1] - b[layer][1])).max() < TOL


# ---- the padding ladder the model states -----------------------------------

#: The ladder of benchmarks/configs/trinity_large_ep8.json in its cell
#: (tiles of 1,024, a window of 4,096, 16,384 rows a slot).
CELL_LADDER = (1024, 2048, 4096, 6144, 8192, 12288, 16384)


@pytest.mark.parametrize("tile, window, cache_len, ladder", [
    (1024, 4096, 16384, CELL_LADDER),
    (1024, 4096, 8192, (1024, 2048, 4096, 6144, 8192)),
    (1024, 4096, 3000, (1024, 2048, 3000)),     # a cache inside the window
    (1024, 1024, 4096, (1024, 2048, 3072, 4096)),   # a window of one tile
    (512, 3000, 8192, (512, 1024, 2048, 3072, 4608, 6144, 8192)),
    (1024, 4096, 40000, CELL_LADDER + (24576, 32768, 40000)),
    (8, 16, 64, (8, 16, 24, 32, 48, 64)),
    (8, 16, 60, (8, 16, 24, 32, 48, 60)),
    (1024, 8, 64, None), (1024, 4096, 1000, None), (16, 8, 64, None)])
def test_the_stated_ladder(tile, window, cache_len, ladder):
    """One bucket up to a tile, powers of two to the window, above it
    each power of two and its one-and-a-half (whole tiles), ``cache_len``
    last; a window or a cache shorter than a tile leaves the ladder to
    the engine."""
    got = _model(tile, sliding_window=window,
                 max_position_embeddings=1 << 16).prefill_buckets(cache_len)
    assert got == ladder
    if ladder:
        assert all(a < b for a, b in zip(got, got[1:]))
        assert got[0] == tile and got[-1] == cache_len
        assert all(b % tile == 0 for b in got[:-1])


def test_the_cells_configuration_states_the_cells_ladder():
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    model = build_model_from_config(os.path.join(
        root, "benchmarks", "configs", "trinity_large_ep8.json"))
    assert model.prefill_buckets(16384) == CELL_LADDER
    # the fullest program is still two prompts of the longest bucket
    assert max(b * max(1, model.prefill_positions_max // b)
               for b in CELL_LADDER) == 2 * 16384


def test_a_window_below_a_tile_gets_the_engines_powers_of_two(params):
    """Every other model of this file: tiles of 1,024, a window of 8."""
    engine = DecodeEngine(_model(), params, slots=2, cache_len=64)
    assert engine.buckets == (8, 16, 32, 64)
    assert DecodeEngine(_model(), params, slots=2, cache_len=48,
                        prefill_smallest=16).buckets == (16, 32, 48)


def test_a_bucket_the_ring_does_not_divide_prefills_and_decodes(params,
                                                                sequences):
    """Tiles of 8 and a window of 16 state the ladder 8, 16, 24, 32, 48,
    64: a 20-token prompt pads to 24 positions, neither a power of two
    nor a multiple of the ring of 16 it is longer than (the ring keeps
    positions 4..19, each at position mod 16), a 37-token one to 48.
    Prefill and 22 decode steps, the rings wrapping, give the float32
    reference's logits; the pad counter reads the hand count."""
    cfg = {**TINY, "sliding_window": 16}
    want = np.asarray(ref.forward(params, jnp.asarray(sequences), cfg))
    engine = DecodeEngine(_model(8, sliding_window=16), params, slots=3,
                          cache_len=64)
    assert engine.buckets == (8, 16, 24, 32, 48, 64)
    assert [rows for _, rows in engine.smodel.cache_rows(64)] == [
        16, 16, 64, 16]
    lengths = {0: 5, 1: 20, 2: 37}
    assert [engine.bucket_for(n, 1) for n in lengths.values()] == [8, 24, 48]
    pad = 'serve_prefill_positions_total{kind="pad"}'
    prompt = 'serve_prefill_positions_total{kind="prompt"}'
    before = _counter(pad), _counter(prompt)
    got = engine.prefill_many([(s, sequences[s, :n], 1)
                               for s, n in lengths.items()])
    assert _counter(pad) - before[0] == (8 - 5) + (24 - 20) + (48 - 37)
    assert _counter(prompt) - before[1] == 5 + 20 + 37
    worst = max(np.abs(got[s][1] - want[s, n - 1]).max()
                for s, n in lengths.items())
    for s, n in lengths.items():
        engine.set_slot(s, int(sequences[s, n]), n)
    for _ in range(22):
        at = {s: int(engine.positions[s]) for s in lengths}
        logits = engine.decode_logits(busy=sorted(lengths))
        for s in lengths:
            worst = max(worst, np.abs(logits[s] - want[s, at[s]]).max())
            engine.set_slot(s, int(sequences[s, at[s] + 1]), at[s] + 1)
    assert int(engine.positions[2]) == 59 and worst < TOL, worst


def test_batcher_serves_the_references_tokens(params):
    """Seven requests through RequestQueue and ContinuousBatcher on three
    slots (so four are admitted mid-decode): every served token is the
    reference's best at its position (gap 0 but for summation order)."""
    engine = DecodeEngine(_model(), params, slots=3, cache_len=64)
    queue = RequestQueue(engine.vocab)
    batcher = ContinuousBatcher(engine, queue, slo_ms=0, eos_id=None)
    rng = np.random.default_rng(11)
    reqs = [queue.submit(rng.integers(0, 97, n).astype(np.int32), new,
                         rid=f"r{i}")
            for i, (n, new) in enumerate([(5, 30), (21, 25), (9, 12),
                                          (33, 20), (3, 40), (14, 9),
                                          (27, 18)])]
    while not all(r.done.is_set() for r in reqs):
        batcher.step()
    for r in reqs:
        assert r.outcome == "ok" and len(r.tokens) == r.max_new
        gaps = ref.served_token_gaps(params, r.prompt, np.asarray(r.tokens),
                                     TINY, pad_to=16)
        assert gaps["widest"] < 1e-4 and gaps["tokens"] == r.max_new


def test_a_late_readback_serves_the_synchronous_orders_tokens_and_counts(
        params, serve_backlog):
    """The same seven requests on three slots, the next step handed to
    the device before the last one's tokens are read (the counts ride
    behind the tokens in the one array read late) and then a read-back
    at every step: request for request the same tokens, boundary for
    boundary the same ``step()``, and the host's and the model's
    counters total the same over the run."""
    engine = DecodeEngine(_model(), params, slots=3, cache_len=64)
    rng = np.random.default_rng(11)
    plan = [(rng.integers(0, 97, n).astype(np.int32), new)
            for n, new in [(5, 30), (21, 25), (9, 12), (33, 20), (3, 40), (14, 9), (27, 18)]]
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


def test_a_window_of_k_tokens_is_k_single_steps(params, sequences):
    """The K-token step (full layers): four tokens at once give the
    logits of four steps of one, and leave the same cache.  A ring takes
    one token a step and says so."""
    model = _model(layer_types=["full_attention"] * 4)
    ck, cv = model.init_cache(2, 64)
    toks = jnp.asarray(sequences[:2, :12])
    _, ck, cv, _ = model.apply({"params": params}, toks, jnp.arange(2),
                               jnp.asarray([12, 9]), ck, cv,
                               method="prefill_into")
    pos = jnp.asarray([12, 9], jnp.int32)
    nxt = jnp.asarray(sequences[:2, 20:24])
    many, ck4, cv4, _ = model.apply({"params": params}, nxt, pos, ck, cv,
                                    method="verify")
    for j in range(4):
        one, ck, cv, _ = model.apply({"params": params}, nxt[:, j], pos + j,
                                     ck, cv, method="decode")
        assert np.abs(np.asarray(one - many[:, j])).max() < TOL
    for a, b in zip(jax.tree.leaves((ck, cv)), jax.tree.leaves((ck4, cv4))):
        assert np.abs(np.asarray(a - b)).max() < TOL
    rings = _model()
    with pytest.raises(ValueError, match="one token a step"):
        rings.apply({"params": params}, nxt, pos, *rings.init_cache(2, 64),
                    method="verify")


# ---- the expert layer ------------------------------------------------------

def _layer_inputs(n=50, seed=2):
    """A tiny expert layer's weights, uncut (16 experts), and n tokens."""
    rng = np.random.default_rng(seed)
    d, f, E = 32, 16, 16
    normal = lambda *s: jnp.asarray(rng.normal(size=s) * 0.2, jnp.float32)
    p = {"router": normal(d, E), "router_bias": normal(E) * 0.1,
         "shared_gate": normal(d, f), "shared_up": normal(d, f),
         "shared_down": normal(f, d), "experts_gate": normal(E, d, f),
         "experts_up": normal(E, d, f), "experts_down": normal(E, f, d)}
    return p, normal(n, d) * 5


@pytest.mark.parametrize("rows", [2048, 8])
def test_the_eight_shares_add_up_to_the_uncut_layer(monkeypatch, rows):
    """Over all eight shares of a 16-expert layer: the parts the shares
    give (each computed by the program's layer, told which two experts it
    holds, in one block of sorted rows and in blocks of 8 that cut
    through an expert's rows), with the shared expert counted once, are
    the uncut reference's layer."""
    p, m = _layer_inputs()
    uncut = {**TINY, "num_experts": 16, "deployment": {"rank": 0}}
    shared, routed = ref.expert_layer(m, p, uncut, ref.make_matmul("f32"))
    sel, w = moe.route(m, p["router"], p["router_bias"], top_k=2,
                       route_scale=2.448)
    total, pairs = moe.gated_ffn(m, p["shared_gate"], p["shared_up"],
                                 p["shared_down"]), 0
    monkeypatch.setattr(moe, "BLOCK_ROWS", rows)
    for rank in range(8):
        held = slice(2 * rank, 2 * rank + 2)
        part, stats = moe.expert_ffn(
            m, sel, w, p["experts_gate"][held], p["experts_up"][held],
            p["experts_down"][held], first_expert=2 * rank,
            experts_known=16)
        # ... and each share is the reference's share.
        _, theirs = ref.expert_layer(
            m, {**p, **{k: p[k][held] for k in (
                "experts_gate", "experts_up", "experts_down")}},
            {**TINY, "num_experts": 2, "deployment": {"rank": rank}},
            ref.make_matmul("f32"))
        assert np.abs(np.asarray(part - theirs)).max() < TOL
        total, pairs = total + part, pairs + int(stats[0])
        assert int(stats[0]) + int(stats[1]) == 50 * 2
    assert pairs == 50 * 2              # every pair computed exactly once
    assert np.abs(np.asarray(total - (shared + routed))).max() < 5e-5


@pytest.mark.parametrize("rows", [2048, 16])
def test_no_pair_is_dropped_when_every_token_routes_to_one_expert(
        monkeypatch, rows):
    """70 tokens, all on expert 5 (and on an absent one): 70 pairs on one
    of four held experts, in one block and over five blocks of 16 rows
    (of the nine that all 140 pairs would fill)."""
    monkeypatch.setattr(moe, "BLOCK_ROWS", rows)
    p, m = _layer_inputs(70)
    sel = jnp.tile(jnp.asarray([[5, 12]], jnp.int32), (70, 1))
    w = jnp.tile(jnp.asarray([[0.7, 0.3]], jnp.float32), (70, 1))
    held = slice(4, 8)
    got, stats = moe.expert_ffn(
        m, sel, w, p["experts_gate"][held], p["experts_up"][held],
        p["experts_down"][held], first_expert=4, experts_known=16)
    want = 0.7 * moe.gated_ffn(m, p["experts_gate"][5], p["experts_up"][5],
                               p["experts_down"][5])
    assert np.abs(np.asarray(got - want)).max() < TOL
    assert stats.tolist() == [70, 70, 1, 128 if rows == 2048 else 5 * 16]


def test_no_pair_is_dropped_when_a_whole_token_step_routes_to_one_expert():
    """Qwen3-Next's token step at its worst: 256 slots x 10 picks, all
    2,560 pairs on ONE of 64 held experts (of 512).  The rule sizes the
    block for even routing (640 rows), so the walk takes four trips and
    every pair is computed."""
    rng = np.random.default_rng(5)
    normal = lambda *s: jnp.asarray(rng.normal(size=s) * 0.2, jnp.float32)
    gate, up, down = normal(64, 32, 16), normal(64, 32, 16), normal(64, 16, 32)
    m = normal(256, 32) * 5
    assert moe.block_rows(2560, 64, 512) == 640
    got, stats = moe.expert_ffn(
        m, jnp.full((256, 10), 5, jnp.int32),
        jnp.full((256, 10), 0.1, jnp.float32), gate, up, down,
        first_expert=0, experts_known=512)
    want = moe.gated_ffn(m, gate[5], up[5], down[5])
    assert np.abs(np.asarray(got - want)).max() < 5e-5
    assert stats.tolist() == [2560, 0, 1, 4 * 640]


#: (pairs = tokens x top-k, experts held, experts the router knows) at the
#: benchmark cells' real shapes, and the rows of a block.
BLOCKS = {
    "trinity token step, 32 slots": (32 * 4, 32, 256, 128),
    "trinity prefill (1024, 1)": (1024 * 4, 32, 256, 1024),
    "trinity prefill (8192, 1)": (8192 * 4, 32, 256, 2048),
    "trinity prefill (4096, 2)": (2 * 4096 * 4, 32, 256, 2048),
    "qwen3_next token step, 256 slots": (256 * 10, 64, 512, 640),
    "qwen3_next prefill (256, 1)": (256 * 10, 64, 512, 640),
    "qwen3_next prefill (1024, 1)": (1024 * 10, 64, 512, 2048),
    "qwen3_next prefill (4096, 2)": (2 * 4096 * 10, 64, 512, 2048),
}


@pytest.mark.parametrize("shape", BLOCKS)
def test_a_block_is_twice_the_pairs_even_routing_holds_here(shape):
    """The rule at the cells' shapes: twice the expected held pairs in
    whole row tiles, capped by ``BLOCK_ROWS`` and by the pairs there
    are."""
    pairs, held, known, want = BLOCKS[shape]
    assert moe.block_rows(pairs, held, known) == want
    assert want % moe.ROW_TILE == 0 and want <= moe.BLOCK_ROWS


@pytest.mark.parametrize("tile", [8, 16, 64, 128])
def test_the_layer_is_the_same_whichever_block_the_rule_picks(monkeypatch,
                                                              tile):
    """200 tokens x 3 picks over 32 experts, 4 held: even routing puts 75
    pairs here, so the rule picks blocks of 152, 160, 192 and 256 rows
    for row tiles of 8 to 128 (one to several trips); the result and the
    first three counts do not depend on it, and the fourth is the trips x
    the rows."""
    rng = np.random.default_rng(11)
    normal = lambda *s: jnp.asarray(rng.normal(size=s) * 0.2, jnp.float32)
    gate, up, down = normal(4, 32, 16), normal(4, 32, 16), normal(4, 16, 32)
    m = normal(200, 32) * 5
    # A skewed routing: expert 9 (held: 8..11) is picked by every token.
    sel = jnp.asarray(np.stack([np.full(200, 9), rng.integers(0, 8, 200),
                                rng.integers(10, 32, 200)], 1), jnp.int32)
    w = jnp.asarray(rng.uniform(0.1, 1.0, (200, 3)), jnp.float32)
    live = jnp.arange(200) < 190
    run = lambda: moe.expert_ffn(m, sel, w, gate, up, down, first_expert=8,
                                 experts_known=32, live=live)
    monkeypatch.setattr(moe, "ROW_TILE", 600)       # one block: every pair
    want, counts = run()
    monkeypatch.setattr(moe, "ROW_TILE", tile)
    rows = moe.block_rows(600, 4, 32)
    assert rows == -(-150 // tile) * tile
    got, stats = run()
    assert np.abs(np.asarray(got - want)).max() < 5e-5
    held = int(counts[0])
    assert held > rows or tile == 128               # several trips
    assert stats.tolist() == counts.tolist()[:3] + [-(-held // rows) * rows]
    assert counts.tolist()[3] == 600


def test_the_counts_are_what_a_hand_made_routing_says():
    """Six tokens, two of them padding; held experts 4..7."""
    p, m = _layer_inputs(6)
    sel = jnp.asarray([[4, 5], [4, 9], [0, 15], [7, 4], [5, 6], [6, 1]],
                      jnp.int32)
    live = jnp.asarray([True, True, True, True, False, False])
    held = slice(4, 8)
    _, stats = moe.expert_ffn(
        m, sel, jnp.ones((6, 2), jnp.float32), p["experts_gate"][held],
        p["experts_up"][held], p["experts_down"][held], first_expert=4,
        experts_known=16, live=live)
    # live pairs on 4..7: (4,5) (4) () (7,4) = 5, on absent: 3; experts
    # 4, 5 and 7 got a pair, 6 only from padding.
    assert stats.tolist() == [5, 3, 3, 12]       # one block of all 12 pairs


def test_the_engines_counters_follow_the_programs_counts(params, sequences):
    """``moe_pairs_total`` adds up to tokens x top-k x expert layers,
    ``moe_rows_walked_total`` to the rows of the blocks the walks handed
    to the grouped products, ``moe_expert_slots_total`` to held experts x
    expert layers a decode step, and ``serve_cache_rows_read_total`` to
    the rows the busy slots' positions reach, a ring cutting them at 8."""
    names = ['moe_pairs_total{where="held"}',
             'moe_pairs_total{where="absent"}', "moe_expert_slots_total",
             "moe_experts_touched_total", "moe_rows_walked_total",
             'serve_cache_rows_read_total{kind="full"}',
             'serve_cache_rows_read_total{kind="window"}']
    before = [_counter(n) for n in names]
    engine = DecodeEngine(_model(), params, slots=3, cache_len=64)
    engine.prefill_many([(0, sequences[0, :5], 1), (2, sequences[1, :19], 1)])
    engine.decode(busy=[0, 2])          # positions 5 and 19
    engine.decode(busy=[2])             # position 20; slot 0 still live
    held, absent, slots, touched, walked, full, window = (
        _counter(n) - b for n, b in zip(names, before))
    # prefill: 24 prompt tokens; two decode steps of two live slots (a
    # slot not advanced still holds a request); 2 choices, 3 expert layers
    assert held + absent == (24 + 2 + 2) * 2 * 3
    assert slots == 2 * 4 * 3 and 0 < touched <= slots
    # every program's pairs fit one block: prompts padded to buckets of 8
    # and 32 positions, two steps of three slots (a parked slot's pairs
    # sort past the held ones but are rows of the block)
    assert walked == (8 + 32 + 2 * 3) * 2 * 3
    assert full == (6 + 20) + 21                    # one full layer
    assert window == 3 * ((6 + 8) + 8)              # three rings of 8
    gauges = obs_metrics.registry().snapshot()["gauges"]
    row = 2 * 2 * 8 * 4                             # K and V, f32 here
    assert gauges['serve_cache_bytes{kind="full"}']["value"] == 3 * 64 * row
    assert gauges['serve_cache_bytes{kind="window"}']["value"] == \
        3 * 3 * 8 * row
    assert engine.cache_bytes == 3 * (64 + 3 * 8) * row


@pytest.mark.parametrize("tiled, slots", [(0, 5), (1, 7), (2, 6)])
def test_the_products_an_engine_traces_are_counted_by_kernel(
        params, sequences, monkeypatch, tiled, slots):
    """``moe_grouped_products_total{kernel}``: every product of programs
    built for the CPU is ``ragged_dot``'s; where the chooser answers with
    tiles (here: a block's rows by a whole expert, or by half of its
    columns — ``tiled`` tiles an expert — interpreted) every one is the
    tiled kernel's — three an expert layer of each program traced, and
    the counts the programs return do not change.  Engines of their own
    sizes, so that each traces its programs under the chooser it is
    given."""
    if tiled:
        monkeypatch.setattr(moe, "product_tiling",
                            lambda rows, k, n, dtype: (rows, k, n // tiled))
    names = ['moe_grouped_products_total{kernel="ragged_dot"}',
             'moe_grouped_products_total{kernel="gmm"}',
             "moe_rows_walked_total"]
    before = [_counter(n) for n in names]

    def serve(slots):
        engine = DecodeEngine(_model(), params, slots=slots, cache_len=48)
        engine.prefill_many([(0, sequences[0, :5], 1),
                             (2, sequences[1, :19], 1)])
        return engine.decode(busy=[0, 2])[[0, 2]].tolist()

    toks = serve(slots)
    ragged, gmm, walked = (_counter(n) - b for n, b in zip(names, before))
    # Two prefill programs and the token step, three expert layers each.
    assert (gmm, ragged) == ((27, 0) if tiled else (0, 27))
    assert walked == (8 + 32 + slots) * 2 * 3
    monkeypatch.undo()
    assert toks == serve(3)             # either kernel serves the same


# ---- what refuses, and what holds -----------------------------------------

def test_a_model_of_full_layers_only_is_not_refused(params):
    """The refusal is of rings, not of the architecture."""
    from distributedtensorflowexample_tpu.serving.engine import (
        refuse_cache_without_rows_by_position as refuse)
    refuse(_model(layer_types=["full_attention"] * 4), "x")
    with pytest.raises(ModeRefusal):
        refuse(_model(), "x")


def test_gpt2_goes_through_the_same_engine_with_every_layer_full():
    from distributedtensorflowexample_tpu.models import build_model
    model = build_model("lm_tiny")
    p = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))[
        "params"]
    engine = DecodeEngine(model, p, slots=2, cache_len=16)
    assert engine.layers_without_rows_by_position == 0
    assert engine.smodel.cache_rows(16) == (("full", 16),) * 2
    assert engine._ck.shape == (2, 2, 16, 2, 32)    # the stacked pair


# ---- one constructor, from a configuration file ----------------------------

def test_the_cli_serves_the_model_from_a_configuration_file(tmp_path,
                                                            params):
    """``tools/serve_lm.py --model_config`` builds the model by the
    constructor the benchmark's family calls, initialises a snapshot,
    promotes it and drives requests through the batcher."""
    import importlib.util
    import os
    path = tmp_path / "tiny_afmoe.json"
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
    # ... and what assumes one row shape is refused by name: exit 2.
    assert cli.main(["--model_config", str(path), "--snapshot",
                     str(tmp_path / "snap"), "--prefix_cache", "4",
                     "--drive", "1", "--max_len", "64"]) == 2
