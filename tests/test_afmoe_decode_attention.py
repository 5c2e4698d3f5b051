"""The token step's attention (ops/attention.py: decode_attention): the
ragged kernel (ops/pallas/decode_attention.py), interpreted on the CPU,
against the einsum chain it replaces on a TPU — alone at every length
that matters, then inside ``AfmoeLM.decode`` with the rings wrapping —
and the counters that say which path a program took and how many rows
it fetched."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributedtensorflowexample_tpu.models import afmoe as afmoe_model
from distributedtensorflowexample_tpu.models import build_model_from_config
from distributedtensorflowexample_tpu.obs import metrics as obs_metrics
from distributedtensorflowexample_tpu.ops import attention as attention_op
from distributedtensorflowexample_tpu.ops.pallas import (
    decode_attention as ragged)
from distributedtensorflowexample_tpu.serving.engine import DecodeEngine

#: tests/test_afmoe.py's tiny model with a window of 24: rings of 24 rows
#: (three blocks of 8) beside a full layer of 64 (four of 16).
TINY = dict(
    model_type="afmoe", vocab_size=97, hidden_size=32,
    num_attention_heads=4, num_key_value_heads=2, head_dim=8,
    intermediate_size=64, moe_intermediate_size=16, num_hidden_layers=4,
    num_dense_layers=1,
    layer_types=["sliding_attention", "sliding_attention", "full_attention",
                 "sliding_attention"],
    num_experts=4, num_experts_per_tok=2, num_shared_experts=1,
    route_scale=2.448, route_norm=True, sliding_window=24, rope_theta=10000,
    rms_norm_eps=1e-5, max_position_embeddings=128, mup_enabled=True,
    published={"num_experts": 16}, deployment={"rank": 1})
BLOCK = 16


def _model(dtype):
    return build_model_from_config(TINY, dtype=dtype, param_dtype=dtype)


def _counter(series: str) -> float:
    got = obs_metrics.registry().snapshot()["counters"].get(series)
    return (got["value"] if isinstance(got, dict) else got) or 0


def _operands(S, R, Hkv, G, Dh, dtype, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(keys[0], (S, 1, Hkv, G, Dh), dtype),
            jax.random.normal(keys[1], (S, R, Hkv, Dh), dtype),
            jax.random.normal(keys[2], (S, R, Hkv, Dh), dtype))


# ---- the kernel alone -------------------------------------------------------

@pytest.mark.parametrize("name, R, Hkv, G, lengths", [
    ("parked", 64, 2, 3, [1, 1, 1]),            # pos == 0: one block each
    ("one_under_an_edge", 64, 2, 3, [15, 31, 47]),
    ("at_an_edge", 64, 2, 3, [16, 32, 48]),
    ("one_over_an_edge", 64, 2, 3, [17, 33, 49]),
    ("every_row", 64, 2, 3, [64, 64, 64]),
    ("very_different_slots", 64, 2, 3, [1, 64, 17, 30, 2, 48]),
    ("six_heads_a_kv_head", 32, 8, 6, [1, 20, 32]),     # the cell's grouping
    ("one_head_a_kv_head", 64, 2, 1, [5, 64, 16]),
    ("one_block_holds_the_layer", 16, 2, 3, [1, 9, 16]),
])
@pytest.mark.parametrize("dtype, tol", [(jnp.float32, 2e-5),
                                        (jnp.bfloat16, 2 ** -7)])
def test_ragged_kernel_is_the_einsum_chain(name, R, Hkv, G, lengths, dtype,
                                           tol):
    """Blocks of 16 rows: a slot reads ``ceil(length / 16)`` of them.  In
    float32 the two differ by summation order; in bfloat16 (the served
    type; outputs of order 1) by at most two of its steps."""
    lengths = jnp.asarray(lengths, jnp.int32)
    q, ck, cv = _operands(len(lengths), R, Hkv, G, 16, dtype)
    want = attention_op.einsum_decode_attention(q, ck, cv, lengths[:, None])
    got = ragged.ragged_decode_attention(q[:, 0], ck, cv, lengths,
                                         block=BLOCK, interpret=True)
    assert got.dtype == cv.dtype and got.shape == want[:, 0].shape
    gap = np.abs(np.asarray(got, np.float32)
                 - np.asarray(want[:, 0], np.float32)).max()
    assert gap <= tol, (name, gap)


def test_rows_past_a_slots_length_are_never_read():
    """NaN in every dead row of K and V: a kernel that multiplied them,
    even by a zero weight, would return NaN."""
    lengths = jnp.asarray([1, 17, 32, 40], jnp.int32)
    q, ck, cv = _operands(4, 64, 2, 3, 16, jnp.float32)
    dead = (jnp.arange(64)[None] >= -(-lengths[:, None] // BLOCK) * BLOCK
            )[:, :, None, None]
    want = ragged.ragged_decode_attention(q[:, 0], ck, cv, lengths,
                                          block=BLOCK, interpret=True)
    got = ragged.ragged_decode_attention(
        q[:, 0], jnp.where(dead, jnp.nan, ck), jnp.where(dead, jnp.nan, cv),
        lengths, block=BLOCK, interpret=True)
    assert np.array_equal(np.asarray(got), np.asarray(want))


# ---- which path a call takes -------------------------------------------------

@pytest.mark.parametrize("rows, n_kv_heads, head_dim, block", [
    (16384, 8, 128, 512), (4096, 8, 128, 512),      # the cell's two layers
    (768, 8, 128, 256), (128, 16, 256, 128),
    (100, 8, 128, 0),           # no block divides the rows
    (4096, 4, 128, 0),          # a cache row is half a tile: a view copies
    (4096, 8, 64, 0)])          # heads narrower than a lane group
def test_the_path_is_decided_by_backend_and_shape(rows, n_kv_heads, head_dim,
                                                  block, monkeypatch):
    fetch = attention_op.decode_fetch_block
    assert fetch(rows, n_kv_heads, head_dim) == 0       # the CPU never
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert fetch(rows, n_kv_heads, head_dim) == block


def test_a_window_of_several_tokens_keeps_the_chain(monkeypatch):
    """K > 1 (a verification window of a full-layer-only model) takes the
    einsum chain whatever the backend, and the counter says so."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    q, ck, cv = _operands(2, 512, 8, 6, 128, jnp.bfloat16)
    q = jnp.concatenate([q, q], axis=1)                 # K == 2
    before = _counter('serve_decode_attention_total{impl="einsum"}')
    out = jax.eval_shape(attention_op.decode_attention, q, ck, cv,
                         jnp.ones((2, 2), jnp.int32))
    assert out.shape == q.shape
    assert _counter('serve_decode_attention_total{impl="einsum"}') \
        == before + 1


# ---- inside the model ----------------------------------------------------------

def _force_ragged(monkeypatch):
    """Take the kernel (interpreted: the backend is the CPU's) wherever a
    block of 16 or 8 rows divides a layer's rows."""
    monkeypatch.setattr(ragged, "BLOCKS", (16, 8))
    fetch = lambda rows, n_kv_heads, head_dim, flat=False: (
        ragged.pick_block(rows) or 0)
    monkeypatch.setattr(attention_op, "decode_fetch_block", fetch)
    monkeypatch.setattr(afmoe_model, "decode_fetch_block", fetch)


@pytest.mark.parametrize("start", [3, 20, 40])
@pytest.mark.parametrize("dtype, tol", [(jnp.float32, 1e-5),
                                        (jnp.bfloat16, 2 ** -7)])
def test_decode_with_the_kernel_gives_the_chains_logits(monkeypatch, start,
                                                        dtype, tol):
    """``AfmoeLM.decode`` on the tiny model, three slots — one at
    ``start``, one seven positions on, one parked at 0 — six steps from
    the same prefilled cache by either path.  From 3 no ring wraps; from
    20 the first slot's rings wrap on the way and the second's prompt was
    already longer than they are; from 40 every row of every ring is
    live.  The logits are of order 0.35, where bfloat16's step is 0.002:
    they differ by up to two of them (float32: 1.5e-7)."""
    model = _model(dtype)
    params = model.init(jax.random.PRNGKey(3),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    rng = np.random.default_rng(start)
    prompts = rng.integers(0, TINY["vocab_size"], (2, 64)).astype(np.int32)
    lengths = jnp.asarray([start, start + 7], jnp.int32)
    ck, cv = model.init_cache(3, 64)
    assert [rows for _, rows in model.cache_rows(64)] == [24, 24, 64, 24]
    _, ck, cv, _ = model.apply(
        {"params": params}, jnp.asarray(prompts), jnp.asarray([0, 2]),
        lengths, ck, cv, method="prefill_into")
    pos = jnp.asarray([start, 0, start + 7], jnp.int32)
    toks = jnp.asarray(rng.integers(0, TINY["vocab_size"], (6, 3)), jnp.int32)

    def run(ck, cv):
        # A fresh function each time: jax would hand the second path the
        # first one's trace.
        step = jax.jit(lambda *a: model.apply({"params": params}, *a,
                                              method="decode")[:3])
        out, at = [], pos
        for tok in toks:
            logits, ck, cv = step(tok, at, ck, cv)
            out.append(np.asarray(logits, np.float32))
            at = at + (at > 0)
        return np.stack(out)

    names = ['serve_decode_attention_total{impl="%s"}' % impl
             for impl in ("einsum", "ragged")]
    before = [_counter(n) for n in names]
    want = run(ck, cv)
    assert [_counter(n) - b for n, b in zip(names, before)] == [4, 0]
    _force_ragged(monkeypatch)
    got = run(ck, cv)
    assert [_counter(n) - b for n, b in zip(names, before)] == [4, 4]
    live = [0, 2]                       # a parked slot's logits are unused
    assert np.abs(got[:, live] - want[:, live]).max() <= tol


# ---- the engine's count of what a step fetches -----------------------------------

def test_rows_fetched_are_every_row_on_the_chain_and_whole_blocks_on_the_kernel(
        monkeypatch):
    """``serve_cache_rows_fetched_total``: the einsum chain reads every
    row every slot holds, a step; the kernel each slot's visible rows
    rounded up to its block — an idle slot's one block among them."""
    names = ['serve_cache_rows_fetched_total{kind="%s"}' % k
             for k in ("full", "window")] + \
            ['serve_cache_rows_read_total{kind="%s"}' % k
             for k in ("full", "window")]
    model = _model(jnp.float32)
    params = model.init(jax.random.PRNGKey(3),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    prompt = np.arange(1, 20, dtype=np.int32)

    def one_step():
        before = [_counter(n) for n in names]
        engine = DecodeEngine(model, params, slots=3, cache_len=64)
        engine.prefill_many([(0, prompt[:5], 1), (2, prompt, 1)])
        engine.decode(busy=[0, 2])      # positions 5 and 19; slot 1 idle
        return [_counter(n) - b for n, b in zip(names, before)]

    # one full layer of 64 rows, three rings of 24; three slots
    assert one_step() == [3 * 64, 3 * 3 * 24, 6 + 20, 3 * (6 + 20)]
    _force_ragged(monkeypatch)
    # full, blocks of 16: 6 -> 16, idle 1 -> 16, 20 -> 32; rings, blocks
    # of 8: 6 -> 8, idle 1 -> 8, 20 -> 24
    assert one_step() == [16 + 16 + 32, 3 * (8 + 8 + 24), 6 + 20,
                          3 * (6 + 20)]
