"""Mamba-2's recurrence in its two forms (ops/state_space.py), and the two
arguments this recurrence's model asked of shared code: a bias on the
short convolution (ops/linear_attention.py) and a scale on attention
(ops/attention.py, ops/pallas/decode_attention.py), whose defaults give
the values they gave."""

import jax.numpy as jnp
import numpy as np
import pytest

from distributedtensorflowexample_tpu.obs import metrics as obs_metrics
from distributedtensorflowexample_tpu.ops import attention as attention_op
from distributedtensorflowexample_tpu.ops import linear_attention as la
from distributedtensorflowexample_tpu.ops import state_space as ss


def _off(a, b):
    """The widest difference, in units of ``b``'s largest value."""
    a, b = np.asarray(a), np.asarray(b)
    return np.abs(a - b).max() / max(1.0, np.abs(b).max())


def _inputs(B=2, T=150, H=4, P=8, N=16, seed=0):
    """Steps of 0.001 to ~1 against A of 1 to 16: decays from ~1 down to
    exp(-16) a step, and a state to start from that is not zero."""
    rng = np.random.default_rng(seed)
    f = lambda *s: rng.normal(size=s).astype(np.float32)
    dt = np.exp(rng.uniform(np.log(1e-3), 0.0, (B, T, H))).astype(np.float32)
    g = -dt * rng.uniform(1.0, 16.0, (H,)).astype(np.float32)
    return tuple(jnp.asarray(x) for x in (
        f(B, T, H, P), dt, g, f(B, T, N), f(B, T, N), f(B, H, P, N)))


def _token_by_token(x, dt, g, b, c, S, live=None):
    ys = []
    for t in range(x.shape[1]):
        y, S = ss.ssd_step(x[:, t], dt[:, t], g[:, t], b[:, t], c[:, t], S,
                           None if live is None else live[:, t])
        ys.append(y)
    return jnp.stack(ys, axis=1), S


@pytest.mark.parametrize("chunk", [256, 128, 64, 50, 37])
@pytest.mark.parametrize("lengths", [None, (150, 77), (64, 1)])
def test_the_chunked_form_is_the_token_form(chunk, lengths):
    """The same outputs at every live position and the same final state,
    from a state that is not zero, for chunk lengths that do (50) and do
    not divide the 150 positions, or hold them all (256), and for rows
    shorter than the sequence;
    past a row's length (``live`` false) neither form decays or writes."""
    x, dt, g, b, c, S0 = _inputs()
    T = x.shape[1]
    live = None if lengths is None else jnp.asarray(
        np.arange(T)[None] < np.asarray(lengths)[:, None])
    y, S = ss.ssd_sequence(x, dt, g, b, c, S0, live, chunk=chunk)
    y_t, S_t = _token_by_token(x, dt, g, b, c, S0, live)
    assert np.isfinite(np.asarray(y)).all()
    seen = np.ones((2, T), bool) if live is None else np.asarray(live)
    assert _off(np.asarray(y)[seen], np.asarray(y_t)[seen]) < 1e-5
    assert _off(S, S_t) < 1e-5
    if lengths == (64, 1):      # row 1: one live position, then nothing
        _, S_1 = ss.ssd_step(x[:, 0], dt[:, 0], g[:, 0], b[:, 0], c[:, 0],
                             S0)
        assert _off(S[1], S_1[1]) < 1e-5


def test_the_token_form_is_the_equations():
    """Written out with numpy for one slot and head: S' = exp(g) S + (dt
    x) B^T, y = S' C."""
    x, dt, g, b, c, S0 = (np.asarray(a) for a in _inputs(B=1, T=1, H=2))
    y, S = ss.ssd_step(*(jnp.asarray(a[:, 0]) for a in (x, dt, g, b, c)),
                       jnp.asarray(S0))
    for h in range(2):
        want = np.exp(g[0, 0, h]) * S0[0, h] + np.outer(
            dt[0, 0, h] * x[0, 0, h], b[0, 0])
        assert np.abs(np.asarray(S)[0, h] - want).max() < 1e-6
        assert np.abs(np.asarray(y)[0, h] - want @ c[0, 0]).max() < 1e-5


def test_the_calls_are_counted_by_form():
    series = 'lm_state_space_total{impl="%s"}'

    def count(impl):
        got = obs_metrics.registry().snapshot()["counters"].get(
            series % impl)
        return (got["value"] if isinstance(got, dict) else got) or 0

    before = count("chunked"), count("recurrent")
    x, dt, g, b, c, S0 = _inputs(T=8)
    ss.ssd_sequence(x, dt, g, b, c, S0)
    ss.ssd_step(x[:, 0], dt[:, 0], g[:, 0], b[:, 0], c[:, 0], S0)
    assert (count("chunked"), count("recurrent")) == (before[0] + 1,
                                                      before[1] + 1)


# ---- what shared code gained, and its defaults -----------------------------

def test_a_convolutions_bias_is_added_and_none_is_what_it_was():
    rng = np.random.default_rng(1)
    f = lambda *s: jnp.asarray(rng.normal(size=s).astype(np.float32))
    x, kernel, bias = f(2, 9, 6), f(4, 6), f(6)
    lengths = jnp.asarray([9, 5])
    y0, s0 = la.causal_conv_sequence(x, kernel, lengths)
    # The value the function gave before it took a bias, written out.
    xp = np.pad(np.asarray(x), ((0, 0), (3, 0), (0, 0)))
    want = sum(np.asarray(kernel)[j] * xp[:, j:j + 9] for j in range(4))
    assert np.array_equal(np.asarray(y0), want.astype(np.float32))
    y1, s1 = la.causal_conv_sequence(x, kernel, lengths, bias)
    assert np.allclose(np.asarray(y1), want + np.asarray(bias), atol=1e-6)
    assert np.array_equal(np.asarray(s0), np.asarray(s1))
    # The step continues the sequence with or without a bias.
    state = s0[:1]                          # row 0 after its 9 inputs
    nxt = f(1, 6)
    full = jnp.concatenate([x[:1], nxt[:, None]], axis=1)
    for bb in (None, bias):
        y_step, _ = la.causal_conv_step(nxt, kernel, state, None, bb)
        y_seq, _ = la.causal_conv_sequence(full, kernel, None, bb)
        assert np.abs(np.asarray(y_step - y_seq[:, -1])).max() < 1e-6


def _attention_inputs(seed=2):
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.normal(size=s).astype(np.float32))
    return f(2, 40, 4, 8), f(2, 40, 2, 8), f(2, 40, 2, 8)


@pytest.mark.parametrize("block", [1024, 16])
def test_attentions_default_scale_is_the_one_it_had(block):
    """``scale`` None and ``Dh ** -0.5`` are one program's values, bit
    for bit, in one tile and in the tiled walk; another scale is the
    softmax of other scores."""
    q, k, v = _attention_inputs()
    was = attention_op.grouped_attention(q, k, v, block=block)
    assert np.array_equal(np.asarray(was), np.asarray(
        attention_op.grouped_attention(q, k, v, block=block,
                                       scale=8 ** -0.5)))
    got = attention_op.grouped_attention(q, k, v, block=block, scale=0.03)
    qh = np.asarray(q).reshape(2, 40, 2, 2, 8)
    s = np.einsum("bthgd,bshd->bhgts", qh, np.asarray(k)) * 0.03
    s = np.where(np.tril(np.ones((40, 40), bool)), s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    want = np.einsum("bhgts,bshd->bthgd", p / p.sum(-1, keepdims=True),
                     np.asarray(v)).reshape(2, 40, 4, 8)
    assert np.abs(np.asarray(got) - want).max() < 1e-5
    assert np.abs(np.asarray(was) - want).max() > 1e-3


def test_decode_attentions_default_scale_is_the_one_it_had():
    """The token step's chain and the ragged kernel (interpret mode): the
    default is ``Dh ** -0.5`` bit for bit, and a stated scale reaches
    both."""
    from distributedtensorflowexample_tpu.ops.pallas import (
        decode_attention as kernel)
    rng = np.random.default_rng(4)
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
    S, R, Hkv, G, Dh = 3, 256, 8, 2, 128
    q, ck, cv = f(S, 1, Hkv, G, Dh), f(S, R, Hkv, Dh), f(S, R, Hkv, Dh)
    lengths = jnp.asarray([[5], [256], [130]])
    was = attention_op.decode_attention(q, ck, cv, lengths)
    assert np.array_equal(np.asarray(was), np.asarray(
        attention_op.decode_attention(q, ck, cv, lengths,
                                      scale=Dh ** -0.5)))
    chain = attention_op.decode_attention(q, ck, cv, lengths, scale=1 / 128)
    assert np.abs(np.asarray(chain - was)).max() > 1e-3
    ragged = lambda **kw: kernel.ragged_decode_attention(
        q[:, 0], ck, cv, lengths[:, 0], interpret=True, **kw)
    assert np.array_equal(np.asarray(ragged()),
                          np.asarray(ragged(scale=Dh ** -0.5)))
    assert np.abs(np.asarray(ragged(scale=1 / 128) - chain[:, 0])).max() \
        < 2e-5
    assert np.abs(np.asarray(ragged() - was[:, 0])).max() < 2e-5
