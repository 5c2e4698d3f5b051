"""The gated delta rule (the linear attention of Gated DeltaNet and of
Kimi Delta Attention) in its two forms over ONE state, and the short
causal convolution that precedes it.

Per head, with a state ``S [Dk, Dv]`` in float32 (``S_0`` = what the
caller hands in, zeros for a new request)::

    S = Diag(exp(g_t)) S;   d_t = beta_t (v_t - S^T k_t);   S = S + k_t d_t^T
    o_t = S^T q_t

``g_t <= 0`` is the log of the step's decay, ``beta_t`` in (0, 1) the
writing strength; ``q`` and ``k`` arrive normalised (and ``q`` scaled) by
the model.  **The decay has two shapes**, told apart by ``g``'s rank: one
number a head (``g [..., H]``, Gated DeltaNet: every row of the state
decays alike, ``Diag`` is a scalar) or one for each of a head's key
channels (``g [..., H, Dk]``, Kimi Delta Attention: row ``i`` of the
state decays by ``exp(g_t[i])``).  The scalar decay is the broadcast
case of the other; every function below takes either.

:func:`recurrent_step` is the equations for one token a slot (decode):
the state is read for ``S^T k`` and ``S^T q`` together, and read and
written once more for the update — ``o_t = S^T (exp(g_t) q_t) + (k_t . q_t)
d_t`` is the same ``S'^T q_t`` without a third pass over ``S'``.  The
products with the state are elementwise float32 (no matrix unit rounds
the state to bfloat16).  Built for a TPU, where the state is whole tiles,
it is one kernel (``ops/pallas/delta_step.py``) that holds a slot's state
in VMEM for all of that — one read, one write; XLA's lowering of the
lines below reads the state twice — and elsewhere those lines.

:func:`chunked_sequence` is the same equations for a whole sequence
(prefill, the training-shape forward) in chunks of :data:`CHUNK`
positions.  With ``G_t`` the running sum of ``g`` inside a chunk and
``S_0`` the state the chunk starts from, the chunk's deltas solve a unit
lower-triangular system::

    (I + A) D = beta (V - exp(G) K S_0),   A_ts = beta_t exp(G_t - G_s) k_t.k_s  (s < t)
    O   = exp(G) Q S_0 + (Q K^T * exp(G_t - G_s))_{s <= t} D
    S_C = exp(G_C) S_0 + (exp(G_C - G) K)^T D

``U = (I + A)^-1 beta V`` and ``W = (I + A)^-1 beta exp(G) K`` are made
for every chunk at once (``D = U - W S_0``); only the walk over the
chunks, three products a chunk, is sequential.  Every product that has
the state, a delta or the solve's output as an operand is float32 at
``Precision.HIGHEST``; under a scalar decay the two products of ``q``
and ``k`` with ``k`` take them as they come.

**Under a decay a channel** ``exp(G_t - G_s)`` is a vector and no longer
factors out of ``k_t . k_s``: the products are ``(k_t exp(G_t)) . (k_s
exp(-G_s))``, and with a decay as strong as ``exp(-5)`` a step
``exp(-G_s)`` would reach ``exp(320)`` over a chunk.  So the chunk's
rows go in blocks of :data:`DECAY_BLOCK` = 16 positions, each relative
to its own first position ``b``: ``(k_t exp(G_t - G_b)) . (k_s exp(G_b -
G_s))`` for the block's rows ``t`` against every ``s <= t`` of the
chunk.  The first factor's exponent lies in ``[-75, 0]``; the second's
is negative for ``s`` before the block and at most ``+75`` inside it
(the later ``s`` are masked before the exponential): everything stays
inside float32, which a lower bound on the decay (the model's ``-5`` a
step) is what guarantees.  These products are float32 at
``Precision.HIGHEST`` too: their operands are no longer the bfloat16
``k`` but ``k`` times a float32 decay.

Both take ``live`` — false on padding (a parked slot, a position past
its prompt's length): such a position neither decays nor writes (``g =
0``, ``beta = 0``), so a prompt's state is the one at its true length
whatever bucket and batch it was prefilled in.  The same holds for
:func:`causal_conv_sequence` / :func:`causal_conv_step`, whose state is
the last ``kernel - 1`` inputs.

``lm_linear_attention_total{impl=chunked|recurrent}`` counts the calls
traced, one per layer of a program.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from distributedtensorflowexample_tpu.obs import metrics as obs_metrics

_CALLS = obs_metrics.counter(
    "lm_linear_attention_total",
    "gated-delta-rule calls traced (one per linear-attention layer of a "
    "program), by the form taken: chunked (a sequence) | recurrent (one "
    "token a slot)")

#: Positions a chunk of :func:`chunked_sequence` holds: the published
#: implementation's; the ``[CHUNK, CHUNK]`` systems fill half a 128-wide
#: matrix unit and a chunk's decays stay far inside float32.
CHUNK = 64
#: Positions whose per-channel decays are taken relative to one position
#: (the module docstring): 15 steps of at most ``exp(-5)`` each are
#: ``exp(+-75)``, inside float32; the published implementation's.
DECAY_BLOCK = 16
_HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32


def recurrent_step(q, k, v, g, beta, state, live=None):
    """One token a slot: ``q``/``k`` ``[S, H, Dk]``, ``v`` ``[S, H, Dv]``,
    ``beta`` ``[S, H]`` and ``g`` ``[S, H]`` or ``[S, H, Dk]`` float32,
    ``state`` ``[S, H, Dk, Dv]`` float32, ``live [S]`` (None: all).
    Returns ``(o [S, H, Dv] float32, state')``; a slot that is not live
    keeps its state."""
    _CALLS.labels(impl="recurrent").inc()
    if jax.default_backend() == "tpu":
        # Imported where the kernel can be taken (jax.experimental.pallas
        # costs every CPU run a second or two to import).
        from distributedtensorflowexample_tpu.ops.pallas import delta_step
        if delta_step.tiles(q.shape[1], q.shape[2], v.shape[2]):
            return delta_step.delta_step(q, k, v, g, beta, state, live)
    q, k, v = (x.astype(F32) for x in (q, k, v))
    a = jnp.exp(g if g.ndim == q.ndim else g[..., None])    # [S, H, Dk | 1]
    sk = jnp.sum(state * (a * k)[..., None], axis=-2)       # (a S)^T k
    sq = jnp.sum(state * (a * q)[..., None], axis=-2)       # (a S)^T q
    d = beta[..., None] * (v - sk)
    o = sq + jnp.sum(k * q, axis=-1, keepdims=True) * d
    new = a[..., None] * state + k[..., None] * d[..., None, :]
    if live is not None:
        new = jnp.where(live[:, None, None, None], new, state)
    return o, new


def _decayed_products(q, k, G, chunk: int):
    """``(q_t . k_s, k_t . k_s)`` with ``exp(G_t - G_s)`` inside the sums,
    a decay a channel: ``q``/``k``/``G`` ``[..., C, Dk]`` float32 ->
    two ``[..., C, C]`` whose entries ``s <= t`` are meant (the others
    are finite and the caller's to mask).  Blocks of
    :data:`DECAY_BLOCK` rows, each relative to its first position (the
    module docstring)."""
    nb = chunk // DECAY_BLOCK
    blocks = lambda x: x.reshape(*x.shape[:-2], nb, DECAY_BLOCK, x.shape[-1])
    first = blocks(G)[..., :1, :]                       # [..., nb, 1, Dk]
    left = jnp.exp(blocks(G) - first)                   # [..., nb, 16, Dk]
    # Block i's rows see the positions up to its own last; a later one's
    # exponent would be positive without bound.
    seen = jnp.arange(chunk)[None] < DECAY_BLOCK * (jnp.arange(nb)[:, None]
                                                    + 1)    # [nb, C]
    right = k[..., None, :, :] * jnp.exp(jnp.where(
        seen[..., None], first - G[..., None, :, :], -jnp.inf))
    out = lambda x: jnp.einsum(
        "...td,...sd->...ts", left * blocks(x), right, precision=_HIGHEST
    ).reshape(*x.shape[:-2], chunk, chunk)
    return out(q), out(k)


def chunked_sequence(q, k, v, g, beta, state, live=None, chunk: int = CHUNK):
    """A whole sequence: ``q``/``k`` ``[B, T, H, Dk]``, ``v`` ``[B, T, H,
    Dv]``, ``beta`` ``[B, T, H]`` and ``g`` ``[B, T, H]`` or ``[B, T, H,
    Dk]`` float32, ``state`` ``[B, H, Dk, Dv]`` float32 (what position 0
    starts from), ``live [B, T]`` (None: all).  Returns ``(o [B, T, H,
    Dv] float32, state' at each row's last live position)``."""
    _CALLS.labels(impl="chunked").inc()
    B, T, H, Dk = q.shape
    Dv = v.shape[-1]
    by_channel = g.ndim == q.ndim
    if not by_channel:
        g = g[..., None]            # the broadcast case: one channel
    if live is not None:
        g = jnp.where(live[..., None, None], g, 0.0)
        beta = jnp.where(live[..., None], beta, 0.0)
    pad = -T % chunk
    if pad:     # padded positions are dead: no decay, nothing written
        q, k, v, g, beta = (jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (
            x.ndim - 2)) for x in (q, k, v, g, beta))
    n = (T + pad) // chunk
    # [B, H, n, C, ...]: a chunk's positions are the rows of its matrices.
    split = lambda x: jnp.moveaxis(
        x.reshape(B, n, chunk, H, *x.shape[3:]), 3, 1)
    q, k, v, g, beta = (split(x) for x in (q, k, v, g, beta))
    G = jnp.cumsum(g, axis=-2)                          # [B,H,n,C,Dk | 1]
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    kf, vf, qf = (x.astype(F32) for x in (k, v, q))
    if by_channel:
        qk, kk = _decayed_products(qf, kf, G, chunk)
    else:
        # exp(G_t - G_s) for s <= t; the masked half would overflow.
        decay = jnp.exp(jnp.where(
            lower, G[..., :, None, 0] - G[..., None, :, 0], -jnp.inf))
        kk = decay * jnp.einsum("bhntd,bhnsd->bhnts", k, k,
                                preferred_element_type=F32)
        qk = decay * jnp.einsum("bhntd,bhnsd->bhnts", q, k,
                                preferred_element_type=F32)
    strict = jnp.tril(jnp.ones((chunk, chunk), bool), -1)
    A = jnp.where(strict, beta[..., None] * kk, 0.0)
    eG = jnp.exp(G)
    rhs = jnp.concatenate([beta[..., None] * vf,
                           beta[..., None] * eG * kf], axis=-1)
    uw = jax.lax.linalg.triangular_solve(
        A + jnp.eye(chunk, dtype=F32), rhs, left_side=True, lower=True,
        unit_diagonal=True)
    U, W = uw[..., :Dv], uw[..., Dv:]
    attn = jnp.where(lower, qk, 0.0)
    q_in = eG * qf                          # what reads the incoming state
    # exp(G_C - G_s) k_s: what of each delta is left at the chunk's end.
    k_out = jnp.exp(G[..., -1:, :] - G) * kf
    g_end = jnp.exp(G[..., -1, :])                      # [B,H,n,Dk | 1]

    def one(S, xs):
        U_c, W_c, attn_c, q_c, k_c, g_c = xs
        D = U_c - jnp.einsum("bhtk,bhkv->bhtv", W_c, S, precision=_HIGHEST)
        o = (jnp.einsum("bhtk,bhkv->bhtv", q_c, S, precision=_HIGHEST)
             + jnp.einsum("bhts,bhsv->bhtv", attn_c, D, precision=_HIGHEST))
        S = g_c[..., None] * S + jnp.einsum(
            "bhtk,bhtv->bhkv", k_c, D, precision=_HIGHEST)
        return S, o

    chunks_first = lambda x: jnp.moveaxis(x, 2, 0)
    state, o = jax.lax.scan(one, state.astype(F32), tuple(
        chunks_first(x) for x in (U, W, attn, q_in, k_out, g_end)))
    # o [n, B, H, C, Dv] -> [B, T, H, Dv]
    o = jnp.moveaxis(o, 0, 2).reshape(B, H, T + pad, Dv)
    return jnp.swapaxes(o, 1, 2)[:, :T], state


def causal_conv_sequence(x, kernel, lengths=None, bias=None):
    """Depthwise causal convolution over a sequence that starts from
    nothing: ``x [B, T, C]``, ``kernel [K, C]`` (``y_t = sum_j kernel[j]
    x_{t - K + 1 + j}``, inputs before position 0 zero; plus ``bias [C]``
    where the layer has one), ``lengths [B]`` each row's live length
    (None: ``T``).  Returns ``(y [B, T, C] float32, state [B, K - 1, C]``:
    the last ``K - 1`` inputs up to each row's length, in ``x``'s type)."""
    B, T, _ = x.shape
    K = kernel.shape[0]
    xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    w = kernel.astype(F32)
    y = sum(w[j] * xp[:, j:j + T].astype(F32) for j in range(K))
    if bias is not None:
        y = y + bias.astype(F32)
    if lengths is None:
        return y, xp[:, T:]
    # Input t sits at row t + K - 1 of xp: the K - 1 before `length`.
    at = lengths[:, None] + jnp.arange(K - 1)[None]             # [B, K-1]
    return y, jnp.take_along_axis(xp, at[:, :, None], axis=1)


def causal_conv_step(x, kernel, state, live=None, bias=None):
    """One token a slot: ``x [S, C]``, ``state [S, K - 1, C]`` the inputs
    before it, ``bias [C]`` where the layer has one.  Returns ``(y [S, C]
    float32, state')``; a slot that is not live keeps its state."""
    window = jnp.concatenate([state, x[:, None].astype(state.dtype)], axis=1)
    y = jnp.sum(kernel.astype(F32)[None] * window.astype(F32), axis=1)
    if bias is not None:
        y = y + bias.astype(F32)
    new = window[:, 1:]
    if live is not None:
        new = jnp.where(live[:, None, None], new, state)
    return y, new
