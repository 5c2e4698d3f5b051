"""The gated delta rule (Gated DeltaNet's linear attention) in its two
forms over ONE state, and the short causal convolution that precedes it.

Per head, with a state ``S [Dk, Dv]`` in float32 (``S_0`` = what the
caller hands in, zeros for a new request)::

    S = exp(g_t) S;   d_t = beta_t (v_t - S^T k_t);   S = S + k_t d_t^T
    o_t = S^T q_t

``g_t <= 0`` is the log of the step's decay, ``beta_t`` in (0, 1) the
writing strength; ``q`` and ``k`` arrive normalised (and ``q`` scaled) by
the model.

:func:`recurrent_step` is the equations for one token a slot (decode):
the state is read for ``S^T k`` and ``S^T q`` together, and read and
written once more for the update — ``o_t = exp(g_t) S^T q_t + (k_t . q_t)
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
``Precision.HIGHEST``; the two products of ``q`` and ``k`` with ``k``
take them as they come.

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
_HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32


def recurrent_step(q, k, v, g, beta, state, live=None):
    """One token a slot: ``q``/``k`` ``[S, H, Dk]``, ``v`` ``[S, H, Dv]``,
    ``g``/``beta`` ``[S, H]`` float32, ``state`` ``[S, H, Dk, Dv]``
    float32, ``live [S]`` (None: all).  Returns ``(o [S, H, Dv] float32,
    state')``; a slot that is not live keeps its state."""
    _CALLS.labels(impl="recurrent").inc()
    if jax.default_backend() == "tpu":
        # Imported where the kernel can be taken (jax.experimental.pallas
        # costs every CPU run a second or two to import).
        from distributedtensorflowexample_tpu.ops.pallas import delta_step
        if delta_step.tiles(q.shape[1], q.shape[2], v.shape[2]):
            return delta_step.delta_step(q, k, v, g, beta, state, live)
    q, k, v = (x.astype(F32) for x in (q, k, v))
    a = jnp.exp(g)[..., None]                                   # [S, H, 1]
    sk = jnp.sum(state * k[..., None], axis=-2)                 # S^T k
    sq = jnp.sum(state * q[..., None], axis=-2)                 # S^T q
    d = beta[..., None] * (v - a * sk)
    o = a * sq + jnp.sum(k * q, axis=-1, keepdims=True) * d
    new = a[..., None] * state + k[..., None] * d[..., None, :]
    if live is not None:
        new = jnp.where(live[:, None, None, None], new, state)
    return o, new


def chunked_sequence(q, k, v, g, beta, state, live=None, chunk: int = CHUNK):
    """A whole sequence: ``q``/``k`` ``[B, T, H, Dk]``, ``v`` ``[B, T, H,
    Dv]``, ``g``/``beta`` ``[B, T, H]`` float32, ``state`` ``[B, H, Dk,
    Dv]`` float32 (what position 0 starts from), ``live [B, T]`` (None:
    all).  Returns ``(o [B, T, H, Dv] float32, state' at each row's last
    live position)``."""
    _CALLS.labels(impl="chunked").inc()
    B, T, H, Dk = q.shape
    Dv = v.shape[-1]
    if live is not None:
        g = jnp.where(live[..., None], g, 0.0)
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
    G = jnp.cumsum(g, axis=-1)                                  # [B,H,n,C]
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    # exp(G_t - G_s) for s <= t; the masked half would overflow.
    decay = jnp.exp(jnp.where(lower, G[..., :, None] - G[..., None, :],
                              -jnp.inf))
    kk = jnp.einsum("bhntd,bhnsd->bhnts", k, k, preferred_element_type=F32)
    qk = jnp.einsum("bhntd,bhnsd->bhnts", q, k, preferred_element_type=F32)
    strict = jnp.tril(jnp.ones((chunk, chunk), bool), -1)
    A = jnp.where(strict, beta[..., None] * decay * kk, 0.0)
    kf, vf, qf = (x.astype(F32) for x in (k, v, q))
    eG = jnp.exp(G)[..., None]
    rhs = jnp.concatenate([beta[..., None] * vf,
                           beta[..., None] * eG * kf], axis=-1)
    uw = jax.lax.linalg.triangular_solve(
        A + jnp.eye(chunk, dtype=F32), rhs, left_side=True, lower=True,
        unit_diagonal=True)
    U, W = uw[..., :Dv], uw[..., Dv:]
    attn = qk * decay                       # zero above the diagonal
    q_in = eG * qf                          # what reads the incoming state
    # exp(G_C - G_s) k_s: what of each delta is left at the chunk's end.
    k_out = jnp.exp(G[..., -1:] - G)[..., None] * kf
    g_end = jnp.exp(G[..., -1])                                 # [B,H,n]

    def one(S, xs):
        U_c, W_c, attn_c, q_c, k_c, g_c = xs
        D = U_c - jnp.einsum("bhtk,bhkv->bhtv", W_c, S, precision=_HIGHEST)
        o = (jnp.einsum("bhtk,bhkv->bhtv", q_c, S, precision=_HIGHEST)
             + jnp.einsum("bhts,bhsv->bhtv", attn_c, D, precision=_HIGHEST))
        S = g_c[..., None, None] * S + jnp.einsum(
            "bhtk,bhtv->bhkv", k_c, D, precision=_HIGHEST)
        return S, o

    chunks_first = lambda x: jnp.moveaxis(x, 2, 0)
    state, o = jax.lax.scan(one, state.astype(F32), tuple(
        chunks_first(x) for x in (U, W, attn, q_in, k_out, g_end)))
    # o [n, B, H, C, Dv] -> [B, T, H, Dv]
    o = jnp.moveaxis(o, 0, 2).reshape(B, H, T + pad, Dv)
    return jnp.swapaxes(o, 1, 2)[:, :T], state


def causal_conv_sequence(x, kernel, lengths=None):
    """Depthwise causal convolution over a sequence that starts from
    nothing: ``x [B, T, C]``, ``kernel [K, C]`` (``y_t = sum_j kernel[j]
    x_{t - K + 1 + j}``, inputs before position 0 zero), ``lengths [B]``
    each row's live length (None: ``T``).  Returns ``(y [B, T, C] float32,
    state [B, K - 1, C]``: the last ``K - 1`` inputs up to each row's
    length, in ``x``'s type)."""
    B, T, _ = x.shape
    K = kernel.shape[0]
    xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    w = kernel.astype(F32)
    y = sum(w[j] * xp[:, j:j + T].astype(F32) for j in range(K))
    if lengths is None:
        return y, xp[:, T:]
    # Input t sits at row t + K - 1 of xp: the K - 1 before `length`.
    at = lengths[:, None] + jnp.arange(K - 1)[None]             # [B, K-1]
    return y, jnp.take_along_axis(xp, at[:, :, None], axis=1)


def causal_conv_step(x, kernel, state, live=None):
    """One token a slot: ``x [S, C]``, ``state [S, K - 1, C]`` the inputs
    before it.  Returns ``(y [S, C] float32, state')``; a slot that is
    not live keeps its state."""
    window = jnp.concatenate([state, x[:, None].astype(state.dtype)], axis=1)
    y = jnp.sum(kernel.astype(F32)[None] * window.astype(F32), axis=1)
    new = window[:, 1:]
    if live is not None:
        new = jnp.where(live[:, None, None], new, state)
    return y, new
