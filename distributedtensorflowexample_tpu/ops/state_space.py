"""Mamba-2's state-space recurrence (state-space duality, SSD) in its two
forms over ONE state: the third recurrence beside the gated delta rule's
two decays (``ops/linear_attention.py``), over the same ``state`` kind of
the engine's cache.

Per head, with a state ``S [P, N]`` in float32 (``S_0`` = what the caller
hands in, zeros for a new request; ``P`` the head's features, ``N`` the
state's width)::

    S_t = exp(g_t) S_{t-1} + (dt_t x_t) B_t^T;    y_t = S_t C_t

``dt_t > 0`` is the step's length (the model's ``softplus``), ``g_t = dt_t
A <= 0`` the log of the step's decay, one number a head; ``B_t`` and
``C_t`` ``[N]`` are ONE pair a position for every head (Mamba-2 with one
group).  There is no delta: what a position writes does not depend on the
state, so unlike the delta rule's the output reads only the UPDATED state
and a chunk's contributions are independent of the state it starts from.
The skip ``D x_t`` and the gate are the model's.

:func:`ssd_step` is the equations for one token a slot (decode), every
product elementwise float32 (no matrix unit rounds the state).  It is
``jnp`` lines on every backend: because the output reads only the updated
state, XLA:TPU lowers them to ONE fused pass a layer that reads the state
once and writes it once in place, at 96% of the HBM's pace inside the
decode program (2.04 ms for 1.61 GB at 192 slots).  A Pallas kernel for
the same pass was written and measured on the chip in PR 41 — 2.86 ms a
call in the program, 2.54 at best in any layout or block size alone
against XLA's 2.52 alone — and was not kept (PERF.md section 6, PR 41);
the delta rule's step, whose output needs the state twice, keeps its
kernel (``ops/pallas/delta_step.py``).

:func:`ssd_sequence` is the same equations for a whole sequence (prefill,
the training-shape forward) in chunks of :data:`CHUNK` positions.  With
``G_t`` the running sum of ``g`` inside a chunk and ``S_0`` the state the
chunk starts from::

    Y   = exp(G) * (C S_0^T) + ((C B^T) * exp(G_t - G_s))_{s <= t} (dt X)
    S_Q = exp(G_Q) S_0 + sum_s exp(G_Q - G_s) (dt_s x_s) B_s^T

``C B^T`` is one ``[Q, Q]`` matrix a chunk for all heads.  Every chunk's
own contribution to its last state (the sum above) is made at once; the
walk over the chunks is then a plain linear recurrence, ``S <- exp(G_Q) S
+ U``, elementwise and with no product in it, that leaves each chunk the
state it starts from; the products with those states are made at once
again.  Exponents are masked before the exponential (all are <= 0: no
decay is ever inverted).  Every product that has the state, a decay or
``dt x`` as an operand is float32 at ``Precision.HIGHEST``; ``C B^T``
takes its operands as they come (bfloat16 products are exact in float32).

Both take ``live`` — false on padding (a parked slot, a position past its
prompt's length): such a position neither decays nor writes (``g = 0``,
``dt = 0``), so a prompt's state is the one at its true length whatever
bucket and batch it was prefilled in.

``lm_state_space_total{impl=chunked|recurrent}`` counts the calls traced,
one per state-space layer of a program.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from distributedtensorflowexample_tpu.obs import metrics as obs_metrics

_CALLS = obs_metrics.counter(
    "lm_state_space_total",
    "state-space (SSD) calls traced (one per state-space layer of a "
    "program), by the form taken: chunked (a sequence) | recurrent (one "
    "token a slot)")

#: Positions a chunk of :func:`ssd_sequence` holds: the published
#: ``mamba_chunk_size``.  The work inside a chunk grows with it (``2 Q P``
#: a head and position, at six passes for float32) and the walk between
#: chunks shrinks; on the chip, two prompts of 1,024 positions at the
#: published widths: 2.91 ms a layer at 64, 1.57 at 128, 1.40 at 256 (my
#: chip run, PR 41).  Every prefill bucket (256, 512, 1,024, whole tiles)
#: is whole chunks of it.
CHUNK = 256
_HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32


def ssd_step(x, dt, g, b, c, state, live=None):
    """One token a slot: ``x [S, H, P]``, ``dt`` and ``g`` ``[S, H]``
    float32, ``b``/``c`` ``[S, N]``, ``state [S, H, P, N]`` float32,
    ``live [S]`` (None: all).  Returns ``(y [S, H, P] float32, state')``;
    a slot that is not live keeps its state."""
    _CALLS.labels(impl="recurrent").inc()
    dx = dt[..., None] * x.astype(F32)                      # [S, H, P]
    new = (jnp.exp(g)[..., None, None] * state
           + dx[..., None] * b.astype(F32)[:, None, None, :])
    y = jnp.sum(new * c.astype(F32)[:, None, None, :], axis=-1)
    if live is not None:
        new = jnp.where(live[:, None, None, None], new, state)
    return y, new


def ssd_sequence(x, dt, g, b, c, state, live=None, chunk: int = CHUNK):
    """A whole sequence: ``x [B, T, H, P]``, ``dt`` and ``g`` ``[B, T,
    H]`` float32, ``b``/``c`` ``[B, T, N]``, ``state [B, H, P, N]``
    float32 (what position 0 starts from), ``live [B, T]`` (None: all).
    Returns ``(y [B, T, H, P] float32, state' at each row's last live
    position)``."""
    _CALLS.labels(impl="chunked").inc()
    B, T, H, P = x.shape
    N = b.shape[-1]
    if live is not None:
        g = jnp.where(live[..., None], g, 0.0)
        dt = jnp.where(live[..., None], dt, 0.0)
    dx = dt[..., None] * x.astype(F32)                      # [B, T, H, P]
    pad = -T % chunk
    if pad:     # padded positions are dead: no decay, nothing written
        dx, g, b, c = (jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (
            a.ndim - 2)) for a in (dx, g, b, c))
    n = (T + pad) // chunk
    # [B, H, n, Q, ...] a head, [B, n, Q, N] the pair all heads share.
    dx = jnp.moveaxis(dx.reshape(B, n, chunk, H, P), 3, 1)
    G = jnp.cumsum(jnp.moveaxis(g.reshape(B, n, chunk, H), 3, 1), axis=-1)
    b, c = (a.reshape(B, n, chunk, N) for a in (b, c))
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    cb = jnp.einsum("bntk,bnsk->bnts", c, b, preferred_element_type=F32)
    # exp(G_t - G_s) for s <= t; the masked half would overflow.
    m = cb[:, None] * jnp.exp(jnp.where(
        lower, G[..., :, None] - G[..., None, :], -jnp.inf))
    y = jnp.einsum("bhnts,bhnsp->bhntp", m, dx, precision=_HIGHEST)
    # What of each position's writing is left at its chunk's end.
    left = jnp.exp(G[..., -1:] - G)[..., None] * dx
    bf, cf = b.astype(F32), c.astype(F32)
    own = jnp.einsum("bhnsp,bnsk->bhnpk", left, bf, precision=_HIGHEST)
    g_end = jnp.exp(G[..., -1])                             # [B, H, n]

    def one(S, xs):
        own_c, g_c = xs
        return g_c[..., None, None] * S + own_c, S      # emits the incoming

    chunks_first = lambda a: jnp.moveaxis(a, 2, 0)
    state, incoming = jax.lax.scan(
        one, state.astype(F32), (chunks_first(own), chunks_first(g_end)))
    incoming = jnp.moveaxis(incoming, 0, 2)             # [B, H, n, P, N]
    y = y + jnp.exp(G)[..., None] * jnp.einsum(
        "bntk,bhnpk->bhntp", cf, incoming, precision=_HIGHEST)
    # y [B, H, n, Q, P] -> [B, T, H, P]
    y = jnp.moveaxis(y.reshape(B, H, T + pad, P), 1, 2)
    return y[:, :T], state
