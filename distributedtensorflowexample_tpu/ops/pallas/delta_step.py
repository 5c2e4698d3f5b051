"""The gated delta rule's token step (``ops/linear_attention.py:
recurrent_step``) as one kernel a layer: a slot's state is brought into
VMEM once, read for ``S^T k`` and ``S^T q``, updated and written back in
place — one read and one write of the state, where XLA's lowering of the
same equations reads it twice (a fusion for the two sums, a fusion for
the update).

Layout.  The state stays as the engine holds it, ``[S, H, Dk, Dv]``
float32, and is aliased onto the output.  Everything else a head needs
is ONE ``(8, 128)`` tile made outside the kernel (a few MB a layer beside
a GB of state): rows ``k``, ``q``, ``beta v``, and ``exp(g)`` and
``exp(g) beta`` broadcast over the row.  A slot that is not live gets
``exp(g) = 1`` and ``beta = 0``: its state comes back as it went in.

The decay's two shapes (``ops/linear_attention.py``) are two bodies of
the one kernel, chosen from ``g``'s rank when the call is traced.  With
a decay a head the lines above hold as they are.  With a decay a key
channel (``g [S, H, Dk]``) the tile's fourth row is ``exp(g)`` itself,
one value a channel, and its fifth ``beta``; the row becomes a column as
``k`` and ``q`` do, the state is scaled by it row by row once, and the
scaled state serves both sums and the update: ``S^T k`` and ``S^T q``
are then of ``Diag(exp(g)) S``.

Inside, per head: a row vector lies along the lanes, and the products
with the state need ``k`` and ``q`` along the SUBLANES (``S[i, j] *
k[i]``); a row becomes a column by masking it with the identity and
summing over the lanes.  Then ``S^T k`` and ``S^T q`` are sums over the
sublanes, ``d = beta v - exp(g) beta S^T k`` a row, the update ``exp(g) S
+ k d^T`` a column times a row, and ``o = exp(g) S^T q + (k . q) d``.
All float32 on the vector unit: no matrix unit rounds the state.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distributedtensorflowexample_tpu.ops.pallas.tiling import (
    LANES, SUBLANES, resolve_interpret)

#: Heads a grid step takes: 8 states of 64 KB, in and out, double
#: buffered — 2 MB of VMEM, and DMAs of 512 KB, long enough to run at
#: the HBM's pace.
HEADS = 8
F32 = jnp.float32


def tiles(heads: int, dk: int, dv: int) -> bool:
    """The shapes the kernel takes: states of whole ``(8, 128)`` tiles,
    keys one lane group wide (a row's tile holds them), heads in whole
    blocks."""
    return dk == LANES and dv == LANES and heads % HEADS == 0


def _kernel(x_ref, s_ref, o_ref, s_out_ref, *, by_channel: bool):
    dk = s_ref.shape[1]
    eye = (jax.lax.broadcasted_iota(jnp.int32, (dk, dk), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (dk, dk), 1))
    column = lambda row: jnp.sum(jnp.where(eye, row, 0.0), axis=1,
                                 keepdims=True)             # [Dk, 1]
    for h in range(s_ref.shape[0]):
        x = x_ref[h]                                        # [8, 128]
        k, q, bv, a, ab = (x[i:i + 1] for i in range(5))    # rows
        S = s_ref[h]                                        # [Dk, Dv]
        k_col = column(k)
        kq = jnp.sum(k * q, axis=1, keepdims=True)
        if by_channel:      # a: exp(g) a channel; ab: beta over the row
            S = column(a) * S
        sk = jnp.sum(S * k_col, axis=0, keepdims=True)      # S^T k
        sq = jnp.sum(S * column(q), axis=0, keepdims=True)  # S^T q
        d = bv - ab * sk
        if by_channel:      # S is the decayed state already
            o_ref[h:h + 1, :] = sq + kq * d
            s_out_ref[h] = S + k_col * d
        else:
            o_ref[h:h + 1, :] = a * sq + kq * d
            s_out_ref[h] = a * S + k_col * d


@functools.partial(jax.jit, static_argnames=("interpret",),
                   donate_argnames=("state",))
def delta_step(q, k, v, g, beta, state, live=None, *,
               interpret: bool | None = None):
    """:func:`ops.linear_attention.recurrent_step`'s contract: ``q``/``k``
    ``[S, H, Dk]``, ``v`` ``[S, H, Dv]``, ``beta`` ``[S, H]``, ``g`` ``[S,
    H]`` or ``[S, H, Dk]``, ``state`` ``[S, H, Dk, Dv]`` float32 (updated
    in place), ``live [S]`` -> ``(o [S, H, Dv] float32, state')``."""
    S, H, Dk = q.shape
    Dv = v.shape[-1]
    by_channel = g.ndim == q.ndim
    a = jnp.exp(g.astype(F32))
    beta = beta.astype(F32)
    if live is not None:
        a = jnp.where(live.reshape((S,) + (1,) * (a.ndim - 1)), a, 1.0)
        beta = jnp.where(live[:, None], beta, 0.0)
    row = lambda s: jnp.broadcast_to(s[..., None], (S, H, LANES))
    x = jnp.stack([k.astype(F32), q.astype(F32),
                   beta[..., None] * v.astype(F32)]
                  + ([a, row(beta)] if by_channel
                     else [row(a), row(a * beta)])
                  + [jnp.zeros((S, H, LANES), F32)] * (SUBLANES - 5), axis=2)
    vec = pl.BlockSpec((None, HEADS, Dv), lambda s, h: (s, h, 0))
    mat = pl.BlockSpec((None, HEADS, Dk, Dv), lambda s, h: (s, h, 0, 0))
    o, state = pl.pallas_call(
        functools.partial(_kernel, by_channel=by_channel),
        grid=(S, H // HEADS),
        in_specs=[pl.BlockSpec((None, HEADS, SUBLANES, LANES),
                               lambda s, h: (s, h, 0, 0)), mat],
        out_specs=[vec, mat],
        out_shape=[jax.ShapeDtypeStruct((S, H, Dv), F32),
                   jax.ShapeDtypeStruct(state.shape, F32)],
        input_output_aliases={1: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=resolve_interpret(interpret),
        name="gated_delta_step",
    )(x, state)
    return o, state
