"""A mixture-of-experts feed-forward layer for ONE share of an
expert-parallel deployment: the layer is told which expert ids it
holds, routes every token over ALL the experts the router knows, and
computes the part of the result its own experts give.  What the absent
experts would have added is left out (their chips add it in the
deployment; nothing here stands in for them or for their traffic).

Routing (:func:`route`) has two forms, which a configuration names as
architecture data (``score_func``), both in float32 over every expert.
The sigmoid-score form: ``s = sigmoid(m @ Wr)``, the top ``k`` of ``s +
bias`` are selected, and the weights are the selected scores themselves
(the bias only selects), normalised over the ``k`` and scaled.  The
softmax form: ``s = softmax(m @ Wr)`` over all the experts, the top
``k`` of ``s`` (this form has no bias), the weights the selected
probabilities, normalised over the ``k``.

The experts (:func:`expert_ffn`) are SiLU-gated, ``(silu(x @ G) * (x @
U)) @ D``.  All (token, expert) pairs are sorted by expert, those on held
experts first; pairs on absent experts and pairs of dead tokens (padding,
parked slots) sort last, into no group.  The sorted rows are walked in
blocks of :data:`BLOCK_ROWS`, only as many blocks as the held pairs fill
(a loop whose trip count the routing decides): each block gathers its
tokens, goes through three grouped products (``jax.lax.ragged_dot``,
which XLA:TPU lowers to a grouped-matmul kernel that reads only the
experts a block has rows for but multiplies every row it is handed —
which is why it is handed held pairs only), and adds its weighted rows
onto their tokens.  No pair is ever dropped: the walk is as long as the
held pairs need, so any imbalance — every token on one expert — fits.
The same code serves prefill (thousands of pairs, a few blocks) and
decode (a few dozen pairs, one block, no loop).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

#: Sorted (token, expert) rows that go through the grouped products at a
#: time.  At d = f = 3072 a block's gathered rows, its two hidden arrays
#: and its output are 2048 x 4 x 3072 x 2 B = 50 MB; a block spans the
#: few experts its rows belong to, so over a walk each touched expert's
#: weights are read about once.
BLOCK_ROWS = 2048

#: What :func:`expert_ffn` counts, in this order, as one int32 vector.
STATS = ("pairs_held", "pairs_absent", "experts_touched")


def route(m, router_kernel, router_bias, *, top_k: int, route_scale: float,
          route_norm: bool = True, score_func: str = "sigmoid"):
    """``m [N, d]`` -> ``(sel [N, k] int32, w [N, k] f32)``: the experts
    each token selects among ALL ``router_kernel.shape[1]`` and the
    weight of each, by the form ``score_func`` names (``router_bias``
    None: nothing but the scores selects).  Scores in float32 (the
    products of bfloat16 operands are exact there)."""
    if score_func not in ("sigmoid", "softmax"):
        raise ValueError(f"unknown score_func {score_func!r}")
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


def _swiglu(g, u, dtype):
    return (jax.nn.silu(g.astype(jnp.float32))
            * u.astype(jnp.float32)).astype(dtype)


def expert_ffn(m, sel, w, gate, up, down, *, first_expert: int, live=None):
    """The held experts' part of the layer: ``m [N, d]``, ``sel``/``w``
    from :func:`route`, ``gate``/``up`` ``[E, d, f]`` and ``down`` ``[E,
    f, d]`` the held experts ``first_expert .. first_expert + E - 1``,
    ``live [N]`` false for tokens that are padding.  Returns ``(y [N,
    d], stats int32[3])`` — the pairs computed here, the live pairs left
    to absent experts, and the held experts that got at least one pair
    (:data:`STATS`)."""
    N, k = sel.shape
    E = gate.shape[0]
    with jax.named_scope("moe.experts"):
        local = sel - first_expert
        here = (local >= 0) & (local < E)
        alive = jnp.ones((N, 1), bool) if live is None else live[:, None]
        # Index among the held experts; E for a pair not computed here.
        key = jnp.where(here & alive, local, E).reshape(-1)
        sizes = jnp.sum(key[:, None] == jnp.arange(E, dtype=key.dtype)[None],
                        axis=0, dtype=jnp.int32)        # pairs per expert
        held = jnp.sum(sizes)
        stats = jnp.stack([held, jnp.sum(alive & ~here, dtype=jnp.int32),
                           jnp.sum(sizes > 0, dtype=jnp.int32)])
        rows = min(BLOCK_ROWS, N * k)
        order = jnp.argsort(key, stable=True)           # held pairs first
        order = jnp.pad(order, (0, -(N * k) % rows))
        ends = jnp.cumsum(sizes)
        w = w.reshape(-1)

        def block(b, y):
            """Sorted rows ``b * rows`` onward: ``y [N, d]`` float32 plus
            their experts' weighted outputs."""
            at = jax.lax.dynamic_slice_in_dim(order, b * rows, rows)
            first = b * rows
            # Each expert's rows inside this block (none for most).
            part = (jnp.clip(ends - first, 0, rows)
                    - jnp.clip(ends - sizes - first, 0, rows))
            token = at // k
            x = m[token]
            g = jax.lax.ragged_dot(x, gate, part)
            u = jax.lax.ragged_dot(x, up, part)
            o = jax.lax.ragged_dot(_swiglu(g, u, m.dtype), down, part)
            # Rows past the held pairs belong to no group and hold
            # nothing that may be read: selected away, not multiplied
            # by zero.
            mine = (first + jnp.arange(rows) < held)[:, None]
            o = jnp.where(mine, o.astype(jnp.float32) * w[at][:, None], 0.0)
            return y.at[token].add(o)

        y = jnp.zeros(m.shape, jnp.float32)
        if N * k <= rows:
            y = block(0, y)
        else:
            y = jax.lax.fori_loop(0, (held + rows - 1) // rows, block, y)
        return y.astype(m.dtype), stats


def gated_ffn(m, gate, up, down):
    """``(silu(m @ G) * (m @ U)) @ D`` for one dense set of weights (the
    leading dense layers and the shared expert); every product comes out
    in the operands' type, as the grouped products do."""
    return jnp.dot(_swiglu(jnp.dot(m, gate), jnp.dot(m, up), m.dtype), down)
