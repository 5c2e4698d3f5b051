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
probabilities, normalised over the ``k``.  Either form may be limited
to groups (``n_group`` > 1): the experts lie in ``n_group`` equal
groups in id order, a group's score is the sum of its two best
selecting scores, only the ``topk_group`` best groups stay, and the top
``k`` are taken among their experts — a token then reaches the chips of
at most ``topk_group`` groups where each group is one chip's experts.
``n_group`` 1 is no limit, and the same program as before there was one.

The experts (:func:`expert_ffn`) are SiLU-gated, ``(silu(x @ G) * (x @
U)) @ D``.  All (token, expert) pairs are sorted by expert, those on held
experts first; pairs on absent experts and pairs of dead tokens (padding,
parked slots) sort last, into no group.  The sorted rows are walked in
blocks, only as many blocks as the held pairs fill (a loop whose trip
count the routing decides): each block gathers its tokens, goes through
three grouped products (``jax.lax.ragged_dot``, which XLA:TPU lowers to
a grouped-matmul kernel that reads only the experts a block has rows for
but multiplies every row it is handed, in row tiles it chooses from the
block's size — which is why it is handed held pairs only, and no more
rows than they are likely to need), and adds its weighted rows onto
their tokens.  No pair is ever dropped: the walk is as long as the held
pairs need, so any imbalance — every token on one expert — fits, in more
trips.

**How many rows a block has** (:func:`block_rows`) follows from what a
call observes — its pairs, the experts held and the experts the router
knows: twice the pairs that even routing puts on held experts, in whole
tiles of :data:`ROW_TILE`, at most :data:`BLOCK_ROWS`.  A call whose
pairs all fit that block runs it once, with no loop: a token step of a
few dozen slots (32 slots x 4 picks over 32 of 256 experts: 128 rows).
Every other call loops: a token step of many slots (256 x 10 picks over
64 of 512 experts: 2,560 pairs, ~320 of them held, blocks of 640 rows,
one trip unless the routing is skewed), and prefill (thousands of held
pairs, a few blocks of ``BLOCK_ROWS``; a short bucket gets the block
its own pairs call for).  On a TPU v5e a product over 64 experts of
``[2048, 512]`` with ~5 rows an expert takes 0.39 ms in a block of 2,048
rows and 0.21 ms in one of 640, where reading the experts once takes
0.16 (PERF.md section 6, PR 35).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

#: The most sorted (token, expert) rows that go through the grouped
#: products at a time: the cap of :func:`block_rows`, reached by the
#: longer prefill buckets (from 1,024 positions at top-10 over 64 of 512
#: experts, from 2,048 at top-4 over 32 of 256).  At d = f = 3072 such
#: a block's gathered rows, its two hidden arrays and its output are
#: 2048 x 4 x 3072 x 2 B = 50 MB; it spans the few experts its rows
#: belong to, so over a walk each touched expert's weights are read
#: about once.
BLOCK_ROWS = 2048

#: A block is a whole number of these rows (a lane tile; the grouped
#: products' kernels walk rows in multiples of it).
ROW_TILE = 128

#: What :func:`expert_ffn` counts, in this order, as one int32 vector.
STATS = ("pairs_held", "pairs_absent", "experts_touched", "rows_walked")


def block_rows(pairs: int, held: int, known: int) -> int:
    """Sorted rows a block hands the grouped products when ``pairs``
    (token, expert) pairs are routed over ``known`` experts of which
    ``held`` are here: twice the pairs even routing puts on held experts,
    in whole row tiles, never more than :data:`BLOCK_ROWS` nor than there
    are pairs."""
    room = -(-2 * pairs * held // known)
    return min(BLOCK_ROWS, pairs, -(-room // ROW_TILE) * ROW_TILE)


def route(m, router_kernel, router_bias, *, top_k: int, route_scale: float,
          route_norm: bool = True, score_func: str = "sigmoid",
          n_group: int = 1, topk_group: int = 1):
    """``m [N, d]`` -> ``(sel [N, k] int32, w [N, k] f32)``: the experts
    each token selects among ALL ``router_kernel.shape[1]`` and the
    weight of each, by the form ``score_func`` names (``router_bias``
    None: nothing but the scores selects), among the ``topk_group`` best
    of ``n_group`` groups where ``n_group`` > 1.  Scores in float32 (the
    products of bfloat16 operands are exact there)."""
    if score_func not in ("sigmoid", "softmax"):
        raise ValueError(f"unknown score_func {score_func!r}")
    with jax.named_scope("moe.route"):
        s = jnp.dot(m, router_kernel, preferred_element_type=jnp.float32)
        s = (jax.nn.sigmoid(s) if score_func == "sigmoid"
             else jax.nn.softmax(s, axis=-1))
        c = s if router_bias is None else s + router_bias.astype(jnp.float32)
        if n_group > 1:
            groups = c.reshape(c.shape[0], n_group, -1)
            best = jnp.sum(jax.lax.top_k(groups, 2)[0], axis=-1)
            last = jax.lax.top_k(best, topk_group)[0][:, -1:]   # [N, 1]
            c = jnp.where((best >= last)[:, :, None], groups,
                          -jnp.inf).reshape(c.shape)
        _, sel = jax.lax.top_k(c, top_k)
        w = jnp.take_along_axis(s, sel, axis=-1)
        if route_norm:
            w = w / jnp.sum(w, axis=-1, keepdims=True)
        return sel.astype(jnp.int32), route_scale * w


def _swiglu(g, u, dtype):
    return (jax.nn.silu(g.astype(jnp.float32))
            * u.astype(jnp.float32)).astype(dtype)


def expert_ffn(m, sel, w, gate, up, down, *, first_expert: int,
               experts_known: int, live=None):
    """The held experts' part of the layer: ``m [N, d]``, ``sel``/``w``
    from :func:`route` over ``experts_known`` experts, ``gate``/``up``
    ``[E, d, f]`` and ``down`` ``[E, f, d]`` the held experts
    ``first_expert .. first_expert + E - 1``, ``live [N]`` false for
    tokens that are padding.  Returns ``(y [N, d], stats int32[4])`` —
    the pairs computed here, the live pairs left to absent experts, the
    held experts that got at least one pair, and the sorted rows the walk
    handed to the grouped products (:data:`STATS`)."""
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
        rows = block_rows(N * k, E, experts_known)
        # One block where every pair fits it, else as many as the held
        # pairs fill.
        one_block = N * k <= rows
        blocks = 1 if one_block else (held + rows - 1) // rows
        stats = jnp.stack([held, jnp.sum(alive & ~here, dtype=jnp.int32),
                           jnp.sum(sizes > 0, dtype=jnp.int32),
                           blocks * rows])
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
        if one_block:
            y = block(0, y)
        else:
            y = jax.lax.fori_loop(0, blocks, block, y)
        return y.astype(m.dtype), stats


def gated_ffn(m, gate, up, down):
    """``(silu(m @ G) * (m @ U)) @ D`` for one dense set of weights (the
    leading dense layers and the shared expert); every product comes out
    in the operands' type, as the grouped products do."""
    return jnp.dot(_swiglu(jnp.dot(m, gate), jnp.dot(m, up), m.dtype), down)
