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
three grouped products (:func:`grouped_product`: each expert's rows times
that expert's matrix, bfloat16 operands, float32 accumulation, the result
in the operands' type), and adds its weighted rows onto their tokens.  No
pair is ever dropped: the walk is as long as the held pairs need, so any
imbalance — every token on one expert — fits, in more trips.

**Which kernel runs a grouped product** (:func:`product_tiling`) follows
from what the call observes — the backend, the block's rows, the expert
matrix's shape and type — as ``ops/attention.py`` chooses its paths, each
product for itself; ``moe_grouped_products_total{kernel}`` counts the
products traced by the kernel taken.  Where the program is built for a
TPU and the block is whole row tiles of bfloat16, the product is JAX's
``megablox.gmm`` in tiles this module states: :data:`ROW_TILE` rows by a
whole expert or by the widest whole-lane split of it that fits — the
fewest equal tiles of columns, each whole lane tiles, that the kernel's
VMEM holds twice over (double-buffered, beside a row tile of the left
operand and of the output), at most :data:`MOST_TILES` of them.  An
expert of ``[2560, 768]`` (3.9 MB) goes whole; one of ``[3072, 3072]``
(18.9 MB) in three tiles of 1,024 columns, ``[768, 4096]`` in two; one of
``[7168, 2048]`` (29.4 MB) would need eight and stays on ``ragged_dot``
(what the kernel costs set-up, not the device, draws that line: see
:data:`MOST_TILES`).  K is never split: a column's sum stays one float32
sum, so the tiles change no bit of the result.  The kernel's grid
runs the column tiles outermost and inside them visits only the row tiles
that hold a group's rows, so each touched expert is still read once and
128 rows are multiplied against it (the block's rows are read once a
column tile: a few hundred KB); its time is the touched experts' bytes at
85–90% of the HBM's pace whatever a token step's block: 0.32 ms for 56
experts of ``[2560, 768]`` (221 MB) in a block of 512 or of 640 rows, and
0.40 for all 64 in a full block of 2,048 (PERF.md section 6, PR 38; the
split tiles' readings: section 6, PR 44).  Everywhere else — a block that
is no whole row tiles, an expert that fits in no such split, the CPU — it
is
``jax.lax.ragged_dot``, which XLA:TPU lowers to a kernel of its own that
picks its row tile from the block's size: a block that is a multiple of
512 rows gets a tile of 512 and the same product takes 0.87–1.0 ms, one of
640 or 384 rows 0.54–0.57.  Both read only the experts a block has rows
for; rows past the held pairs belong to no group, ``gmm`` leaves them
unwritten, and the walk selects them away.

**How many rows a block has** (:func:`block_rows`) follows from the same
observation — a call's pairs, the experts held and the experts the router
knows: twice the pairs that even routing puts on held experts, in whole
tiles of :data:`ROW_TILE`, at most :data:`BLOCK_ROWS`.  A call whose
pairs all fit that block runs it once, with no loop: a token step of a
few dozen slots (32 slots x 4 picks over 32 of 256 experts: 128 rows).
Every other call loops: a token step of many slots (256 x 8 picks over 64
of 512 experts: 2,048 pairs, ~256 of them held, blocks of 512 rows, one
trip unless the routing is skewed), and prefill (thousands of held pairs,
a few blocks of ``BLOCK_ROWS``; a short bucket gets the block its own
pairs call for).  The headroom of twice is what keeps a token step to one
trip, and with the row tile the code's own it costs next to nothing: the
kernel skips the tiles no pair reaches.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from distributedtensorflowexample_tpu.obs import metrics as obs_metrics

_PRODUCTS = obs_metrics.counter(
    "moe_grouped_products_total",
    "grouped products traced (three an expert layer of a program), by "
    "the kernel taken: gmm (megablox.gmm in the tiles ops/moe.py chose: a "
    "row tile by a whole expert or by the widest whole-lane split of it "
    "that fits) | ragged_dot (jax.lax.ragged_dot)")

#: The most sorted (token, expert) rows that go through the grouped
#: products at a time: the cap of :func:`block_rows`, reached by the
#: longer prefill buckets (from 1,024 positions at top-10 over 64 of 512
#: experts, from 2,048 at top-4 over 32 of 256).  What bounds it is the
#: walk's own arrays — a block's gathered rows, its two hidden arrays and
#: its output, four ``[rows, width]`` arrays: 21 to 50 MB at the widths
#: served — and not the products' tile: a block spans the few experts its
#: rows belong to, so over a walk each touched expert's weights are read
#: about once, by either kernel.
BLOCK_ROWS = 2048

#: A block is a whole number of these rows, and they are the row tile of
#: the grouped products where this module chooses it (``megablox.gmm``'s
#: ``tm``): one MXU pass of rows a visit, so an expert with five rows
#: costs 128 rows of products and not 512.  On the chip, tiles of 64 and
#: of 256 rows read 3% slower at a token step's blocks and 256 rows 7%
#: slower on a full block (PERF.md section 6, PR 38).
ROW_TILE = 128

#: Columns of one lane tile: a split of an expert's columns is a whole
#: number of these (``megablox.gmm``'s ``tn``).  ``ops/pallas/tiling``'s
#: constant, stated again: importing that package imports Pallas, which
#: this module leaves to the call that takes the kernel.
LANES = 128

#: The most tiles an expert's columns are split into; an expert that
#: needs more stays on ``ragged_dot``.  Not the device's limit — on the
#: chip the kernel wins at seven and eight tiles too (Kimi-K2.5's
#: ``[2048, 7168]`` and ``[7168, 2048]``: 0.36 and 0.38 ms a token step's
#: product against 0.49) — but set-up's: every (product shape, block size)
#: the kernel is traced for and every program that carries it cost the
#: chip's host 0.2–0.4 s, and such experts bring two shapes a layer
#: (gate/up and down are no longer the same ``[k, n]``).  It stands
#: between the widest split whose cell afforded that (three tiles: warm
#: ``setup_s`` +9%, bound 10%) and the narrowest whose cell did not
#: (seven and eight: +13 to +15%); PERF.md section 6, PR 44.
MOST_TILES = 4

#: VMEM the tiles of a grouped product may fill: a TPU v5e's scoped
#: default, under which Mosaic compiles a kernel that states no limit of
#: its own, as ``megablox.gmm`` does.  The one chip this repo is built
#: and measured on; another generation's default belongs here beside it.
V5E_SCOPED_VMEM = 16 * 2 ** 20

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


def product_tiling(rows: int, k: int, n: int, dtype) -> tuple | None:
    """The tiles ``(rows, k, n)`` in which ``megablox.gmm`` runs a
    grouped product of a ``[rows, k]`` block with experts of ``[k, n]``,
    or None where the product is ``jax.lax.ragged_dot``'s.  One rule:
    built for a TPU, a bfloat16 block of whole row tiles, and
    :data:`ROW_TILE` rows by a whole expert — or by the widest split of
    it into at most :data:`MOST_TILES` equal whole lane tiles of columns
    — fit :data:`V5E_SCOPED_VMEM` as the kernel holds them: the matrix's
    tile and the row tiles of the left operand and of the output
    double-buffered, beside the float32 accumulator and product.  K is
    never split: that costs the bit-equality with ``ragged_dot`` and
    time (PERF.md section 6, PR 38)."""
    if (jax.default_backend() != "tpu" or rows % ROW_TILE
            or jnp.dtype(dtype) != jnp.bfloat16):
        return None
    for split in range(1, MOST_TILES + 1):
        tn = n // split
        if n % split or tn % LANES:
            continue
        tiles = (2 * 2 * (k * tn + ROW_TILE * (k + tn))
                 + 2 * 4 * ROW_TILE * tn)
        if tiles <= V5E_SCOPED_VMEM:
            return (ROW_TILE, k, tn)
    return None


def grouped_product(x, w, sizes):
    """``x [rows, k]`` sorted by group, ``w [E, k, n]``, ``sizes [E]``
    int32 rows a group -> ``[rows, n]`` in ``x``'s type: group ``e``'s
    rows times ``w[e]``, accumulated in float32, by the kernel
    :func:`product_tiling` names for these shapes (the Pallas interpreter
    on the CPU, as this repo's own kernels).  Rows past the groups hold
    nothing that may be read: the tiled kernel never writes them."""
    tiling = product_tiling(x.shape[0], *w.shape[1:], x.dtype)
    _PRODUCTS.labels(kernel="ragged_dot" if tiling is None else "gmm").inc()
    if tiling is None:
        return jax.lax.ragged_dot(x, w, sizes)
    # Imported where the kernel is taken, not with the model:
    # jax.experimental.pallas costs every CPU run a second or two.
    from jax.experimental.pallas.ops.tpu.megablox import gmm
    from distributedtensorflowexample_tpu.ops.pallas.tiling import (
        resolve_interpret)
    return gmm(x, w, sizes, preferred_element_type=x.dtype, tiling=tiling,
               interpret=resolve_interpret(None))


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
            g = grouped_product(x, gate, part)
            u = grouped_product(x, up, part)
            o = grouped_product(_swiglu(g, u, m.dtype), down, part)
            # Rows past the held pairs belong to no group and hold
            # nothing that may be read (the tiled kernel leaves them
            # unwritten): selected away, not multiplied by zero.
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
