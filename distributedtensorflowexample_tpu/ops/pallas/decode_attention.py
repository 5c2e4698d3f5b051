"""Ragged decode attention (the token step's ``attn`` scope in
``models/afmoe.py``): one query token per slot against that slot's cache
rows, fetching only the rows the query can see.

Layout.  The cache arrives as the engine holds it, ``[S, R, Hkv, Dh]``,
and is only VIEWED as ``[S, R * Hkv, Dh]``: with ``Hkv`` a multiple of 8
and ``Dh`` of 128 a cache row is whole ``(8, 128)`` tiles in HBM, so the
view is the same bytes and XLA makes no copy of a 2 GB array.  A row of
the view is one (position, K/V head) pair.  A model with fewer K/V
heads than a tile has sublanes (2 heads of 256) keeps its rows in that
view in the first place, ``[S, R * Hkv, Dh]``, and hands them over as
they are: nothing else of the kernel depends on ``Hkv``.

One matmul for every head.  A block of ``block`` positions is ``[block *
Hkv, Dh]``; the slot's ``Hq = Hkv * G`` query heads multiply all of it,
``s = q k^T`` of shape ``[Hq, block * Hkv]`` with the positions on the
lanes, and the columns of another K/V head are masked with the dead
rows.  The MXU pays for the keys it is handed, not for the query rows
(48 of them ride one pass), so the eightfold wider product costs what
eight per-head products would, and no head is ever sliced out of a tile.
``p v`` over the same columns then lands each query head's output in its
own row: masked columns weigh nothing.

Ragged.  ``lengths[s]`` (scalar-prefetched) is the count of LEADING rows
slot ``s`` sees.  The grid is ``(S, R / block)``; a step past the slot's
``ceil(length / block)`` blocks maps to the block before it (no fetch)
and computes nothing.  The latent sibling has no such steps: see there.

Precision is the einsum chain's: operands in the cache's type, scores
and the running max / sum in float32, probabilities cast to the cache's
type for the weighted sum, float32 accumulator.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distributedtensorflowexample_tpu.ops.pallas.tiling import (
    LANES, SUBLANES, resolve_interpret)

#: Cache rows per block, largest first: the first that divides the
#: layer's rows is taken.  Swept on the v5e at 32 slots x 8 K/V heads x
#: 128, lengths as the benchmark's mixed cell holds them (PERF.md §6,
#: PR 29): a full layer of 16,384 rows, 30% live, 1.06 ms at 512, 1.12 at
#: 256, 1.11 at 1,024 (the einsum chain 3.48); a ring of 4,096 rows, 81%
#: live, 0.63 at 512 and 256, 0.65 at 1,024 (0.74).  A smaller block pays
#: more grid steps, a larger one fetches more rows past a slot's last.
BLOCKS = (512, 256, 128)
_NEG = -1e30          # masked score / initial max: finite, so no inf - inf


def pick_block(rows: int, block=None, blocks=None) -> int | None:
    """Rows per block for a layer of ``rows`` rows, or None where no
    block (of ``blocks``, else of ``BLOCKS``) divides it."""
    for cand in ((block,) if block else blocks or BLOCKS):
        if rows % cand == 0:
            return cand
    return None


def fetch_block(rows: int, n_kv_heads: int, head_dim: int,
                flat: bool = False) -> int:
    """Rows per block where the kernel takes these shapes without a copy
    of the cache — a cache row is whole (8, 128) tiles, or the cache is
    kept ``flat`` as ``[S, rows * Hkv, Dh]``, and a block divides the
    rows — else 0."""
    if (not flat and n_kv_heads % SUBLANES) or head_dim % LANES:
        return 0
    return pick_block(rows) or 0


def _kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            block: int, n_kv_heads: int, group: int, scale: float):
    slot, j = pl.program_id(0), pl.program_id(1)
    length = len_ref[slot]
    n_blocks = (length + block - 1) // block
    hq, cols = q_ref.shape[0], k_ref.shape[0]

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full(m_ref.shape, _NEG, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    @pl.when(j < n_blocks)
    def _():
        s = jax.lax.dot_general(
            q_ref[...], k_ref[...], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale         # [Hq, cols]
        # A column is a (row, K/V head) pair; a query head reads the
        # columns of its own K/V head among the slot's live rows.
        col = jax.lax.broadcasted_iota(jnp.int32, (1, cols), 1)
        head = jax.lax.broadcasted_iota(jnp.int32, (hq, 1), 0) // group
        own = (col % n_kv_heads) == head                        # [Hq, cols]
        live = col // n_kv_heads < length - j * block           # [1, cols]
        s = jnp.where(own & live, s, _NEG)
        m = m_ref[...]
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        l = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
        acc = alpha * acc_ref[...] + jnp.dot(
            p.astype(v_ref.dtype), v_ref[...],
            preferred_element_type=jnp.float32)
        m_ref[...], l_ref[...], acc_ref[...] = m_new, l, acc

        @pl.when(j == n_blocks - 1)
        def _():
            o_ref[...] = (acc / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "block", "interpret"))
def ragged_decode_attention(q, ck, cv, lengths, *, scale: float | None = None,
                            block: int | None = None,
                            interpret: bool | None = None):
    """``softmax(scale q k^T) v`` (``scale`` None: ``1 / sqrt(Dh)``) per
    slot over the slot's first ``lengths[s]`` cache rows: ``q [S, Hkv, G,
    Dh]``, ``ck``/``cv`` ``[S, R, Hkv, Dh]`` (or flat, ``[S, R * Hkv,
    Dh]``), ``lengths [S]`` int32 in ``1..R``.  Returns ``[S, Hkv, G, Dh]``
    in the cache's type.
    Jitted here, so the layers of one program that share a shape share
    one trace."""
    S, Hkv, G, Dh = q.shape
    R = ck.shape[1] // Hkv if ck.ndim == 3 else ck.shape[1]
    block = pick_block(R, block)
    if block is None:
        raise ValueError(f"no block of {BLOCKS} divides {R} cache rows")
    Hq = Hkv * G

    def kv_map(s, j, lens):
        # Past the slot's last live block: the same block again, which
        # the pipeline does not fetch twice.
        return s, jnp.minimum(j, (lens[s] + block - 1) // block - 1), 0

    kv_spec = pl.BlockSpec((None, block * Hkv, Dh), kv_map)
    q_spec = pl.BlockSpec((None, Hq, Dh), lambda s, j, lens: (s, 0, 0))
    out = pl.pallas_call(
        functools.partial(_kernel, block=block, n_kv_heads=Hkv, group=G,
                          scale=scale or Dh ** -0.5),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(S, R // block),
            in_specs=[q_spec, kv_spec, kv_spec], out_specs=q_spec,
            scratch_shapes=[pltpu.VMEM((Hq, 1), jnp.float32),
                            pltpu.VMEM((Hq, 1), jnp.float32),
                            pltpu.VMEM((Hq, Dh), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((S, Hq, Dh), cv.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=resolve_interpret(interpret),
        name="ragged_decode_attention",
    )(jnp.clip(lengths.astype(jnp.int32), 1, R), q.reshape(S, Hq, Dh),
      ck.reshape(S, R * Hkv, Dh), cv.reshape(S, R * Hkv, Dh))
    return out.reshape(S, Hkv, G, Dh)


# --- the latent sibling: ONE shared row a position, key and value ----------
#
# The walk.  The latent kernel walks a slot's live blocks and nothing
# else: its grid is ``(S,)``, one step a slot, the rows stay in HBM, and
# inside the step a ``fori_loop`` runs over the slot's ``ceil(length /
# block)`` blocks, each fetched by the kernel's own copies into one of two
# VMEM buffers — block ``j + 1``, or the NEXT slot's first block after a
# slot's last, is on its way while block ``j`` is computed (the structure
# of ``jax.experimental.pallas.ops.tpu.paged_attention``).  A block goes
# in granules of ``GRANULE`` rows, and of a slot's last block only those
# that hold a live row.  A block a slot does not hold costs no grid step,
# no fetch and no loop iteration.

#: Rows per block of the latent kernel's walk, largest first, and the
#: rows ONE copy moves: a block is fetched granule by granule, and of a
#: slot's last block only the granules that hold a live row.  Swept on
#: the v5e (PERF.md §6, PR 45; ms a call) at Kimi's shapes — 80 slots x
#: 64 heads x 10,240 rows, 5.8 k live a slot — and at Ling's — 256 x 32 x
#: 8,192, 1.5 k live: the grid this walk replaced, one step a block dead
#: or live, 1.208 and 1.570; whole blocks of 512 1.033 / 0.954, of 1,024
#: 0.895 / 0.934, of 2,048 0.949 / 1.211, of 256 1.424 / 1.236; blocks of
#: 1,024 in granules of 128 0.885 / 0.869, of 256 0.887 / 0.875; blocks
#: of 512 in granules of 128 1.026 / 0.926.  The copies alone take 0.849
#: / 0.807 at 512 and the products and softmax alone 0.761 / 0.663 (0.603
#: / 0.562 at 1,024: a block's fixed work is paid half as often), and at
#: 512 the two do not hide each other; at 1,024 the copies are what is
#: left, and a granule cuts what they move past a slot's last row.
LATENT_BLOCKS = (1024, 512, 256, 128)
GRANULE = 128


def latent_fetch_block(rows: int, width: int, v_dim: int) -> int:
    """Rows :func:`latent_decode_attention` fetches at a time — a slot's
    live rows rounded up to this many are what it reads — from a cache of
    ``rows`` rows of ``width`` features whose first ``v_dim`` are the
    values: the values whole lane groups and a block dividing the rows —
    else 0."""
    if v_dim % LANES or width < v_dim:
        return 0
    return GRANULE if pick_block(rows, blocks=LATENT_BLOCKS) else 0


def _latent_kernel(len_ref, q_ref, rows_ref, o_ref, buf_ref, sem_ref, at_ref,
                   m_ref, l_ref, acc_ref, *, block: int, scale: float):
    slot, n_slots = pl.program_id(0), pl.num_programs(0)
    length = len_ref[slot]
    n_blocks = (length + block - 1) // block
    v_dim = o_ref.shape[1]

    def fetch(of_slot, j, buf, then: str):
        """``then`` — ``"start"`` or ``"wait"`` — each granule of block
        ``j`` of a slot that holds a live row: the block's first always,
        the others while the slot's length reaches them."""
        live = len_ref[of_slot] - j * block
        for g in range(block // GRANULE):
            go = getattr(pltpu.make_async_copy(
                rows_ref.at[of_slot, pl.ds(j * block + g * GRANULE, GRANULE)],
                buf_ref.at[buf, pl.ds(g * GRANULE, GRANULE)],
                sem_ref.at[buf]), then)
            if g:
                pl.when(g * GRANULE < live)(go)
            else:
                go()

    @pl.when(slot == 0)
    def _():
        at_ref[0] = 0
        # A granule that is not fetched keeps what its buffer held: rows
        # of an earlier block, finite and masked — never what VMEM held
        # before the call.
        buf_ref[...] = jnp.zeros(buf_ref.shape, buf_ref.dtype)
        fetch(0, 0, 0, "start")

    first = at_ref[0]          # the buffer this slot's first block is in
    m_ref[...] = jnp.full(m_ref.shape, _NEG, jnp.float32)
    l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
    acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    def step(j, _):
        buf = (first + j) % 2
        # The block after this one — the slot's next, or the NEXT slot's
        # first — is on its way while this one is computed.
        more = j + 1 < n_blocks

        @pl.when(more | (slot + 1 < n_slots))
        def _():
            fetch(jnp.where(more, slot, slot + 1), jnp.where(more, j + 1, 0),
                  1 - buf, "start")

        fetch(slot, j, buf, "wait")
        c = buf_ref[buf]                                        # [block, D]
        s = jax.lax.dot_general(
            q_ref[...], c, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale         # [H, block]
        col = jax.lax.broadcasted_iota(jnp.int32, (1, block), 1)
        s = jnp.where(col < length - j * block, s, _NEG)
        m = m_ref[...]
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = alpha * acc_ref[...] + jnp.dot(
            p.astype(c.dtype), c[:, :v_dim],
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    jax.lax.fori_loop(0, n_blocks, step, None)
    at_ref[0] = (first + n_blocks) % 2
    o_ref[...] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("v_dim", "scale", "block",
                                             "interpret"))
def latent_decode_attention(q, rows, lengths, *, v_dim: int, scale: float,
                            block: int | None = None,
                            interpret: bool | None = None):
    """Single-query attention of ``H`` heads over ONE shared row a
    position (latent attention's absorbed form): ``q [S, H, D]``,
    ``rows [S, R, D]`` — a row is the key of every head, and its first
    ``v_dim`` features the value of every head —, ``lengths [S]`` int32
    in ``1..R`` the count of LEADING rows a slot's query sees.  Returns
    ``softmax(scale q rows^T) rows[..., :v_dim]``, ``[S, H, v_dim]`` in
    the rows' type.  A slot's ``ceil(length / GRANULE)`` granules are
    fetched, each ONCE for scores and values alike, and no others."""
    S, H, D = q.shape
    R = rows.shape[1]
    block = pick_block(R, block, LATENT_BLOCKS)
    if block is None or block % GRANULE:
        raise ValueError(
            f"no block of {LATENT_BLOCKS} divides {R} cache rows")
    out = pl.pallas_call(
        functools.partial(_latent_kernel, block=block, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(S,),
            in_specs=[pl.BlockSpec((None, H, D), lambda s, lens: (s, 0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((None, H, v_dim), lambda s, lens: (s, 0, 0)),
            scratch_shapes=[pltpu.VMEM((2, block, D), rows.dtype),
                            pltpu.SemaphoreType.DMA((2,)),
                            pltpu.SMEM((1,), jnp.int32),
                            pltpu.VMEM((H, 1), jnp.float32),
                            pltpu.VMEM((H, 1), jnp.float32),
                            pltpu.VMEM((H, v_dim), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((S, H, v_dim), rows.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=resolve_interpret(interpret),
        name="latent_decode_attention",
    )(jnp.clip(lengths.astype(jnp.int32), 1, R), q, rows)
    return out
