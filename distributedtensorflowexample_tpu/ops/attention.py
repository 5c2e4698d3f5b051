"""Causal self-attention of the LM's training block: one function, two
regimes chosen from what the call can observe.

* **The blocked kernels** (``ops/pallas/attention.py``) when the program
  is being built for a TPU and the shapes tile: no ``[B, H, T, T]`` array
  reaches HBM.
* **The einsum chain** everywhere else — the code ``DecoderBlock``
  carried inline until PR 25, moved here verbatim, so every CPU run (all
  of tier-1) and every shape that does not tile keeps its bits.

There is no flag, environment variable or config field: the backend and
the shapes decide, and ``lm_attention_blocks_total{impl=...}`` says which
was taken, once per call traced (one call per ``DecoderBlock``).

**Under a mesh.**  GSPMD cannot split a Mosaic call.  A step builder
that applies the model in global view over a mesh (``parallel/sync.py``'s
plain step and the eval steps) says so the way jax provides for,
``jax.sharding.use_abstract_mesh``; the kernel then runs per shard under
``jax.shard_map`` over the batch axis, as ``pallas_ce`` does.  Inside a
``shard_map`` body (the bucketed, ZeRO-3 and async steps) the axis is
already manual and the kernel is called as it is.

**The serving block's two shapes of work** (``models/afmoe.py``) decide
the same way.  :func:`grouped_attention` is a whole sequence (prefill):
JAX's splash kernel past one tile on a TPU, a tiled walk elsewhere.
:func:`decode_attention` is the token step, one query per slot against
that slot's cache rows: on a TPU, where the cache's rows are whole tiles,
the ragged kernel (``ops/pallas/decode_attention.py``), which fetches
each slot's visible rows block by block and nothing past them; the
einsum chain over every row the layer holds, dead rows masked
afterwards, everywhere else (the CPU, shapes that do not tile), so every
tier-1 run keeps its bits.  ``serve_decode_attention_total{impl=...}``
says which was taken, once per call traced (one call per layer), and
:func:`decode_fetch_block` tells the engine's
``serve_cache_rows_fetched_total`` how many rows that is.

**Latent attention** (a layer whose cache is ONE compressed row a
position, shared by every head) has the same two shapes.  A sequence is
attended in the EXPANDED form — the model expands each row to per-head
keys and values, the keys wider than the values, and
:func:`grouped_attention` takes them —; the token step in the ABSORBED
form, :func:`latent_decode_attention`: the query is taken into the
row's space, the row is the key of every head and its leading features
the value of every head, so a slot's rows are read once and never
expanded — on a TPU by the ragged kernel's sibling
(``ops/pallas/decode_attention.latent_decode_attention``), elsewhere
by the einsum chain.  ``lm_latent_attention_total{impl=...}`` counts
the calls traced, by form.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from distributedtensorflowexample_tpu.obs import metrics as obs_metrics
from distributedtensorflowexample_tpu.parallel.mesh import DATA_AXIS

_BLOCKS = obs_metrics.counter(
    "lm_attention_blocks_total",
    "causal_attention calls traced (one per DecoderBlock), by the "
    "implementation taken: pallas | einsum")
_DECODE = obs_metrics.counter(
    "serve_decode_attention_total",
    "decode_attention calls traced (one per layer of a token-step "
    "program), by the implementation taken: ragged | latent | einsum")
_LATENT = obs_metrics.counter(
    "lm_latent_attention_total",
    "latent-attention calls traced (one per latent layer of a program), by "
    "the form taken: expanded (a sequence: per-head keys and values made "
    "from the rows) | absorbed (one token a slot against the rows "
    "themselves)")


def einsum_causal_attention(q, k, v):
    """The reference chain: bf16 scores, f32 softmax over the whole
    ``[B, H, T, T]``, bf16 weighted sum.  ``[B, T, H, Dh]`` in and out."""
    T, Dh = q.shape[1], q.shape[3]
    scores = jnp.einsum("bthd,bshd->bhts", q, k) / jnp.asarray(
        Dh ** 0.5, q.dtype)
    # Causal mask: position t attends to s <= t.  Built from iota
    # at trace time — no resident [T, T] constant in HBM.
    causal = (jnp.arange(T)[:, None] >= jnp.arange(T)[None, :])
    scores = jnp.where(causal[None, None], scores,
                       jnp.asarray(-1e9, scores.dtype))
    # Softmax in f32: bf16 exp/normalize is where logit noise
    # turns into loss noise; the [B,H,T,T] f32 probs are exactly
    # the activation bytes remat="block" exists to not keep
    # resident.
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1)
    probs = probs.astype(q.dtype)
    return jnp.einsum("bhts,bshd->bthd", probs, v)


def _auto_batch_axis(batch: int):
    """``DATA_AXIS`` where the mesh in context has it un-split (Auto) and
    wider than one device, and it divides the batch; else None."""
    mesh = jax.sharding.get_abstract_mesh()
    if (mesh.empty or DATA_AXIS not in mesh.axis_names
            or DATA_AXIS in mesh.manual_axes):
        return None
    size = mesh.shape[DATA_AXIS]
    return DATA_AXIS if size > 1 and batch % size == 0 else None


#: Shortest sequence the kernels are taken for.  On the v5e (B = 16,
#: H = 12, Dh = 64, forward + backward, PR 25's sweep) they take 2.08 ms
#: against the einsum chain's 6.68 at T = 1024 and tie with it at 512
#: (0.83 / 0.89) and below (256: 0.55 / 0.57; 128: 0.55 / 0.55), where
#: the scores are a few MB; from 512 up they also stop holding them.
MIN_SEQ_LEN = 512


def _blocked():
    # Imported where a kernel can be taken, not with the model:
    # jax.experimental.pallas costs every CPU run and every serving
    # process a second or two to import.
    from distributedtensorflowexample_tpu.ops.pallas import attention
    return attention


def takes_kernel(q_shape: tuple) -> bool:
    """The predicate: built for a TPU, long enough to win there, and
    the shapes tile."""
    _, t, h, dh = q_shape
    return (jax.default_backend() == "tpu" and t >= MIN_SEQ_LEN
            and _blocked().tiles(t, dh, h))


def causal_attention(q, k, v):
    """softmax(q k^T / sqrt(Dh), causal) v for ``[B, T, H, Dh]`` q, k, v
    of one dtype; returns ``[B, T, H, Dh]``."""
    if not takes_kernel(q.shape):
        _BLOCKS.labels(impl="einsum").inc()
        return einsum_causal_attention(q, k, v)
    _BLOCKS.labels(impl="pallas").inc()
    kernel = _blocked().blocked_causal_attention
    axis = _auto_batch_axis(q.shape[0])
    if axis is not None:
        spec = jax.sharding.PartitionSpec(axis)
        kernel = jax.shard_map(kernel, in_specs=(spec, spec, spec),
                               out_specs=spec, axis_names={axis},
                               check_vma=False)
    return kernel(q, k, v)


# --- grouped-query attention with an optional window ----------------------

#: Query rows (and key rows) per tile of :func:`grouped_attention` once a
#: sequence is longer than one tile.  A tile's scores are ``[B, H, 1024,
#: 1024]`` float32: 201 MB for 48 heads, where the whole ``[H, T, T]`` of a
#: 16,384-token prompt would be 51.5 GB.
ATTN_BLOCK = 1024
_MASKED = -1e30         # finite: a wholly masked tile gives no inf - inf


def _visible(q_pos, k_pos, window):
    """``[Tq, Tk]``: key position <= query position, and within the last
    ``window`` positions where the layer has a window."""
    ok = k_pos[None, :] <= q_pos[:, None]
    if window:
        ok &= q_pos[:, None] - k_pos[None, :] < window
    return ok


def _scores(q, k, ok, scale=None):
    """One tile of scores: ``q [B, Tq, Hkv, G, Dh]``, ``k [B, Tk, Hkv,
    Dh]``, ``ok [Tq, Tk]`` -> float32 ``[B, Hkv, G, Tq, Tk]``, masked;
    times ``scale`` (None: ``Dh ** -0.5``)."""
    s = jnp.einsum("bthgd,bshd->bhgts", q, k,
                   preferred_element_type=jnp.float32)
    return jnp.where(ok, s * (scale or q.shape[-1] ** -0.5), _MASKED)


def takes_splash(q_shape: tuple, block: int, v_dim: int | None = None) -> bool:
    """Built for a TPU, longer than one tile, and shapes the kernel
    tiles: whole blocks of positions, value heads of whole lane groups
    (``v_dim``; the query's width where not given).  Query and key heads
    that are no whole lane groups are padded to them with zeros."""
    _, t, _, dh = q_shape
    return (jax.default_backend() == "tpu" and t > block
            and t % block == 0 and (v_dim or dh) % 128 == 0)


def tile_ladder(cache_len: int, tile: int = ATTN_BLOCK):
    """A ladder of prefill lengths for a model whose prompts cost their
    matmuls and little else below one attention tile: powers of two from
    256 up to a tile, then whole tiles (:func:`takes_splash` asks for
    that) — one, two, three, then doubling —, ``cache_len`` last.
    ``None`` for a cache no longer than the first bucket."""
    if cache_len <= 256:
        return None
    small = [b for b in (256, 512, 1024) if b < min(tile, cache_len)]
    tiles = [t * tile for t in (1, 2, 3) if t * tile < cache_len]
    t = 4
    while t * tile < cache_len:
        tiles.append(t * tile)
        t *= 2
    return tuple(small + tiles) + (cache_len,)


def splash_grouped_attention(q, k, v, *, window: int = 0,
                             block: int = ATTN_BLOCK,
                             interpret: bool = False,
                             scale: float | None = None):
    """:func:`grouped_attention` by JAX's own TPU kernel (splash
    attention: an online softmax over key blocks held in VMEM, blocks
    the causal or window mask empties never visited), one call per
    key/value head over its group of query heads.  The kernel applies
    no scale: q is scaled first, in its own type.  Query/key heads of
    192 features (latent attention's expanded form) go as 256, the last
    64 zeros: the scores are the same."""
    # Imported where the kernel is taken (jax.experimental.pallas costs
    # every CPU run a second or two to import).
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as kernel, splash_attention_mask as masks)
    B, T, Hq, Dh = q.shape
    Hkv, Dv = k.shape[2], v.shape[3]
    G = Hq // Hkv
    q = (q * (scale or Dh ** -0.5)).astype(q.dtype)
    if Dh % 128:
        q, k = (jnp.pad(a, ((0, 0),) * 3 + ((0, -Dh % 128),))
                for a in (q, k))
        Dh = q.shape[3]
    mask = (masks.LocalMask((T, T), (window - 1, 0), 0) if window
            else masks.CausalMask((T, T)))
    attend = kernel.make_splash_mqa_single_device(
        masks.MultiHeadMask([mask] * G), interpret=interpret,
        block_sizes=kernel.BlockSizes(block_q=block, block_kv=block,
                                      block_kv_compute=block))
    q = q.reshape(B, T, Hkv, G, Dh)
    out = jax.vmap(jax.vmap(attend))(           # over B, then over Hkv
        jnp.transpose(q, (0, 2, 3, 1, 4)),      # [B, Hkv, G, T, Dh]
        jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2))
    return jnp.transpose(out, (0, 3, 1, 2, 4)).reshape(B, T, Hq, Dv)


def grouped_attention(q, k, v, *, window: int = 0, block: int = ATTN_BLOCK,
                      scale: float | None = None):
    """Causal softmax attention with fewer key/value heads than query
    heads: ``q [B, T, Hq, Dh]``, ``k`` ``[B, T, Hkv, Dh]``, ``v`` ``[B,
    T, Hkv, Dv]`` (``Dv`` is ``Dh`` in most models; latent attention's
    expanded keys are wider than its values), query head i reading
    key/value head ``i // (Hq // Hkv)``; with ``window`` a query sees
    only the last ``window`` positions, itself included; the scores are
    times ``scale`` (None: ``Dh ** -0.5``; a model states its own where
    its attention has a multiplier).  Returns ``[B, T, Hq, Dv]``.

    One path that adapts to what the call can observe, as
    :func:`causal_attention` does: a sequence of at most ``block``
    positions is one tile of the einsum chain; a longer one goes to the
    TPU's kernel where the program is built for a TPU and the shapes
    tile (:func:`takes_splash`), and is otherwise walked in query tiles,
    each against the key tiles it can see (all up to the diagonal, or
    the few a window reaches), with a running max and sum.  Nothing
    larger than a tile's scores exists in either; in the walk each
    tile's scores still pass through HBM, in the kernel they stay in
    VMEM.  Scores and the softmax are float32; the weighted sum takes
    the probabilities in the operands' type.  Both are forward only (the
    serving prefill and a forward at a training shape)."""
    B, T, Hq, Dh = q.shape
    Hkv, Dv = k.shape[2], v.shape[3]
    if takes_splash(q.shape, block, Dv):
        return splash_grouped_attention(q, k, v, window=window, block=block,
                                        scale=scale)
    q = q.reshape(B, T, Hkv, Hq // Hkv, Dh)
    if T <= block:
        pos = jnp.arange(T)
        s = _scores(q, k, _visible(pos, pos, window), scale)
        p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
        return jnp.einsum("bhgts,bshd->bthgd", p, v).reshape(B, T, Hq, Dv)

    pad = -T % block
    if pad:     # padded keys lie after every real query: never visible
        q, k, v = (jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
                   for a in (q, k, v))
    G = Hq // Hkv

    def rows(a, i):
        return jax.lax.dynamic_slice_in_dim(a, i * block, block, axis=1)

    def query_tile(i):
        qi = rows(q, i)
        q_pos = i * block + jnp.arange(block)

        def key_tile(j, carry):
            m, l, acc = carry
            ok = _visible(q_pos, j * block + jnp.arange(block), window)
            s = _scores(qi, rows(k, j), ok, scale)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            alpha = jnp.exp(m - m_new)
            p = jnp.where(ok, jnp.exp(s - m_new[..., None]), 0.0)
            acc = alpha[..., None] * acc + jnp.einsum(
                "bhgts,bshd->bhgtd", p.astype(v.dtype), rows(v, j),
                preferred_element_type=jnp.float32)
            return m_new, alpha * l + jnp.sum(p, axis=-1), acc

        first = (jnp.maximum(0, i * block - (window - 1)) // block
                 if window else 0)
        init = (jnp.full((B, Hkv, G, block), _MASKED, jnp.float32),
                jnp.zeros((B, Hkv, G, block), jnp.float32),
                jnp.zeros((B, Hkv, G, block, Dv), jnp.float32))
        _, l, acc = jax.lax.fori_loop(first, i + 1, key_tile, init)
        return (acc / l[..., None]).astype(v.dtype)     # [B,Hkv,G,block,Dv]

    out = jax.lax.map(query_tile, jnp.arange((T + pad) // block))
    out = jnp.moveaxis(out, 0, 3)               # [B, Hkv, G, nq, block, Dv]
    out = out.reshape(B, Hq, T + pad, Dv)[:, :, :T]
    return jnp.swapaxes(out, 1, 2)


# --- the token step: one query per slot against its cache rows ------------

def _ragged():
    # Imported where the kernel can be taken (see _blocked).
    from distributedtensorflowexample_tpu.ops.pallas import decode_attention
    return decode_attention


def decode_fetch_block(rows: int, n_kv_heads: int, head_dim: int,
                       flat: bool = False) -> int:
    """Cache rows :func:`decode_attention` fetches at a time from a
    layer of ``rows`` rows a slot — a slot's visible rows rounded up to
    it are what a step reads — or 0 where it takes the einsum chain,
    which reads every row the layer holds.  The predicate: built for a
    TPU, and the cache's rows tile (``flat``: the model keeps them as
    ``[S, rows * Hkv, Dh]``, which tiles whatever ``Hkv`` is)."""
    if jax.default_backend() != "tpu":
        return 0
    return _ragged().fetch_block(rows, n_kv_heads, head_dim, flat)


def einsum_decode_attention(q, ck, cv, lengths, scale=None):
    """The reference chain, the code ``AfmoeBlock.step`` carried inline
    until PR 29: float32 scores (times ``scale``; None: ``Dh ** -0.5``)
    over every row the layer holds, the rows from ``lengths`` on masked,
    float32 softmax, the weighted sum in the cache's type."""
    ok = jnp.arange(ck.shape[1]) < lengths[..., None]           # [S,K,R]
    s = jnp.einsum("skhgd,srhd->shgkr", q, ck,
                   preferred_element_type=jnp.float32)
    s = jnp.where(ok[:, None, None], s * (scale or q.shape[-1] ** -0.5),
                  _MASKED)
    p = jax.nn.softmax(s, axis=-1).astype(cv.dtype)
    return jnp.einsum("shgkr,srhd->skhgd", p, cv)


def decode_attention(q, ck, cv, lengths, scale=None):
    """The token step's attention: ``q [S, K, Hkv, G, Dh]`` (a K-token
    window a slot, plain decode is K == 1; ``G`` query heads a key/value
    head), ``ck``/``cv`` ``[S, R, Hkv, Dh]`` as the engine holds them —
    or flat, ``[S, R * Hkv, Dh]``, as a model keeps them whose K/V heads
    are fewer than a tile's sublanes —, ``lengths [S, K]`` the count of
    LEADING rows each query sees (``1..R``), ``scale`` what the scores
    are multiplied by (None: ``Dh ** -0.5``).  Returns ``[S, K, Hkv, G,
    Dh]``.

    One function, two regimes chosen from what the call can observe: for
    one token a slot, where :func:`decode_fetch_block` finds a block,
    the ragged kernel fetches ``ceil(length / block)`` blocks of a
    slot's rows and no others, with a running max and sum; the einsum
    chain reads all ``R`` and masks.  Scores and softmax are float32 in
    both, the probabilities are cast to the cache's type before the
    second product."""
    S, K, Hkv, _, Dh = q.shape
    flat = ck.ndim == 3
    R = ck.shape[1] // Hkv if flat else ck.shape[1]
    if K > 1 or not decode_fetch_block(R, Hkv, Dh, flat):
        _DECODE.labels(impl="einsum").inc()
        if flat:
            ck, cv = (c.reshape(S, R, Hkv, Dh) for c in (ck, cv))
        return einsum_decode_attention(q, ck, cv, lengths, scale)
    _DECODE.labels(impl="ragged").inc()
    return _ragged().ragged_decode_attention(
        q[:, 0], ck, cv, lengths[:, 0], scale=scale)[:, None]


# --- latent attention: one shared row a position ----------------------------

def latent_fetch_block(rows: int, width: int, v_dim: int) -> int:
    """Cache rows :func:`latent_decode_attention` fetches at a time from
    a latent layer of ``rows`` rows a slot, or 0 where it takes the
    einsum chain, which reads every row (:func:`decode_fetch_block`'s
    contract)."""
    if jax.default_backend() != "tpu":
        return 0
    return _ragged().latent_fetch_block(rows, width, v_dim)


def latent_decode_attention(q, rows, lengths, *, v_dim: int, scale: float):
    """The token step of a latent-attention layer, absorbed form: ``q [S,
    H, D]`` (each head's query taken into the rows' space), ``rows [S, R,
    D]`` as the engine holds them — ONE row a position: the key of every
    head, and its first ``v_dim`` features the value of every head —,
    ``lengths [S]`` the count of LEADING rows each slot's query sees.
    Returns ``softmax(scale q rows^T) rows[..., :v_dim]``, ``[S, H,
    v_dim]``.  Scores and softmax are float32, the probabilities are
    cast to the rows' type for the second product, as in
    :func:`decode_attention`; a slot's live rows only are read where
    :func:`latent_fetch_block` finds a block."""
    _LATENT.labels(impl="absorbed").inc()
    R = rows.shape[1]
    if latent_fetch_block(R, rows.shape[2], v_dim):
        _DECODE.labels(impl="latent").inc()
        return _ragged().latent_decode_attention(q, rows, lengths,
                                                 v_dim=v_dim, scale=scale)
    _DECODE.labels(impl="einsum").inc()
    s = jnp.einsum("shd,srd->shr", q, rows,
                   preferred_element_type=jnp.float32)
    ok = jnp.arange(R)[None] < lengths[:, None]                 # [S, R]
    p = jax.nn.softmax(jnp.where(ok[:, None], s * scale, _MASKED), axis=-1)
    return jnp.einsum("shr,srd->shd", p.astype(rows.dtype),
                      rows[..., :v_dim])


def latent_expanded_attention(q, k, v, *, block: int = ATTN_BLOCK,
                              scale: float | None = None):
    """A whole sequence of a latent-attention layer, expanded form: the
    model has made per-head keys ``k [B, T, H, Dh]`` and values ``v [B,
    T, H, Dv]`` from the rows; :func:`grouped_attention` with the call
    counted (``scale`` None: ``Dh ** -0.5``; a model whose positions are
    stretched states its own)."""
    _LATENT.labels(impl="expanded").inc()
    return grouped_attention(q, k, v, block=block, scale=scale)
