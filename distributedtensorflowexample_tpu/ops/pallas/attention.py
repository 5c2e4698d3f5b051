"""Blocked causal self-attention Pallas kernels (the training block's
``attn`` scope): forward and backward, each ONE kernel, with an online
softmax, so no ``[T, T]`` array is ever written to HBM.

Layout.  q, k, v arrive as the model has them, ``[B, T, H * Dh]`` with
the heads side by side on the last axis, and stay that way: a grid cell
is one batch row and one 128-lane group of that axis — two heads of
width 64 or one of 128 — and holds its whole ``[T, 128]`` q, k and v in
VMEM (256 KB each at T = 1024).  No transpose to ``[B, H, T, Dh]`` is
made in HBM on the way in or out.

Orientation.  Inside a cell the score tiles are kept TRANSPOSED,
``s^T = k_j @ q_i^T`` of shape ``[block_k, block_q]``: the running max,
the running sum, the log-sum-exp and the backward's ``delta`` are then
ROWS ``[1, block_q]`` — reduced over sublanes, broadcast over sublanes,
stored lane-dense — and every product of the backward is a plain
``A @ B`` with no per-tile transpose:

    forward    s^T = k_j  @ q_i^T          acc^T += v_j^T @ p^T
    backward   s^T, dp^T = v_j @ do_i^T    ds^T = p^T * (dp^T - delta)
               dv_j += p^T @ do_i    dk_j += ds^T @ q_i
               dq_i^T += k_j^T @ ds^T

The transposes left are of ``[block, 128]`` operands, once per block and
not once per tile.  With two heads in a lane group, head ``h``'s scores
come from zeroing the other head's features in ``q_i^T`` (a contraction
over 64 of 128 lanes costs the MXU what a contraction over 128 does),
its ``acc^T`` and ``dq^T`` are the 64 sublanes of its features, and its
share of ``dv_j`` / ``dk_j`` lands in its own lanes because ``do_i`` and
``q_i`` are zeroed outside them.

Blocks wholly above the diagonal are skipped (the loops are unrolled at
trace time and never reach them); the diagonal tile alone is masked.

Precision is the einsum chain's or better: q, k, v in their own dtype
(bf16 in the model), scores accumulated and scaled in f32, max / sum /
log-sum-exp in f32, probabilities cast to the operands' dtype for the
weighted sum, every accumulator f32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distributedtensorflowexample_tpu.ops.pallas.tiling import (
    LANES, resolve_interpret)

#: Rows of q (and of k) per score tile, largest first: the first that
#: divides the sequence is taken.  Swept on the v5e at T = 1024, head
#: width 64, forward + backward of one 124M layer at B = 16 (PERF.md §6,
#: PR 25): 512 2.08 ms, 256 2.28, 128 2.63, 1024 2.47 (the einsum chain
#: 6.68).
BLOCKS = (512, 256, 128)
#: A cell keeps whole-sequence operands in VMEM and its loops are
#: unrolled at trace time: (T / block)^2 / 2 tiles a head.
MAX_SEQ_LEN = 2048
_NEG = -1e30          # masked score / initial max: finite, so no inf - inf
_VMEM_LIMIT = 64 * 1024 * 1024


def pick_block(seq_len: int, block: int | None = None) -> int | None:
    """The tile edge for ``seq_len``, or None where it does not tile:
    ``block`` if given, else the largest of :data:`BLOCKS` that divides
    it; always a multiple of the lane width."""
    if seq_len % LANES or seq_len > MAX_SEQ_LEN:
        return None
    for cand in ((block,) if block else BLOCKS):
        if cand % LANES == 0 and seq_len % cand == 0:
            return cand
    return None


def tiles(seq_len: int, head_dim: int, num_heads: int,
          block: int | None = None) -> bool:
    """Whether the kernels take these shapes: a sequence a block
    divides, heads of 64 or 128 features, whole lane groups."""
    return (pick_block(seq_len, block) is not None and head_dim in (64, 128)
            and (num_heads * head_dim) % LANES == 0)


def _t(x):
    """2-D transpose through f32 (the 32-bit transpose unit)."""
    return x.astype(jnp.float32).T.astype(x.dtype)


def _head_mask(shape, axis: int, h: int, head_dim: int):
    """True on the positions of ``axis`` that hold head ``h``'s features
    (None where the lane group is one head)."""
    if head_dim == LANES:
        return None
    idx = jax.lax.broadcasted_iota(jnp.int32, shape, axis)
    return (idx >= h * head_dim) & (idx < (h + 1) * head_dim)


def _keep(x, mask):
    return x if mask is None else jnp.where(mask, x, jnp.zeros_like(x))


def _dot(a, b):
    return jnp.dot(a, b, preferred_element_type=jnp.float32)


def _diag_mask(block: int):
    """[block_k, block_q]: key row <= query column."""
    rows = jax.lax.broadcasted_iota(jnp.int32, (block, block), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (block, block), 1)
    return rows <= cols


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, vt_ref, *,
                block: int, head_dim: int, scale: float):
    n = q_ref.shape[1] // block
    heads = LANES // head_dim
    blk = lambda i: slice(i * block, (i + 1) * block)
    diag = _diag_mask(block)
    for j in range(n):
        vt_ref[j] = _t(v_ref[0, blk(j), :])               # [128, block_k]
    for i in range(n):
        qt = _t(q_ref[0, blk(i), :])                      # [128, block_q]
        out = []
        for h in range(heads):
            feat = slice(h * head_dim, (h + 1) * head_dim)
            qt_h = _keep(qt, _head_mask(qt.shape, 0, h, head_dim))
            m = jnp.full((1, block), _NEG, jnp.float32)
            l = jnp.zeros((1, block), jnp.float32)
            acc = jnp.zeros((head_dim, block), jnp.float32)
            for j in range(i + 1):
                s = _dot(k_ref[0, blk(j), :], qt_h) * scale   # [bk, bq]
                if j == i:
                    s = jnp.where(diag, s, _NEG)
                m_new = jnp.maximum(m, jnp.max(s, axis=0, keepdims=True))
                alpha = jnp.exp(m - m_new)
                p = jnp.exp(s - m_new)
                l = alpha * l + jnp.sum(p, axis=0, keepdims=True)
                acc = alpha * acc + _dot(vt_ref[j, feat, :],
                                         p.astype(vt_ref.dtype))
                m = m_new
            out.append(acc / l)
            lse_ref[0, 0, h:h + 1, blk(i)] = m + jnp.log(l)
        ot = out[0] if heads == 1 else jnp.concatenate(out, axis=0)
        o_ref[0, blk(i), :] = ot.T.astype(o_ref.dtype)


def _bwd_kernel(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref,
                dq_ref, dk_ref, dv_ref, kt_ref, dk_acc, dv_acc, *,
                block: int, head_dim: int, scale: float):
    n = q_ref.shape[1] // block
    heads = LANES // head_dim
    blk = lambda i: slice(i * block, (i + 1) * block)
    diag = _diag_mask(block)
    cdt = q_ref.dtype
    for j in range(n):
        kt_ref[j] = _t(k_ref[0, blk(j), :])               # [128, block_k]
    dk_acc[...] = jnp.zeros_like(dk_acc)
    dv_acc[...] = jnp.zeros_like(dv_acc)
    for i in range(n):
        q_i, do_i = q_ref[0, blk(i), :], do_ref[0, blk(i), :]
        qt, dot_ = _t(q_i), _t(do_i)                      # [128, block_q]
        # delta = rowsum(do * o) per head, as a row: the head's sublanes
        # of (do * o)^T summed.
        prod_t = (do_i.astype(jnp.float32)
                  * o_ref[0, blk(i), :].astype(jnp.float32)).T
        dqt = []
        for h in range(heads):
            feat = slice(h * head_dim, (h + 1) * head_dim)
            on_rows = _head_mask(qt.shape, 0, h, head_dim)
            on_lanes = _head_mask(q_i.shape, 1, h, head_dim)
            qt_h, dot_h = _keep(qt, on_rows), _keep(dot_, on_rows)
            q_h, do_h = _keep(q_i, on_lanes), _keep(do_i, on_lanes)
            delta = jnp.sum(prod_t[feat, :], axis=0, keepdims=True)
            lse = lse_ref[0, 0, h:h + 1, blk(i)]
            acc = jnp.zeros((head_dim, block), jnp.float32)
            for j in range(i + 1):
                s = _dot(k_ref[0, blk(j), :], qt_h) * scale   # [bk, bq]
                if j == i:
                    s = jnp.where(diag, s, _NEG)
                p = jnp.exp(s - lse)
                dp = _dot(v_ref[0, blk(j), :], dot_h)
                ds = (p * (dp - delta)).astype(cdt)
                dv_acc[blk(j), :] += _dot(p.astype(cdt), do_h)
                dk_acc[blk(j), :] += _dot(ds, q_h)
                acc = acc + _dot(kt_ref[j, feat, :], ds)
            dqt.append(acc)
        dq_t = dqt[0] if heads == 1 else jnp.concatenate(dqt, axis=0)
        dq_ref[0, blk(i), :] = (dq_t.T * scale).astype(dq_ref.dtype)
    dk_ref[0] = (dk_acc[...] * scale).astype(dk_ref.dtype)
    dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _specs(b: int, t: int, d: int, block: int, head_dim: int):
    heads = LANES // head_dim
    seq = pl.BlockSpec((1, t, LANES), lambda i, g: (i, 0, g),
                       memory_space=pltpu.VMEM)
    lse = pl.BlockSpec((1, 1, heads, t), lambda i, g: (i, g, 0, 0),
                       memory_space=pltpu.VMEM)
    lse_shape = jax.ShapeDtypeStruct((b, d // LANES, heads, t), jnp.float32)
    params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel"),
        vmem_limit_bytes=_VMEM_LIMIT)
    return (b, d // LANES), seq, lse, lse_shape, params


# The two launches are jitted so that a model's layers share ONE traced
# and ONE lowered kernel each: the kernels' loops are unrolled at trace
# time, and tracing and lowering them once per layer (12 and 36 times a
# step, and again for the model's init) cost the train cells ~8 s of
# set-up on the chip's host even when the compiled step came from the
# cache (my chip runs, PR 25).

@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def _launch_fwd(q, k, v, head_dim, block, interpret):
    b, t, d = q.shape
    grid, seq, lse, lse_shape, params = _specs(b, t, d, block, head_dim)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, block=block, head_dim=head_dim,
                          scale=head_dim ** -0.5),
        grid=grid, in_specs=[seq, seq, seq], out_specs=[seq, lse],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype), lse_shape],
        scratch_shapes=[pltpu.VMEM((t // block, LANES, block), v.dtype)],
        compiler_params=params, interpret=interpret,
        name="causal_attention_fwd",
    )(q, k, v)


@functools.partial(jax.jit, static_argnums=(6, 7, 8))
def _launch_bwd(q, k, v, o, do, stats, head_dim, block, interpret):
    b, t, d = q.shape
    grid, seq, lse, _, params = _specs(b, t, d, block, head_dim)
    like = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, block=block, head_dim=head_dim,
                          scale=head_dim ** -0.5),
        grid=grid, in_specs=[seq, seq, seq, seq, seq, lse],
        out_specs=[seq, seq, seq], out_shape=[like(q), like(k), like(v)],
        scratch_shapes=[pltpu.VMEM((t // block, LANES, block), k.dtype),
                        pltpu.VMEM((t, LANES), jnp.float32),
                        pltpu.VMEM((t, LANES), jnp.float32)],
        compiler_params=params, interpret=interpret,
        name="causal_attention_bwd",
    )(q, k, v, o, do, stats)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _attention(q, k, v, head_dim, block, interpret):
    return _launch_fwd(q, k, v, head_dim, block, interpret)[0]


def _attention_fwd(q, k, v, head_dim, block, interpret):
    o, stats = _launch_fwd(q, k, v, head_dim, block, interpret)
    return o, (q, k, v, o, stats)


def _attention_bwd(head_dim, block, interpret, res, do):
    q, k, v, o, stats = res
    return tuple(_launch_bwd(q, k, v, o, do.astype(q.dtype), stats,
                             head_dim, block, interpret))


_attention.defvjp(_attention_fwd, _attention_bwd)


def blocked_causal_attention(q: jnp.ndarray, k: jnp.ndarray,
                             v: jnp.ndarray, block: int | None = None,
                             interpret: bool | None = None) -> jnp.ndarray:
    """Causal softmax attention ``[B, T, H, Dh] -> [B, T, H, Dh]`` (the
    model's layout) by the kernels above; differentiable in q, k and v.

    ``interpret=None`` selects interpret mode on the ``cpu`` platform
    only (``tiling.resolve_interpret``).  Raises where the shapes do not
    tile (:func:`tiles` says beforehand).
    """
    b, t, h, dh = q.shape
    if not tiles(t, dh, h, block):
        raise ValueError(
            f"blocked_causal_attention does not tile q{q.shape}: T must "
            f"be a multiple of {LANES} and of the block, at most "
            f"{MAX_SEQ_LEN}; head width 64 or 128; H * Dh a multiple of "
            f"{LANES}")
    flat = lambda x: x.reshape(b, t, h * dh)
    out = _attention(flat(q), flat(k), flat(v), dh, pick_block(t, block),
                     resolve_interpret(interpret))
    return out.reshape(b, t, h, dh)
