"""Fused softmax-cross-entropy Pallas kernel (loss head of every workload).

Replaces the reference's ``tf.nn.softmax_cross_entropy_with_logits`` native
op (SURVEY.md §2 C8/C9 loss math) with a TPU kernel: one VMEM pass computes
max, log-sum-exp and the target logit per row — the softmax is never
materialized in HBM.  The backward kernel recomputes the softmax from the
saved logits (FLOPs are free next to the HBM traffic it saves) and emits
``(softmax - target) * g`` in the same pass.

Shapes: logits [B, C] float32, labels [B] int32.  C is padded to the
128-lane tile and masked inside the kernel; rows with label < 0 contribute
zero loss and zero gradient (used by callers to pad B to the row tile).

Returns PER-ROW losses [B] so the batch mean stays an ordinary jnp op —
under data parallelism that mean is where XLA inserts the cross-chip psum,
identical to the XLA loss path (parallel/sync.py).  A ``pallas_call`` is
not auto-partitionable, so multi-device callers wrap this in
``jax.shard_map`` along the batch axis (see ``parallel.sync``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distributedtensorflowexample_tpu.ops.pallas.tiling import (
    LANES as _LANES, SUBLANES, pad_rows as _pad_rows, pick_block,
    resolve_interpret)

_ROW_BLOCK = 512      # rows per grid step; multiple of the 8-sublane tile


def _ce_fwd_kernel(logits_ref, labels_ref, loss_ref, *, num_classes: int,
                   smoothing: float):
    logits = logits_ref[:]                      # [TB, CP] f32
    labels = labels_ref[:]                      # [TB, 1] i32
    col = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
    valid_col = col < num_classes
    masked = jnp.where(valid_col, logits, -jnp.inf)
    m = jnp.max(masked, axis=1, keepdims=True)
    ex = jnp.where(valid_col, jnp.exp(masked - m), 0.0)
    lse = m + jnp.log(jnp.sum(ex, axis=1, keepdims=True))      # [TB, 1]
    picked = jnp.sum(jnp.where(col == labels, logits, 0.0), axis=1,
                     keepdims=True)
    if smoothing > 0.0:
        mean_logit = jnp.sum(jnp.where(valid_col, logits, 0.0), axis=1,
                             keepdims=True) / num_classes
        target = (1.0 - smoothing) * picked + smoothing * mean_logit
    else:
        target = picked
    loss_ref[:] = jnp.where(labels >= 0, lse - target, 0.0)


def _ce_bwd_kernel(logits_ref, labels_ref, g_ref, dlogits_ref, *,
                   num_classes: int, smoothing: float):
    logits = logits_ref[:]
    labels = labels_ref[:]
    g = g_ref[:]                                # [TB, 1] upstream per-row
    col = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
    valid_col = col < num_classes
    masked = jnp.where(valid_col, logits, -jnp.inf)
    m = jnp.max(masked, axis=1, keepdims=True)
    ex = jnp.where(valid_col, jnp.exp(masked - m), 0.0)
    softmax = ex / jnp.sum(ex, axis=1, keepdims=True)
    onehot = jnp.where(col == labels, 1.0, 0.0)
    if smoothing > 0.0:
        target = ((1.0 - smoothing) * onehot
                  + jnp.where(valid_col, smoothing / num_classes, 0.0))
    else:
        target = onehot
    grad = (softmax - target) * g
    dlogits_ref[:] = jnp.where(valid_col & (labels >= 0), grad, 0.0)


def _pad_cols(logits: jnp.ndarray) -> jnp.ndarray:
    c = logits.shape[-1]
    cp = max(_LANES, ((c + _LANES - 1) // _LANES) * _LANES)
    if cp != c:
        logits = jnp.pad(logits, ((0, 0), (0, cp - c)))
    return logits


def _pick_block(padded_b: int) -> int:
    """Largest 8-aligned row block ≤ _ROW_BLOCK dividing the padded batch."""
    return pick_block(padded_b, _ROW_BLOCK)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _ce_rows(logits, labels2d, num_classes, smoothing, interpret):
    rows, _ = _ce_fwd(logits, labels2d, num_classes, smoothing, interpret)
    return rows


def _ce_fwd(logits, labels2d, num_classes, smoothing, interpret):
    b = logits.shape[0]
    block = _pick_block(b)
    grid = (b // block,)
    rows = pl.pallas_call(
        functools.partial(_ce_fwd_kernel, num_classes=num_classes,
                          smoothing=smoothing),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block, logits.shape[1]), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((block, 1), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((block, 1), lambda i: (i, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((b, 1), jnp.float32),
        interpret=interpret,
        name="softmax_ce_fwd",
    )(logits, labels2d)
    return rows, (logits, labels2d)


def _ce_bwd(num_classes, smoothing, interpret, res, g_rows):
    logits, labels2d = res
    b = logits.shape[0]
    block = _pick_block(b)
    grid = (b // block,)
    dlogits = pl.pallas_call(
        functools.partial(_ce_bwd_kernel, num_classes=num_classes,
                          smoothing=smoothing),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block, logits.shape[1]), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((block, 1), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((block, 1), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((block, logits.shape[1]), lambda i: (i, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct(logits.shape, jnp.float32),
        interpret=interpret,
        name="softmax_ce_bwd",
    )(logits, labels2d, g_rows)
    return dlogits, None


_ce_rows.defvjp(_ce_fwd, _ce_bwd)


def fused_softmax_cross_entropy_rows(logits: jnp.ndarray,
                                     labels: jnp.ndarray,
                                     label_smoothing: float = 0.0,
                                     interpret: bool | None = None
                                     ) -> jnp.ndarray:
    """Per-row cross-entropy losses [B] via the fused Pallas kernel.

    ``interpret=None`` selects interpret mode on the ``cpu`` platform only
    (``tiling.resolve_interpret``).  Gradients flow to ``logits`` only.
    """
    interpret = resolve_interpret(interpret)
    b, c = logits.shape
    logits = _pad_cols(logits.astype(jnp.float32))
    labels2d = labels.astype(jnp.int32).reshape(b, 1)
    logits = _pad_rows(logits, SUBLANES, 0.0)
    labels2d = _pad_rows(labels2d, SUBLANES, -1)
    rows = _ce_rows(logits, labels2d, c, float(label_smoothing), interpret)
    return rows[:b, 0]
