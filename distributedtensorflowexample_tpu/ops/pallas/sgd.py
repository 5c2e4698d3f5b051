"""Fused momentum-SGD update Pallas kernel (the per-step optimizer apply).

The reference's update was a native TF ``ApplyMomentum`` op per variable
(library C++, SURVEY.md §2 native-dependency table).  This kernel is the
TPU equivalent: one VMEM pass computes

    m_new = mu * m + g          (optax.sgd(momentum=mu) trace semantics)
    p_new = p - lr * m_new

over the WHOLE parameter set at once.  Every leaf is packed into a single
flat (rows, 128) f32 buffer — the momentum trace lives flat in the
optimizer state, params/grads are flattened per step — so the apply is ONE
``pallas_call`` regardless of how many parameter tensors the model has
(ResNet-20 has ~65; the round-1 per-leaf version launched ~65 kernels plus
per-leaf pad/unpad traffic per step).  ``input_output_aliases`` lets XLA
reuse the flat operands' buffers for the outputs.  ``lr`` arrives as a
traced (1, 1) SMEM scalar so LR schedules stay dynamic; ``mu`` is
compile-time static.

Segment boundaries inside the flat buffer need no masking: the pad tail's
gradient is zero, so its momentum stays zero and its params stay put.

MEASURED ON-CHIP (v5e, 2026-07, one window; the record is not kept): 675
steps/s vs 1,543 for the XLA apply on MNIST-CNN — a 2.3x net slowdown.  The
single kernel launch is cheap; what XLA never pays is the per-step
``_flatten_leaves``/``_unflatten_like`` round-trip (~50 MB of extra HBM
traffic for a 3.3M-param model: build p_flat + g_flat, write both outputs,
then slice updates back out), because its own per-leaf apply fuses into
the gradient computation's epilogue with zero layout change.  Making this
kernel win would require the train state itself to keep params flat (model
views as slices) — not worth the intrusion for an elementwise op XLA
already fuses optimally.  The kernel stays as the opt-in
(``--fused_optimizer``) kernel-authoring reference, numbers documented.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distributedtensorflowexample_tpu.ops.pallas.tiling import (
    LANES as _LANES, SUBLANES, pick_block, resolve_interpret)

_ROW_BLOCK = 1024     # 1024x128 f32 = 512 KiB per operand block in VMEM


def _sgd_kernel(lr_ref, p_ref, m_ref, g_ref, p_out, m_out, *, mu: float):
    lr = lr_ref[0, 0]
    m_new = mu * m_ref[:] + g_ref[:]
    p_out[:] = p_ref[:] - lr * m_new
    m_out[:] = m_new


def _num_rows(n: int) -> int:
    """Rows of the flat (rows, 128) buffer holding ``n`` elements: a
    multiple of the 8-sublane tile, and past one row block a multiple of
    the block itself — otherwise ``pick_block`` falls to whatever power
    of two happens to divide the count (8 rows for lm_base's 57M
    params: a 55,947-step grid of 4 KiB blocks)."""
    rows = max(SUBLANES, -(-n // _LANES))
    multiple = _ROW_BLOCK if rows > _ROW_BLOCK else SUBLANES
    return -(-rows // multiple) * multiple


def _flatten_leaves(leaves, rows: int) -> jnp.ndarray:
    flat = jnp.concatenate(
        [jnp.ravel(x).astype(jnp.float32) for x in leaves])
    return jnp.pad(flat, (0, rows * _LANES - flat.size)).reshape(rows, _LANES)


def _unflatten_like(flat: jnp.ndarray, leaves, treedef):
    """Slice a flat buffer back into the shapes/dtypes of ``leaves``."""
    flat = flat.reshape(-1)
    out, offset = [], 0
    for leaf in leaves:
        out.append(flat[offset:offset + leaf.size]
                   .reshape(leaf.shape).astype(leaf.dtype))
        offset += leaf.size
    return treedef.unflatten(out)


def fused_sgd_flat(p_flat, m_flat, g_flat, lr, mu: float,
                   interpret: bool):
    """One momentum-SGD pass over flat (rows, 128) f32 buffers: a single
    ``pallas_call`` with a 1-D grid over row blocks."""
    rows = p_flat.shape[0]
    lr2d = jnp.asarray(lr, jnp.float32).reshape(1, 1)
    block = pick_block(rows, _ROW_BLOCK)
    spec = pl.BlockSpec((block, _LANES), lambda i: (i, 0),
                        memory_space=pltpu.VMEM)
    return pl.pallas_call(
        functools.partial(_sgd_kernel, mu=float(mu)),
        grid=(rows // block,),
        in_specs=[
            pl.BlockSpec((1, 1), lambda i: (0, 0),
                         memory_space=pltpu.SMEM),
            spec, spec, spec,
        ],
        out_specs=(spec, spec),
        out_shape=(jax.ShapeDtypeStruct((rows, _LANES), jnp.float32),
                   jax.ShapeDtypeStruct((rows, _LANES), jnp.float32)),
        input_output_aliases={1: 0, 2: 1},
        interpret=interpret,
        name="fused_momentum_sgd",
    )(lr2d, p_flat, m_flat, g_flat)


class FusedSgdState(NamedTuple):
    count: jnp.ndarray     # step counter for LR schedules
    trace: jnp.ndarray     # momentum, flat (rows, 128) f32


def fused_momentum_sgd(learning_rate, momentum: float = 0.9, mesh=None):
    """Optax-compatible transformation backed by the fused Pallas kernel.

    Same math as ``optax.sgd(learning_rate, momentum=momentum)``, but the
    state pytree differs (``FusedSgdState`` with a FLAT momentum buffer vs
    optax's per-leaf tuple), so a checkpoint written with one cannot be
    restored with the other — pick the flag per run, not mid-experiment.
    The optax contract returns *updates* (applied by
    ``optax.apply_updates``), so the kernel's result is expressed as
    ``p_new - p``; XLA folds the add/sub pair away.

    A ``pallas_call`` is a custom call XLA cannot auto-partition: on a
    multi-device mesh pass ``mesh`` so the kernel runs per-device under
    ``jax.shard_map`` (all operands are replicated in data parallelism, so
    every device performs the identical update).
    """
    import optax

    def init(params):
        n = sum(x.size for x in jax.tree.leaves(params))
        rows = _num_rows(n)
        return FusedSgdState(count=jnp.zeros([], jnp.int32),
                             trace=jnp.zeros((rows, _LANES), jnp.float32))

    def update(grads, state, params=None):
        if params is None:
            raise ValueError("fused_momentum_sgd requires params")
        lr = learning_rate(state.count) if callable(learning_rate) \
            else learning_rate
        leaves_p, treedef = jax.tree.flatten(params)
        leaves_g = treedef.flatten_up_to(grads)
        rows = state.trace.shape[0]
        p_flat = _flatten_leaves(leaves_p, rows)
        g_flat = _flatten_leaves(leaves_g, rows)
        interpret = resolve_interpret(None)
        if mesh is not None and mesh.size > 1:
            from jax.sharding import PartitionSpec as P

            apply = jax.shard_map(
                lambda p, m, g, lr_: fused_sgd_flat(p, m, g, lr_, momentum,
                                                    interpret),
                mesh=mesh, in_specs=(P(), P(), P(), P()),
                out_specs=(P(), P()), check_vma=False)
            p_new, m_new = apply(p_flat, state.trace, g_flat,
                                 jnp.asarray(lr, jnp.float32))
        else:
            p_new, m_new = fused_sgd_flat(p_flat, state.trace, g_flat, lr,
                                          momentum, interpret)
        updates = _unflatten_like(p_new - p_flat, leaves_p, treedef)
        return updates, FusedSgdState(count=state.count + 1, trace=m_new)

    return optax.GradientTransformation(init, update)


def fused_sgd_apply(params, momentum, grads, lr, mu: float = 0.9,
                    interpret: bool | None = None):
    """Apply one momentum-SGD step to a pytree; returns (params, momentum)
    as trees (parity-test surface; the optax path keeps momentum flat).

    ``lr`` may be a traced scalar (schedule output).  ``interpret=None``
    selects interpret mode on the ``cpu`` platform only.
    """
    leaves_p, treedef = jax.tree.flatten(params)
    leaves_m = treedef.flatten_up_to(momentum)
    leaves_g = treedef.flatten_up_to(grads)
    rows = _num_rows(sum(x.size for x in leaves_p))
    p_new, m_new = fused_sgd_flat(
        _flatten_leaves(leaves_p, rows), _flatten_leaves(leaves_m, rows),
        _flatten_leaves(leaves_g, rows), lr, mu, resolve_interpret(interpret))
    return (_unflatten_like(p_new, leaves_p, treedef),
            _unflatten_like(m_new, leaves_p, treedef))
