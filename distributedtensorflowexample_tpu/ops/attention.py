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
