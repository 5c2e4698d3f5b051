"""Loss/metric math shared by every trainer.

The reference computed ``tf.nn.softmax_cross_entropy_with_logits`` + an
accuracy eval op per script [RECONSTRUCTED]; here they are pure jnp
functions.  The mean over the batch axis is the point where XLA inserts the
cross-replica psum under data parallelism — no explicit collective code.

Everything here runs in f32 on [B, C]-sized tensors by design: the models
upcast logits at their boundary for loss stability, and a per-op bytes
attribution of the compiled MNIST-CNN step (PR 2) priced the whole loss
path at ~10 KB/step — downcasting it to bf16 would trade numerics for
nothing.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def softmax_cross_entropy_rows(logits: jnp.ndarray, labels: jnp.ndarray,
                               label_smoothing: float = 0.0) -> jnp.ndarray:
    """Per-example cross-entropy [B] from int labels; logits [B,C]."""
    num_classes = logits.shape[-1]
    onehot = jax.nn.one_hot(labels, num_classes, dtype=logits.dtype)
    if label_smoothing > 0.0:
        onehot = onehot * (1.0 - label_smoothing) + label_smoothing / num_classes
    log_probs = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.sum(onehot * log_probs, axis=-1)


def softmax_cross_entropy(logits: jnp.ndarray, labels: jnp.ndarray,
                          label_smoothing: float = 0.0) -> jnp.ndarray:
    """Mean cross-entropy from int labels. logits [B,C] f32, labels [B] int."""
    return jnp.mean(softmax_cross_entropy_rows(logits, labels, label_smoothing))


def accuracy(logits: jnp.ndarray, labels: jnp.ndarray) -> jnp.ndarray:
    return jnp.mean((jnp.argmax(logits, axis=-1) == labels).astype(jnp.float32))
