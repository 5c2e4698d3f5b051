"""The GPT-2 family: everything the harness knows of the architecture
(pre-LN blocks, learned positions, a tied head, full multi-head
attention) — the program's model, the seeded weights, and the work a
step REQUIRES.  A configuration names its family (``"family"`` in its
file); the drivers and readers reach these functions through
``run.family`` (families/__init__.py says what each is for).

The weights are float32: the type the program stores and serves this
family's parameters in (the configuration's ``assumed.compute_dtype``).
They are laid out under the parameter names of
``models/transformer_lm.py``, so the tree binds to the trainer's state
and to ``DecodeEngine`` unchanged.  The plain reference calls
``init_params`` with the same seed; it is never handed an array the
program has touched.

The work counts are matrix multiplications only (2 per multiply-add);
causal attention is counted at half the square; recomputation is never
counted."""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

PARAM_BYTES = 4         # float32
CACHE_BYTES = 2         # the serving cache's K and V rows are bfloat16


def build_model(cfg: dict, **kwargs):
    """``TransformerLM`` from the sizes as the file states them, through
    the constructor the trainer and ``tools/serve_lm.py`` use."""
    from distributedtensorflowexample_tpu.models.transformer_lm import (
        TransformerLM)
    d = cfg["n_embd"]
    return TransformerLM(
        vocab_size=cfg["vocab_size"], n_layers=cfg["n_layer"], d_model=d,
        n_heads=cfg["n_head"], d_ff=cfg.get("n_inner") or 4 * d,
        max_len=cfg["n_positions"], **kwargs)


def param_shapes(cfg: dict) -> dict:
    """Leaf shapes of a GPT-2 configuration, as a tree of tuples."""
    d, L = cfg["n_embd"], cfg["n_layer"]
    ff = cfg.get("n_inner") or 4 * d
    ln = {"scale": (d,), "bias": (d,)}
    block = {"ln1": ln, "qkv": {"kernel": (d, 3 * d), "bias": (3 * d,)},
             "attn_out": {"kernel": (d, d), "bias": (d,)}, "ln2": ln,
             "mlp_in": {"kernel": (d, ff), "bias": (ff,)},
             "mlp_out": {"kernel": (ff, d), "bias": (d,)}}
    tree = {"embed": {"embedding": (cfg["vocab_size"], d)},
            "pos": {"embedding": (cfg["n_positions"], d)}, "ln_f": ln}
    tree.update({f"block{i}": block for i in range(L)})
    return tree


def param_count(cfg: dict) -> int:
    return sum(math.prod(s) for s in jax.tree.leaves(
        param_shapes(cfg), is_leaf=lambda x: isinstance(x, tuple)))


def weight_bytes(cfg: dict) -> int:
    """Bytes of the weights as the program stores and serves them."""
    return PARAM_BYTES * param_count(cfg)


def _init(cfg_items: tuple, seed):
    cfg = dict(cfg_items)
    std = cfg["initializer_range"]
    resid = std / math.sqrt(2 * cfg["n_layer"])
    key = jax.random.PRNGKey(seed)
    shapes = param_shapes(cfg)
    paths = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple))[0]
    out = {}
    for i, (path, shape) in enumerate(paths):
        names = [p.key for p in path]
        if names[-1] == "scale":
            leaf = jnp.ones(shape, jnp.float32)
        elif names[-1] == "bias":
            leaf = jnp.zeros(shape, jnp.float32)
        else:
            s = (0.01 if names[0] == "pos" else
                 resid if names[-2] in ("attn_out", "mlp_out") else std)
            leaf = s * jax.random.normal(jax.random.fold_in(key, i), shape,
                                         jnp.float32)
        node = out
        for n in names[:-1]:
            node = node.setdefault(n, {})
        node[names[-1]] = leaf
    return out


def _static(cfg: dict) -> tuple:
    return tuple(sorted((k, cfg[k]) for k in (
        "n_layer", "n_embd", "n_inner", "n_positions", "vocab_size",
        "initializer_range")))


def init_fn(cfg: dict):
    """``seed -> parameter tree``, not yet jitted.  The seed is an
    ARGUMENT of whatever program calls this, never a constant in it: a
    program with the seed baked in would compile anew for every seed."""
    return functools.partial(_init, _static(cfg))


def init_params(cfg: dict, seed: int, sharding=None):
    """The parameter tree of ``cfg`` from ``seed``, on the device, by
    ONE jitted call."""
    return jax.jit(init_fn(cfg), out_shardings=sharding)(jnp.uint32(seed))


def _dims(cfg: dict) -> tuple:
    d = cfg["n_embd"]
    return cfg["n_layer"], d, cfg.get("n_inner") or 4 * d, cfg["vocab_size"]


def forward_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward pass of one token of a ``seq_len`` sequence under causal
    attention: qkv, attention output, the two MLP matrices, the scores
    and the weighted sum over (seq_len + 1) / 2 keys on average, and the
    tied head."""
    L, d, ff, V = _dims(cfg)
    per_layer = 2 * d * 3 * d + 2 * d * d + 2 * 2 * d * ff
    attn = 2 * 2 * d * (seq_len + 1) / 2
    return L * (per_layer + attn) + 2 * d * V


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward plus backward: the backward pass of a matrix
    multiplication is two of the same size."""
    return 3 * forward_flops_per_token(cfg, seq_len)


def decode_step_flops(cfg: dict, live_rows: int, slots: int) -> float:
    """One decode step over ``slots`` single-token queries whose caches
    hold ``live_rows`` rows in total."""
    L, d, ff, V = _dims(cfg)
    per_layer = 2 * d * 3 * d + 2 * d * d + 2 * 2 * d * ff
    return slots * (L * per_layer + 2 * d * V) + L * 2 * 2 * d * live_rows


def decode_step_bytes(cfg: dict, live_rows: int) -> float:
    """Bytes one decode step must move: every weight once (as stored)
    and every live cache row (K and V, all layers) once."""
    L, d, _, _ = _dims(cfg)
    return weight_bytes(cfg) + live_rows * L * 2 * d * CACHE_BYTES
