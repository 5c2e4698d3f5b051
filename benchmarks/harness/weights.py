"""The benchmark's weights: made on the device from ``--seed`` by ONE
jitted call, in float32 (the type the program stores and serves them
in), laid out under the parameter names of ``models/transformer_lm.py``
so the tree binds to the trainer's state and to ``DecodeEngine``
unchanged.  The plain reference calls the same function with the same
seed; it is never handed an array the program has touched."""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp


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
    """The parameter tree of ``cfg`` from ``seed``, on the device."""
    return jax.jit(init_fn(cfg), out_shardings=sharding)(jnp.uint32(seed))
