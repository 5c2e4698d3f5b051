"""The ``afmoe`` family (Arcee's Trinity models): everything the harness
knows of the architecture — RMSNorm sandwich blocks, grouped-query
attention with a gate, window and full layers mixed, a sigmoid router
over routed experts beside a shared one, an untied head — the program's
model, the seeded weights, and the work a step REQUIRES.

A configuration of this family may be ONE CHIP'S SHARE of an
expert-parallel deployment (``models/afmoe.py: dims_from_config`` says
how the file states it): ``num_experts`` experts are held of the
``published.num_experts`` the router scores, and the vocabulary is a
slice.  Every count below is of the share.

The weights are bfloat16: the type the program stores and serves this
family's parameters in (``assumed.compute_dtype``); the router's bias
buffer is float32.  They are laid out under the parameter names of
``models/afmoe.py`` (a test holds the two trees to each other), so the
tree binds to ``DecodeEngine`` unchanged.  The plain reference is handed
``init_params`` of the same seed; it is never handed an array the
program has touched.

The work counts are matrix multiplications only (2 per multiply-add);
a decode step's least bytes are every weight outside the routed experts
once, each routed expert that a pair TOUCHED once, and the cache rows
the queries read, by kind of layer.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

PARAM_BYTES = 2         # bfloat16
CACHE_BYTES = 2         # the serving cache's K and V rows are bfloat16
_SIZE_KEYS = ("vocab_size", "hidden_size", "num_attention_heads",
              "num_key_value_heads", "head_dim", "intermediate_size",
              "moe_intermediate_size", "num_hidden_layers",
              "num_dense_layers", "num_experts", "num_shared_experts")


def build_model(cfg: dict, **kwargs):
    """``AfmoeLM`` through the constructor ``serving/promote.py`` and
    ``tools/serve_lm.py --model_config`` use (the serve driver passes
    ``dtype``; the parameters are bfloat16)."""
    from distributedtensorflowexample_tpu.models import build_model_from_config
    return build_model_from_config(cfg, **kwargs)


def _routed(cfg: dict) -> int:
    return cfg.get("published", {}).get("num_experts", cfg["num_experts"])


def param_shapes(cfg: dict) -> dict:
    """Leaf shapes of a configuration, as a tree of tuples."""
    d, Dh = cfg["hidden_size"], cfg["head_dim"]
    qd, kd = cfg["num_attention_heads"] * Dh, cfg["num_key_value_heads"] * Dh
    attn = {"norm_in": (d,), "norm_post_attn": (d,), "norm_pre_mlp": (d,),
            "norm_post_mlp": (d,), "norm_q": (Dh,), "norm_k": (Dh,),
            "wq": (d, qd), "wk": (d, kd), "wv": (d, kd), "wg": (d, qd),
            "wo": (qd, d)}
    ff = cfg["intermediate_size"]
    dense = {"ffn_gate": (d, ff), "ffn_up": (d, ff), "ffn_down": (ff, d)}
    f, E = cfg["moe_intermediate_size"], cfg["num_experts"]
    fs = cfg["num_shared_experts"] * f
    experts = {"router": (d, _routed(cfg)), "router_bias": (_routed(cfg),),
               "shared_gate": (d, fs), "shared_up": (d, fs),
               "shared_down": (fs, d), "experts_gate": (E, d, f),
               "experts_up": (E, d, f), "experts_down": (E, f, d)}
    tree = {"embed": (cfg["vocab_size"], d), "norm_f": (d,),
            "head": (d, cfg["vocab_size"])}
    for i in range(cfg["num_hidden_layers"]):
        tree[f"block{i}"] = {**attn, **(
            dense if i < cfg["num_dense_layers"] else experts)}
    return tree


def _counts(cfg: dict) -> tuple:
    """(parameters a token's step multiplies by outside the routed
    experts — the embedding is a gather of a few rows, not among them —,
    parameters of one routed expert, expert layers)."""
    shapes = param_shapes(cfg)
    leaves = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple))[0]
    outside = sum(math.prod(s) for path, s in leaves
                  if not path[-1].key.startswith("experts_")
                  and path[-1].key != "embed")
    one = 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]
    return outside, one, cfg["num_hidden_layers"] - cfg["num_dense_layers"]


def param_count(cfg: dict) -> int:
    outside, one, layers = _counts(cfg)
    return (outside + cfg["vocab_size"] * cfg["hidden_size"]
            + one * cfg["num_experts"] * layers)


def weight_bytes(cfg: dict) -> int:
    """Bytes of the weights as the program stores and serves them."""
    return PARAM_BYTES * param_count(cfg)


def _init(cfg_items: tuple, routed: int, seed):
    cfg = dict(cfg_items)
    cfg["published"] = {"num_experts": routed}
    key = jax.random.PRNGKey(seed)
    paths = jax.tree_util.tree_flatten_with_path(
        param_shapes(cfg), is_leaf=lambda x: isinstance(x, tuple))[0]
    out: dict = {}
    for i, (path, shape) in enumerate(paths):
        names = [p.key for p in path]
        if names[-1].startswith("norm_"):
            leaf = jnp.ones(shape, jnp.bfloat16)
        else:
            normal = jax.random.normal(jax.random.fold_in(key, i), shape,
                                       jnp.float32)
            leaf = (0.01 * normal if names[-1] == "router_bias" else
                    (cfg["initializer_range"] * normal).astype(jnp.bfloat16))
        node = out
        for n in names[:-1]:
            node = node.setdefault(n, {})
        node[names[-1]] = leaf
    return out


def init_fn(cfg: dict):
    """``seed -> parameter tree``, not yet jitted; the seed is an
    ARGUMENT of whatever program calls this, never a constant in it."""
    static = tuple(sorted(
        (k, cfg[k]) for k in _SIZE_KEYS)) + (
        ("initializer_range", cfg["assumed"]["initializer_range"]),)
    return functools.partial(_init, static, _routed(cfg))


def init_params(cfg: dict, seed: int, sharding=None):
    """The parameter tree of ``cfg`` from ``seed``, on the device, by
    ONE jitted call."""
    return jax.jit(init_fn(cfg), out_shardings=sharding)(jnp.uint32(seed))


# ---- the work a step requires ---------------------------------------------

def _kinds(cfg: dict) -> tuple:
    """(full-attention layers, window layers)."""
    full = sum(t == "full_attention" for t in cfg["layer_types"])
    return full, len(cfg["layer_types"]) - full


def _token_flops(cfg: dict, pairs_per_token: float) -> float:
    """Matrix products of one token outside attention's scores:
    everything outside the routed experts and ``pairs_per_token`` routed
    experts, summed over the layers."""
    outside, one, _ = _counts(cfg)
    return 2 * (outside + pairs_per_token * one)


def _held_share(cfg: dict) -> float:
    return cfg["num_experts"] / _routed(cfg)


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward plus backward (three forwards) of one token of a
    ``seq_len`` sequence, under even routing; a window layer's scores
    reach at most ``sliding_window`` keys."""
    full, window = _kinds(cfg)
    width = cfg["num_attention_heads"] * cfg["head_dim"]
    keys = full * (seq_len + 1) / 2 + window * min(
        (seq_len + 1) / 2, cfg["sliding_window"])
    layers = cfg["num_hidden_layers"] - cfg["num_dense_layers"]
    pairs = cfg["num_experts_per_tok"] * _held_share(cfg) * layers
    return 3 * (_token_flops(cfg, pairs) + 2 * 2 * width * keys)


def decode_step_flops(cfg: dict, live_rows: int, slots: int, *,
                      window_rows: float | None = None,
                      pairs_held: float | None = None) -> float:
    """One decode step over ``slots`` single-token queries that read
    ``live_rows`` rows in each full layer and ``window_rows`` in each
    window layer (``live_rows`` where not given), ``pairs_held`` (token,
    expert) pairs landing on held experts over all layers (even routing
    where not given)."""
    full, window = _kinds(cfg)
    width = cfg["num_attention_heads"] * cfg["head_dim"]
    if window_rows is None:
        window_rows = live_rows
    if pairs_held is None:
        layers = cfg["num_hidden_layers"] - cfg["num_dense_layers"]
        pairs_held = (slots * cfg["num_experts_per_tok"]
                      * _held_share(cfg) * layers)
    return (slots * _token_flops(cfg, 0.0)
            + 2 * pairs_held * _counts(cfg)[1]
            + 2 * 2 * width * (full * live_rows + window * window_rows))


def decode_step_bytes(cfg: dict, live_rows: int, *,
                      window_rows: float | None = None,
                      experts_touched: float | None = None) -> float:
    """Bytes one decode step must move: every weight outside the routed
    experts once, ``experts_touched`` routed experts once (summed over
    the layers; every held expert where not given), and the K and V rows
    read: ``live_rows`` in each full layer, ``window_rows`` in each
    window layer."""
    outside, one, layers = _counts(cfg)
    full, window = _kinds(cfg)
    if window_rows is None:
        window_rows = live_rows
    if experts_touched is None:
        experts_touched = cfg["num_experts"] * layers
    row = 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * CACHE_BYTES
    return (PARAM_BYTES * (outside + experts_touched * one)
            + row * (full * live_rows + window * window_rows))
