"""The ``bailing_hybrid`` family (inclusionAI's Ling linear models):
everything the harness knows of the architecture — five Kimi Delta
Attention layers to one of latent attention, a leading dense layer, a
sigmoid router limited to groups over routed experts beside a shared one,
an untied head — the program's model, the seeded weights, and the work a
step REQUIRES.

A configuration of this family may be ONE CHIP'S SHARE of an
expert-parallel deployment, stated as ``families/afmoe.py``'s are:
``num_experts`` experts are held of the ``published.num_experts`` the
router scores, and the vocabulary is a slice.  Every count below is of
the share.

The weights are bfloat16 (``assumed.compute_dtype``); the decay's two
vectors (``a_log``, ``dt_bias``) and the router's bias are float32.  They
are laid out under the parameter names of ``models/bailing_hybrid.py`` (a
test holds the two trees to each other).  They are seeded so that what
is new here matters: a step's decay runs from ~0.998 down to ~0.01
channel by channel (the lower bound is reached), the norms' scales are
not 1, the gates' inputs are not small.

The work counts are matrix multiplications (2 per multiply-add) and the
recurrence's elementwise products with the state; a decode step's least
bytes are every weight outside the routed experts once, each routed
expert that a pair TOUCHED once, the recurrent and convolution state of
EVERY slot read and written once (the program has one shape: a parked
slot's is moved too), and the live latent rows once, at the 1,152 bytes
a position needs (the program pads a row to 1,280).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

PARAM_BYTES = 2         # bfloat16
CACHE_BYTES = 2         # latent rows and the convolution's state: bfloat16
STATE_BYTES = 4         # the recurrent state: float32
_SIZE_KEYS = ("vocab_size", "hidden_size", "num_hidden_layers",
              "layer_group_size", "num_attention_heads", "head_dim",
              "short_conv_kernel_size", "kv_lora_rank", "qk_nope_head_dim",
              "qk_rope_head_dim", "v_head_dim", "intermediate_size",
              "moe_intermediate_size", "moe_shared_expert_intermediate_size",
              "num_shared_experts", "first_k_dense_replace", "num_experts")


def build_model(cfg: dict, **kwargs):
    """``BailingHybridLM`` through the constructor ``serving/promote.py``
    and ``tools/serve_lm.py --model_config`` use."""
    from distributedtensorflowexample_tpu.models import build_model_from_config
    return build_model_from_config(cfg, **kwargs)


def _routed(cfg: dict) -> int:
    return cfg.get("published", {}).get("num_experts", cfg["num_experts"])


def _is_latent(cfg: dict, i: int) -> bool:
    return (i + 1) % cfg["layer_group_size"] == 0


def kinds(cfg: dict) -> tuple:
    """(latent-attention layers, Kimi Delta Attention layers, expert
    layers)."""
    n = cfg["num_hidden_layers"]
    latent = sum(_is_latent(cfg, i) for i in range(n))
    return latent, n - latent, n - cfg["first_k_dense_replace"]


def param_shapes(cfg: dict) -> dict:
    """Leaf shapes of a configuration, as a tree of tuples."""
    d, H, D = cfg["hidden_size"], cfg["num_attention_heads"], cfg["head_dim"]
    rank, Dn = cfg["kv_lora_rank"], cfg["qk_nope_head_dim"]
    Dr, Dv = cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    mla = {"wq": (d, H * (Dn + Dr)), "w_kva": (d, rank + Dr),
           "norm_c": (rank,), "w_kvb": (rank, H, Dn + Dv),
           "w_gate": (d, H), "wo": (H * Dv, d)}
    kda = {"w_qkvu": (d, 4 * H * D), "w_f": (d, H * D), "w_b": (d, H),
           "conv": (cfg["short_conv_kernel_size"], 3 * H * D),
           "a_log": (H,), "dt_bias": (H * D,), "norm_o": (D,),
           "wo": (H * D, d)}
    ff = cfg["intermediate_size"]
    dense = {"ffn_gate": (d, ff), "ffn_up": (d, ff), "ffn_down": (ff, d)}
    f, E = cfg["moe_intermediate_size"], cfg["num_experts"]
    fs = (cfg["moe_shared_expert_intermediate_size"]
          * cfg["num_shared_experts"])
    experts = {"router": (d, _routed(cfg)), "router_bias": (_routed(cfg),),
               "shared_gate": (d, fs), "shared_up": (d, fs),
               "shared_down": (fs, d), "experts_gate": (E, d, f),
               "experts_up": (E, d, f), "experts_down": (E, f, d)}
    tree = {"embed": (cfg["vocab_size"], d), "norm_f": (d,),
            "head": (d, cfg["vocab_size"])}
    for i in range(cfg["num_hidden_layers"]):
        tree[f"block{i}"] = {
            "norm_in": (d,), "norm_post": (d,),
            **(mla if _is_latent(cfg, i) else kda),
            **(dense if i < cfg["first_k_dense_replace"] else experts)}
    return tree


def _counts(cfg: dict) -> tuple:
    """(parameters a token's step reads outside the routed experts — the
    embedding is a gather of a few rows, not among them —, parameters of
    one routed expert)."""
    leaves = jax.tree_util.tree_flatten_with_path(
        param_shapes(cfg), is_leaf=lambda x: isinstance(x, tuple))[0]
    outside = sum(math.prod(s) for path, s in leaves
                  if not path[-1].key.startswith("experts_")
                  and path[-1].key != "embed")
    return outside, 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def param_count(cfg: dict) -> int:
    outside, one = _counts(cfg)
    return (outside + cfg["vocab_size"] * cfg["hidden_size"]
            + one * cfg["num_experts"] * kinds(cfg)[2])


def weight_bytes(cfg: dict) -> int:
    """Bytes of the weights as the program stores and serves them (the
    few float32 vectors a layer keeps are counted as two bytes each:
    under 0.1 MB in all)."""
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
        name, k = names[-1], jax.random.fold_in(key, i)
        normal = lambda: jax.random.normal(k, shape, jnp.float32)
        uniform = lambda lo, hi: jax.random.uniform(k, shape, jnp.float32,
                                                    lo, hi)
        if name == "a_log":
            leaf = jnp.log(uniform(0.5, 1.5))
        elif name == "dt_bias":     # with a_log: decays of ~0.998 to ~0.01
            leaf = uniform(-7.0, 2.5)
        elif name == "router_bias":
            leaf = 0.01 * normal()
        elif name.startswith("norm_"):          # plain scales
            leaf = (1.0 + 0.1 * normal()).astype(jnp.bfloat16)
        elif name == "conv":
            leaf = (0.3 * normal()).astype(jnp.bfloat16)
        else:
            leaf = (cfg["initializer_range"] * normal()).astype(jnp.bfloat16)
        node = out
        for n in names[:-1]:
            node = node.setdefault(n, {})
        node[name] = leaf
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

def _kda_width(cfg: dict) -> int:
    return cfg["num_attention_heads"] * cfg["head_dim"]


def state_bytes_per_slot(cfg: dict) -> int:
    """What one slot holds over all Kimi Delta Attention layers: the
    recurrent state (float32) and the convolution's last inputs."""
    one = (STATE_BYTES * _kda_width(cfg) * cfg["head_dim"]
           + CACHE_BYTES * (cfg["short_conv_kernel_size"] - 1)
           * 3 * _kda_width(cfg))
    return kinds(cfg)[1] * one


def latent_row_bytes(cfg: dict) -> int:
    """The compressed row of one position in one latent layer, as the
    mathematics needs it (the program pads it to whole lane groups)."""
    return (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) * CACHE_BYTES


def _held_share(cfg: dict) -> float:
    return cfg["num_experts"] / _routed(cfg)


def _token_flops(cfg: dict, pairs_per_token: float) -> float:
    """One token outside attention's scores: the matrix products with
    everything outside the routed experts and with ``pairs_per_token``
    routed experts, and the recurrence's products with the state (three
    multiply-adds an element: S^T k, the update, S^T q)."""
    outside, one = _counts(cfg)
    state = 3 * kinds(cfg)[1] * _kda_width(cfg) * cfg["head_dim"]
    return 2 * (outside + pairs_per_token * one + state)


def _latent_row_flops(cfg: dict) -> int:
    """One query's work on one cached row in one latent layer, absorbed:
    every head's score over the row and its weighted sum of the latent
    part."""
    return 2 * cfg["num_attention_heads"] * (
        2 * cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward plus backward (three forwards) of one token of a
    ``seq_len`` sequence, under even routing, attention expanded."""
    H = cfg["num_attention_heads"]
    width = H * (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
                 + cfg["v_head_dim"])
    pairs = cfg["num_experts_per_tok"] * _held_share(cfg) * kinds(cfg)[2]
    return 3 * (_token_flops(cfg, pairs)
                + 2 * width * kinds(cfg)[0] * (seq_len + 1) / 2)


def decode_step_flops(cfg: dict, live_rows: int, slots: int, *,
                      pairs_held: float | None = None) -> float:
    """One decode step over ``slots`` single-token queries that read
    ``live_rows`` rows in each latent layer, ``pairs_held`` (token,
    expert) pairs landing on held experts over all layers (even routing
    where not given)."""
    if pairs_held is None:
        pairs_held = (slots * cfg["num_experts_per_tok"] * _held_share(cfg)
                      * kinds(cfg)[2])
    return (slots * _token_flops(cfg, 0.0) + 2 * pairs_held * _counts(cfg)[1]
            + _latent_row_flops(cfg) * kinds(cfg)[0] * live_rows)


def decode_step_bytes(cfg: dict, live_rows: int, *, slots: int,
                      experts_touched: float | None = None) -> float:
    """Bytes one decode step over ``slots`` slots must move: every
    weight outside the routed experts once, ``experts_touched`` routed
    experts once (summed over the layers; every held expert where not
    given), every slot's state read and written once, and the latent
    rows read: ``live_rows`` in each latent layer."""
    outside, one = _counts(cfg)
    if experts_touched is None:
        experts_touched = cfg["num_experts"] * kinds(cfg)[2]
    return (PARAM_BYTES * (outside + experts_touched * one)
            + 2 * slots * state_bytes_per_slot(cfg)
            + latent_row_bytes(cfg) * kinds(cfg)[0] * live_rows)


def kda_step_bytes(cfg: dict, slots: int) -> int:
    """Bytes ONE call of the token step's recurrence kernel
    (``gated_delta_step``, one a Kimi Delta Attention layer) must move:
    every slot's recurrent state read and written once, and per head the
    tile of vectors it is handed and the output row it gives."""
    state = STATE_BYTES * _kda_width(cfg) * cfg["head_dim"]
    return slots * (2 * state + 4 * cfg["num_attention_heads"]
                    * (8 * 128 + cfg["head_dim"]))


def kda_step_flops(cfg: dict, slots: int) -> int:
    """Its operations: four multiply-adds an element of the state (the
    decay a row, S^T k, the update, S^T q)."""
    return 2 * 4 * slots * _kda_width(cfg) * cfg["head_dim"]


def latent_decode_bytes(cfg: dict, live_rows: int) -> int:
    """Bytes ONE call of the token step's latent attention kernel
    (``latent_decode_attention``, one a latent layer) must move: the
    ``live_rows`` rows its queries see, once."""
    return latent_row_bytes(cfg) * live_rows


def latent_decode_flops(cfg: dict, live_rows: int) -> int:
    return _latent_row_flops(cfg) * live_rows
