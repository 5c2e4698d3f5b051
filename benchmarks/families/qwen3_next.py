"""The ``qwen3_next`` family (Qwen3-Next): everything the harness knows
of the architecture — three Gated DeltaNet layers to one of gated
grouped-query attention, a softmax router over routed experts beside a
gated shared one in every layer, an untied head — the program's model,
the seeded weights, and the work a step REQUIRES.

A configuration of this family may be ONE CHIP'S SHARE of an
expert-parallel deployment, stated as ``families/afmoe.py``'s are:
``num_experts`` experts are held of the ``published.num_experts`` the
router scores, and the vocabulary is a slice.  Every count below is of
the share.

The weights are bfloat16 (``assumed.compute_dtype``); the decay's two
vectors (``a_log``, ``dt_bias``) are float32.  They are laid out under
the parameter names of ``models/qwen3_next.py`` (a test holds the two
trees to each other).  They are seeded so that what is new here matters:
the zero-centred norms' scales are normal(0, 0.1) and not 0, the decays
run from ~0.5 to ~0.998 a step, the gates' inputs are not small.

The work counts are matrix multiplications (2 per multiply-add) and the
recurrence's elementwise products with the state; a decode step's least
bytes are every weight outside the routed experts once, each routed
expert that a pair TOUCHED once, the recurrent and convolution state of
EVERY slot read and written once (the program has one shape: a parked
slot's is moved too), and the live K/V rows once.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

PARAM_BYTES = 2         # bfloat16
CACHE_BYTES = 2         # K/V rows and the convolution's state: bfloat16
STATE_BYTES = 4         # the recurrent state: float32
_SIZE_KEYS = ("vocab_size", "hidden_size", "num_hidden_layers",
              "full_attention_interval", "num_attention_heads",
              "num_key_value_heads", "head_dim", "linear_num_key_heads",
              "linear_num_value_heads", "linear_key_head_dim",
              "linear_value_head_dim", "linear_conv_kernel_dim",
              "moe_intermediate_size", "shared_expert_intermediate_size",
              "num_experts")


def build_model(cfg: dict, **kwargs):
    """``Qwen3NextLM`` through the constructor ``serving/promote.py`` and
    ``tools/serve_lm.py --model_config`` use."""
    from distributedtensorflowexample_tpu.models import build_model_from_config
    return build_model_from_config(cfg, **kwargs)


def _routed(cfg: dict) -> int:
    return cfg.get("published", {}).get("num_experts", cfg["num_experts"])


def _is_attention(cfg: dict, i: int) -> bool:
    return (i + 1) % cfg["full_attention_interval"] == 0


def _kinds(cfg: dict) -> tuple:
    """(attention layers, Gated DeltaNet layers)."""
    full = sum(_is_attention(cfg, i) for i in range(cfg["num_hidden_layers"]))
    return full, cfg["num_hidden_layers"] - full


def _lin(cfg: dict) -> tuple:
    """(q/k features, value features, value heads) of a Gated DeltaNet."""
    return (cfg["linear_num_key_heads"] * cfg["linear_key_head_dim"],
            cfg["linear_num_value_heads"] * cfg["linear_value_head_dim"],
            cfg["linear_num_value_heads"])


def param_shapes(cfg: dict) -> dict:
    """Leaf shapes of a configuration, as a tree of tuples."""
    d, Dh = cfg["hidden_size"], cfg["head_dim"]
    qd, kd = cfg["num_attention_heads"] * Dh, cfg["num_key_value_heads"] * Dh
    attn = {"norm_q": (Dh,), "norm_k": (Dh,), "wq": (d, 2 * qd),
            "wk": (d, kd), "wv": (d, kd), "wo": (qd, d)}
    lk, lv, Hv = _lin(cfg)
    gdn = {"w_qkvz": (d, 2 * lk + 2 * lv), "w_ba": (d, 2 * Hv),
           "conv": (cfg["linear_conv_kernel_dim"], 2 * lk + lv),
           "a_log": (Hv,), "dt_bias": (Hv,),
           "norm_o": (cfg["linear_value_head_dim"],), "w_out": (lv, d)}
    f, E = cfg["moe_intermediate_size"], cfg["num_experts"]
    fs = cfg["shared_expert_intermediate_size"]
    every = {"norm_in": (d,), "norm_post": (d,), "router": (d, _routed(cfg)),
             "shared_gate": (d, fs), "shared_up": (d, fs),
             "shared_down": (fs, d), "shared_gate_w": (d, 1),
             "experts_gate": (E, d, f), "experts_up": (E, d, f),
             "experts_down": (E, f, d)}
    tree = {"embed": (cfg["vocab_size"], d), "norm_f": (d,),
            "head": (d, cfg["vocab_size"])}
    for i in range(cfg["num_hidden_layers"]):
        tree[f"block{i}"] = {**every,
                             **(attn if _is_attention(cfg, i) else gdn)}
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
            + one * cfg["num_experts"] * cfg["num_hidden_layers"])


def weight_bytes(cfg: dict) -> int:
    """Bytes of the weights as the program stores and serves them (the
    64 float32 values a Gated DeltaNet layer keeps are counted as two
    bytes each: 1.5 kB in all)."""
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
        if name == "a_log":         # decays of ~0.5 to ~0.998 a step
            leaf = jnp.log(jax.random.uniform(k, shape, jnp.float32,
                                              0.02, 0.5))
        elif name == "dt_bias":
            leaf = jax.random.uniform(k, shape, jnp.float32, -1.0, 1.0)
        elif name == "norm_o":      # a plain scale
            leaf = (1.0 + 0.1 * normal()).astype(jnp.bfloat16)
        elif name.startswith("norm_"):          # RMS0's g: 1 + g scales
            leaf = (0.1 * normal()).astype(jnp.bfloat16)
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

def state_bytes_per_slot(cfg: dict) -> int:
    """What one slot holds over all Gated DeltaNet layers: the recurrent
    state (float32) and the convolution's last inputs."""
    lk, lv, Hv = _lin(cfg)
    one = (STATE_BYTES * Hv * cfg["linear_key_head_dim"]
           * cfg["linear_value_head_dim"]
           + CACHE_BYTES * (cfg["linear_conv_kernel_dim"] - 1)
           * (2 * lk + lv))
    return _kinds(cfg)[1] * one


def kv_row_bytes(cfg: dict) -> int:
    """K and V of one position in one attention layer."""
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * CACHE_BYTES


def _held_share(cfg: dict) -> float:
    return cfg["num_experts"] / _routed(cfg)


def _token_flops(cfg: dict, pairs_per_token: float) -> float:
    """One token outside attention's scores: the matrix products with
    everything outside the routed experts and with ``pairs_per_token``
    routed experts, and the recurrence's products with the state (three
    multiply-adds an element: S^T k, the update, S^T q)."""
    outside, one = _counts(cfg)
    _, lv, _ = _lin(cfg)
    state = 3 * _kinds(cfg)[1] * lv * cfg["linear_key_head_dim"]
    return 2 * (outside + pairs_per_token * one + state)


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward plus backward (three forwards) of one token of a
    ``seq_len`` sequence, under even routing."""
    full, _ = _kinds(cfg)
    width = cfg["num_attention_heads"] * cfg["head_dim"]
    pairs = (cfg["num_experts_per_tok"] * _held_share(cfg)
             * cfg["num_hidden_layers"])
    return 3 * (_token_flops(cfg, pairs)
                + 2 * 2 * width * full * (seq_len + 1) / 2)


def decode_step_flops(cfg: dict, live_rows: int, slots: int, *,
                      pairs_held: float | None = None) -> float:
    """One decode step over ``slots`` single-token queries that read
    ``live_rows`` rows in each attention layer, ``pairs_held`` (token,
    expert) pairs landing on held experts over all layers (even routing
    where not given)."""
    full, _ = _kinds(cfg)
    width = cfg["num_attention_heads"] * cfg["head_dim"]
    if pairs_held is None:
        pairs_held = (slots * cfg["num_experts_per_tok"] * _held_share(cfg)
                      * cfg["num_hidden_layers"])
    return (slots * _token_flops(cfg, 0.0) + 2 * pairs_held * _counts(cfg)[1]
            + 2 * 2 * width * full * live_rows)


def decode_step_bytes(cfg: dict, live_rows: int, *, slots: int,
                      experts_touched: float | None = None) -> float:
    """Bytes one decode step over ``slots`` slots must move: every
    weight outside the routed experts once, ``experts_touched`` routed
    experts once (summed over the layers; every held expert where not
    given), every slot's state read and written once, and the K and V
    rows read: ``live_rows`` in each attention layer."""
    outside, one = _counts(cfg)
    if experts_touched is None:
        experts_touched = cfg["num_experts"] * cfg["num_hidden_layers"]
    return (PARAM_BYTES * (outside + experts_touched * one)
            + 2 * slots * state_bytes_per_slot(cfg)
            + kv_row_bytes(cfg) * _kinds(cfg)[0] * live_rows)


def delta_step_bytes(cfg: dict, slots: int) -> int:
    """Bytes ONE call of the token step's recurrence kernel
    (``gated_delta_step``, one a Gated DeltaNet layer) must move: every
    slot's recurrent state read and written once, and per head the tile
    of vectors it is handed and the output row it gives."""
    _, lv, Hv = _lin(cfg)
    state = STATE_BYTES * lv * cfg["linear_key_head_dim"]
    return slots * (2 * state + 4 * Hv * (8 * 128 + cfg[
        "linear_value_head_dim"]))


def delta_step_flops(cfg: dict, slots: int) -> int:
    """Its operations: three multiply-adds an element of the state."""
    _, lv, _ = _lin(cfg)
    return 2 * 3 * slots * lv * cfg["linear_key_head_dim"]

