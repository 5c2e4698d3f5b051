"""The ``granitemoehybrid`` family (IBM's Granite 4.0-H): everything the
harness knows of the architecture — Mamba-2 state-space layers and
grouped-query attention without position encoding as ``layer_types``
names them, a softmax router over routed experts beside a shared MLP in
every layer, a tied head, four multipliers — the program's model, the
seeded weights, and the work a step REQUIRES.

A configuration of this family may be ONE CHIP'S SHARE of an
expert-parallel deployment, stated as ``families/afmoe.py``'s are:
``num_local_experts`` experts are held of the
``published.num_local_experts`` the router scores, and the vocabulary is
a slice.  Every count below is of the share.

The weights are bfloat16 (``assumed.compute_dtype``); the recurrence's
three vectors (``a_log``, ``dt_bias``, ``d_skip``) are float32.  They are
laid out under the parameter names of ``models/granitemoehybrid.py`` (a
test holds the two trees to each other; :data:`SOURCE_NAMES` says which
tensor of the published checkpoint each is).  They are seeded as Mamba-2
initialises its own (``assumed.weights``), so that a step's decay runs
from ~0.2 to ~0.999 head by head and the state matters.

The work counts are matrix multiplications (2 per multiply-add) and the
recurrence's elementwise products with the state; a decode step's least
bytes are every weight outside the routed experts once (the tied
embedding once, as the head), each routed expert that a pair TOUCHED
once, the recurrent and convolution state of EVERY slot read and written
once (the program has one shape: a parked slot's is moved too), and the
live K/V rows once.  They are counted from the equations and read
nothing of how the program computes them.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

PARAM_BYTES = 2         # bfloat16
CACHE_BYTES = 2         # K/V rows and the convolution's state: bfloat16
STATE_BYTES = 4         # the recurrent state: float32
#: The tied embedding is seeded this many times smaller than the other
#: matrices.  With random weights and a tied head, position t's own token
#: has the logit ``12 |E_tok|^2 / rms(h)`` above the others' noise, ``12
#: std sqrt(d) / rms(h)`` noise deviations: ~12 at one std for all (the
#: largest of 12,544 noise logits is ~4), so every step would serve its
#: own input token whatever the arithmetic did and the comparison that
#: decides ``correct`` would read 0 under any control; at a quarter it is
#: ~3 and greedy decoding still falls into repeating one token (my CPU
#: runs, PERF.md section 6, PR 41).  At a sixteenth it is under one
#: deviation: what is served depends on every layer.
EMBED_SHRINK = 16
_SIZE_KEYS = ("vocab_size", "hidden_size", "num_hidden_layers",
              "num_attention_heads", "num_key_value_heads", "mamba_n_heads",
              "mamba_d_head", "mamba_d_state", "mamba_d_conv",
              "mamba_conv_bias", "intermediate_size",
              "shared_intermediate_size", "num_local_experts")

#: Each parameter of ``param_shapes`` and the published checkpoint's
#: tensor it stands for (``model.layers.<i>.`` before a layer's).
SOURCE_NAMES = {
    "embed": "model.embed_tokens.weight (tied: lm_head too)",
    "norm_f": "model.norm.weight",
    "norm_in": "input_layernorm.weight",
    "norm_post": "post_attention_layernorm.weight",
    "w_in": "mamba.in_proj.weight, columns [gate | hidden_states_B_C]",
    "w_dt": "mamba.in_proj.weight, its last mamba_n_heads columns (dt)",
    "conv": "mamba.conv1d.weight", "conv_bias": "mamba.conv1d.bias",
    "a_log": "mamba.A_log", "dt_bias": "mamba.dt_bias", "d_skip": "mamba.D",
    "norm_y": "mamba.norm.weight", "w_out": "mamba.out_proj.weight",
    "wq": "self_attn.q_proj.weight", "wk": "self_attn.k_proj.weight",
    "wv": "self_attn.v_proj.weight", "wo": "self_attn.o_proj.weight",
    "router": "block_sparse_moe.router.layer.weight",
    "experts_gate": "block_sparse_moe.input_linear.weight, first half",
    "experts_up": "block_sparse_moe.input_linear.weight, second half",
    "experts_down": "block_sparse_moe.output_linear.weight",
    "shared_gate": "shared_mlp.input_linear.weight, first half",
    "shared_up": "shared_mlp.input_linear.weight, second half",
    "shared_down": "shared_mlp.output_linear.weight",
}


def build_model(cfg: dict, **kwargs):
    """``GraniteMoeHybridLM`` through the constructor ``serving/promote.py``
    and ``tools/serve_lm.py --model_config`` use."""
    from distributedtensorflowexample_tpu.models import build_model_from_config
    return build_model_from_config(cfg, **kwargs)


def _routed(cfg: dict) -> int:
    return cfg.get("published", {}).get("num_local_experts",
                                        cfg["num_local_experts"])


def kinds(cfg: dict) -> tuple:
    """(attention layers, state-space layers, expert layers)."""
    n = cfg["num_hidden_layers"]
    full = sum(kind == "attention" for kind in cfg["layer_types"][:n])
    return full, n - full, n if _routed(cfg) else 0


def param_shapes(cfg: dict) -> dict:
    """Leaf shapes of a configuration, as a tree of tuples."""
    d = cfg["hidden_size"]
    Dh = d // cfg["num_attention_heads"]
    kd = cfg["num_key_value_heads"] * Dh
    attn = {"wq": (d, d), "wk": (d, kd), "wv": (d, kd), "wo": (d, d)}
    H, N = cfg["mamba_n_heads"], cfg["mamba_d_state"]
    di = H * cfg["mamba_d_head"]
    conv = di + 2 * N
    ssm = {"w_in": (d, di + conv), "w_dt": (d, H),
           "conv": (cfg["mamba_d_conv"], conv), "a_log": (H,),
           "dt_bias": (H,), "d_skip": (H,), "norm_y": (di,),
           "w_out": (di, d)}
    if cfg["mamba_conv_bias"]:
        ssm["conv_bias"] = (conv,)
    f, E, fs = (cfg["intermediate_size"], cfg["num_local_experts"],
                cfg["shared_intermediate_size"])
    ffn = {"shared_gate": (d, fs), "shared_up": (d, fs),
           "shared_down": (fs, d)}
    if _routed(cfg):
        ffn.update({"router": (d, _routed(cfg)), "experts_gate": (E, d, f),
                    "experts_up": (E, d, f), "experts_down": (E, f, d)})
    tree = {"embed": (cfg["vocab_size"], d), "norm_f": (d,)}
    for i in range(cfg["num_hidden_layers"]):
        tree[f"block{i}"] = {
            "norm_in": (d,), "norm_post": (d,),
            **(attn if cfg["layer_types"][i] == "attention" else ssm), **ffn}
    return tree


def _counts(cfg: dict) -> tuple:
    """(parameters a token's step reads outside the routed experts — the
    tied embedding once, as the head: the gather at the input is a few
    rows —, parameters of one routed expert)."""
    leaves = jax.tree_util.tree_flatten_with_path(
        param_shapes(cfg), is_leaf=lambda x: isinstance(x, tuple))[0]
    outside = sum(math.prod(s) for path, s in leaves
                  if not path[-1].key.startswith("experts_"))
    return outside, 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def param_count(cfg: dict) -> int:
    outside, one = _counts(cfg)
    return outside + one * cfg["num_local_experts"] * kinds(cfg)[2]


def weight_bytes(cfg: dict) -> int:
    """Bytes of the weights as the program stores and serves them (the
    few float32 vectors a layer keeps are counted as two bytes each:
    under 0.01 MB in all)."""
    return PARAM_BYTES * param_count(cfg)


def _init(cfg_items: tuple, routed: int, std: float, seed):
    cfg = dict(cfg_items)
    cfg["published"] = {"num_local_experts": routed}
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
        if name == "a_log":             # A in [1, 16]
            leaf = jnp.log(uniform(1.0, 16.0))
        elif name == "dt_bias":         # softplus(dt_bias) log-uniform
            step = jnp.exp(uniform(math.log(1e-3), math.log(1e-1)))
            leaf = step + jnp.log(-jnp.expm1(-step))
        elif name == "d_skip":
            leaf = jnp.ones(shape, jnp.float32)
        elif name.startswith("norm_"):
            leaf = (1.0 + 0.1 * normal()).astype(jnp.bfloat16)
        elif name == "conv":
            leaf = (0.3 * normal()).astype(jnp.bfloat16)
        elif name == "embed":           # tied: see EMBED_SHRINK
            leaf = (std / EMBED_SHRINK * normal()).astype(jnp.bfloat16)
        else:
            leaf = (std * normal()).astype(jnp.bfloat16)
        node = out
        for n in names[:-1]:
            node = node.setdefault(n, {})
        node[name] = leaf
    return out


def init_fn(cfg: dict):
    """``seed -> parameter tree``, not yet jitted; the seed is an
    ARGUMENT of whatever program calls this, never a constant in it."""
    static = tuple(sorted((k, cfg[k]) for k in _SIZE_KEYS)) + (
        ("layer_types", tuple(cfg["layer_types"])),)
    return functools.partial(_init, static, _routed(cfg),
                             cfg["assumed"]["initializer_range"])


def init_params(cfg: dict, seed: int, sharding=None):
    """The parameter tree of ``cfg`` from ``seed``, on the device, by
    ONE jitted call."""
    return jax.jit(init_fn(cfg), out_shardings=sharding)(jnp.uint32(seed))


# ---- the work a step requires ---------------------------------------------

def _state_elements(cfg: dict) -> int:
    """One state-space layer's recurrent state of one slot, in numbers."""
    return cfg["mamba_n_heads"] * cfg["mamba_d_head"] * cfg["mamba_d_state"]


def _conv_width(cfg: dict) -> int:
    return (cfg["mamba_n_heads"] * cfg["mamba_d_head"]
            + 2 * cfg["mamba_d_state"])


def state_bytes_per_slot(cfg: dict) -> int:
    """What one slot holds over all state-space layers: the recurrent
    state (float32) and the convolution's last inputs."""
    one = (STATE_BYTES * _state_elements(cfg)
           + CACHE_BYTES * (cfg["mamba_d_conv"] - 1) * _conv_width(cfg))
    return kinds(cfg)[1] * one


def kv_row_bytes(cfg: dict) -> int:
    """The K and V rows of one position in one attention layer."""
    head_dim = cfg["hidden_size"] // cfg["num_attention_heads"]
    return 2 * cfg["num_key_value_heads"] * head_dim * CACHE_BYTES


def _held_share(cfg: dict) -> float:
    return cfg["num_local_experts"] / max(1, _routed(cfg))


def _token_flops(cfg: dict, pairs_per_token: float) -> float:
    """One token outside attention's scores: the matrix products with
    everything outside the routed experts and with ``pairs_per_token``
    routed experts, and the recurrence's products with the state (three
    multiply-adds an element: the decay, the writing, the reading)."""
    outside, one = _counts(cfg)
    state = 3 * kinds(cfg)[1] * _state_elements(cfg)
    return 2 * (outside + pairs_per_token * one + state)


def _row_flops(cfg: dict) -> int:
    """One query's work on one cached position in one attention layer:
    every head's score and its weighted sum."""
    return 2 * 2 * cfg["hidden_size"]


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward plus backward (three forwards) of one token of a
    ``seq_len`` sequence, under even routing."""
    pairs = cfg["num_experts_per_tok"] * _held_share(cfg) * kinds(cfg)[2]
    return 3 * (_token_flops(cfg, pairs)
                + _row_flops(cfg) * kinds(cfg)[0] * (seq_len + 1) / 2)


def decode_step_flops(cfg: dict, live_rows: int, slots: int, *,
                      pairs_held: float | None = None) -> float:
    """One decode step over ``slots`` single-token queries that read
    ``live_rows`` rows in each attention layer, ``pairs_held`` (token,
    expert) pairs landing on held experts over all layers (even routing
    where not given)."""
    if pairs_held is None:
        pairs_held = (slots * cfg["num_experts_per_tok"] * _held_share(cfg)
                      * kinds(cfg)[2])
    return (slots * _token_flops(cfg, 0.0) + 2 * pairs_held * _counts(cfg)[1]
            + _row_flops(cfg) * kinds(cfg)[0] * live_rows)


def decode_step_bytes(cfg: dict, live_rows: int, *, slots: int,
                      experts_touched: float | None = None) -> float:
    """Bytes one decode step over ``slots`` slots must move: every
    weight outside the routed experts once, ``experts_touched`` routed
    experts once (summed over the layers; every held expert where not
    given), every slot's state read and written once, and the K/V rows
    read: ``live_rows`` in each attention layer."""
    outside, one = _counts(cfg)
    if experts_touched is None:
        experts_touched = cfg["num_local_experts"] * kinds(cfg)[2]
    return (PARAM_BYTES * (outside + experts_touched * one)
            + 2 * slots * state_bytes_per_slot(cfg)
            + kv_row_bytes(cfg) * kinds(cfg)[0] * live_rows)
