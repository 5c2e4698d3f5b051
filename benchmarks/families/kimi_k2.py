"""The ``kimi_k2`` family (Moonshot's Kimi K2 models: DeepSeek-V3's block
at Kimi's sizes): everything the harness knows of the architecture —
latent attention in EVERY layer with a compressed query and YaRN
positions, a leading dense layer, a sigmoid router over routed experts
beside a shared one, an untied head — the program's model, the seeded
weights, and the work a step REQUIRES.

A configuration of this family may be ONE CHIP'S SHARE of an
expert-parallel deployment, stated as ``families/afmoe.py``'s are:
``n_routed_experts`` experts are held of the
``published.n_routed_experts`` the router scores, and the vocabulary is a
slice.  Every count below is of the share.

The weights are bfloat16 (``assumed.compute_dtype``); the router's bias
is float32, seeded at ``assumed.router_bias_range`` (0.01 where a
configuration states none, as ``families/afmoe.py`` seeds it: the toy
configurations of the tests, whose limits were read at it).  At 7168
features the seeded scores of the experts a token picks lie near 0.97,
where the sigmoid is flat: a bias of 0.01 there is a fifth of the logits'
spread and moves an expert's share of the pairs by half, so which of the
HELD experts a seed's bias favours would set how many a step touches —
the published bias is what evens that load, and a configuration seeds it
small enough to leave the load even.  They are laid out under the
parameter names of ``models/kimi_k2.py`` (a test holds the two trees to
each other; :data:`SOURCE_NAMES` says which tensor of the published
checkpoint each is).  The norms' scales are seeded off one, so that a
dropped scale shows.

The work counts are matrix multiplications (2 per multiply-add); a
decode step's least bytes are every weight outside the routed experts
once, each routed expert that a pair TOUCHED once, and the live latent
rows once in every layer, at the 1,152 bytes a position needs (the
program pads a row to 1,280).  They are counted from the equations and
read nothing of how the program computes them.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

PARAM_BYTES = 2         # bfloat16
CACHE_BYTES = 2         # latent rows: bfloat16
_SIZE_KEYS = ("vocab_size", "hidden_size", "num_hidden_layers",
              "num_attention_heads", "q_lora_rank", "kv_lora_rank",
              "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
              "intermediate_size", "moe_intermediate_size",
              "n_shared_experts", "first_k_dense_replace",
              "n_routed_experts")

#: Each parameter of ``param_shapes`` and the published checkpoint's
#: tensor it stands for (``model.layers.<i>.`` before a layer's).
SOURCE_NAMES = {
    "embed": "model.embed_tokens.weight", "norm_f": "model.norm.weight",
    "head": "lm_head.weight",
    "norm_in": "input_layernorm.weight",
    "norm_post": "post_attention_layernorm.weight",
    "wq_a": "self_attn.q_a_proj.weight",
    "norm_q": "self_attn.q_a_layernorm.weight",
    "wq_b": "self_attn.q_b_proj.weight",
    "w_kva": "self_attn.kv_a_proj_with_mqa.weight",
    "norm_c": "self_attn.kv_a_layernorm.weight",
    "w_uk": "self_attn.kv_b_proj.weight, each head's first "
            "qk_nope_head_dim rows (W_UK), stored [head, rank, feature]",
    "w_uv": "self_attn.kv_b_proj.weight, each head's last v_head_dim "
            "rows (W_UV), stored [head, rank, feature]",
    "wo": "self_attn.o_proj.weight",
    "ffn_gate": "mlp.gate_proj.weight", "ffn_up": "mlp.up_proj.weight",
    "ffn_down": "mlp.down_proj.weight",
    "router": "mlp.gate.weight",
    "router_bias": "mlp.gate.e_score_correction_bias",
    "experts_gate": "mlp.experts.<e>.gate_proj.weight",
    "experts_up": "mlp.experts.<e>.up_proj.weight",
    "experts_down": "mlp.experts.<e>.down_proj.weight",
    "shared_gate": "mlp.shared_experts.gate_proj.weight",
    "shared_up": "mlp.shared_experts.up_proj.weight",
    "shared_down": "mlp.shared_experts.down_proj.weight",
}


def build_model(cfg: dict, **kwargs):
    """``KimiK2LM`` through the constructor ``serving/promote.py`` and
    ``tools/serve_lm.py --model_config`` use."""
    from distributedtensorflowexample_tpu.models import build_model_from_config
    return build_model_from_config(cfg, **kwargs)


def _routed(cfg: dict) -> int:
    return cfg.get("published", {}).get("n_routed_experts",
                                        cfg["n_routed_experts"])


def kinds(cfg: dict) -> tuple:
    """(latent-attention layers, expert layers): every layer is latent."""
    n = cfg["num_hidden_layers"]
    return n, n - cfg["first_k_dense_replace"]


def param_shapes(cfg: dict) -> dict:
    """Leaf shapes of a configuration, as a tree of tuples."""
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    rank, Dn = cfg["kv_lora_rank"], cfg["qk_nope_head_dim"]
    Dr, Dv = cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    q_rank = cfg.get("q_lora_rank")
    query = ({"wq_a": (d, q_rank), "norm_q": (q_rank,),
              "wq_b": (q_rank, H * (Dn + Dr))} if q_rank
             else {"wq": (d, H * (Dn + Dr))})
    mla = {**query, "w_kva": (d, rank + Dr), "norm_c": (rank,),
           "w_uk": (H, rank, Dn), "w_uv": (H, rank, Dv), "wo": (H * Dv, d)}
    ff = cfg["intermediate_size"]
    dense = {"ffn_gate": (d, ff), "ffn_up": (d, ff), "ffn_down": (ff, d)}
    f, E = cfg["moe_intermediate_size"], cfg["n_routed_experts"]
    fs = f * cfg["n_shared_experts"]
    experts = {"router": (d, _routed(cfg)), "router_bias": (_routed(cfg),),
               "shared_gate": (d, fs), "shared_up": (d, fs),
               "shared_down": (fs, d), "experts_gate": (E, d, f),
               "experts_up": (E, d, f), "experts_down": (E, f, d)}
    tree = {"embed": (cfg["vocab_size"], d), "norm_f": (d,),
            "head": (d, cfg["vocab_size"])}
    for i in range(cfg["num_hidden_layers"]):
        tree[f"block{i}"] = {
            "norm_in": (d,), "norm_post": (d,), **mla,
            **(dense if i < cfg["first_k_dense_replace"] else experts)}
    return tree


def _leaves(cfg: dict) -> list:
    return jax.tree_util.tree_flatten_with_path(
        param_shapes(cfg), is_leaf=lambda x: isinstance(x, tuple))[0]


def _counts(cfg: dict) -> tuple:
    """(parameters a token's step reads outside the routed experts — the
    embedding is a gather of a few rows, not among them —, parameters of
    one routed expert)."""
    outside = sum(math.prod(s) for path, s in _leaves(cfg)
                  if not path[-1].key.startswith("experts_")
                  and path[-1].key != "embed")
    return outside, 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def param_count(cfg: dict) -> int:
    outside, one = _counts(cfg)
    return (outside + cfg["vocab_size"] * cfg["hidden_size"]
            + one * cfg["n_routed_experts"] * kinds(cfg)[1])


def weight_bytes(cfg: dict) -> int:
    """Bytes of the weights as the program stores and serves them (the
    router's float32 bias is counted as two bytes an entry: 3 kB in
    all)."""
    return PARAM_BYTES * param_count(cfg)


def _init(cfg_items: tuple, routed: int, seed):
    cfg = dict(cfg_items)
    cfg["published"] = {"n_routed_experts": routed}
    key = jax.random.PRNGKey(seed)
    out: dict = {}
    for i, (path, shape) in enumerate(_leaves(cfg)):
        names = [p.key for p in path]
        name, k = names[-1], jax.random.fold_in(key, i)
        normal = lambda: jax.random.normal(k, shape, jnp.float32)
        if name == "router_bias":
            leaf = cfg["router_bias_range"] * normal()
        elif name.startswith("norm_"):          # plain scales
            leaf = (1.0 + 0.1 * normal()).astype(jnp.bfloat16)
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
    static = tuple(sorted((k, cfg.get(k)) for k in _SIZE_KEYS)) + (
        ("initializer_range", cfg["assumed"]["initializer_range"]),
        ("router_bias_range", cfg["assumed"].get("router_bias_range", 0.01)))
    return functools.partial(_init, static, _routed(cfg))


def init_params(cfg: dict, seed: int, sharding=None):
    """The parameter tree of ``cfg`` from ``seed``, on the device, by
    ONE jitted call."""
    return jax.jit(init_fn(cfg), out_shardings=sharding)(jnp.uint32(seed))


# ---- the work a step requires ---------------------------------------------

def latent_row_bytes(cfg: dict) -> int:
    """The compressed row of one position in one layer, as the
    mathematics needs it (the program pads it to whole lane groups)."""
    return (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) * CACHE_BYTES


def _held_share(cfg: dict) -> float:
    return cfg["n_routed_experts"] / _routed(cfg)


def _latent_row_flops(cfg: dict) -> int:
    """One query's work on one cached row in one layer, absorbed: every
    head's score over the row and its weighted sum of the latent part."""
    return 2 * cfg["num_attention_heads"] * (
        2 * cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward plus backward (three forwards) of one token of a
    ``seq_len`` sequence, under even routing, attention expanded."""
    H = cfg["num_attention_heads"]
    width = H * (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
                 + cfg["v_head_dim"])
    outside, one = _counts(cfg)
    pairs = cfg["num_experts_per_tok"] * _held_share(cfg) * kinds(cfg)[1]
    return 3 * (2 * (outside + pairs * one)
                + 2 * width * kinds(cfg)[0] * (seq_len + 1) / 2)


def decode_step_flops(cfg: dict, live_rows: int, slots: int, *,
                      pairs_held: float | None = None) -> float:
    """One decode step over ``slots`` single-token queries that read
    ``live_rows`` rows in EACH layer, ``pairs_held`` (token, expert)
    pairs landing on held experts over all layers (even routing where
    not given)."""
    outside, one = _counts(cfg)
    if pairs_held is None:
        pairs_held = (slots * cfg["num_experts_per_tok"] * _held_share(cfg)
                      * kinds(cfg)[1])
    return (2 * slots * outside + 2 * pairs_held * one
            + _latent_row_flops(cfg) * kinds(cfg)[0] * live_rows)


def decode_step_bytes(cfg: dict, live_rows: int, *,
                      experts_touched: float | None = None) -> float:
    """Bytes one decode step must move: every weight outside the routed
    experts once, ``experts_touched`` routed experts once (summed over
    the layers; every held expert where not given), and the latent rows
    read: ``live_rows`` in EACH layer (no state rides beside the rows, so
    the slots do not enter)."""
    outside, one = _counts(cfg)
    if experts_touched is None:
        experts_touched = cfg["n_routed_experts"] * kinds(cfg)[1]
    return (PARAM_BYTES * (outside + experts_touched * one)
            + kinds(cfg)[0] * latent_decode_bytes(cfg, live_rows))


def latent_decode_bytes(cfg: dict, live_rows: int) -> int:
    """Bytes ONE call of the token step's latent attention kernel
    (``latent_decode_attention``, one a layer) must move: the
    ``live_rows`` rows its queries see, once."""
    return latent_row_bytes(cfg) * live_rows


def latent_decode_flops(cfg: dict, live_rows: int) -> int:
    return _latent_row_flops(cfg) * live_rows
