"""The five served architectures at tiny widths, for the tests: each
family's configuration, model, seeded parameters and token sequences, and
the helpers the family files (``test_afmoe.py`` ... ``test_kimi_k2.py``)
and the seam's own file (``test_served_lm.py``) share.  float32 compute on
the CPU, so that a comparison is of the mathematics."""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from distributedtensorflowexample_tpu.models import build_model_from_config
from distributedtensorflowexample_tpu.obs import metrics as obs_metrics
from distributedtensorflowexample_tpu.serving.engine import DecodeEngine

YARN = dict(type="yarn", factor=8, original_max_position_embeddings=64,
            beta_fast=4, beta_slow=1, mscale=1, mscale_all_dim=1)
TINY = {
    # A window of 8 positions: every context wraps the rings several times.
    "afmoe": dict(
        model_type="afmoe", vocab_size=97, hidden_size=32,
        num_attention_heads=4, num_key_value_heads=2, head_dim=8,
        intermediate_size=64, moe_intermediate_size=16, num_hidden_layers=4,
        num_dense_layers=1,
        layer_types=["sliding_attention", "sliding_attention",
                     "full_attention", "sliding_attention"],
        num_experts=4, num_experts_per_tok=2, num_shared_experts=1,
        route_scale=2.448, route_norm=True, sliding_window=8,
        rope_theta=10000, rms_norm_eps=1e-5, max_position_embeddings=128,
        mup_enabled=True, published={"num_experts": 16},
        deployment={"rank": 1}),
    # Two whole periods of (linear, linear, linear, full).
    "qwen3_next": dict(
        model_type="qwen3_next", vocab_size=97, hidden_size=32,
        num_hidden_layers=8, full_attention_interval=4,
        num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        partial_rotary_factor=0.25, linear_num_key_heads=2,
        linear_num_value_heads=4, linear_key_head_dim=8,
        linear_value_head_dim=8, linear_conv_kernel_dim=4,
        moe_intermediate_size=16, shared_expert_intermediate_size=16,
        num_experts=4, num_experts_per_tok=3, norm_topk_prob=True,
        rope_theta=10000000, rms_norm_eps=1e-6, max_position_embeddings=256,
        published={"num_experts": 16}, deployment={"rank": 1}),
    # A dense layer, then one whole period of five KDA layers and an MLA
    # layer.
    "bailing_hybrid": dict(
        model_type="bailing_hybrid", vocab_size=97, hidden_size=32,
        num_hidden_layers=7, layer_group_size=6, first_k_dense_replace=1,
        num_attention_heads=4, head_dim=8, short_conv_kernel_size=4,
        kda_lower_bound=-5, kv_lora_rank=16, qk_nope_head_dim=8,
        qk_rope_head_dim=4, v_head_dim=8, intermediate_size=48,
        moe_intermediate_size=16, moe_shared_expert_intermediate_size=16,
        num_shared_experts=1, num_experts=4, num_experts_per_tok=3,
        n_group=8, topk_group=4, norm_topk_prob=True,
        routed_scaling_factor=2.5, rope_theta=6000000, rms_norm_eps=1e-6,
        max_position_embeddings=256, published={"num_experts": 32},
        deployment={"rank": 1}),
    # One period in small: three Mamba-2 layers, attention, two more.
    "granitemoehybrid": dict(
        model_type="granitemoehybrid", vocab_size=97, hidden_size=32,
        num_hidden_layers=6,
        layer_types=["mamba", "mamba", "mamba", "attention", "mamba",
                     "mamba"],
        num_attention_heads=4, num_key_value_heads=2, mamba_n_heads=8,
        mamba_d_head=8, mamba_d_state=16, mamba_d_conv=4, mamba_expand=2,
        mamba_n_groups=1, mamba_conv_bias=True, mamba_proj_bias=False,
        intermediate_size=16, shared_intermediate_size=24,
        num_local_experts=3, num_experts_per_tok=4, embedding_multiplier=12,
        residual_multiplier=0.22, attention_multiplier=0.0625,
        logits_scaling=16, rms_norm_eps=1e-5, max_position_embeddings=512,
        position_embedding_type="nope", tie_word_embeddings=True,
        published={"num_local_experts": 24}, deployment={"rank": 1}),
    # A dense layer, then four expert layers.
    "kimi_k2": dict(
        model_type="kimi_k2", vocab_size=97, hidden_size=32,
        num_hidden_layers=5, first_k_dense_replace=1, num_attention_heads=4,
        q_lora_rank=12, kv_lora_rank=16, qk_nope_head_dim=8,
        qk_rope_head_dim=16, v_head_dim=8, intermediate_size=48,
        moe_intermediate_size=16, n_shared_experts=1, n_routed_experts=4,
        num_experts_per_tok=3, n_group=1, topk_group=1, norm_topk_prob=True,
        routed_scaling_factor=2.827, rope_theta=10000, rope_scaling=YARN,
        rms_norm_eps=1e-5, max_position_embeddings=512,
        scoring_func="sigmoid", topk_method="noaux_tc", hidden_act="silu",
        tie_word_embeddings=False, attention_bias=False,
        published={"n_routed_experts": 32}, deployment={"rank": 1}),
}
FAMILIES = tuple(TINY)
#: The tile of prefill attention a family's tests build with, where it is
#: not the model's own (one tile holds every test sequence, or few do).
ATTN_BLOCK = {"afmoe": 1024, "kimi_k2": 64}
#: Positions of a family's test sequences (four of them).
SEQUENCE = {"afmoe": 60, "qwen3_next": 200, "bailing_hybrid": 200,
            "granitemoehybrid": 300, "kimi_k2": 260}


def model(family: str, attn_block=None, **sizes):
    block = attn_block or ATTN_BLOCK.get(family)
    return build_model_from_config(
        {**TINY[family], **sizes}, dtype=jnp.float32,
        param_dtype=jnp.float32, **({"attn_block": block} if block else {}))


def _off_one(path, x, keys):
    """A norm's scale moved off its initial value, where a norm whose
    scale is dropped (or, in ``qwen3_next``, a plain RMSNorm for the
    zero-centred one) would pass."""
    return (x + 0.2 * jax.random.normal(next(keys), x.shape)
            if path[-1].key.startswith("norm_") else x)


def _granite(path, x, keys):
    """The norms' scales and the skip moved off one, steps twenty times
    Mamba-2's own, a step's projection that matters at 32 features and an
    input projection ten times as large (B and C of ~0.4, as 4,096
    features give them): what is read of the state is then as large as
    the skip, and a step's decay runs from ~0.98 down to nothing."""
    name = path[-1].key
    if name.startswith("norm_") or name in ("d_skip", "w_dt"):
        return x + 0.2 * jax.random.normal(next(keys), x.shape)
    if name == "w_in":
        return 10.0 * x
    return x + 3.0 if name == "dt_bias" else x


_MOVED = {"afmoe": None, "granitemoehybrid": _granite}


def seeded(family: str, built=None):
    """Parameters of ``built`` (the family's tiny model), seeded, and
    moved as the family's comparisons need."""
    built = model(family) if built is None else built
    p = built.init(jax.random.PRNGKey(3),
                   jnp.zeros((1, 8), jnp.int32))["params"]
    moved = _MOVED.get(family, _off_one)
    if moved is None:
        return p
    keys = iter(jax.random.split(jax.random.PRNGKey(4), 64))
    return jax.tree_util.tree_map_with_path(
        lambda path, x: moved(path, x, keys), p)


params = functools.lru_cache(maxsize=None)(seeded)


@functools.lru_cache(maxsize=None)
def sequences(family: str):
    return np.random.default_rng(5).integers(
        0, TINY[family]["vocab_size"], (4, SEQUENCE[family])
    ).astype(np.int32)


def counter(series: str) -> float:
    got = obs_metrics.registry().snapshot()["counters"].get(series)
    return (got["value"] if isinstance(got, dict) else got) or 0


def state_leaves(engine, slot):
    """What the ``state`` layers remember of ``slot``."""
    rows = engine.smodel.cache_rows(engine.cache_len)
    return [np.asarray(c[i][slot]) for i, (kind, _) in enumerate(rows)
            if kind == "state" for c in (engine._ck, engine._cv)]


def serve_alone(family, prompt, steps, slot, cache_len, slots=3):
    """The logits of ``steps`` token steps after ``prompt``, served alone
    in ``slot`` of a fresh engine."""
    engine = DecodeEngine(model(family), params(family), slots=slots,
                          cache_len=cache_len)
    engine.prefill_many([(slot, prompt, 1)])
    return np.stack([engine.decode_logits(busy=[slot])[slot]
                     for _ in range(steps)])
