"""The ``kimi_k2`` decoder (Moonshot's Kimi K2 family; DeepSeek-V3's
block at Kimi's sizes): the sixth LM block, beside ``transformer_lm.py``'s
GPT-2 one, ``afmoe.py``'s, ``qwen3_next.py``'s, ``bailing_hybrid.py``'s and
``granitemoehybrid.py``'s.  EVERY layer mixes tokens by latent attention
(MLA: keys and values of every head expanded from ONE compressed row a
position) whose query is compressed too and whose positions are scaled
(YaRN); the leading layers' feed-forward is dense, the others' a
sigmoid-routed expert layer beside a shared expert.

With d the hidden size, ``RMS(x; g) = x / sqrt(mean(x^2) + eps) * g``, no
bias anywhere::

    layer i:  h = h + mla(RMS(h; g_in));  h = h + ffn_i(RMS(h; g_post))
              ffn_i = dense for i < first_k_dense_replace, else moe
    head:     logits = RMS(h; g_f) W_head           (float32, untied)

    mla(a):   c_q = RMS(a W_qa; g_q);  q = c_q W_qb     the compressed
                                    query (q = a W_q where q_lora_rank is
                                    null); per head [q_nope (Dn) | q_pe (Dr)]
              [c_kv (rank) | k_pe (Dr)] = a W_kva
              c = RMS(c_kv; g_c);  q_pe, k_pe = rope(q_pe), rope(k_pe)
                                    interleaved pairs; ONE k_pe a
                                    position, shared by the heads
              the cache row of a position: [c | k_pe]
              expanded (a sequence):
                  k_nope_h = c W_UK,h;  v_h = c W_UV,h
                  p = softmax(scale q_h . [k_nope_h | k_pe])
                  o_h = sum p v_h                   causal, float32
              absorbed (one token against the rows), the same numbers:
                  qc_h = W_UK,h q_nope,h
                  p = softmax(scale (qc_h . c_s + q_pe,h . k_pe,s))
                  o_h = W_UV,h^T sum p c_s
              return [o_1 .. o_H] W_o               no gate

    rope:     plain: inv_i = theta^(-2i / Dr), scale = (Dn + Dr)^-0.5.
              YaRN (rope_scaling.type "yarn"; :func:`rotary_frequencies`):
              the slow frequencies divided by ``factor``, the fast ones
              kept, a linear ramp between the two corrections;
              cos and sin times m(mscale) / m(mscale_all_dim) and the
              scale times m(mscale_all_dim)^2, m(x) = 0.1 x ln factor + 1

    moe(m):   s = sigmoid(m Wr) float32;  sel = top-k of s + bias
              w = s_sel / sum(s_sel) * routed_scaling_factor
              return sum_e w_e ffn_e(m) + ffn_shared(m)
              ffn(m; G, U, D) = (silu(m G) * (m U)) D

The published ``kv_b_proj`` is ``[W_UK | W_UV]`` a head; it is stored
here as its two halves, head first (``w_uk [H, rank, Dn]``, ``w_uv [H,
rank, Dv]``): the token step's two absorbed products batch over heads,
and in this layout neither form of the attention slices or relays a
matrix inside a program (compiled for a v5e, a ``[rank, H, D]`` matrix
was copied head-first in every layer of every step).

**One definition of a block** (:class:`KimiBlock`) with the two methods
the shell of ``served_lm.py`` walks, ``sequence`` and ``step``;
:class:`KimiK2LM` is that shell and states each layer's cache: every layer
holds ``cache_len`` rows a slot, ONE row a position (kind ``latent``: ``[c
| k_pe]`` padded with zeros to whole lane groups, 576 -> 640 at the
published sizes, as ``bailing_hybrid.py`` pads its own; there is no V
array and no other kind).  A parked slot (position 0) goes to no expert.

**The expert layer holds a share** (``ops/moe.py``): ``experts_held`` of
``n_routed`` from ``first_expert`` on.

Parameters are stored in ``param_dtype`` (bfloat16 in serving), as are
activations and latent rows; norms, the router, softmax, the rotary
angles and logits are float32.  What of a configuration is not computed
here is refused by name (:func:`dims_from_config`); the family's vision
tower is not here (a text request passes through the language model
alone).
"""

from __future__ import annotations

import dataclasses
import math

import flax.linen as nn
import jax
import jax.numpy as jnp

from distributedtensorflowexample_tpu.models.served_lm import (
    CacheLayer, ServedLM, gated_params, rms_norm)
from distributedtensorflowexample_tpu.obs import metrics as obs_metrics
from distributedtensorflowexample_tpu.ops import moe
from distributedtensorflowexample_tpu.ops.attention import (
    latent_decode_attention, latent_expanded_attention, latent_fetch_block)

F32 = jnp.float32
LANES = 128     # a latent row is padded to whole groups of these
#: Positions a tile of this model's prefill attention holds, and the step
#: of its ladder of prompt lengths: with 64 heads a tile of 512 is as much
#: work as ``ops/attention.ATTN_BLOCK`` is with 32, and prompts of a few
#: thousand tokens then pad by a sixteenth at most.
ATTN_TILE = 512

_SCALING = obs_metrics.counter(
    "lm_position_scaling_total",
    "rotary tables traced (one per program of a model whose positions "
    "are rotary), by how the frequencies are scaled: yarn | none")


@dataclasses.dataclass(frozen=True)
class Yarn:
    """``rope_scaling`` of type ``yarn``, in the source's own terms."""
    factor: float
    original_max_len: int
    beta_fast: float
    beta_slow: float
    mscale: float
    mscale_all_dim: float

    def magnitude(self, mscale: float) -> float:
        """m(x) = 0.1 x ln factor + 1 (1 where nothing is stretched)."""
        if self.factor <= 1:
            return 1.0
        return 0.1 * mscale * math.log(self.factor) + 1.0


@dataclasses.dataclass(frozen=True)
class KimiDims:
    """Every size of the architecture (hashable: a flax field)."""
    vocab_size: int
    d_model: int
    n_layers: int
    n_heads: int
    q_rank: int                 # the compressed query; 0: q = a W_q
    kv_rank: int                # the compressed row ...
    rope_dim: int               # ... and the shared rotary key beside it
    nope_dim: int               # a head's features without position
    v_dim: int                  # a head's value features
    d_ff: int                   # the leading dense layers' width
    d_expert: int               # each routed expert's width
    d_shared: int               # the shared expert's
    n_dense_layers: int
    n_routed: int               # experts the router scores
    experts_held: int           # experts this share computes ...
    first_expert: int           # ... from this id on
    top_k: int
    n_group: int
    topk_group: int
    route_scale: float
    route_norm: bool
    rope_theta: float
    yarn: Yarn | None
    eps: float
    max_len: int
    init_std: float = 0.02

    @property
    def row_dim(self) -> int:
        """Features of a latent cache row: [c | k_pe], in whole lane
        groups."""
        return -(-(self.kv_rank + self.rope_dim) // LANES) * LANES

    @property
    def softmax_scale(self) -> float:
        scale = (self.nope_dim + self.rope_dim) ** -0.5
        if self.yarn is None:
            return scale
        return scale * self.yarn.magnitude(self.yarn.mscale_all_dim) ** 2


def rotary_frequencies(c: KimiDims) -> tuple:
    """``(inv_freq [rope_dim / 2] float32, what cos and sin are
    multiplied by)``.  Plain rope: ``theta^(-2i / Dr)`` and 1.  YaRN: a
    pair that turns more than ``beta_fast`` times within the original
    context keeps its frequency, one that turns less than ``beta_slow``
    times has it divided by ``factor``, and between the two corrections
    (``corr(r) = Dr ln(original / (2 pi r)) / (2 ln theta)``, floored and
    ceiled) a linear ramp blends them."""
    half = c.rope_dim // 2
    inv = c.rope_theta ** (-jnp.arange(half, dtype=F32) / half)
    y = c.yarn
    if y is None:
        return inv, 1.0
    corr = lambda turns: (c.rope_dim * math.log(
        y.original_max_len / (turns * 2 * math.pi))
        / (2 * math.log(c.rope_theta)))
    low = max(math.floor(corr(y.beta_fast)), 0)
    high = min(math.ceil(corr(y.beta_slow)), c.rope_dim - 1)
    ramp = jnp.clip((jnp.arange(half, dtype=F32) - low)
                    / (max(high - low, 0.001)), 0.0, 1.0)
    inv = inv * (1.0 - ramp) + inv / y.factor * ramp
    return inv, y.magnitude(y.mscale) / y.magnitude(y.mscale_all_dim)


def rotary(c: KimiDims, positions) -> tuple:
    """``positions [..., T]`` -> ``(cos, sin)``, each ``[..., T, 1, Dr /
    2]`` float32: ONE table a program, shared by its layers."""
    _SCALING.labels(kind="none" if c.yarn is None else "yarn").inc()
    with jax.named_scope("rope" if c.yarn is None else "rope.yarn"):
        inv, mult = rotary_frequencies(c)
        ang = positions[..., None].astype(F32) * inv
        return (jnp.cos(ang)[..., None, :] * mult,
                jnp.sin(ang)[..., None, :] * mult)


def _rope_pairs(x, rot):
    """``x [..., T, H, Dr]`` rotated by ``rot`` (:func:`rotary`):
    features ``(2i, 2i + 1)`` rotate as a pair."""
    cos, sin = rot
    xf = x.astype(F32).reshape(*x.shape[:-1], x.shape[-1] // 2, 2)
    x1, x2 = xf[..., 0], xf[..., 1]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape).astype(x.dtype)


class KimiBlock(nn.Module):
    """One layer: latent attention, then a dense or an expert
    feed-forward."""
    dims: KimiDims
    experts: bool
    dtype: jnp.dtype = jnp.bfloat16
    param_dtype: jnp.dtype = jnp.float32
    attn_block: int = ATTN_TILE

    def setup(self):
        c, pd = self.dims, self.param_dtype
        ones = nn.initializers.ones
        w = nn.initializers.normal(c.init_std)
        d, H = c.d_model, c.n_heads
        P = self.param
        self.norm_in = P("norm_in", ones, (d,), pd)
        self.norm_post = P("norm_post", ones, (d,), pd)
        q_out = H * (c.nope_dim + c.rope_dim)
        if c.q_rank:
            self.wq_a = P("wq_a", w, (d, c.q_rank), pd)
            self.norm_q = P("norm_q", ones, (c.q_rank,), pd)
            self.wq_b = P("wq_b", w, (c.q_rank, q_out), pd)
        else:
            self.wq = P("wq", w, (d, q_out), pd)
        self.w_kva = P("w_kva", w, (d, c.kv_rank + c.rope_dim), pd)
        self.norm_c = P("norm_c", ones, (c.kv_rank,), pd)
        self.w_uk = P("w_uk", w, (H, c.kv_rank, c.nope_dim), pd)
        self.w_uv = P("w_uv", w, (H, c.kv_rank, c.v_dim), pd)
        self.wo = P("wo", w, (H * c.v_dim, d), pd)
        if not self.experts:
            self.ffn = gated_params(P, "ffn", w, pd, d, c.d_ff)
            return
        f, E, fs = c.d_expert, c.experts_held, c.d_shared
        self.router = P("router", w, (d, c.n_routed), pd)
        self.router_bias = P("router_bias", nn.initializers.normal(0.01),
                             (c.n_routed,), F32)
        self.shared = gated_params(P, "shared", w, pd, d, fs)
        self.held = gated_params(P, "experts", w, pd, d, f, E)

    # --- latent attention --------------------------------------------------
    def _mla_q(self, a, rot):
        """a [..., T, d] -> q_nope [..., T, H, Dn], q_pe [..., T, H, Dr]
        (rotated)."""
        c, dt = self.dims, self.dtype
        if c.q_rank:
            with jax.named_scope("mla.q_down"):
                a = rms_norm(jnp.dot(a, self.wq_a.astype(dt)), self.norm_q,
                         c.eps)
        with jax.named_scope("mla.q_up"):
            q = jnp.dot(a, (self.wq_b if c.q_rank else self.wq).astype(dt))
            q = q.reshape(*a.shape[:-1], c.n_heads, c.nope_dim + c.rope_dim)
            return q[..., :c.nope_dim], _rope_pairs(q[..., c.nope_dim:], rot)

    def _mla_rows(self, a, rot):
        """a [..., T, d] -> the positions' cache rows [..., T, row_dim]:
        ``[RMS(c_kv) | rope(k_pe) | zeros]``."""
        c = self.dims
        with jax.named_scope("mla.kv"):
            kva = jnp.dot(a, self.w_kva.astype(self.dtype))
            lat = rms_norm(kva[..., :c.kv_rank], self.norm_c, c.eps)
            k_pe = _rope_pairs(kva[..., None, c.kv_rank:], rot)[..., 0, :]
            pad = jnp.zeros((*a.shape[:-1],
                             c.row_dim - c.kv_rank - c.rope_dim), lat.dtype)
            return jnp.concatenate([lat, k_pe, pad], axis=-1)

    def _mla_out(self, o):
        """o [..., H, Dv] -> [..., d]: the output projection."""
        with jax.named_scope("mla.out"):
            return jnp.dot(o.reshape(*o.shape[:-2], -1),
                           self.wo.astype(self.dtype))

    # --- feed-forward ------------------------------------------------------
    def _ffn(self, h, live):
        """h [..., d], live [...] or None -> (h', stats int32[4])."""
        c, dt = self.dims, self.dtype
        m = rms_norm(h, self.norm_post, c.eps).reshape(-1, c.d_model)
        cast = lambda ws: tuple(x.astype(dt) for x in ws)
        if not self.experts:
            f = moe.gated_ffn(m, *cast(self.ffn))
            stats = jnp.zeros((len(moe.STATS),), jnp.int32)
        else:
            sel, w = moe.route(m, self.router.astype(dt), self.router_bias,
                               top_k=c.top_k, route_scale=c.route_scale,
                               route_norm=c.route_norm, n_group=c.n_group,
                               topk_group=c.topk_group)
            f, stats = moe.expert_ffn(
                m, sel, w, *cast(self.held), first_expert=c.first_expert,
                experts_known=self.router.shape[1],
                live=None if live is None else live.reshape(-1))
            with jax.named_scope("moe.shared"):
                f = f + moe.gated_ffn(m, *cast(self.shared))
        return h + f.reshape(h.shape), stats

    # --- the two shapes of work --------------------------------------------
    def sequence(self, x, lengths, rot):
        """A whole sequence from position 0: x [B, T, d], lengths [B] the
        live length of each row (None: T), ``rot`` the rotary table of
        positions 0..T-1 -> (x', what the layer remembers — the positions'
        rows ``[B, T, row_dim]`` and nothing beside them: None —, stats)."""
        c = self.dims
        B, T, _ = x.shape
        live = None if lengths is None else (
            jnp.arange(T)[None] < lengths[:, None])
        a = rms_norm(x, self.norm_in, c.eps)
        q_nope, q_pe = self._mla_q(a, rot)
        rows = self._mla_rows(a, rot)
        with jax.named_scope("mla.attend"):
            lat = rows[..., :c.kv_rank]
            k_pe = rows[..., None, c.kv_rank:c.kv_rank + c.rope_dim]
            k_nope = jnp.einsum("btc,hcn->bthn", lat,
                                self.w_uk.astype(self.dtype))
            v = jnp.einsum("btc,hcv->bthv", lat, self.w_uv.astype(self.dtype))
            k = jnp.concatenate([k_nope, jnp.broadcast_to(
                k_pe, (B, T, c.n_heads, c.rope_dim))], axis=-1)
            o = latent_expanded_attention(
                jnp.concatenate([q_nope, q_pe], axis=-1), k, v,
                block=self.attn_block, scale=c.softmax_scale)
        x = x + self._mla_out(o)
        x, stats = self._ffn(x, live)
        return x, (rows, None), stats

    def step(self, x, ck, cv, pos, rot):
        """One token a slot: x [S, d], pos [S] its position (``rot`` the
        rotary table of ``pos[:, None]``) and the slot's rows ``ck [S, R,
        row_dim]`` (``cv`` is the empty array that rides beside them): the
        token's row is written at its position, then the query reads rows
        ``0..pos``, each once, never expanded.  A slot at ``pos == 0`` is
        parked: its token goes to no expert."""
        c = self.dims
        S = x.shape[0]
        live = pos > 0
        a = rms_norm(x, self.norm_in, c.eps)
        q_nope, q_pe = self._mla_q(a[:, None], rot)
        with jax.named_scope("cache_update"):
            ck = ck.at[jnp.arange(S), pos].set(
                self._mla_rows(a[:, None], rot)[:, 0])
        with jax.named_scope("mla.absorb"):
            q_lat = jnp.einsum("shn,hcn->shc", q_nope[:, 0],
                               self.w_uk.astype(self.dtype))
            q_row = jnp.concatenate([q_lat, q_pe[:, 0], jnp.zeros(
                (S, c.n_heads, c.row_dim - c.kv_rank - c.rope_dim),
                q_lat.dtype)], axis=-1)
        with jax.named_scope("mla.attend"):
            o = latent_decode_attention(
                q_row, ck, jnp.minimum(pos + 1, ck.shape[1]),
                v_dim=c.kv_rank, scale=c.softmax_scale)
        with jax.named_scope("mla.absorb"):
            o = jnp.einsum("shc,hcv->shv", o, self.w_uv.astype(self.dtype))
        x = x + self._mla_out(o)
        x, stats = self._ffn(x, live)
        return x, ck, cv, stats


class KimiK2LM(ServedLM):
    """The shell (``served_lm.py``) over :class:`KimiBlock`: latent rows in
    every layer, one rotary table a program, a ladder of whole tiles."""
    dims: KimiDims
    attn_block: int = ATTN_TILE

    #: Two prompts of 5,120.
    prefill_positions_max = 10240
    expert_slots = property(lambda self: self.dims.experts_held * (
        self.dims.n_layers - self.dims.n_dense_layers))

    def make_block(self, i):
        return KimiBlock(
            self.dims, i >= self.dims.n_dense_layers, self.dtype,
            self.param_dtype, self.attn_block, name=f"block{i}")

    def cache_layers(self, cache_len: int) -> tuple:
        """Every layer holds ``cache_len`` latent rows ``[row_dim]`` a
        slot, and there is no other kind."""
        rows = ((cache_len, self.dims.row_dim), self.dtype)
        return (CacheLayer("latent", cache_len, rows, None),) \
            * self.dims.n_layers

    def _shared(self, tokens, positions=None) -> tuple:
        """The rotary table of the program's positions, which every layer
        rotates by."""
        return (rotary(self.dims, jnp.arange(tokens.shape[1])[None]
                       if positions is None else positions[:, None]),)

    def prefill_buckets(self, cache_len: int):
        """The lengths a prompt is padded to, one prefill program each:
        256 (below it a program's time is the weights it reads, whatever
        it pads), then every whole tile of attention
        (``ops/attention.takes_splash`` asks for whole tiles),
        ``cache_len`` last.  ``None`` (the engine's powers of two) for a
        cache no longer than that first bucket."""
        if cache_len <= 256:
            return None
        tile = self.attn_block
        first = (256,) if tile > 256 else ()
        return first + tuple(range(tile, cache_len, tile)) + (cache_len,)

    def decode_fetch_block(self, rows: int) -> int:
        c = self.dims
        return latent_fetch_block(rows, c.row_dim, c.kv_rank)


#: What of a ``kimi_k2`` configuration is built here, and only so.
_ONLY = (("hidden_act", "silu"), ("tie_word_embeddings", False),
         ("attention_bias", False), ("scoring_func", "sigmoid"),
         ("topk_method", "noaux_tc"), ("moe_layer_freq", 1),
         ("num_nextn_predict_layers", 0))
_YARN_KEYS = ("factor", "original_max_position_embeddings", "beta_fast",
              "beta_slow", "mscale", "mscale_all_dim")


def _yarn_from(scaling) -> Yarn | None:
    if scaling is None:
        return None
    kind = scaling.get("type", scaling.get("rope_type"))
    if kind != "yarn":
        raise ValueError(f"kimi_k2 with rope_scaling of type {kind!r} is "
                         f"not built here (only 'yarn' or none is)")
    return Yarn(*(scaling[k] for k in _YARN_KEYS))


def dims_from_config(cfg: dict) -> KimiDims:
    """The sizes of a configuration in the source's own keys (a
    ``kimi_k2`` / DeepSeek-V3-shaped ``config.json``).  One chip's share
    of an expert-parallel deployment is stated as ``models/afmoe.py``
    reads it: ``n_routed_experts`` the experts HELD,
    ``published.n_routed_experts`` the router's width,
    ``deployment.rank`` which share this is."""
    for key, want in _ONLY:
        if cfg.get(key, want) != want:
            raise ValueError(f"kimi_k2 with {key} = {cfg[key]!r} is not "
                             f"built here (only {want!r} is)")
    published = cfg.get("published", {})
    held = cfg["n_routed_experts"]
    return KimiDims(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"],
        n_heads=cfg["num_attention_heads"],
        q_rank=cfg.get("q_lora_rank") or 0, kv_rank=cfg["kv_lora_rank"],
        rope_dim=cfg["qk_rope_head_dim"], nope_dim=cfg["qk_nope_head_dim"],
        v_dim=cfg["v_head_dim"], d_ff=cfg["intermediate_size"],
        d_expert=cfg["moe_intermediate_size"],
        d_shared=cfg["moe_intermediate_size"] * cfg["n_shared_experts"],
        n_dense_layers=cfg["first_k_dense_replace"],
        n_routed=published.get("n_routed_experts", held), experts_held=held,
        first_expert=cfg.get("deployment", {}).get("rank", 0) * held,
        top_k=cfg["num_experts_per_tok"], n_group=cfg["n_group"],
        topk_group=cfg["topk_group"],
        route_scale=cfg["routed_scaling_factor"],
        route_norm=cfg["norm_topk_prob"],
        rope_theta=float(cfg["rope_theta"]),
        yarn=_yarn_from(cfg.get("rope_scaling")), eps=cfg["rms_norm_eps"],
        max_len=cfg["max_position_embeddings"])


def build_kimi_k2(config, *, dtype=jnp.bfloat16, param_dtype=jnp.bfloat16,
                  attn_block: int = ATTN_TILE) -> KimiK2LM:
    """The one constructor, from a configuration's dict
    (``models.build_model_from_config`` comes here)."""
    return KimiK2LM(dims_from_config(config), dtype=dtype,
                    param_dtype=param_dtype, attn_block=attn_block)
