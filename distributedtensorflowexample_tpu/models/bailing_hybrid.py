"""The ``bailing_hybrid`` decoder (inclusionAI's Ling linear family): the
fourth LM block, beside ``transformer_lm.py``'s GPT-2 one, ``afmoe.py``'s
and ``qwen3_next.py``'s.  Five layers in six mix tokens by Kimi Delta
Attention (KDA: a gated delta rule whose decay is one number for each of
a head's key channels, its memory a fixed-size recurrent state), the
sixth by latent attention (MLA: keys and values of every head expanded
from ONE compressed row a position); the leading layers' feed-forward is
dense, the others' a sigmoid-routed expert layer whose selection is
limited to groups, beside a shared expert.

With d the hidden size, ``RMS(x; g) = x / sqrt(mean(x^2) + eps) * g``, no
bias anywhere::

    layer i:  h = h + mix_i(RMS(h; g_in));  h = h + ffn_i(RMS(h; g_post))
              mix_i = mla where (i + 1) % layer_group_size == 0, else kda
              ffn_i = dense for i < first_k_dense_replace, else moe
    head:     logits = RMS(h; g_f) W_head           (float32, untied)

    kda(a):   [q | k | v | u] = a W_qkvu            H heads of D each
              x = silu(conv(q | k | v))             depthwise, causal,
                                    kernel 4, no bias; its state is the
                                    last 3 inputs
              q, k = q / |q|_2, k / |k|_2 (eps 1e-6);  q = q / sqrt(D)
              g = lower * sigmoid(exp(A_log_h) (a W_f + dt_bias))
                                    float32, per head AND key channel;
                                    lower = kda_lower_bound < 0, so a
                                    step's decay exp(g) > exp(lower)
              beta = sigmoid(a W_b)                 per head
              per head, S in R^{D x D}, S_0 = 0, float32:
                  S = Diag(exp(g_t)) S;  d_t = beta_t (v_t - S^T k_t)
                  S = S + k_t d_t^T;  o_t = S^T q_t
              o = RMS(o; g_n) * sigmoid(u)          per head
              return o W_o

    mla(a):   q = a W_q             per head [q_nope (Dn) | q_pe (Dr)]
              [c_kv (rank) | k_pe (Dr)] = a W_kva
              c = RMS(c_kv; g_c);  q_pe, k_pe = rope(q_pe), rope(k_pe)
                                    interleaved pairs; ONE k_pe a
                                    position, shared by the heads
              the cache row of a position: [c | k_pe]
              expanded (a sequence):
                  [k_nope | v]_h = c W_kvb,h
                  p = softmax(q_h . [k_nope_h | k_pe] / sqrt(Dn + Dr))
                  o_h = sum p v_h                   causal, float32
              absorbed (one token against the rows), the same numbers:
                  qc_h = W_UK,h q_nope,h            W_kvb,h = [W_UK | W_UV]
                  p = softmax((qc_h . c_s + q_pe,h . k_pe,s) / sqrt(..))
                  o_h = W_UV,h^T sum p c_s
              o_h = o_h * sigmoid(a w_h)            a gate a head
              return o W_o

    moe(m):   s = sigmoid(m Wr) float32;  c = s + bias selects only
              a group's score: the sum of its two best c; the topk_group
              best of n_group groups stay; the top k of their experts
              w = s_sel / sum(s_sel) * routed_scaling_factor
              return sum_e w_e ffn_e(m) + ffn_shared(m)
              ffn(m; G, U, D) = (silu(m G) * (m U)) D

**One definition of a block** (:class:`BailingBlock`) with the two
methods the shell of ``served_lm.py`` walks, ``sequence`` and ``step``;
:class:`BailingHybridLM` is that shell and states each layer's cache: an
MLA layer holds ``cache_len`` rows a slot, ONE row a position (kind
``latent``: ``[c | k_pe]`` padded with zeros to whole lane groups, 576 ->
640 at the published sizes, because the TPU's kernel takes a cache whose
rows are whole tiles without a relaid copy and no other; there is no V
array), a KDA layer NO rows but a state of a fixed size (kind ``state``):
``S [H, D, D]`` float32 and the convolution's last 3 inputs.  A parked
slot (position 0) neither decays nor writes.

**The expert layer holds a share** (``ops/moe.py``): ``experts_held`` of
``n_routed`` from ``first_expert`` on.  With ``n_group`` shares a group
is one share's experts.

Parameters are stored in ``param_dtype`` (bfloat16 in serving), as are
activations, latent rows and the convolution's state; norms, the router,
softmax, the decay, the recurrent state and logits are float32.  The
family's multi-token prediction module is not here (it serves no token
without speculation), nor the clamp on SwiGLU that its deepest layers
carry (a configuration that asks for it is refused).
"""

from __future__ import annotations

import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp

from distributedtensorflowexample_tpu.models.served_lm import (
    CacheLayer, ServedLM, gated_params, rms_norm)
from distributedtensorflowexample_tpu.ops import linear_attention as la
from distributedtensorflowexample_tpu.ops import moe
from distributedtensorflowexample_tpu.ops.attention import (
    ATTN_BLOCK, latent_decode_attention, latent_expanded_attention,
    latent_fetch_block)

F32 = jnp.float32
LANES = 128     # a latent row is padded to whole groups of these


@dataclasses.dataclass(frozen=True)
class BailingDims:
    """Every size of the architecture (hashable: a flax field)."""
    vocab_size: int
    d_model: int
    n_layers: int
    group_size: int             # layer i is MLA where (i+1) % this == 0
    n_heads: int                # of both mixers
    head_dim: int               # KDA: key and value features a head
    conv_kernel: int
    decay_lower: float          # KDA: the least log-decay a step (< 0)
    kv_rank: int                # MLA: the compressed row ...
    rope_dim: int               # ... and the shared rotary key beside it
    nope_dim: int               # MLA: a head's features without position
    v_dim: int                  # MLA: a head's value features
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
    eps: float
    max_len: int
    init_std: float = 0.02

    def is_latent(self, i: int) -> bool:
        return (i + 1) % self.group_size == 0

    @property
    def row_dim(self) -> int:
        """Features of a latent cache row: [c | k_pe], in whole lane
        groups."""
        return -(-(self.kv_rank + self.rope_dim) // LANES) * LANES


def _rope_pairs(x, positions, theta):
    """Rotary positions on all of ``x [..., T, H, Dr]`` at ``positions
    [..., T]``: features ``(2i, 2i + 1)`` rotate as a pair."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = positions[..., None].astype(F32) * inv
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    xf = x.astype(F32).reshape(*x.shape[:-1], half, 2)
    x1, x2 = xf[..., 0], xf[..., 1]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape).astype(x.dtype)


def _uniform(lo: float, hi: float, log: bool = False):
    def init(key, shape, dtype=F32):
        x = jax.random.uniform(key, shape, F32, lo, hi)
        return (jnp.log(x) if log else x).astype(dtype)
    return init


class BailingBlock(nn.Module):
    """One layer: latent attention or KDA, then a dense or an expert
    feed-forward."""
    dims: BailingDims
    latent: bool
    experts: bool
    dtype: jnp.dtype = jnp.bfloat16
    param_dtype: jnp.dtype = jnp.float32
    attn_block: int = ATTN_BLOCK

    def setup(self):
        c, pd = self.dims, self.param_dtype
        ones = nn.initializers.ones
        w = nn.initializers.normal(c.init_std)
        d, H = c.d_model, c.n_heads
        P = self.param
        self.norm_in = P("norm_in", ones, (d,), pd)
        self.norm_post = P("norm_post", ones, (d,), pd)
        if self.latent:
            self.wq = P("wq", w, (d, H * (c.nope_dim + c.rope_dim)), pd)
            self.w_kva = P("w_kva", w, (d, c.kv_rank + c.rope_dim), pd)
            self.norm_c = P("norm_c", ones, (c.kv_rank,), pd)
            self.w_kvb = P("w_kvb", w,
                           (c.kv_rank, H, c.nope_dim + c.v_dim), pd)
            self.w_gate = P("w_gate", w, (d, H), pd)
            self.wo = P("wo", w, (H * c.v_dim, d), pd)
        else:
            hd = H * c.head_dim
            self.w_qkvu = P("w_qkvu", w, (d, 4 * hd), pd)
            self.w_f = P("w_f", w, (d, hd), pd)
            self.w_b = P("w_b", w, (d, H), pd)
            self.conv = P("conv", nn.initializers.normal(0.5),
                          (c.conv_kernel, 3 * hd), pd)
            # exp(A_log) (a W_f + dt_bias) from about -8 to 3: decays of
            # ~0.998 down to ~0.01 a step, channel by channel.
            self.a_log = P("a_log", _uniform(0.5, 1.5, log=True), (H,), F32)
            self.dt_bias = P("dt_bias", _uniform(-7.0, 2.5), (hd,), F32)
            self.norm_o = P("norm_o", ones, (c.head_dim,), pd)
            self.wo = P("wo", w, (hd, d), pd)
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
    def _mla_q(self, a, positions):
        """a [..., T, d] -> q_nope [..., T, H, Dn], q_pe [..., T, H, Dr]
        (rotated)."""
        c = self.dims
        with jax.named_scope("mla.q"):
            q = jnp.dot(a, self.wq.astype(self.dtype)).reshape(
                *a.shape[:-1], c.n_heads, c.nope_dim + c.rope_dim)
            return q[..., :c.nope_dim], _rope_pairs(
                q[..., c.nope_dim:], positions, c.rope_theta)

    def _mla_rows(self, a, positions):
        """a [..., T, d] -> the positions' cache rows [..., T, row_dim]:
        ``[RMS(c_kv) | rope(k_pe) | zeros]``."""
        c = self.dims
        with jax.named_scope("mla.kv"):
            kva = jnp.dot(a, self.w_kva.astype(self.dtype))
            lat = rms_norm(kva[..., :c.kv_rank], self.norm_c, c.eps)
            k_pe = _rope_pairs(kva[..., None, c.kv_rank:], positions,
                               c.rope_theta)[..., 0, :]
            pad = jnp.zeros((*a.shape[:-1],
                             c.row_dim - c.kv_rank - c.rope_dim), lat.dtype)
            return jnp.concatenate([lat, k_pe, pad], axis=-1)

    def _mla_out(self, o, a):
        """o [..., H, Dv], a [..., d] -> [..., d]: a gate a head, then
        the output projection."""
        gate = jax.nn.sigmoid(jnp.dot(a, self.w_gate.astype(self.dtype),
                                      preferred_element_type=F32))
        o = (o.astype(F32) * gate[..., None]).astype(self.dtype)
        return jnp.dot(o.reshape(*o.shape[:-2], -1),
                       self.wo.astype(self.dtype))

    # --- Kimi Delta Attention ----------------------------------------------
    def _kda_proj(self, a):
        """a [..., d] -> (q | k | v before the convolution [..., 3 H D],
        the output gate's input u [..., H, D], beta [..., H] and g [...,
        H, D] float32)."""
        c, dt = self.dims, self.dtype
        with jax.named_scope("kda.proj"):
            hd = c.n_heads * c.head_dim
            qkvu = jnp.dot(a, self.w_qkvu.astype(dt))
            f = jnp.dot(a, self.w_f.astype(dt), preferred_element_type=F32)
            b = jnp.dot(a, self.w_b.astype(dt), preferred_element_type=F32)
            heads = lambda t: t.reshape(*t.shape[:-1], c.n_heads, c.head_dim)
            g = c.decay_lower * jax.nn.sigmoid(
                jnp.exp(self.a_log)[:, None] * heads(f + self.dt_bias))
            return qkvu[..., :3 * hd], heads(qkvu[..., 3 * hd:]), \
                jax.nn.sigmoid(b), g

    def _kda_heads(self, y):
        """The convolution's output y [..., 3 H D] float32 -> q, k
        (normalised, q scaled) and v, each [..., H, D], in the
        activations' type."""
        c = self.dims
        heads = lambda t: t.reshape(*t.shape[:-1], c.n_heads, c.head_dim)
        q, k, v = (heads(t) for t in jnp.split(jax.nn.silu(y), 3, axis=-1))
        unit = lambda t: t * jax.lax.rsqrt(
            jnp.sum(t * t, axis=-1, keepdims=True) + 1e-6)
        q, k = unit(q) * c.head_dim ** -0.5, unit(k)
        return tuple(t.astype(self.dtype) for t in (q, k, v))

    def _kda_out(self, o, u):
        """o [..., H, D] float32, u [..., H, D] -> [..., d]."""
        c = self.dims
        with jax.named_scope("kda.out"):
            o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                                  + c.eps)
            o = o * self.norm_o.astype(F32) * jax.nn.sigmoid(u.astype(F32))
            o = o.astype(self.dtype).reshape(*o.shape[:-2], -1)
            return jnp.dot(o, self.wo.astype(self.dtype))

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
    def sequence(self, x, lengths=None):
        """A whole sequence from position 0: x [B, T, d], lengths [B]
        the live length of each row (None: T) -> (x', what the layer
        remembers, stats).  An MLA layer remembers its rows ``[B, T,
        row_dim]`` (and nothing beside them: None); a KDA layer ``(S [B,
        H, D, D] float32, the convolution's last inputs [B, K - 1, 3 H
        D])`` at each row's length."""
        c = self.dims
        B, T, _ = x.shape
        live = None if lengths is None else (
            jnp.arange(T)[None] < lengths[:, None])
        a = rms_norm(x, self.norm_in, c.eps)
        if self.latent:
            pos = jnp.arange(T)[None]
            q_nope, q_pe = self._mla_q(a, pos)
            rows = self._mla_rows(a, pos)
            with jax.named_scope("mla.attend"):
                lat = rows[..., :c.kv_rank]
                k_pe = rows[..., None, c.kv_rank:c.kv_rank + c.rope_dim]
                kv = jnp.einsum("btc,chn->bthn", lat,
                                self.w_kvb.astype(self.dtype))
                k = jnp.concatenate([kv[..., :c.nope_dim], jnp.broadcast_to(
                    k_pe, (B, T, c.n_heads, c.rope_dim))], axis=-1)
                o = latent_expanded_attention(
                    jnp.concatenate([q_nope, q_pe], axis=-1), k,
                    kv[..., c.nope_dim:], block=self.attn_block)
                x = x + self._mla_out(o, a)
            kept = (rows, None)
        else:
            qkv, u, beta, g = self._kda_proj(a)
            with jax.named_scope("kda.conv"):
                y, conv_state = la.causal_conv_sequence(qkv, self.conv,
                                                        lengths)
                q, k, v = self._kda_heads(y)
            with jax.named_scope("kda.scan"):
                o, S = la.chunked_sequence(
                    q, k, v, g, beta, jnp.zeros(
                        (B, c.n_heads, c.head_dim, c.head_dim), F32), live)
            x = x + self._kda_out(o, u)
            kept = (S, conv_state)
        x, stats = self._ffn(x, live)
        return x, kept, stats

    def step(self, x, ck, cv, pos):
        """One token a slot: x [S, d], pos [S] its position, and what
        the layer remembers of each slot — an MLA layer's rows ``[S, R,
        row_dim]`` (the token's is written at its position, then the
        query reads rows ``0..pos``, each once, never expanded) and an
        empty array, a KDA layer's state and convolution state (read
        whole, written whole).  A slot at ``pos == 0`` is parked: its
        token goes to no expert and its state stays as it is."""
        c = self.dims
        S = x.shape[0]
        live = pos > 0
        a = rms_norm(x, self.norm_in, c.eps)
        if self.latent:
            q_nope, q_pe = self._mla_q(a[:, None], pos[:, None])
            with jax.named_scope("cache_update"):
                ck = ck.at[jnp.arange(S), pos].set(
                    self._mla_rows(a[:, None], pos[:, None])[:, 0])
            with jax.named_scope("mla.attend"):
                w_kvb = self.w_kvb.astype(self.dtype)
                q_lat = jnp.einsum("shn,chn->shc", q_nope[:, 0],
                                   w_kvb[..., :c.nope_dim])
                q_row = jnp.concatenate([q_lat, q_pe[:, 0], jnp.zeros(
                    (S, c.n_heads, c.row_dim - c.kv_rank - c.rope_dim),
                    q_lat.dtype)], axis=-1)
                o = latent_decode_attention(
                    q_row, ck, jnp.minimum(pos + 1, ck.shape[1]),
                    v_dim=c.kv_rank,
                    scale=(c.nope_dim + c.rope_dim) ** -0.5)
                o = jnp.einsum("shc,chv->shv", o, w_kvb[..., c.nope_dim:])
                x = x + self._mla_out(o, a)
        else:
            qkv, u, beta, g = self._kda_proj(a)
            with jax.named_scope("kda.conv"):
                y, cv = la.causal_conv_step(qkv, self.conv, cv, live)
                q, k, v = self._kda_heads(y)
            with jax.named_scope("kda.step"):
                o, ck = la.recurrent_step(q, k, v, g, beta, ck, live)
            x = x + self._kda_out(o, u)
        x, stats = self._ffn(x, live)
        return x, ck, cv, stats


class BailingHybridLM(ServedLM):
    """The shell (``served_lm.py``) over :class:`BailingBlock`: latent rows
    in one layer of a group, KDA's states in the others."""
    dims: BailingDims

    #: Two prompts of 4,096.
    prefill_positions_max = 8192
    expert_slots = property(lambda self: self.dims.experts_held * (
        self.dims.n_layers - self.dims.n_dense_layers))

    def make_block(self, i):
        c = self.dims
        return BailingBlock(
            c, c.is_latent(i), i >= c.n_dense_layers, self.dtype,
            self.param_dtype, self.attn_block, name=f"block{i}")

    def cache_layers(self, cache_len: int) -> tuple:
        """An MLA layer holds ``cache_len`` latent rows ``[row_dim]`` a
        slot; a KDA layer no rows but its state ``[H, D, D]`` float32 and
        the convolution's last inputs ``[K - 1, 3 H D]``."""
        c = self.dims
        latent = CacheLayer("latent", cache_len,
                            ((cache_len, c.row_dim), self.dtype), None)
        state = CacheLayer(
            "state", 0, ((c.n_heads, c.head_dim, c.head_dim), F32),
            ((c.conv_kernel - 1, 3 * c.n_heads * c.head_dim), self.dtype))
        return tuple(latent if c.is_latent(i) else state
                     for i in range(c.n_layers))

    def decode_fetch_block(self, rows: int) -> int:
        c = self.dims
        return rows and latent_fetch_block(rows, c.row_dim, c.kv_rank)


#: What of a ``bailing_hybrid`` configuration is built here, and only so.
_ONLY = (("hidden_act", "silu"), ("q_lora_rank", None),
         ("rope_scaling", None), ("tie_word_embeddings", False),
         ("use_bias", False), ("use_qkv_bias", False),
         ("use_kda_lora", False), ("kda_safe_gate", True),
         ("linear_silu", True), ("rope_interleave", True),
         ("scoring_func", "sigmoid"), ("topk_method", "noaux_tc"),
         ("moe_router_enable_expert_bias", True),
         ("gated_attention_proj_granularity_type", "head_wise"),
         ("use_nGPT", False), ("value_norm", False),
         ("up_proj_norm", False), ("use_mla_nope", False),
         ("scale_router_input", False), ("num_kv_heads_for_linear_attn", 0))


def dims_from_config(cfg: dict) -> BailingDims:
    """The sizes of a configuration in the source's own keys (a
    ``bailing_hybrid`` ``config.json``).  One chip's share of an
    expert-parallel deployment is stated as ``models/afmoe.py`` reads
    it: ``num_experts`` the experts HELD, ``published.num_experts`` the
    router's width, ``deployment.rank`` which share this is."""
    for key, want in _ONLY:
        if cfg.get(key, want) != want:
            raise ValueError(f"bailing_hybrid with {key} = {cfg[key]!r} is "
                             f"not built here (only {want!r} is)")
    layers = cfg["num_hidden_layers"]
    for key in ("expert_swiglu_limit_list", "share_expert_swiglu_limit_list"):
        if any(cfg.get(key, [])[:layers]):
            raise ValueError(
                f"bailing_hybrid with a clamped SwiGLU in a kept layer "
                f"({key} is not 0 below layer {layers}) is not built here")
    published = cfg.get("published", {})
    held = cfg["num_experts"]
    return BailingDims(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_layers=layers, group_size=cfg["layer_group_size"],
        n_heads=cfg["num_attention_heads"], head_dim=cfg["head_dim"],
        conv_kernel=cfg["short_conv_kernel_size"],
        decay_lower=float(cfg["kda_lower_bound"]),
        kv_rank=cfg["kv_lora_rank"], rope_dim=cfg["qk_rope_head_dim"],
        nope_dim=cfg["qk_nope_head_dim"], v_dim=cfg["v_head_dim"],
        d_ff=cfg["intermediate_size"], d_expert=cfg["moe_intermediate_size"],
        d_shared=(cfg["moe_shared_expert_intermediate_size"]
                  * cfg["num_shared_experts"]),
        n_dense_layers=cfg["first_k_dense_replace"],
        n_routed=published.get("num_experts", held), experts_held=held,
        first_expert=cfg.get("deployment", {}).get("rank", 0) * held,
        top_k=cfg["num_experts_per_tok"], n_group=cfg["n_group"],
        topk_group=cfg["topk_group"],
        route_scale=cfg["routed_scaling_factor"],
        route_norm=cfg["norm_topk_prob"],
        rope_theta=float(cfg["rope_theta"]), eps=cfg["rms_norm_eps"],
        max_len=cfg["max_position_embeddings"])


def build_bailing_hybrid(config, *, dtype=jnp.bfloat16,
                         param_dtype=jnp.bfloat16,
                         attn_block: int = ATTN_BLOCK) -> BailingHybridLM:
    """The one constructor, from a configuration's dict
    (``models.build_model_from_config`` comes here)."""
    return BailingHybridLM(dims_from_config(config), dtype=dtype,
                           param_dtype=param_dtype, attn_block=attn_block)
