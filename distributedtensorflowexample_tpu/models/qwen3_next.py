"""The ``qwen3_next`` decoder (Qwen3-Next): the third LM block, beside
``transformer_lm.py``'s GPT-2 one and ``afmoe.py``'s.  Three layers in
four mix tokens by a Gated DeltaNet (a linear attention whose memory is
a fixed-size recurrent state), the fourth by gated softmax attention;
every layer's feed-forward is a softmax-routed expert layer beside a
gated shared expert.

With d the hidden size, ``RMS0(x; g) = x / sqrt(mean(x^2) + eps) * (1 +
g)`` (the family's zero-centred norm), no bias anywhere::

    layer i:  h = h + mix_i(RMS0(h; g_in));  h = h + moe(RMS0(h; g_post))
              mix_i = attn where (i + 1) % full_attention_interval == 0,
              else gdn
    head:     logits = RMS0(h; g_f) W_head          (float32, untied)

    attn(a):  [q | u] = a Wq        per head: Hq x (2 x Dh) outputs, each
                                    head's split into q and the gate u
              k, v = a Wk, a Wv                     Hkv heads of Dh
              q, k = RMS0(q; g_q), RMS0(k; g_k)     over each head's Dh
              q, k = rope(q, k)     on the first partial_rotary_factor x
                                    Dh features (half-split pairs), the
                                    others pass
              o = softmax(q k^T / sqrt(Dh)) v       causal, float32
              return (o * sigmoid(u)) Wo

    gdn(a):   [q | k | v | z] = a W_qkvz;  [b | alpha] = a W_ba
              x = silu(conv(q | k | v))             depthwise, causal,
                                    kernel 4, no bias; its state is the
                                    last 3 inputs
              q, k: Hk heads of Dk, each repeated to Hv / Hk value heads
              q, k = q / |q|_2, k / |k|_2 (eps 1e-6);  q = q / sqrt(Dk)
              beta = sigmoid(b)
              g = -exp(A_log) * softplus(alpha + dt_bias)     float32
              per value head, S in R^{Dk x Dv}, S_0 = 0, float32:
                  S = exp(g_t) S;  d_t = beta_t (v_t - S^T k_t)
                  S = S + k_t d_t^T;  o_t = S^T q_t
              o = o / sqrt(mean(o^2) + eps) * g_n * silu(z)   per head
                                    (a plain scale, not 1 + g)
              return o W_out

    moe(m):   p = softmax(m Wr) over all experts (float32); the top k;
              w = p_sel / sum(p_sel)
              return sum_e w_e ffn_e(m) + sigmoid(m w_sg) * ffn_shared(m)
              ffn(m; G, U, D) = (silu(m G) * (m U)) D

**One definition of a block** (:class:`Qwen3NextBlock`) with the two
methods the shell of ``served_lm.py`` walks, ``sequence`` and ``step``;
:class:`Qwen3NextLM` is that shell and states each layer's cache: an
attention layer holds ``cache_len`` K/V rows a slot (kind ``full``), a
Gated DeltaNet layer holds NO rows but a state of a fixed size (kind
``state``): ``S [Hv, Dk, Dv]`` float32 and the convolution's last 3
inputs.  A state cannot be masked as a stale row can: prefill writes the
state at the prompt's true length (``ops/linear_attention.py`` leaves
padding out), and a parked slot (position 0) neither decays nor writes.
The K/V rows are kept flat, ``[S, rows * Hkv, Dh]``: two K/V heads do
not fill a tile's sublanes, and the token step's ragged kernel reads that
view anyway (``ops/pallas/decode_attention.py``).

**The expert layer holds a share** (``ops/moe.py``), as ``afmoe.py``'s:
``experts_held`` of ``n_routed`` from ``first_expert`` on.

Parameters are stored in ``param_dtype`` (bfloat16 in serving), as are
activations and K/V rows; norms, the router, softmax, the decay, the
recurrent state and logits are float32.  The family's multi-token
prediction head is not here (it serves no token without speculation).
"""

from __future__ import annotations

import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp

from distributedtensorflowexample_tpu.models.served_lm import (
    CacheLayer, ServedLM, gated_params, log_uniform)
from distributedtensorflowexample_tpu.ops import linear_attention as la
from distributedtensorflowexample_tpu.ops import moe
from distributedtensorflowexample_tpu.ops.attention import (
    ATTN_BLOCK, decode_attention, decode_fetch_block, grouped_attention)

F32 = jnp.float32


@dataclasses.dataclass(frozen=True)
class Qwen3NextDims:
    """Every size of the architecture (hashable: a flax field)."""
    vocab_size: int
    d_model: int
    n_layers: int
    full_every: int             # layer i is attention where (i+1) % this == 0
    n_heads: int                # attention: query heads ...
    n_kv_heads: int             # ... key/value heads ...
    head_dim: int               # ... of this many features,
    rotary_dim: int             # the first of which rotate
    lin_k_heads: int            # Gated DeltaNet: q/k heads ...
    lin_v_heads: int            # ... value heads (the state's heads) ...
    lin_k_dim: int
    lin_v_dim: int
    conv_kernel: int
    d_expert: int               # each routed expert's width
    d_shared: int               # the shared expert's
    n_routed: int               # experts the router scores
    experts_held: int           # experts this share computes ...
    first_expert: int           # ... from this id on
    top_k: int
    route_norm: bool
    rope_theta: float
    eps: float
    max_len: int
    init_std: float = 0.02

    def is_attention(self, i: int) -> bool:
        return (i + 1) % self.full_every == 0


def _rms0(x, g, eps):
    xf = x.astype(F32)
    xf = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (xf * (1.0 + g.astype(F32))).astype(x.dtype)


def _partial_rope(x, positions, theta, rotary_dim):
    """Rotary positions on the first ``rotary_dim`` features of ``x [...,
    T, H, Dh]`` at ``positions [..., T]`` (their two halves rotate as
    pairs); the other features pass."""
    half = rotary_dim // 2
    inv = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = positions[..., None].astype(F32) * inv
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    xf = x.astype(F32)
    x1, x2, rest = xf[..., :half], xf[..., half:rotary_dim], \
        xf[..., rotary_dim:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest],
                           axis=-1).astype(x.dtype)


class Qwen3NextBlock(nn.Module):
    """One layer: gated attention or a Gated DeltaNet, then the expert
    feed-forward."""
    dims: Qwen3NextDims
    attention: bool
    dtype: jnp.dtype = jnp.bfloat16
    param_dtype: jnp.dtype = jnp.float32
    attn_block: int = ATTN_BLOCK

    def setup(self):
        c, pd = self.dims, self.param_dtype
        zeros, ones = nn.initializers.zeros, nn.initializers.ones
        w = nn.initializers.normal(c.init_std)
        d = c.d_model
        P = self.param
        self.norm_in = P("norm_in", zeros, (d,), pd)
        self.norm_post = P("norm_post", zeros, (d,), pd)
        if self.attention:
            qd, kd = c.n_heads * c.head_dim, c.n_kv_heads * c.head_dim
            self.norm_q = P("norm_q", zeros, (c.head_dim,), pd)
            self.norm_k = P("norm_k", zeros, (c.head_dim,), pd)
            self.wq = P("wq", w, (d, 2 * qd), pd)
            self.wk = P("wk", w, (d, kd), pd)
            self.wv = P("wv", w, (d, kd), pd)
            self.wo = P("wo", w, (qd, d), pd)
        else:
            kd, vd = c.lin_k_heads * c.lin_k_dim, c.lin_v_heads * c.lin_v_dim
            self.w_qkvz = P("w_qkvz", w, (d, 2 * kd + 2 * vd), pd)
            self.w_ba = P("w_ba", w, (d, 2 * c.lin_v_heads), pd)
            self.conv = P("conv", nn.initializers.normal(0.5),
                          (c.conv_kernel, 2 * kd + vd), pd)
            # Decays between ~0.5 and ~0.998 a step: memories of a few
            # tokens and of hundreds side by side.
            self.a_log = P("a_log", log_uniform(0.02, 0.5),
                           (c.lin_v_heads,), F32)
            self.dt_bias = P("dt_bias", nn.initializers.uniform(1.0),
                             (c.lin_v_heads,), F32)
            self.norm_o = P("norm_o", ones, (c.lin_v_dim,), pd)
            self.w_out = P("w_out", w, (vd, d), pd)
        f, E, fs = c.d_expert, c.experts_held, c.d_shared
        self.router = P("router", w, (d, c.n_routed), pd)
        self.shared = gated_params(P, "shared", w, pd, d, fs)
        self.shared_gate_w = P("shared_gate_w", w, (d, 1), pd)
        self.held = gated_params(P, "experts", w, pd, d, f, E)

    # --- gated attention ---------------------------------------------------
    def _qkvu(self, a, positions):
        """a [..., T, d] -> q [..., T, Hq, Dh], k and v [..., T, Hkv, Dh],
        the gate's input u [..., T, Hq Dh]."""
        c, dt = self.dims, self.dtype
        qu = jnp.dot(a, self.wq.astype(dt)).reshape(
            *a.shape[:-1], c.n_heads, 2 * c.head_dim)
        q, u = qu[..., :c.head_dim], qu[..., c.head_dim:]
        heads = lambda x: x.reshape(*x.shape[:-1], c.n_kv_heads, c.head_dim)
        k = heads(jnp.dot(a, self.wk.astype(dt)))
        v = heads(jnp.dot(a, self.wv.astype(dt)))
        q, k = _rms0(q, self.norm_q, c.eps), _rms0(k, self.norm_k, c.eps)
        q = _partial_rope(q, positions, c.rope_theta, c.rotary_dim)
        k = _partial_rope(k, positions, c.rope_theta, c.rotary_dim)
        return q, k, v, u.reshape(*u.shape[:-2], -1)

    def _attn_out(self, o, u):
        o = o.reshape(u.shape) * jax.nn.sigmoid(u.astype(F32)).astype(o.dtype)
        return jnp.dot(o, self.wo.astype(self.dtype))

    # --- the Gated DeltaNet ------------------------------------------------
    def _gdn_proj(self, a):
        """a [..., d] -> (q | k | v before the convolution [..., 2 Hk Dk +
        Hv Dv], z [..., Hv, Dv], beta and g [..., Hv] float32)."""
        c, dt = self.dims, self.dtype
        with jax.named_scope("gdn.proj"):
            kd, vd = c.lin_k_heads * c.lin_k_dim, c.lin_v_heads * c.lin_v_dim
            qkvz = jnp.dot(a, self.w_qkvz.astype(dt))
            ba = jnp.dot(a, self.w_ba.astype(dt),
                         preferred_element_type=F32)
            b, alpha = ba[..., :c.lin_v_heads], ba[..., c.lin_v_heads:]
            g = -jnp.exp(self.a_log) * jax.nn.softplus(alpha + self.dt_bias)
            z = qkvz[..., 2 * kd + vd:].reshape(
                *a.shape[:-1], c.lin_v_heads, c.lin_v_dim)
            return qkvz[..., :2 * kd + vd], z, jax.nn.sigmoid(b), g

    def _gdn_heads(self, y):
        """The convolution's output y [..., 2 Hk Dk + Hv Dv] float32 ->
        q, k [..., Hv, Dk] (normalised, q scaled, each q/k head repeated
        to its value heads) and v [..., Hv, Dv], in the activations'
        type."""
        c = self.dims
        x = jax.nn.silu(y)
        kd = c.lin_k_heads * c.lin_k_dim
        heads = lambda t, n, w: t.reshape(*t.shape[:-1], n, w)
        q = heads(x[..., :kd], c.lin_k_heads, c.lin_k_dim)
        k = heads(x[..., kd:2 * kd], c.lin_k_heads, c.lin_k_dim)
        v = heads(x[..., 2 * kd:], c.lin_v_heads, c.lin_v_dim)
        unit = lambda t: t * jax.lax.rsqrt(
            jnp.sum(t * t, axis=-1, keepdims=True) + 1e-6)
        q, k = unit(q) * c.lin_k_dim ** -0.5, unit(k)
        rep = c.lin_v_heads // c.lin_k_heads
        q, k = (jnp.repeat(t, rep, axis=-2) for t in (q, k))
        return tuple(t.astype(self.dtype) for t in (q, k, v))

    def _gdn_out(self, o, z):
        """o [..., Hv, Dv] float32, z [..., Hv, Dv] -> [..., d]."""
        c = self.dims
        with jax.named_scope("gdn.out"):
            o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                                  + c.eps)
            o = o * self.norm_o.astype(F32) * jax.nn.silu(z.astype(F32))
            o = o.astype(self.dtype).reshape(*o.shape[:-2], -1)
            return jnp.dot(o, self.w_out.astype(self.dtype))

    # --- feed-forward ------------------------------------------------------
    def _ffn(self, h, live):
        """h [..., d], live [...] or None -> (h', stats int32[4])."""
        c, dt = self.dims, self.dtype
        m = _rms0(h, self.norm_post, c.eps).reshape(-1, c.d_model)
        cast = lambda ws: tuple(x.astype(dt) for x in ws)
        sel, w = moe.route(m, self.router.astype(dt), None, top_k=c.top_k,
                           route_scale=1.0, route_norm=c.route_norm,
                           score_func="softmax")
        f, stats = moe.expert_ffn(
            m, sel, w, *cast(self.held), first_expert=c.first_expert,
            experts_known=self.router.shape[1],
            live=None if live is None else live.reshape(-1))
        with jax.named_scope("moe.shared"):
            gate = jax.nn.sigmoid(jnp.dot(
                m, self.shared_gate_w.astype(dt), preferred_element_type=F32))
            f = f + (gate * moe.gated_ffn(m, *cast(self.shared)).astype(F32)
                     ).astype(dt)
        return h + f.reshape(h.shape), stats

    # --- the two shapes of work --------------------------------------------
    def sequence(self, x, lengths=None):
        """A whole sequence from position 0: x [B, T, d], lengths [B]
        the live length of each row (None: T) -> (x', what the layer
        remembers, stats).  An attention layer remembers ``(k, v)``, each
        [B, T, Hkv, Dh]; a Gated DeltaNet ``(S [B, Hv, Dk, Dv] float32,
        the convolution's last inputs [B, K - 1, C])`` at each row's
        length."""
        c = self.dims
        B, T, _ = x.shape
        live = None if lengths is None else (
            jnp.arange(T)[None] < lengths[:, None])
        a = _rms0(x, self.norm_in, c.eps)
        if self.attention:
            q, k, v, u = self._qkvu(a, jnp.arange(T)[None])
            with jax.named_scope("attn.gated"):
                o = grouped_attention(q, k, v, block=self.attn_block)
                x = x + self._attn_out(o, u)
            kept = (k, v)
        else:
            qkv, z, beta, g = self._gdn_proj(a)
            with jax.named_scope("gdn.conv"):
                y, conv_state = la.causal_conv_sequence(qkv, self.conv,
                                                        lengths)
                q, k, v = self._gdn_heads(y)
            with jax.named_scope("gdn.scan"):
                o, S = la.chunked_sequence(
                    q, k, v, g, beta, jnp.zeros(
                        (B, c.lin_v_heads, c.lin_k_dim, c.lin_v_dim), F32),
                    live)
            x = x + self._gdn_out(o, z)
            kept = (S, conv_state)
        x, stats = self._ffn(x, live)
        return x, kept, stats

    def step(self, x, ck, cv, pos):
        """One token a slot: x [S, d], pos [S] its position, and what
        the layer remembers of each slot — an attention layer's K and V
        rows ``[S, R * Hkv, Dh]`` (the token's are written at its
        position, then the query reads rows ``0..pos``), a Gated
        DeltaNet's state and convolution state (read whole, written
        whole).  A slot at ``pos == 0`` is parked: its token goes to no
        expert and its state stays as it is."""
        c = self.dims
        S = x.shape[0]
        live = pos > 0
        a = _rms0(x, self.norm_in, c.eps)
        if self.attention:
            q, k, v, u = self._qkvu(a[:, None], pos[:, None])
            q = q.reshape(S, 1, c.n_kv_heads, -1, c.head_dim)
            with jax.named_scope("cache_update"):
                rows = pos[:, None] * c.n_kv_heads + jnp.arange(
                    c.n_kv_heads, dtype=pos.dtype)[None]
                sl = jnp.arange(S)[:, None]
                ck = ck.at[sl, rows].set(k[:, 0])
                cv = cv.at[sl, rows].set(v[:, 0])
            with jax.named_scope("attn.gated"):
                R = ck.shape[1] // c.n_kv_heads
                o = decode_attention(q, ck, cv,
                                     jnp.minimum(pos[:, None] + 1, R))
                x = x + self._attn_out(o[:, 0], u[:, 0])
        else:
            qkv, z, beta, g = self._gdn_proj(a)
            with jax.named_scope("gdn.conv"):
                y, cv = la.causal_conv_step(qkv, self.conv, cv, live)
                q, k, v = self._gdn_heads(y)
            with jax.named_scope("gdn.step"):
                o, ck = la.recurrent_step(q, k, v, g, beta, ck, live)
            x = x + self._gdn_out(o, z)
        x, stats = self._ffn(x, live)
        return x, ck, cv, stats


class Qwen3NextLM(ServedLM):
    """The shell (``served_lm.py``) over :class:`Qwen3NextBlock`: the
    family's zero-centred norm before the head, attention rows kept flat
    beside the Gated DeltaNet's states."""
    dims: Qwen3NextDims

    norm = staticmethod(_rms0)
    norm_init = staticmethod(nn.initializers.zeros)
    #: Two prompts of the longest bucket.
    prefill_positions_max = 8192
    expert_slots = property(
        lambda self: self.dims.experts_held * self.dims.n_layers)

    def make_block(self, i):
        return Qwen3NextBlock(
            self.dims, self.dims.is_attention(i), self.dtype,
            self.param_dtype, self.attn_block, name=f"block{i}")

    def cache_layers(self, cache_len: int) -> tuple:
        """An attention layer holds ``cache_len`` K/V rows a slot, flat as
        ``[cache_len * Hkv, Dh]``; a Gated DeltaNet layer no rows but its
        state ``[Hv, Dk, Dv]`` float32 and the convolution's last inputs
        ``[K - 1, C]``."""
        c = self.dims
        kv = ((cache_len * c.n_kv_heads, c.head_dim), self.dtype)
        conv = 2 * c.lin_k_heads * c.lin_k_dim + c.lin_v_heads * c.lin_v_dim
        full = CacheLayer("full", cache_len, kv, kv)
        state = CacheLayer(
            "state", 0, ((c.lin_v_heads, c.lin_k_dim, c.lin_v_dim), F32),
            ((c.conv_kernel - 1, conv), self.dtype))
        return tuple(full if c.is_attention(i) else state
                     for i in range(c.n_layers))

    def decode_fetch_block(self, rows: int) -> int:
        c = self.dims
        return rows and decode_fetch_block(rows, c.n_kv_heads, c.head_dim,
                                           flat=True)


def dims_from_config(cfg: dict) -> Qwen3NextDims:
    """The sizes of a configuration in the source's own keys (a
    ``qwen3_next`` ``config.json``).  One chip's share of an
    expert-parallel deployment is stated as ``models/afmoe.py`` reads
    it: ``num_experts`` the experts HELD, ``published.num_experts`` the
    router's width, ``deployment.rank`` which share this is."""
    for key, want in (("decoder_sparse_step", 1), ("mlp_only_layers", []),
                      ("hidden_act", "silu"), ("use_sliding_window", False),
                      ("rope_scaling", None),
                      ("tie_word_embeddings", False)):
        if cfg.get(key, want) != want:
            raise ValueError(f"qwen3_next with {key} = {cfg[key]!r} is not "
                             f"built here (only {want!r} is)")
    published = cfg.get("published", {})
    held = cfg["num_experts"]
    return Qwen3NextDims(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"],
        full_every=cfg["full_attention_interval"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        rotary_dim=int(cfg["head_dim"] * cfg["partial_rotary_factor"]),
        lin_k_heads=cfg["linear_num_key_heads"],
        lin_v_heads=cfg["linear_num_value_heads"],
        lin_k_dim=cfg["linear_key_head_dim"],
        lin_v_dim=cfg["linear_value_head_dim"],
        conv_kernel=cfg["linear_conv_kernel_dim"],
        d_expert=cfg["moe_intermediate_size"],
        d_shared=cfg["shared_expert_intermediate_size"],
        n_routed=published.get("num_experts", held), experts_held=held,
        first_expert=cfg.get("deployment", {}).get("rank", 0) * held,
        top_k=cfg["num_experts_per_tok"], route_norm=cfg["norm_topk_prob"],
        rope_theta=float(cfg["rope_theta"]), eps=cfg["rms_norm_eps"],
        max_len=cfg["max_position_embeddings"])


def build_qwen3_next(config, *, dtype=jnp.bfloat16, param_dtype=jnp.bfloat16,
                     attn_block: int = ATTN_BLOCK) -> Qwen3NextLM:
    """The one constructor, from a configuration's dict
    (``models.build_model_from_config`` comes here)."""
    return Qwen3NextLM(dims_from_config(config), dtype=dtype,
                       param_dtype=param_dtype, attn_block=attn_block)
