"""The ``granitemoehybrid`` decoder (IBM's Granite 4.0-H): the fifth LM
block, beside ``transformer_lm.py``'s GPT-2 one, ``afmoe.py``'s,
``qwen3_next.py``'s and ``bailing_hybrid.py``'s.  Nine layers in ten mix
tokens by a Mamba-2 state-space layer (a fixed-size recurrent state, no
cache rows), the tenth by grouped-query softmax attention with NO
position encoding; every layer's feed-forward is a softmax-routed expert
layer beside a shared MLP.  Four multipliers scale the embedding, every
residual branch, the attention scores and the logits.

With d the hidden size, ``RMS(x; w) = x / sqrt(mean(x^2) + eps) * w``, no
bias but the convolution's::

    h_0 = embedding_multiplier * E[token]
    layer i:  h = h + residual_multiplier * mix_i(RMS(h; w_in))
              h = h + residual_multiplier * (moe(m) + shared(m)),
                                            m = RMS(h; w_post)
              mix_i = attn where layer_types[i] == "attention", else ssm
    head:     logits = RMS(h; w_f) E^T / logits_scaling   (float32, tied)

    ssm(a):   [z | xBC] = a W_in;  dt = a W_dt      (the source's one
                                    in_proj, stored as two blocks)
              xBC = silu(conv(xBC) + b_conv)        depthwise, causal,
                                    kernel 4; its state the last 3 inputs
              x [H, P], B [N], C [N] = split(xBC)   one B, C for all heads
              dt = softplus(dt + dt_bias);  g = -exp(A_log) dt    float32
              per head, S in R^{P x N}, S_0 = 0, float32:
                  S = exp(g_t) S + (dt_t x_t) B_t^T;  y_t = S C_t + D x_t
              y = RMS(y * silu(z); w_y)             the gate first, ONE
                                    norm over all H P features
              return y W_out

    attn(a):  q, k, v = a Wq, a Wk, a Wv            Hq, Hkv, Hkv heads
              o = softmax(attention_multiplier q k^T) v     causal, float32,
                                    no position enters anywhere
              return o Wo

    moe(m):   l = m Wr (float32); the top k of l; w = softmax over those k
              return sum_e w_e ffn_e(m);  ffn(m; G, U, D) = (silu(m G) *
              (m U)) D;  shared(m) = ffn(m) at its own width, weight 1

**One definition of a block** (:class:`GraniteMoeHybridBlock`) with the
two methods the shell of ``served_lm.py`` walks, ``sequence`` and ``step``;
:class:`GraniteMoeHybridLM` is that shell and states each layer's cache:
an attention layer holds ``cache_len`` K/V rows a slot (kind ``full``), a
state-space layer NO rows but a state of a fixed size (kind ``state``):
``S [H, P, N]`` float32 and the convolution's last 3 inputs.  Prefill
writes the state at the prompt's true length (``ops/state_space.py``
leaves padding out); a parked slot (position 0) neither decays nor writes.

**The expert layer holds a share** (``ops/moe.py``), as the other three
served blocks': ``experts_held`` of ``n_routed`` from ``first_expert``
on.  ``num_local_experts`` 0 is the family's dense sibling's layer: the
shared MLP alone.

Parameters are stored in ``param_dtype`` (bfloat16 in serving), as are
activations, K/V rows and the convolution's state; norms, the router,
softmax, ``dt``, the decay, the recurrent state, the residual sums'
multiplier and logits are float32.
"""

from __future__ import annotations

import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp

from distributedtensorflowexample_tpu.models.served_lm import (
    CacheLayer, ServedLM, gated_params, log_uniform, rms_norm)
from distributedtensorflowexample_tpu.ops import linear_attention as la
from distributedtensorflowexample_tpu.ops import moe
from distributedtensorflowexample_tpu.ops import state_space as ss
from distributedtensorflowexample_tpu.ops.attention import (
    ATTN_BLOCK, decode_attention, decode_fetch_block, grouped_attention)

F32 = jnp.float32
MAMBA, ATTENTION = "mamba", "attention"


@dataclasses.dataclass(frozen=True)
class GraniteMoeHybridDims:
    """Every size of the architecture (hashable: a flax field)."""
    vocab_size: int
    d_model: int
    layer_types: tuple          # "mamba" | "attention" a layer
    n_heads: int                # attention: query heads ...
    n_kv_heads: int             # ... key/value heads, of d_model / n_heads
    ssm_heads: int              # Mamba-2: heads (the state's) ...
    ssm_head_dim: int           # ... of this many features (P) ...
    ssm_state: int              # ... each against a state this wide (N)
    conv_kernel: int
    conv_bias: bool
    d_expert: int               # each routed expert's width
    d_shared: int               # the shared MLP's
    n_routed: int               # experts the router scores (0: dense)
    experts_held: int           # experts this share computes ...
    first_expert: int           # ... from this id on
    top_k: int
    embedding_multiplier: float
    residual_multiplier: float
    attention_multiplier: float
    logits_scaling: float
    eps: float
    max_len: int
    init_std: float = 0.02

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    @property
    def d_inner(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def conv_dim(self) -> int:
        """``[x | B | C]``: what the convolution runs over."""
        return self.d_inner + 2 * self.ssm_state


def _step_bias(lo: float, hi: float):
    """The bias whose softplus is log-uniform in ``[lo, hi]``."""
    def init(key, shape, dtype=F32):
        step = jnp.exp(jax.random.uniform(key, shape, F32, jnp.log(lo),
                                          jnp.log(hi)))
        return (step + jnp.log(-jnp.expm1(-step))).astype(dtype)
    return init


class GraniteMoeHybridBlock(nn.Module):
    """One layer: a Mamba-2 mixer or NoPE attention, then the expert
    feed-forward beside the shared MLP."""
    dims: GraniteMoeHybridDims
    attention: bool
    dtype: jnp.dtype = jnp.bfloat16
    param_dtype: jnp.dtype = jnp.float32
    attn_block: int = ATTN_BLOCK

    def setup(self):
        c, pd = self.dims, self.param_dtype
        ones = nn.initializers.ones
        w = nn.initializers.normal(c.init_std)
        d = c.d_model
        P = self.param
        self.norm_in = P("norm_in", ones, (d,), pd)
        self.norm_post = P("norm_post", ones, (d,), pd)
        if self.attention:
            qd, kd = c.n_heads * c.head_dim, c.n_kv_heads * c.head_dim
            self.wq = P("wq", w, (d, qd), pd)
            self.wk = P("wk", w, (d, kd), pd)
            self.wv = P("wv", w, (d, kd), pd)
            self.wo = P("wo", w, (qd, d), pd)
        else:
            self.w_in = P("w_in", w, (d, c.d_inner + c.conv_dim), pd)
            self.w_dt = P("w_dt", w, (d, c.ssm_heads), pd)
            self.conv = P("conv", nn.initializers.normal(0.3),
                          (c.conv_kernel, c.conv_dim), pd)
            self.conv_bias = P("conv_bias", w, (c.conv_dim,), pd) \
                if c.conv_bias else None
            # Mamba-2's own: A in [1, 16], steps of 0.001 to 0.1.
            self.a_log = P("a_log", log_uniform(1.0, 16.0),
                           (c.ssm_heads,), F32)
            self.dt_bias = P("dt_bias", _step_bias(1e-3, 1e-1),
                             (c.ssm_heads,), F32)
            self.d_skip = P("d_skip", ones, (c.ssm_heads,), F32)
            self.norm_y = P("norm_y", ones, (c.d_inner,), pd)
            self.w_out = P("w_out", w, (c.d_inner, d), pd)
        f, E, fs = c.d_expert, c.experts_held, c.d_shared
        self.shared = gated_params(P, "shared", w, pd, d, fs)
        if c.n_routed:
            self.router = P("router", w, (d, c.n_routed), pd)
            self.held = gated_params(P, "experts", w, pd, d, f, E)

    def _residual(self, h, branch):
        """``h + residual_multiplier * branch``, the sum in float32 (the
        multiplier is no bfloat16 number)."""
        return (h.astype(F32) + self.dims.residual_multiplier
                * branch.astype(F32)).astype(h.dtype)

    # --- NoPE attention ----------------------------------------------------
    def _qkv(self, a):
        """a [..., d] -> q [..., Hq, Dh], k and v [..., Hkv, Dh]: nothing
        of a position enters."""
        c, dt = self.dims, self.dtype
        heads = lambda x, n: x.reshape(*x.shape[:-1], n, c.head_dim)
        return (heads(jnp.dot(a, self.wq.astype(dt)), c.n_heads),
                heads(jnp.dot(a, self.wk.astype(dt)), c.n_kv_heads),
                heads(jnp.dot(a, self.wv.astype(dt)), c.n_kv_heads))

    def _attn_out(self, o, like):
        """Every head's output of a token side by side (``like``'s
        leading shape), through the output projection."""
        return jnp.dot(o.reshape(*like.shape[:-1], -1),
                       self.wo.astype(self.dtype))

    # --- the Mamba-2 mixer -------------------------------------------------
    def _ssm_proj(self, a):
        """a [..., d] -> (z [..., d_inner], x | B | C before the
        convolution [..., conv_dim], dt and g [..., H] float32)."""
        c, dt = self.dims, self.dtype
        with jax.named_scope("ssm.proj"):
            zx = jnp.dot(a, self.w_in.astype(dt))
            step = jax.nn.softplus(jnp.dot(
                a, self.w_dt.astype(dt), preferred_element_type=F32)
                + self.dt_bias)
            return (zx[..., :c.d_inner], zx[..., c.d_inner:], step,
                    -jnp.exp(self.a_log) * step)

    def _ssm_split(self, y):
        """The convolution's output y [..., conv_dim] float32 -> x [...,
        H, P], B and C [..., N], in the activations' type."""
        c = self.dims
        u = jax.nn.silu(y).astype(self.dtype)
        x = u[..., :c.d_inner].reshape(*u.shape[:-1], c.ssm_heads,
                                       c.ssm_head_dim)
        return (x, u[..., c.d_inner:c.d_inner + c.ssm_state],
                u[..., c.d_inner + c.ssm_state:])

    def _ssm_out(self, y, x, z):
        """y [..., H, P] float32 (the state's reading), x [..., H, P], z
        [..., d_inner] -> [..., d]."""
        c = self.dims
        with jax.named_scope("ssm.out"):
            y = y + self.d_skip[:, None] * x.astype(F32)
            y = y.reshape(*y.shape[:-2], -1) * jax.nn.silu(z.astype(F32))
            y = rms_norm(y, self.norm_y, c.eps).astype(self.dtype)
            return jnp.dot(y, self.w_out.astype(self.dtype))

    # --- feed-forward ------------------------------------------------------
    def _ffn(self, h, live):
        """h [..., d], live [...] or None -> (h', stats int32[4])."""
        c, dt = self.dims, self.dtype
        m = rms_norm(h, self.norm_post, c.eps).reshape(-1, c.d_model)
        cast = lambda ws: tuple(x.astype(dt) for x in ws)
        with jax.named_scope("moe.shared"):
            f = moe.gated_ffn(m, *cast(self.shared))
        stats = jnp.zeros((len(moe.STATS),), jnp.int32)
        if c.n_routed:
            # Softmax over all, the top k, renormalised over the k: the
            # softmax over the k selected logits.
            sel, w = moe.route(m, self.router.astype(dt), None,
                               top_k=c.top_k, route_scale=1.0,
                               route_norm=True, score_func="softmax")
            routed, stats = moe.expert_ffn(
                m, sel, w, *cast(self.held), first_expert=c.first_expert,
                experts_known=c.n_routed,
                live=None if live is None else live.reshape(-1))
            f = f + routed
        return self._residual(h, f.reshape(h.shape)), stats

    # --- the two shapes of work --------------------------------------------
    def sequence(self, x, lengths=None):
        """A whole sequence from position 0: x [B, T, d], lengths [B]
        the live length of each row (None: T) -> (x', what the layer
        remembers, stats).  An attention layer remembers ``(k, v)``, each
        [B, T, Hkv, Dh]; a state-space layer ``(S [B, H, P, N] float32,
        the convolution's last inputs [B, K - 1, C])`` at each row's
        length."""
        c = self.dims
        B, T, _ = x.shape
        live = None if lengths is None else (
            jnp.arange(T)[None] < lengths[:, None])
        a = rms_norm(x, self.norm_in, c.eps)
        if self.attention:
            q, k, v = self._qkv(a)
            with jax.named_scope("attn.nope"):
                o = grouped_attention(q, k, v, block=self.attn_block,
                                      scale=c.attention_multiplier)
                x = self._residual(x, self._attn_out(o, x))
            kept = (k, v)
        else:
            z, xbc, dt, g = self._ssm_proj(a)
            with jax.named_scope("ssm.conv"):
                y, conv_state = la.causal_conv_sequence(
                    xbc, self.conv, lengths, self.conv_bias)
                xs, b, cc = self._ssm_split(y)
            with jax.named_scope("ssm.scan"):
                y, S = ss.ssd_sequence(
                    xs, dt, g, b, cc, jnp.zeros(
                        (B, c.ssm_heads, c.ssm_head_dim, c.ssm_state), F32),
                    live)
            x = self._residual(x, self._ssm_out(y, xs, z))
            kept = (S, conv_state)
        x, stats = self._ffn(x, live)
        return x, kept, stats

    def step(self, x, ck, cv, pos):
        """One token a slot: x [S, d], pos [S] its position, and what
        the layer remembers of each slot — an attention layer's K and V
        rows ``[S, R, Hkv, Dh]`` (the token's are written at its
        position, then the query reads rows ``0..pos``), a state-space
        layer's state and convolution state (read whole, written whole).
        A slot at ``pos == 0`` is parked: its token goes to no expert and
        its state stays as it is."""
        c = self.dims
        S = x.shape[0]
        live = pos > 0
        a = rms_norm(x, self.norm_in, c.eps)
        if self.attention:
            q, k, v = self._qkv(a)
            q = q.reshape(S, 1, c.n_kv_heads, -1, c.head_dim)
            with jax.named_scope("cache_update"):
                sl = jnp.arange(S)
                ck = ck.at[sl, pos].set(k)
                cv = cv.at[sl, pos].set(v)
            with jax.named_scope("attn.nope"):
                R = ck.shape[1]
                o = decode_attention(q, ck, cv,
                                     jnp.minimum(pos[:, None] + 1, R),
                                     scale=c.attention_multiplier)
                x = self._residual(x, self._attn_out(o, x))
        else:
            z, xbc, dt, g = self._ssm_proj(a)
            with jax.named_scope("ssm.conv"):
                y, cv = la.causal_conv_step(xbc, self.conv, cv, live,
                                            self.conv_bias)
                xs, b, cc = self._ssm_split(y)
            with jax.named_scope("ssm.step"):
                y, ck = ss.ssd_step(xs, dt, g, b, cc, ck, live)
            x = self._residual(x, self._ssm_out(y, xs, z))
        x, stats = self._ffn(x, live)
        return x, ck, cv, stats


class GraniteMoeHybridLM(ServedLM):
    """The shell (``served_lm.py``) over :class:`GraniteMoeHybridBlock`:
    a scaled embedding that is the head too, K/V rows in the attention
    layers, Mamba-2's states in the others."""
    dims: GraniteMoeHybridDims

    #: Two prompts of 1,024, beside a chip the states of many slots have
    #: nearly filled.
    prefill_positions_max = 2048
    expert_slots = property(lambda self: self.dims.experts_held
                            * len(self.dims.layer_types))

    def make_block(self, i):
        return GraniteMoeHybridBlock(
            self.dims, self.dims.layer_types[i] == ATTENTION, self.dtype,
            self.param_dtype, self.attn_block, name=f"block{i}")

    def cache_layers(self, cache_len: int) -> tuple:
        """An attention layer holds ``cache_len`` K/V rows ``[Hkv, Dh]`` a
        slot; a state-space layer no rows but its state ``[H, P, N]``
        float32 and the convolution's last inputs ``[K - 1, C]``."""
        c = self.dims
        kv = ((cache_len, c.n_kv_heads, c.head_dim), self.dtype)
        full = CacheLayer("full", cache_len, kv, kv)
        state = CacheLayer(
            "state", 0, ((c.ssm_heads, c.ssm_head_dim, c.ssm_state), F32),
            ((c.conv_kernel - 1, c.conv_dim), self.dtype))
        return tuple(full if kind == ATTENTION else state
                     for kind in c.layer_types)

    def _embed(self, tokens):
        x = self.embed.astype(self.dtype)[tokens]
        return x * jnp.asarray(self.dims.embedding_multiplier, self.dtype)

    def _logits(self, x):
        """The tied head (so no ``head``): ``RMS(x) E^T / logits_scaling``."""
        with jax.named_scope("head"):
            x = rms_norm(x, self.norm_f, self.dims.eps)
            logits = jax.lax.dot_general(
                x, self.embed.astype(self.dtype),
                (((x.ndim - 1,), (1,)), ((), ())),
                preferred_element_type=F32)
            return logits / self.dims.logits_scaling

    def decode_fetch_block(self, rows: int) -> int:
        c = self.dims
        return rows and decode_fetch_block(rows, c.n_kv_heads, c.head_dim)


def dims_from_config(cfg: dict) -> GraniteMoeHybridDims:
    """The sizes of a configuration in the source's own keys (a
    ``granitemoehybrid`` ``config.json``).  One chip's share of an
    expert-parallel deployment is stated as the other served models read
    it: ``num_local_experts`` the experts HELD, ``published.
    num_local_experts`` the router's width, ``deployment.rank`` which
    share this is.  What the block does not compute is refused by name,
    not guessed."""
    for key, want in (("position_embedding_type", "nope"),
                      ("mamba_n_groups", 1), ("mamba_proj_bias", False),
                      ("attention_bias", False), ("hidden_act", "silu"),
                      ("normalization_function", "rmsnorm"),
                      ("tie_word_embeddings", True)):
        if cfg.get(key, want) != want:
            raise ValueError(
                f"granitemoehybrid with {key} = {cfg[key]!r} is not built "
                f"here (only {want!r} is)")
    kinds = tuple(cfg["layer_types"])
    if len(kinds) != cfg["num_hidden_layers"] or set(kinds) - {MAMBA,
                                                               ATTENTION}:
        raise ValueError(
            f"layer_types names {len(kinds)} layers of kinds "
            f"{sorted(set(kinds))}; num_hidden_layers says "
            f"{cfg['num_hidden_layers']}, and the kinds built here are "
            f"{MAMBA!r} and {ATTENTION!r}")
    d = cfg["hidden_size"]
    if cfg["mamba_expand"] * d != cfg["mamba_n_heads"] * cfg["mamba_d_head"]:
        raise ValueError(
            f"mamba_expand {cfg['mamba_expand']} x hidden_size {d} is not "
            f"mamba_n_heads {cfg['mamba_n_heads']} x mamba_d_head "
            f"{cfg['mamba_d_head']}")
    held = cfg["num_local_experts"]
    return GraniteMoeHybridDims(
        vocab_size=cfg["vocab_size"], d_model=d, layer_types=kinds,
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        ssm_heads=cfg["mamba_n_heads"], ssm_head_dim=cfg["mamba_d_head"],
        ssm_state=cfg["mamba_d_state"], conv_kernel=cfg["mamba_d_conv"],
        conv_bias=bool(cfg["mamba_conv_bias"]),
        d_expert=cfg["intermediate_size"],
        d_shared=cfg["shared_intermediate_size"],
        n_routed=cfg.get("published", {}).get("num_local_experts", held),
        experts_held=held,
        first_expert=cfg.get("deployment", {}).get("rank", 0) * held,
        top_k=cfg["num_experts_per_tok"],
        embedding_multiplier=float(cfg["embedding_multiplier"]),
        residual_multiplier=float(cfg["residual_multiplier"]),
        attention_multiplier=float(cfg["attention_multiplier"]),
        logits_scaling=float(cfg["logits_scaling"]),
        eps=cfg["rms_norm_eps"], max_len=cfg["max_position_embeddings"])


def build_granitemoehybrid(config, *, dtype=jnp.bfloat16,
                           param_dtype=jnp.bfloat16,
                           attn_block: int = ATTN_BLOCK
                           ) -> GraniteMoeHybridLM:
    """The one constructor, from a configuration's dict
    (``models.build_model_from_config`` comes here)."""
    return GraniteMoeHybridLM(dims_from_config(config), dtype=dtype,
                              param_dtype=param_dtype, attn_block=attn_block)
