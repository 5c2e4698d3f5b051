"""The ``afmoe`` decoder (Arcee's Trinity family): a current LM block
beside ``transformer_lm.py``'s GPT-2 one.

A layer (d the hidden size, ``RMS(x; g) = x / sqrt(mean(x^2) + eps) * g``,
no bias anywhere)::

    a = RMS(h; g_in)
    q, k, v, u = a Wq, a Wk, a Wv, a Wg       Hq query, Hkv key/value heads
    q, k = RMS(q; g_q), RMS(k; g_k)           over each head's features
    q, k = rope(q, k; position)               on a window layer only
    p = softmax(q k^T / sqrt(Dh))             causal; a window layer sees
                                              only the last `window` keys
    h = h + RMS(((p v) * sigmoid(u)) Wo; g_post_attn)
    m = RMS(h; g_pre_mlp)
    f = ffn(m)                                a leading dense layer, or
    f = ffn_shared(m) + sum_e w_e ffn_e(m)    top-k of a sigmoid router
    h = h + RMS(f; g_post_mlp)

with ``ffn(m; G, U, D) = (silu(m G) * (m U)) D``; the embedding is
scaled by ``sqrt(d)``, the head is untied, logits are float32.

**One definition of a block** (:class:`AfmoeBlock`) with the two methods
the shell of ``served_lm.py`` walks: ``sequence`` (a whole sequence: the
forward, and prefill, which also keeps the K/V it made) and ``step`` (a
K-token window per slot against that slot's cache rows; plain decode is
K == 1).  There is no serving twin: :class:`AfmoeLM` is that shell and
states each layer's cache rows: a full-attention layer holds
``cache_len`` rows a slot, a window layer ``min(window, cache_len)`` rows
as a ring, a position's row being ``position mod rows``.

**The expert layer holds a share** (``ops/moe.py``): ``experts_held``
experts from ``first_expert`` on, of the ``n_routed`` the router scores.
What the absent experts would add is left out.  The whole model is the
share with ``experts_held == n_routed``.

Parameters are stored in ``param_dtype`` (bfloat16 in serving); norms,
the router's scores, softmax and logits are computed in float32.
"""

from __future__ import annotations

import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp

from distributedtensorflowexample_tpu.models.served_lm import (
    CacheLayer, ServedLM, gated_params, rms_norm)
from distributedtensorflowexample_tpu.ops import moe
from distributedtensorflowexample_tpu.ops.attention import (
    ATTN_BLOCK, decode_attention, decode_fetch_block, grouped_attention)

WINDOW, FULL = "sliding_attention", "full_attention"


@dataclasses.dataclass(frozen=True)
class AfmoeDims:
    """Every size of the architecture (hashable: a flax field)."""
    vocab_size: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int                   # the leading dense layers' width
    d_expert: int               # each routed and the shared expert's width
    layer_types: tuple          # WINDOW | FULL, one per layer
    n_dense_layers: int
    n_routed: int               # experts the router scores
    experts_held: int           # experts this share computes ...
    first_expert: int           # ... from this id on
    top_k: int
    n_shared: int
    route_scale: float
    route_norm: bool
    window: int
    rope_theta: float
    eps: float
    max_len: int
    embed_scale: float          # sqrt(d_model) under muP, else 1
    init_std: float = 0.02


def _rope(x, positions, theta):
    """Rotary positions on ``x [..., T, H, Dh]`` at ``positions [...,
    T]``: the two halves of a head's features rotate as pairs."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * inv
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., :half], xf[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


class AfmoeBlock(nn.Module):
    """One layer: attention of its kind (``window`` 0 means full) and a
    dense or an expert feed-forward."""
    dims: AfmoeDims
    window: int
    experts: bool
    dtype: jnp.dtype = jnp.bfloat16
    param_dtype: jnp.dtype = jnp.float32
    attn_block: int = ATTN_BLOCK

    def setup(self):
        c, pd = self.dims, self.param_dtype
        ones = nn.initializers.ones
        w = nn.initializers.normal(c.init_std)
        d, qd, kd = c.d_model, c.n_heads * c.head_dim, \
            c.n_kv_heads * c.head_dim
        P = self.param
        for name in ("norm_in", "norm_post_attn", "norm_pre_mlp",
                     "norm_post_mlp"):
            setattr(self, name, P(name, ones, (d,), pd))
        self.norm_q = P("norm_q", ones, (c.head_dim,), pd)
        self.norm_k = P("norm_k", ones, (c.head_dim,), pd)
        self.wq = P("wq", w, (d, qd), pd)
        self.wk = P("wk", w, (d, kd), pd)
        self.wv = P("wv", w, (d, kd), pd)
        self.wg = P("wg", w, (d, qd), pd)
        self.wo = P("wo", w, (qd, d), pd)
        if not self.experts:
            self.ffn = gated_params(P, "ffn", w, pd, d, c.d_ff)
            return
        f, E, fs = c.d_expert, c.experts_held, c.n_shared * c.d_expert
        self.router = P("router", w, (d, c.n_routed), pd)
        self.router_bias = P("router_bias", nn.initializers.normal(0.01),
                             (c.n_routed,), jnp.float32)
        self.shared = gated_params(P, "shared", w, pd, d, fs)
        self.held = gated_params(P, "experts", w, pd, d, f, E)

    # --- attention ---------------------------------------------------------
    def _qkvu(self, h, positions):
        """h [..., T, d], positions [..., T] -> q [..., T, Hq, Dh], k and
        v [..., T, Hkv, Dh], the gate's input u [..., T, Hq Dh]."""
        c, dt = self.dims, self.dtype
        a = rms_norm(h, self.norm_in, c.eps)
        heads = lambda x, n: x.reshape(*x.shape[:-1], n, c.head_dim)
        q = heads(jnp.dot(a, self.wq.astype(dt)), c.n_heads)
        k = heads(jnp.dot(a, self.wk.astype(dt)), c.n_kv_heads)
        v = heads(jnp.dot(a, self.wv.astype(dt)), c.n_kv_heads)
        u = jnp.dot(a, self.wg.astype(dt))
        q = rms_norm(q, self.norm_q, c.eps)
        k = rms_norm(k, self.norm_k, c.eps)
        if self.window:
            q = _rope(q, positions, c.rope_theta)
            k = _rope(k, positions, c.rope_theta)
        return q, k, v, u

    def _attn_out(self, h, o, u):
        o = o.reshape(u.shape) * jax.nn.sigmoid(
            u.astype(jnp.float32)).astype(o.dtype)
        o = jnp.dot(o, self.wo.astype(self.dtype))
        return h + rms_norm(o, self.norm_post_attn, self.dims.eps)

    # --- feed-forward --------------------------------------------------------
    def _ffn(self, h, live):
        """h [..., d], live [...] or None -> (h', stats int32[4])."""
        c, dt = self.dims, self.dtype
        m = rms_norm(h, self.norm_pre_mlp, c.eps).reshape(-1, c.d_model)
        cast = lambda ws: tuple(x.astype(dt) for x in ws)
        if not self.experts:
            f = moe.gated_ffn(m, *cast(self.ffn))
            stats = jnp.zeros((len(moe.STATS),), jnp.int32)
        else:
            sel, w = moe.route(m, self.router.astype(dt), self.router_bias,
                               top_k=c.top_k, route_scale=c.route_scale,
                               route_norm=c.route_norm)
            f, stats = moe.expert_ffn(
                m, sel, w, *cast(self.held), first_expert=c.first_expert,
                experts_known=self.router.shape[1],
                live=None if live is None else live.reshape(-1))
            with jax.named_scope("moe.shared"):
                f = f + moe.gated_ffn(m, *cast(self.shared))
        f = rms_norm(f.reshape(h.shape), self.norm_post_mlp, c.eps)
        return h + f, stats

    # --- the two shapes of work --------------------------------------------
    def sequence(self, x, live=None):
        """A whole sequence: x [B, T, d], live [B, T] (false on padding)
        -> (x', (k [B, T, Hkv, Dh], v), stats).  The training-shape
        forward and prefill are this one method."""
        T = x.shape[1]
        q, k, v, u = self._qkvu(x, jnp.arange(T)[None])
        with jax.named_scope("attn.window" if self.window else "attn.full"):
            o = grouped_attention(q, k, v, window=self.window,
                                  block=self.attn_block)
        x, stats = self._ffn(self._attn_out(x, o, u), live)
        return x, (k, v), stats

    def step(self, x, ck, cv, pos):
        """A K-token window per slot: x [S, K, d], this layer's cache
        rows ck/cv [S, R, Hkv, Dh], pos [S] the position the window
        starts at.  The window's K/V are written first, then read: query
        j sees positions <= pos + j.

        A full layer writes row = position.  A window layer's R rows are
        a ring, row = position mod R: after the write, row r holds the
        newest position <= pos congruent to r, and the query reads the
        rows whose position is not negative (R <= window, so all of them
        lie inside its window).  A ring takes ONE token a step: the keys
        of a longer window would overwrite rows its earlier queries
        still see, which is also why nothing that rolls a cache back
        serves a model with window layers (serving/engine.py).  A slot at
        ``pos == 0`` is parked: its tokens go to no expert.

        Either way the rows a query sees LEAD the layer's rows, so the
        read is one op for both kinds, ``ops.attention.decode_attention``
        with the count of visible rows: for one token a slot on a TPU the
        ragged kernel, which fetches a slot's visible rows and no others
        (a parked slot costs one block); the einsum chain over all ``R``
        rows, dead ones masked, for a K > 1 window and on the CPU."""
        S, K, _ = x.shape
        R = ck.shape[1]
        c = self.dims
        if self.window and K > 1:
            raise ValueError(
                f"a window layer's ring takes one token a step, not {K}: "
                f"the later keys would overwrite rows the earlier queries "
                f"still see")
        positions = pos[:, None] + jnp.arange(K, dtype=pos.dtype)[None]
        q, k, v, u = self._qkvu(x, positions)
        q = q.reshape(S, K, c.n_kv_heads, -1, c.head_dim)
        sl = jnp.arange(S)[:, None]
        with jax.named_scope("cache_update"):
            rows = jnp.mod(positions, R) if self.window else positions
            ck = ck.at[sl, rows].set(k)
            cv = cv.at[sl, rows].set(v)
        with jax.named_scope("attn.window" if self.window else "attn.full"):
            # The visible rows lead: a full layer's are 0..position; a
            # ring's row r holds a position >= 0 iff r <= position or the
            # ring has wrapped, and then every row does.
            o = decode_attention(q, ck, cv, jnp.minimum(positions + 1, R))
        live = jnp.broadcast_to((pos > 0)[:, None], (S, K))
        x, stats = self._ffn(self._attn_out(x, o, u), live)
        return x, ck, cv, stats


class AfmoeLM(ServedLM):
    """The shell (``served_lm.py``) over :class:`AfmoeBlock`: an embedding
    scaled under muP, window and full layers, its own ladder of prompt
    lengths, and a K-token ``verify``."""
    dims: AfmoeDims

    #: Two prompts of the longest bucket the benchmark's cell uses, 3.2 GB
    #: of activations at the published widths.
    prefill_positions_max = 32768
    expert_slots = property(lambda self: self.dims.experts_held * (
        len(self.dims.layer_types) - self.dims.n_dense_layers))

    def make_block(self, i):
        c = self.dims
        return AfmoeBlock(
            c, c.window if c.layer_types[i] == WINDOW else 0,
            i >= c.n_dense_layers, self.dtype, self.param_dtype,
            self.attn_block, name=f"block{i}")

    def cache_layers(self, cache_len: int) -> tuple:
        """A full-attention layer holds ``cache_len`` rows ``[Hkv, Dh]`` a
        slot, a window layer ``min(window, cache_len)`` as a ring."""
        c = self.dims

        def layer(kind, rows):
            kv = ((rows, c.n_kv_heads, c.head_dim), self.dtype)
            return CacheLayer(kind, rows, kv, kv)
        return tuple(layer("window", min(c.window, cache_len))
                     if kind == WINDOW else layer("full", cache_len)
                     for kind in c.layer_types)

    def _embed(self, tokens):
        x = self.embed.astype(self.dtype)[tokens]
        return x * jnp.asarray(self.dims.embed_scale, self.dtype)

    def _real(self, toks, lengths):
        """This family's blocks take the mask [B, P], made once."""
        return jnp.arange(toks.shape[1])[None] < lengths[:, None]

    def prefill_buckets(self, cache_len: int):
        """The lengths a prompt is padded to, one prefill program each,
        from this model's own sizes; ``None`` (the engine's powers of
        two) where the window or the cache is shorter than one attention
        tile.  Every bucket is a program that set-up loads and that
        stays on the chip, and every padded position is computed for
        nothing, so the ladder is fine where padding costs most:

        * one bucket up to a tile (``attn_block`` positions, where
          ``grouped_attention`` changes path anyway), powers of two from
          there to the window;
        * above the window, where a position costs its matmuls and a
          whole window of keys and a power of two would pad up to half
          of the largest programs, each power of two and its
          one-and-a-half: padding at most a third, two buckets an octave
          however long the cache;
        * every bucket whole tiles (``ops/attention.takes_splash`` asks
          for that), ``cache_len`` last whatever it is.

        PERF.md section 6 (PR 31) has what each choice measured."""
        tile = self.attn_block
        if min(self.dims.window, cache_len) < tile:
            return None
        w = -(-self.dims.window // tile)            # the window, in tiles
        tiles, t = set(), 1
        while t < w:
            tiles.add(t)
            t *= 2
        t = w
        while t * tile < cache_len:
            tiles.update((t, -(-3 * t // 2)))
            t *= 2
        return tuple(sorted(t * tile for t in tiles
                            if t * tile < cache_len)) + (cache_len,)

    def decode_fetch_block(self, rows: int) -> int:
        c = self.dims
        return decode_fetch_block(rows, c.n_kv_heads, c.head_dim)

    def verify(self, toks, positions, ck, cv):
        """toks [S, K], positions [S] -> (logits [S, K, V] f32, ck, cv,
        stats): the K-token step (see AfmoeBlock.step) — the shell's walk
        over the blocks, which never looks at a window's length."""
        return super().decode(toks, positions, ck, cv)

    def decode(self, tok, positions, ck, cv):
        """tok [S] -> (logits [S, V], ck, cv, stats): the K == 1 window
        of :meth:`verify`, not a second program."""
        logits, ck, cv, stats = self.verify(tok[:, None], positions, ck, cv)
        return logits[:, 0], ck, cv, stats


def dims_from_config(cfg: dict) -> AfmoeDims:
    """The sizes of a configuration in the source's own keys (an
    ``afmoe`` ``config.json``).  A configuration that is one chip's
    share of an expert-parallel deployment says so beside them:
    ``num_experts`` is then the experts HELD, ``published.num_experts``
    the router's width, and ``deployment.rank`` which share this is."""
    published = cfg.get("published", {})
    held = cfg["num_experts"]
    d = cfg["hidden_size"]
    return AfmoeDims(
        vocab_size=cfg["vocab_size"], d_model=d,
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["intermediate_size"], d_expert=cfg["moe_intermediate_size"],
        layer_types=tuple(cfg["layer_types"]),
        n_dense_layers=cfg["num_dense_layers"],
        n_routed=published.get("num_experts", held), experts_held=held,
        first_expert=cfg.get("deployment", {}).get("rank", 0) * held,
        top_k=cfg["num_experts_per_tok"],
        n_shared=cfg["num_shared_experts"],
        route_scale=cfg["route_scale"], route_norm=cfg["route_norm"],
        window=cfg["sliding_window"], rope_theta=float(cfg["rope_theta"]),
        eps=cfg["rms_norm_eps"], max_len=cfg["max_position_embeddings"],
        embed_scale=d ** 0.5 if cfg.get("mup_enabled") else 1.0)


def build_afmoe(config, *, dtype=jnp.bfloat16, param_dtype=jnp.bfloat16,
                attn_block: int = ATTN_BLOCK) -> AfmoeLM:
    """The one constructor, from a configuration's dict
    (``models.build_model_from_config`` reads the file and comes here:
    the benchmark's family, ``serving/promote.py`` and
    ``tools/serve_lm.py --model_config`` all do)."""
    if len(config["layer_types"]) != config["num_hidden_layers"]:
        raise ValueError(
            f"layer_types names {len(config['layer_types'])} layers, "
            f"num_hidden_layers says {config['num_hidden_layers']}")
    return AfmoeLM(dims_from_config(config), dtype=dtype,
                   param_dtype=param_dtype, attn_block=attn_block)
