"""Plain reference for the ``kimi_k2`` configurations (Moonshot's Kimi K2
family: DeepSeek-V3's block at Kimi's sizes): the forward pass in
straightforward ``jax.numpy``, float32, every matrix multiplication at
``Precision.HIGHEST``, latent attention in its EXPANDED form only (every
position's row expanded to the keys and values of every head, a causal
softmax position by position), the YaRN frequencies computed from the
formula.  No absorbed form, no cache, no kernels, no grouped products,
no batching; it imports nothing of the program and is never given an
array the program has made.  What it shares with ``reference/afmoe.py``
(the matmul of a precision, the SiLU-gated feed-forward, the embedding,
the head) it takes from there.

The equations (d the hidden size, ``RMS(x; g) = x / sqrt(mean(x^2) +
eps) * g``, no bias anywhere; a dagger marks a reading that is a
convention and not a certainty, each listed under ``assumed`` in the
configuration's file)::

    layer i:  h = h + mla(RMS(h; g_in));  h = h + ffn_i(RMS(h; g_post))
              ffn_i = dense for i < first_k_dense_replace, else moe
    logits = RMS(h; g_f) W_head                             (untied)

    mla(a):   c_q = RMS(a W_qa; g_q);  q_h = [q_nope | q_pe] = c_q W_qb
              [c_kv | k_pe] = a W_kva;  c = RMS(c_kv; g_c)
              rope on q_pe and on the one k_pe, interleaved pairs (†)
              k_nope_h = c W_UK,h;  v_h = c W_UV,h
                          (the published kv_b_proj is [W_UK | W_UV] a head;
                          both are stored head first, [H, rank, D])
              o_h = softmax(scale q_h . [k_nope_h | k_pe]) v_h    causal
              return [o_1 .. o_H] W_o                       no gate

    yarn:     f_i = theta^(-2i / Dr), i = 0 .. Dr/2 - 1
              corr(r) = Dr ln(original / (2 pi r)) / (2 ln theta)
              low = floor(corr(beta_fast)), high = ceil(corr(beta_slow))
              ramp_i = clip((i - low) / (high - low), 0, 1)
              inv_freq_i = f_i (1 - ramp_i) + (f_i / factor) ramp_i
              m(x) = 0.1 x ln factor + 1
              cos, sin times m(mscale) / m(mscale_all_dim)
              scale = (Dn + Dr)^-0.5 m(mscale_all_dim)^2

    moe(m):   s = sigmoid(m Wr);  sel = top-k of s + bias
              (n_group 1, topk_group 1: no group limit)
              w = s[sel] / sum s[sel] * routed_scaling_factor
              return sum_{e in sel} w_e ffn_e(m) + ffn_shared(m)

The vision tower is left out (†): a text request passes through the
language model alone.

**The share**, as in ``reference/afmoe.py``: ``n_routed_experts`` experts
are held, ids ``deployment.rank * n_routed_experts`` onward, of the
``published.n_routed_experts`` the router scores; the sum over ``sel``
runs over the held experts only, and that partial result goes on.

It is handed the parameters as the family makes them (bfloat16, 7 GB at
the benchmark's size) and casts one matrix at a time; the dense
feed-forward (its ``G`` alone is 0.53 GB in float32) runs over blocks of
positions, attention over blocks of queries against all keys.

``precision`` selects the CONTROL the comparison must fail: every linear
layer (the experts' and the head included) computed as a lower precision
would; under a control the attention products run in bfloat16.
"""

from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp

from benchmarks.reference.afmoe import (        # noqa: F401 (PRECISIONS)
    F32, HIGHEST, PAD_TO, PRECISIONS, QUERY_BLOCK, _ffn, _rms, embed, head,
    make_matmul)

#: A held expert's biased score nearer than this to the boundary of the
#: selection is a near-tie (served_token_gaps).  Set from readings on the
#: chip at the published widths (PERF.md section 2): a flip of the routing
#: moves a logit by 0.2-0.64 and a token without one by under 0.1; at
#: 0.001 whole flips still pass on two requests in nine, at 0.002 on three
#: seeds in eleven (0.20, 0.36, 0.36), at 0.003 on none of fifteen
#: requests (74-77% of the served tokens judged, the widest judged gap
#: 0.10, int8's 0.63 and more); 0.004 and 0.006 read as 0.003 does and
#: judge 68% and 56%.
ROUTING_TIE = 0.003
#: Positions the dense feed-forward takes at a time: ``[2048, 18432]``
#: float32 is 151 MB where the whole of a 10,240-token sequence is 755.
FFN_BLOCK = 2048


def yarn_frequencies(cfg: dict) -> tuple:
    """``(inv_freq [Dr / 2], what cos and sin are multiplied by, the
    softmax's scale)`` from the formula; plain rope where the
    configuration scales nothing."""
    Dr = cfg["qk_rope_head_dim"]
    theta = float(cfg["rope_theta"])
    i = jnp.arange(Dr // 2, dtype=F32)
    f = theta ** (-2.0 * i / Dr)
    scale = 1.0 / math.sqrt(cfg["qk_nope_head_dim"] + Dr)
    y = cfg.get("rope_scaling")
    if y is None:
        return f, 1.0, scale
    factor, orig = y["factor"], y["original_max_position_embeddings"]
    corr = lambda r: Dr * math.log(orig / (2 * math.pi * r)) / (
        2 * math.log(theta))
    low = max(math.floor(corr(y["beta_fast"])), 0)
    high = min(math.ceil(corr(y["beta_slow"])), Dr - 1)
    ramp = jnp.clip((i - low) / max(high - low, 0.001), 0.0, 1.0)
    m = lambda x: 0.1 * x * math.log(factor) + 1.0 if factor > 1 else 1.0
    return (f * (1.0 - ramp) + f / factor * ramp,
            m(y["mscale"]) / m(y["mscale_all_dim"]),
            scale * m(y["mscale_all_dim"]) ** 2)


def _rope_pairs(x, inv_freq, mult):
    """x [T, H, Dr] at positions 0..T-1: features (2i, 2i + 1) rotate as
    a pair."""
    T = x.shape[0]
    ang = jnp.arange(T, dtype=F32)[:, None] * inv_freq[None]
    cos, sin = jnp.cos(ang)[:, None] * mult, jnp.sin(ang)[:, None] * mult
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape)


def _causal_attention(q, k, v, scale, dtype):
    """q, k [T, H, Dh], v [T, H, Dv] -> [T, H, Dv]; blocks of queries
    against all keys."""
    T, H, Dh = q.shape
    k, v = k.astype(dtype), v.astype(dtype)
    pad = -T % QUERY_BLOCK
    qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).astype(dtype)
    qb = qb.reshape(-1, QUERY_BLOCK, H, Dh)
    s_pos = jnp.arange(T)

    def block(args):
        qi, t0 = args
        t_pos = t0 + jnp.arange(QUERY_BLOCK)
        s = jnp.einsum("thd,shd->hts", qi, k, precision=HIGHEST,
                       preferred_element_type=F32) * scale
        ok = s_pos[None] <= t_pos[:, None]
        p = jax.nn.softmax(jnp.where(ok[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hts,shd->thd", p.astype(dtype), v,
                          precision=HIGHEST, preferred_element_type=F32)

    starts = jnp.arange(qb.shape[0]) * QUERY_BLOCK
    return jax.lax.map(block, (qb, starts)).reshape(-1, H, v.shape[-1])[:T]


def _by_head(w):
    """A head-first up-projection ``[H, rank, D]`` as the one matrix
    ``[rank, H * D]`` the rows are multiplied by."""
    return jnp.moveaxis(w, 0, 1).reshape(w.shape[1], -1)


def latent_attention(a, p, cfg: dict, mm, dtype):
    """a [T, d] -> [T, d]: latent attention, expanded."""
    T, H = a.shape[0], cfg["num_attention_heads"]
    Dn, rank = cfg["qk_nope_head_dim"], cfg["kv_lora_rank"]
    inv_freq, mult, scale = yarn_frequencies(cfg)
    if cfg.get("q_lora_rank"):
        c_q = _rms(mm(a, p["wq_a"]), p["norm_q"], cfg["rms_norm_eps"])
        q = mm(c_q, p["wq_b"])
    else:
        q = mm(a, p["wq"])
    q = q.reshape(T, H, -1)
    kva = mm(a, p["w_kva"])
    c = _rms(kva[:, :rank], p["norm_c"], cfg["rms_norm_eps"])
    k_pe = _rope_pairs(kva[:, None, rank:], inv_freq, mult)     # [T, 1, Dr]
    q = jnp.concatenate(
        [q[..., :Dn], _rope_pairs(q[..., Dn:], inv_freq, mult)], -1)
    k_nope = mm(c, _by_head(p["w_uk"])).reshape(T, H, Dn)
    v = mm(c, _by_head(p["w_uv"])).reshape(T, H, -1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_pe, (T, H, k_pe.shape[-1]))], -1)
    o = _causal_attention(q, k, v, scale, dtype)
    return mm(o.reshape(T, -1), p["wo"])


def dense_ffn(m, p, mm):
    """The leading layers' SwiGLU over blocks of positions."""
    T = m.shape[0]
    pad = -T % FFN_BLOCK
    blocks = jnp.pad(m, ((0, pad), (0, 0))).reshape(-1, FFN_BLOCK, m.shape[1])
    one = lambda x: _ffn(x, p["ffn_gate"], p["ffn_up"], p["ffn_down"], mm)
    return jax.lax.map(one, blocks).reshape(-1, m.shape[1])[:T]


def held_experts(cfg: dict) -> tuple:
    """(first id, count) of the experts this share holds."""
    n = cfg["n_routed_experts"]
    return cfg.get("deployment", {}).get("rank", 0) * n, n


def biased_scores(m, p):
    """m [T, d] -> (s, c) [T, E]: the router's scores and what selects."""
    s = jax.nn.sigmoid(jnp.matmul(m, p["router"].astype(F32),
                                  precision=HIGHEST))
    return s, s + p["router_bias"].astype(F32)


def route(m, p, cfg):
    """m [T, d] -> (sel [T, k], w [T, k]): the experts each token selects
    among all the router's outputs, and their weights."""
    s, c = biased_scores(m, p)
    _, sel = jax.lax.top_k(c, cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(s, sel, axis=-1)
    if cfg["norm_topk_prob"]:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return sel, cfg["routed_scaling_factor"] * w


def expert_layer(m, p, cfg: dict, mm) -> tuple:
    """m [T, d] -> (the shared expert's part, the held experts' part):
    what every share computes alike, and what this share alone adds."""
    sel, w = route(m, p, cfg)
    first, held = held_experts(cfg)

    def one(acc, e):
        gate, up, down = (p[f"experts_{n}"][e] for n in ("gate", "up",
                                                         "down"))
        w_e = jnp.sum(jnp.where(sel == first + e, w, 0.0), axis=-1)
        return acc + w_e[:, None] * _ffn(m, gate, up, down, mm), None

    routed, _ = jax.lax.scan(one, jnp.zeros_like(m), jnp.arange(held))
    shared = _ffn(m, p["shared_gate"], p["shared_up"], p["shared_down"], mm)
    return shared, routed


def held_margin(m, p, cfg: dict):
    """m [T, d] -> [T]: how far each token's selection is from choosing
    another set of HELD experts — the least distance of a held expert's
    biased score from the boundary between the k-th and the (k+1)-th (a
    selected one against the best unselected score, an unselected one
    against the worst selected).  Below a precision's noise in the
    scores, that precision may select otherwise than float32 does."""
    k = cfg["num_experts_per_tok"]
    first, held = held_experts(cfg)
    c = biased_scores(m, p)[1]
    top = jax.lax.top_k(c, k + 1)[0]
    ours = c[:, first:first + held]
    edge = jnp.where(ours >= top[:, k - 1:k], ours - top[:, k:k + 1],
                     top[:, k - 1:k] - ours)
    return jnp.min(edge, axis=-1)


def layer(h, p, cfg: dict, index: int, precision: str = "f32"):
    """One layer on one sequence: h [T, d] float32 -> (h' [T, d], the
    tokens' :func:`held_margin`, infinite on a dense layer)."""
    mm = make_matmul(precision)
    eps = cfg["rms_norm_eps"]
    h = h + latent_attention(_rms(h, p["norm_in"], eps), p, cfg, mm,
                             F32 if precision == "f32" else jnp.bfloat16)
    m = _rms(h, p["norm_post"], eps)
    if index < cfg["first_k_dense_replace"]:
        return h + dense_ffn(m, p, mm), jnp.full((h.shape[0],), jnp.inf)
    return h + sum(expert_layer(m, p, cfg, mm)), held_margin(m, p, cfg)


def forward(params, tokens, cfg: dict, precision: str = "f32"):
    """tokens [B, T] int -> logits [B, T, vocab] float32 (the tests'
    size: whole sequences, every position's logits)."""
    def one(row):
        h = embed(params, row, cfg)
        for i in range(cfg["num_hidden_layers"]):
            h, _ = layer(h, params[f"block{i}"], cfg, i, precision)
        return head(params, h, cfg, precision)
    return jnp.stack([one(row) for row in tokens])


def hidden_and_margins(params, tokens, cfg: dict, precision: str = "f32"):
    """tokens [T] -> (the last layer's output [T, d], each position's
    least :func:`held_margin` over the expert layers [T])."""
    h = embed(params, jnp.asarray(tokens), cfg)
    margin = jnp.full((len(tokens),), jnp.inf)
    for i in range(cfg["num_hidden_layers"]):
        h, m_i = _jitted_layer(_key(cfg), _first_alike(cfg, i), precision)(
            h, params[f"block{i}"])
        margin = jnp.minimum(margin, m_i)
    return h, margin


def served_token_gaps(params, prompt, served, cfg: dict,
                      pad_to: int | None = None,
                      control: str | None = None) -> dict:
    """One teacher-forced pass over ``prompt`` followed by the tokens
    that were ``served`` after it, padded to a multiple of ``pad_to``
    positions (causal attention makes the padding invisible).  For every
    served token: how far its reference logit lies below the reference's
    best at that position.  With ``control``, the token judged at each
    position is instead the one the lower precision puts first there.

    **Near-ties of the routing are not judged**, by
    ``reference/afmoe.py``'s rule: where :func:`held_margin` is below
    :data:`ROUTING_TIE` in some expert layer, bfloat16 and float32 may
    select different experts there, both rightly.  ``widest`` is over
    the other positions (``judged`` of ``tokens``); the gap over all is
    printed beside it."""
    import numpy as np
    pad_to = pad_to or PAD_TO
    seq = np.concatenate([np.asarray(prompt), np.asarray(served)])
    n_p, n_s = len(prompt), len(served)
    padded = np.zeros((-(-len(seq) // pad_to) * pad_to,), np.int32)
    padded[:len(seq)] = seq
    rows = slice(n_p - 1, n_p - 1 + n_s)    # position i predicts token i+1
    h, margin = hidden_and_margins(params, padded, cfg)
    ref = head(params, h[rows], cfg)
    judged = jnp.asarray(np.asarray(served, np.int32))
    if control is not None:
        low = hidden_and_margins(params, padded, cfg, control)[0]
        judged = jnp.argmax(head(params, low[rows], cfg, control), axis=-1)
    best = jnp.max(ref, axis=-1)
    got = jnp.take_along_axis(ref, judged[:, None], axis=-1)[:, 0]
    gaps = np.asarray(jax.device_get(best - got))
    clear = np.asarray(jax.device_get(margin[rows])) >= ROUTING_TIE
    print(f"[bench] reference: {int(clear.sum())} of {n_s} served tokens "
          f"judged ({n_s - int(clear.sum())} at a near-tie of the routing); "
          f"widest gap {gaps[clear].max(initial=0.0):.4f} over them, "
          f"{gaps.max():.4f} over all", flush=True)
    return {"widest": float(gaps[clear].max(initial=0.0)),
            "tokens": int(n_s), "judged": int(clear.sum()),
            "widest_over_all": float(gaps.max())}


def _first_alike(cfg: dict, index: int) -> int:
    """The first layer of ``index``'s kind (a dense or an expert
    feed-forward): layers of one kind share one compiled program."""
    dense = cfg["first_k_dense_replace"]
    return 0 if index < dense else dense


def _key(cfg: dict) -> str:
    """What of a configuration the layer programs depend on."""
    keys = ("rms_norm_eps", "rope_theta", "rope_scaling",
            "num_attention_heads", "q_lora_rank", "qk_nope_head_dim",
            "qk_rope_head_dim", "kv_lora_rank", "v_head_dim",
            "first_k_dense_replace", "n_routed_experts",
            "num_experts_per_tok", "norm_topk_prob",
            "routed_scaling_factor", "deployment", "hidden_size")
    return json.dumps({k: cfg.get(k) for k in keys}, sort_keys=True)


@functools.lru_cache(maxsize=None)
def _jitted_layer(key: str, index: int, precision: str):
    return jax.jit(functools.partial(layer, cfg=json.loads(key),
                                     index=index, precision=precision))
