"""Plain reference for the ``bailing_hybrid`` configurations (inclusionAI's
Ling linear family): the forward pass in straightforward ``jax.numpy``,
float32, every matrix multiplication at ``Precision.HIGHEST``, Kimi
Delta Attention's recurrence token by token (``lax.scan``) with its
decay a key channel, latent attention in its EXPANDED form (every
position's row expanded to the keys and values of every head).  No
chunking, no absorbed form, no cache, no kernels, no grouped products,
no batching; it imports nothing of the program and is never given an
array the program has made.  What it shares with ``reference/afmoe.py``
(the matmul of a precision, the SiLU-gated feed-forward, the embedding,
the head, the share) it takes from there.

The equations (d the hidden size, ``RMS(x; g) = x / sqrt(mean(x^2) +
eps) * g``, no bias anywhere; a dagger marks a reading that is a
convention and not a certainty, each listed under ``assumed`` in the
configuration's file)::

    layer i:  h = h + mix_i(RMS(h; g_in));  h = h + ffn_i(RMS(h; g_post))
              mix_i = mla where (i + 1) % layer_group_size == 0 (†),
              else kda;  ffn_i = dense for i < first_k_dense_replace
    logits = RMS(h; g_f) W_head                             (untied)

    kda(a):   [q | k | v | u] = a W_qkvu    (†: stored as four blocks)
              x = silu(conv(q | k | v))     depthwise, causal, kernel 4
              q, k = q / |q|_2, k / |k|_2 (eps 1e-6);  q = q / sqrt(D)
              g = kda_lower_bound * sigmoid(exp(A_log_h) (a W_f + dt_bias))
                                            (†) per head and key channel
              beta = sigmoid(a W_b)
              per head, S_0 = 0:
                  S = Diag(exp(g_t)) S;  d_t = beta_t (v_t - S^T k_t)
                  S = S + k_t d_t^T;  o_t = S^T q_t
              return (RMS(o; g_n) * sigmoid(u)) W_o         (†) per head

    mla(a):   q_h = [q_nope | q_pe] = a W_q;  [c_kv | k_pe] = a W_kva
              c = RMS(c_kv; g_c);  rope on q_pe and on the one k_pe,
              interleaved pairs
              [k_nope | v]_h = c W_kvb,h
              o_h = softmax(q_h . [k_nope_h | k_pe] / sqrt(Dn + Dr)) v_h
              return (o_h * sigmoid(a w_h))_h W_o           (†) the gate

    moe(m):   s = sigmoid(m Wr);  c = s + bias
              group score = the sum of a group's two best c; the
              topk_group best of n_group groups stay; sel = top-k of c
              among their experts
              w = s[sel] / sum s[sel] * routed_scaling_factor
              return sum_{e in sel} w_e ffn_e(m) + ffn_shared(m)

The multi-token-prediction module is left out (†): it serves no token
without speculation.

**The share**, as in ``reference/afmoe.py``: ``num_experts`` experts are
held, ids ``deployment.rank * num_experts`` onward, of the
``published.num_experts`` the router scores; the sum over ``sel`` runs
over the held experts only, and that partial result goes on.

``precision`` selects the CONTROL the comparison must fail: every linear
layer (the experts' and the head included) computed as a lower precision
would; under a control the attention products run in bfloat16.  The
recurrence stays float32 under every control: the configuration states
its state so.
"""

from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp

from benchmarks.reference.afmoe import (        # noqa: F401 (PRECISIONS)
    F32, HIGHEST, PAD_TO, PRECISIONS, QUERY_BLOCK, _ffn, _rms, embed, head,
    held_experts, make_matmul)

#: A held expert's biased score nearer than this to the boundary of the
#: selection — or a gap this small between the last group kept and the
#: first left out — is a near-tie (served_token_gaps).  About the median
#: of bfloat16's own noise in a biased score at the published widths
#: (0.001 in the first expert layer to 0.004 in the sixth).  Read on the
#: chip at 0.003 and at 0.005: the larger tie judges a tenth of the
#: tokens in place of a quarter and brings the int8 control's reading
#: down faster than the sound program's (PERF.md section 2).
ROUTING_TIE = 0.003


def _rope_pairs(x, theta):
    """x [T, H, Dr] at positions 0..T-1: features (2i, 2i + 1) rotate
    as a pair."""
    T, _, Dr = x.shape
    half = Dr // 2
    inv = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = jnp.arange(T, dtype=F32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape)


def is_latent(cfg: dict, index: int) -> bool:
    return (index + 1) % cfg["layer_group_size"] == 0


def _causal_attention(q, k, v, dtype):
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
                       preferred_element_type=F32) / math.sqrt(Dh)
        ok = s_pos[None] <= t_pos[:, None]
        p = jax.nn.softmax(jnp.where(ok[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hts,shd->thd", p.astype(dtype), v,
                          precision=HIGHEST, preferred_element_type=F32)

    starts = jnp.arange(qb.shape[0]) * QUERY_BLOCK
    return jax.lax.map(block, (qb, starts)).reshape(-1, H, v.shape[-1])[:T]


def latent_attention(a, p, cfg: dict, mm, dtype):
    """a [T, d] -> [T, d]: latent attention, expanded."""
    T, H = a.shape[0], cfg["num_attention_heads"]
    Dn, Dr = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    rank, theta = cfg["kv_lora_rank"], cfg["rope_theta"]
    q = mm(a, p["wq"]).reshape(T, H, Dn + Dr)
    kva = mm(a, p["w_kva"])
    c = _rms(kva[:, :rank], p["norm_c"], cfg["rms_norm_eps"])
    k_pe = _rope_pairs(kva[:, None, rank:], theta)          # [T, 1, Dr]
    q = jnp.concatenate([q[..., :Dn], _rope_pairs(q[..., Dn:], theta)], -1)
    kv = mm(c, p["w_kvb"].reshape(rank, -1)).reshape(T, H, -1)
    k = jnp.concatenate([kv[..., :Dn], jnp.broadcast_to(k_pe, (T, H, Dr))],
                        -1)
    o = _causal_attention(q, k, kv[..., Dn:], dtype)
    o = o * jax.nn.sigmoid(mm(a, p["w_gate"]))[:, :, None]
    return mm(o.reshape(T, -1), p["wo"])


def delta_rule(q, k, v, g, beta):
    """The recurrence, token by token: q, k, g [T, H, Dk], v [T, H, Dv],
    beta [T, H] -> o [T, H, Dv]; S_0 = 0; row i of a head's state decays
    by exp(g_t[i])."""
    def one(S, x):
        q_t, k_t, v_t, g_t, b_t = x
        S = jnp.exp(g_t)[:, :, None] * S
        d = b_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", S, k_t,
                                             precision=HIGHEST))
        S = S + k_t[:, :, None] * d[:, None, :]
        return S, jnp.einsum("hkv,hk->hv", S, q_t, precision=HIGHEST)

    H, Dk, Dv = q.shape[1], q.shape[2], v.shape[2]
    return jax.lax.scan(one, jnp.zeros((H, Dk, Dv), F32),
                        (q, k, v, g, beta))[1]


def kimi_delta_attention(a, p, cfg: dict, mm):
    """a [T, d] -> [T, d]."""
    T, H, D = a.shape[0], cfg["num_attention_heads"], cfg["head_dim"]
    K = cfg["short_conv_kernel_size"]
    hd = H * D
    qkvu = mm(a, p["w_qkvu"])
    w = p["conv"].astype(F32)                                   # [K, 3 H D]
    xp = jnp.pad(qkvu[:, :3 * hd], ((K - 1, 0), (0, 0)))
    x = jax.nn.silu(sum(w[j] * xp[j:j + T] for j in range(K)))
    q, k, v = (x[:, i * hd:(i + 1) * hd].reshape(T, H, D) for i in range(3))
    unit = lambda t: t / jnp.sqrt(jnp.sum(t * t, axis=-1, keepdims=True)
                                  + 1e-6)
    q, k = unit(q) / math.sqrt(D), unit(k)
    f = (mm(a, p["w_f"]) + p["dt_bias"].astype(F32)).reshape(T, H, D)
    g = cfg["kda_lower_bound"] * jax.nn.sigmoid(
        jnp.exp(p["a_log"].astype(F32))[None, :, None] * f)
    beta = jax.nn.sigmoid(mm(a, p["w_b"]))
    o = delta_rule(q, k, v, g, beta)
    o = _rms(o, p["norm_o"], cfg["rms_norm_eps"]) * jax.nn.sigmoid(
        qkvu[:, 3 * hd:].reshape(T, H, D))
    return mm(o.reshape(T, hd), p["wo"])


def biased_scores(m, p):
    """m [T, d] -> (s, c) [T, E]: the router's scores and what selects."""
    s = jax.nn.sigmoid(jnp.matmul(m, p["router"].astype(F32),
                                  precision=HIGHEST))
    return s, s + p["router_bias"].astype(F32)


def limit_to_groups(c, cfg: dict):
    """c [T, E] -> (c with the experts of the groups left out at -inf,
    every group's score [T, G])."""
    G = cfg["n_group"]
    groups = c.reshape(c.shape[0], G, -1)
    score = jnp.sum(jax.lax.top_k(groups, 2)[0], axis=-1)
    last_kept = jax.lax.top_k(score, cfg["topk_group"])[0][:, -1:]
    return jnp.where((score >= last_kept)[:, :, None], groups,
                     -jnp.inf).reshape(c.shape), score


def route(m, p, cfg):
    """m [T, d] -> (sel [T, k], w [T, k]): the experts each token selects
    among all the router's outputs, and their weights."""
    s, c = biased_scores(m, p)
    _, sel = jax.lax.top_k(limit_to_groups(c, cfg)[0],
                           cfg["num_experts_per_tok"])
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
    another set of HELD experts, in the biased score ``c``.  Two edges
    count.  Among the experts of the groups kept: the least distance of
    a held expert's ``c`` from the boundary between the k-th and the
    (k+1)-th (a selected one against the best unselected, an unselected
    one against the worst selected; a held expert whose group is left
    out is infinitely far).  And the groups' own edge: the gap between
    the last group kept and the first left out — another set of groups
    is another set of candidates, so the held experts' selection changes
    with it.  Below a precision's noise in the scores, that precision
    may select otherwise than float32 does."""
    k = cfg["num_experts_per_tok"]
    first, held = held_experts(cfg)
    c, score = limit_to_groups(biased_scores(m, p)[1], cfg)
    top = jax.lax.top_k(c, k + 1)[0]
    ours = c[:, first:first + held]
    edge = jnp.where(ours >= top[:, k - 1:k], ours - top[:, k:k + 1],
                     top[:, k - 1:k] - ours)
    kg = cfg["topk_group"]
    groups = jax.lax.top_k(score, kg + 1)[0]
    return jnp.minimum(jnp.min(edge, axis=-1),
                       groups[:, kg - 1] - groups[:, kg])


def layer(h, p, cfg: dict, index: int, precision: str = "f32"):
    """One layer on one sequence: h [T, d] float32 -> (h' [T, d], the
    tokens' :func:`held_margin`, infinite on a dense layer)."""
    mm = make_matmul(precision)
    eps = cfg["rms_norm_eps"]
    a = _rms(h, p["norm_in"], eps)
    if is_latent(cfg, index):
        h = h + latent_attention(a, p, cfg, mm,
                                 F32 if precision == "f32" else jnp.bfloat16)
    else:
        h = h + kimi_delta_attention(a, p, cfg, mm)
    m = _rms(h, p["norm_post"], eps)
    if index < cfg["first_k_dense_replace"]:
        return (h + _ffn(m, p["ffn_gate"], p["ffn_up"], p["ffn_down"], mm),
                jnp.full((h.shape[0],), jnp.inf))
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
    positions (causal mixing makes the padding invisible).  For every
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
    """The first layer of ``index``'s kind (mixer and feed-forward):
    layers of one kind share one compiled program."""
    kind = lambda i: (is_latent(cfg, i), i < cfg["first_k_dense_replace"])
    return next(j for j in range(index + 1) if kind(j) == kind(index))


def _key(cfg: dict) -> str:
    """What of a configuration the layer programs depend on."""
    keys = ("head_dim", "rms_norm_eps", "layer_group_size", "rope_theta",
            "num_attention_heads", "qk_nope_head_dim", "qk_rope_head_dim",
            "kv_lora_rank", "v_head_dim", "short_conv_kernel_size",
            "kda_lower_bound", "first_k_dense_replace", "num_experts",
            "num_experts_per_tok", "n_group", "topk_group",
            "norm_topk_prob", "routed_scaling_factor", "deployment",
            "hidden_size")
    return json.dumps({k: cfg.get(k) for k in keys}, sort_keys=True)


@functools.lru_cache(maxsize=None)
def _jitted_layer(key: str, index: int, precision: str):
    return jax.jit(functools.partial(layer, cfg=json.loads(key),
                                     index=index, precision=precision))
