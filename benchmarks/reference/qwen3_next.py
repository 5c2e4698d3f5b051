"""Plain reference for the ``qwen3_next`` configurations (Qwen3-Next):
the forward pass in straightforward ``jax.numpy``, float32, every matrix
multiplication at ``Precision.HIGHEST``, the Gated DeltaNet's recurrence
token by token (``lax.scan``).  No chunking, no cache, no kernels, no
grouped products, no batching; it imports nothing of the program and is
never given an array the program has made.  What it shares with
``reference/afmoe.py`` (the matmul of a precision, attention in blocks of
queries, the SiLU-gated feed-forward) it takes from there.

The equations (d the hidden size, ``RMS0(x; g) = x / sqrt(mean(x^2) +
eps) * (1 + g)``, no bias anywhere; a dagger marks a departure from the
published implementation, each listed under ``assumed`` in the
configuration's file)::

    layer i:  h = h + mix_i(RMS0(h; g_in));  h = h + moe(RMS0(h; g_post))
              mix_i = attn where (i + 1) % full_attention_interval == 0,
              else gdn
    logits = RMS0(h; g_f) W_head                            (untied)

    attn(a):  [q | u] = a Wq      per head: 2 x head_dim outputs, the
                                  first half q, the second the gate u
              k, v = a Wk, a Wv;  q, k = RMS0(q; g_q), RMS0(k; g_k)
              q, k = rope(q, k)   on the first partial_rotary_factor x
                                  head_dim features, half-split pairs
              o = softmax(q k^T / sqrt(head_dim)) v, causal; query head
                  i reads K/V head i // (heads / kv heads)
              return (o * sigmoid(u)) Wo

    gdn(a):   [q | k | v | z] = a W_qkvz    (†: stored as four blocks;
                                  the published projection interleaves
                                  them by key head)
              [b | alpha] = a W_ba          (†: likewise two blocks)
              x = silu(conv(q | k | v))     depthwise, causal, kernel 4
              q, k = q / |q|_2, k / |k|_2 (eps 1e-6);  q = q / sqrt(Dk)
              each q/k head serves Hv / Hk consecutive value heads
              beta = sigmoid(b);  g = -exp(A_log) softplus(alpha + dt_bias)
              per value head, S_0 = 0:
                  S = exp(g_t) S;  d_t = beta_t (v_t - S^T k_t)
                  S = S + k_t d_t^T;  o_t = S^T q_t
              o = o / sqrt(mean(o^2) + eps) * g_n * silu(z)
              return o W_out

    moe(m):   p = softmax(m Wr) over all experts;  sel = top-k(p)
              w = p[sel] / sum p[sel]                    (norm_topk_prob)
              return sum_{e in sel} w_e ffn_e(m)
                     + sigmoid(m w_sg) ffn_shared(m)

The family's multi-token-prediction head is left out (†): the
configuration has no key for it and it serves no token without
speculation.

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
    F32, HIGHEST, PAD_TO, PRECISIONS, _attention, _ffn, held_experts,
    make_matmul)

#: A held expert's router LOGIT nearer than this to the boundary of the
#: selection is a near-tie (served_token_gaps; PERF.md section 2 has the
#: readings it was set from).  The softmax keeps the logits' order, and
#: its probabilities over 512 experts are too small to state a tie in.
ROUTING_TIE = 0.02


def _rms0(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * (1.0 + g.astype(F32))


def _partial_rope(x, theta, rotary_dim):
    """x [T, H, Dh] at positions 0..T-1: the first ``rotary_dim``
    features rotate, their two halves as pairs; the others pass."""
    T = x.shape[0]
    half = rotary_dim // 2
    inv = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = jnp.arange(T, dtype=F32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:rotary_dim]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            x[..., rotary_dim:]], -1)


def is_attention(cfg: dict, index: int) -> bool:
    return (index + 1) % cfg["full_attention_interval"] == 0


def attention(a, p, cfg: dict, mm, dtype):
    """a [T, d] -> [T, d]: the gated softmax attention."""
    T, Dh = a.shape[0], cfg["head_dim"]
    eps = cfg["rms_norm_eps"]
    qu = mm(a, p["wq"]).reshape(T, -1, 2 * Dh)
    q, u = qu[..., :Dh], qu[..., Dh:]
    k = mm(a, p["wk"]).reshape(T, -1, Dh)
    v = mm(a, p["wv"]).reshape(T, -1, Dh)
    q, k = _rms0(q, p["norm_q"], eps), _rms0(k, p["norm_k"], eps)
    rotary = int(Dh * cfg["partial_rotary_factor"])
    q = _partial_rope(q, cfg["rope_theta"], rotary)
    k = _partial_rope(k, cfg["rope_theta"], rotary)
    o = _attention(q, k, v, 0, dtype)
    return mm(o.reshape(T, -1) * jax.nn.sigmoid(u.reshape(T, -1)), p["wo"])


def delta_rule(q, k, v, g, beta):
    """The recurrence, token by token: q, k [T, H, Dk], v [T, H, Dv], g
    and beta [T, H] -> o [T, H, Dv]; S_0 = 0."""
    def one(S, x):
        q_t, k_t, v_t, g_t, b_t = x
        S = jnp.exp(g_t)[:, None, None] * S
        d = b_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", S, k_t,
                                             precision=HIGHEST))
        S = S + k_t[:, :, None] * d[:, None, :]
        return S, jnp.einsum("hkv,hk->hv", S, q_t, precision=HIGHEST)

    H, Dk, Dv = q.shape[1], q.shape[2], v.shape[2]
    return jax.lax.scan(one, jnp.zeros((H, Dk, Dv), F32),
                        (q, k, v, g, beta))[1]


def gated_delta_net(a, p, cfg: dict, mm):
    """a [T, d] -> [T, d]."""
    T = a.shape[0]
    Hk, Hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    Dk, Dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    K = cfg["linear_conv_kernel_dim"]
    kd, vd = Hk * Dk, Hv * Dv
    qkvz = mm(a, p["w_qkvz"])
    ba = mm(a, p["w_ba"])
    x, z = qkvz[:, :2 * kd + vd], qkvz[:, 2 * kd + vd:].reshape(T, Hv, Dv)
    w = p["conv"].astype(F32)                                   # [K, C]
    xp = jnp.pad(x, ((K - 1, 0), (0, 0)))
    x = jax.nn.silu(sum(w[j] * xp[j:j + T] for j in range(K)))
    q = x[:, :kd].reshape(T, Hk, Dk)
    k = x[:, kd:2 * kd].reshape(T, Hk, Dk)
    v = x[:, 2 * kd:].reshape(T, Hv, Dv)
    unit = lambda t: t / jnp.sqrt(jnp.sum(t * t, axis=-1, keepdims=True)
                                  + 1e-6)
    q, k = unit(q) / math.sqrt(Dk), unit(k)
    q, k = (jnp.repeat(t, Hv // Hk, axis=1) for t in (q, k))
    beta = jax.nn.sigmoid(ba[:, :Hv])
    g = -jnp.exp(p["a_log"].astype(F32)) * jax.nn.softplus(
        ba[:, Hv:] + p["dt_bias"].astype(F32))
    o = delta_rule(q, k, v, g, beta)
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                          + cfg["rms_norm_eps"])
    o = o * p["norm_o"].astype(F32) * jax.nn.silu(z)
    return mm(o.reshape(T, vd), p["w_out"])


def router_logits(m, p):
    return jnp.matmul(m, p["router"].astype(F32), precision=HIGHEST)


def route(m, p, cfg):
    """m [T, d] -> (sel [T, k], w [T, k]): the experts each token selects
    among all the router's outputs, and their weights."""
    prob = jax.nn.softmax(router_logits(m, p), axis=-1)
    w, sel = jax.lax.top_k(prob, cfg["num_experts_per_tok"])
    if cfg["norm_topk_prob"]:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return sel, w


def expert_layer(m, p, cfg: dict, mm) -> tuple:
    """m [T, d] -> (the gated shared expert's part, the held experts'
    part): what every share computes alike, and what this share alone
    adds."""
    sel, w = route(m, p, cfg)
    first, held = held_experts(cfg)

    def one(acc, e):
        gate, up, down = (p[f"experts_{n}"][e] for n in ("gate", "up",
                                                         "down"))
        w_e = jnp.sum(jnp.where(sel == first + e, w, 0.0), axis=-1)
        return acc + w_e[:, None] * _ffn(m, gate, up, down, mm), None

    routed, _ = jax.lax.scan(one, jnp.zeros_like(m), jnp.arange(held))
    shared = _ffn(m, p["shared_gate"], p["shared_up"], p["shared_down"], mm)
    return jax.nn.sigmoid(mm(m, p["shared_gate_w"])) * shared, routed


def held_margin(m, p, cfg: dict):
    """m [T, d] -> [T]: how far each token's selection is from choosing
    another set of HELD experts — the least distance of a held expert's
    router logit from the boundary between the k-th and the (k+1)-th (a
    selected one against the best unselected, an unselected one against
    the worst selected).  Below a precision's noise in the logits, that
    precision may select otherwise than float32 does."""
    k = cfg["num_experts_per_tok"]
    first, held = held_experts(cfg)
    c = router_logits(m, p)
    top = jax.lax.top_k(c, k + 1)[0]
    ours = c[:, first:first + held]
    edge = jnp.where(ours >= top[:, k - 1:k], ours - top[:, k:k + 1],
                     top[:, k - 1:k] - ours)
    return jnp.min(edge, axis=-1)


def layer(h, p, cfg: dict, index: int, precision: str = "f32"):
    """One layer on one sequence: h [T, d] float32 -> (h' [T, d], the
    tokens' :func:`held_margin`)."""
    mm = make_matmul(precision)
    eps = cfg["rms_norm_eps"]
    a = _rms0(h, p["norm_in"], eps)
    if is_attention(cfg, index):
        h = h + attention(a, p, cfg, mm,
                          F32 if precision == "f32" else jnp.bfloat16)
    else:
        h = h + gated_delta_net(a, p, cfg, mm)
    m = _rms0(h, p["norm_post"], eps)
    return h + sum(expert_layer(m, p, cfg, mm)), held_margin(m, p, cfg)


def embed(params, tokens, cfg: dict):
    return params["embed"].astype(F32)[tokens]


def head(params, h, cfg: dict, precision: str = "f32"):
    return make_matmul(precision)(
        _rms0(h, params["norm_f"], cfg["rms_norm_eps"]), params["head"])


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
    least :func:`held_margin` over the layers [T])."""
    h = embed(params, jnp.asarray(tokens), cfg)
    margin = jnp.full((len(tokens),), jnp.inf)
    every = cfg["full_attention_interval"]
    for i in range(cfg["num_hidden_layers"]):
        # Layers of one kind share one compiled program.
        alike = every - 1 if is_attention(cfg, i) else 0
        h, m_i = _jitted_layer(_key(cfg), alike, precision)(
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
    ``reference/afmoe.py``'s rule: where a held expert's router logit
    lies within :data:`ROUTING_TIE` of the selection's boundary in some
    layer (:func:`held_margin`), bfloat16 and float32 may select
    different experts there, both rightly.  ``widest`` is over the other
    positions (``judged`` of ``tokens``); the gap over all is printed
    beside it."""
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


def _key(cfg: dict) -> str:
    """What of a configuration the layer programs depend on."""
    keys = ("head_dim", "rms_norm_eps", "full_attention_interval",
            "rope_theta", "partial_rotary_factor", "num_experts",
            "num_experts_per_tok", "norm_topk_prob", "deployment",
            "hidden_size", "linear_num_key_heads", "linear_num_value_heads",
            "linear_key_head_dim", "linear_value_head_dim",
            "linear_conv_kernel_dim")
    return json.dumps({k: cfg.get(k) for k in keys}, sort_keys=True)


@functools.lru_cache(maxsize=None)
def _jitted_layer(key: str, index: int, precision: str):
    return jax.jit(functools.partial(layer, cfg=json.loads(key),
                                     index=index, precision=precision))
