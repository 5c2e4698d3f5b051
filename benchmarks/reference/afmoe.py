"""Plain reference for the ``afmoe`` configurations (Arcee's Trinity
family): the forward pass in straightforward ``jax.numpy``, float32,
every matrix multiplication at ``Precision.HIGHEST``.  No cache, no
kernels, no grouped products, no batching; it imports nothing of the
program and is never given an array the program has made.

The equations (d the hidden size, ``RMS(x; g) = x / sqrt(mean(x^2) + eps)
* g``, no bias anywhere; a dagger marks what the source's config has no
key for and the family's published implementation does — each is listed
under ``assumed`` in the configuration's file)::

    h = E[ids] * sqrt(d)                                   (mup_enabled; †)
    a = RMS(h; g_in)
    q, k, v, u = a Wq, a Wk, a Wv, a Wg                    (the gate Wg: †)
    q, k = RMS(q; g_q), RMS(k; g_k) over each head's 128   (†)
    q, k = rope(q, k; theta, position)   sliding layers only; a
                                         full layer applies no position (†)
    p = softmax(q k^T / sqrt(128)) over keys s <= t, and on a sliding
        layer also t - s < sliding_window; query head i reads K/V head
        i // (heads / kv heads)
    h = h + RMS(((p v) * sigmoid(u)) Wo; g_post_attn)      (sandwich: †)
    m = RMS(h; g_pre_mlp);  ffn(m; G, U, D) = (silu(m G) * (m U)) D
    dense layer (l < num_dense_layers):  f = ffn(m)
    expert layer:  s = sigmoid(m Wr);  sel = top-k(s + b)  (b: †)
                   w = route_scale * s[sel] / sum s[sel]   (route_norm)
                   f = ffn_shared(m) + sum_{e in sel} w_e ffn_e(m)
    h = h + RMS(f; g_post_mlp)
    logits = RMS(h; g_f) W_head                            (untied)

**The share.**  A configuration may be one chip's share of an
expert-parallel deployment: ``num_experts`` experts are held, ids
``deployment.rank * num_experts`` onward, of the ``published.num_experts``
the router scores, and the vocabulary is a slice.  The reference is
given the same share and leaves out what the program leaves out: the
sum over ``sel`` runs over the held experts only, and that partial
result goes on to the next layer.

It is handed the parameters as the family makes them (bfloat16, 8.6 GB
at the benchmark's size) and casts one layer — inside an expert layer,
one expert — at a time, so that it fits beside them; attention runs in
blocks of queries against all keys.  Every expert is computed on every
token and selected by the routing weights (zero where not selected):
plain, and eight times the products the routing needs.

``precision`` selects the CONTROL the comparison must fail: the same
mathematics with every linear layer (the experts' and the head
included) computed as a lower precision would — ``bf16`` inputs,
``int8`` (per-row absmax for activations, per-column for weights) or
``fp8`` (e4m3, the same scalings).  Under a control the attention
products run in bfloat16.
"""

from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
PRECISIONS = ("f32", "bf16", "int8", "fp8")
QUERY_BLOCK = 256       # [heads, 256, T] float32 scores: 805 MB at 16,384
PAD_TO = 2048           # a served sequence is padded to a multiple of this
#: A held expert's biased score nearer than this to the boundary of the
#: selection is a near-tie (served_token_gaps; PERF.md section 2 has the
#: readings it was set from).
ROUTING_TIE = 0.01
F32 = jnp.float32


def _fake_quant(x, axis: int, precision: str):
    if precision == "bf16":
        return x.astype(jnp.bfloat16).astype(F32)
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    amax = jnp.where(amax > 0, amax, 1.0)
    if precision == "int8":
        s = amax / 127.0
        return jnp.round(x / s) * s
    s = amax / 448.0                      # e4m3's largest finite value
    return (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s


def make_matmul(precision: str):
    """``a [..., k] @ b [k, n]``, both cast to float32 first; the
    controls round both operands to the lower precision."""
    if precision not in PRECISIONS:
        raise ValueError(f"unknown precision {precision!r}")
    if precision == "f32":
        return lambda a, b: jnp.matmul(a.astype(F32), b.astype(F32),
                                       precision=HIGHEST)
    return lambda a, b: jnp.matmul(
        _fake_quant(a.astype(F32), -1, precision),
        _fake_quant(b.astype(F32), 0, precision), precision=HIGHEST)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * g.astype(F32)


def _rope(x, theta):
    """x [T, H, Dh] at positions 0..T-1: the two halves of a head's
    features rotate as pairs (the published implementation's form)."""
    T, _, Dh = x.shape
    half = Dh // 2
    inv = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = jnp.arange(T, dtype=F32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v, window: int, dtype):
    """q [T, Hq, Dh], k and v [T, Hkv, Dh] -> [T, Hq, Dh]; blocks of
    queries against all keys."""
    T, Hq, Dh = q.shape
    G = Hq // k.shape[1]
    k, v = (jnp.repeat(x, G, axis=1).astype(dtype) for x in (k, v))
    pad = -T % QUERY_BLOCK
    qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).astype(dtype)
    qb = qb.reshape(-1, QUERY_BLOCK, Hq, Dh)
    s_pos = jnp.arange(T)

    def block(args):
        qi, t0 = args
        t_pos = t0 + jnp.arange(QUERY_BLOCK)
        s = jnp.einsum("thd,shd->hts", qi, k, precision=HIGHEST,
                       preferred_element_type=F32) / math.sqrt(Dh)
        ok = s_pos[None] <= t_pos[:, None]
        if window:
            ok &= t_pos[:, None] - s_pos[None] < window
        p = jax.nn.softmax(jnp.where(ok[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hts,shd->thd", p.astype(dtype), v,
                          precision=HIGHEST, preferred_element_type=F32)

    starts = jnp.arange(qb.shape[0]) * QUERY_BLOCK
    return jax.lax.map(block, (qb, starts)).reshape(-1, Hq, Dh)[:T]


def _ffn(m, gate, up, down, mm):
    return mm(jax.nn.silu(mm(m, gate)) * mm(m, up), down)


def route(m, p, cfg):
    """m [T, d] -> (sel [T, k], w [T, k]): the experts each token selects
    among all the router's outputs, and their weights."""
    s = jax.nn.sigmoid(jnp.matmul(m, p["router"].astype(F32),
                                  precision=HIGHEST))
    _, sel = jax.lax.top_k(s + p["router_bias"].astype(F32),
                           cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(s, sel, axis=-1)
    if cfg["route_norm"]:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return sel, cfg["route_scale"] * w


def held_experts(cfg: dict) -> tuple:
    """(first id, count) of the experts this share holds."""
    n = cfg["num_experts"]
    return cfg.get("deployment", {}).get("rank", 0) * n, n


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
    biased score from the boundary between the k-th and the (k+1)-th
    (a selected one against the best unselected score, an unselected one
    against the worst selected).  Below a precision's noise in the
    scores, that precision may select otherwise than float32 does."""
    k = cfg["num_experts_per_tok"]
    first, held = held_experts(cfg)
    c = jax.nn.sigmoid(jnp.matmul(m, p["router"].astype(F32),
                                  precision=HIGHEST)) \
        + p["router_bias"].astype(F32)
    top = jax.lax.top_k(c, k + 1)[0]
    ours = c[:, first:first + held]
    edge = jnp.where(ours >= top[:, k - 1:k], ours - top[:, k:k + 1],
                     top[:, k - 1:k] - ours)
    return jnp.min(edge, axis=-1)


def layer(h, p, cfg: dict, index: int, precision: str = "f32"):
    """One layer on one sequence: h [T, d] float32 -> (h' [T, d], the
    tokens' :func:`held_margin`, infinite on a dense layer)."""
    mm = make_matmul(precision)
    attn_dtype = F32 if precision == "f32" else jnp.bfloat16
    eps, Dh = cfg["rms_norm_eps"], cfg["head_dim"]
    sliding = cfg["layer_types"][index] == "sliding_attention"
    T = h.shape[0]
    a = _rms(h, p["norm_in"], eps)
    q = mm(a, p["wq"]).reshape(T, -1, Dh)
    k = mm(a, p["wk"]).reshape(T, -1, Dh)
    v = mm(a, p["wv"]).reshape(T, -1, Dh)
    u = mm(a, p["wg"])
    q, k = _rms(q, p["norm_q"], eps), _rms(k, p["norm_k"], eps)
    if sliding:
        q, k = _rope(q, cfg["rope_theta"]), _rope(k, cfg["rope_theta"])
    o = _attention(q, k, v, cfg["sliding_window"] if sliding else 0,
                   attn_dtype)
    o = o.reshape(T, -1) * jax.nn.sigmoid(u)
    h = h + _rms(mm(o, p["wo"]), p["norm_post_attn"], eps)
    m = _rms(h, p["norm_pre_mlp"], eps)
    if index < cfg["num_dense_layers"]:
        f = _ffn(m, p["ffn_gate"], p["ffn_up"], p["ffn_down"], mm)
        margin = jnp.full((T,), jnp.inf)
    else:
        f = sum(expert_layer(m, p, cfg, mm))
        margin = held_margin(m, p, cfg)
    return h + _rms(f, p["norm_post_mlp"], eps), margin


def embed(params, tokens, cfg: dict):
    scale = math.sqrt(cfg["hidden_size"]) if cfg.get("mup_enabled") else 1.0
    return params["embed"].astype(F32)[tokens] * scale


def head(params, h, cfg: dict, precision: str = "f32"):
    return make_matmul(precision)(
        _rms(h, params["norm_f"], cfg["rms_norm_eps"]), params["head"])


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

    **Near-ties of the routing are not judged.**  Where a held expert's
    score lies within :data:`ROUTING_TIE` of the selection's boundary in
    some expert layer (:func:`held_margin`), bfloat16 and float32 may
    select different experts there, both rightly, and the logits then
    differ by a whole expert's output: such a position says nothing of
    the program's arithmetic.  ``widest`` is over the other positions
    (``judged`` of ``tokens``), expert layers included."""
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
    """The first layer of ``index``'s kind (attention and feed-forward):
    layers of one kind share one compiled program."""
    kind = lambda i: (cfg["layer_types"][i], i < cfg["num_dense_layers"])
    return next(j for j in range(index + 1) if kind(j) == kind(index))


def _key(cfg: dict) -> str:
    """What of a configuration the layer programs depend on."""
    keys = ("head_dim", "rms_norm_eps", "layer_types", "rope_theta",
            "sliding_window", "num_dense_layers", "num_experts",
            "num_experts_per_tok", "route_norm", "route_scale",
            "deployment", "hidden_size")
    return json.dumps({k: cfg.get(k) for k in keys}, sort_keys=True)


@functools.lru_cache(maxsize=None)
def _jitted_layer(key: str, index: int, precision: str):
    return jax.jit(functools.partial(layer, cfg=json.loads(key),
                                     index=index, precision=precision))
