"""Plain reference for the ``granitemoehybrid`` configurations (IBM's
Granite 4.0-H): the forward pass in straightforward ``jax.numpy``,
float32, every matrix multiplication at ``Precision.HIGHEST``, Mamba-2's
recurrence token by token (``lax.scan``), never its chunked form.  No
cache, no kernels, no grouped products, no batching; it imports nothing
of the program and is never given an array the program has made.  What it
shares with ``reference/afmoe.py`` (the matmul of a precision, the plain
RMS norm, the SiLU-gated feed-forward) it takes from there.

The equations (d the hidden size, ``RMS(x; w) = x / sqrt(mean(x^2) +
eps) * w``, no bias but the convolution's; a dagger marks a reading that
is a convention and not a certainty, each listed under ``assumed`` in the
configuration's file)::

    h_0 = embedding_multiplier E[token]
    layer i:  h = h + residual_multiplier mix_i(RMS(h; w_in))
              m = RMS(h; w_post)
              h = h + residual_multiplier (moe(m) + shared(m))
              mix_i = attn where layer_types[i] == "attention", else ssm
    logits = RMS(h; w_f) E^T / logits_scaling                   (tied)

    ssm(a):   [z | xBC] = a W_in;  dt = a W_dt      (†: the source's one
                                  in_proj stored as two blocks)
              xBC = silu(conv(xBC) + b_conv)        depthwise, causal,
                                  kernel mamba_d_conv
              x [H, P], B [N], C [N] = split(xBC)   (mamba_n_groups 1: one
                                  B and C for all heads)
              dt = softplus(dt + dt_bias);  A = -exp(A_log)
              per head, S_0 = 0:
                  S = exp(dt_t A) S + (dt_t x_t) B_t^T
                  y_t = S C_t + D x_t
              y = RMS(y * silu(z); w_y)             the gate first, one
                                  norm over all H P features
              return y W_out

    attn(a):  q, k, v = a Wq, a Wk, a Wv; no position encoding
              o = softmax(attention_multiplier q k^T) v, causal; query
                  head i reads K/V head i // (heads / kv heads)
              return o Wo

    moe(m):   l = m Wr;  sel = top-k(l);  w = softmax(l[sel])
              return sum_{e in sel} w_e ffn_e(m)
              ffn(m; G, U, D) = (silu(m G) * (m U)) D;  shared = ffn at
              shared_intermediate_size, every token, weight 1
              (†: intermediate_size read as one expert's width)

**The share**, as in ``reference/afmoe.py``: ``num_local_experts``
experts are held, ids ``deployment.rank * num_local_experts`` onward, of
the ``published.num_local_experts`` the router scores; the sum over
``sel`` runs over the held experts only, and that partial result goes
on.  ``num_local_experts`` 0 without ``published`` is the dense sibling's
layer: the shared MLP alone.

``precision`` selects the CONTROL the comparison must fail: every linear
layer (the experts' and the head included) computed as a lower precision
would; under a control the attention products run in bfloat16.  The
recurrence stays float32 under every control: the configuration states
its state so.
"""

from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp

from benchmarks.reference.afmoe import (        # noqa: F401 (PRECISIONS)
    F32, HIGHEST, PAD_TO, PRECISIONS, QUERY_BLOCK, _ffn, _rms, make_matmul)

#: A held expert's router LOGIT nearer than this to the boundary of the
#: selection is a near-tie (served_token_gaps): bfloat16's own noise in a
#: logit at the published width (4,096 features, weights of 0.02: logits
#: of ~1.3) is a few thousandths; ``reference/qwen3_next.py``'s value.
ROUTING_TIE = 0.02


def is_attention(cfg: dict, index: int) -> bool:
    return cfg["layer_types"][index] == "attention"


def attention(a, p, cfg: dict, mm, dtype):
    """a [T, d] -> [T, d]: grouped-query attention with no position
    encoding, blocks of queries against all keys."""
    T = a.shape[0]
    Hq, Hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    Dh = cfg["hidden_size"] // Hq
    q = mm(a, p["wq"]).reshape(T, Hq, Dh)
    k = mm(a, p["wk"]).reshape(T, Hkv, Dh)
    v = mm(a, p["wv"]).reshape(T, Hkv, Dh)
    k, v = (jnp.repeat(x, Hq // Hkv, axis=1).astype(dtype) for x in (k, v))
    pad = -T % QUERY_BLOCK
    qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).astype(dtype)
    qb = qb.reshape(-1, QUERY_BLOCK, Hq, Dh)
    s_pos = jnp.arange(T)

    def block(args):
        qi, t0 = args
        t_pos = t0 + jnp.arange(QUERY_BLOCK)
        s = jnp.einsum("thd,shd->hts", qi, k, precision=HIGHEST,
                       preferred_element_type=F32) \
            * cfg["attention_multiplier"]
        ok = s_pos[None] <= t_pos[:, None]
        prob = jax.nn.softmax(jnp.where(ok[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hts,shd->thd", prob.astype(dtype), v,
                          precision=HIGHEST, preferred_element_type=F32)

    starts = jnp.arange(qb.shape[0]) * QUERY_BLOCK
    o = jax.lax.map(block, (qb, starts)).reshape(-1, Hq * Dh)[:T]
    return mm(o, p["wo"])


def state_space_scan(x, dt, A, B, C):
    """The recurrence, token by token: x [T, H, P], dt [T, H], A [H], B
    and C [T, N] -> S C [T, H, P]; S_0 = 0."""
    def one(S, xs):
        x_t, dt_t, B_t, C_t = xs
        S = jnp.exp(dt_t * A)[:, None, None] * S \
            + (dt_t[:, None] * x_t)[:, :, None] * B_t[None, None, :]
        return S, jnp.sum(S * C_t[None, None, :], axis=-1)

    H, P = x.shape[1:]
    return jax.lax.scan(one, jnp.zeros((H, P, B.shape[-1]), F32),
                        (x, dt, B, C))[1]


def mamba(a, p, cfg: dict, mm):
    """a [T, d] -> [T, d]: the Mamba-2 mixer."""
    T = a.shape[0]
    H, P, N = cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_d_state"]
    K, di = cfg["mamba_d_conv"], H * P
    zx = mm(a, p["w_in"])
    z, xbc = zx[:, :di], zx[:, di:]
    w = p["conv"].astype(F32)                                   # [K, C]
    xp = jnp.pad(xbc, ((K - 1, 0), (0, 0)))
    xbc = sum(w[j] * xp[j:j + T] for j in range(K))
    if cfg["mamba_conv_bias"]:
        xbc = xbc + p["conv_bias"].astype(F32)
    xbc = jax.nn.silu(xbc)
    x = xbc[:, :di].reshape(T, H, P)
    B, C = xbc[:, di:di + N], xbc[:, di + N:]
    dt = jax.nn.softplus(mm(a, p["w_dt"]) + p["dt_bias"].astype(F32))
    y = state_space_scan(x, dt, -jnp.exp(p["a_log"].astype(F32)), B, C)
    y = y + p["d_skip"].astype(F32)[:, None] * x
    y = _rms(y.reshape(T, di) * jax.nn.silu(z), p["norm_y"],
             cfg["rms_norm_eps"])
    return mm(y, p["w_out"])


def held_experts(cfg: dict) -> tuple:
    """(first id, count) of the experts this share holds."""
    n = cfg["num_local_experts"]
    return cfg.get("deployment", {}).get("rank", 0) * n, n


def router_logits(m, p):
    return jnp.matmul(m, p["router"].astype(F32), precision=HIGHEST)


def route(m, p, cfg):
    """m [T, d] -> (sel [T, k], w [T, k]): the k largest logits among all
    the router's outputs, and the softmax over those k."""
    top, sel = jax.lax.top_k(router_logits(m, p), cfg["num_experts_per_tok"])
    return sel, jax.nn.softmax(top, axis=-1)


def expert_layer(m, p, cfg: dict, mm) -> tuple:
    """m [T, d] -> (the shared MLP's part, the held experts' part): what
    every share computes alike, and what this share alone adds."""
    shared = _ffn(m, p["shared_gate"], p["shared_up"], p["shared_down"], mm)
    if "router" not in p:
        return shared, jnp.zeros_like(m)
    sel, w = route(m, p, cfg)
    first, held = held_experts(cfg)

    def one(acc, e):
        gate, up, down = (p[f"experts_{n}"][e] for n in ("gate", "up",
                                                         "down"))
        w_e = jnp.sum(jnp.where(sel == first + e, w, 0.0), axis=-1)
        return acc + w_e[:, None] * _ffn(m, gate, up, down, mm), None

    routed, _ = jax.lax.scan(one, jnp.zeros_like(m), jnp.arange(held))
    return shared, routed


def held_margin(m, p, cfg: dict):
    """m [T, d] -> [T]: how far each token's selection is from choosing
    another set of HELD experts (``reference/qwen3_next.py``'s rule, on
    the router's logits); infinite for a layer with no router."""
    if "router" not in p:
        return jnp.full((m.shape[0],), jnp.inf)
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
    eps, r = cfg["rms_norm_eps"], cfg["residual_multiplier"]
    a = _rms(h, p["norm_in"], eps)
    if is_attention(cfg, index):
        h = h + r * attention(a, p, cfg, mm,
                              F32 if precision == "f32" else jnp.bfloat16)
    else:
        h = h + r * mamba(a, p, cfg, mm)
    m = _rms(h, p["norm_post"], eps)
    return h + r * sum(expert_layer(m, p, cfg, mm)), held_margin(m, p, cfg)


def embed(params, tokens, cfg: dict):
    return params["embed"].astype(F32)[tokens] * cfg["embedding_multiplier"]


def head(params, h, cfg: dict, precision: str = "f32"):
    return make_matmul(precision)(
        _rms(h, params["norm_f"], cfg["rms_norm_eps"]),
        params["embed"].astype(F32).T) / cfg["logits_scaling"]


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
    kinds = cfg["layer_types"]
    for i in range(cfg["num_hidden_layers"]):
        # Layers of one kind share one compiled program.
        h, m_i = _jitted_layer(_key(cfg), kinds.index(kinds[i]), precision)(
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
    keys = ("hidden_size", "rms_norm_eps", "layer_types",
            "num_attention_heads", "num_key_value_heads",
            "attention_multiplier", "residual_multiplier", "mamba_n_heads",
            "mamba_d_head", "mamba_d_state", "mamba_d_conv",
            "mamba_conv_bias", "num_local_experts", "num_experts_per_tok",
            "deployment")
    return json.dumps({k: cfg.get(k) for k in keys}, sort_keys=True)


@functools.lru_cache(maxsize=None)
def _jitted_layer(key: str, index: int, precision: str):
    return jax.jit(functools.partial(layer, cfg=json.loads(key),
                                     index=index, precision=precision))
