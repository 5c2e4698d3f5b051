"""Plain reference for the GPT-2 configurations: the forward pass, the
loss, and momentum-SGD steps in straightforward ``jax.numpy``, float32,
every matrix multiplication at ``Precision.HIGHEST`` (on a TPU a float32
matmul is otherwise done in bfloat16 passes).  No cache, no batching
tricks, no kernels; it imports nothing of the program and is never given
an array the program has made.

It follows the published description (Radford et al. 2019; the
``GPT2LMHeadModel`` of the source config): learned positions, pre-LN
blocks, full causal multi-head attention, tanh-GELU MLP, final LN, head
tied to the token embedding.  Departures, both stated in the
configuration files: LayerNorm epsilon is the file's 1e-6 (the program
cannot set GPT-2's 1e-5), and dropout is 0.

``precision`` selects the CONTROL the comparison must fail: the same
mathematics with its linear layers (and tied head) computed as a lower
precision would — ``bf16`` inputs, ``int8`` (per-row symmetric absmax
for activations, per-column for weights, the usual W8A8 recipe, in the
backward pass too) or ``fp8`` (e4m3 with the same scalings).  Under a
control the attention products run in bfloat16.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
PRECISIONS = ("f32", "bf16", "int8", "fp8")


def _fake_quant(x, axis: int, precision: str):
    """``x`` rounded to what the lower precision can hold, scaled by the
    absmax along ``axis`` (the contraction axis), back in float32."""
    if precision == "bf16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    amax = jnp.where(amax > 0, amax, 1.0)
    if precision == "int8":
        s = amax / 127.0
        return jnp.round(x / s) * s
    s = amax / 448.0                      # e4m3's largest finite value
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _qdot(a, b, precision: str):
    """a [..., k] @ b [k, n] with both operands in ``precision``."""
    return jnp.matmul(_fake_quant(a, -1, precision),
                      _fake_quant(b, 0, precision), precision=HIGHEST)


def make_matmul(precision: str):
    """a [..., k] @ b [k, n].  ``f32`` is the reference; the others
    quantize the operands of the forward product AND of both backward
    products, as a training run in that precision would."""
    if precision not in PRECISIONS:
        raise ValueError(f"unknown precision {precision!r}")
    if precision == "f32":
        return lambda a, b: jnp.matmul(a, b, precision=HIGHEST)

    @jax.custom_vjp
    def mm(a, b):
        return _qdot(a, b, precision)

    def fwd(a, b):
        return mm(a, b), (a, b)

    def bwd(res, g):
        a, b = res
        da = _qdot(g, b.T, precision)
        a2 = a.reshape(-1, a.shape[-1])
        g2 = g.reshape(-1, g.shape[-1])
        db = _qdot(a2.T, g2, precision)
        return da, db

    mm.defvjp(fwd, bwd)
    return mm


def _layer_norm(x, p, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def _gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _block(x, p, n_head, eps, mm, attn_dtype):
    B, T, d = x.shape
    h = _layer_norm(x, p["ln1"], eps)
    qkv = mm(h, p["qkv"]["kernel"]) + p["qkv"]["bias"]
    q, k, v = (t.reshape(B, T, n_head, d // n_head).astype(attn_dtype)
               for t in jnp.split(qkv, 3, axis=-1))
    s = jnp.einsum("bthd,bshd->bhts", q, k, precision=HIGHEST,
                   preferred_element_type=jnp.float32)
    s = s / math.sqrt(d // n_head)
    causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
    s = jnp.where(causal[None, None], s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1).astype(attn_dtype)
    att = jnp.einsum("bhts,bshd->bthd", w, v, precision=HIGHEST,
                     preferred_element_type=jnp.float32).reshape(B, T, d)
    x = x + mm(att, p["attn_out"]["kernel"]) + p["attn_out"]["bias"]
    h = _layer_norm(x, p["ln2"], eps)
    h = _gelu_new(mm(h, p["mlp_in"]["kernel"]) + p["mlp_in"]["bias"])
    return x + mm(h, p["mlp_out"]["kernel"]) + p["mlp_out"]["bias"]


def forward(params, tokens, cfg: dict, precision: str = "f32"):
    """tokens [B, T] int -> logits [B, T, vocab] float32."""
    mm = make_matmul(precision)
    attn_dtype = jnp.float32 if precision == "f32" else jnp.bfloat16
    eps = cfg["layer_norm_epsilon"]
    T = tokens.shape[1]
    x = params["embed"]["embedding"][tokens] + \
        params["pos"]["embedding"][:T][None]
    block = jax.checkpoint(functools.partial(
        _block, n_head=cfg["n_head"], eps=eps, mm=mm,
        attn_dtype=attn_dtype))
    for i in range(cfg["n_layer"]):
        x = block(x, params[f"block{i}"])
    x = _layer_norm(x, params["ln_f"], eps)
    return mm(x, params["embed"]["embedding"].T)


def loss_sum(params, tokens, targets, cfg: dict, precision: str = "f32"):
    """Summed next-token cross-entropy of rows [b, T]."""
    logits = forward(params, tokens, cfg, precision)
    logz = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return jnp.sum(logz - picked)


def _leaf_norms(tree):
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x)))
                      for x in jax.tree.leaves(tree)])


def train_steps(make_params, batches, cfg: dict, *, learning_rate: float,
                momentum: float, rows_per_block: int = 2,
                precision: str = "f32") -> dict:
    """Follow ``len(batches)`` momentum-SGD steps (trace = g + m*trace;
    p -= lr*trace — optax.sgd's rule) from ``make_params()`` on
    ``batches``, each ``[B, T + 1]`` token rows, the mean loss over all
    B*T targets.  Gradients are taken over blocks of ``rows_per_block``
    rows and added straight into the momentum trace, so what is resident
    is the parameters, the trace, one block's gradient and one block's
    float32 logits — it fits beside nothing else of a 774 M model.

    Returns the loss of every step, the per-leaf norm of the FIRST
    gradient (the trace after one step from zero), and the per-leaf norm
    of the parameters' change after the last step (leaves in
    ``jax.tree.leaves`` order)."""
    grad_block = jax.jit(jax.value_and_grad(
        functools.partial(loss_sum, cfg=cfg, precision=precision)))

    @functools.partial(jax.jit, donate_argnums=0)
    def decay(trace):
        return jax.tree.map(lambda t: momentum * t, trace)

    @functools.partial(jax.jit, donate_argnums=0)
    def add(trace, g, scale):
        return jax.tree.map(lambda t, x: t + scale * x, trace, g)

    @functools.partial(jax.jit, donate_argnums=0)
    def descend(p, trace):
        return jax.tree.map(lambda a, t: a - learning_rate * t, p, trace)

    @jax.jit
    def change_norms(p, start):
        return _leaf_norms(jax.tree.map(jnp.subtract, p, start))

    params = make_params()
    trace = jax.tree.map(jnp.zeros_like, params)
    losses, first_grad = [], None
    for batch in batches:
        B, T = batch.shape[0], batch.shape[1] - 1
        scale = 1.0 / (B * T)
        trace = decay(trace)
        total = 0.0
        for r in range(0, B, rows_per_block):
            rows = jnp.asarray(batch[r:r + rows_per_block])
            val, g = grad_block(params, rows[:, :-1], rows[:, 1:])
            total += float(val)
            trace = add(trace, g, scale)
            del g
        losses.append(total * scale)
        if first_grad is None:
            first_grad = jax.device_get(_leaf_norms(trace))
        params = descend(params, trace)
    del trace
    delta = jax.device_get(change_norms(params, make_params()))
    return {"losses": losses, "first_grad_norms": first_grad,
            "delta_norms": delta}


def served_token_gaps(params, prompt, served, cfg: dict,
                      pad_to: int | None = None,
                      control: str | None = None) -> dict:
    """One teacher-forced pass over ``prompt`` followed by the tokens
    that were ``served`` after it, padded to ``pad_to`` positions (the
    configuration's longest sequence unless given).  For every served
    token: how far its reference logit lies below the reference's best
    at that position.  With ``control``, the token judged at each
    position is instead the one the lower precision puts first there."""
    import numpy as np
    pad_to = pad_to or cfg["n_positions"]
    seq = np.concatenate([np.asarray(prompt), np.asarray(served)])
    n_p, n_s = len(prompt), len(served)
    padded = np.zeros((1, pad_to), np.int32)
    padded[0, :len(seq)] = seq
    # Position n_p - 1 + i predicts served[i]; causal attention makes
    # the padding beyond the sequence invisible to those positions.
    rows = slice(n_p - 1, n_p - 1 + n_s)
    ref = _forward_jit(params, jnp.asarray(padded), cfg, "f32")[0, rows]
    judged = jnp.asarray(np.asarray(served, np.int32))
    if control is not None:
        low = _forward_jit(params, jnp.asarray(padded), cfg, control)[0, rows]
        judged = jnp.argmax(low, axis=-1)
    best = jnp.max(ref, axis=-1)
    got = jnp.take_along_axis(ref, judged[:, None], axis=-1)[:, 0]
    gaps = jax.device_get(best - got)
    return {"widest": float(gaps.max()), "tokens": int(n_s)}


def _forward_jit(params, tokens, cfg: dict, precision: str):
    sizes = tuple(sorted((k, v) for k, v in cfg.items()
                         if isinstance(v, (int, float))))
    return _jitted_forward(sizes, precision)(params, tokens)


@functools.lru_cache(maxsize=None)
def _jitted_forward(sizes: tuple, precision: str):
    return jax.jit(functools.partial(forward, cfg=dict(sizes),
                                     precision=precision))
