"""Decoder-only transformer LM — the flagship workload where the perf
knobs finally bind (ROADMAP "New directions" #5).

Every scaling feature since PR 2 (``--remat block``, ZeRO-1
``--shard_update``, knee-sized ``--bucket_grads``) is parity-tested but
HBM-noise at ResNet-20/0.27M params.  This model supplies the scale those
features were built for: a pre-LN, causal, weight-tied decoder with a
config-selectable size ladder (``LM_SIZES``) from ``lm_tiny`` (tier-1
parity tests, ~0.1M params) to ``lm_base`` (~57M params — optimizer
state + activations pressure real memory, arXiv:2004.13336's own
evaluation regime).

Design notes:

* **BN-free by construction** — every normalization is LayerNorm (a
  per-row op with no cross-batch statistics), so the ``--bucket_grads``
  / ZeRO-1 refusals for BatchNorm models never trigger and the bucketed
  per-shard gradient region computes the identical model.
* **Weight-tied embedding** — the output head is ``embed.attend``
  (logits = x @ E^T), halving head params and making the vocab matmul
  the same dot-general family the MFU audit prices.
* **``remat="block"``** — same policy surface as ResNet: each decoder
  block is ``nn.remat``-wrapped so the backward pass recomputes the
  block's forward instead of keeping its activations resident.  At
  lm_base the resident set is dominated by per-block attention
  probabilities ([B, H, T, T]) and MLP activations ([B, T, 4d]) — the
  bytes the PR-2 knob was built to trade for one extra forward.
  Same math bitwise (recomputation replays identical ops).
* **Out-of-vocab poison, not silent clamp** — XLA gathers CLAMP
  out-of-range indices, so a corrupted token batch (the
  ``corrupt_batch`` fault: garbage bytes off the wire) would silently
  train on wrong-but-legal embeddings forever.  Instead the logits are
  poisoned to NaN when any token id falls outside ``[0, vocab)``:
  NaNGuardHook fails fast, the flight recorder dumps the postmortem,
  and a supervised restart resumes from the last healthy snapshot —
  the same refuse-loudly discipline as the uint8 ``nan_loss`` refusal
  (resilience/faults.py).

Compute dtype is ``dtype`` (bfloat16 default) with f32 params and f32
softmax/logits, matching the other models' MXU discipline.
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp

from distributedtensorflowexample_tpu.ops.attention import causal_attention

#: Default vocabulary — deliberately < 256 so (a) token splits store as
#: uint8 in HBM (the quantized-data-path win: 4x less gather traffic
#: than int32) and (b) random garbage bytes are detectably out-of-vocab
#: (the corrupt_batch -> OOV-poison -> NaNGuard path has real teeth).
LM_VOCAB = 250

#: The size ladder.  lm_tiny is the tier-1 parity workload; lm_base is
#: sized so f32 params + momentum alone are ~0.5 GB replicated (~57M
#: params) — the scale where --remat/--shard_update/--bucket_grads stop
#: being HBM-noise.  lm_small is the throughput rung in between (CPU-
#: measurable step times at real-ish shapes).
LM_SIZES = {
    "lm_tiny": dict(n_layers=2, d_model=64, n_heads=2, d_ff=256),
    "lm_small": dict(n_layers=4, d_model=256, n_heads=4, d_ff=1024),
    "lm_base": dict(n_layers=8, d_model=768, n_heads=12, d_ff=3072),
}


class DecoderBlock(nn.Module):
    """Pre-LN decoder block: LN -> causal MHA -> residual, LN -> MLP ->
    residual.  Attention is ``ops.attention.causal_attention``: on the
    CPU and for shapes that do not tile, explicit batched einsums (two
    dot-generals with batch dims)."""
    d_model: int
    n_heads: int
    d_ff: int
    dropout_rate: float = 0.0
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x, train: bool = False):
        B, T, _ = x.shape
        Dh = self.d_model // self.n_heads
        h = nn.LayerNorm(dtype=self.dtype, name="ln1")(x)
        qkv = nn.Dense(3 * self.d_model, dtype=self.dtype, name="qkv")(h)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        q = q.reshape(B, T, self.n_heads, Dh)
        k = k.reshape(B, T, self.n_heads, Dh)
        v = v.reshape(B, T, self.n_heads, Dh)
        # "attn" names the attention operations in the device trace (flax
        # scopes each Dense and LayerNorm by its module name already);
        # metadata only.  causal_attention takes the blocked kernels
        # where the program is built for a TPU and the shapes tile, and
        # the einsum chain everywhere else (ops/attention.py).
        with jax.named_scope("attn"):
            att = causal_attention(q, k, v).reshape(B, T, -1)
        att = nn.Dense(self.d_model, dtype=self.dtype, name="attn_out")(att)
        att = nn.Dropout(self.dropout_rate,
                         deterministic=not train)(att)
        x = x + att
        h = nn.LayerNorm(dtype=self.dtype, name="ln2")(x)
        h = nn.Dense(self.d_ff, dtype=self.dtype, name="mlp_in")(h)
        h = nn.gelu(h)
        h = nn.Dense(self.d_model, dtype=self.dtype, name="mlp_out")(h)
        h = nn.Dropout(self.dropout_rate, deterministic=not train)(h)
        return x + h


class TransformerLM(nn.Module):
    """Decoder-only LM: tokens [B, T] (any integer dtype; uint8 is the
    resident-split storage) -> logits [B, T, vocab] f32."""
    vocab_size: int = LM_VOCAB
    n_layers: int = 2
    d_model: int = 64
    n_heads: int = 2
    d_ff: int = 256
    max_len: int = 512
    dropout_rate: float = 0.0
    dtype: jnp.dtype = jnp.bfloat16
    remat: str = "none"           # none | block

    @nn.compact
    def __call__(self, tokens, train: bool = False):
        if self.remat not in ("none", "block"):
            raise ValueError(f"unknown remat policy {self.remat!r} "
                             "(one of none, block)")
        tokens = tokens.astype(jnp.int32)
        if tokens.ndim != 2:
            raise ValueError(f"token batch must be [B, T], got "
                             f"{tokens.shape}")
        T = tokens.shape[1]
        if T > self.max_len:
            raise ValueError(f"sequence length {T} exceeds max_len "
                             f"{self.max_len}")
        # Refuse-loudly seam (see module docstring): any out-of-vocab id
        # poisons the logits to NaN instead of silently clamping into a
        # wrong embedding row.  The clip below keeps the gather itself
        # in-range; the poison carries the corruption to NaNGuardHook.
        oov = jnp.any((tokens < 0) | (tokens >= self.vocab_size))
        embed = nn.Embed(self.vocab_size, self.d_model, dtype=self.dtype,
                         name="embed")
        x = embed(jnp.clip(tokens, 0, self.vocab_size - 1))
        pos = nn.Embed(self.max_len, self.d_model, dtype=self.dtype,
                       name="pos")(jnp.arange(T, dtype=jnp.int32))
        x = x + pos[None]
        x = nn.Dropout(self.dropout_rate, deterministic=not train)(x)
        block_cls = DecoderBlock
        if self.remat == "block":
            # static_argnums counts __call__'s args with self at 0: the
            # train flag (2) gates dropout and must stay a python bool
            # under the remat trace (the ResNet precedent).
            block_cls = nn.remat(DecoderBlock, static_argnums=(2,))
        for i in range(self.n_layers):
            x = block_cls(self.d_model, self.n_heads, self.d_ff,
                          self.dropout_rate, self.dtype,
                          name=f"block{i}")(x, train)
        x = nn.LayerNorm(dtype=self.dtype, name="ln_f")(x)
        # Weight-tied head: logits = x @ E^T (flax attend), f32 at the
        # boundary like every other model's logits.
        with jax.named_scope("head"):
            logits = embed.attend(x).astype(jnp.float32)
        return logits + jnp.where(oov, jnp.float32(jnp.nan),
                                  jnp.float32(0.0))

    def serving_module(self):
        """What ``DecodeEngine`` serves this model with: its prefill /
        token-step twin (``serving/engine.py: ServingLM``, which binds
        this model's parameter tree unchanged and states the cache)."""
        from distributedtensorflowexample_tpu.serving.engine import (
            serving_lm_for)
        return serving_lm_for(self)


def build_lm(size: str, vocab_size: int = LM_VOCAB,
             dropout: float = 0.0, dtype: jnp.dtype = jnp.bfloat16,
             remat: str = "none", max_len: int = 512) -> TransformerLM:
    """Size-ladder constructor (``LM_SIZES`` keys)."""
    try:
        dims = LM_SIZES[size]
    except KeyError:
        raise ValueError(f"unknown LM size {size!r}; have "
                         f"{sorted(LM_SIZES)}") from None
    return TransformerLM(vocab_size=vocab_size, max_len=max_len,
                         dropout_rate=dropout, dtype=dtype, remat=remat,
                         **dims)
