"""On-device CIFAR augmentation (random reflect-pad-4 crop + hflip).

The host pipeline augments with numpy/C++ (``cifar10.augment``); this is
the same transform expressed as jnp for use INSIDE the jitted train step,
so the device-resident input path (``DeviceDataset`` +
``make_indexed_train_step``) covers the augmented CIFAR workloads too —
batches never touch the host.  Same distribution as the host path (crop
offsets uniform on [0, 8], flip probability 1/2, reflect padding), but a
different RNG stream (``jax.random`` vs the host ``RandomState``), so a
device-augmented run is deterministic per seed yet not bit-identical to a
host-augmented run.

The per-image crop+flip is expressed as two one-hot SELECTOR MATMULS
(one picking output rows, one picking-and-optionally-reversing output
columns), not as ``vmap(dynamic_slice)``: XLA lowers the vmap'd dynamic
crop to a SERIAL per-image while loop on TPU — a chip trace of 2026-08
measured it at ~4.4 ms/step on ResNet-20's batch-256 input; the selector
form runs at batch-gemm speed.  The selection is exact routing:
every output pixel is ``1.0 * one input pixel``.  uint8 pixels are exact
in bfloat16 (integers <= 255 fit its 8-bit mantissa), so one bf16 matmul
pair suffices; float32 pixels are split into three bf16 components
(8+8+8 = 24 mantissa bits, each split subtraction exact by Sterbenz) and
routed per component, so the float path is bitwise-exact too.

All shapes are static and everything is (batched) matmul + elementwise —
XLA fuses the whole thing into the step on the MXU.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

PAD = 4


def _mm_dtype():
    """Matmul component dtype: bfloat16 on accelerators (MXU-native, and
    the 3-way split keeps float32 routing exact); float32 on CPU, whose
    XLA has no bf16 GEMM — f32 dots are exact for one-hot routing, and
    the split degenerates to ``x + 0 + 0`` through the same code path."""
    return jnp.float32 if jax.default_backend() == "cpu" else jnp.bfloat16


def _selector_apply(padded: jnp.ndarray, R: jnp.ndarray,
                    C: jnp.ndarray) -> jnp.ndarray:
    """Route pixels: out[b,r,k,c] = padded[b, yrow(r), xcol(k), c] where
    the one-hot selectors R [B,H,HP] / C [B,HP,W] encode the per-image
    row/column picks.  f32 accumulation — exact for values exact in the
    operand dtype (every output element's dot has ONE nonzero term)."""
    out = jnp.einsum("brh,bhwc->brwc", R, padded,
                     preferred_element_type=jnp.float32)
    return jnp.einsum("brwc,bwk->brkc", out.astype(R.dtype), C,
                      preferred_element_type=jnp.float32)


def _crop_flip_selectors(images: jnp.ndarray, key: jax.Array):
    """(padded, R, C): the reflect-padded input plus the per-image one-hot
    row/column selectors encoding a random crop + hflip draw — the shared
    front half of both augment entry points, so the fused dequant variant
    below draws EXACTLY the same crops/flips as the plain one."""
    b, h, w, c = images.shape
    ky, kx, kf = jax.random.split(key, 3)
    ys = jax.random.randint(ky, (b,), 0, 2 * PAD + 1)
    xs = jax.random.randint(kx, (b,), 0, 2 * PAD + 1)
    flips = jax.random.bernoulli(kf, 0.5, (b,))
    padded = jnp.pad(images, ((0, 0), (PAD, PAD), (PAD, PAD), (0, 0)),
                     mode="reflect")
    hp = h + 2 * PAD
    # R[b, r, hh] = (hh == ys[b] + r): output row r reads padded row
    # ys[b]+r.
    md = _mm_dtype()
    rows = ys[:, None, None] + jnp.arange(h)[None, :, None]
    R = (jnp.arange(hp)[None, None, :] == rows).astype(md)
    # C[b, ww, k] = (ww == xs[b] + (flip ? w-1-k : k)): column pick with
    # the horizontal flip folded into the same selector.
    k = jnp.arange(w)[None, None, :]
    src = jnp.where(flips[:, None, None], w - 1 - k, k) + xs[:, None, None]
    C = (jnp.arange(hp)[None, :, None] == src).astype(md)
    return padded, R, C


def cifar_augment_device(images: jnp.ndarray, key: jax.Array) -> jnp.ndarray:
    """[B, H, W, C] uint8 or float → same shape+dtype, randomly cropped +
    flipped (pure pixel rearrangement, bitwise-exact for both dtypes)."""
    padded, R, C = _crop_flip_selectors(images, key)
    md = R.dtype

    if images.dtype == jnp.uint8:
        out = _selector_apply(padded.astype(md), R, C)
        return out.astype(images.dtype)
    x = padded.astype(jnp.float32)
    hi = x.astype(md)
    mid = (x - hi.astype(jnp.float32)).astype(md)
    lo = (x - hi.astype(jnp.float32) - mid.astype(jnp.float32)).astype(md)
    out = (_selector_apply(hi, R, C) + _selector_apply(mid, R, C)
           ) + _selector_apply(lo, R, C)
    return out.astype(images.dtype)


def cifar_augment_dequant_device(images: jnp.ndarray, key: jax.Array,
                                 scale: jnp.ndarray,
                                 bias: jnp.ndarray) -> jnp.ndarray:
    """[B, H, W, C] uint8 → float32: random crop + hflip AND the affine
    dequant (``f32(u) * scale + bias``, constants from the data pytree's
    ``dq_scale``/``dq_bias``) in ONE pass — the round-5 input-share fix
    for the augmented path.

    The plain route (``cifar_augment_device`` then dequant) materializes
    an augmented uint8 batch between the two: the selector matmuls
    accumulate in f32, cast BACK to uint8, and the dequant re-reads and
    re-converts it.  Here the selectors' f32 output (exact — every output
    pixel's dot has one nonzero term, and bytes are exact in bf16) feeds
    the affine directly, so XLA fuses crop/flip/dequant into the selector
    matmuls' epilogue: no uint8 intermediate, one fewer elementwise pass
    over the batch.  Bitwise-identical to augment-then-dequant: the
    routed f32 values ARE the byte values, so the affine sees the same
    inputs either way (same crops/flips too — ``_crop_flip_selectors`` is
    shared)."""
    if images.dtype != jnp.uint8:
        raise TypeError(f"cifar_augment_dequant_device fuses the uint8 "
                        f"dequant; got {images.dtype} (use "
                        f"cifar_augment_device)")
    padded, R, C = _crop_flip_selectors(images, key)
    out = _selector_apply(padded.astype(R.dtype), R, C)
    # out[b,r,k,c] holds the exact routed byte value in f32; scale/bias
    # are [1] or [C] and broadcast over the trailing channel axis — the
    # same fused multiply-add apply_dequant_affine computes.
    return out * scale + bias
