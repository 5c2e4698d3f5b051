"""Canonical uint8 <-> float32 dequantization arithmetic (host side).

THE definition of what a stored byte means in float: every producer
(mnist/cifar loaders, the native C++ parser, the synthetic generator) and
every consumer (the in-step device dequant in ``parallel.sync``, the host
reference ``dequant_numpy``, the recovery check ``try_quantize``) routes
through the constants and the rounding rule defined here, so bitwise
parity between any two paths is a property of this module, not a
coincidence to re-verify per call site.

The canonical form is the fused AFFINE map ``f32(u) * scale + bias`` with
ONE rounding (an FMA): that is what XLA:TPU emits for the jnp expression,
and it is the fastest dequant measured on chip (one chip window of
2026-08: 1,962.6 steps/s/chip vs 479.6 for the LUT-gather default it
replaced — PERF.md "History").  The host
reference reproduces the single rounding exactly in float64: for byte
inputs and these constants the f64 product and sum are exact, so the one
f32 cast at the end IS the fma rounding.  ``affine_matches_lut`` verifies
per spec, over all 256 byte values, that the affine reproduces the
tabulated loader arithmetic bitwise — true for both shipped specs by
construction (the loaders compute through this module), and the guard
that makes ``dequant_impl="auto"`` fall back to the bitwise one-hot LUT
form if a future spec introduces non-affine host arithmetic (e.g. a
gamma curve).

Numpy-only on purpose: the loaders must stay importable without jax (the
device-side appliers live in ``data.device_dataset``).
"""

from __future__ import annotations

import numpy as np

#: float32 1/255 — the "unit" spec's scale.  Multiplying by this constant
#: (NOT dividing by 255: an f32 division rounds differently on 126 of the
#: 256 byte values, and XLA lowers the division to this multiply anyway)
#: is the canonical byte -> [0,1] conversion everywhere in the repo.
U8_UNIT_SCALE = np.float32(1.0) / np.float32(255.0)


def make_dequant_affine(spec: str) -> tuple[np.ndarray, np.ndarray]:
    """(scale, bias) float32 vectors (shape [1] or [C]) of the canonical
    affine dequant ``f32(u) * scale + bias`` for ``spec``.

    - ``"unit"``: raw pixels, floats are ``u * (1/255)`` (bias 0).
    - ``"cifar"``: mean/std-normalized CIFAR pixels, the whole
      ``(u/255 - MEAN) / STD`` pipeline folded into one affine map with
      the constants reduced in float64.
    """
    if spec == "unit":
        return (np.asarray([U8_UNIT_SCALE], np.float32),
                np.zeros(1, np.float32))
    if spec == "cifar":
        from distributedtensorflowexample_tpu.data.cifar10 import (
            CIFAR10_MEAN, CIFAR10_STD)
        scale = (1.0 / (255.0 * np.float64(CIFAR10_STD))).astype(np.float32)
        bias = (-np.float64(CIFAR10_MEAN) / CIFAR10_STD).astype(np.float32)
        return scale, bias
    raise ValueError(f"unknown dequant spec {spec!r}")


def affine_numpy(u8: np.ndarray, spec: str) -> np.ndarray:
    """The canonical host dequant: ``f32(u) * scale + bias`` with ONE
    rounding, reproduced exactly via float64 (the product of a byte value
    and an f32 constant is exact in f64, as is adding the f32 bias, so the
    final f32 cast is the fused multiply-add's single rounding — bitwise
    what XLA's contracted mul+add computes on the gathered batch)."""
    s, b = make_dequant_affine(spec)
    x = u8.astype(np.float64) * s.astype(np.float64) + b.astype(np.float64)
    return x.astype(np.float32)


def make_dequant_lut(spec: str) -> np.ndarray:
    """The 256 float32 values a uint8 pixel dequantizes to — the
    canonical affine arithmetic tabulated.  Shape [256] ("unit") or
    [256, C] (per-channel normalization).  Consumed by the one-hot-matmul
    and gather dequant impls; bitwise-identical to the affine impl for
    every spec where ``affine_matches_lut`` holds (both shipped specs)."""
    u = np.arange(256, dtype=np.uint8)[:, None]
    out = affine_numpy(u, spec)
    return out[:, 0] if out.shape[1] == 1 else out


def affine_matches_lut(spec: str) -> bool:
    """True iff the affine form reproduces ALL 256 LUT entries bitwise —
    the quantize-time verification that lets ``dequant_impl="auto"``
    lower to the affine fast path while keeping the bitwise-parity
    contract.  Bitwise means bitwise: compared as integer bit patterns,
    so even a -0.0/+0.0 swap would fail."""
    lut = make_dequant_lut(spec)
    u = np.arange(256, dtype=np.uint8)[:, None]
    aff = affine_numpy(u, spec)
    aff = aff[:, 0] if lut.ndim == 1 else aff
    return bool(np.array_equal(lut.view(np.int32), aff.view(np.int32)))


def dequant_numpy(u8: np.ndarray, spec: str) -> np.ndarray:
    """Host-side reference dequantization (the float32 values the loader
    produces for these bytes) — an alias of the canonical affine."""
    return affine_numpy(u8, spec)


def try_quantize(x: np.ndarray, chunk: int = 4096):
    """(uint8 split, dequant spec) if ``x`` is EXACTLY representable as
    ``dequant_numpy(u8, spec)`` for one of the known pipelines (raw
    [0,1] "unit" pixels, or CIFAR mean/std-normalized); else None.

    Exactness is verified bitwise chunk-by-chunk (bounded memory), so a
    caller can never lose precision silently: anything not byte-exact —
    arbitrary float inputs, a future normalization this doesn't know —
    stays float32-resident."""
    if x.dtype != np.float32 or x.ndim < 2 or x.size == 0:
        # Empty splits fall through to the caller's own size validation
        # (min()/max() on a zero-length array would raise here first).
        return None
    lo, hi = float(x.min()), float(x.max())
    candidates = []
    if 0.0 <= lo and hi <= 1.0:
        candidates.append(("unit",
                           lambda c: np.rint(c * 255.0)))
    if x.shape[-1] == 3:
        from distributedtensorflowexample_tpu.data.cifar10 import (
            CIFAR10_MEAN, CIFAR10_STD)
        candidates.append(("cifar", lambda c: np.rint(
            (c.astype(np.float64) * CIFAR10_STD + CIFAR10_MEAN) * 255.0)))
    for spec, recover in candidates:
        out = np.empty(x.shape, np.uint8)
        ok = True
        for i in range(0, len(x), chunk):
            c = x[i:i + chunk]
            u = recover(c)
            if u.min() < 0 or u.max() > 255:
                ok = False
                break
            u = u.astype(np.uint8)
            if not np.array_equal(dequant_numpy(u, spec), c):
                ok = False
                break
            out[i:i + chunk] = u
        if ok:
            return out, spec
    return None
