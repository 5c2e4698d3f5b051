"""Shared TPU tiling helpers for the Pallas kernels in this package.

One source of truth for the lane width and the row-block picker so the
kernels' padding behavior cannot diverge (pallas_guide.md tiling table:
float32 min tile is 8 sublanes x 128 lanes).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

LANES = 128      # last-dim tile width, all dtypes
SUBLANES = 8     # float32 second-to-last-dim tile


def resolve_interpret(interpret: bool | None) -> bool:
    """``interpret=None`` means: the Pallas interpreter on the ``cpu``
    platform (so CPU tests run the identical kernel code), the compiled
    Mosaic kernel everywhere else.  A chip run is never interpreted
    unless the caller says so in as many words."""
    if interpret is None:
        return jax.default_backend() == "cpu"
    return interpret


def pick_block(rows: int, max_block: int) -> int:
    """Largest 8-aligned power-of-two row block ≤ max_block dividing rows."""
    cand = max_block
    while cand >= SUBLANES:
        if rows % cand == 0:
            return cand
        cand //= 2
    raise ValueError(f"{rows} rows not a multiple of {SUBLANES}")


def pad_rows(x: jnp.ndarray, multiple: int, fill) -> jnp.ndarray:
    """Pad the leading dim up to a multiple, filling with ``fill``."""
    b = x.shape[0]
    bp = ((b + multiple - 1) // multiple) * multiple
    if bp != b:
        x = jnp.pad(x, ((0, bp - b),) + ((0, 0),) * (x.ndim - 1),
                    constant_values=fill)
    return x
