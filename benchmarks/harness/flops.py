"""Operations and bytes a GPT-2 step REQUIRES, as functions of shapes.
Matrix multiplications only (2 per multiply-add); causal attention is
counted at half the square; recomputation is never counted."""

from __future__ import annotations


def _dims(cfg: dict) -> tuple:
    d = cfg["n_embd"]
    return cfg["n_layer"], d, cfg.get("n_inner") or 4 * d, cfg["vocab_size"]


def forward_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward pass of one token of a ``seq_len`` sequence under causal
    attention: qkv, attention output, the two MLP matrices, the scores
    and the weighted sum over (seq_len + 1) / 2 keys on average, and the
    tied head."""
    L, d, ff, V = _dims(cfg)
    per_layer = 2 * d * 3 * d + 2 * d * d + 2 * 2 * d * ff
    attn = 2 * 2 * d * (seq_len + 1) / 2
    return L * (per_layer + attn) + 2 * d * V


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward plus backward: the backward pass of a matrix
    multiplication is two of the same size."""
    return 3 * forward_flops_per_token(cfg, seq_len)


def decode_step_flops(cfg: dict, live_rows: int, slots: int) -> float:
    """One decode step over ``slots`` single-token queries whose caches
    hold ``live_rows`` rows in total."""
    L, d, ff, V = _dims(cfg)
    per_layer = 2 * d * 3 * d + 2 * d * d + 2 * 2 * d * ff
    return slots * (L * per_layer + 2 * d * V) + L * 2 * 2 * d * live_rows


def decode_step_bytes(cfg: dict, live_rows: int, weight_bytes: int,
                      cache_bytes_per_el: int = 2) -> float:
    """Bytes one decode step must move: every weight once (as stored)
    and every live cache row (K and V, all layers) once."""
    L, d, _, _ = _dims(cfg)
    return weight_bytes + live_rows * L * 2 * d * cache_bytes_per_el


def roofline_seconds(flops: float, nbytes: float, peaks: dict) -> tuple:
    """(least seconds, which bound) on a device with ``peaks``."""
    t_f = flops / peaks["bf16_flops_per_s"]
    t_b = nbytes / peaks["hbm_bytes_per_s"]
    return (t_f, "compute") if t_f >= t_b else (t_b, "bandwidth")
