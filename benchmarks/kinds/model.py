"""The program's model of a configuration file: ``TransformerLM`` built
from the sizes as the file states them, through the constructor the
trainer and ``tools/serve_lm.py`` use."""

from __future__ import annotations


def transformer_lm(cfg: dict, **kwargs):
    from distributedtensorflowexample_tpu.models.transformer_lm import (
        TransformerLM)
    d = cfg["n_embd"]
    return TransformerLM(
        vocab_size=cfg["vocab_size"], n_layers=cfg["n_layer"], d_model=d,
        n_heads=cfg["n_head"], d_ff=cfg.get("n_inner") or 4 * d,
        max_len=cfg["n_positions"], **kwargs)
