"""A family no file of the harness knows: the same block as GPT-2, with
its sizes under the key names most published configurations use
(``hidden_size``, ``num_hidden_layers``, ...), mapped onto the
program's ``TransformerLM``.  ``test_family.py`` registers this module
as a family and as a reference and drives both kinds of cell with it:
what a later PR does by adding ``families/<f>.py`` and
``reference/<f>.py``."""

from benchmarks.families import gpt2 as _family
from benchmarks.reference import gpt2 as _reference

KEYS = {"n_embd": "hidden_size", "n_layer": "num_hidden_layers",
        "n_head": "num_attention_heads", "n_inner": "intermediate_size",
        "n_positions": "max_position_embeddings"}


def _own_keys(cfg: dict) -> dict:
    """``cfg`` as GPT-2 spells it, from this family's keys ALONE: a
    GPT-2 key that came along in the file is not read."""
    return {**{k: v for k, v in cfg.items() if k not in KEYS},
            **{gpt2: cfg[own] for gpt2, own in KEYS.items()}}


def _with_own_keys(fn, cfg_at: int):
    def call(*args, **kwargs):
        args = list(args)
        if "cfg" in kwargs:
            kwargs["cfg"] = _own_keys(kwargs["cfg"])
        else:
            args[cfg_at] = _own_keys(args[cfg_at])
        return fn(*args, **kwargs)
    return call


# What the harness reads of a family (families/__init__.py) ...
for _name in ("build_model", "init_fn", "init_params",
              "train_flops_per_token", "decode_step_flops",
              "decode_step_bytes"):
    globals()[_name] = _with_own_keys(getattr(_family, _name), 0)
# ... and the reference's.
train_steps = _with_own_keys(_reference.train_steps, 2)
served_token_gaps = _with_own_keys(_reference.served_token_gaps, 3)
