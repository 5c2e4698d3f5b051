from distributedtensorflowexample_tpu.config import CONFIG_MODEL_TYPES
from distributedtensorflowexample_tpu.models.softmax import SoftmaxRegression
from distributedtensorflowexample_tpu.models.mnist_cnn import MnistCNN
from distributedtensorflowexample_tpu.models.resnet import ResNet20, ResNetCIFAR
from distributedtensorflowexample_tpu.models.transformer_lm import (
    LM_SIZES, LM_VOCAB, TransformerLM, build_lm)

import jax.numpy as jnp


def _lm_entry(size):
    # Dropout defaults to 0.0 for the LM family (trainer_lm overrides the
    # RunConfig 0.5 CNN default); remat/dtype knobs flow through like
    # ResNet's.
    return lambda **kw: build_lm(size,
                                 dropout=kw.get("dropout", 0.0),
                                 dtype=kw.get("dtype", jnp.bfloat16),
                                 remat=kw.get("remat", "none"))


_REGISTRY = {
    "softmax": lambda **kw: SoftmaxRegression(num_classes=10),
    "mnist_cnn": lambda **kw: MnistCNN(num_classes=10,
                                       dropout_rate=kw.get("dropout", 0.5),
                                       dtype=kw.get("dtype", jnp.bfloat16)),
    "resnet20": lambda **kw: ResNet20(num_classes=10,
                                      dtype=kw.get("dtype", jnp.bfloat16),
                                      remat=kw.get("remat", "none")),
    **{size: _lm_entry(size) for size in LM_SIZES},
}


def build_model(name: str, **kw):
    """Model registry keyed by the names the trainer CLIs use."""
    try:
        return _REGISTRY[name](**kw)
    except KeyError:
        raise ValueError(f"unknown model {name!r}; have {sorted(_REGISTRY)}") from None


def build_model_from_config(config, **kw):
    """A model from a published configuration (a dict, or the path of
    its JSON file) by its ``model_type``: the one constructor the
    benchmark's families, ``serving/promote.py`` and ``tools/serve_lm.py
    --model_config`` share.  The architecture's module is imported here,
    when a configuration asks for it, not with the package."""
    if not isinstance(config, dict):
        import json
        with open(config) as f:
            config = json.load(f)
    kind = config.get("model_type")
    if kind == "afmoe":
        from distributedtensorflowexample_tpu.models.afmoe import build_afmoe
        return build_afmoe(config, **kw)
    if kind == "qwen3_next":
        from distributedtensorflowexample_tpu.models.qwen3_next import (
            build_qwen3_next)
        return build_qwen3_next(config, **kw)
    if kind == "bailing_hybrid":
        from distributedtensorflowexample_tpu.models.bailing_hybrid import (
            build_bailing_hybrid)
        return build_bailing_hybrid(config, **kw)
    if kind == "granitemoehybrid":
        from distributedtensorflowexample_tpu.models.granitemoehybrid import (
            build_granitemoehybrid)
        return build_granitemoehybrid(config, **kw)
    if kind == "kimi_k2":
        from distributedtensorflowexample_tpu.models.kimi_k2 import (
            build_kimi_k2)
        return build_kimi_k2(config, **kw)
    raise ValueError(
        f"no model is built from a configuration of model_type {kind!r} "
        f"(have: {', '.join(CONFIG_MODEL_TYPES)}; the GPT-2 ladder is built "
        f"by size, LM_SIZES)")


__all__ = ["SoftmaxRegression", "MnistCNN", "ResNet20", "ResNetCIFAR",
           "TransformerLM", "build_lm", "LM_SIZES", "LM_VOCAB",
           "build_model", "build_model_from_config"]
