"""Pallas TPU kernels for the framework's hot ops.

The reference's hot-path math lived in library native code (cuDNN kernels,
TF C++ executor — SURVEY.md §2 "native dependency" table).  Our TPU-native
equivalents are mostly XLA-compiled jnp, but the ops XLA's fusion touches
every step — the loss head, the optimizer update, and the input-path
row gather — also ship as hand-written Pallas kernels: single VMEM pass,
no HBM round-trips between the fused stages, selectable per run
(``RunConfig.pallas_ce`` for the loss head, ``RunConfig.fused_optimizer``
for the update, ``RunConfig.dequant_impl="pallas"`` for the fused
gather+dequant of a uint8-resident split).

The LM block's blocked causal attention (``attention.py``) is the one
kernel with no flag: ``ops.attention.causal_attention`` takes it from the
backend and the shapes, and imports it from its module only then (this
package's other kernels are imported eagerly below).

All kernels run in interpret mode on CPU, so the same code path is
unit-testable without a TPU (SURVEY.md §4 test strategy).
"""

from distributedtensorflowexample_tpu.ops.pallas.cross_entropy import (
    fused_softmax_cross_entropy_rows)
from distributedtensorflowexample_tpu.ops.pallas.dequant import (
    fused_gather_dequant)
from distributedtensorflowexample_tpu.ops.pallas.sgd import (
    fused_momentum_sgd, fused_sgd_apply)

__all__ = [
    "fused_softmax_cross_entropy_rows",
    "fused_gather_dequant",
    "fused_momentum_sgd",
    "fused_sgd_apply",
]
