"""serving/ — the continuous-batching graft-LM inference engine (PR 15).

The north star serves "heavy traffic from millions of users", and until
this package the repo was 100% training.  serving/ is the read path the
training stack's snapshots promote into:

- :mod:`~distributedtensorflowexample_tpu.serving.engine` — the
  donate-and-reuse compiled decode step over a preallocated per-slot
  KV-cache (explicit batched einsums mirroring
  ``models/transformer_lm.py``, token-exact with the training forward),
  pinned by an HLO contract next to the step builder;
- :mod:`~distributedtensorflowexample_tpu.serving.promote` — snapshot →
  serving promotion over the SnapshotStore validity checks (torn newest
  falls back; ``zero3_rows``/``bucket_rows`` states materialize through
  the PR 12 ``Zero3Layout.materialize`` seam);
- :mod:`~distributedtensorflowexample_tpu.serving.queue` — the request
  queue + continuous batcher: new requests admitted into open decode
  slots at step boundaries (never batch-drain), padding-bucketed
  prefill, a latency-SLO admission knob, p50/p99/tokens-per-sec through
  the ``obs/`` registry;
- :mod:`~distributedtensorflowexample_tpu.serving.loadgen` — the
  closed-loop load generator behind ``tools/serve_lm.py --drive``;
- :mod:`~distributedtensorflowexample_tpu.serving.frontend` — the
  opt-in (``SERVE_PORT``) stdlib HTTP request front.

serving/ imports jax by design (it runs the model); the reverse edge is
forbidden — ``obs/`` must never grow a serving import (the stdlib-only
import-graph proof in graftlint stays the arbiter, and
tests/test_serving.py pins the directional edge).
"""

from distributedtensorflowexample_tpu.serving.engine import (  # noqa: F401
    DECODE_HLO_CONTRACT, DecodeEngine, ServingLM, serving_lm_for)
from distributedtensorflowexample_tpu.serving.promote import (  # noqa: F401
    PromotedModel, init_lm_snapshot, promote)
from distributedtensorflowexample_tpu.serving.queue import (  # noqa: F401
    ContinuousBatcher, Request, RequestQueue)

__all__ = [
    "DECODE_HLO_CONTRACT", "DecodeEngine", "ServingLM", "serving_lm_for",
    "PromotedModel", "init_lm_snapshot", "promote",
    "ContinuousBatcher", "Request", "RequestQueue",
]
