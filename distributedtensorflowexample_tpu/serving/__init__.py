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

Import edges (PR 42): the hot path — ``serving.engine`` and
``serving.queue``, what a serving worker and every serving cell of the
benchmark import — loads nothing of ``resilience/``,
``training/checkpoint.py`` or ``serving.promote``.  ``promote`` is the
one module here that opens snapshots, so it imports optax, the training
state and ``resilience.snapshot``, and importing that runs the whole of
``resilience/__init__`` (fleet, scheduler, remediate, shardstore);
through ``as_prompt``, one numpy function that ``queue`` took from it,
every serving process paid for that control plane and for
``orbax.checkpoint`` behind it — 12–14 s of every serving run's set-up
on a chip machine (PERF.md §6, PR 42).
``as_prompt`` now lives in ``queue`` (``promote.as_prompt`` is the same
object), and ``PromotedModel``, ``init_lm_snapshot`` and ``promote``
resolve on first access below: a process that promotes a snapshot pays
those imports when it first names one of them, and one that is handed
its weights never does.  tests/test_import_graph.py holds both paths to
it.
"""

import importlib

from distributedtensorflowexample_tpu.serving.engine import (  # noqa: F401
    DECODE_HLO_CONTRACT, DecodeEngine, ServingLM, serving_lm_for)
from distributedtensorflowexample_tpu.serving.queue import (  # noqa: F401
    ContinuousBatcher, Request, RequestQueue)

__all__ = [
    "DECODE_HLO_CONTRACT", "DecodeEngine", "ServingLM", "serving_lm_for",
    "PromotedModel", "init_lm_snapshot", "promote",
    "ContinuousBatcher", "Request", "RequestQueue",
]

_FROM_PROMOTE = ("PromotedModel", "init_lm_snapshot", "promote")


def __getattr__(name):
    if name in _FROM_PROMOTE:
        module = importlib.import_module(__name__ + ".promote")
        # Importing the submodule bound the MODULE to ``promote`` here;
        # the package's name of that spelling has always been the
        # function, so bind all three for good.  (A caller that imports
        # the submodule itself and reads ``serving.promote`` before any
        # of the three gets the module: import from the submodule.)
        for attr in _FROM_PROMOTE:
            globals()[attr] = getattr(module, attr)
        return globals()[name]
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
