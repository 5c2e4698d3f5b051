"""obs/ — unified telemetry: metrics registry, trace spans, flight
recorder, and exporters for every run on the box.

Rounds 3-5 were one long outage diagnosed by grepping ad-hoc prints out
of watcher logs; this package is the structured replacement — one
instrumentation surface shared by trainers, the supervisor, the bench
family, and the capture queue (the TF-Replicator lesson: one monitoring
surface for every parallelism mode).

Four cooperating pieces, each usable alone:

- :mod:`.metrics` — process-wide registry of counters/gauges/histograms
  with labels, monotonic-clock timestamps, and snapshot/delta semantics.
  The hot path (one counter increment) is lock-free and microbench-
  guarded below 2 us (tests/test_obs.py).
- :mod:`.trace` — nestable span API (``with span("dispatch"): ...``)
  emitting JSONL trace events with step/attempt/phase context picked up
  from the supervisor's env (``SUPERVISE_ATTEMPT``, ``OBS_PHASE``), and
  under it the always-on hot-path tape: ``hot_span`` for per-boundary
  use (a ring record plus a profiler annotation, about a microsecond),
  ``tape()`` to read it.
- :mod:`.recorder` — bounded in-memory flight recorder (ring of recent
  spans, metric deltas, and the loss-tape tail) that dumps atomically
  to ``flight_<pid>.json`` on SIGTERM / NaN-guard trip / supervisor
  escalation, so every dead run leaves a postmortem.
- :mod:`.export` — Prometheus-textfile and JSONL exporters;
  ``tools/obs_report.py`` renders any dump as an OUTAGE_r*-style table.
- :mod:`.timeline` — cross-rank merge of flights/trace JSONL/journals
  into one wall-clock-aligned timeline (spans carry monotonic AND wall
  stamps since round 10), with a Perfetto/Chrome-trace exporter and a
  per-step anatomy decomposition (input/compute/snapshot/hook/other +
  the compiled collective schedule).
- :mod:`.anomaly` — online detectors fed from the same hooks: warmup-
  pinned EWMA step-time regression, cross-rank skew/straggler
  detection, NaN / loss-plateau sentinels; surfaced as registry
  counters, a machine-readable ``health.json``, and flight-recorder
  triggers (a detected anomaly dumps a postmortem BEFORE escalation).
- :mod:`.ledger` — the CROSS-run record: an append-only, crash-tolerant
  ``RUNS.jsonl`` (``OBS_LEDGER=<path>``) of run_start / bounded-
  resolution metric samples / run_end rows plus fleet annotations,
  queryable live and diffable after the fact (``tools/obs_query.py``).
- :mod:`.serve` — the LIVE scrape surface: an opt-in
  (``OBS_HTTP_PORT``) background HTTP thread per process exposing
  ``/metrics`` (Prometheus text), ``/health`` (the §16 contract),
  ``/flight`` (on-demand recorder dump), and ``/ledger/tail``.

Deliberately **stdlib-only**: importing obs never pulls jax, so a
process whose SIGTERM handler must be live before its first heavyweight
import, and the supervisor's lightweight process, both instrument
themselves for free.
"""

from distributedtensorflowexample_tpu.obs.anomaly import (  # noqa: F401
    EwmaRegression, PlateauSentinel, RunHealth, detect_skew, read_health,
    write_health)
from distributedtensorflowexample_tpu.obs.ledger import (  # noqa: F401
    RunLedger, run_table)
from distributedtensorflowexample_tpu.obs.serve import (  # noqa: F401
    ObsServer)
from distributedtensorflowexample_tpu.obs.metrics import (  # noqa: F401
    MetricsRegistry, counter, gauge, histogram, registry)
from distributedtensorflowexample_tpu.obs.recorder import (  # noqa: F401
    FlightRecorder, dump_global, flight_path, install, maybe_install)
from distributedtensorflowexample_tpu.obs.trace import (  # noqa: F401
    add_sink, event, hot_span, remove_sink, span, tape, tape_dropped,
    watch_gc)
