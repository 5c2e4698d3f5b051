# graftlint: stdlib-only
"""The declared environment-knob surface of the package.

Every ``os.environ`` read (or write) of a named knob inside
``distributedtensorflowexample_tpu/`` must have an entry here with a
one-line doc — ``analysis/src_lint.py``'s ``env-registry`` rule proves
it, and the reverse rule (``env-dead``) flags entries no code reads any
more, so this file can neither under- nor over-state the real surface.
``tools/graftlint.py --fix`` inserts ``TODO: document`` stubs for new
knobs; replace the stub with a real one-liner before merging.

Operator-facing knobs are additionally documented in README.md;
supervisor-exported coordination variables (SUPERVISE_*/OBS_RANK/...)
are documented where they are exported.  Keys sorted alphabetically.
"""

from __future__ import annotations

ENV_REGISTRY: dict[str, str] = {
    "BUCKET_GRADS_AUTO_BYTES": (
        "Overrides --bucket_grads auto's measured-knee bucket size "
        "(bytes) without a code change after a chip re-fit "
        "(parallel/bucketing.py)."),
    "DISTTF_TPU_QUIET_SYNTHETIC": (
        "1 = suppress the loud synthetic-fallback warning when a real "
        "dataset is absent (data/synthetic.py; CI noise control)."),
    "DTFE_NATIVE_CACHE": (
        "Build/cache directory for the native C++ dataio extension "
        "(native/loader.py; default: a per-user temp dir)."),
    "FLEET_DRILL_DIE_IN_DISCARD": (
        "Drill seam: rank to SIGKILL mid-discard so the interrupted-"
        "agreement replay path stays tested (resilience/fleet.py)."),
    "FLEET_HOST_DOWN_FILE": (
        "Per-rank host-loss tombstone path (exported by the fleet "
        "supervisor): the host_loss fault writes it and the next spawn "
        "of that rank fails like a dead host (resilience/faults.py, "
        "resilience/fleet.py)."),
    "HEAL_ACTION_BUDGET": (
        "Global remediation-actions ceiling per remediator JOURNAL "
        "(WAL replay restores the spent count; a new journal resets "
        "it); exhaustion degrades to detection-only with a loud "
        "heal_budget_exhausted ledger row "
        "(resilience/remediate.py; default 8)."),
    "HEAL_CANARY_FRACTION": (
        "Share of serving requests routed to a canary candidate while "
        "it proves itself (serving/promote.py; default 0.25)."),
    "HEAL_CANARY_P99_RATIO": (
        "Canary p99 over this multiple of the baseline arm's p99 = "
        "regression, auto-rollback (serving/promote.py; default 2.0)."),
    "HEAL_CANARY_WINDOW": (
        "Canary-arm completions required before a promote/rollback "
        "verdict (serving/promote.py; default 16)."),
    "HEAL_COOLDOWN_S": (
        "Per-(kind, scope) quiet period after a remediation action — "
        "the action-storm guard (resilience/remediate.py; default 30)."),
    "HEAL_DRY_RUN": (
        "1 = remediation commissioning mode: journal heal_dry_run rows "
        "naming what WOULD fire, run no actuator "
        "(resilience/remediate.py)."),
    "HEAL_FLAP_N": (
        "Detections of one (kind, scope) inside the flap window before "
        "a remediation policy may act — a one-poll blip never reaches "
        "an actuator (resilience/remediate.py; default 2)."),
    "HEAL_FLAP_WINDOW_S": (
        "The flap-damping window in seconds "
        "(resilience/remediate.py; default 60)."),
    "HEAL_LR_DROP": (
        "1 = experimental: map loss_plateau to the LR-drop advisory "
        "stub instead of gang rollback — the actuator writes an "
        "advisory file a future trainer LR hook consumes "
        "(resilience/remediate.py)."),
    "JAX_COMPILATION_CACHE_DIR": (
        "Where jax keeps its persistent compile cache.  Set: jax reads "
        "it itself and the code sets no directory; unset: the cache is "
        "<checkout>/.jax_cache (runtime.enable_compilation_cache)."),
    "OBS_ANOMALY_SKIP": (
        "Steps ignored at window start before the anomaly baseline "
        "arms (obs/anomaly.py; default 1 — the compile step)."),
    "OBS_ANOMALY_WARMUP": (
        "Steps used to pin the anomaly detector's step-time baseline "
        "(obs/anomaly.py; default 16)."),
    "OBS_ANOMALY_Z": (
        "EWMA z-score threshold before a step time is flagged anomalous "
        "(obs/anomaly.py; default 8.0)."),
    "OBS_COLLECTIVES": (
        "1 = pay one extra AOT compile to record the collective "
        "inventory of the live step (trainers/common.py)."),
    "OBS_DIR": (
        "Directory flight-recorder postmortems land in "
        "(obs/recorder.py; default: the system temp dir)."),
    "OBS_FLIGHT": (
        "1/true = arm the always-on flight recorder: span ring + "
        "counters + loss tail dumped on exit/signal (obs/recorder.py)."),
    "OBS_HEALTH": (
        "Path of the health heartbeat file the serve thread falls back "
        "to when HTTP is down (obs/serve.py; exported per rank by "
        "supervise_fleet)."),
    "OBS_HTTP_PORT": (
        "Port for the in-process /metrics + /health + /ledger scrape "
        "endpoint; unset/empty = no server (obs/serve.py)."),
    "OBS_LEDGER": (
        "Path of the append-only cross-run RUNS.jsonl ledger; "
        "unset/empty = no ledger (obs/ledger.py)."),
    "OBS_LEDGER_MAX_BYTES": (
        "Ledger size-rotation threshold in bytes (obs/ledger.py; "
        "default 8 MiB)."),
    "OBS_LEDGER_SAMPLE_S": (
        "Minimum seconds between sampled ledger metric rows "
        "(obs/ledger.py; default 30)."),
    "OBS_PHASE": (
        "Capture-phase label stamped on obs events/rows (exported by "
        "the supervisor's capture queue; obs/trace.py, obs/ledger.py)."),
    "OBS_PROM_DIR": (
        "Directory for node-exporter textfile-collector .prom dumps "
        "refreshed per completed supervised task "
        "(resilience/supervisor.py)."),
    "OBS_RANK": (
        "Process rank label for multi-process telemetry files/rows "
        "(exported by fleet/multi-host init; obs/*, trainers/common.py)."),
    "OBS_TRACE_FILE": (
        "Path to append per-process span events (JSONL) for the "
        "cross-rank timeline merge; unset = no trace (obs/trace.py)."),
    "SCHED_DRILL_DIE_AT": (
        "Drill seam: SIGKILL the scheduler right after it journals a "
        "matching record (substring of 'event:action:job'), so the "
        "write-ahead replay path stays tested "
        "(resilience/scheduler.py)."),
    "SCHED_QUEUE": (
        "Default queue file for tools/schedule.py when --queue is not "
        "passed (resilience/scheduler.py)."),
    "SCHED_SLO_PRIORITIES": (
        "Per-kind SLO priority overrides for the scheduler, "
        "'kind=int,...' (lower = more urgent; default serve=0 train=10 "
        "bench=20 drill=30; resilience/scheduler.py)."),
    "SCHED_TICK_S": (
        "Scheduler policy-loop cadence in seconds — the latency floor "
        "on every reap/evict/grow/admit decision "
        "(resilience/scheduler.py; default 0.25)."),
    "SERVE_LOAD_CLIENTS": (
        "Default closed-loop client thread count for serve_lm --drive "
        "(serving/loadgen.py; default 2)."),
    "SERVE_LOAD_REQUESTS": (
        "Default request count one drive issues "
        "(serving/loadgen.py; default 16)."),
    "SERVE_PORT": (
        "Request-front port for the serving worker's POST /generate + "
        "GET /stats HTTP API; 0/unset = in-process only "
        "(serving/frontend.py — distinct from OBS_HTTP_PORT, the "
        "read-only telemetry scrape)."),
    "SERVE_SLO_MS": (
        "End-to-end latency SLO in ms driving serving admission: a "
        "queued request predicted to finish past it is rejected loudly "
        "instead of admitted to miss; 0 = admit everything "
        "(serving/queue.py)."),
    "SERVE_SLOTS": (
        "Default concurrent decode slots for the serving worker "
        "(serving/engine.py; default 4)."),
    "SERVE_SNAPSHOT": (
        "Default SnapshotStore directory tools/serve_lm.py promotes "
        "when --snapshot is not passed (serving/promote.py)."),
    "SIM_MAX_VIRTUAL_S": (
        "Hard ceiling on total virtual seconds one sim run may "
        "advance — a livelocked scenario (eviction ping-pong, a gate "
        "that never opens) dies loudly at the cap instead of pumping "
        "the event queue forever (sim/harness.py; default 10x the "
        "scenario horizon)."),
    "SIM_TEARDOWN_S": (
        "Default request_stop -> unanimous-143 teardown latency for "
        "simulated gangs when the scenario's per-job sim knobs don't "
        "script one — stretch it to drill slow-drain eviction windows "
        "(sim/fleet.py; default 1.0)."),
    "SNAPSHOT_DIR": (
        "Shard-redundant snapshot directory the engine wires a "
        "ShardSnapshotHook + elastic restore into when the update "
        "layout is a row layout (engine/engine.py; unset = Orbax "
        "checkpoints only)."),
    "SNAPSHOT_IO_BACKOFF_S": (
        "First retry backoff for a failed shard-payload write, "
        "doubling per retry (resilience/shardstore.py; default 0.05)."),
    "SNAPSHOT_IO_RETRIES": (
        "Bounded retries per shard-payload write before the save "
        "raises (resilience/shardstore.py; default 2)."),
    "SNAPSHOT_REDUNDANCY": (
        "Copies of every shard in a shard-redundant snapshot set: 1 "
        "own + R-1 ring mirrors, so any R-1 shard losses reconstruct "
        "and R refuse loudly (resilience/shardstore.py, mirrored by "
        "the sim's snapshot_loss world model in sim/fleet.py; "
        "default 2)."),
    "SUPERVISE_ATTEMPT": (
        "Attempt number of the supervised child, exported by the "
        "supervisor so obs rows carry retry provenance (obs/*)."),
    "SUPERVISE_HEARTBEAT": (
        "Heartbeat file path the supervised child touches per step; "
        "the watchdog kills on staleness (trainers/common.py, "
        "resilience/faults.py, obs/recorder.py)."),
    "SUPERVISE_HEARTBEAT_TIMEOUT_S": (
        "The watchdog's staleness edge in seconds, exported to "
        "children so the heartbeat_flap drill can aim at it "
        "(resilience/faults.py)."),
    "TF_CONFIG": (
        "Reference-compatible cluster topology JSON; parsed for "
        "process count/index compatibility, topology itself is "
        "jax.distributed's job (cluster.py)."),
}
