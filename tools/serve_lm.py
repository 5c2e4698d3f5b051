#!/usr/bin/env python
"""serve_lm — the graft-LM serving worker: snapshot → continuous-
batching KV-cache decode under the standard supervision machinery.

  # serve a snapshot over HTTP until TERM (SERVE_PORT or --http):
  python tools/serve_lm.py --snapshot /tmp/lm_snaps --size lm_small --http 8811

  # self-contained demo: init a snapshot if absent, drive 32 requests
  # through the in-process closed loop, write stats, exit 0:
  python tools/serve_lm.py --snapshot /tmp/lm_snaps --init_if_missing \\
      --drive 32 --stats /tmp/serve_stats.json

The worker speaks every operational protocol the training entrypoints
speak, so the fleet/scheduler machinery supervises it unchanged:

- **TERM → drain → 143**: SIGTERM stops admission, decodes every
  in-flight request to completion, rejects the queued tail loudly
  (outcome ``drained``), writes stats, exits 143 — the trainer's
  loss-free preemption protocol with "state saved" re-read as "every
  admitted request answered".  An evicted serving worker relaunches and
  (in --drive mode) re-issues exactly the unfinished request ids from
  its results tape.
- **heartbeat**: touches ``SUPERVISE_HEARTBEAT`` every loop boundary
  (busy or idle), so the supervisor watchdog can tell a wedged decode
  dispatch from a quiet queue.
- **obs**: flight recorder (``OBS_FLIGHT``), run ledger rows
  (``OBS_LEDGER``: run_start with the resolved config + promoted
  snapshot step, bounded samples, run_end with rc), live scrape
  (``OBS_HTTP_PORT`` — /metrics carries the serve_* series: p50/p99
  gauges, queue depth, slot occupancy, tokens/steps counters).

Default backend is a pinned CPU (the drill/test posture: supervised
drills start many workers on one host, and a chip belongs to one process
at a time); ``--real`` serves on the backend jax selects, and the stats
JSON names the ``platform`` that answered.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

RC_PREEMPTED = 143


def main(argv: list[str] | None = None) -> int:
    from distributedtensorflowexample_tpu.config import (
        CONFIG_MODEL_TYPES)   # stdlib only: jax is imported below
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--snapshot", default="",
                   help="SnapshotStore directory to promote (default "
                        "$SERVE_SNAPSHOT)")
    p.add_argument("--size", default="lm_tiny",
                   help="graft-LM size the snapshot holds (LM_SIZES)")
    p.add_argument("--model_config", default="",
                   help="serve a model built from a published "
                        "configuration's JSON file (its model_type names "
                        "the architecture: " + ", ".join(CONFIG_MODEL_TYPES)
                        + ") instead of a --size of the ladder; snapshots "
                        "are stamped with the file's name")
    p.add_argument("--slots", type=int, default=0,
                   help="concurrent decode slots (default $SERVE_SLOTS "
                        "or 4)")
    p.add_argument("--slo_ms", type=float, default=-1.0,
                   help="end-to-end latency SLO driving admission "
                        "(default $SERVE_SLO_MS; 0 = admit everything)")
    p.add_argument("--max_len", type=int, default=64,
                   help="KV-cache rows per slot (prompt + generated)")
    p.add_argument("--http", type=int, default=-1,
                   help="request-front port (default $SERVE_PORT; 0 = "
                        "in-process only)")
    p.add_argument("--init_if_missing", action="store_true",
                   help="write a demo-grade (untrained, seeded) snapshot "
                        "when the store holds no valid one")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--real", action="store_true",
                   help="serve on the configured backend (default pins "
                        "the CPU platform in-process)")
    p.add_argument("--sharded_mesh", type=int, default=0,
                   help="params-stay-sharded decode over a D-device "
                        "mesh (serving/sharded.py): params stay zero3 "
                        "bucket rows at 1/D, gathered per block inside "
                        "the compiled step (0 = replicated engine; on "
                        "CPU without --real this forces D host devices)")
    p.add_argument("--spec_draft", default="",
                   help="speculative decoding: LM_SIZES size that "
                        "DRAFTS (e.g. lm_tiny); the served model "
                        "verifies — output stays bitwise greedy")
    p.add_argument("--spec_draft_snapshot", default="",
                   help="snapshot dir for the draft model (default: "
                        "the served --snapshot dir)")
    p.add_argument("--spec_k", type=int, default=4,
                   help="draft window: tokens drafted per verify round")
    p.add_argument("--sample_temp", type=float, default=0.0,
                   help="sampling temperature (0 = greedy decode; "
                        "sampled tokens draw on per-request RNG lanes, "
                        "deterministic per request id)")
    p.add_argument("--sample_top_k", type=int, default=0,
                   help="restrict sampling to the k most likely tokens "
                        "(0 = full softmax; arms the sampler even at "
                        "default temperature)")
    p.add_argument("--sample_seed", type=int, default=0,
                   help="worker-level seed the per-request RNG lanes "
                        "derive from")
    p.add_argument("--prefix_cache", type=int, default=0,
                   help="share K/V rows across requests with equal "
                        "prompt prefixes (value = resident prompt "
                        "capacity; 0 = off)")
    # The in-process closed-loop drive (demo / drills / bench).
    p.add_argument("--drive", type=int, default=0,
                   help="drive N deterministic requests through the "
                        "in-process closed loop, then exit 0 (0 = serve "
                        "until TERM)")
    p.add_argument("--clients", type=int, default=0,
                   help="closed-loop client threads for --drive "
                        "(default $SERVE_LOAD_CLIENTS or 2)")
    p.add_argument("--drive_max_new", type=int, default=8,
                   help="generated tokens per driven request")
    p.add_argument("--drive_think_ms", type=float, default=0.0,
                   help="closed-loop client think time between "
                        "completions (holds offered load below "
                        "saturation)")
    p.add_argument("--results", default="",
                   help="--drive completion tape (JSONL; re-issues only "
                        "unfinished ids on relaunch)")
    p.add_argument("--stats", default="",
                   help="write the final stats JSON here")
    p.add_argument("--ready_file", default="",
                   help="touch this path once the worker is serving")
    args = p.parse_args(argv)

    if args.sharded_mesh > 1 and not args.real:
        # The pinned-CPU posture needs a mesh to shard over; forcing
        # host devices must happen before the first jax import.
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count="
                f"{args.sharded_mesh}").strip()

    import jax

    from distributedtensorflowexample_tpu.runtime import (
        device_line, device_summary, enable_compilation_cache)
    if not args.real:
        try:
            jax.config.update("jax_platforms", "cpu")
        except RuntimeError:
            pass    # backend already initialized — use it as configured
    # Serving restarts are the POINT (eviction → relaunch), so the
    # compile cache matters operationally, not just in tests: a
    # relaunched worker re-serves without repaying the decode/prefill
    # compiles.
    enable_compilation_cache()

    from distributedtensorflowexample_tpu.obs import ledger as obs_ledger
    from distributedtensorflowexample_tpu.obs import (
        recorder as obs_recorder)
    from distributedtensorflowexample_tpu.obs import serve as obs_serve
    from distributedtensorflowexample_tpu.serving.engine import (
        DecodeEngine, serve_slots_default)
    from distributedtensorflowexample_tpu.serving.frontend import (
        RequestFront, serve_port_default)
    from distributedtensorflowexample_tpu.serving.loadgen import (
        ClosedLoopLoadGen, DriveFile, load_clients_default)
    from distributedtensorflowexample_tpu.serving.promote import (
        init_lm_snapshot, promote, serve_snapshot_default)
    from distributedtensorflowexample_tpu.serving.queue import (
        ContinuousBatcher, RequestQueue, serve_slo_ms_default)
    from distributedtensorflowexample_tpu.training.hooks import (
        touch_heartbeat)
    from distributedtensorflowexample_tpu.utils.signals import (
        sigterm_flag)

    snapshot = args.snapshot or serve_snapshot_default()
    if not snapshot:
        p.error("--snapshot (or SERVE_SNAPSHOT) is required")
    model = None
    if args.model_config:
        if args.sharded_mesh > 0 or args.spec_draft:
            p.error("--model_config serves through DecodeEngine alone "
                    "(no --sharded_mesh, no --spec_draft)")
        from distributedtensorflowexample_tpu.models import (
            build_model_from_config)
        model = build_model_from_config(args.model_config)
        args.size = "config:" + os.path.basename(args.model_config)
    slots = args.slots or serve_slots_default()
    slo_ms = serve_slo_ms_default() if args.slo_ms < 0 else args.slo_ms
    port = serve_port_default() if args.http < 0 else args.http

    rec = obs_recorder.maybe_install()
    if rec is not None:
        rec.note(tool="serve_lm", snapshot=snapshot, size=args.size,
                 slots=slots, slo_ms=slo_ms)
    obs_ledger.maybe_begin(
        "serve_lm", config={"snapshot": snapshot, "size": args.size,
                            "slots": slots, "slo_ms": slo_ms,
                            "max_len": args.max_len, "drive": args.drive,
                            "seed": args.seed,
                            "sharded_mesh": args.sharded_mesh,
                            "spec_draft": args.spec_draft,
                            "spec_k": args.spec_k,
                            "sample_temp": args.sample_temp,
                            "sample_top_k": args.sample_top_k,
                            "prefix_cache": args.prefix_cache})
    obs_serve.maybe_start()
    ledger = obs_ledger.get()

    if args.init_if_missing:
        from distributedtensorflowexample_tpu.resilience.snapshot import (
            SnapshotStore)
        if SnapshotStore(snapshot).latest_valid() is None:
            init_lm_snapshot(snapshot, args.size, seed=args.seed,
                             model=model)
            print(f"serve_lm: initialized demo snapshot in {snapshot}",
                  file=sys.stderr, flush=True)

    t0 = time.monotonic()
    from distributedtensorflowexample_tpu.refusal import ModeRefusal
    try:
        if args.sharded_mesh > 0:
            from distributedtensorflowexample_tpu.serving.promote import (
                promote_sharded)
            from distributedtensorflowexample_tpu.serving.sharded import (
                ShardedDecodeEngine)
            pm = promote_sharded(snapshot, args.size,
                                 mesh_size=args.sharded_mesh)
            engine = ShardedDecodeEngine(pm.model, pm.rows, pm.layout,
                                         slots=slots,
                                         cache_len=args.max_len)
            snap_layout = pm.source_layout
            mode_desc = f", sharded D={pm.layout.num_devices} (params " \
                        f"resident at 1/{pm.layout.num_devices})"
        else:
            pm = promote(snapshot, args.size, model=model)
            engine = DecodeEngine(pm.model, pm.params, slots=slots,
                                  cache_len=args.max_len)
            snap_layout = pm.layout
            mode_desc = ""
        spec = sampler = prefix = None
        if args.spec_draft:
            from distributedtensorflowexample_tpu.serving.spec import (
                SpecDecoder)
            dsnap = args.spec_draft_snapshot or snapshot
            if args.init_if_missing and dsnap != snapshot:
                from distributedtensorflowexample_tpu.resilience. \
                    snapshot import SnapshotStore
                if SnapshotStore(dsnap).latest_valid() is None:
                    init_lm_snapshot(dsnap, args.spec_draft,
                                     seed=args.seed)
            dpm = promote(dsnap, args.spec_draft)
            draft_engine = DecodeEngine(dpm.model, dpm.params,
                                        slots=slots,
                                        cache_len=args.max_len)
            spec = SpecDecoder(engine, draft_engine, k=args.spec_k)
            mode_desc += (f", spec k={args.spec_k} (draft "
                          f"{args.spec_draft} step {dpm.step})")
        if args.sample_temp > 0 or args.sample_top_k > 0:
            from distributedtensorflowexample_tpu.serving.sampling \
                import Sampler
            sampler = Sampler(
                temperature=(args.sample_temp if args.sample_temp > 0
                             else 1.0),
                top_k=args.sample_top_k, seed=args.sample_seed)
            mode_desc += f", sampler {sampler.describe()}"
        if args.prefix_cache > 0:
            from distributedtensorflowexample_tpu.serving.prefix import (
                PrefixCache)
            prefix = PrefixCache(engine, capacity=args.prefix_cache)
            mode_desc += f", prefix cache {args.prefix_cache}"
        queue = RequestQueue(engine.vocab)
        hb_path = os.environ.get("SUPERVISE_HEARTBEAT", "")

        def on_step(batcher) -> None:
            # Heartbeat lives in should_stop below (every loop
            # boundary, busy AND idle) — not here too: at ~0.2 ms/step
            # a second touch per decode step would be thousands of
            # redundant open+utime syscalls a second on the hot loop.
            if ledger is not None:
                ledger.sample(step=engine.decode_steps)

        batcher = ContinuousBatcher(engine, queue, slo_ms=slo_ms,
                                    on_step=on_step, spec=spec,
                                    sampler=sampler,
                                    prefix_cache=prefix)
    except ModeRefusal as e:
        # Impossible flag combinations are refused BY NAME before any
        # request could be admitted into them — exit 2, argparse's own
        # bad-usage code, so the supervisor never retries a config
        # that can only refuse again.
        print(f"serve_lm: refused: {e}", file=sys.stderr, flush=True)
        obs_ledger.end_global(rc=2, errors={"refused": str(e)})
        return 2
    front = RequestFront(queue, batcher, port).start() if port else None
    devices = device_summary()
    print(f"serve_lm: {device_line(devices)}", file=sys.stderr, flush=True)
    print(f"serve_lm: serving {args.size} snapshot step {pm.step} "
          f"({snap_layout}) — {slots} slot(s), cache {args.max_len} "
          f"rows/slot ({engine.cache_bytes >> 10} KiB), SLO "
          f"{slo_ms or 'off'} ms, load time "
          f"{time.monotonic() - t0:.2f}s" + mode_desc
          + (f", HTTP :{front.port}" if front else ""),
          file=sys.stderr, flush=True)
    if args.ready_file:
        touch_heartbeat(args.ready_file)

    drive_done = threading.Event()
    gen = None
    gen_summary: dict = {}
    if args.drive > 0:
        gen = ClosedLoopLoadGen(
            queue, total=args.drive,
            clients=args.clients or load_clients_default(),
            max_new=args.drive_max_new, vocab=engine.vocab,
            seed=args.seed, think_ms=args.drive_think_ms,
            drive_file=DriveFile(args.results) if args.results
            else None)

        def _drive():
            gen_summary.update(gen.run())
            drive_done.set()

        threading.Thread(target=_drive, daemon=True,
                         name="serve-drive").start()

    with sigterm_flag() as term:
        last_beat = [0.0]

        def should_stop() -> bool:
            if hb_path:
                # Beat on idle boundaries too (a quiet queue is
                # healthy; a silent worker is indistinguishable from a
                # wedged dispatch) — but rate-limited: at ~0.2 ms/step
                # an every-boundary touch is thousands of open+utime
                # syscalls a second on the hot loop, and the watchdog
                # only needs seconds-scale freshness.
                now = time.monotonic()
                if now - last_beat[0] >= 0.5:
                    last_beat[0] = now
                    touch_heartbeat(hb_path)
            return bool(term) or drive_done.is_set()

        batcher.run(should_stop=should_stop)
        preempted = bool(term)
    if gen is not None:
        gen.stop.set()
        drive_done.wait(timeout=30)

    if front is not None:
        front.stop()
    stats = batcher.stats()
    stats.update(snapshot_step=pm.step, snapshot_layout=snap_layout,
                 size=args.size, preempted=preempted,
                 drive=gen_summary or None,
                 platform=devices["platform"],
                 device_kind=devices["device_kind"])
    if hasattr(engine, "params_residency"):
        stats["params_residency"] = engine.params_residency()
    if args.stats:
        tmp = args.stats + ".tmp"
        with open(tmp, "w") as f:
            json.dump(stats, f, indent=1, sort_keys=True)
            f.write("\n")
        os.replace(tmp, args.stats)
    print(json.dumps(stats, sort_keys=True), flush=True)
    rc = RC_PREEMPTED if preempted else 0
    obs_ledger.end_global(rc=rc, final_step=engine.decode_steps)
    if preempted:
        print(f"serve_lm: TERM — drained {stats['completed']} "
              f"completed request(s), rejected tail "
              f"{stats['rejected']['drained']}; exit {rc}",
              file=sys.stderr, flush=True)
    return rc


if __name__ == "__main__":
    raise SystemExit(main())
