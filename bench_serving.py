#!/usr/bin/env python
"""bench_serving — the serving-path bench family: closed-loop
throughput, p50/p99 latency, and the throughput-vs-SLO curve.

Six instruments over one engine family (serving/):

1. **Supervised headline** (default on): a REAL ``tools/serve_lm.py``
   worker runs as a child of the resilience Supervisor — heartbeat
   watchdog armed, snapshot promoted through the SnapshotStore validity
   path, the in-process closed loop driving it — and its stats JSON
   supplies the headline tokens/sec + p50/p99.  This is the
   end-to-end number: process boundary, supervision, promotion, and
   continuous batching all on the measured path.
2. **Saturation sweep** (in-process, one jax import): closed-loop
   clients 1..K against the same engine — tokens/sec climbs until the
   decode slots saturate, then latency climbs instead.  The knee is
   the capacity number a capacity planner wants.
3. **SLO sweep**: at saturating load, sweep ``--slo_sweep_ms`` through
   the admission knob: in-SLO goodput (tokens/sec of ACCEPTED work),
   p50/p99 of the accepted work, and the rejection rate at each
   operating point — the throughput-vs-SLO curve the round-15 record
   checks in.
4. **Params-stay-sharded point** (round 17): ``promote_sharded`` +
   ``ShardedDecodeEngine`` at a D-device mesh — closed-loop tokens/sec
   with params resident at 1/D, plus the residency measured from LIVE
   shardings (``params_residency``), including the lm_base/D=4
   instrument the round-12 training-side claim used.
5. **Speculative draft-k sweep** (round 17): self-draft (same
   snapshot drafts → full acceptance, the machinery's upper bound)
   against the SAME workload decoded plain-greedy — tokens/sec,
   acceptance length, and a ``*_mismatch`` column tools/bench_ratchet.py
   holds at ZERO (spec output is bitwise greedy by construction).
6. **Batched-prefill amortization** (round 17): one ``prefill_many``
   call over a same-bucket burst vs the same prompts prefilled solo —
   the per-request speedup continuous batching's admission path banks.

CPU numbers calibrate the machinery and arm chip predictions (the
armed_predictions_round15_serving block in BASELINE_SELF.json);
``--real`` re-runs the same instruments on the configured backend at a
window.  Output: JSON lines (bench.py dialect, ``spread_frac`` stamped
from repeats) + ``--json`` writes the SERVE_lm_* artifact
tools/bench_ratchet.py ratchets and folds into BENCH_trajectory.json.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time
import traceback

_REPO = os.path.dirname(os.path.abspath(__file__))


def _emit(metric: str, value: float, unit: str, detail: dict,
          lines: list) -> None:
    rec = {"metric": metric, "value": round(float(value), 6),
           "unit": unit, "vs_baseline": 1.0, "detail": detail}
    print(json.dumps(rec), flush=True)
    lines.append(rec)


def _run_point(engine, *, requests: int, clients: int, max_new: int,
               slo_ms: float, seed: int) -> dict:
    """One closed-loop operating point against a fresh queue/batcher
    (the engine and its compiled programs are shared across points)."""
    from distributedtensorflowexample_tpu.serving.loadgen import (
        ClosedLoopLoadGen)
    from distributedtensorflowexample_tpu.serving.queue import (
        ContinuousBatcher, RequestQueue)

    queue = RequestQueue(engine.vocab)
    batcher = ContinuousBatcher(engine, queue, slo_ms=slo_ms)
    gen = ClosedLoopLoadGen(queue, total=requests, clients=clients,
                            max_new=max_new, vocab=engine.vocab,
                            seed=seed)
    done = threading.Event()
    box: dict = {}

    def _drive():
        # Rejected ids re-queue forever under a tight SLO; bound the
        # point by letting each id fail at most a few times.
        box.update(gen.run())
        done.set()

    t = threading.Thread(target=_drive, daemon=True)
    steps0 = engine.decode_steps          # engine is shared across points
    t0 = time.monotonic()
    t.start()
    batcher.run(should_stop=done.is_set)
    t.join(timeout=10)
    wall = time.monotonic() - t0
    stats = batcher.stats()
    stats["decode_steps"] = engine.decode_steps - steps0
    goodput = (stats["tokens"] / wall) if wall > 0 else 0.0
    return {"clients": clients, "slo_ms": slo_ms,
            "requests": requests, "completed": stats["completed"],
            "rejected_slo": stats["rejected"]["slo"],
            "tokens": stats["tokens"], "wall_s": round(wall, 3),
            "goodput_tokens_per_sec": round(goodput, 3),
            "p50_ms": stats["p50_ms"], "p99_ms": stats["p99_ms"],
            "decode_steps": stats["decode_steps"],
            "step_ewma_ms": stats["step_ewma_ms"]}


def _oracle_run(engine, prompts, *, spec=None, repeats=3) -> tuple:
    """Decode ``prompts`` to completion through a fresh batcher
    (optionally speculative): submit-all-then-step keeps the workload
    IDENTICAL across configurations, so the returned token map diffs
    bitwise against another configuration's (the ``*_mismatch``
    column).  Returns ``(tokens_by_rid, [tokens/sec per repeat])`` —
    repeat 0 pays the cold compiles and is dropped by callers."""
    from distributedtensorflowexample_tpu.serving.queue import (
        ContinuousBatcher, RequestQueue)
    toks_by_rid: dict = {}
    rates: list = []
    for _ in range(max(1, repeats)):
        queue = RequestQueue(engine.vocab)
        b = ContinuousBatcher(engine, queue, slo_ms=0.0, spec=spec)
        reqs = [queue.submit(p, m, rid=f"o{i}")
                for i, (p, m) in enumerate(prompts)]
        t0 = time.monotonic()
        while any(not r.done.is_set() for r in reqs):
            b.step()
        wall = time.monotonic() - t0
        prev, toks_by_rid = toks_by_rid, {r.rid: list(r.tokens)
                                          for r in reqs}
        if prev and prev != toks_by_rid:
            raise AssertionError(
                "oracle workload not deterministic across repeats")
        total = sum(len(r.tokens) for r in reqs)
        rates.append(round(total / wall, 3) if wall > 0 else 0.0)
    return toks_by_rid, rates


def _supervised_headline(args, snapshot: str, workdir: str) -> dict:
    """The end-to-end point: serve_lm under the Supervisor, heartbeat
    armed, driven by its own closed loop; returns its stats JSON plus
    the supervision verdict."""
    from distributedtensorflowexample_tpu.resilience.supervisor import (
        Supervisor)
    stats_path = os.path.join(workdir, "serve_stats.json")
    hb_path = os.path.join(workdir, "serve.beat")
    argv = [sys.executable, os.path.join(_REPO, "tools", "serve_lm.py"),
            "--snapshot", snapshot, "--size", args.size,
            "--slots", str(args.slots), "--max_len", str(args.max_len),
            "--drive", str(args.requests),
            "--clients", str(args.clients_sweep[-1]),
            "--drive_max_new", str(args.max_new),
            "--seed", str(args.seed), "--stats", stats_path,
            # The worker initializes the demo snapshot itself: this
            # parent has not touched jax yet (one process per chip).
            "--init_if_missing"]
    if args.real:
        argv.append("--real")
    res = Supervisor(heartbeat_timeout_s=180.0).run(
        argv, name="bench_serving_headline",
        stdout_path=os.path.join(workdir, "serve.out"),
        stderr_path=os.path.join(workdir, "serve.err"),
        heartbeat_path=hb_path)
    out = {"supervision": {"status": res.status, "rc": res.returncode,
                           "attempts": res.attempts}}
    try:
        with open(stats_path) as f:
            out["stats"] = json.load(f)
    except (OSError, json.JSONDecodeError) as e:
        out["error"] = f"no stats from supervised worker: {e!r}"
    return out


def main(argv: list[str] | None = None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--size", default="lm_tiny",
                   help="graft-LM size to serve (lm_tiny = CPU-"
                        "measurable; bigger rungs at a window)")
    p.add_argument("--snapshot", default="",
                   help="snapshot dir (default: <workdir>/snaps, "
                        "demo-initialized if empty)")
    p.add_argument("--workdir", default="/tmp/bench_serving")
    p.add_argument("--slots", type=int, default=4)
    p.add_argument("--max_len", type=int, default=64)
    p.add_argument("--requests", type=int, default=0,
                   help="requests per operating point (default "
                        "$SERVE_LOAD_REQUESTS*8 or 128)")
    p.add_argument("--max_new", type=int, default=8)
    p.add_argument("--clients_sweep", default="1,2,4,8")
    p.add_argument("--slo_sweep_ms", default="0,25,50,100")
    p.add_argument("--repeats", type=int, default=3,
                   help="headline-point repeats (spread_frac source)")
    p.add_argument("--supervised_repeats", type=int, default=2,
                   help="supervised end-to-end repeats (its wall "
                        "includes worker cold-start, so its own "
                        "spread_frac matters)")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--host_devices", type=int, default=8,
                   help="CPU calibration: force this many host devices "
                        "so the sharded point has a mesh (0 = leave "
                        "XLA_FLAGS alone; ignored under --real)")
    p.add_argument("--sharded_mesh", type=int, default=0,
                   help="mesh size D for the params-stay-sharded point "
                        "(0 = auto: 4 if available, else 2, else skip)")
    p.add_argument("--spec_k_sweep", default="2,4",
                   help="draft window sizes for the speculative sweep "
                        "(empty = skip)")
    p.add_argument("--skip_supervised", action="store_true",
                   help="skip the supervised end-to-end headline "
                        "(in-process sweeps only)")
    p.add_argument("--real", action="store_true",
                   help="serve on the configured backend (default pins "
                        "CPU in-process)")
    p.add_argument("--json", default="",
                   help="write the SERVE_lm_* record here")
    args = p.parse_args(argv)
    args.clients_sweep = [int(x) for x in
                          args.clients_sweep.split(",") if x]
    args.slo_sweep_ms = [float(x) for x in
                         args.slo_sweep_ms.split(",") if x]
    args.spec_k_sweep = [int(x) for x in
                         args.spec_k_sweep.split(",") if x]

    if args.host_devices > 1 and not args.real:
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count="
                f"{args.host_devices}").strip()

    from distributedtensorflowexample_tpu.obs import ledger as obs_ledger
    from distributedtensorflowexample_tpu.obs import (
        recorder as obs_recorder)
    from distributedtensorflowexample_tpu.obs import serve as obs_serve
    from distributedtensorflowexample_tpu.obs.anomaly import (
        spread_fraction)
    from distributedtensorflowexample_tpu.serving.loadgen import (
        load_requests_default)

    obs_recorder.maybe_install()
    obs_ledger.maybe_begin("bench_serving", config=vars(args))
    obs_serve.maybe_start()
    os.makedirs(args.workdir, exist_ok=True)
    snapshot = args.snapshot or os.path.join(args.workdir, "snaps")
    # Resolve the default BEFORE the supervised section reads
    # args.requests — `--drive 0` tells the worker to serve forever,
    # which turns the headline into a heartbeat-fed hang.
    requests = args.requests = (
        args.requests or max(128, load_requests_default() * 8))
    size = args.size
    lines: list = []
    errors: dict = {}
    shared = {"size": size, "slots": args.slots,
              "max_len": args.max_len, "max_new": args.max_new,
              "requests": requests}

    # 1. supervised end-to-end headline -----------------------------------
    # FIRST, while this parent has not initialized a jax backend: a chip
    # belongs to one process at a time, and the serve_lm child needs it.
    # The parent touches jax only after the child has exited (below).
    if not args.skip_supervised:
        try:
            sup_runs = [
                _supervised_headline(args, snapshot, args.workdir)
                for _ in range(max(1, args.supervised_repeats))]
            rates = [(s.get("stats") or {}).get("tokens_per_sec") or 0.0
                     for s in sup_runs]
            best_i = max(range(len(rates)), key=lambda i: rates[i])
            sup, st = sup_runs[best_i], sup_runs[best_i].get("stats")
            if st and st.get("tokens_per_sec"):
                _emit(f"serve_{size}_supervised_tokens_per_sec",
                      st["tokens_per_sec"], "tokens/sec",
                      {**shared, "platform": st.get("platform"),
                       "supervised": True,
                       "clients": args.clients_sweep[-1],
                       "repeats": rates,
                       "spread_frac": round(spread_fraction(rates), 4),
                       "p50_ms": st.get("p50_ms"),
                       "p99_ms": st.get("p99_ms"),
                       "completed": st.get("completed"),
                       "snapshot_step": st.get("snapshot_step"),
                       "snapshot_layout": st.get("snapshot_layout"),
                       "supervision": sup.get("supervision"),
                       "note": "tools/serve_lm.py under the resilience "
                               "Supervisor (heartbeat armed), driven by "
                               "its in-process closed loop — process "
                               "boundary + promotion + continuous "
                               "batching all on the measured path; the "
                               "wall includes worker cold-start (jax "
                               "import + compiles), so this is the "
                               "relaunch-cost-inclusive number"},
                      lines)
            else:
                errors["supervised"] = sup.get("error") or "no rate"
        except Exception as e:
            errors["supervised"] = repr(e)
            traceback.print_exc()

    # 2 + 3. in-process sweeps (one engine, one compile set) --------------
    import jax
    if not args.real:
        try:
            jax.config.update("jax_platforms", "cpu")
        except RuntimeError:
            pass
    from distributedtensorflowexample_tpu.resilience.snapshot import (
        SnapshotStore)
    from distributedtensorflowexample_tpu.serving.engine import (
        DecodeEngine)
    from distributedtensorflowexample_tpu.serving.promote import (
        init_lm_snapshot, promote)
    if SnapshotStore(snapshot).latest_valid() is None:
        init_lm_snapshot(snapshot, size, seed=args.seed)
    platform = shared["platform"] = jax.default_backend()
    pm = engine = None
    try:
        pm = promote(snapshot, size)
        engine = DecodeEngine(pm.model, pm.params, slots=args.slots,
                              cache_len=args.max_len)
        # Warm: compiles (prefill buckets + decode) out of the tape.
        _run_point(engine, requests=max(8, 2 * args.slots),
                   clients=2, max_new=args.max_new, slo_ms=0.0,
                   seed=args.seed + 999)

        sat_clients = args.clients_sweep[-1]
        reps = []
        rep_points = []
        for r in range(max(1, args.repeats)):
            pt = _run_point(engine, requests=requests,
                            clients=sat_clients, max_new=args.max_new,
                            slo_ms=0.0, seed=args.seed)
            reps.append(pt["goodput_tokens_per_sec"])
            rep_points.append(pt)
        best = max(range(len(reps)), key=lambda i: reps[i])
        headline = rep_points[best]
        spread = round(spread_fraction(reps), 4)
        _emit(f"serve_{size}_tokens_per_sec", reps[best], "tokens/sec",
              {**shared, "clients": sat_clients, "repeats": reps,
               "spread_frac": spread, "p50_ms": headline["p50_ms"],
               "p99_ms": headline["p99_ms"],
               "decode_steps": headline["decode_steps"],
               "step_ewma_ms": headline["step_ewma_ms"],
               "snapshot_step": pm.step,
               "snapshot_layout": pm.layout}, lines)
        _emit(f"serve_{size}_p99_ms", headline["p99_ms"], "ms",
              {**shared, "clients": sat_clients, "spread_frac": spread,
               "p50_ms": headline["p50_ms"],
               "repeats_p99_ms": [p["p99_ms"] for p in rep_points]},
              lines)

        curve_clients = [
            _run_point(engine, requests=requests, clients=c,
                       max_new=args.max_new, slo_ms=0.0,
                       seed=args.seed + 1 + c)
            for c in args.clients_sweep]
        curve_slo = [
            _run_point(engine, requests=requests, clients=sat_clients,
                       max_new=args.max_new, slo_ms=s,
                       seed=args.seed + 101 + int(s))
            for s in args.slo_sweep_ms]
        # The curve row's VALUE is a measured scalar — the best in-SLO
        # goodput across the constrained sweep points — never the
        # sweep's point count (a config choice the ratchet would then
        # gate on: changing --slo_sweep_ms must not read as a perf
        # regression).  Its spread_frac comes from REPEATS OF THAT
        # POINT, not from the unconstrained headline's repeats — a
        # record must not report another metric's noise as its own.
        constrained = [p for p in curve_slo if p["slo_ms"] > 0] \
            or curve_slo
        best_pt = max(constrained,
                      key=lambda p: p["goodput_tokens_per_sec"])
        slo_reps = [best_pt["goodput_tokens_per_sec"]] + [
            _run_point(engine, requests=requests, clients=sat_clients,
                       max_new=args.max_new, slo_ms=best_pt["slo_ms"],
                       seed=args.seed + 201 + r
                       )["goodput_tokens_per_sec"]
            for r in range(max(0, args.repeats - 1))]
        _emit(f"serve_{size}_throughput_vs_slo",
              max(slo_reps), "tokens/sec (best in-SLO goodput)",
              {**shared,
               "spread_frac": round(spread_fraction(slo_reps), 4),
               "repeats": slo_reps,
               "best_point_slo_ms": best_pt["slo_ms"],
               "saturation_sweep": curve_clients,
               "slo_sweep": curve_slo,
               "note": "closed-loop curves: saturation_sweep varies "
                       "clients at SLO off; slo_sweep varies the "
                       "admission SLO at saturating load — in-SLO "
                       "goodput vs rejection rate is the serving "
                       "capacity trade"}, lines)
    except Exception as e:
        errors["sweep"] = repr(e)
        traceback.print_exc()

    # 4. params-stay-sharded point ----------------------------------------
    try:
        import numpy as np
        from distributedtensorflowexample_tpu.serving.promote import (
            promote_sharded)
        from distributedtensorflowexample_tpu.serving.sharded import (
            ShardedDecodeEngine)
        ndev = len(jax.devices())
        D = args.sharded_mesh or (4 if ndev >= 4 else 2)
        if ndev < 2 or D > ndev or args.slots % D:
            errors["sharded"] = (f"needs a divisible mesh: devices="
                                 f"{ndev}, D={D}, slots={args.slots}")
        else:
            spm = promote_sharded(snapshot, size, mesh_size=D)
            seng = ShardedDecodeEngine(spm.model, spm.rows, spm.layout,
                                       slots=args.slots,
                                       cache_len=args.max_len)
            res = seng.params_residency()
            _run_point(seng, requests=max(8, 2 * args.slots), clients=2,
                       max_new=args.max_new, slo_ms=0.0,
                       seed=args.seed + 555)       # compiles out of the tape
            sh_reps, sh_pts = [], []
            for r in range(max(1, args.repeats)):
                pt = _run_point(seng, requests=requests,
                                clients=args.clients_sweep[-1],
                                max_new=args.max_new, slo_ms=0.0,
                                seed=args.seed + 31 + r)
                sh_reps.append(pt["goodput_tokens_per_sec"])
                sh_pts.append(pt)
            sb = max(range(len(sh_reps)), key=lambda i: sh_reps[i])
            _emit(f"serve_{size}_sharded_tokens_per_sec", sh_reps[sb],
                  "tokens/sec",
                  {**shared, "mesh_size": D, "repeats": sh_reps,
                   "spread_frac": round(spread_fraction(sh_reps), 4),
                   "p50_ms": sh_pts[sb]["p50_ms"],
                   "p99_ms": sh_pts[sb]["p99_ms"],
                   "residency": res,
                   "snapshot_layout": spm.source_layout,
                   "note": "params resident at 1/D (zero3 bucket rows), "
                           "one all-gather per bucket INSIDE the "
                           "compiled decode step (pinned by "
                           "SHARDED_DECODE_HLO_CONTRACT); the CPU mesh "
                           "is forced host devices, so this calibrates "
                           "the gather machinery, never chip "
                           "throughput"}, lines)
            _emit(f"serve_{size}_sharded_params_frac_per_device",
                  res["frac_per_device"], "fraction",
                  {**shared, "mesh_size": D, **res,
                   "expected": 1.0 / D}, lines)
        if ndev >= 4:
            # lm_base/D=4: the round-12 training-side residency claim
            # re-measured on the SERVING engine's live shardings — the
            # constructor device_puts the rows at 1/D, so reading the
            # placement needs no decode compile of the 57M-param rung.
            import jax.numpy as jnp
            from distributedtensorflowexample_tpu.models.transformer_lm \
                import build_lm
            from distributedtensorflowexample_tpu.parallel.mesh import (
                make_mesh)
            from distributedtensorflowexample_tpu.parallel.zero3 import (
                Zero3Layout)
            bmodel = build_lm("lm_base", max_len=args.max_len)
            bparams = bmodel.init(jax.random.PRNGKey(args.seed),
                                  jnp.zeros((1, 8), jnp.int32))["params"]
            bl = Zero3Layout(bparams, 8 << 20, make_mesh(4))
            beng = ShardedDecodeEngine(bmodel, bl.init_rows(bparams), bl,
                                       slots=4, cache_len=args.max_len)
            bres = beng.params_residency()
            _emit("serve_lm_base_sharded_params_frac_per_device",
                  bres["frac_per_device"], "fraction",
                  {"platform": platform, "size": "lm_base",
                   "mesh_size": 4, **bres, "expected": 0.25,
                   "note": "live-sharding residency of the 57M-param "
                           "rung at D=4 (the acceptance instrument): "
                           "bytes of the addressable shard vs bytes of "
                           "the logical row, summed over buckets"},
                  lines)
            del beng, bl, bparams
    except Exception as e:
        errors["sharded"] = repr(e)
        traceback.print_exc()

    # 5. speculative draft-k sweep ----------------------------------------
    try:
        if engine is not None and args.spec_k_sweep:
            import numpy as np
            from distributedtensorflowexample_tpu.serving.engine import (
                DecodeEngine)
            from distributedtensorflowexample_tpu.serving.spec import (
                SpecDecoder)
            rng = np.random.default_rng(args.seed + 7)
            n_req = max(16, 4 * args.slots)
            prompts = [(rng.integers(1, engine.vocab, size=int(
                rng.integers(4, 13))).astype(np.int32), args.max_new)
                for _ in range(n_req)]
            greedy_toks, greedy_rates = _oracle_run(engine, prompts)
            greedy_tps = max(greedy_rates[1:] or greedy_rates)
            draft = DecodeEngine(pm.model, pm.params, slots=args.slots,
                                 cache_len=args.max_len)
            sweep: list = []
            mismatch_total = 0
            for k in args.spec_k_sweep:
                spec = SpecDecoder(engine, draft, k=k)
                spec_toks, spec_rates = _oracle_run(engine, prompts,
                                                    spec=spec)
                tps = max(spec_rates[1:] or spec_rates)
                mism = sum(1 for rid in greedy_toks
                           if spec_toks.get(rid) != greedy_toks[rid])
                mismatch_total += mism
                st = spec.stats()
                sweep.append({
                    "k": k, "tokens_per_sec": tps,
                    "repeats": spec_rates,
                    "spread_frac": round(
                        spread_fraction(spec_rates[1:] or spec_rates), 4),
                    "accept_len_mean": st["accept_len_mean"],
                    "rounds": st["rounds"], "mismatch": mism,
                    "uplift_vs_greedy": (round(tps / greedy_tps, 4)
                                         if greedy_tps else None)})
            best = max(sweep, key=lambda s: s["tokens_per_sec"])
            _emit(f"serve_{size}_spec_tokens_per_sec",
                  best["tokens_per_sec"], "tokens/sec",
                  {**shared, "k": best["k"], "requests": n_req,
                   "spread_frac": best["spread_frac"],
                   "greedy_tokens_per_sec": greedy_tps,
                   "greedy_repeats": greedy_rates,
                   "uplift_vs_greedy": best["uplift_vs_greedy"],
                   "draft": f"{size} (self-draft)", "k_sweep": sweep,
                   "note": "self-draft (same snapshot) = full "
                           "acceptance, the machinery's upper bound: "
                           "on CPU the draft steps cost target price, "
                           "so the uplift here calibrates batched-"
                           "verify dispatch amortization only — the "
                           "chip prediction arms the LADDER draft "
                           "(lm_tiny drafting lm_base at ~1/50th the "
                           "step cost), see BASELINE_SELF.json"}, lines)
            _emit(f"serve_{size}_spec_accept_len",
                  best["accept_len_mean"] or 0.0, "tokens/round",
                  {**shared, "k": best["k"], "k_sweep": sweep,
                   "note": "mean tokens emitted per slot-round "
                           "(accepted draft prefix + the verify step's "
                           "own token); k+1 = full acceptance"}, lines)
            _emit(f"serve_{size}_spec_mismatch", float(mismatch_total),
                  "requests",
                  {**shared, "requests_per_k": n_req, "k_sweep": sweep,
                   "note": "speculative output vs plain greedy on the "
                           "identical workload — the ratchet's "
                           "must-be-zero family (*_mismatch): any "
                           "nonzero is a broken acceptance rule, "
                           "never noise"}, lines)
    except Exception as e:
        errors["spec"] = repr(e)
        traceback.print_exc()

    # 6. batched-prefill amortization -------------------------------------
    try:
        if engine is not None:
            import numpy as np
            rng = np.random.default_rng(args.seed + 17)
            B = args.slots
            bp = [rng.integers(1, engine.vocab,
                               size=5 + (i % 4)).astype(np.int32)
                  for i in range(B)]       # all land in the same bucket
            for s in range(B):             # warm both shapes
                engine.prefill(s, bp[s], 1)
            engine.prefill_many([(s, bp[s], 1) for s in range(B)])
            solo_times, batch_times = [], []
            for _ in range(5):
                t0 = time.monotonic()
                for s in range(B):
                    engine.prefill(s, bp[s], 1)
                solo_times.append(time.monotonic() - t0)
                t0 = time.monotonic()
                engine.prefill_many([(s, bp[s], 1) for s in range(B)])
                batch_times.append(time.monotonic() - t0)
            solo, batched = min(solo_times), min(batch_times)
            _emit(f"serve_{size}_prefill_batch_amortization",
                  round(solo / batched, 4) if batched > 0 else 0.0, "x",
                  {**shared, "batch": B,
                   "solo_ms_per_request": round(solo / B * 1000.0, 4),
                   "batched_ms_per_request":
                       round(batched / B * 1000.0, 4),
                   "solo_repeats_ms": [round(t * 1000.0, 3)
                                       for t in solo_times],
                   "batched_repeats_ms": [round(t * 1000.0, 3)
                                          for t in batch_times],
                   "note": "one prefill_many call over a same-bucket "
                           "burst vs the same prompts prefilled solo "
                           "(best of 5, warm): the admission path's "
                           "burst amortization, also the term the "
                           "SLO predictor prices per-request"}, lines)
    except Exception as e:
        errors["prefill_batch"] = repr(e)
        traceback.print_exc()

    if args.json:
        meta = {"metric": "serving_bench_meta",
                "value": float(len(lines)), "unit": "lines",
                "vs_baseline": 1.0,
                "detail": {"family": "SERVE_lm", "platform": platform,
                           "provisional": True,   # meta, not a measurement
                           "errors": errors,
                           "note": ("CPU-platform numbers calibrate the "
                                    "serving machinery and arm chip "
                                    "predictions; never read as chip "
                                    "throughput" if platform == "cpu"
                                    else "capture-window record")}}
        with open(args.json, "w") as f:
            for rec in lines + [meta]:
                f.write(json.dumps(rec) + "\n")
        print(f"bench_serving: wrote {args.json}", file=sys.stderr,
              flush=True)
    obs_ledger.end_global(rc=0, errors=errors or None)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
