#!/usr/bin/env python
"""graft-LM bench family — tokens/sec, MFU, bytes/roofline, and the knob
A/B matrix at the scale where the knobs bind (ROADMAP direction #5).

Three instruments on one workload (models/transformer_lm.py):

1. **Throughput + MFU** (``--throughput_size``, default lm_small): the
   measured tokens/sec line, plus the PR-2 bytes-audit/roofline fields
   and the new MFU line — numerator = measured steps/sec x the
   dot-general/attention FLOP audit (utils/profiling.flops_audit, the
   golden-pinned MFU denominator), never the aggregate cost_analysis
   flops (which lumps in elementwise noise).
2. **Knob A/B matrix** (``--size``, default lm_base ~57M params): the
   remat/shard_update/bucket_grads/zero3 matrix re-run where
   arXiv:2004.13336 actually evaluates — optimizer state + activations
   in the hundreds of MB — with MEASURED wins: per-device
   param+grad+opt residency read from the live array shardings for
   EVERY config (``utils/profiling.state_residency_per_device`` —
   ZeRO-1's opt-only 1/D and ZeRO-3's param+opt 1/D against ~458 MB of
   replicated params+momentum) and per-device peak temp/activation
   bytes from the compiler's own memory analysis (remat's
   resident-activation diet; where ZeRO-3's transient gathered params
   and the 1/D gradient rows live).  The ``zero3`` /
   ``zero3_nooverlap`` pair times the double-buffered AG-prefetch
   schedule against the serial-gather control (pure scheduling —
   bitwise-same math; on the CPU platform the pair only proves both
   schedules compile and run, the overlap win is the armed TPU
   prediction).
3. **Collective inventory** per config (the PR-6 instrument): the
   compiled schedule each knob actually emits.

Default mode forces a multi-device CPU mesh (bench_collectives.py's
in-process route) so every number is driver-measurable today; ``--real``
is the capture-window phase (tools/supervise.py --capture, phase
``lm``): probes with the bench.py env knobs, emits a sentinel when the
backend is down, and self-labels ``platform`` so CPU numbers are never
mistakable for chip numbers.  MFU is quoted against TPU_PEAK_FLOPS
(bench.PEAK_FLOPS, v5e bf16 default) like bench_profile.py — on the CPU
platform the ratio is only the armed prediction's denominator, and the
record says so.

Output: JSON lines (bench.py dialect) + ``--json`` writes the full
BENCH_lm_* artifact.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

_ALL_KNOBS = ("base", "remat", "shard_update", "bucket", "zero1",
              "zero3", "zero3_nooverlap")


def _emit(metric: str, value: float, unit: str, detail: dict,
          lines: list) -> None:
    # 10 decimals: a CPU-platform MFU quoted against TPU peak is ~1e-8
    # and must survive rounding (the armed prediction divides by it).
    rec = {"metric": metric, "value": round(float(value), 10),
           "unit": unit, "vs_baseline": 1.0, "detail": detail}
    print(json.dumps(rec), flush=True)
    lines.append(rec)


def _sentinel(args, attempts: list) -> None:
    line = {"metric": "lm_tokens_per_sec_per_chip", "value": 0.0,
            "unit": "unavailable", "vs_baseline": 0.0,
            "detail": {"error": "backend unreachable — sentinel record; "
                                "probe outcomes supersede this line",
                       "probe_attempts": attempts, "provisional": True}}
    print(json.dumps(line), flush=True)
    if args.json:
        with open(args.json, "w") as f:
            json.dump(line, f, indent=1)


def optstate_bytes_per_device(opt_state) -> int:
    """Per-device bytes of the optimizer state, read from the LIVE array
    shardings (one addressable shard per leaf): the measured form of the
    ZeRO-1 1/D claim — a replicated leaf's shard is the whole leaf, a
    row-sharded leaf's shard is its 1/D block."""
    import jax
    import numpy as np

    total = 0
    for leaf in jax.tree.leaves(opt_state):
        if not hasattr(leaf, "addressable_shards"):
            continue
        shard = leaf.addressable_shards[0]
        total += int(np.prod(shard.data.shape)) * leaf.dtype.itemsize
    return total


def _build(size: str, mesh, batch_per_chip: int, seq_len: int,
           unroll: int, *, remat: str = "none", shard_update: bool = False,
           bucket: bool = False, shard_params: bool = False,
           overlap: bool = True, seed: int = 0,
           split_n: int | None = None):
    """One knob config as an Engine declaration (engine/engine.py): the
    Engine resolves the remat/shard_update/bucket_grads/shard_params
    knobs into the SAME builders and layout passes run_training wires,
    so the bench measures the trainer's programs.  input_fn pins the
    bench's deterministic split sizing; optimizer_fn pins the bare
    float-LR optax.sgd (a schedule-wrapped twin has a DIFFERENT
    opt_state pytree — the measured program must stay the trainer's,
    bitwise)."""
    from distributedtensorflowexample_tpu.config import RunConfig
    from distributedtensorflowexample_tpu.engine import Engine, RunSpec
    from distributedtensorflowexample_tpu.parallel.bucketing import (
        DEFAULT_BUCKET_BYTES)

    D = mesh.size
    global_batch = batch_per_chip * D
    n = split_n if split_n is not None else max(global_batch * 8, 256)

    def input_fn(cfg, split):
        from distributedtensorflowexample_tpu.data.lm import load_lm
        return load_lm("", split, seed=seed, num=n, seq_len=seq_len)

    def optimizer_fn(cfg, _mesh, wrap_shard_update):
        import optax
        tx = optax.sgd(0.1, momentum=0.9)
        if cfg.shard_update and wrap_shard_update:
            from distributedtensorflowexample_tpu.training.optimizers \
                import cross_replica_update_sharding
            tx = cross_replica_update_sharding(tx, _mesh)
        return tx

    cfg = RunConfig(batch_size=batch_per_chip, seed=seed, remat=remat,
                    shard_update=shard_update,
                    bucket_grads=str(DEFAULT_BUCKET_BYTES) if bucket else "",
                    shard_params=shard_params, zero3_overlap=overlap,
                    learning_rate=0.1, momentum=0.9, dropout=0.0)
    spec = RunSpec(model=size, dataset="lm", config=cfg,
                   input_fn=input_fn, optimizer_fn=optimizer_fn)
    built = Engine(spec).build(mesh=mesh, unroll=unroll)
    return built.step, built.ds, built.state, built.global_batch


def _measure_rate(step, ds, state, steps: int, unroll: int,
                  repeats: int) -> tuple[float, list, object]:
    import jax
    calls = max(1, steps // unroll)
    state, metrics = step(state, next(ds))       # compile + warm
    jax.block_until_ready(metrics)
    rates = []
    for _ in range(max(1, repeats)):
        t0 = time.perf_counter()
        for _ in range(calls):
            state, metrics = step(state, next(ds))
        jax.block_until_ready(metrics)
        rates.append(calls * unroll / (time.perf_counter() - t0))
    return max(rates), [round(r, 4) for r in rates], state


def _strip_collectives(inv: dict) -> dict:
    """Record-sized view of a collective inventory (drop per-op rows)."""
    if not inv:
        return {}
    return {k: v for k, v in inv.items() if k != "ops"}


def run_throughput(args, mesh, platform, lines, errors) -> None:
    import bench
    from distributedtensorflowexample_tpu.obs.anomaly import spread_fraction
    from distributedtensorflowexample_tpu.utils.profiling import (
        compiled_program_audit)

    n = mesh.size
    size = args.throughput_size
    step, ds, state, global_batch = _build(
        size, mesh, args.batch_per_chip, args.seq_len, args.unroll,
        seed=args.seed)
    audit = compiled_program_audit(step, (state, ds.peek()),
                                   unroll=args.unroll, top_k=8)
    best, rates, state = _measure_rate(step, ds, state, args.steps,
                                       args.unroll, args.repeats)
    tokens_per_step = global_batch * args.seq_len
    hbm_bw = float(os.environ.get("TPU_HBM_BW", 819e9))     # v5e bytes/s
    detail = {
        "platform": platform, "devices": n, "size": size,
        "global_batch": global_batch, "seq_len": args.seq_len,
        "unroll": args.unroll, "tokens_per_step": tokens_per_step,
        "steps_per_sec": round(best, 4),
        "repeats": rates,
        "spread_frac": round(spread_fraction(rates), 4),
        "token_storage": "uint8" if ds.token_data else "int32",
    }
    mfu_detail = dict(detail)
    fl = audit.get("flops") or {}
    cost = audit.get("cost") or {}
    if fl.get("flops_per_step"):
        # The compiled module is the PER-DEVICE SPMD program: every
        # audited number (flops, bytes, temp arena) is per device, so
        # MFU needs no further /n — per-chip work x rate over per-chip
        # peak IS the utilization.
        model_flops = fl["flops_per_step"]
        detail["model_flops_per_step_per_device"] = model_flops
        detail["model_flops_per_sec_per_device"] = round(
            model_flops * best)
        detail["cost_analysis_flops_per_step_per_device"] = \
            cost.get("flops")
        detail["flops_audit"] = fl
        mfu = model_flops * best / bench.PEAK_FLOPS
        mfu_detail.update(
            model_flops_per_step_per_device=model_flops,
            peak_flops=bench.PEAK_FLOPS,
            note=("MFU numerator = measured rate x the dot/attention "
                  "FLOP audit of the per-device program; denominator = "
                  "TPU_PEAK_FLOPS — on the cpu platform this is the "
                  "armed prediction's denominator, not a CPU "
                  "utilization"))
    else:
        mfu = 0.0
        mfu_detail["error"] = "no flops audit available"
    bz = audit.get("bytes") or {}
    if bz:
        detail["bytes_audit"] = {k: v for k, v in bz.items()
                                 if k != "top_ops"}
        nbytes_eff = bz.get("bytes_effective_per_step")
        if nbytes_eff:
            detail["bw_roofline_effective_steps_per_sec"] = round(
                hbm_bw / nbytes_eff, 2)
            if fl.get("flops_per_step"):
                detail["arith_intensity_effective"] = round(
                    fl["flops_per_step"] / nbytes_eff, 3)
    if audit.get("collectives"):
        detail["collectives"] = _strip_collectives(audit["collectives"])
    _emit(f"{size}_tokens_per_sec_per_chip", best * tokens_per_step / n,
          "tokens/sec/chip", detail, lines)
    _emit(f"{size}_mfu", mfu, "fraction of TPU_PEAK_FLOPS", mfu_detail,
          lines)


def run_ab_matrix(args, mesh, platform, lines, errors) -> None:
    from distributedtensorflowexample_tpu.obs.trace import span
    from distributedtensorflowexample_tpu.utils.profiling import (
        compiled_program_audit)

    D = mesh.size
    size = args.size
    configs = {
        "base": {},
        "remat": {"remat": "block"},
        "shard_update": {"shard_update": True},
        "bucket": {"bucket": True},
        "zero1": {"bucket": True, "shard_update": True},
        "zero3": {"bucket": True, "shard_update": True,
                  "shard_params": True},
        "zero3_nooverlap": {"bucket": True, "shard_update": True,
                            "shard_params": True, "overlap": False},
    }
    if D <= 1:
        # No cross-replica redundancy to shard and nothing to bucket on
        # one device: land the measurable remat A/B, label the rest.
        configs = {"base": {}, "remat": {"remat": "block"}}
    results: dict = {}
    for name, kw in configs.items():
        if args.knobs and name not in args.knobs:
            continue
        try:
            with span(f"lm_ab_{name}", size=size):
                step, ds, state, global_batch = _build(
                    size, mesh, args.ab_batch_per_chip, args.seq_len,
                    args.ab_unroll, seed=args.seed, **kw)
                audit = compiled_program_audit(
                    step, (state, ds.peek()), unroll=args.ab_unroll)
                entry = {
                    "config": kw,
                    "global_batch": global_batch,
                    "opt_state_bytes_per_device":
                        optstate_bytes_per_device(state.opt_state),
                    # Per-device resident param+grad+opt split for EVERY
                    # config: the zero3 A/B's measured baseline column
                    # (grads are step-local on every path — they live in
                    # memory.temp_bytes below).
                    "residency": audit.get("residency") or {},
                    "memory": audit.get("memory") or {},
                    "collectives": _strip_collectives(
                        (audit.get("collectives") or {})),
                    "model_flops_per_step_per_device":
                        (audit.get("flops") or {}).get("flops_per_step"),
                }
                if args.ab_steps > 0 and name in args.ab_timed_knobs:
                    best, rates, _ = _measure_rate(
                        step, ds, state, args.ab_steps, args.ab_unroll,
                        args.ab_repeats)
                    entry["steps_per_sec"] = round(best, 4)
                    entry["tokens_per_sec_per_chip"] = round(
                        best * global_batch * args.seq_len / D, 2)
                    entry["repeats"] = rates
                elif args.ab_steps > 0:
                    entry["timing"] = "skipped (see --ab_timed_knobs)"
                results[name] = entry
        except Exception as e:
            errors[f"ab_{name}"] = repr(e)
            traceback.print_exc()

    base = results.get("base")
    shared = {"platform": platform, "devices": D, "size": size,
              "seq_len": args.seq_len,
              "batch_per_chip": args.ab_batch_per_chip}
    if base:
        base_temp = (base["memory"] or {}).get("temp_bytes")
        base_opt = base["opt_state_bytes_per_device"]
        if "remat" in results and base_temp:
            remat_temp = (results["remat"]["memory"] or {}).get(
                "temp_bytes")
            if remat_temp:
                _emit(f"{size}_remat_activation_savings_frac",
                      1.0 - remat_temp / base_temp, "fraction",
                      {**shared,
                       "temp_bytes_base": base_temp,
                       "temp_bytes_remat": remat_temp,
                       "note": "per-device temp/activation arena from "
                               "the compiler's memory analysis; remat "
                               "recomputes block forwards instead of "
                               "keeping them resident"}, lines)
        for name, metric in (("shard_update",
                              f"{size}_shard_update_optstate_shrink_x"),
                             ("zero1",
                              f"{size}_zero1_optstate_shrink_x")):
            if name in results and base_opt:
                opt = results[name]["opt_state_bytes_per_device"]
                if opt:
                    _emit(metric, base_opt / opt, "x (1/D ideal = D)",
                          {**shared,
                           "opt_state_bytes_per_device_base": base_opt,
                           f"opt_state_bytes_per_device_{name}": opt,
                           "collectives": results[name]["collectives"]
                           .get("multiset", {})},
                          lines)
        base_res = (base.get("residency") or {}).get(
            "state_bytes_per_device")
        if "zero3" in results and base_res:
            z3 = results["zero3"]
            z3_res = (z3.get("residency") or {}).get(
                "state_bytes_per_device")
            if z3_res:
                _emit(f"{size}_zero3_state_residency_shrink_x",
                      base_res / z3_res, "x (1/D ideal = D)",
                      {**shared,
                       "state_bytes_per_device_base": base_res,
                       "state_bytes_per_device_zero3": z3_res,
                       "residency_base": base.get("residency"),
                       "residency_zero3": z3.get("residency"),
                       "temp_bytes_zero3": (z3.get("memory") or {}).get(
                           "temp_bytes"),
                       "collectives": z3["collectives"].get("multiset",
                                                            {}),
                       "note": "per-device resident params+opt from the "
                               "live donated-argument shardings (grads "
                               "are step-local on every path and live "
                               "in temp_bytes); 1/D ideal = D"}, lines)
    # Outside the `if base:` gate on purpose: the ratio needs only the
    # zero3 pair, and the armed next-window capture runs exactly
    # `--knobs zero3,zero3_nooverlap` with no base column.
    on = (results.get("zero3") or {}).get("steps_per_sec")
    off = (results.get("zero3_nooverlap") or {}).get("steps_per_sec")
    if on and off:
        _emit(f"{size}_zero3_overlap_speedup_x", on / off,
              "x (overlap-on over overlap-off wall clock)",
              {**shared,
               "steps_per_sec_overlap_on": on,
               "steps_per_sec_overlap_off": off,
               "note": "double-buffered AG-prefetch vs serial-gather "
                       "control; XLA:CPU dispatches synchronously so "
                       "~1.0x here only proves both schedules "
                       "compile+run — the overlap win is the armed "
                       "TPU prediction (BASELINE_SELF.json)"}, lines)
    detail = {**shared, "matrix": results}
    if errors:
        detail["errors"] = dict(errors)
    if D <= 1:
        detail["note"] = (f"single-device window: shard_update/bucket "
                          f"A/Bs need a multi-device mesh — armed for "
                          f"a bigger window")
    _emit(f"{size}_knob_ab_matrix", float(len(results)), "configs",
          detail, lines)


def main(argv: list[str] | None = None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--real", action="store_true",
                   help="use the default backend (capture-window mode); "
                        "default forces a virtual CPU mesh")
    p.add_argument("--devices", type=int, default=4,
                   help="forced-CPU-mesh size (ignored with --real)")
    p.add_argument("--json", default="",
                   help="write the full record here (BENCH_lm_* artifact)")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--seq_len", type=int, default=128)
    # Throughput + MFU instrument.
    p.add_argument("--throughput_size", default="lm_small")
    p.add_argument("--batch_per_chip", type=int, default=4)
    p.add_argument("--unroll", type=int, default=4)
    p.add_argument("--steps", type=int, default=8)
    p.add_argument("--repeats", type=int, default=2)
    p.add_argument("--skip_throughput", action="store_true")
    # Knob A/B matrix.
    p.add_argument("--size", default="lm_base",
                   help="A/B-matrix model size (lm_base = where the "
                        "knobs bind)")
    p.add_argument("--ab_batch_per_chip", type=int, default=1)
    p.add_argument("--ab_unroll", type=int, default=1)
    p.add_argument("--ab_steps", type=int, default=2,
                   help="measured steps per A/B config (0 = compile-only "
                        "accounting: memory + layout + schedule)")
    p.add_argument("--ab_repeats", type=int, default=1)
    p.add_argument("--knobs", default="",
                   help="comma-separated subset of "
                        f"{_ALL_KNOBS} (default: all)")
    p.add_argument("--ab_timed_knobs",
                   default="base,remat,bucket,zero1,zero3,zero3_nooverlap",
                   help="configs that also get a measured rate; the "
                        "constraint-form shard_update is compile-only by "
                        "default on the CPU mesh (measured at lm_tiny: "
                        "XLA:CPU's partitioner collapses it ~200x, so a "
                        "timed lm_base point would cost minutes to state "
                        "a fact the small-scale number already pins — "
                        "its MEASURED claim here is the layout bytes)")
    p.add_argument("--skip_ab", action="store_true")
    args = p.parse_args(argv)
    args.knobs = [k for k in args.knobs.split(",") if k]
    args.ab_timed_knobs = [k for k in args.ab_timed_knobs.split(",") if k]
    for k in args.knobs + args.ab_timed_knobs:
        if k not in _ALL_KNOBS:
            p.error(f"unknown knob {k!r} (one of {_ALL_KNOBS})")

    if not args.real:
        import jax

        from distributedtensorflowexample_tpu.runtime import (
            cpu_collective_flags)
        if "collective_call_terminate" not in os.environ.get("XLA_FLAGS",
                                                             ""):
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + cpu_collective_flags(warn_s=120, terminate_s=1800))
        for knob, value in (("jax_platforms", "cpu"),
                            ("jax_cpu_enable_async_dispatch", False)):
            try:
                jax.config.update(knob, value)
            except RuntimeError:
                break
        else:
            try:
                jax.config.update("jax_num_cpu_devices", args.devices)
            except RuntimeError:
                pass
    else:
        # bench.py's probe loop (the bench_profile/bench_collectives
        # precedent): CPU-fallback assert, TERM-grace-KILL on a hung
        # probe child, jittered retries, sentinel on a dead backend.
        import bench
        ok, attempts = bench._wait_for_backend()
        if not ok:
            _sentinel(args, attempts)
            return 0

    import jax

    from distributedtensorflowexample_tpu.obs import ledger as obs_ledger
    from distributedtensorflowexample_tpu.obs import recorder as obs_recorder
    from distributedtensorflowexample_tpu.obs import serve as obs_serve
    from distributedtensorflowexample_tpu.parallel import make_mesh

    obs_recorder.maybe_install()
    obs_ledger.maybe_begin("bench_lm", config=vars(args))
    obs_serve.maybe_start()
    mesh = make_mesh()
    platform = jax.default_backend()
    lines: list = []
    errors: dict = {}
    with mesh:
        if not args.skip_throughput:
            try:
                run_throughput(args, mesh, platform, lines, errors)
            except Exception as e:
                errors["throughput"] = repr(e)
                traceback.print_exc()
        if not args.skip_ab:
            try:
                run_ab_matrix(args, mesh, platform, lines, errors)
            except Exception as e:
                errors["ab_matrix"] = repr(e)
                traceback.print_exc()
    if args.json:
        # JSON LINES (bench.py's stdout dialect): that is what
        # tools/bench_ratchet.py's record loader parses, so the lm
        # family ratchets like the headline family.
        meta = {"metric": "lm_bench_meta", "value": float(len(lines)),
                "unit": "lines", "vs_baseline": 1.0,
                "detail": {"family": "BENCH_lm", "platform": platform,
                           "forced_cpu_mesh": not args.real,
                           "provisional": True,   # meta, not a measurement
                           "errors": errors,
                           "note": ("CPU-mesh numbers calibrate layouts/"
                                    "schedules and arm chip predictions; "
                                    "never read as chip throughput"
                                    if platform == "cpu" else
                                    "capture-window record")}}
        with open(args.json, "w") as f:
            for rec in lines + [meta]:
                f.write(json.dumps(rec) + "\n")
        print(f"bench_lm: wrote {args.json}", file=sys.stderr, flush=True)
    obs_ledger.end_global(rc=0, errors=errors or None)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
