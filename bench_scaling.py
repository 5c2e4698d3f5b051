"""Weak-scaling harness — sync-SGD scaling efficiency 1 -> N chips.

The secondary contract metric (BASELINE.json "metric": "sync-SGD scaling
efficiency 1->8 chips"; BASELINE.md target >= 90%).  Weak scaling: fixed
per-chip batch, growing global batch — ideal scaling keeps global steps/sec
constant as devices are added, so

    efficiency(N) = steps_per_sec(N submesh) / steps_per_sec(1 submesh)

Runs the REAL pjit/psum training step (parallel/sync.py) over 1/2/4/8-device
submeshes of whatever is available:

  * real multi-chip hardware -> the contract numbers (run with --real);
  * this environment (one real chip / CI) -> the identical program on an
    8-virtual-device CPU mesh: correctness + overhead trend + the HLO
    collective accounting, so the harness is driver-runnable today and
    chip-ready the day multi-chip hardware appears.

Also reports per-step collective traffic parsed from each submesh's
compiled HLO (op counts + bytes of all-reduce / all-gather /
reduce-scatter / collective-permute / all-to-all) — on a 1-D data mesh the
expected shape is ONE fused gradient all-reduce of ~|params| f32 bytes.

Emits one JSON line per device count and a final summary line
``{"metric": "<mode>_sgd_weak_scaling", ...}``.

``--mode async`` runs the config-2 local-SGD step instead: each device
steps its own virtual worker and the worker average all-reduces only every
``--async_period`` steps, so the sustained collective bytes per step are
the sync mode's divided by the period (reported as
``amortized_bytes_per_step``) — the communication-scaling advantage the
async path buys at the price of bounded staleness.
"""

from __future__ import annotations

import argparse
import json
import math
import re

_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8,
                "s32": 4, "u64": 8, "u32": 4, "s16": 2, "u16": 2,
                "s8": 1, "u8": 1, "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
                "collective-permute", "all-to-all")


def collective_traffic(hlo_text: str) -> dict:
    """Sum output bytes of every collective op in an HLO module text.

    An HLO line reads ``%name = f32[256,10]{1,0} all-reduce(...)`` (or a
    tuple of shapes for variadic all-reduce); we account every
    ``dtype[dims]`` appearing before the op token on such lines.
    """
    shape_re = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
    out: dict = {op: {"count": 0, "bytes": 0} for op in _COLLECTIVES}
    for line in hlo_text.splitlines():
        for op in _COLLECTIVES:
            token = f" {op}("
            if token in line and "=" in line:
                head = line.split(token)[0].split("=", 1)[1]
                total = 0
                for dtype, dims in shape_re.findall(head):
                    if dtype not in _DTYPE_BYTES:
                        continue
                    n = math.prod(int(d) for d in dims.split(",") if d) \
                        if dims else 1
                    total += n * _DTYPE_BYTES[dtype]
                out[op]["count"] += 1
                out[op]["bytes"] += total
                break
    return {op: v for op, v in out.items() if v["count"]}


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--real", action="store_true",
                        help="use the real default backend's devices "
                             "(multi-chip hardware); default is an "
                             "8-virtual-device CPU mesh")
    parser.add_argument("--max_devices", type=int, default=8)
    parser.add_argument("--batch_per_chip", type=int, default=64)
    parser.add_argument("--unroll", type=int, default=16)
    parser.add_argument("--steps", type=int, default=64,
                        help="measured steps per repeat (3 repeats)")
    parser.add_argument("--mode", choices=("sync", "async"), default="sync",
                        help="sync = one gradient all-reduce per step; "
                             "async = local-SGD (config 2), whose worker "
                             "average all-reduces only every "
                             "--async_period steps — the per-step "
                             "collective bytes divide by the period")
    parser.add_argument("--async_period", type=int, default=8)
    args = parser.parse_args()
    if args.mode == "async" and args.async_period < 1:
        parser.error(f"--async_period must be >= 1, got {args.async_period}")

    import jax
    if not args.real:
        # Forced CPU mesh; must run before first backend use.
        import os

        from distributedtensorflowexample_tpu.runtime import (
            cpu_collective_flags)
        if "collective_call_terminate" not in os.environ.get("XLA_FLAGS", ""):
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + cpu_collective_flags(warn_s=120, terminate_s=600))
        for knob, value in (("jax_platforms", "cpu"),
                            ("jax_cpu_enable_async_dispatch", False)):
            try:
                jax.config.update(knob, value)
            except RuntimeError:
                break
        else:
            try:
                jax.config.update("jax_num_cpu_devices", args.max_devices)
            except RuntimeError:
                pass

    import optax

    from distributedtensorflowexample_tpu.config import RunConfig
    from distributedtensorflowexample_tpu.data.synthetic import make_synthetic
    from distributedtensorflowexample_tpu.engine import Engine, RunSpec
    from distributedtensorflowexample_tpu.parallel import make_mesh
    # Same warmup/best-of-repeats measurement the main bench uses.
    from bench import _measure

    # Run ledger + live scrape (env-gated; OBS_LEDGER / OBS_HTTP_PORT).
    from distributedtensorflowexample_tpu.obs import ledger as obs_ledger
    from distributedtensorflowexample_tpu.obs import serve as obs_serve
    obs_ledger.maybe_begin("bench_scaling", config=vars(args))
    obs_serve.maybe_start()

    avail = len(jax.devices())
    counts = [n for n in (1, 2, 4, 8, 16, 32) if n <= min(avail,
                                                          args.max_devices)]
    backend = jax.default_backend()
    results = {}
    for n in counts:
        mesh = make_mesh(n)
        global_batch = args.batch_per_chip * n

        def input_fn(cfg, split, _gb=global_batch):
            return make_synthetic(_gb * args.unroll * 2, (28, 28, 1),
                                  10, seed=0)

        def optimizer_fn(cfg, _mesh, wrap_shard_update):
            return optax.sgd(0.05, momentum=0.9)

        # The config-1/2 workloads as Engine declarations
        # (engine/engine.py): the Engine wires the same indexed
        # sync/async step builders run_training measures.
        spec = RunSpec(
            model="mnist_cnn", dataset="mnist",
            config=RunConfig(batch_size=args.batch_per_chip, seed=0,
                             sync_mode=args.mode,
                             async_period=args.async_period),
            input_fn=input_fn, optimizer_fn=optimizer_fn)
        built = Engine(spec).build(mesh=mesh, unroll=args.unroll)
        step, ds, state = built.step, built.ds, built.state
        with mesh:
            # Per-step collective traffic from a SINGLE-step compile: in
            # the unrolled program the collectives live inside the scan
            # body (once in the module text, executed every sub-step), so
            # the one-step module is the honest per-step accounting.
            # peek, not next: lowering must not advance the perm ring
            # ahead of state.step (the unroll-1 build's own dataset is
            # discarded — only its compiled step is inspected).
            per_step = collective_traffic(
                Engine(spec).build(mesh=mesh, unroll=1).step
                .lower(state, ds.peek()).compile().as_text())
            best, rates, _ = _measure(step, ds, state, args.steps,
                                      args.unroll, warmup_calls=1)
        results[n] = {"steps_per_sec": best,
                      "repeats": rates,
                      "collectives_per_step": per_step}
        line = {
            "devices": n, "backend": backend, "mode": args.mode,
            "global_batch": global_batch,
            "steps_per_sec": round(best, 2),
            "repeats": rates,
            "collectives_per_step": per_step,
        }
        if args.mode == "async":
            # The worker-average all-reduce sits in a lax.cond branch: it
            # appears once in the module text but executes only every
            # --async_period-th step, so the sustained wire cost is the
            # parsed bytes divided by the period — local SGD's whole
            # communication advantage over per-step sync.
            line["amortized_bytes_per_step"] = {
                op: round(v["bytes"] / args.async_period)
                for op, v in per_step.items()}
            # The parsed all-reduce bucket also holds the scalar
            # loss/accuracy metrics psum, which runs EVERY step (not
            # cond-gated), so the division is exact only for the worker
            # average; the error is the ~8-byte metrics psum per step.
            line["amortized_note"] = (
                "exact for the cond-gated worker average only; the "
                "every-step scalar-metrics psum bytes are amortized too")
        print(json.dumps(line), flush=True)

    base = results[counts[0]]["steps_per_sec"]
    efficiency = {str(n): round(results[n]["steps_per_sec"] / base, 4)
                  for n in counts}
    print(json.dumps({
        "metric": f"{args.mode}_sgd_weak_scaling",
        "value": efficiency[str(counts[-1])],
        "unit": f"efficiency_1_to_{counts[-1]}",
        "vs_baseline": 1.0,
        "detail": {"backend": backend, "mode": args.mode,
                   "efficiency": efficiency,
                   "batch_per_chip": args.batch_per_chip,
                   "note": ("real-chip contract numbers require multi-chip "
                            "hardware (--real); virtual CPU meshes share "
                            "one host's cores, so their efficiency reflects "
                            "per-step overhead trend only")},
    }), flush=True)
    obs_ledger.end_global(rc=0)


if __name__ == "__main__":
    main()
