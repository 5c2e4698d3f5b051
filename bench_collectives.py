#!/usr/bin/env python
"""Collective latency/bandwidth characterization — message size x mesh
shape curves with a fitted knee (arXiv:1810.11112's CUDA-aware-MPI
methodology applied to this stack).

PR 2 built the instrument that says which OPS carry the HBM bytes; this
is the comms twin's calibration half: for each collective (psum /
reduce-scatter / all-gather / all-to-all) and each 1-D submesh size,
measure wall latency across a message-size sweep and fit

    t(S) = alpha + S / beta          (alpha = fixed cost, beta = bandwidth)

whose knee ``alpha * beta`` is the message size where transfer time
equals fixed cost (50% efficiency).  The knee is what ``--bucket_grads
auto`` sizes gradient buckets to (parallel/bucketing.py): below it,
per-parameter all-reduces pay mostly alpha; fusing to >= ~4x the knee
pushes alpha's share under ~20%.

Default mode runs the identical programs on a forced multi-device CPU
mesh (the ``jax_num_cpu_devices`` config — the tests' 8-virtual-device
environment), so the curves are driver-measurable today; ``--real`` uses
the default backend and is the capture-window phase
(tools/supervise.py --capture), re-fitting the knee on chips.

Env/sentinel contract (BASELINE.md "bytes-attribution methodology"):
this container's shell profile exports JAX_PLATFORMS=cpu, under which
``--real`` resolves to the CPU backend — the record labels itself
``platform: cpu`` so CPU curves can never be mistaken for chip numbers.
With the env unset (``env -u JAX_PLATFORMS``) and the backend down,
``--real`` probes with the bench.py env knobs (BENCH_PROBE_TIMEOUT_S /
BENCH_RETRY_BUDGET_S / BENCH_RETRY_INTERVAL_S) and emits a sentinel
record instead of hanging, so the capture queue keeps moving.

Output: one JSON line per measured point, a final BENCH_*-family summary
line, and ``--json`` writes the full record (the BENCH_collectives_*
artifact the capture archives).
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

_COLLECTIVES = ("psum", "reduce_scatter", "all_gather", "all_to_all")
# Ring-algorithm wire factors: an all-reduce moves 2(n-1)/n of the payload
# per device, the single-phase collectives (n-1)/n.
_BUS_FACTOR = {"psum": lambda n: 2 * (n - 1) / n,
               "reduce_scatter": lambda n: (n - 1) / n,
               "all_gather": lambda n: (n - 1) / n,
               "all_to_all": lambda n: (n - 1) / n}


def fit_latency_bandwidth(sizes_bytes, times_s) -> dict:
    """Least-squares fit of ``t = alpha + S/beta`` over (size, time)
    points.  Returns alpha (s), beta (bytes/s), the knee ``alpha*beta``
    (bytes), and r2 of the fit; degenerate inputs (one point, zero
    variance, non-positive slope) fall back to knee=None so callers
    never size buckets off a meaningless fit."""
    n = len(sizes_bytes)
    out = {"alpha_s": None, "beta_bytes_per_s": None, "knee_bytes": None,
           "r2": None}
    if n < 2:
        return out
    sx = sum(sizes_bytes)
    sy = sum(times_s)
    sxx = sum(s * s for s in sizes_bytes)
    sxy = sum(s * t for s, t in zip(sizes_bytes, times_s))
    den = n * sxx - sx * sx
    if den <= 0:
        return out
    slope = (n * sxy - sx * sy) / den          # 1/beta
    alpha = (sy - slope * sx) / n
    if slope <= 0 or alpha <= 0:
        return out
    mean_t = sy / n
    ss_tot = sum((t - mean_t) ** 2 for t in times_s)
    ss_res = sum((t - (alpha + slope * s)) ** 2
                 for s, t in zip(sizes_bytes, times_s))
    beta = 1.0 / slope
    out.update(alpha_s=alpha, beta_bytes_per_s=beta,
               knee_bytes=int(alpha * beta),
               r2=None if ss_tot == 0 else round(1 - ss_res / ss_tot, 4))
    return out


def suggest_bucket_bytes(knee_bytes: int | None) -> int | None:
    """--bucket_grads auto sizing from a fitted all-reduce knee: ~4x the
    knee (alpha's share of t(S) down to ~20%), clamped to a sane range
    so a pathological fit can't produce a 1-byte or 1-GB bucket."""
    if not knee_bytes or knee_bytes <= 0:
        return None
    return int(min(max(4 * knee_bytes, 256 << 10), 64 << 20))


def _sentinel(args, attempts: list) -> None:
    line = {"metric": "collective_allreduce_knee_bytes", "value": 0.0,
            "unit": "unavailable", "vs_baseline": 0.0,
            "detail": {"error": "backend unreachable — sentinel record; "
                                "probe outcomes supersede this line",
                       "probe_attempts": attempts, "provisional": True}}
    print(json.dumps(line), flush=True)
    if args.json:
        with open(args.json, "w") as f:
            json.dump(line, f, indent=1)


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--real", action="store_true",
                        help="use the default backend's devices (the "
                             "capture-window mode); default forces a "
                             "virtual CPU mesh so curves are measurable "
                             "with the chip down")
    parser.add_argument("--max_devices", type=int, default=8)
    parser.add_argument("--sizes", default="4096,32768,262144,1048576,4194304",
                        help="comma-separated message sizes in BYTES (the "
                             "full payload per collective)")
    parser.add_argument("--collectives", default=",".join(_COLLECTIVES))
    parser.add_argument("--submeshes", default="2,4,8",
                        help="1-D data-mesh sizes to sweep")
    parser.add_argument("--repeats", type=int, default=7,
                        help="timed calls per point (min is reported: "
                             "the latency floor, arXiv:1810.11112 style)")
    parser.add_argument("--json", default="",
                        help="also write the full record here "
                             "(BENCH_collectives_* artifact)")
    args = parser.parse_args()

    if not args.real:
        # Forced CPU mesh (the same block bench_scaling.py uses), set
        # before first backend use.
        import jax

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
    else:
        # bench.py's probe loop, reused like bench_profile.py does — it
        # carries the contracts a local copy kept losing: the CPU-fallback
        # assert (a backend that silently degrades to CPU must fail the
        # probe, not get measured), TERM-grace-KILL on a hung probe child
        # (never SIGKILL a process mid-backend-init), the jittered
        # sleep between retries, and the JAX_PLATFORMS=cpu /
        # BENCH_SKIP_PROBE skip (an exported CPU pin means there is no
        # chip to probe — measure on CPU and SAY so; the record labels
        # platform cpu below).
        import bench
        ok, attempts = bench._wait_for_backend()
        if not ok:
            _sentinel(args, attempts)
            return

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from distributedtensorflowexample_tpu.obs import ledger as obs_ledger
    from distributedtensorflowexample_tpu.obs import serve as obs_serve

    # Run ledger + live scrape (env-gated; OBS_LEDGER / OBS_HTTP_PORT):
    # the same per-run bookkeeping every bench entrypoint now leaves.
    obs_ledger.maybe_begin("bench_collectives", config=vars(args))
    obs_serve.maybe_start()
    devices = jax.devices()
    platform = jax.default_backend()
    sizes = [int(s) for s in args.sizes.split(",") if s]
    colls = [c for c in args.collectives.split(",") if c]
    for c in colls:
        if c not in _COLLECTIVES:
            parser.error(f"unknown collective {c!r} (one of {_COLLECTIVES})")
    counts = [int(n) for n in args.submeshes.split(",") if n]
    counts = [n for n in counts
              if 1 < n <= min(len(devices), args.max_devices)]
    if not counts:
        if args.real:
            # A single-chip window has no collective mesh to sweep —
            # land a labeled record and keep the capture queue green
            # (multi-chip curves stay armed for a bigger window).
            line = {"metric": "collective_allreduce_knee_bytes",
                    "value": 0.0, "unit": "unavailable",
                    "vs_baseline": 0.0,
                    "detail": {"platform": platform,
                               "error": f"backend exposes "
                                        f"{len(devices)} device(s) — no "
                                        f"multi-device mesh to "
                                        f"characterize; multi-chip "
                                        f"curves stay armed",
                               "provisional": True}}
            print(json.dumps(line), flush=True)
            if args.json:
                with open(args.json, "w") as f:
                    json.dump(line, f, indent=1)
            # A deliberate labeled sentinel IS a reported outcome — the
            # atexit rc=None close is reserved for deaths that never got
            # to say anything.
            obs_ledger.end_global(
                rc=0, note="single-device window sentinel")
            return
        parser.error(f"no usable submesh size (have {len(devices)} devices)")

    axis = "data"

    def make_fn(coll, mesh, n, local_elems):
        if coll == "psum":
            op = lambda x: jax.lax.psum(x, axis)
        elif coll == "reduce_scatter":
            op = lambda x: jax.lax.psum_scatter(
                x, axis, scatter_dimension=0, tiled=True)
        elif coll == "all_gather":
            op = lambda x: jax.lax.all_gather(x, axis, axis=0, tiled=True)
        else:  # all_to_all
            op = lambda x: jax.lax.all_to_all(
                x.reshape(n, -1), axis, split_axis=0,
                concat_axis=0).ravel()
        return jax.jit(jax.shard_map(op, mesh=mesh, in_specs=P(axis),
                                     out_specs=P(axis), check_vma=False))

    points = []
    knees: dict = {}
    for n in counts:
        mesh = Mesh(np.array(devices[:n]), (axis,))
        for coll in colls:
            series = []
            for size in sizes:
                # Full payload = `size` bytes of f32; element count
                # rounded up so every reshape/scatter divides (n*n
                # covers the all_to_all [n, k] split).
                elems = -(-(size // 4) // (n * n)) * (n * n)
                if coll == "all_gather":
                    local = elems // n        # gathers back to `elems`
                else:
                    local = elems
                rng = np.random.default_rng(0)
                host = rng.standard_normal(local * n).astype(np.float32)
                x = jax.device_put(
                    host, NamedSharding(mesh, P(axis)))
                fn = make_fn(coll, mesh, n, local)
                jax.block_until_ready(fn(x))     # compile + warm
                jax.block_until_ready(fn(x))
                best = math.inf
                for _ in range(max(1, args.repeats)):
                    t0 = time.perf_counter()
                    jax.block_until_ready(fn(x))
                    best = min(best, time.perf_counter() - t0)
                payload = elems * 4
                bus = _BUS_FACTOR[coll](n) * payload / best
                point = {"collective": coll, "devices": n,
                         "bytes": payload,
                         "latency_s": round(best, 9),
                         "goodput_bytes_per_s": round(payload / best),
                         "bus_bytes_per_s": round(bus),
                         "platform": platform}
                points.append(point)
                series.append((payload, best))
                print(json.dumps(point), flush=True)
            fit = fit_latency_bandwidth([s for s, _ in series],
                                        [t for _, t in series])
            knees.setdefault(coll, {})[str(n)] = fit

    ar_knee = None
    if "psum" in knees:
        ar_knee = knees["psum"][str(counts[-1])]["knee_bytes"]
    record = {
        "metric": "collective_allreduce_knee_bytes",
        "value": float(ar_knee or 0),
        "unit": "bytes" if ar_knee else "unavailable",
        "vs_baseline": 1.0,
        "detail": {
            "platform": platform,
            "forced_cpu_mesh": not args.real,
            "chip": platform not in ("cpu",),
            "note": ("CPU curves — latency/knee calibrate the CPU mesh "
                     "only, NEVER read as chip numbers; --real in a "
                     "live window re-fits them"
                     if platform == "cpu" else
                     "on-chip curves (capture window)"),
            "devices": counts,
            "sizes_bytes": sizes,
            "repeats": args.repeats,
            "knees": knees,
            "suggested_bucket_bytes": suggest_bucket_bytes(ar_knee),
            "points": points,
        },
    }
    print(json.dumps({k: v for k, v in record.items() if k != "detail"}
                     | {"detail": {k: v for k, v in
                                   record["detail"].items()
                                   if k != "points"}}), flush=True)
    if args.json:
        with open(args.json, "w") as f:
            json.dump(record, f, indent=1)
        print(f"bench_collectives: wrote {args.json}", file=sys.stderr,
              flush=True)
    obs_ledger.end_global(rc=0)


if __name__ == "__main__":
    main()
