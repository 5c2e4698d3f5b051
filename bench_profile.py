"""On-chip ResNet-20 profiling + MFU attribution (VERDICT r2 item 2).

Decomposes the flagship workload's throughput in ONE process/window (the
shared chip's ~10-20x cross-window variance makes cross-window deltas
meaningless, BASELINE_SELF.json note):

  measured(augment)    the contract config-4 path (crop/flip on device)
  measured(no augment) same fused gather/perm-ring path, augment off
  roofline             scanned fixed resident batch — no gather/augment/
                       per-call dispatch (bench._roofline_probe)

  augment share   = 1 - rate_aug / rate_noaug
  input+dispatch  = 1 - rate_noaug / rate_roofline
  compute quality = rate_roofline vs the analytic MXU ceiling (printed as
                    mfu_roofline; the residual is conv MXU underfill at
                    widths 16/32/64 + BN/elementwise HBM traffic —
                    attributed by the trace)

Also captures a jax.profiler trace of a steady-state window (NOT the
compile) when the backend supports it; emits one JSON line per variant,
same shape as bench.py lines.

Usage (on the chip):  python bench_profile.py --unroll 195
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import time
import traceback

import jax

import bench
from distributedtensorflowexample_tpu.obs import recorder as obs_recorder
from distributedtensorflowexample_tpu.obs.trace import span


def _emit(metric: str, value: float, detail: dict) -> None:
    print(json.dumps({"metric": metric, "value": round(value, 2),
                      "unit": "steps/sec/chip", "vs_baseline": 1.0,
                      "detail": detail}), flush=True)


# ResNet-20 at CIFAR shapes is bandwidth-bound (arithmetic intensity
# ~4 FLOP/B vs the v5e ridge ~240), so the honest roofline is
# min(peak_flops/F, hbm_bw/B) — the MFU number alone misattributes a
# bandwidth ceiling as 'low utilization'.  Cost probing shares bench's
# one implementation (bench._cost_per_step).


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--unroll", type=int, default=195,
                    help="fused steps per call (195 = 1 epoch at batch 256)")
    ap.add_argument("--steps", type=int, default=390)
    ap.add_argument("--batch_per_chip", type=int, default=256)
    ap.add_argument("--trace_dir", default="/tmp/resnet_trace")
    ap.add_argument("--skip_trace", action="store_true")
    ap.add_argument("--roofline_length", type=int, default=128,
                    help="scanned steps per roofline repeat (CI shrinks "
                         "this: 128 ResNet steps x 4 runs take tens of "
                         "minutes on the virtual CPU mesh)")
    args = ap.parse_args()

    # Under a supervised capture (or OBS_FLIGHT=1), leave a per-phase
    # flight postmortem (the spans below share OBS_PHASE with the
    # capture journal's task).  sigterm default ON: unlike bench.py this
    # process has no record-survival handler of its own, so without the
    # chained dump a supervisor wall-timeout TERM would kill it with no
    # postmortem at all.
    obs_recorder.maybe_install()
    # Run ledger + live scrape (env-gated; OBS_LEDGER / OBS_HTTP_PORT).
    from distributedtensorflowexample_tpu.obs import ledger as obs_ledger
    from distributedtensorflowexample_tpu.obs import serve as obs_serve
    obs_ledger.maybe_begin("bench_profile", config=vars(args))
    obs_serve.maybe_start()

    probe_attempts: list = []

    def emit_unavailable(why: str) -> None:
        print(json.dumps({
            "metric": "resnet20_attribution", "value": 0.0,
            "unit": "unavailable", "vs_baseline": 0.0,
            "detail": {"error": why[:500],
                       "probe_attempts": probe_attempts[-8:]}}), flush=True)

    # Same outage resilience as bench.main: probe-with-retries before the
    # in-process init, the init itself sentinel-guarded, and a watchdog
    # for calls that block without raising after the backend dies mid-run
    # (round-3 failure shape).
    reachable, attempts = bench._wait_for_backend()
    probe_attempts.extend(attempts)
    if not reachable:
        emit_unavailable("TPU backend unreachable after probe retries "
                         f"(budget {bench.RETRY_BUDGET_S:.0f}s)")
        # A reported sentinel is a clean outcome in the ledger too —
        # rc=None stays reserved for runs that never got to say so.
        obs_ledger.end_global(rc=0, note="backend unreachable sentinel")
        return
    if bench._cpu_platform():
        # CPU-platform runs (CI / virtual mesh) are legitimately slow —
        # the --roofline_length help text warns default sizes take tens
        # of minutes there — and hold no chip to hang on; don't arm.
        # Platform check only (NOT _cpu_pinned): a real TPU run with
        # BENCH_SKIP_PROBE=1 can still wedge mid-profile and, in the
        # detached capture path, would hang forever unwatched.
        watchdog_done = None
    else:
        watchdog_done = bench._arm_watchdog(
            bench.TOTAL_BUDGET_S, lambda: emit_unavailable(
                f"watchdog: profiling exceeded {bench.TOTAL_BUDGET_S:.0f}s "
                "— a call blocked without raising (backend presumed lost "
                "mid-run); lines above are valid completed measurements"))

    from distributedtensorflowexample_tpu.parallel import make_mesh
    try:
        mesh = make_mesh()
    except Exception as e:
        emit_unavailable(f"TPU backend unavailable: {e!r}")
        if watchdog_done is not None:
            watchdog_done.set()
        obs_ledger.end_global(rc=0, note="backend-unavailable sentinel")
        return
    n = mesh.size
    rates = {}
    errors = {}

    def attempt(name, fn):
        """Per-stage fault isolation, like bench.main: a backend failure in
        one variant must not eat the lines the earlier variants already
        paid for (nor the attribution summary below)."""
        try:
            fn()
        except Exception as e:
            errors[name] = repr(e)
            traceback.print_exc()

    HBM_BW = float(os.environ.get("TPU_HBM_BW", 819e9))   # v5e bytes/s

    def run_variant(tag, aug):
        with span(f"profile_{tag}", unroll=args.unroll):
            return _run_variant_inner(tag, aug)

    def _run_variant_inner(tag, aug):
        from distributedtensorflowexample_tpu.utils.profiling import (
            cost_and_bytes_audit)
        step, ds, state, u = bench._make(
            "resnet20", "cifar10", args.batch_per_chip, args.unroll,
            mesh, augment=aug, lr=0.1)
        # One lower+compile serves both the aggregate cost keys AND the
        # per-op bytes table (tools/bytes_audit.py's decomposition): the
        # round-5 record carried only the aggregate, which over-counts
        # the fused resident-split gather by the whole split array —
        # effective bytes re-price it at rows-touched, and that is the
        # honest denominator for the bandwidth roofline below.
        cost, audit = cost_and_bytes_audit(step, (state, ds.peek()),
                                           unroll=u, top_k=8)
        best, reps, state = bench._measure(step, ds, state, args.steps, u)
        rates[tag] = best
        flops, nbytes = cost.get("flops"), cost.get("bytes_accessed")
        detail = {"repeats": reps, "unroll": u, "flops_per_step": flops,
                  "bytes_per_step": nbytes}
        if flops:
            detail["mfu"] = round(flops * best / n / bench.PEAK_FLOPS, 5)
        if flops and nbytes:
            # Compute-vs-bandwidth attribution: which wall does this
            # program's arithmetic intensity put it against?
            detail["arith_intensity_flop_per_byte"] = round(
                flops / nbytes, 2)
            detail["bw_roofline_steps_per_sec"] = round(HBM_BW / nbytes, 1)
            detail["mfu_ceiling_at_bw"] = round(
                (HBM_BW / nbytes) * flops / bench.PEAK_FLOPS, 5)
        if audit:
            detail["bytes_audit"] = audit
            nbytes_eff = audit.get("bytes_effective_per_step")
            if flops and nbytes_eff:
                detail["arith_intensity_effective"] = round(
                    flops / nbytes_eff, 2)
                detail["bw_roofline_effective_steps_per_sec"] = round(
                    HBM_BW / nbytes_eff, 1)
                detail["mfu_ceiling_at_bw_effective"] = round(
                    (HBM_BW / nbytes_eff) * flops / bench.PEAK_FLOPS, 5)
        _emit(f"resnet20_profile_{tag}", best / n, detail)
        return step, ds, state, u

    with mesh:
        for tag, aug in (("augment", "cifar"), ("no_augment", "none")):
            box = []
            attempt(tag, lambda: box.append(run_variant(tag, aug)))
            if not box:
                continue
            step, ds, state, u = box[0]

            if tag == "augment" and not args.skip_trace:
                # Trace ONE steady-state call (state is warm, program
                # cached) — the trace shows the op-level time breakdown
                # the MFU number alone can't give.
                try:
                    jax.profiler.start_trace(args.trace_dir)
                    try:
                        with span("trace_window", unroll=u):
                            t0 = time.perf_counter()
                            state, m = step(state, next(ds))
                            jax.block_until_ready(m)
                            dt = time.perf_counter() - t0
                    finally:
                        # Never leave the profiler running: it would skew
                        # the no_augment + roofline rates measured next.
                        jax.profiler.stop_trace()
                    files = glob.glob(os.path.join(
                        args.trace_dir, "**", "*"), recursive=True)
                    nbytes = sum(os.path.getsize(f) for f in files
                                 if os.path.isfile(f))
                    _emit("resnet20_traced_window", u / dt / n,
                          {"trace_dir": args.trace_dir,
                           "trace_files": len(files),
                           "trace_bytes": nbytes,
                           "steps_in_window": u})
                except Exception as e:
                    traceback.print_exc()
                    print(json.dumps({
                        "metric": "resnet20_traced_window",
                        "value": 0.0, "unit": "unavailable",
                        "vs_baseline": 0.0,
                        "detail": {"error": f"profiler failed: {e!r}"[:400]},
                    }), flush=True)

        def run_roofline():
            with span("roofline", length=args.roofline_length):
                roof = bench._roofline_probe(mesh, args.batch_per_chip,
                                             length=args.roofline_length,
                                             model_name="resnet20",
                                             sample=(32, 32, 3), lr=0.1)
            rates["roofline"] = max(roof)
            _emit("resnet20_roofline", max(roof) / n, {"repeats": roof})

        attempt("roofline", run_roofline)

    # Attribution from whatever survived — partial shares still tell the
    # story of the window (errors ride along for the missing pieces).
    detail = {}
    if "augment" in rates and "no_augment" in rates:
        detail["augment_share"] = round(
            1 - rates["augment"] / rates["no_augment"], 4)
    if "no_augment" in rates and "roofline" in rates:
        detail["input_dispatch_share"] = round(
            1 - rates["no_augment"] / rates["roofline"], 4)
    if errors:
        detail["errors"] = errors
    if detail or ("augment" in rates and "roofline" in rates):
        print(json.dumps({
            "metric": "resnet20_attribution",
            "value": (round(rates["augment"] / rates["roofline"], 4)
                      if "augment" in rates and "roofline" in rates
                      else 0.0),
            "unit": "measured/roofline", "vs_baseline": 1.0,
            "detail": detail}), flush=True)
    if watchdog_done is not None:
        watchdog_done.set()
    obs_ledger.end_global(rc=0, errors=errors or None)


if __name__ == "__main__":
    main()
