"""Per-op bytes attribution for a compiled train step (PR-2 tentpole).

Decomposes XLA cost-analysis ``bytes_accessed`` per HLO op for one of the
contract workloads' train steps and prints a ranked table: which ops carry
the bytes, per category (conv / reduce / cast / layout / gather /
elementwise / collective / matmul), raw AND effective (gather operands
re-priced at rows-actually-touched — the cost convention charges an
indexed read for its WHOLE operand, so a device-resident split makes the
aggregate number a fiction; see utils/profiling.py).

Runs standalone on any backend.  The tier-1 methodology is the CPU
backend (``--backend cpu``): attribution there is static compile
analysis — no chip needed — and the CATEGORY SHARES transfer to TPU
up to two documented backend artifacts (BASELINE.md "bytes-attribution
methodology"): CPU runs convolutions in f32, so the ``cast`` category is
CPU-only convert traffic around the bf16 stream, and CPU layout copies
differ from TPU's.  Also wired into bench_profile.py phase 2, so every
on-chip window archives the on-chip table automatically.

Usage:
  python tools/bytes_audit.py --backend cpu                  # config 4
  python tools/bytes_audit.py --workload mnist_cnn --json out.json
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

WORKLOADS = {
    # name -> (model, dataset, default augment, lr, momentum)
    "resnet20": ("resnet20", "cifar10", "cifar", 0.1, 0.9),
    "mnist_cnn": ("mnist_cnn", "mnist", "none", 0.05, 0.9),
    "softmax": ("softmax", "mnist", "none", 0.5, 0.0),
}


def build_and_audit(workload: str, batch_per_chip: int, unroll: int,
                    augment: str | None = None, top_k: int = 15) -> dict:
    """Build the named workload's indexed train step exactly as the bench
    does (bench._make — same dataset resolution, same step factory),
    compile it, and return the audit record."""
    import bench
    from distributedtensorflowexample_tpu.parallel import make_mesh
    from distributedtensorflowexample_tpu.utils.profiling import (
        cost_and_bytes_audit)

    model, dataset, default_aug, lr, momentum = WORKLOADS[workload]
    aug = default_aug if augment is None else augment
    mesh = make_mesh()
    with mesh:
        step, ds, state, u = bench._make(
            model, dataset, batch_per_chip, unroll, mesh, augment=aug,
            lr=lr, momentum=momentum)
        cost, audit = cost_and_bytes_audit(step, (state, ds.peek()),
                                           unroll=u, top_k=top_k)
    record = {"workload": workload, "model": model, "dataset": dataset,
              "augment": aug, "batch_per_chip": batch_per_chip,
              "unroll": u, "mesh_size": mesh.size,
              "backend": __import__("jax").default_backend(),
              "dequant": ds.dequant_impl or "none",
              "cost_per_step": cost, "audit": audit}
    flops = cost.get("flops")
    eff = audit.get("bytes_effective_per_step")
    if flops and eff:
        hbm_bw = float(os.environ.get("TPU_HBM_BW", 819e9))
        record["arith_intensity_raw"] = round(
            flops / audit["bytes_per_step"], 3)
        record["arith_intensity_effective"] = round(flops / eff, 3)
        # The bandwidth roofline the NEXT on-chip window should see if the
        # effective bytes (not the gather-inflated aggregate) are the true
        # traffic — the armed prediction BASELINE.md records.
        record["bw_roofline_effective_steps_per_sec"] = round(
            hbm_bw / eff, 1)
    return record


def print_table(record: dict, top_k: int = 15) -> None:
    audit = record["audit"]
    if not audit:
        print("no audit available (backend exposed no HLO text?)")
        return
    tot, eff = audit["bytes_per_step"], audit["bytes_effective_per_step"]
    print(f"# {record['workload']}  batch/chip={record['batch_per_chip']}  "
          f"unroll={record['unroll']}  backend={record['backend']}  "
          f"dequant={record['dequant']}")
    flops = record.get("cost_per_step", {}).get("flops")
    if flops:
        print(f"flops/step            {flops / 1e6:12.1f} MFLOP")
    print(f"bytes/step (raw)      {tot / 1e6:12.2f} MB")
    print(f"bytes/step (effective){eff / 1e6:12.2f} MB   "
          f"(phantom gather operands: "
          f"{audit['phantom_gather_bytes_per_step'] / 1e6:.2f} MB)")
    if "arith_intensity_effective" in record:
        print(f"arith intensity       raw {record['arith_intensity_raw']} "
              f"-> effective {record['arith_intensity_effective']} flop/B; "
              f"bw roofline {record['bw_roofline_effective_steps_per_sec']} "
              f"steps/s at TPU_HBM_BW")
    print("\nby category (effective MB/step, raw in parens):")
    raw_cat = audit["by_category_per_step"]
    for cat, b in audit["by_category_effective_per_step"].items():
        print(f"  {cat:12s} {b / 1e6:10.2f}  ({raw_cat.get(cat, 0) / 1e6:.2f})"
              f"  {100 * b / max(1, eff):5.1f}%")
    print(f"\ntop {min(top_k, len(audit['top_ops']))} ops (raw MB/step):")
    for op in audit["top_ops"][:top_k]:
        tail = op["op_name"].split("/")[-3:]
        print(f"  {op['bytes_per_step'] / 1e6:9.2f}  {op['category']:11s} "
              f"{op['opcode']:14s} {op['out'][:28]:28s} {'/'.join(tail)}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", default="resnet20",
                    choices=sorted(WORKLOADS))
    ap.add_argument("--batch_per_chip", type=int, default=256)
    ap.add_argument("--unroll", type=int, default=1,
                    help="fused steps per call; 1 audits the plain step "
                         "(per-step numbers are unroll-normalized either "
                         "way)")
    ap.add_argument("--augment", default=None,
                    help="override the workload's default augment "
                         "(none|cifar)")
    ap.add_argument("--top", type=int, default=15)
    ap.add_argument("--json", default="",
                    help="also write the full record to this path")
    ap.add_argument("--backend", default="default",
                    choices=("default", "cpu"),
                    help="cpu = pin the CPU backend in-process (the tier-1 "
                         "audit methodology: static compile analysis that "
                         "needs no chip)")
    args = ap.parse_args()

    if args.backend == "cpu":
        import jax
        jax.config.update("jax_platforms", "cpu")

    record = build_and_audit(args.workload, args.batch_per_chip,
                             args.unroll, args.augment, top_k=args.top)
    print_table(record, top_k=args.top)
    if args.json:
        with open(args.json, "w") as f:
            json.dump(record, f, indent=1)
        print(f"\nwrote {args.json}")


if __name__ == "__main__":
    main()
