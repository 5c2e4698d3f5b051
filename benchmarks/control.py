"""Read, on the chip and at a cell's own size, the two numbers every
limit of ``correct`` is set from: what sound runs of the program give,
and what the control gives — the reference put in the program's place
and computed in a precision below the configurations' bfloat16 (int8
and fp8-e4m3 linear layers by default).  One process, several seeds,
short windows:

    python3 benchmarks/control.py --workload <cell> --seeds 11 12 13 \
        --seconds 8 [--precisions int8 fp8]

Not part of a benchmark run.  PERF.md records the readings."""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import run as bench_run                 # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--precisions", nargs="+", default=["int8", "fp8"])
    args = ap.parse_args(argv)
    rows = []
    for seed in args.seeds:
        result = bench_run.run_cell(args.workload, seed, args.seconds,
                                    False, controls=tuple(args.precisions))
        rows.append({"seed": seed, "correct": result["correct"],
                     "compared": result.get("compared"),
                     "controls": result.get("controls")})
        print(json.dumps(rows[-1]), flush=True)
    sound, control = {}, {}
    for row in rows:
        for what, value, _ in row["compared"] or []:
            sound[what] = max(sound.get(what, 0.0), value)
        for precision, verdicts in (row["controls"] or {}).items():
            for what, value, _ in verdicts:
                key = (precision, what)
                control[key] = min(control.get(key, float("inf")), value)
    print(f"over {len(rows)} seeds — largest sound reading, smallest "
          f"control reading:")
    for what, value in sound.items():
        lows = ", ".join(f"{p} {v:.6g}" for (p, w), v in control.items()
                         if w == what)
        print(f"  {what}: sound <= {value:.6g}; control >= {lows}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
