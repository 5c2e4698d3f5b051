#!/usr/bin/env python
"""graftlint — two-front static analysis: repo-invariant AST rules +
compiled-HLO contract checks (PR 13; rule table in docs/DESIGN.md §20).

Source front (analysis/src_lint.py — stdlib-only, no jax import):
  stdlib-only     obs/ (+ tagged modules) never reach jax/numpy at
                  import time, proven on the whole import graph
  env-registry    every named os.environ read is declared + documented
                  in analysis/env_registry.py (env-dynamic: dynamic
                  reads must resolve; env-dead: no orphan entries)
  named-refusal   mode-legality refusals (messages naming a --flag)
                  raise refusal.ModeRefusal, not bare ValueError
  clock-seam      no bare time.time()/datetime.now() in obs/ outside
                  the obs/metrics.py _now/_wall seam
  keep-in-sync    paired KEEP-IN-SYNC digest markers agree with their
                  regions' current content
  engine-owns-wiring  raw step-wiring names (parallel/ step builders,
                  worker/opt-state re-layout ctors, shard_map) appear
                  only under engine/ and parallel/ — everywhere else
                  a workload is a RunSpec (allowlist in src_lint)

HLO front (analysis/hlo_lint.py — compiles the per-mode softmax suite
on a CPU mesh plus the serving decode step, then checks each module
against the contract declared next to its step builder in
parallel/{sync,bucketing,zero3}.py and serving/engine.py): zero3's
AG-before-RS prefetch with no step-closing AG, zero1's RS+AG pair,
per-mode collective budgets, donation aliasing (incl. the serving
KV-cache's donate-and-reuse step), dtype ceilings.

Findings flow through the checked-in waiver file
(analysis/waivers.json — dated + reasoned, budget 5, stale waivers are
findings).  Exit 0 = no unwaived findings; 1 = unwaived findings;
2 = internal error.

Usage:
  python -m tools.graftlint                 # both fronts, repo root
  python -m tools.graftlint --front src     # AST rules only (fast)
  python -m tools.graftlint --json - --md report.md
  python -m tools.graftlint --fix           # registry stubs + marker
                                            # digest re-stamp, then re-lint
"""

from __future__ import annotations

import argparse
import json
import os
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

from distributedtensorflowexample_tpu.analysis import (  # noqa: E402
    Finding, apply_waivers, load_waivers, waivers_path)
from distributedtensorflowexample_tpu.analysis import src_lint  # noqa: E402


def _run_hlo_front(bucket_bytes: int) -> list[Finding]:
    """Compile-and-check on 8 virtual CPU devices (the contracts are
    stated for that mesh) unless an exported JAX_PLATFORMS says
    otherwise; the device count is left alone when a caller already
    initialized a backend (the in-process tier-1 run under
    tests/conftest.py)."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax
    try:
        jax.config.update("jax_num_cpu_devices", 8)
    except RuntimeError:
        pass    # backend already initialized — use it as configured
    from distributedtensorflowexample_tpu.analysis import hlo_lint
    return hlo_lint.run_hlo_lint(bucket_bytes=bucket_bytes)


def _render_md(unwaived, waived, stale, fixes) -> str:
    lines = ["# graftlint report", ""]
    if fixes:
        lines += ["## fixes applied", ""]
        lines += [f"- {d}" for d in fixes]
        lines.append("")

    def table(title, items):
        if not items:
            return
        lines.append(f"## {title} ({len(items)})")
        lines.append("")
        lines.append("| rule | where | message |")
        lines.append("|---|---|---|")
        for f in items:
            where = f"{f.path}:{f.line}" if f.line else f.path
            msg = f.message.replace("|", "\\|")
            lines.append(f"| {f.rule} | {where} | {msg} |")
        lines.append("")

    table("unwaived findings", unwaived)
    table("waived findings", waived)
    table("stale waivers", stale)
    if not (unwaived or waived or stale):
        lines.append("clean: no findings.")
    else:
        lines.append(f"verdict: {len(unwaived)} unwaived, "
                     f"{len(waived)} waived, {len(stale)} stale "
                     f"waiver(s).")
    lines.append("")
    return "\n".join(lines)


def _emit(text: str, dest: str) -> None:
    if dest == "-":
        sys.stdout.write(text)
    else:
        with open(dest, "w", encoding="utf-8") as f:
            f.write(text)
        print(f"wrote {dest}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="graftlint", description=__doc__.splitlines()[0])
    ap.add_argument("--root", default=_REPO,
                    help="repo root to lint (default: this checkout)")
    ap.add_argument("--package", default="distributedtensorflowexample_tpu")
    ap.add_argument("--front", choices=("src", "hlo", "all"),
                    default="all",
                    help="src = AST rules only (fast, no jax); hlo = "
                         "compile the mode suite and check contracts; "
                         "all = both (default)")
    ap.add_argument("--json", dest="json_out", default="", metavar="PATH",
                    help="write the JSON report here ('-' = stdout)")
    ap.add_argument("--md", dest="md_out", default="", metavar="PATH",
                    help="write the markdown report here ('-' = stdout; "
                         "default when no --json/--md given)")
    ap.add_argument("--fix", action="store_true",
                    help="apply the mechanical fixes (env-registry "
                         "stubs, keep-in-sync digest re-stamp), then "
                         "re-lint")
    ap.add_argument("--waivers", default="",
                    help="waiver file (default: "
                         "<root>/<package>/analysis/waivers.json)")
    ap.add_argument("--bucket_bytes", type=int, default=16 << 10,
                    help="bucket cap for the HLO mode suite (default "
                         "16 KiB: softmax splits into a real 2-bucket "
                         "ladder)")
    args = ap.parse_args(argv)

    root = os.path.abspath(args.root)
    fixes: list[str] = []
    if args.fix:
        if args.front == "hlo":
            # --fix only mends source-front rules; mutating source
            # files under a front that will not re-lint them would
            # leave the "--fix output re-lints clean" contract
            # unverified (and edit files the user scoped out).
            print("graftlint: --fix applies to source rules only; "
                  "ignored under --front hlo", file=sys.stderr)
        else:
            fixes = src_lint.apply_fixes(root, args.package)

    findings: list[Finding] = []
    ran_rules: set[str] = {"waiver-invalid", "waiver-budget",
                           "waiver-stale"}
    if args.front in ("src", "all"):
        findings += src_lint.run_src_lint(root, args.package)
        ran_rules |= set(src_lint.SRC_RULES)
    if args.front in ("hlo", "all"):
        # _run_hlo_front pins the CPU backend BEFORE importing
        # hlo_lint (which pulls jax via utils/profiling) — keep this
        # ordering: the import must not precede the pin.
        findings += _run_hlo_front(args.bucket_bytes)
        from distributedtensorflowexample_tpu.analysis import hlo_lint
        ran_rules |= set(hlo_lint.HLO_RULES)

    wpath = args.waivers or waivers_path(root, args.package)
    waivers, waiver_findings = load_waivers(wpath)
    unwaived, waived, stale = apply_waivers(
        findings, waivers, ran_rules,
        waiver_file=os.path.relpath(wpath, root))
    unwaived += waiver_findings     # stale waivers gate too, rendered
                                    # as their own table below
    payload = {
        "ok": not (unwaived or stale),
        "front": args.front,
        "unwaived": [f.as_dict() for f in unwaived + stale],
        "waived": [f.as_dict() for f in waived],
        "fixes": fixes,
    }
    if args.json_out:
        _emit(json.dumps(payload, indent=1, sort_keys=True) + "\n",
              args.json_out)
    if args.md_out or not args.json_out:
        _emit(_render_md(unwaived, waived, stale, fixes),
              args.md_out or "-")
    return 0 if not (unwaived or stale) else 1


def _cli() -> int:
    """Exit-code contract: 0 clean, 1 unwaived findings, 2 internal
    error (a crash in the linter/compile suite must never read as
    'findings' to a CI gate)."""
    try:
        return main()
    except SystemExit:
        raise
    except Exception:
        import traceback
        traceback.print_exc()
        return 2


if __name__ == "__main__":
    sys.exit(_cli())
