#!/usr/bin/env python
"""Run any entrypoint — or the whole on-chip capture sequence — under the
resilience supervisor (heartbeat watchdog, jittered backoff, bounded
retries, journaled resume).

Two modes:

  # one supervised command (trainer, bench, anything):
  python tools/supervise.py --retries 5 --heartbeat_timeout_s 600 \
      -- python -m distributedtensorflowexample_tpu.trainers.trainer_sync_mnist \
         --dataset synthetic --train_steps 5000
  # exit code mirrors the child's final verdict (0 ok, 3 wedged, else rc)

  # the 4-phase capture window (the supervised replacement for
  # tools/bench_capture.sh's inline bash phases — same artifact-value
  # order, same env knobs, same keep() semantics), journaled so a second
  # recovery window resumes exactly where the first died:
  python tools/supervise.py --capture

Capture mode honors bench_capture.sh's env surface (OUT, OUT_HEADLINE,
PROFILE_OUT, BYTES_OUT, COLLECTIVES_OUT, LM_OUT, TRACE_TGZ, CLI_OUT,
TRACE_DIR, LOG, CAPTURE_PIDFILE, BENCH_RETRY_BUDGET_S, BYTES_ARGS —
the graftlint keep-in-sync digest pins the two phase tables to each
other) and writes the SAME
pidfile, so tools/tpu_watch.sh's liveness/stale-kill machinery sees a
supervised capture exactly like a bash one.  The journal
(SUPERVISE_JOURNAL, default alongside the log) is what the bash path
never had: phases already recorded done are skipped on relaunch, and a
wedge verdict (rc=3) persists across supervisor restarts so chip-bound
phases stay skipped while the CPU-only bytes audit still lands.

Either mode: exporting OBS_PROM_DIR makes every completed task refresh
<OBS_PROM_DIR>/supervise.prom (node-exporter textfile-collector
dialect) with the live attempt/kill/heartbeat counters.  For N-process
gangs, see tools/supervise_fleet.py.
"""

from __future__ import annotations

import argparse
import atexit
import glob
import os
import shutil
import subprocess
import sys
import tempfile
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

from distributedtensorflowexample_tpu.obs import recorder as obs_recorder  # noqa: E402
from distributedtensorflowexample_tpu.resilience.supervisor import (  # noqa: E402
    Journal, RetryPolicy, Supervisor, Task, TaskQueue)


def _write_pidfile(path: str) -> None:
    """bench_capture.sh's pidfile contract: the watcher reads it for
    liveness, and the EXIT cleanup removes it only if still ours."""
    with open(path, "w") as f:
        f.write(str(os.getpid()))

    def _cleanup():
        try:
            with open(path) as f:
                mine = f.read().strip() == str(os.getpid())
        except OSError:
            return
        if mine:
            os.remove(path)

    atexit.register(_cleanup)


def _capture_tasks(start_ts: float,
                   full_bench_done_prior: bool = False) -> list[Task]:
    # Mirrored in tools/bench_capture.sh (the flagged bash fallback):
    # phase set, artifact filenames, env knobs, gate strings.  Any
    # phase change must land in BOTH until the bash path is retired —
    # enforced by graftlint's keep-in-sync rule (the digest below
    # covers both regions; `python -m tools.graftlint --fix` re-stamps
    # after a deliberate re-sync).  tests/test_resilience.py::
    # test_supervise_capture_queue_shape pins this queue's shape.
    # KEEP-IN-SYNC(capture-phases) digest=705886ff9619
    env = os.environ
    py = sys.executable
    log = env.get("LOG", "/tmp/bench_capture.log")
    out = env.get("OUT", "BENCH_auto_r05.json")
    out_headline = env.get("OUT_HEADLINE", "BENCH_headline_r05.json")
    profile_out = env.get("PROFILE_OUT", "PROFILE_auto_r05.json")
    bytes_out = env.get("BYTES_OUT", "BYTES_AUDIT_r05.json")
    collectives_out = env.get("COLLECTIVES_OUT", "BENCH_collectives_r06.json")
    lm_out = env.get("LM_OUT", "BENCH_lm_r08.json")
    trace_tgz = env.get("TRACE_TGZ", "resnet_trace_r05.tgz")
    cli_out = env.get("CLI_OUT", "CLI_r05.log")
    trace_dir = env.get("TRACE_DIR", "/tmp/resnet_trace")
    # Detached capture: the full retry budget is affordable here (the
    # 900-s default exists for the DRIVER's ~23-25-min kill window).
    retry_budget = env.get("BENCH_RETRY_BUDGET_S", "2400")
    bench_env = {"BENCH_RETRY_BUDGET_S": retry_budget}
    bytes_args = env.get("BYTES_ARGS",
                         "--batch_per_chip 256 --unroll 1").split()

    def tar_trace() -> None:
        if not os.path.isdir(trace_dir):
            return
        size_mb = sum(
            os.path.getsize(os.path.join(r, f))
            for r, _, fs in os.walk(trace_dir) for f in fs) // 2**20
        if size_mb <= 25:
            subprocess.run(["tar", "czf", trace_tgz,
                            "-C", os.path.dirname(trace_dir),
                            os.path.basename(trace_dir)], check=False)

    def keep_json(tmp: str, final: str):
        """keep() semantics for --json artifacts: promote a non-empty
        tmp, drop an empty one (a killed attempt never clobbers a
        previous window's artifact)."""
        def _keep() -> None:
            if os.path.exists(tmp):
                if os.path.getsize(tmp):
                    os.replace(tmp, final)
                else:
                    os.remove(tmp)
        return _keep

    keep_bytes_json = keep_json(bytes_out + ".tmp", bytes_out)
    keep_collectives_json = keep_json(collectives_out + ".tmp",
                                      collectives_out)
    keep_lm_json = keep_json(lm_out + ".tmp", lm_out)

    def fresh_measured() -> bool:
        """Phase-4 gate from bench_capture.sh: the trainer has no
        probe/watchdog layer, so it only runs once a full bench this
        CAPTURE produced a measured line (not a leftover file, not a
        sentinel).  'This capture' is the journal's notion, not this
        process's: on a resumed window full_bench is skipped as
        done_prior and OUT's mtime predates start_ts, yet it IS this
        capture's artifact — the journaled completion is exactly the
        provenance the bash mtime check could only approximate."""
        try:
            if (os.path.getmtime(out) < start_ts
                    and not full_bench_done_prior):
                return False
            with open(out) as f:
                return '"unit": "steps/sec/chip"' in f.read()
        except OSError:
            return False

    def rm_trace_dir() -> None:
        # A stale trace from an earlier run must not get tarred as THIS
        # window's artifact.
        subprocess.run(["rm", "-rf", trace_dir], check=False)

    return [
        # phase 1: the contract metric, fastest possible — a ~9-minute
        # window must convert the headline before anything else.
        Task("headline_bench", [py, "bench.py"], priority=10,
             stdout_path=out_headline, stderr_path=log,
             env={**bench_env, "BENCH_HEADLINE_ONLY": "1"}),
        # phase 2: ResNet attribution + trace (never yet landed on chip).
        Task("profile", [py, "bench_profile.py", "--trace_dir", trace_dir],
             priority=20, stdout_path=profile_out, stderr_path=log,
             pre=rm_trace_dir,
             env=bench_env, post=tar_trace),
        # phase 2b: CPU bytes table — needs_chip=False is what keeps it
        # alive through a wedge verdict (the one artifact a dead chip
        # can't block).
        Task("bytes_audit_cpu",
             [py, "tools/bytes_audit.py", "--backend", "cpu",
              "--workload", "resnet20", *bytes_args,
              "--json", bytes_out + ".tmp"],
             priority=25, needs_chip=False, stderr_path=log,
             post=keep_bytes_json),
        # phase 2c: collective latency/bandwidth curves + knee re-fit on
        # the live backend (bench_collectives.py --real).  Probes with
        # bench.py's env knobs and emits a sentinel record when the
        # backend is down, so the queue keeps moving; with the shell
        # profile's JAX_PLATFORMS=cpu export still in force the record
        # self-labels platform=cpu (never mistakable for chip curves).
        Task("collectives",
             [py, "bench_collectives.py", "--real",
              "--json", collectives_out + ".tmp"],
             priority=27, stderr_path=log,
             env=bench_env, post=keep_collectives_json),
        # phase 2d: the graft-LM family (bench_lm.py --real): tokens/sec
        # + MFU + the lm_base knob A/B matrix on the live backend.  Same
        # sentinel/platform-labeling discipline as 2c — probes with the
        # bench env knobs, emits a sentinel when the backend is down,
        # and under an exported JAX_PLATFORMS=cpu the record self-labels
        # platform=cpu so CPU numbers never read as chip numbers.
        Task("lm",
             [py, "bench_lm.py", "--real", "--json", lm_out + ".tmp"],
             priority=28, stderr_path=log,
             env=bench_env, post=keep_lm_json),
        # phase 3: the full six-workload record.
        Task("full_bench", [py, "bench.py"], priority=30, stdout_path=out,
             stderr_path=log, env=bench_env),
        # phase 4: out-of-box CLI throughput.  Unlike bash (which could
        # only refuse to start it), the supervisor bounds it: SIGTERM +
        # grace first — the trainer saves and exits 143 — KILL only as
        # the last resort.
        Task("cli_trainer",
             [py, "-m",
              "distributedtensorflowexample_tpu.trainers."
              "trainer_sync_mnist",
              "--dataset", "synthetic", "--train_steps", "5000",
              "--batch_size", "64", "--log_every", "1000",
              "--log_dir", "/tmp/cli_bench_r05", "--resume", "false"],
             priority=40, stdout_path=cli_out, stderr_path=log,
             wall_timeout_s=1800.0,
             gate=fresh_measured),
    ]
    # KEEP-IN-SYNC-END(capture-phases)


def _capture_ended(journal_path: str) -> bool:
    """True if the journal's capture RUN already ended (capture_end
    journaled) — the resume semantics exist for a supervisor that DIED
    mid-run, not for suppressing the next recovery window's capture."""
    try:
        with open(journal_path) as f:
            return any('"event": "capture_end"' in line for line in f)
    except OSError:
        return False


def run_capture(args) -> int:
    os.chdir(_REPO)
    pidfile = os.environ.get("CAPTURE_PIDFILE", "/tmp/bench_capture.pid")
    _write_pidfile(pidfile)
    journal_path = os.environ.get("SUPERVISE_JOURNAL",
                                  "/tmp/supervise_capture.jsonl")
    # Flight files (the supervisor's own + every phase child's) land in
    # one directory NEXT TO the journal: postmortems archived beside the
    # provenance record they cross-reference.  Children inherit OBS_DIR;
    # an operator export of OBS_DIR wins.
    obs_dir_preset = "OBS_DIR" in os.environ
    flight_dir = os.environ.setdefault(
        "OBS_DIR",
        os.path.splitext(journal_path)[0] + "_flight")
    if _capture_ended(journal_path):
        # Previous window's capture ran to its end (complete OR wedged
        # verdict): rotate it away so THIS edge captures fresh, like the
        # bash path always did — otherwise every later window replays
        # all phases as done_prior and the watcher's once-per-window
        # capture silently becomes a no-op.  The flight dir rotates WITH
        # the journal (only the default dir — an operator's OBS_DIR is
        # theirs to manage): stale postmortems must not be rendered, or
        # counted, as this window's, and PID reuse across windows could
        # even overwrite them.
        os.replace(journal_path, journal_path + ".prev")
        if not obs_dir_preset and os.path.isdir(flight_dir):
            shutil.rmtree(flight_dir + ".prev", ignore_errors=True)
            os.replace(flight_dir, flight_dir + ".prev")
        print(f"supervise: previous capture ended — journal rotated to "
              f"{journal_path}.prev (flight dir alongside)",
              file=sys.stderr, flush=True)
    os.makedirs(flight_dir, exist_ok=True)
    obs_recorder.install(sigterm=False)
    start_ts = time.time()
    journal = Journal(journal_path)
    sup = Supervisor(policy=RetryPolicy(retries=0),  # bench self-retries
                     journal=journal, kill_grace_s=30.0, seed=args.seed)
    prior_done = journal.replay()["done"]
    queue = TaskQueue(_capture_tasks(
        start_ts, full_bench_done_prior="full_bench" in prior_done), sup)
    results = queue.run()
    if "terminated" not in results.values():
        # A terminated run (watcher killed us) must NOT journal an end:
        # the next window resumes from the first unfinished phase.
        journal.write("capture_end", results=results)
    print(f"supervise: capture done: {results}", file=sys.stderr, flush=True)
    # The supervisor's own flight is written NOW (not left to atexit)
    # so the inventory line below counts every file the advertised
    # obs_report invocation will render.
    obs_recorder.dump_global("capture_end")
    flights = sorted(glob.glob(os.path.join(flight_dir, "flight_*.json")))
    print(f"supervise: {len(flights)} flight file(s) in {flight_dir} — "
          f"render with: python tools/obs_report.py --dir {flight_dir} "
          f"--journal {journal_path}", file=sys.stderr, flush=True)
    return 3 if "wedged" in results.values() else 0


def run_command(args, argv: list[str]) -> int:
    # The supervisor's own flight (attempt counters, heartbeat-age
    # gauge, escalation reason) — written on watchdog kills and exit.
    obs_recorder.install(sigterm=False)
    sup = Supervisor(
        policy=RetryPolicy(retries=args.retries,
                           backoff_base_s=args.backoff_base_s,
                           backoff_max_s=args.backoff_max_s),
        journal=Journal(args.journal),
        heartbeat_timeout_s=args.heartbeat_timeout_s,
        wall_timeout_s=args.timeout_s,
        kill_grace_s=args.kill_grace_s,
        seed=args.seed)
    res = sup.run(argv, name=args.name, stdout_path=args.stdout,
                  heartbeat_path=args.heartbeat)
    if res.status == "ok":
        return 0
    if res.status == "terminated":
        # We were SIGTERM'd and forwarded it (child group killed with
        # grace): report 143 so a wrapper honoring the 0/143/3 protocol
        # sees a clean termination, not a crash to backoff-retry.
        return 143
    return res.returncode if res.returncode is not None else 1


def main(argv: list[str] | None = None) -> int:
    argv = sys.argv[1:] if argv is None else list(argv)
    child: list[str] = []
    if "--" in argv:
        split = argv.index("--")
        argv, child = argv[:split], argv[split + 1:]
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--capture", action="store_true",
                   help="run the journaled 4-phase capture queue "
                        "(bench_capture.sh's supervised replacement)")
    p.add_argument("--retries", type=int, default=3)
    p.add_argument("--backoff_base_s", type=float, default=1.0)
    p.add_argument("--backoff_max_s", type=float, default=60.0)
    p.add_argument("--timeout_s", type=float, default=0.0,
                   help="wall deadline per attempt (0 = none)")
    p.add_argument("--heartbeat_timeout_s", type=float, default=0.0,
                   help="kill when the heartbeat file goes stale this "
                        "long (0 = no heartbeat watchdog)")
    p.add_argument("--heartbeat", default="",
                   help="heartbeat file path (exported to the child as "
                        "SUPERVISE_HEARTBEAT; trainers touch it at step "
                        "boundaries)")
    p.add_argument("--kill_grace_s", type=float, default=10.0,
                   help="SIGTERM-to-SIGKILL grace (covers the child's "
                        "save-on-exit)")
    p.add_argument("--journal", default="", help="JSON-lines journal path")
    p.add_argument("--stdout", default="",
                   help="child stdout file (keep() semantics: an empty "
                        "attempt never clobbers a previous one)")
    p.add_argument("--name", default="", help="task name for the journal")
    p.add_argument("--seed", type=int, default=None,
                   help="backoff-jitter seed (tests)")
    args = p.parse_args(argv)
    args.journal = args.journal or None
    args.stdout = args.stdout or None
    args.heartbeat = args.heartbeat or None
    if args.heartbeat_timeout_s and not args.heartbeat:
        # The advertised one-liner passes only the timeout; without a
        # derived path the watchdog would silently arm against NOTHING
        # (no SUPERVISE_HEARTBEAT exported, no beats, no kills) — the
        # flagship protection reduced to a no-op.
        args.heartbeat = os.path.join(
            tempfile.gettempdir(), f"supervise_hb_{os.getpid()}")
        print(f"supervise: heartbeat file defaulted to {args.heartbeat}",
              file=sys.stderr, flush=True)

    if args.capture:
        return run_capture(args)
    if not child:
        p.error("nothing to run: pass --capture, or -- CMD ARGS...")
    return run_command(args, child)


if __name__ == "__main__":
    raise SystemExit(main())
