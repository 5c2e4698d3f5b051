"""End-to-end scheduler ACCEPTANCE drill (the ISSUE's criterion): a
mixed faultline queue on the forced CPU mesh where (a) a rank's HOST is
lost mid-queue (the host_loss fault — tombstone + SIGKILL, respawn
fails like a dead host, the elastic gang shrinks and completes), and
(b) a higher-priority serving job EVICTS a running bench job through
the TERM→143→snapshot protocol — and the victim's resumed digest and
loss tape are BITWISE-equal to an uninterrupted run (zero lost steps),
with every decision answerable afterwards from ledger rows alone
(``obs_query why``).

Each job rank is a real OS process running tools/faultline.py (a fresh
jax import per child).
"""

import glob
import json
import os
import sys

import pytest

from distributedtensorflowexample_tpu.resilience.scheduler import (
    Job, Scheduler)
from distributedtensorflowexample_tpu.resilience.supervisor import (
    RetryPolicy)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FAULTLINE = os.path.join(REPO, "tools", "faultline.py")

pytestmark = [pytest.mark.sched, pytest.mark.faults]


def _faultline_job(base, job, plan, steps, **kw):
    jdir = os.path.join(str(base), "jobs", job)
    spec = {
        "job": job,
        "argv": [sys.executable, FAULTLINE, "--plan", plan,
                 "--steps", str(steps), "--model", "softmax",
                 "--workdir", os.path.join(jdir, "rank{rank}"),
                 "--keep", "20", "--seed", "0"],
        "snapshots": os.path.join(jdir, "rank{rank}", "snapshots"),
        "steps": steps, "est_step_time_s": 1.0,
        # generous: TERM lands mid-slow-step sleep, and the save +
        # emit must complete under suite-level CPU contention
        "kill_grace_s": 30.0,
        # explicit: a fresh jax import + compile under suite load can
        # dwarf any cost-derived deadline for these tiny step counts —
        # the deadline knob is exercised in tests/test_scheduler.py
        "wall_timeout_s": 600.0}
    spec.update(kw)
    return Job.from_dict(spec)


def _straight_run(capsys, workdir: str, steps: int) -> dict:
    """The uninterrupted reference, in-process (shares the warm jit
    cache): same model/seed/steps, no faults, no delays — boundary
    sleeps never change the math, so the digests must match bitwise."""
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import faultline
    finally:
        sys.path.pop(0)
    rc = faultline.main(["--plan", "none", "--steps", str(steps),
                         "--model", "softmax", "--workdir", workdir,
                         "--keep", "20", "--seed", "0"])
    out = [l for l in capsys.readouterr().out.splitlines() if l.strip()]
    assert rc == 0
    return json.loads(out[-1])


def _outs(base, job):
    """All JSON tails a job's placements left, placement order."""
    recs = []
    for path in sorted(glob.glob(os.path.join(
            str(base), "sched", "jobs", job, "out", "place*", "*.out"))):
        with open(path) as f:
            lines = [l for l in f.read().splitlines() if l.strip()]
        if lines:
            recs.append((path, json.loads(lines[-1])))
    return recs


def test_acceptance_mixed_queue_host_loss_and_slo_eviction(tmp_path,
                                                           capsys):
    steps = 12
    wd = str(tmp_path / "sched")
    ledger = os.path.join(wd, "RUNS.jsonl")
    jobs = [
        # (a) rank 1's host dies at step 3 (down "forever" — arg 0):
        # crash teardown, respawn fails on the tombstone, elastic
        # shrink, the survivor resumes from the agreement and finishes.
        _faultline_job(tmp_path, "ktrain", "host_loss@3%1", steps,
                       ranks=2, kind="train", elastic=True,
                       fleet_retries=4),
        # (b) the victim: slow_rank paces it (~0.4 s/step) so the
        # serving job's arrival finds it mid-run; snapshots land every
        # step, so the eviction is loss-free by construction.
        _faultline_job(tmp_path, "bench1", "slow_rank@1:0.4", steps,
                       kind="bench"),
        # priority 0, needs the whole mesh, ready the moment bench1's
        # step-3 snapshot commits (no wall-clock guessing).
        _faultline_job(tmp_path, "serve1", "none", 4, ranks=2,
                       kind="serve",
                       after_file=os.path.join(
                           str(tmp_path), "jobs", "bench1", "rank0",
                           "snapshots", "snap_00000003.npz")),
        _faultline_job(tmp_path, "t1", "none", 4, kind="train"),
    ]
    sched = Scheduler(
        jobs, devices=2, workdir=wd, tick_s=0.1, poll_s=0.05, seed=0,
        retry_policy=RetryPolicy(retries=10**6, backoff_base_s=0.1,
                                 backoff_max_s=0.5))
    summary = sched.run()
    assert summary["jobs"] == {"ktrain": "done", "bench1": "done",
                               "serve1": "done", "t1": "done"}, summary
    assert summary["status"] == "ok"
    assert summary["evictions"] >= 1 and summary["shrinks"] >= 1

    rows = [json.loads(l) for l in open(ledger) if l.strip()]
    sched_rows = [r for r in rows
                  if str(r.get("event", "")).startswith("sched_")]

    # (a) the host loss shrank ktrain's gang — and it still finished
    shrink = [r for r in sched_rows if r["event"] == "sched_shrink"
              and r["job"] == "ktrain"]
    assert shrink and shrink[0]["lost"] == [1]
    k_outs = [rec for _, rec in _outs(tmp_path, "ktrain")]
    finals = [r for r in k_outs if r["status"] == "ok"
              and r["step"] == steps]
    assert finals, k_outs
    straight = _straight_run(capsys, str(tmp_path / "straight"), steps)
    # the surviving rank's timeline is bitwise the straight run's
    assert all(r["digest"] == straight["digest"] for r in finals)

    # (b) bench1 was evicted for serve1, TERM→143 with a snapshot...
    evict = [r for r in sched_rows if r["event"] == "sched_evict"
             and r["job"] == "bench1"]
    assert len(evict) == 1
    assert evict[0]["for_job"] == "serve1" and evict[0]["clean"] is True
    # ...and the resumed run is BITWISE the uninterrupted run: final
    # digest equal, and the concatenated loss tape equal — zero lost
    # steps, zero recomputed steps.
    b_outs = _outs(tmp_path, "bench1")
    assert len(b_outs) >= 2, b_outs
    preempted = b_outs[0][1]
    final = b_outs[-1][1]
    assert preempted["status"] == "preempted"
    assert final["status"] == "ok" and final["step"] == steps
    assert final["start_step"] == preempted["step"]     # resumed THERE
    assert final["digest"] == straight["digest"]
    tape = preempted["losses"] + final["losses"]
    assert tape == straight["losses"]

    # obs_query answers "why was bench1 preempted" from the ledger alone
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import obs_query
    finally:
        sys.path.pop(0)
    rc = obs_query.main(["why", "bench1", "--ledger", ledger])
    out = capsys.readouterr().out
    assert rc == 0
    assert "EVICTED" in out and "`serve1`" in out
    assert "preempted 1x (for `serve1`)" in out
    assert "finally completed" in out
    rc = obs_query.main(["why", "ktrain", "--ledger", ledger])
    out = capsys.readouterr().out
    assert rc == 0 and "SHRINK" in out and "host down" in out
