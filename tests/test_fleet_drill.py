"""End-to-end gang drills (the ISSUE's ACCEPTANCE criterion): a 2-rank
sync mnist_cnn fleet where a rank-targeted FaultPlan kills one rank
mid-run — gang teardown, resume-step agreement, gang restart — and the
resumed params/opt-state/loss-tape are BITWISE-equal to an
uninterrupted run, with per-rank flights + the fleet journal
cross-checking the restart count and the agreed step.

Each rank is a real OS process running tools/faultline.py (a fresh jax
import per child), so this file is the suite's longest (~150 s).
"""

import glob
import json
import os
import sys

import pytest

from distributedtensorflowexample_tpu.obs import anomaly as obs_anomaly
from distributedtensorflowexample_tpu.obs import timeline as obs_timeline
from distributedtensorflowexample_tpu.resilience.fleet import FleetSupervisor
from distributedtensorflowexample_tpu.resilience.supervisor import (
    Journal, RetryPolicy)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FAULTLINE = os.path.join(REPO, "tools", "faultline.py")

pytestmark = [pytest.mark.fleet, pytest.mark.faults]


def _straight_run(capsys, workdir: str, steps: int) -> dict:
    """The uninterrupted reference, in-process (shares the warm jit
    cache): same model/seed/steps, no faults."""
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import faultline
    finally:
        sys.path.pop(0)
    rc = faultline.main(["--plan", "none", "--steps", str(steps),
                         "--model", "mnist_cnn", "--workdir", workdir,
                         "--keep", "10", "--seed", "0"])
    out = [l for l in capsys.readouterr().out.splitlines() if l.strip()]
    assert rc == 0
    return json.loads(out[-1])


def _rank_argv(base, plan: str, steps: int) -> list[str]:
    return [sys.executable, FAULTLINE, "--plan", plan,
            "--steps", str(steps), "--model", "mnist_cnn",
            "--workdir", os.path.join(str(base), "rank{rank}"),
            "--keep", "10", "--seed", "0"]


def _journal_events(path: str) -> list[dict]:
    with open(path) as f:
        return [json.loads(line) for line in f]


def _last_json(path: str) -> dict:
    with open(path) as f:
        lines = [l for l in f.read().splitlines() if l.strip()]
    return json.loads(lines[-1])


def test_acceptance_rank_kill_gang_restart_bitwise(tmp_path, capsys):
    """ACCEPTANCE: rank 1 SIGKILLed at step 4 by `kill@4%1` (no save, no
    exit hooks — a lost host, not a preemption).  The fleet tears down
    rank 0 (which saves cooperatively under TERM), agrees on the max
    common valid step, discards rank 0's divergent newer snapshots,
    restarts the gang with FLEET_RESUME_STEP exported — and every
    rank's final digest and loss tape reproduce the uninterrupted run
    exactly."""
    steps = 8
    journal_path = str(tmp_path / "fleet.jsonl")
    flight_dir = str(tmp_path / "flight")
    fleet = FleetSupervisor(
        2, policy=RetryPolicy(retries=2, backoff_base_s=0.01,
                              backoff_max_s=0.02),
        journal=Journal(journal_path),
        kill_grace_s=30.0,          # must cover rank 0's save-on-TERM
        poll_s=0.1, seed=0, workdir=str(tmp_path / "fleet"))
    res = fleet.run(
        _rank_argv(tmp_path, "kill@4%1", steps), name="drill",
        snapshot_dir_template=os.path.join(str(tmp_path), "rank{rank}",
                                           "snapshots"),
        stdout_dir=str(tmp_path / "out"),
        env_extra={"OBS_DIR": flight_dir})
    assert res.status == "ok", res.reasons
    assert res.gang_attempts == 2 and res.restarts == 1
    assert res.last_rcs == {0: 0, 1: 0}

    # the agreement: rank 1 died at 4 with step 4 already snapshotted
    # (SnapshotHook runs before FaultInjectionHook), rank 0 was torn
    # down somewhere >= its own last save — agreed step is what the
    # journal says, and it is a real mid-run step
    events = _journal_events(journal_path)
    agree = next(e for e in events if e["event"] == "resume_agreement")
    agreed = agree["agreed"]
    assert 1 <= agreed <= 4, agree
    assert res.agreed_steps == [agreed]
    assert max(agree["per_rank"]["1"]) == 4     # rank 1's last save
    # rank 1's SIGKILL death is journaled with its signal rc; when rank
    # 0 was still mid-run (the usual case) the whole gang was torn down
    # — but mnist_cnn steps are sub-millisecond post-compile, so rank 0
    # finishing all 8 inside one poll window is a legal race too.
    assert any(e["event"] == "rank_exit" and e.get("rank") == 1
               and e.get("rc") == -9 for e in events)
    for tear in (e for e in events if e["event"] == "gang_teardown"):
        assert tear["why"] == "rank_crash" and tear["rank"] == 1

    straight = _straight_run(capsys, str(tmp_path / "straight"), steps)

    for rank in (0, 1):
        final = _last_json(
            str(tmp_path / "out" / f"rank{rank}_attempt1.out"))
        assert final["status"] == "ok" and final["step"] == steps
        assert final["start_step"] == agreed      # resumed the AGREED step
        # bitwise: every state leaf (params, opt state, rng, step)
        assert final["digest"] == straight["digest"], f"rank {rank}"
        # loss tape: the resumed tape is exactly the straight tape's
        # suffix past the agreed step
        assert final["losses"] == straight["losses"][agreed:], f"rank {rank}"
    # rank 0's first attempt ran PAST the kill (torn down mid-run ->
    # "preempted", or finished inside the poll window -> "ok"); either
    # way its emitted tape is a bitwise prefix of the straight tape —
    # the overlap with the redone steps reproduces exactly
    first0 = _last_json(str(tmp_path / "out" / "rank0_attempt0.out"))
    assert first0["status"] in ("preempted", "ok")
    n = len(first0["losses"])
    assert n >= 1 and first0["losses"] == straight["losses"][:n]

    # per-rank flights (flight_<rank>_<pid>.json): every rank left at
    # least one postmortem whose attempt/rank fields line up with the
    # journal's two gang attempts
    for rank in (0, 1):
        flights = [json.load(open(p)) for p in
                   glob.glob(os.path.join(flight_dir,
                                          f"flight_{rank}_*.json"))]
        assert flights, f"rank {rank} left no flight"
        assert {f["rank"] for f in flights} == {rank}
        assert max(f["attempt"] for f in flights) == 1
    # rank 0's attempt-0 flight documents how that attempt ended
    # ("preempted" when torn down mid-run, "exit" when it finished)
    r0_reasons = {f["attempt"]: f["reason"] for f in
                  (json.load(open(p)) for p in
                   glob.glob(os.path.join(flight_dir, "flight_0_*.json")))}
    assert r0_reasons.get(0) in ("preempted", "exit")


def test_wedged_rank_heartbeat_drill_restarts_bitwise(tmp_path, capsys):
    """'wedge rank 0's heartbeat': rank 0 blocks in-dispatch at step 3
    (beats stop, process lives) while rank 1 races ahead; the per-rank
    watchdog tears the gang down, the agreement rolls rank 1 BACK to
    rank 0's last provable step (discarding rank 1's newer snapshots),
    and the restarted gang still lands bitwise on the straight run."""
    steps = 6
    journal_path = str(tmp_path / "fleet.jsonl")
    fleet = FleetSupervisor(
        2, policy=RetryPolicy(retries=2, backoff_base_s=0.01,
                              backoff_max_s=0.02),
        journal=Journal(journal_path),
        # The timeout must comfortably exceed the child's jax compile
        # (the stretch between the arming first beat and the first
        # boundary beat — several seconds here, tens under suite load):
        # a tight edge kills HEALTHY ranks mid-compile, which is
        # exactly the supervisor's beat-vs-wall lesson.  The wedge arg
        # (240 s) must in turn exceed timeout+grace so the watchdog,
        # not the sleep running out, is what ends the attempt.
        heartbeat_timeout_s=60.0,
        # the wedged rank sleeps through TERM (PEP 475 resumes the
        # sleep), so the grace only delays its SIGKILL — keep it short;
        # rank 1 is long finished by the time the watchdog fires
        kill_grace_s=6.0,
        poll_s=0.1, seed=0, workdir=str(tmp_path / "fleet"))
    res = fleet.run(
        _rank_argv(tmp_path, "wedge@3:240%0", steps), name="wedge_drill",
        snapshot_dir_template=os.path.join(str(tmp_path), "rank{rank}",
                                           "snapshots"),
        stdout_dir=str(tmp_path / "out"))
    assert res.status == "ok", res.reasons
    assert res.gang_attempts == 2 and res.restarts == 1
    tear = next(e for e in _journal_events(journal_path)
                if e["event"] == "gang_teardown")
    assert tear["why"] == "rank_heartbeat" and tear["rank"] == 0
    agree = next(e for e in _journal_events(journal_path)
                 if e["event"] == "resume_agreement")
    agreed = agree["agreed"]
    # 0 is legal: rank 1 TERM'd before its first completed step has
    # nothing valid, and the agreement degrades to a full fresh start
    assert 0 <= agreed <= 3

    straight = _straight_run(capsys, str(tmp_path / "straight"), steps)
    for rank in (0, 1):
        final = _last_json(
            str(tmp_path / "out" / f"rank{rank}_attempt1.out"))
        assert final["status"] == "ok" and final["step"] == steps
        assert final["start_step"] == agreed
        assert final["digest"] == straight["digest"], f"rank {rank}"
        assert final["losses"] == straight["losses"][agreed:], f"rank {rank}"


@pytest.mark.timeline
def test_acceptance_slow_rank_straggler_named_and_timeline_skew(tmp_path):
    """ACCEPTANCE (round 10): a 2-rank mnist_cnn fleet where a
    rank-targeted `slow_rank` fault turns rank 1 into a persistent
    straggler mid-run — no crash, no restart.  The online detectors
    must (a) fire rank 1's step-time regression within 3 steps of
    injection (its baseline is pinned over its OWN healthy warmup; the
    injection boundary's delay lands in the NEXT window sample), (b)
    name rank 1 — and only rank 1 — a straggler in the fleet
    health.json and journal, with lag evidence, and (c) leave flights
    whose merged timeline makes the skew visible: rank 1's
    post-injection steps are seconds wide where rank 0's stay sub-
    second, in a Perfetto trace carrying both rank lanes.

    The injected delay (3 s) and the OBS_ANOMALY_* drill knobs are
    scaled to THIS box: two contending jax processes step mnist_cnn in
    ~0.1-0.6 s with heavy scheduler jitter (measured while building
    round 10), so the live criterion's 0.25 s — 100x a TPU step — is
    inside CPU noise here.  The detector math is pinned in
    tests/test_obs.py; this drill pins the end-to-end wiring."""
    steps = 12
    inject = 8
    workdir = str(tmp_path / "fleet")
    journal_path = os.path.join(workdir, "fleet.jsonl")
    flight_dir = os.path.join(workdir, "flight")
    os.makedirs(workdir, exist_ok=True)
    fleet = FleetSupervisor(
        2, policy=RetryPolicy(retries=0, backoff_base_s=0.01,
                              backoff_max_s=0.02),
        journal=Journal(journal_path),
        kill_grace_s=30.0, poll_s=0.1, seed=0, workdir=workdir)
    argv = _rank_argv(tmp_path, f"slow_rank@{inject}:3.0%1", steps)
    argv += ["--snapshot_every", "100"]     # no snapshot noise in windows
    res = fleet.run(
        argv, name="straggler_drill",
        stdout_dir=str(tmp_path / "out"),
        # skip=2 drops the compile-dominated boundaries, warmup=3 pins
        # the baseline over boundaries 3-5 (steady state, before the
        # step-8 injection), z=5 clears contended-CPU sigma with the
        # 3 s delta in <= 2 slowed windows.  Production keeps the env
        # defaults (skip 1, warmup 16, z 8).
        env_extra={"OBS_DIR": flight_dir, "OBS_ANOMALY_WARMUP": "3",
                   "OBS_ANOMALY_SKIP": "2", "OBS_ANOMALY_Z": "5"})
    assert res.status == "ok", res.reasons
    assert res.gang_attempts == 1 and res.restarts == 0   # detection ONLY
    assert res.last_rcs == {0: 0, 1: 0}

    # (a) rank 1's own health.json: regression fired within <= 3 steps
    # of the injection (the delay at boundary `inject` lands in the
    # window ENDING at inject+1 — FaultInjectionHook runs last)
    h1 = obs_anomaly.read_health(os.path.join(workdir,
                                              "health_rank1.json"))
    reg = h1["flags"]["step_time_regression"]
    assert reg["fired_step"] is not None, h1["detectors"]["step_time"]
    assert inject + 1 <= reg["fired_step"] <= inject + 3, reg
    # rank 0's health reported too (a spurious regression there is
    # tolerated — one scheduler hiccup on sub-ms steps can score — but
    # it can never be named straggler: it IS the front rank)
    h0 = obs_anomaly.read_health(os.path.join(workdir,
                                              "health_rank0.json"))
    assert h0["step"] == steps

    # (b) the fleet monitor named rank 1 — journal annotation with lag
    # evidence, aggregate health.json straggler list, and only rank 1
    events = _journal_events(journal_path)
    strag = [e for e in events if e["event"] == "anomaly"
             and e["kind"] == "straggler"]
    assert [e["rank"] for e in strag] == [1]
    assert strag[0]["max_step"] - strag[0]["step"] >= 3   # real lag
    assert 4 <= strag[0]["step"] <= steps
    assert "lag" in strag[0]["why"]
    assert any(e["event"] == "anomaly" and e["rank"] == 1
               and e["kind"] == "step_time_regression" for e in events)
    fleet_health = obs_anomaly.read_health(os.path.join(workdir,
                                                        "health.json"))
    assert fleet_health["kind"] == "fleet"
    assert fleet_health["stragglers"] == [1]
    assert "1" in {str(k) for k in fleet_health["skew"]["lag_steps"]}

    # (c) merged timeline: both rank lanes present, skew visible in the
    # per-step anatomy (rank 1's slowed windows vs rank 0's), Perfetto
    # export carries both lanes + the straggler journal marker
    sources = obs_timeline.fleet_dir_sources(flight_dir=flight_dir,
                                             journal=journal_path)
    assert os.path.join(workdir, "health.json") in sources["health_paths"]
    merged = obs_timeline.merge(**sources)
    assert merged["coverage"]["ranks_present"] == [0, 1]
    assert not merged["coverage"]["unreadable"]
    anatomy = obs_timeline.step_anatomy(merged)
    slow = [r for r in anatomy
            if r["rank"] == 1 and r["step_to"] > inject]
    fast = [r for r in anatomy
            if r["rank"] == 0 and r["step_to"] > inject]
    assert slow and fast
    # every post-injection rank-1 window absorbs a 3 s boundary delay;
    # rank 0's contended-CPU windows stay well under half of that
    assert all(r["window_s"] >= 1.5 for r in slow), slow
    assert all(r["window_s"] < 1.5 for r in fast), fast
    trace = obs_timeline.chrome_trace(merged)
    lanes = {e["pid"] for e in trace["traceEvents"] if e.get("ph") == "X"}
    assert {0, 1} <= lanes
    assert any(e.get("ph") == "i" and e.get("name") == "anomaly"
               for e in trace["traceEvents"])
