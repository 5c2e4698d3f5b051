"""The ledger-driven control plane (resilience/scheduler.py +
tools/schedule.py): cost-priced admission, priority packing, loss-free
SLO eviction, elastic shrink/grow policy, bounded retry + quarantine,
write-ahead journal replay after a SIGKILL, and the obs_query `why`
verb that answers for every decision from ledger rows alone.

Inline on purpose: every gang child here is a stdlib-only script
(milliseconds each, no jax import), so the whole file's verdicts land
inside the tier-1 budget.  The jax-heavy end-to-end drill (faultline
jobs, bitwise eviction-resume parity) lives in tests/test_sched_drill.py.
"""

import json
import os
import subprocess
import sys
import textwrap
import time

import pytest

from distributedtensorflowexample_tpu.resilience.faults import (
    FaultInjectionHook, FaultPlan, FaultSpec, mark_host_down)
from distributedtensorflowexample_tpu.resilience.fleet import FleetSupervisor
from distributedtensorflowexample_tpu.resilience.scheduler import (
    SCHED_EVENTS, Job, Scheduler, load_queue, predict_cost,
    slo_priorities, tick_default)
from distributedtensorflowexample_tpu.resilience.supervisor import (
    Journal, RetryPolicy)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

pytestmark = pytest.mark.sched


def _sched(tmp_path, jobs, **kw):
    kw.setdefault("devices", 2)
    kw.setdefault("workdir", str(tmp_path / "sched"))
    kw.setdefault("tick_s", 0.05)
    kw.setdefault("poll_s", 0.02)
    kw.setdefault("seed", 0)
    kw.setdefault("retry_policy", RetryPolicy(retries=10**6,
                                              backoff_base_s=0.05,
                                              backoff_max_s=0.1))
    return Scheduler(jobs, **kw)


def _ledger_rows(tmp_path) -> list[dict]:
    with open(tmp_path / "sched" / "RUNS.jsonl") as f:
        return [json.loads(line) for line in f if line.strip()]


def _sched_rows(tmp_path, job=None, event=None) -> list[dict]:
    rows = [r for r in _ledger_rows(tmp_path)
            if str(r.get("event", "")).startswith("sched_")]
    if job is not None:
        rows = [r for r in rows if r.get("job") == job]
    if event is not None:
        rows = [r for r in rows if r.get("event") == event]
    return rows


def _script(tmp_path, name, body) -> str:
    path = tmp_path / name
    path.write_text(textwrap.dedent(body))
    return str(path)


# ---- job description + env knobs ----------------------------------------

def test_job_validation_is_loud(tmp_path):
    with pytest.raises(ValueError, match="unknown field"):
        Job.from_dict({"job": "a", "argv": ["x"], "prioritee": 1})
    with pytest.raises(ValueError, match="ranks"):
        Job(job="a", argv=["x"], ranks=0)
    with pytest.raises(ValueError, match="path-safe"):
        Job(job="a/b", argv=["x"])
    with pytest.raises(ValueError, match="path-safe"):
        Job(job="..", argv=["x"])    # must not escape the jobs/ dir
    with pytest.raises(ValueError, match="duplicate"):
        _sched(tmp_path, [Job(job="a", argv=["x"]),
                          Job(job="a", argv=["y"])])


def test_load_queue_accepts_both_shapes(tmp_path):
    path = tmp_path / "q.json"
    path.write_text(json.dumps([{"job": "a", "argv": ["x"]}]))
    assert [j.job for j in load_queue(str(path))] == ["a"]
    path.write_text(json.dumps({"jobs": [{"job": "b", "argv": ["x"]}]}))
    assert [j.job for j in load_queue(str(path))] == ["b"]


def test_slo_priorities_env_override(monkeypatch):
    monkeypatch.delenv("SCHED_SLO_PRIORITIES", raising=False)
    base = slo_priorities()
    assert base["serve"] < base["train"] < base["bench"] < base["drill"]
    monkeypatch.setenv("SCHED_SLO_PRIORITIES", "bench=5, custom=1, bad")
    out = slo_priorities()
    assert out["bench"] == 5 and out["custom"] == 1
    assert out["serve"] == base["serve"]        # defaults survive
    # a job's explicit priority beats the kind table
    assert Job(job="a", argv=["x"], kind="bench",
               priority=2).resolved_priority(out) == 2
    assert Job(job="b", argv=["x"], kind="bench").resolved_priority(out) == 5


def test_tick_env_knob(monkeypatch):
    monkeypatch.delenv("SCHED_TICK_S", raising=False)
    assert tick_default() == 0.25
    monkeypatch.setenv("SCHED_TICK_S", "0.5")
    assert tick_default() == 0.5
    monkeypatch.setenv("SCHED_TICK_S", "bogus")
    assert tick_default() == 0.25


# ---- the cost model ------------------------------------------------------

def test_predict_cost_trajectory_then_declared(tmp_path):
    traj = tmp_path / "trajectory.json"
    traj.write_text(
        json.dumps({"family": "BENCH_lm_cpu", "round": 8,
                    "file": "BENCH_lm_cpu_r08.json",
                    "metrics": {"lm_steps_per_sec": 4.0,
                                "lm_small_steps_per_sec": 2.0}}) + "\n"
        + json.dumps({"family": "BENCH_lm_cpu", "round": 12,
                      "file": "BENCH_lm_cpu_r12.json",
                      "metrics": {"lm_steps_per_sec": 8.0}}) + "\n")
    job = Job(job="a", argv=["x"], family="lm_cpu", steps=16,
              est_step_time_s=9.0)
    cost = predict_cost(job, str(traj))
    # newest round wins, measured beats declared
    assert cost["source"] == "trajectory:BENCH_lm_cpu_r12.json"
    assert cost["step_time_s"] == pytest.approx(1 / 8.0)
    assert cost["predicted_s"] == pytest.approx(2.0)
    # conservative: the SLOWEST rate of the newest row prices the job
    old = predict_cost(Job(job="b", argv=["x"], family="lm_cpu",
                           steps=2), str(tmp_path / "nope.json"))
    assert old["source"] is None and old["predicted_s"] is None
    declared = predict_cost(job, "")
    assert declared["source"] == "declared"
    assert declared["predicted_s"] == pytest.approx(144.0)


def test_admission_refusals(tmp_path):
    """Unplaceable width and over-ceiling cost refuse at admission —
    ledger rows say why, and the queue still drains."""
    py = sys.executable
    jobs = [Job(job="wide", argv=[py, "-c", "pass"], ranks=3),
            Job(job="costly", argv=[py, "-c", "pass"],
                steps=100, est_step_time_s=10.0),
            Job(job="ok", argv=[py, "-c", "pass"])]
    summary = _sched(tmp_path, jobs, max_job_s=60.0).run()
    assert summary["jobs"] == {"wide": "refused", "costly": "refused",
                               "ok": "done"}
    refuse = {r["job"]: r for r in _sched_rows(tmp_path,
                                               event="sched_refuse")}
    assert "mesh has 2" in refuse["wide"]["why"]
    assert "exceeds the per-job ceiling" in refuse["costly"]["why"]
    assert refuse["costly"]["predicted_s"] == pytest.approx(1000.0)


# ---- the 8-job mixed-queue acceptance (stdlib children) ------------------

def _victim_script(tmp_path, iters=10, sleep=0.15):
    """A long 'bench' job with resumable progress: each loop appends one
    line and sleeps; SIGTERM = save-and-exit-143 (the 143 protocol in
    miniature).  The progress file is the zero-lost-steps witness: the
    resumed run continues at exactly the next index, so a lost or
    repeated step shows up as a gap or duplicate line."""
    return _script(tmp_path, "victim.py", f"""
        import os, signal, sys, time
        prog = os.environ["PROG"]
        def term(s, f):
            sys.exit(143)
        signal.signal(signal.SIGTERM, term)
        while True:
            n = sum(1 for _ in open(prog)) if os.path.exists(prog) else 0
            if n >= {iters}:
                sys.exit(0)
            with open(prog, "a") as f:
                f.write(f"i{{n}}\\n")
            time.sleep({sleep})
    """)


def test_mixed_queue_acceptance_evict_retry_quarantine(tmp_path):
    """The 8-job mixed queue, inline: quick trains, a crash-retry job,
    a wedged job (quarantined, not requeued), an unplaceable job
    (refused), and a slow bench job a late-ready priority-0 'serve'
    job evicts loss-free — zero manual intervention, every decision a
    ledger row, and `obs_query why` explains the eviction after the
    fact from the ledger alone."""
    py = sys.executable
    prog = str(tmp_path / "progress")
    crash_marker = str(tmp_path / "crashed_once")
    victim = _victim_script(tmp_path)
    crashy = _script(tmp_path, "crashy.py", """
        import os, sys
        m = os.environ["MARKER"]
        if not os.path.exists(m):
            open(m, "w").close()
            os.kill(os.getpid(), 9)    # hard loss on the first placement
        sys.exit(0)
    """)
    jobs = [
        Job(job="t1", argv=[py, "-c", "pass"], kind="train"),
        Job(job="t2", argv=[py, "-c", "pass"], kind="train"),
        Job(job="t3", argv=[py, "-c", "pass"], kind="train"),
        # killed mid-queue on its first placement; the scheduler's
        # bounded retry (fleet_retries=0 pushes it up a level) requeues
        # it with backoff and the second placement completes.
        Job(job="kill1", argv=[py, crashy], kind="train", retries=2,
            fleet_retries=0, env={"MARKER": crash_marker}),
        Job(job="wedge1", argv=[py, "-c", "import sys; sys.exit(3)"],
            kind="drill", retries=3),
        Job(job="wide1", argv=[py, "-c", "pass"], ranks=3, kind="train"),
        Job(job="bench1", argv=[py, victim], kind="bench",
            env={"PROG": prog}),
        # ready the moment bench1 proves mid-run progress; needs the
        # whole mesh, so admission must evict.
        Job(job="serve1", argv=[py, "-c", "pass"], kind="serve",
            ranks=2, after_file=prog),
    ]
    summary = _sched(tmp_path, jobs).run()
    assert summary["jobs"] == {
        "t1": "done", "t2": "done", "t3": "done", "kill1": "done",
        "wedge1": "quarantined", "wide1": "refused",
        "bench1": "done", "serve1": "done"}
    assert summary["status"] == "degraded"      # the quarantine
    # bench1 is evicted exactly once; under CI contention a second
    # still-running low-priority job may legally be co-evicted
    assert summary["evictions"] >= 1
    assert len(_sched_rows(tmp_path, job="bench1",
                           event="sched_evict")) == 1

    # zero lost steps, zero repeated steps: the progress tape is exact
    lines = open(prog).read().split()
    assert lines == [f"i{i}" for i in range(10)]

    # every decision is a ledger row
    evict = _sched_rows(tmp_path, job="bench1", event="sched_evict")
    assert len(evict) == 1
    assert evict[0]["for_job"] == "serve1" and evict[0]["clean"] is True
    assert evict[0]["rcs"] == {"0": 143}
    retry = _sched_rows(tmp_path, job="kill1", event="sched_retry")
    assert retry and retry[0]["retry"] == 1
    quarantine = _sched_rows(tmp_path, job="wedge1",
                             event="sched_quarantine")
    assert quarantine and "wedged" in quarantine[0]["why"]
    # quarantined means NOT requeued: exactly one placement
    assert len(_sched_rows(tmp_path, job="wedge1",
                           event="sched_place")) == 1
    done_rows = _sched_rows(tmp_path, event="sched_done")
    assert {r["job"] for r in done_rows} == {"t1", "t2", "t3", "kill1",
                                             "bench1", "serve1"}
    qdone = _sched_rows(tmp_path, event="sched_queue_done")
    assert qdone and qdone[-1]["counts"]["done"] == 6

    # the WAL balances: every intent seq has a matching applied record
    events = Journal(str(tmp_path / "sched" / "sched.jsonl")).events()
    intents = {e["seq"] for e in events if e["event"] == "sched_intent"}
    applied = {e.get("seq") for e in events
               if e["event"].startswith("sched_")
               and e["event"] != "sched_intent"}
    assert intents <= applied

    # obs_query why: the preemption is answerable from ledger rows alone
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import obs_query
    finally:
        sys.path.pop(0)
    import io
    from contextlib import redirect_stdout
    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = obs_query.main(["why", "bench1", "--ledger",
                             str(tmp_path / "sched" / "RUNS.jsonl")])
    out = buf.getvalue()
    assert rc == 0
    assert "EVICTED" in out and "`serve1`" in out
    assert "preempted 1x (for `serve1`)" in out
    assert "finally completed" in out
    # prefix resolution + the not-found refusal
    buf = io.StringIO()
    with redirect_stdout(buf):
        assert obs_query.main(["why", "wedge", "--ledger",
                               str(tmp_path / "sched" /
                                   "RUNS.jsonl")]) == 0
    assert "QUARANTINED" in buf.getvalue()
    with pytest.raises(SystemExit, match="not found"):
        obs_query.main(["why", "nope", "--ledger",
                        str(tmp_path / "sched" / "RUNS.jsonl")])


# ---- elastic shrink + grow as scheduler policy ---------------------------

def test_scheduler_shrink_then_grow_policy(tmp_path):
    """host_loss shape end-to-end at the policy level (stdlib child
    standing in for the faultline drill): rank 1's host dies on the
    first gang attempt (tombstone + SIGKILL), the elastic gang shrinks
    and keeps running; when the tombstone expires the scheduler's
    recovery probe cleanly stops the job (TERM→143) and relaunches it
    at FULL width — sched_shrink and sched_grow rows tell the story."""
    py = sys.executable
    child = _script(tmp_path, "elastic.py", """
        import json, os, signal, sys, time
        rank = int(os.environ["OBS_RANK"])
        n = int(os.environ["FLEET_NUM_RANKS"])
        attempt = int(os.environ["SUPERVISE_ATTEMPT"])
        print(json.dumps({"rank": rank, "n": n}), flush=True)
        if attempt == 0 and n == 2 and rank == 1 \\
                and not os.path.exists(os.environ["ONCE"]):
            open(os.environ["ONCE"], "w").close()
            with open(os.environ["FLEET_HOST_DOWN_FILE"], "w") as f:
                json.dump({"ts": time.time(), "down_s": 1.2}, f)
            os.kill(os.getpid(), 9)
        if n == 1:
            # shrunken: keep "training" until the grow-stop's TERM
            signal.signal(signal.SIGTERM, lambda s, f: sys.exit(143))
            time.sleep(30)
            sys.exit(1)
        sys.exit(0)
    """)
    jobs = [Job(job="el", argv=[py, child], kind="train", ranks=2,
                elastic=True, fleet_retries=4,
                env={"ONCE": str(tmp_path / "once")})]
    summary = _sched(tmp_path, jobs).run()
    assert summary["jobs"] == {"el": "done"}
    assert summary["shrinks"] >= 1 and summary["grows"] >= 1
    shrink = _sched_rows(tmp_path, job="el", event="sched_shrink")
    assert shrink and shrink[0]["ranks"] == 1 and shrink[0]["lost"] == [1]
    grow = _sched_rows(tmp_path, job="el", event="sched_grow")
    assert any(g.get("recovered") == [1] for g in grow)
    # the final placement ran at full width again
    place = _sched_rows(tmp_path, job="el", event="sched_place")
    assert place[-1]["ranks"] == 2 and place[-1]["resumed"] is True
    done = _sched_rows(tmp_path, job="el", event="sched_done")
    assert done and done[0]["rcs"] == {"0": 0, "1": 0}


# ---- anomaly detections feed eviction policy (ROADMAP direction 5) -------

def test_straggling_job_yields_to_queued_healthy_job(tmp_path):
    """The heal rung: a 2-rank bench job whose rank 1 is NAMED
    straggler by the fleet's monitor (lag + its own regression flag —
    health files written by the children themselves, the detect_skew
    contract) is evicted by the ANOMALY policy so an EQUAL-priority
    queued train job gets the mesh — plain SLO preemption could never
    justify this eviction (it only fires on strictly-less-urgent
    victims), so the sched_evict row's why names the straggler.  The
    bench requeues uncharged, relaunches clean (marker file drops the
    straggle), and its progress tape is gap- and duplicate-free."""
    py = sys.executable
    prog = str(tmp_path / "progress")
    child = _script(tmp_path, "strag.py", """
        import json, os, signal, sys, time
        signal.signal(signal.SIGTERM, lambda s, f: sys.exit(143))
        rank = int(os.environ["OBS_RANK"])
        hp = os.environ["OBS_HEALTH"]
        prog = os.environ["PROG"]
        once = os.environ["ONCE"] + f".r{rank}"
        straggle = not os.path.exists(once)
        open(once, "w").close()

        def health(step, firing, ewma):
            payload = {
                "version": 1, "kind": "rank", "rank": rank,
                "step": step, "updated_unix": time.time(),
                "flags": {"step_time_regression":
                          {"firing": firing,
                           "fired_step": 3 if firing else None},
                          "nan_loss": {"firing": False,
                                       "fired_step": None},
                          "loss_plateau": {"firing": False,
                                           "fired_step": None}},
                "detectors": {"step_time": {"ewma_s": ewma}}}
            tmp = hp + ".tmp"
            with open(tmp, "w") as f:
                json.dump(payload, f)
            os.replace(tmp, hp)

        for i in range(200):
            if rank == 0:
                # healthy front rank: advance + tape progress
                n = (sum(1 for _ in open(prog))
                     if os.path.exists(prog) else 0)
                if n >= 10:
                    health(100 + n, False, 0.01)
                    time.sleep(5)       # wait for the gang's fate
                    sys.exit(0)
                with open(prog, "a") as f:
                    f.write(f"i{n}\\n")
                health(10 + n, False, 0.01)
            elif straggle:
                # frozen at step 2 with its own regression firing
                health(2, True, 2.0)
            else:
                health(100 + i, False, 0.01)
                if i > 10:
                    sys.exit(0)
            time.sleep(0.1)
        sys.exit(0)
    """)
    jobs = [
        Job(job="bench1", argv=[py, child], kind="bench", ranks=2,
            fleet_retries=0, retries=2,
            env={"PROG": prog, "ONCE": str(tmp_path / "once")}),
        # equal priority, pinned: only the anomaly policy can evict
        # for this job — the SLO evictor needs strictly-lower urgency.
        Job(job="train1", argv=[py, "-c", "pass"], kind="train",
            priority=20, ranks=2),
    ]
    summary = _sched(tmp_path, jobs).run()
    assert summary["jobs"] == {"bench1": "done", "train1": "done"}
    evict = _sched_rows(tmp_path, job="bench1", event="sched_evict")
    assert len(evict) == 1 and evict[0]["for_job"] == "train1"
    assert "straggler" in evict[0]["why"]
    assert evict[0]["clean"] is True            # TERM→143, loss-free
    heal = [r for r in _ledger_rows(tmp_path)
            if str(r.get("event", "")).startswith("heal_")]
    kinds = [r["event"] for r in heal]
    assert "heal_detect" in kinds and "heal_evict" in kinds
    detect = next(r for r in heal if r["event"] == "heal_detect")
    assert detect["job"] == "bench1" and detect["kind"] == "straggler"
    he = next(r for r in heal if r["event"] == "heal_evict")
    assert he["detail"]["for_job"] == "train1"
    # the victim's tape is exact across the eviction: nothing lost,
    # nothing repeated
    assert open(prog).read().split() == [f"i{i}" for i in range(10)]
    # obs_query why folds both row families into one story
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import obs_query
    finally:
        sys.path.pop(0)
    import io
    from contextlib import redirect_stdout
    buf = io.StringIO()
    with redirect_stdout(buf):
        assert obs_query.main(["why", "bench1", "--ledger",
                               str(tmp_path / "sched"
                                   / "RUNS.jsonl")]) == 0
    out = buf.getvalue()
    assert "anomaly detected: straggler" in out
    assert "HEALED by eviction" in out
    assert "self-healed 1x (evict)" in out


def test_heal_dry_run_detects_but_never_evicts(tmp_path, monkeypatch):
    """HEAL_DRY_RUN: the same straggling gang is DETECTED (heal_detect
    + heal_dry_run rows) but nothing stops it — the bench runs to its
    own completion and the queued job simply waits."""
    monkeypatch.setenv("HEAL_DRY_RUN", "1")
    py = sys.executable
    child = _script(tmp_path, "strag_dry.py", """
        import json, os, signal, sys, time
        signal.signal(signal.SIGTERM, lambda s, f: sys.exit(143))
        rank = int(os.environ["OBS_RANK"])
        hp = os.environ["OBS_HEALTH"]
        t0 = time.time()
        i = 0
        while time.time() - t0 < 4.0:
            payload = {
                "version": 1, "kind": "rank", "rank": rank,
                "step": (2 if rank else 50 + i),
                "updated_unix": time.time(),
                "flags": {"step_time_regression":
                          {"firing": rank == 1, "fired_step":
                           2 if rank == 1 else None},
                          "nan_loss": {"firing": False,
                                       "fired_step": None},
                          "loss_plateau": {"firing": False,
                                           "fired_step": None}},
                "detectors": {"step_time":
                              {"ewma_s": 2.0 if rank else 0.01}}}
            tmp = hp + ".tmp"
            with open(tmp, "w") as f:
                json.dump(payload, f)
            os.replace(tmp, hp)
            i += 1
            time.sleep(0.1)
        sys.exit(0)
    """)
    jobs = [
        Job(job="bench1", argv=[py, child], kind="bench", ranks=2,
            fleet_retries=0),
        Job(job="train1", argv=[py, "-c", "pass"], kind="train",
            priority=20, ranks=2),
    ]
    summary = _sched(tmp_path, jobs).run()
    assert summary["jobs"] == {"bench1": "done", "train1": "done"}
    assert not _sched_rows(tmp_path, job="bench1", event="sched_evict")
    heal = [r["event"] for r in _ledger_rows(tmp_path)
            if str(r.get("event", "")).startswith("heal_")]
    assert "heal_detect" in heal and "heal_dry_run" in heal
    assert "heal_evict" not in heal


def test_heal_intent_replay_before_any_job_runs_is_clean_noop(tmp_path):
    """A scheduler SIGKILLed between the remediator's heal_intent and
    its applied row: the next incarnation re-applies the intent during
    construction, through _heal_evict, while every job is still queued
    — the documented idempotent noop ("job not running"), never an
    error row from half-initialized scheduler state."""
    workdir = tmp_path / "sched"
    workdir.mkdir(parents=True)
    dead = Journal(str(workdir / "sched.jsonl"))
    dead.write("heal_detect", key="a:l0:straggler:rank0",
               kind="straggler", job="a")
    dead.write("heal_intent", seq=1, action="evict",
               key="a:l0:straggler:rank0", kind="straggler", job="a")
    sched = _sched(tmp_path, [Job(job="a", argv=[sys.executable,
                                                 "-c", "pass"])])
    heal = [r for r in sched.journal.events()
            if str(r.get("event", "")).startswith("heal_")]
    assert not any(r.get("error") for r in heal)
    sup = [r for r in heal if r["event"] == "heal_suppressed"]
    assert sup and sup[-1]["reason"].startswith("noop")


# ---- write-ahead journal: SIGKILL mid-decision + orphan sweep ------------

def test_sigkill_mid_decision_replays_and_sweeps_orphans(tmp_path):
    """The acceptance drill's crash half, at the exact worst seam: the
    scheduler commits an EVICT intent to its journal and is SIGKILLed
    before delivering it (SCHED_DRILL_DIE_AT).  The victim's gang is
    now an orphan still appending to its store.  Rerunning the SAME
    command replays the journal idempotently: the dangling intent is
    resolved, the orphaned rank group is swept (its pid was journaled
    at spawn), and the queue finishes — with the victim's progress
    tape still gap- and duplicate-free."""
    py = sys.executable
    wd = str(tmp_path / "sched")
    prog = str(tmp_path / "progress")
    victim = _victim_script(tmp_path, iters=12, sleep=0.2)
    queue = tmp_path / "q.json"
    queue.write_text(json.dumps({"jobs": [
        {"job": "victim", "argv": [py, victim], "kind": "bench",
         "env": {"PROG": prog}},
        {"job": "serve", "argv": [py, "-c", "pass"], "kind": "serve",
         "ranks": 2, "after_file": prog},
    ]}))
    args = [py, os.path.join(REPO, "tools", "schedule.py"),
            "--queue", str(queue), "--workdir", wd, "--devices", "2",
            "--tick_s", "0.05"]
    env = dict(os.environ, SCHED_DRILL_DIE_AT="sched_intent:evict")
    r1 = subprocess.run(args, env=env, capture_output=True, text=True,
                        cwd=REPO, timeout=120)
    assert r1.returncode == -9, r1.stderr[-800:]
    assert "dying after sched_intent:evict:victim" in r1.stderr
    # the victim gang is orphaned and still running
    env.pop("SCHED_DRILL_DIE_AT")
    r2 = subprocess.run(args, env=env, capture_output=True, text=True,
                        cwd=REPO, timeout=120)
    assert r2.returncode == 0, r2.stderr[-800:]
    rows = [json.loads(l) for l in open(os.path.join(wd, "RUNS.jsonl"))
            if l.strip()]
    events = [r["event"] for r in rows
              if str(r.get("event", "")).startswith("sched_")]
    assert "sched_orphan_killed" in events, events
    assert "sched_intent_dropped" in events     # the dangling evict
    assert events.count("sched_queue_done") == 1
    done = {r["job"] for r in rows if r.get("event") == "sched_done"}
    assert done == {"victim", "serve"}
    lines = open(prog).read().split()
    assert lines == [f"i{i}" for i in range(12)]
    # the replay restored placement provenance: the relaunch is attempt
    # 2 and RESUMING (agree_first) — not a fresh attempt-1 placement
    # clobbering the dead incarnation's stdout dir
    places = [r for r in rows if r.get("event") == "sched_place"
              and r.get("job") == "victim"]
    assert [p["attempt"] for p in places] == [1, 2]
    assert places[0]["resumed"] is False and places[1]["resumed"] is True


def test_unsatisfiable_after_file_gate_fails_instead_of_spinning(
        tmp_path):
    """A job gated on a file nothing left in the queue can produce must
    FAIL with a why, not tick the scheduler forever (the gate's
    producer crashed out before creating it)."""
    py = sys.executable
    jobs = [
        Job(job="producer", argv=[py, "-c", "import sys; sys.exit(9)"],
            kind="train", retries=0, fleet_retries=0),
        Job(job="gated", argv=[py, "-c", "pass"], kind="serve",
            after_file=str(tmp_path / "never_created")),
    ]
    summary = _sched(tmp_path, jobs).run()
    assert summary["jobs"] == {"producer": "failed", "gated": "failed"}
    fail = _sched_rows(tmp_path, job="gated", event="sched_fail")
    assert fail and "can no longer be satisfied" in fail[0]["why"]


# ---- the serve job kind runs a REAL serving worker (PR 15) ---------------

def test_serve_job_kind_runs_serve_lm_evictions_are_loss_free(tmp_path):
    """The `serve` job kind finally launches a real workload: a
    tools/serve_lm.py worker (snapshot promoted through the validity
    path, continuous-batched decode, closed-loop driven).  The drill
    exercises BOTH eviction directions on a 1-device mesh:

    1. serve (priority 0) arrives mid-bench and evicts the bench job —
       the PR 14 SLO-preemption path, now with a real serving workload
       behind it;
    2. an urgent priority--1 job arrives mid-SERVE and evicts the
       SERVING WORKER: TERM → drain in-flight requests to completion →
       exit 143 (clean, rcs {"0": 143}) — the trainer's loss-free
       preemption protocol with "state saved" read as "every admitted
       request answered".  The relaunch re-issues exactly the
       unfinished request ids from the results tape, so the final tape
       holds every id exactly once: zero lost requests, zero repeats.
    """
    py = sys.executable
    prog = str(tmp_path / "progress")
    res = str(tmp_path / "serve_results.jsonl")
    stats = str(tmp_path / "serve_stats.json")
    victim = _victim_script(tmp_path)
    n_req = 12
    serve_argv = [py, os.path.join(REPO, "tools", "serve_lm.py"),
                  "--snapshot", str(tmp_path / "snaps"),
                  "--size", "lm_tiny", "--init_if_missing",
                  "--slots", "2", "--max_len", "32",
                  "--drive", str(n_req), "--clients", "2",
                  "--drive_max_new", "4", "--drive_think_ms", "600",
                  "--results", res, "--stats", stats]
    jobs = [
        Job(job="bench1", argv=[py, victim], kind="bench",
            env={"PROG": prog}),
        # ready the moment bench1 proves mid-run progress; the 1-device
        # mesh is busy, so admission must evict bench1.
        Job(job="serve1", argv=serve_argv, kind="serve",
            after_file=prog, retries=2, wall_timeout_s=300.0,
            kill_grace_s=15.0),
        # ready the moment serve1 completes its first request (the
        # results tape exists); outranks even `serve`, so admission
        # must evict the SERVING worker — the teardown under test.
        Job(job="urgent1", argv=[py, "-c", "pass"], kind="train",
            priority=-1, after_file=res),
    ]
    summary = _sched(tmp_path, jobs, devices=1).run()
    assert summary["jobs"] == {"bench1": "done", "serve1": "done",
                               "urgent1": "done"}
    # bench evicted for serve, serve evicted for urgent — both clean
    evict_b = _sched_rows(tmp_path, job="bench1", event="sched_evict")
    assert evict_b and evict_b[0]["for_job"] == "serve1"
    evict_s = _sched_rows(tmp_path, job="serve1", event="sched_evict")
    assert len(evict_s) == 1 and evict_s[0]["for_job"] == "urgent1"
    assert evict_s[0]["clean"] is True
    assert evict_s[0]["rcs"] == {"0": 143}      # TERM -> drain -> 143
    # the serving worker resumed: two placements, the second resuming
    places = _sched_rows(tmp_path, job="serve1", event="sched_place")
    assert [p["attempt"] for p in places] == [1, 2]
    assert places[1]["resumed"] is True
    # loss-free: every driven request id exactly once across both
    # placements — drained in-flight requests completed (never lost),
    # completed ids never re-issued (never repeated)
    ids = sorted(json.loads(line)["id"] for line in open(res))
    assert ids == list(range(n_req))
    # the bench victim's own tape stayed exact through ITS eviction
    assert open(prog).read().split() == [f"i{i}" for i in range(10)]
    # and the worker's runs are ledgered: run_start/run_end rows from
    # serve_lm itself (the fleet exports OBS_LEDGER to its ranks)
    serve_runs = [r for r in _ledger_rows(tmp_path)
                  if r.get("event") == "run_start"
                  and r.get("entrypoint") == "serve_lm"]
    assert len(serve_runs) == 2                 # one per placement
    final_stats = json.load(open(stats))
    assert final_stats["preempted"] is False    # the resume finished
    assert final_stats["size"] == "lm_tiny"


# ---- the host_loss fault + fleet seam ------------------------------------

def test_host_loss_grammar_and_named_plan():
    plan = FaultPlan.parse("host_loss@3:5.0%1", 10, 0)
    assert plan.specs == [FaultSpec("host_loss", 3, 5.0, rank=1)]
    assert plan.specs[0] in plan.loop_specs     # a boundary fault
    named = FaultPlan.parse("host_loss", 10, 0)
    assert named.specs[0].kind == "host_loss"
    assert named.specs[0].rank == 1 and named.specs[0].arg == 2.0
    assert named.for_rank(0).specs == []        # pinned to rank 1


def test_host_loss_refused_without_seam(monkeypatch):
    """A host_loss with no tombstone seam would SIGKILL the process and
    report a drill that drilled nothing — refused loudly instead."""
    monkeypatch.delenv("FLEET_HOST_DOWN_FILE", raising=False)
    hook = FaultInjectionHook(FaultPlan.parse("host_loss@1", 4, 0))
    with pytest.raises(ValueError, match="FLEET_HOST_DOWN_FILE"):
        hook.after_step(1, None, {})


def test_host_down_tombstone_expiry(tmp_path):
    """mark_host_down + FleetSupervisor.host_down: fresh = down,
    expired self-heals (the tombstone is removed), torn = still down,
    down_s=0 = down until removed."""
    fleet = FleetSupervisor(2, workdir=str(tmp_path / "fleet"))
    path = fleet._host_down_path(1)
    assert fleet.host_down(1) is False          # no tombstone
    mark_host_down(path, down_s=30.0, rank=1)
    assert fleet.host_down(1) is True
    mark_host_down(path, down_s=0.05, rank=1)
    time.sleep(0.08)
    assert fleet.host_down(1) is False          # expired + self-removed
    assert not os.path.exists(path)
    mark_host_down(path, down_s=0.0, rank=1)    # down forever
    time.sleep(0.05)
    assert fleet.host_down(1) is True
    with open(path, "w") as f:
        f.write('{"ts": 1')                     # torn mid-write
    assert fleet.host_down(1) is True


# ---- queue-completion record ---------------------------------------------

def test_schedule_record_is_one_json_line_per_metric(tmp_path):
    """tools/schedule.py --record: one JSON line per metric, each
    self-labelled platform=cpu, jobs/min from the makespan."""
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import schedule as schedule_cli
    finally:
        sys.path.pop(0)
    summary = {"status": "ok", "counts": {"done": 8},
               "makespan_s": 120.0, "evictions": 1, "shrinks": 1,
               "grows": 1, "retries": 1, "jobs": {"a": "done"}}
    rec_path = tmp_path / "queue_record.json"
    schedule_cli.write_record(str(rec_path), summary, devices=4)
    recs = [json.loads(line) for line in
            rec_path.read_text().splitlines()]
    by_metric = {r["metric"]: r for r in recs}
    assert set(by_metric) == {"sched_queue_jobs_done",
                              "sched_queue_jobs_per_min"}
    assert all(r["platform"] == "cpu" for r in recs)
    assert by_metric["sched_queue_jobs_done"]["value"] == 8
    assert by_metric["sched_queue_jobs_per_min"]["value"] == 4.0
    assert by_metric["sched_queue_jobs_done"]["detail"]["devices"] == 4


# ---- fleet-level request_stop (the eviction primitive) -------------------

def test_fleet_request_stop_returns_evicted_without_restart(tmp_path):
    """The eviction primitive under the scheduler: request_stop tears
    the gang down through TERM (rcs 143) and run() returns 'evicted'
    WITHOUT a restart — distinct from the platform-preemption path,
    which restarts immediately."""
    import threading
    child = _script(tmp_path, "stopchild.py", """
        import signal, sys, time
        signal.signal(signal.SIGTERM, lambda s, f: sys.exit(143))
        time.sleep(60)
        sys.exit(0)
    """)
    fleet = FleetSupervisor(
        2, policy=RetryPolicy(retries=2, backoff_base_s=0.01),
        journal=Journal(str(tmp_path / "fleet.jsonl")),
        kill_grace_s=2.0, poll_s=0.02, seed=0,
        workdir=str(tmp_path / "fleet"))
    box = []
    t = threading.Thread(target=lambda: box.append(
        fleet.run([sys.executable, child], name="stoppable")))
    t.start()
    time.sleep(0.5)                 # both ranks up and sleeping
    fleet.request_stop("evicted")
    t.join(timeout=30)
    assert not t.is_alive() and box
    res = box[0]
    assert res.status == "evicted" and res.gang_attempts == 1
    assert res.last_rcs == {0: 143, 1: 143}
    events = Journal(str(tmp_path / "fleet.jsonl")).events()
    tear = next(e for e in events if e["event"] == "gang_teardown")
    assert tear["why"] == "evicted"
    # a stop landing between attempts: no gang is launched at all
    fleet2 = FleetSupervisor(1, workdir=str(tmp_path / "f2"), seed=0)
    fleet2.request_stop("evicted")
    res2 = fleet2.run([sys.executable, "-c", "pass"], name="never")
    assert res2.status == "evicted" and res2.last_rcs == {}


def test_sched_events_schema_is_closed():
    """The KEEP-IN-SYNC pair's content contract: every event the
    scheduler writes through _applied/_observe is in SCHED_EVENTS (plus
    the replay-only intent_dropped), and obs_query's why renderer
    covers exactly the declared set."""
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import obs_query
    finally:
        sys.path.pop(0)
    assert set(obs_query._WHY_RENDER) == set(SCHED_EVENTS)
