"""Gang supervision (resilience/fleet.py): per-rank fault targeting,
the resume-step agreement over torn/divergent rank manifests, and the
fleet state machine driven by real OS processes.

Inline on purpose: the gang children here are stdlib-only scripts
(milliseconds each, no jax import), so the whole file's verdicts land
inside the tier-1 budget.  The jax-heavy end-to-end drill (2-rank
mnist_cnn, rank-targeted kill, bitwise resume parity) lives in
tests/test_fleet_drill.py.
"""

import json
import os
import stat
import sys
import time
import zlib

import pytest

from distributedtensorflowexample_tpu.obs import recorder as obs_recorder
from distributedtensorflowexample_tpu.obs import trace as obs_trace
from distributedtensorflowexample_tpu.resilience.faults import FaultPlan
from distributedtensorflowexample_tpu.resilience.fleet import (
    FleetSupervisor, RankLossRefused, RankLossStructurallyIllegal)
from distributedtensorflowexample_tpu.resilience.snapshot import (
    SnapshotStore, newest_common_step, valid_steps)
from distributedtensorflowexample_tpu.resilience.supervisor import (
    Journal, RetryPolicy, Supervisor)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

pytestmark = pytest.mark.fleet


# ---- per-rank fault targeting (resilience/faults.py) --------------------

def test_fault_rank_grammar():
    """kind[@step][:arg][%rank]: 'kill rank 1 at step 37' is one token."""
    p = FaultPlan.parse("kill@37%1,wedge@3:2.5%0,preemption@5", 50, 0)
    by_kind = {s.kind: s for s in p.specs}
    assert (by_kind["kill"].step, by_kind["kill"].rank) == (37, 1)
    assert (by_kind["wedge"].step, by_kind["wedge"].arg,
            by_kind["wedge"].rank) == (3, 2.5, 0)
    assert by_kind["preemption"].rank is None      # untargeted: every rank


def test_fault_rank_targeting_is_deterministic_and_shares_anchor():
    """Every rank parses the SAME text+seed, so an unpinned rank-targeted
    fault lands on ONE fleet-wide anchor step — and re-parsing
    reproduces it exactly (the seed-reproducible drill contract)."""
    a = FaultPlan.parse("kill%1", 10, 7)
    b = FaultPlan.parse("kill%1", 10, 7)
    assert a.specs == b.specs
    assert 1 <= a.specs[0].step < 10
    # a different seed explores a different schedule, same grammar
    c = FaultPlan.parse("kill%1", 10, 8)
    assert c.specs[0].kind == "kill" and c.specs[0].rank == 1
    # rank filtering: rank 1 keeps the kill, rank 0 sees no faults;
    # untargeted specs survive on every rank
    assert [s.kind for s in a.for_rank(1).specs] == ["kill"]
    assert a.for_rank(0).specs == []
    d = FaultPlan.parse("kill@4%1,preemption@2", 10, 0)
    assert [s.kind for s in d.for_rank(0).specs] == ["preemption"]
    assert [s.kind for s in d.for_rank(1).specs] == ["preemption", "kill"]


# ---- resume-step agreement (resilience/snapshot.py) ---------------------

def _write_snap(directory, step, payload=b"snapshot-payload-bytes",
                torn=False):
    """A committed snapshot the manifest surface accepts, without a
    TrainState: the agreement reads manifests + payload bytes only."""
    os.makedirs(directory, exist_ok=True)
    pp = os.path.join(directory, f"snap_{step:08d}.npz")
    with open(pp, "wb") as f:
        f.write(payload)
    man = {"version": 1, "step": step, "nbytes": len(payload),
           "crc32": zlib.crc32(payload), "leaves": 1, "cursor": None,
           "meta": None}
    with open(os.path.join(directory, f"snap_{step:08d}.json"), "w") as f:
        json.dump(man, f)
    if torn:
        with open(pp, "r+b") as f:
            f.truncate(len(payload) // 2)


def test_newest_common_step_picks_max_common_valid(tmp_path):
    """Divergent newest (one rank ran ahead) and torn newest (killed
    mid-write) both fall away; the agreement is the newest step EVERY
    rank can prove."""
    r0, r1 = str(tmp_path / "r0"), str(tmp_path / "r1")
    for s in (3, 4, 5):
        _write_snap(r0, s)                 # rank 0 ran ahead to 5
    for s in (3, 4):
        _write_snap(r1, s)
    _write_snap(r1, 5, torn=True)          # rank 1's 5 tore mid-write
    assert valid_steps(r0) == [3, 4, 5]
    assert valid_steps(r1) == [3, 4]       # the torn 5 is invisible
    assert newest_common_step([r0, r1]) == 4


def test_newest_common_step_empty_and_disjoint(tmp_path):
    r0, r1 = str(tmp_path / "r0"), str(tmp_path / "r1")
    _write_snap(r0, 2)
    assert newest_common_step([r0, r1]) is None    # r1 has nothing
    _write_snap(r1, 3)
    assert newest_common_step([r0, r1]) is None    # nothing in common


def test_discard_newer_drops_divergent_timeline(tmp_path):
    d = str(tmp_path / "r0")
    for s in (2, 3, 4, 5):
        _write_snap(d, s)
    store = SnapshotStore(d)
    assert store.discard_newer(3) == [4, 5]
    assert valid_steps(d) == [2, 3]
    # no leftover manifests either: a stale manifest would make save()
    # dedupe the replayed step away
    assert not [f for f in os.listdir(d) if "00000004" in f]
    assert store.discard_newer(0) == [2, 3]        # 0 = discard all


# ---- the gang state machine (stdlib children, real processes) -----------

def _child(tmp_path, body: str) -> list[str]:
    path = tmp_path / "child.py"
    path.write_text(body)
    return [sys.executable, str(path)]


def _fleet(tmp_path, **kw):
    kw.setdefault("policy", RetryPolicy(retries=2, backoff_base_s=0.01,
                                        backoff_max_s=0.02))
    kw.setdefault("journal", Journal(str(tmp_path / "fleet.jsonl")))
    kw.setdefault("kill_grace_s", 1.0)
    kw.setdefault("poll_s", 0.05)
    kw.setdefault("seed", 0)
    kw.setdefault("workdir", str(tmp_path / "fleet"))
    return FleetSupervisor(2, **kw)


def _journal_events(tmp_path) -> list[dict]:
    with open(tmp_path / "fleet.jsonl") as f:
        return [json.loads(line) for line in f]


def test_gang_ok_and_cluster_env_surface(tmp_path, monkeypatch):
    """Every rank gets the trainers' documented env surface: TF_CONFIG
    (task index = rank), OBS_RANK, FLEET_NUM_RANKS, SUPERVISE_ATTEMPT —
    and {rank} substitution fans one argv out to per-rank args.  A
    stale FLEET_RESUME_STEP leaking in from the FLEET's own environment
    is scrubbed: only an agreement pass this fleet ran may export one."""
    monkeypatch.setenv("FLEET_RESUME_STEP", "99")   # stale outer export
    argv = _child(tmp_path, """
import json, os, sys
cfg = json.loads(os.environ["TF_CONFIG"])
print(json.dumps({"rank": os.environ["OBS_RANK"], "tag": sys.argv[1],
                  "idx": cfg["task"]["index"],
                  "workers": len(cfg["cluster"]["worker"]),
                  "n": os.environ["FLEET_NUM_RANKS"],
                  "attempt": os.environ["SUPERVISE_ATTEMPT"],
                  "resume": os.environ.get("FLEET_RESUME_STEP"),
                  "hb": os.path.basename(os.environ["SUPERVISE_HEARTBEAT"])}))
""") + ["tag{rank}"]
    fleet = _fleet(tmp_path)
    res = fleet.run(argv, name="envs", stdout_dir=str(tmp_path / "out"))
    assert res.status == "ok" and res.gang_attempts == 1
    assert res.restarts == 0 and res.last_rcs == {0: 0, 1: 0}
    for r in (0, 1):
        rec = json.loads(
            (tmp_path / "out" / f"rank{r}_attempt0.out").read_text())
        assert rec == {"rank": str(r), "tag": f"tag{r}", "idx": r,
                       "workers": 2, "n": "2", "attempt": "0",
                       "resume": None, "hb": f"hb_rank{r}"}


def test_rank_crash_tears_down_whole_gang_then_restarts(tmp_path):
    """One rank's crash is a GANG event: the healthy rank (mid-'step',
    would run 60 s) is torn down immediately, and the relaunch carries
    the next SUPERVISE_ATTEMPT."""
    argv = _child(tmp_path, """
import os, sys, time
r, a = int(os.environ["OBS_RANK"]), int(os.environ["SUPERVISE_ATTEMPT"])
if a == 0 and r == 1:
    sys.exit(7)
if a == 0:
    time.sleep(60)     # must be torn down, never waited out
sys.exit(0)
""")
    fleet = _fleet(tmp_path)
    t0 = time.monotonic()
    res = fleet.run(argv, name="crash")
    assert res.status == "ok" and res.gang_attempts == 2
    assert res.restarts == 1
    assert time.monotonic() - t0 < 30, "teardown must not wait the 60s"
    events = [e["event"] for e in _journal_events(tmp_path)]
    assert "gang_teardown" in events
    tear = next(e for e in _journal_events(tmp_path)
                if e["event"] == "gang_teardown")
    assert tear["why"] == "rank_crash" and tear["rank"] == 1


def test_gang_crash_budget_exhausts(tmp_path):
    argv = _child(tmp_path, "raise SystemExit(1)")
    fleet = _fleet(tmp_path, policy=RetryPolicy(retries=1,
                                                backoff_base_s=0.01,
                                                backoff_max_s=0.02))
    res = fleet.run(argv, name="dead")
    assert res.status == "exhausted" and res.gang_attempts == 2


def test_unanimous_preemption_exempt_from_budget(tmp_path):
    """The 143 consensus path: every rank preempted-with-save restarts
    the gang without touching the crash budget — 3 preemptions complete
    under retries=0."""
    argv = _child(tmp_path, """
import os, sys
sys.exit(143 if int(os.environ["SUPERVISE_ATTEMPT"]) < 3 else 0)
""")
    fleet = _fleet(tmp_path, policy=RetryPolicy(retries=0))
    res = fleet.run(argv, name="preempt_storm")
    assert res.status == "ok" and res.gang_attempts == 4
    assert res.preemptions == 3 and res.restarts == 3


def test_preemption_divergence_is_budgeted(tmp_path):
    """One rank exits 143 while the other trains on past the consensus
    grace: the gang cleanly lost a member but NOT unanimously — torn
    down and restarted through the budgeted path, not the exemption."""
    argv = _child(tmp_path, """
import os, sys, time
r, a = int(os.environ["OBS_RANK"]), int(os.environ["SUPERVISE_ATTEMPT"])
if a == 0 and r == 0:
    sys.exit(143)
if a == 0:
    time.sleep(60)
sys.exit(0)
""")
    fleet = _fleet(tmp_path, preempt_grace_s=0.3)
    t0 = time.monotonic()
    res = fleet.run(argv, name="diverge")
    assert res.status == "ok" and res.gang_attempts == 2
    assert res.preemptions == 0          # NOT the exempt path
    assert time.monotonic() - t0 < 30
    tear = next(e for e in _journal_events(tmp_path)
                if e["event"] == "gang_teardown")
    assert tear["why"] == "preempt_divergence"


def test_rank_heartbeat_loss_tears_down_gang(tmp_path):
    """'wedge rank 0's heartbeat': rank 0 beats once then blocks without
    exiting; the per-rank heartbeat watchdog reads the stale beat and
    tears the gang down (the failure a wall clock alone notices too
    late)."""
    argv = _child(tmp_path, """
import os, sys, time
r, a = int(os.environ["OBS_RANK"]), int(os.environ["SUPERVISE_ATTEMPT"])
open(os.environ["SUPERVISE_HEARTBEAT"], "a").close()    # first beat: arms
if a == 0 and r == 0:
    time.sleep(60)      # wedged: beats stop, process lives
sys.exit(0)
""")
    fleet = _fleet(tmp_path, heartbeat_timeout_s=0.7)
    t0 = time.monotonic()
    res = fleet.run(argv, name="wedge")
    assert res.status == "ok" and res.gang_attempts == 2
    assert time.monotonic() - t0 < 30
    tear = next(e for e in _journal_events(tmp_path)
                if e["event"] == "gang_teardown")
    assert tear["why"] == "rank_heartbeat" and tear["rank"] == 0


def test_rank_lost_taxonomy(tmp_path):
    """A host that cannot even exec degrades LOUDLY: worker-tiled state
    makes the shrink structurally illegal; replicated state refuses
    without --elastic; --elastic continues on the survivors."""
    exe0 = tmp_path / "exe0"
    exe0.write_text("#!/bin/sh\nexit 0\n")
    exe0.chmod(exe0.stat().st_mode | stat.S_IXUSR)
    argv = [str(tmp_path / "exe{rank}")]       # exe1 does not exist

    with pytest.raises(RankLossStructurallyIllegal, match="worker-tiled"):
        _fleet(tmp_path, worker_tiled=True,
               workdir=str(tmp_path / "f1")).run(argv, name="lost")
    with pytest.raises(RankLossRefused, match="--elastic"):
        _fleet(tmp_path, workdir=str(tmp_path / "f2")).run(argv,
                                                           name="lost")
    fleet = _fleet(tmp_path, elastic=True, workdir=str(tmp_path / "f3"))
    res = fleet.run(argv, name="lost")
    assert res.status == "ok" and res.ranks == [0]
    assert any(e["event"] == "rank_lost" and e["rank"] == 1
               for e in _journal_events(tmp_path))


def test_shrink_then_grow_restores_full_width(tmp_path):
    """The grow-on-recovery satellite: rank 1's host dies (tombstone +
    SIGKILL — the host_loss shape), the next spawn fails with the
    spawn-OSError the tombstone seam injects, the elastic gang shrinks
    to rank 0 and keeps working — then the tombstone expires, the
    recovery re-probe before the next relaunch re-adds rank 1, and the
    final gang runs at FULL width with ``{num_ranks}`` templating
    restored to 2 (the value each child both receives in
    FLEET_NUM_RANKS and sees substituted into its argv)."""
    argv = _child(tmp_path, """
import json, os, sys, time
rank = int(os.environ["OBS_RANK"])
n = int(os.environ["FLEET_NUM_RANKS"])
attempt = int(os.environ["SUPERVISE_ATTEMPT"])
print(json.dumps({"rank": rank, "n": n, "attempt": attempt,
                  "tag": sys.argv[1]}), flush=True)
if attempt == 0 and rank == 1:
    with open(os.environ["FLEET_HOST_DOWN_FILE"], "w") as f:
        json.dump({"ts": time.time(), "down_s": 0.8}, f)
    os.kill(os.getpid(), 9)
if n == 1:
    time.sleep(1.0)     # outlive the tombstone so the re-probe can grow
    sys.exit(1)         # force one more budgeted restart
sys.exit(0)
""") + ["w{num_ranks}"]
    fleet = _fleet(tmp_path, elastic=True,
                   policy=RetryPolicy(retries=4, backoff_base_s=0.01,
                                      backoff_max_s=0.02))
    res = fleet.run(argv, name="grow", stdout_dir=str(tmp_path / "out"))
    assert res.status == "ok", res.reasons
    assert res.ranks == [0, 1]          # full width again
    assert fleet.lost_ranks == []
    events = _journal_events(tmp_path)
    assert any(e["event"] == "rank_lost" and e["rank"] == 1
               for e in events)
    rec = next(e for e in events if e["event"] == "rank_recovered")
    assert rec["rank"] == 1 and rec["ranks"] == [0, 1]
    # the shrunken attempt really ran at width 1, the final one at 2 —
    # and the {num_ranks} argv templating tracked both
    outs = {}
    for name in os.listdir(tmp_path / "out"):
        text = (tmp_path / "out" / name).read_text().strip()
        if not text:
            continue        # torn down before its first print
        rec = json.loads(text)
        outs[(rec["rank"], rec["attempt"])] = rec
    shrunk = [r for r in outs.values() if r["n"] == 1]
    assert shrunk and all(r["tag"] == "w1" and r["rank"] == 0
                          for r in shrunk)
    last_attempt = max(a for _, a in outs)
    for rank in (0, 1):
        final = outs[(rank, last_attempt)]
        assert final["n"] == 2 and final["tag"] == "w2"


def test_agreement_pass_exports_step_and_discards_divergence(tmp_path):
    """The restart half end-to-end: rank 0's store ran ahead (3,4,5),
    rank 1 holds (3,4) + a torn 5 — after a crash the fleet agrees on
    4, DELETES every newer snapshot on every rank, and exports
    FLEET_RESUME_STEP=4 to the relaunched children."""
    snaps = {r: str(tmp_path / f"rank{r}" / "snapshots") for r in (0, 1)}
    for s in (3, 4, 5):
        _write_snap(snaps[0], s)
    for s in (3, 4):
        _write_snap(snaps[1], s)
    _write_snap(snaps[1], 5, torn=True)
    argv = _child(tmp_path, """
import os, sys
if int(os.environ["SUPERVISE_ATTEMPT"]) == 0:
    sys.exit(1)
print(os.environ["FLEET_RESUME_STEP"])
""")
    fleet = _fleet(tmp_path)
    res = fleet.run(argv, name="agree",
                    snapshot_dir_template=str(tmp_path / "rank{rank}"
                                              / "snapshots"),
                    stdout_dir=str(tmp_path / "out"))
    assert res.status == "ok" and res.agreed_steps == [4]
    for r in (0, 1):
        out = (tmp_path / "out" / f"rank{r}_attempt1.out").read_text()
        assert out.strip() == "4"
        assert valid_steps(snaps[r]) == [3, 4]     # 5 discarded on both
    agree = next(e for e in _journal_events(tmp_path)
                 if e["event"] == "resume_agreement")
    assert agree["agreed"] == 4
    assert agree["per_rank"] == {"0": [3, 4, 5], "1": [3, 4]}
    assert agree["discarded"]["0"] == [5]


def test_interrupted_agreement_discard_replayed_idempotently(
        tmp_path, monkeypatch):
    """The ROADMAP fault-library straggler: the supervisor dies
    MID-``discard_newer`` — rank 0's divergent snapshots already swept,
    rank 1's untouched (the ``FLEET_DRILL_DIE_IN_DISCARD`` seam).  The
    write-ahead ``resume_agreement`` record lets a restarted supervisor
    replay the discard BEFORE its first launch: rank 1's
    abandoned-timeline snapshot is gone, the first gang already exports
    the agreed step (no per-rank own-newest restores), and the replay
    is idempotent — the already-swept rank loses nothing, and a third
    incarnation (completion record present) replays nothing at all."""
    snaps = {r: str(tmp_path / f"rank{r}" / "snapshots") for r in (0, 1)}
    for s in (3, 4, 5):
        _write_snap(snaps[0], s)
    for s in (3, 4, 6):
        _write_snap(snaps[1], s)
    tmpl = str(tmp_path / "rank{rank}" / "snapshots")
    monkeypatch.setenv("FLEET_DRILL_DIE_IN_DISCARD", "0")
    with pytest.raises(RuntimeError, match="mid-discard"):
        _fleet(tmp_path)._agree("agree", tmpl)
    assert valid_steps(snaps[0]) == [3, 4]      # swept before the death
    assert valid_steps(snaps[1]) == [3, 4, 6]   # divergent survivor
    monkeypatch.delenv("FLEET_DRILL_DIE_IN_DISCARD")
    # Restarted supervisor, same journal: the interrupted intent must
    # replay before any child launches.
    argv = _child(tmp_path, """
import os
print(os.environ["FLEET_RESUME_STEP"])
""")
    res = _fleet(tmp_path).run(argv, name="agree",
                               snapshot_dir_template=tmpl,
                               stdout_dir=str(tmp_path / "out"))
    assert res.status == "ok" and res.gang_attempts == 1
    for r in (0, 1):
        assert valid_steps(snaps[r]) == [3, 4]
        out = (tmp_path / "out" / f"rank{r}_attempt0.out").read_text()
        assert out.strip() == "4"               # pinned to the agreement
    done = [e for e in _journal_events(tmp_path)
            if e["event"] == "resume_discard_done"]
    assert done and done[-1].get("replayed") is True
    assert done[-1]["discarded"] == {"0": [], "1": [6]}  # idempotent half
    # Completion record present -> a third incarnation replays nothing.
    assert _fleet(tmp_path, workdir=str(tmp_path / "f2"))\
        ._replay_agreement("agree", tmpl) is None


def test_supervise_fleet_cli_exhausted_never_exits_143(tmp_path,
                                                       monkeypatch):
    """An exhausted fleet whose final attempt happened to contain a
    preempted rank must not exit 143 — that code means 'terminated
    cleanly' to an outer supervisor, which would restart the exhausted
    fleet budget-free forever.  The crashing rank's own rc wins."""
    # the CLI setdefaults OBS_DIR process-wide; pin it so the export
    # does not leak past this test into later files
    monkeypatch.setenv("OBS_DIR", str(tmp_path / "flight"))
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import supervise_fleet
    finally:
        sys.path.pop(0)
    script = tmp_path / "mixed.py"
    script.write_text("""
import os, sys
sys.exit(143 if os.environ["OBS_RANK"] == "0" else 7)
""")
    rc = supervise_fleet.main([
        "--num_ranks", "2", "--retries", "0", "--backoff_base_s", "0.01",
        "--workdir", str(tmp_path / "wd"), "--snapshots", "none",
        "--seed", "0", "--",
        sys.executable, str(script)])
    assert rc == 7


# ---- obs wiring ---------------------------------------------------------

def test_flight_filename_and_payload_carry_rank(monkeypatch, tmp_path):
    """Multi-process flights must not collide on pid alone: OBS_RANK
    puts the rank in the filename AND the payload."""
    monkeypatch.setenv("OBS_DIR", str(tmp_path))
    assert os.path.basename(obs_recorder.flight_path()) == \
        f"flight_{os.getpid()}.json"
    monkeypatch.setenv("OBS_RANK", "2")
    assert os.path.basename(obs_recorder.flight_path()) == \
        f"flight_2_{os.getpid()}.json"
    rec = obs_recorder.FlightRecorder()
    assert rec.payload("test")["rank"] == 2


def test_trace_span_context_carries_rank(monkeypatch):
    monkeypatch.delenv("OBS_RANK", raising=False)
    assert "rank" not in obs_trace.event("ctx_check", 0.0)
    monkeypatch.setenv("OBS_RANK", "3")
    assert obs_trace.event("ctx_check", 0.0)["rank"] == 3


def test_prometheus_collector_export_after_tasks(monkeypatch, tmp_path):
    """OBS_PROM_DIR (the round-7 ROADMAP leftover): a completed
    supervisor task and a fleet run both refresh textfile-collector
    exports."""
    monkeypatch.setenv("OBS_PROM_DIR", str(tmp_path / "prom"))
    sup = Supervisor(policy=RetryPolicy(retries=0), seed=0)
    res = sup.run(_child(tmp_path, "raise SystemExit(0)"), name="noop")
    assert res.status == "ok"
    text = (tmp_path / "prom" / "supervise.prom").read_text()
    assert "# TYPE supervisor_attempts_total counter" in text
    fleet = _fleet(tmp_path)
    assert fleet.run(_child(tmp_path, "raise SystemExit(0)"),
                     name="noop").status == "ok"
    text = (tmp_path / "prom" / "fleet.prom").read_text()
    assert "# TYPE fleet_gang_restarts_total counter" in text
    assert "# TYPE fleet_rank_exits_total counter" in text


def test_obs_report_renders_per_rank_timeline(tmp_path, capsys):
    """A fleet journal renders the per-rank timeline section: who died,
    what tore the gang down, which step the restart agreed on."""
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import obs_report
    finally:
        sys.path.pop(0)
    jp = tmp_path / "fleet.jsonl"
    rows = [
        {"ts": 1.0, "event": "gang_start", "task": "drill", "attempt": 0,
         "ranks": [0, 1], "resume_step": None},
        {"ts": 2.0, "event": "rank_exit", "task": "drill", "attempt": 0,
         "rank": 1, "rc": -9},
        {"ts": 2.1, "event": "gang_teardown", "task": "drill",
         "attempt": 0, "why": "rank_crash", "rank": 1},
        {"ts": 2.4, "event": "resume_agreement", "task": "drill",
         "agreed": 4, "per_rank": {"0": [3, 4, 5], "1": [3, 4]},
         "discarded": {"0": [5], "1": []}},
        {"ts": 3.0, "event": "gang_end", "task": "drill", "attempt": 0,
         "outcome": "crash", "why": "rank 1 rc=-9"},
    ]
    jp.write_text("".join(json.dumps(r) + "\n" for r in rows))
    assert obs_report.main(["--journal", str(jp)]) == 0
    out = capsys.readouterr().out
    assert "Per-rank timeline" in out
    assert "`resume_agreement`" in out and "agreed step 4" in out
    assert "rank_crash" in out
    # the plain journal table carries the rank column too
    assert "| rank |" in out


# ---- faultline plumbing (in-process, jax already warm) ------------------

def _faultline(capsys, *args):
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import faultline
    finally:
        sys.path.pop(0)
    rc = faultline.main(list(args))
    captured = capsys.readouterr()
    out = [l for l in captured.out.splitlines() if l.strip()]
    rec = json.loads(out[-1]) if out else {}
    rec["_stderr"] = captured.err
    return rc, rec


@pytest.mark.faults
def test_faultline_rank_targeted_fault_fires_only_on_its_rank(tmp_path,
                                                              capsys):
    """'preempt rank 1 at step 2' as ONE shared plan text: rank 0 runs
    clean to the end, rank 1 is preempted at exactly step 2."""
    rc, rec = _faultline(capsys, "--plan", "preemption@2%1", "--steps",
                         "4", "--workdir", str(tmp_path / "r0"),
                         "--seed", "0", "--rank", "0")
    assert rc == 0 and rec["status"] == "ok" and rec["step"] == 4
    assert rec["rank"] == 0
    rc, rec = _faultline(capsys, "--plan", "preemption@2%1", "--steps",
                         "4", "--workdir", str(tmp_path / "r1"),
                         "--seed", "0", "--rank", "1")
    assert rc == 143 and rec["status"] == "preempted" and rec["step"] == 2
    assert rec["rank"] == 1


@pytest.mark.faults
def test_faultline_honors_fleet_resume_step(tmp_path, capsys, monkeypatch):
    """FLEET_RESUME_STEP pins the restore to the agreed step (never this
    rank's own newest), and an agreed step the store cannot prove is a
    loud refusal — the divergence fix the satellite names."""
    wd = str(tmp_path / "fl")
    rc, _ = _faultline(capsys, "--plan", "none", "--steps", "4",
                       "--workdir", wd, "--seed", "0")
    assert rc == 0                      # store now holds steps 2,3,4
    monkeypatch.setenv("FLEET_RESUME_STEP", "2")
    rc, rec = _faultline(capsys, "--plan", "none", "--steps", "4",
                         "--workdir", wd, "--seed", "0")
    assert rc == 0 and rec["start_step"] == 2      # not its newest (4)
    monkeypatch.setenv("FLEET_RESUME_STEP", "9")
    rc, rec = _faultline(capsys, "--plan", "none", "--steps", "9",
                         "--workdir", wd, "--seed", "0")
    assert rc == 1
    assert "not valid in this rank's store" in rec["_stderr"]


@pytest.mark.timeline
def test_poll_health_stale_beat_evidence_is_cadence_gated(tmp_path):
    """The stalled-heartbeat straggler evidence is gated twice: a rank
    that EXITED is never evidenced by its (necessarily) stopped beat,
    and a live rank's no-beat span only counts once it exceeds
    skew_time_ratio x that rank's OWN observed beat cadence — raw
    heartbeat age at a coarse beat cadence (production trainers beat
    every ~64 steps) is noise, not evidence.  A live rank whose beat
    then genuinely freezes IS named, with the stall in the journal."""
    from distributedtensorflowexample_tpu.obs import anomaly as obs_anomaly
    fleet = _fleet(tmp_path, health_path="", skew_lag_steps=3,
                   skew_time_ratio=4.0)
    fleet._stragglers, fleet._flagged = set(), set()
    fleet._beat_obs = {}

    def _poll(**kw):
        fleet._health_polled_t = -float("inf")
        fleet._poll_health("t", 0, [0, 1], **kw)

    for rank, last in ((0, 12), (1, 5)):       # rank 1 frozen at step 5
        h = obs_anomaly.RunHealth(rank=rank)
        for s in range(1, last + 1):
            h.observe_window(s, 1, 0.01)       # healthy 10ms steps
        h.write(fleet._health_path(rank))
        open(fleet._hb_path(rank), "w").close()
    _poll()                                    # learn mtimes
    assert fleet._stragglers == set()          # no cadence known yet
    time.sleep(0.05)
    now = time.time()
    for rank in (0, 1):                        # one beat each: cadence
        os.utime(fleet._hb_path(rank), (now, now))
    _poll()                                    # interval ~0.05 s learned
    assert fleet._stragglers == set()
    time.sleep(0.3)                            # rank 1's beat freezes
    now = time.time()
    os.utime(fleet._hb_path(0), (now, now))
    _poll(exited={1: 143})                     # exited: never evidence
    assert fleet._stragglers == set()
    _poll(exited={})                           # live + frozen: named
    assert fleet._stragglers == {1}
    events = _journal_events(tmp_path)
    strag = [e for e in events if e.get("kind") == "straggler"]
    assert [e["rank"] for e in strag] == [1]
    assert "stale" in strag[0]["why"]
    # a TRANSIENT detector firing (fired_step latched, firing already
    # decayed below threshold between 0.5 s polls) still annotates the
    # journal — the same fired-or-firing read obs_report renders
    h = obs_anomaly.read_health(fleet._health_path(1))
    h["flags"]["step_time_regression"] = {"firing": False,
                                          "fired_step": 4}
    obs_anomaly.write_health(fleet._health_path(1), h)
    _poll(exited={})
    assert any(e.get("kind") == "step_time_regression"
               and e.get("rank") == 1
               for e in _journal_events(tmp_path))
