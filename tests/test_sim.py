"""The deterministic fleet simulator (sim/ + tools/sim_run.py): the
REAL scheduler + remediator on a virtual clock.

The claims under test, in order of importance:

1. **identity** — the sim executes the unmodified control plane:
   ``type(world.scheduler) is Scheduler`` (not a subclass, not a
   reimplementation), same for the remediation engine.
2. **fidelity** — a tiny queue run BOTH ways (live: real
   FleetSupervisor + stdlib children; sim: virtual clock + SimGang)
   produces the same per-job decision sequence in the ledger, and
   ``obs_query why`` tells the same story from either run's rows.
3. **determinism** — two same-seed runs produce bitwise-identical
   ledger AND write-ahead-journal bytes, even through a storm that
   exercises shrink/grow, heal eviction, SLO preemption, and the
   serve autoscale loop.
4. **scale** — 10,000 simulated ranks on a 4-slice mesh finish inside
   the tier-1 budget (<60 s wall for ~220 virtual seconds).

Everything here asserts against rows the REAL code wrote — never
against sim-internal state.
"""

import io
import json
import os
import sys
import textwrap
import time
from contextlib import redirect_stdout

import pytest

from distributedtensorflowexample_tpu.resilience.remediate import (
    Remediator)
from distributedtensorflowexample_tpu.resilience.scheduler import (
    Job, Scheduler)
from distributedtensorflowexample_tpu.resilience.supervisor import (
    Journal, RetryPolicy)
from distributedtensorflowexample_tpu.sim import (
    SimWorld, load_scenario, sim_metrics)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

pytestmark = pytest.mark.sim


def _tool(name):
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        return __import__(name)
    finally:
        sys.path.pop(0)


def _world(tmp_path, scenario, sub="sim"):
    world = SimWorld(load_scenario(dict(scenario)), str(tmp_path / sub))
    world.run()
    return world


def _rows(ledger_path) -> list[dict]:
    with open(ledger_path) as f:
        return [json.loads(line) for line in f if line.strip()]


def _kinds(rows, job, prefix="sched_") -> list[str]:
    return [r["event"] for r in rows
            if r.get("job") == job
            and str(r.get("event", "")).startswith(prefix)]


def _evict_rows(rows, job) -> list[dict]:
    return [r for r in rows if r.get("event") == "sched_evict"
            and r.get("job") == job]


# ---- the scenario DSL refuses quietly-wrong input ------------------------

def test_scenario_validation_is_loud():
    base = {"name": "x", "jobs": [{"job": "a", "steps": 4,
                                   "est_step_time_s": 0.5}]}
    with pytest.raises(ValueError, match="unknown event kind"):
        load_scenario(dict(base, events=[{"at": 1, "kind": "meteor",
                                          "job": "a"}]))
    with pytest.raises(ValueError, match="unknown job"):
        load_scenario(dict(base, events=[{"at": 1, "kind": "host_loss",
                                          "job": "nope"}]))
    with pytest.raises(ValueError, match="outside"):
        load_scenario(dict(base, horizon_s=10,
                           events=[{"at": 99, "kind": "host_loss",
                                    "job": "a"}]))
    with pytest.raises(ValueError, match="needs steps"):
        load_scenario({"name": "x", "jobs": [{"job": "a"}]})
    with pytest.raises(ValueError, match="knee_per_replica"):
        load_scenario(dict(base, serve={"replicas": 2}))


def test_sim_max_virtual_s_ceiling_dies_loudly(tmp_path, monkeypatch):
    """SIM_MAX_VIRTUAL_S: a scenario that cannot quiesce inside the
    ceiling raises instead of spinning the event loop forever."""
    monkeypatch.setenv("SIM_MAX_VIRTUAL_S", "5")
    scenario = {"name": "livelock", "horizon_s": 50, "devices": 2,
                "jobs": [{"job": "a", "ranks": 1, "steps": 1000,
                          "est_step_time_s": 1.0}]}
    world = SimWorld(load_scenario(scenario), str(tmp_path / "lv"))
    assert world.max_virtual_s == 5.0
    with pytest.raises(RuntimeError, match="SIM_MAX_VIRTUAL_S"):
        world.run()


def test_snapshot_loss_reconstructs_then_rolls_back(tmp_path):
    """The snapshot_loss world model mirrors resilience/shardstore.py:
    a single shard loss is absorbed by the ring mirror (R=2 default —
    no progress impact); a SECOND loss on the same job exceeds
    redundancy, rolls progress back to the quorum floor pinned at the
    first loss, and relaunches through the real scheduler's eviction
    path — time is lost, steps are re-earned, steps_lost stays 0."""
    scenario = {
        "name": "snaploss", "seed": 5, "tick_s": 0.25, "horizon_s": 300,
        "devices": 2,
        "jobs": [{"job": "t", "kind": "train", "ranks": 2, "steps": 30,
                  "est_step_time_s": 0.5, "retries": 3}],
        "events": [
            {"at": 4.0, "kind": "snapshot_loss", "job": "t", "rank": 0},
            {"at": 8.0, "kind": "snapshot_loss", "job": "t", "rank": 1},
        ],
    }
    world = _world(tmp_path, scenario, "snap")
    assert world.summary["summary"]["jobs"] == {"t": "done"}
    assert world.summary["snapshots"] == {
        "losses": 2, "reconstructs": 1, "rollbacks": 1}
    assert world.hub.steps_lost() == 0.0
    # Scenarios without a scripted snapshot_loss keep their exact
    # summary shape (no "snapshots" key) — pinned by every other test's
    # summary assertions staying unchanged.


# ---- bitwise determinism through a storm ---------------------------------

def _storm_scenario() -> dict:
    """A small storm touching every decision family at once: elastic
    shrink + grow (host_loss/recover), anomaly heal eviction
    (straggler + a queued beneficiary), SLO preemption (late serve
    job), and the autoscale loop (serve_load steps)."""
    return {
        "name": "storm", "seed": 3, "tick_s": 0.25, "horizon_s": 400,
        "devices": 4,
        "jobs": [
            {"job": "t1", "kind": "train", "ranks": 2, "steps": 60,
             "est_step_time_s": 0.5, "retries": 3, "elastic": True},
            {"job": "t2", "kind": "bench", "ranks": 2, "steps": 60,
             "est_step_time_s": 0.5, "retries": 3},
            {"job": "w1", "kind": "train", "ranks": 2, "steps": 6,
             "est_step_time_s": 0.5, "start_after_s": 6.0},
            {"job": "s1", "kind": "serve", "ranks": 2, "steps": 6,
             "est_step_time_s": 0.5, "start_after_s": 8.0},
        ],
        "serve": {"replicas": 1, "knee_per_replica": 100.0,
                  "max_replicas": 4, "poll_s": 5.0, "flap_n": 2,
                  "flap_window_s": 60, "cooldown_s": 15, "budget": 8},
        "events": [
            {"at": 5.0, "kind": "host_loss", "job": "t1", "rank": 1},
            {"at": 12.0, "kind": "host_recover", "job": "t1", "rank": 1},
            {"at": 10.0, "kind": "straggler", "job": "t2", "rank": 0},
            {"at": 30.0, "kind": "serve_load", "offered_per_s": 350.0},
            {"at": 60.0, "kind": "serve_load", "offered_per_s": 20.0},
        ],
    }


def _run_bytes(tmp_path, scenario, sub):
    world = _world(tmp_path, scenario, sub)
    with open(world.ledger_path, "rb") as f:
        ledger = f.read()
    wal = os.path.join(world.workdir, "sched", "sched.jsonl")
    with open(wal, "rb") as f:
        return world, ledger, f.read()


def test_same_seed_is_bitwise_identical(tmp_path):
    scenario = _storm_scenario()
    w1, ledger1, wal1 = _run_bytes(tmp_path, scenario, "r1")
    w2, ledger2, wal2 = _run_bytes(tmp_path, scenario, "r2")
    assert ledger1 and wal1                     # the storm wrote rows
    assert ledger1 == ledger2                   # ledger: bitwise
    assert wal1 == wal2                         # WAL: bitwise
    assert w1.summary == w2.summary
    assert w1.hub.steps_lost() == 0.0           # resume forgot nothing
    # the distilled record is pure function of those bytes
    rows1 = sim_metrics.distill(w1, prefix="sim_storm")
    rows2 = sim_metrics.distill(w2, prefix="sim_storm")
    assert rows1 == rows2
    by_name = {r["metric"]: r["value"] for r in rows1}
    assert by_name["sim_storm_fleet_steps_lost"] == 0.0
    assert by_name["sim_storm_wal_unbalanced_violations"] == 0
    assert by_name["sim_storm_evictions"] >= 1
    assert by_name["sim_storm_jobs_done"] == 4


# ---- identity + the self-healed timeline, rendered like live -------------

def test_sim_runs_the_real_control_plane_and_why_reads_like_live(
        tmp_path):
    """A straggler named mid-run with a queued beneficiary: the REAL
    remediation engine detects, flap-guards, then evicts through the
    REAL scheduler WAL; the relaunch sheds the straggle and completes.
    `obs_query why` renders the same self-healed timeline the live
    straggler test asserts — same strings, same ledger grammar."""
    scenario = {
        "name": "heal", "seed": 0, "tick_s": 0.25, "horizon_s": 400,
        "devices": 2,
        "jobs": [
            {"job": "bench1", "kind": "bench", "ranks": 2, "steps": 60,
             "est_step_time_s": 0.5, "retries": 2},
            {"job": "train1", "kind": "train", "ranks": 2, "steps": 4,
             "est_step_time_s": 0.5, "priority": 20,
             "start_after_s": 6.0},
        ],
        "events": [{"at": 8.0, "kind": "straggler", "job": "bench1",
                    "rank": 1}],
    }
    world = _world(tmp_path, scenario)
    # identity: the sim did not subclass or reimplement the control
    # plane — the decisions came from the same code a live run executes
    assert type(world.scheduler) is Scheduler
    assert type(world.scheduler._remediator) is Remediator
    assert world.scheduler.fleet_factory is not None
    summary = world.summary["summary"]
    assert summary["jobs"] == {"bench1": "done", "train1": "done"}
    rows = _rows(world.ledger_path)
    evict = _evict_rows(rows, "bench1")
    assert len(evict) == 1 and evict[0]["for_job"] == "train1"
    assert "straggler" in evict[0]["why"]
    assert evict[0]["clean"] is True and evict[0]["rcs"] == {"0": 143,
                                                             "1": 143}
    heal_kinds = _kinds(rows, "bench1", prefix="heal_")
    assert "heal_detect" in heal_kinds and "heal_evict" in heal_kinds
    he = next(r for r in rows if r.get("event") == "heal_evict")
    assert he["detail"]["for_job"] == "train1"
    assert world.hub.steps_lost() == 0.0
    # the resumed placement starts at the snapshotted step
    places = [r for r in rows if r.get("event") == "sched_place"
              and r.get("job") == "bench1"]
    assert [p["resumed"] for p in places] == [False, True]
    # obs_query why: the same renderer, the same verdict strings the
    # LIVE straggler test asserts (tests/test_scheduler.py)
    obs_query = _tool("obs_query")
    buf = io.StringIO()
    with redirect_stdout(buf):
        assert obs_query.main(["why", "bench1", "--ledger",
                               world.ledger_path]) == 0
    out = buf.getvalue()
    assert "anomaly detected: straggler" in out
    assert "HEALED by eviction" in out
    assert "self-healed 1x (evict)" in out
    assert "finally completed" in out


# ---- fidelity: the same queue, live children vs simulated gangs ----------

def test_live_and_sim_make_the_same_decisions(tmp_path):
    """One tiny queue, run twice: LIVE (real FleetSupervisor, stdlib
    children, wall clock) and SIMULATED (SimGang, virtual clock).  The
    per-job sched_* decision sequences in the two ledgers must be
    identical — same admission, same eviction (same for_job, same
    clean-143 teardown), same resume, same completion."""
    py = sys.executable
    prog = str(tmp_path / "progress")
    victim = tmp_path / "victim.py"
    victim.write_text(textwrap.dedent("""
        import os, signal, sys, time
        prog = os.environ["PROG"]
        signal.signal(signal.SIGTERM, lambda s, f: sys.exit(143))
        while True:
            n = sum(1 for _ in open(prog)) if os.path.exists(prog) else 0
            if n >= 10:
                sys.exit(0)
            with open(prog, "a") as f:
                f.write(f"i{n}\\n")
            time.sleep(0.15)
    """))
    live_jobs = [
        Job(job="a", argv=[py, str(victim)], kind="bench",
            env={"PROG": prog}),
        Job(job="b", argv=[py, "-c", "pass"], kind="serve", ranks=2,
            start_after_s=0.6),
    ]
    live = Scheduler(live_jobs, devices=2,
                     workdir=str(tmp_path / "live"),
                     tick_s=0.05, poll_s=0.02, seed=0,
                     retry_policy=RetryPolicy(retries=3,
                                              backoff_base_s=0.05,
                                              backoff_max_s=0.1))
    live_summary = live.run()
    assert live_summary["jobs"] == {"a": "done", "b": "done"}
    live_rows = _rows(str(tmp_path / "live" / "RUNS.jsonl"))

    sim_scenario = {
        "name": "mirror", "seed": 0, "tick_s": 0.25, "horizon_s": 400,
        "devices": 2,
        "jobs": [
            {"job": "a", "kind": "bench", "steps": 40,
             "est_step_time_s": 0.5},
            {"job": "b", "kind": "serve", "ranks": 2, "steps": 4,
             "est_step_time_s": 0.5, "start_after_s": 5.0},
        ],
    }
    world = _world(tmp_path, sim_scenario)
    assert world.summary["summary"]["jobs"] == {"a": "done",
                                                "b": "done"}
    sim_rows = _rows(world.ledger_path)

    # the decision sequences are identical, job by job
    for job in ("a", "b"):
        assert _kinds(live_rows, job) == _kinds(sim_rows, job), job
    # and the evictions agree on every field policy decided
    ev_live, = _evict_rows(live_rows, "a")
    ev_sim, = _evict_rows(sim_rows, "a")
    for field in ("for_job", "clean", "rcs"):
        assert ev_live[field] == ev_sim[field], field
    for rows in (live_rows, sim_rows):
        places = [r for r in rows if r.get("event") == "sched_place"
                  and r.get("job") == "a"]
        assert [p["resumed"] for p in places] == [False, True]
    # the live victim's progress tape stayed exact (the sim's analogue
    # is steps_lost == 0)
    assert open(prog).read().split() == [f"i{i}" for i in range(10)]
    assert world.hub.steps_lost() == 0.0


# ---- multi-slice packing, refusal, and priced cross-slice eviction -------

def test_multi_slice_packing_refusal_and_priced_eviction(tmp_path):
    """Two 4-device slices: gangs pack best-fit onto slices (a gang
    holds ONE slice), a job wider than the widest slice is REFUSED
    with the slice table in the row, and the late serve job's eviction
    plan prices the victim's snapshot migration with the fitted
    collective model (price_s in the sched_evict row)."""
    scenario = {
        "name": "slices", "seed": 0, "tick_s": 0.25, "horizon_s": 600,
        "slices": {"podA": 4, "podB": 4},
        "collective_fit": {"alpha_s": 0.00035273878968362894,
                           "beta_bytes_per_s": 692186226.9354594},
        "jobs": [
            {"job": "t1", "kind": "train", "ranks": 4, "steps": 60,
             "est_step_time_s": 0.5, "state_bytes": 1 << 26,
             "retries": 2},
            {"job": "t2", "kind": "train", "ranks": 4, "steps": 60,
             "est_step_time_s": 0.5, "state_bytes": 1 << 26,
             "retries": 2},
            {"job": "wide", "kind": "train", "ranks": 6, "steps": 4,
             "est_step_time_s": 0.5},
            {"job": "s1", "kind": "serve", "ranks": 4, "steps": 4,
             "est_step_time_s": 0.5, "start_after_s": 6.0},
        ],
    }
    world = _world(tmp_path, scenario)
    summary = world.summary["summary"]
    assert summary["jobs"]["wide"] == "refused"
    assert sorted(v for k, v in summary["jobs"].items()
                  if k != "wide") == ["done", "done", "done"]
    rows = _rows(world.ledger_path)
    # refusal: wider than the widest slice, and the row says so
    refuse, = [r for r in rows if r.get("event") == "sched_refuse"]
    assert refuse["job"] == "wide"
    assert "widest slice has 4" in refuse["why"]
    assert refuse["slices"] == {"podA": 4, "podB": 4}
    # packing: both slices held, every placement names its slice
    places = [r for r in rows if r.get("event") == "sched_place"]
    assert all(p.get("slice") in ("podA", "podB") for p in places)
    assert {p["slice"] for p in places} == {"podA", "podB"}
    # the serve job preempted one trainer; the eviction is priced by
    # the fitted collective model (the victim's state may move slices)
    evicts = [r for r in rows if r.get("event") == "sched_evict"]
    assert len(evicts) == 1 and evicts[0]["for_job"] == "s1"
    assert evicts[0]["slice"] in ("podA", "podB")
    assert evicts[0]["price_s"] > 0.0
    assert world.hub.steps_lost() == 0.0


# ---- the autoscale policy against the measured knee ----------------------

def test_autoscale_spike_scales_up_refuses_past_max_then_scales_down(
        tmp_path):
    """The serve remediation policy end-to-end on virtual time: a
    traffic spike scales replicas up (heal_scale_up rows in the SAME
    ledger), a spike past max_replicas is REFUSED as a noop (the
    guardrail row says the ceiling bound), and sustained underload
    flap-filters before scaling down."""
    knee = 100.0
    scenario = {
        "name": "spike", "seed": 0, "tick_s": 0.25, "horizon_s": 420,
        "devices": 2,
        "jobs": [{"job": "anchor", "kind": "serve", "ranks": 2,
                  "steps": 800, "est_step_time_s": 0.5}],
        "serve": {"replicas": 1, "knee_per_replica": knee,
                  "min_replicas": 1, "max_replicas": 3, "poll_s": 5.0,
                  "flap_n": 2, "flap_window_s": 120, "cooldown_s": 20,
                  "budget": 10},
        "events": [
            {"at": 30.0, "kind": "serve_load",
             "offered_per_s": 10 * knee},        # past max capacity
            {"at": 240.0, "kind": "serve_load",
             "offered_per_s": 0.1 * knee},       # collapse
        ],
    }
    world = _world(tmp_path, scenario)
    assert type(world.serve_remediator) is Remediator
    serve = world.summary["serve"]
    assert serve["final_replicas"] == 1          # scaled down at the end
    assert serve["breach_s"] > 0.0               # the spike was real
    assert serve["actions_used"] <= 10
    rows = _rows(world.ledger_path)
    ups = [r for r in rows if r.get("event") == "heal_scale_up"]
    downs = [r for r in rows if r.get("event") == "heal_scale_down"]
    assert ups and downs
    sup = [r for r in rows if r.get("event") == "heal_suppressed"]
    reasons = [r.get("reason", "") for r in sup]
    # the ceiling refusal: overload persists at max_replicas and the
    # actuator answers noop instead of scaling into thin air
    assert any("max_replicas" in w for w in reasons)
    # the flap guardrail bound at least once (first detections filter)
    assert any(w.startswith("flap") for w in reasons)
    # determinism holds with the serve loop in play too
    world2 = _world(tmp_path, scenario, "again")
    assert world2.summary["serve"] == serve


# ---- 10,000 ranks inside the tier-1 budget -------------------------------

def test_ten_thousand_ranks_under_a_minute(tmp_path):
    """The battery's host-loss-wave scenario, run once: 24 jobs /
    10,000 ranks over four 2600-device slices, three rolling loss
    waves — the REAL scheduler drives every placement, shrink, and
    grow, and the whole thing quiesces in seconds of wall time."""
    sim_run = _tool("sim_run")
    scenario = sim_run.battery_scenarios()[0]
    assert scenario["name"] == "fleet10k"
    t0 = time.monotonic()
    world = _world(tmp_path, scenario)
    elapsed = time.monotonic() - t0
    assert elapsed < 60.0, f"10k-rank sim took {elapsed:.1f}s wall"
    assert world.summary["total_ranks"] == 10_000
    assert type(world.scheduler) is Scheduler
    summary = world.summary["summary"]
    assert summary["counts"]["done"] == 24
    assert summary["shrinks"] >= 1               # the loss waves landed
    assert world.hub.steps_lost() == 0.0
    rows = _rows(world.ledger_path)
    assert {r.get("slice") for r in rows
            if r.get("event") == "sched_place"} == {
                "podA", "podB", "podC", "podD"}
    assert sim_metrics.wal_unbalanced(
        world.scheduler.journal.events()) == 0


# ---- the full battery + record kit (slow) --------------------------------

@pytest.mark.slow
def test_battery_record_and_determinism_gate(tmp_path):
    """tools/sim_run.py --battery: all four storms, each run twice for
    the same-seed byte comparison; rc 0 means every must-be-zero
    invariant (determinism, steps_lost, WAL balance) held."""
    sim_run = _tool("sim_run")
    out = str(tmp_path / "SIM_fleet_cpu_r18.json")
    rc = sim_run.main(["--battery", "--workdir",
                       str(tmp_path / "battery"), "--out", out])
    assert rc == 0
    recs = [json.loads(line) for line in open(out)]
    by_name = {r["metric"]: r["value"] for r in recs}
    for name in ("fleet10k", "epidemic10k", "servespike", "cascade10k"):
        assert by_name[f"sim_{name}_determinism_violations"] == 0
        assert by_name[f"sim_{name}_fleet_steps_lost"] == 0.0
        assert by_name[f"sim_{name}_wal_unbalanced_violations"] == 0
    assert by_name["sim_epidemic10k_evictions"] >= 1
    assert by_name["sim_servespike_autoscale_actions"] >= 2
