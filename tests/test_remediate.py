"""The self-healing layer (resilience/remediate.py): policy mapping,
guardrail semantics (flap damping, cooldown, budget, dry-run), WAL
replay after a SIGKILL, the watcher sources, the actuator factories,
canary promotion verdicts, the heal_* ledger rows obs_query renders,
and the HEAL_* bench-record family's ratchet rules.

Inline on purpose: the policy engine is stdlib+obs, the watchers read
plain JSON files, and the one jax-touching test (rollback pinning over
a real SnapshotStore) uses the cheap softmax state — verdicts land
inside the tier-1 budget.  The end-to-end fleet drills (faultline
children, bitwise-resume parity) live in tests/test_heal_drill.py.
"""

import io
import json
import os
import sys
from contextlib import redirect_stdout

import pytest

from distributedtensorflowexample_tpu.obs import anomaly as obs_anomaly
from distributedtensorflowexample_tpu.obs import ledger as obs_ledger
from distributedtensorflowexample_tpu.resilience.remediate import (
    DEFAULT_POLICY, HEAL_ACTIONS, HEAL_EVENTS, AnomalyEvent, FleetTarget,
    Guardrails, HealRule, HealthWatcher, LedgerWatcher, Remediator,
    ServeWatcher, budget_default, cooldown_default, dry_run_default,
    flap_n_default, flap_window_default, make_rollback_actuator,
    make_slo_actuator)
from distributedtensorflowexample_tpu.resilience.supervisor import Journal

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

pytestmark = pytest.mark.heal


def _rem(tmp_path, actuators, *, clock=None, dry_run=False, scope="job1",
         policy=None, **guard_kw):
    guard_kw.setdefault("flap_n", 2)
    guard_kw.setdefault("flap_window_s", 30.0)
    guard_kw.setdefault("cooldown_s", 10.0)
    guard_kw.setdefault("budget", 4)
    return Remediator(
        Journal(str(tmp_path / "heal.jsonl")),
        str(tmp_path / "RUNS.jsonl"),
        actuators=actuators, scope=scope, dry_run=dry_run,
        policy=policy,
        guardrails=Guardrails(clock=clock, **guard_kw))


def _rows(tmp_path, event=None):
    path = tmp_path / "RUNS.jsonl"
    if not path.exists():
        return []
    rows = [json.loads(l) for l in open(path) if l.strip()]
    if event is not None:
        rows = [r for r in rows if r.get("event") == event]
    return rows


def _ev(kind="straggler", key=None, **kw):
    return AnomalyEvent(kind=kind, key=key or f"{kind}:rank1",
                        scope="job1", rank=1, **kw)


# ---- env knobs -----------------------------------------------------------

def test_env_knob_defaults(monkeypatch):
    for name in ("HEAL_DRY_RUN", "HEAL_COOLDOWN_S", "HEAL_ACTION_BUDGET",
                 "HEAL_FLAP_N", "HEAL_FLAP_WINDOW_S"):
        monkeypatch.delenv(name, raising=False)
    assert dry_run_default() is False
    assert cooldown_default() == 30.0
    assert budget_default() == 8
    assert flap_n_default() == 2
    assert flap_window_default() == 60.0
    monkeypatch.setenv("HEAL_DRY_RUN", "1")
    monkeypatch.setenv("HEAL_COOLDOWN_S", "5")
    monkeypatch.setenv("HEAL_ACTION_BUDGET", "3")
    monkeypatch.setenv("HEAL_FLAP_N", "0")      # floored at 1
    assert dry_run_default() is True
    assert cooldown_default() == 5.0
    assert budget_default() == 3
    assert flap_n_default() == 1


# ---- guardrails ----------------------------------------------------------

def test_flap_damping_suppresses_one_shot_blip(tmp_path):
    """One detection (a z-score grazing the threshold for one poll)
    never reaches the actuator; a HELD condition crosses the bar on the
    flap_n-th observation inside the window."""
    calls = []
    clock = [0.0]
    rem = _rem(tmp_path, {"evict": lambda ev: calls.append(ev) or {}},
               clock=lambda: clock[0])
    assert rem.observe(_ev()) == "flap"
    # the blip decays; the window expires with no second detection
    clock[0] += 60.0
    assert not calls
    sup = _rows(tmp_path, "heal_suppressed")
    assert sup and sup[0]["reason"] == "flap"
    # a held condition: two polls inside the window -> action
    assert rem.observe(_ev()) == "flap"        # window restarted
    clock[0] += 1.0
    assert rem.observe(_ev()) == "acted"
    assert len(calls) == 1
    assert len(_rows(tmp_path, "heal_evict")) == 1
    # exactly one detect row for the one distinct anomaly key
    assert len(_rows(tmp_path, "heal_detect")) == 1


def test_cooldown_prevents_action_storm(tmp_path):
    calls = []
    clock = [0.0]
    rem = _rem(tmp_path, {"evict": lambda ev: calls.append(ev) or {}},
               clock=lambda: clock[0], flap_n=1)
    assert rem.observe(_ev()) == "acted"
    for _ in range(5):
        clock[0] += 1.0
        assert rem.observe(_ev()) == "cooldown"
    assert len(calls) == 1
    # suppression rows are per-episode, not per-poll: ONE cooldown row
    sup = _rows(tmp_path, "heal_suppressed")
    assert [r["reason"] for r in sup] == ["cooldown"]
    clock[0] += 10.0
    assert rem.observe(_ev()) == "acted"
    assert len(calls) == 2


def test_budget_exhaustion_degrades_to_detection_only(tmp_path):
    calls = []
    clock = [0.0]
    rem = _rem(tmp_path, {"evict": lambda ev: calls.append(ev) or {}},
               clock=lambda: clock[0], flap_n=1, budget=2,
               cooldown_s=0.0)
    for i in range(2):
        assert rem.observe(_ev(key=f"s:{i}")) == "acted"
        clock[0] += 1.0
    # budget gone: loud row ONCE, then detection-only forever
    assert rem.observe(_ev(key="s:2")) == "budget"
    assert rem.observe(_ev(key="s:3")) == "budget"
    assert len(calls) == 2
    loud = _rows(tmp_path, "heal_budget_exhausted")
    assert len(loud) == 1 and loud[0]["budget"] == 2
    # detections still land (the round-10 stance survives)
    assert len(_rows(tmp_path, "heal_detect")) == 4


def test_dry_run_fires_no_actuator(tmp_path):
    calls = []
    rem = _rem(tmp_path, {"evict": lambda ev: calls.append(ev) or {}},
               dry_run=True, flap_n=1)
    assert rem.observe(_ev()) == "dry_run"
    assert rem.observe(_ev()) == "dry_run"
    assert not calls
    dry = _rows(tmp_path, "heal_dry_run")
    assert len(dry) == 1 and dry[0]["action"] == "evict"
    assert not _rows(tmp_path, "heal_evict")


def test_noop_actuator_spends_no_budget(tmp_path):
    rem = _rem(tmp_path, {"evict": lambda ev: {"noop": "nothing waits"}},
               flap_n=1, budget=2)
    assert rem.observe(_ev()) == "noop: nothing waits"
    assert rem.guardrails.actions_used == 0
    sup = _rows(tmp_path, "heal_suppressed")
    assert sup and sup[-1]["reason"].startswith("noop")


def test_errored_actuator_retries_on_cooldown_not_every_poll(tmp_path):
    """A crashing actuator anchors the cooldown (budget uncharged): a
    held condition retries once per cooldown, not once per 0.25s poll
    — which would flood the WAL with fsync'd intent/error rows."""
    calls = []
    clock = [0.0]

    def boom(ev):
        calls.append(ev)
        raise RuntimeError("down")

    rem = _rem(tmp_path, {"evict": boom}, flap_n=1, cooldown_s=10.0,
               clock=lambda: clock[0])
    assert rem.observe(_ev()) == "error"
    clock[0] += 1.0
    assert rem.observe(_ev()) == "cooldown"      # not retried per poll
    assert len(calls) == 1
    clock[0] += 10.0
    assert rem.observe(_ev()) == "error"         # retried post-cooldown
    assert len(calls) == 2
    assert rem.guardrails.actions_used == 0      # crashes spend nothing


def test_unmatched_policy_kind_is_detection_only(tmp_path):
    rem = _rem(tmp_path, {}, flap_n=1)
    assert rem.observe(_ev(kind="weird_new_kind")) == "detected"
    assert _rows(tmp_path, "heal_detect")
    assert not _rows(tmp_path, "heal_suppressed")


def test_missing_actuator_is_loud_detection_only(tmp_path):
    rem = _rem(tmp_path, {}, flap_n=1)       # policy maps, no actuator
    assert rem.observe(_ev()) == "no_actuator"
    sup = _rows(tmp_path, "heal_suppressed")
    assert sup and sup[0]["reason"] == "no_actuator"


# ---- WAL replay (SIGKILL between intent and effect) ----------------------

def test_wal_replay_reapplies_unmatched_intent_idempotently(tmp_path):
    """A remediator SIGKILLed between journaling heal_intent and
    running the actuator: the restarted incarnation re-applies the
    intent exactly once (replayed=true on its applied row), and a THIRD
    incarnation — the intent now matched — re-applies nothing."""
    jp = str(tmp_path / "heal.jsonl")
    journal = Journal(jp)
    # the dead incarnation's tail: detect + intent, no applied row
    journal.write("heal_detect", key="s:rank1", kind="straggler",
                  job="job1")
    journal.write("heal_intent", seq=1, action="evict", key="s:rank1",
                  kind="straggler", job="job1")
    calls = []
    rem = Remediator(Journal(jp), str(tmp_path / "RUNS.jsonl"),
                     actuators={"evict": lambda ev: calls.append(ev)
                                or {"ok": 1}},
                     guardrails=Guardrails(flap_n=1, budget=4,
                                           clock=lambda: 0.0))
    assert len(calls) == 1                    # re-applied exactly once
    applied = _rows(tmp_path, "heal_evict")
    assert len(applied) == 1 and applied[0]["replayed"] is True
    assert rem.guardrails.actions_used == 1   # counts against budget
    calls2 = []
    rem2 = Remediator(Journal(jp), str(tmp_path / "RUNS.jsonl"),
                      actuators={"evict": lambda ev: calls2.append(ev)
                                 or {}},
                      guardrails=Guardrails(flap_n=1, budget=4,
                                            clock=lambda: 0.0))
    assert not calls2                         # idempotent: matched now
    assert rem2.guardrails.actions_used == 1  # budget restored, once
    assert "s:rank1" in rem2._detected        # detect latch restored


def test_replay_restores_budget_and_detect_latch(tmp_path):
    clock = [0.0]
    rem = _rem(tmp_path, {"evict": lambda ev: {}}, flap_n=1, budget=2,
               cooldown_s=0.0, clock=lambda: clock[0])
    rem.observe(_ev(key="a"))
    clock[0] += 1
    rem.observe(_ev(key="b"))
    rem2 = Remediator(
        Journal(str(tmp_path / "heal.jsonl")),
        str(tmp_path / "RUNS.jsonl"),
        actuators={"evict": lambda ev: {}}, scope="job1",
        guardrails=Guardrails(flap_n=1, budget=2, cooldown_s=0.0,
                              clock=lambda: clock[0]))
    # budget already spent by the previous incarnation: first new
    # observation trips the loud exhaustion row, not an action
    assert rem2.observe(_ev(key="c")) == "budget"
    assert len(_rows(tmp_path, "heal_budget_exhausted")) == 1


def test_replay_does_not_charge_errored_actions(tmp_path):
    """Actuator failures write error rows to balance the WAL but spend
    no budget live — a restarted incarnation must not count them
    either, or N failures + a restart would wake up budget-exhausted
    with zero actions ever actually run."""
    def boom(ev):
        raise RuntimeError("actuator down")
    clock = [0.0]
    rem = _rem(tmp_path, {"evict": boom}, flap_n=1, budget=2,
               cooldown_s=0.0, clock=lambda: clock[0])
    assert rem.observe(_ev(key="a")) == "error"
    clock[0] += 1
    assert rem.observe(_ev(key="b")) == "error"
    assert rem.guardrails.actions_used == 0
    rem2 = Remediator(
        Journal(str(tmp_path / "heal.jsonl")),
        str(tmp_path / "RUNS.jsonl"),
        actuators={"evict": lambda ev: {}}, scope="job1",
        guardrails=Guardrails(flap_n=1, budget=2, cooldown_s=0.0,
                              clock=lambda: clock[0]))
    assert rem2.guardrails.actions_used == 0
    assert rem2.observe(_ev(key="c")) == "acted"


# ---- watchers ------------------------------------------------------------

def _write_health(path, rank, step, *, nan_step=None, firing=False,
                  fired_step=None, ewma=0.01):
    payload = {
        "version": obs_anomaly.HEALTH_VERSION, "kind": "rank",
        "rank": rank, "step": step, "updated_unix": 123.0,
        "flags": {
            "step_time_regression": {"firing": firing,
                                     "fired_step": fired_step},
            "nan_loss": {"firing": nan_step is not None,
                         "fired_step": nan_step},
            "loss_plateau": {"firing": False, "fired_step": None}},
        "detectors": {"step_time": {"ewma_s": ewma}}}
    obs_anomaly.write_health(str(path), payload)


def test_health_watcher_condition_held_semantics(tmp_path):
    hw = HealthWatcher(str(tmp_path / "health_rank*.json"),
                       scope="job1")
    assert hw.poll() == []
    # a firing regression emits ONE event per poll while held
    _write_health(tmp_path / "health_rank1.json", 1, 10, firing=True,
                  fired_step=8)
    evs = hw.poll()
    assert [e.kind for e in evs] == ["step_time_regression"]
    assert evs[0].rank == 1 and evs[0].step == 8
    assert evs[0].detail["updated_unix"] == 123.0
    assert hw.poll()                          # still held -> re-emitted
    # decayed blip: firing False stops the stream (fired_step latched
    # in the payload must NOT keep feeding the flap counter)
    _write_health(tmp_path / "health_rank1.json", 1, 20, firing=False,
                  fired_step=8)
    assert hw.poll() == []
    # nan is permanent: a post-mortem file still reports it
    _write_health(tmp_path / "health_rank1.json", 1, 12, nan_step=12)
    evs = hw.poll()
    assert [e.kind for e in evs] == ["nan_loss"]
    assert evs[0].step == 12


def test_health_watcher_fleet_stragglers(tmp_path):
    agg = tmp_path / "health.json"
    obs_anomaly.write_health(str(agg), {
        "version": 1, "kind": "fleet", "updated_unix": 5.0,
        "stragglers": [1],
        "skew": {"why": {"1": "lag 4 steps with regression firing"}}})
    hw = HealthWatcher(str(tmp_path / "health_rank*.json"),
                       fleet_health=str(agg), scope="job1")
    evs = hw.poll()
    assert [e.kind for e in evs] == ["straggler"]
    assert evs[0].rank == 1 and "lag 4" in evs[0].detail["why"]


def test_ledger_watcher_tails_new_rows_only(tmp_path):
    lp = str(tmp_path / "RUNS.jsonl")
    lw = LedgerWatcher(lp, scope="job1")
    assert lw.poll() == []
    obs_ledger.log_event("anomaly", path=lp, rank=1, kind="straggler",
                         fired_step=9, task="t")
    obs_ledger.log_event("run_start", path=lp, run="x")   # not a kind
    evs = lw.poll()
    assert [e.kind for e in evs] == ["straggler"]
    assert lw.poll() == []                    # consumed
    obs_ledger.log_event("rank_lost", path=lp, rank=1, task="t",
                         error="host down")
    obs_ledger.log_event("rank_lost", path=lp, rank=1, task="t",
                         error="host down")
    evs = lw.poll()
    assert [e.kind for e in evs] == ["rank_lost", "rank_lost"]
    # distinct keys per occurrence: repeated losses accumulate toward
    # the repeated-offender flap bar instead of deduping to one
    assert len({e.key for e in evs}) == 2


def test_serve_watcher_breach_and_episode_rearm(tmp_path):
    stats = {"p99_ms": 50.0, "completed": 20}
    sw = ServeWatcher(lambda: stats, breach_ms=100.0)
    assert sw.poll() == []
    stats["p99_ms"] = 300.0
    (ev,) = sw.poll()
    assert ev.kind == "serve_p99_breach" and ev.key == "serve_p99:e0"
    assert sw.poll()[0].key == "serve_p99:e0"   # same episode
    stats["p99_ms"] = 80.0
    assert sw.poll() == []                      # recovered
    stats["p99_ms"] = 400.0
    assert sw.poll()[0].key == "serve_p99:e1"   # NEW episode key
    # too few completions = no evidence, and a raising stats_fn is
    # "no data", never a crash
    assert ServeWatcher(lambda: {"p99_ms": 999, "completed": 1},
                        breach_ms=10).poll() == []
    assert ServeWatcher(lambda: 1 / 0, breach_ms=10).poll() == []


def test_serve_new_episode_gets_fresh_decision(tmp_path):
    """The episode label reaches the guardrails: a breach that provably
    recovered and breached AGAIN is a fresh decision, not a cooldown
    leftover — while re-observations of the SAME episode stay damped."""
    calls = []
    clock = [0.0]
    rem = _rem(tmp_path,
               {"slo_tighten": lambda ev: calls.append(ev) or {}},
               scope="serve", flap_n=1, cooldown_s=30.0,
               clock=lambda: clock[0])
    e0 = AnomalyEvent(kind="serve_p99_breach", key="serve_p99:e0",
                      scope="serve", episode="e0")
    assert rem.observe(e0) == "acted"
    clock[0] += 1.0
    assert rem.observe(e0) == "cooldown"        # same episode: damped
    clock[0] += 1.0
    e1 = AnomalyEvent(kind="serve_p99_breach", key="serve_p99:e1",
                      scope="serve", episode="e1")
    assert rem.observe(e1) == "acted"           # new episode: fresh
    assert len(calls) == 2
    # the episode survives the WAL: applied rows carry it
    applied = _rows(tmp_path, "heal_slo_tighten")
    assert [r.get("episode") for r in applied] == ["e0", "e1"]


# ---- actuators -----------------------------------------------------------

def test_slo_actuator_clamps_never_loosens():
    box = {"slo": 0.0}
    act = make_slo_actuator(lambda: box["slo"],
                            lambda v: box.__setitem__("slo", v), 150.0)
    detail = act(AnomalyEvent(kind="serve_p99_breach", key="k",
                              detail={"p99_ms": 400.0}))
    assert box["slo"] == 150.0 and detail["was"] == 0.0
    box["slo"] = 80.0                          # already tighter
    act(AnomalyEvent(kind="serve_p99_breach", key="k2"))
    assert box["slo"] == 80.0                  # never loosened


def test_fleet_target_noop_without_fleet():
    t = FleetTarget()
    assert t.request_stop("heal_evict") == {"noop": "no live fleet"}
    assert t.ranks() == []


def test_rollback_actuator_pins_last_good_below_fired_step(tmp_path):
    """The NaN rollback: newest COMMON valid step strictly below the
    anomaly's fired_step wins; everything newer is discarded on every
    rank — validity-checked through the real SnapshotStore."""
    import jax.numpy as jnp
    import optax

    from distributedtensorflowexample_tpu.models import build_model
    from distributedtensorflowexample_tpu.resilience.snapshot import (
        SnapshotStore, valid_steps)
    from distributedtensorflowexample_tpu.training.state import TrainState

    model = build_model("softmax")
    state = TrainState.create(model, optax.sgd(0.1, momentum=0.9),
                              jnp.zeros((2, 28, 28, 1), jnp.float32))
    template = str(tmp_path / "rank{rank}" / "snaps")
    for rank, steps in ((0, (3, 4, 5, 6)), (1, (3, 4, 5))):
        store = SnapshotStore(template.replace("{rank}", str(rank)),
                              keep=10)
        for s in steps:
            store.save(state.replace(step=jnp.asarray(s)), force=True)
    act = make_rollback_actuator(template, ranks=(0, 1))
    detail = act(AnomalyEvent(kind="nan_loss", key="n", step=5))
    # common valid = {3,4,5}; strictly below fired_step 5 -> 4
    assert detail["last_good"] == 4
    assert detail["discarded"]["0"] == [5, 6]
    assert detail["discarded"]["1"] == [5]
    assert valid_steps(template.replace("{rank}", "0")) == [3, 4]
    assert valid_steps(template.replace("{rank}", "1")) == [3, 4]
    # idempotent: the replayed intent finds the work already done
    detail2 = act(AnomalyEvent(kind="nan_loss", key="n", step=5))
    assert detail2["last_good"] == 4
    assert detail2["discarded"] == {"0": [], "1": []}


# ---- canary promotion ----------------------------------------------------

def test_canary_probe_rejects_nan_params_before_exposure():
    import numpy as np

    from distributedtensorflowexample_tpu.serving.promote import (
        Canary, params_healthy)
    good = {"w": np.ones((2, 2), np.float32),
            "ids": np.arange(4, dtype=np.int32)}    # ints never "NaN"
    bad = {"w": np.array([1.0, np.nan], np.float32)}
    assert params_healthy(good) and not params_healthy(bad)
    c = Canary(0, 1, fraction=0.5, window=4)
    assert c.state == "probing"
    assert c.admit_candidate(bad) is False
    assert c.state == "rolled_back" and "non-finite" in c.reason
    assert c.verdict() == "rollback"
    assert c.route("anything") == "baseline"    # nothing ever routes


def test_canary_p99_regression_rolls_back_clean_window_promotes():
    import numpy as np

    from distributedtensorflowexample_tpu.serving.promote import Canary
    ok_params = {"w": np.ones(2, np.float32)}
    # regression arm
    c = Canary(0, 1, fraction=0.5, window=4, p99_ratio=2.0)
    assert c.admit_candidate(ok_params)
    routes = {c.route(f"req{i}") for i in range(64)}
    assert routes == {"baseline", "canary"}     # both arms see traffic
    assert c.route("req7") == c.route("req7")   # deterministic
    for _ in range(8):
        c.observe("baseline", 0.010)
    for _ in range(4):
        c.observe("canary", 0.100)
    assert c.verdict() == "rollback"
    assert "p99" in c.reason and c.state == "rolled_back"
    # clean arm
    c2 = Canary(0, 1, fraction=0.5, window=4)
    assert c2.admit_candidate(ok_params)
    assert c2.verdict() is None                 # window still filling
    for _ in range(8):
        c2.observe("baseline", 0.010)
    for _ in range(4):
        c2.observe("canary", 0.012)
    assert c2.verdict() == "promote" and c2.state == "promoted"
    # a failed canary request rolls back regardless of latency
    c3 = Canary(0, 1, window=50)
    assert c3.admit_candidate(ok_params)
    c3.observe("canary", 0.01, ok=False)
    assert c3.verdict() == "rollback"
    assert c3.payload()["canary_failures"] == 1


def test_canary_env_knobs(monkeypatch):
    # NB: ``import ...serving.promote as promote`` would bind the
    # re-exported promote() FUNCTION (serving/__init__ shadows the
    # submodule attribute); from-imports resolve the module directly.
    from distributedtensorflowexample_tpu.serving.promote import (
        canary_fraction_default, canary_p99_ratio_default,
        canary_window_default)
    for name in ("HEAL_CANARY_FRACTION", "HEAL_CANARY_WINDOW",
                 "HEAL_CANARY_P99_RATIO"):
        monkeypatch.delenv(name, raising=False)
    assert canary_fraction_default() == 0.25
    assert canary_window_default() == 16
    assert canary_p99_ratio_default() == 2.0
    monkeypatch.setenv("HEAL_CANARY_FRACTION", "0.5")
    monkeypatch.setenv("HEAL_CANARY_WINDOW", "8")
    assert canary_fraction_default() == 0.5
    assert canary_window_default() == 8


def test_batcher_slo_seam_and_recent_p99():
    from distributedtensorflowexample_tpu.serving.queue import (
        Request, recent_p99_ms)
    reqs = []
    for i, lat in enumerate((0.01, 0.02, 0.5)):
        r = Request(rid=f"r{i}", prompt=None, max_new=1, submit_t=0.0)
        r.done_t = lat
        reqs.append(r)
    assert recent_p99_ms(reqs) == 500.0
    assert recent_p99_ms(reqs, window=2) == 500.0
    assert recent_p99_ms([]) is None


# ---- obs_query why + schema closure --------------------------------------

def _obs_query():
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import obs_query
    finally:
        sys.path.pop(0)
    return obs_query


def test_heal_events_schema_is_closed():
    """The KEEP-IN-SYNC pair's content contract: obs_query's heal
    renderer covers exactly the declared heal_* row set, and every
    action has its applied event declared."""
    obs_query = _obs_query()
    assert set(obs_query._HEAL_RENDER) == set(HEAL_EVENTS)
    for action in HEAL_ACTIONS:
        assert f"heal_{action}" in HEAL_EVENTS
    for rule in DEFAULT_POLICY.values():
        assert rule.action in HEAL_ACTIONS


def test_obs_query_why_renders_heal_rows(tmp_path):
    """`obs_query why <job>` reconstructs the remediation story from
    ledger rows alone: detections, the applied action, suppressions,
    and a self-healed verdict fragment — interleaved with sched_* rows
    in one timeline."""
    lp = str(tmp_path / "RUNS.jsonl")
    obs_ledger.log_event("sched_place", path=lp, src="sched",
                         job="bench1", ranks=1, devices=2, attempt=1)
    obs_ledger.log_event("heal_detect", path=lp, src="heal",
                         job="bench1", kind="straggler", rank=1,
                         source="fleet", key="bench1:straggler:rank1")
    obs_ledger.log_event("heal_suppressed", path=lp, src="heal",
                         job="bench1", kind="straggler", action="evict",
                         reason="flap", key="bench1:straggler:rank1")
    obs_ledger.log_event("heal_evict", path=lp, src="heal",
                         job="bench1", kind="straggler", rank=1,
                         detail={"for_job": "train1"})
    obs_ledger.log_event("sched_done", path=lp, src="sched",
                         job="bench1", rcs={"0": 0})
    obs_query = _obs_query()
    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = obs_query.main(["why", "bench1", "--ledger", lp])
    out = buf.getvalue()
    assert rc == 0
    assert "anomaly detected: straggler on rank 1" in out
    assert "SUPPRESSED by guardrail: flap" in out
    assert "HEALED by eviction" in out
    assert "self-healed 1x (evict)" in out
    assert "finally completed" in out
    # an applied row carrying error= is a crashed actuator, not a heal:
    # rendered as FAILED, never counted into the self-healed verdict
    obs_ledger.log_event("heal_rollback", path=lp, src="heal",
                         job="bench1", kind="nan_loss",
                         error="boom: store unreachable")
    buf = io.StringIO()
    with redirect_stdout(buf):
        assert obs_query.main(["why", "bench1", "--ledger", lp]) == 0
    out = buf.getvalue()
    assert "action rollback FAILED (nan_loss): boom" in out
    assert "self-healed 1x (evict)" in out      # still only the evict


# ---- run_remediated with stdlib children ---------------------------------

def test_run_remediated_heals_and_relaunches(tmp_path):
    """End-to-end over stdlib children (no jax): rank 0 writes a
    firing-regression health file on its first launch and sleeps; the
    watcher feeds the engine, the evict actuator stops the gang
    (TERM→143), and the relaunch — which sees the bumped
    SUPERVISE_ATTEMPT, the transient-fault convention — runs clean to
    rc 0.  The heal story is in the ledger."""
    import textwrap

    from distributedtensorflowexample_tpu.resilience import remediate
    from distributedtensorflowexample_tpu.resilience.fleet import (
        FleetSupervisor)
    from distributedtensorflowexample_tpu.resilience.supervisor import (
        RetryPolicy)
    child = tmp_path / "child.py"
    child.write_text(textwrap.dedent("""
        import json, os, signal, sys, time
        attempt = int(os.environ.get("SUPERVISE_ATTEMPT", "0"))
        if attempt == 0:
            signal.signal(signal.SIGTERM, lambda s, f: sys.exit(143))
            hp = os.environ["OBS_HEALTH"]
            payload = {
                "version": 1, "kind": "rank", "rank": 0, "step": 5,
                "updated_unix": time.time(),
                "flags": {"step_time_regression":
                          {"firing": True, "fired_step": 4},
                          "nan_loss": {"firing": False,
                                       "fired_step": None},
                          "loss_plateau": {"firing": False,
                                           "fired_step": None}},
                "detectors": {"step_time": {"ewma_s": 2.0}}}
            with open(hp, "w") as f:
                json.dump(payload, f)
            time.sleep(60)
        sys.exit(0)
    """))
    workdir = str(tmp_path / "fleet")
    journal = Journal(os.path.join(workdir, "fleet.jsonl"))
    ledger = os.path.join(workdir, "RUNS.jsonl")

    def make_fleet():
        return FleetSupervisor(
            1, policy=RetryPolicy(retries=0, backoff_base_s=0.01),
            journal=journal, kill_grace_s=5.0, poll_s=0.02, seed=0,
            workdir=workdir, ledger_path=ledger)

    target = remediate.FleetTarget()
    rem = remediate.Remediator(
        journal=journal, ledger_path=ledger, scope="drill",
        actuators={"evict": remediate.make_evict_actuator(target)},
        guardrails=Guardrails(flap_n=2, cooldown_s=5.0, budget=2,
                              flap_window_s=30.0))
    watchers = [remediate.HealthWatcher(
        os.path.join(workdir, "health_rank*.json"), scope="drill")]
    out = remediate.run_remediated(
        make_fleet, [sys.executable, str(child)], rem, watchers,
        target=target, name="drill", poll_s=0.1, max_heals=2)
    assert out["status"] == "ok"
    assert out["healed"] == 1
    assert out["results"][0].status == "evicted"
    assert out["results"][0].last_rcs == {0: 143}     # loss-free stop
    assert out["results"][1].status == "ok"
    rows = [json.loads(l) for l in open(ledger) if l.strip()]
    events = [r["event"] for r in rows
              if str(r.get("event", "")).startswith("heal_")]
    assert "heal_detect" in events and "heal_evict" in events
