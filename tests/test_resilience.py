"""resilience/ — fault injection, crash-consistent snapshots, supervised
recovery (ISSUE 3 tentpole).

The contract under test is the repo's parity discipline applied to
failure: a run interrupted by any injected fault and resumed from a
snapshot must be BITWISE identical — params, optimizer state, and the
step-by-step metric trajectory — to an uninterrupted run of the same
total steps, on CPU, with the torn-write and poisoned-state edges
refusing to restore rather than silently diverging.

Single-device, no collectives.
"""

import json
import os
import signal
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from distributedtensorflowexample_tpu.data.synthetic import make_synthetic
from distributedtensorflowexample_tpu.models import build_model
from distributedtensorflowexample_tpu.parallel.sync import make_train_step
from distributedtensorflowexample_tpu.resilience import (
    FaultInjectionHook, FaultPlan, FaultSpec, FaultyBatches, MetricsTapeHook,
    NaNGuardHook, RetryPolicy, SnapshotHook, SnapshotStore, Supervisor, Task,
    TaskQueue)
from distributedtensorflowexample_tpu.resilience.supervisor import Journal
from distributedtensorflowexample_tpu.training.hooks import HeartbeatHook
from distributedtensorflowexample_tpu.training.loop import TrainLoop
from distributedtensorflowexample_tpu.utils.signals import sigterm_flag


def _fresh_state(model_name: str = "softmax", seed: int = 0):
    from distributedtensorflowexample_tpu.training.state import TrainState
    return TrainState.create(build_model(model_name),
                             optax.sgd(0.1, momentum=0.9),
                             jnp.zeros((8, 28, 28, 1), jnp.float32),
                             seed=seed)


def _batches(n: int, batch: int = 8):
    x, y = make_synthetic(batch * n, (28, 28, 1), 10, seed=3)
    return [{"image": jnp.asarray(x[i * batch:(i + 1) * batch]),
             "label": jnp.asarray(y[i * batch:(i + 1) * batch])}
            for i in range(n)]


def _trees_equal(a, b) -> bool:
    leaves = zip(jax.tree.leaves(a), jax.tree.leaves(b), strict=True)
    return all(np.array_equal(np.asarray(x), np.asarray(y)) for x, y in leaves)


@pytest.fixture(scope="module")
def sgd_step():
    # One jitted fn for the whole module: the jit cache keys on input
    # structure, so softmax and mnist_cnn states each compile once.
    return make_train_step()


# --- SnapshotStore ---------------------------------------------------------

def test_snapshot_roundtrip_bitwise(tmp_path, sgd_step):
    state = _fresh_state()
    for b in _batches(3):
        state, _ = sgd_step(state, b)
    store = SnapshotStore(str(tmp_path / "snaps"))
    assert store.latest_valid() is None           # empty store
    empty = _fresh_state(seed=5)
    assert store.restore(empty) is empty          # identity on empty dir
    assert store.save(state, cursor={"seed": 0, "step": 3})
    assert not store.save(state)                  # duplicate step no-op
    assert store.steps() == [3]
    restored = store.restore(_fresh_state(seed=99))
    assert int(restored.step) == 3
    assert _trees_equal(restored.params, state.params)
    assert _trees_equal(restored.opt_state, state.opt_state)
    assert np.array_equal(np.asarray(restored.rng), np.asarray(state.rng))
    man = store.manifest(3)
    assert man["cursor"] == {"seed": 0, "step": 3}
    assert man["nbytes"] > 0 and "crc32" in man


def test_snapshot_rotation_keeps_newest(tmp_path, sgd_step):
    state = _fresh_state()
    store = SnapshotStore(str(tmp_path / "snaps"), keep=2)
    for b in _batches(3):
        state, _ = sgd_step(state, b)
        store.save(state)
    assert store.steps() == [2, 3]


def test_torn_payload_discarded_with_log_and_fallback(tmp_path, sgd_step,
                                                      capsys):
    """Satellite: truncate the newest snapshot; recovery falls back to
    the previous manifest-valid one and logs the discard."""
    store = SnapshotStore(str(tmp_path / "snaps"))
    state = _fresh_state()
    params_at = {}
    for b in _batches(3):
        state, _ = sgd_step(state, b)
        store.save(state)
        # host copy NOW: the next step call donates (deletes) this state
        params_at[int(state.step)] = jax.tree.map(np.asarray, state.params)
    assert store.tear_latest() == 3
    ok, why = store.validate(3)
    assert not ok and "torn" in why
    assert store.latest_valid() == 2
    err = capsys.readouterr().err
    assert "discarding snapshot 3" in err and "falling back" in err
    restored = store.restore(_fresh_state(seed=9))
    assert int(restored.step) == 2
    assert _trees_equal(restored.params, params_at[2])


def test_redo_save_heals_torn_snapshot_at_same_step(tmp_path, sgd_step,
                                                    capsys):
    """The duplicate-step dedupe must not protect a TORN snapshot from
    its own repair: after a fallback-and-redo reaches the torn step
    again, the save overwrites it."""
    store = SnapshotStore(str(tmp_path / "snaps"))
    state = _fresh_state()
    state, _ = sgd_step(state, _batches(1)[0])
    store.save(state)
    store.tear_latest()
    assert store.latest_valid() is None
    assert store.save(state)                   # heals, not deduped away
    assert "re-writing invalid snapshot 1" in capsys.readouterr().err
    assert store.latest_valid() == 1
    assert not store.save(state)               # valid now: dedupe again


def test_crc_mismatch_detected(tmp_path, sgd_step):
    """Same-length corruption (a flipped byte, not a truncation) is
    caught by the crc — size alone would pass."""
    store = SnapshotStore(str(tmp_path / "snaps"))
    state = _fresh_state()
    state, _ = sgd_step(state, _batches(1)[0])
    store.save(state)
    path = store._payload_path(1)
    blob = bytearray(open(path, "rb").read())
    blob[len(blob) // 2] ^= 0xFF
    open(path, "wb").write(bytes(blob))
    ok, why = store.validate(1)
    assert not ok and "crc32" in why
    assert store.latest_valid() is None


# --- FaultPlan -------------------------------------------------------------

def test_fault_plan_is_seed_addressable():
    a = FaultPlan.parse("preempt", 100, seed=0)
    b = FaultPlan.parse("preempt", 100, seed=0)
    c = FaultPlan.parse("preempt", 100, seed=1)
    assert [s.step for s in a.specs] == [s.step for s in b.specs]
    assert 1 <= a.specs[0].step < 100
    assert 1 <= c.specs[0].step < 100   # different seed: still in range
    # explicit pins and args parse
    p = FaultPlan.parse("preemption@3,wedge@5:0.25", 10, seed=0)
    assert [(s.kind, s.step, s.arg) for s in p.specs] == [
        ("preemption", 3, 0.0), ("wedge", 5, 0.25)]
    # torn_snapshot expands to tear + preempt at the SAME anchor step
    t = FaultPlan.parse("torn_snapshot", 50, seed=4)
    steps = {s.step for s in t.specs}
    assert len(steps) == 1 and {s.kind for s in t.specs} == {
        "torn_snapshot", "preemption"}
    with pytest.raises(ValueError, match="unknown fault kind"):
        FaultSpec("meteor", 3)


def test_faulty_batches_corrupts_exact_window():
    plan = FaultPlan.parse("nan_loss@3", 6, seed=0)
    clean = _batches(3)
    # steps_per_next=2: windows cover steps (1,2), (3,4), (5,6) — only
    # the window containing step 3 may be poisoned.
    fb = FaultyBatches(iter(clean), plan, steps_per_next=2)
    w1, w2, w3 = next(fb), next(fb), next(fb)
    assert np.isfinite(np.asarray(w1["image"])).all()
    assert np.isnan(np.asarray(w2["image"])).all()
    assert np.isfinite(np.asarray(w3["image"])).all()
    # a resumed wrapper whose start_step already passed the fault does
    # not re-fire it
    fb2 = FaultyBatches(iter(clean), plan, start_step=4)
    assert np.isfinite(np.asarray(next(fb2)["image"])).all()


def test_nan_loss_on_uint8_batch_is_refused():
    """nan_loss has no uint8 representation; degrading silently to
    legal random bytes would let the NaN-guard drill pass without the
    guard ever firing — refuse loudly instead."""
    img = np.zeros((4, 2, 2, 1), np.uint8)
    batch = {"image": img, "label": np.zeros((4,), np.int32)}
    fb = FaultyBatches(iter([batch]),
                       FaultPlan.parse("nan_loss@1", 4, seed=0))
    with pytest.raises(ValueError, match="uint8"):
        next(fb)


def test_corrupt_uint8_batch_is_deterministic():
    img = np.zeros((4, 2, 2, 1), np.uint8)
    batch = {"image": img, "label": np.zeros((4,), np.int32)}
    out1 = FaultyBatches(iter([batch]), FaultPlan.parse(
        "corrupt_batch@1", 4, seed=7))
    out2 = FaultyBatches(iter([batch]), FaultPlan.parse(
        "corrupt_batch@1", 4, seed=7))
    a, b = next(out1)["image"], next(out2)["image"]
    assert np.asarray(a).dtype == np.uint8
    assert np.array_equal(np.asarray(a), np.asarray(b))
    assert not np.array_equal(np.asarray(a), img)   # actually corrupted


# --- the resume-parity contract (satellite: mnist_cnn) ---------------------

def test_preemption_resume_parity_mnist_cnn(tmp_path, sgd_step):
    """Interrupt mnist_cnn at step 3 via injected SIGTERM preemption,
    resume from the snapshot, and assert BITWISE equality of params,
    optimizer state, and the full metric trajectory against an
    uninterrupted 6-step run (acceptance criterion; CPU only)."""
    batches = _batches(6)

    straight_tape = MetricsTapeHook()
    straight = TrainLoop(sgd_step, iter(batches), 6,
                         hooks=[straight_tape]).run(
        _fresh_state("mnist_cnn"))

    store = SnapshotStore(str(tmp_path / "snaps"))
    plan = FaultPlan.parse("preemption@3", 6, seed=0)
    tape1 = MetricsTapeHook()
    with sigterm_flag() as preempted:
        loop = TrainLoop(
            sgd_step, iter(batches), 6,
            hooks=[tape1, SnapshotHook(store, every=1, cursor={"seed": 0}),
                   FaultInjectionHook(plan)],
            should_stop=preempted)
        first = loop.run(_fresh_state("mnist_cnn"))
    assert bool(preempted) and int(first.step) == 3

    resumed = store.restore(_fresh_state("mnist_cnn", seed=42))
    assert int(resumed.step) == 3
    # the manifest's dataset cursor names the resume position
    assert store.manifest(store.latest_valid())["cursor"] == {
        "seed": 0, "step": 3}
    tape2 = MetricsTapeHook()
    resumed = TrainLoop(sgd_step, iter(batches[3:]), 6,
                        hooks=[tape2]).run(resumed)

    assert int(resumed.step) == int(straight.step) == 6
    assert _trees_equal(resumed.params, straight.params)
    assert _trees_equal(resumed.opt_state, straight.opt_state)
    # metric trajectory: interrupted + resumed tapes concatenate to the
    # uninterrupted tape EXACTLY (same steps, bit-equal losses)
    assert tape1.tape + tape2.tape == straight_tape.tape


def test_nan_guard_refuses_to_snapshot_poisoned_state(tmp_path, sgd_step):
    """An injected NaN batch kills the run at the poisoned step and the
    newest snapshot on disk is the LAST HEALTHY step — never the
    poisoned one."""
    plan = FaultPlan.parse("nan_loss@2", 6, seed=0)
    store = SnapshotStore(str(tmp_path / "snaps"))
    batches = FaultyBatches(iter(_batches(6)), plan)
    # guard BEFORE the snapshot hook: the raise must beat the save
    loop = TrainLoop(sgd_step, batches, 6,
                     hooks=[NaNGuardHook(),
                            SnapshotHook(store, every=1)])
    with pytest.raises(FloatingPointError, match="non-finite loss"):
        loop.run(_fresh_state())
    assert store.latest_valid() == 1


# --- supervisor ------------------------------------------------------------

def _script(tmp_path, name: str, body: str) -> list[str]:
    path = tmp_path / name
    path.write_text(body)
    return [sys.executable, str(path)]


def test_default_task_name_resolves_module_children():
    dn = Supervisor._default_name
    assert dn(["python", "-m",
               "distributedtensorflowexample_tpu.trainers."
               "trainer_sync_mnist", "--train_steps", "5"]) == \
        "trainer_sync_mnist"
    assert dn(["env", "JAX_PLATFORMS=cpu", "python", "bench.py"]) == \
        "bench.py"
    assert dn(["/usr/bin/python3", "tools/faultline.py"]) == "faultline.py"


def test_retry_policy_backoff_math():
    p = RetryPolicy(backoff_base_s=1.0, backoff_factor=2.0,
                    backoff_max_s=5.0, jitter=0.5)
    assert p.delay_s(0, 0.5) == 1.0          # rand 0.5 -> no jitter
    assert p.delay_s(1, 0.5) == 2.0
    assert p.delay_s(10, 0.5) == 5.0         # capped
    assert 0.5 <= p.delay_s(0, 0.0) <= 1.5   # jitter bounds
    assert p.delay_s(0, 1.0) == 1.5


def test_supervisor_retries_until_success(tmp_path):
    """Crash on attempts 0-1, succeed on attempt 2 — the supervisor's
    SUPERVISE_ATTEMPT env is what the child keys on (the same contract
    faultline's transient faults use)."""
    argv = _script(tmp_path, "flaky.py", """
import os, sys
sys.exit(0 if int(os.environ["SUPERVISE_ATTEMPT"]) >= 2 else 1)
""")
    sup = Supervisor(policy=RetryPolicy(retries=3, backoff_base_s=0.01,
                                        backoff_max_s=0.02), seed=0)
    res = sup.run(argv, name="flaky")
    assert res.status == "ok" and res.attempts == 3


def test_supervisor_exhausts_bounded_retries(tmp_path):
    argv = _script(tmp_path, "dead.py", "raise SystemExit(1)")
    sup = Supervisor(policy=RetryPolicy(retries=2, backoff_base_s=0.01,
                                        backoff_max_s=0.02), seed=0)
    res = sup.run(argv, name="dead")
    assert res.status == "exhausted" and res.attempts == 3
    assert res.returncode == 1


def test_supervisor_wedge_verdict_is_not_retried(tmp_path):
    """rc=3 is bench's watchdog 'backend provably wedged' — retrying
    burns the recovery window against a dead backend."""
    argv = _script(tmp_path, "wedged.py", "raise SystemExit(3)")
    sup = Supervisor(policy=RetryPolicy(retries=5, backoff_base_s=0.01),
                     seed=0)
    res = sup.run(argv, name="wedged")
    assert res.status == "wedged" and res.attempts == 1


def test_supervisor_heartbeat_watchdog_kills_wedged_child(tmp_path):
    """Attempt 0 beats once then wedges mid-run (the round-3 'blocked
    >60 min without raising' shape); the heartbeat watchdog kills the
    process group and the retry succeeds."""
    hb = str(tmp_path / "beat")
    argv = _script(tmp_path, "wedge_then_ok.py", """
import os, sys, time
open(os.environ["SUPERVISE_HEARTBEAT"], "a").close()   # first beat
if os.environ["SUPERVISE_ATTEMPT"] == "0":
    time.sleep(60)      # wedged mid-run: beats stop
sys.exit(0)
""")
    sup = Supervisor(policy=RetryPolicy(retries=1, backoff_base_s=0.01),
                     heartbeat_timeout_s=1.0, kill_grace_s=0.2,
                     poll_s=0.05, seed=0)
    t0 = time.monotonic()
    res = sup.run(argv, name="wedge", heartbeat_path=hb)
    assert res.status == "ok" and res.attempts == 2
    assert "heartbeat_timeout" in " ".join(res.reasons)
    assert time.monotonic() - t0 < 30       # killed in ~1s, not 60


def test_supervisor_heartbeat_not_armed_for_beatless_child(tmp_path):
    """A child that never opts into the heartbeat protocol (bench.py's
    shape: healthy but beat-less, e.g. deep in its probe-retry budget)
    must NOT be killed on heartbeat grounds — arming waits for the
    first beat; bounding a beat-less child is the wall timeout's job."""
    argv = _script(tmp_path, "beatless.py",
                   "import time; time.sleep(2.5)")
    sup = Supervisor(policy=RetryPolicy(retries=0),
                     heartbeat_timeout_s=1.0, kill_grace_s=0.2,
                     poll_s=0.05, seed=0)
    res = sup.run(argv, name="beatless",
                  heartbeat_path=str(tmp_path / "beat"))
    assert res.status == "ok", res.reasons


def test_supervisor_preemptions_do_not_consume_crash_budget(tmp_path):
    """A run preempted more times than --retries still completes: each
    143 saved state and made progress — only crashes are bounded."""
    argv = _script(tmp_path, "preempt_storm.py", """
import os, sys
sys.exit(143 if int(os.environ["SUPERVISE_ATTEMPT"]) < 3 else 0)
""")
    sup = Supervisor(policy=RetryPolicy(retries=1, backoff_base_s=0.01),
                     seed=0)
    res = sup.run(argv, name="storm")
    assert res.status == "ok" and res.attempts == 4   # 3 preempts + ok


def test_supervisor_stale_heartbeat_file_does_not_kill_fresh_child(
        tmp_path):
    """A heartbeat file left by a previous run has a stale mtime; the
    supervisor must reset it at spawn or the first poll reads the fresh
    child as wedged and kills it before it can write its first beat."""
    hb = tmp_path / "beat"
    hb.write_text("")
    stale = time.time() - 3600
    os.utime(hb, (stale, stale))
    argv = _script(tmp_path, "slow_start.py", """
import os, time
time.sleep(0.5)     # longer than poll_s: a stale-mtime bug kills here
open(os.environ["SUPERVISE_HEARTBEAT"], "a").close()
""")
    sup = Supervisor(policy=RetryPolicy(retries=0),
                     heartbeat_timeout_s=2.0, kill_grace_s=0.2,
                     poll_s=0.05, seed=0)
    res = sup.run(argv, name="slow", heartbeat_path=str(hb))
    assert res.status == "ok", res.reasons


def test_supervisor_preempted_restart_and_stdout_keep(tmp_path):
    """rc=143 (preempted-with-save) restarts immediately; an attempt
    that wrote nothing to stdout must not clobber the previous
    attempt's kept output."""
    out = str(tmp_path / "out.json")
    argv = _script(tmp_path, "preempt_then_quiet.py", """
import os, sys
if os.environ["SUPERVISE_ATTEMPT"] == "0":
    print('{"partial": true}')
    sys.exit(143)
sys.exit(0)         # attempt 1: succeeds but prints NOTHING
""")
    sup = Supervisor(policy=RetryPolicy(retries=2, backoff_base_s=0.01),
                     seed=0)
    res = sup.run(argv, name="preempt", stdout_path=out)
    assert res.status == "ok" and res.attempts == 2
    # attempt 0's partial output survived attempt 1's empty stdout
    assert json.load(open(out)) == {"partial": True}


def test_supervisor_sigterm_forwards_to_child_group(tmp_path):
    """The watcher's stale-capture sweep TERMs the SUPERVISOR's group;
    children live in their own sessions, so the supervisor must forward
    the TERM to the child group — a dead supervisor must never leave a
    live chip-holding phase orphaned behind it."""
    child_pid_file = tmp_path / "child.pid"
    runner = tmp_path / "runner.py"
    runner.write_text(f"""
import sys
sys.path.insert(0, {REPO!r})
from distributedtensorflowexample_tpu.resilience import (
    RetryPolicy, Supervisor)
sup = Supervisor(policy=RetryPolicy(retries=0), poll_s=0.05,
                 kill_grace_s=0.2, seed=0)
res = sup.run([sys.executable, "-c",
               "import os, time;"
               "open({str(child_pid_file)!r}, 'w').write(str(os.getpid()));"
               "time.sleep(60)"], name="holder")
print(res.status)
""")
    proc = subprocess.Popen([sys.executable, str(runner)],
                            stdout=subprocess.PIPE, text=True)
    deadline = time.time() + 20
    while time.time() < deadline and not child_pid_file.exists():
        time.sleep(0.1)
    child_pid = int(child_pid_file.read_text())
    proc.terminate()                       # the watcher's TERM
    out, _ = proc.communicate(timeout=30)
    assert "terminated" in out
    # the child must be gone too (forwarded kill), not orphaned
    deadline = time.time() + 10
    while time.time() < deadline:
        try:
            os.kill(child_pid, 0)
        except ProcessLookupError:
            break
        time.sleep(0.1)
    else:
        os.kill(child_pid, signal.SIGKILL)
        pytest.fail(f"child {child_pid} survived the supervisor's death")


def test_task_queue_stops_on_terminated_supervisor(tmp_path, monkeypatch):
    """A terminated supervisor must stop the queue WITHOUT journaling
    completion — the next window resumes from the interrupted task."""
    from distributedtensorflowexample_tpu.resilience import (
        supervisor as sup_mod)
    sup = Supervisor(policy=RetryPolicy(retries=0),
                     journal=Journal(str(tmp_path / "j.jsonl")), seed=0)
    monkeypatch.setattr(
        sup, "run",
        lambda *a, **k: sup_mod.SupervisedResult("terminated", None, 1))
    tasks = [Task("a", ["true"], priority=1),
             Task("b", ["true"], priority=2)]
    results = TaskQueue(tasks, sup).run()
    assert results == {"a": "terminated"}      # b never attempted
    assert sup.journal.replay()["done"] == set()


def test_journal_replay_skips_torn_tail(tmp_path):
    j = Journal(str(tmp_path / "j.jsonl"))
    j.write("task_done", task="a")
    with open(j._path, "a") as f:
        f.write('{"event": "task_done", "task": "b"')   # torn mid-write
    state = j.replay()
    assert state["done"] == {"a"} and not state["wedged"]


def test_task_queue_priority_wedge_and_journal_resume(tmp_path):
    """Priority order; a wedge verdict skips later chip-bound tasks but
    NOT the CPU-only one; a second queue over the same journal resumes
    with done/wedged state intact (the two-window capture story)."""
    jpath = str(tmp_path / "q.jsonl")
    mark = lambda n: _script(
        tmp_path, f"{n}.py",
        f"open({str(tmp_path / (n + '.ran'))!r}, 'w').write('x')")
    tasks = [
        Task("first", mark("first"), priority=10),
        Task("wedger", _script(tmp_path, "wedger.py",
                               "raise SystemExit(3)"), priority=20),
        Task("chip_bound", mark("chip"), priority=30),
        Task("cpu_only", mark("cpu"), priority=25, needs_chip=False),
        Task("gated", mark("gated"), priority=15, gate=lambda: False),
    ]
    sup = Supervisor(policy=RetryPolicy(retries=0), journal=Journal(jpath),
                     seed=0)
    results = TaskQueue(tasks, sup).run()
    assert results == {"first": "done", "gated": "skipped_gate",
                       "wedger": "wedged", "cpu_only": "done",
                       "chip_bound": "skipped_wedged"}
    assert (tmp_path / "first.ran").exists()
    assert (tmp_path / "cpu.ran").exists()
    assert not (tmp_path / "chip.ran").exists()
    # second window: same journal — done tasks skip, wedge persists
    (tmp_path / "first.ran").unlink()
    sup2 = Supervisor(policy=RetryPolicy(retries=0), journal=Journal(jpath),
                      seed=0)
    results2 = TaskQueue(tasks, sup2).run()
    assert results2["first"] == "done_prior"
    assert results2["chip_bound"] == "skipped_wedged"
    assert not (tmp_path / "first.ran").exists()    # truly skipped


def test_heartbeat_hook_touches_at_boundaries(tmp_path, sgd_step):
    hb = str(tmp_path / "beat")
    loop = TrainLoop(sgd_step, iter(_batches(3)), 3,
                     hooks=[HeartbeatHook(hb, every=1)])
    assert not os.path.exists(hb)
    loop.run(_fresh_state())
    assert os.path.exists(hb)


# --- tools/supervise.py ----------------------------------------------------

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_supervise_cli_generic_mode(tmp_path):
    """tools/supervise.py -- CMD: exit code mirrors the child's final
    verdict and the journal records each attempt."""
    script = tmp_path / "child.py"
    script.write_text("""
import os, sys
sys.exit(0 if int(os.environ["SUPERVISE_ATTEMPT"]) >= 1 else 7)
""")
    jpath = tmp_path / "j.jsonl"
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "supervise.py"),
         "--retries", "2", "--backoff_base_s", "0.01", "--seed", "0",
         "--journal", str(jpath), "--",
         sys.executable, str(script)],
        capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr
    events = [json.loads(l)["event"] for l in open(jpath)]
    assert events == ["attempt_start", "attempt_end",
                      "attempt_start", "attempt_end"]


def test_supervise_cli_derives_heartbeat_path(tmp_path):
    """--heartbeat_timeout_s without --heartbeat must still arm the
    watchdog (derived path exported as SUPERVISE_HEARTBEAT) — the
    advertised one-liner must not silently run unprotected."""
    script = tmp_path / "child.py"
    script.write_text("""
import os, sys
sys.exit(0 if os.environ.get("SUPERVISE_HEARTBEAT") else 9)
""")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "supervise.py"),
         "--retries", "0", "--heartbeat_timeout_s", "30", "--",
         sys.executable, str(script)],
        capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, (proc.stdout, proc.stderr)
    assert "heartbeat file defaulted" in proc.stderr
