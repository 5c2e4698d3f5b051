"""End-to-end trainer runs (tiny) — the reference's run-to-verify checks
as real tests (SURVEY.md §4 convergence smoke tests)."""

import jax
import numpy as np
import pytest

from distributedtensorflowexample_tpu.trainers import (
    trainer_local_mnist, trainer_mirrored_cifar, trainer_ps_mnist,
    trainer_sync_mnist)


def _common_flags(tmp_log_dir, extra=()):
    return ["--log_dir", tmp_log_dir, "--data_dir", "/nonexistent",
            "--dataset", "synthetic",   # explicit opt-in: no real bytes here
            "--resume", "false", "--log_every", "20", *extra]


def test_local_softmax_converges(tmp_log_dir):
    summary = trainer_local_mnist.main(_common_flags(
        tmp_log_dir, ["--train_steps", "150", "--batch_size", "64"]))
    assert summary["final_accuracy"] > 0.9
    assert summary["steps"] == 150


def test_sync_cnn_smoke(tmp_log_dir):
    summary = trainer_sync_mnist.main(_common_flags(
        tmp_log_dir, ["--train_steps", "30", "--batch_size", "16",
                      "--learning_rate", "0.02"]))
    assert summary["steps"] == 30
    assert summary["num_replicas"] == jax.device_count()
    assert np.isfinite(summary["final_accuracy"])


def test_eval_every_writes_scalars(tmp_log_dir, small_synthetic):
    """--eval_every wires the EvalHook: periodic eval_accuracy scalars in
    scalars.jsonl at the boundary-crossing steps."""
    import json
    import os

    trainer_local_mnist.main(_common_flags(
        tmp_log_dir, ["--train_steps", "40", "--batch_size", "32",
                      "--eval_every", "20"]))
    with open(os.path.join(tmp_log_dir, "scalars.jsonl")) as f:
        scalars = [json.loads(l) for l in f]
    evals = [s for s in scalars if "eval_accuracy" in s]
    assert [s["step"] for s in evals] == [20, 40]
    assert all(0.0 <= s["eval_accuracy"] <= 1.0 for s in evals)


def test_missing_real_data_is_a_crisp_error(tmp_log_dir):
    """Without --dataset synthetic, an empty --data_dir must fail by name
    (VERDICT r4 #5) — never silently train on substituted data."""
    with pytest.raises(FileNotFoundError, match="--dataset synthetic"):
        trainer_local_mnist.main(
            ["--log_dir", tmp_log_dir, "--data_dir", "/nonexistent",
             "--resume", "false", "--train_steps", "1"])


def test_dataset_trainer_mismatch_is_an_error(tmp_log_dir):
    """--dataset cifar10 on an MNIST trainer is a config error, caught
    before any data is read."""
    with pytest.raises(ValueError, match="does not match"):
        trainer_local_mnist.main(
            ["--log_dir", tmp_log_dir, "--dataset", "cifar10",
             "--resume", "false", "--train_steps", "1"])


def test_ps_role_exits_with_notice(tmp_log_dir, capsys):
    summary = trainer_ps_mnist.main(
        ["--job_name", "ps", "--task_index", "0",
         "--ps_hosts", "h:1", "--worker_hosts", "h:2"])
    assert summary["exited"]
    assert "exit" in capsys.readouterr().out.lower()


def test_mirrored_resnet_smoke(tmp_log_dir, small_synthetic):
    summary = trainer_mirrored_cifar.main(_common_flags(
        tmp_log_dir, ["--train_steps", "10", "--batch_size", "8",
                      "--warmup_steps", "2"]))
    assert summary["steps"] == 10
    assert np.isfinite(summary["final_accuracy"])


def test_sigterm_preemption_saves_and_resumes(tmp_path):
    """TPU preemption parity (SURVEY §5 failure recovery): the platform
    sends SIGTERM before reclaiming a slice — the trainer must write a
    final checkpoint, exit 143, and auto-resume on restart.  Subprocess
    test: signal handlers need the trainee's own main thread."""
    import os
    import re
    import subprocess
    import sys

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"       # CPU backend in the child
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    args = [sys.executable, "-u", "-m",
            "distributedtensorflowexample_tpu.trainers.trainer_sync_mnist",
            "--batch_size", "32", "--dataset", "synthetic",
            "--steps_per_loop", "1", "--log_every", "5",
            "--log_dir", str(tmp_path), "--learning_rate", "0.01"]
    import threading

    p = subprocess.Popen(args + ["--train_steps", "100000"], env=env,
                         cwd=root, stdout=subprocess.PIPE,
                         stderr=subprocess.STDOUT, text=True)
    saw = []
    got_step = threading.Event()

    def drain():
        # Deadline-safe: a blocking for-line read on the main thread
        # could hang the whole session if the child wedges pre-output.
        for line in p.stdout:
            saw.append(line)
            if line.startswith("step ") and "loss" in line:
                got_step.set()
        got_step.set()                 # EOF: unblock the waiter either way

    t = threading.Thread(target=drain, daemon=True)
    t.start()
    try:
        assert got_step.wait(timeout=300), "no output within deadline"
        assert p.poll() is None, (
            "trainer exited early:\n" + "".join(saw)[-2000:])
        p.terminate()                  # the platform's preemption signal
        p.wait(timeout=240)
    finally:
        if p.poll() is None:
            p.kill()
            p.wait()
        t.join(timeout=30)
    full = "".join(saw)
    assert p.returncode == 143, (p.returncode, full[-2000:])
    m = re.search(r"SIGTERM at step (\d+): checkpoint saved", full)
    assert m, full[-2000:]
    saved = int(m.group(1))
    assert saved >= 5

    r = subprocess.run(args + ["--train_steps", str(saved + 10)], env=env,
                       cwd=root, capture_output=True, text=True,
                       timeout=600)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-500:]
    assert f"resumed from checkpoint at step {saved}" in r.stdout, \
        r.stdout[-2000:]
    assert f"step {saved + 10}: final_accuracy" in r.stdout


def test_multiworker_trainer_single_process(tmp_log_dir, small_synthetic):
    """Config 5 entrypoint degenerates correctly to one process (the same
    SPMD program; the mesh simply spans one host's devices)."""
    from distributedtensorflowexample_tpu.trainers import (
        trainer_multiworker_cifar)

    summary = trainer_multiworker_cifar.main(_common_flags(
        tmp_log_dir, ["--train_steps", "6", "--batch_size", "8",
                      "--num_processes", "1", "--warmup_steps", "2",
                      "--log_every", "3"]))
    assert summary["steps"] == 6
    assert summary["num_replicas"] == jax.device_count()
    assert np.isfinite(summary["final_accuracy"])
