"""Multi-host bootstrap over real OS processes (SURVEY.md §4: the rebuild's
version of the reference's 'N processes on localhost' launch).

Spawns 2 python processes with a reference-style TF_CONFIG; each resolves
the cluster, calls jax.distributed.initialize (Gloo CPU collectives), forms
one 2-device mesh, and runs the workload under test.
"""

import os
import socket
import subprocess
import sys

import pytest


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _spawn_two_workers(script_template: str, tmp_path,
                       devices_per_proc: int = 1, shared_logdir: bool = False,
                       unbuffered: bool = False) -> list:
    """Launch 2 OS worker processes with a reference-style TF_CONFIG and
    return the running Popens (the ONE spawn contract every multihost
    test shares).  ``devices_per_proc`` > 1 gives each process that many
    virtual CPU devices; ``shared_logdir`` formats the same {logdir} into
    both workers (the real multi-host checkpointing shape) instead of a
    per-worker scratch dir."""
    workers = [f"127.0.0.1:{_free_port()}", f"127.0.0.1:{_free_port()}"]
    procs = []
    for idx in range(2):
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"    # two ranks, one host: CPU only
        env["JAX_NUM_CPU_DEVICES"] = str(devices_per_proc)
        env["TF_CONFIG"] = (
            '{"cluster": {"worker": ["%s", "%s"]}, '
            '"task": {"type": "worker", "index": %d}}'
            % (workers[0], workers[1], idx))
        logdir = str(tmp_path / ("shared" if shared_logdir else f"w{idx}"))
        script = script_template.format(logdir=logdir,
                                        ndev=devices_per_proc)
        argv = [sys.executable] + (["-u"] if unbuffered else []) + \
            ["-c", script]
        procs.append(subprocess.Popen(
            argv, env=env, cwd=os.path.dirname(os.path.dirname(__file__)),
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True))
    return procs


def _run_two_workers(script_template: str, tmp_path,
                     devices_per_proc: int = 1,
                     timeout: int = 280) -> list[str]:
    """Spawn (see _spawn_two_workers), wait for both, assert both exited
    0, and return their outputs."""
    procs = _spawn_two_workers(script_template, tmp_path, devices_per_proc)
    outputs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=timeout)
            outputs.append(out)
    finally:
        for p in procs:   # never leak workers if one hangs
            if p.poll() is None:
                p.kill()
                p.wait()
    for idx, (p, out) in enumerate(zip(procs, outputs)):
        assert p.returncode == 0, f"worker {idx} failed:\n{out}"
    return outputs


_WORKER_SCRIPT = """
import jax
jax.config.update("jax_platforms", "cpu")
from distributedtensorflowexample_tpu.data import cifar10
cifar10._SYNTH_SIZES = {{"train": 512, "test": 256}}
from distributedtensorflowexample_tpu.trainers import trainer_multiworker_cifar
s = trainer_multiworker_cifar.main([
    "--train_steps", "4", "--batch_size", "4", "--log_dir", {logdir!r},
    "--data_dir", "/nonexistent", "--dataset", "synthetic",
    "--resume", "false", "--log_every", "2",
])
print("SUMMARY steps=%d replicas=%d acc=%.4f"
      % (s["steps"], s["num_replicas"], s["final_accuracy"]))
"""


def test_two_process_tf_config_training(tmp_path):
    outputs = _run_two_workers(_WORKER_SCRIPT, tmp_path)
    for out in outputs:
        assert "SUMMARY steps=4 replicas=2" in out, out
    # Chief-only logging: step lines from process 0 only.
    assert "step 2:" in outputs[0]
    assert "step 2:" not in outputs[1]
    # Sanity: the collective program returns one global accuracy, so both
    # processes must report the identical summary value.  (Slice
    # correctness of the resident eval is pinned by the dedicated test
    # below, which compares against the host-fed evaluate.)
    accs = [out.split("acc=")[1].split()[0] for out in outputs]
    assert accs[0] == accs[1], f"process accuracies diverged: {accs}"
    assert 0.0 <= float(accs[0]) <= 1.0


_ASYNC_SCRIPT = """
import jax
jax.config.update("jax_platforms", "cpu")
from distributedtensorflowexample_tpu.data import mnist
mnist._SYNTH_SIZES = {{"train": 256, "test": 128}}
from distributedtensorflowexample_tpu.trainers import trainer_ps_mnist
s = trainer_ps_mnist.main([
    "--train_steps", "8", "--batch_size", "8", "--global_batch", "true",
    "--steps_per_loop", "2", "--async_period", "4",
    "--log_dir", {logdir!r}, "--data_dir", "/nonexistent",
    "--dataset", "synthetic",
    "--resume", "false", "--log_every", "4", "--learning_rate", "0.05",
])
print("SUMMARY steps=%d replicas=%d acc=%.4f"
      % (s["steps"], s["num_replicas"], s["final_accuracy"]))
"""


def test_two_process_async_local_sgd(tmp_path):
    """Config 2 (async local-SGD, device-resident, fused steps) over 2 real
    OS processes: worker-tiled state spans the 2-device mesh, the periodic
    averaging all-reduce crosses the process boundary, and the consolidated
    eval agrees."""
    outputs = _run_two_workers(_ASYNC_SCRIPT, tmp_path)
    for out in outputs:
        assert "SUMMARY steps=8 replicas=2" in out, out
    accs = [out.split("acc=")[1].split()[0] for out in outputs]
    assert accs[0] == accs[1], f"process accuracies diverged: {accs}"


_EVAL_SCRIPT = """
import jax
jax.config.update("jax_platforms", "cpu")
from distributedtensorflowexample_tpu import cluster
from distributedtensorflowexample_tpu.config import RunConfig
info = cluster.resolve(RunConfig())            # TF_CONFIG from the env
cluster.maybe_initialize_distributed(info)
import optax
from distributedtensorflowexample_tpu.data import mnist
mnist._SYNTH_SIZES = {{"train": 512, "test": 256}}
from distributedtensorflowexample_tpu.data.mnist import load_mnist
from distributedtensorflowexample_tpu.models import build_model
from distributedtensorflowexample_tpu.parallel import (
    batch_sharding, make_mesh, replicated_sharding)
from distributedtensorflowexample_tpu.parallel.sync import (
    evaluate, make_resident_eval)
from distributedtensorflowexample_tpu.training.state import TrainState
mesh = make_mesh()
assert mesh.size == 2 and jax.process_count() == 2
x, y = load_mnist("/nonexistent", "test", source="synthetic")
state = TrainState.create_sharded(build_model("softmax"), optax.sgd(0.1),
                                  (64, 28, 28, 1), 3,
                                  replicated_sharding(mesh))
with mesh:
    host = evaluate(state, x, y, batch_size=64,
                    sharding=batch_sharding(mesh))
    res = make_resident_eval(x, y, batch_size=64, mesh=mesh)(state)
print("EVALS host=%.6f resident=%.6f" % (host, res))
assert abs(host - res) < 1e-9, (host, res)
print("EVAL_OK {logdir}")
"""


def test_two_process_resident_eval_matches_host_eval(tmp_path):
    """The device-resident eval's per-process COLUMN slices of the test
    split must reproduce the host-fed evaluate() exactly over 2 real
    processes — a wrong local slice shows up as a different accuracy."""
    outputs = _run_two_workers(_EVAL_SCRIPT, tmp_path)
    for out in outputs:
        assert "EVAL_OK" in out, out


# ---- N processes x M devices/process (VERDICT r2 item 4) ----------------
# All round-2 multihost coverage ran 2 procs x 1 device; the device-order
# assumptions (put_global_batch's contiguous row-range per process,
# make_resident_eval's per-process column slices, async worker tiling
# spanning processes) only bite when M > 1.

_NXM_TRAIN_SCRIPT = """
import jax
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", {ndev})
jax.config.update("jax_cpu_enable_async_dispatch", False)
from distributedtensorflowexample_tpu.data import mnist
mnist._SYNTH_SIZES = {{"train": 256, "test": 128}}
from distributedtensorflowexample_tpu.trainers import (
    trainer_ps_mnist, trainer_sync_mnist)
common = ["--train_steps", "4", "--batch_size", "8", "--global_batch",
          "true", "--data_dir", "/nonexistent", "--dataset", "synthetic",
          "--resume", "false",
          "--log_every", "2", "--learning_rate", "0.05"]
s = trainer_sync_mnist.main(
    common + ["--steps_per_loop", "2", "--log_dir", {logdir!r} + "/sync"])
print("SYNC steps=%d replicas=%d acc=%.6f"
      % (s["steps"], s["num_replicas"], s["final_accuracy"]))
s = trainer_sync_mnist.main(
    common + ["--device_data", "off", "--log_dir", {logdir!r} + "/host"])
print("HOSTFED steps=%d replicas=%d acc=%.6f"
      % (s["steps"], s["num_replicas"], s["final_accuracy"]))
s = trainer_ps_mnist.main(
    common + ["--steps_per_loop", "2", "--async_period", "2",
              "--log_dir", {logdir!r} + "/async"])
print("ASYNC steps=%d replicas=%d acc=%.6f"
      % (s["steps"], s["num_replicas"], s["final_accuracy"]))
s = trainer_sync_mnist.main(
    common + ["--steps_per_loop", "2", "--data_sharding", "sharded",
              "--log_dir", {logdir!r} + "/shard"])
print("SHARDED steps=%d replicas=%d acc=%.6f"
      % (s["steps"], s["num_replicas"], s["final_accuracy"]))
"""


def test_nxm_training_all_modes(tmp_path):
    """2 procs x 4 devices: sync device-resident, sync host-fed
    (Batcher + put_local_batch), async local-SGD (8 worker tiles
    spanning 2 processes), and sharded-resident (each process uploads
    only ITS devices' row blocks) all train and agree bitwise across
    processes."""
    # 4 trainings x several compiles per worker: give the launch the time
    # budget of four ordinary multihost tests (was 840 for three).
    outputs = _run_two_workers(_NXM_TRAIN_SCRIPT, tmp_path,
                               devices_per_proc=4, timeout=1120)
    for tag in ("SYNC", "HOSTFED", "ASYNC", "SHARDED"):
        lines = [l for out in outputs for l in out.splitlines()
                 if l.startswith(tag + " ")]
        assert len(lines) == 2, outputs
        assert all("steps=4 replicas=8" in l for l in lines), lines
        accs = {l.split("acc=")[1] for l in lines}
        assert len(accs) == 1, f"{tag} diverged across processes: {lines}"


_PREEMPT_SCRIPT = """
import jax
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_cpu_enable_async_dispatch", False)
from distributedtensorflowexample_tpu.data import mnist
mnist._SYNTH_SIZES = {{"train": 256, "test": 128}}
from distributedtensorflowexample_tpu.trainers import trainer_sync_mnist
trainer_sync_mnist.main([
    "--train_steps", "100000", "--batch_size", "8", "--global_batch",
    "true", "--steps_per_loop", "1", "--log_every", "5",
    "--log_dir", {logdir!r}, "--data_dir", "/nonexistent",
    "--dataset", "synthetic", "--resume", "true",
    "--learning_rate", "0.05",
])
"""


def test_two_process_preemption_consensus(tmp_path):
    """SIGTERM delivered to ONE worker only: the per-boundary stop
    consensus (process_allgather of the local flag) must stop BOTH
    processes at the same step — the un-signaled worker exits 143 too,
    and the collective checkpoint save (ONE shared --log_dir, the real
    multi-host deployment shape) completes instead of hanging in a
    half-abandoned psum."""
    import threading

    procs = _spawn_two_workers(_PREEMPT_SCRIPT, tmp_path,
                               shared_logdir=True, unbuffered=True)
    logs = [[], []]
    progressed = threading.Event()

    def drain(i):
        for line in procs[i].stdout:
            logs[i].append(line)
            if i == 0 and line.startswith("step ") and "loss" in line:
                progressed.set()
        if i == 0:
            progressed.set()           # EOF: unblock the waiter

    threads = [threading.Thread(target=drain, args=(i,), daemon=True)
               for i in range(2)]
    try:
        for t in threads:
            t.start()
        assert progressed.wait(timeout=300), "no training progress"
        assert procs[0].poll() is None, "".join(logs[0])[-2000:]
        procs[0].terminate()           # ONLY worker 0 is preempted
        for p in procs:
            p.wait(timeout=280)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait()
        for t in threads:
            t.join(timeout=30)
    out0, out1 = "".join(logs[0]), "".join(logs[1])
    assert procs[0].returncode == 143, (procs[0].returncode, out0[-2000:])
    assert procs[1].returncode == 143, (procs[1].returncode, out1[-2000:])
    # Chief (worker 0) announces the save; the collective checkpoint
    # landed in the shared directory.
    assert "SIGTERM at step" in out0, out0[-2000:]
    assert "SIGTERM at step" not in out1          # chief-only notice
    saved_dirs = [d for d in (tmp_path / "shared" / "checkpoints").iterdir()
                  if d.name.isdigit()]
    assert saved_dirs, out0[-1000:]


def test_divergent_checkpoint_dirs_fail_by_name(tmp_path):
    """Processes pointed at DIFFERENT --log_dir values with checkpointing
    on must fail with the named error up front — the alternative is a
    split-brain Orbax barrier that wedges the first save (observed)."""
    procs = _spawn_two_workers(_PREEMPT_SCRIPT, tmp_path, unbuffered=True)
    outs = []
    try:
        for p in procs:
            outs.append(p.communicate(timeout=280)[0])
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait()
    for idx, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode != 0, f"worker {idx} unexpectedly succeeded"
        assert "differs across the 2 processes" in out, (idx, out[-2000:])


_NXM_EVAL_SCRIPT = """
import numpy as np
import jax
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", {ndev})
jax.config.update("jax_cpu_enable_async_dispatch", False)
from distributedtensorflowexample_tpu import cluster
from distributedtensorflowexample_tpu.config import RunConfig
info = cluster.resolve(RunConfig())            # TF_CONFIG from the env
cluster.maybe_initialize_distributed(info)
import optax
from distributedtensorflowexample_tpu.data import mnist
mnist._SYNTH_SIZES = {{"train": 512, "test": 256}}
from distributedtensorflowexample_tpu.data.mnist import load_mnist
from distributedtensorflowexample_tpu.data.pipeline import put_global_batch
from distributedtensorflowexample_tpu.models import build_model
from distributedtensorflowexample_tpu.parallel import (
    batch_sharding, make_mesh, replicated_sharding)
from distributedtensorflowexample_tpu.parallel.sync import (
    evaluate, make_resident_eval)
from distributedtensorflowexample_tpu.training.state import TrainState
mesh = make_mesh()
assert mesh.size == 2 * {ndev} and jax.process_count() == 2

# put_global_batch: every process holds the same global array; each of the
# 2*M shards must get exactly its global row-range (the contiguous
# row-range-per-process assumption).
x = np.arange(16 * 3, dtype=np.float32).reshape(16, 3)
arr = put_global_batch({{"v": x}}, batch_sharding(mesh))["v"]
for shard in arr.addressable_shards:
    np.testing.assert_array_equal(np.asarray(shard.data), x[shard.index])
print("PUT_GLOBAL_OK")

xs, ys = load_mnist("/nonexistent", "test", source="synthetic")
state = TrainState.create_sharded(build_model("softmax"), optax.sgd(0.1),
                                  (64, 28, 28, 1), 3,
                                  replicated_sharding(mesh))
with mesh:
    host = evaluate(state, xs, ys, batch_size=64,
                    sharding=batch_sharding(mesh))
    res = make_resident_eval(xs, ys, batch_size=64, mesh=mesh)(state)
print("EVALS host=%.6f resident=%.6f" % (host, res))
assert abs(host - res) < 1e-9, (host, res)
print("EVAL_OK {logdir}")
"""


def test_nxm_put_global_batch_and_resident_eval(tmp_path):
    """2 procs x 4 devices: put_global_batch's per-shard rows are exactly
    the global row-ranges, and the resident eval's column slices reproduce
    the host-fed evaluate bitwise."""
    outputs = _run_two_workers(_NXM_EVAL_SCRIPT, tmp_path,
                               devices_per_proc=4, timeout=560)
    for out in outputs:
        assert "PUT_GLOBAL_OK" in out, out
        assert "EVAL_OK" in out, out
