"""Engine front-end parity (PR 19, arXiv:1902.00465).

The tentpole's acceptance gate: the declarative Engine produces the
SAME programs, trajectories, and telemetry rows the per-caller wiring
used to hand-build — bitwise, per ported replication mode.  Each
parametrized case builds one mode twice: ground truth via the raw
``parallel/`` builders (the pre-engine wiring, reproduced here on
purpose — tests/ are exempt from the ``engine-owns-wiring`` source
rule for exactly this), and the same declaration through
``Engine(spec).build()``; the loss tape and final params must match
bit-for-bit, the compiled step's collective multiset must be
identical, and the ledger rows the full ``run()`` surface writes must
carry the schema ``tools/obs_query.py diff`` derives
``update_layout`` from.

The payoff demo (trainers/trainer_tiny_mlp.py) is held to its
promises too: ~50 lines, a full hook stack resolved via
``describe()`` (``jax.eval_shape`` — zero FLOPs, nothing compiled),
and the complete SIGTERM preemption -> resume drill.
"""

import os
import re
import subprocess
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributedtensorflowexample_tpu.config import RunConfig
from distributedtensorflowexample_tpu.data import DeviceDataset
from distributedtensorflowexample_tpu.data.synthetic import make_synthetic
from distributedtensorflowexample_tpu.engine import (
    Engine, RunSpec, resolve_update_layout)
from distributedtensorflowexample_tpu.models import build_model
from distributedtensorflowexample_tpu.obs import ledger as obs_ledger
from distributedtensorflowexample_tpu.parallel import (
    make_mesh, replicated_sharding)
from distributedtensorflowexample_tpu.parallel.async_ps import (
    make_indexed_async_train_step, make_worker_state)
from distributedtensorflowexample_tpu.parallel.bucketing import (
    init_bucketed_opt_state, resolve_bucket_bytes)
from distributedtensorflowexample_tpu.parallel.sync import (
    make_indexed_train_step)
from distributedtensorflowexample_tpu.parallel.zero3 import Zero3Layout
from distributedtensorflowexample_tpu.training.optimizers import (
    build_optimizer, update_shardings)
from distributedtensorflowexample_tpu.training.state import TrainState
from distributedtensorflowexample_tpu.utils.profiling import (
    collective_inventory_of)

pytestmark = pytest.mark.engine

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEMO = os.path.join(REPO, "distributedtensorflowexample_tpu", "trainers",
                    "trainer_tiny_mlp.py")
STEPS = 4

#: (case id, config overrides, resolved mode, update layout, collective
#: ops the mode's compiled schedule must contain — None: no fixed
#: contract to pin beyond parity, the async worker average is
#: cond-gated).
MODES = [
    ("sync_dp", {}, "sync_dp", "tree", {"all-reduce"}),
    ("sync_dp_gspmd_update", {"shard_update": True}, "sync_dp", "tree",
     {"all-reduce"}),
    ("async_ps", {"sync_mode": "async", "async_period": 2}, "async_ps",
     "tree", None),
    ("bucketed", {"bucket_grads": "4096"}, "bucketed", "tree",
     {"all-reduce"}),
    ("zero1", {"bucket_grads": "4096", "shard_update": True}, "zero1",
     "bucket_rows", {"reduce-scatter", "all-gather"}),
    ("zero3", {"bucket_grads": "4096", "shard_params": True}, "zero3",
     "zero3_rows", {"reduce-scatter", "all-gather"}),
]

_IDS = [m[0] for m in MODES]


def _cfg(**kw):
    kw.setdefault("batch_size", 8)
    kw.setdefault("train_steps", STEPS)
    kw.setdefault("learning_rate", 0.1)
    kw.setdefault("momentum", 0.9)
    kw.setdefault("dropout", 0.0)
    kw.setdefault("dataset", "synthetic")
    kw.setdefault("seed", 0)
    return RunConfig(**kw)


def _blobs(cfg, split):
    return make_synthetic(256 if split == "train" else 128, (8, 8, 1),
                          10, seed=cfg.seed,
                          sample_seed=cfg.seed + (split == "test"))


def _spec(cfg):
    return RunSpec(model="softmax", dataset="mnist", config=cfg,
                   input_fn=_blobs)


def _tape(step, ds, state, mesh, steps=STEPS):
    """Loss tape + final state + compiled collective multiset for one
    (step, dataset, state) triple — the three parity surfaces."""
    inv = collective_inventory_of(step, (state, ds.peek()), unroll=1)
    losses = []
    with mesh:
        for _ in range(steps):
            state, m = step(state, next(ds))
            losses.append(np.asarray(m["loss"]))
    jax.block_until_ready(state)
    return np.stack(losses), state, inv["multiset"]


def _ground_truth(cfg, steps=STEPS):
    """The pre-engine wiring, verbatim: the exact construction order
    (seed usage, state creation, layout pass, step factory) the
    trainers' shared runner and the bench builders hand-applied before
    PR 19 moved it into Engine."""
    mesh = make_mesh(cfg.num_devices)
    num = mesh.size
    gb = cfg.batch_size * num
    x, y = _blobs(cfg, "train")
    ds = DeviceDataset(x, y, gb, mesh=mesh, seed=cfg.seed)
    bucket_bytes = resolve_bucket_bytes(cfg.bucket_grads)
    sync = cfg.sync_mode == "sync"
    zero3_on = (cfg.shard_params and bool(bucket_bytes) and num > 1
                and sync)
    zero1_on = (bool(bucket_bytes) and cfg.shard_update and num > 1
                and sync and not zero3_on)
    model = build_model("softmax", dropout=cfg.dropout,
                        dtype=jnp.dtype(cfg.dtype), remat=cfg.remat)
    tx = build_optimizer(cfg, mesh=mesh,
                         wrap_shard_update=not (zero1_on or zero3_on))
    state = TrainState.create_sharded(model, tx, (gb,) + x.shape[1:],
                                      cfg.seed, replicated_sharding(mesh))
    z3 = None
    if zero3_on:
        z3 = Zero3Layout(state.params, bucket_bytes, mesh)
        state = state.replace(opt_state=init_bucketed_opt_state(
            tx, state.params, bucket_bytes, mesh))
        state = state.replace(params=z3.init_rows(state.params))
    elif zero1_on:
        state = state.replace(opt_state=init_bucketed_opt_state(
            tx, state.params, bucket_bytes, mesh))
    elif cfg.shard_update:
        state = state.replace(opt_state=jax.device_put(
            state.opt_state, update_shardings(state.opt_state, mesh)))
    if not sync:
        state = make_worker_state(state, num, mesh)
        step = make_indexed_async_train_step(
            num, cfg.async_period, gb, ds.steps_per_epoch, mesh=mesh,
            num_slots=ds.num_slots, bucket_bytes=bucket_bytes)
    else:
        step = make_indexed_train_step(
            gb, ds.steps_per_epoch, mesh=mesh, num_replicas=num,
            num_slots=ds.num_slots, bucket_bytes=bucket_bytes,
            bucket_shard_update=zero1_on, zero3_layout=z3,
            zero3_overlap=cfg.zero3_overlap)
    return _tape(step, ds, state, mesh, steps)


# --- the bitwise parity gate, per ported mode -------------------------------

@pytest.mark.parametrize("case,overrides,mode,layout,ops", MODES,
                         ids=_IDS)
def test_engine_build_matches_raw_wiring_bitwise(case, overrides, mode,
                                                 layout, ops):
    """Engine.build vs the raw builders: same loss tape (bitwise), same
    final params (bitwise), same compiled collective multiset."""
    gt_losses, gt_state, gt_ms = _ground_truth(_cfg(**overrides))
    eb = Engine(_spec(_cfg(**overrides))).build()
    assert eb.mode == mode
    en_losses, en_state, en_ms = _tape(eb.step, eb.ds, eb.state, eb.mesh)
    np.testing.assert_array_equal(gt_losses, en_losses)
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(a, b),
                 gt_state.params, en_state.params)
    assert gt_ms == en_ms
    if ops is not None:
        assert ops <= set(en_ms), en_ms


# --- describe(): resolution without compilation, per mode -------------------

@pytest.mark.parametrize("case,overrides,mode,layout,ops", MODES,
                         ids=_IDS)
def test_describe_and_stdlib_layout_resolution(case, overrides, mode,
                                               layout, ops):
    """describe() and the stdlib resolve_update_layout agree with the
    mode registry — including on a raw ledger config DICT, which is
    what obs_query's diff feeds it."""
    import dataclasses
    cfg = _cfg(**overrides)
    d = Engine(_spec(cfg)).describe()
    assert d["mode"] == mode
    assert d["update_layout"] == layout
    assert d["mesh_size"] == jax.device_count()
    assert resolve_update_layout(cfg, jax.device_count()) == layout
    assert resolve_update_layout(dataclasses.asdict(cfg),
                                 jax.device_count()) == layout


def test_spec_module_is_importable_without_jax():
    """The obs_query seam: resolve_update_layout must import (and run)
    in a stdlib-only process — jax poisoned outright."""
    code = ("import sys; sys.modules['jax'] = None; "
            "from distributedtensorflowexample_tpu.engine import "
            "resolve_update_layout; "
            "print(resolve_update_layout({'sync_mode': 'sync'}, 8))")
    r = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr[-1000:]
    assert r.stdout.strip() == "tree"


# --- the full run() surface: ledger row schema, per mode --------------------

@pytest.mark.parametrize("case,overrides,mode,layout,ops", MODES,
                         ids=_IDS)
def test_run_ledger_rows_per_mode(case, overrides, mode, layout, ops,
                                  tmp_path, monkeypatch):
    """Engine.run writes the run_start/run_end rows obs_query consumes:
    the resolved config + top-level mesh_size (enough to DERIVE the
    update layout — the diff table's first row), and a clean rc=0 end
    at the declared step count."""
    path = str(tmp_path / "RUNS.jsonl")
    monkeypatch.setenv("OBS_LEDGER", path)
    monkeypatch.setattr(obs_ledger, "_GLOBAL", None)
    cfg = _cfg(log_dir=str(tmp_path / "logs"), checkpoint_every=0,
               resume=False, **overrides)
    summary = Engine(_spec(cfg)).run()
    assert summary["steps"] == STEPS
    assert np.isfinite(summary["final_accuracy"])
    rows, torn = obs_ledger.read_rows(path)
    assert torn == 0
    start = [r for r in rows if r["event"] == "run_start"][0]
    end = [r for r in rows if r["event"] == "run_end"][0]
    assert {"v", "ts", "event", "run", "entrypoint", "config",
            "config_digest", "platform", "mesh_size", "num_processes",
            "dataset"} <= set(start)
    assert start["entrypoint"] == "trainer:softmax"
    assert start["mesh_size"] == jax.device_count()
    assert resolve_update_layout(start["config"],
                                 int(start["mesh_size"])) == layout
    assert end["rc"] == 0 and end["final_step"] == STEPS
    monkeypatch.setattr(obs_ledger, "_GLOBAL", None)


# --- the ~50-line payoff demo -----------------------------------------------

def test_demo_stays_small():
    """The tentpole's headline number: a new workload is a declaration,
    ~50 lines all-in."""
    with open(DEMO, encoding="utf-8") as f:
        assert len(f.read().splitlines()) <= 60


def test_demo_describe_pins_full_hook_stack(monkeypatch):
    """The demo's declaration resolves to the COMPLETE supervised
    surface — checkpoint/eval/heartbeat/metrics/anomaly hooks and the
    abstract TrainState — via eval_shape, with nothing compiled."""
    from distributedtensorflowexample_tpu.config import parse_flags
    from distributedtensorflowexample_tpu.trainers import trainer_tiny_mlp
    monkeypatch.setenv("SUPERVISE_HEARTBEAT", "/tmp/hb")
    cfg = parse_flags(["--checkpoint_every", "50", "--eval_every", "100"],
                      batch_size=32, train_steps=300, learning_rate=0.1,
                      momentum=0.9, dataset="tiny_blobs", dropout=0.0)
    spec = RunSpec(model="tiny_mlp", dataset="tiny_blobs", config=cfg,
                   model_fn=lambda cfg: trainer_tiny_mlp.TinyMLP(),
                   input_fn=trainer_tiny_mlp.blobs)
    d = Engine(spec).describe(sample_shape=(32, 8, 8, 1))
    assert d["hooks"] == ["CheckpointHook", "EvalHook", "HeartbeatHook",
                          "MetricsHook", "AnomalyHook"]
    assert d["mode"] == "sync_dp" and d["update_layout"] == "tree"
    assert d["checkpointing"] and not d["token_data"]
    shapes = jax.tree.map(lambda s: s.shape, d["abstract_state"].params)
    assert shapes == {"hidden": {"kernel": (64, 32), "bias": (32,)},
                      "logits": {"kernel": (32, 4), "bias": (4,)}}


def test_demo_sigterm_preemption_saves_and_resumes(tmp_path):
    """The acceptance drill: the 50-line declaration gets the six
    trainers' preemption story for free — SIGTERM -> final checkpoint
    -> exit 143 -> restart auto-resumes from the saved step.
    Subprocess: signal handlers need the trainee's own main thread."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"       # CPU backend in the child
    args = [sys.executable, "-u", "-m",
            "distributedtensorflowexample_tpu.trainers.trainer_tiny_mlp",
            "--batch_size", "16", "--steps_per_loop", "1",
            "--log_every", "5", "--log_dir", str(tmp_path)]

    p = subprocess.Popen(args + ["--train_steps", "100000"], env=env,
                         cwd=REPO, stdout=subprocess.PIPE,
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
                       cwd=REPO, capture_output=True, text=True,
                       timeout=600)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-500:]
    assert f"resumed from checkpoint at step {saved}" in r.stdout, \
        r.stdout[-2000:]
    assert "final accuracy:" in r.stdout
