"""The Engine: one replicated-execution front-end (ROADMAP direction 4,
arXiv:1902.00465).

``Engine(spec).run()`` is what every reference trainer's shared runner
used to be — resolve cluster flags → (maybe) jax.distributed.initialize
→ build the mesh → data → model/optimizer/state (sharded at init) →
replication-mode layout passes → hooks → loop → final eval — now owned
by ONE object driven by a declarative
:class:`~distributedtensorflowexample_tpu.engine.spec.RunSpec`.
``Engine(spec).build()`` is the same construction stack cut down to the
measuring surface: dataset + state + compiled step, no hooks, no eval,
no checkpoint (benchmarks/kinds/train.py, chip_smoke.py).  Both paths
MOVED here from trainers/common.py with operation order preserved (seed
usage, state-creation order, layout passes), so loss tapes and
collective multisets are bitwise-identical to the pre-engine wiring
(tests/test_engine.py pins this per mode).

The replication strategies themselves still live in parallel/ — the
Engine selects and composes them (spec.MODES declares each mode's
update layout + graftlint HLO contract); the ``engine-owns-wiring``
source rule (analysis/src_lint.py) keeps raw step construction from
leaking back outside these two packages.
"""

from __future__ import annotations

import dataclasses
import functools
import os

import jax
import jax.numpy as jnp
import numpy as np

from distributedtensorflowexample_tpu import cluster
from distributedtensorflowexample_tpu.config import RunConfig
from distributedtensorflowexample_tpu.data import (
    Batcher, DeviceDataset, DevicePrefetcher, load_cifar10, load_lm,
    load_mnist)
from distributedtensorflowexample_tpu.data.cifar10 import (
    augment as cifar_augment)
from distributedtensorflowexample_tpu.engine.spec import (
    RunSpec, resolve_mode)
from distributedtensorflowexample_tpu.models import build_model
from distributedtensorflowexample_tpu.parallel import (
    batch_sharding, make_mesh, replicated_sharding)
from distributedtensorflowexample_tpu.parallel.async_ps import (
    consolidate, make_async_train_step, make_indexed_async_train_step,
    make_worker_state)
from distributedtensorflowexample_tpu.parallel.sync import (
    evaluate, make_indexed_train_step, make_resident_eval, make_train_step)
from distributedtensorflowexample_tpu.refusal import ModeRefusal
from distributedtensorflowexample_tpu.runtime import (
    device_line, device_summary, enable_compilation_cache)
from distributedtensorflowexample_tpu.training.checkpoint import (
    CheckpointManager)
from distributedtensorflowexample_tpu.training.hooks import (
    CheckpointHook, EvalHook)
from distributedtensorflowexample_tpu.training.loop import TrainLoop
from distributedtensorflowexample_tpu.training.metrics import MetricsLogger
from distributedtensorflowexample_tpu.training.optimizers import (
    build_optimizer)
from distributedtensorflowexample_tpu.training.state import TrainState
from distributedtensorflowexample_tpu.utils.profiling import ProfilerHook

_SAMPLE_SHAPES = {"mnist": (28, 28, 1), "cifar10": (32, 32, 3)}

# Auto --steps_per_loop unroll ceiling.  64 amortizes a ~1.4 ms per-call
# dispatch latency to <2% of even MNIST-scale step times while keeping
# compiled programs small and hook/log boundaries responsive; the bench's
# much larger sweeps (unroll in the thousands) stay a bench concern.
_AUTO_UNROLL_CAP = 64

# Multi-host preemption consensus cadence in GLOBAL steps: how stale the
# unanimous-stop decision may be.  Tens of steps of detection latency is
# negligible against a preemption grace period, and polling every
# boundary at unroll 1 would add a cross-host sync to every step.
_CONSENSUS_POLL_STEPS = 64


def auto_steps_per_loop(remaining: int, steps_per_epoch: int,
                        cap: int = _AUTO_UNROLL_CAP,
                        intervals: tuple = (), start: int = 0) -> int:
    """The unroll --steps_per_loop=0 selects (VERDICT r4 #4): the largest
    value <= min(cap, steps_per_epoch, remaining) that divides the
    remaining step count, every positive interval in ``intervals``
    (log/eval/checkpoint periods), AND the resumed ``start`` step.
    Dividing the remainder means the default CLI can never trip the
    steps-must-be-a-multiple error a hand-picked value is validated
    against below; dividing the intervals (and the start, since call
    boundaries are ``start + k*d``) means periodic hooks fire ON their
    exact interval marks rather than drifting to the next boundary after
    each mark.  A user asking for --log_every 1 therefore gets genuine
    per-step logging."""
    import math
    g = math.gcd(remaining, start)      # gcd(x, 0) == x: fresh runs free
    for iv in intervals:
        if iv and iv > 0:
            g = math.gcd(g, iv)
    hi = min(cap, steps_per_epoch, remaining)
    for d in range(min(hi, g), 1, -1):
        if g % d == 0:
            return d
    return 1


def _load_dataset(cfg: RunConfig, name: str, split: str):
    """``name`` is the workload's dataset family (shapes, model);
    ``cfg.dataset`` selects the SOURCE: the real bytes (default — missing
    files are a crisp error), or ``synthetic`` as the explicit opt-in to
    the deterministic synthetic split (VERDICT r4 #5: no silent
    substitution on the trainer surface)."""
    if cfg.dataset not in (name, "synthetic"):
        raise ModeRefusal(
            f"--dataset {cfg.dataset!r} does not match this trainer's "
            f"dataset {name!r}; pass --dataset {name} (real bytes in "
            f"--data_dir) or --dataset synthetic")
    source = "synthetic" if cfg.dataset == "synthetic" else "real"
    if name == "mnist":
        return load_mnist(cfg.data_dir, split, seed=cfg.seed, source=source)
    if name == "cifar10":
        return load_cifar10(cfg.data_dir, split, seed=cfg.seed,
                            source=source)
    if name == "lm":
        # Token corpus for the transformer-LM family: both sources
        # resolve to the deterministic synthetic chain (no real-corpus
        # format exists yet — data/lm.py), so no fallback warning fires.
        return load_lm(cfg.data_dir, split, seed=cfg.seed, source=source)
    raise ValueError(f"unknown dataset {name!r}")


def _refuse_incompatible_restore(saved: dict | None, current: dict,
                                 log_dir: str, is_chief: bool) -> None:
    """Named refusal for structurally-incompatible restores (reference
    parity: a Saver restore into a mismatched graph also failed — ours
    names the topology fact instead of an Orbax shape error).  ``saved``
    is None for pre-metadata checkpoints: restore proceeds, Orbax itself
    still catches true layout mismatches."""
    if not saved:
        return
    if saved.get("sync_mode", current["sync_mode"]) != current["sync_mode"]:
        raise ModeRefusal(
            f"checkpoint in {log_dir}/checkpoints was written by a "
            f"sync_mode={saved['sync_mode']!r} run; restoring it into "
            f"sync_mode={current['sync_mode']!r} would mismatch the state "
            f"layout (worker-tiled vs replicated). Use a fresh --log_dir "
            f"or rerun with --sync_mode={saved['sync_mode']}")
    # Pre-PR-6 checkpoints carry no update_layout key; the only layout
    # they can hold is the params-shaped tree — default to that, never
    # to the CURRENT run's layout (which would wave a legacy checkpoint
    # into a bucket_rows run and die on an unnamed Orbax mismatch).
    saved_layout = saved.get("update_layout", "tree")
    if saved_layout != current.get("update_layout"):
        raise ModeRefusal(
            f"checkpoint in {log_dir}/checkpoints holds "
            f"{saved_layout!r} optimizer state; this run uses "
            f"{current['update_layout']!r} (--bucket_grads with "
            f"--shard_update stores per-bucket flat rows instead of the "
            f"params-shaped tree; --shard_params stores the PARAMS as "
            f"rows too — zero3_rows). Resume with the writing run's "
            f"knobs or start fresh with a new --log_dir")
    if (saved_layout.endswith("_rows")
            and saved.get("mesh_size") is not None
            and saved["mesh_size"] != current["mesh_size"]):
        # Bucket rows are a function of D ([D, ceil(n/D)] layout +
        # padding): a different mesh size is at best an unnamed Orbax
        # shape error and at worst — when the padded totals happen to
        # match — a silently PERMUTED momentum (or, for zero3_rows,
        # PARAM) restore.
        raise ModeRefusal(
            f"checkpoint in {log_dir}/checkpoints holds {saved_layout} "
            f"state laid out for mesh_size="
            f"{saved['mesh_size']}; this run has mesh_size="
            f"{current['mesh_size']} — the 1/D row layout is structural. "
            f"Resume on {saved['mesh_size']} devices or start fresh "
            f"with a new --log_dir")
    if (saved.get("num_workers") is not None
            and saved["num_workers"] != current["num_workers"]):
        raise ModeRefusal(
            f"checkpoint in {log_dir}/checkpoints holds async worker-tiled "
            f"state for num_workers={saved['num_workers']}; this run has "
            f"num_workers={current['num_workers']} (mesh size "
            f"{current['mesh_size']}). The leading worker axis is "
            f"structural — resume on {saved['num_workers']} devices or "
            f"start fresh with a new --log_dir")
    if (is_chief and saved.get("mesh_size") is not None
            and saved["mesh_size"] != current["mesh_size"]):
        print(f"note: resuming a mesh_size={saved['mesh_size']} checkpoint "
              f"on mesh_size={current['mesh_size']} (fine for sync mode: "
              f"state is replicated)", flush=True)


def apply_update_layout(state, tx, *, update_layout: str,
                        bucket_bytes=None, mesh=None,
                        shard_update: bool = False):
    """The ONE state re-layout pass every construction path shares
    (trainers, bench builders, serving promotion): take the
    replicated-tree state ``create_sharded`` laid out and re-lay it into
    the mode's working layout, so the step's donation aliases from call
    one.  Returns ``(state, zero3_layout_or_None)``.

    * ``zero3_rows`` — optimizer state FIRST (it reads the full params),
      then the params themselves become 1/D bucket rows; init_rows
      DONATES the replicated tree, so full params stop being resident
      right here.
    * ``bucket_rows`` — optimizer state as per-bucket flat rows (the
      bucketed ZeRO-1 schedule); params stay replicated.
    * ``tree`` + ``shard_update`` — re-lay the optimizer state into the
      GSPMD constraint form's 1/D-per-device sharding (no
      replicated->sharded recompile on call two).
    """
    if update_layout == "zero3_rows":
        from distributedtensorflowexample_tpu.parallel.bucketing import (
            init_bucketed_opt_state)
        from distributedtensorflowexample_tpu.parallel.zero3 import (
            Zero3Layout)
        zero3_layout = Zero3Layout(state.params, bucket_bytes, mesh)
        state = state.replace(opt_state=init_bucketed_opt_state(
            tx, state.params, bucket_bytes, mesh))
        state = state.replace(params=zero3_layout.init_rows(state.params))
        return state, zero3_layout
    if update_layout == "bucket_rows":
        from distributedtensorflowexample_tpu.parallel.bucketing import (
            init_bucketed_opt_state)
        state = state.replace(opt_state=init_bucketed_opt_state(
            tx, state.params, bucket_bytes, mesh))
        return state, None
    if shard_update:
        from distributedtensorflowexample_tpu.training.optimizers import (
            update_shardings)
        state = state.replace(opt_state=jax.device_put(
            state.opt_state, update_shardings(state.opt_state, mesh)))
    return state, None


@dataclasses.dataclass
class EngineBuild:
    """What ``Engine.build`` hands the bench surface: the compiled step
    + its dataset + the laid-out state, plus the resolution facts the
    caller used to recompute by hand."""

    step: object
    ds: object
    state: object
    mesh: object
    unroll: int
    global_batch: int
    num_replicas: int
    mode: str
    bucket_bytes: object = None
    zero3_layout: object = None


class Engine:
    """Runs a :class:`RunSpec`.  ``run()`` is the full supervised
    training surface (hooks, checkpoints, telemetry, preemption);
    ``build()`` is the bench surface (step + data + state only);
    ``describe()`` resolves the declaration without compiling anything.
    """

    def __init__(self, spec: RunSpec):
        self.spec = spec

    # --- the declarative seams (RunSpec callables or the registries) ---

    def _model(self, cfg: RunConfig):
        if self.spec.model_fn is not None:
            return self.spec.model_fn(cfg)
        return build_model(self.spec.model, dropout=cfg.dropout,
                           dtype=jnp.dtype(cfg.dtype), remat=cfg.remat)

    def _optimizer(self, cfg: RunConfig, mesh, wrap_shard_update: bool):
        if self.spec.optimizer_fn is not None:
            return self.spec.optimizer_fn(cfg, mesh, wrap_shard_update)
        return build_optimizer(cfg, mesh=mesh,
                               wrap_shard_update=wrap_shard_update)

    def _input(self, cfg: RunConfig, split: str):
        if self.spec.input_fn is not None:
            return self.spec.input_fn(cfg, split)
        return _load_dataset(cfg, self.spec.dataset, split)

    # --- knob resolution (the exact cascade run_training applied) ------

    def _resolve_flags(self, cfg: RunConfig, num_replicas: int):
        """Pure flag validation BEFORE data loading: a bogus flag should
        fail by name, not after (or instead of) a multi-second dataset
        read.  Returns ``(bucket_bytes, zero3_on, bucket_zero1)``."""
        if cfg.sync_mode == "async" and cfg.fused_optimizer:
            # The async step vmaps the optimizer apply over virtual
            # workers; a pallas_call has no batching rule XLA can
            # partition over the worker-sharded axis. (The Pallas CE
            # head IS supported in async — it runs on the flattened
            # batch outside the vmap.)
            raise ModeRefusal(
                "--fused_optimizer is not supported with sync_mode=async")
        if cfg.device_data not in ("auto", "on", "off"):
            raise ValueError(f"unknown device_data {cfg.device_data!r}")
        # Token datasets (the transformer-LM family) are integer splits:
        # the host Batcher/prefetch path is a float-image pipeline whose
        # uint8 convention means "quantized pixels" — dequantizing ids
        # to floats would silently train on garbage, so the off-path is
        # refused by name instead.
        if self.spec.resolved_token_data() and cfg.device_data == "off":
            raise ModeRefusal(
                "the lm dataset is an integer token split and runs on the "
                "device-resident input path only; --device_data off selects "
                "the host float-image Batcher, which would dequantize token "
                "ids into pixels. Drop --device_data off")
        if cfg.sync_mode not in ("sync", "async"):
            raise ValueError(f"unknown sync_mode {cfg.sync_mode!r}")
        if cfg.data_sharding not in ("replicated", "sharded"):
            raise ValueError(f"unknown data_sharding {cfg.data_sharding!r}")
        if cfg.data_sharding == "sharded" and cfg.device_data == "off":
            raise ModeRefusal("--data_sharding sharded requires the "
                             "device-resident input path (device_data)")
        from distributedtensorflowexample_tpu.data.device_dataset import (
            DEQUANT_IMPLS)
        if cfg.dequant_impl not in DEQUANT_IMPLS:
            raise ValueError(f"unknown dequant_impl {cfg.dequant_impl!r} "
                             f"(one of {DEQUANT_IMPLS})")
        if cfg.dequant_impl == "pallas" and (cfg.device_data == "off"
                                             or cfg.data_sharding
                                             == "sharded"):
            raise ModeRefusal("--dequant_impl pallas fuses the on-device "
                             "row gather with the dequant; it requires the "
                             "replicated device-resident input path")
        if cfg.shard_update and cfg.sync_mode == "async":
            raise ModeRefusal(
                "--shard_update shards ONE replicated update across the "
                "mesh; async mode's state is already worker-tiled (each "
                "device owns its workers' whole update) — there is no "
                "cross-replica redundancy to shard away")
        from distributedtensorflowexample_tpu.parallel.bucketing import (
            resolve_bucket_bytes)
        bucket_bytes = resolve_bucket_bytes(cfg.bucket_grads)  # by name
        if bucket_bytes and cfg.fused_optimizer:
            raise ModeRefusal(
                "--bucket_grads restructures the gradient reduction around "
                "the optimizer apply; the Pallas fused apply is a custom "
                "call with its own layout contract — use one or the other")
        if cfg.shard_params and cfg.sync_mode != "sync":
            raise ModeRefusal(
                "--shard_params shards the sync data-parallel step's "
                "params across the mesh; async mode's state is "
                "worker-tiled (each device already owns its workers' "
                "whole copy) — there is no cross-replica redundancy to "
                "shard away")
        if cfg.shard_params and not bucket_bytes:
            raise ModeRefusal(
                "--shard_params lays params out in the knee-sized "
                "dtype-homogeneous bucket rows; pass --bucket_grads (auto, "
                "or a byte cap) to size them")
        # ZeRO-3 (--shard_params, parallel/zero3.py) subsumes the ZeRO-1
        # bucket schedule: params, grads AND optimizer state all live as
        # 1/D bucket rows.  On a 1-device mesh there is nothing to shard
        # and the plain step is used as-is (same fall-through as ZeRO-1
        # below).
        zero3_on = cfg.shard_params and bool(bucket_bytes) \
            and num_replicas > 1 and cfg.sync_mode == "sync"
        # The explicit per-bucket ZeRO-1 schedule replaces the GSPMD
        # constraint form of --shard_update (see parallel/bucketing.py);
        # on a 1-device mesh there is nothing to reduce and the plain
        # step (with the constraint wrapper's 1-extent no-op) is used
        # as-is.
        bucket_zero1 = bool(bucket_bytes) and cfg.shard_update \
            and num_replicas > 1 and cfg.sync_mode == "sync" \
            and not zero3_on
        return bucket_bytes, zero3_on, bucket_zero1

    # --- the declaration, resolved without compiling anything ----------

    def describe(self, sample_shape: tuple | None = None) -> dict:
        """What this spec RESOLVES to — mode, update layout, declared
        HLO contract, hook stack — without building a mesh or compiling
        a step.  With ``sample_shape``, also the abstract TrainState
        (``jax.eval_shape`` over state creation: zero FLOPs), which is
        what tests pin a workload's full surface against."""
        cfg = self.spec.config
        num_replicas = cfg.num_devices or jax.device_count()
        bucket_bytes, zero3_on, bucket_zero1 = self._resolve_flags(
            cfg, num_replicas)
        decl = resolve_mode(cfg, num_replicas)
        hooks = []
        if cfg.checkpoint_every > 0 or cfg.resume:
            if cfg.checkpoint_every > 0:
                hooks.append("CheckpointHook")
        if os.environ.get("SNAPSHOT_DIR", "") and (zero3_on or bucket_zero1):
            hooks.append("ShardSnapshotHook")
        if cfg.eval_every > 0:
            hooks.append("EvalHook")
        if cfg.profile_dir:
            hooks.append("ProfilerHook")
        if os.environ.get("SUPERVISE_HEARTBEAT", ""):
            hooks.append("HeartbeatHook")
        hooks += ["MetricsHook", "AnomalyHook"]
        out = {
            "entrypoint": f"trainer:{self.spec.model}",
            "mode": decl.name,
            "update_layout": ("zero3_rows" if zero3_on else
                              "bucket_rows" if bucket_zero1 else "tree"),
            "contract": decl.contract,
            "bucket_bytes": bucket_bytes,
            "mesh_size": num_replicas,
            "token_data": self.spec.resolved_token_data(),
            "checkpointing": cfg.checkpoint_every > 0 or cfg.resume,
            "hooks": hooks,
        }
        if sample_shape is not None:
            model = self._model(cfg)
            tx = self._optimizer(cfg, None, wrap_shard_update=False)
            dtype = jnp.int32 if out["token_data"] else jnp.float32
            out["abstract_state"] = jax.eval_shape(
                functools.partial(TrainState.create, model, tx,
                                  seed=cfg.seed),
                jax.ShapeDtypeStruct(tuple(sample_shape), dtype))
        return out

    # --- the measuring surface -----------------------------------------

    def build(self, mesh=None, unroll: int = 1) -> EngineBuild:
        """Dataset + laid-out state + compiled step for one knob config
        — the trainer's construction stack with no hooks, no eval, no
        checkpointing (the harness measures the step, the trainer
        surface supervises it).  Train split only; ``unroll`` is the
        lax.scan fusion."""
        cfg = self.spec.config
        enable_compilation_cache()
        if mesh is None:
            mesh = make_mesh(cfg.num_devices)
        num_replicas = mesh.size
        bucket_bytes, zero3_on, bucket_zero1 = self._resolve_flags(
            cfg, num_replicas)
        global_batch = (cfg.batch_size if cfg.global_batch
                        else cfg.batch_size * num_replicas)
        if global_batch % num_replicas:
            raise ValueError(f"global batch {global_batch} not divisible "
                             f"by {num_replicas} replicas")
        token_data = self.spec.resolved_token_data()
        train_x, train_y = self._input(cfg, "train")
        ds = DeviceDataset(train_x, train_y, global_batch, mesh=mesh,
                           seed=cfg.seed, steps_per_next=unroll,
                           quantize=cfg.quantize,
                           dequant_impl=cfg.dequant_impl,
                           data_sharding=cfg.data_sharding,
                           token_data=token_data)
        model = self._model(cfg)
        tx = self._optimizer(cfg, mesh,
                             wrap_shard_update=not (bucket_zero1
                                                    or zero3_on))
        sample_shape = (global_batch,) + tuple(train_x.shape[1:])
        state = TrainState.create_sharded(model, tx, sample_shape,
                                          cfg.seed,
                                          replicated_sharding(mesh))
        state, zero3_layout = apply_update_layout(
            state, tx,
            update_layout=("zero3_rows" if zero3_on else
                           "bucket_rows" if bucket_zero1 else "tree"),
            bucket_bytes=bucket_bytes, mesh=mesh,
            shard_update=cfg.shard_update)
        ce_impl = "pallas" if cfg.pallas_ce else "xla"
        device_augment = "cifar" if self.spec.augment else "none"
        if cfg.sync_mode == "async":
            state = make_worker_state(state, num_replicas, mesh)
            step = make_indexed_async_train_step(
                num_replicas, cfg.async_period, global_batch,
                ds.steps_per_epoch, cfg.label_smoothing, ce_impl=ce_impl,
                mesh=mesh, unroll_steps=unroll, augment=device_augment,
                num_slots=ds.num_slots, data_sharding=cfg.data_sharding,
                dequant_impl=cfg.dequant_impl, bucket_bytes=bucket_bytes)
        else:
            step = make_indexed_train_step(
                global_batch, ds.steps_per_epoch, cfg.label_smoothing,
                ce_impl=ce_impl, mesh=mesh, unroll_steps=unroll,
                augment=device_augment, num_replicas=num_replicas,
                replicas_to_aggregate=cfg.replicas_to_aggregate,
                num_slots=ds.num_slots, data_sharding=cfg.data_sharding,
                dequant_impl=cfg.dequant_impl, bucket_bytes=bucket_bytes,
                bucket_shard_update=bucket_zero1,
                zero3_layout=zero3_layout,
                zero3_overlap=cfg.zero3_overlap)
        return EngineBuild(
            step=step, ds=ds, state=state, mesh=mesh, unroll=unroll,
            global_batch=global_batch, num_replicas=num_replicas,
            mode=resolve_mode(cfg, num_replicas).name,
            bucket_bytes=bucket_bytes, zero3_layout=zero3_layout)

    # --- the full trainer surface --------------------------------------

    def run(self) -> dict:
        """Train per the spec; returns a summary dict (used by tests and
        bench).  This IS the shared trainer runner's flow, moved — every
        operation in its original order."""
        spec = self.spec
        cfg: RunConfig = spec.config
        model_name, dataset_name = spec.model, spec.dataset
        augment = spec.augment
        info = cluster.resolve(cfg)
        if info.role == "ps":
            print(cluster.PS_NOTICE, flush=True)
            return {"role": "ps", "exited": True}
        cluster.maybe_initialize_distributed(info)
        enable_compilation_cache()
        if info.is_distributed:
            # Rank-labeled telemetry: every obs surface (flight filename,
            # span context — obs/recorder.py, obs/trace.py) reads
            # OBS_RANK.  The fleet supervisor exports it at spawn; a
            # hand-launched worker gets it here from its resolved cluster
            # identity, so two ranks' flight files can never collide on
            # pid alone.
            os.environ.setdefault("OBS_RANK", str(info.process_id))

        mesh = make_mesh(cfg.num_devices)
        is_chief = info.is_chief and jax.process_index() == 0
        devices = device_summary(mesh.devices.flat)
        if is_chief:
            # Trainers run wherever jax puts them (tests need the CPU),
            # so the device is SAID, once, and repeated in the summary:
            # a CPU run cannot pass for a chip run.
            print(device_line(devices), flush=True)
        if jax.process_count() > 1:
            # Every later decision with a collective in it — loop length,
            # unroll, eval/checkpoint cadence, the SHARED checkpoint
            # directory (divergent paths split-brain Orbax's
            # collective-save barriers and WEDGE the first save —
            # observed), the stop consensus — assumes the processes were
            # launched with the same flags.  Verify once, up front,
            # unconditionally (a guard gated on per-process config would
            # itself be a mismatched collective), and fail by name
            # instead of hanging later.  Per-process-legitimate fields
            # (cluster identity, local data / profile paths) are
            # excluded.
            import zlib

            from jax.experimental import multihost_utils
            per_process = {"job_name", "task_index", "process_id",
                           "ps_hosts", "worker_hosts",
                           "coordinator_address", "num_processes",
                           "data_dir", "profile_dir"}
            if not (cfg.checkpoint_every > 0 or cfg.resume):
                # Without checkpointing there is no collective touching
                # the path — per-worker scratch log dirs are legitimate
                # (the reference's workers logged locally).  Enablement
                # itself is in the digest, so divergent enablement still
                # errors.
                per_process = per_process | {"log_dir"}
            blob = repr(sorted(
                (k, v) for k, v in dataclasses.asdict(cfg).items()
                if k not in per_process)).encode()
            digests = multihost_utils.process_allgather(
                np.uint32(zlib.crc32(blob)))
            if len({int(d) for d in digests}) > 1:
                raise ModeRefusal(
                    f"run configuration differs across the "
                    f"{jax.process_count()} processes (config digests "
                    f"{sorted({int(d) for d in digests})}). Collective "
                    "decisions (train_steps, steps_per_loop, "
                    "eval/checkpoint cadence, the shared --log_dir) must "
                    "agree on every process — launch all workers with "
                    "identical flags (only cluster identity, --data_dir "
                    "and --profile_dir may differ)")
        num_replicas = mesh.size
        global_batch = (cfg.batch_size if cfg.global_batch
                        else cfg.batch_size * num_replicas)
        if global_batch % num_replicas:
            raise ValueError(f"global batch {global_batch} not divisible "
                             f"by {num_replicas} replicas")

        token_data = spec.resolved_token_data()
        bucket_bytes, zero3_on, bucket_zero1 = self._resolve_flags(
            cfg, num_replicas)

        train_x, train_y = self._input(cfg, "train")
        test_x, test_y = self._input(cfg, "test")
        data_shard = batch_sharding(mesh)
        repl = replicated_sharding(mesh)

        # Device-resident input path (data/device_dataset.py): the split
        # lives in HBM and batches are gathered on device — no per-step
        # H2D copy.  "auto" (the default) uses it in both sync and async
        # modes; augmentation runs on device (data/augment_device.py).
        use_device_data = cfg.device_data != "off"
        if not use_device_data:
            batcher = Batcher(train_x, train_y, global_batch,
                              seed=cfg.seed,
                              process_index=jax.process_index(),
                              process_count=jax.process_count(),
                              augment_fn=cifar_augment if augment else None,
                              quantize=cfg.quantize)
            # eval/train symmetry: the resident eval below resolves the
            # SAME --dequant_impl; the host-fed steps resolve it in
            # dequant_host_batch.
            batches = DevicePrefetcher(batcher, sharding=data_shard)

        model = self._model(cfg)
        tx = self._optimizer(cfg, mesh,
                             wrap_shard_update=not (bucket_zero1
                                                    or zero3_on))
        # Sample shape comes from the loaded split itself (images:
        # [N,H,W,C], tokens: [N,T]) — _SAMPLE_SHAPES stays as
        # documentation of the image families' shapes.
        sample_shape = (global_batch,) + tuple(train_x.shape[1:])
        state = TrainState.create_sharded(model, tx, sample_shape,
                                          cfg.seed, repl)
        if bucket_bytes and cfg.sync_mode == "sync" and num_replicas > 1 \
                and state.batch_stats:
            raise ModeRefusal(
                f"--bucket_grads cannot run {model_name!r}: its BatchNorm "
                f"computes global-batch statistics, which the bucketed "
                f"per-shard gradient region would silently turn into "
                f"per-shard statistics (a different model, not a "
                f"different collective schedule). Use the default fused "
                f"all-reduce for BatchNorm models")
        update_layout = ("zero3_rows" if zero3_on else
                         "bucket_rows" if bucket_zero1 else "tree")
        snap_dir = os.environ.get("SNAPSHOT_DIR", "")
        shard_store = None
        if snap_dir and update_layout != "tree":
            # Shard-redundant row-layout snapshots (resilience/
            # shardstore.py): per-rank 1/D shard files + ring mirrors
            # under a sha256 quorum manifest.  The layout facts come
            # from the TREE params — they are what the manifest records,
            # and they are D-independent, which is what makes the
            # elastic restore below legal.
            from distributedtensorflowexample_tpu.resilience.shardstore \
                import ShardLayout, ShardSnapshotHook, ShardStore
            shard_store = ShardStore(
                snap_dir,
                layout=ShardLayout.for_params(update_layout, bucket_bytes,
                                              state.params, num_replicas),
                keep=cfg.keep_checkpoints)
        restored_from_shards = False
        if shard_store is not None and cfg.resume \
                and shard_store.latest_valid() is not None:
            # The engine-integrated ELASTIC restore: a quorum-valid
            # shard set written at ANY mesh width regroups onto this
            # one THROUGH the same apply_update_layout pass the
            # non-resume path runs — bitwise (tests/test_checkpoint.py).
            # The by-name cross-width refusal in
            # _refuse_incompatible_restore still guards the Orbax path,
            # where no regroup exists.
            state, shard_aux = shard_store.restore_elastic(
                state, tx, mesh=mesh)
            zero3_layout = shard_aux["zero3_layout"]
            restored_from_shards = True
            if jax.process_index() == 0:
                print(f"resumed from shard set at step "
                      f"{shard_aux['step']} (written at "
                      f"D={shard_aux['from_ranks']}, this mesh is "
                      f"D={num_replicas})", flush=True)
        else:
            state, zero3_layout = apply_update_layout(
                state, tx, update_layout=update_layout,
                bucket_bytes=bucket_bytes, mesh=mesh,
                shard_update=cfg.shard_update)

        is_async = cfg.sync_mode == "async"
        if is_async and cfg.replicas_to_aggregate:
            raise ModeRefusal(
                "--replicas_to_aggregate is a SyncReplicasOptimizer "
                "(sync-mode) concept; async mode has no aggregation "
                "barrier to relax")
        if is_async:
            # Local-SGD emulation of the reference's async-PS staleness:
            # one virtual worker per device, averaged every
            # --async_period steps.
            state = make_worker_state(state, num_replicas, mesh)

        logger = MetricsLogger(cfg.log_dir, num_chips=num_replicas,
                               is_chief=is_chief, log_every=cfg.log_every)
        hooks = []
        manager = None
        # Topology facts of THIS run, persisted next to the checkpoints
        # so a later resume can be refused by name instead of dying on an
        # Orbax shape mismatch (async state is worker-tiled: leading axis
        # = num_workers, so worker count is structural; sync state is
        # replicated and restores fine across mesh sizes — recorded but
        # not refused).
        run_meta = {"sync_mode": cfg.sync_mode, "mesh_size": num_replicas,
                    "num_workers": num_replicas if is_async else None,
                    # bucket_rows: optimizer state stored as per-bucket
                    # flat 1/D rows (the bucketed ZeRO-1 schedule);
                    # zero3_rows: params AND optimizer state stored as
                    # rows (ZeRO-3) — both structurally different from
                    # the params-shaped tree layout, so a cross-layout
                    # resume must be refused by name.
                    "update_layout": ("zero3_rows" if zero3_on else
                                      "bucket_rows" if bucket_zero1 else
                                      "tree")}
        if cfg.checkpoint_every > 0 or cfg.resume:
            manager = CheckpointManager(f"{cfg.log_dir}/checkpoints",
                                        max_to_keep=cfg.keep_checkpoints,
                                        async_save=cfg.async_checkpoint,
                                        run_metadata=run_meta)
            if cfg.resume and not restored_from_shards \
                    and manager.latest_step() is not None:
                _refuse_incompatible_restore(manager.saved_run_metadata(),
                                             run_meta, cfg.log_dir,
                                             is_chief)
                state = manager.restore(state)
                if is_chief:
                    print(f"resumed from checkpoint at step "
                          f"{int(state.step)}", flush=True)
            if cfg.checkpoint_every > 0:
                hooks.append(CheckpointHook(manager, cfg.checkpoint_every))
        if shard_store is not None:
            # Rides next to (not instead of) the Orbax hook: the shard
            # set is what the fleet's resume agreement and the elastic
            # restore read.
            hooks.append(ShardSnapshotHook(shard_store,
                                           every=max(1,
                                                     cfg.checkpoint_every),
                                           cursor={"seed": cfg.seed}))

        # Eval batch must divide across the mesh like the train batch
        # does.
        eval_batch = max(global_batch,
                         (1000 // num_replicas) * num_replicas
                         or num_replicas)
        if use_device_data:
            # Test split resident in HBM too: one dispatch per eval, and
            # eval wall time stops polluting the training window.
            _evaluate = make_resident_eval(test_x, test_y,
                                           batch_size=eval_batch,
                                           mesh=mesh, quantize=cfg.quantize,
                                           dequant_impl=cfg.dequant_impl,
                                           token_data=token_data)
        else:
            _evaluate = functools.partial(evaluate, images=test_x,
                                          labels=test_y,
                                          batch_size=eval_batch,
                                          sharding=data_shard)
        if zero3_on:
            # Eval consumes the full tree; gather the 1/D rows back once
            # per eval (jitted+cached per layout — a transient full copy,
            # like the forward's own gathered temporaries).
            _row_eval = _evaluate
            _evaluate = lambda s: _row_eval(
                s.replace(params=zero3_layout.materialize(s.params)))
        # Async state carries per-worker copies; eval on their average.
        eval_fn = ((lambda s: _evaluate(consolidate(s)))
                   if is_async else _evaluate)
        if cfg.eval_every > 0:
            hooks.append(EvalHook(eval_fn, cfg.eval_every, logger))
        if cfg.profile_dir:
            hooks.append(ProfilerHook(cfg.profile_dir,
                                      cfg.profile_start_step,
                                      cfg.profile_num_steps))

        ce_impl = "pallas" if cfg.pallas_ce else "xla"
        device_augment = "cifar" if augment else "none"
        steps_per_call = 1
        ds = None
        if use_device_data:
            remaining = cfg.train_steps - int(state.step)
            if cfg.steps_per_loop == 0:
                # Auto (the default): out of the box the shipped CLI
                # fuses multiple steps per dispatch like the bench does,
                # instead of paying the ~1.4 ms/step dispatch tax at
                # unroll 1.
                steps_per_call = (auto_steps_per_loop(
                    remaining, len(train_x) // global_batch,
                    intervals=(cfg.log_every, cfg.eval_every,
                               cfg.checkpoint_every),
                    start=int(state.step))
                    if remaining > 0 else 1)
                if steps_per_call > 1 and is_chief:
                    # Say what the default chose: the user sees logs
                    # arrive in strides and should know why (and how to
                    # opt out).
                    print(f"steps_per_loop auto: fusing {steps_per_call} "
                          f"steps per dispatch (--steps_per_loop 1 for "
                          f"per-step dispatch)", flush=True)
            else:
                steps_per_call = max(1, cfg.steps_per_loop)
                if remaining > 0 and remaining % steps_per_call:
                    # The loop advances in steps_per_call strides; a
                    # non-multiple remainder would silently under-run the
                    # target step count.
                    raise ModeRefusal(
                        f"remaining steps {remaining} (train_steps "
                        f"{cfg.train_steps} - resumed step "
                        f"{int(state.step)}) must be a multiple of "
                        f"--steps_per_loop {steps_per_call}")
            # Constructed after a possible resume so epoch slots line up
            # with the restored global step.
            ds = DeviceDataset(train_x, train_y, global_batch, mesh=mesh,
                               seed=cfg.seed, start_step=int(state.step),
                               steps_per_next=steps_per_call,
                               quantize=cfg.quantize,
                               dequant_impl=cfg.dequant_impl,
                               data_sharding=cfg.data_sharding,
                               token_data=token_data)
            batches = ds
            if is_chief and ds.dequant_impl is not None:
                # `auto` resolves per backend (the affine form must be
                # bitwise on THIS one, else one-hot): say which kernel
                # the step will run, so a quiet fallback is visible.
                print(f"dequant_impl: {ds.dequant_impl} "
                      f"(--dequant_impl {cfg.dequant_impl})", flush=True)
        elif cfg.steps_per_loop > 1:
            raise ModeRefusal("--steps_per_loop > 1 requires the "
                             "device-resident input path (device_data)")

        if is_async and use_device_data:
            train_step = make_indexed_async_train_step(
                num_replicas, cfg.async_period, global_batch,
                ds.steps_per_epoch, cfg.label_smoothing, ce_impl=ce_impl,
                mesh=mesh, unroll_steps=steps_per_call,
                augment=device_augment, num_slots=ds.num_slots,
                data_sharding=cfg.data_sharding,
                dequant_impl=cfg.dequant_impl, bucket_bytes=bucket_bytes)
        elif is_async:
            train_step = make_async_train_step(num_replicas,
                                               cfg.async_period,
                                               cfg.label_smoothing,
                                               ce_impl=ce_impl, mesh=mesh,
                                               dequant=batcher.dequant,
                                               dequant_impl=cfg.dequant_impl,
                                               quantize=cfg.quantize,
                                               bucket_bytes=bucket_bytes)
        elif use_device_data:
            train_step = make_indexed_train_step(
                global_batch, ds.steps_per_epoch, cfg.label_smoothing,
                ce_impl=ce_impl, mesh=mesh, unroll_steps=steps_per_call,
                augment=device_augment, num_replicas=num_replicas,
                replicas_to_aggregate=cfg.replicas_to_aggregate,
                num_slots=ds.num_slots, data_sharding=cfg.data_sharding,
                dequant_impl=cfg.dequant_impl, bucket_bytes=bucket_bytes,
                bucket_shard_update=bucket_zero1,
                zero3_layout=zero3_layout,
                zero3_overlap=cfg.zero3_overlap)
        else:
            train_step = make_train_step(
                cfg.label_smoothing, ce_impl=ce_impl, mesh=mesh,
                num_replicas=num_replicas,
                replicas_to_aggregate=cfg.replicas_to_aggregate,
                dequant=batcher.dequant, dequant_impl=cfg.dequant_impl,
                quantize=cfg.quantize, bucket_bytes=bucket_bytes,
                bucket_shard_update=bucket_zero1,
                zero3_layout=zero3_layout,
                zero3_overlap=cfg.zero3_overlap)
        # Preemption safety (TPU-first failure recovery, SURVEY §5): the
        # platform sends SIGTERM before reclaiming a slice/VM.  The
        # handler only SETS A FLAG — the loop polls it at call boundaries
        # and stops cleanly (end hooks run, final checkpoint written),
        # then the process exits 143 so a restarted job auto-resumes
        # (--resume default) from the last completed step.  Raising from
        # the handler instead is unsafe: the step donates its input
        # state, and an exception landing mid-call leaves deleted buffers
        # (see TrainLoop).
        from distributedtensorflowexample_tpu.utils.signals import (
            sigterm_flag)

        stop_agreed = []
        preempted = None    # bound by the sigterm_flag context below

        if jax.process_count() > 1:
            # Multi-host: the stop decision must be UNANIMOUS at the SAME
            # call boundary — a lone process breaking out would leave the
            # others blocked in the next step's gradient psum until the
            # SIGKILL, and the collective Orbax save requires every
            # process to call it with the same step.  process_allgather
            # at a boundary is itself a collective all processes reach in
            # lockstep.  Polled roughly every _CONSENSUS_POLL_STEPS
            # global steps (every boundary for fused windows that big): a
            # per-call cross-host sync at unroll 1 would tax every step
            # to detect a rare event, and tens of steps of detection
            # latency is nothing against a preemption grace period.
            from jax.experimental import multihost_utils

            poll_every = max(1, _CONSENSUS_POLL_STEPS // steps_per_call)
            boundary = [0]

            def _consensus():
                agreed = bool(multihost_utils.process_allgather(
                    np.int32(bool(preempted))).max())
                if agreed:
                    stop_agreed.append(True)
                return agreed

            def _should_stop():
                i = boundary[0]
                boundary[0] += 1
                if i % poll_every:
                    return False    # uniform skip: same count everywhere
                return _consensus()
        else:
            def _consensus():
                if preempted:
                    stop_agreed.append(True)
                return bool(preempted)

            _should_stop = _consensus

        # Supervised runs (tools/supervise.py) export
        # SUPERVISE_HEARTBEAT; the boundary touches are what let the
        # watchdog distinguish a wedged dispatch from a long quiet
        # stretch of healthy fused steps.
        hb_path = os.environ.get("SUPERVISE_HEARTBEAT", "")
        if hb_path:
            from distributedtensorflowexample_tpu.training.hooks import (
                HeartbeatHook)
            hooks.append(HeartbeatHook(hb_path,
                                       every=_CONSENSUS_POLL_STEPS))
        # Telemetry (obs/): the registry feed is always on — its boundary
        # cost is the lock-free path, microbench-guarded in
        # tests/test_obs.py — while the flight recorder (a
        # flight_<pid>.json postmortem on every death) arms for
        # supervised runs automatically and for anything else via
        # OBS_FLIGHT=1.
        from distributedtensorflowexample_tpu.obs import (
            recorder as obs_recorder)
        from distributedtensorflowexample_tpu.training.hooks import (
            MetricsHook)
        # Per-step collective accounting (OBS_COLLECTIVES=1): inventory
        # the compiled step's collectives once and feed the registry
        # counters per boundary.  Opt-in because the AOT
        # lower().compile() does NOT share the jit executable cache on
        # this jax pin — arming it costs one extra compile of the train
        # step (device-resident path only: it has a peekable batch to
        # lower against).
        collectives = None
        if os.environ.get("OBS_COLLECTIVES") == "1" and use_device_data:
            from distributedtensorflowexample_tpu.utils.profiling import (
                collective_inventory_of)
            inv = collective_inventory_of(train_step, (state, ds.peek()),
                                          unroll=steps_per_call)
            if inv and inv.get("multiset"):
                collectives = inv
                note = ""
                if is_async and cfg.async_period > 1:
                    # The worker-average psums are cond-gated on the
                    # period: the module-weight inventory counts them at
                    # every step, so SUSTAINED wire traffic is the totals
                    # divided by the period (an approximation: the
                    # every-step scalar-metrics psum pair — 8 B — is
                    # amortized along with it).  The per-op
                    # gauges keep the raw compiled schedule; only the
                    # cumulative counters amortize.
                    collectives = dict(
                        inv,
                        total_count_per_step=(inv["total_count_per_step"]
                                              / cfg.async_period),
                        total_out_bytes_per_step=(
                            inv["total_out_bytes_per_step"]
                            / cfg.async_period))
                    note = (f", sustained /{cfg.async_period} (cond-gated "
                            f"worker average): "
                            f"{collectives['total_out_bytes_per_step']:.0f}"
                            f" B")
                if is_chief:
                    print(f"collectives per step: {inv['multiset']} "
                          f"({inv['total_out_bytes_per_step']} B out in "
                          f"the compiled schedule{note})", flush=True)
        hooks.append(MetricsHook(every=cfg.log_every,
                                 collectives=collectives))
        # Online anomaly detection (obs/anomaly.py): always-on — the
        # per-boundary cost is a few float ops, guarded with MetricsHook's
        # budget — AFTER MetricsHook so the loss sentinels read the gauge
        # it just set instead of paying a second device fetch.  Detection
        # only: a firing bumps counters, dumps a flight, and (under a
        # supervisor that exported OBS_HEALTH) refreshes the health.json
        # the fleet reads for its skew/straggler pass.
        from distributedtensorflowexample_tpu.training.hooks import (
            AnomalyHook)
        hooks.append(AnomalyHook(every=cfg.log_every,
                                 health_path=os.environ.get("OBS_HEALTH",
                                                            "")))
        rec = obs_recorder.maybe_install()
        if rec is not None:
            # (rank, attempt, phase land in the flight payload itself —
            # the recorder reads OBS_RANK/SUPERVISE_ATTEMPT/OBS_PHASE.)
            rec.note(trainer=model_name, dataset=dataset_name,
                     sync_mode=cfg.sync_mode, log_dir=cfg.log_dir)
            if collectives is not None:
                rec.note(collectives_per_step=collectives["multiset"],
                         collective_bytes_per_step=collectives[
                             "total_out_bytes_per_step"])
        # Cross-run ledger (OBS_LEDGER) + live scrape surface
        # (OBS_HTTP_PORT): the run_start row carries the RESOLVED config
        # — what obs_query diffs two runs by — and MetricsHook feeds the
        # bounded samples; /metrics and /health answer while training.
        from distributedtensorflowexample_tpu.obs import (
            ledger as obs_ledger)
        from distributedtensorflowexample_tpu.obs import serve as obs_serve
        obs_ledger.maybe_begin(
            entrypoint=f"trainer:{model_name}",
            config=dataclasses.asdict(cfg),
            platform=jax.default_backend(), mesh_size=num_replicas,
            num_processes=jax.process_count(), dataset=dataset_name)
        obs_serve.maybe_start()

        with sigterm_flag() as preempted:
            with mesh:
                loop = TrainLoop(train_step, batches, cfg.train_steps,
                                 hooks, logger,
                                 steps_per_call=steps_per_call,
                                 should_stop=_should_stop)
                state = loop.run(state)
                if not stop_agreed:
                    # One more uniform consensus poll (every process
                    # reaches this point in lockstep): a signal that
                    # landed after the last boundary poll — or during the
                    # loop's final steps — still saves BEFORE the final
                    # eval spends grace time.  A signal landing inside
                    # the eval dispatch itself remains unhonorable
                    # mid-collective.
                    _consensus()
                if stop_agreed:
                    # End hooks already force-saved (CheckpointHook.end);
                    # a manager without the periodic hook (resume-only
                    # run) still gets the final save.  Skip the final
                    # eval — the slice is being reclaimed.
                    if manager is not None and cfg.checkpoint_every == 0:
                        manager.save(int(state.step), state, force=True)
                        manager.wait()
                    if is_chief:
                        saved = ("checkpoint saved, restart auto-resumes"
                                 if manager is not None else
                                 "NO checkpoint manager "
                                 "(--checkpoint_every 0 --resume false) "
                                 "— NOTHING SAVED")
                        print(f"SIGTERM at step {int(state.step)}: "
                              f"{saved}; exiting 143", flush=True)
                    logger.close()
                    # Explicit dump (not just atexit): the postmortem
                    # should say PREEMPTED, with the final step/loss
                    # already rung.
                    obs_recorder.dump_global("preempted")
                    # The ledger row too — atexit would close it rc=None
                    # ("never reported"), but this exit DID report.
                    obs_ledger.end_global(rc=143,
                                          final_step=int(state.step))
                    raise SystemExit(143)
                final_acc = eval_fn(state)

        if manager is not None and cfg.checkpoint_every == 0:
            manager.save(int(state.step), state, force=True)
            manager.wait()
        logger.scalar(int(state.step), "final_accuracy", final_acc)
        steps_per_sec = logger.last_steps_per_sec
        logger.close()
        obs_ledger.end_global(rc=0, final_step=int(state.step),
                              final_accuracy=round(float(final_acc), 6))
        return {"final_accuracy": final_acc,
                "steps": int(state.step),
                "steps_per_sec": steps_per_sec,
                "steps_per_sec_per_chip": steps_per_sec / max(1,
                                                              num_replicas),
                "num_replicas": num_replicas,
                "global_batch": global_batch,
                "platform": devices["platform"],
                "device_kind": devices["device_kind"],
                "dequant_impl": ds.dequant_impl if ds is not None else None}
