#!/usr/bin/env python
"""faultline — reproduce any injected-fault scenario from the CLI.

Runs a small CPU training loop with a named FaultPlan wired in, speaking
the supervisor's exit-code protocol, so every resilience scenario is one
command (and one tier-1-safe smoke test):

  python tools/faultline.py --plan preempt --steps 8 --workdir /tmp/fl
  # SIGTERM at a seed-drawn mid-run step -> snapshot saved -> exit 143
  python tools/faultline.py --plan preempt --steps 8 --workdir /tmp/fl
  # resumes from the snapshot, finishes, exit 0

Plans (resilience/faults.py NAMED_PLANS): preempt, wedge, nan_loss,
corrupt_batch, torn_snapshot, heartbeat_flap, journal_torn, slow_rank,
shard_loss, bitflip, none — or explicit specs like
``preemption@3`` / ``wedge@2:5.0`` / ``slow_rank@5:0.5%1`` (rank 1
turns persistent straggler at step 5: every later boundary delayed
0.5 s, heartbeats alive, survives resume), comma-separated.  The same
``(--plan, --steps, --seed)`` triple reproduces the same scenario
anywhere.  Under the supervisor, faults are TRANSIENT by default: they
fire on attempt 0 only (SUPERVISE_ATTEMPT), like the real corrupted
batch or torn write they model.

``--layout zero3`` runs the drill on a ``--mesh``-wide virtual CPU
mesh with ZeRO-3 row state and the shard-redundant ShardStore
(resilience/shardstore.py) in place of the monolithic SnapshotStore:
snapshots are per-rank shard files + ring mirrors under a quorum
manifest, resume goes through the engine's elastic regroup (so a
``--mesh 2`` resume of a ``--mesh 4`` run is legal AND bitwise at the
restore boundary), and the ``shard_loss``/``bitflip`` plans delete or
rot exactly one shard after the final save.  ``%RANK`` on those plans
names the MESH-SHARD index inside this process's store, not a fleet
rank.  The emitted ``params_digest`` hashes the MATERIALIZED params —
the width-independent parity handle (the row digest is 1/D-structured
and only comparable at equal width).

Fleet drills (tools/supervise_fleet.py) run one faultline per rank with
the SAME plan text: a ``%rank`` suffix pins a spec to one rank
(``kill@5%1`` = kill rank 1 at step 5), and this process keeps only the
specs for ITS rank (--rank, default OBS_RANK).  When the fleet's
resume-step agreement exported FLEET_RESUME_STEP, the restore targets
exactly that step — never this rank's own newest, which may sit on a
divergent timeline the gang has discarded.

stdout is one JSON line: status, start/end step, a sha256 digest over
every state leaf (params, optimizer state, BN stats, RNG, step — the
cheap cross-process bitwise-parity handle), and the (step, loss) tape.
Everything else goes to stderr.
"""

from __future__ import annotations

import argparse
import contextlib
import hashlib
import json
import os
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)


def _digest(state) -> str:
    import jax
    import numpy as np

    from distributedtensorflowexample_tpu.training.checkpoint import (
        saveable_state_dict)
    h = hashlib.sha256()
    for leaf in jax.tree.leaves(saveable_state_dict(state)):
        h.update(np.asarray(leaf).tobytes())
    return h.hexdigest()


def _params_digest(state, zero3_layout) -> str:
    """sha256 over the MATERIALIZED params: the width-independent half
    of the parity handle (row leaves are 1/D-structured, so the full
    state digest only compares at equal mesh width)."""
    import jax
    import numpy as np
    h = hashlib.sha256()
    params = zero3_layout.materialize(state.params)
    for leaf in jax.tree.leaves(params):
        h.update(np.asarray(leaf).tobytes())
    return h.hexdigest()


_LM_DRILL_SEQ = 32      # short sequences keep LM drills tier-1-cheap
_DRILL_BUCKET_BYTES = 1 << 20   # zero3 drills: one-ish bucket per dtype


def _batch_stream(batch_size: int, seed: int, start_step: int,
                  pool_size: int = 4, model: str = "softmax"):
    """Deterministic, step-addressable batches: step s always sees pool
    slot (s-1) % pool_size, so a resumed run replays the identical
    stream from its restored step — the dataset-cursor contract the
    snapshot manifest records (here the cursor IS the step).  LM models
    get int32 token batches (the host-fed integer convention: uint8
    would read as quantized pixels to the dequant seam)."""
    import jax.numpy as jnp

    if model.startswith("lm_"):
        from distributedtensorflowexample_tpu.data.lm import (
            make_synthetic_tokens)
        from distributedtensorflowexample_tpu.models.transformer_lm import (
            LM_VOCAB)
        seq = make_synthetic_tokens(batch_size * pool_size, _LM_DRILL_SEQ,
                                    LM_VOCAB, seed, sample_seed=seed + 1)
        x = seq[:, :-1].astype("int32")
        y = seq[:, 1:].astype("int32")
    else:
        from distributedtensorflowexample_tpu.data.synthetic import (
            make_synthetic)
        x, y = make_synthetic(batch_size * pool_size, (28, 28, 1), 10,
                              seed=seed + 1)
    pool = [{"image": jnp.asarray(x[i * batch_size:(i + 1) * batch_size]),
             "label": jnp.asarray(y[i * batch_size:(i + 1) * batch_size])}
            for i in range(pool_size)]

    def gen():
        s = start_step
        while True:
            yield pool[s % pool_size]
            s += 1

    return gen()


def main(argv: list[str] | None = None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--plan", default="preempt",
                   help="named plan or kind[@step][:arg] specs, "
                        "comma-separated (see resilience/faults.py)")
    p.add_argument("--steps", type=int, default=8)
    p.add_argument("--workdir", default="/tmp/faultline",
                   help="snapshot directory (shared across attempts — "
                        "this is what resume resumes from)")
    p.add_argument("--model", default="softmax",
                   choices=["softmax", "mnist_cnn", "lm_tiny"],
                   help="lm_tiny drills the transformer-LM trainer: "
                        "corrupt_batch garbage ids land out-of-vocab, "
                        "the model's OOV poison NaNs the loss, and "
                        "NaNGuard + the flight recorder take it from "
                        "there (models/transformer_lm.py)")
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--snapshot_every", type=int, default=1)
    p.add_argument("--keep", type=int, default=3)
    p.add_argument("--resume", default="true",
                   help="resume from the latest manifest-valid snapshot "
                        "(or from FLEET_RESUME_STEP when a fleet "
                        "agreement pass exported one)")
    p.add_argument("--rank", type=int, default=None,
                   help="this process's rank for %%rank-targeted fault "
                        "specs (default: OBS_RANK, else 0)")
    p.add_argument("--layout", default="tree",
                   choices=["tree", "zero3"],
                   help="zero3: ZeRO-3 row state on a --mesh-wide "
                        "virtual CPU mesh with the shard-redundant "
                        "ShardStore (shard_loss/bitflip plans live "
                        "here; resume is elastic across widths)")
    p.add_argument("--mesh", type=int, default=4,
                   help="virtual CPU mesh width for --layout zero3")
    p.add_argument("--transient", default="true",
                   help="faults fire on SUPERVISE_ATTEMPT=0 only (a "
                        "retry models recovered hardware); false "
                        "re-fires every attempt")
    args = p.parse_args(argv)
    truthy = lambda v: str(v).lower() in ("1", "true", "t", "yes", "y")

    # A drill starts many victims on one host and a chip belongs to one
    # process at a time, so the CPU platform is the default; an exported
    # JAX_PLATFORMS is obeyed.
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax
    if args.layout == "zero3":
        # Row layouts need a real multi-device mesh; give this process
        # --mesh virtual CPU devices BEFORE the backend spins up.
        jax.config.update("jax_num_cpu_devices", args.mesh)
    import jax.numpy as jnp
    import optax

    from distributedtensorflowexample_tpu.models import build_model
    from distributedtensorflowexample_tpu.obs import recorder as obs_recorder
    from distributedtensorflowexample_tpu.parallel.sync import (
        make_train_step)
    from distributedtensorflowexample_tpu.resilience import (
        FaultInjectionHook, FaultPlan, FaultyBatches, MetricsTapeHook,
        NaNGuardHook, SnapshotHook, SnapshotStore)
    from distributedtensorflowexample_tpu.resilience.faults import (
        tear_journal)
    from distributedtensorflowexample_tpu.training.hooks import (
        AnomalyHook, HeartbeatHook, MetricsHook)
    from distributedtensorflowexample_tpu.training.loop import TrainLoop
    from distributedtensorflowexample_tpu.training.state import TrainState
    from distributedtensorflowexample_tpu.utils.signals import sigterm_flag

    attempt = int(os.environ.get("SUPERVISE_ATTEMPT", "0"))
    # Supervised drills leave a flight_<pid>.json postmortem per attempt
    # (OBS_FLIGHT=1 opts a bare run in) — the cross-check surface for
    # the supervisor journal + snapshot manifest (tests/test_obs.py).
    rank = (args.rank if args.rank is not None
            else int(os.environ.get("OBS_RANK", "0")))
    rec = obs_recorder.maybe_install(sigterm=False)
    if rec is not None:
        rec.note(tool="faultline", plan=args.plan, model=args.model,
                 workdir=args.workdir)
    # Run ledger + live scrape (env-gated): a fleet drill's per-attempt
    # rows land in the RUNS.jsonl the fleet supervisor exported, and
    # OBS_HTTP_PORT answers /metrics///health while the drill runs.
    from distributedtensorflowexample_tpu.obs import ledger as obs_ledger
    from distributedtensorflowexample_tpu.obs import serve as obs_serve
    obs_ledger.maybe_begin(
        "faultline", config={"plan": args.plan, "steps": args.steps,
                             "model": args.model, "seed": args.seed,
                             "batch": args.batch, "rank": rank})
    obs_serve.maybe_start()
    plan = FaultPlan.parse(args.plan, args.steps, args.seed)
    if any(s.rank is not None for s in plan.specs):
        # Every rank parses the SAME text (same seed anchor), then keeps
        # only its own specs — "kill rank 1 at step 5" is one shared
        # scenario, not per-rank guesswork.
        plan = plan.for_rank(rank)
        print(f"faultline: rank {rank} plan: "
              + (", ".join(f"{s.kind}@{s.step}" for s in plan.specs)
                 or "(no faults target this rank)"),
              file=sys.stderr, flush=True)
    if plan and truthy(args.transient) and attempt > 0:
        print(f"faultline: attempt {attempt}: plan {args.plan!r} already "
              f"fired (transient) — clean run", file=sys.stderr, flush=True)
        plan = FaultPlan([], seed=args.seed, name=f"{args.plan} (cleared)")

    snap_dir = os.path.join(args.workdir, "snapshots")
    store = SnapshotStore(snap_dir, keep=args.keep)
    model = build_model(args.model)
    sample = (jnp.zeros((args.batch, _LM_DRILL_SEQ), jnp.int32)
              if args.model.startswith("lm_") else
              jnp.zeros((args.batch, 28, 28, 1), jnp.float32))
    tx = optax.sgd(0.1, momentum=0.9)
    state = TrainState.create(model, tx, sample, seed=args.seed)
    mesh = None
    zero3_layout = None
    shard_store = None
    if args.layout == "zero3":
        from distributedtensorflowexample_tpu.engine.engine import (
            apply_update_layout)
        from distributedtensorflowexample_tpu.parallel import make_mesh
        from distributedtensorflowexample_tpu.resilience import (
            ShardLayout, ShardSnapshotHook, ShardStore)
        mesh = make_mesh(args.mesh)
        shard_store = ShardStore(
            snap_dir,
            layout=ShardLayout.for_params("zero3_rows",
                                          _DRILL_BUCKET_BYTES,
                                          state.params, args.mesh),
            keep=args.keep)
    agreed_txt = os.environ.get("FLEET_RESUME_STEP", "")
    if truthy(args.resume):
        if agreed_txt:
            # The fleet's agreement pass picked the max common valid
            # step and discarded everything newer; restoring this
            # rank's own newest instead would silently resume a
            # DIFFERENT global step than the other ranks (the
            # divergence the agreement exists to prevent).
            agreed = int(agreed_txt)
            if agreed > 0:
                active = shard_store if shard_store is not None else store
                ok, why = active.validate(agreed)
                if not ok:
                    print(f"faultline: fleet agreed resume step {agreed} "
                          f"is not valid in this rank's store ({why}) — "
                          f"the agreement pass guarantees every rank "
                          f"holds it; refusing to resume from a "
                          f"divergent snapshot", file=sys.stderr,
                          flush=True)
                    obs_ledger.end_global(rc=1)
                    return 1
                if shard_store is not None:
                    state, shard_aux = shard_store.restore_elastic(
                        state, tx, mesh=mesh, step=agreed)
                    zero3_layout = shard_aux["zero3_layout"]
                else:
                    state = store.restore(state, step=agreed)
            # agreed == 0: no common step existed — start fresh.
        elif shard_store is not None:
            if shard_store.latest_valid() is not None:
                # The elastic restore: ANY saved width regroups onto
                # this mesh through the engine's one re-layout pass.
                state, shard_aux = shard_store.restore_elastic(
                    state, tx, mesh=mesh)
                zero3_layout = shard_aux["zero3_layout"]
        else:
            state = store.restore(state)
    if args.layout == "zero3" and zero3_layout is None:
        # Fresh start (nothing restored): lay the tree state out as
        # rows the same way the engine does.
        state, zero3_layout = apply_update_layout(
            state, tx, update_layout="zero3_rows",
            bucket_bytes=_DRILL_BUCKET_BYTES, mesh=mesh)
    start_step = int(state.step)
    if start_step:
        print(f"faultline: resumed from snapshot at step {start_step}",
              file=sys.stderr, flush=True)

    batches = FaultyBatches(
        _batch_stream(args.batch, args.seed, start_step,
                      model=args.model), plan,
        start_step=start_step)
    tape = MetricsTapeHook()
    # Order is load-bearing: MetricsHook first so the flight recorder
    # rings every step's loss INCLUDING a poisoned one (the evidence);
    # then the NaN guard, which must raise BEFORE SnapshotHook sees the
    # poisoned step, so no snapshot of a non-finite state ever reaches
    # disk; FaultInjectionHook goes last so the step that a
    # preemption/wedge covers is already snapshotted.
    # AnomalyHook right after MetricsHook (it reads the loss gauge the
    # latter sets) and BEFORE FaultInjectionHook: an injected slow_rank
    # delay lands in the NEXT boundary's window sample, so the per-rank
    # health.json a fleet drill reads (OBS_HEALTH, exported by the
    # fleet supervisor) flags the straggler while it is still running.
    from distributedtensorflowexample_tpu.obs.anomaly import RunHealth
    hooks = [MetricsHook(every=1),
             AnomalyHook(every=1,
                         health_path=os.environ.get("OBS_HEALTH", ""),
                         health=RunHealth(rank=rank)),
             NaNGuardHook(), tape,
             (ShardSnapshotHook(shard_store, every=args.snapshot_every,
                                cursor={"seed": args.seed})
              if shard_store is not None else
              SnapshotHook(store, every=args.snapshot_every,
                           cursor={"seed": args.seed})),
             FaultInjectionHook(plan)]
    hb = os.environ.get("SUPERVISE_HEARTBEAT", "")
    if hb:
        hooks.append(HeartbeatHook(hb))

    def emit(status: str, digest_state=None, **extra) -> None:
        rec = {"status": status, "plan": args.plan, "seed": args.seed,
               "attempt": attempt, "rank": rank,
               "start_step": start_step,
               "losses": [[s, loss] for s, loss in tape.tape], **extra}
        if digest_state is not None:
            rec["step"] = int(digest_state.step)
            rec["digest"] = _digest(digest_state)
            if zero3_layout is not None:
                rec["params_digest"] = _params_digest(digest_state,
                                                      zero3_layout)
        print(json.dumps(rec, sort_keys=True), flush=True)

    step_fn = (make_train_step(mesh=mesh, zero3_layout=zero3_layout)
               if mesh is not None else make_train_step())
    mesh_ctx = mesh if mesh is not None else contextlib.nullcontext()
    with sigterm_flag() as preempted:
        loop = TrainLoop(step_fn, batches, args.steps,
                         hooks=hooks, should_stop=preempted)
        try:
            with mesh_ctx:
                state = loop.run(state)
        except FloatingPointError as e:
            # The guard fired before the poisoned state could be saved;
            # the newest snapshot on disk is the last healthy step.  No
            # digest: the local state reference was donated into the
            # loop (its buffers are gone), and a poisoned state has no
            # parity claim to attest anyway.
            print(f"faultline: {e}", file=sys.stderr, flush=True)
            emit("fault", error=str(e),
                 step=start_step + len(tape.tape))
            obs_ledger.end_global(rc=1,
                                  final_step=start_step + len(tape.tape))
            return 1
        # Post-exit faults: applied AFTER the final save — the torn
        # snapshot/journal shapes recovery must survive by falling back
        # (previous valid snapshot; journal replay skipping the tail).
        for spec in plan.post_exit_specs:
            if spec.step > int(state.step):
                continue
            if spec.kind == "torn_snapshot":
                torn = store.tear_latest()
                print(f"faultline: tore snapshot {torn} mid-file",
                      file=sys.stderr, flush=True)
            elif spec.kind in ("shard_loss", "bitflip"):
                if shard_store is None:
                    print(f"faultline: {spec.kind} needs the shard "
                          f"store (--layout zero3) — no-op",
                          file=sys.stderr, flush=True)
                elif spec.kind == "shard_loss":
                    hit = shard_store.drop_rank_dir(spec.rank or 0)
                    print(f"faultline: dropped mesh-shard "
                          f"{spec.rank or 0}'s whole directory from "
                          f"shard set {hit}", file=sys.stderr,
                          flush=True)
                else:
                    hit = shard_store.flip_payload_byte(spec.rank or 0)
                    step_hit, off = hit if hit else (None, None)
                    print(f"faultline: flipped payload byte {off} of "
                          f"mesh-shard {spec.rank or 0} in shard set "
                          f"{step_hit} (silent rot)", file=sys.stderr,
                          flush=True)
            elif spec.kind == "journal_torn":
                jp = os.environ.get("SUPERVISE_JOURNAL", "")
                if jp and tear_journal(jp):
                    print(f"faultline: tore journal {jp} mid-line",
                          file=sys.stderr, flush=True)
                else:
                    print("faultline: journal_torn had no journal to "
                          "tear (SUPERVISE_JOURNAL unset or empty) — "
                          "no-op", file=sys.stderr, flush=True)
        if preempted:
            obs_recorder.dump_global("preempted")
            emit("preempted", digest_state=state)
            obs_ledger.end_global(rc=143, final_step=int(state.step))
            return 143
    emit("ok", digest_state=state)
    obs_ledger.end_global(rc=0, final_step=int(state.step))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
