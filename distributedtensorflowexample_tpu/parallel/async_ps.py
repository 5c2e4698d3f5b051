"""Async parameter-server emulation via local SGD (SURVEY.md §7 step 6,
option b — config 2, BASELINE.json configs[1]).

The reference's async mode: each worker pulls variables from the PS, steps
on its own minibatch, and pushes updates with no inter-worker sync — stale
gradients ARE the semantics (SURVEY.md §3b).  True asynchrony has no
XLA-native analog (one program, lockstep devices), so we emulate the
statistical behavior TPU-natively:

* each of the mesh's devices hosts one *virtual worker* — a full parameter
  copy, sharded along ``DATA_AXIS`` on a leading worker axis; on a
  multi-device mesh the per-worker compute runs under ``jax.shard_map``
  over that axis (``_build_shard_map_step``), so every device steps ITS
  workers' params on ITS batch shard with zero cross-device traffic
  between averaging points by construction (letting GSPMD partition a
  plain ``vmap`` instead was measured to all-gather the worker-tiled conv
  weights — see the shard_map builder's docstring);
* every ``period`` steps the copies are averaged (an explicit ``psum``
  over the worker axis, riding ICI) — bounded staleness instead of
  unbounded PS races, same "workers diverge then reconcile" dynamics,
  fully deterministic and restartable.

``period=1`` recovers exact sync SGD; large ``period`` approaches
independent workers.  The branch is a ``lax.cond`` so the whole step stays
one compiled program.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
import optax

from distributedtensorflowexample_tpu.ops.losses import accuracy
from distributedtensorflowexample_tpu.parallel.mesh import DATA_AXIS
from distributedtensorflowexample_tpu.parallel.sync import (
    make_device_gather, make_loss_rows)
from distributedtensorflowexample_tpu.training.state import TrainState


def make_worker_state(state: TrainState, num_workers: int, mesh) -> TrainState:
    """Tile replicated state into per-worker copies sharded over the mesh.

    Leading axis = virtual worker id; NamedSharding P(DATA_AXIS) puts one
    worker's copy on each device.
    """
    wshard = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec(DATA_AXIS))

    def tile(x):
        x = jnp.asarray(x)
        tiled = jnp.broadcast_to(x[None], (num_workers,) + x.shape)
        return jax.lax.with_sharding_constraint(tiled, wshard)

    tile_tree = jax.jit(lambda t: jax.tree.map(tile, t), out_shardings=wshard)
    return state.replace(params=tile_tree(state.params),
                         opt_state=tile_tree(state.opt_state),
                         batch_stats=tile_tree(state.batch_stats))


def consolidate(state: TrainState) -> TrainState:
    """Average the worker copies back into one replicated state (for eval,
    checkpoint hand-off to sync mode, or end of training)."""

    def avg(t):
        return jax.tree.map(lambda x: jnp.mean(x.astype(jnp.float32), axis=0)
                            .astype(x.dtype), t)

    return state.replace(params=jax.jit(avg)(state.params),
                         # optimizer moments averaged too (momentum is linear)
                         opt_state=jax.jit(avg)(state.opt_state),
                         batch_stats=jax.jit(avg)(state.batch_stats)
                         if state.batch_stats else state.batch_stats)


def _worker_updates(state: TrainState, loss_rows: Callable, n_workers: int,
                    params, opt_state, stats, images, labels, rngs):
    """One local-SGD update for ``n_workers`` worker copies stacked on the
    leading axis — the per-worker body shared by the vmap (full worker
    axis) and shard_map (device-local slice) paths.

    Per-worker gradients come from ONE ``value_and_grad`` of the summed
    per-worker mean losses: worker ``w``'s parameters only reach
    ``loss_w``, so d(sum)/d(params_w) IS that worker's gradient — same
    math as a per-worker grad transform, but the loss head runs on the
    worker-major flattened [n*Bw, C] logits OUTSIDE the vmap, where the
    Pallas CE kernel can apply (a ``pallas_call`` has no batching rule).

    Returns (new_params, new_opt, new_stats, loss_w, logits) — params
    un-averaged; the caller applies its period-aligned worker average.
    """
    has_bn = bool(stats)

    def fwd(p, st, img, rng):
        variables = {"params": p}
        if has_bn:
            variables["batch_stats"] = st
            logits, updated = state.apply_fn(
                variables, img, train=True,
                rngs={"dropout": rng}, mutable=["batch_stats"])
            return logits, updated["batch_stats"]
        logits = state.apply_fn(variables, img, train=True,
                                rngs={"dropout": rng})
        return logits, st

    def loss_all(params):
        logits, new_stats = jax.vmap(fwd)(params, stats, images, rngs)
        rows = loss_rows(logits.reshape(-1, logits.shape[-1]),
                         labels.reshape(-1))
        loss_w = rows.reshape(n_workers, -1).mean(axis=1)
        return jnp.sum(loss_w), (loss_w, logits, new_stats)

    (_, (loss_w, logits, new_stats)), grads = jax.value_and_grad(
        loss_all, has_aux=True)(params)
    updates, new_opt = jax.vmap(state.tx.update)(grads, opt_state, params)
    new_params = jax.vmap(optax.apply_updates)(params, updates)
    return new_params, new_opt, new_stats, loss_w, logits


def _build_async_step_fn(num_workers: int, period: int,
                         label_smoothing: float = 0.0, ce_impl: str = "xla",
                         mesh=None, bucket_bytes: int | None = None) -> Callable:
    """The un-jitted local-SGD (state, batch) -> (state, metrics) body over
    worker-tiled state, shared by the host-fed and indexed factories.

    The batch arrives as the usual global batch sharded on DATA_AXIS; it
    is reshaped to [workers, per_worker_batch, ...] (device-local, no data
    movement) and stepped by the shared ``_worker_updates`` body.

    On a multi-device mesh the whole per-worker computation runs under
    ``jax.shard_map`` over the worker axis (``_build_shard_map_step``);
    with no mesh (or one device) this plain ``vmap`` body is used.
    """
    period = max(1, int(period))
    if mesh is not None and mesh.size > 1:
        return _build_shard_map_step(num_workers, period, label_smoothing,
                                     ce_impl, mesh,
                                     bucket_bytes=bucket_bytes)
    # Single device: the worker average is local (no collectives), so
    # bucket_bytes has nothing to fuse here.
    loss_rows = make_loss_rows(label_smoothing, ce_impl, mesh)

    def step(state: TrainState, batch) -> tuple[TrainState, dict]:
        W = num_workers

        # [G, ...] -> [W, G/W, ...]; shards are device-local so this is free.
        wbatch = jax.tree.map(
            lambda x: x.reshape((W, x.shape[0] // W) + x.shape[1:]), batch)
        step_rng = jax.random.fold_in(state.rng, state.step)
        worker_rngs = jax.random.split(step_rng, W)
        flat_labels = wbatch["label"].reshape(-1)

        new_params, new_opt, new_stats, loss_w, logits = _worker_updates(
            state, loss_rows, W, state.params, state.opt_state,
            state.batch_stats, wbatch["image"], wbatch["label"], worker_rngs)

        new_step = state.step + 1

        def average(tree):
            return jax.tree.map(
                lambda x: jnp.broadcast_to(
                    jnp.mean(x.astype(jnp.float32), axis=0,
                             keepdims=True).astype(x.dtype), x.shape), tree)

        new_params = jax.lax.cond(new_step % period == 0,
                                  average, lambda t: t, new_params)
        new_state = state.replace(step=new_step, params=new_params,
                                  opt_state=new_opt, batch_stats=new_stats)
        metrics = {"loss": jnp.mean(loss_w),
                   "accuracy": accuracy(
                       logits.reshape(-1, logits.shape[-1]), flat_labels)}
        return new_state, metrics

    return step


def _build_shard_map_step(num_workers: int, period: int,
                          label_smoothing: float, ce_impl: str,
                          mesh, bucket_bytes: int | None = None) -> Callable:
    """Multi-device local-SGD step: the per-worker compute runs under
    ``jax.shard_map`` over the worker axis, so every device steps ONLY its
    own workers' parameter copies — zero collectives between averaging
    points, by construction.

    Why not let GSPMD partition the ``vmap`` body?  Seen in the compiled
    module on the 8-device CPU mesh: the vmapped conv
    lowers to one grouped convolution whose worker axis is folded into the
    channel dim, and the SPMD partitioner then ALL-GATHERS the worker-tiled
    conv weights and activations (4 all-gathers sized like the gathered
    operands per step) and re-computes every worker's conv on every device
    — redundant compute and wire traffic that explicit per-device
    ``shard_map`` eliminates.  The cond-gated worker average becomes an
    explicit ``psum`` over the worker axis; everything else is local.

    Math is identical to the vmap body: same per-worker rngs, same
    separable summed-loss gradients, same period-aligned average (floats
    reduce in a different order, so results agree to fp tolerance, not
    bitwise, with the vmap path).
    """
    from jax.sharding import PartitionSpec as P

    D = mesh.size
    if num_workers % D:
        raise ValueError(
            f"num_workers {num_workers} must be a multiple of the mesh "
            f"size {D} (one or more whole virtual workers per device)")
    local_W = num_workers // D
    W = num_workers
    loss_rows = make_loss_rows(label_smoothing, ce_impl, mesh=None)

    def step(state: TrainState, batch) -> tuple[TrainState, dict]:
        wbatch = jax.tree.map(
            lambda x: x.reshape((W, x.shape[0] // W) + x.shape[1:]), batch)
        step_rng = jax.random.fold_in(state.rng, state.step)
        worker_rngs = jax.random.split(step_rng, W)

        def shard_body(step_no, params, opt_state, stats, images, labels,
                       rngs):
            # Everything here is the device's local [local_W, ...] slice.
            new_params, new_opt, new_stats, loss_w, logits = _worker_updates(
                state, loss_rows, local_W, params, opt_state, stats, images,
                labels, rngs)

            def average(tree):
                if bucket_bytes:
                    # The per-leaf tree psum below is the per-parameter
                    # collective pattern --bucket_grads fuses: one psum
                    # per knee-sized bucket of local worker-sums instead
                    # of one per leaf.  Bitwise: concatenation regroups
                    # which psum carries each element, never its
                    # cross-device addition order.
                    from distributedtensorflowexample_tpu.parallel.bucketing import (
                        bucketed_tree_psum)
                    sums = jax.tree.map(
                        lambda x: jnp.sum(x.astype(jnp.float32), axis=0,
                                          keepdims=True), tree)
                    sums = bucketed_tree_psum(sums, bucket_bytes, DATA_AXIS)
                    return jax.tree.map(
                        lambda x, s: jnp.broadcast_to(
                            (s / W).astype(x.dtype), x.shape), tree, sums)

                def avg(x):
                    s = jnp.sum(x.astype(jnp.float32), axis=0, keepdims=True)
                    s = jax.lax.psum(s, DATA_AXIS) / W
                    return jnp.broadcast_to(s.astype(x.dtype), x.shape)
                return jax.tree.map(avg, tree)

            new_params = jax.lax.cond((step_no + 1) % period == 0,
                                      average, lambda t: t, new_params)
            flat_logits = logits.reshape(-1, logits.shape[-1])
            flat_labels = labels.reshape(-1)
            total = flat_labels.shape[0] * D      # static global batch
            local_correct = jnp.sum(
                (jnp.argmax(flat_logits, axis=-1) == flat_labels)
                .astype(jnp.float32))
            # One fused all-reduce for both scalar metrics.
            loss_sum, correct = jax.lax.psum(
                (jnp.sum(loss_w), local_correct), DATA_AXIS)
            return (new_params, new_opt, new_stats, loss_sum / W,
                    correct / total)

        wspec = P(DATA_AXIS)
        body = jax.shard_map(
            shard_body, mesh=mesh,
            in_specs=(P(), wspec, wspec, wspec, wspec, wspec, wspec),
            out_specs=(wspec, wspec, wspec, P(), P()), check_vma=False)
        new_params, new_opt, new_stats, loss, acc = body(
            state.step, state.params, state.opt_state, state.batch_stats,
            wbatch["image"], wbatch["label"], worker_rngs)
        new_state = state.replace(step=state.step + 1, params=new_params,
                                  opt_state=new_opt, batch_stats=new_stats)
        return new_state, {"loss": loss, "accuracy": acc}

    return step


def make_async_train_step(num_workers: int, period: int,
                          label_smoothing: float = 0.0, ce_impl: str = "xla",
                          mesh=None, dequant: str | None = None,
                          dequant_impl: str = "auto",
                          quantize: str = "auto",
                          bucket_bytes: int | None = None) -> Callable:
    """Build the jitted host-fed local-SGD step over worker-tiled state.

    ``dequant``: spec for host-fed uint8 batches (``batcher.dequant``);
    ``dequant_impl``/``quantize``: the in-step dequant kernel knobs,
    resolved by the same rule as every other path (see
    sync.dequant_host_batch).  ``bucket_bytes`` (--bucket_grads) fuses
    the period-gated worker-average psums into knee-sized buckets."""
    from distributedtensorflowexample_tpu.parallel.sync import (
        dequant_host_batch)
    inner = _build_async_step_fn(num_workers, period, label_smoothing,
                                 ce_impl, mesh, bucket_bytes=bucket_bytes)

    def step(state: TrainState, batch):
        return inner(state, dequant_host_batch(batch, dequant, dequant_impl,
                                               quantize))

    return jax.jit(step, donate_argnums=0)


def make_indexed_async_train_step(num_workers: int, period: int,
                                  batch_size: int, steps_per_epoch: int,
                                  label_smoothing: float = 0.0,
                                  ce_impl: str = "xla", mesh=None,
                                  unroll_steps: int = 1,
                                  augment: str = "none",
                                  num_slots: int | None = None,
                                  data_sharding: str = "replicated",
                                  dequant_impl: str = "auto",
                                  bucket_bytes: int | None = None) -> Callable:
    """Local-SGD step over a device-resident dataset — async's analog of
    ``sync.make_indexed_train_step``: same on-device gather from the
    perm ring (multi-epoch fused windows supported), same ``lax.scan``
    multi-step fusion; the period-aligned worker averaging runs inside
    the scan (``new_step % period`` is exact whatever the unroll), so
    fused windows and averaging periods compose freely."""
    from distributedtensorflowexample_tpu.parallel.sync import (
        _resolve_num_slots)
    num_slots = _resolve_num_slots(unroll_steps, steps_per_epoch, num_slots)
    inner = _build_async_step_fn(num_workers, period, label_smoothing,
                                 ce_impl, mesh, bucket_bytes=bucket_bytes)
    gather = make_device_gather(batch_size, steps_per_epoch, augment, mesh,
                                num_slots=num_slots,
                                data_sharding=data_sharding,
                                dequant_impl=dequant_impl)

    def one(state: TrainState, data) -> tuple[TrainState, dict]:
        return inner(state, gather(state.step, state.rng, data))

    if unroll_steps == 1:
        return jax.jit(one, donate_argnums=0)

    def step(state: TrainState, data) -> tuple[TrainState, dict]:
        new_state, stacked = jax.lax.scan(
            lambda st, _: one(st, data), state, None, length=unroll_steps)
        return new_state, jax.tree.map(lambda m: jnp.mean(m, axis=0), stacked)

    return jax.jit(step, donate_argnums=0)
