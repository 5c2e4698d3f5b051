"""Sync data-parallel train/eval steps — the SPMD replacement for the
reference's SyncReplicasOptimizer barrier (SURVEY.md §3c), MirroredStrategy
NCCL ring (§3d), and MultiWorkerMirroredStrategy collectives.

One jitted function is traced once and compiled for the whole mesh.  The
batch arrives sharded along ``DATA_AXIS``; params are replicated.  The loss
mean over the batch axis makes XLA emit a psum over ICI for the gradients —
that single collective IS the reference's gradient-aggregation machinery
(PS accumulators + token queues, or the NCCL ring), compiler-scheduled and
overlapped with backprop.

The train state is donated: parameters are updated in place in HBM, no
realloc per step.
"""

from __future__ import annotations

import contextlib

from typing import Callable

import jax
import jax.numpy as jnp
import optax

from distributedtensorflowexample_tpu.data.pipeline import put_global_batch
from distributedtensorflowexample_tpu.parallel.mesh import DATA_AXIS
from distributedtensorflowexample_tpu.ops.losses import accuracy
from distributedtensorflowexample_tpu.training.state import TrainState

# What the compiled default sync step must look like, checked by
# analysis/hlo_lint.py against the lowered module text (PR 13): one
# gradient all-reduce per param leaf plus the two scalar metric
# all-reduces and nothing else on the wire, state donation actually
# aliased (in-place HBM update — the claim in this module's docstring),
# and no float upcast past f32 (the quantized input paths dequantize to
# f32, never f64).  Symbols resolve at check time: P = param leaves.
HLO_CONTRACT = {
    "mode": "sync_dp",
    "collective_budget": {"all-reduce": "P+2"},
    "require_alias": True,
    "dtype_ceiling": "f32",
}


def global_view(mesh):
    """Context for applying a model in global view (one GSPMD program
    over ``mesh``): tells whatever is traced inside which mesh it is
    being partitioned over, the way jax provides for
    (``jax.sharding.get_abstract_mesh``).  ``ops.attention`` reads it to
    run its kernels per shard — GSPMD cannot split a Mosaic call.  A
    no-op without a mesh or on one device."""
    if mesh is None or mesh.size <= 1:
        return contextlib.nullcontext()
    return jax.sharding.use_abstract_mesh(mesh.abstract_mesh)


def _per_example_rows(impl: Callable) -> Callable:
    """Adapt a [rows, C] loss kernel to ALSO accept sequence logits
    [B, T, C] / labels [B, T] (the transformer-LM head): tokens flatten
    into rows — row-major, so a batch-axis sharding of B carries over to
    B*T contiguously — and fold back to ONE per-EXAMPLE value (mean over
    T).  Returning [B] keeps every downstream consumer (batch mean,
    partial aggregation's per-replica row weights, the bucketed step's
    sum/global_batch) shape-identical to the image models'."""
    def rows(logits, labels):
        if logits.ndim == 3:
            b = logits.shape[0]
            r = impl(logits.reshape(-1, logits.shape[-1]),
                     labels.reshape(-1))
            return jnp.mean(r.reshape(b, -1), axis=1)
        return impl(logits, labels)
    return rows


def make_loss_rows(label_smoothing: float = 0.0, ce_impl: str = "xla",
                   mesh=None) -> Callable:
    """Per-example loss head [B,C] -> [B] (or [B,T,C]/[B,T] -> [B] for
    sequence models — see :func:`_per_example_rows`), shared by the sync
    and async step builders.

    ``ce_impl="pallas"`` uses the fused Pallas kernel.  A ``pallas_call``
    is a custom call XLA cannot auto-partition, so on a multi-device mesh
    the kernel runs per-shard under ``jax.shard_map`` over the batch axis;
    reductions outside it remain ordinary jnp ops, keeping the gradient
    psum identical to the XLA path.
    """
    if ce_impl not in ("xla", "pallas"):
        raise ValueError(f"unknown ce_impl {ce_impl!r}")
    if ce_impl == "xla":
        from distributedtensorflowexample_tpu.ops.losses import (
            softmax_cross_entropy_rows)
        return _per_example_rows(
            lambda l, y: softmax_cross_entropy_rows(l, y, label_smoothing))
    from distributedtensorflowexample_tpu.ops.pallas import (
        fused_softmax_cross_entropy_rows)
    # The token-flatten adapter sits INSIDE the shard_map: the kernel
    # sees its shard's [local_b * T, C] rows, reductions over T stay
    # per-example and local.
    fused = _per_example_rows(
        lambda l, y: fused_softmax_cross_entropy_rows(l, y,
                                                      label_smoothing))
    if mesh is not None and mesh.size > 1:
        from jax.sharding import PartitionSpec as P
        fused = jax.shard_map(fused, mesh=mesh,
                              in_specs=(P(DATA_AXIS), P(DATA_AXIS)),
                              out_specs=P(DATA_AXIS), check_vma=False)
    return fused


def _resolve_num_slots(unroll_steps: int, steps_per_epoch: int,
                       num_slots: int | None) -> int:
    """Default + validate a step factory's perm-ring size against the ONE
    sizing rule (DeviceDataset.ring_slots_for)."""
    from distributedtensorflowexample_tpu.data.device_dataset import (
        DeviceDataset)
    if unroll_steps < 1:
        raise ValueError(f"unroll_steps {unroll_steps} must be >= 1")
    needed = DeviceDataset.ring_slots_for(unroll_steps, steps_per_epoch)
    if num_slots is None:
        return needed
    if num_slots < needed:
        raise ValueError(
            f"num_slots {num_slots} cannot hold a {unroll_steps}-step "
            f"window over {steps_per_epoch}-step epochs (needs {needed})")
    return num_slots


def _dequant_gathered(img, data, dequant_impl: str):
    """Dequantize a gathered uint8 batch: the ONE dispatch both indexed
    gathers share.  The constants ride in the data pytree (affine/pallas
    datasets carry ``dq_scale``/``dq_bias``, LUT-family datasets carry
    the 256-entry ``lut``), so which family runs is static at trace time
    and no call site can silently train on raw bytes; ``dequant_impl``
    only refines WITHIN the LUT family (one-hot matmul vs the
    known-slow elementwise gather diagnostic) and catches a
    factory/dataset mismatch as a trace-time error instead of a wrong
    kernel."""
    if "tokens" in data:
        # Token split (DeviceDataset token_data=True): the uint8 batch
        # is ids, not quantized pixels — the model upcasts after the
        # gather.  Static dispatch on pytree structure, like the
        # dq_scale/lut families.
        return img
    if img.dtype != jnp.uint8:
        return img
    from distributedtensorflowexample_tpu.data.device_dataset import (
        apply_dequant_affine, apply_dequant_gather, apply_dequant_lut)
    if "dq_scale" in data:
        if dequant_impl in ("onehot", "lut"):
            raise ValueError(
                f"step factory asked for dequant_impl={dequant_impl!r} but "
                f"the dataset resolved to the affine family (it carries "
                f"dq_scale/dq_bias) — pass the same dequant_impl to "
                f"DeviceDataset and the step factory")
        return apply_dequant_affine(img, data["dq_scale"], data["dq_bias"])
    if "lut" in data:
        if dequant_impl in ("affine", "pallas"):
            raise ValueError(
                f"step factory asked for dequant_impl={dequant_impl!r} but "
                f"the dataset resolved to the LUT family (it carries lut) "
                f"— pass the same dequant_impl to DeviceDataset and the "
                f"step factory")
        if dequant_impl == "lut":
            return apply_dequant_gather(img, data["lut"])
        return apply_dequant_lut(img, data["lut"])
    raise TypeError("gathered batch is uint8 but the data pytree carries "
                    "no dequant constants (not a DeviceDataset product?)")


def make_device_gather(batch_size: int, steps_per_epoch: int,
                       augment: str = "none", mesh=None, *,
                       num_slots: int,
                       data_sharding: str = "replicated",
                       dequant_impl: str = "auto") -> Callable:
    """(step, rng, data) -> batch: the on-device minibatch gather from a
    resident split (see ``data.DeviceDataset``), shared by the sync and
    async indexed step builders.  ``num_slots`` must equal the dataset's
    perm-ring size (``ds.num_slots``).

    A uint8-resident split (4x less gather traffic) dequantizes on the
    gathered batch only: the dequant constants ride in the data pytree
    and the dispatch is on the pytree structure (static at trace time),
    so quantization needs NO step-factory plumbing and no call site can
    silently train on raw bytes.  ``dequant_impl`` mirrors the dataset's
    knob (``data.device_dataset.DEQUANT_IMPLS``): ``auto`` follows the
    pytree (the affine fast path for both shipped loader specs);
    ``pallas`` fuses the row gather and the affine dequant into ONE
    kernel pass (ops/pallas/dequant.py — replicated datasets only);
    ``lut`` forces the elementwise-gather diagnostic (the dequant tax
    of PERF.md "History").

    ``data_sharding="sharded"`` pairs with a row-sharded
    ``DeviceDataset(data_sharding="sharded")``: each device gathers its
    batch shard from ITS row block under ``shard_map`` — local indices,
    zero collectives (the dataset's interleaved per-shard permutation
    guarantees every position a device reads lives in its block).  The
    returned batch is sharded along the batch axis exactly like the
    replicated gather's, so the step body downstream is unchanged."""
    if augment not in ("none", "cifar"):
        raise ValueError(f"unknown augment {augment!r}")
    if data_sharding not in ("replicated", "sharded"):
        raise ValueError(f"unknown data_sharding {data_sharding!r}")
    from distributedtensorflowexample_tpu.data.device_dataset import (
        DEQUANT_IMPLS)
    if dequant_impl not in DEQUANT_IMPLS:
        raise ValueError(f"unknown dequant_impl {dequant_impl!r} "
                         f"(one of {DEQUANT_IMPLS})")
    if data_sharding == "sharded":
        if mesh is None:
            raise ValueError("data_sharding='sharded' requires a mesh")
        if dequant_impl == "pallas":
            raise ValueError(
                "dequant_impl='pallas' fuses the gather over the WHOLE "
                "resident split; pair it with data_sharding='replicated'")
        return _make_sharded_gather(batch_size, steps_per_epoch, augment,
                                    mesh, num_slots=num_slots,
                                    dequant_impl=dequant_impl)

    def gather(step, rng, data):
        # In-epoch position from the global step; modulo first so the
        # int32 product can't overflow on long runs.  The epoch names its
        # slot in the perm ring (see DeviceDataset).
        slot = (step // steps_per_epoch) % num_slots
        pos = (step % steps_per_epoch) * batch_size
        idx = jax.lax.dynamic_slice(data["perm"], (slot, pos),
                                    (1, batch_size))[0]
        if dequant_impl == "pallas" and "dq_scale" in data:
            # Fused row-gather + affine dequant: uint8 rows leave HBM
            # once and arrive as the float32 batch — no materialized u8
            # minibatch, no second dequant pass (VERDICT r4 #3, the
            # profile-chosen kernel).  Augment (if any) runs after, on
            # f32 — bitwise-commutable, the selectors route exactly.
            from distributedtensorflowexample_tpu.ops.pallas import (
                fused_gather_dequant)
            fused = fused_gather_dequant
            if mesh is not None and mesh.size > 1:
                # XLA cannot partition a Mosaic kernel (it refuses by
                # name on the chip; only the CPU interpreter ever let
                # this through): run it per device.  The split and the
                # constants are replicated, each device gathers ITS
                # slice of the index vector — the batch comes out
                # sharded the way the constraint below wants it.
                from jax.sharding import PartitionSpec as P
                fused = jax.shard_map(
                    fused_gather_dequant, mesh=mesh,
                    in_specs=(P(), P(DATA_AXIS), P(), P()),
                    out_specs=P(DATA_AXIS), check_vma=False)
            img = fused(data["images"], idx,
                        data["dq_scale"], data["dq_bias"])
            if augment == "cifar":
                from distributedtensorflowexample_tpu.data.augment_device import (
                    cifar_augment_device)
                akey = jax.random.fold_in(
                    jax.random.fold_in(rng, 0x5EED), step)
                img = cifar_augment_device(img, akey)
        else:
            img = jnp.take(data["images"], idx, axis=0)
            if augment == "cifar":
                # On-device crop/flip (data/augment_device.py): a
                # dedicated stream folded from the state rng — disjoint
                # from the dropout stream, which folds in only the step.
                # Runs BEFORE dequantization: crop/flip only rearranges
                # pixels, so it commutes bitwise with the elementwise
                # dequant, and on a uint8-resident split any materialized
                # pad/crop intermediate is 4x smaller.  On the affine
                # path the dequant is FUSED into the selector matmuls'
                # f32 output (one pass, no u8 cast-back — the round-5
                # ResNet input-share fix).
                akey = jax.random.fold_in(
                    jax.random.fold_in(rng, 0x5EED), step)
                # Forced LUT-family impls skip the fused form so the
                # dequant below runs the kernel the caller named (or
                # raises the family mismatch) instead of silently
                # measuring affine.
                if (img.dtype == jnp.uint8 and "dq_scale" in data
                        and dequant_impl not in ("onehot", "lut")):
                    from distributedtensorflowexample_tpu.data.augment_device import (
                        cifar_augment_dequant_device)
                    img = cifar_augment_dequant_device(
                        img, akey, data["dq_scale"], data["dq_bias"])
                else:
                    from distributedtensorflowexample_tpu.data.augment_device import (
                        cifar_augment_device)
                    img = cifar_augment_device(img, akey)
            img = _dequant_gathered(img, data, dequant_impl)
        batch = {"image": img,
                 "label": jnp.take(data["labels"], idx, axis=0)}
        if mesh is not None and mesh.size > 1:
            # Dataset + perm are replicated, so the gather is local on
            # every device; the constraint re-shards the minibatch along
            # the batch axis (slice-keeping, no collective) so the rest of
            # the step runs data-parallel exactly like the host-fed path.
            from distributedtensorflowexample_tpu.parallel.mesh import (
                batch_sharding)
            batch = jax.lax.with_sharding_constraint(batch,
                                                     batch_sharding(mesh))
        return batch

    return gather


def _make_sharded_gather(batch_size: int, steps_per_epoch: int,
                         augment: str, mesh, *, num_slots: int,
                         dequant_impl: str = "auto") -> Callable:
    """The ``data_sharding="sharded"`` gather (see ``make_device_gather``):
    runs under ``shard_map`` over the data axis, each device slicing its
    bpd positions out of the (replicated) perm ring and translating them
    into its local row space — index math only, no collective."""
    from jax.sharding import PartitionSpec as P

    D = mesh.shape[DATA_AXIS]
    if batch_size % D:
        raise ValueError(f"sharded data: batch {batch_size} must divide "
                         f"across {D} devices")
    bpd = batch_size // D

    def gather(step, rng, data):
        has_lut = "lut" in data
        has_affine = "dq_scale" in data
        has_tokens = "tokens" in data

        def local(step, rng, images, labels, perm, *dq):
            d = jax.lax.axis_index(DATA_AXIS)
            rows = images.shape[0]              # this device's row block
            slot = (step // steps_per_epoch) % num_slots
            pos = (step % steps_per_epoch) * batch_size + d * bpd
            idx = jax.lax.dynamic_slice(perm, (slot, pos), (1, bpd))[0]
            idx = idx - d * rows                # global -> local row space
            img = jnp.take(images, idx, axis=0)
            dq_data = ({"lut": dq[0]} if has_lut else
                       {"dq_scale": dq[0], "dq_bias": dq[1]} if has_affine
                       else {})
            if augment == "cifar":
                # Same stream layout as the replicated gather, plus the
                # device index: each shard draws independent crops/flips
                # (same distribution; draws differ from replicated mode).
                akey = jax.random.fold_in(
                    jax.random.fold_in(jax.random.fold_in(rng, 0x5EED), step),
                    d)
                if (img.dtype == jnp.uint8 and has_affine
                        and dequant_impl not in ("onehot", "lut")):
                    # Affine dequant fused into the selector matmuls'
                    # f32 output — same one-pass form as the replicated
                    # gather (see make_device_gather); a forced LUT-
                    # family impl takes the plain route so the dequant
                    # below runs (or rejects) the named kernel.
                    from distributedtensorflowexample_tpu.data.augment_device import (
                        cifar_augment_dequant_device)
                    img = cifar_augment_dequant_device(img, akey,
                                                       dq[0], dq[1])
                else:
                    from distributedtensorflowexample_tpu.data.augment_device import (
                        cifar_augment_device)
                    img = cifar_augment_device(img, akey)
            if not has_tokens:          # token ids pass through raw
                img = _dequant_gathered(img, dq_data, dequant_impl)
            return img, jnp.take(labels, idx, axis=0)

        args = [step, rng, data["images"], data["labels"], data["perm"]]
        in_specs = [P(), P(), P(DATA_AXIS), P(DATA_AXIS), P()]
        if has_lut:
            args.append(data["lut"])
            in_specs.append(P())
        elif has_affine:
            args.extend([data["dq_scale"], data["dq_bias"]])
            in_specs.extend([P(), P()])
        img, lab = jax.shard_map(
            local, mesh=mesh, in_specs=tuple(in_specs),
            out_specs=(P(DATA_AXIS), P(DATA_AXIS)), check_vma=False)(*args)
        return {"image": img, "label": lab}

    return gather


def _build_step_fn(label_smoothing: float = 0.0, ce_impl: str = "xla",
                   mesh=None, num_replicas: int = 1,
                   replicas_to_aggregate: int = 0,
                   bucket_bytes: int | None = None,
                   bucket_shard_update: bool = False,
                   zero3_layout=None, zero3_overlap: bool = True) -> Callable:
    """The un-jitted (state, batch) -> (state, metrics) step body, shared
    by the plain and the device-resident (indexed) step factories.

    ``ce_impl="pallas"`` swaps the loss head for the fused Pallas kernel
    (ops/pallas/cross_entropy.py).  A ``pallas_call`` is a custom call XLA
    cannot auto-partition, so on a multi-device mesh the kernel runs
    per-shard under ``jax.shard_map`` over the batch axis; the batch mean
    outside it remains an ordinary jnp op, keeping the gradient psum
    identical to the XLA path.

    ``replicas_to_aggregate=R`` (with ``0 < R < num_replicas``) implements
    SyncReplicasOptimizer's partial aggregation: each step only R of the N
    replicas' gradients enter the update.  The reference aggregated the
    first R gradients to *arrive* (backup workers absorbing stragglers —
    a race); lockstep SPMD has no stragglers to drop, so the TPU-native
    analog selects a deterministic rotating subset — replica ``i``
    contributes at step ``s`` iff ``(i - s) mod N < R`` — which preserves
    the statistical semantics (each step averages R replica gradients;
    every replica contributes equally over any N consecutive steps).
    Implemented as a per-row weight on the loss, so the gradient psum
    stays the one XLA collective; unselected replicas' rows carry zero
    weight and their gradient contribution vanishes.

    ``bucket_bytes`` (the ``--bucket_grads`` knob) swaps this body for
    the bucketed shard_map step (parallel/bucketing.py): per-parameter
    gradient all-reduces fuse into knee-sized buckets, and with
    ``bucket_shard_update`` the explicit per-bucket reduce-scatter +
    sharded-update + all-gather ZeRO-1 schedule.  On a single-device
    mesh there is nothing to reduce, so the knob falls through to this
    plain body.

    ``zero3_layout`` (the ``--shard_params`` knob, parallel/zero3.py)
    goes one stage further: params AND grads live as 1/D bucket rows,
    each bucket's params all-gathered just before use (double-buffered
    prefetch unless ``zero3_overlap`` is off) and reduce-scattered in
    the backward by the gather's own transpose.  Takes precedence over
    the ZeRO-1 schedule (it subsumes it); same single-device
    fall-through.
    """
    if zero3_layout is not None and mesh is not None \
            and mesh.shape[DATA_AXIS] > 1:
        from distributedtensorflowexample_tpu.parallel.zero3 import (
            build_zero3_step_fn)
        return build_zero3_step_fn(label_smoothing, ce_impl, mesh,
                                   num_replicas, replicas_to_aggregate,
                                   zero3_layout, overlap=zero3_overlap)
    if bucket_bytes and mesh is not None and mesh.shape[DATA_AXIS] > 1:
        from distributedtensorflowexample_tpu.parallel.bucketing import (
            build_bucketed_step_fn)
        return build_bucketed_step_fn(label_smoothing, ce_impl, mesh,
                                      num_replicas, replicas_to_aggregate,
                                      bucket_bytes,
                                      shard_update=bucket_shard_update)
    R, N = int(replicas_to_aggregate), max(1, int(num_replicas))
    if not 0 <= R <= N:
        raise ValueError(
            f"replicas_to_aggregate {R} must be in [0, {N}] (0 = all)")
    partial_agg = 0 < R < N
    loss_rows = make_loss_rows(label_smoothing, ce_impl, mesh)

    def compute_loss(logits, labels, step):
        rows = loss_rows(logits, labels)
        if not partial_agg:
            return jnp.mean(rows)
        batch = logits.shape[0]
        per_shard = batch // N
        replica_of_row = jnp.arange(batch, dtype=jnp.int32) // per_shard
        selected = ((replica_of_row - step) % N) < R
        return jnp.sum(rows * selected.astype(rows.dtype)) / (R * per_shard)

    def step(state: TrainState, batch) -> tuple[TrainState, dict]:
        step_rng = jax.random.fold_in(state.rng, state.step)
        has_bn = bool(state.batch_stats)

        def loss_fn(params):
            variables = {"params": params}
            if has_bn:
                variables["batch_stats"] = state.batch_stats
                logits, updated = state.apply_fn(
                    variables, batch["image"], train=True,
                    rngs={"dropout": step_rng}, mutable=["batch_stats"])
                new_stats = updated["batch_stats"]
            else:
                logits = state.apply_fn(variables, batch["image"], train=True,
                                        rngs={"dropout": step_rng})
                new_stats = state.batch_stats
            with jax.named_scope("loss"):
                loss = compute_loss(logits, batch["label"], state.step)
            return loss, (logits, new_stats)

        with global_view(mesh):
            (loss, (logits, new_stats)), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(state.params)
        updates, new_opt_state = state.tx.update(grads, state.opt_state,
                                                 state.params)
        new_params = optax.apply_updates(state.params, updates)
        new_state = state.replace(step=state.step + 1, params=new_params,
                                  opt_state=new_opt_state,
                                  batch_stats=new_stats)
        metrics = {"loss": loss, "accuracy": accuracy(logits, batch["label"])}
        return new_state, metrics

    return step


def dequant_host_batch(batch, dequant: str | None,
                       dequant_impl: str = "auto", quantize: str = "auto"):
    """Dequantize a HOST-FED uint8 batch in-step (4x less H2D per step
    than uploading float32).  Float batches pass through.  A uint8 batch
    with no spec is a TRACE-TIME error: silently training on raw 0-255
    bytes is the failure this guard exists to prevent — pass
    ``dequant=batcher.dequant`` (``data.pipeline.Batcher``).

    ``dequant_impl`` resolves through the SAME rule as the resident path
    (``data.device_dataset.resolve_dequant_impl``), so host-fed and
    resident training dequantize through the same kernel — the affine
    fast path for both shipped loader specs.  ``pallas`` degenerates to
    affine here: there is no gather to fuse with on an uploaded batch."""
    img = batch["image"]
    if img.dtype != jnp.uint8:
        return batch
    if dequant is None:
        raise TypeError(
            "host-fed batch images are uint8 but the train step was "
            "built without dequant=; pass dequant=batcher.dequant")
    from distributedtensorflowexample_tpu.data.device_dataset import (
        dequantize_images, resolve_dequant_impl)
    # quantize travels too: the rule's speed-over-bits escape for
    # non-affine-representable specs (quantize="scale") must resolve
    # identically here and on the resident path.
    impl = resolve_dequant_impl(dequant, dequant_impl, quantize)
    impl = "affine" if impl == "pallas" else impl
    return dict(batch, image=dequantize_images(img, dequant, impl))


def make_train_step(label_smoothing: float = 0.0, ce_impl: str = "xla",
                    mesh=None, num_replicas: int = 1,
                    replicas_to_aggregate: int = 0,
                    dequant: str | None = None,
                    dequant_impl: str = "auto",
                    quantize: str = "auto",
                    bucket_bytes: int | None = None,
                    bucket_shard_update: bool = False,
                    zero3_layout=None,
                    zero3_overlap: bool = True) -> Callable:
    """Build the jitted (state, batch) -> (state, metrics) step.

    ``dequant``: spec for HOST-FED uint8 batches (``batcher.dequant``);
    the resident/indexed path dequantizes in its gather instead.
    ``dequant_impl``/``quantize``: the in-step dequant kernel knobs (same
    resolution rule as the resident path — see ``dequant_host_batch``).
    ``bucket_bytes``/``bucket_shard_update``: the ``--bucket_grads``
    collective schedule; ``zero3_layout``/``zero3_overlap``: the
    ``--shard_params`` ZeRO-3 schedule (see ``_build_step_fn``)."""
    inner = _build_step_fn(label_smoothing, ce_impl, mesh,
                           num_replicas, replicas_to_aggregate,
                           bucket_bytes=bucket_bytes,
                           bucket_shard_update=bucket_shard_update,
                           zero3_layout=zero3_layout,
                           zero3_overlap=zero3_overlap)

    def step(state: TrainState, batch):
        return inner(state, dequant_host_batch(batch, dequant, dequant_impl,
                                               quantize))

    return jax.jit(step, donate_argnums=0)


def make_indexed_train_step(batch_size: int, steps_per_epoch: int,
                            label_smoothing: float = 0.0,
                            ce_impl: str = "xla", mesh=None,
                            unroll_steps: int = 1,
                            augment: str = "none", num_replicas: int = 1,
                            replicas_to_aggregate: int = 0,
                            num_slots: int | None = None,
                            data_sharding: str = "replicated",
                            dequant_impl: str = "auto",
                            bucket_bytes: int | None = None,
                            bucket_shard_update: bool = False,
                            zero3_layout=None,
                            zero3_overlap: bool = True) -> Callable:
    """Step over a device-resident dataset (see ``data.DeviceDataset``).

    The batch is GATHERED ON DEVICE from the resident split: the step
    receives ``{"images", "labels", "perm"}`` (full arrays + a two-slot
    epoch permutation pair) and slices its minibatch out of the right
    perm row at the position derived from ``state.step`` — so the host
    transfers nothing per step.  This is the TPU-native kill for the
    feed_dict/H2D per-step copy (SURVEY.md §3a, §7 "hard parts"): at
    MNIST-sized step times the transfer IS the bottleneck (measured
    ~1.4 ms vs a ~0.07 ms step on a v5e chip, rounds 2-5).

    Semantics match the host Batcher exactly: shuffled epochs without
    replacement, batch_size rows per step, global step drives the epoch
    position (deterministic across resume).

    ``unroll_steps=K`` fuses K consecutive SGD updates into one compiled
    call with ``lax.scan`` — K full, sequential, per-batch updates (same
    math, the global step advances by K), one host dispatch.  When the
    device is reached through a high-latency link the dispatch round-trip
    dominates MNIST-sized steps, and this divides it by K — the TPU-native
    analog of Keras ``steps_per_execution``.  Each scanned sub-step picks
    its epoch's perm slot (``(step // steps_per_epoch) % num_slots``) so a
    window may cross epoch boundaries — ANY ``K >= 1`` works, even
    multi-epoch windows (the dataset sizes its perm ring to match; pass
    the same ``unroll_steps`` as its ``steps_per_next`` and, if you
    constructed the dataset yourself, ``num_slots=ds.num_slots``);
    returned metrics are the mean over the K updates.
    """
    num_slots = _resolve_num_slots(unroll_steps, steps_per_epoch, num_slots)
    inner = _build_step_fn(label_smoothing, ce_impl, mesh, num_replicas,
                           replicas_to_aggregate,
                           bucket_bytes=bucket_bytes,
                           bucket_shard_update=bucket_shard_update,
                           zero3_layout=zero3_layout,
                           zero3_overlap=zero3_overlap)
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


_EVAL_STEP = None


def make_eval_step() -> Callable:
    """Jitted (state, batch) -> (sum correct, count) for exact test accuracy.

    A single module-level jitted function: jax caches compilations per
    (apply_fn, shapes), so periodic evals reuse the compiled graph instead
    of rebuilding a fresh closure (and recompiling) per eval.
    """
    global _EVAL_STEP
    if _EVAL_STEP is not None:
        return _EVAL_STEP

    def step(state: TrainState, batch):
        variables = {"params": state.params}
        if state.batch_stats:
            variables["batch_stats"] = state.batch_stats
        logits = state.apply_fn(variables, batch["image"], train=False)
        correct = jnp.sum(
            (jnp.argmax(logits, axis=-1) == batch["label"]).astype(jnp.int32))
        return correct, batch["label"].shape[0]

    _EVAL_STEP = jax.jit(step)
    return _EVAL_STEP


def make_resident_eval(images, labels, batch_size: int = 1000,
                       mesh=None, quantize: str = "auto",
                       dequant_impl: str = "auto",
                       token_data: bool = False) -> Callable:
    """Device-resident exact-accuracy eval: ONE dispatch per eval.

    The host-fed ``evaluate`` re-uploads the split 1000 rows at a time on
    every call — through a high-latency link that wall time pollutes the
    training window.  The test split fits in HBM exactly like the train
    split does, so this uploads it once (padded to a whole number of
    batches, pad labels -1 so they never match an argmax), shards each
    batch row-wise over the mesh, and jits a ``lax.scan`` over the batches
    — the whole eval is a single compiled call returning one scalar.
    Like the train split, a quantizable split is held as uint8 (4x less
    HBM + upload) and dequantized in the scan body.  ``quantize`` and
    ``dequant_impl`` mirror the train-path flags and resolve through the
    SAME rule (``data.device_dataset.resolve_dequant_impl``), so a
    bitwise train/eval parity check exercises one kernel, not two
    (``pallas`` degenerates to affine here: the scan slices resident
    batches, there is no row gather to fuse).

    ``token_data=True`` (the LM family): the split is integer ids — no
    dequant machinery runs, the model upcasts, and accuracy normalizes
    per LABEL ELEMENT (per token for [N, T] targets; identical to the
    per-example count for [N] image labels).

    Returns ``eval_fn(state) -> float`` (exact accuracy over the split).
    """
    import numpy as np

    from distributedtensorflowexample_tpu.data.device_dataset import (
        _try_quantize, dequantize_images, resolve_dequant_impl)

    if quantize not in ("auto", "off", "exact", "scale"):
        raise ValueError(f"unknown quantize mode {quantize!r}")
    dequant = None
    if not token_data and quantize != "off":
        q = _try_quantize(np.asarray(images))
        if q is not None:
            images, dequant = q
    impl = (resolve_dequant_impl(dequant, dequant_impl, quantize)
            if dequant is not None else None)
    impl = "affine" if impl == "pallas" else impl

    n = len(labels)
    # Accuracy denominator: label ELEMENTS of the real split (tokens for
    # a [N, T] LM split; == n for [N] image labels).  Pad labels are -1
    # and never match an argmax, so only the denominator needs care.
    denom = int(np.asarray(labels).size)
    if mesh is not None and batch_size % mesh.size:
        raise ValueError(f"eval batch {batch_size} must divide across "
                         f"{mesh.size} devices")
    num_batches = -(-n // batch_size)
    pad = num_batches * batch_size - n
    if pad:
        images = np.concatenate(
            [images, np.zeros((pad,) + images.shape[1:], images.dtype)])
        labels = np.concatenate(
            [labels, np.full((pad,) + labels.shape[1:], -1, labels.dtype)])
    xs = np.ascontiguousarray(
        images.reshape((num_batches, batch_size) + images.shape[1:]))
    ys = np.ascontiguousarray(
        labels.reshape((num_batches, batch_size) + labels.shape[1:]))

    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec as P
        shard = NamedSharding(mesh, P(None, DATA_AXIS))
        if jax.process_count() > 1:
            # Every process holds the full split; its devices own a
            # contiguous slice of the (sharded) batch axis — mesh device
            # order groups devices by process (see put_global_batch).
            pc, pi = jax.process_count(), jax.process_index()
            per = batch_size // pc
            put = lambda a: jax.make_array_from_process_local_data(
                shard, np.ascontiguousarray(a[:, pi * per:(pi + 1) * per]))
        else:
            put = lambda a: jax.device_put(a, shard)
    else:
        put = jax.device_put
    xs, ys = put(xs), put(ys)

    @jax.jit
    def run(state: TrainState, xs, ys):
        variables = {"params": state.params}
        if state.batch_stats:
            variables["batch_stats"] = state.batch_stats

        def body(total, xy):
            bx, by = xy
            if dequant is not None:
                bx = dequantize_images(bx, dequant, impl)
            with global_view(mesh):
                logits = state.apply_fn(variables, bx, train=False)
            correct = jnp.sum(
                (jnp.argmax(logits, axis=-1) == by).astype(jnp.int32))
            return total + correct, None

        total, _ = jax.lax.scan(body, jnp.zeros((), jnp.int32), (xs, ys))
        return total

    return lambda state: int(run(state, xs, ys)) / denom


def evaluate(state: TrainState, images, labels, batch_size: int = 1000,
             sharding=None) -> float:
    """Exact accuracy over a full split, batched to bound HBM use.

    Every process holds the full split (the reference's eval behavior);
    under multi-host the batch helper keeps only locally-owned rows.
    Host-fed — see ``make_resident_eval`` for the device-resident path
    the trainers use by default.
    """
    jitted = make_eval_step()

    def eval_step(state, batch):
        # The mesh in context is part of jit's cache key: the one
        # module-level program is traced again for another mesh.
        with global_view(getattr(sharding, "mesh", None)):
            return jitted(state, batch)

    n = len(labels)
    usable = (n // batch_size) * batch_size
    total_correct = 0

    def put(batch):
        return put_global_batch(batch, sharding) if sharding is not None else batch

    for i in range(0, usable, batch_size):
        batch = put({"image": images[i:i + batch_size],
                     "label": labels[i:i + batch_size]})
        correct, _ = eval_step(state, batch)
        total_correct += int(correct)
    # Remainder evaluated shape-stable by padding to batch_size with
    # label -1 (never matches an argmax class).
    rem = n - usable
    if rem:
        import numpy as np
        pad = batch_size - rem
        batch = put({"image": np.concatenate(
                         [images[usable:],
                          np.zeros((pad,) + images.shape[1:], images.dtype)]),
                     "label": np.concatenate(
                         [labels[usable:],
                          np.full((pad,), -1, labels.dtype)])})
        correct, _ = eval_step(state, batch)
        total_correct += int(correct)
    return total_correct / n
