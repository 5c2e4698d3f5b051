"""Bucketed, overlap-friendly gradient collectives (``--bucket_grads``).

The GSPMD sync step emits ONE all-reduce PER PARAMETER in the backward
pass (measured on this jax pin: 8 gradient all-reduces + 2 scalar metric
all-reduces for the 8-leaf mnist_cnn step) — every one pays the fixed
per-collective latency alpha.  arXiv:1810.11112's characterization says
collective cost is ``t(S) = alpha + S/beta`` with a message-size knee at
``alpha*beta``: below the knee latency dominates and fusing messages is
nearly free throughput.  This module acts on it.

Two modes, selected by ``--shard_update``:

* **bucketed all-reduce** (``--bucket_grads`` alone): the step body runs
  under ``shard_map`` over the data axis — each device computes its local
  partial gradients (bitwise the partials GSPMD computes), the leaves are
  flattened and concatenated into dtype-homogeneous buckets of at most
  ``bucket_bytes``, and each bucket is ONE ``lax.psum``.  Strictly fewer
  all-reduce ops per step, identical total gradient bytes (the metric
  scalars ride their own fused psum pair, as in the async step).

* **explicit ZeRO-1 bucket schedule** (with ``--shard_update``): per
  bucket, leaves are laid out ``[D, ceil(n_i/D)]`` (each leaf padded to a
  multiple of D and split into D row blocks) and concatenated column-wise,
  so ``lax.psum_scatter`` hands device d exactly the d-th block of every
  leaf; the optimizer update runs on that 1/D row (optimizer state lives
  in the SAME row layout — ``init_bucketed_opt_state``), and ONE
  ``lax.all_gather`` of the updated row rebuilds the replicated params.
  This is arXiv:2004.13336's reduce-scatter + sharded-update + all-gather
  schedule made EXPLICIT and bucket-granular: each bucket's reduce-scatter
  depends only on that bucket's gradients, so the scheduler can overlap it
  with the rest of the backward pass (the GSPMD-constraint form of
  ``--shard_update`` hangs everything off the full gradient tree).  The
  collective inventory (utils/profiling.collective_inventory) proves the
  schedule: N_params all-reduces become N_buckets (reduce-scatter,
  all-gather) pairs at unchanged total reduction bytes (+ padding to
  multiples of D, reported by ``plan_buckets``).

The bucket-row machinery here — ``plan_buckets`` (static, order-
preserving membership), ``_rows2d``/``_bucket_flat2d``/``_unbucket_rows``
(the ``[D, ceil(n/D)]`` layout and its inverse), padding accounting, and
``init_bucketed_opt_state`` (optimizer moments AS rows) — is also the
resident layout of the ZeRO-3 step (parallel/zero3.py): same plan, same
rows, with the params themselves joining the optimizer state in 1/D
residency and the all-gather moving to the forward as a prefetch.

Parity contract (the remat/shard_update template): bucketing itself is
bitwise — any two bucket sizes produce identical results (same elementwise
additions, regrouped).  Against the GSPMD default the shard_map backward
may fuse differently, so the gate is bitwise where the program permits
(softmax: pinned bitwise in tests/test_collectives.py, both modes) and
allclose for conv models — the SAME standard ``cross_replica_update_
sharding`` documents for the constraint form, and for the same reason
(summation order, not math).  Dropout models draw per-shard masks (the
rng folds in the device index — the ``_make_sharded_gather`` augment
precedent: same distribution, draws differ from the replicated step).
BatchNorm models are REFUSED by name: the GSPMD step computes
global-batch statistics and a per-shard region would silently change
them to per-shard statistics — a different model, not a different
schedule (run_training refuses before building the step).
"""

from __future__ import annotations

import os
from typing import Callable

import jax
import jax.numpy as jnp
import optax

from distributedtensorflowexample_tpu.parallel.mesh import DATA_AXIS
from distributedtensorflowexample_tpu.refusal import ModeRefusal

# --bucket_grads auto: from an XLA:CPU fit, never timed on chips (ROADMAP
# Queue 1 item 7) — the 8-virtual-device psum knee came out at 244 KB
# (r2=0.99), and 4x the knee, where the alpha/latency share of
# t(S) = alpha + S/beta is down to ~20%, rounds to 1 MiB (DESIGN.md §15).
# BUCKET_GRADS_AUTO_BYTES overrides without a code change.
DEFAULT_BUCKET_BYTES = 1 << 20

# Compiled-schedule contracts, checked by analysis/hlo_lint.py against
# the lowered module text (PR 13) — the static twin of the runtime
# golden multisets in tests/test_collectives.py.  Symbols resolve at
# check time: B = buckets in the plan.
#
# Bucketed all-reduce: N_params gradient ARs collapse to one AR per
# bucket + the fused metrics pair; nothing else may appear on the wire.
BUCKETED_HLO_CONTRACT = {
    "mode": "bucketed_allreduce",
    "collective_budget": {"all-reduce": "B+2"},
    "require_alias": True,
    "dtype_ceiling": "f32",
}
# ZeRO-1 (arXiv:2004.13336): per bucket one reduce-scatter then its
# UPDATE-CLOSING all-gather (rs_ag_paired — the AG textually follows
# its RS: gather the updated row, not the gradient), plus the metrics
# pair.  Contrast zero3.HLO_CONTRACT, where the pairing flips.
ZERO1_HLO_CONTRACT = {
    "mode": "zero1",
    "rs_ag_paired": True,
    "collective_budget": {"reduce-scatter": "B", "all-gather": "B",
                          "all-reduce": 2},
    "require_alias": True,
    "dtype_ceiling": "f32",
}


def resolve_bucket_bytes(flag: str) -> int | None:
    """``--bucket_grads`` resolution: ``""`` = off (None), ``auto`` = the
    measured-knee default (env BUCKET_GRADS_AUTO_BYTES overrides, same
    validation — an override of 0 silently disabling the bucketing the
    flag explicitly asked for would be the worst kind of knob), else a
    positive byte count.  Bad values fail by name at flag-validation
    time, not in the middle of a trace."""
    if not flag:
        return None
    if flag == "auto":
        env = os.environ.get("BUCKET_GRADS_AUTO_BYTES")
        if env is None:
            return DEFAULT_BUCKET_BYTES
        flag, source = env, "BUCKET_GRADS_AUTO_BYTES"
    else:
        source = "--bucket_grads"
    try:
        nbytes = int(flag)
    except ValueError:
        # ModeRefusal even though the flag name rides in `source` (the
        # named-refusal lint can only see literal --tokens): these ARE
        # mode-legality refusals and must stay on the one grep.
        raise ModeRefusal(f"{source} must be 'auto' or a byte count, "
                          f"got {flag!r}") from None
    if nbytes <= 0:
        raise ModeRefusal(f"{source} byte count must be positive, "
                          f"got {nbytes}")
    return nbytes


def plan_buckets(leaves, bucket_bytes: int) -> list[list[int]]:
    """Group leaf INDICES into dtype-homogeneous buckets of at most
    ``bucket_bytes`` (a single leaf over the cap gets its own bucket —
    never split, so leaf<->bucket membership is static).  Order-
    preserving over the canonical ``jax.tree`` flatten order, so the
    plan is a pure function of the param tree + cap: every device, every
    restart, and the opt-state initializer agree on it."""
    buckets: list[list[int]] = []
    cur: list[int] = []
    cur_bytes, cur_dt = 0, None
    for i, leaf in enumerate(leaves):
        nb = leaf.size * leaf.dtype.itemsize
        if cur and (leaf.dtype != cur_dt or cur_bytes + nb > bucket_bytes):
            buckets.append(cur)
            cur, cur_bytes = [], 0
        cur.append(i)
        cur_bytes += nb
        cur_dt = leaf.dtype
    if cur:
        buckets.append(cur)
    return buckets


def bucket_padding_bytes(leaves, num_devices: int) -> int:
    """Bytes of zero-padding the ZeRO-1 row layout adds (each leaf padded
    to a multiple of the mesh size) — the "±padding, reported" term in
    the unchanged-total-bytes claim.  Independent of bucket membership:
    padding is per-leaf, whatever bucket the leaf lands in."""
    return sum(((-leaf.size) % num_devices) * leaf.dtype.itemsize
               for leaf in leaves)


def _rows2d(leaf, num_devices: int):
    """Flatten *leaf*, zero-pad to a multiple of ``num_devices``, and
    split into D row blocks: ``[D, ceil(n/D)]``.  Row d is the d-th
    contiguous block — the shard device d owns under the ZeRO-1 layout."""
    flat = leaf.ravel()
    pad = (-flat.size) % num_devices
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    return flat.reshape(num_devices, -1)


def _bucket_flat2d(leaves, idxs, num_devices: int):
    """The bucket's ``[D, W]`` layout: per-leaf row blocks concatenated
    column-wise, so every row holds the SAME leaves' d-th blocks.
    ``ravel()`` of this is exactly the vector ``psum_scatter`` splits
    into per-device rows."""
    return jnp.concatenate([_rows2d(leaves[i], num_devices) for i in idxs],
                           axis=1)


def _unbucket_rows(full_rows, leaves_template, idxs):
    """Inverse of :func:`_bucket_flat2d`: slice the gathered ``[D, W]``
    array back into leaf-shaped arrays (padding dropped)."""
    D = full_rows.shape[0]
    out = {}
    off = 0
    for i in idxs:
        leaf = leaves_template[i]
        w = -(-leaf.size // D)
        out[i] = full_rows[:, off:off + w].ravel()[:leaf.size].reshape(
            leaf.shape)
        off += w
    return out


def init_bucketed_opt_state(tx: optax.GradientTransformation, params,
                            bucket_bytes: int, mesh):
    """Optimizer state for the ZeRO-1 bucket schedule: ``tx.init`` over
    the tuple of per-bucket FLAT row vectors (global shape ``[D*W_b]``,
    sharded one row per device along the data axis), replacing the
    params-shaped state ``TrainState.create_sharded`` laid out.  The
    layout is the step's exact working set — momentum (and any other
    params-shaped moment) lives only as the 1/D row each device updates,
    which is the ZeRO-1 state-residency win made structural instead of
    constraint-hinted.  Scalars (schedule counts) stay replicated."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    D = mesh.shape[DATA_AXIS]
    leaves = jax.tree.leaves(params)
    states = []
    for idxs in plan_buckets(leaves, bucket_bytes):
        flat = _bucket_flat2d(leaves, idxs, D).ravel()
        states.append(tx.init(flat))
    row = NamedSharding(mesh, P(DATA_AXIS))
    repl = NamedSharding(mesh, P())
    return jax.tree.map(
        lambda x: jax.device_put(x, row if getattr(x, "ndim", 0) else repl),
        tuple(states))


def build_bucketed_step_fn(label_smoothing: float, ce_impl: str, mesh,
                           num_replicas: int, replicas_to_aggregate: int,
                           bucket_bytes: int,
                           shard_update: bool = False) -> Callable:
    """The bucketed (state, batch) -> (state, metrics) step body — the
    shard_map twin of ``sync._build_step_fn`` (see module docstring for
    the two modes and the parity contract).  The caller jits it with the
    same donation the plain body gets."""
    from distributedtensorflowexample_tpu.parallel.sync import make_loss_rows
    from jax.sharding import PartitionSpec as P

    if mesh is None or mesh.shape[DATA_AXIS] <= 1:
        raise ValueError("bucketed gradient collectives need a multi-device "
                         "data mesh (there is nothing to reduce on one "
                         "device) — callers fall back to the plain step")
    D = mesh.shape[DATA_AXIS]
    R, N = int(replicas_to_aggregate), max(1, int(num_replicas))
    if not 0 <= R <= N:
        raise ValueError(
            f"replicas_to_aggregate {R} must be in [0, {N}] (0 = all)")
    partial_agg = 0 < R < N
    # Per-shard loss head (mesh=None): the Pallas CE kernel applies
    # directly on the local rows, exactly as in the async shard_map step.
    loss_rows = make_loss_rows(label_smoothing, ce_impl, mesh=None)

    def step(state, batch):
        if state.batch_stats:
            raise ModeRefusal(
                "--bucket_grads cannot run a BatchNorm model: the default "
                "GSPMD step computes global-batch statistics and the "
                "bucketed per-shard region would silently turn them into "
                "per-shard statistics (a different model, not a different "
                "collective schedule). Use the default fused all-reduce "
                "for BN models")

        wspec = P(DATA_AXIS)
        pspec = jax.tree.map(lambda _: P(), state.params)
        if shard_update:
            # Bucket-row opt state: vectors are one row per device,
            # schedule counts replicated (init_bucketed_opt_state).
            ospec = jax.tree.map(
                lambda x: wspec if getattr(x, "ndim", 0) else P(),
                state.opt_state)
        else:
            ospec = jax.tree.map(lambda _: P(), state.opt_state)

        def body(step_no, rng, params, opt_state, img, lab):
            d = jax.lax.axis_index(DATA_AXIS)
            step_rng = jax.random.fold_in(rng, step_no)
            local_b = img.shape[0]
            global_b = local_b * D

            def loss_fn(p):
                # Per-shard dropout stream: the device index folds in
                # (same distribution as the replicated draw; draws
                # differ — the sharded-gather augment precedent).
                logits = state.apply_fn(
                    {"params": p}, img, train=True,
                    rngs={"dropout": jax.random.fold_in(step_rng, d)})
                with jax.named_scope("loss"):
                    rows = loss_rows(logits, lab)
                if not partial_agg:
                    return jnp.sum(rows) / global_b, logits
                # SyncReplicasOptimizer partial aggregation, in GLOBAL
                # row coordinates (batch sharding is contiguous per
                # device, so local row r is global row d*local_b + r).
                per_shard = global_b // N
                row_ids = jnp.arange(local_b, dtype=jnp.int32) + d * local_b
                selected = ((row_ids // per_shard - step_no) % N) < R
                return (jnp.sum(rows * selected.astype(rows.dtype))
                        / (R * per_shard), logits)

            (loss_part, logits), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params)
            gleaves, tdef = jax.tree.flatten(grads)
            buckets = plan_buckets(gleaves, bucket_bytes)

            if not shard_update:
                red = list(gleaves)
                for idxs in buckets:
                    flat = jnp.concatenate([gleaves[i].ravel()
                                            for i in idxs])
                    flat = jax.lax.psum(flat, DATA_AXIS)
                    off = 0
                    for i in idxs:
                        n = gleaves[i].size
                        red[i] = flat[off:off + n].reshape(gleaves[i].shape)
                        off += n
                full_grads = jax.tree.unflatten(tdef, red)
                updates, new_opt = state.tx.update(full_grads, opt_state,
                                                   params)
                new_params = optax.apply_updates(params, updates)
            else:
                pleaves = jax.tree.leaves(params)
                new_leaves = list(pleaves)
                new_opt_list = []
                for bi, idxs in enumerate(buckets):
                    # Reduce-scatter the bucket: row d of the summed
                    # [D, W] layout lands on device d — the 1/D shard
                    # this device updates.
                    g_flat = _bucket_flat2d(gleaves, idxs, D).ravel()
                    g_row = jax.lax.psum_scatter(
                        g_flat, DATA_AXIS, scatter_dimension=0, tiled=True)
                    p_row = jax.lax.dynamic_slice_in_dim(
                        _bucket_flat2d(pleaves, idxs, D), d, 1, 0)[0]
                    u_row, st = state.tx.update(g_row, opt_state[bi], p_row)
                    new_p_row = optax.apply_updates(p_row, u_row)
                    new_opt_list.append(st)
                    # One all-gather of the UPDATED row closes the
                    # bucket; its only dependency is this bucket's
                    # reduce-scatter + elementwise update, so buckets
                    # pipeline instead of meeting at a full-tree barrier.
                    full = jax.lax.all_gather(
                        new_p_row, DATA_AXIS, axis=0,
                        tiled=True).reshape(D, -1)
                    for i, piece in _unbucket_rows(full, pleaves,
                                                   idxs).items():
                        new_leaves[i] = piece
                new_params = jax.tree.unflatten(
                    jax.tree.structure(params), new_leaves)
                new_opt = tuple(new_opt_list)

            correct = jnp.sum(
                (jnp.argmax(logits, axis=-1) == lab).astype(jnp.float32))
            # One fused psum pair for both scalar metrics (async-step
            # idiom) instead of GSPMD's two standalone scalar all-reduces.
            loss, correct = jax.lax.psum((loss_part, correct), DATA_AXIS)
            # Accuracy normalizes per label ELEMENT (tokens for a [b, T]
            # LM shard; == global_b for [b] image labels).
            return new_params, new_opt, loss, correct / (lab.size * D)

        body_m = jax.shard_map(
            body, mesh=mesh,
            in_specs=(P(), P(), pspec, ospec, wspec, wspec),
            out_specs=(pspec, ospec, P(), P()), check_vma=False)
        new_params, new_opt, loss, acc = body_m(
            state.step, state.rng, state.params, state.opt_state,
            batch["image"], batch["label"])
        new_state = state.replace(step=state.step + 1, params=new_params,
                                  opt_state=new_opt)
        return new_state, {"loss": loss, "accuracy": acc}

    return step


def bucketed_tree_psum(tree, bucket_bytes: int, axis_name: str = DATA_AXIS):
    """Fuse a per-leaf tree psum into dtype-homogeneous bucketed psums —
    the same fewer-larger-collectives trade for ANY tree-shaped
    all-reduce (the async step's worker average uses it: its per-leaf
    psum inside ``jax.tree.map`` is exactly the per-parameter pattern
    ``--bucket_grads`` exists to fuse).  Bitwise: concatenation regroups
    which psum carries each element, never the element's cross-device
    addition."""
    leaves, tdef = jax.tree.flatten(tree)
    out = list(leaves)
    for idxs in plan_buckets(leaves, bucket_bytes):
        flat = jax.lax.psum(
            jnp.concatenate([leaves[i].ravel() for i in idxs]), axis_name)
        off = 0
        for i in idxs:
            n = leaves[i].size
            out[i] = flat[off:off + n].reshape(leaves[i].shape)
            off += n
    return jax.tree.unflatten(tdef, out)
