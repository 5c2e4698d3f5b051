"""ZeRO-3 / FSDP: full param+grad sharding with a double-buffered
all-gather/compute overlap schedule (``--shard_params``).

ZeRO-1 (parallel/bucketing.py's composed ``--bucket_grads`` +
``--shard_update`` schedule) shards the OPTIMIZER state 1/D and gathers
the updated params back to a replicated tree every step — params and
grads still cost a full copy per device, which is what caps the lm
ladder at "what one device holds".  This module extends the same
knee-sized, dtype-homogeneous bucket-row layout (arXiv:2004.13336 §ZeRO
stage 3) to params and grads:

* **Resident layout**: params live as per-bucket flat rows — bucket b
  is the ``[D, ceil(n_b/D)]`` layout of PR 6 (`_bucket_flat2d`: each
  leaf zero-padded to a multiple of D, split into D row blocks,
  concatenated column-wise) raveled to one ``[D*W_b]`` array sharded
  one row per device along the data axis.  Optimizer state lives in the
  SAME rows (``init_bucketed_opt_state`` — unchanged from ZeRO-1).
  Per-device persistent state is therefore (params + opt moments)/D
  (+ the reported row padding); nothing params-shaped is resident.

* **Gather-before-use, free-after-last-use**: the forward all-gathers
  each bucket's row just before the model consumes its leaves; the
  gathered full leaves are step-local TEMPORARIES (XLA frees them after
  their last backward use, and the donated row buffers alias in place),
  so the full tree never exists as persistent state — the compiler
  memory analysis shows it in ``temp_bytes``, not ``argument_bytes``
  (the measured form of the 1/D claim: see
  ``utils/profiling.state_residency_per_device``).

* **Grads reduce-scattered per bucket, BY CONSTRUCTION**: the gather is
  differentiated through — ``jax.lax.all_gather``'s transpose IS
  ``psum_scatter`` — so autodiff places one reduce-scatter per bucket
  at exactly the point in the backward pass where that bucket's
  gradient contributions are complete (last-consumed bucket's RS first:
  the overlappable schedule falls out of the chain rule).  The gradient
  a device ever holds is its 1/D row; the full gradient tree is never
  materialized, not even transiently as a single object.

* **Double-buffered prefetch** (``overlap=True``, the default): bucket
  i's all-gather is chained — through a ``custom_vjp`` identity whose
  forward is ``lax.optimization_barrier`` (the barrier has no AD rule
  on this jax pin, hence the wrapper) — onto a scalar probe of bucket
  i-2's gathered output, so at most TWO gathered buckets are in flight
  ahead of their consumers: gather i+1 issues while bucket i's leaves
  are being consumed, the classic double buffer.  ``overlap=False``
  chains on bucket i-1 instead (strictly serial gathers) — the A/B
  control.  XLA:CPU dispatches synchronously, so a CPU wall-clock pair
  only proves the schedule compiles both ways; the overlap win itself
  has never been timed on chips (ROADMAP names ``--zero3_overlap`` as a
  debt).  There the latency-hiding scheduler can turn the
  independent AG-prefetch chain into async collectives hidden under
  block compute — graft-LM's block ladder supplies the gather points
  (leaves flatten embed → block0..blockN → ln_f, so knee-sized buckets
  track block boundaries).

Update: per bucket, ``tx.update`` runs on the 1/D grad row against the
1/D param row and row-layout moments, and the updated row is written
straight back — NO trailing all-gather (ZeRO-1's step-closing AG
disappears; the next step's forward re-gathers, which is the ZeRO-3
trade: one extra AG of params per step in exchange for 1/D residency).

Parity contract: same as the ZeRO-1 bucket schedule and for the same
reasons — the gathered leaves are bitwise the replicated leaves
(concatenate/reshape move bytes, never arithmetic), the RS performs the
same cross-device additions psum_scatter performed, so softmax is
bitwise vs the bucketed baseline and conv/LM models hold to the
documented allclose standard (summation order, not math).  BatchNorm
models are refused by name (the bucketing.py argument verbatim);
dropout folds in the device index (per-shard streams).  The overlap
knob is pure scheduling: overlap on/off is bitwise-identical.

Checkpoint/resume: ``run_meta.update_layout = "zero3_rows"`` — params
AND optimizer state are bucket rows, a function of D, so cross-layout
and cross-mesh-size resumes are refused by name (trainers/common.py),
exactly like ``bucket_rows``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
import optax

from distributedtensorflowexample_tpu.parallel.bucketing import (
    _bucket_flat2d, _unbucket_rows, bucket_padding_bytes, plan_buckets)
from distributedtensorflowexample_tpu.parallel.mesh import DATA_AXIS
from distributedtensorflowexample_tpu.refusal import ModeRefusal

# The ZeRO-3 schedule as a compiled-HLO contract (analysis/hlo_lint.py,
# PR 13) — the static form of the claims in the module docstring, each
# previously pinned only by runtime golden multisets: every bucket's
# forward-prefetch all-gather textually PRECEDES its reduce-scatter
# (ag_rs_paired — autodiff's all_gather transpose placed the RS in the
# backward), NO all-gather after the last RS (the updated 1/D row
# writes straight back; a trailing AG would be ZeRO-1's update-closing
# gather leaking into a schedule that promises none), exactly one
# AG + one RS per bucket + the fused metrics pair on the wire, donation
# aliased (the row buffers update in place), no float upcast past f32.
# Symbols resolve at check time: B = buckets in the layout's plan.
HLO_CONTRACT = {
    "mode": "zero3",
    "ag_rs_paired": True,
    "no_trailing_all_gather": True,
    "collective_budget": {"all-gather": "B", "reduce-scatter": "B",
                          "all-reduce": 2},
    "require_alias": True,
    "dtype_ceiling": "f32",
}


@dataclasses.dataclass(frozen=True)
class LeafSpec:
    """Shape+dtype of one param leaf — the static template
    ``_unbucket_rows``/``plan_buckets`` slice against once the real
    leaves live only as bucket rows.  Hashable (jit cache key)."""
    shape: tuple
    dtype: Any          # np.dtype — hashable, itemsize-bearing

    @property
    def size(self) -> int:
        n = 1
        for d in self.shape:
            n *= int(d)
        return n


class Zero3Layout:
    """Everything static about one ZeRO-3 layout: the leaf template, the
    treedef, the bucket plan (PR 6's ``plan_buckets`` over the canonical
    flatten order — pure function of tree + cap, every device/restart
    agrees), and the mesh size.  One instance serves the state
    converters, the step builder, and the eval-side materializer."""

    def __init__(self, params, bucket_bytes: int, mesh):
        if mesh is None or mesh.shape[DATA_AXIS] <= 1:
            raise ValueError(
                "ZeRO-3 param sharding needs a multi-device data mesh "
                "(there is nothing to shard on one device) — callers "
                "fall back to the plain step")
        leaves, self.treedef = jax.tree.flatten(params)
        self.leaf_specs = tuple(
            LeafSpec(tuple(l.shape), np.dtype(l.dtype)) for l in leaves)
        self.plan = tuple(tuple(b)
                          for b in plan_buckets(self.leaf_specs,
                                                bucket_bytes))
        self.bucket_bytes = int(bucket_bytes)
        self.num_devices = int(mesh.shape[DATA_AXIS])
        self.mesh = mesh
        self.padding_bytes = bucket_padding_bytes(self.leaf_specs,
                                                  self.num_devices)
        self._materialize_jit = None

    @property
    def num_buckets(self) -> int:
        return len(self.plan)

    # --- state conversion -------------------------------------------------
    def init_rows(self, params) -> tuple:
        """Full (replicated) params -> the resident row layout: one flat
        ``[D*W_b]`` array per bucket, sharded one row per device.  The
        input is DONATED — converting frees the replicated copy, so the
        full tree stops being resident the moment the layout exists."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        D = self.num_devices
        plan = self.plan

        def to_rows(p):
            lv = jax.tree.leaves(p)
            return tuple(_bucket_flat2d(lv, idxs, D).ravel()
                         for idxs in plan)

        row = NamedSharding(self.mesh, P(DATA_AXIS))
        return jax.jit(to_rows, out_shardings=row,
                       donate_argnums=0)(params)

    def materialize(self, rows: tuple):
        """Rows -> the full params tree (for eval / export — never the
        train step, whose gathers live inside the differentiated body).
        Jitted once per layout; jax re-gathers across the mesh as the
        replicated output sharding demands."""
        if self._materialize_jit is None:
            from jax.sharding import NamedSharding, PartitionSpec as P
            D = self.num_devices
            specs, plan, treedef = self.leaf_specs, self.plan, self.treedef

            def gather(rows):
                leaves: list = [None] * len(specs)
                for bi, idxs in enumerate(plan):
                    full = rows[bi].reshape(D, -1)
                    for i, piece in _unbucket_rows(full, specs,
                                                   idxs).items():
                        leaves[i] = piece
                return jax.tree.unflatten(treedef, leaves)

            repl = NamedSharding(self.mesh, P())
            self._materialize_jit = jax.jit(gather, out_shardings=repl)
        return self._materialize_jit(rows)


# --- the double-buffer tie ------------------------------------------------
# ``lax.optimization_barrier`` pins issue order in the compiled schedule
# but has no differentiation rule on this jax pin, and the gathers it
# must order live INSIDE the differentiated loss.  This custom_vjp
# identity carries the barrier through AD: forward barriers ``x`` on the
# scalar ``probe`` (x cannot be scheduled before probe exists), backward
# passes the cotangent straight through (the probe's is zero — it is a
# scheduling edge, not math).

@jax.custom_vjp
def _tie(x, probe):
    out, _ = jax.lax.optimization_barrier((x, probe))
    return out


def _tie_fwd(x, probe):
    return _tie(x, probe), None


def _tie_bwd(_, ct):
    return ct, jnp.zeros((), jnp.float32)


_tie.defvjp(_tie_fwd, _tie_bwd)


def build_zero3_step_fn(label_smoothing: float, ce_impl: str, mesh,
                        num_replicas: int, replicas_to_aggregate: int,
                        layout: Zero3Layout,
                        overlap: bool = True) -> Callable:
    """The ZeRO-3 (state, batch) -> (state, metrics) step body — the
    shard_map sibling of ``bucketing.build_bucketed_step_fn``.  The
    state's ``params`` must be ``layout.init_rows`` output (and
    ``opt_state`` the matching ``init_bucketed_opt_state`` rows); the
    caller jits it with the same donation the other bodies get.  See
    the module docstring for the schedule and the parity contract."""
    from distributedtensorflowexample_tpu.parallel.sync import make_loss_rows
    from jax.sharding import PartitionSpec as P

    D = layout.num_devices
    if mesh.shape[DATA_AXIS] != D:
        raise ValueError(f"step mesh size {mesh.shape[DATA_AXIS]} does "
                         f"not match the layout's {D} — the row layout "
                         f"is a function of D")
    R, N = int(replicas_to_aggregate), max(1, int(num_replicas))
    if not 0 <= R <= N:
        raise ValueError(
            f"replicas_to_aggregate {R} must be in [0, {N}] (0 = all)")
    partial_agg = 0 < R < N
    loss_rows = make_loss_rows(label_smoothing, ce_impl, mesh=None)
    specs, plan, treedef = layout.leaf_specs, layout.plan, layout.treedef
    # Double buffer = at most 2 gathered buckets in flight ahead of
    # their consumers; the serial control chains each gather on its
    # predecessor instead.
    depth = 2 if overlap else 1

    def step(state, batch):
        if state.batch_stats:
            raise ModeRefusal(
                "--shard_params cannot run a BatchNorm model: the default "
                "GSPMD step computes global-batch statistics and the "
                "sharded per-device region would silently turn them into "
                "per-shard statistics (a different model, not a different "
                "collective schedule). Use the default fused all-reduce "
                "for BN models")
        if not (isinstance(state.params, tuple)
                and len(state.params) == len(plan)):
            raise ValueError(
                f"ZeRO-3 step expects params as {len(plan)} bucket rows "
                f"(Zero3Layout.init_rows); got "
                f"{type(state.params).__name__} — the state was not "
                f"converted to the resident row layout")

        wspec = P(DATA_AXIS)
        pspec = jax.tree.map(lambda _: wspec, state.params)
        ospec = jax.tree.map(
            lambda x: wspec if getattr(x, "ndim", 0) else P(),
            state.opt_state)

        def body(step_no, rng, p_rows, opt_state, img, lab):
            d = jax.lax.axis_index(DATA_AXIS)
            step_rng = jax.random.fold_in(rng, step_no)
            local_b = img.shape[0]
            global_b = local_b * D

            def loss_fn(p_rows):
                # The AG-prefetch schedule: one tiled all-gather per
                # bucket, issue order pinned by the _tie chain.  Leaves
                # sliced out of the gathered rows are bitwise the
                # replicated leaves; differentiating THROUGH the gather
                # is what places one psum_scatter per bucket in the
                # backward pass (all_gather's transpose).
                full_rows = []
                for bi, row in enumerate(p_rows):
                    j = bi - depth
                    if j >= 0:
                        row = _tie(row, full_rows[j].ravel()[0].astype(
                            jnp.float32))
                    full_rows.append(jax.lax.all_gather(
                        row, DATA_AXIS, axis=0, tiled=True).reshape(D, -1))
                leaves: list = [None] * len(specs)
                for bi, idxs in enumerate(plan):
                    for i, piece in _unbucket_rows(full_rows[bi], specs,
                                                   idxs).items():
                        leaves[i] = piece
                params = jax.tree.unflatten(treedef, leaves)
                logits = state.apply_fn(
                    {"params": params}, img, train=True,
                    rngs={"dropout": jax.random.fold_in(step_rng, d)})
                with jax.named_scope("loss"):
                    rows = loss_rows(logits, lab)
                if not partial_agg:
                    return jnp.sum(rows) / global_b, logits
                # SyncReplicasOptimizer partial aggregation in GLOBAL
                # row coordinates (the bucketed-step form, verbatim).
                per_shard = global_b // N
                row_ids = jnp.arange(local_b, dtype=jnp.int32) + d * local_b
                selected = ((row_ids // per_shard - step_no) % N) < R
                return (jnp.sum(rows * selected.astype(rows.dtype))
                        / (R * per_shard), logits)

            (loss_part, logits), g_rows = jax.value_and_grad(
                loss_fn, has_aux=True)(p_rows)
            # g_rows[bi] is this device's 1/D reduce-scattered grad row
            # (psum_scatter placed by the gather's transpose).  The
            # update is pure elementwise on rows; the updated row writes
            # straight back — no step-closing all-gather (the next
            # forward re-gathers: the ZeRO-3 trade).
            new_rows, new_opt = [], []
            for bi in range(len(plan)):
                u_row, st = state.tx.update(g_rows[bi], opt_state[bi],
                                            p_rows[bi])
                new_rows.append(optax.apply_updates(p_rows[bi], u_row))
                new_opt.append(st)
            correct = jnp.sum(
                (jnp.argmax(logits, axis=-1) == lab).astype(jnp.float32))
            # One fused psum pair for both scalar metrics (the bucketed-
            # step idiom).
            loss, correct = jax.lax.psum((loss_part, correct), DATA_AXIS)
            return (tuple(new_rows), tuple(new_opt), loss,
                    correct / (lab.size * D))

        body_m = jax.shard_map(
            body, mesh=mesh,
            in_specs=(P(), P(), pspec, ospec, wspec, wspec),
            out_specs=(pspec, ospec, P(), P()), check_vma=False)
        new_rows, new_opt, loss, acc = body_m(
            state.step, state.rng, state.params, state.opt_state,
            batch["image"], batch["label"])
        new_state = state.replace(step=state.step + 1, params=new_rows,
                                  opt_state=new_opt)
        return new_state, {"loss": loss, "accuracy": acc}

    return step
