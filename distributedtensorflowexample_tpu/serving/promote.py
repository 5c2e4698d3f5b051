"""Snapshot → serving promotion: the training stack's recovery format
is the serving stack's model source.

A serving worker must never trust a snapshot MORE than the supervisor
does, so promotion goes through the exact SnapshotStore validity
machinery (manifest-last commit, size+crc re-check, newest-valid
fallback past a torn final write — resilience/snapshot.py): a corrupted
newest snapshot costs one snapshot interval of model freshness, never
the serving worker.

Layout awareness: training snapshots are written in the layout the run
trained in (``run_meta.update_layout``): plain ``tree``, ZeRO-1
``bucket_rows`` (optimizer state as per-bucket 1/D rows), or ZeRO-3
``zero3_rows`` (params AND optimizer state as rows).  The TRAINER
refuses cross-layout resumes by name, because resuming must be bitwise;
serving only needs the params, so promotion instead *materializes*:
a row-layout snapshot restores into a row-shaped template and the full
param tree is gathered back through the PR 12 seam
(``Zero3Layout.materialize`` — the same jitted gather eval/export use),
never through a second opinion about the bucket plan.

The promotion template's optimizer is the repo-wide training default
(SGD + momentum): the snapshot payload is the full
``saveable_state_dict`` leaf list, and restoring demands a
leaf-count-identical template even though serving discards everything
but the params.  A snapshot written by a run with a different optimizer
fails the leaf-count check loudly (SnapshotStore.restore's existing
error) rather than mis-binding.
"""

from __future__ import annotations

import dataclasses
import os
import sys

import jax.numpy as jnp
import numpy as np
import optax

from distributedtensorflowexample_tpu.models import build_model
from distributedtensorflowexample_tpu.refusal import ModeRefusal
from distributedtensorflowexample_tpu.resilience.snapshot import (
    SnapshotStore)
# A second name for queue.py's definition: queue.py must not import
# this module (tests/test_import_graph.py).
from distributedtensorflowexample_tpu.serving.queue import (  # noqa: F401
    as_prompt)
from distributedtensorflowexample_tpu.training.state import TrainState

_LAYOUTS = ("tree", "bucket_rows", "zero3_rows")


def _log(msg: str) -> None:
    print(f"serve.promote: {msg}", file=sys.stderr, flush=True)


def serve_snapshot_default() -> str:
    """``SERVE_SNAPSHOT``: the snapshot directory tools/serve_lm.py
    loads when ``--snapshot`` is not passed — empty means the flag is
    required."""
    return os.environ.get("SERVE_SNAPSHOT", "")


def _default_tx():
    # The repo-wide training default (trainers, faultline):
    # promotion templates must mirror what the snapshot writers ran.
    return optax.sgd(0.1, momentum=0.9)


@dataclasses.dataclass
class PromotedModel:
    """What promotion hands the engine: the full (materialized) param
    tree plus the provenance the serving ledger rows carry."""
    model: object               # the training TransformerLM (arch facts)
    params: object              # full tree, layout-independent
    step: int                   # snapshot step served
    layout: str                 # update_layout the snapshot was written in
    manifest: dict              # the winning snapshot's manifest


def _template(model, tx, layout: str, meta: dict, sample_len: int):
    """(template TrainState, zero3 layout-or-None) for a snapshot's
    declared layout — row layouts rebuild the exact bucket geometry
    from the manifest's recorded mesh size + bucket cap."""
    base = TrainState.create(model, tx,
                             jnp.zeros((1, sample_len), jnp.int32))
    if layout == "tree":
        return base, None
    mesh_size = meta.get("mesh_size")
    bucket_bytes = meta.get("bucket_bytes")
    if not mesh_size or not bucket_bytes:
        raise ValueError(
            f"snapshot layout {layout!r} needs manifest meta "
            f"mesh_size+bucket_bytes to rebuild the row geometry; this "
            f"manifest carries {sorted(meta)} — it was not written by a "
            f"layout-stamping writer")
    import jax

    from distributedtensorflowexample_tpu.engine.engine import (
        apply_update_layout)
    from distributedtensorflowexample_tpu.parallel import (
        make_mesh, replicated_sharding)
    if mesh_size > len(jax.devices()):
        raise ModeRefusal(
            f"snapshot was written at mesh_size {mesh_size} "
            f"(--shard_params/--shard_update rows are a function of D) "
            f"but this process sees {len(jax.devices())} device(s) — "
            f"materializing needs a mesh at least that wide")
    mesh = make_mesh(int(mesh_size))
    # The row converters shard across the mesh; the template's params
    # must live ON it first (TrainState.create places single-device).
    # The re-layout itself is the Engine's shared pass — the one the
    # snapshot writer ran — so the row geometry can't drift.
    repl = jax.device_put(base.params, replicated_sharding(mesh))
    rowed, z3 = apply_update_layout(
        base.replace(params=repl), tx, update_layout=layout,
        bucket_bytes=int(bucket_bytes), mesh=mesh)
    if layout == "bucket_rows":
        # Params stay the single-device create() tree: only the
        # optimizer state is row-shaped in a ZeRO-1 snapshot.
        return base.replace(opt_state=rowed.opt_state), None
    return base.replace(opt_state=rowed.opt_state, params=rowed.params), z3


def promote(snapshot_dir: str, size: str, *, step: int | None = None,
            tx=None, sample_len: int = 8, model=None) -> PromotedModel:
    """Load the newest VALID snapshot of a graft-LM ``size`` from
    ``snapshot_dir`` and return the full serving params.  ``model`` is
    the model itself where it was built from a configuration file
    (``models.build_model_from_config``) and not from the size ladder;
    ``size`` is then the name its snapshots are stamped with.

    - newest-first with fallback: a torn/corrupt newest snapshot is
      discarded (counted on ``snapshot_fallbacks_total``) and the
      previous valid one serves — the supervisor's contract, reused;
    - layout cross-check: a manifest stamped with a different model
      size than requested is refused by name (binding a 4-layer tree
      into an 8-layer template would fail anyway, but late and
      unreadably);
    - row layouts materialize through ``Zero3Layout.materialize``.
    """
    store = SnapshotStore(snapshot_dir)
    if step is None:
        step = store.latest_valid()
    if step is None:
        raise ValueError(
            f"no valid snapshot in {snapshot_dir!r} — nothing to "
            f"promote (run training, or serve_lm's init_if_missing "
            f"mode for a demo-grade init)")
    man = store.manifest(step) or {}
    meta = man.get("meta") or {}
    snap_model = meta.get("model")
    if snap_model and snap_model != size:
        raise ModeRefusal(
            f"snapshot {step} in {snapshot_dir} was written by model "
            f"{snap_model!r}; this worker was asked to serve --size "
            f"{size!r} — refusing to bind across architectures")
    layout = meta.get("update_layout", "tree")
    if layout not in _LAYOUTS:
        raise ValueError(f"snapshot {step} declares unknown "
                         f"update_layout {layout!r} (one of {_LAYOUTS})")
    model = model or build_model(size)
    template, z3 = _template(model, tx or _default_tx(), layout, meta,
                             sample_len)
    state = store.restore(template, step=step)
    params = z3.materialize(state.params) if z3 is not None \
        else state.params
    _log(f"promoted snapshot step {step} ({layout}) from "
         f"{snapshot_dir}")
    return PromotedModel(model=model, params=params, step=int(step),
                         layout=layout, manifest=man)


@dataclasses.dataclass
class ShardedPromotion:
    """What sharded promotion hands the row-resident engine: the
    bucket rows at 1/D per device plus the layout that explains them —
    the full tree is NEVER a member (that absence is the point)."""
    model: object               # the training TransformerLM (arch facts)
    rows: tuple                 # per-bucket [D*W_b] rows, 1/D resident
    layout: object              # the Zero3Layout (plan, mesh, treedef)
    step: int                   # snapshot step served
    source_layout: str          # update_layout the snapshot was written in
    manifest: dict              # the winning snapshot's manifest


def promote_sharded(snapshot_dir: str, size: str, *,
                    step: int | None = None, tx=None,
                    sample_len: int = 8, mesh_size: int | None = None,
                    bucket_bytes: int | None = None) -> ShardedPromotion:
    """Promotion that keeps params SHARDED: the serving twin of
    :func:`promote` for the params-stay-sharded engine
    (serving/sharded.py).  A ``zero3_rows`` snapshot restores into its
    row template and the rows are handed over AS IS — no
    ``Zero3Layout.materialize``, so the full tree is never resident in
    the worker, which is what the measured-1/D acceptance criterion
    means.  A ``tree``/``bucket_rows`` snapshot starts replicated by
    format; its params convert DOWN through ``Zero3Layout.init_rows``
    (which donates — the replicated copy stops existing the moment the
    layout does).

    ``mesh_size`` for a ``zero3_rows`` snapshot is the manifest's (rows
    are a function of D; asking for a different one is refused by
    name).  For replicated formats it defaults to the manifest's
    recorded mesh, else every visible device."""
    import jax

    from distributedtensorflowexample_tpu.parallel import (
        make_mesh, replicated_sharding)
    from distributedtensorflowexample_tpu.parallel.bucketing import (
        DEFAULT_BUCKET_BYTES)
    from distributedtensorflowexample_tpu.parallel.zero3 import (
        Zero3Layout)

    store = SnapshotStore(snapshot_dir)
    if step is None:
        step = store.latest_valid()
    if step is None:
        raise ValueError(
            f"no valid snapshot in {snapshot_dir!r} — nothing to "
            f"promote (run training, or serve_lm's init_if_missing "
            f"mode for a demo-grade init)")
    man = store.manifest(step) or {}
    meta = man.get("meta") or {}
    snap_model = meta.get("model")
    if snap_model and snap_model != size:
        raise ModeRefusal(
            f"snapshot {step} in {snapshot_dir} was written by model "
            f"{snap_model!r}; this worker was asked to serve --size "
            f"{size!r} — refusing to bind across architectures")
    layout_name = meta.get("update_layout", "tree")
    if layout_name not in _LAYOUTS:
        raise ValueError(f"snapshot {step} declares unknown "
                         f"update_layout {layout_name!r} "
                         f"(one of {_LAYOUTS})")
    model = build_model(size)
    if layout_name == "zero3_rows":
        snap_mesh = int(meta.get("mesh_size") or 0)
        if mesh_size is not None and int(mesh_size) != snap_mesh:
            raise ModeRefusal(
                f"snapshot {step} holds zero3_rows written at mesh_size "
                f"{snap_mesh} but --sharded_mesh {mesh_size} was "
                f"requested — the row layout is a function of D; "
                f"re-shard through a training-side conversion, or serve "
                f"at the snapshot's mesh size")
        template, z3 = _template(model, tx or _default_tx(), layout_name,
                                 meta, sample_len)
        state = store.restore(template, step=step)
        _log(f"promoted snapshot step {step} (zero3_rows, rows kept "
             f"sharded at 1/{z3.num_devices}) from {snapshot_dir}")
        return ShardedPromotion(model=model, rows=tuple(state.params),
                                layout=z3, step=int(step),
                                source_layout=layout_name, manifest=man)
    # Replicated-by-format snapshot: restore full, convert DOWN.
    template, _ = _template(model, tx or _default_tx(), layout_name,
                            meta, sample_len)
    state = store.restore(template, step=step)
    D = int(mesh_size or meta.get("mesh_size") or len(jax.devices()))
    if D > len(jax.devices()):
        raise ModeRefusal(
            f"--sharded_mesh {D} exceeds the {len(jax.devices())} "
            f"visible device(s) — the row layout shards one row per "
            f"device")
    bb = int(bucket_bytes or meta.get("bucket_bytes")
             or DEFAULT_BUCKET_BYTES)
    mesh = make_mesh(D)
    repl = jax.device_put(state.params, replicated_sharding(mesh))
    z3 = Zero3Layout(repl, bb, mesh)
    rows = z3.init_rows(repl)       # donates: the full copy dies here
    _log(f"promoted snapshot step {step} ({layout_name} → zero3 rows "
         f"at 1/{D}, bucket_bytes {bb}) from {snapshot_dir}")
    return ShardedPromotion(model=model, rows=tuple(rows), layout=z3,
                            step=int(step), source_layout=layout_name,
                            manifest=man)


def init_lm_snapshot(snapshot_dir: str, size: str, seed: int = 0,
                     sample_len: int = 8, model=None) -> int:
    """Write a demo-grade snapshot: a seeded, untrained graft-LM state
    in the standard store format (the serving path exercises the FULL
    promotion machinery against it — validity checks, layout stamp,
    fallback).  Returns the snapshot step (0).  Idempotent: an existing
    valid snapshot wins (save() dedupes by step).  ``model`` as in
    :func:`promote`."""
    model = model or build_model(size)
    state = TrainState.create(model, _default_tx(),
                              jnp.zeros((1, sample_len), jnp.int32),
                              seed=seed)
    store = SnapshotStore(snapshot_dir)
    store.save(state, cursor={"seed": seed, "step": 0},
               meta={"model": size, "update_layout": "tree",
                     "writer": "init_lm_snapshot"})
    return int(state.step)


# --- canary promotion (the self-healing rung, resilience/remediate.py) -----

def _env_float(name: str, default: float) -> float:
    try:
        return float(os.environ.get(name, ""))
    except ValueError:
        return default


def canary_fraction_default() -> float:
    """``HEAL_CANARY_FRACTION``: share of requests routed to a canary
    candidate while it proves itself (default 0.25)."""
    return _env_float("HEAL_CANARY_FRACTION", 0.25)


def canary_window_default() -> int:
    """``HEAL_CANARY_WINDOW``: canary-arm completions required before a
    promote/rollback verdict (default 16)."""
    return int(_env_float("HEAL_CANARY_WINDOW", 16))


def canary_p99_ratio_default() -> float:
    """``HEAL_CANARY_P99_RATIO``: canary p99 over this multiple of the
    baseline arm's p99 inside the window = regression → rollback
    (default 2.0)."""
    return _env_float("HEAL_CANARY_P99_RATIO", 2.0)


def params_healthy(params) -> bool:
    """Every float leaf finite — the pre-exposure canary probe: a
    NaN-poisoned snapshot (the OOV-poison shape, a torn quantizer, a
    diverged run an operator promoted by mistake) is caught BEFORE a
    single request routes to it.  Cheap relative to one prefill."""
    import jax
    for leaf in jax.tree.leaves(params):
        arr = np.asarray(leaf)
        if np.issubdtype(arr.dtype, np.floating) \
                and not np.all(np.isfinite(arr)):
            return False
    return True


class Canary:
    """Canary promotion state machine: a candidate snapshot serves a
    deterministic ``fraction`` of requests first, and the promotion
    commits only after a clean observation window — auto-rollback on a
    NaN probe or a p99 regression vs the baseline arm.

    State: ``probing`` → (``rolled_back`` | ``serving``) →
    (``promoted`` | ``rolled_back``).  This object owns the DECISION
    only; the serving harness owns the two engine arms and the drain
    (an in-flight canary request always decodes to completion —
    rollback must never drop admitted work, exactly the eviction
    protocol's rule).  Verdicts land as ``heal_canary_promote`` /
    ``heal_canary_rollback`` ledger rows via the remediation engine."""

    def __init__(self, baseline_step: int, candidate_step: int, *,
                 fraction: float | None = None,
                 window: int | None = None,
                 p99_ratio: float | None = None):
        self.baseline_step = int(baseline_step)
        self.candidate_step = int(candidate_step)
        self.fraction = canary_fraction_default() if fraction is None \
            else float(fraction)
        self.window = canary_window_default() if window is None \
            else int(window)
        self.p99_ratio = canary_p99_ratio_default() if p99_ratio is None \
            else float(p99_ratio)
        self.state = "probing"
        self.reason = ""
        self._lat: dict[str, list] = {"canary": [], "baseline": []}
        self._bad: int = 0

    def admit_candidate(self, candidate_params) -> bool:
        """The pre-exposure probe; False = immediate rollback (the
        candidate never serves)."""
        if not params_healthy(candidate_params):
            self.state = "rolled_back"
            self.reason = ("candidate params carry non-finite values — "
                           "rolled back before serving a single request")
            return False
        self.state = "serving"
        return True

    def route(self, rid: str) -> str:
        """Deterministic request routing while ``serving``: the same
        rid always lands on the same arm (a retried request must not
        flap arms mid-experiment)."""
        if self.state != "serving":
            return "baseline"
        import zlib
        bucket = zlib.crc32(str(rid).encode()) % 10_000
        return "canary" if bucket < self.fraction * 10_000 else "baseline"

    def observe(self, arm: str, latency_s: float, ok: bool = True) -> None:
        if not ok and arm == "canary":
            self._bad += 1
        self._lat.setdefault(arm, []).append(float(latency_s))

    @staticmethod
    def _p99(tape: list) -> float | None:
        if not tape:
            return None
        from distributedtensorflowexample_tpu.serving.queue import (
            percentile)
        return percentile(sorted(tape), 0.99)

    def verdict(self) -> str | None:
        """None while the window is still filling; else ``promote`` /
        ``rollback`` (state committed, latched)."""
        if self.state in ("promoted", "rolled_back"):
            return ("promote" if self.state == "promoted"
                    else "rollback")
        if self._bad:
            self.state = "rolled_back"
            self.reason = (f"{self._bad} canary request(s) failed "
                           f"(NaN/garbage outcome) inside the window")
            return "rollback"
        can = self._lat["canary"]
        if len(can) < self.window:
            return None
        p99c = self._p99(can)
        p99b = self._p99(self._lat["baseline"])
        if p99b and p99c is not None and p99c > self.p99_ratio * p99b:
            self.state = "rolled_back"
            self.reason = (f"canary p99 {p99c * 1000:.1f}ms > "
                           f"{self.p99_ratio:g}x baseline p99 "
                           f"{p99b * 1000:.1f}ms over {len(can)} "
                           f"canary completions")
            return "rollback"
        self.state = "promoted"
        self.reason = (f"clean window: {len(can)} canary completions, "
                       f"p99 {0 if p99c is None else p99c * 1000:.1f}ms"
                       + (f" vs baseline {p99b * 1000:.1f}ms" if p99b
                          else ""))
        return "promote"

    def payload(self) -> dict:
        p99c, p99b = self._p99(self._lat["canary"]), \
            self._p99(self._lat["baseline"])
        return {
            "state": self.state, "reason": self.reason,
            "baseline_step": self.baseline_step,
            "candidate_step": self.candidate_step,
            "fraction": self.fraction, "window": self.window,
            "p99_ratio": self.p99_ratio,
            "canary_n": len(self._lat["canary"]),
            "baseline_n": len(self._lat["baseline"]),
            "canary_p99_ms": (None if p99c is None
                              else round(p99c * 1000, 3)),
            "baseline_p99_ms": (None if p99b is None
                                else round(p99b * 1000, 3)),
            "canary_failures": self._bad}
