"""Crash-consistent snapshots: atomic payload + manifest-last commit.

Orbax (training/checkpoint.py) remains the trainer-surface manager; this
store is the RECOVERY format the supervisor trusts after an unclean
death, built so every failure mode of the write path is detectable:

- payload first: the full state pytree (``saveable_state_dict`` — the
  same field set Orbax saves) as one ``.npz`` blob, written to a tmp
  file, ``fsync``ed, then ``os.replace``d into place (atomic on POSIX);
- manifest last: a small JSON carrying step, payload byte size, crc32,
  leaf count, the dataset cursor (seed + step — a ``DeviceDataset``
  rebuilt with that ``start_step`` replays the identical batch order),
  and caller metadata.  A manifest only exists once its payload rename
  committed, and validation re-checks size+crc, so a write torn ANYWHERE
  (mid-payload, mid-rename, post-hoc truncation) is detected and that
  snapshot discarded in favor of the previous valid one — never
  restored.

Resume is bitwise: params, optimizer state, BN stats and the RNG key
round-trip exactly (npz preserves dtype+bits), and the manifest cursor
lines the data pipeline up with the restored global step — the same
parity discipline the dequant and remat work established, verified in
tests/test_resilience.py.
"""

from __future__ import annotations

import io
import json
import os
import re
import sys
import zlib

import jax
import numpy as np

from distributedtensorflowexample_tpu.obs import metrics as obs_metrics
from distributedtensorflowexample_tpu.obs.trace import span
from distributedtensorflowexample_tpu.training.checkpoint import (
    saveable_state_dict)
from distributedtensorflowexample_tpu.training.hooks import Hook, _EveryN
from distributedtensorflowexample_tpu.training.state import TrainState

MANIFEST_VERSION = 1
_PAYLOAD_RE = re.compile(r"^snap_(\d{8})\.npz$")

_SAVES = obs_metrics.counter(
    "snapshot_saves_total", "committed snapshot writes (payload+manifest)")
# The round-6 ROADMAP names this metric verbatim: a failed save (disk
# full) is logged + counted, never fatal — hence no _total suffix.
_SAVE_FAILURES = obs_metrics.counter(
    "snapshot_save_failures", "snapshot writes refused by the OS "
    "(disk full et al.) that the run survived")
_RESTORES = obs_metrics.counter(
    "snapshot_restores_total", "successful restores from a snapshot")
_FALLBACKS = obs_metrics.counter(
    "snapshot_fallbacks_total",
    "invalid (torn/corrupt) snapshots discarded in favor of an older one")


def _log(msg: str) -> None:
    # stderr: tools with a JSON-lines stdout protocol (bench, faultline)
    # must never see prose on fd 1.
    print(f"snapshot: {msg}", file=sys.stderr, flush=True)


class SnapshotStore:
    """Keep-N rotating store of crash-consistent state snapshots."""

    def __init__(self, directory: str, keep: int = 3):
        self._dir = os.path.abspath(directory)
        os.makedirs(self._dir, exist_ok=True)
        self._keep = keep

    # --- paths -----------------------------------------------------------
    def _payload_path(self, step: int) -> str:
        return os.path.join(self._dir, f"snap_{step:08d}.npz")

    def _manifest_path(self, step: int) -> str:
        return os.path.join(self._dir, f"snap_{step:08d}.json")

    def steps(self) -> list[int]:
        """Steps with a committed payload file, ascending (a payload may
        still fail validation — see :meth:`latest_valid`)."""
        out = []
        for name in os.listdir(self._dir):
            m = _PAYLOAD_RE.match(name)
            if m:
                out.append(int(m.group(1)))
        return sorted(out)

    # --- write -----------------------------------------------------------
    def _atomic_write(self, path: str, data: bytes) -> None:
        # Kept as a method (the fault tests' monkeypatch seam for
        # disk-full injection); the mechanism is the shared obs one.
        from distributedtensorflowexample_tpu.obs.recorder import (
            atomic_write)
        atomic_write(path, data)

    def save(self, state: TrainState, cursor: dict | None = None,
             meta: dict | None = None, force: bool = False) -> bool:
        """Write one snapshot; returns False if ``step`` already has a
        committed manifest (periodic + final hooks overlap, like the
        Orbax manager's duplicate-step no-op) unless ``force``."""
        step = int(state.step)
        if not force and os.path.exists(self._manifest_path(step)):
            if self.validate(step)[0]:
                return False
            # An INVALID snapshot at this step (torn payload behind an
            # intact manifest) must not dedupe away its own repair: the
            # redo of the lost step is exactly what heals it.
            _log(f"re-writing invalid snapshot {step}")
        saveable = saveable_state_dict(state)
        leaves = [np.asarray(x) for x in jax.tree.leaves(saveable)]
        buf = io.BytesIO()
        # Zero-padded index keys: np.load returns files in archive order,
        # but the restore sorts by key so the leaf order is structural,
        # not an artifact of zip internals.
        np.savez(buf, **{f"leaf_{i:05d}": a for i, a in enumerate(leaves)})
        payload = buf.getvalue()
        self._atomic_write(self._payload_path(step), payload)
        manifest = {
            "version": MANIFEST_VERSION,
            "step": step,
            "nbytes": len(payload),
            "crc32": zlib.crc32(payload),
            "leaves": len(leaves),
            "cursor": cursor,
            "meta": meta,
        }
        self._atomic_write(self._manifest_path(step),
                           json.dumps(manifest).encode())
        _SAVES.inc()
        self._prune()
        return True

    def _prune(self) -> None:
        for step in self.steps()[:-self._keep] if self._keep else []:
            for p in (self._payload_path(step), self._manifest_path(step)):
                try:
                    os.remove(p)
                except OSError:
                    pass

    # --- validate / read -------------------------------------------------
    def manifest(self, step: int) -> dict | None:
        try:
            with open(self._manifest_path(step)) as f:
                return json.load(f)
        except (OSError, json.JSONDecodeError):
            return None

    def _checked_payload(self, step: int) -> tuple[bytes | None, str]:
        """One read serving both validation and restore: the payload
        bytes iff the manifest parses AND size+crc32 match what it
        committed, else (None, why)."""
        man = self.manifest(step)
        if man is None:
            return None, "manifest missing or unreadable"
        try:
            with open(self._payload_path(step), "rb") as f:
                payload = f.read()
        except OSError:
            return None, "payload missing"
        if len(payload) != man.get("nbytes"):
            return None, (f"payload torn: {len(payload)} bytes on disk, "
                          f"manifest committed {man.get('nbytes')}")
        if zlib.crc32(payload) != man.get("crc32"):
            return None, "payload corrupt: crc32 mismatch"
        return payload, "ok"

    def validate(self, step: int) -> tuple[bool, str]:
        payload, why = self._checked_payload(step)
        return payload is not None, why

    def latest_valid(self) -> int | None:
        """Newest step that passes validation; every newer invalid one is
        logged as discarded (the supervisor's fallback contract: a torn
        final write costs one snapshot interval, never the run)."""
        for step in reversed(self.steps()):
            ok, why = self.validate(step)
            if ok:
                return step
            _FALLBACKS.inc()
            _log(f"discarding snapshot {step} ({why}); "
                 f"falling back to the previous one")
        return None

    def restore(self, state: TrainState, step: int | None = None) -> TrainState:
        """Restore into the structure (and shardings) of ``state``;
        identity when the store is empty (CheckpointManager parity)."""
        step = self.latest_valid() if step is None else step
        if step is None:
            return state
        # Single read: _checked_payload validates from the same bytes it
        # returns, so restoring a large state costs one payload pass
        # here, not separate validate + load reads.
        payload, why = self._checked_payload(step)
        if payload is None:
            raise ValueError(f"snapshot {step} failed validation: {why}")
        with np.load(io.BytesIO(payload)) as z:
            loaded = [z[k] for k in sorted(z.files)]
        template = saveable_state_dict(state)
        t_leaves, treedef = jax.tree.flatten(template)
        if len(loaded) != len(t_leaves):
            raise ValueError(
                f"snapshot {step} holds {len(loaded)} leaves; this run's "
                f"state has {len(t_leaves)} — the model/optimizer changed "
                f"since the snapshot was written")
        # numpy's archive keeps the bytes of a bfloat16 leaf but not its
        # type (it comes back as void): the template says what it was.
        loaded = [r.view(t.dtype) if r.dtype.kind == "V"
                  and r.dtype.itemsize == np.dtype(t.dtype).itemsize else r
                  for t, r in zip(t_leaves, loaded)]
        restored_leaves = [
            jax.device_put(r, t.sharding) if isinstance(t, jax.Array) else r
            for t, r in zip(t_leaves, loaded)]
        restored = jax.tree.unflatten(treedef, restored_leaves)
        _RESTORES.inc()
        return state.replace(**restored)

    def discard_newer(self, step: int) -> list[int]:
        """Delete every snapshot (payload + manifest) newer than
        ``step`` — the fleet agreement pass's divergence discard.  A
        rank that ran AHEAD of the agreed resume step holds snapshots
        from a timeline the gang is abandoning; leaving them on disk
        would poison the NEXT recovery (save() dedupes against an
        existing valid manifest, so the stale future step would never
        be overwritten by the replayed one, and a later restore would
        silently jump onto the abandoned timeline).  Returns the
        discarded steps, ascending."""
        dropped = []
        for s in self.steps():
            if s <= step:
                continue
            failed = None
            for p in (self._payload_path(s), self._manifest_path(s)):
                try:
                    os.remove(p)
                except FileNotFoundError:
                    pass
                except OSError as e:
                    failed = e
            if failed is not None and self.validate(s)[0]:
                # A still-VALID snapshot the OS would not let us delete
                # must not be reported discarded: the caller journals
                # this list as the agreement's proof, and a later
                # restore-newest would silently jump onto the abandoned
                # timeline the record claims is gone.  (A half-removed
                # snapshot that now fails validation is harmless — the
                # fallback path already skips it.)
                _log(f"FAILED to discard snapshot {s} ({failed}) — it is "
                     f"still restorable as newest; fix the store "
                     f"permissions before trusting a resume from here")
                continue
            dropped.append(s)
        # Shard sets past the agreed step are the SAME divergent
        # timeline in the row-layout format — the agreement's discard
        # must cover both or a later quorum-valid shard step would
        # resurrect it (resilience/shardstore.py).
        from distributedtensorflowexample_tpu.resilience import (
            shardstore as _shardstore)
        dropped = sorted(set(dropped)
                         | set(_shardstore.discard_newer(self._dir, step)))
        if dropped:
            _log(f"discarded snapshot(s) {dropped} newer than agreed "
                 f"step {step} (divergent timeline)")
        return dropped

    # --- fault-injection surface -----------------------------------------
    def tear_latest(self) -> int | None:
        """Truncate the newest payload mid-file (fault injection: a
        checkpoint write that died between payload bytes and the torn
        half surviving a rename — or post-hoc media loss).  Returns the
        torn step, or None if the store is empty."""
        steps = self.steps()
        if not steps:
            return None
        path = self._payload_path(steps[-1])
        size = os.path.getsize(path)
        with open(path, "r+b") as f:
            f.truncate(size // 2)
        return steps[-1]


def valid_steps(directory: str) -> list[int]:
    """Steps in ``directory`` that pass validation, ascending — one
    rank's input to the fleet's resume-step agreement and the
    Remediator rollback actuator's notion of "good".  Both snapshot
    formats count: monolithic payloads here (size + crc32) UNIONed
    with the shard store's quorum-valid sets (every 1/D shard + the
    replicated payload digest-intact, resilience/shardstore.py) — so
    "the newest step the gang can provably agree on" already means
    shard quorum for row-layout runs.  Reads manifests and payload
    bytes only, never deserializes state."""
    from distributedtensorflowexample_tpu.resilience import (
        shardstore as _shardstore)
    store = SnapshotStore(directory)
    steps = {s for s in store.steps() if store.validate(s)[0]}
    steps.update(_shardstore.quorum_valid_steps(directory))
    return sorted(steps)


def newest_common_step(manifest_dirs: list[str]) -> int | None:
    """The maximum step EVERY directory holds a valid snapshot for —
    the gang's agreed resume point (resilience/fleet.py).

    Each rank snapshots independently, so after an unclean gang death
    the newest steps diverge: the killed rank stopped at k, a survivor
    ran on to k+m before teardown, and a torn final write fails
    validation entirely.  Restoring per-rank newest would silently
    resume DIFFERENT global steps on different ranks (the divergence
    this helper exists to make visible); the newest COMMON valid step
    is the latest state the whole fleet can provably agree on, and
    resuming there is bitwise-identical to an uninterrupted run.

    Returns None when no common valid step exists (some rank has
    nothing valid) — the gang must start fresh."""
    common: set[int] | None = None
    for d in manifest_dirs:
        steps = set(valid_steps(d))
        common = steps if common is None else common & steps
        if not common:
            return None
    return max(common) if common else None


class SnapshotHook(Hook):
    """Periodic + final crash-consistent snapshot (CheckpointHook's shape,
    SnapshotStore's format).  ``cursor`` is the static part of the dataset
    cursor (e.g. ``{"seed": cfg.seed}``); the step is stamped at save
    time so the manifest always names the batch-stream position a resume
    must rebuild (``DeviceDataset(..., start_step=cursor["step"])``)."""

    def __init__(self, store: SnapshotStore, every: int = 1,
                 cursor: dict | None = None):
        self._store = store
        self._due = _EveryN(every)
        self._cursor = dict(cursor or {})
        self._last_saved: int | None = None

    def _stamped(self, state) -> dict:
        return {**self._cursor, "step": int(state.step)}

    def begin(self, loop) -> None:
        self._due = _EveryN(self._due._every, int(loop.start_step))
        self._last_saved = None

    def _save(self, state, force: bool = False) -> bool:
        """One guarded write.  An OSError (disk full, the round-6
        ROADMAP fault) is logged and counted, never raised: losing ONE
        snapshot interval is recoverable by design (that's what keep-N
        and the manifest fallback exist for), while killing the run
        here would convert a full /tmp into a lost training job.  The
        next interval retries against whatever space exists then."""
        step = int(state.step)
        try:
            with span("snapshot", step=step):
                self._store.save(state, cursor=self._stamped(state),
                                 force=force)
            return True
        except OSError as e:
            _SAVE_FAILURES.inc()
            _log(f"save at step {step} failed ({e}) — continuing; the "
                 f"newest valid snapshot on disk is unchanged and the "
                 f"next interval retries")
            return False

    def after_step(self, step, state, metrics) -> bool:
        if self._due(step) and self._save(state):
            self._last_saved = int(state.step)
        return False

    def end(self, state) -> None:
        # force is for an OFF-GRID final step; when the last periodic
        # save already covered this exact step, a forced rewrite would
        # re-serialize and double-fsync the whole state for nothing.
        if int(state.step) == self._last_saved:
            return
        self._save(state, force=True)
