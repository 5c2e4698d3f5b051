"""Checkpoint/resume via Orbax (SURVEY.md §5: the mandated mapping from
``MonitoredTrainingSession`` checkpoint hooks / ``Saver``).

Semantics preserved from the reference: periodic saves, keep-N rotation,
auto-restore-from-latest on startup, chief-only effective writes (Orbax is
multi-host aware — every process must call save, primary writes).  Gained:
async saves (training does not stall on serialization).

``orbax.checkpoint`` is imported where a manager is built, not with this
module: it pulls tensorstore, grpc and ``google.cloud.logging`` with it
(11–13 s of a fresh interpreter on a chip machine, PERF.md §6 PR 42), and
``saveable_state_dict`` — all that resilience/ takes from here — needs
none of it.
"""

from __future__ import annotations

import json
import os
from typing import Any

import jax

from distributedtensorflowexample_tpu.training.state import TrainState


def saveable_state_dict(state: TrainState) -> dict[str, Any]:
    """The serializable subset of a TrainState — THE one definition of
    what a checkpoint contains, shared with the crash-consistent
    snapshot format (resilience/snapshot.py) so the two restore paths
    can never drift on which fields make a run resumable."""
    # tx/apply_fn are static code, not state — exclude from serialization.
    return {"step": state.step, "params": state.params,
            "opt_state": state.opt_state, "batch_stats": state.batch_stats,
            "rng": state.rng}


_saveable = saveable_state_dict


class CheckpointManager:
    """Building one is what imports ``orbax.checkpoint``.  A run that
    checkpoints (``cfg.checkpoint_every > 0 or cfg.resume``) builds its
    manager during set-up (engine/engine.py), so it pays the import
    there and never in the middle of the loop; a run that opens no
    checkpoint never pays it."""

    def __init__(self, directory: str, max_to_keep: int = 3,
                 async_save: bool = True, run_metadata: dict | None = None):
        """``run_metadata``: small JSON-able facts about the writing run
        (e.g. ``sync_mode``) persisted next to the checkpoints so a later
        run can refuse a structurally-incompatible restore with a clear
        error instead of a shape mismatch deep inside Orbax."""
        import orbax.checkpoint as ocp
        self._ocp = ocp
        self._dir = os.path.abspath(directory)
        os.makedirs(self._dir, exist_ok=True)
        self._mgr = ocp.CheckpointManager(
            self._dir,
            options=ocp.CheckpointManagerOptions(
                max_to_keep=max_to_keep,
                enable_async_checkpointing=async_save))
        self._run_metadata = run_metadata

    def save(self, step: int, state: TrainState, force: bool = False) -> bool:
        step = int(step)
        if step in self._mgr.all_steps():
            return False  # periodic save already covered this step
        self._write_run_metadata()
        return self._mgr.save(
            step, args=self._ocp.args.StandardSave(_saveable(state)),
            force=force)

    def _write_run_metadata(self) -> None:
        """Keep the metadata describing the CURRENT writer: a reused
        directory whose new (non-resumed) run differs must overwrite, or a
        later resume of the new checkpoints would be wrongly refused."""
        if self._run_metadata is None:
            return
        path = os.path.join(self._dir, "run_metadata.json")
        if self.saved_run_metadata() == self._run_metadata:
            return
        if jax.process_index() == 0:  # chief-only, atomic via rename
            tmp = path + ".tmp"
            with open(tmp, "w") as f:
                json.dump(self._run_metadata, f)
            os.replace(tmp, path)

    def saved_run_metadata(self) -> dict | None:
        """Metadata of the run that wrote this directory (None if absent —
        e.g. a checkpoint written before metadata existed)."""
        path = os.path.join(self._dir, "run_metadata.json")
        if not os.path.exists(path):
            return None
        with open(path) as f:
            return json.load(f)

    def latest_step(self) -> int | None:
        return self._mgr.latest_step()

    def restore(self, state: TrainState, step: int | None = None) -> TrainState:
        """Restore into the structure (and shardings) of ``state``."""
        step = self._mgr.latest_step() if step is None else step
        if step is None:
            return state
        template = jax.tree.map(lambda x: x, _saveable(state))
        restored = self._mgr.restore(
            step, args=self._ocp.args.StandardRestore(template))
        return state.replace(**restored)

    def wait(self) -> None:
        self._mgr.wait_until_finished()

    def close(self) -> None:
        self._mgr.wait_until_finished()
        self._mgr.close()
