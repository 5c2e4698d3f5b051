"""Cluster-flag resolution — ClusterSpec/TF_CONFIG compatibility onto SPMD.

The reference bootstrapped ``tf.train.ClusterSpec`` + ``tf.train.Server`` per
process and parked PS roles in ``server.join()`` (SURVEY.md §3b, component
C7).  Under the SPMD rebuild there are no parameter-server processes at all
(BASELINE.json north star: "no gRPC PS processes ... in the loop"), so this
module maps the old topology flags onto the one concept that remains — how
many JAX processes exist and which one is this:

* ``--worker_hosts``/``--task_index`` or a ``TF_CONFIG`` env var resolve to
  (num_processes, process_id, coordinator_address) for
  ``jax.distributed.initialize``.
* ``--job_name=ps`` is accepted and exits immediately with a notice: PS
  capability is subsumed by replicated NamedSharding (documented semantic
  change, SURVEY.md §7 step 6).
* chief == process 0 (the reference's is_chief == task_index 0).
"""

from __future__ import annotations

import dataclasses
import json
import os

import jax

from distributedtensorflowexample_tpu.config import RunConfig


@dataclasses.dataclass
class ClusterInfo:
    num_processes: int = 1
    process_id: int = 0
    coordinator_address: str = ""
    is_chief: bool = True
    role: str = "worker"            # "worker" | "ps" (ps = exit-with-notice)

    @property
    def is_distributed(self) -> bool:
        return self.num_processes > 1


def tf_config_env(workers: list[str], index: int,
                  task_type: str = "worker") -> str:
    """Serialize the reference-style ``TF_CONFIG`` for worker ``index``
    — the inverse of :func:`_from_tf_config`, kept in this module so
    the writer and the parser can't drift.  The fleet supervisor
    (resilience/fleet.py) exports exactly this to every rank it
    launches, so a child trainer resolves the same ``ClusterInfo`` a
    hand-launched worker with the documented env surface would."""
    return json.dumps({"cluster": {"worker": list(workers)},
                       "task": {"type": task_type, "index": index}})


def _from_tf_config() -> ClusterInfo | None:
    raw = os.environ.get("TF_CONFIG", "")
    if not raw:
        return None
    try:
        tf_config = json.loads(raw)
        clus = tf_config["cluster"]
        task = tf_config.get("task", {})
        task_type = str(task.get("type", "worker"))
        idx = int(task.get("index", 0))
    except (json.JSONDecodeError, KeyError, TypeError, ValueError):
        return None
    if task_type == "ps":
        return ClusterInfo(role="ps", is_chief=False)
    # TF task ordering: an optional single-entry "chief" job precedes the
    # "worker" job; both participate in training.  (An "evaluator" never
    # joins the training cluster — treat like ps: nothing to serve here.)
    if task_type == "evaluator":
        return ClusterInfo(role="ps", is_chief=False)
    chief = list(clus.get("chief", []))
    workers = chief + list(clus.get("worker", []))
    if not workers:
        return None
    pid = idx if task_type == "chief" else len(chief) + idx
    return ClusterInfo(num_processes=len(workers), process_id=pid,
                       coordinator_address=workers[0], is_chief=(pid == 0))


def resolve(cfg: RunConfig) -> ClusterInfo:
    """Resolve cluster flags + env into a ClusterInfo (no side effects)."""
    if cfg.job_name == "ps":
        return ClusterInfo(role="ps", is_chief=False)
    info = _from_tf_config()
    if info is not None:
        return info
    if cfg.coordinator_address:
        pid = cfg.process_id if cfg.process_id >= 0 else cfg.task_index
        return ClusterInfo(num_processes=cfg.num_processes, process_id=pid,
                           coordinator_address=cfg.coordinator_address,
                           is_chief=(pid == 0))
    workers = cfg.worker_host_list
    if len(workers) > 1 and cfg.job_name == "worker":
        pid = cfg.process_id if cfg.process_id >= 0 else cfg.task_index
        return ClusterInfo(num_processes=len(workers), process_id=pid,
                           coordinator_address=workers[0],
                           is_chief=(pid == 0))
    return ClusterInfo()


def maybe_initialize_distributed(info: ClusterInfo) -> None:
    """``jax.distributed.initialize`` — the tf.train.Server replacement.

    Idempotent: a second trainer run in the same process (tests, notebooks,
    back-to-back ``main()`` calls) must reuse the live runtime — a repeat
    ``initialize`` raises once the XLA backend exists."""
    if info.is_distributed and not jax.distributed.is_initialized():
        jax.distributed.initialize(
            coordinator_address=info.coordinator_address,
            num_processes=info.num_processes,
            process_id=info.process_id)


PS_NOTICE = (
    "[distributedtensorflowexample_tpu] --job_name=ps: parameter-server "
    "processes are obsolete in the TPU-native SPMD runtime — variables live "
    "replicated/sharded on the device mesh and gradient aggregation is an "
    "XLA collective. This process has nothing to serve and will exit. "
    "Launch only worker roles.")
