"""Process-level JAX settings every entry point shares: where the
persistent compile cache lives, the XLA:CPU collective deadlines the
virtual-device mesh needs, and the one-line description of the devices
a run actually got.

Written for the one installation there is (jax 0.9): call sites use
``jax.shard_map``, the ``jax_num_cpu_devices`` config and
``jax.distributed.is_initialized`` directly.
"""

from __future__ import annotations

import os

import jax

#: The cache's home when ``JAX_COMPILATION_CACHE_DIR`` is unset: a fixed
#: directory inside the checkout, derived from this file's own path.  The
#: path is part of what makes a cache reusable, so it is never a temp
#: dir, a pid or a timestamp.
REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_cache")


def enable_compilation_cache() -> str:
    """Turn on jax's persistent compilation cache and return its
    directory.  If ``JAX_COMPILATION_CACHE_DIR`` is set, jax reads it
    itself and this sets NO directory (whoever placed the cache owns its
    path); otherwise the cache is ``<checkout>/.jax_cache``.  Called by
    every entry point that compiles — Engine, tools/serve_lm.py,
    chip_smoke.py, tests/conftest.py."""
    # Programs compiling faster than this are cheaper to rebuild than to
    # look up; jax's own default (1 s) skips most of the test suite's.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR", "")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", REPO_CACHE_DIR)
    return REPO_CACHE_DIR


def cpu_collective_flags(warn_s: int = 60, terminate_s: int = 300) -> str:
    """The XLA:CPU collective-rendezvous deadline flags, for appending
    to ``XLA_FLAGS`` before first backend use.  CPU virtual-device runs
    only (tests, CPU drills): a participant thread that never arrives
    then aborts after ``terminate_s`` instead of hanging the process."""
    return (f" --xla_cpu_collective_call_warn_stuck_timeout_seconds={warn_s}"
            f" --xla_cpu_collective_call_terminate_timeout_seconds="
            f"{terminate_s}")


def device_summary(devices=None) -> dict:
    """What ran: ``platform``, ``device_kind`` and ``device_count`` of
    ``devices`` (default: every visible device), as jax reports them."""
    devices = list(jax.devices() if devices is None else devices)
    return {"platform": devices[0].platform,
            "device_kind": devices[0].device_kind,
            "device_count": len(devices)}


def device_line(summary: dict) -> str:
    """``devices: N x <device_kind> (<platform>)`` from a
    :func:`device_summary` — the line the chief prints at start, so a
    CPU run can never be mistaken for a chip run."""
    return (f"devices: {summary['device_count']} x "
            f"{summary['device_kind']} ({summary['platform']})")
