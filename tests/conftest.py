"""Test environment: the CPU platform with 8 virtual devices.

Replaces the reference's "4 processes on localhost with distinct ports"
trick (SURVEY.md §4): the real mesh/NamedSharding/psum code path runs
unchanged on fake CPU devices — no TPU needed for distribution tests.
Tests never touch the chip: the platform is pinned to ``cpu`` in-process
below, whatever ``JAX_PLATFORMS`` says.
"""

import os

import jax
import pytest

from distributedtensorflowexample_tpu.runtime import (
    cpu_collective_flags, enable_compilation_cache)

# In-process CPU collectives need every virtual device's thread in flight
# at once; a participant that never arrives would hang the run, so the
# rendezvous gets a deadline (XLA aborts the process when it passes).
# XLA_FLAGS is parsed at first BACKEND INIT, not at import, so appending
# after `import jax` is in time.  An UNKNOWN name in XLA_FLAGS is a fatal
# abort whose message pytest's capture eats (rc=1, no output):
# tests/test_utils.py pins that the backend accepts exactly these flags.
if "--xla_cpu_collective_call" not in os.environ.get("XLA_FLAGS", ""):
    # idempotent: xdist workers inherit the controller's value
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + cpu_collective_flags(warn_s=60, terminate_s=300))

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)
# Persistent compilation cache: the suite is compile-dominated (dozens of
# jit programs), and the cache is keyed by HLO+flags+topology, so the
# 8-virtual-device programs hit across xdist workers and across
# consecutive suite runs.  The one rule
# (runtime.enable_compilation_cache): JAX_COMPILATION_CACHE_DIR if set,
# else <checkout>/.jax_cache.
enable_compilation_cache()
# Synchronous CPU dispatch: a deep async queue of collective programs
# multiplies the concurrent-thread demand and with it the starvation
# window.  Purely a test-environment knob — the TPU runtime throttles its
# own queue.
jax.config.update("jax_cpu_enable_async_dispatch", False)


@pytest.fixture()
def tmp_log_dir(tmp_path):
    return str(tmp_path / "logs")


@pytest.fixture()
def small_synthetic(monkeypatch):
    """Shrink the synthetic fallback splits: the device-resident path
    replicates the whole split per virtual device, and full-size programs
    can stretch XLA:CPU's 8-thread collective rendezvous past its
    deadline under load (flaky aborts).  Semantics under test don't
    depend on split size."""
    from distributedtensorflowexample_tpu.data import cifar10, mnist
    monkeypatch.setattr(mnist, "_SYNTH_SIZES", {"train": 2048, "test": 512})
    monkeypatch.setattr(cifar10, "_SYNTH_SIZES",
                        {"train": 2048, "test": 512})


@pytest.fixture()
def serve_backlog():
    """``serve(engine, plan, run_ahead, eos_id=None, arrivals=None)``:
    every (prompt, max_new) of ``plan`` queued behind a fresh greedy
    ContinuousBatcher — at once, or request i before boundary
    ``arrivals[i]`` — stepped until all are answered, then drained.
    ``run_ahead=False`` holds the batcher to a read-back at every step
    (the synchronous order, the oracle of the late read-back's tests).
    Returns the requests, one row a boundary and what the registry's
    counters moved by."""
    import types

    from distributedtensorflowexample_tpu.obs import metrics as obs_metrics
    from distributedtensorflowexample_tpu.serving.queue import (
        ContinuousBatcher, RequestQueue)

    def counters() -> dict:
        return dict(obs_metrics.registry().snapshot()["counters"])

    def serve(engine, plan, run_ahead: bool, eos_id=None, arrivals=None):
        queue = RequestQueue(engine.vocab)
        batcher = ContinuousBatcher(engine, queue, slo_ms=0.0, eos_id=eos_id)
        assert batcher._may_run_ahead
        batcher._may_run_ahead = run_ahead
        before = counters()
        due = [0] * len(plan) if arrivals is None else list(arrivals)
        reqs: list = []
        rows: list = []
        while len(reqs) < len(plan) or not all(
                r.done.is_set() for r in reqs):
            while len(reqs) < len(plan) and due[len(reqs)] <= len(rows):
                prompt, max_new = plan[len(reqs)]
                reqs.append(queue.submit(prompt, max_new,
                                         rid=f"b{len(reqs)}"))
            had = [len(r.tokens) for r in reqs]
            n = batcher.step()
            rows.append(types.SimpleNamespace(
                n=n, positions=engine.positions.copy(),
                in_flight=batcher._flying is not None,
                had=had, got=[len(r.tokens) - h for r, h in zip(reqs, had)],
                done=[r.done.is_set() for r in reqs],
                owners=[(s.req, s.issued) for s in batcher._slots]))
            assert len(rows) < 10_000
        batcher.drain()
        after = counters()
        moved = {k: after[k] - before.get(k, 0) for k in after
                 if after[k] != before.get(k, 0)}
        return types.SimpleNamespace(reqs=reqs, rows=rows, moved=moved,
                                     batcher=batcher)

    return serve
