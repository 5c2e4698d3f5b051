"""utils/: profiling trace capture, device-honest timing, chief logging."""

import glob
import os

import jax
import jax.numpy as jnp

from distributedtensorflowexample_tpu.utils import (
    ProfilerHook, RateMeter, Timer, chief_print, timed_block, trace_context)


class _FakeTime:
    """Settable clock standing in for the metrics module's ``time``."""

    def __init__(self):
        self.now = 0.0

    def perf_counter(self):
        return self.now


def test_metrics_logger_excludes_hook_time(monkeypatch):
    """steps_per_sec is a TRAINING rate: hook wall time reported via
    exclude() must not depress the next window, and over-discounting must
    skip the rate rather than emit a bogus one (deterministic fake clock)."""
    from distributedtensorflowexample_tpu.training import metrics as m

    clock = _FakeTime()
    monkeypatch.setattr(m, "time", clock)
    logger = m.MetricsLogger(log_every=100)
    logger.start(0)

    clock.now = 10.0                       # 100 steps in 10s of training
    logger.maybe_log(100, {"loss": jnp.asarray(1.0)})
    assert logger.last_steps_per_sec == 10.0

    logger.exclude(5.0)                    # a 5s eval/checkpoint hook
    clock.now = 25.0                       # +10s training, +5s hook
    logger.maybe_log(200, {"loss": jnp.asarray(1.0)})
    assert logger.last_steps_per_sec == 10.0   # hook time discounted

    logger.exclude(100.0)                  # hook outlived the window
    clock.now = 30.0
    logger.maybe_log(300, {"loss": jnp.asarray(1.0)})
    assert logger.last_steps_per_sec == 10.0   # bogus rate skipped


def test_trace_context_writes_xplane(tmp_path):
    logdir = str(tmp_path / "trace")
    with trace_context(logdir):
        jax.block_until_ready(jnp.ones((8, 8)) @ jnp.ones((8, 8)))
    assert glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                     recursive=True), "no xplane trace written"


def test_profiler_hook_window(tmp_path):
    logdir = str(tmp_path / "hooktrace")
    hook = ProfilerHook(logdir, start_step=2, num_steps=2)
    m = jnp.zeros(())
    for step in range(1, 6):
        hook.after_step(step, None, m)
    hook.end(None)
    assert glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                     recursive=True)


def test_profiler_hook_slides_window_on_resume(tmp_path):
    """A run resuming past the configured window still captures a trace."""
    logdir = str(tmp_path / "resumed")
    hook = ProfilerHook(logdir, start_step=2, num_steps=2)
    m = jnp.zeros(())
    for step in range(50, 56):  # checkpoint resume landed at step 50
        hook.after_step(step, None, m)
    hook.end(None)
    assert glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                     recursive=True)


def test_profiler_hook_is_one_shot(tmp_path, monkeypatch):
    """After the window completes, tracing must never re-arm."""
    import distributedtensorflowexample_tpu.utils.profiling as prof
    starts = []
    monkeypatch.setattr(prof.jax.profiler, "start_trace",
                        lambda d: starts.append(d))
    monkeypatch.setattr(prof.jax.profiler, "stop_trace", lambda: None)
    hook = ProfilerHook(str(tmp_path), start_step=2, num_steps=2)
    m = jnp.zeros(())
    for step in range(1, 30):
        hook.after_step(step, None, m)
    hook.end(None)
    assert len(starts) == 1


def test_profiler_hook_stops_on_early_end(tmp_path):
    logdir = str(tmp_path / "early")
    hook = ProfilerHook(logdir, start_step=1, num_steps=100)
    hook.after_step(1, None, jnp.zeros(()))
    hook.end(None)  # loop stopped inside window; must not leak active trace
    assert glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                     recursive=True)


def test_timer_measures_and_counts():
    t = Timer()
    for _ in range(3):
        with t.measure() as out:
            out["result"] = jnp.ones((16, 16)) @ jnp.ones((16, 16))
    assert t.count == 3
    assert t.total > 0
    assert abs(t.mean - t.total / 3) < 1e-12


def test_timed_block_sink():
    sink = []
    with timed_block("x", sink=sink) as out:
        out["result"] = jnp.ones((4,)) * 2
    assert len(sink) == 1 and sink[0][0] == "x" and sink[0][1] > 0


def test_rate_meter():
    m = RateMeter(window=4)
    assert m.rate == 0.0
    for _ in range(5):
        m.tick()
    assert m.rate > 0


def test_chief_print(capsys):
    chief_print("hello-chief")
    assert "hello-chief" in capsys.readouterr().out


def test_conftest_xla_flags_accepted_by_backend():
    """An UNKNOWN name in XLA_FLAGS fatally aborts the process at first
    backend init, and pytest capture eats the `F... Unknown flag` log —
    the whole suite dies with rc=1 and ZERO output (round-3 incident:
    a plausible-but-wrong flag rename killed every device test silently).
    Pin that the conftest's exact flag string is known to this jaxlib by
    touching a collective in a subprocess."""
    import os
    import subprocess
    import sys

    code = (
        "import jax; jax.config.update('jax_platforms', 'cpu');"
        "jax.config.update('jax_num_cpu_devices', 2);"
        "import numpy as np; import jax.numpy as jnp;"
        "from jax.sharding import Mesh, PartitionSpec as P;"
        "m = Mesh(np.array(jax.devices()), ('d',));"
        "f = jax.shard_map(lambda x: jax.lax.psum(x, 'd'), mesh=m,"
        "              in_specs=P('d'), out_specs=P());"
        "print('FLAGS_OK', float(f(jnp.ones(4))[0]))"
    )
    env = dict(os.environ)
    assert "--xla_cpu_collective_call" in env.get("XLA_FLAGS", ""), \
        "conftest did not install the rendezvous flags"
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=240)
    assert r.returncode == 0, (r.stdout, r.stderr)
    assert "FLAGS_OK" in r.stdout
    assert "Unknown flag" not in r.stderr
