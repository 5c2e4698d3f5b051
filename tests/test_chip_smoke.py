"""What keeps a CPU or interpret-mode run from passing for a chip run:
chip_smoke.py refuses to run without a TPU, the compile cache follows one
rule, the Pallas wrappers interpret on the ``cpu`` platform only, and
the trainers say what device they got.
"""

import os
import shutil
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.experimental import pallas as pl

from distributedtensorflowexample_tpu import runtime
from distributedtensorflowexample_tpu.ops.attention import causal_attention
from distributedtensorflowexample_tpu.ops.pallas import (
    fused_gather_dequant, fused_sgd_apply, fused_softmax_cross_entropy_rows)
from distributedtensorflowexample_tpu.ops.pallas.tiling import (
    resolve_interpret)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run(argv, cwd, **env):
    return subprocess.run(argv, cwd=cwd, env=dict(os.environ, **env),
                          capture_output=True, text=True, timeout=120)


def test_chip_smoke_refuses_the_cpu_pin():
    """Under the sandbox's exported CPU pin the smoke forces the TPU
    platform, finds none, and exits non-zero before any leg — naming
    what it found and printing no result line."""
    r = _run([sys.executable, SMOKE], REPO, JAX_PLATFORMS="cpu")
    assert r.returncode not in (0, 2, 3), (r.stdout, r.stderr)
    assert "no TPU" in r.stderr and "JAX_PLATFORMS='cpu'" in r.stderr
    assert '"ok"' not in r.stdout and "leg " not in r.stdout


def test_chip_smoke_alone_fails(tmp_path):
    """A directory holding chip_smoke.py and nothing else of the repo:
    non-zero, no result line (it drives the program, it is not one)."""
    shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
    r = _run([sys.executable, "chip_smoke.py"], str(tmp_path))
    assert r.returncode not in (0, 2, 3), (r.stdout, r.stderr)
    assert "distributedtensorflowexample_tpu" in r.stderr
    assert r.stdout == ""


@pytest.fixture()
def config_updates(monkeypatch):
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: calls.append((name, value)))
    return calls


def test_compile_cache_placed_from_outside(monkeypatch, config_updates):
    """JAX_COMPILATION_CACHE_DIR set: jax reads it itself and the code
    sets NO cache directory."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/placed/by/the/host")
    assert runtime.enable_compilation_cache() == "/placed/by/the/host"
    assert "jax_compilation_cache_dir" not in dict(config_updates)


def test_compile_cache_defaults_into_the_checkout(monkeypatch,
                                                  config_updates):
    """Unset: a fixed directory inside the checkout, derived from the
    package's own path."""
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(REPO, ".jax_cache")
    assert runtime.enable_compilation_cache() == want
    assert dict(config_updates)["jax_compilation_cache_dir"] == want


@pytest.mark.parametrize("backend,interpreted", [
    ("cpu", True), ("tpu", False), ("gpu", False)])
def test_interpret_mode_only_on_the_cpu_platform(monkeypatch, backend,
                                                 interpreted):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert resolve_interpret(None) is interpreted
    assert resolve_interpret(True) is True
    assert resolve_interpret(False) is False


class _Launched(Exception):
    pass


@pytest.mark.parametrize("launch", [
    lambda: fused_softmax_cross_entropy_rows(
        jnp.zeros((8, 10)), jnp.zeros((8,), jnp.int32)),
    lambda: fused_sgd_apply({"w": jnp.ones((3, 5))}, {"w": jnp.zeros((3, 5))},
                            {"w": jnp.ones((3, 5))}, 0.1),
    lambda: fused_gather_dequant(
        jnp.zeros((4, 2, 2, 1), jnp.uint8), jnp.zeros((2,), jnp.int32),
        jnp.ones((1,)), jnp.zeros((1,))),
    # through the model's own entry: on a TPU backend these shapes take
    # the kernels, and they are handed to pallas_call compiled
    lambda: causal_attention(*(jnp.zeros((2, 512, 2, 64), jnp.bfloat16),) * 3),
], ids=["cross_entropy", "sgd", "dequant", "causal_attention"])
def test_every_wrapper_launches_compiled_off_cpu(monkeypatch, launch):
    """On any platform but ``cpu`` each public wrapper hands pallas_call
    ``interpret=False`` — the kernel is compiled or the call fails, it is
    never quietly interpreted."""
    def fake_pallas_call(*args, interpret, **kwargs):
        raise _Launched(interpret)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(pl, "pallas_call", fake_pallas_call)
    with pytest.raises(_Launched) as launched:
        launch()
    assert launched.value.args == (False,)


def test_trainer_names_its_device(tmp_path, capsys):
    """The chief prints ``devices: N x <kind> (<platform>)`` once and the
    summary carries platform and device_kind."""
    from distributedtensorflowexample_tpu.trainers import trainer_tiny_mlp
    summary = trainer_tiny_mlp.main(
        ["--train_steps", "8", "--batch_size", "8", "--num_devices", "1",
         "--log_dir", str(tmp_path), "--resume", "false"])
    assert summary["platform"] == "cpu"
    assert summary["device_kind"] == jax.devices()[0].device_kind
    assert "devices: 1 x cpu (cpu)" in capsys.readouterr().out
