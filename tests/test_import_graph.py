"""What a run imports before it does any work (PERF.md §6, PR 42).

Every run of the benchmark, every trainer and every serving worker is a
new process, and on a chip machine its imports are tens of seconds of
set-up.  ``orbax.checkpoint`` alone (tensorstore, grpc,
``google.cloud.logging``, aiohttp, cryptography behind it) was 11–13 s
of each, for runs that never open a checkpoint.  The contract these tests
hold: a module is imported where it is first used.

Each case is one fresh interpreter — ``sys.modules`` of the test process
has long held everything — with a time limit of its own.
"""

import importlib.util
import os
import subprocess
import sys
import types

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = "distributedtensorflowexample_tpu"
LIMIT_S = 180

# The checkpoint stack: what ``import orbax.checkpoint`` drags in and no
# run needs until it opens a checkpoint.  Prefixes of dotted names.  (The
# bare namespace ``google.cloud`` is there after ``import jax,
# flax.linen`` alone and is nobody's business.)
CHECKPOINT_STACK = ("orbax", "tensorstore", "google.cloud.logging",
                    "google.api_core", "grpc")

# benchmarks/kinds/serve.py: run() — also what a serving worker that is
# handed its weights imports.
SERVE_IMPORTS = f"""
from {PKG}.serving.engine import DecodeEngine
from {PKG}.serving.queue import ContinuousBatcher, RequestQueue
"""
# benchmarks/kinds/train.py: run()
TRAIN_IMPORTS = f"""
from {PKG}.config import RunConfig
from {PKG}.engine import Engine, RunSpec
from {PKG}.obs import metrics as obs_metrics
from {PKG}.parallel import replicated_sharding
from {PKG}.training.hooks import AnomalyHook, MetricsHook
from {PKG}.training.loop import TrainLoop
from {PKG}.training.metrics import MetricsLogger
"""

ENTRY_PATHS = {
    "serve": (SERVE_IMPORTS, CHECKPOINT_STACK + (
        f"{PKG}.resilience", f"{PKG}.training.checkpoint",
        f"{PKG}.serving.promote")),
    "train": (TRAIN_IMPORTS, CHECKPOINT_STACK),
}

# Names the child defines before the case's own lines.
PRELUDE = """
import sys

def loaded(*prefixes):
    return sorted(m for m in sys.modules if m.startswith(prefixes))
"""

# A small TrainState to save and restore, built in the child.
FRESH_STATE = f"""
import jax, jax.numpy as jnp, numpy as np, optax
from {PKG}.models import build_model
from {PKG}.training.state import TrainState

def fresh(seed):
    return TrainState.create(build_model("softmax"),
                             optax.sgd(0.1, momentum=0.9),
                             jnp.zeros((8, 28, 28, 1), jnp.float32),
                             seed=seed)

def same_params(a, b):
    return all(np.array_equal(np.asarray(x), np.asarray(y)) for x, y in zip(
        jax.tree.leaves(a.params), jax.tree.leaves(b.params), strict=True))
"""


def fresh_python(body: str) -> str:
    """Run ``body`` in a new interpreter from the checkout's root, on
    the CPU; its stdout.  A non-zero exit fails the test with the
    child's output."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c", PRELUDE + body], cwd=REPO,
                       env=env, capture_output=True, text=True,
                       timeout=LIMIT_S)
    assert r.returncode == 0, f"{r.stdout}\n{r.stderr}"
    return r.stdout


@pytest.mark.parametrize("path", sorted(ENTRY_PATHS))
def test_entry_path_loads_nothing_of_the_checkpoint_stack(path):
    imports, forbidden = ENTRY_PATHS[path]
    out = fresh_python(imports + f"""
found = loaded(*{forbidden!r})
assert not found, "loaded at import time: %s" % found[:12]
assert "jax" in sys.modules     # the check saw the real imports
print("clean")
""")
    assert "clean" in out


@pytest.mark.parametrize("name", ["PromotedModel", "promote",
                                  "init_lm_snapshot"])
def test_serving_promotion_name_resolves_on_first_access(name):
    """``serving.__all__`` keeps every name; the three that come from
    ``serving.promote`` load it when first asked for, not before."""
    fresh_python(f"""
import {PKG}.serving as serving
assert {name!r} in serving.__all__
assert not loaded("{PKG}.serving.promote", "{PKG}.resilience")
got = serving.{name}
assert "{PKG}.serving.promote" in sys.modules
import importlib
module = importlib.import_module("{PKG}.serving.promote")
assert got is getattr(module, {name!r})
# the package's ``promote`` is the function, as it always was, even
# though importing the submodule binds the module to that name first
assert callable(serving.promote) and serving.promote is module.promote
assert serving.PromotedModel is module.PromotedModel
assert not loaded("orbax")      # promotion reads snapshots, not Orbax
""")


@pytest.mark.parametrize("module, config", [
    ("kimi_k2", "kimi_k2_5_ep32"), ("bailing_hybrid", "ling3_flash_ep8")])
def test_an_architectures_module_loads_when_a_configuration_asks(module,
                                                                  config):
    """``models`` and the serving path load no architecture's module (its
    imports, counters and kernels' wrappers would be set-up seconds of
    every cell that does not run it); ``build_model_from_config`` loads
    the one a configuration names, and no other; the shell they share
    (``models/served_lm.py``) comes with the first of them, not with the
    engine."""
    from distributedtensorflowexample_tpu.config import CONFIG_MODEL_TYPES
    others = sorted(set(CONFIG_MODEL_TYPES) - {module})
    fresh_python(SERVE_IMPORTS + f"""
import {PKG}.models as models
blocks = tuple("{PKG}.models." + m for m in {[module] + others!r})
shell = "{PKG}.models.served_lm"
assert not loaded(shell, *blocks), loaded(shell, *blocks)
model = models.build_model_from_config("benchmarks/configs/{config}.json")
assert blocks[0] in sys.modules and shell in sys.modules
assert not loaded(*blocks[1:]), loaded(*blocks[1:])
assert model.serving_module() is model
""")


def test_as_prompt_has_one_definition_and_two_names():
    fresh_python(f"""
from {PKG}.serving import queue
assert not loaded("{PKG}.serving.promote")
from {PKG}.serving.promote import as_prompt
assert as_prompt is queue.as_prompt
assert as_prompt([1, 2, 3], 8).dtype.name == "int32"
""")


def test_unknown_serving_name_is_an_attribute_error():
    fresh_python(f"""
import {PKG}.serving as serving
try:
    serving.no_such_name
except AttributeError as e:
    assert "no_such_name" in str(e)
else:
    raise SystemExit("no AttributeError")
assert not loaded("{PKG}.serving.promote")
""")


def test_checkpoint_manager_is_what_loads_orbax(tmp_path):
    """Importing training/checkpoint.py costs nothing; building a
    manager imports Orbax, and the manager saves and restores as before
    (tests/test_checkpoint.py and tests/test_engine.py hold the rest:
    rotation, bitwise resume, the Engine's SIGTERM -> resume drill)."""
    fresh_python(FRESH_STATE + f"""
from {PKG}.training.checkpoint import CheckpointManager, saveable_state_dict
assert not loaded(*{CHECKPOINT_STACK!r})
state = fresh(0)
assert sorted(saveable_state_dict(state)) == [
    "batch_stats", "opt_state", "params", "rng", "step"]
assert not loaded("orbax")
mgr = CheckpointManager({str(tmp_path / "ckpt")!r}, async_save=False)
assert "orbax.checkpoint" in sys.modules
assert mgr.save(0, state)
mgr.wait()
restored = mgr.restore(fresh(99))
assert same_params(restored, state)
mgr.close()
""")


def test_snapshot_store_round_trips_without_orbax(tmp_path):
    """resilience/ takes ``saveable_state_dict`` from
    training/checkpoint.py and nothing of Orbax: the control plane (the
    supervisors import jax but never a backend) no longer loads it."""
    fresh_python(FRESH_STATE + f"""
from {PKG} import resilience
state = fresh(0)
store = resilience.SnapshotStore({str(tmp_path / "snap")!r})
store.save(state)
restored = store.restore(fresh(99))
assert same_params(restored, state)
assert not loaded(*{CHECKPOINT_STACK!r})
""")


def test_import_s_reads_the_two_import_stages_of_a_run():
    """The benchmark's per-layer metric of this layer
    (benchmarks/metrics/import_s.py): the two import stages' seconds,
    nothing where a run has neither."""
    path = os.path.join(REPO, "benchmarks", "metrics", "import_s.py")
    spec = importlib.util.spec_from_file_location("import_s", path)
    reader = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reader)
    run = types.SimpleNamespace(stages=[
        ("import_and_devices", 20.5), ("program_import", 6.25),
        ("weights", 3.0)])
    assert reader.read(run) == 26.75
    assert reader.read(types.SimpleNamespace(stages=[("weights", 3.0)])) is None
