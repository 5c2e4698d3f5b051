"""The LM's attention kernels compiled for a TPU v5e that is described,
not attached: what the interpreter cannot show — Mosaic's verdict on the
tiling and the VMEM budget at the train cells' real shapes, and that the
GSPMD step over four chips runs them per shard.  Nothing runs; no time
or result comes from here.

The topology is described inside a fixture, never at import: only one
process may load the TPU's library, and every xdist worker imports this
file.  All such tests live in this one file for the same reason."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from distributedtensorflowexample_tpu.ops.pallas import (
    attention as blocked)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                      # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture()
def uncached():
    # A program compiled for a described device is written to the
    # persistent cache but can never be read back.
    from jax.experimental.compilation_cache import compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("shape", [
    (16, 1024, 12, 64),         # gpt2_124m.train_seq1024
    (24, 1024, 20, 64),         # gpt2_774m.train_seq1024
    (4, 2048, 8, 128),          # one head a lane group, the longest T
])
def test_attention_kernels_compile_for_v5e(topo, uncached, shape):
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16,
                             sharding=SingleDeviceSharding(topo.devices[0]))

    def fwd_bwd(q, k, v, w):
        out, vjp = jax.vjp(lambda q, k, v: blocked.blocked_causal_attention(
            q, k, v, interpret=False), q, k, v)
        return out, vjp(w)

    text = jax.jit(fwd_bwd).lower(x, x, x, x).compile().as_text()
    assert text.count("tpu_custom_call") == 2


@pytest.mark.parametrize("remat,calls_per_layer", [("none", 2), ("block", 3)])
def test_sync_step_over_four_chips_runs_the_kernels_per_shard(
        topo, uncached, monkeypatch, remat, calls_per_layer):
    """``parallel/sync.py``'s plain step, 124M widths, T = 1024, over
    ``data=4``: the kernels are in the program (twice forward under
    remat), nothing is all-gathered to feed them, the only collectives
    are the gradients' all-reduces, and no ``[B, 12, 1024, 1024]`` array
    is left."""
    from distributedtensorflowexample_tpu.models.transformer_lm import (
        TransformerLM)
    from distributedtensorflowexample_tpu.parallel.sync import (
        make_train_step)
    from distributedtensorflowexample_tpu.training.state import TrainState
    # The process runs on the CPU backend; the program is built for the
    # described TPU, so the two things the op asks the backend are
    # answered for it here.
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    layers, batch, seq = 2, 8, 1024
    mesh = Mesh(np.array(topo.devices[:4]), ("data",))
    model = TransformerLM(vocab_size=50257, n_layers=layers, d_model=768,
                          n_heads=12, d_ff=3072, max_len=seq, remat=remat)
    tx = optax.sgd(0.01, momentum=0.9)
    state = jax.eval_shape(lambda: TrainState.create(
        model, tx, jnp.zeros((batch, seq), jnp.int32)))

    def placed(tree, spec):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=NamedSharding(mesh, spec)), tree)

    tokens = jax.ShapeDtypeStruct((batch, seq), jnp.int32)
    text = make_train_step(mesh=mesh).lower(
        placed(state, P()),
        placed({"image": tokens, "label": tokens}, P("data"))
    ).compile().as_text()
    assert text.count("tpu_custom_call") == calls_per_layer * layers
    assert " all-gather(" not in text and " all-to-all(" not in text
    assert not re.search(r"\[\d+,12,1024,1024\]", text)
