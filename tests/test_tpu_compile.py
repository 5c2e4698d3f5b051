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


def _grouped_products(text: str, kernel: str = "ragged-dot-none") -> list:
    """``(rows, columns)`` of every grouped product's result in a
    compiled program's text: by XLA:TPU's ``ragged_dot`` kernel, or
    (``kernel="gmm"``) by ``megablox.gmm`` in the tiles ``ops/moe.py``
    chose."""
    return [(int(r), int(c)) for r, c in re.findall(
        rf"%{kernel}\S* = bf16\[(\d+),(\d+)\]\S* custom-call\(",
        text)]


@pytest.mark.parametrize("rows, k, n, tn", [
    (512, 2560, 768, 768),      # ling3_flash_ep8, gate and up
    (2048, 768, 2560, 2560),    # its down, a full prefill block
    (640, 2048, 512, 512),      # qwen3_next_ep8, gate and up
    (2048, 512, 2048, 2048),    # its down, a full prefill block
    (512, 2560, 1280, 1280),    # the largest whole expert: 16.4 of 16.8 MB
    (512, 2560, 1536, 768),     # the next: its columns in two tiles
    (128, 3072, 3072, 1024),    # trinity_large_ep8's token step: in three,
    (2048, 3072, 3072, 1024),   # and a full prefill block (15.7 of 16.8 MB)
    (512, 2560, 4096, 1024),    # the most tiles the rule splits into: four
    (512, 768, 4096, 2048),     # granite4_h_small_ep8's down: in two
    (512, 4096, 768, 768),      # its gate and up, whole
])
def test_the_tiles_the_walk_chooses_compile_for_v5e(topo, uncached,
                                                    monkeypatch, rows, k, n,
                                                    tn):
    """``ops/moe.product_tiling``'s answer is one Mosaic accepts inside
    the VMEM the kernel compiles under, at the cells' shapes and at the
    edges of the rule — a whole expert, or its columns in the fewest
    equal whole-lane tiles that fit, four at most: one kernel, no copy
    of the experts."""
    from distributedtensorflowexample_tpu.ops import moe
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert moe.product_tiling(rows, k, n, jnp.bfloat16) == (128, k, tn)
    one = SingleDeviceSharding(topo.devices[0])
    sds = lambda shape, t: jax.ShapeDtypeStruct(shape, t, sharding=one)
    compiled = jax.jit(moe.grouped_product).lower(
        sds((rows, k), jnp.bfloat16), sds((64, k, n), jnp.bfloat16),
        sds((64,), jnp.int32)).compile()
    assert _grouped_products(compiled.as_text(), "gmm") == [(rows, n)]
    assert compiled.memory_analysis().temp_size_in_bytes < 2e6


# ---- the afmoe share at the benchmark cell's own sizes ---------------------

def _trinity_programs(topo, slots=32, cache_len=16384):
    """The model of benchmarks/configs/trinity_large_ep8.json with shapes
    for its parameters and for a 32-slot, 16,384-row engine's cache, all
    on the described chip."""
    import os
    from distributedtensorflowexample_tpu.models import (
        build_model_from_config)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    model = build_model_from_config(os.path.join(
        root, "benchmarks", "configs", "trinity_large_ep8.json"))
    one = SingleDeviceSharding(topo.devices[0])
    on_chip = lambda tree: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one), tree)
    params = on_chip(jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]))
    ck, cv = on_chip(jax.eval_shape(
        lambda: model.init_cache(slots, cache_len)))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one)
    return model, params, ck, cv, i32


def test_trinity_decode_step_compiles_for_v5e_beside_a_full_chip(
        topo, uncached, monkeypatch):
    """The decode program of the cell (32 slots, 16,384 rows) as the
    chip builds it: the grouped products are ``megablox.gmm``'s in the
    tiles ``ops/moe.product_tiling`` chose (three a layer, four expert
    layers; an expert's columns in three tiles of 1,024), every layer's
    cache is aliased onto its input, and the program's temporaries are
    small beside 12.9 GB of weights and cache."""
    from distributedtensorflowexample_tpu.serving import engine as eng
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    model, params, ck, cv, i32 = _trinity_programs(topo)
    # A new function: jax caches a trace by its arguments, not by the
    # backend the ops were told.
    compiled = jax.jit(lambda *args: eng._decode_step_fn(model, *args),
                       donate_argnums=(1, 2)).lower(
        params, ck, cv, i32(32), i32(32), i32(4, 32)).compile()
    mem = compiled.memory_analysis()
    assert 12.9e9 < mem.argument_size_in_bytes < 13.0e9
    assert mem.alias_size_in_bytes == 2 * 32 * (16384 + 4 * 4096) * 2048
    assert mem.temp_size_in_bytes < 0.5e9
    assert _grouped_products(compiled.as_text(), "gmm") == [(128, 3072)] * 12
    assert not _grouped_products(compiled.as_text())


@pytest.mark.parametrize("batch, bucket, block", [
    (1, 256, 256), (2, 16384, 2048), (1, 6144, 2048)])
def test_trinity_prefill_compiles_for_v5e_and_fits(topo, uncached, batch,
                                                   bucket, block,
                                                   monkeypatch):
    """A prefill program below the cell's ladder, the fullest one, and a
    bucket of the ladder that is no power of two (six tiles, a window
    and a half): no ``[H, T, T]`` scores (a 16,384-token prompt's would
    be 51.5 GB), the head at the last position only, and weights, cache
    and the program's activations together inside the chip's 16.9 GB.
    Past one tile the attention of every layer is the TPU's kernel, not
    the tiled walk's loop (the backend is the CPU's here, so the test
    says "built for a TPU" itself).  The grouped products over the walk's
    blocks are ``megablox.gmm``'s — an expert of 18.9 MB fits no VMEM
    tile, a third of its columns does (``ops/moe.product_tiling``) — and
    none is left to ``ragged_dot``."""
    from distributedtensorflowexample_tpu.ops import attention as attention_op
    from distributedtensorflowexample_tpu.serving import engine as eng
    monkeypatch.setattr(attention_op.jax, "default_backend", lambda: "tpu")
    model, params, ck, cv, i32 = _trinity_programs(topo)
    compiled = eng._prefill_bucketed.lower(
        model, params, ck, cv, i32(batch, bucket), i32(batch),
        i32(batch)).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16.9e9
    assert mem.temp_size_in_bytes < 3.5e9
    text = compiled.as_text()
    assert ("splash" in text) == (bucket > attention_op.ATTN_BLOCK)
    assert (bucket in model.prefill_buckets(16384)) == (bucket > 256)
    assert _grouped_products(text, "gmm") == [(block, 3072)] * 12
    assert not _grouped_products(text)


# ---- the token step's ragged attention (PR 29) ------------------------------

@pytest.mark.parametrize("rows", [16384, 4096])     # the full layer, a ring
def test_decode_attention_compiles_to_one_kernel_for_v5e(topo, uncached,
                                                         monkeypatch, rows):
    """The op at the cell's shapes: one Mosaic call, and the cache goes
    to it as the engine holds it — the program has no temporary, so no
    relaid copy of a 1 GB operand."""
    from distributedtensorflowexample_tpu.ops import attention as attention_op
    monkeypatch.setattr(attention_op.jax, "default_backend", lambda: "tpu")
    one = SingleDeviceSharding(topo.devices[0])
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                    sharding=one)
    cache = sds((32, rows, 8, 128), jnp.bfloat16)
    compiled = jax.jit(attention_op.decode_attention).lower(
        sds((32, 1, 8, 6, 128), jnp.bfloat16), cache, cache,
        sds((32, 1), jnp.int32)).compile()
    assert compiled.as_text().count(
        'custom_call_target="tpu_custom_call"') == 1
    assert compiled.memory_analysis().temp_size_in_bytes < 1e6


@pytest.mark.parametrize("slots, heads, rows", [
    (80, 64, 10240),            # kimi_k2_5_ep32.serve_reasoning_backlog
    (256, 32, 8192),            # ling3_flash_ep8.serve_longform_backlog
])
def test_latent_decode_attention_compiles_to_one_kernel_for_v5e(
        topo, uncached, monkeypatch, slots, heads, rows):
    """The latent op at the two cells' shapes: one Mosaic call a layer
    with one grid step a slot, the rows left in HBM for the kernel's own
    copies — a slot's 10 to 13 MB of them held whole and twice would not
    fit the 16 MiB of scoped VMEM; the kernel holds two blocks of 1,024
    rows, 2.6 MB — and no temporary, so no relaid copy of a 1 to 2.7 GB
    operand."""
    from distributedtensorflowexample_tpu.ops import attention as attention_op
    from distributedtensorflowexample_tpu.ops.pallas import (
        decode_attention as ragged)
    monkeypatch.setattr(attention_op.jax, "default_backend", lambda: "tpu")
    one = SingleDeviceSharding(topo.devices[0])
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                    sharding=one)
    op = lambda q, c, n: attention_op.latent_decode_attention(
        q, c, n, v_dim=512, scale=0.1447)
    args = (sds((slots, heads, 640), jnp.bfloat16),
            sds((slots, rows, 640), jnp.bfloat16), sds((slots,), jnp.int32))
    calls = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                calls.append(eqn.params["grid_mapping"])
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)
    walk(jax.make_jaxpr(op)(*args).jaxpr)
    (call,) = calls
    block = ragged.pick_block(rows, blocks=ragged.LATENT_BLOCKS)
    assert ragged.latent_fetch_block(rows, 640, 512) == ragged.GRANULE
    assert call.grid == (slots,)
    assert "any" in str(call.block_mappings[1].transformed_block_aval)
    assert str(call.scratch_avals[0]) == (
        f"Ref<vmem>{{bfloat16[2,{block},640]}}")
    compiled = jax.jit(op).lower(*args).compile()
    assert compiled.as_text().count(
        'custom_call_target="tpu_custom_call"') == 1
    assert compiled.memory_analysis().temp_size_in_bytes < 1e6


def test_trinity_decode_step_takes_the_ragged_kernel_in_every_layer(
        topo, uncached, monkeypatch):
    """The cell's decode program built for a TPU: five ragged kernels
    (one a layer), the cache's donation still aliased, and no copy of a
    cache-sized array anywhere in it."""
    from distributedtensorflowexample_tpu.ops import attention as attention_op
    from distributedtensorflowexample_tpu.serving import engine as eng
    monkeypatch.setattr(attention_op.jax, "default_backend", lambda: "tpu")
    model, params, ck, cv, i32 = _trinity_programs(topo)
    # jax caches a function's trace by its arguments, not by the backend
    # the op was told: a new function, so the test above leaves no trace.
    compiled = jax.jit(lambda *args: eng._decode_step_fn(model, *args),
                       donate_argnums=(1, 2)).lower(
        params, ck, cv, i32(32), i32(32), i32(4, 32)).compile()
    text = compiled.as_text()
    # (XLA:TPU's own ragged_dot is a tpu_custom_call too: count ours.)
    kernels = [line for line in text.splitlines()
               if re.search(r"%ragged_decode_attention\S* = \S+ custom-call\(",
                            line)]
    assert len(kernels) == 5
    assert all('custom_call_target="tpu_custom_call"' in k for k in kernels)
    # 32 slots x 4 picks are one block of 128 sorted rows, no loop
    # (ops/moe.block_rows), each product the tiled kernel's.
    assert _grouped_products(text, "gmm") == [(128, 3072)] * 12
    assert not _grouped_products(text)
    assert "moe.experts/while" not in text
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == 2 * 32 * (16384 + 4 * 4096) * 2048
    assert mem.temp_size_in_bytes < 0.5e9
    assert not re.search(r"bf16\[32,(16384|4096|131072|32768),[^\]]*\]\S* "
                         r"copy\(", text)
    _vectors_stay_on_the_chip(compiled, 32)


def _vectors_stay_on_the_chip(compiled, slots: int) -> None:
    """The greedy program's outputs: the tokens with the model's four
    counts behind them (the host's one read-back), then each slot's next
    token and position, left on the device as the next step's inputs,
    then the caches."""
    toks, tok, pos = compiled.out_info[:3]
    assert (toks.shape, tok.shape, pos.shape) == (
        (slots + 4,), (slots,), (slots,))
    assert toks.dtype == tok.dtype == pos.dtype == jnp.int32


# ---- the qwen3_next share at the benchmark cell's own sizes (PR 34) ---------

def _qwen3_next_programs(topo, slots=256, cache_len=4096):
    """The model of benchmarks/configs/qwen3_next_ep8.json with shapes for
    its parameters and for a 256-slot, 4,096-row engine's cache — K/V
    rows of two layers, recurrent and convolution states of six — all on
    the described chip."""
    import os
    from distributedtensorflowexample_tpu.models import (
        build_model_from_config)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    model = build_model_from_config(os.path.join(
        root, "benchmarks", "configs", "qwen3_next_ep8.json"))
    one = SingleDeviceSharding(topo.devices[0])
    on_chip = lambda tree: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one), tree)
    params = on_chip(jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]))
    ck, cv = on_chip(jax.eval_shape(
        lambda: model.init_cache(slots, cache_len)))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one)
    return model, params, ck, cv, i32


def test_qwen3_next_decode_step_compiles_for_v5e_in_place(topo, uncached,
                                                          monkeypatch):
    """The cell's decode program built for a TPU: the ragged kernel in
    both attention layers over the flat rows of two K/V heads, the
    recurrence's kernel in the six Gated DeltaNet layers, the experts'
    grouped products by ``megablox.gmm`` over blocks sized to the pairs
    this share holds,
    every layer's rows AND states aliased onto their inputs (7.6 GB: the
    recurrent state is updated in place), no copy of a state- or
    cache-sized array, and small temporaries beside 11.5 GB of weights
    and cache."""
    from distributedtensorflowexample_tpu.ops import attention as attention_op
    from distributedtensorflowexample_tpu.serving import engine as eng
    monkeypatch.setattr(attention_op.jax, "default_backend", lambda: "tpu")
    model, params, ck, cv, i32 = _qwen3_next_programs(topo)
    compiled = jax.jit(lambda *args: eng._decode_step_fn(model, *args),
                       donate_argnums=(1, 2)).lower(
        params, ck, cv, i32(256), i32(256), i32(4, 256)).compile()
    text = compiled.as_text()
    kernels = [line for line in text.splitlines()
               if re.search(r"%ragged_decode_attention\S* = \S+ custom-call\(",
                            line)]
    assert len(kernels) == 2
    steps = [line for line in text.splitlines()
             if re.search(r"%gated_delta_step\S* = .* custom-call\(", line)]
    assert len(steps) == 6              # one a Gated DeltaNet layer
    # 256 slots x 10 picks = 2,560 pairs, ~320 of them on the 64 held
    # experts: the walk hands the grouped products blocks of 640 sorted
    # rows (ops/moe.block_rows), not prefill's 2,048 — and an expert of
    # 2 MB is one VMEM tile, so the kernel is the one whose tiles
    # ops/moe.product_tiling chose, three a layer.
    products = _grouped_products(text, "gmm")
    assert sorted(set(products)) == [(640, 512), (640, 2048)]
    assert len(products) == 3 * 8
    assert not _grouped_products(text)
    mem = compiled.memory_analysis()
    state = 32 * 128 * 128 * 4 + 3 * 8192 * 2
    assert mem.alias_size_in_bytes == 256 * (
        2 * 2 * 4096 * 2 * 256 * 2 + 6 * state)
    assert 11.5e9 < mem.argument_size_in_bytes < 11.6e9
    assert mem.temp_size_in_bytes < 1.0e9
    assert not re.search(r"f32\[256,32,128,128\]\S* copy\(", text)
    assert not re.search(r"bf16\[256,8192,256\]\S* copy\(", text)
    _vectors_stay_on_the_chip(compiled, 256)


@pytest.mark.parametrize("batch, bucket", [(2, 256), (1, 1024), (2, 4096)])
def test_qwen3_next_prefill_compiles_for_v5e_and_fits(topo, uncached, batch,
                                                      bucket, monkeypatch):
    """The ladder's first bucket, one tile (the einsum chain's last) and
    the fullest program: the chunked scan and its triangular solve
    compile, attention past one tile is the TPU's kernel at heads of 256,
    and weights, cache and activations fit the chip."""
    from distributedtensorflowexample_tpu.ops import attention as attention_op
    from distributedtensorflowexample_tpu.serving import engine as eng
    monkeypatch.setattr(attention_op.jax, "default_backend", lambda: "tpu")
    model, params, ck, cv, i32 = _qwen3_next_programs(topo)
    compiled = jax.jit(lambda *args: eng._prefill_bucketed.__wrapped__(
        model, *args), donate_argnums=(1, 2)).lower(
        params, ck, cv, i32(batch, bucket), i32(batch), i32(batch)).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.5e9
    assert ("splash" in compiled.as_text()) == (
        bucket > attention_op.ATTN_BLOCK)
    assert bucket in model.prefill_buckets(4096)


# ---- the bailing_hybrid share at the benchmark cell's own sizes (PR 37) -----

def _ling3_programs(topo, slots=256, cache_len=8192):
    """The model of benchmarks/configs/ling3_flash_ep8.json with shapes
    for its parameters and for a 256-slot, 8,192-row engine's cache —
    latent rows of one layer, recurrent and convolution states of six —
    all on the described chip."""
    import os
    from distributedtensorflowexample_tpu.models import (
        build_model_from_config)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    model = build_model_from_config(os.path.join(
        root, "benchmarks", "configs", "ling3_flash_ep8.json"))
    one = SingleDeviceSharding(topo.devices[0])
    on_chip = lambda tree: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one), tree)
    params = on_chip(jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]))
    ck, cv = on_chip(jax.eval_shape(
        lambda: model.init_cache(slots, cache_len)))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one)
    return model, params, ck, cv, i32


def test_ling3_decode_step_compiles_for_v5e_in_place(topo, uncached,
                                                     monkeypatch):
    """The cell's decode program built for a TPU: the latent kernel in
    the one MLA layer over rows of 640 (one shared row a position, key
    and value), the recurrence's kernel — its per-channel body — in the
    six KDA layers, the experts' grouped products by ``megablox.gmm``
    over blocks sized to the pairs this share holds, the latent rows AND
    the states aliased
    onto their inputs (6.0 GB, updated in place), no copy of a state- or
    cache-sized array, and small temporaries beside 11.8 GB of weights
    and cache."""
    from distributedtensorflowexample_tpu.ops import attention as attention_op
    from distributedtensorflowexample_tpu.serving import engine as eng
    monkeypatch.setattr(attention_op.jax, "default_backend", lambda: "tpu")
    model, params, ck, cv, i32 = _ling3_programs(topo)
    compiled = jax.jit(lambda *args: eng._decode_step_fn(model, *args),
                       donate_argnums=(1, 2)).lower(
        params, ck, cv, i32(256), i32(256), i32(4, 256)).compile()
    text = compiled.as_text()
    kernel = lambda name: [
        line for line in text.splitlines()
        if re.search(rf"%{name}\S* = .* custom-call\(", line)]
    assert len(kernel("latent_decode_attention")) == 1
    assert len(kernel("gated_delta_step")) == 6     # one a KDA layer
    assert not kernel("ragged_decode_attention")
    # 256 slots x 8 picks = 2,048 pairs, ~256 of them on the 64 held
    # experts: blocks of 512 sorted rows (ops/moe.block_rows), and an
    # expert of 3.9 MB fits VMEM twice: the kernel whose tiles
    # ops/moe.product_tiling chose, three a layer, not ragged_dot's.
    products = _grouped_products(text, "gmm")
    assert sorted(set(products)) == [(512, 768), (512, 2560)]
    assert len(products) == 3 * 6
    assert not _grouped_products(text)
    mem = compiled.memory_analysis()
    state = 32 * 128 * 128 * 4 + 3 * 3 * 4096 * 2
    assert mem.alias_size_in_bytes == 256 * (8192 * 640 * 2 + 6 * state)
    assert 11.7e9 < mem.argument_size_in_bytes < 11.9e9
    assert mem.temp_size_in_bytes < 1.0e9
    assert not re.search(r"f32\[256,32,128,128\]\S* copy\(", text)
    assert not re.search(r"bf16\[256,8192,640\]\S* copy\(", text)
    _vectors_stay_on_the_chip(compiled, 256)


@pytest.mark.parametrize("batch, bucket", [(2, 256), (1, 1024), (2, 4096)])
def test_ling3_prefill_compiles_for_v5e_and_fits(topo, uncached, batch,
                                                 bucket, monkeypatch):
    """The ladder's first bucket, one tile (the einsum chain's last) and
    the fullest program the cell warms: the chunked scan with its decays
    in blocks of 16 and its triangular solve compile, latent attention
    past one tile is the TPU's kernel at query/key heads of 192 padded to
    256 and value heads of 128, and weights, cache and activations fit
    the chip."""
    from distributedtensorflowexample_tpu.ops import attention as attention_op
    from distributedtensorflowexample_tpu.serving import engine as eng
    monkeypatch.setattr(attention_op.jax, "default_backend", lambda: "tpu")
    model, params, ck, cv, i32 = _ling3_programs(topo)
    compiled = jax.jit(lambda *args: eng._prefill_bucketed.__wrapped__(
        model, *args), donate_argnums=(1, 2)).lower(
        params, ck, cv, i32(batch, bucket), i32(batch), i32(batch)).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.5e9
    assert ("splash" in compiled.as_text()) == (
        bucket > attention_op.ATTN_BLOCK)
    assert bucket in model.prefill_buckets(8192)


# ---- the granite-4.0-h share at the benchmark cell's own sizes (PR 41) ------

def _granite4_programs(topo, slots=192, cache_len=2048):
    """The model of benchmarks/configs/granite4_h_small_ep8.json with
    shapes for its parameters and for a 192-slot, 2,048-row engine's cache
    — K/V rows of one layer, recurrent and convolution states of nine —
    all on the described chip."""
    import os
    from distributedtensorflowexample_tpu.models import (
        build_model_from_config)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    model = build_model_from_config(os.path.join(
        root, "benchmarks", "configs", "granite4_h_small_ep8.json"))
    one = SingleDeviceSharding(topo.devices[0])
    on_chip = lambda tree: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one), tree)
    params = on_chip(jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]))
    ck, cv = on_chip(jax.eval_shape(
        lambda: model.init_cache(slots, cache_len)))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one)
    return model, params, ck, cv, i32


def test_granite4_decode_step_compiles_for_v5e_in_place(topo, uncached,
                                                        monkeypatch):
    """The cell's decode program built for a TPU: no kernel of this
    repo's in the nine Mamba-2 layers (XLA's one fused pass over the state
    was faster than the kernel written for it, PERF.md section 6, PR 41),
    the ragged kernel in the one attention
    layer (8 K/V heads of 128: whole tiles, no flat view), the experts'
    three products by ``megablox.gmm`` — gate and up by whole experts,
    the down product, whose expert does not fit VMEM twice, with its
    columns in two tiles; the K/V rows AND
    the states aliased onto their inputs (8.9 GB, updated in place), no
    copy of a state- or cache-sized array, and small temporaries beside
    13 GB of weights and cache."""
    from distributedtensorflowexample_tpu.ops import attention as attention_op
    from distributedtensorflowexample_tpu.serving import engine as eng
    monkeypatch.setattr(attention_op.jax, "default_backend", lambda: "tpu")
    model, params, ck, cv, i32 = _granite4_programs(topo)
    compiled = jax.jit(lambda *args: eng._decode_step_fn(model, *args),
                       donate_argnums=(1, 2)).lower(
        params, ck, cv, i32(192), i32(192), i32(4, 192)).compile()
    text = compiled.as_text()
    kernel = lambda name: [
        line for line in text.splitlines()
        if re.search(rf"%{name}\S* = .* custom-call\(", line)]
    assert len(kernel("ragged_decode_attention")) == 1
    assert not kernel("gated_delta_step")
    # 192 slots x 10 picks = 1,920 pairs, ~240 of them on the 9 held
    # experts: blocks of 512 sorted rows (ops/moe.block_rows); the gate
    # and up products [4096, 768] fit VMEM twice over, the down product
    # [768, 4096] does in halves (ops/moe.product_tiling's one rule):
    # thirty products by gmm in ten layers, none by ragged_dot.
    assert sorted(_grouped_products(text, "gmm")) == (
        [(512, 768)] * 20 + [(512, 4096)] * 10)
    assert not _grouped_products(text)
    mem = compiled.memory_analysis()
    state = 128 * 64 * 128 * 4 + 3 * 8448 * 2
    assert mem.alias_size_in_bytes == 192 * (2048 * 4096 + 9 * state)
    assert 12.9e9 < mem.argument_size_in_bytes < 13.2e9
    assert mem.temp_size_in_bytes < 1.0e9
    assert not re.search(r"f32\[192,128,64,128\]\S* copy\(", text)
    assert not re.search(r"bf16\[192,2048,8,128\]\S* copy\(", text)
    _vectors_stay_on_the_chip(compiled, 192)


@pytest.mark.parametrize("batch, bucket", [(2, 256), (1, 512), (2, 1024)])
def test_granite4_prefill_compiles_for_v5e_and_fits(topo, uncached, batch,
                                                    bucket, monkeypatch):
    """The ladder's three buckets the cell warms, the fullest last: the
    chunked scan compiles (no solve: a plain recurrence over the chunks),
    attention up to one tile is the einsum chain, and weights, cache and
    activations fit the chip."""
    from distributedtensorflowexample_tpu.ops import attention as attention_op
    from distributedtensorflowexample_tpu.serving import engine as eng
    monkeypatch.setattr(attention_op.jax, "default_backend", lambda: "tpu")
    model, params, ck, cv, i32 = _granite4_programs(topo)
    compiled = jax.jit(lambda *args: eng._prefill_bucketed.__wrapped__(
        model, *args), donate_argnums=(1, 2)).lower(
        params, ck, cv, i32(batch, bucket), i32(batch), i32(batch)).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.5e9
    text = compiled.as_text()
    assert "splash" not in text and "triangular" not in text.lower()
    assert bucket in model.prefill_buckets(2048)


# ---- the kimi_k2 share at the benchmark cell's own sizes (PR 43) ------------

def _kimi_k2_programs(topo, slots=80, cache_len=10240):
    """The model of benchmarks/configs/kimi_k2_5_ep32.json with shapes for
    its parameters and for an 80-slot, 10,240-row engine's cache — latent
    rows in all five layers and nothing else — all on the described
    chip."""
    import os
    from distributedtensorflowexample_tpu.models import (
        build_model_from_config)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    model = build_model_from_config(os.path.join(
        root, "benchmarks", "configs", "kimi_k2_5_ep32.json"))
    one = SingleDeviceSharding(topo.devices[0])
    on_chip = lambda tree: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one), tree)
    params = on_chip(jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]))
    ck, cv = on_chip(jax.eval_shape(
        lambda: model.init_cache(slots, cache_len)))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one)
    return model, params, ck, cv, i32


def test_kimi_k2_decode_step_compiles_for_v5e_in_place(topo, uncached,
                                                       monkeypatch):
    """The cell's decode program built for a TPU: the latent kernel in
    each of the five layers over rows of 640 at 64 heads, the experts'
    grouped products on ``ragged_dot`` (an expert of [7168, 2048] is 58.7
    MB of tiles against 16 MB of scoped VMEM and fits in eight tiles of
    columns, the down matrix in seven: more than
    ``ops/moe.product_tiling`` splits into) over blocks of 128 sorted
    rows, the rows of every layer
    aliased onto their inputs (5.24 GB, updated in place), no copy of a
    cache-sized array, and small temporaries beside 12.2 GB of weights
    and rows."""
    from distributedtensorflowexample_tpu.ops import attention as attention_op
    from distributedtensorflowexample_tpu.serving import engine as eng
    monkeypatch.setattr(attention_op.jax, "default_backend", lambda: "tpu")
    model, params, ck, cv, i32 = _kimi_k2_programs(topo)
    compiled = jax.jit(lambda *args: eng._decode_step_fn(model, *args),
                       donate_argnums=(1, 2)).lower(
        params, ck, cv, i32(80), i32(80), i32(4, 80)).compile()
    text = compiled.as_text()
    kernel = lambda name: [
        line for line in text.splitlines()
        if re.search(rf"%{name}\S* = .* custom-call\(", line)]
    assert len(kernel("latent_decode_attention")) == 5
    assert not kernel("ragged_decode_attention")
    # 80 slots x 8 picks = 640 pairs, ~20 of them on the 12 held experts:
    # one block of 128 sorted rows (ops/moe.block_rows), three products a
    # layer in each of four expert layers.
    assert not _grouped_products(text, "gmm")
    assert sorted(_grouped_products(text)) == (
        [(128, 2048)] * 8 + [(128, 7168)] * 4)
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == 80 * 5 * 10240 * 640 * 2
    assert 12.1e9 < mem.argument_size_in_bytes < 12.4e9
    assert mem.temp_size_in_bytes < 1.0e9
    assert not re.search(r"bf16\[80,10240,640\]\S* copy\(", text)
    _vectors_stay_on_the_chip(compiled, 80)


@pytest.mark.parametrize("batch, bucket", [(1, 3072), (2, 5120)])
def test_kimi_k2_prefill_compiles_for_v5e_and_fits(topo, uncached, batch,
                                                   bucket, monkeypatch):
    """The first and the fullest program the cell warms: latent attention
    past one tile of 512 is the TPU's kernel at 64 query/key heads of 192
    padded to 256 and value heads of 128, in each of five layers, and
    weights, rows and activations fit the chip."""
    from distributedtensorflowexample_tpu.ops import attention as attention_op
    from distributedtensorflowexample_tpu.serving import engine as eng
    monkeypatch.setattr(attention_op.jax, "default_backend", lambda: "tpu")
    model, params, ck, cv, i32 = _kimi_k2_programs(topo)
    compiled = jax.jit(lambda *args: eng._prefill_bucketed.__wrapped__(
        model, *args), donate_argnums=(1, 2)).lower(
        params, ck, cv, i32(batch, bucket), i32(batch), i32(batch)).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.5e9
    assert "splash" in compiled.as_text()
    assert bucket in model.prefill_buckets(10240)
