"""The round-5 dequant-tax fix: affine fast path + fused kernels.

Four contracts, each pinned bitwise (compared as integer bit patterns —
"close" is not a thing this file asserts):

1. EXACTNESS — the fused affine ``f32(u) * scale + bias`` reproduces all
   256 LUT entries of every shipped loader spec on the host; whether
   this backend's jit does too is what ``dequant_affine_is_bitwise``
   answers, and ``dequant_impl="auto"`` lowers to the fast path exactly
   when it does (one backend fuses the multiply-add, another rounds
   twice: the tests assert the rule, not one compiler's outcome).
2. PARITY — training through the affine impl equals training through the
   LUT impls bit-for-bit on params, across every data path: replicated
   resident, sharded resident, async local-SGD, and host-fed.
3. LOWERING — the default auto path on MNIST/CIFAR-shaped splits
   contains NO 256-entry gather in its jaxpr (the op a chip window
   measured at ~10 ns/element: PERF.md "History"), with a positive
   control proving the detector sees the gather when it IS there.
4. KERNELS — the fused Pallas gather+dequant and the fused
   augment+dequant emit bitwise-identical batches to their unfused
   forms (interpret mode on CPU: same kernel code the TPU runs).
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from distributedtensorflowexample_tpu.data import DeviceDataset
from distributedtensorflowexample_tpu.data.dequant import (
    affine_matches_lut, affine_numpy, make_dequant_affine, make_dequant_lut)
from distributedtensorflowexample_tpu.data.device_dataset import (
    apply_dequant_affine, apply_dequant_lut, dequant_affine_is_bitwise,
    resolve_dequant_impl)
from distributedtensorflowexample_tpu.data.synthetic import make_synthetic
from distributedtensorflowexample_tpu.models import build_model
from distributedtensorflowexample_tpu.parallel import (
    make_mesh, replicated_sharding)
from distributedtensorflowexample_tpu.parallel.sync import (
    make_device_gather, make_indexed_train_step, make_resident_eval,
    make_train_step)
from distributedtensorflowexample_tpu.training.state import TrainState

SPECS = ("unit", "cifar")


def _bitwise_equal(a, b):
    a, b = np.asarray(a), np.asarray(b)
    assert a.dtype == b.dtype == np.float32
    np.testing.assert_array_equal(a.view(np.int32), b.view(np.int32))


def _data(n=320, shape=(28, 28, 1), seed=0):
    # NOT 256 rows: a [256]-shaped labels vector (or a 256-row split) is
    # indistinguishable from a LUT table by operand shape alone, and the
    # jaxpr detector below must not flag the legitimate row gathers.
    return make_synthetic(n, shape, 10, seed=seed)


def _table_values(u8, spec):
    """The batch through the implementation ``auto`` resolves for *spec*
    on this backend: the table's entries bit for bit, by the rule."""
    if resolve_dequant_impl(spec) == "affine":
        s, b = make_dequant_affine(spec)
        return jax.jit(apply_dequant_affine)(jnp.asarray(u8), jnp.asarray(s),
                                             jnp.asarray(b))
    return jax.jit(apply_dequant_lut)(jnp.asarray(u8),
                                      jnp.asarray(make_dequant_lut(spec)))


def _same_bits(a, b) -> bool:
    return np.array_equal(np.asarray(a).view(np.int32),
                          np.asarray(b).view(np.int32))


def _cifar_normalized(x):
    """Normalize [0,1] byte-grid pixels the way load_cifar10 does: through
    the canonical single-rounding affine (data.dequant) — NOT a separate
    f32 (x - MEAN) / STD, which double-rounds and is not byte-exact."""
    return affine_numpy(np.rint(x * 255.0).astype(np.uint8), "cifar")


# ---- 1. exactness: affine == LUT over all 256 entries -------------------

@pytest.mark.parametrize("spec", SPECS)
def test_affine_reproduces_all_256_lut_entries_bitwise(spec):
    """The quantize-time verification, spelled out: every byte value's
    affine image equals its tabulated loader value, bit for bit."""
    lut = make_dequant_lut(spec)
    u = np.arange(256, dtype=np.uint8)[:, None]
    aff = affine_numpy(u, spec)
    aff = aff[:, 0] if lut.ndim == 1 else aff
    assert lut.dtype == aff.dtype == np.float32
    np.testing.assert_array_equal(lut.view(np.int32),
                                  np.ascontiguousarray(aff).view(np.int32))
    assert affine_matches_lut(spec)


@pytest.mark.parametrize("spec", SPECS)
def test_backend_affine_is_bitwise(spec):
    """The backend half of the auto-lowering guard answers for THIS
    backend's jitted multiply-add: true exactly when it reproduces the
    table (a backend that splits the fma into mul+add double-rounds the
    biased spec and must answer false).  ``unit`` has no bias, so one
    rounding on any backend."""
    lut = make_dequant_lut(spec)
    s, b = make_dequant_affine(spec)
    u = np.arange(256, dtype=np.uint8)
    if lut.ndim == 2:
        u = np.broadcast_to(u[:, None], (256, lut.shape[1]))
    got = jax.jit(apply_dequant_affine)(jnp.asarray(u), jnp.asarray(s),
                                        jnp.asarray(b))
    assert dequant_affine_is_bitwise(spec) == _same_bits(
        got, np.ascontiguousarray(lut))
    if spec == "unit":
        assert dequant_affine_is_bitwise(spec)


def test_resolve_dequant_impl_rules(monkeypatch):
    """auto lowers to affine exactly when the spec is affine-exact on the
    host AND on this backend; otherwise the bitwise one-hot fallback
    (unless the caller asked for speed-over-bits via quantize='scale');
    named impls pass through."""
    from distributedtensorflowexample_tpu.data import device_dataset as dd
    for spec in SPECS:
        assert resolve_dequant_impl(spec) == (
            "affine" if dequant_affine_is_bitwise(spec) else "onehot")
    for backend_exact, impl in ((True, "affine"), (False, "onehot")):
        with monkeypatch.context() as m:
            m.setattr(dd, "dequant_affine_is_bitwise",
                      lambda spec: backend_exact)
            assert resolve_dequant_impl("cifar") == impl
    for forced in ("affine", "onehot", "lut", "pallas"):
        assert resolve_dequant_impl("unit", forced) == forced
    with pytest.raises(ValueError, match="dequant_impl"):
        resolve_dequant_impl("unit", "bogus")
    # A hypothetical non-affine-representable spec (e.g. a gamma curve):
    # auto must keep the bitwise contract through onehot.
    monkeypatch.setattr(dd, "affine_matches_lut", lambda spec: False)
    assert resolve_dequant_impl("unit", "auto", "auto") == "onehot"
    assert resolve_dequant_impl("unit", "auto", "exact") == "onehot"
    assert resolve_dequant_impl("unit", "auto", "scale") == "affine"


# ---- 2. bitwise training parity across every data path ------------------

def _train_replicated(impl, x, y, mesh, steps_per_next=2, calls=3,
                      data_sharding="replicated"):
    ds = DeviceDataset(x, y, 32, mesh=mesh, seed=2, quantize="auto",
                       dequant_impl=impl, steps_per_next=steps_per_next,
                       data_sharding=data_sharding)
    assert ds.dequant == "unit"
    state = TrainState.create_sharded(build_model("softmax"),
                                      optax.sgd(0.1), (32, 28, 28, 1), 0,
                                      replicated_sharding(mesh))
    step = make_indexed_train_step(32, ds.steps_per_epoch, mesh=mesh,
                                   unroll_steps=steps_per_next,
                                   num_slots=ds.num_slots,
                                   data_sharding=data_sharding,
                                   dequant_impl=impl)
    with mesh:
        for _ in range(calls):
            state, metrics = step(state, next(ds))
        jax.block_until_ready(metrics)
    return np.asarray(jax.tree.leaves(state.params)[0]), float(
        metrics["loss"])


@pytest.mark.parametrize("other", ["onehot", "lut"])
def test_training_parity_affine_vs_lut_replicated(other):
    x, y = _data()
    mesh = make_mesh()
    p_a, l_a = _train_replicated("affine", x, y, mesh)
    p_o, l_o = _train_replicated(other, x, y, mesh)
    assert l_a == l_o
    np.testing.assert_array_equal(p_a, p_o)


def test_training_parity_affine_vs_lut_sharded():
    x, y = _data(512)
    mesh = make_mesh()
    p_a, l_a = _train_replicated("affine", x, y, mesh,
                                 data_sharding="sharded")
    p_o, l_o = _train_replicated("onehot", x, y, mesh,
                                 data_sharding="sharded")
    assert l_a == l_o
    np.testing.assert_array_equal(p_a, p_o)


def test_training_parity_affine_vs_lut_async():
    from distributedtensorflowexample_tpu.parallel.async_ps import (
        make_indexed_async_train_step, make_worker_state)

    x, y = _data(512)
    mesh = make_mesh()

    def run(impl):
        ds = DeviceDataset(x, y, 64, mesh=mesh, seed=5, steps_per_next=4,
                           dequant_impl=impl)
        state = TrainState.create_sharded(
            build_model("softmax"), optax.sgd(0.1), (64, 28, 28, 1), 0,
            replicated_sharding(mesh))
        state = make_worker_state(state, mesh.size, mesh)
        step = make_indexed_async_train_step(
            mesh.size, 4, 64, ds.steps_per_epoch, mesh=mesh,
            unroll_steps=4, num_slots=ds.num_slots, dequant_impl=impl)
        with mesh:
            state, m = step(state, next(ds))
            state, m = step(state, next(ds))
            jax.block_until_ready(m)
        return np.asarray(jax.tree.leaves(state.params)[0])

    np.testing.assert_array_equal(run("affine"), run("onehot"))


def test_training_parity_affine_vs_lut_host_fed():
    """dequant_host_batch resolves the SAME impl knob: a uint8 host batch
    trained through affine equals onehot and lut bit-for-bit (pallas
    degenerates to affine — no gather to fuse with on an upload)."""
    x, y = _data(64)
    u8 = np.rint(x * 255.0).astype(np.uint8)

    def run(impl):
        state = TrainState.create(build_model("softmax"), optax.sgd(0.1),
                                  np.zeros((64, 28, 28, 1), np.float32))
        step = make_train_step(dequant="unit", dequant_impl=impl)
        batch = {"image": jnp.asarray(u8), "label": jnp.asarray(y)}
        for _ in range(3):
            state, m = step(state, batch)
        jax.block_until_ready(m)
        return np.asarray(jax.tree.leaves(state.params)[0])

    ref = run("affine")
    for other in ("onehot", "lut", "pallas", "auto"):
        np.testing.assert_array_equal(ref, run(other))


def test_gather_rejects_mismatched_factory_and_dataset():
    """A step factory forced to one impl family over a dataset resolved
    to the other is a TRACE-TIME error, not a silently different kernel
    (the train/eval-asymmetry hazard, caught at build)."""
    x, y = _data()
    ds = DeviceDataset(x, y, 32, seed=0, dequant_impl="affine")
    g = make_device_gather(32, ds.steps_per_epoch, num_slots=ds.num_slots,
                           dequant_impl="onehot")
    with pytest.raises(ValueError, match="affine family"):
        jax.jit(g)(jnp.asarray(0, jnp.int32), jax.random.PRNGKey(0),
                   ds.peek())
    ds_l = DeviceDataset(x, y, 32, seed=0, dequant_impl="lut")
    g_a = make_device_gather(32, ds_l.steps_per_epoch,
                             num_slots=ds_l.num_slots, dequant_impl="affine")
    with pytest.raises(ValueError, match="LUT family"):
        jax.jit(g_a)(jnp.asarray(0, jnp.int32), jax.random.PRNGKey(0),
                     ds_l.peek())


def test_resident_eval_honors_dequant_impl():
    """Eval resolves the SAME rule as training, so a train/eval parity
    check exercises one kernel — and every impl yields the identical
    accuracy (the dequants are bitwise-equal, so the logits are too)."""
    x, y = _data(200)
    state = TrainState.create(build_model("softmax"), optax.sgd(0.1),
                              np.zeros((50, 28, 28, 1), np.float32))
    accs = {impl: make_resident_eval(x, y, batch_size=50,
                                     dequant_impl=impl)(state)
            for impl in ("auto", "affine", "onehot", "lut", "pallas")}
    assert len(set(accs.values())) == 1, accs


# ---- 3. lowering: the default auto path has no 256-entry gather ---------

def _gather_eqns(jaxpr):
    """Every gather-family eqn in ``jaxpr`` (recursively through inner
    jaxprs) whose first operand is LUT-shaped — [256] or [256, C] — the
    table read the affine lowering exists to eliminate.  The ndim cap
    keeps a legitimate row gather over a 256-row split
    (``take(images[256, H, W, C], idx)``) out of the net; a [256] LABELS
    vector is shape-indistinguishable from a unit LUT, which is why
    ``_data`` defaults to 320 rows."""
    from jax import core
    found = []

    def walk(jx):
        for eqn in jx.eqns:
            if "gather" in eqn.primitive.name:
                shapes = [tuple(getattr(v.aval, "shape", ())) or ()
                          for v in eqn.invars]
                if any(s and s[0] == 256 and len(s) <= 2 for s in shapes):
                    found.append((eqn.primitive.name, shapes))
        for sub in core.subjaxprs(jx):
            walk(sub)

    walk(jaxpr.jaxpr if hasattr(jaxpr, "jaxpr") else jaxpr)
    return found


@pytest.mark.parametrize("shape,spec", [((28, 28, 1), "unit"),
                                        ((32, 32, 3), "cifar")])
def test_default_auto_path_has_no_256_gather(shape, spec):
    """The acceptance-criteria jaxpr check: quantize=auto + dequant_impl=
    auto on an MNIST/CIFAR-shaped split traces to a program with NO
    256-entry table gather, whichever bitwise form the rule resolved."""
    x, y = _data(shape=shape)
    if spec == "cifar":
        x = _cifar_normalized(x)
    ds = DeviceDataset(x, y, 32, seed=0)              # all-default knobs
    assert ds.dequant == spec
    assert ds.dequant_impl == resolve_dequant_impl(spec) != "lut"
    g = make_device_gather(32, ds.steps_per_epoch, num_slots=ds.num_slots)
    jaxpr = jax.make_jaxpr(g)(jnp.asarray(0, jnp.int32),
                              jax.random.PRNGKey(0), ds.peek())
    assert _gather_eqns(jaxpr) == []


def test_256_gather_detector_positive_control():
    """dequant_impl='lut' (the demoted round-4 diagnostic) MUST trip the
    detector — otherwise the test above could pass because the detector
    rotted, not because the lowering is right."""
    x, y = _data()
    ds = DeviceDataset(x, y, 32, seed=0, dequant_impl="lut")
    g = make_device_gather(32, ds.steps_per_epoch, num_slots=ds.num_slots,
                           dequant_impl="lut")
    jaxpr = jax.make_jaxpr(g)(jnp.asarray(0, jnp.int32),
                              jax.random.PRNGKey(0), ds.peek())
    assert _gather_eqns(jaxpr), "lut impl shows no 256-gather: detector rot"


def test_full_train_step_default_has_no_256_gather():
    """Same check one level up, on the whole jitted train step the bench
    actually times (the gather could hide behind factory plumbing)."""
    x, y = _data()
    mesh = make_mesh()
    ds = DeviceDataset(x, y, 32, mesh=mesh, seed=0, steps_per_next=2)
    state = TrainState.create_sharded(build_model("softmax"),
                                      optax.sgd(0.1), (32, 28, 28, 1), 0,
                                      replicated_sharding(mesh))
    step = make_indexed_train_step(32, ds.steps_per_epoch, mesh=mesh,
                                   unroll_steps=2, num_slots=ds.num_slots)
    with mesh:
        jaxpr = jax.make_jaxpr(lambda s, d: step(s, d))(state, ds.peek())
    assert _gather_eqns(jaxpr) == []


# ---- 4. fused kernels: bitwise parity with their unfused forms ----------

@pytest.mark.parametrize("spec,shape", [("unit", (28, 28, 1)),
                                        ("cifar", (32, 32, 3))])
def test_pallas_fused_gather_dequant_parity(spec, shape):
    """The Pallas kernel (interpret mode on CPU — the same kernel code a
    TPU compiles) == take-then-dequant through the form ``auto`` resolves
    on this backend, bitwise, repeated indices included."""
    from distributedtensorflowexample_tpu.ops.pallas import (
        fused_gather_dequant)

    rng = np.random.RandomState(3)
    imgs = rng.randint(0, 256, (40,) + shape, dtype=np.uint8)
    idx = np.array([7, 0, 39, 7, 21, 3, 3, 12], np.int32)   # dups on purpose
    s, b = make_dequant_affine(spec)
    out = fused_gather_dequant(jnp.asarray(imgs), jnp.asarray(idx),
                               jnp.asarray(s), jnp.asarray(b))
    _bitwise_equal(out, _table_values(imgs[idx], spec))


@pytest.mark.parametrize("mesh_size", [0, 4])
def test_pallas_gather_path_matches_affine_gather(mesh_size):
    """dequant_impl='pallas' through make_device_gather == the unfused
    affine gather, bitwise, labels included — on one device and on a
    mesh, where the kernel runs per device under shard_map (XLA:TPU
    refuses to partition a Mosaic call; the CPU interpreter never
    minded, so only the wrapped form is what the chip runs)."""
    from distributedtensorflowexample_tpu.parallel import make_mesh
    mesh = make_mesh(mesh_size) if mesh_size else None
    x, y = _data()
    outs = {}
    for impl in ("affine", "pallas"):
        ds = DeviceDataset(x, y, 32, mesh=mesh, seed=4, dequant_impl=impl)
        g = make_device_gather(32, ds.steps_per_epoch, mesh=mesh,
                               num_slots=ds.num_slots, dequant_impl=impl)
        outs[impl] = jax.jit(g)(jnp.asarray(1, jnp.int32),
                                jax.random.PRNGKey(2), ds.peek())
    _bitwise_equal(outs["affine"]["image"], outs["pallas"]["image"])
    np.testing.assert_array_equal(np.asarray(outs["affine"]["label"]),
                                  np.asarray(outs["pallas"]["label"]))


def test_pallas_rejects_sharded_and_validates():
    x, y = _data(512)
    mesh = make_mesh()
    with pytest.raises(ValueError, match="replicated"):
        make_device_gather(64, 8, mesh=mesh, num_slots=3,
                           data_sharding="sharded", dequant_impl="pallas")
    with pytest.raises(ValueError, match="dequant_impl"):
        make_device_gather(64, 8, num_slots=3, dequant_impl="bogus")
    with pytest.raises(ValueError, match="dequant_impl"):
        DeviceDataset(x, y, 64, dequant_impl="bogus")


def test_fused_augment_dequant_matches_unfused():
    """cifar_augment_dequant_device (the augment-path input fix) ==
    augment then affine — the same crops/flips, the same bits — and ==
    augment then one-hot LUT exactly where the rule says this backend's
    affine is the table."""
    from distributedtensorflowexample_tpu.data.augment_device import (
        cifar_augment_dequant_device, cifar_augment_device)

    u8 = np.random.RandomState(1).randint(0, 256, (16, 32, 32, 3),
                                          dtype=np.uint8)
    s, b = make_dequant_affine("cifar")
    lut = make_dequant_lut("cifar")
    key = jax.random.PRNGKey(9)
    fused = jax.jit(lambda u: cifar_augment_dequant_device(
        u, key, jnp.asarray(s), jnp.asarray(b)))(jnp.asarray(u8))
    aug = jax.jit(lambda u: cifar_augment_device(u, key))(jnp.asarray(u8))
    unfused_affine = jax.jit(apply_dequant_affine)(
        aug, jnp.asarray(s), jnp.asarray(b))
    unfused_onehot = jax.jit(apply_dequant_lut)(aug, jnp.asarray(lut))
    _bitwise_equal(fused, unfused_affine)
    np.testing.assert_allclose(fused, unfused_onehot, rtol=1e-6, atol=1e-6)
    assert _same_bits(fused, unfused_onehot) == \
        dequant_affine_is_bitwise("cifar")
    with pytest.raises(TypeError, match="uint8"):
        cifar_augment_dequant_device(jnp.zeros((2, 32, 32, 3), jnp.float32),
                                     key, jnp.asarray(s), jnp.asarray(b))


def test_augmented_gather_parity_affine_vs_onehot():
    """End to end through make_device_gather with augment='cifar': the
    fused augment+dequant (affine family) and the augment-then-onehot
    path draw the same crops, and emit the same bits exactly where the
    rule says this backend's affine is the table."""
    x, y = _data(128, shape=(32, 32, 3))
    xn = _cifar_normalized(x)
    outs = {}
    for impl in ("affine", "onehot"):
        ds = DeviceDataset(xn, y, 32, seed=7, dequant_impl=impl)
        assert ds.dequant == "cifar"
        g = make_device_gather(32, ds.steps_per_epoch, augment="cifar",
                               num_slots=ds.num_slots, dequant_impl=impl)
        outs[impl] = jax.jit(g)(jnp.asarray(0, jnp.int32),
                                jax.random.PRNGKey(5), ds.peek())
    np.testing.assert_allclose(outs["affine"]["image"],
                               outs["onehot"]["image"], rtol=1e-6, atol=1e-6)
    assert _same_bits(outs["affine"]["image"], outs["onehot"]["image"]) == \
        dequant_affine_is_bitwise("cifar")


# ---- prefetch / ring sizing (the input-dispatch overlap) ----------------

def test_ring_slots_cover_two_consecutive_windows():
    """ring_slots_for sizes for TWO windows (prefetch computes window
    N+1's permutations while window N is in flight) plus margin."""
    for window, spe in ((1, 10), (10, 10), (25, 10), (4, 100)):
        slots = DeviceDataset.ring_slots_for(window, spe)
        # Epochs two consecutive windows can touch, worst case:
        worst = -(-2 * window // spe) + 1
        assert slots >= worst, (window, spe, slots, worst)


def test_prefetch_is_pure_overlap():
    """prefetch() after each next() (what TrainLoop does post-dispatch)
    changes NOTHING a step can observe: for every window, the perm rows
    of every epoch that window reads are identical to a consumer that
    never prefetches.  (The FULL ring legitimately differs — prefetch's
    whole point is writing future epochs' slots early — so the check is
    on the slots the in-flight window gathers from, which is all the
    jitted gather ever dereferences.)"""
    x, y = _data(128)
    spn = 2
    a = DeviceDataset(x, y, 32, seed=11, steps_per_next=spn)
    b = DeviceDataset(x, y, 32, seed=11, steps_per_next=spn)
    spe = a.steps_per_epoch
    step = 0
    for _ in range(2 * spe):                     # cross several epochs
        da, db = next(a), next(b)
        # Materialize BEFORE prefetch(): the ring-row update donates the
        # old perm buffer (by design — the real consumer is the already-
        # enqueued step, stream-ordered before the overwrite), so the
        # yielded pytree's host handle dies once prefetch dispatches.
        pa, pb = np.asarray(da["perm"]), np.asarray(db["perm"])
        b.prefetch()
        for epoch in range(step // spe, (step + spn - 1) // spe + 1):
            s = epoch % a.num_slots
            np.testing.assert_array_equal(pa[s], pb[s], err_msg=(
                f"step {step} epoch {epoch} slot {s}"))
        step += spn


def test_train_loop_calls_prefetch_hook():
    """TrainLoop drives batches.prefetch() right after each dispatch —
    the overlap only happens if the loop actually calls it."""
    from distributedtensorflowexample_tpu.training.loop import TrainLoop

    calls = []

    class Batches:
        def __next__(self):
            return {"n": len(calls)}

        def prefetch(self):
            calls.append(1)

    class State:
        step = 0

    loop = TrainLoop(lambda s, b: (s, {"loss": jnp.float32(0.0)}),
                     Batches(), num_steps=3)
    loop.run(State())
    assert len(calls) == 3


# ---- 5. host-fed attestation ---------------------------------------------

def test_host_fed_auto_path_has_no_256_gather():
    """Under auto the host-fed path may not silently regress to the table
    gather: the Batcher quantizes the split and carries the spec, the
    in-step dequant resolves through the SAME rule (dequant_host_batch),
    and the jitted host-fed step contains no 256-gather."""
    from distributedtensorflowexample_tpu.data.pipeline import Batcher

    x, y = _data(64)
    batcher = Batcher(np.asarray(x), np.asarray(y), 32, quantize="auto")
    assert batcher.dequant is not None
    assert resolve_dequant_impl(batcher.dequant, "auto", "auto") == "affine"
    step = make_train_step(dequant=batcher.dequant)       # auto default
    state = TrainState.create(build_model("softmax"), optax.sgd(0.1),
                              np.zeros((32, 28, 28, 1), np.float32))
    batch = next(iter(batcher))
    assert batch["image"].dtype == np.uint8               # quantized feed
    jaxpr = jax.make_jaxpr(lambda s, b: step(s, b))(
        state, {"image": jnp.asarray(batch["image"]),
                "label": jnp.asarray(batch["label"])})
    assert _gather_eqns(jaxpr) == [], (
        "host-fed auto path traces a 256-entry table gather")
