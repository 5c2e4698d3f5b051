"""Sync data parallelism on an 8-virtual-device mesh (SURVEY.md §4, §7 step 2).

These run the REAL pjit/NamedSharding/psum path on fake CPU devices —
the rebuild's replacement for the reference's localhost multi-process tests.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributedtensorflowexample_tpu.data.synthetic import make_synthetic
from distributedtensorflowexample_tpu.models import build_model
from distributedtensorflowexample_tpu.parallel import (
    batch_sharding, make_mesh, replicated_sharding)
from distributedtensorflowexample_tpu.parallel.sync import (
    evaluate, make_train_step)
from distributedtensorflowexample_tpu.training.state import TrainState
import optax


def _make_state(model_name, sample_shape, mesh, lr=0.1, seed=0):
    model = build_model(model_name)
    tx = optax.sgd(lr)
    return TrainState.create_sharded(model, tx, sample_shape, seed,
                                     replicated_sharding(mesh))


def _batch(mesh, n=64, shape=(28, 28, 1), seed=0):
    x, y = make_synthetic(n, shape, 10, seed=seed)
    return jax.device_put({"image": x, "label": y}, batch_sharding(mesh))


def test_virtual_device_mesh():
    mesh = make_mesh()
    assert mesh.size == jax.device_count()
    assert jax.device_count() == 8   # tests/conftest.py


def test_train_step_runs_sharded():
    mesh = make_mesh()
    state = _make_state("softmax", (64, 28, 28, 1), mesh)
    batch = _batch(mesh)
    step = make_train_step()
    state, metrics = step(state, batch)
    assert int(state.step) == 1
    assert np.isfinite(float(metrics["loss"]))
    # Params stay fully replicated after the step.
    leaf = jax.tree.leaves(state.params)[0]
    assert leaf.sharding.is_fully_replicated


def test_batch_is_actually_sharded():
    mesh = make_mesh()
    batch = _batch(mesh)
    assert len(batch["image"].sharding.device_set) == mesh.size
    assert (batch["image"].addressable_shards[0].data.shape[0]
            == 64 // mesh.size)


def test_loss_decreases_under_dp():
    mesh = make_mesh()
    state = _make_state("softmax", (64, 28, 28, 1), mesh, lr=0.5)
    step = make_train_step()
    x, y = make_synthetic(64 * 30, (28, 28, 1), 10, seed=0)
    losses = []
    for i in range(30):
        sl = slice(i * 64, (i + 1) * 64)
        batch = jax.device_put({"image": x[sl], "label": y[sl]},
                               batch_sharding(mesh))
        state, metrics = step(state, batch)
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0] * 0.7


def test_one_vs_eight_device_equivalence():
    """Same global batch ⇒ numerically identical update on 1 and all
    visible devices: the determinism guarantee the reference's sync mode
    only approximated."""
    step = make_train_step()
    results = []
    for ndev in (1, jax.device_count()):
        mesh = make_mesh(ndev)
        state = _make_state("softmax", (64, 28, 28, 1), mesh, lr=0.5, seed=7)
        for i in range(3):
            x, y = make_synthetic(64, (28, 28, 1), 10, seed=100 + i)
            batch = jax.device_put({"image": x, "label": y},
                                   batch_sharding(mesh))
            state, _ = step(state, batch)
        results.append(jax.device_get(state.params))
    flat1 = jax.tree.leaves(results[0])
    flat8 = jax.tree.leaves(results[1])
    for a, b in zip(flat1, flat8):
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-6)


def test_cnn_with_dropout_under_dp():
    mesh = make_mesh()
    state = _make_state("mnist_cnn", (32, 28, 28, 1), mesh, lr=0.05)
    step = make_train_step()
    batch = _batch(mesh, n=32)
    state, metrics = step(state, batch)
    assert np.isfinite(float(metrics["loss"]))


def test_resnet_bn_under_dp():
    mesh = make_mesh()
    state = _make_state("resnet20", (16, 32, 32, 3), mesh, lr=0.05)
    step = make_train_step()
    batch = _batch(mesh, n=16, shape=(32, 32, 3))
    old_stats = jax.device_get(state.batch_stats)
    state, metrics = step(state, batch)
    assert np.isfinite(float(metrics["loss"]))
    new_stats = jax.device_get(state.batch_stats)
    # BN running stats must actually update.
    diffs = jax.tree.map(lambda a, b: float(np.abs(a - b).max()),
                         old_stats, new_stats)
    assert max(jax.tree.leaves(diffs)) > 0


def test_evaluate_exact():
    mesh = make_mesh()
    state = _make_state("softmax", (64, 28, 28, 1), mesh)
    x, y = make_synthetic(2048, (28, 28, 1), 10, seed=1)
    acc = evaluate(state, x, y, batch_size=512, sharding=batch_sharding(mesh))
    assert 0.0 <= acc <= 1.0


def test_resident_eval_matches_host_eval():
    """make_resident_eval (one dispatch, split in HBM) computes the exact
    same accuracy as the host-fed evaluate, including the padded tail."""
    from distributedtensorflowexample_tpu.parallel.sync import (
        make_resident_eval)

    mesh = make_mesh()
    state = _make_state("softmax", (64, 28, 28, 1), mesh)
    x, y = make_synthetic(1100, (28, 28, 1), 10, seed=2)   # non-multiple tail
    want = evaluate(state, x, y, batch_size=512,
                    sharding=batch_sharding(mesh))
    got = make_resident_eval(x, y, batch_size=512, mesh=mesh)(state)
    assert got == pytest.approx(want, abs=1e-9)


def test_resident_eval_batch_must_divide_mesh():
    from distributedtensorflowexample_tpu.parallel.sync import (
        make_resident_eval)

    x, y = make_synthetic(100, (28, 28, 1), 10, seed=2)
    with pytest.raises(ValueError, match="divide"):
        make_resident_eval(x, y, batch_size=50, mesh=make_mesh())


def test_resident_eval_quantize_off_skips_lut_path(monkeypatch):
    """--quantize off reaches eval too (ADVICE r4): the split stays
    float32-resident and _try_quantize is never consulted, while the
    accuracy is identical to the quantized path (which is bitwise by
    construction)."""
    import distributedtensorflowexample_tpu.data.device_dataset as dd
    from distributedtensorflowexample_tpu.parallel.sync import (
        make_resident_eval)

    mesh = make_mesh()
    state = _make_state("softmax", (64, 28, 28, 1), mesh)
    x, y = make_synthetic(1024, (28, 28, 1), 10, seed=3)
    want = make_resident_eval(x, y, batch_size=512, mesh=mesh)(state)

    def boom(*a, **k):
        raise AssertionError("_try_quantize consulted under quantize='off'")
    monkeypatch.setattr(dd, "_try_quantize", boom)
    got = make_resident_eval(x, y, batch_size=512, mesh=mesh,
                             quantize="off")(state)
    assert got == pytest.approx(want, abs=1e-9)
    with pytest.raises(ValueError, match="quantize"):
        make_resident_eval(x, y, batch_size=512, mesh=mesh, quantize="no")


def test_partial_aggregation_uses_rotating_subset():
    """replicas_to_aggregate=R: the update at step s is driven by exactly
    the R replicas with ((i - s) mod N) < R — verified by comparing against
    a manual step on just those replicas' shards."""
    from distributedtensorflowexample_tpu.ops.losses import (
        softmax_cross_entropy)

    mesh = make_mesh()
    N, R, b = 8, 3, 64
    per = b // N
    step = make_train_step(num_replicas=N, replicas_to_aggregate=R)
    x, y = make_synthetic(b, (28, 28, 1), 10, seed=4)

    for s in (0, 1, 5):
        state = _make_state("softmax", (b, 28, 28, 1), mesh, lr=0.5, seed=1)
        state = state.replace(step=jnp.asarray(s, jnp.int32))
        batch = jax.device_put({"image": x, "label": y}, batch_sharding(mesh))
        new_state, _ = step(state, batch)

        # Manual reference: grad of the mean loss over the selected rows.
        sel = [i for i in range(N) if (i - s) % N < R]
        rows = np.concatenate([np.arange(i * per, (i + 1) * per) for i in sel])
        ref = _make_state("softmax", (b, 28, 28, 1), mesh, lr=0.5, seed=1)

        def loss_fn(params):
            logits = ref.apply_fn({"params": params},
                                  jnp.asarray(x[rows]), train=True,
                                  rngs={"dropout": jax.random.fold_in(
                                      ref.rng, s)})
            return softmax_cross_entropy(logits, jnp.asarray(y[rows]))

        grads = jax.grad(loss_fn)(ref.params)
        want = jax.tree.map(lambda p, g: p - 0.5 * g, ref.params, grads)
        jax.tree.map(lambda a, c: np.testing.assert_allclose(a, c, rtol=1e-5,
                                                             atol=1e-6),
                     new_state.params, want)


def test_partial_aggregation_full_r_matches_plain():
    mesh = make_mesh()
    x, y = make_synthetic(64, (28, 28, 1), 10, seed=5)
    batch = lambda: jax.device_put({"image": x, "label": y},
                                   batch_sharding(mesh))
    s1 = _make_state("softmax", (64, 28, 28, 1), mesh, lr=0.5, seed=2)
    s2 = _make_state("softmax", (64, 28, 28, 1), mesh, lr=0.5, seed=2)
    s1, _ = make_train_step()(s1, batch())
    s2, _ = make_train_step(num_replicas=8, replicas_to_aggregate=8)(
        s2, batch())
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(a, b),
                 s1.params, s2.params)


def test_partial_aggregation_validation():
    with pytest.raises(ValueError, match="replicas_to_aggregate"):
        make_train_step(num_replicas=4, replicas_to_aggregate=5)
