"""Pallas kernel parity tests (interpret mode on CPU — SURVEY.md §4).

Each kernel is checked value- and gradient-exact against the pure-jnp
reference implementation it replaces.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from distributedtensorflowexample_tpu.ops.losses import softmax_cross_entropy
from distributedtensorflowexample_tpu.ops.pallas import (
    fused_sgd_apply, fused_softmax_cross_entropy_rows)


def _ref_rows(logits, labels, smoothing=0.0):
    num_classes = logits.shape[-1]
    onehot = jax.nn.one_hot(labels, num_classes, dtype=logits.dtype)
    if smoothing > 0.0:
        onehot = onehot * (1.0 - smoothing) + smoothing / num_classes
    return -jnp.sum(onehot * jax.nn.log_softmax(logits, axis=-1), axis=-1)


@pytest.mark.parametrize("batch,classes", [(32, 10), (64, 100), (24, 10)])
@pytest.mark.parametrize("smoothing", [0.0, 0.1])
def test_ce_rows_match_reference(batch, classes, smoothing):
    rng = np.random.RandomState(0)
    logits = jnp.asarray(rng.randn(batch, classes).astype(np.float32)) * 5
    labels = jnp.asarray(rng.randint(0, classes, size=batch, dtype=np.int32))
    got = fused_softmax_cross_entropy_rows(logits, labels, smoothing)
    want = _ref_rows(logits, labels, smoothing)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("smoothing", [0.0, 0.1])
def test_ce_gradient_matches_reference(smoothing):
    rng = np.random.RandomState(1)
    logits = jnp.asarray(rng.randn(32, 10).astype(np.float32))
    labels = jnp.asarray(rng.randint(0, 10, size=32, dtype=np.int32))

    def fused(l):
        return jnp.mean(fused_softmax_cross_entropy_rows(l, labels, smoothing))

    def ref(l):
        return softmax_cross_entropy(l, labels, smoothing)

    v1, g1 = jax.value_and_grad(fused)(logits)
    v2, g2 = jax.value_and_grad(ref)(logits)
    np.testing.assert_allclose(v1, v2, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(g1, g2, rtol=1e-5, atol=1e-6)


def test_ce_jit_and_weighted_vjp():
    # Non-uniform cotangent exercises the per-row backward scaling.
    rng = np.random.RandomState(2)
    logits = jnp.asarray(rng.randn(16, 10).astype(np.float32))
    labels = jnp.asarray(rng.randint(0, 10, size=16, dtype=np.int32))
    w = jnp.linspace(0.1, 2.0, 16)

    @jax.jit
    def fused(l):
        return jnp.sum(w * fused_softmax_cross_entropy_rows(l, labels))

    def ref(l):
        return jnp.sum(w * _ref_rows(l, labels))

    np.testing.assert_allclose(jax.grad(fused)(logits), jax.grad(ref)(logits),
                               rtol=1e-5, atol=1e-6)


def _tree():
    rng = np.random.RandomState(3)
    mk = lambda *s: jnp.asarray(rng.randn(*s).astype(np.float32))
    return {"conv": {"kernel": mk(5, 5, 1, 32), "bias": mk(32)},
            "dense": {"kernel": mk(300, 7), "bias": mk(7)}}


@pytest.mark.parametrize("mu", [0.0, 0.9])
def test_fused_sgd_matches_optax(mu):
    params, grads, mom = _tree(), _tree(), jax.tree.map(jnp.zeros_like, _tree())
    mom = jax.tree.map(lambda x: x * 0.5, _tree())
    lr = 0.13
    p_new, m_new = fused_sgd_apply(params, mom, grads, lr, mu)

    # optax.sgd(momentum=mu): m_t = mu*m + g ; update = -lr*m_t
    want_m = jax.tree.map(lambda m, g: mu * m + g, mom, grads)
    want_p = jax.tree.map(lambda p, m: p - lr * m, params, want_m)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-6),
                 p_new, want_p)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-6),
                 m_new, want_m)


def test_fused_sgd_traced_lr_under_jit():
    params, grads = _tree(), _tree()
    mom = jax.tree.map(jnp.zeros_like, params)
    sched = optax.cosine_decay_schedule(0.1, 100)

    @jax.jit
    def step(params, mom, grads, count):
        return fused_sgd_apply(params, mom, grads, sched(count), 0.9)

    p_new, m_new = step(params, mom, grads, jnp.asarray(7))
    lr = float(sched(7))
    want_p = jax.tree.map(lambda p, g: p - lr * g, params, grads)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-5),
                 p_new, want_p)


def test_pallas_step_matches_xla_step_on_mesh():
    """Full sync-DP train step with both Pallas paths on the 8-device mesh
    matches the XLA step numerically (same batch, same init)."""
    from distributedtensorflowexample_tpu.data.synthetic import make_synthetic
    from distributedtensorflowexample_tpu.models import build_model
    from distributedtensorflowexample_tpu.ops.pallas import fused_momentum_sgd
    from distributedtensorflowexample_tpu.parallel import (
        batch_sharding, make_mesh, replicated_sharding)
    from distributedtensorflowexample_tpu.parallel.sync import make_train_step
    from distributedtensorflowexample_tpu.training.state import TrainState

    mesh = make_mesh()
    x, y = make_synthetic(64, (28, 28, 1), 10, seed=0)
    batch = jax.device_put({"image": x, "label": y}, batch_sharding(mesh))
    model = build_model("softmax")

    def run(tx, **step_kw):
        state = TrainState.create_sharded(model, tx, (64, 28, 28, 1), 0,
                                          replicated_sharding(mesh))
        with mesh:
            state, metrics = make_train_step(**step_kw)(state, batch)
        return state, metrics

    s_ref, m_ref = run(optax.sgd(0.1, momentum=0.9))
    s_pal, m_pal = run(fused_momentum_sgd(0.1, momentum=0.9, mesh=mesh),
                       ce_impl="pallas", mesh=mesh)
    np.testing.assert_allclose(float(m_ref["loss"]), float(m_pal["loss"]),
                               rtol=1e-5)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-4,
                                                         atol=1e-6),
                 s_ref.params, s_pal.params)


def test_fused_sgd_is_one_kernel_launch():
    """The whole parameter set updates in ONE pallas_call (round 1 launched
    one per leaf — ~65 for ResNet-20), with the momentum trace stored as a
    single flat (rows, 128) buffer."""
    from distributedtensorflowexample_tpu.ops.pallas import fused_momentum_sgd

    tx = fused_momentum_sgd(0.1, momentum=0.9)
    params = _tree()
    state = tx.init(params)
    assert state.trace.ndim == 2 and state.trace.shape[1] == 128

    jaxpr = jax.make_jaxpr(
        lambda g, s, p: tx.update(g, s, p))(_tree(), state, params)
    assert str(jaxpr).count("pallas_call") == 1

    # Zero-momentum first step == plain SGD update.
    grads = _tree()
    updates, state2 = tx.update(grads, state, params)
    jax.tree.map(lambda u, g: np.testing.assert_allclose(u, -0.1 * g,
                                                         rtol=1e-6,
                                                         atol=1e-7),
                 updates, grads)
    assert int(state2.count) == 1


def test_fused_optimizer_flag_rejects_incompatible_config():
    from distributedtensorflowexample_tpu.config import RunConfig
    from distributedtensorflowexample_tpu.training.optimizers import (
        build_optimizer)

    with pytest.raises(ValueError, match="momentum"):
        build_optimizer(RunConfig(fused_optimizer=True, momentum=0.0))
    with pytest.raises(ValueError, match="weight_decay"):
        build_optimizer(RunConfig(fused_optimizer=True, momentum=0.9,
                                  weight_decay=1e-4))


def test_fused_optimizer_rejected_in_async_mode(tmp_path):
    """The Pallas CE head works under async (tests/test_async.py); the
    fused optimizer apply cannot (pallas under the worker vmap) and must
    fail fast with a clear error."""
    from distributedtensorflowexample_tpu.config import RunConfig
    from distributedtensorflowexample_tpu.trainers.common import run_training

    cfg = RunConfig(sync_mode="async", fused_optimizer=True, momentum=0.9,
                    train_steps=1, batch_size=64, global_batch=True,
                    dataset="synthetic", data_dir=str(tmp_path),
                    log_dir=str(tmp_path / "logs"), resume=False)
    with pytest.raises(ValueError, match="fused_optimizer"):
        run_training(cfg, "softmax", "mnist")


# ---- blocked causal attention (ops/pallas/attention.py) -----------------

from distributedtensorflowexample_tpu.ops import attention as attention_op
from distributedtensorflowexample_tpu.ops.attention import (
    causal_attention, einsum_causal_attention)
from distributedtensorflowexample_tpu.ops.pallas.attention import (
    blocked_causal_attention)


def _qkvw(heads=2, head_dim=64, batch=2, seq=256, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    return [jax.random.normal(k, (batch, seq, heads, head_dim), jnp.float32)
            for k in keys]


def _weighted(att, w):
    return lambda q, k, v: jnp.sum(att(q, k, v) * w)


@pytest.mark.parametrize("heads,head_dim", [(2, 64), (4, 64), (1, 128)])
@pytest.mark.parametrize("block", [128, 256])
def test_attention_forward_matches_einsum_chain(block, heads, head_dim):
    q, k, v, _ = _qkvw(heads, head_dim)
    got = blocked_causal_attention(q, k, v, block=block)
    np.testing.assert_allclose(got, einsum_causal_attention(q, k, v),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("heads,head_dim", [(2, 64), (1, 128)])
@pytest.mark.parametrize("block", [128, 256])
def test_attention_gradients_match_einsum_chain(block, heads, head_dim):
    q, k, v, w = _qkvw(heads, head_dim, seed=1)
    att = lambda q, k, v: blocked_causal_attention(q, k, v, block=block)
    got = jax.grad(_weighted(att, w), (0, 1, 2))(q, k, v)
    want = jax.grad(_weighted(einsum_causal_attention, w), (0, 1, 2))(q, k, v)
    for g, r, name in zip(got, want, ("dq", "dk", "dv")):
        np.testing.assert_allclose(g, r, rtol=1e-4, atol=1e-5,
                                   err_msg=name)


def test_attention_first_row_has_every_later_key_masked():
    # Query 0 sees key 0 alone: its output is v[0] whatever the scores
    # are (huge ones, so a leak of a masked key would show), and keys
    # 1.. get no gradient from it.
    q, k, v, _ = _qkvw(seed=2)
    out = blocked_causal_attention(q * 30.0, k * 30.0, v, block=128)
    np.testing.assert_allclose(out[:, 0], v[:, 0], rtol=1e-6, atol=1e-6)
    assert np.isfinite(np.asarray(out)).all()
    dk = jax.grad(lambda k: jnp.sum(
        blocked_causal_attention(q, k, v, block=128)[:, 0]))(k)
    assert float(jnp.max(jnp.abs(dk[:, 1:]))) == 0.0


def test_attention_bf16_is_no_further_from_f32_than_the_einsum_chain():
    q, k, v, _ = _qkvw(seed=3)
    exact = einsum_causal_attention(q, k, v)
    low = [x.astype(jnp.bfloat16) for x in (q, k, v)]
    gap = lambda out: float(jnp.max(jnp.abs(out.astype(jnp.float32)
                                            - exact)))
    assert blocked_causal_attention(*low).dtype == jnp.bfloat16
    assert gap(blocked_causal_attention(*low)) <= 1.25 * gap(
        einsum_causal_attention(*low))


@pytest.mark.parametrize("wrap", ["jit", "remat", "vmap", "shard_map",
                                  "global_view"])
def test_attention_under_transforms(wrap, monkeypatch):
    from distributedtensorflowexample_tpu.parallel import (
        batch_sharding, make_mesh)
    from distributedtensorflowexample_tpu.parallel.sync import global_view
    mesh = make_mesh()
    batch = mesh.size if wrap in ("shard_map", "global_view") else 2
    q, k, v, w = _qkvw(batch=batch, seq=128, seed=4)
    att = blocked_causal_attention
    if wrap == "jit":
        run = jax.jit(jax.value_and_grad(_weighted(att, w), (0, 1, 2)))
    elif wrap == "remat":
        import flax.linen as nn

        class Block(nn.Module):
            @nn.compact
            def __call__(self, q, k, v):
                return att(q, k, v)

        block = nn.remat(Block)()
        run = jax.jit(jax.value_and_grad(_weighted(
            lambda q, k, v: block.apply({}, q, k, v), w), (0, 1, 2)))
    elif wrap == "vmap":
        # The async step vmaps workers over the model: pallas_call's
        # batching rule, forward and backward.
        stacked = jax.vmap(att)
        run = jax.jit(jax.value_and_grad(lambda q, k, v: jnp.sum(
            stacked(q[None], k[None], v[None])[0] * w), (0, 1, 2)))
    elif wrap == "shard_map":
        spec = jax.sharding.PartitionSpec("data")
        sharded = jax.shard_map(att, mesh=mesh, in_specs=(spec,) * 3,
                                out_specs=spec, check_vma=False)
        run = jax.jit(jax.value_and_grad(_weighted(sharded, w), (0, 1, 2)))
    else:
        # What parallel/sync.py's plain step does: global view over the
        # mesh, the kernel taken (forced here: the CPU never takes it),
        # so causal_attention must shard it over the batch axis itself.
        monkeypatch.setattr(attention_op, "takes_kernel", lambda s: True)
        q, k, v, w = jax.device_put((q, k, v, w), batch_sharding(mesh))

        def loss(q, k, v):
            with global_view(mesh):
                return _weighted(causal_attention, w)(q, k, v)

        run = jax.jit(jax.value_and_grad(loss, (0, 1, 2)))
        assert "shard_map" in str(jax.make_jaxpr(loss)(q, k, v))
    value, grads = run(q, k, v)
    want, want_grads = jax.value_and_grad(
        _weighted(einsum_causal_attention, w), (0, 1, 2))(q, k, v)
    np.testing.assert_allclose(value, want, rtol=1e-5)
    for g, r in zip(grads, want_grads):
        np.testing.assert_allclose(g, r, rtol=1e-4, atol=1e-5)


def test_attention_refuses_shapes_that_do_not_tile():
    q, k, v, _ = _qkvw(heads=2, head_dim=32, seq=128)
    with pytest.raises(ValueError, match="does not tile"):
        blocked_causal_attention(q, k, v)
    q, k, v, _ = _qkvw(seq=96)
    with pytest.raises(ValueError, match="does not tile"):
        blocked_causal_attention(q, k, v)
