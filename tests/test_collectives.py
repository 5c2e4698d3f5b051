"""Collective accounting + bucketed gradient collectives (PR 6).

The HLO collective inventory (utils/profiling.collective_inventory) is
pinned on synthetic text, the golden per-trainer multisets generalize
test_device_data.py's collective-set assertion into pinned
measurements, and the ``--bucket_grads`` schedules
are parity-gated (bitwise where the program permits — softmax, both
modes — and the shard_update allclose standard for conv models, same
reason: summation order, not math).

Inline and tier-1-safe: single-digit fused dispatches per test, no full
training loops.
"""

import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from distributedtensorflowexample_tpu.data import DeviceDataset
from distributedtensorflowexample_tpu.data.synthetic import make_synthetic
from distributedtensorflowexample_tpu.models import build_model
from distributedtensorflowexample_tpu.parallel import (
    make_mesh, replicated_sharding)
from distributedtensorflowexample_tpu.parallel.bucketing import (
    DEFAULT_BUCKET_BYTES, bucket_padding_bytes, init_bucketed_opt_state,
    plan_buckets, resolve_bucket_bytes)
from distributedtensorflowexample_tpu.parallel.sync import (
    make_indexed_train_step)
from distributedtensorflowexample_tpu.training.state import TrainState
from distributedtensorflowexample_tpu.utils.profiling import (
    collective_inventory, collective_inventory_of)

pytestmark = pytest.mark.collectives

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _data(n=512, shape=(28, 28, 1)):
    return make_synthetic(n, shape, 10, seed=0)


def _state(model, tx, b=64, shape=(28, 28, 1)):
    return TrainState.create_sharded(model, tx, (b,) + shape, 0,
                                     replicated_sharding(make_mesh()))


# ---- the parser ---------------------------------------------------------

_HLO = """
ENTRY %main (p0: f32[8]) -> f32[8] {
  %p0 = f32[8]{0} parameter(0)
  %ar = f32[8]{0} all-reduce(f32[8]{0} %p0), replica_groups={{0,1,2,3,4,5,6,7}}, to_apply=%add
  %ars = f32[8]{0} all-reduce-start(f32[8]{0} %ar), replica_groups={{0,1},{2,3}}
  %ard = f32[8]{0} all-reduce-done(f32[8]{0} %ars)
  %w = f32[8]{0} while(f32[8]{0} %ard), condition=%cond, body=%body
  ROOT %t = f32[8]{0} add(f32[8]{0} %w, f32[8]{0} %ar)
}
%body (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  %rs = f32[1]{0} reduce-scatter(f32[8]{0} %p), replica_groups=[1,8]<=[8], dimensions={0}
  %ag = f32[8]{0} all-gather(f32[1]{0} %rs), dimensions={0}
  ROOT %r = f32[8]{0} add(f32[8]{0} %ag, f32[8]{0} %p)
}
%cond (p: f32[8]) -> pred[] {
  %p = f32[8]{0} parameter(0)
  ROOT %lt = pred[] constant(1)
}
"""


def test_collective_inventory_parsing():
    """Opcode normalization (-start counted once, -done skipped), operand
    vs output bytes, replica-group capture, and scan-body weighting."""
    inv = collective_inventory(_HLO, unroll=2)
    # entry: 2 all-reduces (plain + start/done pair), each weight 1 ->
    # 0.5/step at unroll 2; body: weight 2 -> 1/step.
    assert inv["multiset"] == {"all-reduce": 1, "all-gather": 1,
                               "reduce-scatter": 1}
    per = inv["per_step"]
    assert per["all-reduce"]["out_bytes"] == 32          # 2 x 32 B / 2
    assert per["reduce-scatter"] == {"count": 1, "out_bytes": 4,
                                     "accounting_bytes": 4 + 32}
    assert per["all-gather"] == {"count": 1, "out_bytes": 32,
                                 "accounting_bytes": 32 + 4}
    groups = {r["name"]: r["replica_groups"] for r in inv["ops"]}
    assert groups["ar"] == "{{0,1,2,3,4,5,6,7}}"
    assert groups["ars"] == "{{0,1},{2,3}}"
    assert groups["rs"] == "[1,8]<=[8]"
    assert not any(r["name"] == "ard" for r in inv["ops"])
    assert collective_inventory("")["multiset"] == {}


# ---- golden per-trainer multisets (the generalized collective-set
# assertion: sync / shard_update / async each pin their inventory) ------

def test_sync_softmax_golden_inventory():
    """The sync data-parallel softmax step: 2 per-parameter gradient
    all-reduces (kernel 31360 B + bias 40 B) + 2 scalar metric
    all-reduces — 31408 B/step on the wire, at any unroll (scan bodies
    weight by trip count, so per-step accounting is unroll-invariant)."""
    mesh = make_mesh()
    x, y = _data()
    state = _state(build_model("softmax"), optax.sgd(0.1, momentum=0.9))
    ds1 = DeviceDataset(x, y, 64, mesh=mesh, seed=0)
    ds4 = DeviceDataset(x, y, 64, mesh=mesh, seed=0, steps_per_next=4)
    with mesh:
        one = make_indexed_train_step(64, ds1.steps_per_epoch, mesh=mesh,
                                      num_slots=ds1.num_slots)
        inv1 = collective_inventory_of(one, (state, ds1.peek()))
        fused = make_indexed_train_step(64, ds4.steps_per_epoch, mesh=mesh,
                                        num_slots=ds4.num_slots,
                                        unroll_steps=4)
        inv4 = collective_inventory_of(fused, (state, ds4.peek()), unroll=4)
    assert inv1["multiset"] == {"all-reduce": 4}
    assert inv1["total_out_bytes_per_step"] == 31408
    assert inv4["multiset"] == inv1["multiset"]
    assert inv4["total_out_bytes_per_step"] == \
        inv1["total_out_bytes_per_step"]


def test_shard_update_golden_inventory():
    """The GSPMD-constraint form of --shard_update on THIS backend: the
    partitioner keeps plain all-reduces (no reduce-scatter/all-gather
    decomposition on XLA:CPU) — the measured fact that motivates the
    explicit bucketed ZeRO-1 schedule, which is the configuration that
    actually emits the paper's reduce-scatter + all-gather (pinned in
    test_bucketed_zero1_golden_inventory)."""
    from distributedtensorflowexample_tpu.training.optimizers import (
        cross_replica_update_sharding, update_shardings)
    mesh = make_mesh()
    x, y = _data()
    ds = DeviceDataset(x, y, 64, mesh=mesh, seed=0)
    tx = cross_replica_update_sharding(optax.sgd(0.1, momentum=0.9), mesh)
    state = _state(build_model("softmax"), tx)
    state = state.replace(opt_state=jax.device_put(
        state.opt_state, update_shardings(state.opt_state, mesh)))
    step = make_indexed_train_step(64, ds.steps_per_epoch, mesh=mesh,
                                   num_slots=ds.num_slots)
    with mesh:
        inv = collective_inventory_of(step, (state, ds.peek()))
    assert inv["multiset"] == {"all-reduce": 4}
    assert inv["total_out_bytes_per_step"] == 31408


def test_async_golden_inventory_and_bucketed_average():
    """The async local-SGD step: per-leaf worker-average all-reduces
    (cond-gated on the period — counted at module weight; sustained
    bytes divide by the period) + the fused scalar metrics psum pair.
    --bucket_grads fuses the per-leaf average psums into one bucket."""
    from distributedtensorflowexample_tpu.parallel.async_ps import (
        make_indexed_async_train_step, make_worker_state)
    mesh = make_mesh()
    x, y = _data()
    ds = DeviceDataset(x, y, 64, mesh=mesh, seed=0)
    state = _state(build_model("softmax"), optax.sgd(0.1))
    state = make_worker_state(state, mesh.size, mesh)
    with mesh:
        plain = make_indexed_async_train_step(
            mesh.size, 8, 64, ds.steps_per_epoch, mesh=mesh,
            num_slots=ds.num_slots)
        inv = collective_inventory_of(plain, (state, ds.peek()))
        bucketed = make_indexed_async_train_step(
            mesh.size, 8, 64, ds.steps_per_epoch, mesh=mesh,
            num_slots=ds.num_slots, bucket_bytes=1 << 20)
        inv_b = collective_inventory_of(bucketed, (state, ds.peek()))
    assert inv["multiset"] == {"all-reduce": 4}     # w, b, loss, acc
    assert inv["total_out_bytes_per_step"] == 31408
    assert inv_b["multiset"] == {"all-reduce": 3}   # bucket, loss, acc
    assert inv_b["total_out_bytes_per_step"] == 31408


def test_async_bucketed_average_bitwise():
    """Bucketing the worker average is bitwise: same cross-device
    additions, regrouped into one psum."""
    from distributedtensorflowexample_tpu.parallel.async_ps import (
        make_indexed_async_train_step, make_worker_state)
    mesh = make_mesh()
    x, y = _data()
    mk = lambda: DeviceDataset(x, y, 64, mesh=mesh, seed=2,
                               steps_per_next=4)
    mk_state = lambda: make_worker_state(
        _state(build_model("softmax"), optax.sgd(0.1)), mesh.size, mesh)
    outs = []
    with mesh:
        for bb in (None, 1 << 20):
            ds = mk()
            state = mk_state()
            step = make_indexed_async_train_step(
                mesh.size, 4, 64, ds.steps_per_epoch, mesh=mesh,
                unroll_steps=4, num_slots=ds.num_slots, bucket_bytes=bb)
            state, m = step(state, next(ds))    # crosses the period
            outs.append((jax.tree.leaves(state.params),
                         float(m["loss"])))
    (p0, l0), (p1, l1) = outs
    assert l0 == l1
    for a, c in zip(p0, p1):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(c))


# ---- the bucketed schedules -------------------------------------------

def test_bucketed_golden_inventory_and_bitwise_parity():
    """--bucket_grads on softmax: strictly fewer all-reduce ops (4 -> 3:
    one gradient bucket + the metrics pair), unchanged total collective
    bytes, and BITWISE-identical params/loss/metrics vs the GSPMD
    default (batch_stats empty-by-construction on softmax, so the full
    remat-style parity triple holds bitwise)."""
    mesh = make_mesh()
    x, y = _data()
    mk = lambda: DeviceDataset(x, y, 64, mesh=mesh, seed=4)
    mk_state = lambda: _state(build_model("softmax"),
                              optax.sgd(0.1, momentum=0.9))
    ds = mk()
    ref = make_indexed_train_step(64, ds.steps_per_epoch, mesh=mesh,
                                  num_slots=ds.num_slots)
    bkt = make_indexed_train_step(64, ds.steps_per_epoch, mesh=mesh,
                                  num_slots=ds.num_slots,
                                  bucket_bytes=DEFAULT_BUCKET_BYTES)
    s_ref, s_bkt = mk_state(), mk_state()
    with mesh:
        inv_ref = collective_inventory_of(ref, (s_ref, ds.peek()))
        inv_bkt = collective_inventory_of(bkt, (s_bkt, ds.peek()))
        ds_r, ds_b = mk(), mk()
        for _ in range(3):
            s_ref, m_ref = ref(s_ref, next(ds_r))
            s_bkt, m_bkt = bkt(s_bkt, next(ds_b))
    assert inv_bkt["multiset"] == {"all-reduce": 3}
    assert inv_bkt["per_step"]["all-reduce"]["count"] < \
        inv_ref["per_step"]["all-reduce"]["count"]
    assert inv_bkt["total_out_bytes_per_step"] == \
        inv_ref["total_out_bytes_per_step"]
    assert float(m_ref["loss"]) == float(m_bkt["loss"])
    assert float(m_ref["accuracy"]) == float(m_bkt["accuracy"])
    assert s_bkt.batch_stats == s_ref.batch_stats
    jax.tree.map(lambda a, c: np.testing.assert_array_equal(a, c),
                 s_ref.params, s_bkt.params)


def test_bucketed_zero1_golden_inventory_and_bitwise_parity():
    """--bucket_grads + --shard_update: the explicit ZeRO-1 bucket
    schedule — per bucket ONE reduce-scatter (grad shard in), ONE
    all-gather (updated params out) — the first configuration whose
    compiled HLO actually carries arXiv:2004.13336's collective pair on
    this backend (the constraint form keeps plain all-reduces, pinned
    above).  Reduction bytes are conserved up to the reported row
    padding; softmax parity is bitwise including the metrics."""
    mesh = make_mesh()
    D = mesh.size
    x, y = _data()
    mk = lambda: DeviceDataset(x, y, 64, mesh=mesh, seed=4)
    mk_tx = lambda: optax.sgd(0.1, momentum=0.9)
    ds = mk()
    ref = make_indexed_train_step(64, ds.steps_per_epoch, mesh=mesh,
                                  num_slots=ds.num_slots)
    z1 = make_indexed_train_step(64, ds.steps_per_epoch, mesh=mesh,
                                 num_slots=ds.num_slots,
                                 bucket_bytes=DEFAULT_BUCKET_BYTES,
                                 bucket_shard_update=True)
    s_ref = _state(build_model("softmax"), mk_tx())
    s_z = _state(build_model("softmax"), mk_tx())
    s_z = s_z.replace(opt_state=init_bucketed_opt_state(
        mk_tx(), s_z.params, DEFAULT_BUCKET_BYTES, mesh))
    # ZeRO-1 state residency: every non-scalar optimizer leaf is a
    # bucket row — 1/D of the padded params per device, by construction.
    pleaves = jax.tree.leaves(s_ref.params)
    padded = sum(l.size for l in pleaves) * 4 + bucket_padding_bytes(
        pleaves, D)
    rows = [l for l in jax.tree.leaves(s_z.opt_state)
            if getattr(l, "ndim", 0)]
    assert sum(r.size for r in rows) * 4 == padded
    assert all(not r.sharding.is_fully_replicated for r in rows)
    with mesh:
        inv = collective_inventory_of(z1, (s_z, ds.peek()))
        ds_r, ds_z = mk(), mk()
        for _ in range(3):
            s_ref, m_ref = ref(s_ref, next(ds_r))
            s_z, m_z = z1(s_z, next(ds_z))
    assert inv["multiset"] == {"all-gather": 1, "all-reduce": 2,
                               "reduce-scatter": 1}
    per = inv["per_step"]
    assert per["reduce-scatter"]["out_bytes"] == padded // D
    assert per["all-gather"]["out_bytes"] == padded
    assert per["all-reduce"]["out_bytes"] == 8          # the metrics pair
    assert float(m_ref["loss"]) == float(m_z["loss"])
    jax.tree.map(lambda a, c: np.testing.assert_array_equal(a, c),
                 s_ref.params, s_z.params)


def test_zero3_golden_inventory_prefetch_order_and_bitwise_parity():
    """--shard_params on softmax (PR 12): the ZeRO-3 schedule — the
    whole tree fits ONE knee-sized bucket, so per step ONE param
    all-gather in the FORWARD (prefetch: it textually precedes the
    reduce-scatter in the compiled module, where ZeRO-1's
    update-closing AG follows its RS), ONE reduce-scatter placed by the
    gather's transpose in the backward, the fused metrics pair — and NO
    step-closing all-gather (the updated 1/D row writes straight back).
    Reduction bytes conserved up to the reported row padding; parity vs
    the GSPMD default is BITWISE including metrics (the ZeRO-1
    standard), and both params and opt state live as 1/D rows."""
    from distributedtensorflowexample_tpu.parallel.zero3 import Zero3Layout
    mesh = make_mesh()
    D = mesh.size
    x, y = _data()
    mk = lambda: DeviceDataset(x, y, 64, mesh=mesh, seed=4)
    mk_tx = lambda: optax.sgd(0.1, momentum=0.9)
    ds = mk()
    ref = make_indexed_train_step(64, ds.steps_per_epoch, mesh=mesh,
                                  num_slots=ds.num_slots)
    s_ref = _state(build_model("softmax"), mk_tx())
    s_z = _state(build_model("softmax"), mk_tx())
    pleaves = jax.tree.leaves(s_ref.params)
    padded = sum(l.size for l in pleaves) * 4 + bucket_padding_bytes(
        pleaves, D)
    layout = Zero3Layout(s_z.params, DEFAULT_BUCKET_BYTES, mesh)
    z3 = make_indexed_train_step(64, ds.steps_per_epoch, mesh=mesh,
                                 num_slots=ds.num_slots,
                                 zero3_layout=layout)
    s_z = s_z.replace(opt_state=init_bucketed_opt_state(
        mk_tx(), s_z.params, DEFAULT_BUCKET_BYTES, mesh))
    s_z = s_z.replace(params=layout.init_rows(s_z.params))
    # ZeRO-3 residency: params AND opt moments are 1/D rows.
    for leaf in list(s_z.params) + [l for l in jax.tree.leaves(
            s_z.opt_state) if getattr(l, "ndim", 0)]:
        assert not leaf.sharding.is_fully_replicated
    assert sum(r.size for r in s_z.params) * 4 == padded
    with mesh:
        compiled = z3.lower(s_z, ds.peek()).compile()
        inv = collective_inventory(compiled.as_text())
        ds_r, ds_z = mk(), mk()
        for _ in range(3):
            s_ref, m_ref = ref(s_ref, next(ds_r))
            s_z, m_z = z3(s_z, next(ds_z))
    assert inv["multiset"] == {"all-gather": 1, "all-reduce": 2,
                               "reduce-scatter": 1}
    per = inv["per_step"]
    assert per["all-gather"]["out_bytes"] == padded
    assert per["reduce-scatter"]["out_bytes"] == padded // D
    assert per["all-reduce"]["out_bytes"] == 8          # the metrics pair
    # The AG-prefetch pin: HLO prints computations in topological order,
    # and the zero3 module's param gather precedes the backward's RS —
    # ZeRO-1's module (pinned above) has the opposite order (its AG
    # closes the update).
    hlo = compiled.as_text()
    assert hlo.index("all-gather") < hlo.index("reduce-scatter")
    assert float(m_ref["loss"]) == float(m_z["loss"])
    assert float(m_ref["accuracy"]) == float(m_z["accuracy"])
    full = layout.materialize(s_z.params)
    jax.tree.map(lambda a, c: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(c)), s_ref.params, full)


def test_zero3_lm_tiny_multi_bucket_golden_inventory():
    """The per-bucket schedule at lm_tiny: a sub-knee bucket cap splits
    the tree into several buckets — the compiled module carries exactly
    one AG + one RS PER BUCKET (the prefetch ladder), metrics on the
    fused pair, gradient reduction bytes
    conserved up to the row padding."""
    from distributedtensorflowexample_tpu.data.lm import load_lm
    from distributedtensorflowexample_tpu.parallel.zero3 import Zero3Layout
    mesh = make_mesh()
    D = mesh.size
    x, y = load_lm("", "train", num=128, seq_len=16, seed=0)
    mk_tx = lambda: optax.sgd(0.1, momentum=0.9)
    ds = DeviceDataset(x, y, 32, mesh=mesh, seed=0, token_data=True)
    state = TrainState.create_sharded(
        build_model("lm_tiny"), mk_tx(), (32, 16), 0,
        replicated_sharding(mesh))
    bb = 64 << 10
    layout = Zero3Layout(state.params, bb, mesh)
    assert layout.num_buckets >= 3       # a real multi-bucket ladder
    z3 = make_indexed_train_step(32, ds.steps_per_epoch, mesh=mesh,
                                 num_slots=ds.num_slots,
                                 zero3_layout=layout)
    s_z = state.replace(opt_state=init_bucketed_opt_state(
        mk_tx(), state.params, bb, mesh))
    s_z = s_z.replace(params=layout.init_rows(s_z.params))
    with mesh:
        inv = collective_inventory_of(z3, (s_z, ds.peek()))
    n = layout.num_buckets
    assert inv["multiset"] == {"all-gather": n, "all-reduce": 2,
                               "reduce-scatter": n}
    pleaves = layout.leaf_specs
    padded = sum(l.size * l.dtype.itemsize for l in pleaves) \
        + bucket_padding_bytes(pleaves, D)
    per = inv["per_step"]
    assert per["all-gather"]["out_bytes"] == padded
    assert per["reduce-scatter"]["out_bytes"] == padded // D


@pytest.mark.lm
def test_lm_golden_inventory():
    """The transformer-LM trainer's golden multisets (the third trainer
    family): 30 param leaves -> 30 per-parameter gradient all-reduces +
    the 2 metric scalars on the GSPMD default; ONE knee-sized bucket +
    the fused metrics pair under --bucket_grads (the whole lm_tiny tree
    fits one bucket); the explicit per-bucket RS+AG pair + metrics under
    the composed ZeRO-1 schedule.  BN-free by construction, so unlike
    resnet20 every schedule is legal for this model."""
    from distributedtensorflowexample_tpu.data.lm import load_lm
    from distributedtensorflowexample_tpu.parallel.bucketing import (
        init_bucketed_opt_state)
    mesh = make_mesh()
    x, y = load_lm("", "train", num=128, seq_len=16, seed=0)
    mk_tx = lambda: optax.sgd(0.1, momentum=0.9)
    ds = DeviceDataset(x, y, 32, mesh=mesh, seed=0, token_data=True)
    state = TrainState.create_sharded(
        build_model("lm_tiny"), mk_tx(), (32, 16), 0,
        replicated_sharding(mesh))
    plain = make_indexed_train_step(32, ds.steps_per_epoch, mesh=mesh,
                                    num_slots=ds.num_slots)
    bkt = make_indexed_train_step(32, ds.steps_per_epoch, mesh=mesh,
                                  num_slots=ds.num_slots,
                                  bucket_bytes=DEFAULT_BUCKET_BYTES)
    z1 = make_indexed_train_step(32, ds.steps_per_epoch, mesh=mesh,
                                 num_slots=ds.num_slots,
                                 bucket_bytes=DEFAULT_BUCKET_BYTES,
                                 bucket_shard_update=True)
    s_z = state.replace(opt_state=init_bucketed_opt_state(
        mk_tx(), state.params, DEFAULT_BUCKET_BYTES, mesh))
    with mesh:
        inv_p = collective_inventory_of(plain, (state, ds.peek()))
        inv_b = collective_inventory_of(bkt, (state, ds.peek()))
        inv_z = collective_inventory_of(z1, (s_z, ds.peek()))
    assert inv_p["multiset"] == {"all-reduce": 32}      # 30 grads + 2
    assert inv_b["multiset"] == {"all-reduce": 3}       # 1 bucket + 2
    assert inv_z["multiset"] == {"all-gather": 1, "all-reduce": 2,
                                 "reduce-scatter": 1}
    # Gradient bytes conserved by bucketing (metrics pair rides along).
    assert inv_b["total_out_bytes_per_step"] >= \
        inv_p["total_out_bytes_per_step"] - 16


def test_bucket_size_invariance_and_fewer_ops_on_cnn():
    """mnist_cnn (8 grad leaves -> 8 per-parameter all-reduces + 2
    metric scalars on the default path): bucketing is bitwise ACROSS
    bucket sizes (the knob's own invariance — same additions,
    regrouped), strictly fewer all-reduces at unchanged total bytes,
    and matches the GSPMD default to the shard_update allclose standard
    (the shard_map backward fuses differently on this backend; the
    deviation is reduction order, not math)."""
    mesh = make_mesh()
    x, y = _data()
    mk = lambda: DeviceDataset(x, y, 64, mesh=mesh, seed=7)
    model = build_model("mnist_cnn", dropout=0.0)
    mk_state = lambda: _state(model, optax.sgd(0.1, momentum=0.9))
    ds = mk()
    ref = make_indexed_train_step(64, ds.steps_per_epoch, mesh=mesh,
                                  num_slots=ds.num_slots)
    big = make_indexed_train_step(64, ds.steps_per_epoch, mesh=mesh,
                                  num_slots=ds.num_slots,
                                  bucket_bytes=16 << 20)
    small = make_indexed_train_step(64, ds.steps_per_epoch, mesh=mesh,
                                    num_slots=ds.num_slots,
                                    bucket_bytes=64 << 10)
    s_ref, s_big, s_small = mk_state(), mk_state(), mk_state()
    with mesh:
        inv_ref = collective_inventory_of(ref, (s_ref, ds.peek()))
        inv_big = collective_inventory_of(big, (s_big, ds.peek()))
        ds_r, ds_b, ds_s = mk(), mk(), mk()
        for _ in range(2):
            s_ref, _ = ref(s_ref, next(ds_r))
            s_big, _ = big(s_big, next(ds_b))
            s_small, _ = small(s_small, next(ds_s))
    assert inv_ref["multiset"] == {"all-reduce": 10}
    assert inv_big["multiset"] == {"all-reduce": 3}
    assert inv_big["total_out_bytes_per_step"] == \
        inv_ref["total_out_bytes_per_step"]
    jax.tree.map(lambda a, c: np.testing.assert_array_equal(a, c),
                 s_big.params, s_small.params)       # bitwise across sizes
    # vs the GSPMD default: XLA:CPU fuses the conv backward differently
    # inside the shard_map region, seeding ~1e-4 reduction-order grad
    # deviations that two momentum steps amplify — same-math, different
    # order (measured against single-device ground truth: BOTH paths
    # deviate from it at the same magnitude).  The bitwise gates are the
    # cross-bucket-size identity above and the softmax tests.
    jax.tree.map(lambda a, c: np.testing.assert_allclose(
        np.asarray(a), np.asarray(c), rtol=2e-2, atol=1e-3),
        s_ref.params, s_big.params)


def test_bucketed_partial_aggregation_bitwise():
    """replicas_to_aggregate under bucketing: the rotating-subset row
    weights are computed in GLOBAL row coordinates inside the shard_map
    region — bitwise against the GSPMD form on softmax."""
    mesh = make_mesh()
    x, y = _data()
    mk = lambda: DeviceDataset(x, y, 64, mesh=mesh, seed=3)
    mk_state = lambda: _state(build_model("softmax"), optax.sgd(0.2))
    ds = mk()
    kw = dict(mesh=mesh, num_slots=ds.num_slots,
              num_replicas=mesh.size, replicas_to_aggregate=3)
    ref = make_indexed_train_step(64, ds.steps_per_epoch, **kw)
    bkt = make_indexed_train_step(64, ds.steps_per_epoch,
                                  bucket_bytes=1 << 20, **kw)
    s_ref, s_bkt = mk_state(), mk_state()
    with mesh:
        ds_r, ds_b = mk(), mk()
        for _ in range(3):
            s_ref, m_ref = ref(s_ref, next(ds_r))
            s_bkt, m_bkt = bkt(s_bkt, next(ds_b))
    assert float(m_ref["loss"]) == float(m_bkt["loss"])
    jax.tree.map(lambda a, c: np.testing.assert_array_equal(a, c),
                 s_ref.params, s_bkt.params)


def test_bn_model_refused_by_name():
    """The step body refuses batch_stats-carrying state at trace time
    (run_training refuses earlier, by model, with the same words)."""
    import types
    from distributedtensorflowexample_tpu.parallel.bucketing import (
        build_bucketed_step_fn)
    fn = build_bucketed_step_fn(0.0, "xla", make_mesh(), 8, 0, 1 << 20)
    fake = types.SimpleNamespace(batch_stats={"bn": 1})
    with pytest.raises(ValueError, match="BatchNorm"):
        fn(fake, {"image": None, "label": None})
    # and the builder itself refuses a mesh with nothing to reduce
    with pytest.raises(ValueError, match="multi-device"):
        build_bucketed_step_fn(0.0, "xla", None, 1, 0, 1 << 20)


# ---- knob resolution + planning ---------------------------------------

def test_resolve_bucket_bytes(monkeypatch):
    assert resolve_bucket_bytes("") is None
    assert resolve_bucket_bytes("auto") == DEFAULT_BUCKET_BYTES
    monkeypatch.setenv("BUCKET_GRADS_AUTO_BYTES", "123456")
    assert resolve_bucket_bytes("auto") == 123456
    assert resolve_bucket_bytes("65536") == 65536
    with pytest.raises(ValueError, match="byte count"):
        resolve_bucket_bytes("bogus")
    with pytest.raises(ValueError, match="positive"):
        resolve_bucket_bytes("0")
    # The env override goes through the SAME validation: 0 must not
    # silently disable the bucketing the flag explicitly asked for.
    monkeypatch.setenv("BUCKET_GRADS_AUTO_BYTES", "0")
    with pytest.raises(ValueError, match="BUCKET_GRADS_AUTO_BYTES"):
        resolve_bucket_bytes("auto")
    monkeypatch.setenv("BUCKET_GRADS_AUTO_BYTES", "junk")
    with pytest.raises(ValueError, match="BUCKET_GRADS_AUTO_BYTES"):
        resolve_bucket_bytes("auto")


def test_bucket_rows_restore_refusals():
    """Layout guards: a legacy checkpoint (no update_layout key) can only
    hold the params-shaped tree — it must be refused into a bucket_rows
    run by name, and bucket_rows across mesh sizes is structural (the
    1/D row layout could restore PERMUTED, not just shape-mismatched)."""
    from distributedtensorflowexample_tpu.trainers.common import (
        _refuse_incompatible_restore)
    cur = {"sync_mode": "sync", "mesh_size": 8, "num_workers": None,
           "update_layout": "bucket_rows"}
    with pytest.raises(ValueError, match="'tree'"):
        _refuse_incompatible_restore({"sync_mode": "sync", "mesh_size": 8},
                                     cur, "/l", True)
    with pytest.raises(ValueError, match="structural"):
        _refuse_incompatible_restore(
            {"sync_mode": "sync", "mesh_size": 4,
             "update_layout": "bucket_rows"}, cur, "/l", True)
    # tree->tree across mesh sizes stays allowed (sync state replicated)
    cur_t = dict(cur, update_layout="tree")
    _refuse_incompatible_restore(
        {"sync_mode": "sync", "mesh_size": 4, "update_layout": "tree"},
        cur_t, "/l", False)
    # zero3_rows (PR 12): params themselves are 1/D rows — the same
    # structural refusals, by the layout's name
    cur_z = dict(cur, update_layout="zero3_rows")
    with pytest.raises(ValueError, match="zero3_rows"):
        _refuse_incompatible_restore(
            {"sync_mode": "sync", "mesh_size": 8, "update_layout": "tree"},
            cur_z, "/l", True)
    with pytest.raises(ValueError, match="structural"):
        _refuse_incompatible_restore(
            {"sync_mode": "sync", "mesh_size": 4,
             "update_layout": "zero3_rows"}, cur_z, "/l", True)


def test_plan_buckets_and_padding():
    mk = lambda shape, dt=np.float32: np.zeros(shape, dt)
    leaves = [mk(100), mk(200), mk(50, np.int32), mk(4000)]
    # dtype change forces a split; the cap forces another
    plan = plan_buckets(leaves, 1300 * 4)
    assert plan == [[0, 1], [2], [3]]
    assert [i for b in plan for i in b] == list(range(4))  # order kept
    # an over-cap leaf still gets its own bucket, never split
    assert plan_buckets([mk(10_000)], 4) == [[0]]
    assert bucket_padding_bytes([mk(10), mk(16)], 8) == 6 * 4


# ---- obs wiring --------------------------------------------------------

def test_metrics_hook_collective_counters():
    from distributedtensorflowexample_tpu.obs import metrics as obs_metrics
    from distributedtensorflowexample_tpu.training.hooks import MetricsHook
    summary = {"multiset": {"all-reduce": 3},
               "per_step": {"all-reduce": {"count": 3, "out_bytes": 31408,
                                           "accounting_bytes": 62816}},
               "total_count_per_step": 3,
               "total_out_bytes_per_step": 31408}
    before = obs_metrics.registry().snapshot()["counters"]
    hook = MetricsHook(every=10, collectives=summary)

    class _Loop:
        start_step = 0
    hook.begin(_Loop())
    hook.after_step(4, None, {})      # a 4-step fused boundary
    hook.after_step(8, None, {})
    after = obs_metrics.registry().snapshot()["counters"]

    def delta(name):
        return after.get(name, 0) - before.get(name, 0)
    assert delta("collective_ops_total") == 3 * 8
    assert delta("collective_bytes_total") == 31408 * 8
    gauges = obs_metrics.registry().snapshot()["gauges"]
    assert gauges['collective_ops_per_step{op="all-reduce"}']["value"] == 3
    # absent summary: no collective counting, hot path untouched
    h2 = MetricsHook(every=10)
    assert h2._coll_ops is None


def test_obs_report_collectives_section():
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import obs_report
    finally:
        sys.path.pop(0)
    flight = {"reason": "exit", "pid": 1,
              "metrics": {
                  "counters": {"collective_ops_total": 120,
                               "collective_bytes_total": 1256320},
                  "gauges": {
                      'collective_ops_per_step{op="all-reduce"}':
                          {"value": 3},
                      'collective_bytes_per_step{op="all-reduce"}':
                          {"value": 31408}}}}
    text = obs_report.render_flight("flight_1.json", flight)
    assert "### Collectives" in text
    assert "`all-reduce`" in text
    assert "31408" in text
    assert "collective_bytes_total" in text
    # no collective series -> no section
    assert "### Collectives" not in obs_report.render_flight(
        "flight_2.json", {"metrics": {"counters": {"x": 1}}})


# ---- slow_rank straggler fault (satellite; grammar tests ride the
# fleet suite's patterns, behavior pinned here) --------------------------

def test_slow_rank_plan_and_determinism():
    from distributedtensorflowexample_tpu.resilience.faults import (
        NAMED_PLANS, FaultPlan)
    p1 = FaultPlan.parse("slow_rank@3:0.5%1", 10, seed=7)
    (s,) = p1.specs
    assert (s.kind, s.step, s.arg, s.rank) == ("slow_rank", 3, 0.5, 1)
    assert p1.loop_specs == p1.specs            # a loop-level fault
    assert not p1.for_rank(0).specs             # pinned to rank 1
    assert p1.for_rank(1).specs == p1.specs
    # named plan + default arg; unpinned step is seed-deterministic
    a = FaultPlan.parse("slow_rank", 20, seed=5).specs[0]
    b = FaultPlan.parse("slow_rank", 20, seed=5).specs[0]
    assert "slow_rank" in NAMED_PLANS
    assert a.step == b.step and a.arg == 0.25
    assert FaultPlan.parse("slow_rank:0.1", 20, seed=6).specs[0].arg == 0.1


def test_slow_rank_hook_delays_every_boundary_and_survives_resume():
    from distributedtensorflowexample_tpu.resilience.faults import (
        FaultInjectionHook, FaultPlan)

    class _Loop:
        start_step = 0

    delay = 0.05
    hook = FaultInjectionHook(FaultPlan.parse(f"slow_rank@2:{delay}", 10))
    hook.begin(_Loop())
    t0 = time.perf_counter()
    hook.after_step(1, None, {})
    assert time.perf_counter() - t0 < delay / 2     # not yet active
    for step in (2, 3):
        t0 = time.perf_counter()
        hook.after_step(step, None, {})
        assert time.perf_counter() - t0 >= delay    # every boundary after
    # resume past the fault step: the rank is STILL slow, but the
    # injection isn't re-counted as a fresh fault
    from distributedtensorflowexample_tpu.obs import metrics as obs_metrics
    before = obs_metrics.registry().snapshot()["counters"].get(
        'faults_injected_total{kind="slow_rank"}', 0)
    resumed = FaultInjectionHook(FaultPlan.parse(f"slow_rank@2:{delay}", 10))

    class _Resumed:
        start_step = 5
    resumed.begin(_Resumed())
    t0 = time.perf_counter()
    resumed.after_step(6, None, {})
    assert time.perf_counter() - t0 >= delay
    after = obs_metrics.registry().snapshot()["counters"].get(
        'faults_injected_total{kind="slow_rank"}', 0)
    assert after == before
