"""graft-LM flagship workload (PR 8): model/data/trainer wiring, knob
parity at lm_tiny, and the OOV-poison -> NaNGuard path.

Tier-1-safe: lm_tiny at short sequences, single-digit fused
dispatches per test (the test_collectives discipline).  The one
param-count check here uses eval_shape — no 57M-param init ever runs
in tier-1.

Golden collective multisets for the LM trainer live in
tests/test_collectives.py next to the other per-trainer goldens.
"""

import json
import os
import subprocess
import sys

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from distributedtensorflowexample_tpu.data import DeviceDataset
from distributedtensorflowexample_tpu.data.lm import (
    LM_SEQ_LEN, load_lm, make_synthetic_tokens)
from distributedtensorflowexample_tpu.models import (
    LM_SIZES, LM_VOCAB, build_model)
from distributedtensorflowexample_tpu.parallel import (
    make_mesh, replicated_sharding)
from distributedtensorflowexample_tpu.parallel.bucketing import (
    DEFAULT_BUCKET_BYTES, init_bucketed_opt_state)
from distributedtensorflowexample_tpu.parallel.sync import (
    make_indexed_train_step, make_resident_eval)
from distributedtensorflowexample_tpu.training.state import TrainState
from distributedtensorflowexample_tpu.utils.profiling import (
    state_residency_per_device)

pytestmark = pytest.mark.lm

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEQ = 32            # short drill sequences; the shipped split is 128


def _data(n=256, seq=SEQ, seed=0):
    return load_lm("", "train", seed=seed, num=n, seq_len=seq)


def _tx():
    return optax.sgd(0.1, momentum=0.9)


def _state(mesh, batch, seq=SEQ, tx=None, **kw):
    model = build_model("lm_tiny", **kw)
    return TrainState.create_sharded(model, tx or _tx(), (batch, seq), 0,
                                     replicated_sharding(mesh))


def _opt_state_bytes_per_device(state) -> int:
    return state_residency_per_device(state)["opt_state_bytes_per_device"]


def _digest(tree) -> str:
    h = hashlib.sha256()
    for leaf in jax.tree.leaves(tree):
        h.update(np.asarray(leaf).tobytes())
    return h.hexdigest()


# ---- model + registry ---------------------------------------------------

def test_registry_sizes_and_lm_base_param_floor():
    """The size ladder is registered, and lm_base clears the >=50M-param
    floor the scale-up exists for — counted via eval_shape (no init)."""
    for size in LM_SIZES:
        assert build_model(size) is not None
    model = build_model("lm_base")
    shapes = jax.eval_shape(
        lambda r: model.init({"params": r, "dropout": r},
                             jnp.zeros((2, 8), jnp.int32), train=False),
        jax.random.PRNGKey(0))
    n_params = sum(int(np.prod(l.shape))
                   for l in jax.tree.leaves(shapes["params"]))
    assert n_params >= 50_000_000, n_params
    # BN-free by construction: no batch_stats collection exists, so the
    # bucket_grads/ZeRO-1 BatchNorm refusals can never trigger.
    assert "batch_stats" not in shapes
    with pytest.raises(ValueError, match="unknown LM size"):
        from distributedtensorflowexample_tpu.models import build_lm
        build_lm("lm_huge")
    with pytest.raises(ValueError, match="remat"):
        build_model("lm_tiny", remat="bogus").init(
            {"params": jax.random.PRNGKey(0)},
            jnp.zeros((1, 4), jnp.int32))


def test_oov_tokens_poison_logits_to_nan():
    """XLA gathers clamp out-of-range ids silently; the LM refuses
    loudly instead — any token >= vocab NaNs the logits, which is what
    hands a corrupt_batch straight to NaNGuardHook."""
    model = build_model("lm_tiny")
    rng = jax.random.PRNGKey(0)
    good = jnp.zeros((2, 8), jnp.int32)
    variables = model.init({"params": rng, "dropout": rng}, good)
    ok = model.apply(variables, good)
    assert bool(jnp.all(jnp.isfinite(ok)))
    bad = good.at[1, 3].set(LM_VOCAB)       # first illegal id
    poisoned = model.apply(variables, bad)
    assert bool(jnp.all(jnp.isnan(poisoned)))
    # uint8 input works too (the resident-split storage dtype).
    ok8 = model.apply(variables, jnp.zeros((2, 8), jnp.uint8))
    np.testing.assert_array_equal(np.asarray(ok8), np.asarray(ok))


# ---- token data path ----------------------------------------------------

def test_token_split_storage_marker_and_quantize_off():
    x, y = _data()
    assert x.dtype == np.uint8 and y.dtype == np.int32
    assert x.shape == (256, SEQ) and y.shape == (256, SEQ)
    # Targets are the 1-shifted inputs (same underlying walk).
    full = make_synthetic_tokens(256, SEQ, LM_VOCAB, 0, sample_seed=1)
    np.testing.assert_array_equal(x, full[:, :-1].astype(np.uint8))
    np.testing.assert_array_equal(y, full[:, 1:])

    ds = DeviceDataset(x, y, 16, token_data=True)
    assert ds.dequant is None and ds.dequant_impl is None
    data = ds.peek()
    assert "tokens" in data and data["images"].dtype == jnp.uint8
    off = DeviceDataset(x, y, 16, token_data=True, quantize="off")
    assert off.peek()["images"].dtype == jnp.int32

    with pytest.raises(ValueError, match="integer token split"):
        DeviceDataset(x.astype(np.float32), y, 16, token_data=True)
    wide = x.astype(np.int32) + 300          # ids past the byte range
    with pytest.raises(ValueError, match="uint8 range"):
        DeviceDataset(wide, y, 16, token_data=True)
    assert DeviceDataset(wide, y, 16, token_data=True,
                         quantize="off").peek()["images"].dtype == jnp.int32


def test_single_device_step_and_resident_eval_token_denominator():
    x, y = _data(n=64, seq=16)
    ds = DeviceDataset(x, y, 16, token_data=True)
    model = build_model("lm_tiny")
    state = TrainState.create(model, _tx(), jnp.zeros((16, 16), jnp.int32))
    step = make_indexed_train_step(16, ds.steps_per_epoch,
                                   num_slots=ds.num_slots)
    state, metrics = step(state, next(ds))
    loss = float(metrics["loss"])
    acc = float(metrics["accuracy"])
    assert np.isfinite(loss) and 0.0 <= acc <= 1.0
    # Resident eval normalizes PER TOKEN: cross-check against a direct
    # argmax count over the full split.
    ev = make_resident_eval(x, y, batch_size=32, token_data=True)
    got = ev(state)
    logits = model.apply({"params": state.params}, jnp.asarray(x))
    want = float(np.mean(np.argmax(np.asarray(logits), -1) == y))
    assert got == pytest.approx(want, abs=1e-9)


@pytest.mark.parametrize("scope", ["attn", "head", "loss"])
def test_lm_step_carries_the_named_scopes(scope):
    """The names a device trace is split by (forward / backward,
    attention's share, the head and the loss) are on the step's
    operations:
    metadata only, read here from the lowered text with debug info."""
    from distributedtensorflowexample_tpu.parallel.sync import (
        make_train_step)
    model = build_model("lm_tiny")
    state = TrainState.create(model, _tx(), jnp.zeros((2, 16), jnp.int32))
    batch = {"image": jnp.zeros((2, 16), jnp.int32),
             "label": jnp.zeros((2, 16), jnp.int32)}
    text = jax.jit(make_train_step()).lower(state, batch).as_text(
        debug_info=True)
    # e.g. loc("jvp(TransformerLM)/block0/attn/div") and, for a scope
    # opened under the gradient, loc("jvp(loss)/div")
    import re
    named = re.compile(r'loc\("[^"]*\b%s\)*/' % scope)
    paths = [line for line in text.splitlines() if named.search(line)]
    assert paths, scope
    if scope == "attn":
        # forward under the model's block, backward under its transpose
        assert any("jvp(" in p and "block0/attn/" in p for p in paths)
        assert any("transpose(" in p and "block0/attn/" in p
                   for p in paths)


# ---- knob parity at lm_tiny (the satellite gates) -----------------------

def _run_pair(mesh, step_a, state_a, step_b, state_b, seq=SEQ, calls=2,
              batch=32, seed=3):
    x, y = _data(seq=seq, seed=seed)
    ds_a = DeviceDataset(x, y, batch, mesh=mesh, seed=seed,
                         token_data=True)
    ds_b = DeviceDataset(x, y, batch, mesh=mesh, seed=seed,
                         token_data=True)
    with mesh:
        for _ in range(calls):
            state_a, m_a = step_a(state_a, next(ds_a))
            state_b, m_b = step_b(state_b, next(ds_b))
    return state_a, m_a, state_b, m_b


# The LM parity standard: the FORWARD pass is bitwise (identical ops,
# identical fusion — pinned via the loss below), but the bf16 einsum
# chain's backward reassociates under remat/shard_map recompilation, so
# gradients (hence params after a step) carry one-bf16-ulp-scale noise
# — measured max |delta| ~4e-5 after 2 steps at lm_tiny.  Same standard
# and reason as the conv models' shard_update gate: summation order,
# not math.  (ResNet's remat stays bitwise on this backend — its conv
# backward compiles identically under remat; the LM's einsum chain is
# what the compiler reassociates.)
_ATOL, _RTOL = 5e-4, 1e-3


def _assert_close(a, b):
    jax.tree.map(lambda p, q: np.testing.assert_allclose(
        np.asarray(p, np.float64), np.asarray(q, np.float64),
        rtol=_RTOL, atol=_ATOL), a, b)


def test_remat_block_parity():
    """remat='block' on the LM: the recomputed forward IS the forward
    (loss bitwise at step one), params to the bf16 parity standard."""
    mesh = make_mesh()
    x, y = _data(seed=3)
    ds = DeviceDataset(x, y, 32, mesh=mesh, seed=3, token_data=True)
    plain = make_indexed_train_step(32, ds.steps_per_epoch, mesh=mesh,
                                    num_slots=ds.num_slots)
    remat = make_indexed_train_step(32, ds.steps_per_epoch, mesh=mesh,
                                    num_slots=ds.num_slots)
    s_p = _state(mesh, 32)
    s_r = _state(mesh, 32, remat="block")
    ds_a = DeviceDataset(x, y, 32, mesh=mesh, seed=3, token_data=True)
    ds_b = DeviceDataset(x, y, 32, mesh=mesh, seed=3, token_data=True)
    with mesh:
        s_p, m_p = plain(s_p, next(ds_a))
        s_r, m_r = remat(s_r, next(ds_b))
        # Step one: SAME initial params -> the forward (and its loss)
        # must be bitwise identical; only the backward reassociates.
        assert float(m_p["loss"]) == float(m_r["loss"])
        s_p, m_p = plain(s_p, next(ds_a))
        s_r, m_r = remat(s_r, next(ds_b))
    _assert_close(s_p.params, s_r.params)


def test_bucket_grads_size_invariance_and_parity():
    """Bucketing is bitwise ACROSS bucket sizes on the LM (same
    additions, regrouped); vs the GSPMD default the shard_map backward
    may fuse the einsum chain differently, so that gate is allclose —
    the conv-model standard, same reason (summation order, not math)."""
    mesh = make_mesh()
    x, y = _data(seed=3)
    ds = DeviceDataset(x, y, 32, mesh=mesh, seed=3, token_data=True)
    mk = lambda bb: make_indexed_train_step(
        32, ds.steps_per_epoch, mesh=mesh, num_slots=ds.num_slots,
        bucket_bytes=bb)
    ref = make_indexed_train_step(32, ds.steps_per_epoch, mesh=mesh,
                                  num_slots=ds.num_slots)
    big, small = mk(DEFAULT_BUCKET_BYTES), mk(16 << 10)
    s_big, s_small, s_ref = (_state(mesh, 32) for _ in range(3))
    s_big, m_big, s_small, m_small = _run_pair(mesh, big, s_big,
                                               small, s_small)
    assert _digest(s_big.params) == _digest(s_small.params)
    assert float(m_big["loss"]) == float(m_small["loss"])
    x2, y2 = _data(seed=3)
    ds_r = DeviceDataset(x2, y2, 32, mesh=mesh, seed=3, token_data=True)
    with mesh:
        for _ in range(2):
            s_ref, m_ref = ref(s_ref, next(ds_r))
    _assert_close(s_ref.params, s_big.params)
    assert float(m_ref["loss"]) == pytest.approx(float(m_big["loss"]),
                                                 abs=1e-3)


def test_composed_zero1_schedule_parity_and_state_residency():
    """--bucket_grads + --shard_update at lm_tiny: the explicit
    per-bucket RS+AG schedule trains the same model (allclose standard)
    while every non-scalar optimizer leaf lives as a 1/D bucket row —
    the measured-at-lm_base residency win, structurally pinned here."""
    mesh = make_mesh()
    D = mesh.size
    x, y = _data(seed=3)
    ds = DeviceDataset(x, y, 32, mesh=mesh, seed=3, token_data=True)
    ref = make_indexed_train_step(32, ds.steps_per_epoch, mesh=mesh,
                                  num_slots=ds.num_slots)
    z1 = make_indexed_train_step(32, ds.steps_per_epoch, mesh=mesh,
                                 num_slots=ds.num_slots,
                                 bucket_bytes=DEFAULT_BUCKET_BYTES,
                                 bucket_shard_update=True)
    s_ref = _state(mesh, 32)
    s_z = _state(mesh, 32)
    s_z = s_z.replace(opt_state=init_bucketed_opt_state(
        _tx(), s_z.params, DEFAULT_BUCKET_BYTES, mesh))
    repl = _opt_state_bytes_per_device(s_ref)
    shard = _opt_state_bytes_per_device(s_z)
    assert shard <= repl / D * 1.05 + 64        # 1/D (+row padding)
    s_ref, m_ref, s_z, m_z = _run_pair(mesh, ref, s_ref, z1, s_z)
    _assert_close(s_ref.params, s_z.params)


def test_zero3_schedule_parity_and_full_state_residency():
    """--shard_params at lm_tiny (PR 12): the ZeRO-3 per-bucket AG/RS
    schedule trains the same model (allclose standard — the shard_map
    backward reassociates the einsum chain, same as every other knob)
    while params AND optimizer moments live as 1/D bucket rows — the
    full-state residency win, structurally pinned here.  Overlap on/off
    is checked bitwise-equal in tests/test_zero3.py; this gate uses the
    default double buffer."""
    from distributedtensorflowexample_tpu.parallel.zero3 import Zero3Layout
    mesh = make_mesh()
    D = mesh.size
    x, y = _data(seed=3)
    ds = DeviceDataset(x, y, 32, mesh=mesh, seed=3, token_data=True)
    ref = make_indexed_train_step(32, ds.steps_per_epoch, mesh=mesh,
                                  num_slots=ds.num_slots)
    s_ref = _state(mesh, 32)
    s_z = _state(mesh, 32)
    repl = state_residency_per_device(s_ref)
    layout = Zero3Layout(s_z.params, DEFAULT_BUCKET_BYTES, mesh)
    z3 = make_indexed_train_step(32, ds.steps_per_epoch, mesh=mesh,
                                 num_slots=ds.num_slots,
                                 zero3_layout=layout)
    s_z = s_z.replace(opt_state=init_bucketed_opt_state(
        _tx(), s_z.params, DEFAULT_BUCKET_BYTES, mesh))
    s_z = s_z.replace(params=layout.init_rows(s_z.params))
    rows = state_residency_per_device(s_z)
    # params+opt both 1/D (+row padding): the FULL-state shrink, not
    # just ZeRO-1's opt-only one.
    assert rows["params_bytes_per_device"] <= \
        repl["params_bytes_per_device"] / D * 1.05 + 64
    assert rows["state_bytes_per_device"] <= \
        repl["state_bytes_per_device"] / D * 1.05 + 128
    s_ref, m_ref, s_z, m_z = _run_pair(mesh, ref, s_ref, z3, s_z)
    full = layout.materialize(s_z.params)
    _assert_close(s_ref.params, full)


def test_shard_update_constraint_form_parity():
    """The GSPMD-constraint --shard_update on the LM: same training
    (allclose — summation order, the documented standard) with the
    optimizer state laid out 1/D per device."""
    from distributedtensorflowexample_tpu.training.optimizers import (
        cross_replica_update_sharding, update_shardings)
    mesh = make_mesh()
    x, y = _data(seed=3)
    ds = DeviceDataset(x, y, 32, mesh=mesh, seed=3, token_data=True)
    ref = make_indexed_train_step(32, ds.steps_per_epoch, mesh=mesh,
                                  num_slots=ds.num_slots)
    su = make_indexed_train_step(32, ds.steps_per_epoch, mesh=mesh,
                                 num_slots=ds.num_slots)
    s_ref = _state(mesh, 32)
    s_su = _state(mesh, 32, tx=cross_replica_update_sharding(_tx(), mesh))
    s_su = s_su.replace(opt_state=jax.device_put(
        s_su.opt_state, update_shardings(s_su.opt_state, mesh)))
    assert _opt_state_bytes_per_device(s_su) < \
        _opt_state_bytes_per_device(s_ref)
    s_ref, m_ref, s_su, m_su = _run_pair(mesh, ref, s_ref, su, s_su,
                                         calls=1)
    _assert_close(s_ref.params, s_su.params)


# ---- trainer surface ----------------------------------------------------

def test_trainer_lm_end_to_end(tmp_log_dir):
    from distributedtensorflowexample_tpu.trainers.trainer_lm import main
    summary = main(["--train_steps", "24", "--batch_size", "4",
                    "--log_every", "24", "--log_dir", tmp_log_dir,
                    "--resume", "false", "--eval_every", "0"])
    assert summary["steps"] == 24
    # 24 steps already lift per-token accuracy well above the 1/250
    # uniform floor (the Markov structure is that learnable).
    assert summary["final_accuracy"] > 0.05


def test_trainer_lm_refuses_host_fed_path(tmp_log_dir):
    from distributedtensorflowexample_tpu.trainers.trainer_lm import main
    with pytest.raises(ValueError, match="device-resident"):
        main(["--train_steps", "4", "--batch_size", "4",
              "--device_data", "off", "--log_dir", tmp_log_dir,
              "--resume", "false"])


# ---- faults: corrupt_batch on token pipelines ---------------------------

@pytest.mark.faults
def test_corrupt_batch_token_semantics_and_nan_loss_refusal():
    from distributedtensorflowexample_tpu.resilience import (
        FaultPlan, FaultyBatches)
    tokens = {"image": jnp.zeros((4, 8), jnp.int32),
              "label": jnp.zeros((4, 8), jnp.int32)}
    plan = FaultPlan.parse("corrupt_batch@1", 4)
    fb = FaultyBatches(iter([tokens] * 2), plan)
    bad = np.asarray(next(fb)["image"])
    assert bad.dtype == np.int32
    assert (bad >= LM_VOCAB).any()          # garbage ids land OOV
    # uint8 token batches corrupt to random bytes — still OOV-capable
    # because LM_VOCAB < 256 by design.
    u8 = {"image": jnp.zeros((4, 64), jnp.uint8),
          "label": jnp.zeros((4, 64), jnp.int32)}
    fb8 = FaultyBatches(iter([u8] * 2), FaultPlan.parse("corrupt_batch@1", 4))
    bad8 = np.asarray(next(fb8)["image"])
    assert bad8.dtype == np.uint8 and (bad8 >= LM_VOCAB).any()
    # nan_loss on ANY integer pipeline is refused loudly (no NaN int
    # exists; np.full would wrap to silent garbage).
    nb = FaultyBatches(iter([tokens] * 2), FaultPlan.parse("nan_loss@1", 4))
    with pytest.raises(ValueError, match="no NaN integer"):
        next(nb)


@pytest.mark.faults
def test_named_plan_corrupt_batch_rank_targets_rank_1():
    from distributedtensorflowexample_tpu.resilience import FaultPlan
    plan = FaultPlan.parse("corrupt_batch_rank", 16)
    assert len(plan.specs) == 1 and plan.specs[0].rank == 1
    assert plan.specs[0].kind == "corrupt_batch"
    assert not plan.for_rank(0).specs          # other ranks unaffected
    assert plan.for_rank(1).specs == plan.specs
    # One reproducible scenario: every rank parsing the same (text,
    # steps, seed) triple sees the same seed-drawn mid-run anchor.
    assert plan.specs[0].step == \
        FaultPlan.parse("corrupt_batch_rank", 16).specs[0].step
    assert 1 <= plan.specs[0].step < 16


@pytest.mark.faults
def test_faultline_lm_corrupt_batch_trips_nan_guard(tmp_path):
    """ACCEPTANCE for the fault satellite: corrupt_batch on the LM
    trainer -> garbage ids -> OOV poison -> NaNGuard kills the run
    before a poisoned snapshot, through the real faultline CLI."""
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "faultline.py"),
         "--plan", "corrupt_batch", "--model", "lm_tiny",
         "--steps", "5", "--workdir", str(tmp_path / "fl")],
        capture_output=True, text=True, timeout=300)
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["status"] == "fault"
    assert "non-finite loss" in line["error"]
    # The healthy prefix made it to the tape; the poisoned step did not.
    assert all(np.isfinite(l) for _, l in line["losses"])


# ---- causal_attention: which implementation a call takes (PR 25) --------

from distributedtensorflowexample_tpu.obs import metrics as obs_metrics
from distributedtensorflowexample_tpu.ops import attention as attention_op


def _inline_attention_before_pr25(q, k, v, dtype):
    """DecoderBlock's attention as it stood inline until PR 25, kept
    here as the pin: ``[B, T, H, Dh]`` -> ``[B, T, H * Dh]``."""
    B, T, _, Dh = q.shape
    scores = jnp.einsum("bthd,bshd->bhts", q, k) / jnp.asarray(
        Dh ** 0.5, dtype)
    causal = (jnp.arange(T)[:, None] >= jnp.arange(T)[None, :])
    scores = jnp.where(causal[None, None], scores,
                       jnp.asarray(-1e9, scores.dtype))
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1)
    probs = probs.astype(dtype)
    return jnp.einsum("bhts,bshd->bthd", probs, v).reshape(B, T, -1)


def _attention_counts():
    got = obs_metrics.registry().snapshot()["counters"]
    return {impl: got.get('lm_attention_blocks_total{impl="%s"}' % impl, 0)
            for impl in ("pallas", "einsum")}


@pytest.mark.parametrize("shape,dtype", [
    ((4, SEQ, 2, 32), jnp.bfloat16),       # lm_tiny: never tiles
    ((2, 512, 2, 64), jnp.bfloat16),       # tiles, but this is the CPU
    ((2, 96, 4, 64), jnp.float32),         # T no multiple of the block
])
def test_causal_attention_on_cpu_is_the_einsum_chain_bitwise(shape, dtype):
    keys = jax.random.split(jax.random.PRNGKey(5), 3)
    q, k, v = (jax.random.normal(kk, shape, jnp.float32).astype(dtype)
               for kk in keys)
    before = _attention_counts()
    got = jax.jit(lambda q, k, v: attention_op.causal_attention(
        q, k, v).reshape(shape[0], shape[1], -1))(q, k, v)
    want = jax.jit(lambda q, k, v: _inline_attention_before_pr25(
        q, k, v, dtype))(q, k, v)
    assert got.dtype == want.dtype
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))
    after = _attention_counts()
    assert after["einsum"] == before["einsum"] + 1
    assert after["pallas"] == before["pallas"]


@pytest.mark.parametrize("shape,takes", [
    ((16, 1024, 12, 64), True),     # gpt2_124m.train_seq1024
    ((24, 1024, 20, 64), True),     # gpt2_774m.train_seq1024
    ((4, 2048, 8, 128), True),
    ((8, 768, 12, 64), True),       # block 256
    ((32, 128, 12, 64), False),     # lm_base: tiles, too short to win
    ((16, 1000, 12, 64), False),    # no block divides T
    ((16, 4096, 12, 64), False),    # longer than a cell holds in VMEM
    ((16, 1024, 24, 32), False),    # head width
    ((16, 1024, 3, 64), False),     # half a lane group left over
])
def test_takes_kernel_is_decided_by_backend_and_shape(shape, takes,
                                                      monkeypatch):
    assert not attention_op.takes_kernel(shape)          # the CPU never
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert attention_op.takes_kernel(shape) is takes


@pytest.mark.parametrize("remat", ["none", "block"])
@pytest.mark.parametrize("impl", ["einsum", "pallas"])
def test_lm_attention_blocks_total_counts_layers_per_trace(impl, remat,
                                                           monkeypatch):
    from distributedtensorflowexample_tpu.models.transformer_lm import (
        TransformerLM)
    n_layers = 3
    model = TransformerLM(n_layers=n_layers, d_model=128, n_heads=2,
                          d_ff=256, max_len=128, remat=remat)
    tokens = jnp.zeros((2, 128), jnp.int32)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0), tokens)
    if impl == "pallas":
        monkeypatch.setattr(attention_op, "takes_kernel", lambda s: True)
    other = "einsum" if impl == "pallas" else "pallas"

    def loss(p):
        return jnp.sum(model.apply(p, tokens, train=True))

    before = _attention_counts()
    jax.eval_shape(jax.grad(loss), params)      # traced, never run
    after = _attention_counts()
    assert after[impl] - before[impl] == n_layers
    assert after[other] == before[other]


def test_importing_the_model_does_not_import_pallas():
    # jax.experimental.pallas costs a second or two to import; a CPU
    # run or a serving process, which never take a kernel, do not pay it.
    code = ("import sys\n"
            "import distributedtensorflowexample_tpu.models.transformer_lm\n"
            "print('jax.experimental.pallas' in sys.modules)\n")
    r = subprocess.run([sys.executable, "-c", code], cwd=REPO, timeout=120,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"),
                       capture_output=True, text=True)
    assert r.returncode == 0, r.stderr
    assert r.stdout.strip() == "False"
