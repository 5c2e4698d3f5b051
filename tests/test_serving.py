"""serving/ — the continuous-batching inference engine (PR 15 + 17):
decode parity with the training forward, mid-decode admission
(continuous batching, not batch-drain), SLO admission, OOV refusal,
snapshot → serving promotion edges (torn-newest fallback, row-layout
materialization, sharded promotion), the decode-step HLO contracts
(replicated AND params-stay-sharded), speculative decoding's greedy
oracle, batched prefill, per-request sampling lanes, the prefix cache,
and the obs/ import direction.

Inline and tier-1-safe: lm_tiny at tiny slot/cache geometry.  The
sharded tests follow tests/test_collectives.py's precedent — shard_map
collectives over forced host devices run inline.  The engine fixture is
module-scoped so its prefill/decode compiles are paid once.  The
end-to-end serve_lm drill (real subprocess, eviction, TERM→143) lives
in tests/test_scheduler.py next to the other control-plane drills.
"""

import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from distributedtensorflowexample_tpu.models import build_model
from distributedtensorflowexample_tpu.obs import metrics as obs_metrics
from distributedtensorflowexample_tpu.obs import trace as obs_trace
from distributedtensorflowexample_tpu.refusal import ModeRefusal
from distributedtensorflowexample_tpu.resilience.snapshot import (
    SnapshotStore)
from distributedtensorflowexample_tpu.serving.engine import (
    DECODE_HLO_CONTRACT, DecodeEngine, serve_slots_default)
from distributedtensorflowexample_tpu.serving.loadgen import (
    DriveFile, make_prompt)
from distributedtensorflowexample_tpu.serving.prefix import PrefixCache
from distributedtensorflowexample_tpu.serving.promote import (
    init_lm_snapshot, promote, promote_sharded)
from distributedtensorflowexample_tpu.serving.queue import (
    ContinuousBatcher, RequestQueue, percentile, serve_slo_ms_default)
from distributedtensorflowexample_tpu.serving.sampling import Sampler
from distributedtensorflowexample_tpu.serving.sharded import (
    SHARDED_DECODE_HLO_CONTRACT, ShardedDecodeEngine)
from distributedtensorflowexample_tpu.serving.spec import SpecDecoder
from distributedtensorflowexample_tpu.training.state import TrainState

pytestmark = pytest.mark.serving

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SIZE = "lm_tiny"
CACHE = 32


def _tx():
    return optax.sgd(0.1, momentum=0.9)


@pytest.fixture(scope="module")
def lm_state():
    model = build_model(SIZE)
    return model, TrainState.create(model, _tx(),
                                    jnp.zeros((1, 8), jnp.int32))


@pytest.fixture(scope="module")
def engine(lm_state):
    model, state = lm_state
    return DecodeEngine(model, state.params, slots=3, cache_len=CACHE)


@pytest.fixture(scope="module")
def draft_engine(lm_state):
    """A draft net that genuinely DISAGREES with the target (same
    architecture, params halved) — speculative acceptance must survive
    rejection, not just the self-draft fast path."""
    model, state = lm_state
    scaled = jax.tree.map(lambda a: a * 0.5, state.params)
    return DecodeEngine(model, scaled, slots=3, cache_len=CACHE)


@pytest.fixture(scope="module")
def sharded_engine(lm_state):
    from distributedtensorflowexample_tpu.parallel import (
        make_mesh, replicated_sharding)
    from distributedtensorflowexample_tpu.parallel.zero3 import (
        Zero3Layout)
    model, state = lm_state
    if len(jax.devices()) < 2:
        pytest.skip("params-stay-sharded decode needs >= 2 devices")
    mesh = make_mesh(2)
    # Host round-trip first: init_rows DONATES its input, and a
    # device_put of already-resident buffers may alias them — donating
    # an alias would delete the replicated fixture's params.
    repl = jax.device_put(jax.tree.map(np.asarray, state.params),
                          replicated_sharding(mesh))
    layout = Zero3Layout(repl, 16 << 10, mesh)
    return ShardedDecodeEngine(model, layout.init_rows(repl), layout,
                               slots=2, cache_len=CACHE)


def _greedy_reference(model, params, prompt, n, got=None):
    """Teacher-forced greedy through the TRAINING forward — the truth
    the engine must reproduce token-for-token.  With ``got`` (the
    engine's candidate tokens), verification is ONE forward over
    [prompt + got]: argmax at each position must select the next
    candidate, which by induction proves ``got`` IS the greedy chain —
    n eager growing-prefix forwards collapse to one.  Without ``got``
    it generates the chain the slow sequential way."""
    if got is not None:
        assert len(got) == n
        seq = [int(t) for t in prompt] + [int(t) for t in got]
        logits = model.apply({"params": params},
                             jnp.asarray([seq], jnp.int32), train=False)
        P = len(prompt)
        return [int(jnp.argmax(logits[0, P - 1 + i])) for i in range(n)]
    seq = list(int(t) for t in prompt)
    out = []
    for _ in range(n):
        logits = model.apply({"params": params},
                             jnp.asarray([seq], jnp.int32), train=False)
        nxt = int(jnp.argmax(logits[0, -1]))
        out.append(nxt)
        seq.append(nxt)
    return out


def _engine_greedy(engine, slot, prompt, n):
    toks = [engine.prefill(slot, np.asarray(prompt, np.int32),
                           max_new=n)]
    while len(toks) < n:
        step = engine.decode()
        toks.append(int(step[slot]))
    return toks


# ---- decode parity -------------------------------------------------------

def test_decode_matches_training_forward_token_exact(lm_state, engine):
    """The KV-cache decode (prefill + single-query steps) generates
    token-for-token what teacher-forced greedy through the training
    model generates: the cache path is the same math, masked rows
    contribute exactly 0.0 after the f32 exp."""
    model, state = lm_state
    prompt = [5, 9, 17, 3, 88, 120, 7]
    got = _engine_greedy(engine, 0, prompt, 6)
    assert got == _greedy_reference(model, state.params, prompt, 6,
                                    got=got)
    # A second prompt through a DIFFERENT slot, same engine, same truth
    # (slot reuse after retirement is the continuous-batching steady
    # state).
    prompt2 = [200, 1, 42]
    got2 = _engine_greedy(engine, 2, prompt2, 5)
    assert got2 == _greedy_reference(model, state.params, prompt2, 5,
                                     got=got2)


def test_prefill_bucket_table_and_refusals(engine):
    assert engine.bucket_for(3, 4) == 8          # smallest bucket
    assert engine.bucket_for(9, 4) == 16         # next power of two
    assert engine.bucket_for(CACHE - 4, 4) == CACHE
    with pytest.raises(ModeRefusal, match="--max_len"):
        engine.bucket_for(CACHE - 2, 4)          # can never finish
    with pytest.raises(ModeRefusal, match="--max_len"):
        # a cache longer than the positional table is refused at build
        DecodeEngine(engine.model, engine.params, slots=1,
                     cache_len=engine.model.max_len + 1)


@pytest.mark.parametrize("cache_len, smallest, ladder", [
    (CACHE, 8, (8, 16, 32)), (48, 8, (8, 16, 32, 48)),
    (64, 16, (16, 32, 64)), (8, 8, (8,))])
def test_a_serving_lm_states_no_ladder_and_keeps_the_powers_of_two(
        lm_state, cache_len, smallest, ladder):
    """``ServingLM``'s ``prefill_buckets`` leaves the choice to the
    engine (None): GPT-2's engines pad to the next power of two, the
    cache's length last, as they always did (a model that states a
    ladder: tests/test_afmoe.py)."""
    model, state = lm_state
    engine = DecodeEngine(model, state.params, slots=1, cache_len=cache_len,
                          prefill_smallest=smallest)
    assert engine.smodel.prefill_buckets(cache_len) is None
    assert engine.buckets == ladder


# ---- continuous batching -------------------------------------------------

def test_request_admitted_mid_decode_completes_bitwise(lm_state, engine):
    """THE continuous-batching acceptance: B is admitted while A is
    mid-decode (A visibly unfinished at B's admission) and B's output
    equals B decoded solo — admission into an open slot of a RUNNING
    batch, with zero cross-request contamination."""
    model, state = lm_state
    prompt_a = [10, 20, 30, 40, 50]
    prompt_b = [7, 7, 99]
    solo_b = _engine_greedy(engine, 1, prompt_b, 5)

    queue = RequestQueue(engine.vocab)
    batcher = ContinuousBatcher(engine, queue, slo_ms=0.0)
    ra = queue.submit(prompt_a, 12, rid="A")
    batcher.step()                   # admits A, first decode
    batcher.step()
    assert not ra.done.is_set()      # A is mid-decode
    rb = queue.submit(prompt_b, 5, rid="B")
    batcher.step()                   # B admitted into an open slot NOW
    assert rb.admit_t is not None and not ra.done.is_set(), \
        "B must join while A is still decoding — batch-drain detected"
    while not (ra.done.is_set() and rb.done.is_set()):
        assert batcher.step() > 0
    assert ra.outcome == "ok" and rb.outcome == "ok"
    assert rb.tokens == solo_b       # bitwise: no contamination from A
    assert ra.tokens[:6] == _greedy_reference(
        model, state.params, prompt_a, 6, got=ra.tokens[:6])
    assert len(ra.tokens) == 12 and ra.first_token_t <= rb.admit_t


def test_slo_admission_rejects_predicted_misses(engine):
    """A request the step-time EWMA predicts past the SLO is rejected
    loudly at admission — never admitted to miss."""
    queue = RequestQueue(engine.vocab)
    batcher = ContinuousBatcher(engine, queue, slo_ms=50.0)
    batcher._step_ewma_s = 0.050     # 50 ms/step: 8 tokens >> 50 ms SLO
    req = queue.submit([1, 2, 3], 8)
    batcher.step()
    assert req.done.is_set() and req.outcome == "slo_rejected"
    # SLO off admits the same request
    batcher2 = ContinuousBatcher(engine, queue, slo_ms=0.0)
    batcher2._step_ewma_s = 0.050
    req2 = queue.submit([1, 2, 3], 2)
    batcher2.step()
    assert req2.outcome in ("", "ok") and req2.admit_t is not None
    while not req2.done.is_set():
        batcher2.step()
    assert req2.outcome == "ok"


def test_drain_answers_inflight_and_rejects_queued(engine):
    """The TERM half: drain decodes in-flight requests to completion
    and rejects the queued tail as ``drained`` — nothing admitted is
    lost, nothing queued hangs forever."""
    queue = RequestQueue(engine.vocab)
    batcher = ContinuousBatcher(engine, queue, slo_ms=0.0)
    inflight = [queue.submit([3, 1, 4], 6, rid=f"f{i}")
                for i in range(3)]                  # fills all 3 slots
    batcher.step()
    queued = queue.submit([9, 9], 4, rid="tail")    # no slot for it
    batcher.drain()
    assert all(r.done.is_set() and r.outcome == "ok" and
               len(r.tokens) == 6 for r in inflight)
    assert queued.outcome == "drained" and queued.tokens == []
    assert batcher.stats()["rejected"]["drained"] == 1
    # The submit/drain race is closed at the queue: a submit landing
    # AFTER drain is answered 'drained' synchronously — no caller is
    # ever left blocked on a request nothing will decode.
    late = queue.submit([1, 2], 3, rid="late")
    assert late.done.is_set() and late.outcome == "drained"
    assert len(queue) == 0
    # Retired slots are PARKED: decode advances only busy frontiers,
    # so an idle slot cannot drift toward the cache edge.
    assert engine.positions.tolist() == [0] * engine.slots


def test_oversized_request_refused_not_fatal(engine):
    """A request that can never finish inside the cache is refused by
    name AT ADMISSION — one impossible request costs itself, never the
    serving loop (the batcher thread has no handler above it)."""
    queue = RequestQueue(engine.vocab)
    batcher = ContinuousBatcher(engine, queue, slo_ms=0.0)
    bad = queue.submit(list(range(CACHE - 2)), 8)    # 30 + 8 > 32
    ok = queue.submit([1, 2, 3], 3)
    batcher.step()
    assert bad.done.is_set() and bad.outcome == "refused"
    assert "--max_len" in bad.error
    while not ok.done.is_set():
        batcher.step()                               # loop survived
    assert ok.outcome == "ok" and len(ok.tokens) == 3
    assert batcher.stats()["rejected"]["refused"] == 1


def test_oov_request_refused_by_name(engine):
    queue = RequestQueue(engine.vocab)
    with pytest.raises(ModeRefusal, match="out-of-vocab"):
        queue.submit([5, engine.vocab + 7], 4)
    with pytest.raises(ValueError, match="non-empty"):
        queue.submit([], 4)
    with pytest.raises(ValueError, match="integers"):
        queue.submit([1.5, 2.5], 4)
    assert len(queue) == 0           # nothing leaked into the queue


# ---- snapshot -> serving promotion edges ---------------------------------

def test_promotion_falls_back_past_torn_newest(tmp_path, lm_state):
    """A torn newest snapshot must cost one interval of freshness,
    never the worker: promotion discards it (validity machinery) and
    serves the previous valid step."""
    model, state = lm_state
    d = str(tmp_path / "snaps")
    init_lm_snapshot(d, SIZE, seed=0)
    store = SnapshotStore(d)
    newer = state.replace(step=jnp.asarray(7, jnp.int32))
    store.save(newer, meta={"model": SIZE, "update_layout": "tree"})
    assert promote(d, SIZE).step == 7
    store.tear_latest()
    pm = promote(d, SIZE)
    assert pm.step == 0              # fell back, did not die
    # nothing valid left: promotion refuses loudly with a what-to-do
    for s in store.steps():
        os.remove(store._payload_path(s))
    with pytest.raises(ValueError, match="no valid snapshot"):
        promote(d, SIZE)


def test_promotion_refuses_cross_model_by_name(tmp_path):
    d = str(tmp_path / "snaps")
    init_lm_snapshot(d, SIZE, seed=0)
    with pytest.raises(ModeRefusal, match="--size"):
        promote(d, "lm_small")


def test_promotion_materializes_zero3_and_bucket_rows(tmp_path,
                                                      lm_state):
    """Row-layout snapshots (ZeRO-3 zero3_rows: params as 1/D bucket
    rows; ZeRO-1 bucket_rows: optimizer state as rows) promote to the
    BITWISE full param tree through the PR 12 materialize seam."""
    import jax

    from distributedtensorflowexample_tpu.parallel import (
        make_mesh, replicated_sharding)
    from distributedtensorflowexample_tpu.parallel.bucketing import (
        init_bucketed_opt_state)
    from distributedtensorflowexample_tpu.parallel.zero3 import (
        Zero3Layout)
    model, state = lm_state
    mesh = make_mesh(2)
    bucket_bytes = 16 << 10
    full = jax.tree.map(np.asarray, state.params)     # host truth copy
    repl = jax.device_put(state.params, replicated_sharding(mesh))

    # zero3_rows: params AND opt state as rows
    d3 = str(tmp_path / "z3")
    meta3 = {"model": SIZE, "update_layout": "zero3_rows",
             "mesh_size": 2, "bucket_bytes": bucket_bytes}
    layout = Zero3Layout(repl, bucket_bytes, mesh)
    opt = init_bucketed_opt_state(_tx(), repl, bucket_bytes, mesh)
    rows_state = state.replace(opt_state=opt,
                               params=layout.init_rows(repl))
    SnapshotStore(d3).save(rows_state, meta=meta3)
    pm = promote(d3, SIZE)
    assert pm.layout == "zero3_rows"
    got = jax.tree.map(np.asarray, pm.params)
    for a, b in zip(jax.tree.leaves(full), jax.tree.leaves(got)):
        assert a.dtype == b.dtype and np.array_equal(a, b)

    # bucket_rows: tree params, row opt state
    d1 = str(tmp_path / "z1")
    meta1 = {"model": SIZE, "update_layout": "bucket_rows",
             "mesh_size": 2, "bucket_bytes": bucket_bytes}
    z1_state = state.replace(opt_state=init_bucketed_opt_state(
        _tx(), state.params, bucket_bytes, mesh))
    SnapshotStore(d1).save(z1_state, meta=meta1)
    pm1 = promote(d1, SIZE)
    assert pm1.layout == "bucket_rows"
    for a, b in zip(jax.tree.leaves(full),
                    jax.tree.leaves(jax.tree.map(np.asarray,
                                                 pm1.params))):
        assert np.array_equal(a, b)

    # a rows manifest without its geometry meta is refused loudly
    d_bad = str(tmp_path / "bad")
    SnapshotStore(d_bad).save(rows_state, meta={
        "model": SIZE, "update_layout": "zero3_rows"})
    with pytest.raises(ValueError, match="mesh_size"):
        promote(d_bad, SIZE)


# ---- the decode-step HLO contract ----------------------------------------

def test_decode_hlo_contract_holds_and_catches_violations(engine):
    """The compiled decode step honors DECODE_HLO_CONTRACT (donation
    aliased, no donated-buffer copy, zero collectives, f32 ceiling) —
    and the contract actually has teeth against a donation-less
    compile of the same program."""
    import jax

    from distributedtensorflowexample_tpu.analysis.hlo_lint import (
        check_contract)
    hlo = engine.decode_hlo()
    assert check_contract(hlo, DECODE_HLO_CONTRACT) == []
    # Teeth: the SAME step compiled WITHOUT donation must fail the
    # aliasing clause — the contract distinguishes the schedules.
    undonated = jax.jit(engine._decode_fn).lower(
        *engine.decode_args()).compile().as_text()
    findings = check_contract(undonated, DECODE_HLO_CONTRACT)
    assert any(f.rule == "hlo-donation" for f in findings)


def test_serving_suite_is_wired_into_the_hlo_front():
    """graftlint's HLO front includes BOTH serving decode contracts
    (replicated 0-collective and sharded exactly-B-gathers), so
    `python -m tools.graftlint` gates them like the ZeRO schedules."""
    from distributedtensorflowexample_tpu.analysis import hlo_lint
    progs = hlo_lint.serving_suite()
    assert [p["mode"] for p in progs] == ["serve_decode",
                                          "serve_decode_sharded"]
    assert progs[0]["contract"] is DECODE_HLO_CONTRACT
    assert progs[1]["contract"] is SHARDED_DECODE_HLO_CONTRACT
    assert progs[1]["symbols"]["B"] >= 1
    for prog in progs:
        fs = hlo_lint.check_contract(prog["hlo"], prog["contract"],
                                     symbols=prog["symbols"])
        assert fs == [], [f.message for f in fs]


# ---- params-stay-sharded decode (PR 17 tentpole a) -----------------------

def test_sharded_decode_bitwise_and_resident_at_one_over_d(
        lm_state, engine, sharded_engine):
    """The row-resident engine generates token-for-token what the
    replicated engine generates (both slots live, one per device), and
    its LIVE params residency is exactly 1/D — the full tree is never
    materialized."""
    prompts = ([4, 8, 15, 16, 23], [42, 7])
    want = [_engine_greedy(engine, 0, prompts[0], 6),
            _engine_greedy(engine, 1, prompts[1], 6)]
    got = [[sharded_engine.prefill(s, np.asarray(p, np.int32),
                                   max_new=6)]
           for s, p in enumerate(prompts)]
    for _ in range(5):
        step = sharded_engine.decode(busy=[0, 1])
        got[0].append(int(step[0]))
        got[1].append(int(step[1]))
    assert got == want
    res = sharded_engine.params_residency()
    assert res["num_devices"] == 2
    assert res["frac_per_device"] == 0.5           # exactly 1/D
    assert res["params_bytes_per_device"] * 2 == \
        res["params_bytes_total"]


def test_sharded_engine_refuses_bad_geometry_by_name(sharded_engine):
    with pytest.raises(ModeRefusal, match="--slots"):
        ShardedDecodeEngine(sharded_engine.model, sharded_engine.rows,
                            sharded_engine.layout, slots=3,
                            cache_len=CACHE)       # 3 % 2 != 0
    with pytest.raises(ModeRefusal, match="--max_len"):
        ShardedDecodeEngine(sharded_engine.model, sharded_engine.rows,
                            sharded_engine.layout, slots=2,
                            cache_len=sharded_engine.model.max_len + 1)


def test_sharded_hlo_contract_pins_the_gather_schedule(sharded_engine):
    """Exactly one all-gather per bucket, pinned: the compiled step
    passes its own contract, FAILS the replicated path's 0-collective
    budget (an unbudgeted gather can never slip in silently), and a
    changed bucket count is a finding in either direction."""
    from distributedtensorflowexample_tpu.analysis.hlo_lint import (
        check_contract)
    hlo = sharded_engine.decode_hlo()
    B = sharded_engine.layout.num_buckets
    assert B >= 2                     # the schedule is a real schedule
    assert check_contract(hlo, SHARDED_DECODE_HLO_CONTRACT,
                          symbols={"B": B}) == []
    fs = check_contract(hlo, DECODE_HLO_CONTRACT)
    assert any(f.rule == "hlo-budget" and "all-gather" in f.message
               for f in fs), [f.message for f in fs]
    fs2 = check_contract(hlo, SHARDED_DECODE_HLO_CONTRACT,
                         symbols={"B": B + 1})
    assert any(f.rule == "hlo-budget" for f in fs2)


def test_promote_sharded_keeps_rows_and_serves_bitwise(tmp_path,
                                                       lm_state,
                                                       engine):
    """Sharded promotion from a TREE snapshot hands back rows (never a
    materialized tree on the serving path) that decode bitwise what
    the replicated promotion of the same snapshot decodes."""
    model, state = lm_state
    d = str(tmp_path / "snaps")
    init_lm_snapshot(d, SIZE, seed=0)
    spm = promote_sharded(d, SIZE, mesh_size=2, bucket_bytes=16 << 10)
    assert spm.source_layout == "tree"
    assert spm.layout.num_devices == 2
    seng = ShardedDecodeEngine(spm.model, spm.rows, spm.layout,
                               slots=2, cache_len=CACHE)
    pm = promote(d, SIZE)
    reng = DecodeEngine(pm.model, pm.params, slots=2, cache_len=CACHE)
    prompt = [9, 1, 1, 2, 3, 5, 8]
    want = [reng.prefill(0, np.asarray(prompt, np.int32), max_new=5)]
    got = [seng.prefill(0, np.asarray(prompt, np.int32), max_new=5)]
    for _ in range(4):
        want.append(int(reng.decode(busy=[0])[0]))
        got.append(int(seng.decode(busy=[0])[0]))
    assert got == want
    # a mesh that cannot exist is refused by name, not deadlocked
    with pytest.raises(ModeRefusal, match="--sharded_mesh"):
        promote_sharded(d, SIZE, mesh_size=len(jax.devices()) + 1)


# ---- speculative decoding (PR 17 tentpole b) -----------------------------

def test_spec_decode_is_bitwise_greedy_incl_mid_decode_admission(
        engine, draft_engine):
    """THE speculative acceptance: a disagreeing draft + batched
    verify emits exactly plain greedy's tokens — including for a
    request admitted mid-decode into a running speculative batch."""
    prompt_a, prompt_b = [10, 20, 30, 40, 50], [7, 7, 99]
    solo_a = _engine_greedy(engine, 0, prompt_a, 9)
    solo_b = _engine_greedy(engine, 1, prompt_b, 5)
    queue = RequestQueue(engine.vocab)
    spec = SpecDecoder(engine, draft_engine, k=3)
    batcher = ContinuousBatcher(engine, queue, slo_ms=0.0, spec=spec)
    ra = queue.submit(prompt_a, 9, rid="A")
    batcher.step()                    # admits A, first spec round
    assert not ra.done.is_set()       # A is mid-decode
    rb = queue.submit(prompt_b, 5, rid="B")
    while not (ra.done.is_set() and rb.done.is_set()):
        batcher.step()
    assert ra.outcome == "ok" and rb.outcome == "ok"
    assert ra.tokens == solo_a        # bitwise the greedy oracle
    assert rb.tokens == solo_b
    st = spec.stats()
    assert st["emitted"] == (9 - 1) + (5 - 1)   # first tokens = prefill
    assert st["rounds"] >= 2 and st["drafted"] >= 3 * st["rounds"] // 2
    assert 1.0 <= st["accept_len_mean"] <= 4.0


def test_spec_round_truncates_at_eos_like_greedy(engine, draft_engine):
    """A verify round may emit several tokens at once; an eos inside
    the window must truncate exactly where plain greedy stops — the
    round never hands out tokens greedy would not have produced."""
    prompt = [5, 9, 17, 3]
    ref = _engine_greedy(engine, 0, prompt, 8)
    eos = ref[4]

    def run(spec):
        queue = RequestQueue(engine.vocab)
        b = ContinuousBatcher(engine, queue, slo_ms=0.0, eos_id=eos,
                              spec=spec)
        r = queue.submit(prompt, 8, rid="E")
        while not r.done.is_set():
            b.step()
        return r.tokens

    expected = ref[:ref.index(eos) + 1]
    assert run(None) == expected
    assert run(SpecDecoder(engine, draft_engine, k=3)) == expected


def test_drain_completes_inflight_speculative_batch(engine,
                                                    draft_engine):
    """TERM under speculation: drain keeps drafting+verifying the
    in-flight batch to completion — outputs stay the greedy oracle's,
    and both engines' freed slots end parked."""
    prompts = {0: [3, 1, 4], 1: [2, 7, 1, 8], 2: [6, 6, 6]}
    solo = {s: _engine_greedy(engine, s, p, 7)
            for s, p in prompts.items()}
    queue = RequestQueue(engine.vocab)
    spec = SpecDecoder(engine, draft_engine, k=3)
    batcher = ContinuousBatcher(engine, queue, slo_ms=0.0, spec=spec)
    reqs = [queue.submit(p, 7, rid=f"d{s}")
            for s, p in sorted(prompts.items())]
    batcher.step()                    # admit all 3, one round
    assert not all(r.done.is_set() for r in reqs)
    batcher.drain()
    for s, r in enumerate(reqs):
        assert r.outcome == "ok" and r.tokens == solo[s]
    assert engine.positions.tolist() == [0] * engine.slots
    assert draft_engine.positions.tolist() == [0] * engine.slots


def test_spec_refusals_by_name(engine, draft_engine, lm_state):
    model, state = lm_state
    with pytest.raises(ValueError, match="k 0"):
        SpecDecoder(engine, draft_engine, k=0)
    with pytest.raises(ValueError, match="lockstep"):
        SpecDecoder(engine, DecodeEngine(model, state.params, slots=2,
                                         cache_len=CACHE), k=2)
    with pytest.raises(ModeRefusal, match="--spec_draft"):
        ContinuousBatcher(engine, RequestQueue(engine.vocab),
                          spec=SpecDecoder(engine, draft_engine, k=2),
                          sampler=Sampler(seed=0))


def test_spec_self_draft_full_acceptance_under_slot_churn(lm_state, engine):
    """The bench-shaped regression: MANY mixed-bucket requests churning
    through few slots, self-draft (draft == target params).  Two bugs
    hid here that the short solo oracles missed: (1) a separate
    single-query decode program whose bf16 logits could TIE-FLIP an
    argmax against the verify program's (decode is now the K == 1
    verify window — one program family), and (2) fully-accepted rounds
    (e == k+1) leaving one unwritten draft-cache row below the new
    frontier, collapsing acceptance within a few rounds.  With both
    fixed, a self-draft must match bitwise AND accept every proposal —
    acceptance below 100% here means the program family's numerics
    split again."""
    model, state = lm_state
    rng = np.random.default_rng(7)
    prompts = [(rng.integers(1, engine.vocab, size=int(
        rng.integers(4, 13))).astype(np.int32), 8) for _ in range(16)]

    def run(spec):
        queue = RequestQueue(engine.vocab)
        b = ContinuousBatcher(engine, queue, slo_ms=0.0, spec=spec)
        reqs = [queue.submit(p, m, rid=f"c{i}")
                for i, (p, m) in enumerate(prompts)]
        while any(not r.done.is_set() for r in reqs):
            b.step()
        return {r.rid: list(r.tokens) for r in reqs}

    greedy = run(None)
    # One self-draft engine for both k values: every admission prefills
    # the slot and parked rows are scatter-before-read, so leftover
    # state from the k=2 run cannot leak into k=4 — and the engines'
    # programs are shared process-wide anyway (module-level jit cache).
    draft = DecodeEngine(model, state.params, slots=engine.slots,
                         cache_len=CACHE)
    for k in (2, 4):
        spec = SpecDecoder(engine, draft, k=k)
        assert run(spec) == greedy, f"spec k={k} diverged from greedy"
        st = spec.stats()
        # Self-draft full acceptance is EXACT arithmetic: each request
        # needs 7 round tokens (prefill emits the first), so its rounds
        # emit min(k+1, remaining) until done — k=2: 3+3+1 with two
        # fully-accepted rounds (min(k, e) = 2, 2, 1 accepted), k=4:
        # 5+2 with one (4, 2).  Any shortfall = acceptance loss.
        assert st["emitted"] == 16 * 7
        per_req_accept = {2: 2 + 2 + 1, 4: 4 + 2}[k]
        assert st["accepted_draft"] == 16 * per_req_accept
        assert st["accept_len_mean"] == pytest.approx(
            {2: 7 / 3, 4: 7 / 2}[k], abs=1e-3)


# ---- batched prefill -----------------------------------------------------

def test_batched_prefill_matches_solo(engine):
    """One bucketed prefill_many over a burst produces per-slot exactly
    the solo prefill's token and cache (the continuation proves the
    cache: any cross-slot contamination diverges within a step)."""
    prompts = [[3, 1, 4, 1, 5], [9, 2, 6], [5, 3, 5, 8, 9, 7]]
    solo = [_engine_greedy(engine, 0, p, 4) for p in prompts]
    out = engine.prefill_many([(s, np.asarray(p, np.int32), 4)
                               for s, p in enumerate(prompts)])
    toks = [[int(out[s][0])] for s in range(3)]
    for _ in range(3):
        step = engine.decode(busy=[0, 1, 2])
        for s in range(3):
            toks[s].append(int(step[s]))
    assert toks == solo


# ---- sampling lanes ------------------------------------------------------

def test_sampler_lanes_are_deterministic_and_refuse_bad_knobs():
    rng = np.random.default_rng(0)
    logits = rng.normal(size=64).astype(np.float32)
    s1 = Sampler(temperature=0.8, top_k=5, seed=3)
    s2 = Sampler(temperature=0.8, top_k=5, seed=3)
    draws = [s1.sample("r1", i, logits) for i in range(16)]
    assert draws == [s2.sample("r1", i, logits) for i in range(16)]
    assert draws != [s1.sample("r2", i, logits) for i in range(16)]
    assert Sampler(top_k=1, seed=0).sample("x", 0, logits) == \
        int(np.argmax(logits))        # top-1 degenerates to greedy
    with pytest.raises(ValueError, match="--sample_temp"):
        Sampler(temperature=0.0)
    with pytest.raises(ValueError, match="--sample_top_k"):
        Sampler(top_k=-1)


def test_sampled_serving_is_deterministic_per_request_id(engine):
    """Same rid + same snapshot + same knobs → same tokens, regardless
    of admission order or slot placement (replayed runs agree)."""
    def run():
        queue = RequestQueue(engine.vocab)
        b = ContinuousBatcher(engine, queue, slo_ms=0.0,
                              sampler=Sampler(temperature=0.7,
                                              top_k=10, seed=5))
        r = queue.submit([8, 6, 7], 6, rid="fixed")
        while not r.done.is_set():
            b.step()
        return r.tokens

    a, b = run(), run()
    assert a == b and len(a) == 6


def test_sampler_refused_with_sharded_engine_by_name():
    class _NoLogitsSeam:                 # the sharded engine's shape
        slots = 2
    with pytest.raises(ModeRefusal, match="--sharded_mesh"):
        ContinuousBatcher(_NoLogitsSeam(), RequestQueue(16),
                          sampler=Sampler(seed=0))


# ---- prefix cache --------------------------------------------------------

def test_prefix_cache_full_and_partial_hits_bitwise(engine):
    """A full hit pays zero forward work, a partial hit pays only the
    suffix — both continue bitwise the cold path (the engine's masked
    pad rows make stored rows exact, not approximate)."""
    head = [11, 22, 33, 44, 55]
    ext = head + [66, 77]
    solo_head = _engine_greedy(engine, 0, head, 5)
    solo_ext = _engine_greedy(engine, 0, ext, 5)
    pc = PrefixCache(engine, capacity=8)

    def run(prompt, rid):
        queue = RequestQueue(engine.vocab)
        b = ContinuousBatcher(engine, queue, slo_ms=0.0,
                              prefix_cache=pc)
        r = queue.submit(prompt, 5, rid=rid)
        while not r.done.is_set():
            b.step()
        return r.tokens

    assert run(head, "cold") == solo_head
    assert pc.stats()["misses"] == 1 and pc.stats()["hits"] == 0
    assert run(head, "warm") == solo_head            # full hit
    assert pc.stats()["hits"] == 1
    assert run(ext, "extended") == solo_ext          # partial hit
    st = pc.stats()
    assert st["partial_hits"] == 1
    assert st["rows_reused"] == 2 * len(head)        # full 5 + partial 5
    assert st["entries"] == 2                        # head + ext
    with pytest.raises(ModeRefusal, match="--prefix_cache"):
        PrefixCache(object(), capacity=4)            # sharded-shaped


# ---- knobs, helpers, import direction ------------------------------------

def test_env_knob_defaults(monkeypatch):
    monkeypatch.delenv("SERVE_SLOTS", raising=False)
    monkeypatch.delenv("SERVE_SLO_MS", raising=False)
    assert serve_slots_default() == 4
    assert serve_slo_ms_default() == 0.0
    monkeypatch.setenv("SERVE_SLOTS", "7")
    monkeypatch.setenv("SERVE_SLO_MS", "125.5")
    assert serve_slots_default() == 7
    assert serve_slo_ms_default() == 125.5
    monkeypatch.setenv("SERVE_SLOTS", "bogus")
    assert serve_slots_default() == 4


def test_percentiles_and_drive_file(tmp_path):
    assert percentile([], 0.5) == 0.0
    tape = sorted([1.0, 2.0, 3.0, 4.0, 100.0])
    assert percentile(tape, 0.5) == 3.0
    assert percentile(tape, 0.99) == 100.0
    df = DriveFile(str(tmp_path / "res.jsonl"))
    assert df.done_ids() == {}
    df.append(3, [1, 2])
    df.append(0, [9])
    with open(df.path, "a") as f:
        f.write('{"id": 7, "tok')          # torn tail: id 7 re-issues
    assert df.done_ids() == {3: [1, 2], 0: [9]}
    # deterministic prompts: same id -> same bytes, ids differ
    a = make_prompt(17, 250, seed=3)
    assert np.array_equal(a, make_prompt(17, 250, seed=3))
    assert not np.array_equal(a, make_prompt(18, 250, seed=3)) \
        or len(a) != len(make_prompt(18, 250, seed=3))


# ---- the program's own spans and counters ---------------------------------

def _tape_since(t: float) -> list:
    """The tape's entries that closed after ``t`` (by the stamp, not by
    the ring's length: a full ring keeps its length), less the
    collector's: it strikes where it will."""
    return [e for e in obs_trace.tape() if e[2] >= t and e[0] != "host.gc"]


@pytest.fixture(scope="module")
def traced_run(lm_state):
    """A small greedy run driven by ``batcher.step()`` on an engine of
    its own (so the gauge counts this run's shapes): five requests of
    known prompt lengths through three slots.  Returns the finished
    requests, the tape entries the run wrote and the counters' moves."""
    model, state = lm_state
    engine = DecodeEngine(model, state.params, slots=3, cache_len=CACHE)
    queue = RequestQueue(engine.vocab)
    batcher = ContinuousBatcher(engine, queue, slo_ms=0.0)

    def counters():
        snap = obs_metrics.registry().snapshot()["counters"]
        return {k: snap.get(
            'serve_prefill_positions_total{kind="%s"}' % k, 0)
            for k in ("prompt", "pad")}

    before, t_before = counters(), time.monotonic()
    lengths = [3, 5, 8, 9, 2]
    # Boundary 1 admits 3, 5 and 8 tokens: all bucket 8, one [3, 8]
    # block.  The others wait for a slot: 9 tokens (bucket 16) and 2
    # (bucket 8) are admitted as the 2-token requests retire.
    reqs = [queue.submit(list(range(1, n + 1)), max_new,
                         rid=f"t{i}")
            for i, (n, max_new) in enumerate(zip(lengths,
                                                 [2, 2, 6, 3, 3]))]
    while not all(r.done.is_set() for r in reqs):
        assert batcher.step() > 0
    assert obs_trace.tape_dropped() == 0 or len(obs_trace.tape()) \
        == obs_trace.TAPE_LEN
    after = counters()
    return {"reqs": reqs, "engine": engine, "lengths": lengths,
            "tape": _tape_since(t_before),
            "moved": {k: after[k] - before[k] for k in after}}


def test_request_events_partition_its_life_under_one_rid(traced_run):
    for req in traced_run["reqs"]:
        mine = {e[0]: e for e in traced_run["tape"] if e[4] == req.rid}
        assert set(mine) == {"serve_queue", "serve_prefill",
                             "serve_decode"}, (req.rid, sorted(mine))
        q, p, d = (mine[n] for n in ("serve_queue", "serve_prefill",
                                     "serve_decode"))
        assert q[1] == req.submit_t and d[2] == pytest.approx(
            req.done_t, abs=1e-9)
        # end to start: nothing of the request's life is left out or
        # counted twice
        assert q[2] == pytest.approx(p[1], abs=1e-9)
        assert p[2] == pytest.approx(d[1], abs=1e-9)
        assert p[1] == req.prefill_t and d[1] == req.first_token_t


def test_request_stamps_are_ordered_and_admit_t_keeps_its_meaning(
        traced_run):
    for req in traced_run["reqs"]:
        assert req.submit_t <= req.prefill_t <= req.first_token_t \
            <= req.done_t
        # admit_t is still stamped together with the first token (the
        # benchmark finds the admitting prefill by it)
        assert req.admit_t == req.first_token_t
    # the late admissions waited in the queue, and the tape says so
    waits = {e[4]: e[2] - e[1] for e in traced_run["tape"]
             if e[0] == "serve_queue"}
    assert waits["t3"] > waits["t0"] and waits["t4"] > waits["t0"]


def test_every_engine_span_lies_inside_a_serve_step(traced_run):
    tape = traced_run["tape"]
    steps = [e for e in tape if e[0] == "serve.step"]
    inner = [e for e in tape if e[0].startswith(("engine.", "serve."))
             and e[0] != "serve.step"]
    assert steps and {e[0] for e in inner} == {
        "serve.admit", "serve.retire", "engine.prefill.pack",
        "engine.prefill.dispatch", "engine.prefill.readback",
        "engine.decode.dispatch", "engine.decode.readback",
        "engine.decode.wait", "engine.decode.account"}
    for e in inner:
        assert e[3] == ("engine.decode.readback"
                        if e[0] == "engine.decode.wait" else "serve.step"), e
        assert any(s[1] <= e[1] and e[2] <= s[2] for s in steps), e
    # one decode dispatch, read-back (with its wait) and account, one
    # admit and one retire a step
    for name in ("engine.decode.dispatch", "engine.decode.readback",
                 "engine.decode.wait", "engine.decode.account",
                 "serve.admit", "serve.retire"):
        assert sum(e[0] == name for e in tape) == len(steps)
    # steps do not overlap, and the tape closes them in order
    assert all(a[2] <= b[1] for a, b in zip(steps, steps[1:]))


#: What of a decode-only ``serve.step`` its named pieces may leave
#: uncovered: ``_busy()``, the step-time EWMA, a counter and six spans'
#: own stamps are tens of microseconds; the room is for a loaded host.
STEP_HOLE_S = 1e-3


def test_a_decode_only_step_holds_its_pieces_in_order(traced_run):
    tape = traced_run["tape"]
    holes = []
    for step in (e for e in tape if e[0] == "serve.step"):
        held = sorted((e for e in tape if e is not step and e[4] is None
                       and step[1] <= e[1] and e[2] <= step[2]),
                      key=lambda e: (e[1], -e[2]))
        if any(e[0].startswith("engine.prefill.") for e in held):
            continue
        # a step handed over (0, 1 or 2 of them: the one in flight needs
        # none, the first to run ahead brings its successor) is accounted
        # while the device works, then ONE step is read
        handed = [e for e in held if e[0] == "engine.decode.dispatch"]
        assert [e[0] for e in held] == [
            "serve.admit",
            *["engine.decode.dispatch", "engine.decode.account"]
            * len(handed),
            "engine.decode.readback", "engine.decode.wait", "serve.retire"]
        assert len(handed) <= 2
        admit, *_, readback, wait, retire = held
        # each ends before the next begins; the wait alone is nested,
        # at the read-back's head
        flat = [e for e in held if e is not wait]
        for a, b in zip(flat, flat[1:]):
            assert a[2] <= b[1], (a, b)
        assert wait[3] == "engine.decode.readback" \
            and readback[1] <= wait[1] and wait[2] <= readback[2]
        assert wait[1] - readback[1] < wait[2] - wait[1] + 1e-4
        assert all(e[3] == "serve.step" for e in flat)
        holes.append((step[2] - step[1]) - sum(
            e[2] - e[1] for e in held if e is not wait))
    assert len(holes) >= 3
    assert sorted(holes)[len(holes) // 2] < STEP_HOLE_S, holes


def test_the_spans_change_no_token(traced_run, lm_state):
    model, state = lm_state
    for req, n in zip(traced_run["reqs"], traced_run["lengths"]):
        assert req.tokens == _greedy_reference(
            model, state.params, list(range(1, n + 1)), len(req.tokens),
            got=req.tokens), req.rid


def test_the_batcher_watches_the_collector_once(engine):
    import gc
    for _ in range(2):
        ContinuousBatcher(engine, RequestQueue(engine.vocab), slo_ms=0.0)
    assert gc.callbacks.count(obs_trace._on_gc) == 1


def test_an_idle_poll_leaves_no_span_on_the_tape(engine):
    """``run()`` polls an empty queue every 20 ms; those boundaries stay
    off the ring (it is for the busy periods) — and a boundary that only
    rejected a request is work, and stays on it."""
    q = RequestQueue(engine.vocab)
    batcher = ContinuousBatcher(engine, q, slo_ms=0.0)
    t_before = time.monotonic()
    for _ in range(3):
        assert batcher.step() == 0
    assert _tape_since(t_before) == []
    q.submit(list(range(1, CACHE + 8)), 4, rid="too-long")
    assert batcher.step() == 0 and batcher.rejected[-1].rid == "too-long"
    assert [e[0] for e in _tape_since(t_before)] == [
        "serve.admit", "serve.step"]


def test_prefill_position_counters_equal_a_hand_count(traced_run):
    # [3, 5, 8] in one [3, 8] block; then 9 -> [1, 16] and 2 -> [1, 8]
    lengths = traced_run["lengths"]
    assert traced_run["moved"]["prompt"] == sum(lengths) == 27
    assert traced_run["moved"]["pad"] == (3 * 8 - 16) + (16 - 9) + (8 - 2)
    packs = [e for e in traced_run["tape"]
             if e[0] == "engine.prefill.pack"]
    assert len(packs) == 3


def test_prefill_programs_gauge_counts_distinct_shapes(traced_run):
    engine = traced_run["engine"]
    assert engine._warm_buckets == {(8, 3), (16, 1), (8, 1)}
    gauge = obs_metrics.registry().snapshot()["gauges"][
        "serve_prefill_programs"]
    assert gauge["value"] == 3


def test_latency_gauges_read_a_window_not_the_whole_tape(engine,
                                                         monkeypatch):
    from distributedtensorflowexample_tpu.serving import queue as squeue
    monkeypatch.setattr(squeue, "GAUGE_WINDOW", 3)
    q = RequestQueue(engine.vocab)
    batcher = ContinuousBatcher(engine, q, slo_ms=0.0)
    # An early bad episode: completions that took "100 s".
    for i in range(2):
        r = squeue.Request(rid=f"old{i}", prompt=np.array([1]), max_new=1,
                           submit_t=0.0)
        r.finish("ok", 100.0)
        batcher.completed.append(r)
    reqs = [q.submit([1, 2, 3], 1, rid=f"new{i}") for i in range(4)]
    while not all(r.done.is_set() for r in reqs):
        batcher.step()
    p99 = obs_metrics.registry().snapshot()["gauges"][
        "serve_latency_p99_ms"]["value"]
    # read at the 5th and 6th completion over the newest 3: the old
    # episode has left the gauge (the whole tape would still say 100 s)
    assert p99 < 50_000, p99


def test_decode_step_carries_the_named_scopes(engine):
    """``attn``, ``head`` and ``cache_update`` name their operations in
    the decode program (metadata only: what a device trace is split
    by)."""
    from distributedtensorflowexample_tpu.serving.engine import (
        _decode_step)
    text = _decode_step.lower(
        engine.smodel, *engine.decode_args()).as_text(debug_info=True)
    for scope in ("attn", "head", "cache_update"):
        assert f"/{scope}/" in text, scope
    assert "block0.verify/attn/" in text
    assert "block0.verify/cache_update/" in text


# ---- the late read-back (PR 40) ---------------------------------------------

def _backlog_plan(seed: int, count: int, new_from: int, vocab: int) -> list:
    rng = np.random.default_rng([seed, 40])
    return [(rng.integers(1, vocab, size=int(rng.integers(2, 9))).tolist(),
             int(rng.integers(new_from, 9))) for _ in range(count)]


def _steps_by_readback() -> dict:
    got = obs_metrics.registry().snapshot()["counters"]
    return {k: got.get(f'serve_decode_steps_total{{readback="{k}"}}', 0)
            for k in ("late", "same_step")}


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_late_readback_by_count_is_the_synchronous_order_step_for_step(
        engine, serve_backlog, seed):
    """Nine requests behind three slots, every one ending by ``max_new``
    (known when its last step is handed over): the streams, ``step()``'s
    return at every boundary and the number of boundaries are the
    synchronous order's, every boundary but the first and those around a
    free slot read their step late, and ``engine.positions`` is exact
    for every step handed over."""
    plan = _backlog_plan(seed, 9, 2, engine.vocab)
    sync = serve_backlog(engine, plan, run_ahead=False)
    before = _steps_by_readback()
    late = serve_backlog(engine, plan, run_ahead=True)
    moved = {k: v - before[k] for k, v in _steps_by_readback().items()}
    assert [r.tokens for r in late.reqs] == [r.tokens for r in sync.reqs]
    assert [len(r.tokens) for r in late.reqs] == [m for _, m in plan]
    assert [row.n for row in late.rows] == [row.n for row in sync.rows]
    assert not any(row.in_flight for row in sync.rows)
    assert sync.moved.get(
        'serve_decode_steps_total{readback="late"}', 0) == 0
    assert moved["late"] >= len(late.rows) // 2
    assert moved["late"] + moved["same_step"] == len(late.rows)
    for run in (sync, late):
        lengths = {id(r): len(p) for r, (p, _) in zip(run.reqs, plan)}
        for row in run.rows:
            # a slot's position: its request's prompt and every token it
            # was issued but the newest, which no step has been fed yet
            assert row.positions.tolist() == [
                0 if req is None else lengths[id(req)] + issued - 1
                for req, issued in row.owners]
            # at most one token a boundary to a request that had any; a
            # request admitted at this boundary may add its first
            assert all(g <= (1 if h else 2)
                       for g, h in zip(row.got, row.had))
    assert not engine.positions.any()
    assert engine.settle() is None


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_late_readback_stops_at_an_eos_and_delivers_nothing_after_it(
        engine, serve_backlog, seed):
    """Requests that end at an EOS (known only when the token is read,
    with the next step in flight) mixed with ones that end by count or
    on their prefill's token: request for request the synchronous
    order's streams, the EOS last where there is one."""
    plan = _backlog_plan(seed, 10, 3, engine.vocab)
    plan.insert(4, ([7, 8, 9], 1))
    free = serve_backlog(engine, plan, run_ahead=False)
    # a token that some stream holds before its end
    inner = [t for r in free.reqs for t in r.tokens[1:-1]]
    eos = max(set(inner), key=inner.count)
    sync = serve_backlog(engine, plan, run_ahead=False, eos_id=eos)
    late = serve_backlog(engine, plan, run_ahead=True, eos_id=eos)
    assert [r.tokens for r in late.reqs] == [r.tokens for r in sync.reqs]
    cut = [r for r, (_, m) in zip(late.reqs, plan) if len(r.tokens) < m]
    assert cut and all(r.tokens[-1] == eos for r in cut)
    assert all(eos not in r.tokens[:-1] for r in late.reqs)
    # one of them at least met its EOS with the next step in flight
    ended = {i: next(b for b, row in enumerate(late.rows) if row.done[i])
             for i, r in enumerate(late.reqs)
             if any(r is c for c in cut)}
    assert any(late.rows[b].in_flight for b in ended.values())
    for row in late.rows:
        assert all(g <= (1 if h else 2) for g, h in zip(row.got, row.had))
    assert not engine.positions.any()
    assert engine.settle() is None


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_late_readback_with_arrivals_between_boundaries(
        engine, serve_backlog, seed):
    """Requests that arrive while others decode, none to three a
    boundary, so the lag goes from 0 to 1 and back as slots fill and
    free; odd seeds end some at an EOS: request for request the
    synchronous order's streams, at most one decode token a boundary,
    nothing left in flight."""
    rng = np.random.default_rng([seed, 41])
    plan = _backlog_plan(seed + 10, 14, 1, engine.vocab)
    arrivals = np.cumsum(rng.integers(0, 4, size=len(plan))).tolist()
    eos = None
    if seed % 2:
        free = serve_backlog(engine, plan, run_ahead=False,
                             arrivals=arrivals)
        inner = [t for r in free.reqs for t in r.tokens[1:-1]]
        eos = max(set(inner), key=inner.count)
    sync = serve_backlog(engine, plan, run_ahead=False, eos_id=eos,
                         arrivals=arrivals)
    late = serve_backlog(engine, plan, run_ahead=True, eos_id=eos,
                         arrivals=arrivals)
    assert [r.tokens for r in late.reqs] == [r.tokens for r in sync.reqs]
    lags = [row.in_flight for row in late.rows]
    assert True in lags and False in lags[:-1]
    for row in late.rows:
        assert all(g <= (1 if h else 2) for g, h in zip(row.got, row.had))
    assert not engine.positions.any() and engine.settle() is None


def test_drain_reads_the_step_in_flight(engine):
    queue = RequestQueue(engine.vocab)
    batcher = ContinuousBatcher(engine, queue, slo_ms=0.0)
    reqs = [queue.submit([1 + i, 2, 3], 6, rid=f"d{i}") for i in range(5)]
    for _ in range(2):
        batcher.step()
    assert batcher._flying is not None      # three slots, two queued
    batcher.drain()
    assert batcher._flying is None and engine.settle() is None
    assert [r.outcome for r in reqs] == ["ok"] * 3 + ["drained"] * 2
    assert all(len(r.tokens) == 6 for r in reqs[:3])
    assert not engine.positions.any()


@pytest.mark.parametrize("queued, reads", [
    (2, ["same_step"]),                         # a slot stays free
    (3, ["same_step", "late", "late", "late", "late"]),
    (5, ["same_step", "late", "late", "late", "late"])])
def test_a_step_is_handed_over_ahead_only_while_no_slot_is_free(
        engine, queued, reads):
    """By the slots, not by a flag: with a free slot and an empty queue
    every step is read at the boundary that handed it over (a request
    arriving mid-step is prefilled at the next boundary); with every
    slot busy every boundary after the first reads its step late."""
    queue = RequestQueue(engine.vocab)
    batcher = ContinuousBatcher(engine, queue, slo_ms=0.0)
    for i in range(queued):
        queue.submit([5 + i, 6], 7, rid=f"q{i}")
    seen = []
    for _ in range(5):
        before = _steps_by_readback()
        assert batcher.step() == min(queued, engine.slots)
        seen += [k for k, v in _steps_by_readback().items()
                 if v > before[k]]
    assert seen == reads * (5 // len(reads))
    assert (batcher._flying is not None) == (queued >= engine.slots)
    batcher.drain()


@pytest.mark.parametrize("what", ["sampler", "spec", "prefix_cache",
                                  "sharded"])
def test_whoever_owns_a_steps_tokens_reads_it_at_its_own_boundary(
        request, engine, what):
    """A sampler draws each token from the step's logits, a draft's
    verify owns the window, a prefix cache extends suffixes through the
    verify program and the sharded engine has no seam: every slot busy,
    and still no step is read late."""
    kw, eng = {}, engine
    if what == "sampler":
        kw["sampler"] = Sampler(temperature=0.8, top_k=8, seed=3)
    elif what == "spec":
        kw["spec"] = SpecDecoder(
            engine, request.getfixturevalue("draft_engine"), k=2)
    elif what == "prefix_cache":
        kw["prefix_cache"] = PrefixCache(engine)
    else:
        eng = request.getfixturevalue("sharded_engine")
    queue = RequestQueue(eng.vocab)
    batcher = ContinuousBatcher(eng, queue, slo_ms=0.0, **kw)
    reqs = [queue.submit([3 + i, 4, 5], 5, rid=f"o{i}")
            for i in range(eng.slots + 2)]
    before = _steps_by_readback()
    while not all(r.done.is_set() for r in reqs):
        assert batcher.step() > 0
        assert batcher._flying is None
    moved = {k: v - before[k] for k, v in _steps_by_readback().items()}
    assert moved["late"] == 0 and moved["same_step"] > 0
    assert all(len(r.tokens) == 5 for r in reqs)


def test_obs_never_imports_serving():
    """The import direction is one-way: serving/ may use obs/ (metrics,
    ledger), obs/ must stay stdlib-only and serving-free — the
    graftlint import-graph proof guards the jax half; this guards the
    package-internal half."""
    import ast
    obs_dir = os.path.join(REPO, "distributedtensorflowexample_tpu",
                           "obs")
    for name in sorted(os.listdir(obs_dir)):
        if not name.endswith(".py"):
            continue
        with open(os.path.join(obs_dir, name)) as f:
            tree = ast.parse(f.read())
        for node in ast.walk(tree):
            mods = []
            if isinstance(node, ast.Import):
                mods = [a.name for a in node.names]
            elif isinstance(node, ast.ImportFrom) and node.module:
                mods = [node.module]
            assert not any(".serving" in m or m == "serving"
                           for m in mods), \
                f"obs/{name} imports serving ({mods})"
