"""The serving seam (models/served_lm.py: ServedLM, and DecodeEngine's side
of it), once for the five served architectures: what is true of a model
because it is the shell, whatever its blocks compute.  The architectures'
own tests — the reference comparisons, the tolerance controls, routing,
kernels, ladders, the cells' configurations, the CLI — are in
test_afmoe.py, test_qwen3_next.py, test_bailing_hybrid.py,
test_granitemoehybrid.py and test_kimi_k2.py; the tiny configurations and
the helpers all six files share are in served_families.py."""

import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import served_families as fam
from distributedtensorflowexample_tpu.config import CONFIG_MODEL_TYPES
from distributedtensorflowexample_tpu.models import (
    build_model, build_model_from_config)
from distributedtensorflowexample_tpu.models.served_lm import (
    CACHE_KINDS, CacheLayer, ServedLM, rms_norm)
from distributedtensorflowexample_tpu.obs import metrics as obs_metrics
from distributedtensorflowexample_tpu.refusal import ModeRefusal
from distributedtensorflowexample_tpu.serving import engine as eng
from distributedtensorflowexample_tpu.serving.engine import (
    DECODE_HLO_CONTRACT, SERVING_SEAM, DecodeEngine)

STATE = ("qwen3_next", "bailing_hybrid", "granitemoehybrid")
#: The longest cache these tests serve a family with (afmoe's tiny model
#: has 128 positions; kimi_k2's ladder begins past 256).
CACHE_LEN = {"afmoe": 128, "qwen3_next": 256, "bailing_hybrid": 256,
             "granitemoehybrid": 256, "kimi_k2": 128}
#: How far one prompt's last logits, and its states and rows, may lie
#: apart between two buckets (float32, the summation's order only).
#: granite divides its logits by 16: they lie within +-0.1 and read 1e-8,
#: so they are held to test_granitemoehybrid.py's TOL.
LOGIT_TOL = {"afmoe": 2e-5, "qwen3_next": 2e-5, "bailing_hybrid": 2e-5,
             "granitemoehybrid": 2e-7, "kimi_k2": 2e-5}
STATE_TOL = {"afmoe": 2e-5, "qwen3_next": 2e-5, "bailing_hybrid": 2e-5,
             "granitemoehybrid": 1e-5, "kimi_k2": 2e-5}


def _engine(family, **kw):
    return DecodeEngine(fam.model(family), fam.params(family), slots=2,
                        cache_len=32, **kw)


def _gauge(series: str) -> float:
    got = obs_metrics.registry().snapshot()["gauges"].get(series)
    return got["value"] if isinstance(got, dict) else got


# ---- one statement of a layer's cache, and what follows from it -----------

@pytest.mark.parametrize("family", fam.FAMILIES)
def test_the_layer_statement_is_what_the_cache_holds(family):
    """``cache_rows``, ``init_cache`` and ``cache_slot_bytes`` are one
    statement (``cache_layers``) read three ways, on an unbound module;
    the engine's ``serve_cache_bytes{kind}`` add up to its cache."""
    model = fam.model(family)
    layers = model.cache_layers(64)
    assert model.n_layers == len(layers) == \
        fam.TINY[family]["num_hidden_layers"]
    assert model.cache_rows(64) == tuple((la.kind, la.rows) for la in layers)
    assert {la.kind for la in layers} <= set(CACHE_KINDS)
    ck, cv = model.init_cache(3, 64)
    for la, k, v, held in zip(layers, ck, cv, model.cache_slot_bytes(64)):
        assert (k.shape, k.dtype) == ((3, *la.k[0]), la.k[1])
        shape, dtype = ((0,), jnp.float32) if la.v is None else (
            (3, *la.v[0]), la.v[1])
        assert (v.shape, v.dtype) == (shape, dtype)
        assert 3 * held == k.nbytes + v.nbytes
        assert (la.rows == 0) == (la.kind == "state")
    engine = DecodeEngine(model, fam.params(family), slots=3, cache_len=64)
    by_kind = {la.kind for la in layers}
    assert sum(_gauge('serve_cache_bytes{kind="%s"}' % k)
               for k in by_kind) == engine.cache_bytes


def test_the_bytes_by_kind_are_what_the_engine_reckoned_before():
    """``ServingLM`` and ``AfmoeLM`` stated no ``cache_slot_bytes`` before
    the shell and the engine split their caches' bytes by rows: the same
    numbers (written here from the parent's gauges) by the one way left."""
    lm = build_model("lm_tiny")
    p = lm.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))[
        "params"]
    engine = DecodeEngine(lm, p, slots=2, cache_len=16)
    assert engine.cache_bytes == 16384
    assert _gauge('serve_cache_bytes{kind="full"}') == 16384
    engine = DecodeEngine(fam.model("afmoe"), fam.params("afmoe"), slots=3,
                          cache_len=64)
    assert engine.cache_bytes == 33792
    assert _gauge('serve_cache_bytes{kind="full"}') == 24576
    assert _gauge('serve_cache_bytes{kind="window"}') == 9216


# ---- a family built from the shell and a ten-line block --------------------

class _ToyBlock(nn.Module):
    """Layer 0 adds the mean of the positions so far, read off K/V rows
    that hold them; layer 1 their sum, kept as a state beside the last
    input (a convolution's state in small)."""
    full: bool

    def sequence(self, x, lengths):
        B, T, _ = x.shape
        lengths = jnp.full((B,), T) if lengths is None else lengths
        seen = jnp.cumsum(x, axis=1)
        if self.full:
            mean = seen / (jnp.arange(T) + 1.0)[None, :, None]
            return x + mean, (x[:, :, None], x[:, :, None]), _NO_STATS
        last = (lengths - 1)[:, None, None]
        return x + seen, (jnp.take_along_axis(seen, last, axis=1)[:, 0],
                          jnp.take_along_axis(x, last, axis=1)), _NO_STATS

    def step(self, x, ck, cv, pos):
        if self.full:
            s = jnp.arange(len(pos))
            ck, cv = ck.at[s, pos].set(x[:, None]), cv.at[s, pos].set(
                x[:, None])
            seen = jnp.arange(ck.shape[1])[None] <= pos[:, None]
            mean = (cv[:, :, 0] * seen[..., None]).sum(1) / (pos + 1.0)[:,
                                                                       None]
            return x + mean, ck, cv, _NO_STATS
        live = (pos > 0)[:, None]
        ck = jnp.where(live, ck + x, ck)
        return x + ck, ck, jnp.where(live[..., None], x[:, None], cv), \
            _NO_STATS


_NO_STATS = np.zeros(4, np.int32)


@dataclasses.dataclass(frozen=True)
class _ToyDims:
    vocab_size: int = 31
    d_model: int = 8
    max_len: int = 64
    eps: float = 1e-5
    init_std: float = 0.5


class _ToyLM(ServedLM):
    """All of a family: its blocks, its layers' statement."""

    def make_block(self, i):
        return _ToyBlock(i == 0, name=f"block{i}")

    def cache_layers(self, cache_len):
        kv = ((cache_len, 1, 8), self.dtype)
        return (CacheLayer("full", cache_len, kv, kv),
                CacheLayer("state", 0, ((8,), jnp.float32),
                           ((1, 8), self.dtype)))


def test_a_family_of_two_layers_built_from_the_shell_is_served():
    """One ``full`` and one ``state`` layer behind a ten-line block: the
    engine serves it (prefill in a bucket, then token steps, are the
    training-shape forward's logits at every position), and its three
    readings of the statement agree."""
    model = _ToyLM(_ToyDims(), jnp.float32, jnp.float32)
    toks = np.random.default_rng(0).integers(0, 31, (1, 20)).astype(np.int32)
    p = model.init(jax.random.PRNGKey(1), jnp.asarray(toks))["params"]
    assert set(p) == {"embed", "norm_f", "head"}
    assert model.cache_rows(16) == (("full", 16), ("state", 0))
    assert model.cache_slot_bytes(16) == (2 * 16 * 8 * 4, 8 * 4 + 8 * 4)
    ck, cv = jax.eval_shape(lambda: model.init_cache(3, 16))
    assert [x.shape for x in ck] == [(3, 16, 1, 8), (3, 8)]
    assert [x.shape for x in cv] == [(3, 16, 1, 8), (3, 1, 8)]
    want = np.asarray(model.apply({"params": p}, jnp.asarray(toks)))[0]
    engine = DecodeEngine(model, p, slots=2, cache_len=32)
    assert engine.buckets == (8, 16, 32)        # below a tile: the engine's
    assert engine.layers_without_rows_by_position == 1
    (_, got), = engine.prefill_many([(1, toks[0, :11], 1)]).values()
    assert np.abs(got - want[10]).max() < 1e-5
    for t in range(11, 20):
        engine.set_slot(1, toks[0, t], t)
        got = engine.decode_logits(busy=[1])[1]
        assert np.abs(got - want[t]).max() < 1e-5, t


@pytest.mark.parametrize("member", SERVING_SEAM)
def test_a_module_that_lacks_a_member_of_the_seam_is_refused_by_its_name(
        member):
    """Everything ``DecodeEngine`` asks of a serving module is a member of
    ``ServedLM``, and of GPT-2's ``ServingLM``; a module without one of
    them is refused when the engine is built, by the member's name."""
    assert hasattr(ServedLM, member) and hasattr(eng.ServingLM, member)

    class Lacking:
        vocab_size, max_len = 31, 64

        def serving_module(self):
            return self

    for name in SERVING_SEAM:
        if name != member:
            setattr(Lacking, name, getattr(ServedLM, name))
    with pytest.raises(TypeError, match=f"lacks {member} "):
        DecodeEngine(Lacking(), {}, slots=2, cache_len=16)


# ---- a slot's cache between requests ----------------------------------------

@pytest.mark.parametrize("family", fam.FAMILIES)
def test_a_reused_slot_serves_what_a_fresh_engine_serves_bitwise(family):
    """Slot 1 serves a 40-token prompt for 25 steps, is parked, and is then
    given another request: admission overwrites the states the first left
    (nothing masks a stale state) and masks its rows past the second's
    frontier, so the second request's logits are, bit for bit, a fresh
    engine's."""
    sequences, n = fam.sequences(family), CACHE_LEN[family]
    engine = DecodeEngine(fam.model(family), fam.params(family), slots=3,
                          cache_len=n)
    engine.prefill_many([(1, sequences[0, :40], 1)])
    for _ in range(25):
        engine.decode_logits(busy=[1])
    engine.set_slot(1, 0, 0)                        # retired: parked
    # Parked slots compute with everyone else.
    assert np.isfinite(engine.decode_logits(busy=[])).all()
    engine.prefill_many([(1, sequences[1, :13], 1)])
    got = np.stack([engine.decode_logits(busy=[1])[1] for _ in range(20)])
    assert np.array_equal(got, fam.serve_alone(family, sequences[1, :13], 20,
                                               1, n))


@pytest.mark.parametrize("family", fam.FAMILIES)
def test_a_request_admitted_mid_decode_serves_what_it_serves_alone(family):
    """Slot 0 is 9 steps into a request when slot 2 is admitted: slot 2's
    logits are bitwise those of an engine that serves it alone, and slot
    0's do not notice."""
    sequences, n = fam.sequences(family), CACHE_LEN[family]
    engine = DecodeEngine(fam.model(family), fam.params(family), slots=3,
                          cache_len=n)
    engine.prefill_many([(0, sequences[0, :17], 1)])
    first = [engine.decode_logits(busy=[0])[0] for _ in range(9)]
    engine.prefill_many([(2, sequences[2, :33], 1)])
    both = [engine.decode_logits(busy=[0, 2]) for _ in range(12)]
    assert np.array_equal(
        np.stack([b[2] for b in both]),
        fam.serve_alone(family, sequences[2, :33], 12, 2, n))
    assert np.array_equal(
        np.stack(first + [b[0] for b in both]),
        fam.serve_alone(family, sequences[0, :17], 21, 0, n))


@pytest.mark.parametrize("family", STATE)
def test_parked_slots_keep_their_state_and_stay_finite(family):
    """A parked slot (position 0) computes with everyone else — the
    program has one shape — but neither decays nor writes its state."""
    sequences = fam.sequences(family)
    engine = DecodeEngine(fam.model(family), fam.params(family), slots=2,
                          cache_len=64)
    engine.prefill_many([(0, sequences[0, :9], 1), (1, sequences[1, :9], 1)])
    engine.set_slot(1, 0, 0)
    before = fam.state_leaves(engine, 1)
    for _ in range(5):
        logits = engine.decode_logits(busy=[0])
        assert np.isfinite(logits).all()
    for a, b in zip(before, fam.state_leaves(engine, 1)):
        assert np.array_equal(a, b)


def _rows(engine, slot, n):
    """The first ``n`` positions' rows of ``slot`` in every layer that
    holds rows (all of a ring shorter than that), K and V."""
    out = []
    for i, (kind, rows) in enumerate(
            engine.smodel.cache_rows(engine.cache_len)):
        for c in (engine._ck, engine._cv):
            if rows and c[i].size:
                per = c[i].shape[1] // rows         # 1, or Hkv of flat rows
                out.append(np.asarray(c[i][slot, :min(n, rows) * per]))
    return out


@pytest.mark.parametrize("family", fam.FAMILIES)
def test_one_prompt_in_two_buckets_and_in_a_mixed_batch_leaves_one_state(
        family):
    """A 21-token prompt alone in its bucket of 32, in a bucket of 128
    (an engine whose ladder starts there), and beside a 30-token prompt
    in one batch: the same last logits, the same recurrent and
    convolution states (padding neither decays nor writes) and the same
    21 rows (a ring's last positions)."""
    sequences, n = fam.sequences(family), CACHE_LEN[family]
    logit_tol, tol = LOGIT_TOL[family], STATE_TOL[family]
    prompt = sequences[0, :21]
    make = lambda **kw: DecodeEngine(fam.model(family), fam.params(family),
                                     slots=2, cache_len=n, **kw)
    alone = make()
    (_, want), = alone.prefill_many([(1, prompt, 1)]).values()
    wide = make(prefill_smallest=128)
    assert wide.bucket_for(21, 1) == 128
    (_, got), = wide.prefill_many([(1, prompt, 1)]).values()
    assert np.abs(got - want).max() < logit_tol
    mixed = make()
    out = mixed.prefill_many([(0, sequences[3, :30], 1), (1, prompt, 1)])
    assert (32, 2) in mixed._warm_buckets
    assert np.abs(out[1][1] - want).max() < logit_tol
    held = lambda e: fam.state_leaves(e, 1) + _rows(e, 1, 21)
    assert len(held(alone)) == sum(
        1 if la.v is None else 2
        for la in fam.model(family).cache_layers(n))
    for engine in (wide, mixed):
        for a, b in zip(held(engine), held(alone)):
            assert np.abs(a - b).max() < tol


# ---- what refuses, and what holds -------------------------------------------

#: What the refusal calls the layers of each family that are not
#: ``cache_len`` K/V rows by position.
REFUSED_AS = {"afmoe": "window-attention layers .3 of this model's 4",
              "qwen3_next": "recurrent-state layers .6 of this model's 8",
              "bailing_hybrid": "recurrent-state layers .6 of this model's 7",
              "granitemoehybrid": "recurrent-state layers .5 of this model's "
                                  "6",
              "kimi_k2": "latent-attention layers .5 of this model's 5"}


@pytest.mark.parametrize("what", ["PrefixCache", "SpecDecoder",
                                  "ShardedDecodeEngine", "read_rows",
                                  "write_rows", "verify_step", "extend"])
@pytest.mark.parametrize("family", fam.FAMILIES)
def test_what_reads_or_rolls_back_rows_by_position_refuses_the_family_by_name(
        family, what):
    from distributedtensorflowexample_tpu.serving.prefix import PrefixCache
    from distributedtensorflowexample_tpu.serving.sharded import (
        ShardedDecodeEngine)
    from distributedtensorflowexample_tpu.serving.spec import SpecDecoder
    engine = _engine(family)
    calls = {
        "PrefixCache": lambda: PrefixCache(engine),
        "SpecDecoder": lambda: SpecDecoder(engine, _engine(family)),
        "ShardedDecodeEngine": lambda: ShardedDecodeEngine(
            engine.model, (), None),
        "read_rows": lambda: engine.read_rows(0, 4),
        "write_rows": lambda: engine.write_rows(0, None, None),
        "verify_step": lambda: engine.verify_step(
            np.zeros((2, 2), np.int32), np.zeros((2,), np.int32)),
        "extend": lambda: engine.extend(0, [1, 2], 3),
    }
    with pytest.raises(ModeRefusal, match=REFUSED_AS[family]):
        calls[what]()


#: By family: the ``state`` layers whose recurrent state XLA:CPU copies
#: before it updates it, the scopes of the decode program, and those a
#: prefill program has and has not.
PROGRAMS = {
    "afmoe": ((), ("moe.route", "moe.experts", "moe.shared", "attn.window",
                   "attn.full"), ("attn.window", "attn.full"), ()),
    "qwen3_next": ((0, 1, 2, 4, 5, 6), (
        "gdn.proj", "gdn.conv", "gdn.step", "gdn.out", "attn.gated",
        "moe.route", "moe.experts", "moe.shared"),
        ("gdn.scan", "attn.gated"), ("gdn.step",)),
    "bailing_hybrid": ((0, 1, 2, 3, 4, 6), (
        "kda.proj", "kda.conv", "kda.step", "kda.out", "mla.q", "mla.kv",
        "mla.attend", "moe.route", "moe.experts", "moe.shared"),
        ("kda.scan", "mla.attend"), ("kda.step",)),
    "granitemoehybrid": ((0, 1, 2, 4, 5), (
        "ssm.proj", "ssm.conv", "ssm.step", "ssm.out", "attn.nope",
        "moe.route", "moe.experts", "moe.shared"),
        ("ssm.scan", "attn.nope"), ("ssm.step",)),
    "kimi_k2": ((), (
        "rope.yarn", "mla.q_down", "mla.q_up", "mla.kv", "mla.absorb",
        "mla.attend", "mla.out", "moe.route", "moe.experts", "moe.shared"),
        ("mla.attend", "rope.yarn", "mla.q_down"), ("mla.absorb",)),
}


@pytest.mark.parametrize("family", fam.FAMILIES)
def test_the_decode_program_honours_the_hlo_contract(family):
    """Donation aliased for every layer's rows and convolution state, no
    collective, nothing wider than f32; the jitted function's name has
    ``decode_step`` in it (the benchmark finds the program's device time
    by that) and the scopes the traced metrics read are there.  XLA:CPU,
    whose text this is, copies the recurrent states before it updates
    them (one finding each, and no other): the TPU's compiler updates
    them in place, which tests/test_tpu_compile.py holds it to at the
    cells' own sizes."""
    from distributedtensorflowexample_tpu.analysis.hlo_lint import (
        check_contract)
    copied, decode, prefill, never = PROGRAMS[family]
    engine = _engine(family)
    found = check_contract(engine.decode_hlo(), DECODE_HLO_CONTRACT)
    assert sorted(f.key for f in found) == [
        f"hlo-donation:serve_decode:copy:ck_{i}_.1" for i in copied]
    assert "decode_step" in eng._decode_step.__name__
    lower = lambda f, *a: f.lower(engine.smodel, engine.params, engine._ck,
                                  engine._cv, *a).as_text(debug_info=True)
    text = lower(eng._decode_step, *engine.decode_args()[3:])
    for scope in decode + ("cache_update", "head"):
        assert f"/{scope}/" in text, scope
    i32 = lambda *s: np.zeros(s, np.int32)
    text = lower(eng._prefill_bucketed, i32(1, 32), i32(1), i32(1) + 5)
    for scope in prefill + ("cache_update", "head"):
        assert f"/{scope}/" in text, scope
    for scope in never:
        assert f"/{scope}/" not in text, scope


@pytest.mark.parametrize("family", fam.FAMILIES)
def test_a_cache_longer_than_the_models_positions_is_refused(family):
    with pytest.raises(ModeRefusal, match="exceeds"):
        DecodeEngine(fam.model(family), fam.params(family), slots=2,
                     cache_len=fam.TINY[family]["max_position_embeddings"]
                     + 1)


@pytest.mark.parametrize("kind", CONFIG_MODEL_TYPES + ("gpt2",))
def test_the_tuple_of_model_types_is_the_builders_branches(kind):
    """``config.CONFIG_MODEL_TYPES`` (``serve_lm --model_config``'s help
    and the refusal's list) names what ``build_model_from_config`` builds,
    each a :class:`ServedLM`, and nothing it does not: a branch added
    without its name, or a name without its branch, fails here."""
    import inspect
    import re
    branches = re.findall(r'kind == "(\w+)"',
                          inspect.getsource(build_model_from_config))
    assert tuple(sorted(branches)) == CONFIG_MODEL_TYPES
    assert set(fam.FAMILIES) == set(CONFIG_MODEL_TYPES)
    if kind in CONFIG_MODEL_TYPES:
        assert isinstance(fam.model(kind), ServedLM)
        return
    with pytest.raises(ValueError, match=", ".join(CONFIG_MODEL_TYPES)):
        build_model_from_config({"model_type": kind})


@pytest.mark.parametrize("family", fam.FAMILIES)
def test_a_head_is_made_where_the_shells_logits_read_it(family):
    """A family that overrides ``_logits`` (granite: the embedding is the
    head) has said so once: the shell makes no ``head`` for it."""
    own = type(fam.model(family))._logits is not ServedLM._logits
    assert own == (family == "granitemoehybrid")
    assert ("head" in fam.params(family)) == (not own)
    assert {"embed", "norm_f"} <= set(fam.params(family))


def test_one_norm_and_one_table_of_kinds():
    """``rms_norm`` is the four families' norm written out, and every kind
    a family states has its way into a slot."""
    x = np.random.default_rng(1).normal(size=(3, 16)).astype(np.float32)
    g = np.linspace(0.5, 1.5, 16).astype(np.float32)
    want = x / np.sqrt((x.astype(np.float64) ** 2).mean(-1, keepdims=True)
                       + 1e-5) * g
    assert np.abs(np.asarray(rms_norm(jnp.asarray(x), jnp.asarray(g), 1e-5))
                  - want).max() < 1e-6
    assert set(CACHE_KINDS) == {"full", "window", "latent", "state"} == set(
        eng._NO_ROWS_BY_POSITION) | {"full"}
