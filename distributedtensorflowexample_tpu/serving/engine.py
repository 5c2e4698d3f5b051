"""The decode engine: one donate-and-reuse compiled step over a
preallocated per-slot KV-cache.

Training computes every position of every sequence each step; serving
generates one token per live request per step, so the arithmetic that
matters is (a) the prompt's one-time *prefill* (full causal attention,
exactly the training forward) and (b) the steady-state *decode* step: a
single-query attention against the K/V rows every earlier position
already produced.  This module keeps those rows resident and compiles
ONE decode step whose cache arguments are DONATED.

**The engine asks the model** for what it serves with
(``model.serving_module()``), and what it asks is one class:
``models/served_lm.py: ServedLM``, the shell the five served
architectures subclass (:data:`SERVING_SEAM` names its members here, and a
module that lacks one is refused by that name).  :class:`ServingLM`
below, GPT-2's, states the same members beside its stacked cache.  The
module states each layer's cache (``cache_rows(cache_len)`` -> ``(kind,
rows)`` a layer, ``cache_slot_bytes(cache_len)`` -> bytes a slot holds in
each) and allocates it (``init_cache(slots, cache_len)`` -> ``(ck, cv)``).
The engine holds that pair, donates it to every program and rebinds what
comes back; it never looks inside.  The four kinds of layer (``full``,
``window``, ``latent``, ``state``) are ``served_lm.py``'s to define; what
the engine knows of them: a ``state`` layer holds NO rows (``rows`` is 0)
but a state that is read AND written whole every step
(``serve_state_bytes_total``), and only ``full`` layers' rows can be read,
written or rolled back by position (:data:`_NO_ROWS_BY_POSITION`).
``serve_cache_bytes{kind}`` is ``cache_slot_bytes`` summed by kind.

Two layouts exist today:

* ``ServedLM``'s: one array a layer in each of ``ck`` and ``cv``, of the
  kind, shape and type the family's ``cache_layers`` states (``models/
  afmoe.py``: window rings beside full rows, where one shape for all layers
  would hold every window layer at ``cache_len`` rows; ``qwen3_next.py``,
  ``bailing_hybrid.py``, ``granitemoehybrid.py``: rows or latent rows in
  the attention layers, a recurrent state and a convolution's in the
  others; ``kimi_k2.py``: latent rows and nothing else).  What moves the
  stacked K/V pair refuses each of them by name.
* ``TransformerLM``'s (:class:`ServingLM`, every layer full): the two
  stacked ``[L, S, T, H, Dh]`` buffers it always had.  Donation aliases
  them (:data:`DECODE_HLO_CONTRACT`, checked on freshly compiled XLA:CPU
  text by graftlint's HLO front), but on the TPU the step still copies
  one whole layer's slice a layer a step (``ck[i]`` / ``jnp.stack``):
  ``copy_time_pct.backlog`` measures those copies at 52% of GPT-2's
  decode step (PERF.md section 5), ``copy_time_pct.mixed`` reads the new
  model's.  They are measured and open, not removed here: the layout is
  kept as it is because ``gpt2_124m.serve_backlog`` cannot yet measure an
  engine twice as fast (PERF.md section 7 (4)).

``read_rows`` / ``write_rows`` (the prefix cache) and ``verify_step`` /
``extend`` (speculation, suffix extension) assume the stacked layout and
a cache a rejected token can be rolled back from; on a ring a window's
writes overwrite rows that a rollback would need, and a recurrent state
that has taken a token cannot give it back at all.  For a model with
window or state layers they refuse by name
(:func:`refuse_cache_without_rows_by_position`), as do ``PrefixCache``,
``SpecDecoder`` and ``ShardedDecodeEngine``.

Numerics: the serving modules mirror ``models/transformer_lm.py``
sub-module for sub-module — same flax layers, same names (so a training
param tree binds directly), same explicit batched einsums with the same
contraction dims, softmax in f32, logits in f32.  A single-query decode
attends over masked cache rows whose ``-1e9`` scores underflow to
exactly 0.0 after the f32 exp, so the engine's greedy tokens are
token-for-token IDENTICAL to teacher-forced greedy decoding through the
training model (pinned in tests/test_serving.py) — and because every
slot's math is batch-dim-independent (einsums batch over slots,
LayerNorm is per-row), a request's output does not depend on what the
other slots are doing.  Continuous batching is therefore free of
cross-request contamination *by construction*, and the mid-decode
admission test asserts bitwise-equal output against a solo run.

Out-of-vocab requests never reach the device: admission refuses them by
name (``refusal.ModeRefusal``, serving/queue.py) — the training-side
NaN-poison exists to catch corruption mid-flight, but a live batch must
not be poisoned by one bad request.
"""

from __future__ import annotations

import functools
import os

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from distributedtensorflowexample_tpu.obs import metrics as obs_metrics
from distributedtensorflowexample_tpu.obs.trace import hot_span
from distributedtensorflowexample_tpu.refusal import ModeRefusal

_PREFILL_POSITIONS = obs_metrics.counter(
    "serve_prefill_positions_total", "token positions run through "
    "bucketed prefill, by kind (prompt = real tokens, pad = the rest of "
    "each [B, bucket] block)")
_POS_PROMPT = _PREFILL_POSITIONS.labels(kind="prompt")
_POS_PAD = _PREFILL_POSITIONS.labels(kind="pad")
_PREFILL_PROGRAMS = obs_metrics.gauge(
    "serve_prefill_programs", "distinct (bucket, B) prefill shapes — one "
    "compiled program each — of the engine that last met a cold one (one "
    "series a process: beside a draft engine, whichever wrote last)")

_CACHE_BYTES = obs_metrics.gauge(
    "serve_cache_bytes", "bytes of the engine's cache, by the kind of "
    "layer that holds them (full = cache_len K/V rows a slot, window = a "
    "ring of rows, latent = cache_len compressed rows a slot that every "
    "head shares, state = no rows but a recurrent state of a fixed size)")
_STATE_BYTES = obs_metrics.counter(
    "serve_state_bytes_total", "bytes of recurrent state the decode steps "
    "moved, summed over steps: state layers x bytes a slot x 2 (a state "
    "is read and written whole), over all the slots (an idle slot's is "
    "moved too: the program has one shape)")
_ROWS_READ = obs_metrics.counter(
    "serve_cache_rows_read_total", "cache rows the busy slots' queries "
    "attended, summed over decode steps and layers, by kind of layer (a "
    "query at position p reads p + 1 rows of a full or a latent layer and "
    "min(p + 1, rows) of a window layer's ring)")
_ROWS_FETCHED = obs_metrics.counter(
    "serve_cache_rows_fetched_total", "cache rows the decode steps' "
    "attention fetched, summed over steps and layers, by kind of layer: "
    "every slot's visible rows rounded up to the block the model's "
    "attention fetches by (decode_fetch_block), or every row the layer "
    "holds where it reads them all")
_MOE_PAIRS = obs_metrics.counter(
    "moe_pairs_total", "(token, expert) pairs the live tokens of prefill "
    "and decode programs routed, by where the expert is: held (computed "
    "here) or absent (another share's)")
_MOE_ROWS = obs_metrics.counter(
    "moe_rows_walked_total", "sorted rows the expert walks of prefill and "
    "decode programs handed to the grouped products (blocks walked x rows "
    "a block): what moe_pairs_total{where=held} fills")
_MOE_TOUCHED = obs_metrics.counter(
    "moe_experts_touched_total", "held experts that got at least one "
    "pair, summed over expert layers and decode steps")
_MOE_SLOTS = obs_metrics.counter(
    "moe_expert_slots_total", "held experts x expert layers, summed over "
    "decode steps: what moe_experts_touched_total is a share of")

#: The decode step's compiled-HLO contract (graftlint HLO front,
#: analysis/hlo_lint.py `serving_suite`): the KV-cache donation actually
#: aliased (require_alias) and no ENTRY copy of a donated cache buffer
#: (no_donated_copy) — together, the "steady-state decode reallocates
#: nothing cache-shaped" claim; no collective may appear (decode is a
#: single-device program today — an exact 0 budget makes ANY collective
#: a finding); no float wider than f32 anywhere (the f32
#: softmax/logits ceiling the training models hold).  The program it is
#: checked on is ``_decode_step_fn(module, params, ck, cv, tok, pos,
#: host) -> (tokens with the model's counts, tok', pos', ck', cv')``:
#: the two caches are what is donated and what the alias clauses are
#: about; the two ``[S]`` int32 vectors that stay on the device between
#: steps are not (a step in flight still owns the ones it was given).
DECODE_HLO_CONTRACT = {
    "mode": "serve_decode",
    "require_alias": True,
    "no_donated_copy": True,
    "collective_budget": {"all-reduce": 0},
    "dtype_ceiling": "f32",
}

#: What ``DecodeEngine`` asks of a serving module, by name: the members of
#: ``models/served_lm.py: ServedLM`` it reads (``verify`` is asked only of
#: a model whose every layer is ``full``; ``expert_slots`` only of one that
#: returns counts).
SERVING_SEAM = ("prefill_into", "decode", "cache_rows", "cache_slot_bytes",
                "init_cache", "prefill_buckets", "decode_fetch_block",
                "prefill_positions_max")

#: Default decode-slot count (SERVE_SLOTS overrides): enough concurrency
#: to show continuous batching on the CPU demo without compiling a wide
#: program tier-1 never fills.
DEFAULT_SLOTS = 4


def serve_slots_default() -> int:
    """``SERVE_SLOTS``: default concurrent decode slots for
    tools/serve_lm.py (the CLI flag overrides)."""
    try:
        return max(1, int(os.environ.get("SERVE_SLOTS", "")))
    except ValueError:
        return DEFAULT_SLOTS


class ServingBlock(nn.Module):
    """One decoder block with the training block's exact sub-module
    names (``ln1``/``qkv``/``attn_out``/``ln2``/``mlp_in``/``mlp_out``)
    so the training param tree binds unchanged, and two methods:
    :meth:`prefill` (full causal attention — the training forward's
    einsums verbatim, plus the K/V it produced) and :meth:`verify`
    (a K-token teacher-forced window against the slot's cache rows;
    plain decode is the K == 1 window).

    There is deliberately NO separate single-query decode method.  An
    earlier revision had one, and its einsums ("shd,sthd->sht") were a
    DIFFERENT compiled structure from the window's ("skhd,sthd->shkt")
    — close enough to agree almost always, far enough that on the bf16
    logit grid a near-tied argmax could flip between the two programs
    (observed: two tokens both at logit 2.59375, decode picking one,
    verify the other).  Speculative decoding's bitwise-greedy oracle
    cannot rest on two programs that may disagree at ties, so decode IS
    verify at K == 1: one program family, one numerics, and the only
    cross-shape assumption left — per-element stability when K is a
    pure batch dimension — is the same one bucketed prefill already
    relies on (B=1 vs B=3 prompts bitwise, pinned in tests)."""
    d_model: int
    n_heads: int
    d_ff: int
    dtype: jnp.dtype = jnp.bfloat16

    def setup(self):
        self.ln1 = nn.LayerNorm(dtype=self.dtype, name="ln1")
        self.qkv = nn.Dense(3 * self.d_model, dtype=self.dtype,
                            name="qkv")
        self.attn_out = nn.Dense(self.d_model, dtype=self.dtype,
                                 name="attn_out")
        self.ln2 = nn.LayerNorm(dtype=self.dtype, name="ln2")
        self.mlp_in = nn.Dense(self.d_ff, dtype=self.dtype, name="mlp_in")
        self.mlp_out = nn.Dense(self.d_model, dtype=self.dtype,
                                name="mlp_out")

    def _mlp(self, x):
        h = self.ln2(x)
        h = self.mlp_in(h)
        h = nn.gelu(h)
        h = self.mlp_out(h)
        return x + h

    def prefill(self, x):
        """x [B, P, d] -> (x', k [B, P, H, Dh], v [B, P, H, Dh])."""
        B, P, _ = x.shape
        Dh = self.d_model // self.n_heads
        h = self.ln1(x)
        qkv = self.qkv(h)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        q = q.reshape(B, P, self.n_heads, Dh)
        k = k.reshape(B, P, self.n_heads, Dh)
        v = v.reshape(B, P, self.n_heads, Dh)
        with jax.named_scope("attn"):
            scores = jnp.einsum("bthd,bshd->bhts", q, k) / jnp.asarray(
                Dh ** 0.5, self.dtype)
            causal = (jnp.arange(P)[:, None] >= jnp.arange(P)[None, :])
            scores = jnp.where(causal[None, None], scores,
                               jnp.asarray(-1e9, scores.dtype))
            probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1)
            probs = probs.astype(self.dtype)
            att = jnp.einsum("bhts,bshd->bthd", probs, v).reshape(B, P, -1)
        x = x + self.attn_out(att)
        return self._mlp(x), k, v

    def verify(self, x, ck, cv, pos):
        """A K-token window per slot: x [S, K, d], cache rows ck/cv
        [S, T, H, Dh], pos [S] (the row the window starts at).  The
        window's K/V scatter at rows ``pos..pos+K-1`` precedes the
        read; window query j attends rows ``<= pos+j`` — the decode
        mask extended one causal diagonal into the window.

        This is the ONLY token-step program: plain decode is this
        window at K == 1 (:meth:`ServingBlock.decode` was deleted for
        cause — see the class docstring).  Window query j's math per
        (slot, head, query) touches K only as a batch dimension, so its
        argmax equals what j sequential K == 1 steps would have
        produced under the same kernel-batch-stability that already
        underwrites bucketed prefill (pinned bitwise in
        tests/test_serving.py).  A slot parked at ``pos == T`` scatters
        out of bounds (dropped) and its outputs are garbage by
        construction — callers discard non-busy rows."""
        S, K, _ = x.shape
        T = ck.shape[1]
        Dh = self.d_model // self.n_heads
        h = self.ln1(x)
        qkv = self.qkv(h)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        q = q.reshape(S, K, self.n_heads, Dh)
        k = k.reshape(S, K, self.n_heads, Dh)
        v = v.reshape(S, K, self.n_heads, Dh)
        rows = pos[:, None] + jnp.arange(K, dtype=pos.dtype)[None]  # [S, K]
        sl = jnp.arange(S)[:, None]
        with jax.named_scope("cache_update"):
            ck = ck.at[sl, rows].set(k)
            cv = cv.at[sl, rows].set(v)
        with jax.named_scope("attn"):
            scores = jnp.einsum("skhd,sthd->shkt", q, ck) / jnp.asarray(
                Dh ** 0.5, self.dtype)
            live = (jnp.arange(T)[None, None, :]
                    <= rows[:, :, None])                        # [S,K,T]
            scores = jnp.where(live[:, None], scores,
                               jnp.asarray(-1e9, scores.dtype))
            probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1)
            probs = probs.astype(self.dtype)
            att = jnp.einsum("shkt,sthd->skhd", probs, cv).reshape(S, K, -1)
        x = x + self.attn_out(att)
        return self._mlp(x), ck, cv



class ServingLM(nn.Module):
    """The decode-side TransformerLM: same top-level names (``embed``,
    ``pos``, ``block{i}``, ``ln_f``) and weight-tied f32 logits, with
    prefill/decode methods instead of the training ``__call__``.
    ``max_len`` must equal the TRAINING model's (it is the positional
    table's row count — a param shape, not a serving knob; the serving
    cache length is the engine's separate ``cache_len``)."""
    vocab_size: int
    n_layers: int
    d_model: int
    n_heads: int
    d_ff: int
    max_len: int
    dtype: jnp.dtype = jnp.bfloat16

    def setup(self):
        self.embed = nn.Embed(self.vocab_size, self.d_model,
                              dtype=self.dtype, name="embed")
        self.pos = nn.Embed(self.max_len, self.d_model, dtype=self.dtype,
                            name="pos")
        self.blocks = [ServingBlock(self.d_model, self.n_heads,
                                    self.d_ff, self.dtype,
                                    name=f"block{i}")
                       for i in range(self.n_layers)]
        self.ln_f = nn.LayerNorm(dtype=self.dtype, name="ln_f")

    def prefill(self, tokens):
        """tokens [B, P] -> (logits [B, P, V] f32,
        k [L, B, P, H, Dh], v [L, B, P, H, Dh]).  Batched: B queued
        prompts padded into one bucket share one forward, so admission
        under burst pays one dispatch instead of B (each prompt's math
        is batch-independent — same rows, same results)."""
        P = tokens.shape[1]
        x = self.embed(tokens)
        x = x + self.pos(jnp.arange(P, dtype=jnp.int32))[None]
        ks, vs = [], []
        for blk in self.blocks:
            x, k, v = blk.prefill(x)
            ks.append(k)
            vs.append(v)
        x = self.ln_f(x)
        with jax.named_scope("head"):
            logits = self.embed.attend(x).astype(jnp.float32)
        return logits, jnp.stack(ks), jnp.stack(vs)

    def verify(self, toks, positions, ck, cv):
        """toks [S, K], positions [S], caches [L, S, T, H, Dh] ->
        (logits [S, K, V] f32, ck, cv) — the speculative-verify /
        suffix-extend program (see ServingBlock.verify)."""
        K = toks.shape[1]
        x = self.embed(toks) + self.pos(
            positions[:, None] + jnp.arange(K, dtype=jnp.int32)[None])
        new_k, new_v = [], []
        for i, blk in enumerate(self.blocks):
            x, k_i, v_i = blk.verify(x, ck[i], cv[i], positions)
            new_k.append(k_i)
            new_v.append(v_i)
        ck = jnp.stack(new_k)
        cv = jnp.stack(new_v)
        x = self.ln_f(x)
        with jax.named_scope("head"):
            logits = self.embed.attend(x).astype(jnp.float32)
        return logits, ck, cv

    def decode(self, tok, positions, ck, cv):
        """tok [S], positions [S], caches [L, S, T, H, Dh] ->
        (logits [S, V] f32, ck, cv) — the K == 1 window of
        :meth:`verify`, NOT a separate program (see ServingBlock: two
        token-step programs can flip a near-tied argmax between them,
        which breaks the speculative path's bitwise-greedy oracle)."""
        logits, ck, cv = self.verify(tok[:, None], positions, ck, cv)
        return logits[:, 0], ck, cv

    def prefill_into(self, toks, slots_ix, lengths, ck, cv):
        """toks [B, Pb] — B queued prompts in one bucketed forward;
        slots_ix/lengths [B].  Each prompt's K/V rows scatter into its
        own slot; returns the f32 logits at each prompt's true last
        position (pad rows beyond it are never read), ck, cv."""
        logits, k, v = self.prefill(toks)
        ck = ck.at[:, slots_ix, :toks.shape[1]].set(k)
        cv = cv.at[:, slots_ix, :toks.shape[1]].set(v)
        last = jnp.take_along_axis(
            logits, (lengths - 1)[:, None, None], axis=1)[:, 0]
        return last, ck, cv

    # --- what a serving module states to DecodeEngine ----------------------
    #: No limit on the positions of one prefill program.
    prefill_positions_max = None

    def cache_rows(self, cache_len: int) -> tuple:
        """``(kind, rows)`` per layer: every layer full."""
        return (("full", cache_len),) * self.n_layers

    def cache_slot_bytes(self, cache_len: int) -> tuple:
        """Bytes one slot holds in each layer: its K and V rows."""
        row = 2 * self.d_model * jnp.dtype(self.dtype).itemsize
        return (cache_len * row,) * self.n_layers

    def prefill_buckets(self, cache_len: int):
        """None: the engine's powers of two (:func:`_prefill_buckets`)."""
        return None

    def decode_fetch_block(self, rows: int) -> int:
        """0: the token step's attention reads every row a layer holds."""
        return 0

    def init_cache(self, slots: int, cache_len: int) -> tuple:
        """The stacked pair ``[L, S, T, H, Dh]`` (the module docstring
        says why this model keeps it)."""
        shape = (self.n_layers, slots, cache_len, self.n_heads,
                 self.d_model // self.n_heads)
        return jnp.zeros(shape, self.dtype), jnp.zeros(shape, self.dtype)


def serving_lm_for(model) -> ServingLM:
    """The serving twin of a ``TransformerLM`` — every architecture field
    copied, so the training param tree binds bit-for-bit (what
    ``TransformerLM.serving_module()`` returns)."""
    return ServingLM(vocab_size=model.vocab_size,
                     n_layers=model.n_layers, d_model=model.d_model,
                     n_heads=model.n_heads, d_ff=model.d_ff,
                     max_len=model.max_len, dtype=model.dtype,
                     parent=None)    # a module of its own, not a child


#: Why a kind of layer whose cache is not ``cache_len`` K/V rows by
#: position cannot be served by what reads, writes or rolls back such rows:
#: (what the layers are called, what they keep, what that breaks).
_NO_ROWS_BY_POSITION = {
    "window": (
        "window-attention layers",
        "keep a ring of the last positions, a row being position mod the "
        "ring's length",
        "a ring has overwritten the rows that needs"),
    "state": (
        "recurrent-state layers",
        "keep no cache rows but a state that every token rewrites whole",
        "a state that has taken a token can neither be cut at a position "
        "nor give the token back"),
    "latent": (
        "latent-attention layers",
        "keep one compressed row a position that every head shares as key "
        "and as value, and no V array",
        "what that moves is the stacked K/V pair of every head, which such "
        "a layer never holds"),
}


def refuse_cache_without_rows_by_position(model, what: str) -> None:
    """``what`` needs every layer's cache to be the same ``cache_len``
    K/V rows, addressed by position; a model with window layers keeps
    rings, one with state layers keeps no rows at all, one with latent
    layers no K/V pair, and each is refused by name."""
    rows = model.serving_module().cache_rows(1)
    for kind, (called, keep, breaks) in _NO_ROWS_BY_POSITION.items():
        n = sum(k == kind for k, _ in rows)
        if n:
            raise ModeRefusal(
                f"{what} does not serve a model with {called} ({n} of this "
                f"model's {len(rows)} {keep}): it reads, writes or rolls "
                f"back cache rows by position, and {breaks} — serve this "
                f"model through DecodeEngine alone")


def _prefill_buckets(cache_len: int, smallest: int = 8) -> tuple:
    """Padding buckets for prefill: powers of two from ``smallest`` up
    to ``cache_len`` (inclusive as the final bucket).  Each bucket is
    one compiled prefill program; a prompt pads to the smallest bucket
    that fits, so N distinct prompt lengths cost log(N) compiles, not
    N.  This is the ladder of a serving module whose
    ``prefill_buckets(cache_len)`` answers None; ``DecodeEngine`` takes
    the module's own where it states one (``ServedLM``, ``AfmoeLM``)."""
    out = []
    b = smallest
    while b < cache_len:
        out.append(b)
        b *= 2
    out.append(cache_len)
    return tuple(out)


# --- the compiled programs (module-level: ONE jit cache per process) ------
# jax.jit keys its compile cache on (function identity, static args,
# shapes).  Built as closures inside ``DecodeEngine.__init__`` these were
# per-INSTANCE jit objects, so a second engine of identical geometry
# recompiled every program the first had already paid for (~3 s per
# engine on one CPU core) — and fresh engines are routine: a spec DRAFT
# engine next to its target, a promoted replica, every test.  The
# ServingLM module passes STATICALLY (flax modules hash by config), so
# equal-config engines share programs process-wide; donation stays on
# the cache operands only.

# A serving module's methods return ``(logits, ck, cv)`` and may add a
# fourth, a small int32 vector of counts (``ops/moe.STATS``, summed over
# layers): the greedy programs then return it behind the tokens, ONE
# int32 array, so the host's one read-back brings both.

def _with_stats(toks, rest: tuple):
    return jnp.concatenate([toks.ravel(), *rest]) if rest else toks


def _decode_step_fn(smodel, params, ck, cv, tok, pos, host):
    # tok, pos [S]: what the step before this one left on the device.
    # host int32 [4, S], the one upload a step: which slots advance,
    # which the host has written since the last step, and the host's
    # token and position for those (a prefill's, a parked slot's).  The
    # model's arithmetic on the cache and the logits is what it was when
    # the host kept both vectors.
    advance, written, tok_host, pos_host = host
    tok = jnp.where(written > 0, tok_host, tok)
    pos = jnp.where(written > 0, pos_host, pos)
    logits, ck, cv, *stats = smodel.apply({"params": params}, tok, pos, ck,
                                          cv, method="decode")
    toks = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return (_with_stats(toks, stats), jnp.where(advance > 0, toks, tok),
            pos + advance, ck, cv)


_decode_step = jax.jit(_decode_step_fn, static_argnums=0,
                       donate_argnums=(2, 3))


@functools.partial(jax.jit, static_argnums=0, donate_argnums=(2, 3))
def _decode_logits_step(smodel, params, ck, cv, tok, pos):
    # The sampling seam: same decode program, f32 logits out instead of
    # the fused argmax (greedy keeps its own program — and its pinned
    # HLO contract — untouched).
    return smodel.apply({"params": params}, tok, pos, ck, cv,
                        method="decode")[:3]


@functools.partial(jax.jit, static_argnums=0, donate_argnums=(2, 3))
def _verify_window(smodel, params, ck, cv, toks, pos):
    logits, ck, cv = smodel.apply({"params": params}, toks, pos, ck, cv,
                                  method="verify")[:3]
    return (jnp.argmax(logits, axis=-1).astype(jnp.int32),
            logits, ck, cv)


@functools.partial(jax.jit, static_argnums=0, donate_argnums=(2, 3))
def _prefill_bucketed(smodel, params, ck, cv, toks, slots_ix, lengths):
    # toks [B, Pb] — B queued prompts in one bucketed forward;
    # slots_ix/lengths [B].  The model writes each prompt's K/V rows into
    # its own slot; the "first generated token" is the argmax at each
    # prompt's true last position.
    last, ck, cv, *stats = smodel.apply(
        {"params": params}, toks, slots_ix, lengths, ck, cv,
        method="prefill_into")
    toks = jnp.argmax(last, axis=-1).astype(jnp.int32)
    return _with_stats(toks, stats), last, ck, cv


@functools.partial(jax.jit, donate_argnums=(0, 1))
def _splice_rows(ck, cv, k_rows, v_rows, slot):
    # Prefix-cache import: splice stored [L, W, H, Dh] rows into one
    # slot (rows beyond the real prefix are stale bucket padding —
    # masked until overwritten, like prefill's own).
    ck = jax.lax.dynamic_update_slice(ck, k_rows[:, None],
                                      (0, slot, 0, 0, 0))
    cv = jax.lax.dynamic_update_slice(cv, v_rows[:, None],
                                      (0, slot, 0, 0, 0))
    return ck, cv


class DecodeEngine:
    """Slots + caches + the two compiled programs (bucketed prefill,
    the donated decode step).  Host-side bookkeeping (which slot is
    live, each request's tokens) belongs to the ContinuousBatcher; this
    class owns only the device state and refuses geometry it cannot
    serve.

    Donation discipline: both programs donate the cache buffers, so
    after every call the PREVIOUS cache handles are dead — the engine
    always rebinds, and no caller ever holds a cache reference.

    The greedy step's token and position vectors stay on the device
    too, one step's outputs being the next one's inputs, so that
    :meth:`decode` can hand the device a step before it has read the
    one before it; ``decode_logits``, ``verify_step`` and ``extend``
    keep the synchronous form they have, because there the host
    produces or owns the tokens and there is nothing to read late."""

    def __init__(self, model, params, *,
                 slots: int = DEFAULT_SLOTS, cache_len: int = 128,
                 prefill_smallest: int = 8):
        if cache_len > model.max_len:
            raise ModeRefusal(
                f"--max_len {cache_len} exceeds the model's positional "
                f"table ({model.max_len} rows) — the snapshot was "
                f"trained with max_len {model.max_len}; a longer cache "
                f"would index past the table, not extrapolate it")
        if slots < 1:
            raise ValueError(f"slots {slots} must be >= 1")
        self.model = model
        # The model's own serving programs and cache: the engine copies
        # no field of any architecture (module docstring).
        self.smodel = model.serving_module()
        self.params = params
        self.slots = int(slots)
        self.cache_len = int(cache_len)
        self.vocab = int(model.vocab_size)
        missing = [m for m in SERVING_SEAM if not hasattr(self.smodel, m)]
        if missing:
            raise TypeError(
                f"{type(self.smodel).__name__} is no serving module: it "
                f"lacks {', '.join(missing)} (models/served_lm.py: ServedLM "
                f"states what DecodeEngine asks of one)")
        # The padding ladder is the module's (None: it leaves the choice
        # here, the powers of two).
        self.buckets = (self.smodel.prefill_buckets(self.cache_len)
                        or _prefill_buckets(self.cache_len, prefill_smallest))
        self._ck, self._cv = self.smodel.init_cache(self.slots,
                                                    self.cache_len)
        layers = self.smodel.cache_rows(self.cache_len)
        self.cache_bytes = sum(
            x.nbytes for x in jax.tree.leaves((self._ck, self._cv)))
        # Per kind of layer that holds rows: (rows-read counter,
        # rows-fetched counter, layers, rows a slot holds in each, rows
        # the model's decode attention fetches at a time or 0 for all of
        # them).
        kinds: dict = {}
        for kind, rows in layers:
            kinds[kind] = (kinds.get(kind, (0, rows))[0] + 1, rows)
        self._kinds = [(_ROWS_READ.labels(kind=kind),
                        _ROWS_FETCHED.labels(kind=kind), n, rows,
                        self.smodel.decode_fetch_block(rows))
                       for kind, (n, rows) in kinds.items()
                       if kind != "state"]
        # The bytes of each kind: what a slot holds in each layer, by the
        # module's own count.
        by_kind = dict.fromkeys(kinds, 0)
        for (kind, _), held in zip(
                layers, self.smodel.cache_slot_bytes(self.cache_len)):
            by_kind[kind] += self.slots * held
        for kind, held in by_kind.items():
            _CACHE_BYTES.labels(kind=kind).set(held)
        # What one slot's states cost a decode step: read and written.
        self._state_bytes_slot = 2 * by_kind.get("state", 0) // self.slots
        #: Layers whose cache is not ``cache_len`` K/V rows by position.
        self.layers_without_rows_by_position = sum(
            n for kind, (n, _) in kinds.items()
            if kind in _NO_ROWS_BY_POSITION)
        # Each slot's last token and position live ON THE DEVICE, a
        # greedy step's outputs being the next one's inputs (``_tok``,
        # ``_pos``), so that a step can be handed over before the one
        # before it is read (:meth:`decode`).  The host keeps mirrors:
        # ``positions`` is exact for every step dispatched (it advances
        # by the slots that advance, known without the device);
        # ``last_tokens`` is one step behind while a step is in flight.
        # What the host writes into a slot (a prefill's first token and
        # length, :meth:`set_slot`) goes into the mirrors and is marked
        # in ``_written``; the next greedy step takes the marked slots
        # from its one small upload in place of the device's.
        self.positions = np.zeros((self.slots,), np.int32)
        self.last_tokens = np.zeros((self.slots,), np.int32)
        self._tok = self._pos = jnp.zeros((self.slots,), jnp.int32)
        self._written = np.ones((self.slots,), bool)
        #: The greedy step handed to the device and not read yet: (its
        #: tokens on the device, the slots whose ``last_tokens`` it sets).
        self._flying: tuple | None = None
        self.decode_steps = 0
        self.prefills = 0
        # Which (bucket, batch) prefill shapes have compiled: the first
        # call per shape pays the jit compile, and callers timing
        # prefill for an admission predictor must know to exclude it.
        self._warm_buckets: set = set()
        self.last_prefill_was_cold = False

        # The compiled programs live at module level (shared jit cache
        # across engines — see the block above _decode_step); this
        # UNJITTED binding exists for callers that need a fresh
        # variant lowering of the decode step (the HLO contract's
        # donation-teeth test compiles it WITHOUT donation).
        self._decode_fn = functools.partial(_decode_step_fn, self.smodel)

    # --- the two steps ----------------------------------------------------
    def bucket_for(self, prompt_len: int, max_new: int) -> int:
        """Smallest padding bucket holding ``prompt_len``, refusing
        work that cannot finish inside the cache."""
        if prompt_len < 1:
            raise ValueError("empty prompt")
        if prompt_len + max_new > self.cache_len:
            raise ModeRefusal(
                f"prompt ({prompt_len} tokens) + --max_new ({max_new}) "
                f"exceeds the engine's --max_len cache ({self.cache_len} "
                f"rows/slot) — the request can never finish; raise "
                f"--max_len or shorten the request")
        for b in self.buckets:
            if prompt_len <= b:
                return b
        raise AssertionError("bucket table misses cache_len")  # unreachable

    def prefill(self, slot: int, prompt: np.ndarray,
                max_new: int = 1) -> int:
        """Fill ``slot``'s cache rows from the prompt and return the
        first generated token.  Pads to the chosen bucket with token 0 —
        pad rows land in the cache beyond the slot's frontier, where the
        decode mask excludes them until a real token overwrites each."""
        (tok, _), = self.prefill_many([(slot, prompt, max_new)]).values()
        return tok

    def prefill_many(self, assignments: list) -> dict:
        """Batched prefill: ``assignments`` is [(slot, prompt, max_new),
        ...]; prompts sharing a padding bucket share ONE forward (the
        burst-amortization rung: B admissions cost one dispatch per
        bucket, not B).  Returns {slot: (first_token, last_logits)} —
        the f32 logits at each prompt's last position, for callers that
        sample the first token instead of taking the fused argmax.
        ``last_prefill_was_cold`` reports whether ANY group compiled."""
        groups: dict = {}
        for slot, prompt, max_new in assignments:
            prompt = np.asarray(prompt, np.int32).ravel()
            bucket = self.bucket_for(len(prompt), max_new)
            groups.setdefault(bucket, []).append((slot, prompt))
        out: dict = {}
        cold = False
        # A model may cap the positions one prefill program takes (its
        # activations beside a nearly full chip): a larger group goes as
        # several programs of the same bucket.
        most = self.smodel.prefill_positions_max
        split = [(bucket, group[i:i + n])
                 for bucket, group in sorted(groups.items())
                 for n in [max(1, most // bucket) if most else len(group)]
                 for i in range(0, len(group), n)]
        for bucket, group in split:
            B = len(group)
            with hot_span("engine.prefill.pack"):
                padded = np.zeros((B, bucket), np.int32)
                slots_ix = np.zeros((B,), np.int32)
                lengths = np.zeros((B,), np.int32)
                for i, (slot, prompt) in enumerate(group):
                    padded[i, :len(prompt)] = prompt
                    slots_ix[i] = slot
                    lengths[i] = len(prompt)
                toks_in = jnp.asarray(padded)
            with hot_span("engine.prefill.dispatch"):
                toks, last, self._ck, self._cv = _prefill_bucketed(
                    self.smodel, self.params, self._ck, self._cv,
                    toks_in, slots_ix, lengths)
            with hot_span("engine.prefill.readback"):
                toks = self._count_pairs(np.asarray(toks), B)
                last = np.asarray(last)
            for i, (slot, prompt) in enumerate(group):
                self.set_slot(slot, toks[i], len(prompt))
                out[slot] = (int(toks[i]), last[i])
            self.prefills += B
            real = int(lengths.sum())
            _POS_PROMPT.inc(real)
            _POS_PAD.inc(B * bucket - real)
            if (bucket, B) not in self._warm_buckets:
                cold = True
                self._warm_buckets.add((bucket, B))
                _PREFILL_PROGRAMS.set(len(self._warm_buckets))
        self.last_prefill_was_cold = cold
        return out

    def decode(self, busy=None, then=None) -> np.ndarray:
        """One decode step over ALL slots (idle slots compute too — the
        program has one static shape; their outputs are ignored and
        their stale rows are overwritten the next time the slot is
        live).  Returns the next token per slot and advances the BUSY
        slots' frontiers (``busy=None`` advances all): an idle slot's
        parked frontier must not drift toward the cache/positional-
        table edge one row per step of everyone else's work.

        ONE call, which may read a step one call late.  ``then`` names
        the slots of the step AFTER the one this call returns: it is
        handed to the device before this call reads anything, its inputs
        being this step's outputs on the device, and the next call
        returns it (``busy`` is then not looked at: that step is in
        flight already).  So the device goes from one step into the
        next while the host reads, accounts and retires.  Without
        ``then`` a call leaves nothing in flight, and one that found
        nothing in flight is the synchronous step it always was.
        :meth:`settle` reads a step left in flight without another.

        The spans, by name: ``engine.decode.dispatch`` (a jitted call
        until it returns, the one small upload in it),
        ``engine.decode.account`` after each dispatch (the counters and
        the numpy state, the device at work meanwhile) and
        ``engine.decode.readback``, whose head ``engine.decode.wait`` is
        the rest of the device's step and the runtime's latency and
        nothing else; the rest of it is the copy and the counts."""
        if self._flying is None:
            self._dispatch(busy)
        step, self._flying = self._flying, None
        if then is not None:
            self._dispatch(then)
        return self._read(step)

    def settle(self):
        """Read the step in flight, if there is one: its tokens, else
        None.  Nothing is in flight after it."""
        if self._flying is None:
            return None
        step, self._flying = self._flying, None
        return self._read(step)

    def _advancing(self, busy) -> np.ndarray:
        """``busy`` as a mask over the slots (None: every slot)."""
        if busy is None:
            return np.ones(self.slots, bool)
        advance = np.zeros(self.slots, bool)
        advance[list(busy)] = True
        return advance

    def _dispatch(self, busy) -> None:
        advance = self._advancing(busy)
        with hot_span("engine.decode.dispatch"):
            toks, self._tok, self._pos, self._ck, self._cv = _decode_step(
                self.smodel, self.params, self._ck, self._cv, self._tok,
                self._pos, self._host_vectors(advance))
            # Started here, not at the read: the copy then queues behind
            # the step, and a later start costs a round trip to the device.
            toks.copy_to_host_async()
        with hot_span("engine.decode.account"):
            self._written[:] = False
            self._count_rows_read(advance)
            if self._state_bytes_slot:
                _STATE_BYTES.labels(whose="all").inc(
                    self._state_bytes_slot * self.slots)
            self.positions = self.positions + advance.astype(np.int32)
            self.decode_steps += 1
        self._flying = (toks, advance)

    def _host_vectors(self, advance: np.ndarray) -> np.ndarray:
        """The greedy program's one upload (``_decode_step_fn``)."""
        return np.stack([advance, self._written, self.last_tokens,
                         self.positions]).astype(np.int32, copy=False)

    def _read(self, step: tuple) -> np.ndarray:
        toks, advance = step
        with hot_span("engine.decode.readback"):
            with hot_span("engine.decode.wait"):
                toks.block_until_ready()
            out = self._count_pairs(np.asarray(toks), self.slots,
                                    decode=True)
            self.last_tokens = np.where(advance, out, self.last_tokens) \
                .astype(np.int32)
        return out

    def _count_pairs(self, out: np.ndarray, n: int,
                     decode: bool = False) -> np.ndarray:
        """Split what a greedy program returned into its ``n`` tokens and
        the model's counts behind them (``ops/moe.STATS``; none from a
        model without experts), and add the counts to the counters."""
        if len(out) > n:
            held, absent, touched, walked = (int(x) for x in out[n:])
            _MOE_PAIRS.labels(where="held").inc(held)
            _MOE_PAIRS.labels(where="absent").inc(absent)
            _MOE_ROWS.inc(walked)
            if decode:
                _MOE_TOUCHED.inc(touched)
                _MOE_SLOTS.inc(self.smodel.expert_slots)
        return out[:n]

    def _count_rows_read(self, busy: np.ndarray) -> None:
        reach = self.positions + 1              # rows a slot's query sees
        for read, fetched, n, rows, block in self._kinds:
            seen = np.minimum(reach, rows)
            read.inc(n * int(seen[busy].sum()))
            # An idle slot's query is computed too: its rows are fetched.
            fetched.inc(n * (int((-(-seen // block) * block).sum())
                             if block else self.slots * rows))

    def decode_logits(self, busy=None) -> np.ndarray:
        """One decode step returning the f32 logits [S, V] instead of
        the fused argmax — the sampling path.  Advances the busy slots'
        frontiers like :meth:`decode`, but the caller OWNS each busy
        slot's next token: it must ``set_slot(slot, token,
        positions[slot])`` before the next step (greedy's fused-argmax
        program, and its HLO contract, are untouched by this seam)."""
        self.settle()
        with hot_span("engine.decode.dispatch"):
            logits, self._ck, self._cv = _decode_logits_step(
                self.smodel, self.params, self._ck, self._cv,
                self.last_tokens, self.positions)
        with hot_span("engine.decode.readback"):
            out = np.asarray(logits)
        # The host owns both vectors on this path: a greedy step after
        # it takes every slot from the host's.
        self._written[:] = True
        self.positions = self.positions + self._advancing(busy).astype(
            np.int32)
        self.decode_steps += 1
        return out

    def verify_step(self, toks, positions) -> tuple:
        """One batched K-token verify over all slots: toks [S, K],
        positions [S] (a slot not participating passes position ==
        cache_len — its scatters drop out of bounds and its output rows
        are garbage to discard).  Returns (greedy [S, K] int32,
        logits [S, K, V] f32).  Advances NOTHING — the caller owns
        accept/rollback bookkeeping via :meth:`set_slot`."""
        if self.layers_without_rows_by_position:
            refuse_cache_without_rows_by_position(
                self.model, "verify_step (speculation, suffix extension)")
        with hot_span("engine.decode.dispatch"):
            g, logits, self._ck, self._cv = _verify_window(
                self.smodel, self.params, self._ck, self._cv,
                jnp.asarray(np.asarray(toks, np.int32)),
                jnp.asarray(np.asarray(positions, np.int32)))
        self.decode_steps += 1
        with hot_span("engine.decode.readback"):
            return np.asarray(g), np.asarray(logits)

    def extend(self, slot: int, tokens, start: int) -> tuple:
        """Append already-known ``tokens`` to ``slot``'s cache at rows
        ``start..`` (the prefix-cache suffix path) via the verify
        program, padded to a power-of-two window.  Returns
        (next_token, last_logits) at the final appended position."""
        tokens = np.asarray(tokens, np.int32).ravel()
        n = len(tokens)
        if n < 1:
            raise ValueError("empty extension")
        K = 1
        while K < n:
            K *= 2
        toks = np.zeros((self.slots, K), np.int32)
        pos = np.full((self.slots,), self.cache_len, np.int32)
        toks[slot, :n] = tokens
        pos[slot] = int(start)
        g, logits = self.verify_step(toks, pos)
        return int(g[slot, n - 1]), logits[slot, n - 1]

    def read_rows(self, slot: int, width: int) -> tuple:
        """Export ``slot``'s first ``width`` K/V rows as independent
        device arrays [L, width, H, Dh] (the prefix-cache registration
        read).  Blocked to completion so the copies cannot race the
        next step's cache donation."""
        if self.layers_without_rows_by_position:
            refuse_cache_without_rows_by_position(
                self.model, "read_rows (the prefix cache)")
        k = self._ck[:, slot, :width]
        v = self._cv[:, slot, :width]
        return jax.block_until_ready(k), jax.block_until_ready(v)

    def write_rows(self, slot: int, k_rows, v_rows) -> None:
        """Import stored K/V rows into ``slot`` (the prefix-cache hit
        write); the caller then ``set_slot``s the real prefix length."""
        if self.layers_without_rows_by_position:
            refuse_cache_without_rows_by_position(
                self.model, "write_rows (the prefix cache)")
        self._ck, self._cv = _splice_rows(
            self._ck, self._cv, k_rows, v_rows, np.int32(slot))

    def set_slot(self, slot: int, last_token: int, position: int) -> None:
        """Host bookkeeping hook (the batcher parks retired slots at
        position 0 so their frontier never walks off the cache end).
        The next greedy step takes the slot from here and not from the
        device; a step in flight no longer sets its ``last_tokens``."""
        self.last_tokens[slot] = int(last_token)
        self.positions[slot] = int(position)
        self._written[slot] = True
        if self._flying is not None:
            self._flying[1][slot] = False

    # --- the contract surface --------------------------------------------
    def decode_hlo(self) -> str:
        """Freshly compiled decode-step text — what graftlint's HLO
        front checks :data:`DECODE_HLO_CONTRACT` against.  Compiled
        from the UNDONATED argument values via a separate lowering (the
        live step's buffers must not be consumed by a lint pass)."""
        return _decode_step.lower(
            self.smodel, *self.decode_args()).compile().as_text()

    def decode_args(self) -> tuple:
        """What the greedy decode program takes after the module, every
        slot advancing: for whoever lowers it again (this contract, the
        tests that read its text)."""
        return (self.params, self._ck, self._cv, self._tok, self._pos,
                self._host_vectors(np.ones(self.slots, bool)))
