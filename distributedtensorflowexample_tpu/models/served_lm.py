"""The LM shell of the served architectures, and the serving seam:
:class:`ServedLM` is what ``serving/engine.py: DecodeEngine`` asks of a
model, stated once.  A family (``afmoe.py``, ``qwen3_next.py``,
``bailing_hybrid.py``, ``granitemoehybrid.py``, ``kimi_k2.py``) is a
subclass that states its blocks (``make_block``), what a slot holds in each
layer (``cache_layers``) and what truly differs; only those modules import
this one.

**The programs** are the shell's: ``__call__`` (the training-shape
forward), ``prefill_into`` and ``decode``.  Each embeds its tokens, walks
the blocks by one of their two methods — ``sequence(x, lengths) -> (x',
(k, v), stats)``, a whole sequence from position 0 (``lengths`` None: no
padding), and ``step(x, ck_l, cv_l, positions) -> (x', ck_l', cv_l',
stats)`` — and takes the head where logits are wanted; ``stats`` (``ops/
moe.STATS``) are summed over the layers.  A family with experts also
states ``expert_slots``: held experts x expert layers, what one step can
touch at most.

**The cache** is the ``(ck, cv)`` pair the engine donates, one array a
layer in each, and a layer is one of four kinds (:data:`CACHE_KINDS`):

* ``full``: ``rows`` K and V rows a slot, a row a position, in whatever
  shape the family keeps a row (``qwen3_next.py`` keeps them flat);
* ``window``: a ring of the last ``rows`` positions, row = position mod
  ``rows``;
* ``latent``: ``rows`` rows a slot by position, ONE compressed row a
  position that every head shares as key and as value, and no second array
  (an empty one rides in ``cv``);
* ``state``: no rows but a recurrent state and a convolution's last inputs,
  of sizes that do not depend on the cache's length.

What a kind's two arrays are is :class:`CacheLayer`; how a prompt is
written into them is this module's table, and nobody else's decision.
(:func:`rms_norm`, :func:`log_uniform` and :func:`gated_params` are not the
seam: helpers the families' BLOCKS share, here as ``ops/moe.py`` may not move.)
"""

from __future__ import annotations

import math
from typing import Any, NamedTuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from distributedtensorflowexample_tpu.ops.attention import (
    ATTN_BLOCK, tile_ladder)

F32 = jnp.float32


def rms_norm(x, g, eps):
    """``x / sqrt(mean(x^2) + eps) * g`` over the last axis, in float32."""
    xf = x.astype(F32)
    xf = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (xf * g.astype(F32)).astype(x.dtype)


def log_uniform(lo: float, hi: float):
    """The initialiser whose exponential is uniform in ``[lo, hi]``."""
    def init(key, shape, dtype=F32):
        return jnp.log(jax.random.uniform(key, shape, F32, lo, hi)) \
            .astype(dtype)
    return init


def gated_params(make, name, init, dtype, d, f, *experts) -> tuple:
    """The gate, up and down matrices of a gated feed-forward ``d -> f ->
    d`` (``ops/moe.gated_ffn``), ``experts`` of each where given, made by a
    block's ``self.param`` as ``{name}_gate``, ``_up`` and ``_down``."""
    return tuple(make(f"{name}_{n}", init, (*experts, *shape), dtype)
                 for n, shape in (("gate", (d, f)), ("up", (d, f)),
                                  ("down", (f, d))))


class CacheLayer(NamedTuple):
    """What one slot holds in one layer: the kind, the rows addressed by
    position (0: a state), and ``(shape, dtype)`` of the layer's array in
    ``ck`` and in ``cv`` behind the slot axis (``v`` None: none)."""
    kind: str
    rows: int
    k: tuple
    v: tuple | None


# --- how a prompt is written into a slot, by kind --------------------------
# ``k`` and ``v`` are what the layer's ``sequence`` kept of B prompts padded
# to P positions, ``slots_ix [B]`` their slots, ``lengths [B]`` their true
# lengths.  Rows beyond a prompt's length are stale and masked; a state
# cannot be masked, so a slot's is OVERWRITTEN with the one at that length.

def _write_rows(ck_l, cv_l, k, v, slots_ix, lengths):
    """K and V rows by position, in the arrays' own row shape.  A ring
    shorter than the bucket keeps each prompt's last ``R`` real positions,
    each at ``position mod R``."""
    k, v = (t.reshape(t.shape[0], -1, *ck_l.shape[2:]) for t in (k, v))
    R = ck_l.shape[1]
    if k.shape[1] > R:
        last = lengths[:, None] - 1
        at = last - jnp.mod(last - jnp.arange(R)[None], R)
        at = jnp.maximum(at, 0)[:, :, None, None]               # [B,R,1,1]
        k = jnp.take_along_axis(k, at, axis=1)
        v = jnp.take_along_axis(v, at, axis=1)
    return (ck_l.at[slots_ix, :k.shape[1]].set(k),
            cv_l.at[slots_ix, :v.shape[1]].set(v))


def _write_latent(ck_l, cv_l, k, v, slots_ix, lengths):
    return ck_l.at[slots_ix, :k.shape[1]].set(k), cv_l


def _write_state(ck_l, cv_l, k, v, slots_ix, lengths):
    return ck_l.at[slots_ix].set(k), cv_l.at[slots_ix].set(
        v.astype(cv_l.dtype))


CACHE_KINDS = {"full": _write_rows, "window": _write_rows,
               "latent": _write_latent, "state": _write_state}


class ServedLM(nn.Module):
    """tokens [B, T] -> logits [B, T, vocab] float32, and everything
    ``DecodeEngine`` asks of a serving module.  ``dims`` is the family's
    frozen dataclass of sizes; the shell reads ``vocab_size``, ``d_model``,
    ``max_len``, ``eps`` and ``init_std`` of it."""
    dims: Any
    dtype: jnp.dtype = jnp.bfloat16
    param_dtype: jnp.dtype = jnp.float32
    attn_block: int = ATTN_BLOCK

    # --- what a family states ----------------------------------------------
    def make_block(self, i: int) -> nn.Module:
        """Layer ``i``'s block, named ``block{i}``."""
        raise NotImplementedError

    def cache_layers(self, cache_len: int) -> tuple:
        """A :class:`CacheLayer` a layer, from ``dims`` alone (the engine
        asks an unbound module); a layer's kind is the same for every
        ``cache_len``."""
        raise NotImplementedError

    #: The final norm, ``(x, scale, eps) -> x``, and its scale's start.
    norm = staticmethod(rms_norm)
    norm_init = staticmethod(nn.initializers.ones)
    #: Positions one prefill program takes at most (the engine splits a
    #: larger group into several programs of the bucket); None: no limit.
    prefill_positions_max = None

    def _embed(self, tokens):
        return self.embed.astype(self.dtype)[tokens]

    def _logits(self, x):
        with jax.named_scope("head"):
            x = self.norm(x, self.norm_f, self.dims.eps)
            return jnp.dot(x, self.head.astype(self.dtype),
                           preferred_element_type=F32)

    def _shared(self, tokens, positions=None) -> tuple:
        """What every block of ONE program takes behind its own arguments,
        made once a program (``positions`` None: a sequence from 0)."""
        return ()

    def _real(self, toks, lengths):
        """How a prefill program tells its blocks the real positions from
        the padding: the prompts' lengths."""
        return lengths

    def decode_fetch_block(self, rows: int) -> int:
        """Rows the token step's attention fetches at a time from a layer
        that holds ``rows`` a slot; 0 where it reads them all."""
        return 0

    def prefill_buckets(self, cache_len: int):
        """The lengths a prompt is padded to, one prefill program each
        (``ops/attention.tile_ladder``): powers of two from 256 up to a
        tile of attention, then whole tiles, ``cache_len`` last; ``None``
        (the engine's powers of two) for a cache shorter than the first."""
        return tile_ladder(cache_len, self.attn_block)

    # --- what follows from that ----------------------------------------------
    vocab_size = property(lambda self: self.dims.vocab_size)
    max_len = property(lambda self: self.dims.max_len)
    n_layers = property(lambda self: len(self.cache_layers(1)))

    def setup(self):
        c, pd = self.dims, self.param_dtype
        w = nn.initializers.normal(c.init_std)
        self.embed = self.param("embed", w, (c.vocab_size, c.d_model), pd)
        self.blocks = [self.make_block(i) for i in range(self.n_layers)]
        self.norm_f = self.param("norm_f", self.norm_init, (c.d_model,), pd)
        if type(self)._logits is ServedLM._logits:  # an override reads its own
            self.head = self.param("head", w, (c.d_model, c.vocab_size), pd)

    def serving_module(self):
        return self

    def cache_rows(self, cache_len: int) -> tuple:
        """``(kind, rows)`` a layer."""
        return tuple(layer[:2] for layer in self.cache_layers(cache_len))

    def cache_slot_bytes(self, cache_len: int) -> tuple:
        """Bytes one slot holds in each layer."""
        return tuple(sum(math.prod(shape) * jnp.dtype(dtype).itemsize
                         for shape, dtype in filter(None, layer[2:]))
                     for layer in self.cache_layers(cache_len))

    def init_cache(self, slots: int, cache_len: int) -> tuple:
        """``(ck, cv)``, one array a layer in each."""
        layers = self.cache_layers(cache_len)
        make = lambda a: (jnp.zeros((0,), self.dtype) if a is None
                          else jnp.zeros((slots, *a[0]), a[1]))
        return (tuple(make(layer.k) for layer in layers),
                tuple(make(layer.v) for layer in layers))

    def __call__(self, tokens, train: bool = False):
        """The training-shape forward (``train`` is accepted for the
        trainers' calling convention; the models have no dropout)."""
        tokens = tokens.astype(jnp.int32)
        x = self._embed(tokens)
        shared = self._shared(tokens)
        for blk in self.blocks:
            x = blk.sequence(x, None, *shared)[0]
        return self._logits(x)

    def prefill_into(self, toks, slots_ix, lengths, ck, cv):
        """toks [B, P], slots_ix [B], lengths [B] -> (logits at each
        prompt's LAST position [B, V] f32 — the head is never taken over
        the bucket — ck, cv, stats)."""
        real = self._real(toks, lengths)
        x = self._embed(toks)
        shared = self._shared(toks)
        new_k, new_v, stats = [], [], 0
        for blk, layer, ck_l, cv_l in zip(self.blocks, self.cache_layers(1),
                                          ck, cv):
            x, (k, v), st = blk.sequence(x, real, *shared)
            stats = stats + st
            with jax.named_scope("cache_update"):
                ck_l, cv_l = CACHE_KINDS[layer.kind](ck_l, cv_l, k, v,
                                                     slots_ix, lengths)
            new_k.append(ck_l)
            new_v.append(cv_l)
        last = jnp.take_along_axis(x, (lengths - 1)[:, None, None], axis=1)
        return self._logits(last[:, 0]), tuple(new_k), tuple(new_v), stats

    def decode(self, tok, positions, ck, cv):
        """tok [S], positions [S] -> (logits [S, V] f32, ck, cv, stats):
        the one token step.  There is no K-token ``verify`` for a model
        with state layers: a state that has taken K tokens cannot give
        back the last of them (``serving/engine.py`` refuses what would
        need it)."""
        x = self._embed(tok)
        shared = self._shared(tok, positions)
        new_k, new_v, stats = [], [], 0
        for blk, ck_l, cv_l in zip(self.blocks, ck, cv):
            x, ck_l, cv_l, st = blk.step(x, ck_l, cv_l, positions, *shared)
            new_k.append(ck_l)
            new_v.append(cv_l)
            stats = stats + st
        return self._logits(x), tuple(new_k), tuple(new_v), stats
