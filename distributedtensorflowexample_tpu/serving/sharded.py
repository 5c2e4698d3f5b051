"""Params-stay-sharded decode: the ZeRO-3 read path.

The replicated engine (serving/engine.py) materializes the full param
tree before serving — the read path paid none of what PR 12's ZeRO-3
bought the write path (lm_base residency 458→115 MB/device).  This
module keeps the TRAINING-side resident layout resident at serve time:
params stay the per-bucket flat ``[D*W_b]`` rows sharded one row per
device (``parallel/zero3.py``'s layout, verbatim), and the compiled
decode step all-gathers each bucket's row *inside* the program just
before its einsums consume the leaves — the gathered tree is a
step-local TEMPORARY the compiler frees after last use, so persistent
params residency is exactly 1/D (measured from live shardings:
:meth:`ShardedDecodeEngine.params_residency`, the method of
``utils/profiling.state_residency_per_device``).

The gather schedule is zero3's own: one tiled all-gather per bucket,
issue order pinned by the ``_tie`` double-buffer chain (bucket i's
gather chained onto a scalar probe of bucket i-2's output, so at most
two gathered buckets are in flight ahead of their consumers — on CPU a
compile-shape statement, on TPU the latency-hiding win).  The schedule
is not emergent: :data:`SHARDED_DECODE_HLO_CONTRACT` budgets EXACTLY
one all-gather per bucket (symbolic ``"B"`` — fewer is a regression,
more is a finding, and any other collective is an unbudgeted finding by
construction), keeps the donated-cache aliasing claims, and graftlint's
HLO front checks it on freshly compiled text next to the replicated
path's 0-collective budget.

The KV-cache shards over the SLOT axis (``shard_map``): each device
holds ``slots/D`` slots' rows and decodes them against the gathered
params — slot math is batch-independent (engine.py's argument), so the
sharded step's tokens are bitwise the replicated engine's (pinned in
tests/test_serving.py against the same snapshot).  ``slots`` must
divide evenly across the mesh; anything else is refused by name.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from distributedtensorflowexample_tpu.models.transformer_lm import (
    TransformerLM)
from distributedtensorflowexample_tpu.parallel.bucketing import (
    _unbucket_rows)
from distributedtensorflowexample_tpu.parallel.mesh import DATA_AXIS
from distributedtensorflowexample_tpu.parallel.zero3 import (
    Zero3Layout, _tie)
from distributedtensorflowexample_tpu.refusal import ModeRefusal
from distributedtensorflowexample_tpu.serving.engine import (
    DEFAULT_SLOTS, ServingLM, _prefill_buckets,
    refuse_cache_without_rows_by_position, serving_lm_for)

#: The sharded decode step's compiled-HLO contract (graftlint HLO
#: front, next to the replicated path's DECODE_HLO_CONTRACT): donated
#: caches actually aliased and never ENTRY-copied (steady-state decode
#: still reallocates nothing cache-shaped), EXACTLY one all-gather per
#: param bucket (symbolic "B" = the layout's plan length — shrinking
#: the schedule is as much a finding as growing it), and since
#: collectives absent from the budget are findings by construction, any
#: all-reduce/reduce-scatter appearing in a decode step is caught the
#: way zero3's AG-before-RS is pinned.  f32 ceiling as everywhere.
SHARDED_DECODE_HLO_CONTRACT = {
    "mode": "serve_decode_sharded",
    "require_alias": True,
    "no_donated_copy": True,
    "collective_budget": {"all-gather": "B"},
    "dtype_ceiling": "f32",
}


class ShardedDecodeEngine:
    """The DecodeEngine's row-resident twin: same public surface (the
    ContinuousBatcher drives either), but ``params`` is the zero3
    bucket-row tuple at 1/D per device and the caches shard over the
    slot axis.  Speculative decoding, sampling, and the prefix cache
    are replicated-path features (they need the logits/verify seams);
    the batcher refuses those combinations by name."""

    def __init__(self, model: TransformerLM, rows, layout: Zero3Layout,
                 *, slots: int = DEFAULT_SLOTS, cache_len: int = 128,
                 prefill_smallest: int = 8, overlap: bool = True):
        from jax.sharding import NamedSharding, PartitionSpec as P
        refuse_cache_without_rows_by_position(
            model, "--sharded_mesh (ShardedDecodeEngine)")
        if cache_len > model.max_len:
            raise ModeRefusal(
                f"--max_len {cache_len} exceeds the model's positional "
                f"table ({model.max_len} rows) — the snapshot was "
                f"trained with max_len {model.max_len}; a longer cache "
                f"would index past the table, not extrapolate it")
        D = layout.num_devices
        if slots < 1:
            raise ValueError(f"slots {slots} must be >= 1")
        if slots % D != 0:
            raise ModeRefusal(
                f"--slots {slots} does not divide across the {D}-device "
                f"mesh — the KV-cache shards over the slot axis "
                f"(slots/D rows per device), so the slot count must be "
                f"a multiple of the mesh size; use --slots "
                f"{((slots + D - 1) // D) * D}")
        self.model = model
        self.smodel = serving_lm_for(model)
        self.layout = layout
        self.mesh = layout.mesh
        self.slots = int(slots)
        self.cache_len = int(cache_len)
        self.vocab = int(model.vocab_size)
        self.buckets = _prefill_buckets(self.cache_len, prefill_smallest)
        # Rows re-pinned to the resident sharding (a restore may hand
        # them back single-device); this is a 1/D-sized placement, never
        # a materialization.
        row_sh = NamedSharding(self.mesh, P(DATA_AXIS))
        self.rows = tuple(jax.device_put(r, row_sh) for r in rows)
        L = model.n_layers
        H = model.n_heads
        Dh = model.d_model // H
        shape = (L, self.slots, self.cache_len, H, Dh)
        cache_sh = NamedSharding(self.mesh, P(None, DATA_AXIS))
        self._ck = jax.device_put(jnp.zeros(shape, model.dtype), cache_sh)
        self._cv = jax.device_put(jnp.zeros(shape, model.dtype), cache_sh)
        self.cache_bytes = 2 * int(np.prod(shape)) * \
            np.dtype(model.dtype).itemsize
        self.positions = np.zeros((self.slots,), np.int32)
        self.last_tokens = np.zeros((self.slots,), np.int32)
        self.decode_steps = 0
        self.prefills = 0
        self._warm_buckets: set = set()
        self.last_prefill_was_cold = False

        smodel = self.smodel
        specs, plan, treedef = (layout.leaf_specs, layout.plan,
                                layout.treedef)
        depth = 2 if overlap else 1
        Sl = self.slots // D

        def gather_params(p_rows):
            # zero3's AG-prefetch schedule, verbatim: one tiled
            # all-gather per bucket, issue order pinned by the _tie
            # chain; the gathered leaves are bitwise the replicated
            # leaves (concatenate/reshape move bytes, never arithmetic).
            full_rows = []
            for bi, row in enumerate(p_rows):
                j = bi - depth
                if j >= 0:
                    row = _tie(row, full_rows[j].ravel()[0].astype(
                        jnp.float32))
                full_rows.append(jax.lax.all_gather(
                    row, DATA_AXIS, axis=0, tiled=True).reshape(D, -1))
            leaves: list = [None] * len(specs)
            for bi, idxs in enumerate(plan):
                for i, piece in _unbucket_rows(full_rows[bi], specs,
                                               idxs).items():
                    leaves[i] = piece
            return jax.tree.unflatten(treedef, leaves)

        def _decode_body(p_rows, ck, cv, tok, pos):
            # Local view: ck/cv [L, S/D, T, H, Dh], tok/pos [S/D] — each
            # device decodes its own slots against the gathered tree.
            params = gather_params(p_rows)
            logits, ck, cv = smodel.apply({"params": params}, tok, pos,
                                          ck, cv,
                                          method=ServingLM.decode)
            return jnp.argmax(logits, axis=-1).astype(jnp.int32), ck, cv

        def _prefill_body(p_rows, ck, cv, toks, slot, length):
            # Replicated compute, owner-only write: every device runs
            # the prompt forward (prefill is the rare step; simplicity
            # beats a scatter here), and only the slot's owner lands the
            # K/V rows — non-owners resolve ``local`` to S/D, one past
            # their shard, and the scatter drops out of bounds.
            params = gather_params(p_rows)
            logits, k, v = smodel.apply({"params": params}, toks,
                                        method=ServingLM.prefill)
            d = jax.lax.axis_index(DATA_AXIS)
            local = jnp.where((slot >= d * Sl) & (slot < (d + 1) * Sl),
                              slot - d * Sl, Sl).astype(jnp.int32)
            ck = ck.at[:, local, :toks.shape[1]].set(k[:, 0])
            cv = cv.at[:, local, :toks.shape[1]].set(v[:, 0])
            last = jax.lax.dynamic_index_in_dim(logits[0], length - 1,
                                                axis=0, keepdims=False)
            return jnp.argmax(last).astype(jnp.int32), ck, cv

        P_ = jax.sharding.PartitionSpec
        pspec = jax.tree.map(lambda _: P_(DATA_AXIS), self.rows)
        cspec = P_(None, DATA_AXIS)
        self._decode_fn = jax.shard_map(
            _decode_body, mesh=self.mesh,
            in_specs=(pspec, cspec, cspec, P_(DATA_AXIS), P_(DATA_AXIS)),
            out_specs=(P_(DATA_AXIS), cspec, cspec), check_vma=False)
        self._decode_jit = jax.jit(self._decode_fn,
                                   donate_argnums=(1, 2))
        self._prefill_jit = jax.jit(jax.shard_map(
            _prefill_body, mesh=self.mesh,
            in_specs=(pspec, cspec, cspec, P_(), P_(), P_()),
            out_specs=(P_(), cspec, cspec), check_vma=False),
            donate_argnums=(1, 2))

    # --- the steps (DecodeEngine's surface) --------------------------------
    def bucket_for(self, prompt_len: int, max_new: int) -> int:
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
        prompt = np.asarray(prompt, np.int32).ravel()
        P = len(prompt)
        bucket = self.bucket_for(P, max_new)
        self.last_prefill_was_cold = bucket not in self._warm_buckets
        self._warm_buckets.add(bucket)
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :P] = prompt
        tok, self._ck, self._cv = self._prefill_jit(
            self.rows, self._ck, self._cv, jnp.asarray(padded),
            np.int32(slot), np.int32(P))
        self.positions[slot] = P
        self.last_tokens[slot] = int(tok)
        self.prefills += 1
        return int(tok)

    def prefill_many(self, assignments: list) -> dict:
        """Sequential on the sharded path (prefill compute is
        replicated per device; batching it is the REPLICATED engine's
        amortization rung) — same return shape so the batcher drives
        either engine.  No last-logits seam: sampling is refused with
        this engine by name upstream."""
        out: dict = {}
        cold = False
        for slot, prompt, max_new in assignments:
            tok = self.prefill(slot, prompt, max_new)
            cold = cold or self.last_prefill_was_cold
            out[slot] = (tok, None)
        self.last_prefill_was_cold = cold
        return out

    def decode(self, busy=None) -> np.ndarray:
        toks, self._ck, self._cv = self._decode_jit(
            self.rows, self._ck, self._cv, self.last_tokens,
            self.positions)
        out = np.asarray(toks)
        advance = (np.ones(self.slots, bool) if busy is None
                   else np.zeros(self.slots, bool))
        if busy is not None:
            advance[list(busy)] = True
        self.last_tokens = np.where(advance, out, self.last_tokens) \
            .astype(np.int32)
        self.positions = self.positions + advance.astype(np.int32)
        self.decode_steps += 1
        return out

    def set_slot(self, slot: int, last_token: int, position: int) -> None:
        self.last_tokens[slot] = int(last_token)
        self.positions[slot] = int(position)

    # --- the contract surface ---------------------------------------------
    def decode_hlo(self) -> str:
        """Freshly compiled sharded decode-step text — what graftlint
        checks :data:`SHARDED_DECODE_HLO_CONTRACT` against (symbol
        ``B`` = the layout's bucket count)."""
        lowered = jax.jit(self._decode_fn, donate_argnums=(1, 2)).lower(
            self.rows, self._ck, self._cv, self.last_tokens,
            self.positions)
        return lowered.compile().as_text()

    def params_residency(self) -> dict:
        """The 1/D claim from LIVE shardings (bytes of the addressable
        shard vs bytes of the logical array) — rows are ``[D*W_b]``
        sharded one row per
        device, so ``frac_per_device`` is exactly ``1/D``, and a silent
        replication regression shows up as 1.0, not as folklore."""
        total = 0
        per_dev = 0
        for row in jax.tree.leaves(self.rows):
            itemsize = np.dtype(row.dtype).itemsize
            total += int(row.size) * itemsize
            shard = row.addressable_shards[0]
            per_dev += int(np.prod(shard.data.shape)) * itemsize
        return {
            "params_bytes_total": int(total),
            "params_bytes_per_device": int(per_dev),
            "frac_per_device": per_dev / total if total else 0.0,
            "num_devices": self.layout.num_devices,
            "num_buckets": self.layout.num_buckets,
        }
