"""Device-resident dataset: the whole split lives in HBM, minibatches are
gathered on device (pairs with ``parallel.sync.make_indexed_train_step``).

The reference fed every step over the feed_dict / input-pipeline boundary
(SURVEY.md §3a: "the feed-dict copy is the per-step overhead").  At MNIST
scale that copy is THE bottleneck on TPU — measured ~1.4 ms of H2D per
step against a ~0.07 ms compiled step on one v5e chip — and no amount of
prefetch depth hides a transfer that is 20x the step.  MNIST (183 MB) and
CIFAR-10 (590 MB) fit trivially in HBM, so the TPU-native design uploads
the split once and moves nothing per step: the epoch's shuffled index
order is itself computed on device (``jax.random.permutation``), and the
step slices its batch out of it by global-step position.

Epoch multi-buffering: the dataset holds a ring of S epoch permutations in
one device array of shape ``(S, epoch_len)`` — epoch ``e`` in slot
``e % S``.  The train step picks the slot from ``state.step //
steps_per_epoch`` per fused sub-step, so one compiled multi-step call may
cross up to ``S - 1`` epoch boundaries mid-scan.  That decouples the
dispatch-amortizing unroll (``steps_per_next`` / ``unroll_steps``) from
epoch arithmetic entirely: ``S`` is sized automatically from
``steps_per_next`` (every epoch TWO consecutive windows can touch, plus a
margin slot), so multi-epoch fused windows work and the next window's
permutations are computed by ``prefetch()`` INSIDE the in-flight step's
window (the loop calls it right after the step dispatch) instead of at
the next dispatch boundary.  Ring-slot overwrites are safe out of order:
the jitted row
update donates the buffer, and the device stream sequences it after every
already-enqueued step that reads the old row.

Shuffling semantics match the host ``Batcher``: epochs without
replacement, the sub-batch remainder rows dropped per epoch.

Per-epoch host work: one tiny jitted row update into the perm pair.
Per-step host work: a dict re-yield.

Multi-host: every process holds the identical split (same loaders, same
seed — the reference's workers did the same), the arrays are replicated on
the mesh, and every process computes the identical permutations; the train
step re-shards each gathered batch along the data axis on device.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


# Host-side canonical dequant arithmetic lives in data.dequant (numpy-
# only, shared with the loaders); re-exported here because this module is
# its historical home and every consumer imports it from here.
from distributedtensorflowexample_tpu.data.dequant import (  # noqa: F401
    affine_matches_lut, affine_numpy, make_dequant_affine, make_dequant_lut)
from distributedtensorflowexample_tpu.data.dequant import (
    dequant_numpy as _dequant_numpy)
from distributedtensorflowexample_tpu.data.dequant import (
    try_quantize as _try_quantize)

#: The in-step dequant implementations a caller may request.  "auto"
#: resolves per split at quantize time (see ``resolve_dequant_impl``);
#: the rest force one kernel:
#:   affine  f32(u) * scale + bias — one fused multiply-add per pixel,
#:           the fastest measured form and bitwise-identical to the LUT
#:           for every spec where ``affine_matches_lut`` holds (both
#:           shipped specs; re-verified on device per backend)
#:   onehot  one-hot @ LUT matmul — bitwise by construction on any
#:           backend (each dot has exactly one nonzero term); the
#:           fallback for non-affine-representable splits
#:   lut     lut[u] elementwise gather — once the default: measured
#:           ~10 ns/element on TPU (56% of the ResNet step; 479.6 vs
#:           1,962.6 steps/s/chip in one chip window of 2026-08, PERF.md
#:           "History").  Kept ONLY as a named diagnostic.
#:   pallas  fused row-gather + affine dequant in one Pallas kernel
#:           (ops/pallas/dequant.py) — gathers uint8 rows and emits the
#:           float32 batch in a single HBM pass
DEQUANT_IMPLS = ("auto", "affine", "onehot", "lut", "pallas")

_AFFINE_DEVICE_OK: dict[tuple[str, str], bool] = {}


def dequant_affine_is_bitwise(spec: str) -> bool:
    """True iff THIS backend's jitted affine dequant reproduces all 256
    LUT entries bitwise.  The host check (``affine_matches_lut``) proves
    the arithmetic is affine-representable; this one additionally pins
    the backend's rounding (the affine is one FUSED multiply-add — a
    backend that emitted a separate mul and add would double-round and
    diverge on the biased specs).  One tiny jit per (spec, backend) per
    process, cached."""
    key = (spec, jax.default_backend())
    hit = _AFFINE_DEVICE_OK.get(key)
    if hit is not None:
        return hit
    lut = make_dequant_lut(spec)
    s, b = make_dequant_affine(spec)
    u = np.arange(256, dtype=np.uint8)
    if lut.ndim == 2:
        u = np.broadcast_to(u[:, None], (256, lut.shape[1]))
    # lower().compile() and call the executable directly, with PLAIN
    # numpy operands: the check may run INSIDE an outer trace
    # (resolve_dequant_impl is reached from dequant_host_batch, which
    # lives in the jitted step), where any jnp op — including asarray or
    # a jit call — would be traced symbolically, and the whole point is
    # a CONCRETE answer about this backend's compiled rounding.  The
    # compiled executable converts numpy args itself, outside tracing.
    args = (np.ascontiguousarray(u), s, b)
    compiled = jax.jit(apply_dequant_affine).lower(*args).compile()
    got = np.asarray(compiled(*args))
    ok = bool(np.array_equal(got.view(np.int32),
                             np.ascontiguousarray(lut).view(np.int32)))
    _AFFINE_DEVICE_OK[key] = ok
    return ok


def resolve_dequant_impl(spec: str | None, dequant_impl: str = "auto",
                         quantize: str = "auto") -> str:
    """The ONE resolution rule for which in-step dequant kernel runs —
    shared by the train path (``DeviceDataset``), eval
    (``parallel.sync.make_resident_eval``), the host-fed path
    (``dequant_host_batch``), so no pair of consumers can
    silently resolve differently (the train/eval-asymmetry hazard).

    ``auto`` lowers to the affine fast path when the split's 256-entry
    LUT is bitwise-reproducible by ``f32(u) * scale + bias`` (verified
    against ``make_dequant_affine`` on the host AND on this backend —
    true for the MNIST "unit" and CIFAR "cifar" loader specs); otherwise
    it keeps the bitwise contract through the one-hot LUT form, unless
    the caller asked for ``quantize="scale"`` (explicitly speed-over-
    bits), which stays affine."""
    if dequant_impl not in DEQUANT_IMPLS:
        raise ValueError(f"unknown dequant_impl {dequant_impl!r} "
                         f"(one of {DEQUANT_IMPLS})")
    if dequant_impl != "auto":
        return dequant_impl
    if spec is None:
        return "affine"     # no dequant will run; name the fast default
    if affine_matches_lut(spec) and dequant_affine_is_bitwise(spec):
        return "affine"
    return "affine" if quantize == "scale" else "onehot"


def apply_dequant_affine(u8: jnp.ndarray, scale: jnp.ndarray,
                         bias: jnp.ndarray) -> jnp.ndarray:
    """uint8 pixels -> ~float32 via the fused affine form (see
    make_dequant_affine for the ~1-ulp caveat and the measured wins)."""
    return u8.astype(jnp.float32) * scale + bias


def apply_dequant_lut(u8: jnp.ndarray, lut: jnp.ndarray) -> jnp.ndarray:
    """uint8 pixels -> float32 through a [256] / [256, C] LUT, expressed
    as a one-hot matmul so it runs on the MXU.

    The obvious ``lut[idx]`` gather is catastrophically slow on TPU: a
    chip trace of 2026-08 measured it at ~10 ns/element — 8.2 ms/step on
    ResNet-20's batch, 56% of the whole step; the A/B of the same window
    read 479 steps/s with the gather vs 1,620 with this form.

    Exactness: the one-hot rows are exact {0,1} in bfloat16 and each
    output element's dot product has exactly ONE nonzero term, so the
    result is the table entry itself — PROVIDED the table operand loses
    no bits.  A float32 table downcast to bfloat16 would lose 16
    mantissa bits, so the table is split into three bfloat16 components
    (f32 has 24 mantissa bits = 3 x 8): ``hi = bf16(v)``,
    ``mid = bf16(v - hi)``, ``lo = bf16(v - hi - mid)``.  Every split
    subtraction is exact (Sterbenz: operands within a factor of 2), the
    residual after two splits has <= 8 significant bits so ``lo`` is
    exact, and the f32 reconstruction ``(hi + mid) + lo`` is exact
    because each partial sum is representable.  Three bf16 matmuls, each
    picking one component, summed in that order — bitwise-identical to
    the host table (asserted on-chip by the quantize parity tests)."""
    from distributedtensorflowexample_tpu.data.augment_device import (
        _mm_dtype)
    md = _mm_dtype()   # bf16 on accelerators; f32 on CPU (no bf16 GEMM
    #                    there, and f32 one-hot dots are exact anyway —
    #                    the split terms below degenerate to v + 0 + 0)
    idx = u8.astype(jnp.int32)
    oh = (idx[..., None] == jnp.arange(256, dtype=jnp.int32)).astype(md)
    hi = lut.astype(md)
    mid = (lut - hi.astype(jnp.float32)).astype(md)
    lo = (lut - hi.astype(jnp.float32)
          - mid.astype(jnp.float32)).astype(md)
    if lut.ndim == 1:
        part = lambda t: jnp.einsum(
            "...k,k->...", oh, t, preferred_element_type=jnp.float32)
    else:
        # Per-channel table: channel c of pixel p uses column c —
        # contraction over the 256 axis with c as a batch dim.
        part = lambda t: jnp.einsum(
            "...ck,kc->...c", oh, t, preferred_element_type=jnp.float32)
    return (part(hi) + part(mid)) + part(lo)


def apply_dequant_gather(u8: jnp.ndarray, lut: jnp.ndarray) -> jnp.ndarray:
    """uint8 pixels -> float32 via an ELEMENTWISE ``lut[u]`` gather — the
    former default, measured as the dequant tax in one chip window of
    2026-08 (~10 ns/element, 56% of the ResNet step; 479.6 steps/s/chip
    vs 1,962.6 affine).  Retained ONLY as the ``dequant_impl="lut"``
    diagnostic; nothing resolves to it automatically."""
    idx = u8.astype(jnp.int32)
    if lut.ndim == 1:
        return jnp.take(lut, idx, axis=0)
    # Per-channel table: channel c of pixel p reads lut[u[p, c], c].
    return jnp.take_along_axis(
        lut, idx.reshape(-1, lut.shape[1]), axis=0).reshape(u8.shape)


def dequantize_images(u8: jnp.ndarray, spec: str,
                      dequant_impl: str = "onehot") -> jnp.ndarray:
    """uint8 pixels -> the float32 values the loader would have produced,
    through the named impl (default: the backend-independent bitwise
    one-hot form; pass the resolved impl for the fast path)."""
    if dequant_impl == "affine":
        s, b = make_dequant_affine(spec)
        return apply_dequant_affine(u8, jnp.asarray(s), jnp.asarray(b))
    if dequant_impl == "lut":
        return apply_dequant_gather(u8, jnp.asarray(make_dequant_lut(spec)))
    if dequant_impl != "onehot":
        # Callers pass a RESOLVED impl ("auto"/"pallas" must be lowered
        # via resolve_dequant_impl first) — routing a typo to the one-hot
        # kernel silently would be the wrong-kernel hazard the resolver
        # exists to prevent.
        raise ValueError(f"unresolved dequant_impl {dequant_impl!r} "
                         f"(expected affine, onehot, or lut)")
    return apply_dequant_lut(u8, jnp.asarray(make_dequant_lut(spec)))


class DeviceDataset:
    """Iterator yielding ``{"images", "labels", "perm"}`` device pytrees.

    ``perm`` has shape ``(num_slots, epoch_len)``: epoch ``e``'s shuffled
    index order lives in slot ``e % num_slots``.  The arrays are the same
    device buffers every step — only one perm row is replaced, once per
    epoch.  Pass ``start_step`` (e.g. after a resume) so epoch slots line
    up with the step's position arithmetic.  Pass ``num_slots`` to the
    step factory (``make_indexed_train_step(..., num_slots=ds.num_slots)``)
    so its slot arithmetic matches.
    """

    @staticmethod
    def ring_slots_for(window_steps: int, steps_per_epoch: int) -> int:
        """Perm-ring size for a ``window_steps``-step fused window: every
        epoch TWO consecutive windows can touch (a K-step window starting
        mid-epoch spans ceil(K / spe) boundaries at worst; sizing for 2K
        lets ``prefetch()`` compute the NEXT window's permutations while
        the current window is still in flight — inside the donated step
        window, off the dispatch boundary) plus one margin slot so the
        epoch prefetched one ahead never evicts a row an in-flight window
        still reads.  THE single source of the slot arithmetic — the step
        factories use it for their defaults, so dataset and gather can't
        drift."""
        return -(-2 * window_steps // steps_per_epoch) + 2

    def __init__(self, images: np.ndarray, labels: np.ndarray,
                 batch_size: int, mesh=None, seed: int = 0,
                 shuffle: bool = True, start_step: int = 0,
                 steps_per_next: int = 1, quantize: str = "auto",
                 dequant_impl: str = "auto",
                 data_sharding: str = "replicated",
                 token_data: bool = False):
        """``steps_per_next``: global steps consumed per ``next()`` — set to
        the train step's ``unroll_steps`` so the perm ring is refreshed on
        the right call.  Any value >= 1 works; the ring is sized to hold
        every epoch one window can touch plus a prefetch slot.

        ``quantize`` stores the split as uint8 in HBM when the float32
        pixels are BITWISE-recoverable from one of the known 8-bit
        pipelines (verified element-exact at build time; see
        ``data.dequant.try_quantize``): the per-step on-device gather
        then moves 4x fewer bytes.  ``"auto"``/``"scale"``/``"exact"``
        all select uint8 storage; ``"off"`` keeps the split
        float32-resident (raw uint8 input still dequantizes, exactly,
        since storage is already 8-bit).

        ``dequant_impl`` picks the in-step dequant kernel
        (``DEQUANT_IMPLS``; resolution rule: ``resolve_dequant_impl``).
        The default ``"auto"`` lowers to the fused AFFINE fast path —
        verified bitwise against the 256-entry LUT at quantize time, true
        for both shipped loader specs (one chip window of 2026-08:
        affine 1,962.6 steps/s/chip vs 479.6 for the LUT-gather default
        it replaced, vs 1,654 float32-resident) — and falls back to the
        bitwise one-hot form only for a split whose host arithmetic an
        affine map cannot reproduce.

        The dequant constants travel INSIDE the yielded data pytree
        (``data["lut"]`` or ``data["dq_scale"]/["dq_bias"]``) and the
        device gather dispatches on the pytree structure, so no call
        site can forget to dequantize.  The RESOLVED impl is recorded on
        ``self.dequant_impl`` (None when nothing dequantizes) so a run
        can say which kernel actually ran.

        ``data_sharding="sharded"`` (VERDICT r4 #8) shards the resident
        split ROW-WISE over the mesh's data axis instead of replicating
        it: per-device HBM for the split drops by the mesh size, lifting
        the per-device ceiling for datasets bigger than CIFAR.  The epoch
        permutation is then built per device shard (device ``d`` shuffles
        its own rows) and interleaved so the step's standard slice
        arithmetic hands every device positions that live in ITS shard —
        the gather stays collective-free (``sync.make_device_gather``'s
        shard_map branch translates to local row space).  Shuffling
        semantics become per-shard (the reference's per-worker dataset
        sharding under MultiWorkerMirroredStrategy) rather than global;
        rows beyond ``mesh_size * (n // mesh_size)`` are dropped.  Pass
        the SAME mode to the step factory.

        ``token_data=True`` marks an INTEGER split (transformer-LM
        tokens): no dequantization ever runs — the per-step gather
        yields raw token ids and the model upcasts.  ``quantize`` then
        selects the storage width instead of a dequant pipeline: any
        non-"off" mode stores ids that fit a byte as uint8 (4x less
        resident HBM + gather traffic than int32 — the quantized data
        path's win applied to tokens); "off" keeps/restores int32.  The
        yielded pytree carries a ``"tokens"`` marker leaf so the
        gather's dequant dispatch (static on pytree structure, like the
        dq_scale/lut keys) passes the batch through instead of refusing
        the uint8-without-constants shape."""
        if quantize not in ("auto", "off", "exact", "scale"):
            raise ValueError(f"unknown quantize mode {quantize!r}")
        self.quantize = quantize
        if data_sharding not in ("replicated", "sharded"):
            raise ValueError(f"unknown data_sharding {data_sharding!r}")
        if data_sharding == "sharded" and mesh is None:
            raise ValueError("data_sharding='sharded' requires a mesh")
        self.data_sharding = data_sharding
        self.token_data = bool(token_data)
        self.dequant: str | None = None
        if token_data:
            images = np.asarray(images)
            if not np.issubdtype(images.dtype, np.integer):
                raise ValueError(
                    f"token_data=True expects an integer token split, got "
                    f"{images.dtype} (float pipelines are the image path)")
            if quantize == "off":
                if images.dtype != np.int32:
                    images = images.astype(np.int32)
            elif images.dtype != np.uint8:
                if images.size and (images.min() < 0 or images.max() > 255):
                    raise ValueError(
                        "token ids exceed uint8 range; store them int32 "
                        "with quantize='off' (a silent wrap would corrupt "
                        "every out-of-byte id)")
                images = images.astype(np.uint8)
        elif images.dtype == np.uint8:
            # Raw bytes: downstream floats are u * (1/255) by convention.
            self.dequant = "unit"
        elif quantize != "off":
            q = _try_quantize(np.asarray(images))
            if q is not None:
                images, self.dequant = q
        # The in-step kernel, resolved ONCE here (the same rule eval and
        # the host-fed path use) and recorded for the run's summary.
        self.dequant_impl: str | None = (
            resolve_dequant_impl(self.dequant, dequant_impl, quantize)
            if self.dequant is not None else None)
        if len(images) < batch_size:
            raise ValueError(
                f"dataset of {len(images)} examples is smaller than "
                f"batch {batch_size}")
        if data_sharding == "sharded":
            # The data-axis extent, NOT mesh.size: they agree on today's
            # 1-D meshes, but the P(DATA_AXIS) row placement and the
            # gather's shard count are defined by the axis — a future
            # multi-axis mesh must not silently mis-translate indices.
            from distributedtensorflowexample_tpu.parallel.mesh import (
                DATA_AXIS)
            self._D = mesh.shape[DATA_AXIS]
        else:
            self._D = 1
        if data_sharding == "sharded":
            if batch_size % self._D:
                raise ValueError(
                    f"sharded data: batch {batch_size} must divide across "
                    f"{self._D} devices")
            n_used = self._D * (len(images) // self._D)
            images, labels = images[:n_used], labels[:n_used]
            self._rows_per_dev = n_used // self._D
            self._bpd = batch_size // self._D
            # Per-shard epoch arithmetic: each device steps through ITS
            # rows_per_dev rows in bpd-row sub-batches.
            self.steps_per_epoch = self._rows_per_dev // self._bpd
        else:
            self.steps_per_epoch = len(images) // batch_size
        self._n = len(images)
        self.epoch_len = self.steps_per_epoch * batch_size
        if steps_per_next < 1:
            raise ValueError(
                f"steps_per_next {steps_per_next} must be >= 1")
        self.num_slots = self.ring_slots_for(steps_per_next,
                                             self.steps_per_epoch)
        self._spn = steps_per_next
        self._step = int(start_step)
        self._slot_epochs: list[int | None] = [None] * self.num_slots

        if mesh is not None:
            from distributedtensorflowexample_tpu.parallel.mesh import (
                DATA_AXIS, replicated_sharding)
            repl = replicated_sharding(mesh)
            if jax.process_count() > 1:
                put = lambda x: jax.make_array_from_process_local_data(repl, x)
            else:
                put = lambda x: jax.device_put(x, repl)
            if data_sharding == "sharded":
                from jax.sharding import NamedSharding, PartitionSpec as P
                rows = NamedSharding(mesh, P(DATA_AXIS))
                if jax.process_count() > 1:
                    # Mesh device order groups devices by process (see
                    # put_global_batch): process p owns a contiguous row
                    # block of the sharded split.
                    pc, pi = jax.process_count(), jax.process_index()
                    per = self._n // pc
                    put_rows = lambda x: jax.make_array_from_process_local_data(
                        rows, np.ascontiguousarray(x[pi * per:(pi + 1) * per]))
                else:
                    put_rows = lambda x: jax.device_put(x, rows)
            else:
                put_rows = put
        else:
            repl, put = None, jax.device_put
            put_rows = put
        self.images = put_rows(np.ascontiguousarray(images))
        self.labels = put_rows(np.ascontiguousarray(labels))
        # The dequant constants ride in the yielded pytree; WHICH keys
        # are present encodes the impl family statically (pytree
        # structure), so the gather dispatches at trace time with no
        # factory plumbing: affine/pallas carry (scale, bias), the LUT
        # forms carry the 256-entry table.
        self._lut, self._affine = None, None
        if self.dequant_impl in ("affine", "pallas"):
            s, b = make_dequant_affine(self.dequant)
            self._affine = (put(s), put(b))
        elif self.dequant_impl is not None:
            self._lut = put(make_dequant_lut(self.dequant))
        # Token splits: a replicated scalar whose PRESENCE in the pytree
        # (not its value) tells the gather this uint8 batch is ids, not
        # quantized pixels — the same static-structure dispatch the
        # dq_scale/lut keys use.
        self._tokens_marker = (put(np.zeros((), np.int32))
                               if self.token_data else None)

        base = jax.random.PRNGKey(seed)

        def make_perm(epoch: jnp.ndarray) -> jnp.ndarray:
            key = jax.random.fold_in(base, epoch)
            if data_sharding == "sharded":
                # Per-shard shuffle, interleaved so global positions
                # [s*B + d*bpd, s*B + (d+1)*bpd) always hold indices from
                # device d's row block — the step's standard slice
                # arithmetic then never needs a cross-device gather.
                D, L, bpd = self._D, self._rows_per_dev, self._bpd
                keys = jax.vmap(lambda d: jax.random.fold_in(key, d))(
                    jnp.arange(D))
                if shuffle:
                    local = jax.vmap(
                        lambda k: jax.random.permutation(k, L))(keys)
                else:
                    local = jnp.broadcast_to(jnp.arange(L), (D, L))
                local = local[:, :self.steps_per_epoch * bpd]
                local = local + (jnp.arange(D) * L)[:, None]
                order = (local.reshape(D, self.steps_per_epoch, bpd)
                         .transpose(1, 0, 2).reshape(-1))
                return order.astype(jnp.int32)
            if shuffle:
                order = jax.random.permutation(key, self._n)
            else:
                order = jnp.arange(self._n)
            return order[:self.epoch_len].astype(jnp.int32)

        def set_row(pair, row, slot):
            return jax.lax.dynamic_update_slice(pair, row[None], (slot, 0))

        jit_kw = {"out_shardings": repl} if repl is not None else {}
        self._make_perm = jax.jit(make_perm, **jit_kw)
        # Donated: the stale epoch's row is overwritten in place in HBM;
        # the runtime sequences the write after any in-flight reads.
        self._set_row = jax.jit(set_row, donate_argnums=0, **jit_kw)
        self._ring = jax.jit(
            lambda: jnp.zeros((self.num_slots, self.epoch_len), jnp.int32),
            **jit_kw)()

    def _ensure_epoch(self, epoch: int) -> None:
        slot = epoch % self.num_slots
        if self._slot_epochs[slot] != epoch:
            perm = self._make_perm(jnp.asarray(epoch, jnp.int32))
            self._ring = self._set_row(self._ring, perm,
                                       jnp.asarray(slot, jnp.int32))
            self._slot_epochs[slot] = epoch

    def __iter__(self):
        return self

    def peek(self):
        """The next window's data WITHOUT consuming it — for compile/cost
        probes that must not advance the ring past the training state."""
        first = self._step // self.steps_per_epoch
        last = (self._step + self._spn - 1) // self.steps_per_epoch
        # The epochs THIS window reads, plus one ahead (the pre-round-5
        # contract: the next epoch is resident before it is first read).
        # In the steady state ``prefetch()`` — called by the loop AFTER
        # the step dispatch — already computed this exact set inside the
        # in-flight step's window, so this loop is a pure host check;
        # consumers that never call prefetch compute it here at the
        # dispatch boundary instead.
        for epoch in range(first, last + 2):
            self._ensure_epoch(epoch)
        data = {"images": self.images, "labels": self.labels,
                "perm": self._ring}
        if self._lut is not None:
            data["lut"] = self._lut
        if self._affine is not None:
            data["dq_scale"], data["dq_bias"] = self._affine
        if self._tokens_marker is not None:
            data["tokens"] = self._tokens_marker
        return data

    def __next__(self):
        data = self.peek()
        self._step += self._spn
        return data

    def prefetch(self) -> None:
        """Dispatch the NEXT window's permutation updates (plus one epoch
        of margin) — called by the train loop right AFTER it enqueues the
        step consuming the previous window, so the perm computation and
        the donated row writes overlap the in-flight step instead of
        taxing the next dispatch boundary.  Out-of-order slot overwrites
        are safe: the donated row update is stream-ordered after every
        already-enqueued read of the old row, and ``ring_slots_for``
        sizes the ring so two consecutive windows' epochs plus the margin
        never collide."""
        first = self._step // self.steps_per_epoch
        last = (self._step + self._spn - 1) // self.steps_per_epoch
        for epoch in range(first, last + 2):
            self._ensure_epoch(epoch)
