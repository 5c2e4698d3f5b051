#!/usr/bin/env python3
"""chip_smoke — the quickest proof that the system still starts on the chip.

    python3 chip_smoke.py        # from the root of a checkout, on a TPU host

Drives the main paths once through the entry points a user calls, at the
full width of ``lm_base`` (8 L, d 768, 12 heads, d_ff 3072, ~57 M params)
with seeded random weights and the synthetic corpora (no network, no real
bytes):

- train: ``trainer_lm --size lm_base`` (scan-fused steps, one Orbax
  checkpoint, a second call that resumes and trains on),
  ``trainer_sync_mnist`` at its CLI defaults (the paper headline) and again
  with every Pallas kernel switched on, ``trainer_mirrored_cifar``
  (ResNet-20, on-device augmentation);
- kernels: each Pallas kernel lowered at the shapes the trainers produce,
  checked to hold a Mosaic custom call (not an interpreted loop), compiled
  directly and compared with its XLA reference on the chip;
- serve: ``tools/serve_lm.py --real --size lm_base --init_if_missing
  --drive 16``, every answer checked against a teacher-forced forward of
  the training model;
- with four or more devices also ``lm_base`` under zero1 and zero3 and
  ``serve_lm --sharded_mesh 4``, with the spread of work over the devices
  checked from ``memory_stats`` and the live shardings.

Everything runs in THIS process: a chip belongs to one process at a time,
and one process drives every device of the host.  Every failure is fatal
(an exception ends the run, exit code 1, no result line).  Without a TPU
the script exits non-zero before any leg.  It prints pass/fail, wall and
compile seconds — never a rate or a utilization: those are the
benchmark's to measure.  The last line of standard output is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}
"""

from __future__ import annotations

import contextlib
import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
PACKAGE = "distributedtensorflowexample_tpu"
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class SmokeFailure(RuntimeError):
    """A check of the smoke did not hold."""


def require(ok: bool, why: str) -> None:
    if not ok:
        raise SmokeFailure(why)


class Meter:
    """Wall and compile seconds of the legs, fed by jax's own monitoring
    events (a persistent-cache hit skips the backend compile, so a warm
    second run shows up here as fewer compile seconds and more hits)."""

    def __init__(self):
        self.compile_s = 0.0
        self.cache_hits = 0

    def install(self) -> None:
        import jax.monitoring
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, seconds: float, **_) -> None:
        if event == BACKEND_COMPILE_EVENT:
            self.compile_s += seconds

    def _event(self, event: str, **_) -> None:
        if event == CACHE_HIT_EVENT:
            self.cache_hits += 1

    @contextlib.contextmanager
    def leg(self, name: str, entry: str):
        """Time one leg and print its line.  No except clause: a failing
        leg's exception is the end of the run."""
        print(f"--- leg {name}: {entry}", file=sys.stderr, flush=True)
        facts: dict = {}
        t0, c0 = time.monotonic(), self.compile_s
        # The program's own chatter (step lines with their rates, the
        # worker's stats) goes to stderr: stdout is the smoke's record.
        with contextlib.redirect_stdout(sys.stderr):
            yield facts
        wall, comp = time.monotonic() - t0, self.compile_s - c0
        parts = " ".join(f"{k}={_fmt(v)}" for k, v in facts.items())
        print(f"leg {name}: entry={entry} {parts} wall_s={wall:.1f} "
              f"compile_s={comp:.1f} ok", flush=True)


def _fmt(v) -> str:
    return f"{v:.4f}" if isinstance(v, float) else str(v)


# --- training ---------------------------------------------------------------

def _loss_records(log_dir: str) -> list[dict]:
    """The trainer's own scalar log (training/metrics.py): one JSON line
    per log boundary."""
    path = os.path.join(log_dir, "scalars.jsonl")
    if not os.path.exists(path):
        return []
    with open(path) as f:
        rows = [json.loads(line) for line in f if line.strip()]
    return [r for r in rows if "loss" in r]


def run_trainer(facts: dict, main, argv: list[str], log_dir: str,
                devices: dict, classes: int) -> dict:
    """One call of a trainer's ``main(argv)``; the checks every training
    leg shares: it ran on the chip, and its loss is finite and lower at
    the end than at the start.  The start is the first logged loss — or
    chance level, ``ln(classes)``, when the run was already below that
    at its first record (synthetic MNIST converges inside the default
    100-step log interval)."""
    earlier = _loss_records(log_dir)
    summary = main(argv + ["--dataset", "synthetic", "--log_dir", log_dir,
                           "--data_dir", os.path.join(log_dir, "no_data")])
    losses = _loss_records(log_dir)[len(earlier):]
    require(summary["platform"] == devices["platform"]
            and summary["device_kind"] == devices["device_kind"]
            and summary["num_replicas"] == devices["device_count"],
            f"trainer ran on {summary['platform']}/{summary['device_kind']}"
            f" x{summary['num_replicas']}, expected {devices}")
    require(len(losses) >= 2, f"fewer than two loss records in {log_dir}")
    # A resumed call is judged against the start of the whole run.
    first, last = (earlier + losses)[0], losses[-1]
    require(all(math.isfinite(r["loss"]) for r in losses),
            f"non-finite loss in {log_dir}/scalars.jsonl")
    require(math.isfinite(summary["final_accuracy"]),
            "final accuracy is not finite")
    require(last["loss"] < max(first["loss"], math.log(classes)),
            f"loss did not fall: {first['loss']:.4f} at step "
            f"{first['step']} -> {last['loss']:.4f} at step {last['step']} "
            f"(chance {math.log(classes):.4f})")
    facts.update(steps=summary["steps"], logged_from=losses[0]["step"],
                 loss_first=first["loss"], loss_last=last["loss"],
                 final_accuracy=float(summary["final_accuracy"]))
    if summary.get("dequant_impl"):
        facts["dequant_impl"] = summary["dequant_impl"]
    return summary


def train_legs(meter: Meter, work: str, devices: dict) -> None:
    from distributedtensorflowexample_tpu.models import LM_VOCAB
    from distributedtensorflowexample_tpu.trainers import (
        trainer_lm, trainer_mirrored_cifar, trainer_sync_mnist)

    lm_dir = os.path.join(work, "lm_base")
    lm = ["--size", "lm_base", "--log_every", "16"]
    with meter.leg("train_lm_base", "trainers.trainer_lm.main") as facts:
        run_trainer(facts, trainer_lm.main,
                    lm + ["--train_steps", "64", "--checkpoint_every", "64"],
                    lm_dir, devices, LM_VOCAB)
        require(os.path.isdir(os.path.join(lm_dir, "checkpoints", "64")),
                "no Orbax checkpoint at step 64")
    with meter.leg("resume_lm_base", "trainers.trainer_lm.main") as facts:
        summary = run_trainer(facts, trainer_lm.main,
                              lm + ["--train_steps", "128"], lm_dir,
                              devices, LM_VOCAB)
        require(summary["steps"] == 128 and facts["logged_from"] > 64,
                f"second call did not resume from step 64: first record "
                f"at step {facts['logged_from']}, ended at "
                f"{summary['steps']}")

    with meter.leg("train_sync_mnist",
                   "trainers.trainer_sync_mnist.main") as facts:
        summary = run_trainer(facts, trainer_sync_mnist.main, [],
                              os.path.join(work, "sync_mnist"), devices, 10)
        require(summary["steps"] == 2000, "CLI default is 2000 steps")
        require(summary["final_accuracy"] > 0.9,
                f"synthetic MNIST accuracy {summary['final_accuracy']}")
    with meter.leg("train_sync_mnist_pallas",
                   "trainers.trainer_sync_mnist.main") as facts:
        run_trainer(facts, trainer_sync_mnist.main,
                    ["--train_steps", "200", "--log_every", "50",
                     "--pallas_ce", "true", "--fused_optimizer", "true",
                     "--dequant_impl", "pallas"],
                    os.path.join(work, "sync_mnist_pallas"), devices, 10)
    with meter.leg("train_mirrored_cifar",
                   "trainers.trainer_mirrored_cifar.main") as facts:
        run_trainer(facts, trainer_mirrored_cifar.main,
                    ["--train_steps", "256", "--log_every", "32"],
                    os.path.join(work, "mirrored_cifar"), devices, 10)


def sharded_train_legs(meter: Meter, work: str, devices: dict) -> None:
    """lm_base under zero1 and zero3 on every device of the host, then
    the proof that the work was spread."""
    import jax

    from distributedtensorflowexample_tpu.engine import Engine
    from distributedtensorflowexample_tpu.models import LM_VOCAB
    from distributedtensorflowexample_tpu.parallel import make_mesh
    from distributedtensorflowexample_tpu.trainers import trainer_lm
    from distributedtensorflowexample_tpu.utils.profiling import (
        state_residency_per_device)

    count = devices["device_count"]
    require(make_mesh(0).size == count,
            f"default mesh is {make_mesh(0).size} wide, {count} devices")
    base = ["--size", "lm_base", "--train_steps", "32", "--log_every", "8",
            "--bucket_grads", "auto"]
    zero1 = base + ["--shard_update", "true"]
    zero3 = base + ["--shard_params", "true"]
    with meter.leg("train_lm_base_zero1",
                   "trainers.trainer_lm.main") as facts:
        run_trainer(facts, trainer_lm.main, zero1,
                    os.path.join(work, "lm_zero1"), devices, LM_VOCAB)
    with meter.leg("train_lm_base_zero3",
                   "trainers.trainer_lm.main") as facts:
        run_trainer(facts, trainer_lm.main, zero3,
                    os.path.join(work, "lm_zero3"), devices, LM_VOCAB)
        # Residency from the LIVE shardings of the state this trainer
        # builds: params as 1/D bucket rows (row padding is the only
        # slack).
        build = Engine(trainer_lm.build_spec(
            zero3 + ["--dataset", "synthetic"])).build()
        require(build.mode == "zero3", f"resolved mode {build.mode}")
        resident = state_residency_per_device(build.state)
        full = sum(math.prod(leaf.shape) * leaf.dtype.itemsize
                   for leaf in build.zero3_layout.leaf_specs)
        frac = resident["params_bytes_per_device"] / full
        require(1 / count <= frac <= 1.02 / count,
                f"zero3 params resident at {frac:.4f} of the tree per "
                f"device, expected 1/{count}")
        facts["params_frac_per_device"] = frac
        del build
    # Every leg so far ran mesh-wide (the single-device kernel and
    # replicated-decode legs come later), so a device left out of the
    # mesh would still sit near zero here.
    peaks = [d.memory_stats()["peak_bytes_in_use"] for d in jax.devices()]
    require(min(peaks) > 0 and max(peaks) <= 2 * min(peaks),
            f"device memory peaks are not comparable: {peaks}")
    print(f"chip_smoke: spread: peak_bytes_in_use per device {peaks}",
          flush=True)


def attention_sync_leg(meter: Meter, devices: dict) -> None:
    """The sync_dp step (one GSPMD program over ``data``) at T = 1024 and
    the 124M widths, on every device and on one: the blocked attention
    kernels must engage in both (GSPMD cannot split a Mosaic call, so
    under the mesh they run per shard), and the first steps' losses must
    agree to the train cells' ``loss_gap``."""
    import jax.numpy as jnp
    import numpy as np

    from distributedtensorflowexample_tpu.config import RunConfig
    from distributedtensorflowexample_tpu.data.lm import (
        make_synthetic_tokens)
    from distributedtensorflowexample_tpu.engine import Engine, RunSpec
    from distributedtensorflowexample_tpu.models import LM_VOCAB
    from distributedtensorflowexample_tpu.models.transformer_lm import (
        TransformerLM)
    from distributedtensorflowexample_tpu.obs import metrics as obs_metrics

    count, seq, rows, steps, layers = devices["device_count"], 1024, 8, 3, 4
    tokens = make_synthetic_tokens(rows * steps, seq, LM_VOCAB, seed=25)
    taken = obs_metrics.counter("lm_attention_blocks_total")

    def losses(num_devices: int) -> list:
        cfg = RunConfig(
            batch_size=rows, global_batch=True, learning_rate=0.01,
            momentum=0.9, seed=25, dataset="synthetic", resume=False,
            num_devices=num_devices, dtype="bfloat16", steps_per_loop=1,
            quantize="off", device_data="on", log_dir="")
        spec = RunSpec(
            model="lm_base", dataset="lm", config=cfg, token_data=True,
            model_fn=lambda c: TransformerLM(
                vocab_size=LM_VOCAB, n_layers=layers, d_model=768,
                n_heads=12, d_ff=3072, max_len=seq,
                dtype=jnp.dtype(c.dtype), remat=c.remat),
            input_fn=lambda c, split: (tokens[:, :-1], tokens[:, 1:]))
        build = Engine(spec).build(unroll=1)
        require(build.mode == "sync_dp" and build.mesh.size == num_devices,
                f"resolved {build.mode} on {build.mesh.size} devices")
        before = {impl: taken.labels(impl=impl).value
                  for impl in ("pallas", "einsum")}
        state, out = build.state, []
        with build.mesh:
            for _ in range(steps):
                state, metrics = build.step(state, next(build.ds))
                out.append(float(metrics["loss"]))
        require(taken.labels(impl="pallas").value - before["pallas"]
                >= layers and taken.labels(impl="einsum").value
                == before["einsum"],
                f"the step on {num_devices} device(s) did not take the "
                f"attention kernels in all {layers} blocks")
        return out

    with meter.leg("sync_attention_t1024",
                   "Engine.build sync_dp, 124M widths") as facts:
        wide, one = losses(count), losses(1)
        gap = float(np.max(np.abs(np.subtract(wide, one))))
        require(all(np.isfinite(wide)) and gap <= 5e-4,
                f"losses on {count} devices {wide} and on one {one} "
                f"differ by {gap:.2e} (limit 5e-4)")
        facts.update(devices=count, loss_first=wide[0], loss_last=wide[-1],
                     loss_gap_to_one_device=gap)


# --- kernels ----------------------------------------------------------------

def kernel_checks() -> list:
    """``(name, check)`` for each Pallas kernel at the shapes the trainers
    produce.  A check goes through the kernel's public wrapper with
    ``interpret`` left on auto, lowers it (the text must hold the Mosaic
    custom call, not an interpreted loop), compiles it directly — a
    kernel Mosaic refuses raises here, it cannot vanish into an empty
    audit — and compares the result with the XLA reference on the chip."""
    import jax
    import jax.numpy as jnp

    from distributedtensorflowexample_tpu.data.device_dataset import (
        apply_dequant_affine, make_dequant_affine)
    from distributedtensorflowexample_tpu.models import build_model
    from distributedtensorflowexample_tpu.ops.attention import (
        einsum_causal_attention)
    from distributedtensorflowexample_tpu.ops.losses import (
        softmax_cross_entropy_rows)
    from distributedtensorflowexample_tpu.ops.pallas import (
        fused_gather_dequant, fused_sgd_apply,
        fused_softmax_cross_entropy_rows)
    from distributedtensorflowexample_tpu.ops.pallas.attention import (
        blocked_causal_attention)

    def compare(name, kernel_fn, reference_fn, args, rtol, atol):
        lowered = jax.jit(kernel_fn).lower(*args)
        require("tpu_custom_call" in lowered.as_text(),
                f"{name}: the lowered program holds no Mosaic custom call")
        got = lowered.compile()(*args)
        want = jax.jit(reference_fn)(*args)
        for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want),
                        strict=True):
            require(g.shape == w.shape and g.dtype == w.dtype,
                    f"{name}: {g.shape}/{g.dtype} vs {w.shape}/{w.dtype}")
            err = jnp.abs(g - w)
            require(bool(jnp.all(err <= atol + rtol * jnp.abs(w)))
                    and bool(jnp.all(jnp.isfinite(g))),
                    f"{name}: differs from the XLA reference, max abs "
                    f"error {float(jnp.max(err)):.3e}")

    def key(i):
        return jax.random.fold_in(jax.random.PRNGKey(0), i)

    def cross_entropy(b, c):
        # Forward and backward.  f32 exp/log differ by ulps between
        # Mosaic and XLA, on losses of order 10.
        def with_grad(rows_fn):
            def fn(logits, labels, weights):
                rows, vjp = jax.vjp(lambda x: rows_fn(x, labels), logits)
                return rows, vjp(weights)[0]
            return fn
        args = (3.0 * jax.random.normal(key(1), (b, c)),
                jax.random.randint(key(2), (b,), 0, c),
                jax.random.uniform(key(3), (b,)))
        compare(f"softmax_ce[{b},{c}]",
                with_grad(fused_softmax_cross_entropy_rows),
                with_grad(softmax_cross_entropy_rows), args,
                rtol=1e-4, atol=1e-4)

    def momentum_sgd():
        model = build_model("lm_base")
        shapes = jax.eval_shape(
            lambda: model.init(jax.random.PRNGKey(0),
                               jnp.zeros((1, 128), jnp.int32)))
        n = sum(x.size for x in jax.tree.leaves(shapes["params"]))
        args = tuple(jax.random.normal(key(i), (n,)) for i in (4, 5, 6))
        compare(f"fused_momentum_sgd[{n}]",
                lambda p, m, g, lr: fused_sgd_apply(
                    {"w": p}, {"w": m}, {"w": g}, lr, mu=0.9),
                lambda p, m, g, lr: ({"w": p - lr * (0.9 * m + g)},
                                     {"w": 0.9 * m + g}),
                args + (jnp.float32(0.1),), rtol=1e-6, atol=1e-6)

    def gather_dequant(spec, shape, batch):
        # The resident uint8 split at its real size; bitwise.
        scale, bias = (jnp.asarray(a) for a in make_dequant_affine(spec))
        args = (jax.random.bits(key(7), shape, jnp.uint8),
                jax.random.randint(key(8), (batch,), 0, shape[0]),
                scale, bias)
        compare(f"fused_gather_dequant[{spec}]", fused_gather_dequant,
                lambda images, idx, s, b: apply_dequant_affine(
                    images[idx], s, b),
                args, rtol=0.0, atol=0.0)

    def causal_attention(b, t, h, dh):
        # Forward and dq / dk / dv at a train cell's shapes, bf16 as the
        # model runs it, against the einsum chain in the same precision
        # (both within bf16's rounding of the f32 answer).
        def with_grad(att):
            def fn(q, k, v, w):
                out, vjp = jax.vjp(att, q, k, v)
                return (out,) + vjp(w)
            return fn
        args = tuple(jax.random.normal(key(i), (b, t, h, dh)).astype(
            jnp.bfloat16) for i in (9, 10, 11, 12))
        compare(f"causal_attention[{b},{t},{h},{dh}]",
                with_grad(blocked_causal_attention),
                with_grad(einsum_causal_attention), args,
                rtol=2e-2, atol=4e-2)

    return [
        ("causal_attention[4,1024,12,64]",
         lambda: causal_attention(4, 1024, 12, 64)),
        ("softmax_ce[64,10]", lambda: cross_entropy(64, 10)),
        ("softmax_ce[2048,250]", lambda: cross_entropy(2048, 250)),
        ("fused_momentum_sgd[lm_base]", momentum_sgd),
        ("fused_gather_dequant[mnist]",
         lambda: gather_dequant("unit", (60000, 28, 28, 1), 64)),
        ("fused_gather_dequant[cifar]",
         lambda: gather_dequant("cifar", (50000, 32, 32, 3), 128)),
    ]


def kernel_leg(meter: Meter) -> None:
    with meter.leg("kernels", "ops.pallas (lower, compile, compare)") \
            as facts:
        for name, check in kernel_checks():
            check()
            facts[name] = "mosaic+matches_xla"


# --- serving ----------------------------------------------------------------

def _serve_lm():
    spec = importlib.util.spec_from_file_location(
        "serve_lm", os.path.join(HERE, "tools", "serve_lm.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def check_greedy(snapshot: str, size: str, tape: dict, seed: int) -> float:
    """Every served answer against the repo's own reference (tests/
    test_serving.py): ONE teacher-forced forward of the TRAINING model
    over [prompt + answer] — at each position the served token must be
    the forward's argmax.  The model computes in bfloat16, and the
    single-query decode and the full forward round in different orders:
    at this width a bf16 and an f32 forward of the same seeded weights
    differ by up to 0.04 on logits of unit spread and |max| ~4 (CPU,
    PR 21).  So a token counts as the argmax when its logit is within
    2^-6 of the row's largest magnitude (~0.07) of the maximum; a wrong
    cache row or position moves logits by their whole spread.  Returns
    the worst gap seen."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributedtensorflowexample_tpu.serving.loadgen import make_prompt
    from distributedtensorflowexample_tpu.serving.promote import promote

    pm = promote(snapshot, size)
    vocab = pm.model.vocab_size
    prompts = {rid: make_prompt(rid, vocab, seed) for rid in tape}
    width = max(len(prompts[r]) + len(tape[r]) for r in tape)
    batch = np.zeros((len(tape), width), np.int32)
    for row, rid in enumerate(sorted(tape)):
        seq = list(prompts[rid]) + list(tape[rid])
        batch[row, :len(seq)] = seq     # causal: the tail never looks back
    logits = np.asarray(jax.jit(
        lambda params, x: pm.model.apply({"params": params}, x,
                                         train=False))(
        pm.params, jnp.asarray(batch)), np.float32)
    require(bool(np.all(np.isfinite(logits))), "reference logits not finite")
    worst = 0.0
    for row, rid in enumerate(sorted(tape)):
        start = len(prompts[rid]) - 1
        for i, token in enumerate(tape[rid]):
            at = logits[row, start + i]
            gap = float(at.max() - at[token])
            require(gap <= 2.0 ** -6 * max(1.0, float(np.abs(at).max())),
                    f"request {rid} token {i}: served {token} (logit "
                    f"{at[token]:.5f}), reference argmax "
                    f"{int(at.argmax())} ({at.max():.5f})")
            worst = max(worst, gap)
    return worst


def serve_leg(meter: Meter, work: str, devices: dict, name: str,
              extra: list[str]) -> None:
    from distributedtensorflowexample_tpu.serving.loadgen import DriveFile

    out = os.path.join(work, name)
    os.makedirs(out)
    snapshot, stats_path = os.path.join(out, "snap"), os.path.join(
        out, "stats.json")
    tape_path = os.path.join(out, "tape.jsonl")
    flags = ["--real", "--size", "lm_base", "--init_if_missing", "--drive",
             "16"] + extra
    with meter.leg(name, "tools/serve_lm.py " + " ".join(flags)) as facts:
        rc = _serve_lm().main(flags + ["--snapshot", snapshot, "--stats",
                                       stats_path, "--results", tape_path])
        require(rc == 0, f"serve_lm exited {rc}")
        with open(stats_path) as f:
            stats = json.load(f)
        require(stats["platform"] == devices["platform"]
                and stats["device_kind"] == devices["device_kind"],
                f"served on {stats['platform']}/{stats['device_kind']}")
        require(stats["completed"] == 16
                and not any(stats["rejected"].values())
                and stats["drive"]["gave_up"] == 0,
                f"requests lost or rejected: {stats}")
        tape = DriveFile(tape_path).done_ids()
        require(sorted(tape) == list(range(16))
                and stats["tokens"] == sum(len(t) for t in tape.values()),
                "the completion tape does not hold the 16 answers")
        facts.update(requests=stats["completed"], tokens=stats["tokens"],
                     worst_logit_gap=check_greedy(snapshot, "lm_base",
                                                  tape, seed=0))
        if "params_residency" in stats:
            resident = stats["params_residency"]
            frac = resident["frac_per_device"]
            require(abs(frac - 1 / resident["num_devices"]) < 1e-9,
                    f"sharded decode holds {frac} of the params per device "
                    f"on {resident['num_devices']} devices")
            facts["params_frac_per_device"] = frac


# --- the run ----------------------------------------------------------------

def main() -> int:
    if not os.path.isdir(os.path.join(HERE, PACKAGE)):
        print(f"chip_smoke: FAIL: no {PACKAGE}/ beside {__file__} — run it "
              f"from the root of a checkout", file=sys.stderr)
        return 1
    if HERE not in sys.path:
        sys.path.insert(0, HERE)
    # Force the device instead of hoping for it: with the variable unset
    # or naming another platform jax would run everything below on the
    # CPU with at most a warning.
    inherited = os.environ.get("JAX_PLATFORMS")
    os.environ["JAX_PLATFORMS"] = "tpu"
    import jax
    try:
        found = jax.devices()
    except RuntimeError as e:
        print(f"chip_smoke: FAIL: no TPU on this machine (platform 'tpu' "
              f"requested; the environment had JAX_PLATFORMS="
              f"{inherited!r}): {e}", file=sys.stderr)
        return 1
    from distributedtensorflowexample_tpu.runtime import (
        device_line, device_summary, enable_compilation_cache)
    devices = device_summary(found)
    print(f"chip_smoke: {device_line(devices)}", flush=True)
    if devices["platform"] != "tpu":
        print(f"chip_smoke: FAIL: jax chose platform "
              f"{devices['platform']!r}, not 'tpu'", file=sys.stderr)
        return 1
    print(f"chip_smoke: compile cache: {enable_compilation_cache()}",
          flush=True)
    meter = Meter()
    meter.install()
    t0 = time.monotonic()
    work = tempfile.mkdtemp(prefix="chip_smoke_")
    try:
        train_legs(meter, work, devices)
        if devices["device_count"] >= 4:
            sharded_train_legs(meter, work, devices)
            attention_sync_leg(meter, devices)
        kernel_leg(meter)
        serve_leg(meter, work, devices, "serve_lm_base", [])
        if devices["device_count"] >= 4:
            serve_leg(meter, work, devices, "serve_lm_base_sharded",
                      ["--sharded_mesh", "4"])
    finally:
        shutil.rmtree(work, ignore_errors=True)
    print(f"chip_smoke: all legs ok: wall_s={time.monotonic() - t0:.1f} "
          f"compile_s={meter.compile_s:.1f} "
          f"persistent_cache_hits={meter.cache_hits}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": devices["platform"], "kind": devices["device_kind"],
        "count": devices["device_count"]}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
