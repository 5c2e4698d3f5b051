"""Training cells: the trainer's main path — ``Engine.build`` and the
program's own ``TrainLoop`` with the hooks ``Engine.run`` always
installs — driven for a fixed time at a fixed batch and sequence
length, every dispatch ending in ``block_until_ready``.

Set-up builds ONE object (the compiled step with its state), drives it
through its first steps on the window's own call and feed, and hands the
same object to the window.  After the window the program's state is
freed and the plain reference follows those first steps in float32."""

from __future__ import annotations

import gc
import time

import numpy as np

from benchmarks.harness import schema, stats, tokens
from benchmarks.harness.readers import memory_peak_bytes
from benchmarks.harness.spans import Spans
from benchmarks.harness.tracing import TracedTail


class _Probe:
    """A hook of the benchmark's own, first in the loop's list: reads,
    at the first steps only, what the comparison needs — each step's
    loss, the first gradient as the optimizer got it (momentum's trace
    after one step from zero IS that gradient), and the parameters'
    change after the last of them (``check_steps``, the cell's: three,
    or two where three would take the reference longer than the window)."""

    def __init__(self, leaf_norms, delta_norms, check_steps: int):
        self._leaf_norms, self._delta_norms = leaf_norms, delta_norms
        self._check_steps = check_steps
        self.losses: list = []
        self.first_grad_norms = None
        self.delta_norms = None

    def begin(self, loop) -> None:
        pass

    def after_step(self, step, state, metrics) -> bool:
        if step > self._check_steps:
            return False
        self.losses.append(float(metrics["loss"]))
        if step == 1:
            self.first_grad_norms = np.asarray(
                self._leaf_norms(_momentum_trace(state.opt_state,
                                                 state.params)))
        if step == self._check_steps:
            self.delta_norms = np.asarray(self._delta_norms(state.params))
        return False

    def end(self, state) -> None:
        pass


def _momentum_trace(opt_state, params):
    """The subtree of ``opt_state`` shaped like ``params``: optax's
    momentum trace."""
    import jax
    want = jax.tree.structure(params)
    found = [s for s in jax.tree.leaves(
        opt_state, is_leaf=lambda x: jax.tree.structure(x) == want)
        if jax.tree.structure(s) == want]
    if len(found) != 1:
        raise RuntimeError(f"expected one params-shaped tree in the "
                           f"optimizer state, found {len(found)}")
    return found[0]


def leaf_gaps(got: np.ndarray, ref: np.ndarray) -> np.ndarray:
    """Each leaf's gap between the program's norm and the reference's,
    against the reference's norm of that leaf or of the median leaf,
    whichever is larger (some gradients are all but 0)."""
    return np.abs(got - ref) / np.maximum(ref, np.median(ref))


def worst_leaf_gap(got: np.ndarray, ref: np.ndarray) -> float:
    return float(np.max(leaf_gaps(got, ref)))


def judge(program, ref: dict, limits: dict) -> list:
    """[(what, value, limit)]: each step's loss against the
    reference's, and the two norms by the worst leaf.  ``program`` is
    (losses, first-gradient norms, parameter-change norms)."""
    out = [(f"loss_step{i + 1}_gap", abs(a - b), limits["loss_gap"])
           for i, (a, b) in enumerate(zip(program[0], ref["losses"]))]
    out.append(("first_grad_norm_worst_leaf",
                worst_leaf_gap(program[1], ref["first_grad_norms"]),
                limits["first_grad_norm_worst_leaf"]))
    out.append(("param_change_norm_worst_leaf",
                worst_leaf_gap(program[2], ref["delta_norms"]),
                limits["param_change_norm_worst_leaf"]))
    return out


def run(run, devices) -> None:
    import jax
    import jax.numpy as jnp

    t_import = time.monotonic()
    from distributedtensorflowexample_tpu.config import RunConfig
    from distributedtensorflowexample_tpu.engine import Engine, RunSpec
    from distributedtensorflowexample_tpu.obs import metrics as obs_metrics
    from distributedtensorflowexample_tpu.parallel import (
        replicated_sharding)
    from distributedtensorflowexample_tpu.training.hooks import (
        AnomalyHook, MetricsHook)
    from distributedtensorflowexample_tpu.training.loop import TrainLoop
    from distributedtensorflowexample_tpu.training.metrics import (
        MetricsLogger)

    run.stages.append(("program_import", time.monotonic() - t_import))
    cfg, family = run.config, run.family
    seed = stats.seed31(run.seed)
    T, B = run.param("seq_len"), run.param("batch_per_chip")
    lr, mom = run.param("learning_rate"), run.param("momentum")
    log_every = run.param("log_every")
    check_steps = run.param("check_steps")

    with run.stage("tokens"):
        corpus = tokens.markov_tokens(run.param("corpus_rows"), T,
                                      cfg["vocab_size"], seed)

    run_cfg = RunConfig(
        batch_size=B, learning_rate=lr, momentum=mom, dropout=0.0,
        seed=seed, dataset="synthetic", log_every=log_every, resume=False,
        num_devices=len(devices), dtype="bfloat16",
        remat=run.param("remat"), steps_per_loop=1, quantize="off",
        device_data="on", log_dir="")
    spec = RunSpec(
        model=run.cell["config"], dataset="lm", config=run_cfg,
        token_data=True,
        model_fn=lambda c: family.build_model(
            cfg, dropout_rate=c.dropout, dtype=jnp.dtype(c.dtype),
            remat=c.remat),
        input_fn=lambda c, split: (corpus[:, :-1], corpus[:, 1:]))
    with run.stage("engine_build"):
        build = Engine(spec).build(unroll=1)
    with run.stage("weights"):
        sharding = replicated_sharding(build.mesh)
        state = build.state.replace(
            params=family.init_params(cfg, seed, sharding))
        jax.block_until_ready(state.params)
    leaf_names = [jax.tree_util.keystr(path) for path, _ in
                  jax.tree_util.tree_flatten_with_path(state.params)[0]]

    leaf_norms = jax.jit(lambda tree: jnp.stack(
        [jnp.sqrt(jnp.sum(jnp.square(x))) for x in jax.tree.leaves(tree)]))
    init = family.init_fn(cfg)
    delta_from_seed = jax.jit(lambda p, s: jnp.stack(
        [jnp.sqrt(jnp.sum(jnp.square(a - b))) for a, b in zip(
            jax.tree.leaves(p), jax.tree.leaves(init(s)))]))
    probe = _Probe(leaf_norms,
                   lambda p: delta_from_seed(p, jnp.uint32(seed)),
                   check_steps)

    # The rows the first steps will read, from the feed itself: the
    # dataset's own epoch permutation, sliced as the step slices it.
    # (Under the mesh, as the loop runs: the context is part of jit's
    # cache key, and the feed's programs must not compile again inside
    # the window at the first new epoch.)
    per_step = build.global_batch
    with build.mesh:
        perm = np.asarray(build.ds.peek()["perm"])[0]
    check_rows = [perm[i * per_step:(i + 1) * per_step]
                  for i in range(check_steps)]

    spans = Spans()
    ends: list = []             # completion time of every dispatch
    last = {}                   # the newest dispatch's loss, on the device

    def timed_step(st, batch):
        with spans.span("train_dispatch"):
            out = build.step(st, batch)
            jax.block_until_ready(out[1])
        ends.append(time.monotonic())
        last["loss"] = out[1]["loss"]
        return out

    stop = {"at_step": None, "at_time": None}

    def should_stop() -> bool:
        if stop["at_step"] is not None:
            return len(ends) >= stop["at_step"]
        return time.monotonic() >= stop["at_time"]

    hooks = [probe, MetricsHook(every=log_every),
             AnomalyHook(every=log_every)]
    spans.wrap(hooks[1], "after_step", "train_hook")
    spans.wrap(hooks[2], "after_step", "train_hook")
    loop = TrainLoop(timed_step, build.ds, 2 ** 31 - 1, hooks,
                     MetricsLogger("", num_chips=len(devices),
                                   log_every=log_every),
                     steps_per_call=1, should_stop=should_stop)

    counters = {n: obs_metrics.counter(f"loop_{n}_seconds_total")
                for n in ("input", "step", "hook")}
    with build.mesh:
        with run.stage("first_steps"):
            stop["at_step"] = run.param("warm_steps")
            state = loop.run(state)
        first_loss = probe.losses[0]

        t_open = run.open_window()
        before = {n: c.value for n, c in counters.items()}
        stop.update(at_step=None, at_time=t_open + run.seconds)
        n_before = len(ends)
        state = loop.run(state)
        t_close = time.monotonic()
        run.close_window()
        after = {n: c.value for n, c in counters.items()}
        n_window = len(ends) - n_before
        if run.traced:
            with TracedTail(run):
                stop["at_time"] = time.monotonic() + run.param(
                    "trace_seconds")
                state = loop.run(state)

    # Whole dispatches inside the window: all but one that ran past it.
    inside = [t for t in ends[n_before:] if t <= t_open + run.seconds]
    tokens_per_dispatch = per_step * T
    run.memory_peak_bytes = memory_peak_bytes(devices)
    run.attempted = n_window
    run.facts.update(
        tokens_per_dispatch=tokens_per_dispatch, seq_len=T,
        steps_per_dispatch=1, window=(t_open, t_close),
        **{f"loop_{n}_s": after[n] - before[n] for n in counters})
    run.samples["train_dispatch_s"] = spans.durations(
        "train_dispatch", (t_open, t_close))
    run.spans = spans
    if len(inside) >= 2:
        run.end_to_end["train_tokens_per_s_per_chip"] = (
            (len(inside) - 1) * tokens_per_dispatch
            / (inside[-1] - inside[0]) / len(devices))
    final_loss = float(last["loss"])
    print(f"[bench] loss: first step {first_loss:.4f}, steps 1-"
          f"{check_steps} {probe.losses}, last {final_loss}",
          flush=True)

    # --- free the program, then the reference follows the first steps ---
    program = (probe.losses[:check_steps], probe.first_grad_norms,
               probe.delta_norms)
    del state, build, loop, hooks, probe, leaf_norms, delta_from_seed, spec
    gc.collect()
    jax.clear_caches()
    ref_mod = schema.load_module(cfg, "reference")
    t0 = time.monotonic()
    ref_args = dict(
        make_params=lambda: family.init_params(cfg, seed),
        batches=[corpus[r] for r in check_rows], cfg=cfg, learning_rate=lr,
        momentum=mom, rows_per_block=run.param("reference_rows_per_block"))
    ref = ref_mod.train_steps(**ref_args)
    print(f"[bench] reference: {check_steps} float32 steps in "
          f"{time.monotonic() - t0:.2f} s (not counted in setup_s)",
          flush=True)
    for what, got, want in (
            ("first_grad_norm", program[1], ref["first_grad_norms"]),
            ("param_change_norm", program[2], ref["delta_norms"])):
        # Which leaf the worst gap is: a refused run then names it.
        gaps = leaf_gaps(got, want)
        i = int(np.argmax(gaps))
        print(f"[bench] {what}: worst leaf {leaf_names[i]} program "
              f"{got[i]:.6g} reference {want[i]:.6g} (median leaf "
              f"{np.median(want):.6g}); leaves over half the worst: "
              f"{int(np.sum(gaps > gaps[i] / 2))} of {len(gaps)}",
              flush=True)
    for what, value, limit in judge(program, ref, run.param("limits")):
        run.compare(what, value, limit)
    for precision in run.controls:
        low = ref_mod.train_steps(**ref_args, precision=precision)
        run.control_verdicts[precision] = judge(
            (low["losses"], low["first_grad_norms"], low["delta_norms"]),
            ref, run.param("limits"))
    if not np.isfinite(program[0]).all() or not (
            final_loss == final_loss and final_loss < first_loss):
        # A loss that does not fall over the window is a failed run.
        run.failed += 1
        print(f"[bench] FAIL: loss did not fall ({first_loss} -> "
              f"{final_loss})", flush=True)
