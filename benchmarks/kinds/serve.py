"""Serving cells: ``DecodeEngine`` behind ``ContinuousBatcher`` — what
``tools/serve_lm.py`` runs — under one general generator whose
parameters are the traffic file's.

``arrivals: backlog``  every request is queued before the window opens
and more are queued than it can drain; the end-to-end number is output
tokens per second between whole decode boundaries.
``arrivals: open``     requests are submitted by one thread at due times
fixed by the seed, whatever the system does; latency is timed from the
due time.

Both start from a seeded population already in flight (requests whose
outputs are cut to a residual, admitted a few per boundary), so the
window opens on a system in its steady mix and not on a start-up wave.
The multiset of (prompt, output) lengths and of arrival gaps is the
same for every seed; the seed sets their order and the token ids.
"""

from __future__ import annotations

import collections
import gc
import threading
import time

import numpy as np

from benchmarks.harness import schema, stats, tokens
from benchmarks.harness.readers import memory_peak_bytes
from benchmarks.harness.spans import Spans
from benchmarks.harness.tracing import TracedTail


#: What the benchmark notes after every boundary of the serving loop.
Boundary = collections.namedtuple(
    "Boundary", "time tokens_so_far busy live_cache_rows")


class Planned:
    """One request of the seeded plan."""
    __slots__ = ("index", "prompt", "max_new", "due", "req", "token_times")

    def __init__(self, index, prompt, max_new, due=None):
        self.index, self.prompt, self.max_new, self.due = (
            index, prompt, int(max_new), due)
        self.req = None             # the program's Request, once submitted
        self.token_times: list = []


def plan_requests(run, rng, count: int, first_residual: int) -> list:
    """``count`` requests: the cell's multiset of length pairs, cycled,
    each cycle in a new seeded order; the first ``first_residual`` have
    their output cut to a stratified share of itself (what is left of a
    request caught in flight)."""
    n = run.param("length_pairs")
    pl, ol = stats.length_pairs(n, run.param("prompt_tokens"),
                                run.param("output_tokens"),
                                run.param("cache_len"))
    order = np.concatenate([rng.permutation(n)
                            for _ in range(-(-count // n))])[:count]
    share = (rng.permutation(first_residual) + 0.5) / max(1, first_residual)
    vocab = run.config["vocab_size"]
    out = []
    for i, k in enumerate(order):
        new = int(ol[k])
        if i < first_residual:
            new = max(2, int(np.ceil(share[i] * new)))
        out.append(Planned(i, tokens.uniform_prompt(rng, pl[k], vocab), new))
    return out


def bucket_of(length: int, buckets) -> int:
    return next(b for b in buckets if length <= b)


def backlog_shapes(plan, buckets, slots: int, per_step: int,
                   steps: int) -> set:
    """The (bucket, B) prefill shapes the closed backlog will ask for,
    by replaying on lengths alone what the run does: ``per_step``
    requests are queued before each boundary until one boundary leaves
    some of them waiting, then all the rest; at every boundary the free
    slots are filled from the head of the queue, prompts of one padding
    bucket share a prefill, and a request of n tokens holds its slot
    for n - 1 decode steps."""
    left = [0] * slots
    head, avail, shapes = 0, 0, set()
    for _ in range(steps):
        if avail < len(plan):
            avail = len(plan) if head < avail else avail + per_step
        free = [i for i, r in enumerate(left) if r == 0]
        group: dict = {}
        while free and head < min(avail, len(plan)):
            p = plan[head]
            head += 1
            left[free.pop(0)] = p.max_new - 1
            b = bucket_of(len(p.prompt), buckets)
            group[b] = group.get(b, 0) + 1
        shapes.update(group.items())
        left = [max(0, r - 1) for r in left]
    return shapes


def open_shapes(plan, buckets, slots: int, ramp: int, per_step: int,
                within_s: float) -> set:
    """Shapes an open loop may ask for: the ramp's groups, and for every
    bucket each B up to the most arrivals of that bucket the seeded
    schedule puts within any ``within_s`` seconds (several boundaries'
    worth, so no jitter of the boundaries can pass it)."""
    shapes = set()
    for i in range(0, ramp, per_step):
        group: dict = {}
        for p in plan[i:min(ramp, i + per_step)]:
            b = bucket_of(len(p.prompt), buckets)
            group[b] = group.get(b, 0) + 1
        shapes.update(group.items())
    by_bucket: dict = {}
    for p in plan[ramp:]:
        by_bucket.setdefault(bucket_of(len(p.prompt), buckets),
                             []).append(p.due)
    for b, dues in by_bucket.items():
        dues = np.sort(np.asarray(dues))
        most = int(np.max(np.searchsorted(dues, dues + within_s, "right")
                          - np.arange(len(dues))))
        shapes.update((b, k) for k in range(1, min(slots, max(2, most)) + 1))
    return shapes


def warm(engine, shapes) -> None:
    """Compile (or load) each prefill shape and the decode step, then
    park every slot again."""
    for bucket, B in sorted(shapes):
        length = bucket if bucket + 1 <= engine.cache_len else bucket - 1
        engine.prefill_many([(s, np.zeros((length,), np.int32), 1)
                             for s in range(B)])
    engine.decode(busy=[])
    for s in range(engine.slots):
        engine.set_slot(s, 0, 0)


def run(run, devices) -> None:
    import jax
    import jax.numpy as jnp

    t_import = time.monotonic()
    from distributedtensorflowexample_tpu.serving.engine import DecodeEngine
    from distributedtensorflowexample_tpu.serving.queue import (
        ContinuousBatcher, RequestQueue)

    run.stages.append(("program_import", time.monotonic() - t_import))
    cfg, family = run.config, run.family
    seed = stats.seed31(run.seed)
    rng = np.random.default_rng([seed, 2])
    slots, cache_len = run.param("slots"), run.param("cache_len")
    arrivals = run.param("arrivals")
    ramp, per_step = run.param("in_flight_at_open"), run.param(
        "ramp_per_boundary")
    tail_s = run.param("trace_seconds") if run.traced else 0.0

    with run.stage("plan"):
        horizon = run.seconds + tail_s
        if arrivals == "open":
            rate = run.param("requests_per_s")
            count = ramp + int(np.ceil(rate * horizon))
        else:
            count = ramp + int(np.ceil(
                run.param("backlog_requests_per_s") * (horizon + 10)))
        plan = plan_requests(run, rng, count, ramp)
        if arrivals == "open":
            n = run.param("length_pairs")
            gaps = np.concatenate([
                rng.permutation(stats.exponential_quantiles(n, 1.0 / rate))
                for _ in range(-(-(count - ramp) // n))])[:count - ramp]
            for p, due in zip(plan[ramp:], np.cumsum(gaps)):
                p.due = float(due)          # seconds after the window opens

    with run.stage("weights"):
        model = family.build_model(cfg, dtype=jnp.bfloat16)
        params = family.init_params(cfg, seed)
        jax.block_until_ready(params)
    with run.stage("engine"):
        engine = DecodeEngine(model, params, slots=slots,
                              cache_len=cache_len)
        queue = RequestQueue(engine.vocab)
        batcher = ContinuousBatcher(engine, queue, slo_ms=0, eos_id=None)
    with run.stage("warm_shapes"):
        if arrivals == "open":
            shapes = open_shapes(plan, engine.buckets, slots, ramp,
                                 per_step, run.param("burst_window_s"))
        else:
            steps = int((horizon + 10) * run.param("boundaries_per_s"))
            shapes = backlog_shapes(plan, engine.buckets, slots, per_step,
                                    steps)
        # The same grid for every seed (so set-up does the same work),
        # and beyond it whatever this seed's plan can still ask for.
        shapes |= {(int(b), k) for b, most in run.param(
            "prefill_batches").items() for k in range(1, most + 1)}
        warm(engine, shapes)
        print(f"[bench] warmed {len(shapes)} prefill shapes "
              f"{sorted(shapes)}", flush=True)

    spans = Spans()
    spans.wrap(engine, "decode", "serve_decode")
    spans.wrap(engine, "prefill_many", "serve_prefill")

    submitted: list = []        # Planned, in submission order
    live: list = []
    state = {"next_admit": 0, "tokens": 0, "admitted": 0}
    boundaries: list = []       # a Boundary after every step of the loop

    def submit(p: Planned) -> None:
        p.req = queue.submit(p.prompt, p.max_new, rid=f"r{p.index}")
        submitted.append(p)

    def boundary() -> int:
        """One step of the serving loop and the benchmark's bookkeeping
        at its end: which requests got a token, and when."""
        with spans.span("serve_decode_boundary"):
            n = batcher.step()
        now = time.monotonic()
        while state["next_admit"] < len(submitted):
            p = submitted[state["next_admit"]]
            if p.req.admit_t is None and not p.req.done.is_set():
                break
            state["next_admit"] += 1
            if p.req.admit_t is not None:
                p.token_times.append(p.req.first_token_t)
                live.append(p)
        for p in live:
            if len(p.req.tokens) > len(p.token_times):
                p.token_times.append(now)
        live[:] = [p for p in live if not p.req.done.is_set()]
        state["tokens"] += n + batcher.admitted_total - state["admitted"]
        state["admitted"] = batcher.admitted_total
        boundaries.append(Boundary(now, state["tokens"], n,
                                   int(engine.positions.sum())))
        return n

    with run.stage("ramp"):
        i = 0
        if arrivals == "open":
            while i < ramp:
                for p in plan[i:min(ramp, i + per_step)]:
                    submit(p)
                i += per_step
                boundary()
        else:
            # A few per boundary until a boundary leaves some waiting
            # (every slot is busy), then the whole backlog.
            while i < len(plan):
                k = len(plan) if len(queue) else i + per_step
                for p in plan[i:k]:
                    submit(p)
                i = k
                boundary()

    t_open = run.open_window()
    t_close = t_open + run.seconds
    t_end = t_close + tail_s
    n_ramp_boundaries = len(boundaries)

    def generate() -> None:
        for p in plan[ramp:]:
            due = t_open + p.due
            if due >= t_end:
                break
            delay = due - time.monotonic()
            if delay > 0:
                time.sleep(delay)
            submit(p)

    gen = None
    if arrivals == "open":
        gen = threading.Thread(target=generate, name="bench-loadgen",
                               daemon=True)
        gen.start()

    def serve_until(t: float) -> None:
        while time.monotonic() < t:
            if boundary() == 0:
                queue.wait_nonempty(0.005)

    serve_until(t_close)
    run.close_window()
    run.memory_peak_bytes = memory_peak_bytes(devices)
    if run.traced:
        n_tail = len(boundaries)
        with TracedTail(run):
            serve_until(t_end)
        run.facts["tail_boundaries"] = boundaries[n_tail:]
    if gen is not None:
        gen.join(timeout=30)
        if gen.is_alive():
            raise RuntimeError("the load generator did not stop")
        # Grace: every request due in the window gets its first token.
        due_in = [p for p in submitted[ramp:] if t_open + p.due < t_close]
        grace = time.monotonic() + run.param("grace_s")
        while time.monotonic() < grace and any(
                p.req.admit_t is None and not p.req.done.is_set()
                for p in due_in):
            boundary()

    # ---- the window's numbers ------------------------------------------
    inside = [b for b in boundaries[n_ramp_boundaries - 1:]
              if b.time <= t_close]
    gaps = sorted(((b.time - a.time, b.time - t_open)
                   for a, b in zip(inside, inside[1:])), reverse=True)[:3]
    print("[bench] window opened at %.1f s; longest boundaries: %s" % (
        t_open - run.t_start, ", ".join(
            f"{1e3 * g:.0f} ms ending {t:.1f} s in" for g, t in gaps)),
        flush=True)
    run.spans = spans
    run.facts.update(window=(t_open, t_close), slots=slots,
                     boundaries=inside)
    run.samples["decode_step_s"] = spans.durations(
        "serve_decode", (t_open, t_close))
    run.samples["prefill_s"] = spans.durations(
        "serve_prefill", (t_open, t_close))
    finished = [p for p in submitted if p.req.done.is_set()
                and p.req.outcome == "ok"
                and t_open <= p.req.done_t <= t_close]
    if arrivals == "backlog":
        if len(inside) >= 2:
            run.end_to_end["serve_tokens_per_s"] = (
                (inside[-1].tokens_so_far - inside[0].tokens_so_far)
                / (inside[-1].time - inside[0].time))
        bad = [p for p in submitted if p.req.done.is_set()
               and p.req.outcome != "ok"]
        run.attempted = len(finished) + len(bad)
        run.failed = len(bad)
        if not len(queue):
            run.failed += 1
            print("[bench] FAIL: the backlog ran dry inside the window; "
                  "raise backlog_requests_per_s", flush=True)
    else:
        starts = sorted(t0 for t0, _ in spans.tape.get("serve_prefill", []))
        ttft, wait, late, missed = [], [], [], 0
        for p in due_in:
            due = t_open + p.due
            late.append(p.req.submit_t - due)
            if p.req.first_token_t is None:
                missed += 1
                continue
            ttft.append(p.req.first_token_t - due)
            k = np.searchsorted(starts, p.req.admit_t, "right") - 1
            wait.append(starts[k] - due)
        itl = [b - a for p in submitted
               for a, b in zip(p.token_times[1:], p.token_times[2:])
               if t_open <= b <= t_close]
        run.samples.update(ttft_s=ttft, queue_wait_s=wait, late_s=late,
                           itl_s=itl)
        for name, s in (("serve_ttft_p95_ms", ttft),
                        ("serve_itl_p95_ms", itl)):
            q = stats.percentile(s, 0.95)
            if q is None:
                print(f"[bench] {name}: only {len(s)} samples, fewer than "
                      f"{stats.MIN_BEYOND} beyond the 95th percentile; "
                      f"left out", flush=True)
            else:
                run.end_to_end[name] = 1e3 * q
        run.attempted = len(due_in)
        run.failed = missed + sum(p.req.outcome not in ("", "ok")
                                  for p in due_in)
        if ttft:
            print(f"[bench] ttft ms: median {1e3 * np.median(ttft):.1f}, "
                  f"max {1e3 * max(ttft):.1f}; queued at the end "
                  f"{len(queue)}, in flight {len(live)}", flush=True)
        print(f"[bench] {len(due_in)} requests due in the window, "
              f"{len(ttft)} first tokens, {len(itl)} token gaps, "
              f"{len(finished)} finished inside it", flush=True)

    # ---- free the program; the reference reads what was served ---------
    wrong = [p for p in finished if len(p.req.tokens) != p.max_new]
    run.failed += len(wrong)
    sample = pick_sample(finished, rng, run.param("reference_requests"))
    served = [(p.prompt, np.asarray(p.req.tokens, np.int32)) for p in sample]
    del engine, batcher, queue, params, model, submitted, live, plan
    gc.collect()
    jax.clear_caches()
    ref_mod = schema.load_module(cfg, "reference")
    t0 = time.monotonic()
    ref_params = family.init_params(cfg, seed)
    widest, n_tok = 0.0, 0
    for prompt, toks in served:
        got = ref_mod.served_token_gaps(ref_params, prompt, toks, cfg)
        widest, n_tok = max(widest, got["widest"]), n_tok + got["tokens"]
    print(f"[bench] reference: {len(served)} requests, {n_tok} served "
          f"tokens in {time.monotonic() - t0:.2f} s (not counted in "
          f"setup_s)", flush=True)
    limit = run.param("limits")["served_logit_gap_widest"]
    if served:
        run.compare("served_logit_gap_widest", widest, limit)
    for precision in run.controls:
        low = max(ref_mod.served_token_gaps(
            ref_params, prompt, toks, cfg, control=precision)["widest"]
            for prompt, toks in served)
        run.control_verdicts[precision] = [
            ("served_logit_gap_widest", low, limit)]


def pick_sample(finished: list, rng, count: int) -> list:
    """A seeded sample of the requests the window finished, the longest
    (prompt plus served tokens) always in it."""
    if not finished:
        return []
    longest = max(finished, key=lambda p: len(p.prompt) + len(p.req.tokens))
    rest = [p for p in finished if p is not longest]
    take = min(count - 1, len(rest))
    picked = [rest[i] for i in rng.permutation(len(rest))[:take]]
    return [longest] + picked
