"""Trace capture + accounting from a compiled module's optimized HLO.

SURVEY.md §5 maps the reference's (absent, library-default) tracing row to
``jax.profiler`` + TensorBoard.  Entry points:

* :func:`trace_context` — capture a trace around any code block; view with
  TensorBoard's profile plugin or Perfetto (``xplane.pb`` under *logdir*).
* :class:`ProfilerHook` — a training :class:`~..training.hooks.Hook` that
  captures steps ``(start_step, start_step + num_steps]`` of the live loop,
  which is how "why is steps/sec low" questions get answered on real chips.
* :func:`entry_walk` — one parse of an optimized-HLO text, walked from
  ENTRY with execution weights; ``analysis/hlo_lint.py`` checks its
  contracts on it.
* :func:`collective_inventory` / :func:`collective_inventory_of` — which
  collectives a compiled step carries, with their bytes and replica groups.
* :func:`state_residency_per_device` — a train state's resident bytes per
  device, from the live shardings (the measured form of the ZeRO 1/D claims).
"""

from __future__ import annotations

import contextlib
import re
from collections import defaultdict

import jax

from distributedtensorflowexample_tpu.training.hooks import Hook


@contextlib.contextmanager
def trace_context(logdir: str):
    """Capture a ``jax.profiler`` trace of the enclosed block into *logdir*."""
    jax.profiler.start_trace(logdir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


class ProfilerHook(Hook):
    """Trace a window of live training steps.

    Starts capture after step ``start_step`` completes and stops once at
    least ``num_steps`` further steps have run, so the window contains only
    steady-state steps (never compilation, provided ``start_step`` > 0).
    The hook sees the loop at call boundaries: with a multi-step train call
    (``steps_per_loop`` K) the window rounds up to whole calls, capturing
    up to K-1 extra steps.
    Chief-only by construction on multi-host: every process traces its own
    devices into a per-process subdirectory, matching ``jax.profiler``
    multi-host semantics.
    """

    def __init__(self, logdir: str, start_step: int = 10, num_steps: int = 5):
        self._logdir = logdir
        self._start = max(0, start_step)
        self._stop = self._start + max(1, num_steps)
        self._active = False
        self._done = False

    def after_step(self, step, state, metrics) -> bool:
        if self._done:
            return False
        if not self._active and step > self._start:
            # Resume landed inside or past the window: slide it forward so
            # a requested trace still captures (stop - start) steady-state
            # steps instead of a truncated or empty one.  One-shot: _done
            # prevents re-arming after a completed capture.
            width = self._stop - self._start
            self._start = step
            self._stop = step + width
        if self._start <= step < self._stop and not self._active:
            # Drain in-flight device work so the trace begins at a step
            # boundary rather than mid-pipeline.
            jax.block_until_ready(metrics)
            jax.profiler.start_trace(self._logdir)
            self._active = True
        elif step >= self._stop and self._active:
            jax.block_until_ready(metrics)
            jax.profiler.stop_trace()
            self._active = False
            self._done = True
        return False

    def end(self, state) -> None:
        if self._active:  # loop stopped inside the trace window
            jax.profiler.stop_trace()
            self._active = False
            self._done = True


# ---------------------------------------------------------------------------
# Optimized-HLO text parse.  Every instruction line carries its output
# shape AND its operands' shapes inline, so bytes per instruction =
# output + operands — the convention HloCostAnalysis uses.

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "s4": 1, "u4": 1, "pred": 1, "c64": 8, "c128": 16,
}
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s+=\s+"     # instruction name
    # Output shape: lazy up to the first `opcode(` — tuple types may
    # contain /*index=N*/ comments, so no explicit char class.
    r"(.*?)\s+"
    r"([\w\-]+)\(")                            # opcode
_COMP_RE = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s+\(.*\)\s*->\s*.*\{\s*$")
_CALLS_RE = re.compile(r"(calls|to_apply|body|condition|true_computation"
                       r"|false_computation)=%?([\w.\-]+)")
# N-ary conditionals print their targets as a brace list instead of
# named fields: `branch_computations={%b0, %b1, ...}`.
_BRANCHES_RE = re.compile(r"branch_computations=\{([^}]*)\}")


def _shape_bytes(token: str) -> int:
    """Total bytes of every ``dtype[d0,d1,...]`` shape in *token* (tuple
    shapes and operand lists sum their members; layout suffixes ignored)."""
    total = 0
    for dt, dims in _SHAPE_RE.findall(token):
        width = _DTYPE_BYTES.get(dt)
        if width is None:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * width
    return total


def _split_computations(hlo_text: str):
    """{computation name: [(name, out_token, opcode, raw line), ...]},
    plus the ENTRY computation's name."""
    comps: dict[str, list] = {}
    cur = None
    entry = None
    for line in hlo_text.splitlines():
        mc = _COMP_RE.match(line)
        if mc:
            cur = mc.group(2)
            comps[cur] = []
            if mc.group(1):
                entry = cur
            continue
        if line.startswith("}"):
            cur = None
            continue
        if cur is None:
            continue
        mi = _INSTR_RE.match(line)
        if mi:
            # mi.end() sits just past `opcode(` — the operand list start.
            # (The line's FIRST paren may belong to a tuple output type.)
            comps[cur].append((mi.group(1), mi.group(2), mi.group(3), line,
                               mi.end()))
    return comps, entry


def _operand_token(line: str, start: int) -> str:
    """The operand list of an instruction line: everything inside the
    call parens opened at ``start`` (shapes are printed inline per
    operand).  ``start`` comes from the instruction regex — the line's
    first paren may belong to a tuple OUTPUT type, not the call."""
    inner = line[start:]
    depth = 1
    for i, ch in enumerate(inner):
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
            if depth == 0:
                return inner[:i]
    return inner


def _computation_weights(comps: dict, entry: str, unroll: int) -> dict:
    """Execution weight per computation, walked from ENTRY:
    ``call``/``conditional`` targets inherit the caller's weight,
    ``while`` bodies are weighted ``unroll`` times (the ONE while in our
    programs is the ``lax.scan`` over fused train steps, whose trip count
    IS the unroll).  Fusion ``calls=`` and reduce ``to_apply=``
    computations stay excluded — their internals don't touch memory (or
    the wire) separately."""
    weights: dict[str, int] = defaultdict(int)

    def visit(name: str, weight: int) -> None:
        weights[name] += weight
        for _, _, opcode, line, _ in comps.get(name, ()):
            if opcode == "while":
                for _, target in _CALLS_RE.findall(line):
                    visit(target, weight * max(1, unroll))
            elif opcode in ("call", "conditional"):
                for _, target in _CALLS_RE.findall(line):
                    visit(target, weight)
                mb = _BRANCHES_RE.search(line)
                if mb:
                    for target in mb.group(1).split(","):
                        target = target.strip().lstrip("%")
                        if target:
                            visit(target, weight)

    visit(entry, 1)
    return weights


def entry_walk(hlo_text: str, unroll: int = 1) -> tuple[dict, str | None,
                                                        dict]:
    """The public seam over the ENTRY-walk every per-program instrument
    shares: ``(computations, entry_name, execution_weights)`` for one
    optimized-HLO text.  ``computations`` maps name -> instruction
    tuples ``(name, out_token, opcode, raw_line, operand_start)``;
    ``entry_name`` is None when the text has no ENTRY (weights then
    empty).  Callers: the collective inventory below and
    ``analysis/hlo_lint.py``'s contract checks — one parse, one opinion
    about what the module contains."""
    comps, entry = _split_computations(hlo_text)
    if entry is None:
        return comps, None, {}
    return comps, entry, _computation_weights(comps, entry, unroll)


def state_residency_per_device(state) -> dict:
    """Per-device RESIDENT bytes of a train state, read from the live
    array shardings (one addressable shard per leaf — a replicated
    leaf's shard is the whole leaf, a row-sharded leaf's shard is its
    1/D block), split by field.  This is the measured form of the
    ZeRO 1/D claims: the state arrays ARE the compiled step's donated
    arguments, so these bytes are what ``memory_analysis().
    argument_size_in_bytes`` charges for the state (the data split and
    perm ride the same argument total; gradients are step-local and
    live in ``temp_bytes``)."""
    def shard_bytes(tree) -> int:
        total = 0
        for leaf in jax.tree.leaves(tree):
            if not hasattr(leaf, "addressable_shards"):
                continue
            shard = leaf.addressable_shards[0]
            n = 1
            for d in shard.data.shape:
                n *= int(d)
            total += n * leaf.dtype.itemsize
        return total

    params = shard_bytes(getattr(state, "params", ()))
    opt = shard_bytes(getattr(state, "opt_state", ()))
    stats = shard_bytes(getattr(state, "batch_stats", ()))
    return {"params_bytes_per_device": params,
            "opt_state_bytes_per_device": opt,
            "batch_stats_bytes_per_device": stats,
            "state_bytes_per_device": params + opt + stats}


# ---------------------------------------------------------------------------
# Per-collective accounting: which collectives carry the wire traffic —
# the sync trainer's gradient all-reduce, the --shard_update
# reduce-scatter/all-gather schedule.  The optimized HLO names every
# collective with its shapes and replica groups inline: per-instruction
# rows, a per-step multiset, and totals in the out+operands convention.

_COLLECTIVE_OPCODES = frozenset({
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
    "collective-permute"})
# Literal forms: nested brace lists ({{0,1},{2,3}}), the empty {}, and
# the iota form ([1,8]<=[8]).
_REPLICA_GROUPS_RE = re.compile(
    r"replica_groups=(\{\{.*?\}\}|\{\}|\[[^\]]*\](?:<=\[[^\]]*\])?)")


def collective_inventory(hlo_text: str, unroll: int = 1) -> dict:
    """Per-collective accounting from optimized HLO text.

    Each collective instruction becomes a row: ``opcode`` (async
    ``-start`` forms normalized to the base op; ``-done`` halves skipped
    — one wire transfer, not two), ``count`` (execution weight, whole
    module — scan bodies weighted by trip count), ``out_bytes`` /
    ``operand_bytes`` per execution, ``accounting_bytes`` (out +
    operands, the HloCostAnalysis convention), and ``replica_groups``
    (the partition literal: which devices reduce together).

    The summary normalizes by ``unroll`` so records from
    differently-fused programs compare directly:

    * ``per_step``: {opcode: {count, out_bytes, accounting_bytes}}
    * ``multiset``: {opcode: count} — the golden per-trainer inventory
      (the ``test_device_data`` collective-set assertion, generalized
      into a measurement)
    * ``total_*_per_step`` rollups.

    ``out_bytes`` is the per-op OUTPUT size; for a same-size
    all-reduce output==operand, for all-gather output is the gathered
    size, for reduce-scatter the scattered shard.  Collectives inside a
    ``conditional`` (e.g. the async worker average, gated on the period)
    are counted at the caller's weight — sustained traffic for
    period-gated ops is count/period, which the caller divides."""
    comps, entry, weights = entry_walk(hlo_text, unroll)
    empty = {"ops": [], "per_step": {}, "multiset": {},
             "total_count_per_step": 0, "total_out_bytes_per_step": 0,
             "total_accounting_bytes_per_step": 0, "unroll": max(1, unroll)}
    if entry is None:
        return empty

    rows = []
    for comp, weight in weights.items():
        for name, out_tok, opcode, line, args_at in comps.get(comp, ()):
            base = opcode[:-6] if opcode.endswith("-start") else opcode
            if base not in _COLLECTIVE_OPCODES or opcode.endswith("-done"):
                continue
            operands = _operand_token(line, args_at)
            out_b = _shape_bytes(out_tok)
            op_b = sum(_shape_bytes(s.group(0))
                       for s in _SHAPE_RE.finditer(operands))
            mg = _REPLICA_GROUPS_RE.search(line)
            rows.append({"opcode": base, "name": name, "count": weight,
                         "out_bytes": out_b, "operand_bytes": op_b,
                         "accounting_bytes": out_b + op_b,
                         "replica_groups": mg.group(1) if mg else "",
                         "out": out_tok.strip()[:60]})
    rows.sort(key=lambda r: -r["out_bytes"] * r["count"])

    u = max(1, unroll)

    def norm(x):
        # per-step weights are whole numbers for everything our programs
        # emit; keep exactness when they are, floats when they are not
        q = x / u
        return int(q) if q == int(q) else round(q, 6)

    per_step: dict[str, dict] = {}
    for r in rows:
        d = per_step.setdefault(r["opcode"],
                                {"count": 0, "out_bytes": 0,
                                 "accounting_bytes": 0})
        d["count"] += r["count"]
        d["out_bytes"] += r["out_bytes"] * r["count"]
        d["accounting_bytes"] += r["accounting_bytes"] * r["count"]
    for d in per_step.values():
        for k in d:
            d[k] = norm(d[k])
    return {
        "ops": rows,
        "per_step": dict(sorted(per_step.items(),
                                key=lambda kv: -kv[1]["out_bytes"])),
        "multiset": {op: d["count"] for op, d in sorted(per_step.items())},
        "total_count_per_step": norm(sum(r["count"] for r in rows)),
        "total_out_bytes_per_step": norm(
            sum(r["out_bytes"] * r["count"] for r in rows)),
        "total_accounting_bytes_per_step": norm(
            sum(r["accounting_bytes"] * r["count"] for r in rows)),
        "unroll": u,
    }


def collective_inventory_of(step, args, unroll: int = 1) -> dict:
    """Lower+compile a jitted *step* once and inventory its collectives.
    Degrades to ``{}`` when the backend can't lower/expose the module.
    NOTE: an AOT ``lower().compile()`` does NOT populate the jit's own
    executable cache on this jax pin, so calling this costs one extra
    compile of the program — callers gate it (OBS_COLLECTIVES=1) rather
    than paying it on every run."""
    try:
        compiled = step.lower(*args).compile()
        return collective_inventory(compiled.as_text(), unroll=unroll)
    except Exception:
        return {}
