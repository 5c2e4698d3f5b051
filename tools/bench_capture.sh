#!/bin/bash
# One on-chip capture window, ordered by artifact value (round-3 data:
# windows between outages ran as short as ~9 minutes, and the ResNet
# attribution has never yet executed on hardware):
#   phase 1  bench.py BENCH_HEADLINE_ONLY=1  -> the contract metric +
#            same-window roofline, fastest possible ($OUT_HEADLINE)
#   phase 2  bench_profile.py                -> ResNet attribution +
#            jax.profiler trace ($PROFILE_OUT, trace tarred if small)
#   phase 3  bench.py (full)                 -> all six workload lines
#            ($OUT) — spends whatever window remains
#   phase 4  trainer CLI at its defaults     -> out-of-box auto-unroll
#            throughput ($CLI_OUT, bounded 5000 steps) — confirms the
#            round-5 BASELINE.md prediction
# Each phase's output is kept even if a later phase dies; a watchdog
# exit (rc=3: backend provably wedged) stops the remaining phases.
# Launched by tools/tpu_watch.sh on backend recovery, or by hand:
#   setsid nohup tools/bench_capture.sh &
#
# Detached on purpose: a tool-timeout SIGKILL gives a chip-holding
# process no chance to release its device, so captures must never run
# under a harness timeout.
#
# The phase table below is mirrored in tools/supervise.py
# _capture_tasks (the supervised default path): phase set, artifact
# filenames, env knobs, gates.  Any phase change must land in BOTH
# until this bash path is retired — enforced by graftlint's
# keep-in-sync rule: the digest on the marker a few lines down covers
# both regions' content, so editing either side stales both digests
# until you re-sync and `python -m tools.graftlint --fix` re-stamps.

cd "$(dirname "$0")/.." || exit 1

# CAPTURE_SUPERVISED=1 delegates the whole sequence to the journaled
# supervisor (tools/supervise.py --capture): same phases, same env knobs,
# same pidfile — plus resume-across-windows and wedge-aware skipping.
# tools/tpu_watch.sh launches supervise.py directly on a recovery edge
# (CAPTURE_LAUNCHER=supervised, its default); this guard gives hand
# launches of THIS script the same path, with the inline bash phases
# below kept as the flagged fallback (CAPTURE_SUPERVISED=0, the default
# here, preserves the battle-tested behavior for `bash tools/bench_capture.sh`).
if [ "${CAPTURE_SUPERVISED:-0}" = 1 ]; then
  exec python tools/supervise.py --capture
fi

# KEEP-IN-SYNC(capture-phases) digest=705886ff9619
OUT=${OUT:-BENCH_auto_r05.json}
OUT_HEADLINE=${OUT_HEADLINE:-BENCH_headline_r05.json}
PROFILE_OUT=${PROFILE_OUT:-PROFILE_auto_r05.json}
BYTES_OUT=${BYTES_OUT:-BYTES_AUDIT_r05.json}
COLLECTIVES_OUT=${COLLECTIVES_OUT:-BENCH_collectives_r06.json}
LM_OUT=${LM_OUT:-BENCH_lm_r08.json}
TRACE_TGZ=${TRACE_TGZ:-resnet_trace_r05.tgz}
CLI_OUT=${CLI_OUT:-CLI_r05.log}
TRACE_DIR=${TRACE_DIR:-/tmp/resnet_trace}
LOG=${LOG:-/tmp/bench_capture.log}
CAPTURE_PIDFILE=${CAPTURE_PIDFILE:-/tmp/bench_capture.pid}

# Pidfile = the watcher's liveness signal (tools/tpu_watch.sh reads it
# instead of pgrep argv-matching, so any launch spelling works).  EXIT
# trap removes it only if it is still OURS — a stale-killed capture must
# not race a fresh one's pidfile away.
echo $$ > "$CAPTURE_PIDFILE"
cleanup_pidfile() {
  [ "$(cat "$CAPTURE_PIDFILE" 2>/dev/null)" = "$$" ] \
    && rm -f "$CAPTURE_PIDFILE"
}
trap cleanup_pidfile EXIT

# Detached capture: no outer harness timeout, so the full 40-min retry
# budget is affordable here (bench.py's default shrank to 900 s to fit
# under the DRIVER's ~23-25-min kill — that constraint does not apply
# to this path).  Exported so every phase gets it.
export BENCH_RETRY_BUDGET_S=${BENCH_RETRY_BUDGET_S:-2400}

# Keep whatever landed even on a failed phase: every line is flushed as
# it completes, so a partial file is a valid partial capture.
keep() { # $1=tmp $2=final
  if [ -s "$1" ]; then mv "$1" "$2"; else rm -f "$1"; fi
}

# Phase 2b body, callable from two places: the normal phase-2b slot AND
# every wedge bail.  The CPU audit needs no chip, so a wedged chip must
# never cost us the one artifact that doesn't need the chip — but it
# must not run BEFORE the on-chip phases either (it burns real window
# wall time on this shared host).  Guarded by an in-process flag: at
# most once per capture RUN (a $BYTES_OUT left by a PREVIOUS window
# must not suppress this window's fresh table — the phase-4
# fresh_measured stale-file lesson).
BYTES_AUDIT_RAN=0
run_bytes_audit() {
  [ "$BYTES_AUDIT_RAN" = 1 ] && return 0
  BYTES_AUDIT_RAN=1
  python tools/bytes_audit.py --backend cpu --workload resnet20 \
    ${BYTES_ARGS:---batch_per_chip 256 --unroll 1} \
    --json "$BYTES_OUT.tmp" >> "$LOG" 2>&1
  echo "bytes audit (cpu) rc=$?" >> "$LOG"
  # keep() checks -s on the JSON; the tool writes it only on success.
  keep "$BYTES_OUT.tmp" "$BYTES_OUT"
}

# $1=rc $2=msg — a watchdog exit (rc=3) means the backend is provably
# wedged; stop burning the window on the remaining ON-CHIP phases (the
# CPU-only audit still lands first — it holds no chip to hang on).
bail_if_wedged() {
  [ "$1" -eq 3 ] || return 0
  echo "$2" >> "$LOG"
  run_bytes_audit
  date -u >> "$LOG"
  exit 3
}

START_TS=$(date +%s)
date -u >> "$LOG"

# --- phase 1: headline only -----------------------------------------------
BENCH_HEADLINE_ONLY=1 python bench.py > "$OUT_HEADLINE.tmp" 2>> "$LOG"
rc1=$?
keep "$OUT_HEADLINE.tmp" "$OUT_HEADLINE"
echo "headline-only bench rc=$rc1" >> "$LOG"
bail_if_wedged "$rc1" "remaining phases skipped: watchdog fired (backend wedged)"

# --- phase 2: ResNet attribution + trace ----------------------------------
# A stale trace from an earlier run must not get tarred as THIS window's
# artifact.
rm -rf "$TRACE_DIR"
python bench_profile.py --trace_dir "$TRACE_DIR" > "$PROFILE_OUT.tmp" 2>> "$LOG"
rc2=$?
keep "$PROFILE_OUT.tmp" "$PROFILE_OUT"
echo "profile rc=$rc2" >> "$LOG"
if [ "$rc2" -eq 0 ] && [ -d "$TRACE_DIR" ]; then
  sz=$(du -sm "$TRACE_DIR" | cut -f1)
  if [ "$sz" -le 25 ]; then
    tar czf "$TRACE_TGZ" -C "$(dirname "$TRACE_DIR")" "$(basename "$TRACE_DIR")"
    echo "trace tarred (${sz}MB) -> $TRACE_TGZ" >> "$LOG"
  else
    echo "trace too big to commit (${sz}MB), left in $TRACE_DIR" >> "$LOG"
  fi
fi
# --- phase 2b: per-op bytes attribution (CPU backend, no chip) -----------
# The on-chip per-op table rides inside $PROFILE_OUT (bench_profile emits
# detail.bytes_audit per variant); this archives the CPU-methodology
# table alongside it for the A/B BASELINE.md documents.  Runs on the CPU
# backend IN-PROCESS (--backend cpu pins it inside the tool); a
# wedge bail in ANY phase also runs it on the way out (see
# run_bytes_audit), so a dead chip cannot block it — re-driven
# end-to-end against the down backend, PR 2: phases 1-3 sentinel, the
# audit JSON still lands.
run_bytes_audit
bail_if_wedged "$rc2" "full bench skipped: profile watchdog fired (backend wedged)"

# --- phase 2c: collective latency/bandwidth curves + knee -----------------
# bench_collectives.py --real: probes with the bench env knobs and emits
# a sentinel record when the backend is down (never hangs the window);
# under an exported JAX_PLATFORMS=cpu the record self-labels
# platform=cpu so CPU curves are never mistaken for chip numbers.
python bench_collectives.py --real --json "$COLLECTIVES_OUT.tmp" \
  >> "$LOG" 2>> "$LOG"
rc2c=$?
keep "$COLLECTIVES_OUT.tmp" "$COLLECTIVES_OUT"
echo "collectives rc=$rc2c" >> "$LOG"

# --- phase 2d: graft-LM family (bench_lm.py --real) -----------------------
# tokens/sec + MFU + the lm_base knob A/B matrix on the live backend;
# same sentinel/platform-labeling discipline as phase 2c.
python bench_lm.py --real --json "$LM_OUT.tmp" \
  >> "$LOG" 2>> "$LOG"
rc2d=$?
keep "$LM_OUT.tmp" "$LM_OUT"
echo "lm rc=$rc2d" >> "$LOG"

# --- phase 3: full bench --------------------------------------------------
python bench.py > "$OUT.tmp" 2>> "$LOG"
rc3=$?
keep "$OUT.tmp" "$OUT"
echo "full bench rc=$rc3" >> "$LOG"
bail_if_wedged "$rc3" "cli phase skipped: full-bench watchdog fired (backend wedged)"

# --- phase 4: out-of-box CLI throughput (round-5 auto-unroll claim) --------
# Only when THIS WINDOW's latest evidence ($OUT — phase 3, not phase 1,
# whose measurement may predate a mid-window death; the mtime check
# excludes a prior window's leftover file) contains a MEASURED line: the
# trainer has no probe/watchdog layer, so against a dead backend (bench
# exits 0 with unavailability sentinels, not rc=3) it would hang at
# init holding the pidfile until the watcher's next stale-kill edge.
fresh_measured() {
  [ -s "$OUT" ] || return 1
  [ "$(stat -c %Y "$OUT" 2>/dev/null || echo 0)" -ge "$START_TS" ] || return 1
  grep -q '"unit": "steps/sec/chip"' "$OUT"
}
if ! fresh_measured; then
  echo "cli phase skipped: no fresh measured line in $OUT this window" >> "$LOG"
  date -u >> "$LOG"
  exit 0
fi
# BASELINE.md round-5 prediction: the shipped trainer CLI at its defaults
# (auto steps_per_loop) should land near the bench's fused path instead
# of the ~1.4 ms/step dispatch tax.  Bounded step count, no outer
# timeout (a SIGKILL lets a chip-holding process release nothing).
python -m distributedtensorflowexample_tpu.trainers.trainer_sync_mnist \
  --dataset synthetic --train_steps 5000 --batch_size 64 \
  --log_every 1000 --log_dir /tmp/cli_bench_r05 --resume false \
  > "$CLI_OUT.tmp" 2>> "$LOG"
rc4=$?
keep "$CLI_OUT.tmp" "$CLI_OUT"
echo "cli out-of-box rc=$rc4 last=$(grep -o 'steps_per_sec_per_chip=[0-9.]*' \
  "$CLI_OUT" 2>/dev/null | tail -1)" >> "$LOG"
date -u >> "$LOG"
# KEEP-IN-SYNC-END(capture-phases)
