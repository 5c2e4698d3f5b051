"""End-to-end self-healing ACCEPTANCE drills (the ISSUE's criterion),
via the real harness in tools/heal_drill.py: faultline children under a
FleetSupervisor, the remediation engine watching real health files and
ledger rows, real actuators — and the healed timeline proved BITWISE
against an uninterrupted reference run (steps_lost == 0).

Runs on the fast softmax workload (tools/heal_drill.py runs the lm_tiny
battery); each child is a fresh jax subprocess, so this file is among
the suite's longest.
"""

import io
import json
import os
import sys
from contextlib import redirect_stdout

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

pytestmark = [pytest.mark.heal, pytest.mark.faults]


def _heal_drill():
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import heal_drill
    finally:
        sys.path.pop(0)
    return heal_drill


def _by_metric(rows):
    return {r["metric"]: r for r in rows}


def test_nan_rollback_drill_bitwise(tmp_path):
    """NaN-poison → the remediator (fleet retries=0: the POLICY owns
    the restart decision) rolls back to the pinned last-good snapshot
    and relaunches; the healed run's digest and concatenated tape are
    bitwise the uninterrupted run's."""
    hd = _heal_drill()
    rows = _by_metric(hd.drill_nan(str(tmp_path), "softmax"))
    rec = rows["heal_nan_steps_lost"]
    assert rec["value"] == 0
    assert rec["detail"]["bitwise_resume"] is True
    assert rec["detail"]["heals"] == 1          # one heal relaunch
    assert rows["heal_nan_mttr_ms"]["value"] > 0
    # the rollback decision is on the ledger, renderable by obs_query
    ledger = os.path.join(str(tmp_path), "nan", "RUNS.jsonl")
    events = [json.loads(l)["event"] for l in open(ledger) if l.strip()]
    assert "heal_detect" in events and "heal_rollback" in events
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import obs_query
    finally:
        sys.path.pop(0)
    buf = io.StringIO()
    with redirect_stdout(buf):
        assert obs_query.main(["why", "drill", "--ledger", ledger]) == 0
    out = buf.getvalue()
    assert "anomaly detected: nan_loss" in out
    assert "HEALED by rollback" in out
    assert "'last_good'" in out                 # the pinned step named


def test_ckpt_shard_fault_drills_bitwise(tmp_path):
    """Shard-redundant checkpointing under REAL fleet recovery: a D=4
    ZeRO-3 gang is preempted, its snapshot set is damaged post-exit
    (one rank's directory deleted; separately one payload byte
    flipped), the resume agreement still votes for that step and the
    relaunch reconstructs the shard from its ring mirror — final state
    bitwise the uninterrupted run, zero steps lost, zero unrecovered
    mismatches."""
    hd = _heal_drill()
    rows = _by_metric(hd.drill_ckpt(str(tmp_path)))
    for plan in ("shard_loss", "bitflip"):
        rec = rows[f"heal_ckpt_{plan}_steps_lost"]
        assert rec["value"] == 0
        assert rec["detail"]["bitwise_resume"] is True
        assert rec["detail"]["reconstructs"] >= 1
        assert rows[f"heal_ckpt_{plan}_mttr_ms"]["value"] is not None
    assert rows["ckpt_shard_restore_failures"]["value"] == 0
    assert rows["ckpt_digest_mismatch_unrecovered"]["value"] == 0
    # the reconstruction (and for bitflip, the rot catch) is on the
    # ledger and renderable by obs_query why
    ledger = os.path.join(str(tmp_path), "ckpt_bitflip", "RUNS.jsonl")
    events = [json.loads(l)["event"] for l in open(ledger) if l.strip()]
    assert "ckpt_digest_mismatch" in events
    assert "ckpt_reconstruct" in events
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import obs_query
    finally:
        sys.path.pop(0)
    buf = io.StringIO()
    with redirect_stdout(buf):
        assert obs_query.main(["why", "drill", "--ledger", ledger]) == 0
    out = buf.getvalue()
    assert "BIT ROT caught" in out
    assert "ring mirror" in out


def test_slow_rank_evict_drill_bitwise(tmp_path):
    """Straggler → loss-free eviction (request_stop → TERM→143) →
    relaunch resumes from the agreed step — bitwise, zero lost steps."""
    hd = _heal_drill()
    rows = _by_metric(hd.drill_slow_rank(str(tmp_path), "softmax",
                                         delay_s=1.5))
    rec = rows["heal_slow_rank_steps_lost"]
    assert rec["value"] == 0
    assert rec["detail"]["bitwise_resume"] is True
    assert rec["detail"]["heals"] >= 1
    assert rec["detail"]["action"] == "heal_evict"
    assert rows["heal_slow_rank_mttd_ms"]["value"] is not None
    ledger = os.path.join(str(tmp_path), "slow_rank", "RUNS.jsonl")
    events = [json.loads(l)["event"] for l in open(ledger) if l.strip()]
    assert "heal_evict" in events
