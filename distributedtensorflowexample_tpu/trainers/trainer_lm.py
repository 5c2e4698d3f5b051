"""graft-LM — the flagship transformer workload (ROADMAP direction #5).

A decoder-only LM (models/transformer_lm.py) on the deterministic
synthetic token corpus (data/lm.py), run through the SAME shared trainer
runner as every reference config — so sync, async-PS emulation,
``--remat block``, ``--shard_update``, ``--bucket_grads``,
``--shard_params`` (ZeRO-3), device-resident (uint8 token) data,
checkpoints, supervision, and telemetry all apply unchanged.  BN-free
by construction: the bucketing/ZeRO BatchNorm refusals never trigger.

  python -m distributedtensorflowexample_tpu.trainers.trainer_lm \
      --size lm_tiny --train_steps 600
  python -m ...trainer_lm --size lm_base --shard_update true \
      --bucket_grads auto --remat block      # the knobs, where they bind
  python -m ...trainer_lm --size lm_base --shard_params true \
      --bucket_grads auto                    # ZeRO-3: params+grads+opt
                                             # resident 1/D per device,
                                             # double-buffered per-bucket
                                             # all-gather prefetch; NOTE
                                             # the checkpoint layout
                                             # becomes zero3_rows (resume
                                             # needs the same knobs+D)

``--size`` selects the ladder rung (lm_tiny | lm_small | lm_base —
models.LM_SIZES); everything else is the standard flag surface.
"""

from __future__ import annotations

import argparse
import sys

from distributedtensorflowexample_tpu.config import parse_flags
from distributedtensorflowexample_tpu.engine import Engine, RunSpec
from distributedtensorflowexample_tpu.models import LM_SIZES


def build_spec(argv=None) -> RunSpec:
    """The declaration ``main`` runs, resolved from ``argv`` — also what
    a caller hands ``Engine(...).build()`` to get this trainer's state
    and step without the hook stack."""
    sp = argparse.ArgumentParser(add_help=False)
    sp.add_argument("--size", default="lm_tiny", choices=sorted(LM_SIZES))
    ns, rest = sp.parse_known_args(argv)
    overrides = dict(batch_size=16, train_steps=600, learning_rate=0.1,
                     momentum=0.9, dataset="lm", dropout=0.0,
                     log_every=100)
    if ns.size == "lm_base":
        # Defaults from an XLA:CPU A/B at lm_base/D=4 (compiled-module
        # numbers, never timed on chips: ROADMAP Queue 1 item 7):
        # remat=block cut the per-device temp arena 24.6% at bit-equal
        # forward math, and bucket_grads fused 104
        # per-parameter all-reduces into 68 knee-sized ones at
        # unchanged math.  Both are parity-safe knobs; --shard_update
        # stays opt-in because it changes the checkpoint's
        # optimizer-state layout (a resume contract, not just a
        # schedule).  The learning rate is the chip's verdict (PR 21,
        # one v5e, 256 steps each): the ladder's 0.1 with momentum 0.9
        # and no warmup is too hot at this depth and width — loss 5.67,
        # 5.46, 4.82 at steps 16-48, then 7.85 and worse (the CPU
        # oscillates the same way) — while 0.03 fell to the corpus's
        # ~1.3-nat floor by step 128.  Explicit flags still win — these
        # are argparse defaults.
        overrides.update(remat="block", bucket_grads="auto",
                         learning_rate=0.03)
    cfg = parse_flags(rest, description=__doc__, **overrides)
    return RunSpec(model=ns.size, dataset="lm", config=cfg)


def main(argv=None) -> dict:
    return Engine(build_spec(argv)).run()


if __name__ == "__main__":
    summary = main(sys.argv[1:])
    print(f"final accuracy: {summary.get('final_accuracy', float('nan')):.4f}")
