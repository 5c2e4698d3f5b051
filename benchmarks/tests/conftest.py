"""Tests of the benchmark's own yardstick.  Run by hand:

    JAX_PLATFORMS=cpu python3 -m pytest benchmarks/tests -q

They are not part of ``tests/`` (the tier-1 count does not move)."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

#: A GPT-2 of toy sizes: what the chip-less tests override the
#: configuration file's sizes with.
TINY_CONFIG = dict(n_layer=2, n_embd=64, n_head=4, n_positions=64,
                   vocab_size=300)
TINY_TRAIN = dict(seq_len=64, batch_per_chip=4, corpus_rows=64,
                  log_every=5, trace_seconds=1, learning_rate=0.05)
TINY_SERVE = dict(
    slots=8, cache_len=64, length_pairs=32,
    prompt_tokens=dict(median=12, sigma=0.8, min=4, max=40),
    output_tokens=dict(median=10, sigma=0.6, min=3, max=20),
    in_flight_at_open=8, backlog_requests_per_s=400,
    boundaries_per_s=400, trace_seconds=1,
    prefill_batches={"8": 2, "16": 2})
