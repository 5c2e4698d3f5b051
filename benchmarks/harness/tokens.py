"""Token inputs from the seed.  The training corpus has the form of the
program's own ``data/lm.make_synthetic_tokens`` (a seeded order-1 Markov
chain with peaked transitions, so a loss can fall), copied here because
the yardstick's inputs may not change when the program does."""

from __future__ import annotations

import numpy as np


def markov_tokens(num: int, seq_len: int, vocab: int, seed: int,
                  follow: float = 0.85) -> np.ndarray:
    """[num, seq_len + 1] int32: from token t the next is ``perm[t]``
    with probability ``follow``, else uniform.  Rows differ (their
    starts and their departures from the chain are drawn per row)."""
    rng = np.random.default_rng([seed, 1])
    perm = rng.permutation(vocab).astype(np.int32)
    seq = np.empty((num, seq_len + 1), np.int32)
    seq[:, 0] = rng.integers(0, vocab, size=num)
    follows = rng.random((seq_len, num)) < follow
    rand_tok = rng.integers(0, vocab, size=(seq_len, num), dtype=np.int32)
    for t in range(1, seq_len + 1):
        seq[:, t] = np.where(follows[t - 1], perm[seq[:, t - 1]],
                             rand_tok[t - 1])
    return seq


def uniform_prompt(rng: np.random.Generator, length: int,
                   vocab: int) -> np.ndarray:
    return rng.integers(0, vocab, size=int(length), dtype=np.int32)
