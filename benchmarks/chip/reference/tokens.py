"""The synthetic token stream, written out again for the reference.

The same algorithm as the program's ``SyntheticTokenSource`` (a noisy
affine recurrence t' = (5 t + 17) mod V, first tokens and flips drawn by
Zipf's law from a Philox stream keyed by the seed and counted by the
step), so the reference can make each step's batch from the seed
without taking the program's arrays.
"""
from __future__ import annotations

import numpy as np


def block(vocab: int, seed: int, step: int, batch: int, seq: int,
          noise: float = 0.15) -> np.ndarray:
    cdf = np.cumsum(1.0 / np.arange(1, vocab + 1))
    cdf = cdf / cdf[-1]
    rng = np.random.Generator(np.random.Philox(key=seed,
                                               counter=[0, 0, 0, step]))

    def zipf(size):
        ids = np.searchsorted(cdf, rng.random(size))
        return np.minimum(ids, vocab - 1).astype(np.int32)

    out = np.empty((batch, seq + 1), dtype=np.int32)
    out[:, 0] = zipf(batch)
    flips = rng.random((batch, seq)) < noise
    rand = zipf((batch, seq))
    for t in range(seq):
        nxt = (out[:, t] * 5 + 17) % vocab
        out[:, t + 1] = np.where(flips[:, t], rand[:, t], nxt)
    return out


def batch_at(vocab: int, seed: int, step: int, batch: int,
             seq: int) -> dict:
    """Step ``step``'s (0-based) tokens and next-token labels."""
    blk = block(vocab, seed, step, batch, seq)
    return {"tokens": blk[:, :-1], "labels": blk[:, 1:]}
