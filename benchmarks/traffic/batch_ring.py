"""`batch_ring`: a ring of distinct host batches for a train loop.

Mix parameters: `batch`, `seq`, `ring` (how many distinct batches). Every
batch is drawn from the seed: token ids uniform over the vocabulary, MLM
labels = the ids, next-sentence labels uniform over {0, 1}. The ring is
host `numpy`, so the loop that feeds it pays the host-to-device copy.
"""
from __future__ import annotations

import numpy as np


def generate(mix: dict, seed: int, vocab: int) -> list:
    """[(ids [batch, seq] int32, nsp [batch] int32), ...], `ring` long."""
    rng = np.random.default_rng(seed)
    shape = (int(mix["batch"]), int(mix["seq"]))
    return [(rng.integers(0, vocab, shape).astype(np.int32),
             rng.integers(0, 2, shape[:1]).astype(np.int32))
            for _ in range(int(mix["ring"]))]
