"""Training batches: token ids uniform over the vocabulary, a fresh batch
for every step, each a pure function of (seed, step).

Every seed gives the same amount of work: the same batch shape at every
step. Read from the job's file: ``batch_size`` and ``seq_len``.
"""
from __future__ import annotations

import numpy as np


class Batches:
    def __init__(self, job: dict, seed: int, vocab_size: int):
        self.seed = int(seed)
        self.vocab_size = int(vocab_size)
        self.shape = (int(job["batch_size"]), int(job["seq_len"]) + 1)
        self.tokens_per_step = int(job["batch_size"]) * int(job["seq_len"])

    def batch(self, step: int) -> np.ndarray:
        """``[batch_size, seq_len + 1]`` int32 ids of step ``step``, made
        on the host."""
        rng = np.random.default_rng([self.seed, step])
        return rng.integers(0, self.vocab_size, size=self.shape,
                            dtype=np.int32)


def build(job: dict, seed: int, vocab_size: int) -> Batches:
    return Batches(job, seed, vocab_size)
