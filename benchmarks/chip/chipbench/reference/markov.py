"""The synthetic corpus, copied from the trainer's data pipeline: a fixed
random first-order Markov chain over the vocabulary (each token has
`fan_out` successors), sampled per step from the seed.  The benchmark makes
the reference's rows with this copy, so the reference reads no batch the
trainer made."""

from __future__ import annotations

import numpy as np


class MarkovTokens:
    def __init__(self, vocab_size: int, seed: int, fan_out: int = 8):
        self.vocab_size, self.seed, self.fan_out = vocab_size, seed, fan_out
        rng = np.random.default_rng(seed)
        self._succ = rng.integers(0, vocab_size, (vocab_size, fan_out),
                                  dtype=np.int32)

    def sequences(self, step: int, count: int, seq_len: int) -> np.ndarray:
        rng = np.random.default_rng((self.seed, 7919, step))
        out = np.empty((count, seq_len + 1), dtype=np.int32)
        state = rng.integers(0, self.vocab_size, count, dtype=np.int32)
        choices = rng.integers(0, self.fan_out, (count, seq_len + 1))
        for t in range(seq_len + 1):
            out[:, t] = state
            state = self._succ[state, choices[:, t]]
        return out


def step_batch(source: MarkovTokens, step: int, accum: int, rows: int,
               seq_len: int) -> tuple[np.ndarray, np.ndarray]:
    """(tokens, labels), each (accum, rows, seq_len), of 0-based step
    `step`: `accum` microbatches of `rows` sequences (all workers' rows)."""
    seqs = source.sequences(step, accum * rows, seq_len).reshape(
        accum, rows, seq_len + 1)
    return seqs[..., :-1], seqs[..., 1:].copy()
