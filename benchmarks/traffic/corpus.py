"""A seeded Markov byte corpus, made in bulk: every byte has ``fanout``
successors with fixed odds, so text has structure a model can learn and no
two windows are alike."""
from __future__ import annotations

import numpy as np

BYTE_OFFSET = 6  # ids 0..5 are the byte tokenizer's specials ([PAD] 0, [MASK] 3)
MASK_ID = 3


def markov_bytes(rng: np.random.Generator, tokens: int, fanout: int = 8, chains: int = 1024):
    """``tokens`` token ids (bytes + ``BYTE_OFFSET``) as one int32 array:
    ``chains`` parallel walks laid end to end."""
    steps = -(-tokens // chains)
    succ = rng.integers(0, 256, size=(256, fanout), dtype=np.int64)
    odds = rng.dirichlet(np.full(fanout, 0.6))
    choice = rng.choice(fanout, size=(steps, chains), p=odds)
    state = rng.integers(0, 256, size=chains)
    out = np.empty((steps, chains), dtype=np.int32)
    for t in range(steps):
        state = succ[state, choice[t]]
        out[t] = state
    return (out.T.reshape(-1)[:tokens] + BYTE_OFFSET).astype(np.int32)
