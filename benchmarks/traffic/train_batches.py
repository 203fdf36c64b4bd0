"""Training batches as the program's collators shape them: dicts of
``input_ids``, ``labels`` and ``pad_mask``, every row another window of the
corpus. One generator for every training cell; the cell's file picks the
task and the sizes."""
from __future__ import annotations

import numpy as np

from .corpus import BYTE_OFFSET, MASK_ID, markov_bytes

IGNORE = -100


class TrainBatches:
    """``next_batch()`` hands out one host batch. The seed fixes the corpus
    and the order of windows; shapes never change."""

    def __init__(self, params: dict, seed: int):
        self.task = params["task"]
        if self.task not in ("clm", "mlm"):
            raise ValueError(f"unknown training task {self.task!r}")
        self.batch, self.seq_len = int(params["batch"]), int(params["seq_len"])
        self.mask_prob = float(params.get("mask_prob", 0.15))
        self.rng = np.random.default_rng([int(seed), 0x7261])
        self.corpus = markov_bytes(
            self.rng, int(params["corpus_tokens"]), int(params.get("markov_fanout", 8))
        )
        self.tokens_per_batch = self.batch * self.seq_len

    def _windows(self, width: int) -> np.ndarray:
        starts = self.rng.integers(0, len(self.corpus) - width, size=self.batch)
        return self.corpus[starts[:, None] + np.arange(width)[None, :]]

    def next_batch(self) -> dict:
        pad = np.zeros((self.batch, self.seq_len), dtype=bool)
        if self.task == "clm":
            w = self._windows(self.seq_len + 1)
            return {"input_ids": w[:, :-1].copy(), "labels": w[:, 1:].copy(), "pad_mask": pad}
        ids = self._windows(self.seq_len)
        u = self.rng.random(ids.shape)
        chosen = u < self.mask_prob
        labels = np.where(chosen, ids, IGNORE).astype(np.int32)
        # of the chosen: 80% [MASK], 10% a random byte, 10% left as they are
        inputs = np.where(u < 0.8 * self.mask_prob, MASK_ID, ids)
        randomised = (u >= 0.8 * self.mask_prob) & (u < 0.9 * self.mask_prob)
        random_ids = self.rng.integers(BYTE_OFFSET, BYTE_OFFSET + 256, size=ids.shape)
        inputs = np.where(randomised, random_ids, inputs).astype(np.int32)
        return {"input_ids": inputs, "labels": labels, "pad_mask": pad}
