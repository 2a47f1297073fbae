"""The general generator: every traffic mix is a data file that this module
reads.

``kind: train_tokens``: batches of ``batch`` rows of ``seq_len`` tokens,
uniform over the vocabulary, the labels the next token of each row. Step
``k`` of seed ``s`` draws from its own stream ``(s, k)``, so every step's
rows differ and equal seeds give equal batches. The mix also sets the
monitor's sampling interval.
"""

from __future__ import annotations

from typing import Dict

import numpy as np


class TokenSource:
    """A data source with the program's ``get(step)`` interface."""

    def __init__(self, mix: dict, vocab_size: int, seed: int):
        if mix["kind"] != "train_tokens":
            raise ValueError(f"not a token mix: {mix['kind']!r}")
        self.seq_len = int(mix["seq_len"])
        self.batch = int(mix["batch"])
        self.vocab_size = int(vocab_size)
        self.seed = int(seed)

    def get(self, step: int) -> Dict[str, np.ndarray]:
        rng = np.random.default_rng([self.seed, int(step)])
        toks = rng.integers(0, self.vocab_size,
                            (self.batch, self.seq_len + 1))
        return {"tokens": toks[:, :-1].astype(np.int32),
                "labels": toks[:, 1:].astype(np.int32),
                "loss_mask": np.ones((self.batch, self.seq_len), np.float32)}
