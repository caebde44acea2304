"""The one generator of training traffic: batches drawn from a mix file.

Batch ``i`` of a run is a pure function of (seed, i): token ids from the
mix's law over the held vocabulary, ``sequences_per_step`` rows of
``sequence_length + 1`` tokens, split into inputs and next-token labels.
The Zipf law (p(t) ∝ 1/(t+1)^a) is the sampling law of the program's
``SyntheticTokenDataset``, copied here so that the yardstick does not
move with the program.
"""

from __future__ import annotations

import numpy as np


class TrafficSource:
    def __init__(self, mix: dict, vocab: int, seed: int):
        law = mix["token_law"]
        if law["kind"] != "zipf":
            raise ValueError(f"unknown token law {law['kind']!r}")
        w = 1.0 / np.arange(1, vocab + 1, dtype=np.float64) ** law["a"]
        self._probs = w / w.sum()
        self.vocab = vocab
        self.seed = seed
        self.rows = mix["sequences_per_step"]
        self.seq = mix["sequence_length"]

    @property
    def tokens_per_step(self) -> int:
        return self.rows * self.seq

    def batch(self, i: int) -> dict:
        rng = np.random.default_rng((self.seed, i))
        toks = rng.choice(self.vocab, size=(self.rows, self.seq + 1),
                          p=self._probs).astype(np.int32)
        return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}

    def __iter__(self):
        i = 0
        while True:
            yield self.batch(i)
            i += 1
