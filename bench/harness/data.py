"""The one traffic generator: token batches for each FL client, made
from the seed and the traffic file.

Every client has its own stream. A stream yields `{"tokens", "labels"}`
of shape (batch, seq): tokens uniform over the vocabulary, and each
label the token's image under a fixed random map of the vocabulary,
except for a `label_noise` share drawn uniformly. The map makes the
loss reducible (the next token is predictable from the current one).
Every seed gives the same sizes; only the values differ. Rows are drawn
in bulk with numpy, so a stream costs the host a fraction of a
millisecond per batch.

A stream remembers the batches it has yielded until `keep` are stored,
so that the reference can follow the program's first rounds on the very
same rows.
"""
from __future__ import annotations

from typing import Dict, Iterator, List

import numpy as np


class TokenStream(Iterator[Dict[str, np.ndarray]]):
    def __init__(self, seed: int, client: int, vocab: int, batch: int,
                 seq: int, label_noise: float, keep: int = 0):
        self.rng = np.random.default_rng([seed, client])
        self.map = self.rng.integers(0, vocab, size=vocab, dtype=np.int32)
        self.vocab, self.batch, self.seq = vocab, batch, seq
        self.noise = label_noise
        self.keep = keep
        self.kept: List[Dict[str, np.ndarray]] = []

    def __next__(self) -> Dict[str, np.ndarray]:
        shape = (self.batch, self.seq)
        tokens = self.rng.integers(0, self.vocab, size=shape,
                                   dtype=np.int32)
        flip = self.rng.random(shape) < self.noise
        noise = self.rng.integers(0, self.vocab, size=shape, dtype=np.int32)
        labels = np.where(flip, noise, self.map[tokens]).astype(np.int32)
        out = {"tokens": tokens, "labels": labels}
        if len(self.kept) < self.keep:
            self.kept.append(out)
        return out


def client_streams(seed: int, traffic, vocab: int, keep: int = 0):
    return [TokenStream(seed, c, vocab, traffic["batch"], traffic["seq"],
                        traffic["label_noise"], keep=keep)
            for c in range(traffic["clients"])]
