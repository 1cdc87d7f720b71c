"""Synthetic token pipeline with per-client distribution skew (the
benchmark's own copy of ``repro.data.tokens``, so that the traffic it
generates stays fixed whatever the program does to its own copy).

Each client i has its own affine recurrence ``t_{j+1} = (a_i t_j + b_i + eps)
mod V``: the sequences are learnable (low conditional entropy) but the
transition law differs per client.  The law is the program's; the copy
draws a whole chunk of steps at once (one generator per client and
chunk), so that making a window's batches costs milliseconds.
Deterministic given (seed, client, chunk).
"""
from __future__ import annotations

import numpy as np

__all__ = ["TokenStream"]


class TokenStream:
    """Infinite deterministic per-client batch stream."""

    def __init__(self, n_clients: int, vocab: int, batch: int, seq: int,
                 seed: int = 0, noise: float = 0.05):
        self.n_clients, self.vocab = n_clients, vocab
        self.batch, self.seq = batch, seq
        self.seed, self.noise = seed, noise
        rng = np.random.default_rng(seed)
        # client-specific affine laws; a_i odd so the map is a bijection
        self.a = (rng.integers(1, max(vocab // 2, 2), n_clients) * 2 + 1) % vocab
        self.b = rng.integers(0, vocab, n_clients)

    def chunk_at(self, chunk: int, length: int) -> np.ndarray:
        """(length, n_clients, batch, seq) int32 token batches of the
        ``length`` steps of chunk number ``chunk``."""
        out = np.empty((length, self.n_clients, self.batch, self.seq),
                       np.int32)
        rows = length * self.batch
        for i in range(self.n_clients):
            rng = np.random.default_rng((self.seed, i, chunk))
            t = rng.integers(0, self.vocab, rows)
            eps = rng.integers(0, self.vocab, (self.seq, rows)) \
                * (rng.random((self.seq, rows)) < self.noise)
            seqs = np.empty((self.seq, rows), np.int64)
            for j in range(self.seq):
                seqs[j] = t
                t = (self.a[i] * t + self.b[i] + eps[j]) % self.vocab
            out[:, i] = seqs.T.reshape(length, self.batch, self.seq)
        return out
