"""Deterministic synthetic datasets (no external data offline — DESIGN.md §8).

All streams are *seekable*: ``batch_at(step)`` is a pure function of
(seed, step), which makes checkpoint-resume bit-exact and lets the trainer
skip to any step after an elastic restart.

* ``LMStream``   — token sequences from a fixed random bigram chain: enough
  learnable structure that CE drops well below the uniform entropy, so
  optimizer comparisons (Fig. 4 / Table 4 analogues) are meaningful.
* ``AEStream``   — MNIST-like [0,1] images: smooth random low-rank blobs.
* ``ClassStream``— gaussian-blob classification.
"""
from __future__ import annotations

import dataclasses
from typing import Iterator, Optional

import jax
import jax.numpy as jnp
import numpy as np


def _entropy(p: np.ndarray) -> np.ndarray:
    """Elementwise -p log p (0 at p = 0)."""
    return -p * np.log(np.maximum(p, 1e-12))


@dataclasses.dataclass
class LMStream:
    """Bigram-chain token stream.  Each token has ``min(vocab, SUCCESSORS)``
    possible next tokens with peaky random probabilities; at or below
    ``SUCCESSORS`` that is the dense (vocab, vocab) chain.

    Above it a (vocab, SUCCESSORS) table of uniformly drawn successors keeps
    the host memory O(vocab) — a 151936-token vocab would need a 185 GB
    dense table.  Such a chain alone has a flat unigram, which no text has
    and which leaves nothing to learn in a few steps, so there each next
    token is, with probability ``ZIPF_MIX``, drawn from a Zipf(1) unigram
    over the token ids instead."""
    SUCCESSORS = 512
    ZIPF_MIX = 0.5

    vocab: int
    seq_len: int
    batch: int
    seed: int = 0
    concentration: float = 0.3   # lower = peakier bigrams = more learnable

    def __post_init__(self):
        rng = np.random.default_rng(self.seed)
        k = min(self.vocab, self.SUCCESSORS)
        logits = rng.gumbel(size=(self.vocab, k)) / self.concentration
        probs = np.exp(logits - logits.max(-1, keepdims=True))
        probs /= probs.sum(-1, keepdims=True)
        self._cum = np.cumsum(probs, axis=-1)
        self._succ = None if k == self.vocab else \
            rng.integers(0, self.vocab, (self.vocab, k)).astype(np.int32)

    def batch_at(self, step: int) -> dict:
        rng = np.random.default_rng((self.seed, step))
        toks = np.empty((self.batch, self.seq_len + 1), np.int32)
        toks[:, 0] = rng.integers(0, self.vocab, self.batch)
        u = rng.random((self.batch, self.seq_len))
        if self._succ is not None:
            # exp(U ln V) has density 1/(x ln V) on [1, V): its floor - 1
            # is Zipf(1) over the ids 0..V-2
            zipf = np.exp(rng.random((self.batch, self.seq_len))
                          * np.log(self.vocab)).astype(np.int32) - 1
            from_zipf = rng.random((self.batch, self.seq_len)) \
                < self.ZIPF_MIX
        # vectorized bigram sampling: invert the per-row CDF
        for t in range(self.seq_len):
            prev = toks[:, t]
            idx = (self._cum[prev] < u[:, t:t + 1]).sum(-1)
            if self._succ is None:
                toks[:, t + 1] = idx
            else:
                toks[:, t + 1] = np.where(from_zipf[:, t], zipf[:, t],
                                          self._succ[prev, idx])
        return {'tokens': jnp.asarray(toks[:, :-1]),
                'labels': jnp.asarray(toks[:, 1:])}

    def __iter__(self) -> Iterator[dict]:
        step = 0
        while True:
            yield self.batch_at(step)
            step += 1

    @property
    def uniform_ce(self) -> float:
        return float(np.log(self.vocab))

    @property
    def bigram_ce(self) -> float:
        """Entropy of the generating chain — the achievable CE floor."""
        probs = np.diff(self._cum, axis=-1, prepend=0.0)
        if self._succ is None:
            return float(_entropy(probs).sum(-1).mean())
        # each row is the mixture q z + (1-q) p, z the Zipf unigram: the
        # q z terms off the row's successors plus the mixed ones on them
        # (a successor drawn twice counts as two: a slight overestimate)
        q = self.ZIPF_MIX
        z = np.log1p(1.0 / np.arange(1, self.vocab + 1)) / np.log(self.vocab)
        z[-1] = 0.0                      # id V-1 is never drawn
        zs = q * z[self._succ]
        rows = (_entropy(q * z).sum() - _entropy(zs).sum(-1)
                + _entropy(zs + (1.0 - q) * probs).sum(-1))
        return float(rows.mean())


@dataclasses.dataclass
class AEStream:
    """Smooth blob images in [0,1], shape (batch, d) with d = side*side."""
    batch: int
    side: int = 28
    rank: int = 6
    seed: int = 0

    def batch_at(self, step: int) -> dict:
        rng = np.random.default_rng((self.seed, step))
        g = np.linspace(-1, 1, self.side)
        basis = np.stack([np.exp(-((g[:, None] - rng.uniform(-1, 1)) ** 2 +
                                   (g[None, :] - rng.uniform(-1, 1)) ** 2)
                                 / rng.uniform(0.05, 0.4))
                          for _ in range(self.rank)])
        w = rng.random((self.batch, self.rank)).astype(np.float32)
        img = np.einsum('br,rhw->bhw', w, basis)
        img = img / np.maximum(img.max(axis=(1, 2), keepdims=True), 1e-6)
        return {'x': jnp.asarray(img.reshape(self.batch, -1), jnp.float32)}

    def __iter__(self):
        step = 0
        while True:
            yield self.batch_at(step)
            step += 1


@dataclasses.dataclass
class ClassStream:
    """Gaussian blobs: (batch, dim) -> labels in [0, classes)."""
    batch: int
    dim: int = 64
    classes: int = 10
    seed: int = 0
    spread: float = 3.0

    def __post_init__(self):
        rng = np.random.default_rng(self.seed)
        self._centers = rng.normal(size=(self.classes, self.dim)) * self.spread

    def batch_at(self, step: int) -> dict:
        rng = np.random.default_rng((self.seed, step))
        y = rng.integers(0, self.classes, self.batch)
        x = self._centers[y] + rng.normal(size=(self.batch, self.dim))
        return {'x': jnp.asarray(x, jnp.float32), 'y': jnp.asarray(y, jnp.int32)}

    def __iter__(self):
        step = 0
        while True:
            yield self.batch_at(step)
            step += 1
