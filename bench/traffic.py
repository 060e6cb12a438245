"""The benchmark's token stream: one general generator driven by a traffic file.

A copy of the construction the program's own synthetic stream uses, kept
here so that no later change to the program can move the yardstick: above
``successors`` tokens of vocabulary each next token follows a sparse random
bigram chain (``successors`` uniformly drawn successors per token, peaky
Gumbel-softmax probabilities), mixed with probability ``zipf_mix`` with a
Zipf(1) unigram over the ids, so that the unigram resembles text.  At or
below ``successors`` the chain is dense.

``batch_at(step)`` is a pure function of (seed, step): the program's
prefetcher can seek it, and the reference regenerates the same batches.
Every seed gives the same batch and sequence shapes; the seed changes only
which tokens are drawn.
"""
from __future__ import annotations

import numpy as np


class TokenStream:
    """Seekable stream of ``{'tokens', 'labels'}`` int32 (batch, seq_len)."""

    def __init__(self, vocab: int, seq_len: int, batch: int, seed: int,
                 successors: int = 512, zipf_mix: float = 0.5,
                 concentration: float = 0.3):
        self.vocab, self.seq_len, self.batch = vocab, seq_len, batch
        self.seed = int(seed)
        self.zipf_mix = zipf_mix
        rng = np.random.default_rng(self.seed)
        k = min(vocab, successors)
        # Gumbel(0, 1) = -log Exp(1), drawn in float32: the table is
        # (vocab, successors) and its set-up is paid by every run
        expo = rng.standard_exponential((vocab, k), np.float32)
        logits = -np.log(np.maximum(expo, np.float32(1e-30), out=expo))
        logits /= np.float32(concentration)
        logits -= logits.max(-1, keepdims=True)
        probs = np.exp(logits, out=logits)
        probs /= probs.sum(-1, keepdims=True)
        self._cum = np.cumsum(probs, axis=-1, dtype=np.float32)
        self._succ = None if k == vocab else \
            rng.integers(0, vocab, (vocab, k)).astype(np.int32)

    @classmethod
    def from_traffic(cls, traffic: dict, vocab: int, seed: int,
                     batch: int | None = None) -> 'TokenStream':
        s = traffic['stream']
        return cls(vocab=vocab, seq_len=traffic['seq_len'],
                   batch=traffic['batch'] if batch is None else batch,
                   seed=seed, successors=s['successors'],
                   zipf_mix=s['zipf_mix'], concentration=s['concentration'])

    def tokens_at(self, step: int) -> np.ndarray:
        """(batch, seq_len + 1) int32: inputs are [:, :-1], labels [:, 1:]."""
        rng = np.random.default_rng((self.seed, step))
        toks = np.empty((self.batch, self.seq_len + 1), np.int32)
        toks[:, 0] = rng.integers(0, self.vocab, self.batch)
        u = rng.random((self.batch, self.seq_len))
        if self._succ is not None:
            # exp(U ln V) has density 1/(x ln V) on [1, V): its floor - 1 is
            # Zipf(1) over the ids 0..V-2
            zipf = np.exp(rng.random((self.batch, self.seq_len))
                          * np.log(self.vocab)).astype(np.int32) - 1
            from_zipf = rng.random((self.batch, self.seq_len)) < self.zipf_mix
        for t in range(self.seq_len):
            prev = toks[:, t]
            # float32 sums can end a hair below 1: clamp to the last id
            idx = np.minimum((self._cum[prev] < u[:, t:t + 1]).sum(-1),
                             self._cum.shape[1] - 1)
            if self._succ is None:
                toks[:, t + 1] = idx
            else:
                toks[:, t + 1] = np.where(from_zipf[:, t], zipf[:, t],
                                          self._succ[prev, idx])
        return toks

    def batch_at(self, step: int) -> dict:
        import jax.numpy as jnp
        toks = self.tokens_at(step)
        return {'tokens': jnp.asarray(toks[:, :-1]),
                'labels': jnp.asarray(toks[:, 1:])}
