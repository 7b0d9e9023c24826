"""Seeded token streams: the zipf-Markov process of the repository's
synthetic corpus, copied here so that no later change to the program can
move the benchmark's inputs.

Token t+1 is one of ``BRANCH`` affine successors of token t, drawn from a
zipf-like distribution, with occasional uniform noise. Every row is its own
stream keyed by (seed, step, row); the recurrence runs over positions with
all rows at once, which gives the same tokens as the row-by-row original.
"""
from __future__ import annotations

from typing import Dict

import numpy as np

BRANCH = 4
NOISE = 0.05
MASK_RATE = 0.15


def _branch_probs() -> np.ndarray:
    p = 1.0 / (np.arange(1, BRANCH + 1) ** 1.5)
    return p / p.sum()


def gen_tokens(seed: int, step: int, batch: int, seq: int,
               vocab: int) -> np.ndarray:
    """tokens (batch, seq + 1), int64, for one global step."""
    base = ((np.uint64(seed % 2 ** 31) * np.uint64(1000003)
             + np.uint64(step) * np.uint64(8191)) % np.uint64(2 ** 31))
    probs = _branch_probs()
    first = np.empty(batch, np.int64)
    branches = np.empty((batch, seq + 1), np.int64)
    noise = np.empty((batch, seq + 1), bool)
    rand = np.empty((batch, seq + 1), np.int64)
    for r in range(batch):
        rng = np.random.RandomState(int((base + np.uint64(r)) % (2 ** 31)))
        first[r] = rng.randint(0, vocab)
        branches[r] = rng.choice(BRANCH, size=seq + 1, p=probs)
        noise[r] = rng.rand(seq + 1) < NOISE
        rand[r] = rng.randint(0, vocab, size=seq + 1)
    out = np.empty((batch, seq + 1), np.int64)
    tok = first
    for t in range(seq + 1):
        out[:, t] = tok
        nxt = (tok * (2 * branches[:, t] + 1) + branches[:, t] * 7919
               + 13) % vocab
        tok = np.where(noise[:, t], rand[:, t], nxt)
    return out


def mlm_batch(seed: int, step: int, batch: int, seq: int,
              vocab: int) -> Dict[str, np.ndarray]:
    """A masked-LM batch: 15% of positions replaced by the [MASK] id
    (vocab - 1) in ``tokens``, their originals in ``labels``."""
    toks = gen_tokens(seed, step, batch, seq, vocab)[:, :-1].astype(np.int32)
    rng = np.random.RandomState((seed * 97 + step) % 2 ** 31)
    mask = rng.rand(batch, seq) < MASK_RATE
    return {"tokens": np.where(mask, vocab - 1, toks).astype(np.int32),
            "mask": mask, "labels": toks}
