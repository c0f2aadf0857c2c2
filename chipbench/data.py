"""Synthetic token batches: a vectorised copy of the program's
``TokenStream`` generator (structured mode), bit for bit.

Per (seed, shard, step) one ``numpy`` generator draws, in this order, the
first token of each row, a 10% noise mask and a row of random tokens; token
``t`` is the random one where the mask is set and ``(3 * prev + 1) mod V``
elsewhere.  The recurrence is solved in closed form from the last reset:
``f^n(v) = 3^n v + (3^n - 1) / 2  (mod V)``.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

NOISE = 0.1


def _stream_rng(seed: int, shard: int, step: int) -> np.random.Generator:
    return np.random.default_rng((seed * 1_000_003 + shard) * 1_000_003
                                 + step)


def token_rows(seed: int, shard: int, step: int, rows: int, seq: int,
               vocab: int) -> Dict[str, np.ndarray]:
    """One pod's batch: tokens, labels (shifted by one) and mask."""
    rng = _stream_rng(seed, shard, step)
    S, V = seq + 1, vocab
    first = rng.integers(0, V, size=rows)
    noise = rng.random((rows, S)) < NOISE
    rand = rng.integers(0, V, size=(rows, S))
    reset = noise.copy()
    reset[:, 0] = True
    start = np.where(noise, rand, 0)
    start[:, 0] = first
    pos = np.arange(S)
    last = np.maximum.accumulate(np.where(reset, pos, 0), axis=1)
    n = pos[None, :] - last
    pow3 = np.ones(S, np.int64)
    geo = np.zeros(S, np.int64)
    for i in range(1, S):
        pow3[i] = pow3[i - 1] * 3 % V
        geo[i] = (geo[i - 1] * 3 + 1) % V
    v = np.take_along_axis(start, last, axis=1).astype(np.int64)
    toks = ((pow3[n] * v + geo[n]) % V).astype(np.int32)
    return {"tokens": toks[:, :-1], "labels": toks[:, 1:],
            "mask": np.ones((rows, seq), np.float32)}


def batch_pool(seed: int, n_batches: int, pods: int, rows: int, seq: int,
               vocab: int) -> List[Dict[str, np.ndarray]]:
    """``n_batches`` distinct stacked batches, ``(pods, rows, seq)`` each;
    pod ``p`` draws from its own shard."""
    pool = []
    for step in range(n_batches):
        parts = [token_rows(seed, p, step, rows, seq, vocab)
                 for p in range(pods)]
        pool.append({k: np.stack([q[k] for q in parts]) for k in parts[0]})
    return pool
