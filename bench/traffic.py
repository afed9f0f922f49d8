"""The general traffic generator: a mix file's parameters -> token batches.

``zipf_markov`` copies ``repro.data.synthetic.SyntheticLM``'s distribution
(a Zipf unigram base with a sparse order-1 Markov overlay: each id prefers a
few fixed successors), vectorised over rows so a pool of full training
batches is made in set-up time: the row dimension is drawn in bulk and only
the Markov chain walks the sequence.
"""
from __future__ import annotations

import numpy as np


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), stream])


def zipf_markov(mix: dict, vocab: int, seed: int) -> np.ndarray:
    """``(pool_batches, batch, seq_len)`` int32 ids in ``[0, vocab)``.

    The successor table depends on the seed alone, the draws on the seed
    and the mix; every seed gives the same shapes.
    """
    n, b, s = mix["pool_batches"], mix["batch"], mix["seq_len"]
    k = mix["successors"]
    base = 1.0 / np.arange(1, vocab + 1) ** mix["zipf_a"]
    cdf = np.cumsum(base / base.sum())
    succ = _rng(seed, 1).integers(0, vocab, size=(vocab, k))
    g = _rng(seed, 2)
    rows = n * b
    base_pick = np.minimum(np.searchsorted(cdf, g.random((rows, s))),
                           vocab - 1)
    use_markov = g.random((rows, s)) < mix["markov_weight"]
    succ_col = g.integers(0, k, size=(rows, s))
    out = np.empty((rows, s), np.int32)
    prev = base_pick[:, 0]
    out[:, 0] = prev
    for t in range(1, s):
        prev = np.where(use_markov[:, t], succ[prev, succ_col[:, t]],
                        base_pick[:, t])
        out[:, t] = prev
    return out.reshape(n, b, s)


GENERATORS = {"zipf_markov": zipf_markov}


def make_pool(mix: dict, vocab: int, seed: int) -> np.ndarray:
    return GENERATORS[mix["generator"]](mix, vocab, seed)
