"""Seeded traffic: which prompt length each flow gets, when it arrives, and
its token ids.

Every seed gets the same work in another order: prompt lengths are drawn
from blocks that hold each length in its exact share, shuffled per block,
and open-loop arrivals use one fixed set of gaps (the quantiles of the
exponential distribution at the stated rate), shuffled.  So two seeds
differ in order and token ids, not in the amount of work.
"""

from __future__ import annotations

import numpy as np


def _rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng([seed % 2**64, *stream])


def bucket_block(buckets: dict, block: int) -> list[int]:
    """One block of ``block`` prompt lengths, each length ``round(share x
    block)`` times.  ``buckets`` maps a length (a string in JSON) to its
    share; the shares times ``block`` must be whole numbers."""
    out = []
    for length, share in sorted(buckets.items(), key=lambda kv: int(kv[0])):
        n = share * block
        if abs(n - round(n)) > 1e-9:
            raise ValueError(f"share {share} of length {length} is not a "
                             f"whole number of a block of {block}")
        out += [int(length)] * round(n)
    if len(out) != block:
        raise ValueError(f"shares {buckets} do not fill a block of {block}")
    return out


def prompt_lengths(seed: int, buckets: dict, block: int, n: int) -> list[int]:
    """The prompt length of flows ``0 .. n-1``."""
    base = bucket_block(buckets, block)
    rng = _rng(seed, 1)
    out: list[int] = []
    while len(out) < n:
        out += [base[i] for i in rng.permutation(block)]
    return out[:n]


def arrival_offsets(seed: int, rate: float, seconds: float) -> list[float]:
    """Open-loop send times in [0, seconds): ``round(rate x seconds)``
    arrivals whose gaps are the exponential quantiles at ``rate``, in a
    seeded order."""
    n = max(int(round(rate * seconds)), 1)
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q) / rate
    gaps = gaps[_rng(seed, 2).permutation(n)]
    # the midpoint quantiles sum to less than n / rate, so every arrival
    # falls inside the window
    return [float(x) for x in np.cumsum(gaps) - gaps[0]]


def prompts(seed: int, flow: int, batch: int, length: int, vocab: int
            ) -> np.ndarray:
    """Token ids of flow ``flow``: ``[batch, length]`` int32, uniform over
    the vocabulary."""
    return _rng(seed, 3, flow).integers(0, vocab, size=(batch, length),
                                        dtype=np.int32)


def sample(seed: int, candidates: list, n: int, must: list = ()) -> list:
    """``n`` of ``candidates`` drawn from the seed, the ``must`` ones first."""
    chosen = [c for c in must if c in candidates]
    rest = [c for c in candidates if c not in chosen]
    order = _rng(seed, 4).permutation(len(rest))
    chosen += [rest[i] for i in order[: max(n - len(chosen), 0)]]
    return chosen
