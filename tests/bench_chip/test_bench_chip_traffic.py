"""The chip benchmark's seeded traffic repeats exactly by seed, and every
seed gets the same work in another order."""

from __future__ import annotations

from collections import Counter

import numpy as np
import pytest

from benchmarks.chip import traffic as tr

BUCKETS = {"128": 0.5, "256": 0.3, "512": 0.2}
SEEDS = [0, 7, 2**31 + 11, 2**40 + 3]


@pytest.mark.parametrize("seed", SEEDS)
def test_the_same_seed_gives_the_same_traffic(seed):
    assert tr.prompt_lengths(seed, BUCKETS, 10, 57) == \
        tr.prompt_lengths(seed, BUCKETS, 10, 57)
    assert tr.arrival_offsets(seed, 12.0, 30) == \
        tr.arrival_offsets(seed, 12.0, 30)
    np.testing.assert_array_equal(tr.prompts(seed, 5, 8, 128, 92544),
                                  tr.prompts(seed, 5, 8, 128, 92544))
    assert tr.sample(seed, list(range(40)), 5, must=[39]) == \
        tr.sample(seed, list(range(40)), 5, must=[39])


def test_seeds_differ_in_order_not_in_work():
    lengths = [tr.prompt_lengths(s, BUCKETS, 10, 50) for s in SEEDS]
    assert len({tuple(x) for x in lengths}) == len(SEEDS)
    for x in lengths:
        assert Counter(x) == {128: 25, 256: 15, 512: 10}
        # every block of ten holds each length in its exact share
        assert all(Counter(x[i:i + 10]) == {128: 5, 256: 3, 512: 2}
                   for i in range(0, 50, 10))
    arrivals = [tr.arrival_offsets(s, 12.0, 30) for s in SEEDS]
    assert len({tuple(a) for a in arrivals}) == len(SEEDS)
    gaps = [sorted(np.diff(a).round(9)) for a in arrivals]
    for a, g in zip(arrivals, gaps):
        assert len(a) == 360 and a[0] == 0.0 and a[-1] < 30
        assert a == sorted(a)
    # the same gaps, in another order (one gap is the first arrival's)
    assert all(len(set(g) - set(gaps[0])) <= 1 for g in gaps)
    p = [tr.prompts(s, 0, 8, 64, 1000) for s in SEEDS]
    assert not np.array_equal(p[0], p[1])
    assert all(x.dtype == np.int32 and x.min() >= 0 and x.max() < 1000
               for x in p)


def test_flows_get_distinct_prompts_and_the_sample_holds_the_must():
    assert not np.array_equal(tr.prompts(1, 0, 8, 64, 1000),
                              tr.prompts(1, 1, 8, 64, 1000))
    s = tr.sample(3, list(range(10)), 4, must=[9])
    assert s[0] == 9 and len(set(s)) == 4 and set(s) <= set(range(10))
    assert tr.sample(3, [1, 2], 5) in ([1, 2], [2, 1])


def test_shares_that_do_not_fill_a_block_are_refused():
    with pytest.raises(ValueError):
        tr.bucket_block({"128": 0.55, "256": 0.45}, 10)
    with pytest.raises(ValueError):
        tr.bucket_block({"128": 0.5}, 10)
