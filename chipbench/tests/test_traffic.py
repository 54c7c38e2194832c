"""The generators: the same seed gives the same inputs, another seed the
same work in another order."""

import numpy as np

from chipbench import traffic

MIX = {"kind": "open_loop", "rate_per_s": 500, "ids": {"dist": "zipf", "s": 1.0}}
CFG = {"nodes": 4001, "train_nodes": 1200}


def test_open_loop_repeats_and_keeps_its_work():
    d1, i1 = traffic.open_loop(MIX, 100_003, 7, 4.0)
    d2, i2 = traffic.open_loop(MIX, 100_003, 7, 4.0)
    d3, i3 = traffic.open_loop(MIX, 100_003, 2**31 + 9, 4.0)
    assert np.array_equal(d1, d2) and np.array_equal(i1, i2)
    assert len(d1) == len(d3) == 2000
    assert not np.array_equal(i1, i3)
    # the same multiset of gaps, in another order
    g1, g3 = np.diff(d1), np.diff(d3)
    assert abs(g1.sum() - g3.sum()) < 0.1 and len(g1) == len(g3)
    assert np.allclose(np.sort(g1)[5:-5], np.sort(g3)[5:-5], rtol=1e-2)
    assert 0 < d1[0] and d1[-1] < 4.0 and np.all(np.diff(d1) > 0)
    # exponential gaps: coefficient of variation 1
    assert abs(np.std(g1) / np.mean(g1) - 1.0) < 0.05


def test_zipf_is_skewed_and_in_range():
    ids = traffic.node_ids(MIX["ids"], 20_000, 100_003, 3)
    assert ids.min() >= 0 and ids.max() < 100_003
    _, counts = np.unique(ids, return_counts=True)
    # rank 0 alone takes 1/ln(N) of a Zipf(1) mix
    assert 0.04 < counts.max() / len(ids) < 0.09
    # the same popularity profile for every seed
    _, c2 = np.unique(traffic.node_ids(MIX["ids"], 20_000, 100_003, 4),
                      return_counts=True)
    assert np.array_equal(np.sort(counts), np.sort(c2))


def test_train_batches():
    mix = {"kind": "train_epochs", "batch": 32, "run_ahead": 2}
    a = traffic.train_batches(mix, CFG, 5, 64)
    b = traffic.train_batches(mix, CFG, 5, 64)
    first = [next(a) for _ in range(40)]     # more than two epochs of 18
    again = [next(b) for _ in range(40)]
    assert all(np.array_equal(x, y) for x, y in zip(first, again))
    assert all(len(np.unique(x)) == 64 and x.dtype == np.int32 for x in first)
    epoch = np.concatenate(first[:18])
    assert len(np.unique(epoch)) == len(epoch)          # rows that all differ
    assert set(np.concatenate(first[18:36])) <= set(
        np.concatenate([epoch, np.setdiff1d(np.concatenate(first[18:36]),
                                            epoch)]))
