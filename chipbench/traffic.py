"""The one traffic generator. A mix is a data file under ``traffic/``; this
reads its parameters and makes the run's inputs from ``--seed``.

Every seed of a mix gets the SAME multiset of gaps and of popularity
ranks, in another order and over another relabelling of the nodes, so
that a seed changes which nodes are asked and when, never how much work
a run holds.

kinds
  ``train_epochs``  epochs over the configuration's training nodes in a
                    fresh order each, cut into batches (the tail of an
                    epoch that does not fill a batch is dropped)
  ``open_loop``     arrivals on a schedule at ``rate_per_s``: the gaps are
                    the ``n`` mid-quantiles of the exponential distribution
                    (a Poisson process's gaps, stratified), shuffled
  ``closed_loop``   ``callers`` callers that each wait for their reply and
                    then ask again; only the ids are drawn here
ids (serve kinds)
  ``{"dist": "zipf", "s": 1.0}`` ranks by the continuous inverse CDF at the
  ``n`` mid-quantiles, or ``{"dist": "uniform"}``; a rank becomes a node
  through a seeded bijection ``(a * rank + b) mod nodes``.
"""

from __future__ import annotations

import math

import numpy as np


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), int(stream)])


def train_batches(mix: dict, config: dict, seed: int, global_batch: int):
    """Yields ``int32 [global_batch]`` batches of distinct training nodes
    for ever."""
    rng = _rng(seed, 1)
    nodes = int(config["nodes"])
    train = rng.permutation(nodes)[:int(config["train_nodes"])].astype(np.int32)
    per_epoch = train.shape[0] // global_batch
    if per_epoch < 1:
        raise ValueError("fewer training nodes than one batch")
    while True:
        order = rng.permutation(train)
        for b in range(per_epoch):
            yield order[b * global_batch:(b + 1) * global_batch]


def _ranks(ids: dict, n: int, nodes: int) -> np.ndarray:
    u = (np.arange(n) + 0.5) / n
    if ids["dist"] == "uniform":
        r = u * nodes
    elif ids["dist"] == "zipf":
        s = float(ids["s"])
        if s == 1.0:
            r = np.exp(u * math.log(nodes + 1.0)) - 1.0
        else:
            top = (nodes + 1.0) ** (1.0 - s)
            r = (1.0 + u * (top - 1.0)) ** (1.0 / (1.0 - s)) - 1.0
    else:
        raise ValueError(f"unknown id distribution {ids['dist']!r}")
    return np.clip(r.astype(np.int64), 0, nodes - 1)


def node_ids(ids: dict, n: int, nodes: int, seed: int) -> np.ndarray:
    """``n`` node ids: the mix's ranks, shuffled, relabelled."""
    rng = _rng(seed, 2)
    ranks = rng.permutation(_ranks(ids, n, nodes))
    while True:
        a = int(rng.integers(1, nodes))
        if math.gcd(a, nodes) == 1:
            break
    b = int(rng.integers(0, nodes))
    return ((a * ranks + b) % nodes).astype(np.int32)


def open_loop(mix: dict, nodes: int, seed: int, seconds: float):
    """``(due [n] seconds from the window's start, ids [n])``; ``n`` =
    ``rate_per_s * seconds``, and the last arrival is due just inside the
    window."""
    n = max(1, int(round(float(mix["rate_per_s"]) * seconds)))
    u = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-u)                     # mean 1, exponential quantiles
    gaps *= seconds / gaps.sum()
    due = np.cumsum(_rng(seed, 3).permutation(gaps)) - 0.5 * gaps[0]
    return due, node_ids(mix["ids"], n, nodes, seed)


def closed_loop(mix: dict, nodes: int, seed: int) -> np.ndarray:
    """The ids a closed loop asks for, in order (``pool`` of them; the
    loop starts over at the pool's end)."""
    return node_ids(mix["ids"], int(mix["pool"]), nodes, seed)
