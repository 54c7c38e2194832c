"""The world recipe ``planted_tiered``: ``planted``'s graph, labels and
features from the seed for a table LARGER than the chip's memory, laid
out as a ``quiver_tpu.Feature`` store keeps it: rows in degree-descending
order, the hotter ``hot_rows`` of them on the device, the rest in the
host's pinned memory. The table never exists whole, on the chip or as one
host array.

    make(config, seed, sharding) -> {
        "indptr" [nodes+1], "indices" [edges], "labels" [nodes]     device
        "order" [nodes]       node id -> storage row (its degree rank)  device
        "feat_hot" [hot_rows, dim]     storage rows [0, hot_rows)       device
        "feat_cold" [nodes-hot_rows, dim]  the rest     pinned host memory}

What differs from ``planted``, and why (the configuration states it under
``assumed``):

* The degree order is arithmetic. Rank ``r`` (0 = the highest degree) is
  node ``(a*r + b) mod nodes``, ``a`` and ``b`` from the seed, ``a`` small
  and coprime with ``nodes`` so that the product fits 32 unsigned bits.
  The degrees are the lognormal's quantiles in that order, ``exp(sigma *
  z_r)`` with ``z_r = -ndtri((r + 0.5) / nodes)``, capped, scaled to the
  edge slots and floored as ``planted`` does; the remainder goes one slot
  each to the highest ranks, so degrees never rise with the rank and the
  first ``hot_rows`` storage rows ARE the ``hot_rows`` highest-degree
  nodes. The seed decides which node has which degree, not the multiset.
* A neighbour is drawn with probability proportional to its degree, as
  the endpoints of an undirected citation graph are: among edge endpoints
  the density of ``z`` is the same normal shifted by ``sigma``, so a
  neighbour is the node at rank ``nodes * Phi(-z')``, ``z' ~ N(sigma,
  1)``: elementwise work over the edge slots in blocks, no gather. (With
  ``planted``'s uniform neighbours every hot set hits at exactly its
  size.) The rank is taken in groups of four with two random bits inside
  a group: float32 cannot tell neighbouring ranks apart past 2**24.

The two tiers are made on the device one after the other, each filled
in place a block at a time: the cold one first, moved to the host's
pinned memory in ONE transfer from the device and dropped there, then the
hot one, so the device holds a tier at a time. Nothing of the table
passes through a numpy array: on the chip machine the runtime holds
13.5 GB of the host's 40 GiB before anything is made, a numpy array of
7.1 GB takes 13-23 s to place in pinned memory and is held twice while it
goes (my chip run, PR 32), and a program whose result lies in host
memory, which moves 7.1 GB in 0.5 s there, is one the CPU backend of the
tests does not have.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.scipy.special import ndtr, ndtri

from chipbench.world import seed_key

FEAT_BLOCK_ROWS = 1 << 18
EDGE_BLOCK = 1 << 24
# the degrees are scaled a hair under the edge slots, so that flooring
# leaves a remainder that is never negative whatever a float32 sum drifts
SCALE_MARGIN = 1e-4


def rank_map(nodes: int, seed: int):
    """``(a, b)`` of the seed's degree order: rank ``r`` is node
    ``(a*r + b) mod nodes``. ``a`` is coprime with ``nodes`` and
    ``a * nodes < 2**32``."""
    rng = np.random.default_rng([int(seed) & 0x7FFFFFFF, int(seed) >> 31, 17])
    top = min((2**32 - 1) // max(nodes, 1), 151)
    fits = [a for a in range(3, top + 1) if math.gcd(a, nodes) == 1] or [1]
    return int(rng.choice(fits)), int(rng.integers(0, nodes))


def node_of_rank(r, a, b, nodes):
    r = r.astype(jnp.uint32)
    return (((r * jnp.uint32(a)) % jnp.uint32(nodes) + jnp.uint32(b))
            % jnp.uint32(nodes)).astype(jnp.int32)


def order_map(nodes, a, b):
    """Node id -> rank (its storage row): the ranks, sorted by the node
    each belongs to."""
    rank = jnp.arange(nodes, dtype=jnp.int32)
    return jax.lax.sort((node_of_rank(rank, a, b, nodes), rank),
                        num_keys=1)[1]


def _graph(key, *, nodes, edges, classes, dim, a, b, degree_sigma,
           degree_cap):
    kidx, klab, kcen = jax.random.split(key, 3)
    rank = jnp.arange(nodes, dtype=jnp.int32)
    z = -ndtri((rank.astype(jnp.float32) + 0.5) / nodes)
    raw = jnp.minimum(jnp.exp(degree_sigma * z), float(degree_cap))
    scaled = raw * (edges * (1.0 - SCALE_MARGIN)
                    / jnp.sum(raw, dtype=jnp.float32))
    deg = jnp.minimum(jnp.floor(scaled).astype(jnp.int32), degree_cap)
    short = edges - jnp.sum(deg, dtype=jnp.int32)
    deg = deg + (rank < short)                  # by rank, never rising
    order = order_map(nodes, a, b)
    indptr = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(deg[order], dtype=jnp.int32)])

    block = min(EDGE_BLOCK, edges)
    groups = -(-nodes // 4)

    def fill(i, indices):
        # the last block is clamped onto the array's end and overwrites
        # part of the one before it: deterministic, and in place
        start = jnp.minimum(i * block, edges - block)
        kz, kj = jax.random.split(jax.random.fold_in(kidx, i))
        zn = degree_sigma + jax.random.normal(kz, (block,), jnp.float32)
        group = jnp.minimum((ndtr(-zn) * groups).astype(jnp.int32),
                            groups - 1)
        within = (jax.random.bits(kj, (block,), jnp.uint8) & 3).astype(
            jnp.int32)
        r = jnp.minimum(group * 4 + within, nodes - 1)
        return jax.lax.dynamic_update_slice(
            indices, node_of_rank(r, a, b, nodes), (start,))

    indices = jax.lax.fori_loop(0, -(-edges // block), fill,
                                jnp.zeros((edges,), jnp.int32))
    labels = jax.random.randint(klab, (nodes,), 0, classes, dtype=jnp.int32)
    centers = jax.random.normal(kcen, (classes, dim), jnp.float32)
    return {"indptr": indptr, "indices": indices, "labels": labels,
            "order": order}, centers


def _rows(labels, centers, kfeat, start, *, rows, dim, a, b, nodes):
    """Storage rows ``[start, start + rows)``: row ``s`` is the node of
    rank ``s``, its features = class centre + 0.5 * noise; the noise is
    keyed by ``start``."""
    node = node_of_rank(start + jnp.arange(rows, dtype=jnp.int32), a, b,
                         nodes)
    noise = jax.random.normal(jax.random.fold_in(kfeat, start), (rows, dim),
                              jnp.float32)
    return centers[labels[node]] + 0.5 * noise


def _tier(labels, centers, kfeat, *, first, rows, dim, a, b, nodes):
    """Storage rows ``[first, first + rows)`` as one array, filled a block
    at a time in place."""
    block = max(min(FEAT_BLOCK_ROWS, rows), 1)

    def fill(i, feat):
        # the last block is clamped onto the tier's end, as above
        at = jnp.minimum(i * block, rows - block)
        return jax.lax.dynamic_update_slice(
            feat, _rows(labels, centers, kfeat, first + at, rows=block,
                        dim=dim, a=a, b=b, nodes=nodes), (at, 0))

    return jax.lax.fori_loop(0, -(-rows // block), fill,
                             jnp.zeros((rows, dim), jnp.float32))


def pinned(sharding):
    """``sharding``'s (or the default device's) pinned-host twin."""
    if sharding is None:
        sharding = jax.sharding.SingleDeviceSharding(jax.devices()[0])
    return sharding.with_memory_kind("pinned_host")


def make(config: dict, seed: int, sharding=None) -> dict:
    """The arrays above for ``seed`` (``sharding``: a one-device
    ``Sharding`` or None). The cold tier is placed in pinned host memory
    and nowhere else: a backend without that memory kind raises."""
    nodes, dim = int(config["nodes"]), int(config["feature_dim"])
    edges, hot = int(config["edges"]), int(config["hot_rows"])
    cold = nodes - hot
    a, b = rank_map(nodes, seed)
    key = seed_key(seed)
    on = {} if sharding is None else {"out_shardings": sharding}
    graph, centers = jax.jit(functools.partial(
        _graph, nodes=nodes, edges=edges, dim=dim, a=a, b=b,
        classes=int(config["num_classes"]),
        degree_sigma=float(config["degree_sigma"]),
        degree_cap=int(config["degree_cap"])), **on)(key)
    if int(graph["indptr"][-1]) != edges:
        raise SystemExit(
            "chipbench: planted_tiered's degrees sum to "
            f"{int(graph['indptr'][-1])}, not to the {edges} edge slots")
    kfeat = jax.random.fold_in(key, 5)
    tier = lambda first, rows: jax.jit(functools.partial(
        _tier, first=first, rows=rows, dim=dim, a=a, b=b, nodes=nodes),
        **on)(graph["labels"], centers, kfeat)
    # the cold tier first, while the device has room for it: made there,
    # moved to the host's pinned memory, dropped; then the hot tier
    made = tier(hot, cold)
    world = dict(graph, feat_cold=jax.device_put(made, pinned(sharding)))
    world["feat_cold"].block_until_ready()
    made.delete()
    world["feat_hot"] = tier(0, hot)
    return world


def cold_counts(config: dict, seed: int, batch: int, steps: int) -> list:
    """A host simulation, for sizing ``cold_budget`` before any chip call:
    the number of distinct cold nodes in the final frontier of ``steps``
    batches of ``batch`` uniform seeds, under this file's laws (a pick is
    a node of rank ``nodes * Phi(-z')``; a node of rank ``r`` has the
    degree of quantile ``r``; a frontier node draws ``min(degree, k)``
    picks; a frontier holds every distinct node met so far). Numpy alone;
    it stands for the graph in distribution, not pick by pick."""
    from math import erf
    nodes, edges = int(config["nodes"]), int(config["edges"])
    hot, sigma = int(config["hot_rows"]), float(config["degree_sigma"])
    rng = np.random.default_rng([int(seed) & 0x7FFFFFFF, 23])
    phi = np.vectorize(lambda x: 0.5 * (1.0 + erf(x / math.sqrt(2.0))))
    # degree by rank from the inverse normal by bisection-free table
    grid = np.linspace(-6.5, 6.5, 200001)
    cdf = phi(grid)
    u = (np.arange(nodes, dtype=np.float64) + 0.5) / nodes
    z = -np.interp(u, cdf, grid)
    raw = np.minimum(np.exp(sigma * z), float(config["degree_cap"]))
    deg = np.floor(raw * (edges * (1 - SCALE_MARGIN) / raw.sum())).astype(
        np.int64)
    deg[:edges - int(deg.sum())] += 1
    out = []
    for _ in range(steps):
        front = np.unique(rng.integers(0, nodes, batch))
        for k in config["fanout"]:
            n = np.minimum(deg[front], int(k)).sum()
            zn = sigma + rng.standard_normal(int(n))
            picks = np.minimum((np.interp(-zn, grid, cdf) * nodes).astype(
                np.int64), nodes - 1)
            front = np.union1d(front, picks)
        out.append(int((front >= hot).sum()))
    return out
