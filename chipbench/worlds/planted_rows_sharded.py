"""The world recipe ``planted_rows_sharded``: ``planted``'s graph, labels
and features from the seed, with the TABLE row-sharded over the chips of a
mesh and never held whole, on a chip or on the host.

    make(config, seed, mesh) -> {"indptr", "indices", "labels", "g2h", "g2l"   replicated
                                 "feat" [chips * rows_per_chip, dim]          P(axis, None)}

The partition book is dealt from the seed: every group of ``chips``
consecutive node ids goes one node to a chip, in a rotation drawn for that
group, so a node's owner is arbitrary (NOT ``id // rows_per_chip``), every
chip owns ``ceil(nodes / chips)`` rows at the most (the last group may be
short: its missing nodes are padding rows at the end of their shards) and
a node's local row is its group's number. ``g2h[v]`` is the owner of node
``v`` and ``g2l[v]`` its row there: the table's row of node ``v`` is
``feat[g2h[v] * rows_per_chip + g2l[v]]``.

Two jitted calls, so that neither's temporaries meet the other's: the
graph with the book (``indices`` filled in blocks: one call for 8e8 random
integers would hold several arrays of that size at once), then the table,
each chip filling its own shard in blocks from the labels of the nodes it
owns. Every shape is the configuration's and none depends on the seed.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from chipbench.world import seed_key

FEAT_BLOCK_ROWS = 1 << 18
EDGE_BLOCK = 1 << 24


def rows_per_chip(nodes: int, chips: int) -> int:
    return -(-int(nodes) // int(chips))


def _graph(key, *, nodes, edges, classes, dim, chips, degree_sigma,
           degree_cap):
    kdeg, kidx, klab, kcen, kbook = jax.random.split(key, 5)
    z = jax.random.normal(kdeg, (nodes,), jnp.float32)
    raw = jnp.minimum(jnp.exp(degree_sigma * z), float(degree_cap))
    # planted's fit of the drawn degrees to the edge slots: scaled,
    # floored, the remainder one slot a node from node 0 up
    scaled = raw * (edges / jnp.sum(raw, dtype=jnp.float32))
    deg = jnp.minimum(jnp.floor(scaled).astype(jnp.int32), degree_cap)
    short = edges - jnp.sum(deg, dtype=jnp.int32)
    take = jnp.arange(nodes, dtype=jnp.int32) < jnp.abs(short)
    deg = jnp.maximum(deg + jnp.where(take, jnp.sign(short), 0), 0)
    indptr = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(deg, dtype=jnp.int32)])

    block = min(EDGE_BLOCK, edges)

    def fill(b, indices):
        # the last block is clamped onto the array's end and overwrites
        # part of the one before it: deterministic, and in place
        start = jnp.minimum(b * block, edges - block)
        drawn = jax.random.randint(jax.random.fold_in(kidx, b), (block,), 0,
                                   nodes, dtype=jnp.int32)
        return jax.lax.dynamic_update_slice(indices, drawn, (start,))

    indices = jax.lax.fori_loop(0, -(-edges // block), fill,
                                jnp.zeros((edges,), jnp.int32))
    labels = jax.random.randint(klab, (nodes,), 0, classes, dtype=jnp.int32)
    centers = jax.random.normal(kcen, (classes, dim), jnp.float32)

    # the book: group v // chips deals its nodes round the chips, from a
    # drawn start
    turn = jax.random.randint(kbook, (rows_per_chip(nodes, chips),), 0,
                              chips, dtype=jnp.int32)
    v = jnp.arange(nodes, dtype=jnp.int32)
    g2l = v // chips
    g2h = (v % chips + turn[g2l]) % chips
    return {"indptr": indptr, "indices": indices, "labels": labels,
            "g2h": g2h, "g2l": g2l}, (centers, turn)


def _table(labels, centers, turn, kfeat, *, nodes, dim, chips, axis):
    """This chip's shard: row ``l`` is the node of group ``l`` that the
    book dealt to this chip, its features = class centre + 0.5 * noise."""
    me = jax.lax.axis_index(axis).astype(jnp.int32)
    mine = rows_per_chip(nodes, chips)
    rows = min(FEAT_BLOCK_ROWS, mine)
    key = jax.random.fold_in(jax.random.wrap_key_data(kfeat), me)

    def fill(b, feat):
        start = jnp.minimum(b * rows, mine - rows)
        local = start + jnp.arange(rows, dtype=jnp.int32)
        node = local * chips + (me - turn[local]) % chips
        # a short last group: the rows of its missing nodes are padding
        lab = labels[jnp.minimum(node, nodes - 1)]
        noise = jax.random.normal(jax.random.fold_in(key, b), (rows, dim),
                                  jnp.float32)
        return jax.lax.dynamic_update_slice(
            feat, centers[lab] + 0.5 * noise, (start, 0))

    return jax.lax.fori_loop(0, -(-mine // rows), fill,
                             jnp.zeros((mine, dim), jnp.float32))


def make(config: dict, seed: int, mesh) -> dict:
    """The arrays above for ``seed`` over ``mesh`` (one axis; its size is
    the number of chips the rows are divided over)."""
    (axis,) = mesh.axis_names
    chips = int(mesh.shape[axis])
    nodes, dim = int(config["nodes"]), int(config["feature_dim"])
    rep = NamedSharding(mesh, P())
    key = seed_key(seed)
    graph, (centers, turn) = jax.jit(functools.partial(
        _graph, nodes=nodes, edges=int(config["edges"]),
        classes=int(config["num_classes"]), dim=dim, chips=chips,
        degree_sigma=float(config["degree_sigma"]),
        degree_cap=int(config["degree_cap"])), out_shardings=rep)(key)
    table = jax.jit(shard_map(
        functools.partial(_table, nodes=nodes, dim=dim, chips=chips,
                          axis=axis),
        mesh=mesh, in_specs=(P(), P(), P(), P()), out_specs=P(axis, None),
        check_vma=False))
    kfeat = jax.random.key_data(jax.random.fold_in(key, 5))
    return dict(graph, feat=table(graph["labels"], centers, turn, kfeat))
