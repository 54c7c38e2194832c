"""The world recipe ``planted_typed``: a heterogeneous graph merged into
ONE id space, made ON THE DEVICE from the seed.

The configuration names its node types in id order (``node_types``: a
count a type; type ``t`` holds the ids after those of the types before
it) and its relations (``relations``: a target type, a source type, a
count of edge slots and a degree cap each). A node's CSR row holds the
slots of every relation whose target type is its own, in relation order.
Per relation: lognormal degrees (sigma ``degree_sigma``) over the target
type's nodes, scaled to the relation's slots, floored, capped, the
remainder one slot each to the type's lowest-numbered nodes
(``planted``'s recipe); every slot's neighbour is uniform over the
source type's ids. ``relation`` stores each slot's relation (int8): the
reference reads it by plain indexing, the program never does.

Rows (``feat``, ``precision.storage``): the first type's (papers) are
``planted``'s, class centre + 0.5 noise, filled block by block and
rounded once. Every other type's row is the mean of the rows of its
neighbours under the first relation into it whose source type comes
before it (authors: their papers; institutions: their authors), summed
in float32 over the stored rows, block by block, and rounded once; a
node with no such neighbour gets a zero row. Labels are drawn for every
node; only the first type's are trained on.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from chipbench.world import seed_key

FEAT_BLOCK_ROWS = 1 << 18
SLOT_BLOCK = 1 << 20
STORAGE = {"float16": jnp.float16, "bfloat16": jnp.bfloat16}


def layout(config: dict):
    """``(names, starts, counts, relations)``: the types in id order, the
    first id and the count of each, and each relation as ``(target type,
    source type, slots, cap)`` by type index."""
    names = list(config["node_types"])
    counts = [int(config["node_types"][n]) for n in names]
    starts = [sum(counts[:i]) for i in range(len(counts))]
    rels = [(names.index(r["target"]), names.index(r["source"]),
             int(r["slots"]), int(r["degree_cap"]))
            for r in config["relations"]]
    return names, starts, counts, rels


def _degrees(key, count, slots, sigma, cap):
    z = jax.random.normal(key, (count,), jnp.float32)
    raw = jnp.minimum(jnp.exp(sigma * z), float(cap))
    scaled = raw * (slots / jnp.sum(raw, dtype=jnp.float32))
    deg = jnp.minimum(jnp.floor(scaled).astype(jnp.int32), cap)
    short = slots - jnp.sum(deg, dtype=jnp.int32)
    take = jnp.arange(count, dtype=jnp.int32) < jnp.abs(short)
    return jnp.maximum(deg + jnp.where(take, jnp.sign(short), 0), 0)


def _row_of_slots(indptr, edges):
    """The CSR row of every slot (a scatter of the row starts, a cumsum)."""
    z = jnp.zeros((edges,), jnp.int32)
    inner = indptr[1:-1]
    z = z.at[jnp.clip(inner, 0, max(edges - 1, 0))].add(
        jnp.where(inner < edges, 1, 0).astype(jnp.int32))
    return jnp.cumsum(z).astype(jnp.int32)


def _graph(key, *, starts, counts, rels, sigma, edges):
    nodes = sum(counts)
    kdeg, kidx = jax.random.split(key)
    # per node: its relations' degrees, in relation order
    degs = [jnp.zeros((nodes,), jnp.int32) for _ in rels]
    for r, (tt, _, slots, cap) in enumerate(rels):
        d = _degrees(jax.random.fold_in(kdeg, r), counts[tt], slots, sigma,
                     cap)
        degs[r] = jax.lax.dynamic_update_slice(degs[r], d, (starts[tt],))
    total = sum(degs)
    indptr = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(total, dtype=jnp.int32)])
    row = _row_of_slots(indptr, edges)
    # one read a slot of its row's start and of where each relation's
    # run in the row ends; the relation is how many runs end at or
    # before the slot's place
    ends = jnp.cumsum(jnp.stack(degs, axis=1), axis=1, dtype=jnp.int32)
    heads = jnp.concatenate([indptr[:-1, None], ends], axis=1)[row]
    place = jnp.arange(edges, dtype=jnp.int32) - heads[:, 0]
    rel = jnp.sum(place[:, None] >= heads[:, 1:], axis=1, dtype=jnp.int32)
    lo = jnp.zeros((edges,), jnp.int32)
    hi = jnp.ones((edges,), jnp.int32)
    for r, (_, st, _, _) in enumerate(rels):
        lo = jnp.where(rel == r, starts[st], lo)
        hi = jnp.where(rel == r, starts[st] + counts[st], hi)
    indices = jax.random.randint(kidx, (edges,), lo, hi, dtype=jnp.int32)
    return indptr, indices, rel.astype(jnp.int8), row, degs


def _mean_rows(feat, indptr, indices, rel, row, *, r, count_of, lo, count):
    """Rows ``[lo, lo + count)`` of ``feat`` set to the mean of their
    relation-``r`` neighbours' rows, summed in float32, chunk by chunk of
    targets, each chunk's slots block by block."""
    dim, storage = feat.shape[1], feat.dtype
    chunk = min(FEAT_BLOCK_ROWS, count)
    block = min(SLOT_BLOCK, indices.shape[0])
    for c in range(-(-count // chunk)):
        first = lo + min(c * chunk, count - chunk)
        begin, end = indptr[first], indptr[first + chunk]

        def add(b, acc):
            # a block past the end is clamped back onto it; the slots it
            # then holds twice are left to the block before
            at = begin + b * block
            s0 = jnp.minimum(at, indices.shape[0] - block)
            take = lambda a: jax.lax.dynamic_slice(a, (s0,), (block,))
            slots = s0 + jnp.arange(block, dtype=jnp.int32)
            mine = (slots >= at) & (slots < end) & (take(rel) == r)
            src = jnp.where(mine, take(indices), 0)
            rows = feat[src].astype(jnp.float32) * mine[:, None]
            return acc.at[jnp.where(mine, take(row) - first, chunk)].add(
                rows, mode="drop")

        trips = (end - begin + block - 1) // block
        acc = jax.lax.fori_loop(0, trips, add,
                                jnp.zeros((chunk, dim), jnp.float32))
        n = jax.lax.dynamic_slice(count_of, (first,), (chunk,))
        mean = acc / jnp.maximum(n, 1).astype(jnp.float32)[:, None]
        feat = jax.lax.dynamic_update_slice(feat, mean.astype(storage),
                                            (first, 0))
    return feat


def _world(key, *, starts, counts, rels, edges, dim, classes, sigma,
           storage):
    kgraph, klab, kcen, kfeat = jax.random.split(key, 4)
    nodes = sum(counts)
    indptr, indices, rel, row, degs = _graph(
        kgraph, starts=starts, counts=counts, rels=rels, sigma=sigma,
        edges=edges)
    labels = jax.random.randint(klab, (nodes,), 0, classes, dtype=jnp.int32)
    centers = jax.random.normal(kcen, (classes, dim), jnp.float32)

    first = counts[0]
    rows = min(FEAT_BLOCK_ROWS, first)

    def fill(b, feat):
        # the last block is clamped onto the type's end, as in ``planted``
        start = jnp.minimum(b * rows, first - rows)
        lab = jax.lax.dynamic_slice(labels, (start,), (rows,))
        noise = jax.random.normal(jax.random.fold_in(kfeat, b), (rows, dim),
                                  jnp.float32)
        return jax.lax.dynamic_update_slice(
            feat, (centers[lab] + 0.5 * noise).astype(storage), (start, 0))

    feat = jax.lax.fori_loop(0, -(-first // rows), fill,
                             jnp.zeros((nodes, dim), storage))
    for t in range(1, len(counts)):
        r = next(i for i, (tt, st, _, _) in enumerate(rels)
                 if tt == t and st < t)
        feat = _mean_rows(feat, indptr, indices, rel, row, r=r,
                          count_of=degs[r], lo=starts[t], count=counts[t])
    return {"indptr": indptr, "indices": indices, "relation": rel,
            "feat": feat, "labels": labels}


def make(config: dict, seed: int, sharding=None) -> dict:
    """``indptr [nodes+1]``, ``indices [edges]``, ``relation [edges]``
    (int8), ``feat [nodes, dim]`` in the configuration's storage dtype,
    ``labels [nodes]``, on the device, in one jitted call."""
    storage = config["precision"]["storage"]
    if storage not in STORAGE:
        raise SystemExit(f"chipbench: world planted_typed stores a 16-bit "
                         f"float, one of {sorted(STORAGE)}; the "
                         f"configuration says {storage!r}")
    _, starts, counts, rels = layout(config)
    if sum(counts) != int(config["nodes"]) or \
            sum(r[2] for r in rels) != int(config["edges"]):
        raise SystemExit("chipbench: world planted_typed: the types' counts "
                         "and the relations' slots must add up to `nodes` "
                         "and `edges`")
    fn = functools.partial(
        _world, starts=starts, counts=counts, rels=rels,
        edges=int(config["edges"]), dim=int(config["feature_dim"]),
        classes=int(config["num_classes"]),
        sigma=float(config["degree_sigma"]), storage=STORAGE[storage])
    jitted = jax.jit(fn, out_shardings=sharding) if sharding is not None \
        else jax.jit(fn)
    return jitted(seed_key(seed))
