"""The world recipe ``planted_igbh``: a heterogeneous graph whose
relations each keep their own neighbour lists, made ON THE DEVICE from
the seed.

The configuration names its node types in id order (``node_types``: a
count a type; type ``t`` holds the global ids after those of the types
before it, which is where its rows lie in the one table) and its
relations (``relations``: a target type, a source type and a count of
edge slots each). Relation ``r`` is a CSR of its own over the target
type's LOCAL ids whose entries are the source type's local ids
(``quiver_tpu.ops.RelationIndices``' layout): lognormal degrees (sigma
``degree_sigma``) over the target type's nodes, scaled to the relation's
slots, capped at the source type's count (the hubs: fields and
institutes), rounded up or down at random so that a relation of mean
0.17 keeps its spread; the slots that rounding and the cap leave short
are spread over random nodes under the cap, those left over taken from
the largest degrees; every slot's neighbour uniform over the source
type's ids.

Rows (``feat``, ``precision.storage``): every node's row is the centre of
its seeded class plus 0.5 noise, filled block by block and rounded once:
each type has rows of its own at the papers' scale. Labels are drawn for
every node; only the papers' (type 0) are trained on.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from chipbench.world import seed_key

FEAT_BLOCK_ROWS = 1 << 18
STORAGE = {"float16": jnp.float16, "bfloat16": jnp.bfloat16}


def layout(config: dict):
    """``(names, starts, counts, relations)``: the types in id order, the
    first global id and the count of each, and each relation as
    ``(target type, source type, slots)`` by type index."""
    names = list(config["node_types"])
    counts = [int(config["node_types"][n]) for n in names]
    starts = [sum(counts[:i]) for i in range(len(counts))]
    rels = [(names.index(r["target"]), names.index(r["source"]),
             int(r["slots"])) for r in config["relations"]]
    return names, starts, counts, rels


def _degrees(key, count, slots, sigma, cap):
    kz, ku, kp = jax.random.split(key, 3)
    z = jax.random.normal(kz, (count,), jnp.float32)
    raw = jnp.minimum(jnp.exp(sigma * z), float(cap))
    scaled = raw * (slots / jnp.sum(raw, dtype=jnp.float32))
    u = jax.random.uniform(ku, (count,), jnp.float32)
    deg = jnp.minimum(jnp.floor(scaled + u).astype(jnp.int32), cap)
    short = slots - jnp.sum(deg, dtype=jnp.int32)
    # short: the slots spread over the nodes under the cap, in a random
    # order, as evenly as their room allows; over: one fewer to the
    # `-short` largest degrees
    rank = jax.random.permutation(kp, count).astype(jnp.int32)
    room = cap - deg
    order = jnp.argsort(jnp.where(room > 0, rank, count + rank))
    place = jnp.zeros((count,), jnp.int32).at[order].set(
        jnp.arange(count, dtype=jnp.int32))
    open_ = jnp.maximum(jnp.sum(room > 0, dtype=jnp.int32), 1)
    more = jnp.where(room > 0, short // open_
                     + (place < short % open_).astype(jnp.int32), 0)
    by_degree = jnp.zeros((count,), jnp.int32).at[jnp.argsort(-deg)].set(
        jnp.arange(count, dtype=jnp.int32))
    fix = jnp.where(short >= 0, jnp.minimum(more, room),
                    -(by_degree < -short).astype(jnp.int32))
    return deg + fix


def _world(key, *, counts, rels, dim, classes, sigma, storage):
    kgraph, klab, kcen, kfeat = jax.random.split(key, 4)
    indptrs, indices = [], []
    for r, (tt, st, slots) in enumerate(rels):
        kd, ki = jax.random.split(jax.random.fold_in(kgraph, r))
        deg = _degrees(kd, counts[tt], slots, sigma, counts[st])
        indptrs.append(jnp.concatenate([jnp.zeros((1,), jnp.int32),
                                        jnp.cumsum(deg, dtype=jnp.int32)]))
        indices.append(jax.random.randint(ki, (slots,), 0, counts[st],
                                          dtype=jnp.int32))
    nodes = sum(counts)
    labels = jax.random.randint(klab, (nodes,), 0, classes, dtype=jnp.int32)
    centers = jax.random.normal(kcen, (classes, dim), jnp.float32)
    rows = min(FEAT_BLOCK_ROWS, nodes)

    def fill(b, feat):
        # the last block is clamped onto the table's end, as in ``planted``
        start = jnp.minimum(b * rows, nodes - rows)
        lab = jax.lax.dynamic_slice(labels, (start,), (rows,))
        noise = jax.random.normal(jax.random.fold_in(kfeat, b), (rows, dim),
                                  jnp.float32)
        return jax.lax.dynamic_update_slice(
            feat, (centers[lab] + 0.5 * noise).astype(storage), (start, 0))

    feat = jax.lax.fori_loop(0, -(-nodes // rows), fill,
                             jnp.zeros((nodes, dim), storage))
    return {"indptr": tuple(indptrs), "indices": tuple(indices),
            "feat": feat, "labels": labels}


def make(config: dict, seed: int, sharding=None) -> dict:
    """``indptr`` and ``indices``: a tuple each, relation by relation (``[
    target count + 1]`` and ``[slots]`` int32, local ids), ``feat [nodes,
    dim]`` in the configuration's storage dtype, ``labels [nodes]``, on
    the device, in one jitted call."""
    storage = config["precision"]["storage"]
    if storage not in STORAGE:
        raise SystemExit(f"chipbench: world planted_igbh stores a 16-bit "
                         f"float, one of {sorted(STORAGE)}; the "
                         f"configuration says {storage!r}")
    _, _, counts, rels = layout(config)
    if sum(counts) != int(config["nodes"]) or \
            sum(r[2] for r in rels) != int(config["edges"]):
        raise SystemExit("chipbench: world planted_igbh: the types' counts "
                         "and the relations' slots must add up to `nodes` "
                         "and `edges`")
    fn = functools.partial(
        _world, counts=counts, rels=rels,
        dim=int(config["feature_dim"]), classes=int(config["num_classes"]),
        sigma=float(config["degree_sigma"]), storage=STORAGE[storage])
    jitted = jax.jit(fn, out_shardings=sharding) if sharding is not None \
        else jax.jit(fn)
    out = jitted(seed_key(seed))
    held = [int(p[-1]) for p in out["indptr"]]
    if held != [r[2] for r in rels]:
        raise SystemExit(f"chipbench: world planted_igbh: the relations hold "
                         f"{held} slots, the configuration says "
                         f"{[r[2] for r in rels]} (a cap too small for them)")
    return out
