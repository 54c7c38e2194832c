"""The world recipe ``planted`` (what a configuration gets that names no
``world``), made ON THE DEVICE from the seed.

A seeded planted-label graph in CSR form (``chip_smoke.make_world``'s
recipe, generated where it is used): lognormal degrees (sigma 1, capped),
uniform neighbours, features = class centre + 0.5 * noise. Every shape is
the configuration's and none depends on the seed: ``indices`` has exactly
``edges`` slots, so one compiled program serves every seed of a cell.

How the drawn degrees are fitted to the slots: the capped lognormal
draws are scaled by ``edges / sum``, floored, and the remainder (fewer
than ``nodes`` slots) goes one each to the lowest-numbered nodes, so a
degree can pass the cap by one.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from chipbench.world import seed_key

FEAT_BLOCK_ROWS = 1 << 18


def _world(key, *, nodes, edges, dim, classes, degree_sigma, degree_cap):
    kdeg, kidx, klab, kcen, kfeat = jax.random.split(key, 5)
    z = jax.random.normal(kdeg, (nodes,), jnp.float32)
    raw = jnp.minimum(jnp.exp(degree_sigma * z), float(degree_cap))
    # float32 sums of 1e7 terms drift; the remainder below absorbs it
    scaled = raw * (edges / jnp.sum(raw, dtype=jnp.float32))
    deg = jnp.minimum(jnp.floor(scaled).astype(jnp.int32), degree_cap)
    short = edges - jnp.sum(deg, dtype=jnp.int32)
    # |short| < nodes in practice; spread it one slot a node from node 0 up
    step = jnp.sign(short)
    take = jnp.arange(nodes, dtype=jnp.int32) < jnp.abs(short)
    deg = jnp.maximum(deg + jnp.where(take, step, 0), 0)
    indptr = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(deg, dtype=jnp.int32)])
    indices = jax.random.randint(kidx, (edges,), 0, nodes, dtype=jnp.int32)
    labels = jax.random.randint(klab, (nodes,), 0, classes, dtype=jnp.int32)
    centers = jax.random.normal(kcen, (classes, dim), jnp.float32)

    rows = min(FEAT_BLOCK_ROWS, nodes)
    blocks = -(-nodes // rows)

    def fill(b, feat):
        # the last block is clamped onto the table's end and overwrites
        # part of the one before it: deterministic, and in place
        start = jnp.minimum(b * rows, nodes - rows)
        lab = jax.lax.dynamic_slice(labels, (start,), (rows,))
        noise = jax.random.normal(jax.random.fold_in(kfeat, b), (rows, dim),
                                  jnp.float32)
        return jax.lax.dynamic_update_slice(
            feat, centers[lab] + 0.5 * noise, (start, 0))

    feat = jax.lax.fori_loop(0, blocks, fill,
                             jnp.zeros((nodes, dim), jnp.float32))
    return {"indptr": indptr, "indices": indices, "feat": feat,
            "labels": labels}


def make(config: dict, seed: int, sharding=None) -> dict:
    """``indptr [nodes+1]``, ``indices [edges]``, ``feat [nodes, dim]``,
    ``labels [nodes]`` on the device (replicated over ``sharding``'s mesh
    where one is given), in one jitted call."""
    fn = functools.partial(
        _world, nodes=int(config["nodes"]), edges=int(config["edges"]),
        dim=int(config["feature_dim"]), classes=int(config["num_classes"]),
        degree_sigma=float(config["degree_sigma"]),
        degree_cap=int(config["degree_cap"]))
    jitted = jax.jit(fn, out_shardings=sharding) if sharding is not None \
        else jax.jit(fn)
    return jitted(seed_key(seed))
