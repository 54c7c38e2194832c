"""The world recipe ``planted_half``: ``planted``'s world with the table
kept in a 16-bit float, made ON THE DEVICE from the seed.

The graph, the labels, the class centres and the noise are ``planted``'s,
drawn from the same keys (``worlds/planted.py`` says how the degrees are
fitted to the edge slots): the same seed gives the same graph, and a row
here is ``planted``'s float32 row rounded once to the configuration's
``precision.storage`` (``float16`` or ``bfloat16``). The table is filled
block by block in that dtype, so the float32 table, twice the size, never
exists: only one block of ``FEAT_BLOCK_ROWS`` rows is float32 at a time.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from chipbench.world import seed_key

FEAT_BLOCK_ROWS = 1 << 18
STORAGE = {"float16": jnp.float16, "bfloat16": jnp.bfloat16}


def _world(key, *, nodes, edges, dim, classes, degree_sigma, degree_cap,
           storage):
    kdeg, kidx, klab, kcen, kfeat = jax.random.split(key, 5)
    z = jax.random.normal(kdeg, (nodes,), jnp.float32)
    raw = jnp.minimum(jnp.exp(degree_sigma * z), float(degree_cap))
    scaled = raw * (edges / jnp.sum(raw, dtype=jnp.float32))
    deg = jnp.minimum(jnp.floor(scaled).astype(jnp.int32), degree_cap)
    short = edges - jnp.sum(deg, dtype=jnp.int32)
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
        # the last block is clamped onto the table's end, as in ``planted``
        start = jnp.minimum(b * rows, nodes - rows)
        lab = jax.lax.dynamic_slice(labels, (start,), (rows,))
        noise = jax.random.normal(jax.random.fold_in(kfeat, b), (rows, dim),
                                  jnp.float32)
        return jax.lax.dynamic_update_slice(
            feat, (centers[lab] + 0.5 * noise).astype(storage), (start, 0))

    feat = jax.lax.fori_loop(0, blocks, fill,
                             jnp.zeros((nodes, dim), storage))
    return {"indptr": indptr, "indices": indices, "feat": feat,
            "labels": labels}


def make(config: dict, seed: int, sharding=None) -> dict:
    """``indptr [nodes+1]``, ``indices [edges]``, ``feat [nodes, dim]`` in
    the configuration's storage dtype, ``labels [nodes]``, on the device,
    in one jitted call."""
    storage = config["precision"]["storage"]
    if storage not in STORAGE:
        raise SystemExit(f"chipbench: world planted_half stores a 16-bit "
                         f"float, one of {sorted(STORAGE)}; the "
                         f"configuration says {storage!r}")
    fn = functools.partial(
        _world, nodes=int(config["nodes"]), edges=int(config["edges"]),
        dim=int(config["feature_dim"]), classes=int(config["num_classes"]),
        degree_sigma=float(config["degree_sigma"]),
        degree_cap=int(config["degree_cap"]), storage=STORAGE[storage])
    jitted = jax.jit(fn, out_shardings=sharding) if sharding is not None \
        else jax.jit(fn)
    return jitted(seed_key(seed))
