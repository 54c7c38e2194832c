"""Ablation timing of the fused multihop sampler. (dev tool)"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from quiver_tpu.ops.sample import (sample_layer, compact_layer)
from quiver_tpu.utils.compile_cache import place_compile_cache

place_compile_cache()

N = 2_450_000
AVG = 25
ITERS = 20
SIZES = [15, 10, 5]
BATCH = 1024
key = jax.random.key(0)


def timed(label, fn, *args):
    out = jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    dt = (time.perf_counter() - t0) / ITERS * 1e3
    print(f"{label:45s} {dt:8.3f} ms/batch")
    return out


def scan(body):
    def f(*args):
        def step(c, i):
            return body(c, i, *args), None
        tot, _ = jax.lax.scan(step, jnp.int32(0),
                              jnp.arange(ITERS, dtype=jnp.int32))
        return tot
    return jax.jit(f)


def make_graph():
    @jax.jit
    def mk(k):
        ln = jax.random.normal(k, (N,)) + jnp.log(float(AVG))
        deg = jnp.clip(jnp.exp(ln).astype(jnp.int32), 0, 10_000)
        return jnp.concatenate([jnp.zeros((1,), jnp.int32), jnp.cumsum(deg)])
    indptr = mk(key)
    e = int(indptr[-1])
    indices = jax.jit(lambda k: jax.random.randint(k, (e,), 0, N,
                                                   dtype=jnp.int32))(
        jax.random.fold_in(key, 1))
    jax.block_until_ready(indices)
    return indptr, indices


def multihop(indptr, indices, seeds, kk, do_compact=(True, True, True),
             do_sample_gather=True):
    cur = seeds
    total = jnp.int32(0)
    for i, k in enumerate(SIZES):
        sub = jax.random.fold_in(kk, i)
        if do_sample_gather:
            nbrs, cnt = sample_layer(indptr, indices, cur, k, sub)
        else:
            # fake neighbors: skip the indices gather but keep shapes
            s = cur.shape[0]
            nbrs = jax.random.randint(sub, (s, k), 0, N, dtype=jnp.int32)
            cnt = jnp.full((s,), k, jnp.int32)
        if do_compact[i]:
            lay = compact_layer(cur, nbrs)
            cur = lay.n_id
            total = total + lay.n_count
        else:
            cur = jnp.concatenate([cur, nbrs.reshape(-1)])
            total = total + jnp.sum(cnt)
    return total


def main():
    indptr, indices = make_graph()

    def full(c, i, indptr, indices):
        kb = jax.random.fold_in(key, i)
        seeds = jax.random.randint(kb, (BATCH,), 0, N, dtype=jnp.int32)
        return c + multihop(indptr, indices, seeds, kb)

    timed("full multihop", scan(full), indptr, indices)

    def no_last_compact(c, i, indptr, indices):
        kb = jax.random.fold_in(key, i)
        seeds = jax.random.randint(kb, (BATCH,), 0, N, dtype=jnp.int32)
        return c + multihop(indptr, indices, seeds, kb,
                            do_compact=(True, True, False))

    timed("multihop minus final compact", scan(no_last_compact),
          indptr, indices)

    def no_compact(c, i, indptr, indices):
        kb = jax.random.fold_in(key, i)
        seeds = jax.random.randint(kb, (BATCH,), 0, N, dtype=jnp.int32)
        return c + multihop(indptr, indices, seeds, kb,
                            do_compact=(False, False, False))

    timed("multihop no compacts", scan(no_compact), indptr, indices)

    def no_gather(c, i, indptr, indices):
        kb = jax.random.fold_in(key, i)
        seeds = jax.random.randint(kb, (BATCH,), 0, N, dtype=jnp.int32)
        return c + multihop(indptr, indices, seeds, kb,
                            do_sample_gather=False)

    timed("multihop compacts only (fake sample)", scan(no_gather),
          indptr, indices)


if __name__ == "__main__":
    main()
