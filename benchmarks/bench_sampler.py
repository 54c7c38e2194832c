"""Sampler benchmark: SEPS (sampled edges / second).

Mirrors the reference benchmark (benchmarks/sample/bench_sampler.py,
metric defined at :14-16) on a synthetic products-scale graph, comparing
the jnp sampler and the Pallas kernel path.

Usage: python benchmarks/bench_sampler.py [--nodes N] [--batch B]
       [--sizes 15 10 5] [--batches K] [--pallas]
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--nodes", type=int, default=2_450_000)
    p.add_argument("--avg-deg", type=int, default=25)
    p.add_argument("--batch", type=int, default=1024)
    p.add_argument("--batches", type=int, default=20)
    p.add_argument("--sizes", type=int, nargs="+", default=[15, 10, 5])
    p.add_argument("--pallas", action="store_true",
                   help="use the Pallas sampling kernel (single hop, "
                        "sizes[0]) — compare against --hop1 variants")
    p.add_argument("--hop1", default=None,
                   choices=["exact", "wide", "rotation", "wexact",
                            "wwindow"],
                   help="single-hop jnp sampler at sizes[0] — the "
                        "apples-to-apples baseline for --pallas; "
                        "wide = the wide-fetch exact path "
                        "(sample_layer_exact_wide, same i.i.d. draw as "
                        "exact); wexact/wwindow = the weighted (GAT) "
                        "draw, exact pool vs windowed")
    p.add_argument("--row-cap", type=int, default=2048)
    args = p.parse_args()

    from _common import configure_jax
    jax = configure_jax()
    import jax.numpy as jnp
    from quiver_tpu.ops import (as_index_rows_overlapping, edge_row_ids,
                                permute_csr, sample_layer,
                                sample_layer_exact_wide,
                                sample_layer_rotation,
                                sample_layer_weighted,
                                sample_layer_weighted_window,
                                sample_multihop)
    from quiver_tpu.ops.pallas.sample_kernel import (
        pad_indices, sample_layer_pallas)

    if args.pallas and jax.devices()[0].platform != "tpu":
        # pltpu.prng_seed has no native CPU lowering, and the TPU
        # interpreter is orders of magnitude too slow at bench sizes —
        # this comparison is chip-only (tests/test_pallas.py covers the
        # kernel's logic under the interpreter at toy sizes). Checked
        # before the ~61M-edge graph build, which would be wasted work.
        sys.exit("--pallas needs a real TPU")

    key = jax.random.key(0)
    n = args.nodes

    @jax.jit
    def build(k):
        ln = jax.random.normal(k, (n,)) + jnp.log(float(args.avg_deg))
        deg = jnp.clip(jnp.exp(ln).astype(jnp.int32), 0, 10_000)
        indptr = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                                  jnp.cumsum(deg)])
        return indptr

    indptr = build(jax.random.fold_in(key, 1))
    e = int(indptr[-1])
    indices = jax.jit(
        lambda k: jax.random.randint(k, (e,), 0, n, dtype=jnp.int32)
    )(jax.random.fold_in(key, 2))

    # the graph arrays are jit ARGUMENTS everywhere below: a closed-over
    # device array is embedded in the HLO as a literal constant, a
    # few hundred MB of it at this scale
    if args.hop1 in ("wexact", "wwindow"):
        # ONE weights build for both weighted arms — the comparison
        # stays apples-to-apples if the distribution is ever tweaked
        wts = jax.jit(lambda k: jax.random.uniform(k, (e,)) + 0.1)(
            jax.random.fold_in(key, 8))
    if args.pallas:
        big = pad_indices(indices, args.row_cap)

        @jax.jit
        def run(indptr, big, seeds, k):
            seed_scalar = jax.random.randint(k, (), 0, 2 ** 31 - 1)
            nbrs, counts = sample_layer_pallas(
                indptr, big, seeds, args.sizes[0], seed_scalar,
                row_cap=args.row_cap)
            return nbrs, jnp.sum(counts)
    elif args.hop1 == "exact":
        big = indices

        @jax.jit
        def run(indptr, big, seeds, k):
            nbrs, counts = sample_layer(indptr, big, seeds,
                                        args.sizes[0], k)
            return nbrs, jnp.sum(counts)
    elif args.hop1 == "wide":
        # flat + overlapping layout view of the SAME un-shuffled array
        big = (indices,
               jax.block_until_ready(
                   jax.jit(as_index_rows_overlapping)(indices)))

        @jax.jit
        def run(indptr, big, seeds, k):
            nbrs, counts = sample_layer_exact_wide(
                indptr, big[0], big[1], seeds, args.sizes[0], k,
                stride=128)
            return nbrs, jnp.sum(counts)
    elif args.hop1 == "wexact":
        big = (indices, wts)

        @jax.jit
        def run(indptr, big, seeds, k):
            nbrs, counts = sample_layer_weighted(
                indptr, big[0], big[1], seeds, args.sizes[0], k)
            return nbrs, jnp.sum(counts)
    elif args.hop1 == "wwindow":
        rids = jax.jit(edge_row_ids, static_argnums=1)(indptr, e)
        perm, (wperm,) = jax.jit(
            lambda ix, w, r, kk: permute_csr(ix, r, kk, extra=(w,))
        )(indices, wts, rids, jax.random.fold_in(key, 9))
        big = (jax.block_until_ready(jax.jit(as_index_rows_overlapping)(
                   perm)),
               jax.block_until_ready(jax.jit(as_index_rows_overlapping)(
                   wperm)))

        @jax.jit
        def run(indptr, big, seeds, k):
            nbrs, counts = sample_layer_weighted_window(
                indptr, big[0], big[1], seeds, args.sizes[0], k,
                stride=128)
            return nbrs, jnp.sum(counts)
    elif args.hop1 == "rotation":
        rids = jax.jit(edge_row_ids, static_argnums=1)(indptr, e)
        big = jax.block_until_ready(jax.jit(
            lambda ix, r, kk: as_index_rows_overlapping(
                permute_csr(ix, r, kk)))(indices, rids,
                                         jax.random.fold_in(key, 9)))

        @jax.jit
        def run(indptr, big, seeds, k):
            nbrs, counts = sample_layer_rotation(indptr, big, seeds,
                                                 args.sizes[0], k,
                                                 stride=128)
            return nbrs, jnp.sum(counts)
    else:
        big = indices

        @jax.jit
        def run(indptr, big, seeds, k):
            n_id, layers = sample_multihop(indptr, big, seeds,
                                           args.sizes, k)
            return n_id, sum(l.edge_count.astype(jnp.int32)
                             for l in layers)

    @jax.jit
    def make_seeds(k):
        return jax.random.randint(k, (args.batch,), 0, n, dtype=jnp.int32)

    out, edges = run(indptr, big, make_seeds(jax.random.fold_in(key, 50)),
                     jax.random.fold_in(key, 51))
    jax.block_until_ready(out)

    total = 0
    t0 = time.perf_counter()
    for i in range(args.batches):
        out, edges = run(indptr, big,
                         make_seeds(jax.random.fold_in(key, 100 + i)),
                         jax.random.fold_in(key, 200 + i))
        total += int(edges)
    jax.block_until_ready(out)
    dt = time.perf_counter() - t0
    label = ("pallas-hop1" if args.pallas else
             f"jnp-hop1-{args.hop1}" if args.hop1 else f"jnp {args.sizes}")
    print(f"[{label}] {total} edges in {dt:.3f}s -> "
          f"SEPS = {total / dt / 1e6:.2f} M")


if __name__ == "__main__":
    main()
