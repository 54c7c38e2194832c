"""Fused single-kernel sample+gather hop A/B (qt-fuse).

Three checks in one pass, each printed as a one-line JSON record with a
``metric`` key (the chip-suite log grammar ``bench_regress.py``
parses):

1. ``fused_bit_equal`` — the fused kernel's picks AND dequantized rows
   against the split two-program oracle (``sample_layer_pallas`` +
   ``quant.gather_rows``), same PRNG stream, exact bit equality, masked
   ``-1`` tail seeds included. 1.0 or the run fails.
2. ``fused_vs_split_steps_per_s`` — timed steps/s ratio fused/split at
   one BLOCK of seeds (higher is better; on CPU both sides run the
   interpret-mode emulator, so treat the CPU number as a smoke figure,
   not kernel truth — the chip run is the record).
3. ``fused_gather_index_bytes`` — the fused hop's modeled gather
   indexing bytes from the cost model: 0 by construction (frontier ids
   never leave VMEM), tracked inverted so any regression that
   reintroduces the frontier-id HBM round trip fails the sweep.

The qt-fuse-deep multi-hop arm (round 21) repeats all three at the
production fanouts [15,10,5] — the WHOLE ladder as one program
(``fused_multihop``: interior hops sample in-kernel, compaction between
hops, only leaf rows written) against the per-hop split composition:

4. ``fused_multihop_bit_equal`` — frontier ids, every layer's
   topology, and the final feature block against the split
   ``sample_multihop``-style oracle, exact bit equality on valid
   slots. 1.0 or the run fails.
5. ``fused_multihop_vs_split_steps_per_s`` — timed whole-walk ratio
   (same CPU-interpret caveat as the single-hop figure; the leaf
   gather's DMAs emulate serially there, so the batch is small and the
   chip run is the record).
6. ``fused_multihop_gather_index_bytes`` — modeled indexing bytes for
   the whole walk from the registry's ``fused_multihop`` entry: 0
   across ALL hops, vs the split train step's per-walk baseline.

Usage: python benchmarks/bench_fused.py [--iters K] [--multihop]
(default runs the single-hop checks 1-3, keeping the long-lived log
records shape-stable; ``--multihop`` runs checks 4-6 instead — the
chip suite's fuse section drives both as separate lines)
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from _common import configure_jax

jax = configure_jax()
import jax.numpy as jnp
import numpy as np

from quiver_tpu.analysis.costmodel import cost_of
from quiver_tpu.analysis.registry import build_entry_specs
from quiver_tpu.ops import quant
from quiver_tpu.ops.pallas.fused import (default_interpret, default_rng,
                                         fused_hot_hop,
                                         fused_hot_hop_reference,
                                         fused_multihop,
                                         fused_multihop_reference,
                                         pad_indices)

N, DIM, BS, K, ROW_CAP = 4096, 128, 128, 4, 128
# production fanout ladder for the multi-hop arm; the batch is small
# because the frontier cap compounds per hop (MH_BS·16·11·6 leaf rows)
# and the CPU-interpret emulator walks the leaf gather serially.
MH_SIZES, MH_BS = [15, 10, 5], 8


def emit(metric, value, unit, **extra):
    print(json.dumps({"metric": metric, "value": value, "unit": unit,
                      **extra}), flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=5)
    ap.add_argument("--multihop", action="store_true",
                    help="run the multi-hop [15,10,5] arm instead of "
                         "the single-hop checks")
    args = ap.parse_args()

    rng = np.random.default_rng(18)
    deg = rng.integers(0, 24, N)
    indptr = np.zeros(N + 1, np.int64)
    indptr[1:] = np.cumsum(deg)
    indptr = jnp.asarray(indptr.astype(np.int32))
    indices = pad_indices(jnp.asarray(
        rng.integers(0, N, int(deg.sum())).astype(np.int32)), ROW_CAP)
    feat = quant.quantize(jnp.asarray(
        rng.standard_normal((N, DIM)).astype(np.float32)), "int8")
    seeds = np.full((BS,), -1, np.int32)
    seeds[:BS - 8] = rng.choice(N, BS - 8, replace=False)
    seeds = jnp.asarray(seeds)
    kernel_rng, interpret = default_rng(), default_interpret()

    if args.multihop:
        run_multihop(args, rng, indptr, indices, feat, kernel_rng,
                     interpret)
        return

    def fused(s):
        return fused_hot_hop(indptr, indices, seeds, feat, K, s,
                             row_cap=ROW_CAP, rng=kernel_rng,
                             interpret=interpret)

    def split(s):
        return fused_hot_hop_reference(indptr, indices, seeds, feat, K,
                                       s, row_cap=ROW_CAP,
                                       rng=kernel_rng,
                                       interpret=interpret)

    # 1. bit equivalence (also the compile pass for both programs)
    got = jax.block_until_ready(fused(jnp.int32(0)))
    want = jax.block_until_ready(split(jnp.int32(0)))
    names = ("nbrs", "counts", "seed_rows", "pick_rows")
    for g, w, name in zip(got, want, names):
        g, w = np.asarray(g), np.asarray(w)
        if g.tobytes() != w.tobytes():
            emit("fused_bit_equal", 0.0, "bool", diverged=name)
            raise SystemExit(f"fused kernel diverges from the split "
                             f"oracle on {name}")
    emit("fused_bit_equal", 1.0, "bool", rng=kernel_rng,
         interpret=interpret)

    # 2. timed A/B
    def steps_per_s(fn):
        t0 = time.perf_counter()
        for r in range(args.iters):
            out = fn(jnp.int32(r + 1))
        jax.block_until_ready(out)
        return args.iters / (time.perf_counter() - t0)

    fused_sps = steps_per_s(fused)
    split_sps = steps_per_s(split)
    emit("fused_vs_split_steps_per_s",
         round(fused_sps / split_sps, 4), "ratio",
         fused_steps_per_s=round(fused_sps, 2),
         split_steps_per_s=round(split_sps, 2),
         platform=jax.devices()[0].platform)

    # 3. modeled index bytes: fused entry vs the split train step
    fused_cost = cost_of(build_entry_specs("fused_hot_hop")[0])
    split_cost = cost_of(build_entry_specs("train_step")[0])
    emit("fused_gather_index_bytes",
         int(fused_cost.gather_index_bytes), "bytes",
         split_train_step_index_bytes=int(
             split_cost.gather_index_bytes),
         fused_gather_bytes=int(fused_cost.gather_bytes))


def run_multihop(args, rng, indptr, indices, feat, kernel_rng,
                 interpret):
    # the whole [15,10,5] walk as one program vs the per-hop split
    mh_seeds = jnp.asarray(
        rng.choice(N, MH_BS, replace=False).astype(np.int32))

    def mh_key(r):
        return jax.random.fold_in(jax.random.key(0), r)

    def mh_fused(r):
        return fused_multihop(indptr, indices, mh_seeds, feat,
                              MH_SIZES, mh_key(r), row_cap=ROW_CAP,
                              rng=kernel_rng, interpret=interpret)

    def mh_split(r):
        return fused_multihop_reference(indptr, indices, mh_seeds,
                                        feat, MH_SIZES, mh_key(r),
                                        row_cap=ROW_CAP,
                                        rng=kernel_rng,
                                        interpret=interpret)

    # 4. bit equivalence across the whole walk (also the compile pass)
    g_nid, g_layers, g_x = jax.block_until_ready(mh_fused(0))
    w_nid, w_layers, w_x = jax.block_until_ready(mh_split(0))
    diverged = None
    if np.asarray(g_nid).tobytes() != np.asarray(w_nid).tobytes():
        diverged = "n_id"
    for i, (g, w) in enumerate(zip(g_layers, w_layers)):
        for fld in ("n_id", "n_count", "row", "col", "edge_count"):
            if diverged is None and (
                    np.asarray(getattr(g, fld)).tobytes()
                    != np.asarray(getattr(w, fld)).tobytes()):
                diverged = f"layer{i}.{fld}"
    valid = np.asarray(g_nid) >= 0
    gx, wx = np.asarray(g_x)[valid], np.asarray(w_x)[valid]
    if diverged is None and gx.tobytes() != wx.tobytes():
        diverged = "x"
    if diverged is not None:
        emit("fused_multihop_bit_equal", 0.0, "bool",
             diverged=diverged, sizes=MH_SIZES)
        raise SystemExit(f"fused multi-hop walk diverges from the "
                         f"split oracle on {diverged}")
    emit("fused_multihop_bit_equal", 1.0, "bool", sizes=MH_SIZES,
         rng=kernel_rng, interpret=interpret)

    # 5. timed whole-walk A/B
    def mh_steps_per_s(fn):
        t0 = time.perf_counter()
        for r in range(args.iters):
            out = fn(r + 1)
        jax.block_until_ready(out)
        return args.iters / (time.perf_counter() - t0)

    mh_fused_sps = mh_steps_per_s(mh_fused)
    mh_split_sps = mh_steps_per_s(mh_split)
    emit("fused_multihop_vs_split_steps_per_s",
         round(mh_fused_sps / mh_split_sps, 4), "ratio",
         fused_steps_per_s=round(mh_fused_sps, 2),
         split_steps_per_s=round(mh_split_sps, 2),
         sizes=MH_SIZES, batch=MH_BS,
         platform=jax.devices()[0].platform)

    # 6. modeled index bytes for the whole walk: zero across ALL hops
    mh_cost = cost_of(build_entry_specs("fused_multihop")[0])
    split_cost = cost_of(build_entry_specs("train_step")[0])
    emit("fused_multihop_gather_index_bytes",
         int(mh_cost.gather_index_bytes), "bytes",
         split_train_step_index_bytes=int(
             split_cost.gather_index_bytes),
         fused_gather_bytes=int(mh_cost.gather_bytes))


if __name__ == "__main__":
    main()
