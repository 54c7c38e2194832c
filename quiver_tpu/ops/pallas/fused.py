"""Fused single-kernel sample+gather hot hop — frontier ids stay in VMEM.

Sampling and feature lookup are two separate XLA programs on the jnp
path, with the frontier ids materialized as an HBM array between them —
the exact seam the paper's warp-per-seed sampler + warp-per-row gather
design exists to hide (and the one the sample-and-aggregate fusion line,
arxiv 2209.02916, and C-SAW's sample-then-collect pipeline, 2009.06693,
attack by keeping picks on-chip). PR 12 priced that seam:
``costmodel.gather_index_bytes`` counts 2,080 B of pure frontier-id
traffic per train_step batch.

This kernel walks ONE hop for a block of 128 seeds and gathers the
feature rows of every seed and every pick before returning:

  phase A (sample, per block)
    - DMA the 128-entry rows of ``indptr`` holding each seed's pair
      HBM->SMEM (degrees/starts are computed in-kernel — the wrapper
      issues NO gather, which is what makes ``gather_index_bytes=0`` a
      verifiable model output);
    - DMA each seed's CSR neighbor window HBM->VMEM from the row its
      first entry sits in (``_dma`` rules), residual shifting the
      position compare;
    - the ``sample_kernel`` vectorized partial Fisher-Yates picks k
      positions per seed ([BLOCK, k] lanes, pluggable PRNG);
    - iota-compare extraction materializes picks + counts.
  phase B (gather, same kernel invocation)
    - the picks are DMA'd VMEM->SMEM once (SMEM is the scalar-
      addressable space; frontier ids never leave the core);
    - a double-buffered pipeline (the ``gather`` kernel's _N_BUF scheme)
      DMAs each of the BLOCK*(1+k) hot-tier rows — int8 codes plus the
      fp32 scale/zero sidecars for a quantized tier — and applies the
      folded ``code * scale + zero`` FMA in-register (bit-identical to
      ``quant.gather_rows``), multiply-masking invalid (-1 / cold) rows
      to zero exactly like ``masked_feature_gather``.

Round 21 (qt-fuse-deep) extends the path to the FULL fanout ladder:
``fused_multihop`` walks every hop with the same kernel family —
interior hops run the sampling-only variant (phase A alone, with the
``indptr`` pairs still resolved in-kernel, so no hop ever issues an
XLA gather), the gather-free sort-based ``compact_layer`` dedups each
picked frontier into the next hop's static-budget seed block, and the
LEAF hop runs the full sample+gather kernel. Because every hop's
compacted frontier keeps the previous frontier as its slot-[0, v)
prefix, the leaf hop's seeds ARE the whole walk's interior — one
in-kernel gather over (leaf seeds + leaf picks) covers every frontier
node, and the assembled ``[cap, dim]`` block is bit-identical to the
split oracle's ``masked_feature_gather`` over the final ``n_id``
(valid slots; never-touched padding slots are +0.0 here vs the
oracle's multiply-masked signed zero — same documented wobble as the
single-hop reassembly). ``gather_index_bytes == 0`` across ALL hops is
therefore a verifiable model output for the multi-hop entry too.

Scope and contract:

- hot tier only. Picks whose storage row falls outside
  ``hot_rows`` (cold tier) come back zero-masked alongside valid=False
  semantics; callers route them to the unchanged tiered lookup.
- per-hop dedup-budget truncation: each hop's compacted frontier is a
  STATIC ``s_i * (1 + k_i)`` budget (the ``layer_shapes`` capacity the
  split path uses) — duplicates collapse, never truncate, so the
  budgets are exact, not lossy.
- ``row_cap`` truncation is inherited from ``sample_kernel``: rows with
  degree > row_cap sample uniformly from their first row_cap neighbors.
- with ``rng="hash"`` the kernel is bit-identical, under interpret mode,
  to the two-program oracle (``sample_layer_pallas`` with the same rng
  + ``quant.gather_rows``) — ``fused_hot_hop_reference`` below IS that
  oracle. "tpu" rng swaps in the on-core generator (TPU-only).
- ``feature_order`` (old id -> storage row) is translated in-kernel via
  one serial 128-entry-row DMA per frontier slot — correct, but a known
  cost cliff; all-hot identity-order stores skip it.
- a quantized (int8) table has no compiled form: Mosaic cannot DMA one
  row out of four packed to a sublane, so ``interpret=False`` raises.

Compiles for the v5e at products sizes (``tests/test_chip_compile.py``)
and runs there (``chip_smoke.py``); interpreted, with the "hash"
generator, everywhere else (``_dma.default_interpret``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import quant
from . import _dma
from ._dma import ALIGN, make_rand_bits, pad_feature_dim, split_start
from .sample_kernel import (BLOCK, _extract_picks, _fy_positions, _stage_rows,
                            _window_scratch)
from .sample_kernel import sample_layer_pallas

# feature-row DMA pipeline depth (the gather kernel's scheme)
_N_BUF = 4

# re-exported so callers configure the fused path without reaching into
# _dma (shared spelling lives there)
default_rng = _dma.default_rng
default_interpret = _dma.default_interpret
pad_indices = _dma.pad_indices


def _make_fused_kernel(*, k, row_cap, rng, n_nodes, n_order=0, tier_n=1,
                       hot_rows=0, dim=0, out_dt=None, quantized=False,
                       has_forder=False, with_gather=True):
    n_win = _dma.win_rows(row_cap)
    n_rows = BLOCK * (1 + k)        # seeds first, then flattened picks

    def kernel(*refs):
        it = iter(refs)
        seeds_blk = next(it)
        seed_ref = next(it)
        indptr_hbm = next(it)
        indices_hbm = next(it)
        if with_gather:
            data_hbm = next(it)
            scale_hbm = next(it) if quantized else None
            zero_hbm = next(it) if quantized else None
            forder_hbm = next(it) if has_forder else None
        nbrs_ref = next(it)
        cnt_ref = next(it)
        if with_gather:
            seed_rows_ref = next(it)
            pick_rows_ref = next(it)
        ptr_smem = next(it)
        ptr_sems = next(it)
        stage_vmem = next(it)
        row_sems = next(it)
        rows_vmem = next(it)
        if with_gather:
            picks_smem = next(it)
            pick_sem = next(it)
            code_vmem = next(it)
            feat_sems = next(it)
            if quantized:
                scale_smem = next(it)
                zero_smem = next(it)
                scale_sems = next(it)
                zero_sems = next(it)
            if has_forder:
                tid_smem = next(it)
                trow_smem = next(it)
                tid_sem = next(it)

        blk = pl.program_id(0)
        rand_bits = make_rand_bits(rng, seed_ref[0], blk)

        def seed_at(i):
            return seeds_blk[0, 0, i]

        # ---- phase A: sample (degrees/starts resolved IN-KERNEL) ----
        # indptr[s] and indptr[s+1] each arrive as the 128-entry row of
        # ``indptr_hbm`` that holds them (a pair can straddle two rows)
        def ptr_copies(i):
            s = jnp.clip(seed_at(i), 0, n_nodes - 1)
            return s, [pltpu.make_async_copy(
                indptr_hbm.at[(s + j) // ALIGN], ptr_smem.at[i, j],
                ptr_sems.at[i, j]) for j in (0, 1)]

        def ptr_start(i, _):
            for c in ptr_copies(i)[1]:
                c.start()
            return 0

        jax.lax.fori_loop(0, BLOCK, ptr_start, 0)

        def row_copy(i, start):
            return pltpu.make_async_copy(
                indices_hbm.at[pl.ds(split_start(start)[0], n_win)],
                stage_vmem.at[i], row_sems.at[i])

        def start_of(i):
            # same semantics as the split wrapper: invalid seeds read
            # degree 0 at start 0
            s = jnp.clip(seed_at(i), 0, n_nodes - 1)
            return jnp.where(seed_at(i) >= 0,
                             ptr_smem[i, 0, s % ALIGN], 0)

        b_iota = jax.lax.broadcasted_iota(jnp.int32, (BLOCK, 1), 0)

        def row_start(i, carry):
            degv, offv = carry
            s, copies = ptr_copies(i)
            for c in copies:
                c.wait()
            start = start_of(i)
            deg = jnp.where(seed_at(i) >= 0,
                            ptr_smem[i, 1, (s + 1) % ALIGN] - start, 0)
            row_copy(i, start).start()
            onehot = b_iota == i
            return (jnp.where(onehot, deg, degv),
                    jnp.where(onehot, split_start(start)[1], offv))

        degs, offs = jax.lax.fori_loop(                   # [BLOCK, 1]
            0, BLOCK, row_start,
            (jnp.zeros((BLOCK, 1), jnp.int32),
             jnp.zeros((BLOCK, 1), jnp.int32)))

        pos = _fy_positions(degs, k, row_cap, rand_bits)  # [BLOCK, k]

        def row_wait(i, _):
            row_copy(i, start_of(i)).wait()
            return 0

        jax.lax.fori_loop(0, BLOCK, row_wait, 0)

        _stage_rows(stage_vmem, rows_vmem)
        counts = jnp.minimum(degs, k).astype(jnp.int32)
        nbrs_ref[...] = _extract_picks(rows_vmem[...], pos, offs, counts, k)
        cnt_ref[...] = counts

        if not with_gather:     # sampling-only variant stops here
            return

        # ---- phase B: gather (frontier ids never leave the core) ----
        # picks to SMEM once — the scalar-addressable space the DMA
        # engine can take row addresses from
        cp = pltpu.make_async_copy(nbrs_ref, picks_smem, pick_sem)
        cp.start()
        cp.wait()

        def raw_id(i):
            i = jnp.asarray(i, jnp.int32)
            is_seed = i < BLOCK
            si = jnp.where(is_seed, i, 0)
            pi = jnp.where(is_seed, 0, i - BLOCK)
            prow = pi // k
            pcol = pi - prow * k
            return jnp.where(is_seed, seed_at(si),
                             picks_smem[prow, pcol])

        if has_forder:
            # old id -> storage row: the 128-entry row of ``forder_hbm``
            # holding it, one serial DMA per frontier slot (documented
            # cost cliff; identity-order stores skip this)
            def translate(i, _):
                safe = jnp.clip(raw_id(i), 0, n_order - 1)
                t = pltpu.make_async_copy(
                    forder_hbm.at[safe // ALIGN], trow_smem, tid_sem)
                t.start()
                t.wait()
                tid_smem[i] = trow_smem[safe % ALIGN]
                return 0

            jax.lax.fori_loop(0, n_rows, translate, 0)

        def srow_valid(i):
            rid = raw_id(i)
            if has_forder:
                tid = tid_smem[jnp.asarray(i, jnp.int32)]
                return (jnp.clip(tid, 0, tier_n - 1),
                        (rid >= 0) & (tid < hot_rows))
            return jnp.clip(rid, 0, tier_n - 1), rid >= 0

        def feat_copies(slot, i):
            srow = srow_valid(i)[0]
            cps = [pltpu.make_async_copy(
                data_hbm.at[srow], code_vmem.at[slot],
                feat_sems.at[slot])]
            if quantized:
                cps.append(pltpu.make_async_copy(
                    scale_hbm.at[srow], scale_smem.at[slot],
                    scale_sems.at[slot]))
                cps.append(pltpu.make_async_copy(
                    zero_hbm.at[srow], zero_smem.at[slot],
                    zero_sems.at[slot]))
            return cps

        for w in range(_N_BUF - 1):                       # warm up
            for c in feat_copies(w, w):
                c.start()

        def gather_body(i, _):
            slot = jax.lax.rem(i, _N_BUF)
            next_i = i + (_N_BUF - 1)

            @pl.when(next_i < n_rows)
            def _():
                for c in feat_copies(jax.lax.rem(next_i, _N_BUF),
                                     next_i):
                    c.start()

            for c in feat_copies(slot, i):
                c.wait()
            # multiply-mask (NOT select): bit-parity with the oracle's
            # ``rows * (ids >= 0)`` including -0.0
            maskv = srow_valid(i)[1].astype(out_dt)
            code = code_vmem[slot]                        # [dim]
            if quantized:
                prod = code.astype(out_dt) * scale_smem[slot, 0]
                z = zero_smem[slot, 0]

                # two-step store: materializing the product forces the
                # oracle's mul-then-add rounding — the single-expression
                # form contracts to a one-rounding FMA under the CPU
                # backend and drifts 1 ulp from quant.gather_rows
                def dequant_into(ref, j):
                    ref[j, :] = prod
                    ref[j, :] = (ref[j, :] + z) * maskv

                @pl.when(i < BLOCK)
                def _():
                    dequant_into(seed_rows_ref, i)

                @pl.when(i >= BLOCK)
                def _():
                    dequant_into(pick_rows_ref, i - BLOCK)
            else:
                x = code * maskv

                @pl.when(i < BLOCK)
                def _():
                    seed_rows_ref[i, :] = x

                @pl.when(i >= BLOCK)
                def _():
                    pick_rows_ref[i - BLOCK, :] = x

            return 0

        jax.lax.fori_loop(0, n_rows, gather_body, 0)

    return kernel


@functools.partial(
    jax.jit,
    static_argnames=("k", "row_cap", "rng", "interpret", "hot_rows"))
def _fused_hot_hop(indptr, indices_padded, seeds, feat, k, seed,
                   row_cap, rng, interpret, feature_order, hot_rows):
    n_nodes = indptr.shape[0] - 1
    bs = seeds.shape[0]
    pad = (-bs) % BLOCK
    if pad:
        seeds = jnp.concatenate(
            [seeds, jnp.full((pad,), -1, seeds.dtype)])
    padded_bs = seeds.shape[0]
    grid = padded_bs // BLOCK
    n_rows = BLOCK * (1 + k)

    data, scale, zero = quant.tier_parts(feat)
    quantized = scale is not None
    if quantized and not interpret:
        # asked of the v5e's compiler (PR 23): an int8 table in HBM is
        # tiled (8,128)(4,1), four rows to a sublane, and Mosaic refuses
        # the per-row DMA — "Slice shape along dimension 0 must be
        # aligned to tiling (8), but is 1". No fallback: say so.
        raise NotImplementedError(
            "fused_hot_hop cannot compile its per-row DMA over a "
            f"{data.dtype} table (Mosaic: 'Slice shape along dimension 0 "
            "must be aligned to tiling (8), but is 1'); use a float32 "
            "hot tier with fused_hot_hop, or the split path")
    out_dt = quant.tier_dtype(feat)
    tier_n = quant.tier_rows(feat)
    out_dim = data.shape[1]
    data = pad_feature_dim(data, "fused_hot_hop")
    dim = data.shape[1]
    has_forder = feature_order is not None
    n_order = feature_order.shape[0] if has_forder else 0
    hot = tier_n if hot_rows is None else hot_rows

    kernel = _make_fused_kernel(
        k=k, row_cap=row_cap, rng=rng, n_nodes=n_nodes, n_order=n_order,
        tier_n=tier_n, hot_rows=hot, dim=dim, out_dt=out_dt,
        quantized=quantized, has_forder=has_forder)

    in_specs = [
        pl.BlockSpec((1, 1, BLOCK), lambda b: (b, 0, 0),
                     memory_space=pltpu.SMEM),
        pl.BlockSpec(memory_space=pltpu.SMEM),
        pl.BlockSpec(memory_space=pl.ANY),
        pl.BlockSpec(memory_space=pl.ANY),
        pl.BlockSpec(memory_space=pl.ANY),
    ]
    operands = [seeds.astype(jnp.int32).reshape(grid, 1, BLOCK),
                jnp.asarray(seed, jnp.int32).reshape(1),
                _dma.as_rows(indptr.astype(jnp.int32)),
                indices_padded,
                data]
    if quantized:
        in_specs += [pl.BlockSpec(memory_space=pl.ANY)] * 2
        operands += [scale, zero]
    if has_forder:
        in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
        operands.append(_dma.as_rows(feature_order.astype(jnp.int32)))

    scratch = [
        pltpu.SMEM((BLOCK, 2, ALIGN), jnp.int32),  # indptr pair rows
        pltpu.SemaphoreType.DMA((BLOCK, 2)),
        *_window_scratch(row_cap, indices_padded.dtype),
        pltpu.SMEM((BLOCK, k), jnp.int32),        # picks, on-core
        pltpu.SemaphoreType.DMA,
        pltpu.VMEM((_N_BUF, dim), data.dtype),    # feature-row pipeline
        pltpu.SemaphoreType.DMA((_N_BUF,)),
    ]
    if quantized:
        scratch += [
            pltpu.SMEM((_N_BUF, 1), out_dt),
            pltpu.SMEM((_N_BUF, 1), out_dt),
            pltpu.SemaphoreType.DMA((_N_BUF,)),
            pltpu.SemaphoreType.DMA((_N_BUF,)),
        ]
    if has_forder:
        scratch += [
            pltpu.SMEM((n_rows,), jnp.int32),
            pltpu.SMEM((ALIGN,), jnp.int32),
            pltpu.SemaphoreType.DMA,
        ]

    # exact traffic model for the analysis plane (costmodel prices
    # pallas_call from this estimate when present): per block — the
    # indptr pairs, the staged CSR windows, one tier row (codes +
    # sidecars) per seed/pick, the order translation, and the outputs.
    idx_item = jnp.dtype(indices_padded.dtype).itemsize
    out_item = jnp.dtype(out_dt).itemsize
    bytes_accessed = grid * (
        BLOCK * 4                                  # seeds (SMEM block)
        + BLOCK * 2 * ALIGN * 4                    # indptr pair rows
        + BLOCK * _dma.win(row_cap) * idx_item     # CSR staging windows
        + n_rows * quant.row_read_bytes(feat)      # tier rows
        + (n_rows * ALIGN * 4 if has_forder else 0)  # order translation
        + BLOCK * (k + 1) * 4                      # nbrs + counts out
        + n_rows * dim * out_item)                 # feature rows out
    flops = 2 * grid * n_rows * dim if quantized else 0

    nbrs, cnt, seed_rows, pick_rows = pl.pallas_call(
        kernel,
        grid=(grid,),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((BLOCK, k), lambda b: (b, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((BLOCK, 1), lambda b: (b, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((BLOCK, dim), lambda b: (b, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((BLOCK * k, dim), lambda b: (b, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((padded_bs, k), jnp.int32),
            jax.ShapeDtypeStruct((padded_bs, 1), jnp.int32),
            jax.ShapeDtypeStruct((padded_bs, dim), out_dt),
            jax.ShapeDtypeStruct((padded_bs * k, dim), out_dt),
        ],
        scratch_shapes=scratch,
        interpret=interpret,
        cost_estimate=pl.CostEstimate(
            flops=flops, transcendentals=0,
            bytes_accessed=int(bytes_accessed)),
        compiler_params=pltpu.CompilerParams(has_side_effects=True),
    )(*operands)
    return (nbrs[:bs], cnt[:bs, 0],
            seed_rows[:bs, :out_dim], pick_rows[:bs * k, :out_dim])


def fused_hot_hop(indptr, indices_padded, seeds, feat, k, seed,
                  row_cap: int = 2048, rng: str | None = None,
                  interpret: bool | None = None,
                  feature_order=None, hot_rows: int | None = None):
    """One fused hop: sample ``k`` neighbors per seed AND gather the
    hot-tier feature rows of seeds + picks in a single Pallas kernel.

    Returns ``(nbrs [bs,k], counts [bs], seed_rows [bs,d],
    pick_rows [bs*k,d])`` with ``pick_rows`` flattened row-major over
    ``nbrs`` and invalid (-1 / cold-tier) rows zero-masked.

    ``feat`` is a plain array or :class:`quant.QuantizedTensor` (the
    dequant FMA runs in-register); ``feature_order`` an optional
    old-id -> storage-row map with ``hot_rows`` bounding the hot tier.
    ``rng`` / ``interpret`` default per backend (``_dma``): the kernel
    runs interpreted with the portable "hash" PRNG off-TPU.
    """
    if rng is None:
        rng = default_rng()
    if interpret is None:
        interpret = default_interpret()
    return _fused_hot_hop(indptr, indices_padded, seeds, feat, k, seed,
                          row_cap, rng, interpret, feature_order,
                          hot_rows)


@functools.partial(
    jax.jit, static_argnames=("k", "row_cap", "rng", "interpret"))
def _fused_sample_hop(indptr, indices_padded, seeds, k, seed,
                      row_cap, rng, interpret):
    """Sampling-only variant of the fused kernel (phase A alone): the
    ``indptr`` pairs are still resolved IN-KERNEL, so unlike the
    ``sample_layer_pallas`` wrapper (whose XLA-side ``indptr[safe]`` /
    ``indptr[safe+1]`` reads are gathers the cost model prices) an
    interior hop contributes zero ``gather_index_bytes``."""
    n_nodes = indptr.shape[0] - 1
    bs = seeds.shape[0]
    pad = (-bs) % BLOCK
    if pad:
        seeds = jnp.concatenate(
            [seeds, jnp.full((pad,), -1, seeds.dtype)])
    padded_bs = seeds.shape[0]
    grid = padded_bs // BLOCK

    kernel = _make_fused_kernel(
        k=k, row_cap=row_cap, rng=rng, n_nodes=n_nodes,
        with_gather=False)

    in_specs = [
        pl.BlockSpec((1, 1, BLOCK), lambda b: (b, 0, 0),
                     memory_space=pltpu.SMEM),
        pl.BlockSpec(memory_space=pltpu.SMEM),
        pl.BlockSpec(memory_space=pl.ANY),
        pl.BlockSpec(memory_space=pl.ANY),
    ]
    operands = [seeds.astype(jnp.int32).reshape(grid, 1, BLOCK),
                jnp.asarray(seed, jnp.int32).reshape(1),
                _dma.as_rows(indptr.astype(jnp.int32)),
                indices_padded]
    scratch = [
        pltpu.SMEM((BLOCK, 2, ALIGN), jnp.int32),  # indptr pair rows
        pltpu.SemaphoreType.DMA((BLOCK, 2)),
        *_window_scratch(row_cap, indices_padded.dtype),
    ]
    idx_item = jnp.dtype(indices_padded.dtype).itemsize
    bytes_accessed = grid * (
        BLOCK * 4                                  # seeds (SMEM block)
        + BLOCK * 2 * ALIGN * 4                    # indptr pair rows
        + BLOCK * _dma.win(row_cap) * idx_item     # CSR staging windows
        + BLOCK * (k + 1) * 4)                     # nbrs + counts out

    nbrs, cnt = pl.pallas_call(
        kernel,
        grid=(grid,),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((BLOCK, k), lambda b: (b, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((BLOCK, 1), lambda b: (b, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((padded_bs, k), jnp.int32),
            jax.ShapeDtypeStruct((padded_bs, 1), jnp.int32),
        ],
        scratch_shapes=scratch,
        interpret=interpret,
        cost_estimate=pl.CostEstimate(
            flops=0, transcendentals=0,
            bytes_accessed=int(bytes_accessed)),
        compiler_params=pltpu.CompilerParams(has_side_effects=True),
    )(*operands)
    return nbrs[:bs], cnt[:bs, 0]


def fused_sample_hop(indptr, indices_padded, seeds, k, seed,
                     row_cap: int = 2048, rng: str | None = None,
                     interpret: bool | None = None):
    """One gather-free fused hop: phase A of the fused kernel — in-kernel
    ``indptr`` resolution, CSR window staging, Fisher-Yates picks —
    without the feature pipeline. Bit-identical picks to
    ``sample_layer_pallas`` with the same rng/seed; zero
    ``gather_index_bytes`` (the split wrapper's XLA indptr reads are
    gathers, this one's are kernel DMAs)."""
    if rng is None:
        rng = default_rng()
    if interpret is None:
        interpret = default_interpret()
    return _fused_sample_hop(indptr, indices_padded, seeds, k, seed,
                             row_cap, rng, interpret)


def _hop_seed(key, i):
    """Per-hop kernel-PRNG seed. Hop 0 reduces exactly to the single-hop
    builders' ``fold_in(key, 0)`` derivation, so a 1-element ``sizes``
    ladder is bit-identical to the qt-fuse path."""
    info = jnp.iinfo(jnp.int32)
    return jax.random.randint(jax.random.fold_in(key, i), (),
                              info.min, info.max, jnp.int32)


@functools.partial(jax.jit, static_argnames=("sizes", "row_cap", "rng",
                                             "interpret"))
def _sample_multihop_impl(indptr, indices_padded, seeds, key, *, sizes,
                          row_cap, rng, interpret):
    from ..sample import compact_layer
    cur = seeds.astype(jnp.int32)
    layers = []
    for i, k in enumerate(sizes):
        with jax.named_scope(f"qt_fused_hop{i}"):
            nbrs, _ = _fused_sample_hop(
                indptr, indices_padded, cur, int(k), _hop_seed(key, i),
                row_cap, rng, interpret)
            layers.append(compact_layer(cur, nbrs, seeds_dense=True))
        cur = layers[-1].n_id
    return cur, layers


def fused_sample_multihop(indptr, indices_padded, seeds, sizes, key,
                          row_cap: int = 2048, rng: str | None = None,
                          interpret: bool | None = None):
    """Walk the whole fanout ladder with the sampling-only fused kernel:
    every hop's degrees/starts resolve in-kernel, the sort-based
    (gather-free) ``compact_layer`` dedups each picked frontier into the
    next hop's static seed budget. Drop-in for ``sample_multihop`` on
    exact-method ladders when the caller does its own feature lookup
    (the sharded serve step's ``dist_lookup_local`` leg) — returns
    ``(n_id, layers)`` with the identical static ``layer_shapes``
    budgets. ``seeds`` must be dense (distinct valid ids, -1 tail only);
    compaction keeps every hop's output dense. The whole walk — kernels
    AND inter-hop compaction — is one jitted program: standalone callers
    pay one dispatch, not one per hop."""
    if not sizes:
        raise ValueError("sizes must name at least one hop")
    if rng is None:
        rng = default_rng()
    if interpret is None:
        interpret = default_interpret()
    return _sample_multihop_impl(
        indptr, indices_padded, seeds, key,
        sizes=tuple(int(k) for k in sizes), row_cap=int(row_cap),
        rng=rng, interpret=interpret)


def fused_multihop(indptr, indices_padded, seeds, feat, sizes, key,
                   row_cap: int = 2048, rng: str | None = None,
                   interpret: bool | None = None,
                   feature_order=None, hot_rows: int | None = None):
    """The full fused frontier walk: interior hops run the sampling-only
    kernel (``fused_sample_hop`` — in-kernel indptr, no XLA gather), the
    LEAF hop runs the sample+gather kernel, and the gather-free
    ``compact_layer`` dedups between hops. Because each compacted
    frontier keeps its predecessor as the slot-[0, v) prefix, the leaf
    hop's seeds are the entire interior — its in-kernel gather over
    (seeds + picks) covers every frontier node, and the two-scatter
    reassembly below yields the final ``[cap, dim]`` block with no HBM
    id round trip anywhere: ``gather_index_bytes == 0`` across ALL hops.

    Returns ``(n_id, layers, x)`` — the same triple shape the split
    ``sample_multihop`` + ``masked_feature_gather`` pair produces, with
    ``x`` bit-identical on valid slots (never-scattered padding slots
    are +0.0 vs the oracle's multiply-masked signed zero — the
    documented single-hop wobble; losses/logits still pin bit-equal).
    ``seeds`` must be dense (distinct valid ids, -1 tail only). Per-hop
    kernel seeds derive from ``fold_in(key, i)``; a 1-hop ladder is
    bit-identical to the qt-fuse single-hop path. Like the sampling-only
    walk, the whole ladder compiles to ONE program — hops, compaction
    and the two-scatter reassembly dispatch together."""
    if not sizes:
        raise ValueError("sizes must name at least one hop")
    if rng is None:
        rng = default_rng()
    if interpret is None:
        interpret = default_interpret()
    return _multihop_impl(
        indptr, indices_padded, seeds, feat, key, feature_order,
        sizes=tuple(int(k) for k in sizes), row_cap=int(row_cap),
        rng=rng, interpret=interpret,
        hot_rows=None if hot_rows is None else int(hot_rows))


@functools.partial(jax.jit, static_argnames=("sizes", "row_cap", "rng",
                                             "interpret", "hot_rows"))
def _multihop_impl(indptr, indices_padded, seeds, feat, key,
                   feature_order, *, sizes, row_cap, rng, interpret,
                   hot_rows):
    from ..sample import compact_layer
    cur = seeds.astype(jnp.int32)
    layers = []
    last = len(sizes) - 1
    for i, k in enumerate(sizes):
        with jax.named_scope(f"qt_fused_hop{i}"):
            if i < last:
                nbrs, _ = _fused_sample_hop(
                    indptr, indices_padded, cur, int(k),
                    _hop_seed(key, i), row_cap, rng, interpret)
            else:
                leaf_seeds = cur
                nbrs, _, seed_rows, pick_rows = _fused_hot_hop(
                    indptr, indices_padded, cur, feat, int(k),
                    _hop_seed(key, i), row_cap, rng, interpret,
                    feature_order, hot_rows)
            layers.append(compact_layer(cur, nbrs, seeds_dense=True))
        cur = layers[-1].n_id
    leaf = layers[-1]
    s = leaf_seeds.shape[0]
    cap = leaf.n_id.shape[0]
    x = jnp.zeros((cap, seed_rows.shape[1]), seed_rows.dtype)
    # valid leaf seed i owns slot i (dense invariant kept by every
    # compaction); each valid pick's col is its compacted slot.
    # Duplicates carry identical bits so the scatter is
    # order-independent; -1s route to the dropped slot ``cap``.
    x = x.at[jnp.where(leaf_seeds >= 0, jnp.arange(s), cap)].set(
        seed_rows, mode="drop")
    x = x.at[jnp.where(leaf.col >= 0, leaf.col, cap)].set(
        pick_rows, mode="drop")
    return leaf.n_id, layers, x


def _oracle_rows(feat, ids, feature_order, hot_rows):
    """The jnp reference lookup the fused gather must match bit-for-bit:
    ``feature_order`` translation, hot-tier bounds check, and the
    multiply-mask that zeroes invalid/cold rows."""
    tier_n = quant.tier_rows(feat)
    if feature_order is not None:
        t = feature_order[jnp.clip(ids, 0,
                                   feature_order.shape[0] - 1)]
        hot = tier_n if hot_rows is None else hot_rows
        valid = (ids >= 0) & (t < hot)
        safe = jnp.clip(t, 0, tier_n - 1)
    else:
        valid = ids >= 0
        safe = jnp.clip(ids, 0, tier_n - 1)
    x = quant.gather_rows(feat, safe)
    return x * valid.astype(x.dtype)[:, None]


def fused_hot_hop_reference(indptr, indices_padded, seeds, feat, k,
                            seed, row_cap: int = 2048,
                            rng: str = "hash",
                            interpret: bool | None = None,
                            feature_order=None,
                            hot_rows: int | None = None):
    """The split two-program oracle: ``sample_layer_pallas`` (same rng,
    frontier ids round-tripping through HBM) followed by the jnp
    ``quant.gather_rows`` path. With ``rng="hash"`` the fused kernel is
    bit-identical to this under interpret mode — the acceptance gate."""
    if interpret is None:
        interpret = default_interpret()
    nbrs, counts = sample_layer_pallas(
        indptr, indices_padded, seeds, k, seed, row_cap=row_cap,
        rng=rng, interpret=interpret)
    return (nbrs, counts,
            _oracle_rows(feat, seeds, feature_order, hot_rows),
            _oracle_rows(feat, nbrs.reshape(-1).astype(jnp.int32),
                         feature_order, hot_rows))


def fused_multihop_reference(indptr, indices_padded, seeds, feat, sizes,
                             key, row_cap: int = 2048,
                             rng: str = "hash",
                             interpret: bool | None = None,
                             feature_order=None,
                             hot_rows: int | None = None):
    """The split multi-hop oracle: per-hop ``sample_layer_pallas`` (same
    rng and ``fold_in(key, i)`` seeds, frontier ids round-tripping
    through HBM every hop) + ``compact_layer`` + one jnp gather over the
    final frontier. With ``rng="hash"`` under interpret mode,
    ``fused_multihop`` matches this bit-for-bit on ``n_id``, the layer
    COOs, and every valid row of ``x`` — the multi-hop acceptance
    gate."""
    if not sizes:
        raise ValueError("sizes must name at least one hop")
    if interpret is None:
        interpret = default_interpret()
    from ..sample import compact_layer
    cur = seeds.astype(jnp.int32)
    layers = []
    for i, k in enumerate(sizes):
        nbrs, _ = sample_layer_pallas(
            indptr, indices_padded, cur, int(k), _hop_seed(key, i),
            row_cap=row_cap, rng=rng, interpret=interpret)
        layers.append(compact_layer(cur, nbrs, seeds_dense=True))
        cur = layers[-1].n_id
    x = _oracle_rows(feat, cur, feature_order, hot_rows)
    return cur, layers, x
