"""Pallas TPU neighbor-sampling kernel.

The hot-path equivalent of the reference's warp-per-row reservoir kernel
``CSRRowWiseSampleKernel`` (cuda_random.cu.hpp:7-69). Design, TPU-first:

- grid over blocks of 128 seeds; each block DMAs its seeds' neighbor
  windows (``row_cap`` entries and the row they start in) from the CSR
  ``indices`` array in HBM into a VMEM staging buffer (the TPU analogue
  of the reference's UVA streaming reads), then lays them out one seed
  per sublane.
- selection is a *vectorized* partial Fisher-Yates over the whole block
  ([BLOCK, k] lanes in the VPU) using a pluggable PRNG — same
  distribution as the jnp oracle, no atomics, no serial per-row loops.
- the chosen positions are materialized with an iota-compare reduction
  over the staged rows (VPU), avoiding unsupported dynamic VMEM gathers.

Contract matches ``ops.sample.sample_layer``: (nbrs [bs,k] -1-filled,
counts = min(deg, k)). Rows with degree > ``row_cap`` sample uniformly
from their first ``row_cap`` neighbors (documented truncation; CSR
neighbor order is arbitrary, and row_cap=2048 covers the >99.9th degree
percentile of the target graphs).

``indices`` reaches the kernel as ``[rows, 128]`` with a window of
trailing sentinel entries (``pad_indices``), so fixed-size window DMAs
can start at any row and never read out of bounds.

The layout rules the chip's compiler imposes (CSR arrays as 128-lane
rows, the staging-window width, the residual shifting the position
compare, whole-tile DMA destinations) live in ``_dma`` — shared with
the gather and fused kernels so each constraint has exactly one
spelling. Inside the kernel every per-seed vector is ``[BLOCK, 1]``,
seeds along sublanes: Mosaic's layout pass aborts on rank-1 vector math.

``rng`` selects the draw backend (``_dma.make_rand_bits``): "tpu" is
the on-core generator (TPU-only on this jax — no CPU interpret
lowering), "hash" a pure-jnp counter hash that interprets everywhere
and draws identical streams across kernels seeded alike (what the
fused kernel's bit-equivalence oracle runs on). ``rng`` / ``interpret``
default from the backend (``_dma.default_rng`` / ``default_interpret``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import _dma
from ._dma import make_rand_bits, split_start

BLOCK = 128

# re-exported API (shared spelling lives in _dma)
ALIGN = _dma.ALIGN
pad_indices = _dma.pad_indices


def _fy_positions(degs: jax.Array, k: int, row_cap: int, rand_bits):
    """Vectorized partial Fisher-Yates inside the kernel: positions
    [BLOCK, k] without replacement in [0, min(deg, row_cap)).
    ``degs`` is ``[BLOCK, 1]`` and every intermediate stays rank 2 with
    seeds along sublanes. ``rand_bits(bs) -> uint32[bs, 1]`` is the
    injected draw op (one call per step, so backends with a call counter
    stay reproducible)."""
    bs = degs.shape[0]
    pool = jnp.minimum(degs, row_cap)                     # candidate pool
    pos_log = jnp.full((bs, k), -1, jnp.int32)
    val_log = jnp.zeros((bs, k), jnp.int32)
    out = jnp.zeros((bs, k), jnp.int32)
    steps = jax.lax.broadcasted_iota(jnp.int32, (1, k), 1)  # [1, k]

    def lookup(pos_log, val_log, x):
        match = pos_log == x
        last = jnp.max(jnp.where(match, steps, -1), axis=1, keepdims=True)
        # take_along_axis-free: select the logged value at step `last`
        onehot = (steps == last) & (last >= 0)
        logged = jnp.sum(jnp.where(onehot, val_log, 0), axis=1,
                         keepdims=True)
        return jnp.where(last >= 0, logged, x)

    for i in range(k):
        rbits = rand_bits(bs)
        span = jnp.maximum(pool - i, 1).astype(jnp.uint32)
        j = (i + (rbits % span)).astype(jnp.int32)
        a_j = lookup(pos_log, val_log, j)
        a_i = lookup(pos_log, val_log, jnp.full((bs, 1), i, jnp.int32))
        onehot_i = steps == i
        out = jnp.where(onehot_i, a_j, out)
        pos_log = jnp.where(onehot_i, j, pos_log)
        val_log = jnp.where(onehot_i, a_i, val_log)
    return out                                            # [bs, k]


def _stage_rows(stage_ref, rows_ref):
    """Copy the DMA staging buffer ``[BLOCK, win_rows, ALIGN]`` into
    ``rows_ref [BLOCK, win]``, one seed per sublane: a DMA can only fill
    whole tiles (``_dma``), the position compare wants a seed's window
    along the lanes of its own row."""
    n_seeds, n_win, _ = stage_ref.shape

    # eight seeds at a time: a store to a traced row must cover whole
    # (8, 128) tiles
    def body(g, _):
        g8 = pl.ds(pl.multiple_of(g * 8, 8), 8)
        for r in range(n_win):
            rows_ref[g8, r * ALIGN:(r + 1) * ALIGN] = stage_ref[g8, r, :]
        return 0

    jax.lax.fori_loop(0, n_seeds // 8, body, 0)


def _extract_picks(rows, pos, offs, counts, k: int):
    """The entries of ``rows [BLOCK, win]`` at window positions
    ``pos + offs``, as ``[BLOCK, k]`` with -1 past ``counts``: an
    iota-compare reduction, because Mosaic has no dynamic VMEM gather."""
    w_iota = jax.lax.broadcasted_iota(jnp.int32, rows.shape, 1)
    steps = jax.lax.broadcasted_iota(jnp.int32, (1, k), 1)
    shifted = pos + offs                                  # window coords
    picks = jnp.full(pos.shape, -1, jnp.int32)
    for i in range(k):
        at_i = jnp.sum(jnp.where(steps == i, shifted, 0), axis=1,
                       keepdims=True)
        sel = jnp.sum(jnp.where(w_iota == at_i, rows, 0), axis=1,
                      keepdims=True)
        picks = jnp.where((steps == i) & (i < counts),
                          sel.astype(jnp.int32), picks)
    return picks


def _make_kernel(k: int, row_cap: int, rng: str):
    n_win = _dma.win_rows(row_cap)

    def kernel(rows_smem, meta_ref, seed_ref, indices_hbm,
               out_ref, cnt_ref, stage_vmem, sems, rows_vmem):
        blk = pl.program_id(0)
        rand_bits = make_rand_bits(rng, seed_ref[0], blk)

        # stage BLOCK neighbor windows HBM -> VMEM; rows_smem carries the
        # row of ``indices_hbm`` each seed's first neighbor sits in
        def row_copy(i):
            return pltpu.make_async_copy(
                indices_hbm.at[pl.ds(rows_smem[0, 0, i], n_win)],
                stage_vmem.at[i], sems.at[i])

        def start_dma(i, _):
            row_copy(i).start()
            return 0

        jax.lax.fori_loop(0, BLOCK, start_dma, 0)

        degs = meta_ref[:, 0:1]                           # [BLOCK, 1]
        offs = meta_ref[:, 1:2]                           # [BLOCK, 1] < 128
        pos = _fy_positions(degs, k, row_cap, rand_bits)  # [BLOCK, k]

        def wait_dma(i, _):
            row_copy(i).wait()
            return 0

        jax.lax.fori_loop(0, BLOCK, wait_dma, 0)

        _stage_rows(stage_vmem, rows_vmem)
        counts = jnp.minimum(degs, k).astype(jnp.int32)
        out_ref[...] = _extract_picks(rows_vmem[...], pos, offs, counts, k)
        cnt_ref[...] = counts

    return kernel


def sample_layer_pallas(indptr: jax.Array, indices_padded: jax.Array,
                        seeds: jax.Array, k: int, seed,
                        row_cap: int = 2048,
                        rng: str | None = None,
                        interpret: bool | None = None):
    """Drop-in for ``ops.sample.sample_layer`` backed by the TPU kernel.

    ``indices_padded`` comes from ``pad_indices``; ``seed`` is a scalar
    int32 (derive from a jax PRNG key via ``jax.random.randint``).
    ``rng`` / ``interpret`` default per backend (``_dma``): compiled with
    the on-core generator on a TPU, interpreted with the portable "hash"
    generator (identical draw stream to the fused kernel's) elsewhere.
    """
    if rng is None:
        rng = _dma.default_rng()
    if interpret is None:
        interpret = _dma.default_interpret()
    return _sample_layer_pallas(indptr, indices_padded, seeds, k, seed,
                                row_cap, rng, interpret)


@functools.partial(jax.jit,
                   static_argnames=("k", "row_cap", "rng", "interpret"))
def _sample_layer_pallas(indptr, indices_padded, seeds, k, seed, row_cap,
                         rng, interpret):
    n = indptr.shape[0] - 1
    bs = seeds.shape[0]
    pad = (-bs) % BLOCK
    if pad:
        seeds = jnp.concatenate([seeds, jnp.full((pad,), -1, seeds.dtype)])
    padded_bs = seeds.shape[0]

    valid = seeds >= 0
    safe = jnp.clip(seeds, 0, max(n - 1, 0)).astype(indptr.dtype)
    starts = jnp.where(valid, indptr[safe], 0).astype(jnp.int32)
    degs = jnp.where(valid, (indptr[safe + 1] - indptr[safe]), 0) \
        .astype(jnp.int32)
    rows, offs = split_start(starts)

    grid = padded_bs // BLOCK
    out, cnt = pl.pallas_call(
        _make_kernel(k, row_cap, rng),
        grid=(grid,),
        in_specs=[
            pl.BlockSpec((1, 1, BLOCK), lambda b: (b, 0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((BLOCK, 2), lambda b: (b, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
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
        scratch_shapes=_window_scratch(row_cap, indices_padded.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(has_side_effects=True),
    )(rows.reshape(grid, 1, BLOCK),
      jnp.stack([degs, offs], axis=1),
      jnp.asarray(seed, jnp.int32).reshape(1),
      indices_padded)
    return out[:bs], cnt[:bs, 0]


def _window_scratch(row_cap: int, dtype):
    """DMA staging buffer, its semaphores, and the one-seed-per-sublane
    copy the position compare reads (``_stage_rows``)."""
    return [
        pltpu.VMEM((BLOCK, _dma.win_rows(row_cap), ALIGN), dtype),
        pltpu.SemaphoreType.DMA((BLOCK,)),
        pltpu.VMEM((BLOCK, _dma.win(row_cap)), dtype),
    ]
