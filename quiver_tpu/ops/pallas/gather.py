"""Pallas sparse row-gather kernel — the feature-collection hot op.

TPU-native equivalent of the reference's warp-per-row gather kernel
``quiver_tensor_gather`` (shard_tensor.cu.hpp:7-61, launched at max
occupancy from quiver_feature.cu:243-293): each requested row is DMA'd
from the feature array (resident in HBM) into the output block, with the
row id list scalar-prefetched so DMA addresses are known before the body
runs.

Pipelined ``_N_BUF`` deep: later rows' DMAs are in flight while row i
completes. There is no fallback: ``interpret=False`` (the default)
compiles for the TPU or raises; CPU tests pass ``interpret=True``;
``gather_rows_reference`` is the jnp oracle.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._dma import pad_feature_dim

# rows of the output processed by one grid step
_BLOCK_ROWS = 256
_N_BUF = 4


def _gather_kernel(ids_ref, feat_ref, out_ref, scratch, sems):
    """Grid dim 0 walks id blocks; each block DMAs its rows feat->out."""
    block = pl.program_id(0)
    base = block * _BLOCK_ROWS

    def get_dma(slot, i):
        row = ids_ref[base + i]
        return pltpu.make_async_copy(
            feat_ref.at[row], scratch.at[slot], sems.at[slot])

    # warm up the pipeline
    for w in range(_N_BUF - 1):
        get_dma(w, w).start()

    def body(i, _):
        slot = jax.lax.rem(i, _N_BUF)
        next_i = i + (_N_BUF - 1)

        @pl.when(next_i < _BLOCK_ROWS)
        def _():
            get_dma(jax.lax.rem(next_i, _N_BUF), next_i).start()

        get_dma(slot, i).wait()
        out_ref[i, :] = scratch[slot]
        return 0

    jax.lax.fori_loop(0, _BLOCK_ROWS, body, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def gather_rows(feat: jax.Array, ids: jax.Array,
                interpret: bool = False) -> jax.Array:
    """out[i] = feat[ids[i]] with ids in [0, N). ids length must be a
    multiple of the block size (pad with any valid id and slice after).

    Mosaic requires the per-row HBM DMA slice to be lane-aligned: the
    feature dim must be a multiple of 128. Other dims are zero-padded
    here — a full-table copy per call, so hot paths should store their
    table 128-padded up front and hit the fast branch."""
    b = ids.shape[0]
    out_dim = feat.shape[1]
    feat = pad_feature_dim(feat, "gather_rows")
    dim = feat.shape[1]
    if b % _BLOCK_ROWS:
        pad = _BLOCK_ROWS - b % _BLOCK_ROWS
        ids = jnp.concatenate([ids, jnp.zeros((pad,), ids.dtype)])
    padded = ids.shape[0]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(padded // _BLOCK_ROWS,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(
            (_BLOCK_ROWS, dim), lambda b, ids: (b, 0),
            memory_space=pltpu.VMEM),
        scratch_shapes=[
            pltpu.VMEM((_N_BUF, dim), feat.dtype),
            pltpu.SemaphoreType.DMA((_N_BUF,)),
        ],
    )
    out = pl.pallas_call(
        _gather_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((padded, dim), feat.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(has_side_effects=True),
    )(ids.astype(jnp.int32), feat)
    return out[:b, :out_dim]


def gather_rows_reference(feat: jax.Array, ids: jax.Array) -> jax.Array:
    """jnp oracle for the kernel."""
    return jnp.take(feat, ids, axis=0)
