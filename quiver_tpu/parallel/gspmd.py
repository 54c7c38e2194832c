"""GSPMD 2-D mesh training: data x model (tensor) parallelism.

Beyond reference parity (the reference's only model story is vanilla
DDP, survey §2.3): the fused train step runs under ``jax.jit`` over a
``(data, model)`` mesh with

- the batch (seeds/labels) sharded over ``data``,
- every 2-D dense kernel of the GNN column-sharded over ``model`` (its
  bias and the following activation column-sharded to match),
- graph topology and features replicated,

and XLA/GSPMD inserts the collectives (the per-layer ``all_gather`` of
the column-sharded activations feeding the next layer's row span, the
cross-``data`` gradient reduction). No shard_map, no hand-written
collectives: annotate shardings, let the partitioner work.

TP is profitable when hidden_dim is large (wide GNNs, e.g.
MAG240M-class 1024-wide configs); at hidden=256 it mostly demonstrates
capability. Numerics match the single-chip step up to reduction order
(tested in tests/test_gspmd.py). Shard-friendly dims: hidden/out dims
should be divisible by the ``model`` axis size.
"""

from __future__ import annotations

from typing import Callable, Sequence

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .frontier import Walk, documented, walk_doc
from .train import (TrainState, _apply_update, _fused_loss,
                    cross_entropy_logits)

# GSPMD cannot partition the fused Pallas kernels, and the step never took
# a gather of its own
_KNOBS = ("method", "indices_stride")


def _leaf_spec(leaf, model_axis: str) -> P:
    """Column-shard 2-D kernels over ``model_axis``; shard 1-D biases
    the same way so each lands with its kernel's output columns;
    replicate scalars/everything else."""
    ndim = getattr(leaf, "ndim", 0)
    if ndim == 2:
        return P(None, model_axis)
    if ndim == 1:
        return P(model_axis)
    return P()


def state_sharding(state: TrainState, mesh: Mesh,
                   model_axis: str = "model"):
    """TP placement for a TrainState: params AND optimizer moments get
    the same layout (adam's mu/nu mirror the param tree), step scalar
    replicated."""
    return jax.tree.map(
        lambda leaf: NamedSharding(mesh, _leaf_spec(leaf, model_axis)),
        state)


def shard_state(state: TrainState, mesh: Mesh,
                model_axis: str = "model") -> TrainState:
    """Place an (unsharded) TrainState onto the mesh with TP layout."""
    return jax.device_put(state, state_sharding(state, mesh, model_axis))


@documented(walk_doc(_KNOBS))
def build_gspmd_train_step(model, tx, sizes: Sequence[int], mesh: Mesh,
                           data_axis: str = "data",
                           model_axis: str = "model",
                           loss_fn: Callable = cross_entropy_logits,
                           **walk):
    """fn(state, feat, forder, indptr, indices, seeds, labels, key[,
    indices_rows]) -> (state, loss), with ``state`` placed by
    ``shard_state`` and seeds/labels of global batch length (any
    multiple of the ``data`` axis size) sharded over ``data_axis``;
    topology/features (and the ``indices_rows`` view) replicated. One
    jitted program; XLA partitions the sampler over the batch shards and
    the matmuls over the model shards."""
    walk = Walk.of("build_gspmd_train_step", _KNOBS, sizes, walk)
    cache = {}

    def step(state: TrainState, feat, forder, indptr, indices, seeds,
             labels, key, *rows):
        loss, grads = jax.value_and_grad(
            lambda p: _fused_loss(model, loss_fn, walk, seeds.shape[0], p,
                                  feat, forder, indptr, indices, seeds,
                                  labels, key, rows[0] if rows else None)
        )(state.params)
        return _apply_update(state, tx, grads), loss

    repl = NamedSharding(mesh, P())
    data = NamedSharding(mesh, P(data_axis))

    def sharded_step(state, feat, forder, indptr, indices, seeds, labels,
                     key, indices_rows=None):
        walk.check_rows(indices_rows)
        has_rows = indices_rows is not None   # windowed always; exact may
        fn = cache.get(has_rows)
        if fn is None:
            st_sh = state_sharding(state, mesh, model_axis)
            shardings = [st_sh, repl, repl, repl, repl, data, data, repl]
            if has_rows:
                shardings.append(repl)
            fn = jax.jit(
                step,
                in_shardings=tuple(shardings),
                out_shardings=(st_sh, repl))
            cache[has_rows] = fn
        extra = (indices_rows,) if has_rows else ()
        return fn(state, feat, forder, indptr, indices, seeds, labels,
                  key, *extra)

    return sharded_step
