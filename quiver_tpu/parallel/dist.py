"""Multi-host fused training: sample + distributed feature exchange +
forward/backward + update as ONE XLA program.

The TPU answer to the reference's multi-node training benchmark
(benchmarks/ogbn-papers100M/train_quiver_multi_node.py:270-411: per-rank
DDP processes, DistFeature lookups through the hand-scheduled NCCL
exchange, TCPStore bootstrap). Here every host's shard, inside a single
``shard_map`` over the ``host`` axis:

  1. samples its own seed shard's k-hop frontier (topology replicated),
  2. fetches the frontier's feature rows from whichever hosts own them —
     the fused dispatch + ``all_to_all`` exchange + scatter of
     ``comm.dist_lookup_local`` (features stay partitioned, nothing is
     ever all-gathered),
  3. runs forward/backward and ``pmean``s gradients.

One jit, zero host round trips, no bootstrap beyond
``jax.distributed.initialize``; the same program runs on the virtual
CPU mesh, a TPU slice (ICI), or multi-slice (DCN). The loss definition
is literally the shared ``_fused_loss`` with the feature gather swapped
for the partitioned exchange, so dist/DP loss parity holds exactly
(tests/test_dist_train.py).
"""

from __future__ import annotations

from typing import Callable, Sequence

import jax
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from ..comm import default_exchange_cap, dist_lookup_local
from ..pyg.sage_sampler import layer_shapes
from .train import (TrainState, _check_donatable, _check_rows,
                    _fused_loss, _metered_loss_fn, _pmean_update,
                    cross_entropy_logits, _COLLECT_DOC, _DONATED_DOC)


def build_dist_train_step(model, tx, sizes: Sequence[int],
                          per_host_batch: int, mesh: Mesh,
                          rows_per_host: int,
                          axis: str = "host",
                          loss_fn: Callable = cross_entropy_logits,
                          method: str = "exact",
                          indices_stride: int | None = None,
                          with_replicate: bool = False,
                          hub_frac: float | None = None,
                          donate: bool = True,
                          exchange_cap=None,
                          collect_metrics: bool = False,
                          merge_counters: bool = False):
    """fn(state, spmd_feat, g2h, g2l, indptr, indices, seeds, labels,
    key[, indices_rows][, is_rep, rep_rank, bases]) -> (state, loss).

    ``spmd_feat`` [H*rows_per_host, dim] is the partition-sharded store
    (``DistFeature.from_partition``'s layout, or shards already on
    their devices through ``DistFeature.from_shards`` — pass
    ``dist._spmd_feat``;
    a ``dtype_policy`` store passes its QuantizedTensor pytree whole:
    the P(axis) spec shards its leaves together and the exchange ships
    the narrow payload, dequantizing after the collective);
    ``g2h``/``g2l`` the replicated owner / local-row maps
    (``PartitionInfo.global2host/global2local``); ``seeds``/``labels``
    [H*per_host_batch] sharded over ``axis``; topology replicated.

    ``method="rotation"|"window"`` requires the shuffled
    ``indices_rows`` view (refresh per epoch; ``indices_stride=128``
    for the overlapping layout). ``with_replicate=True`` adds the three
    replicated-node operands (``DistFeature._rep_args``) so replicated
    nodes resolve against the calling host's replica tail instead of
    being mis-routed to their owner with a tail-local index.

    ``exchange_cap`` (``True | int | None``) switches the feature
    exchange to the COMPACT deduplicated collective
    (``comm.dist_lookup_local``): the frontier's valid ids dedup once,
    bucket by owner into a static [H, cap] request block, and the wire
    carries [H, cap] / [H, cap, width] instead of the dense
    [H, B] / [H, B, width] — B being the full multi-hop frontier cap,
    mostly -1 padding plus repeated hubs, so this is the step that
    makes the multi-host path bandwidth-optimal. ``True`` sizes ``cap``
    from the frontier cap and host count
    (``comm.default_exchange_cap``); an int pins it — prefer
    ``PartitionInfo.plan_exchange_cap(...).cap``, which sizes from the
    partition's degree mass. The exchange's memory is bounded by
    ``cap``: a per-owner bucket that overflows it is served by further
    rounds of the same [H, cap] exchange (a shard-uniform loop), never
    by the dense blocks — loss-identical in every case.

    The lookup's ops sit under the scope ``qt_exchange``
    (``profiling.QT_EXCHANGE``), where the one-chip steps have
    ``qt_gather``.
    """
    sizes = list(sizes)
    h_count = mesh.shape[axis]
    if merge_counters and not collect_metrics:
        raise ValueError("merge_counters=True requires "
                         "collect_metrics=True")
    if exchange_cap is True:
        frontier = layer_shapes(per_host_batch, sizes)[-1].n_id_cap
        exchange_cap = default_exchange_cap(frontier, h_count)
    elif exchange_cap is not None:
        exchange_cap = int(exchange_cap)

    def make_per_shard(has_rows):
        # shard_map arity is fixed at build time; ``has_rows`` says
        # whether extra[0] is the rows view (mandatory for
        # rotation/window, optional wide-path input for exact)
        def per_shard(state: TrainState, feat, g2h, g2l, indptr, indices,
                      seeds, labels, key, *extra):
            rows = extra[0] if has_rows else None
            rep = extra[1:] if (has_rows and with_replicate) else \
                (extra if with_replicate else None)
            key = jax.random.fold_in(key, jax.lax.axis_index(axis))

            def gather(feat_, n_id, _forder, collector=None):
                # dtype=None: the lookup resolves the store's own
                # dequantized dtype — a bf16 or quantized spmd_feat
                # must not upcast through an fp32 default, and a
                # QuantizedTensor has no .dtype to pass anyway
                return dist_lookup_local(n_id, g2h, g2l, feat_, axis,
                                         h_count, rows_per_host,
                                         rep=rep or None,
                                         exchange_cap=exchange_cap,
                                         collector=collector)

            loss_of, unpack = _metered_loss_fn(
                collect_metrics,
                lambda p, col: _fused_loss(model, loss_fn, sizes,
                                           per_host_batch, p, feat, None,
                                           indptr, indices, seeds, labels,
                                           key, method, rows,
                                           indices_stride, gather=gather,
                                           hub_frac=hub_frac,
                                           collector=col))
            loss, counters, grads = unpack(loss_of(state.params))
            new_state, loss = _pmean_update(state, tx, grads, loss, axis)
            if collect_metrics:
                if merge_counters:
                    # device-side cross-host fold: every shard leaves
                    # holding the GLOBAL [N] vector (psum/pmax slot
                    # semantics), so any host's local read sees the
                    # whole mesh's picture
                    from ..metrics import pmerge_counters
                    return new_state, loss, pmerge_counters(counters,
                                                            axis)
                # per-shard counters, [1, N] here -> [H, N] outside
                return new_state, loss, counters[None]
            return new_state, loss

        return per_shard

    def make_jitted(has_rows):
        specs = [P(), P(axis), P(), P(), P(), P(), P(axis), P(axis), P()]
        if has_rows:
            specs.append(P())            # indices_rows, replicated
        if with_replicate:
            specs += [P(), P(), P()]     # is_rep, rep_rank, bases
        if collect_metrics:
            outs = (P(), P(), P() if merge_counters else P(axis))
        else:
            outs = (P(), P())
        return jax.jit(shard_map(
            make_per_shard(has_rows), mesh=mesh,
            in_specs=tuple(specs),
            out_specs=outs,
            check_vma=False), donate_argnums=(0,) if donate else ())

    jitted_by_rows = {True: make_jitted(True), False: make_jitted(False)}
    checked = set()

    def step(state, feat, g2h, g2l, indptr, indices, seeds, labels, key,
             indices_rows=None, rep_args=()):
        _check_rows(method, indices_rows, "dist")
        jitted = jitted_by_rows[indices_rows is not None]
        extra = (indices_rows,) if indices_rows is not None else ()
        if with_replicate:
            if len(rep_args) != 3:
                raise TypeError(
                    "with_replicate dist step requires rep_args = "
                    "(is_rep, rep_rank, bases) — pass "
                    "DistFeature._rep_args")
            extra += tuple(rep_args)
        elif rep_args:
            raise TypeError("rep_args given but with_replicate=False")
        if donate:
            _check_donatable("build_dist_train_step", jitted, checked,
                             state, feat, g2h, g2l, indptr, indices,
                             seeds, labels, key, *extra)
        return jitted(state, feat, g2h, g2l, indptr, indices, seeds,
                      labels, key, *extra)

    step.jitted_fns = tuple(jitted_by_rows.values())
    return step


if build_dist_train_step.__doc__:        # None under python -OO
    build_dist_train_step.__doc__ += _DONATED_DOC + _COLLECT_DOC
