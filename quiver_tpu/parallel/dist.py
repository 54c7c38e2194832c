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

from jax.sharding import Mesh, PartitionSpec as P

# a module global on purpose: the exchange below looks it up when the
# step is TRACED, so a caller can swap it around a trace
from ..comm import dist_lookup_local
from .frontier import SAMPLING_KNOBS, Walk, documented, walk_doc
from .train import (_COLLECT_DOC, _DONATED_DOC, _fused_loss, _sharded_step,
                    cross_entropy_logits)


@documented(walk_doc(SAMPLING_KNOBS), _DONATED_DOC, _COLLECT_DOC)
def build_dist_train_step(model, tx, sizes: Sequence[int],
                          per_host_batch: int, mesh: Mesh,
                          rows_per_host: int,
                          axis: str = "host",
                          loss_fn: Callable = cross_entropy_logits,
                          with_replicate: bool = False,
                          donate: bool = True,
                          exchange_cap=None,
                          collect_metrics: bool = False,
                          merge_counters: bool = False, **walk):
    """fn(state, spmd_feat, g2h, g2l, indptr, indices, seeds, labels,
    key[, indices_rows][, rep_args=(is_rep, rep_rank, bases)])
    -> (state, loss).

    ``spmd_feat`` [H*rows_per_host, dim] is the partition-sharded store
    (``DistFeature.from_partition``'s layout, or shards already on
    their devices through ``DistFeature.from_shards`` — pass
    ``dist._spmd_feat``;
    a ``dtype_policy`` store passes its QuantizedTensor pytree whole:
    the P(axis) spec shards its leaves together and the exchange ships
    the narrow payload, dequantizing after the collective);
    ``g2h``/``g2l`` the replicated owner / local-row maps
    (``PartitionInfo.global2host/global2local``), which the lookup
    folds into one book a step (``g2h * rows_per_host + g2l``) that
    only the frontier's distinct ids read; ``seeds``/``labels``
    [H*per_host_batch] sharded over ``axis``; topology replicated.

    ``with_replicate=True`` adds the three replicated-node operands
    (``DistFeature._rep_args``) so replicated nodes resolve against the
    calling host's replica tail instead of being mis-routed to their
    owner with a tail-local index.

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
    ``qt_gather``; ``qt_exchange_route`` holds the book and each
    frontier slot's read of its owner and place, ``qt_exchange_bucket``
    the distinct ids' read of the book (``comm.dist_lookup_local``)."""
    who = "build_dist_train_step"
    h_count = mesh.shape[axis]

    def exchange(store, n_id, _forder, collector=None, exchange_cap=None):
        # no dtype: the lookup resolves the store's own dequantized
        # dtype — a bf16 or quantized spmd_feat must not upcast through
        # an fp32 default, and a QuantizedTensor has no .dtype to pass
        feat, g2h, g2l, rep = store
        return dist_lookup_local(n_id, g2h, g2l, feat, axis, h_count,
                                 rows_per_host, rep=rep or None,
                                 exchange_cap=exchange_cap,
                                 collector=collector)

    walk = Walk.of(who, SAMPLING_KNOBS, sizes, walk, gather=exchange,
                   exchange=(h_count, exchange_cap, per_host_batch))

    def loss(p, col, key, rows, feat, g2h, g2l, indptr, indices, seeds,
             labels, *rep):
        return _fused_loss(model, loss_fn, walk, per_host_batch, p,
                           (feat, g2h, g2l, rep), None, indptr, indices,
                           seeds, labels, key, rows, col)

    run = _sharded_step(
        who, walk, loss, tx, mesh, axis,
        (P(axis), P(), P(), P(), P(), P(axis), P(axis)),
        (P(), P(), P()) if with_replicate else (),   # is_rep, rep_rank, bases
        donate, collect_metrics, merge_counters)

    def step(state, feat, g2h, g2l, indptr, indices, seeds, labels, key,
             indices_rows=None, rep_args=()):
        if with_replicate:
            if len(rep_args) != 3:
                raise TypeError(
                    "with_replicate dist step requires rep_args = "
                    "(is_rep, rep_rank, bases) — pass "
                    "DistFeature._rep_args")
        elif rep_args:
            raise TypeError("rep_args given but with_replicate=False")
        return run(state, (feat, g2h, g2l, indptr, indices, seeds, labels,
                           key), indices_rows, tuple(rep_args))

    step.jitted_fns = run.jitted_fns
    return step
