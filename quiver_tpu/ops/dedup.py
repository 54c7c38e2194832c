"""Static-budget frontier deduplication.

Multi-hop frontiers repeat hub nodes many times (a 3-hop products
frontier revisits high-degree nodes at every hop), so a gather that
reads one row per frontier *slot* moves duplicate-factor-times more
bytes than one that reads one row per unique *node*. These helpers make
that dedup jittable with static shapes: ``unique_within_budget`` ranks
the distinct values of an id array into a fixed-size table (the
hub-budget/compaction pattern of ``sample_layer_exact_wide``) plus an
inverse map back to the original positions; both come out of one sort
of the ids with their positions (a second sort sends the ranks back
along its permutation: no search over the table). Consumers gather each
unique row once and expand — with a ``lax.cond`` full-gather fallback
when the unique count overflows the budget, so exactness never depends
on the budget (FastSample's dedup/compaction lever, arxiv 2311.17847,
expressed in fixed-shape XLA).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

I32_MAX = jnp.iinfo(jnp.int32).max
_I32_MAX = I32_MAX          # back-compat alias (fill value, public)


def unique_within_budget(ids: jax.Array, budget: int, valid=None,
                         collector=None):
    """Compact the distinct values of ``ids`` into a static-size table.

    Returns ``(uniq, inv, n_uniq)``:

      uniq   [budget] int32 — the first ``min(n_uniq, budget)`` distinct
             values in ascending order, int32-max fill past ``n_uniq``
             (keeps the table sorted; consumers clip before gathering)
      inv    [n] int32 in [0, budget) — ``uniq[inv[i]] == ids[i]`` for
             every counted position ``i`` whenever ``n_uniq <= budget``
             (garbage, but in-range, at uncounted positions and on
             overflow — callers must gate on ``n_uniq`` / ``valid``)
      n_uniq []  int32 — the true distinct count (may exceed budget;
             callers branch to a full gather via ``lax.cond`` then)

    ``valid`` (optional [n] bool) excludes positions from the count —
    excluded slots neither consume budget nor get a meaningful ``inv``.
    Positions are excluded by keying them to int32 max, so ids must stay
    below it (node/row ids always do).

    ``collector`` (optional ``metrics.Collector``) records the observed
    dup statistics — counted ids, true distinct count, and whether the
    budget overflowed — with pure jnp ops on values this function
    already computes (no host sync, no effect on the returned arrays).

    Cost note: ``inv`` rides back along the sort's own permutation.
    The keys are sorted WITH their positions and the ranks of the
    sorted keys are sorted back by position: two sorts and the
    compaction scatter, no search. On a TPU v5e at 1,081,344 slots
    (the row-sharded papers100M step's frontier) that is 7.9 ms;
    scattering the ranks back instead (``unique_indices``) 11.4 ms;
    the ``searchsorted`` over ``uniq`` that stood here until PR 29 (a
    binary search of ~log2(budget) rounds, each a gather of ``n``
    elements, chosen on a CPU-backend timing) 168.8 ms, 45 % of that
    step (PERF.md section 6, PR 29).
    No data-dependent shapes.
    """
    ids = ids.astype(jnp.int32)
    n = ids.shape[0]
    key = ids if valid is None else jnp.where(valid, ids, _I32_MAX)
    skey, perm = jax.lax.sort((key, jnp.arange(n, dtype=jnp.int32)),
                              num_keys=1, is_stable=False)
    first = jnp.concatenate([jnp.ones((1,), bool), skey[1:] != skey[:-1]])
    new = (first & (skey != _I32_MAX)) if valid is not None else first
    n_uniq = jnp.sum(new).astype(jnp.int32)
    urank = jnp.cumsum(new).astype(jnp.int32) - 1
    tgt = jnp.where(new & (urank < budget), urank, budget)  # budget = drop
    uniq = jnp.full((budget,), _I32_MAX, jnp.int32).at[tgt].set(
        skey, mode="drop")
    # inv[perm[j]] = urank[j]; perm is a permutation, so sorting by it
    # is exact (urank is -1 where nothing is counted: clipped in range)
    _, inv = jax.lax.sort((perm, jnp.clip(urank, 0, budget - 1)),
                          num_keys=1, is_stable=False)
    if collector is not None:
        from ..metrics import (DEDUP_CALLS, DEDUP_OVERFLOW, DEDUP_TOTAL,
                               DEDUP_UNIQUE)
        total = n if valid is None else jnp.sum(valid)
        collector.add(DEDUP_CALLS, 1)
        collector.add(DEDUP_TOTAL, total)
        collector.add(DEDUP_UNIQUE, n_uniq)
        collector.add(DEDUP_OVERFLOW, n_uniq > budget)
    return uniq, inv, n_uniq


def dedup_take(table: jax.Array, ids: jax.Array, budget: int,
               valid=None, collector=None) -> jax.Array:
    """``jnp.take(table, ids, axis=0)`` reading each distinct id ONCE.

    The only ``table``-sized read on the narrow path is a
    [budget, dim] gather of the unique rows; positions then expand from
    that small array. When the distinct count overflows ``budget`` a
    ``lax.cond`` falls back to the full positional gather — identical
    results in every case, only the traffic bound degrades. Rows at
    excluded (``valid=False``) positions and at the int32-max fill are
    whatever the clipped reads produce — callers mask them.

    Pays off when ``table`` lives in a slow tier (pinned host memory)
    and ``ids`` carries duplicates (frontier duplicate factor > ~1.3);
    a duplicate-free batch degenerates to the same bytes as the plain
    gather plus one sort. ``table`` may be a quantized tier
    (``ops.quant.QuantizedTensor``): the narrow path then reads
    [budget, dim] int8 + sidecars and dequantizes only the unique rows.
    """
    from . import quant
    n = ids.shape[0]
    rows = quant.tier_rows(table)
    take = lambda t_ids: quant.gather_rows(
        table, jnp.clip(t_ids, 0, max(rows - 1, 0)))
    if budget >= n:
        return take(ids)
    uniq, inv, n_uniq = unique_within_budget(ids, budget, valid=valid,
                                             collector=collector)

    def narrow(_):
        uniq_rows = take(uniq)                          # [budget, dim]
        return jnp.take(uniq_rows, inv, axis=0)

    def full(_):
        return take(ids)

    return jax.lax.cond(n_uniq > budget, full, narrow, None)


def unique_np(ids, valid=None) -> np.ndarray:
    """Host-side frontier dedup — the numpy mirror of
    ``unique_within_budget`` minus the static budget (the cold-tier
    prefetcher's staging thread runs on the host, where data-dependent
    shapes are free): the sorted distinct VALID ids. ``valid=None``
    treats negative ids as padding, matching the device convention."""
    ids = np.asarray(ids)
    mask = (ids >= 0) if valid is None else (np.asarray(valid) & (ids >= 0))
    return np.unique(ids[mask])


def compact_exchange_slots(ids, cap: int, hosts: int,
                           owner=None) -> int:
    """Analytic mirror of ``comm.dist_lookup_local``'s compact exchange
    for one shard's batch: request slots shipped per collective
    direction — ``cap * hosts`` a round, and as many rounds as the
    fullest per-owner bucket of the batch's distinct valid ids needs
    (``ceil(fullest / cap)``; one when every bucket fits) — or the full
    batch when ``cap`` can't beat the dense block. The program runs
    the rounds of the neediest SHARD on all of them (the count is
    ``pmax``'d); this mirrors one shard's own need. ``owner`` maps id
    -> owning host (``PartitionInfo.global2host``); None models a
    balanced hash partition (``id % hosts``). The benches' exchange
    bytes/batch figures come from this ONE copy of the logic; the
    structural (jaxpr-level) pin of the same bound lives in
    tests/_traffic.py::collective_payloads."""
    ids = np.asarray(jax.device_get(ids))
    n = int(ids.shape[0])
    if cap is None or cap >= n:
        return n
    uniq = np.unique(ids[ids >= 0])
    own = (uniq % hosts if owner is None
           else np.asarray(jax.device_get(owner))[uniq])
    fullest = int(np.bincount(own, minlength=hosts).max(initial=0))
    return max(1, -(-fullest // cap)) * cap * hosts
