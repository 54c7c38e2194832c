"""Fused multi-hop sampling: the whole k-hop frontier expansion as one
traceable function (used by GraphSageSampler and by the end-to-end
jitted training step)."""

from __future__ import annotations

from typing import List, NamedTuple, Sequence, Tuple

import jax
import jax.numpy as jnp

from .. import profiling
from .sample import (LayerSample, _compact_core, as_index_rows,
                     as_index_rows_overlapping,
                     compact_layer, edge_rows, permute_csr, sample_layer,
                     sample_layer_exact_wide, sample_layer_rotation,
                     sample_layer_window, suggest_hub_cap)
from .weighted import sample_layer_weighted, sample_layer_weighted_window


def sample_multihop(indptr: jax.Array, indices: jax.Array, seeds: jax.Array,
                    sizes: Sequence[int], key: jax.Array,
                    edge_weight: jax.Array | None = None,
                    method: str = "exact",
                    indices_rows: jax.Array | None = None,
                    eid=None,
                    indices_stride: int | None = None,
                    seeds_dense: bool = False,
                    weight_rows: jax.Array | None = None,
                    hub_frac: float | None = None,
                    collector=None,
                    node_types=None,
                    ) -> Tuple[jax.Array, List[LayerSample]]:
    """Expand ``seeds`` through ``sizes`` hops. Returns the final frontier
    ``n_id`` (static cap, -1 fill) and the per-hop LayerSamples in
    sampling order (innermost target hop first).

    ``method``: ``"exact"`` (default; i.i.d. Fisher-Yates subsets — k
    scattered loads per seed, or, when ``indices_rows`` is ALSO passed
    (a layout view of the same un-shuffled ``indices``), the wide-fetch
    exact path ``sample_layer_exact_wide``: one/two row gathers for
    every low-degree seed, scattered loads only for hub rows — same
    draw, lower memory traffic. WARNING: the rows view MUST be built
    from ``indices`` in its given order — a permuted view cannot be
    detected here and would pair original-order edge slots with
    permuted-order values, silently corrupting ``eid`` tracking),
    ``"rotation"`` (~3x faster on TPU: wide
    row fetches per seed; draws consecutive runs of the row order, so
    rows must be shuffled with ``permute_csr`` — at least once, ideally
    per epoch — or endpoint neighbors are under-sampled; pass the
    shuffled array as ``indices`` and its ``as_index_rows`` view as
    ``indices_rows``), or ``"window"`` (same row fetches as rotation
    but an i.i.d. k-subset of a >=129-entry window — independent
    subsets within an epoch, exact for deg <= window under any row
    order; hub rows anchor the window at a rotation-style random
    offset, so any mixing reshuffle serves, butterfly included). If
    ``indices_rows`` is omitted in rotation/window
    mode, one ``permute_csr`` is applied internally so the draw is
    still marginally uniform — correct but slower per call; callers on
    the hot path should shuffle per epoch themselves.
    ``edge_weight`` (CSR-slot-aligned) switches every hop to weighted
    sampling — the exact [bs, row_cap] pool draw by default; with a
    windowed ``method`` AND ``weight_rows`` (the weight layout from the
    same shuffle: ``reshuffle_csr(..., extra=(edge_weight,))`` then
    ``as_index_rows*``), hops use the ~8x-cheaper windowed weighted
    draw instead (``sample_layer_weighted_window``'s truncation
    caveats apply).

    ``indices_stride``: set to the build width (128) when
    ``indices_rows`` came from ``as_index_rows_overlapping`` — rotation
    then does ONE row gather per seed instead of two (2x index memory).

    ``seeds_dense`` promises the hop-0 ``seeds`` are valid-first (-1
    fill only at the tail, e.g. a raw training batch with no padding or
    a ``compact_ids`` output) — drops one operand from hop 0's
    compaction sort. Hops >= 1 always take that path (their seeds are
    the previous hop's ``n_id``, valid-first by construction).

    ``hub_frac`` (static float, ``ExactBucketMeta.frac`` from
    ``CSRTopo.exact_bucket_meta()``) sizes each hop's wide-exact
    scattered-load budget from the graph's cached degree-bucket split
    instead of the blind bs//2 default — only consumed by the exact
    wide-fetch path; ignored elsewhere.

    ``eid`` enables per-edge id tracking (off by default — it adds one
    scattered gather per sampled edge, which the fused training path
    doesn't want): ``True`` stamps each sampled edge with its CSR slot;
    an array stamps ``eid[slot]`` (pass ``CSRTopo.eid`` for original COO
    positions; under rotation pass the co-permuted map built from
    ``permute_csr(..., with_slot_map=True)``). The ids land in each
    ``LayerSample.e_id`` (-1 fill).

    ``collector`` (optional ``metrics.Collector``) records the final
    frontier's fill — valid slots vs the static cap, the number the
    dedup budgets and exchange caps are sized against — with one jnp
    reduction on the returned ``n_id`` (no host sync, output unchanged).

    ``node_types`` (an ``ops.relation.NodeTypes``: a typed graph's id
    ranges and relations) gives every hop the relation of each sampled
    edge, in ``LayerSample.rel`` (and ``node_types`` itself in
    ``LayerSample.types``): read off the seed's and the pick's
    global ids where the draw ends (under ``qt_draw_neighbors``), with
    compares and selects and no gather. The draw itself is unchanged.
    """
    cur = seeds.astype(jnp.int32)
    track_eid = eid is not None
    windowed = method in ("rotation", "window")
    if weight_rows is not None and (edge_weight is None or not windowed):
        # the coupled-parameter mistake in the other direction: a built
        # weight layout that the dispatch below would silently ignore
        raise ValueError(
            "weight_rows is only consumed by windowed WEIGHTED sampling "
            "— pass edge_weight (the trigger) and a rotation/window "
            "method with it, or drop it")
    if (edge_weight is not None and windowed and indices_rows is not None
            and weight_rows is None):
        # silently running the exact pool draw here would ignore the
        # supplied rows AND pair (possibly permuted) neighbor ids with
        # unpermuted weights
        raise ValueError(
            "weighted windowed sampling needs weight_rows co-shuffled "
            "with indices_rows (reshuffle_csr(..., extra=(edge_weight,)) "
            "then as_index_rows* both); drop indices_rows for the exact "
            "pool draw")
    if edge_weight is not None and not windowed and indices_rows is not None:
        # exact weighted runs the scattered pool draw; silently dropping
        # a rows view the caller built (expecting the wide-fetch exact
        # speedup to survive adding weights) is the same coupled-
        # parameter trap the windowed guards above reject loudly
        raise ValueError(
            "indices_rows is not consumed by exact WEIGHTED sampling "
            "(the pool draw is scattered) — drop indices_rows, or use a "
            "rotation/window method with weight_rows for the windowed "
            "weighted draw")
    if edge_weight is None and windowed and indices_rows is None:
        # the no-arg fallback must not sample consecutive runs of the
        # caller's (possibly raw CSR) order — that permanently
        # under-samples row-endpoint neighbors
        pkey = jax.random.fold_in(key, len(sizes))  # hops use 0..len-1
        rids = edge_rows(indptr, indices.shape[0])
        as_rows = (as_index_rows if indices_stride is None else
                   (lambda ix: as_index_rows_overlapping(
                       ix, width=indices_stride)))
        if track_eid:
            # rotation slots index the permuted array; compose the
            # caller's eid map with the permutation's slot map
            permuted, smap = permute_csr(indices, rids, pkey,
                                         with_slot_map=True)
            eid = smap if eid is True else jnp.asarray(eid)[smap]
            indices_rows = as_rows(permuted)
        else:
            indices_rows = as_rows(permute_csr(indices, rids, pkey))
    layers: List[LayerSample] = []
    for i, k in enumerate(sizes):
      # named scope per hop: XProf traces attribute time to hop stages
      # instead of one opaque multihop blob
      with jax.named_scope(f"qt_sample_hop{i}"):
        slots = None
        # the hop's two halves, named apart: the one draw (whichever
        # method arm, with its key) and the compaction with its e_id
        # bookkeeping
        with profiling.scope(profiling.QT_DRAW):
          sub = jax.random.fold_in(key, i)
          if edge_weight is not None and windowed and weight_rows is not None:
              if indices_rows is None:
                  raise ValueError(
                      "windowed weighted sampling needs indices_rows from "
                      "the same shuffle as weight_rows (reshuffle_csr with "
                      "extra=(edge_weight,), then as_index_rows* both)")
              out = sample_layer_weighted_window(
                  indptr, indices_rows, weight_rows, cur, k, sub,
                  stride=indices_stride, with_slots=track_eid)
          elif edge_weight is not None:
              out = sample_layer_weighted(indptr, indices, edge_weight,
                                          cur, k, sub, with_slots=track_eid)
          elif method == "rotation":
              out = sample_layer_rotation(indptr, indices_rows, cur, k, sub,
                                          with_slots=track_eid,
                                          stride=indices_stride)
          elif method == "window":
              out = sample_layer_window(indptr, indices_rows, cur, k, sub,
                                        with_slots=track_eid,
                                        stride=indices_stride)
          elif indices_rows is not None:
              # exact + rows layout = the wide-fetch exact draw (same
              # contract as sample_layer, fewer scattered loads); the
              # rows view MUST be of the same un-shuffled ``indices``.
              # The hub budget is static per hop: frontier width is a
              # compile-time shape and hub_frac is cached graph metadata
              out = sample_layer_exact_wide(
                  indptr, indices, indices_rows, cur, k, sub,
                  stride=indices_stride, with_slots=track_eid,
                  hub_cap=suggest_hub_cap(int(cur.shape[0]), hub_frac))
          else:
              out = sample_layer(indptr, indices, cur, k, sub,
                                 with_slots=track_eid)
          nbrs = out[0]
          if node_types is not None:
              with profiling.scope(profiling.QT_DRAW_NEIGHBORS):
                  rel = node_types.relation(cur[:, None], nbrs).reshape(-1)
        if track_eid:
            slots = out[2]
        with profiling.scope(profiling.QT_COMPACT):
          # hop >= 1 seeds are the previous hop's n_id — valid-first by
          # _compact_core's own output invariant — so the cheaper dense
          # seed path is always safe there; hop 0 takes it only when the
          # caller promises a valid-first batch (``seeds_dense``)
          layer = compact_layer(cur, nbrs, seeds_dense=(i > 0) or seeds_dense)
          if track_eid:
              flat = slots.reshape(-1)
              if eid is True:
                  ids = flat
              else:
                  ids = jnp.asarray(eid)[jnp.clip(flat, 0)]
              layer = layer._replace(e_id=jnp.where(flat >= 0, ids, -1))
        if node_types is not None:
            layer = layer._replace(rel=rel, types=node_types)
        layers.append(layer)
        cur = layer.n_id
    count_walk(collector, cur, layers)
    return cur, layers


def count_walk(collector, n_id, layers) -> None:
    """What a walk counts into a ``metrics.Collector`` (None: nothing):
    the final frontier's valid slots against its static cap, and the
    hops' valid edge slots against theirs: what share of a model's
    per-row and per-edge work is padding. Over a typed graph (the layers
    state their ``types``), also the valid edge slots of the relation
    that holds the most of them over all hops: how uneven the groups of
    a product by relation are."""
    if collector is None:
        return
    from ..metrics import (EDGE_CAP, EDGE_REL_MAX, EDGE_VALID, FRONTIER_CAP,
                           FRONTIER_VALID)
    collector.add(FRONTIER_VALID, jnp.sum(n_id >= 0))
    collector.add(FRONTIER_CAP, int(n_id.shape[0]))
    collector.add(EDGE_VALID, sum(l.edge_count for l in layers))
    collector.add(EDGE_CAP, sum(int(l.col.shape[0]) for l in layers))
    types = layers[0].types if layers else None
    if types is not None:
        collector.add(EDGE_REL_MAX, jnp.max(jnp.stack([
            sum(jnp.sum(l.rel == r, dtype=jnp.int32) for l in layers)
            for r in range(types.num_relations)])))


class TypedLayer(NamedTuple):
    """One hop of the per-relation walk (``typed_hop``), fixed shapes.
    Types and relations are indices of the walk's ``NodeTypes``.

    frontier:   per node type, ``[cap]`` LOCAL ids after the hop: the
                type's frontier before the hop keeps its slots at the
                head, the new nodes follow in ascending id order, -1
                fill; None where the type has no frontier yet
    count:      per node type, ``[]`` valid entries of ``frontier``
    col:        per relation, ``[targets x k]``: slot ``e`` holds a pick
                of target ``e // k`` (``k`` the relation's fanout), its
                place in the source type's ``frontier``; -1 fill. None
                where the relation was not drawn (its target type had no
                frontier, or its fanout is 0)
    row:        per relation, the target's place (``e // k``), -1 fill
    edge_count: per relation, ``[]`` valid slots
    seed_count: per relation, ``[]`` valid targets (the target type's
                ``count`` before the hop)
    slot:       per relation, the CSR slot of each pick (-1 fill) where
                the draw hands it out, else None
    drops:      ``[]`` valid picks masked because their node fell past
                its type's cap
    types:      the walk's ``NodeTypes`` (static, no leaves), where the
                walk has one
    """

    frontier: tuple
    count: tuple
    col: tuple
    row: tuple
    edge_count: tuple
    seed_count: tuple
    slot: tuple
    drops: jax.Array
    types: object = None


def typed_hop(frontier, count, pairs, draw, fanouts, key, caps=None,
              dense: bool = False) -> TypedLayer:
    """One hop over a typed graph whose relations keep their own lists:
    every relation ``r = (t, s)`` of ``pairs`` whose target type has a
    frontier and whose ``fanouts[r]`` is positive draws ``draw(r,
    frontier[t], k, fold_in(key, r)) -> (picks [len, k], slots or
    None)`` (``picks`` in type ``s``'s local ids, -1 fill) under
    ``qt_draw_relation``; then, under ``qt_type_union``, each type's
    frontier becomes the first-occurrence union of itself (the prefix:
    its valid entries, distinct, keep their slots) and the picks of it,
    in relation order, cut to ``caps[s]`` where that is smaller. A pick
    whose node falls past the cap is masked and counted in ``drops``.
    ``dense`` promises every frontier is valid-first (a walk's always
    are; hop 0's batch where the caller says so)."""
    relations, types = len(pairs), len(frontier)
    picks = [None] * relations
    slots = [None] * relations
    with profiling.scope(profiling.QT_DRAW):
        for r, (t, _) in enumerate(pairs):
            k = fanouts[r]
            if frontier[t] is None or not k:
                continue
            with profiling.scope(profiling.QT_DRAW_RELATION):
                picks[r], slots[r] = draw(r, frontier[t], int(k),
                                          jax.random.fold_in(key, r))
    new_f, new_c = list(frontier), list(count)
    col = [None] * relations
    drops = jnp.int32(0)
    with profiling.scope(profiling.QT_COMPACT):
        with profiling.scope(profiling.QT_TYPE_UNION):
            for s in range(types):
                mine = [r for r in range(relations)
                        if picks[r] is not None and pairs[r][1] == s]
                if not mine:
                    continue
                prev = frontier[s] if frontier[s] is not None \
                    else jnp.full((0,), -1, jnp.int32)
                p = prev.shape[0]
                flat = jnp.concatenate([picks[r].reshape(-1) for r in mine])
                n_id, n_count, local = _compact_core(
                    jnp.concatenate([prev.astype(jnp.int32), flat]), p,
                    seeds_dense=dense)
                extra = jnp.where(flat >= 0, local[p:], -1)
                cap = None if caps is None else caps[s]
                if cap is not None and cap < n_id.shape[0]:
                    n_id, n_count = n_id[:cap], jnp.minimum(n_count, cap)
                    past = extra >= cap
                    drops = drops + jnp.sum(past, dtype=jnp.int32)
                    extra = jnp.where(past, -1, extra)
                new_f[s], new_c[s] = n_id, n_count
                at = 0
                for r in mine:
                    size = picks[r].size
                    col[r] = extra[at:at + size]
                    at += size
        row = [None] * relations
        edges = [None] * relations
        seeds = [None] * relations
        for r, (t, _) in enumerate(pairs):
            if col[r] is None:
                continue
            targets, k = picks[r].shape
            row[r] = jnp.where(col[r] >= 0, jnp.repeat(
                jnp.arange(targets, dtype=jnp.int32), k), -1)
            edges[r] = jnp.sum(col[r] >= 0, dtype=jnp.int32)
            seeds[r] = count[t]
            if slots[r] is not None:
                slots[r] = jnp.where(col[r] >= 0, slots[r].reshape(-1), -1)
    return TypedLayer(tuple(new_f), tuple(new_c), tuple(col), tuple(row),
                      tuple(edges), tuple(seeds), tuple(slots), drops)


def sample_typed_multihop(indptrs, graph, seeds: jax.Array,
                          sizes: Sequence[int], key: jax.Array,
                          collector=None, seeds_dense: bool = True):
    """The per-relation walk over ``graph`` (``ops.relation
    .RelationIndices``; ``indptrs`` its relations' row pointers): hop
    ``i`` runs ``typed_hop`` from ``fold_in(key, i)`` with fanout
    ``sizes[i]`` for every relation and the graph's caps for hop ``i``,
    each relation drawing ``min(degree, k)`` distinct neighbours a target
    with the exact draw (``sample_layer``: a Fisher-Yates write log, so a
    hub row costs what any row does). ``seeds`` are ids of type 0 (-1
    fill; valid-first where ``seeds_dense``).

    Returns ``(n_id, layers)``: the final frontiers of every type as
    global ids, in type order, each at its static cap (-1 fill), and the
    ``TypedLayer``s in sampling order. ``collector`` records the type
    frontiers' fill, the relation blocks' fill and ``type_cap_drops``."""
    types = graph.types
    frontier = [None] * types.num_types
    count = [None] * types.num_types
    frontier[0] = seeds.astype(jnp.int32)
    count[0] = jnp.sum(seeds >= 0, dtype=jnp.int32)

    def draw(r, targets, k, sub):
        return sample_layer(indptrs[r], graph.indices[r], targets, k,
                            sub)[0], None

    layers = []
    for i, k in enumerate(sizes):
        with jax.named_scope(f"qt_sample_hop{i}"):
            layer = typed_hop(
                frontier, count, types.relations, draw,
                [k] * types.num_relations, jax.random.fold_in(key, i),
                [graph.cap(t, i) for t in range(types.num_types)],
                dense=(i > 0) or seeds_dense)
        layers.append(layer._replace(types=types))
        frontier, count = layer.frontier, layer.count
    n_id = jnp.concatenate([jnp.where(f >= 0, f + types.start(t), -1)
                            for t, f in enumerate(frontier) if f is not None])
    count_typed_walk(collector, layers)
    return n_id, layers


def count_typed_walk(collector, layers) -> None:
    """``count_walk`` of the per-relation walk: the final type frontiers'
    valid rows against their caps, the relation blocks' valid slots
    against theirs over every hop, and the picks no cap could take."""
    if collector is None:
        return
    from ..metrics import (EDGE_CAP, EDGE_VALID, FRONTIER_CAP,
                           FRONTIER_VALID, TYPE_CAP_DROPS)
    last = layers[-1]
    collector.add(FRONTIER_VALID, sum(c for c in last.count if c is not None))
    collector.add(FRONTIER_CAP, sum(int(f.shape[0]) for f in last.frontier
                                    if f is not None))
    collector.add(EDGE_VALID, sum(e for l in layers for e in l.edge_count
                                  if e is not None))
    collector.add(EDGE_CAP, sum(int(c.shape[0]) for l in layers for c in l.col
                                if c is not None))
    collector.add(TYPE_CAP_DROPS, sum(l.drops for l in layers))


def sample_multihop_dedup(indptr: jax.Array, indices: jax.Array,
                          batch: jax.Array, sizes: Sequence[int],
                          key: jax.Array, **kwargs):
    """`sample_multihop` for batches that may contain DUPLICATE ids (e.g.
    the unsupervised [seeds | walk-positives | negatives] triple,
    reference examples/pyg/graph_sage_unsup_quiver.py:56-58). The batch is
    deduplicated first (the compaction contract requires distinct seeds);
    returns (n_id, layers, batch_locals) where ``batch_locals[i]`` is the
    row of ``batch[i]`` in the model output — the collapse semantics of
    the reference's first-occurrence hashtable."""
    from .sample import compact_ids

    ubatch, _, blocals = compact_ids(batch.astype(jnp.int32))
    kwargs.setdefault("seeds_dense", True)   # compact_ids output is dense
    n_id, layers = sample_multihop(indptr, indices, ubatch, sizes, key,
                                   **kwargs)
    return n_id, layers, blocals
