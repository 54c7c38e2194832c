"""The neighbourhood stage: ``(topology, seeds, key) -> (n_id, x, layers)``.

Every step builder (``train.py``, ``dist.py``, ``gspmd.py``,
``serving.py``) draws its frontier and reads its rows through
``walk_frontier``; what that walk decides is ONE frozen ``Walk``, built
and validated by ``Walk.of``. A new draw, a new gather or a new knob
enters here and nowhere else.

The gather protocol, stated once: ``gather(feat, n_id, forder,
collector=None) -> rows [len(n_id), dim]``, zero on the -1 padding.
``feat`` is whatever the gather reads — an array or quantized store for
the two local gathers below, the ``(device_part, host)`` pair of a
``Feature`` store's tiered lookup, the ``(shard, g2h, g2l[, rep])``
operands of the cross-shard exchange — and the step passes it through
unopened. A ``collector`` (``metrics.Collector``) takes counts the
gather already computes.
"""

from __future__ import annotations

import dataclasses
import functools
import textwrap
from typing import Callable, Sequence

import jax
import jax.numpy as jnp

from .. import profiling
from ..comm import default_exchange_cap
from ..ops.relation import RelationIndices, split_typed
from ..ops.sample_multihop import (TypedLayer, count_walk, sample_multihop,
                                   sample_typed_multihop)
from ..profiling import hot_path
from ..pyg.sage_sampler import Adj, RelationBlocks, layer_shapes


def layers_to_adjs(layers, batch_size: int, sizes: Sequence[int]):
    """LayerSamples (sampling order) -> Adj list (outermost hop first).

    Precondition: every layer is a ``compact_layer`` output over dense
    seeds (``seeds_dense=True``'s promise: the hop-0 batch is valid-first
    with -1 at the tail only; hops >= 1 always are). A valid seed's local
    id is then its position, so edge slot ``e`` targets ``e // fanout``
    or nothing, which is what each ``Adj.fanout`` set here states. A
    caller that cannot promise it builds its ``Adj``s without a fanout.
    Each ``Adj`` also states how many of its target slots hold a node
    (``valid_targets``: the hop's count of valid seeds) and, over a typed
    graph, each edge slot's relation (``relation``) and the graph's count
    of relations (``relations``), for every step builder alike.

    The ``TypedLayer``s of a per-relation walk become ``RelationBlocks``,
    one a hop (outermost first), each with an ``Adj`` a relation drawn:
    its slot ``e`` targets ``e // fanout`` as above, and it states its
    relation as a static int, its ``fanout`` and its ``valid_targets``."""
    if layers and isinstance(layers[0], TypedLayer):
        return _relation_blocks(layers, sizes)
    shapes = layer_shapes(batch_size, sizes)
    adjs = []
    for layer, shape in zip(layers, shapes):
        adjs.append(Adj(edge_index=jnp.stack([layer.col, layer.row]),
                        e_id=layer.e_id,
                        size=(shape.n_id_cap, shape.num_seeds),
                        mask=layer.col >= 0, fanout=shape.fanout,
                        valid_targets=layer.seed_count,
                        relation=layer.rel,
                        relations=(None if layer.types is None
                                   else layer.types.num_relations)))
    return adjs[::-1]


def _relation_blocks(layers, sizes):
    """``layers_to_adjs`` of a per-relation walk's ``TypedLayer``s."""
    types = layers[0].types
    out, before = [], None
    for layer, k in zip(layers, sizes):
        sources = [0 if f is None else int(f.shape[0]) for f in layer.frontier]
        if before is None:
            # hop 0's targets are the batch, whose rows its blocks target
            before = [0] * len(sources)
            for r, col in enumerate(layer.col):
                if col is not None:
                    before[types.relations[r][0]] = int(col.shape[0]) // k
        adjs = [Adj(edge_index=jnp.stack([col, layer.row[r]]), e_id=None,
                    size=(sources[types.relations[r][1]],
                          before[types.relations[r][0]]),
                    mask=col >= 0, fanout=int(k),
                    valid_targets=layer.seed_count[r], relation=r,
                    relations=types.num_relations)
                for r, col in enumerate(layer.col) if col is not None]
        out.append(RelationBlocks(adjs, sources, before, types))
        before = sources
    return out[::-1]


@hot_path
def masked_feature_gather(feat, n_id: jax.Array,
                          feature_order=None,
                          collector=None) -> jax.Array:
    """Feature rows for a -1-padded frontier, through the optional
    hot-order indirection (reference feature.py:296-301); padded rows
    come back zeroed so aggregation stays exact. ``feat`` may be a
    plain array or a quantized store (``ops.quant`` — e.g.
    ``quant.quantize(feat, "int8")``): dequantization fuses into the
    gather, so the step reads narrow rows + sidecars and the model
    consumes float activations unchanged. A table stored in a float
    narrower than float32 (float16, bfloat16) is read as stored and the
    gathered block converted to float32 HERE, once and exactly, before the
    mask: the store's dtype decides, a float32 table is untouched.
    ``collector`` is the gather protocol's (one tier: nothing to count)."""
    from ..ops import quant
    with profiling.scope(profiling.QT_GATHER):
        ids = n_id
        if feature_order is not None:
            ids = feature_order[jnp.clip(n_id, 0)]
        safe = jnp.clip(ids, 0, quant.tier_rows(feat) - 1)
        x = quant.gather_rows(feat, safe)
        if jnp.issubdtype(x.dtype, jnp.floating) and x.dtype.itemsize < 4:
            x = x.astype(jnp.float32)
        return x * (n_id >= 0).astype(x.dtype)[:, None]


@hot_path
def dedup_feature_gather(feat, n_id: jax.Array,
                         feature_order=None,
                         budget: int | None = None,
                         collector=None) -> jax.Array:
    """``masked_feature_gather`` reading each distinct valid id ONCE:
    the frontier's -1 padding (the bulk of a static multi-hop cap) and
    any repeated ids collapse into a static-``budget`` unique table,
    the feature read is one [budget, dim] gather, and positions expand
    from it. Falls back to the plain full gather via ``lax.cond`` when
    the unique count overflows — identical output in every case.
    Default budget: ``max(len(n_id)//4, 256)``."""
    from ..ops.dedup import unique_within_budget
    from ..ops.quant import default_cold_budget
    n = n_id.shape[0]
    if budget is None:
        budget = default_cold_budget(n)
    if budget >= n:
        return masked_feature_gather(feat, n_id, feature_order)
    valid = n_id >= 0

    def narrow(_):
        # uniq's int32-max fill clips to the LAST feature row — those
        # slots hold real (unused) data, NOT zeros: inv never points a
        # valid position at them, and invalid positions carry in-range-
        # garbage inv that the re-mask below zeroes
        rows_u = masked_feature_gather(feat, uniq, feature_order)
        x = jnp.take(rows_u, inv, axis=0)
        return x * valid.astype(x.dtype)[:, None]

    # one scope over the unique table, both reads and the expansion
    with profiling.scope(profiling.QT_GATHER):
        uniq, inv, n_uniq = unique_within_budget(n_id, budget, valid=valid,
                                                 collector=collector)
        return jax.lax.cond(n_uniq > budget,
                            lambda _: masked_feature_gather(feat, n_id,
                                                            feature_order),
                            narrow, None)


def feature_splice(feature):
    """Splice a ``Feature`` store's fused tiered lookup into a step
    program (``build_train_step(gather=)``, ``build_serve_step(gather=)``;
    ``ServeEngine`` calls it for a store it is given): returns
    ``(feat_args, forder, gather)`` where ``feat_args`` is the
    ``(device_part, host_tier)`` pytree the step passes through and
    ``gather`` runs the store's own traceable lookup body (masked,
    dedup_cold, quantized tiers — all its conventions) on it. A pure-HBM
    store needs none: ``gather`` is None, ``feat_args`` its device part."""
    from ..ops import quant
    if feature.mmap_array is not None:
        raise ValueError(
            "a step program cannot fuse a disk/mmap-tier Feature store "
            "(its cold reads are host-driven); splice a store whose "
            "tiers are HBM/host arrays")
    host = feature._host_offload
    if host is None and feature.host_part is not None:
        # numpy cold tier: commit once so the lookup fuses — a step
        # cannot afford a per-batch host round trip. Commit to
        # PINNED HOST memory (the store's own offload placement), not
        # device HBM: the cold tier is cold precisely because it does
        # not fit there. Loud jnp fallback only where host-offload is
        # unusable (CPU: host and device memory are the same arena).
        from ..utils.placement import pinned_put
        devs = jax.devices()
        dev = devs[feature.rank if feature.rank < len(devs) else 0]
        leaves, tree = jax.tree_util.tree_flatten(feature.host_part)
        got = pinned_put(leaves, dev, True, "the spliced cold tier",
                         mesh=feature.mesh, usage="gather")
        if got is not None:
            host = jax.tree_util.tree_unflatten(tree, got)
        else:
            host = quant.tree_map_tier(jnp.asarray, feature.host_part)
    if host is None:
        # pure-HBM store: the default masked gather over the cache part
        # IS the store's lookup (same translate + clip + mask semantics)
        return feature.device_part, feature.feature_order, None
    raw = feature._lookup_tiered_raw

    def gather(feat_args, n_id, forder, collector=None):
        dev, host_t = feat_args
        if collector is None:
            return raw(dev, host_t, n_id, forder, True)
        rows, vec = raw(dev, host_t, n_id, forder, True, True)
        collector.absorb(vec)
        return rows
    # for the fused walk: its kernel reads ``feat_args[0]``, the hot tier,
    # and routes the picks beyond these rows back through ``gather``
    gather.hot_rows = 0 if feature.device_part is None \
        else quant.tier_rows(feature.device_part)
    return (feature.device_part, host), feature.feature_order, gather


# The walk's knobs: declared, defaulted and documented here and nowhere
# else. A builder takes them as a ``**walk`` it does not open and names
# (``takes``) the ones its step can honour.
KNOBS = {
    "method": ("exact", (
        '``"exact"`` (i.i.d. Fisher-Yates subsets), ``"rotation"`` or '
        '``"window"`` (wide row fetches; see ``ops.sample_multihop``). The '
        "windowed methods REQUIRE the step's ``indices_rows`` operand: the "
        "per-epoch shuffled ``as_index_rows`` view (refresh with "
        '``reshuffle_csr``). ``"exact"`` takes it optionally, as a layout '
        "view of the UN-shuffled indices, which switches the scattered draw "
        "to the wide-fetch exact path (same draw, fewer scattered loads).")),
    "indices_stride": (None, (
        "set to the build width (128) when ``indices_rows`` is the "
        "``as_index_rows_overlapping`` view: one row gather per seed, 2x "
        "index memory. Read only where ``indices_rows`` is given.")),
    "hub_frac": (None, (
        "the cached ``CSRTopo.exact_bucket_meta().frac``: sizes the "
        'wide-exact hub budget when ``"exact"`` gets an ``indices_rows``.')),
    "dedup_gather": (None, (
        "``True`` or an int unique budget: the frontier's rows come through "
        "``dedup_feature_gather`` (one read per distinct node, not per "
        "frontier slot).")),
    "fused_hot_hop": (False, (
        "``True`` swaps the sample -> gather pair for the fused Pallas walk "
        "(``ops.pallas.fused.fused_multihop``): interior hops run the "
        "sampling-only kernel, the leaf hop fuses reservoir sampling with "
        "the per-pick feature-row DMA (int8 dequant in-register), and "
        'frontier ids never reach HBM at ANY hop. ``"exact"`` only, any '
        "``sizes`` ladder; composes with none of ``dedup_gather``, "
        "``indices_stride``, ``hub_frac``, ``indices_rows``. The draw is the "
        "KERNEL's stream (hop ``i`` seeded from ``fold_in(key, i)``; "
        "``ops/pallas/_dma.py`` picks generator and interpret mode from the "
        "platform), so results are bit-comparable with the split Pallas "
        "oracle (``fused_multihop_reference``), not with the "
        "``sample_multihop`` path. Where the rows come through a "
        "cross-shard exchange the kernel only samples "
        "(``fused_sample_multihop``); over a tiered store it reads the hot "
        "tier and the cold slots are overlaid from the store's own lookup.")),
    "fused_row_cap": (2048, (
        "the fused walk's in-VMEM CSR window per seed (degrees beyond it "
        "are truncated: the sample kernel's contract).")),
}
ALL_KNOBS = tuple(KNOBS)
SAMPLING_KNOBS = ("method", "indices_stride", "hub_frac")


def walk_doc(takes: Sequence[str]) -> str:
    """The docstring paragraph of a builder that takes ``takes``."""
    head = ("``**walk``: the knobs of the neighbourhood walk "
            "(``parallel.frontier.Walk.of`` declares, defaults and validates "
            "them; any other keyword is a ``TypeError``):")
    fill = functools.partial(textwrap.fill, width=76,
                             subsequent_indent=" " * 8)
    return "\n\n" + "\n".join(
        [fill(head, initial_indent=" " * 4, subsequent_indent=" " * 4)] +
        [fill(f"``{name}={KNOBS[name][0]!r}``: {KNOBS[name][1]}",
              initial_indent=" " * 6) for name in takes])


def documented(*paragraphs: str):
    """Stamp shared paragraphs onto a builder's docstring, so each
    contract is written once."""
    def stamp(builder):
        if builder.__doc__:                # None under python -OO
            builder.__doc__ += "".join(paragraphs)
        return builder
    return stamp


_WINDOWED = ("rotation", "window")


@dataclasses.dataclass(frozen=True)
class Walk:
    """What one step's walk has decided; ``Walk.of`` is the only
    constructor."""
    who: str                       # the builder, for messages
    sizes: tuple
    method: str
    indices_stride: int | None
    hub_frac: float | None
    gather: Callable | None        # None: ``masked_feature_gather``
    row_cap: int | None            # set: the fused Pallas walk
    hot_rows: int | None           # fused over a tiered gather: its hot tier

    @classmethod
    def of(cls, who: str, takes: Sequence[str], sizes: Sequence[int],
           knobs: dict, gather: Callable | None = None,
           exchange: tuple | None = None) -> "Walk":
        """``knobs`` is the builder's ``**walk``; one outside ``takes``
        is refused by name. ``gather`` is a caller's callable (see the
        protocol above); a tiered one says how many rows its hot tier
        holds (``gather.hot_rows``). ``exchange=(hosts, exchange_cap,
        batch)`` marks it as a cross-shard exchange that takes
        ``exchange_cap=``: ``True`` sizes the cap from the frontier of
        ``batch`` seeds and the host count, an int pins it."""
        for name in knobs:
            if name not in takes:
                raise TypeError(
                    f"{who}() got an unexpected keyword argument "
                    f"{name!r}" + (" (a knob of the walk that this "
                                   "step cannot honour)"
                                   if name in KNOBS else ""))
        k = {name: knobs.get(name, KNOBS[name][0]) for name in KNOBS}
        sizes = tuple(sizes)
        fused = bool(k["fused_hot_hop"])
        if fused:
            if not sizes:
                raise ValueError(
                    "fused_hot_hop needs at least one hop in sizes")
            if k["method"] != "exact":
                raise ValueError("fused_hot_hop requires method='exact', "
                                 f"got {k['method']!r}")
            if k["dedup_gather"] is not None:
                raise ValueError(
                    "fused_hot_hop gathers in-kernel (one DMA per frontier "
                    "slot); dedup_gather does not compose with it")
            if k["indices_stride"] is not None or k["hub_frac"] is not None:
                raise ValueError(
                    "fused_hot_hop takes neither indices_stride nor "
                    "hub_frac (no wide-exact/rotation layout views in the "
                    "fused kernel)")
        if gather is None and k["dedup_gather"] is not None:
            budget = k["dedup_gather"]
            gather = functools.partial(
                dedup_feature_gather,
                budget=None if budget is True else int(budget))
        hot_rows = getattr(gather, "hot_rows", None)
        if exchange is not None:
            hosts, cap, batch = exchange
            if cap is True:
                cap = default_exchange_cap(
                    layer_shapes(batch, sizes)[-1].n_id_cap, hosts)
            elif cap is not None:
                cap = int(cap)
            gather = functools.partial(gather, exchange_cap=cap)
        elif fused and gather is not None and hot_rows is None:
            raise ValueError(
                "fused_hot_hop over a spliced tiered gather needs the "
                "gather's hot_rows (the hot-tier row count) to route "
                "cold picks back through the tiered lookup")
        return cls(who, sizes, k["method"], k["indices_stride"],
                   k["hub_frac"], gather,
                   int(k["fused_row_cap"]) if fused else None,
                   hot_rows if fused else None)

    def check_rows(self, indices_rows) -> None:
        """The ``indices_rows`` contract of ``method``, for a step to ask
        before it picks an arity and for the walk itself."""
        if indices_rows is None:
            if self.method in _WINDOWED:
                raise TypeError(
                    f"{self.who}: method={self.method!r} requires "
                    "indices_rows (the shuffled as_index_rows/"
                    "as_index_rows_overlapping view; refresh per epoch "
                    "via permute_csr)")
        elif self.row_cap is not None:
            raise TypeError(
                f"{self.who}: fused_hot_hop does not take indices_rows "
                "(the fused walk does its own in-kernel CSR reads every "
                "hop)")


@hot_path
def walk_frontier(walk: Walk, feat, forder, indptr, indices, seeds, key,
                  indices_rows=None, collector=None, rows_collector=None):
    """Draw the frontier of ``seeds`` and read its rows: returns
    ``(n_id, x, layers)``, the final frontier (static cap, -1 fill), its
    ``[cap, dim]`` feature block (zero on the padding) and the per-hop
    ``LayerSample``s in sampling order (``layers_to_adjs`` turns them
    into the model's ``Adj``s). A caller that wants the sample it drew
    has it in ``n_id``.

    Batch contract: ``seeds`` are distinct valid ids with -1 padding at
    the TAIL only (labels and logits are indexed by batch position, and
    interior holes would shift seeds to rank-based rows), so hop 0 takes
    the cheaper dense-seed compaction. Hop ``i`` draws from
    ``fold_in(key, i)``: a caller folds its shard or splits its chain
    BEFORE it calls.

    ``collector`` takes the frontier's fill and the gather's counts,
    unless ``rows_collector`` takes the latter (the sharded serve step
    counts its replicated sampling apart from each shard's exchange).

    ``indices`` may be an ``ops.relation.TypedIndices``: the graph is
    then typed, and every hop's ``LayerSample.rel`` holds each edge
    slot's relation. It may be an ``ops.relation.RelationIndices``
    (``indptr`` the tuple of its relations' row pointers): every relation
    then draws its own ``min(degree, fanout)`` a target
    (``sample_typed_multihop``), each node type keeps a frontier of its
    own, ``layers`` are ``TypedLayer``s and ``x`` is the tuple of the
    types' row blocks (None where a type has no frontier), each read
    from the one table by its global ids."""
    if rows_collector is None:
        rows_collector = collector
    walk.check_rows(indices_rows)
    if isinstance(indices, RelationIndices):
        if walk.method != "exact" or indices_rows is not None \
                or walk.row_cap is not None:
            raise ValueError(
                f"{walk.who}: a graph whose relations keep their own lists "
                "takes the exact draw, without indices_rows or the fused "
                "walk")
        n_id, layers = sample_typed_multihop(
            tuple(indptr), indices, seeds, walk.sizes, key,
            collector=collector)
        gather = walk.gather or masked_feature_gather
        x, at = [], 0
        for f in layers[-1].frontier:
            if f is None:
                x.append(None)
                continue
            x.append(gather(feat, n_id[at:at + f.shape[0]], forder,
                            collector=rows_collector))
            at += f.shape[0]
        return n_id, tuple(x), layers
    indices, types = split_typed(indices)
    if walk.row_cap is None:
        n_id, layers = sample_multihop(
            indptr, indices, seeds, walk.sizes, key, method=walk.method,
            indices_rows=indices_rows,
            indices_stride=(walk.indices_stride
                            if indices_rows is not None else None),
            seeds_dense=True, hub_frac=walk.hub_frac, collector=collector,
            node_types=types)
        x = (walk.gather or masked_feature_gather)(
            feat, n_id, forder, collector=rows_collector)
        return n_id, x, layers

    if types is not None:
        raise ValueError(
            f"{walk.who}: fused_hot_hop draws no relations; a typed graph "
            "takes the sample_multihop walk")
    # the fused Pallas walk: frontier ids stay on chip at EVERY hop
    from ..ops.pallas.fused import (fused_multihop, fused_sample_multihop,
                                    pad_indices)
    padded = pad_indices(indices, walk.row_cap)
    if walk.gather is not None and walk.hot_rows is None:
        # the rows come through an exchange: the kernel only samples
        n_id, layers = fused_sample_multihop(indptr, padded, seeds,
                                             walk.sizes, key,
                                             row_cap=walk.row_cap)
        count_walk(collector, n_id, layers)
        return n_id, walk.gather(feat, n_id, forder,
                                 collector=rows_collector), layers
    # the leaf hop samples AND gathers in one kernel; ``x`` is
    # bit-identical to ``masked_feature_gather`` over the same picks
    tiered = walk.gather is not None
    n_id, layers, x = fused_multihop(
        indptr, padded, seeds, feat[0] if tiered else feat,
        list(walk.sizes), key, row_cap=walk.row_cap, feature_order=forder,
        hot_rows=walk.hot_rows)
    count_walk(collector, n_id, layers)
    if tiered:
        # cold fix-up: the kernel zeroed every frontier slot whose
        # translated row falls outside the hot tier; those slots — and
        # ONLY those — come from the store's unchanged tiered lookup
        # (hot slots masked to -1 so the store reads nothing for them).
        # The FINAL layer's n_id is the whole walk's frontier.
        ids = layers[-1].n_id
        t = forder[jnp.clip(ids, 0)] if forder is not None \
            else jnp.clip(ids, 0)
        is_cold = (ids >= 0) & (t >= walk.hot_rows)
        with profiling.scope(profiling.QT_GATHER):
            x_cold = walk.gather(feat, jnp.where(is_cold, ids, -1), forder,
                                 collector=rows_collector)
        x = jnp.where(is_cold[:, None], x_cold, x)
    return n_id, x, layers
