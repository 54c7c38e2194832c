"""PyG-compatible k-hop neighbor samplers, TPU-native.

Re-provides the capabilities of the reference ``GraphSageSampler`` /
``MixedGraphSageSampler`` / ``SampleJob`` (pyg/sage_sampler.py:40-375) with
a jit-first design:

- the whole multi-hop sample (every layer's sample + compaction) is ONE
  jitted XLA program per (batch_size,) — the reference crosses the
  Python->C++ boundary twice per layer (survey §3.1); here there are zero
  per-layer host round trips.
- output shapes are static (capacity + valid counts); invalid slots hold
  -1. ``Adj.size`` reports capacities; masks derive from ``edge_index >= 0``.
- modes: ``HBM`` (topology resident in device HBM, ≈ reference GPU/DMA),
  ``HOST`` (topology in host memory, device pulls on demand, ≈ UVA
  zero-copy), ``CPU`` (sampling on host CPU via the native C++ engine).
- RNG is an explicit, reproducible key chain instead of ad-hoc per-thread
  curand seeds (quiver.cu.hpp:129-135).
"""

from __future__ import annotations

import time
from typing import Generic, List, NamedTuple, Sequence, TypeVar

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.sample import compact_layer, sample_layer, sample_prob
from ..utils import CSRTopo

T_co = TypeVar("T_co", covariant=True)


from ..utils.placement import pinned_put as _pinned_put  # shared helper


@jax.tree_util.register_pytree_node_class
class Adj:
    """One message-passing hop, PyG orientation (source -> target).

    edge_index: [2, cap_edges] int32, -1 fill; row 0 = source (neighbor)
                local id, row 1 = target (seed) local id.
    e_id:       [cap_edges] global edge id per sampled edge (-1 fill)
                when the sampler tracks edge ids
                (``GraphSageSampler(..., with_eid=True)``); ``None``
                otherwise (the reference ships the same shape empty,
                sage_sampler.py:143).
    mask:       [cap_edges] bool validity of each edge slot (equivalent
                to ``edge_index[0] >= 0``; kept explicit so consumers
                don't have to rederive it).
    size:       (cap_source_nodes, cap_target_nodes) static capacities —
                pytree aux data, so Adjs cross jit boundaries safely.
    fanout:     static like ``size``; ``None`` promises nothing. An int
                ``k`` states the slot layout and nothing else: every
                valid edge slot ``e`` targets node ``e // k``, and
                ``edge_index.shape[1] == size[1] * k``. Consumers may
                then reduce over the fanout axis without reading row 1
                (``models.sage.masked_mean_aggregate``). Set where the
                producer guarantees it (``parallel.train.layers_to_adjs``).
    valid_targets: [] int32, how many of the ``size[1]`` target slots
                hold a node. Valid targets come first (a hop's valid
                seeds keep the local ids ``[0, count)``), so target ``t``
                is real iff ``t < valid_targets``; ``target_mask()`` says
                it row by row. A real target may have no valid edge (an
                isolated node): edge validity does not tell the two
                apart, which is why a model that takes statistics over a
                batch's rows, or gives a target an edge of its own, reads
                this. ``None`` promises nothing: every target slot is
                then taken for a node.
    relation:   [cap_edges] int32, the relation of each edge slot (-1
                fill) where the graph is typed (``ops.relation``):
                ``parallel.train.layers_to_adjs`` states it for every
                step builder. ``None`` on an untyped graph. A static
                ``int`` where every slot holds the one relation: a block
                of the per-relation walk (``RelationBlocks``); it is then
                aux data, not a leaf.
    relations:  static like ``size``: how many relations the typed graph
                has (``NodeTypes.num_relations``), stated with
                ``relation``; ``None`` on an untyped graph.

    Supports PyG-style destructuring: ``edge_index, e_id, size = adj``.
    """

    __slots__ = ("edge_index", "e_id", "size", "mask", "fanout",
                 "valid_targets", "relation", "relations")

    def __init__(self, edge_index, e_id, size, mask=None, fanout=None,
                 valid_targets=None, relation=None, relations=None):
        self.edge_index = edge_index
        self.e_id = e_id
        self.size = tuple(size)
        self.mask = mask if mask is not None else edge_index[0] >= 0
        self.fanout = fanout
        self.valid_targets = valid_targets
        self.relation = relation
        self.relations = relations

    def target_mask(self):
        """``[size[1]]`` bool: which target slots hold a node."""
        if self.valid_targets is None:
            return jnp.ones((self.size[1],), bool)
        return jnp.arange(self.size[1], dtype=jnp.int32) < self.valid_targets

    def __iter__(self):
        return iter((self.edge_index, self.e_id, self.size))

    def to(self, *args, **kwargs):  # API compat; placement is explicit in jax
        return self

    def tree_flatten(self):
        one = self.relation if isinstance(self.relation, int) else None
        return ((self.edge_index, self.e_id, self.mask, self.valid_targets,
                 None if one is not None else self.relation),
                (self.size, self.fanout, self.relations, one))

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        size, fanout, relations, one = aux
        return cls(leaves[0], leaves[1], size, leaves[2], fanout, leaves[3],
                   leaves[4] if one is None else one, relations)


@jax.tree_util.register_pytree_node_class
class RelationBlocks:
    """One hop of a per-relation walk as the model reads it
    (``parallel.frontier.layers_to_adjs`` over ``TypedLayer``s): ``adjs``,
    one ``Adj`` a relation drawn at the hop (its ``relation`` the static
    index into ``types.relations``, ``fanout`` and ``valid_targets``
    stated, its sources local ids into the source type's frontier after
    the hop, its targets into the target type's before it). Static:
    ``sources[t]`` the rows of type ``t``'s frontier after the hop (the
    layer's inputs), ``targets[t]`` before it (the layer's outputs), 0
    where the type has none; ``types`` the ``NodeTypes``."""

    __slots__ = ("adjs", "sources", "targets", "types")

    def __init__(self, adjs, sources, targets, types):
        self.adjs = tuple(adjs)
        self.sources = tuple(int(c) for c in sources)
        self.targets = tuple(int(c) for c in targets)
        self.types = types

    def tree_flatten(self):
        return (self.adjs,), (self.sources, self.targets, self.types)

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        return cls(leaves[0], *aux)


class _LayerShape(NamedTuple):
    num_seeds: int
    fanout: int
    n_id_cap: int


def layer_shapes(batch_size: int, sizes: Sequence[int]) -> List[_LayerShape]:
    shapes = []
    s = batch_size
    for k in sizes:
        cap = s + s * k
        shapes.append(_LayerShape(num_seeds=s, fanout=k, n_id_cap=cap))
        s = cap
    return shapes


class GraphSageSampler:
    """k-hop sampler returning ``(n_id, batch_size, adjs)`` like PyG's
    ``NeighborSampler`` (reference: sage_sampler.py:118-147)."""

    def __init__(self, csr_topo: CSRTopo, sizes: Sequence[int],
                 device=None, mode: str = "HBM", seed: int = 0,
                 edge_weight=None, sampling: str = "exact",
                 with_eid: bool = False, layout: str = "pair",
                 shuffle: str = "sort", allow_fallback: bool = True,
                 wide_exact: bool = True,
                 collect_metrics: bool = False):
        if mode not in ("HBM", "HOST", "CPU", "UVA", "GPU"):
            raise ValueError(f"unknown sampler mode {mode!r}")
        # accept reference mode names: UVA -> HOST tier, GPU -> HBM
        mode = {"UVA": "HOST", "GPU": "HBM"}.get(mode, mode)
        self.mode = mode
        self.sizes = list(sizes)
        self.csr_topo = csr_topo
        self.device = device
        # CSR-slot-aligned edge weights => weighted (attention) sampling;
        # use ops.weighted.csr_weights_from_eid for COO-ordered weights.
        # CPU mode draws through the native engine's weighted path
        # (qt_sample_layer_weighted) with the same row_cap truncation,
        # so host and device draws share one distribution. Length is
        # validated HERE: the native engine reads weights[slot] through
        # a raw pointer, so a short array would be an out-of-bounds
        # read, not a Python exception.
        if edge_weight is not None:
            e = int(csr_topo.edge_count)
            got = int(np.shape(edge_weight)[0])
            if got != e:
                raise ValueError(
                    f"edge_weight has {got} entries but the topology "
                    f"has {e} edges (weights are CSR-slot-aligned; use "
                    "ops.csr_weights_from_eid for COO-ordered weights)")
        self.edge_weight = edge_weight
        self._weight_np = None     # cached f32 copy for the CPU engine
        self._eid_np = None        # cached eid map for the CPU engine
        # sampling="rotation": ~3x faster device path (wide row fetches
        # per seed over a shuffled CSR copy instead of k scattered
        # loads); "window" costs the same fetches but draws exact i.i.d.
        # k-subsets of each seed's >=129-entry shuffled window (subset-
        # independent within an epoch, exact for deg <= window). Both
        # shuffle once at init; call reshuffle() at each epoch boundary
        # so draws stay marginally uniform.
        if sampling not in ("exact", "rotation", "window"):
            raise ValueError(f"unknown sampling method {sampling!r}")
        if sampling in ("rotation", "window") and mode == "CPU":
            sampling = "exact"   # the CPU engine has its own sampler
        # weighted + rotation/window = the windowed weighted draw
        # (sample_layer_weighted_window): weight-exact for deg <= 129,
        # in-window renormalization bias on hubs (see its docstring) —
        # an explicit caller choice, not a silent fallback
        if sampling in ("rotation", "window") and \
                max(sizes, default=0) > 128:
            raise ValueError(
                f"{sampling} sampling supports fanouts <= 128")
        # with_eid: stamp every sampled edge with its global edge id
        # (CSRTopo.eid -> original COO position; CSR slot if no eid map),
        # delivered in Adj.e_id. Costs one scattered gather per edge, so
        # it is opt-in. CPU mode: the native engine emits each pick's
        # CSR slot (qt_sample_layer* out_slots), mapped through
        # CSRTopo.eid the same way.
        self.with_eid = with_eid
        self.sampling = sampling
        # layout="overlap": rotation/window do ONE 256-wide row gather
        # per seed instead of two 128-wide (fastest measured config,
        # docs/introduction.md) at 2x index memory. shuffle="butterfly":
        # the ~40x cheaper epoch reshuffle (masked swap network composed
        # across epochs) instead of the exact per-epoch sort.
        if layout not in ("pair", "overlap"):
            raise ValueError(f"unknown rotation layout {layout!r}")
        if shuffle not in ("sort", "butterfly"):
            raise ValueError(f"unknown shuffle {shuffle!r}")
        if shuffle == "butterfly" and edge_weight is not None and \
                sampling in ("rotation", "window"):
            # the WEIGHTED windowed draw anchors its window at the
            # segment start and relies on the reshuffle to re-place hub
            # neighbors uniformly; butterfly moves an element <= 255
            # positions per epoch, so a hub's far neighbors would stay
            # unreachable for many epochs — silent sampling bias.
            # (Unweighted rotation AND window are safe: both walk the
            # whole segment with a random per-draw anchor.)
            raise ValueError(
                "shuffle='butterfly' cannot provide the weighted "
                "windowed draw's mandatory hub re-placement (bounded "
                "per-epoch displacement; it anchors at the segment "
                "start) — use shuffle='sort' for weighted "
                "rotation/window")
        self.layout = layout
        self.shuffle = shuffle
        # HOST-mode placement on backends without pinned_host memory:
        # True = loud logged fallback to default placement, False = raise
        self.allow_fallback = allow_fallback
        # wide_exact: exact mode's wide-fetch path needs a layout view of
        # the indices — +E (pair) or +2E (overlap) memory in the
        # topology's tier. False keeps the zero-extra-copy scattered draw
        # (same statistics, k scattered loads per seed) for graphs whose
        # indices already fill most of HBM.
        self.wide_exact = wide_exact
        # collect_metrics: the jitted sample program also emits the
        # metrics.NUM_COUNTERS device counter vector (frontier fill vs
        # the static cap); sample() stashes it on ``self.last_counters``
        # — a device array, read lazily (StepStats.add_counters) so
        # sampling stays sync-free. CPU mode has no jitted program and
        # leaves last_counters as None.
        self.collect_metrics = bool(collect_metrics)
        self.last_counters = None
        self._key = jax.random.key(seed)
        self._placed = None
        self._weight_placed = None
        self._rot = None          # shuffled row view (pair or overlap)
        self._exact_rows = None   # un-shuffled row view (wide exact path)
        self._rot_w = None        # co-shuffled weight row view
        self._rot_eid = None      # slot->edge-id map in permuted coords
        self._permuted = None     # flat permuted indices (butterfly state)
        self._permuted_w = None   # flat co-permuted weights (butterfly)
        self._row_ids = None
        self._fns = {}

    # -- placement ----------------------------------------------------------
    def lazy_init_quiver(self):
        if self._placed is not None:
            return
        if self.mode == "CPU":
            self._placed = (np.asarray(self.csr_topo.indptr),
                            np.asarray(self.csr_topo.indices))
            return
        if getattr(self.csr_topo, "requires_host_sampling", lambda: False)():
            raise ValueError(
                "topology offsets exceed int32 in 32-bit jax mode; device "
                "sampling would silently wrap them — use mode='CPU' (the "
                "native host engine handles int64 offsets) or enable "
                "jax_enable_x64")
        dev = self.device
        if dev is None or isinstance(dev, int):
            platforms = [d for d in jax.devices()]
            dev = platforms[self.device or 0]
        if self.mode == "HOST":
            # host-resident topology (UVA analogue): keep arrays in host
            # memory; XLA streams them to device per sample step
            got = _pinned_put(
                [self.csr_topo.indptr, self.csr_topo.indices], dev,
                self.allow_fallback, "the topology")
            placed = (tuple(got) if got is not None else
                      (np.asarray(self.csr_topo.indptr),
                       np.asarray(self.csr_topo.indices)))
        else:
            placed = (jax.device_put(self.csr_topo.indptr, dev),
                      jax.device_put(self.csr_topo.indices, dev))
        self._placed = placed

    def _ensure_weights_placed(self):
        """Materialize the edge-weight array once — pinned host in HOST
        mode (E-sized arrays don't fit HBM there; same placement as the
        indices). The single entry point for sample() AND reshuffle(),
        whichever runs first."""
        if self._weight_placed is not None:
            return
        self._weight_placed = jnp.asarray(self.edge_weight)
        if self.mode == "HOST":
            got = _pinned_put([self._weight_placed],
                              list(self._weight_placed.devices())[0],
                              self.allow_fallback, "the edge weights")
            if got is not None:
                self._weight_placed = got[0]

    @staticmethod
    def _rows_np(flat, width=128, overlap=False):
        """numpy twin of ops.as_index_rows(_overlapping) — same layout
        formulas (asserted equal in tests) built WITHOUT touching device
        memory, for HOST mode where the E/2E view must never transit
        HBM."""
        e = flat.shape[0]
        nrows = (e + 2 * width - 1) // width + 1
        pad = nrows * width - e
        base = np.concatenate(
            [flat, np.zeros((pad,), flat.dtype)]).reshape(nrows, width)
        if not overlap:
            return base
        nxt = np.concatenate([base[1:], np.zeros_like(base[:1])])
        return np.concatenate([base, nxt], axis=1)

    def _ensure_exact_rows(self):
        """Layout view (pair/overlap per ``self.layout``) of the placed,
        UN-shuffled indices — the wide-fetch exact path's input. Built
        once. HOST mode builds it host-side (numpy) and pins it WITHOUT
        ever committing the E/2E array to device HBM — the mode exists
        because the topology doesn't fit there."""
        if self._exact_rows is not None:
            return self._exact_rows
        if self.mode == "HOST":
            rows_np = self._rows_np(np.asarray(self._placed[1]),
                                    overlap=self.layout == "overlap")
            dev = self.device
            if dev is None or isinstance(dev, int):
                dev = jax.devices()[self.device or 0]
            got = _pinned_put([rows_np], dev, self.allow_fallback,
                              "the exact rows view")
            # fallback: commit ONCE to default placement — caching raw
            # numpy would re-transfer the E/2E view every sample()
            rows = got[0] if got is not None else jnp.asarray(rows_np)
        else:
            from ..ops.sample import (as_index_rows,
                                      as_index_rows_overlapping)
            as_rows = (as_index_rows_overlapping
                       if self.layout == "overlap" else as_index_rows)
            rows = as_rows(jnp.asarray(self._placed[1]))
        self._exact_rows = rows
        return rows

    def reshuffle(self, key=None):
        """Re-shuffle every CSR row's neighbor order (rotation sampling's
        freshness source). Called automatically on first sample; call at
        each epoch boundary thereafter. shuffle="sort": exact uniform
        per-row shuffle (one 2-key sort over the edge array, ~650ms per
        100M edges). shuffle="butterfly": the ~40x cheaper masked swap
        network, composed across calls (this method keeps the running
        permuted state and the composed edge-id map for you)."""
        from ..ops.sample import (as_index_rows, as_index_rows_overlapping,
                                  butterfly_shuffle, edge_row_ids,
                                  permute_csr)
        self.lazy_init_quiver()
        indptr, indices = self._placed
        indptr = jnp.asarray(indptr)
        indices = jnp.asarray(indices)
        if self._row_ids is None:
            self._row_ids = jax.jit(edge_row_ids, static_argnums=1)(
                indptr, int(indices.shape[0]))
        pkey = key if key is not None else self.next_key()
        base = self.csr_topo.eid if self.with_eid else None
        weighted = self.edge_weight is not None
        bfly = self.shuffle == "butterfly"
        if weighted:
            self._ensure_weights_placed()
        if bfly:
            # composed state: feed the previous epoch's outputs back in
            src = self._permuted if self._permuted is not None else indices
            wsrc = (self._permuted_w if self._permuted_w is not None
                    else self._weight_placed)
        else:
            src, wsrc = indices, self._weight_placed
        extra = (wsrc,) if weighted else None
        fn = butterfly_shuffle if bfly else permute_csr
        out = fn(src, self._row_ids, pkey, with_slot_map=self.with_eid,
                 extra=extra)
        wp = None
        if self.with_eid and weighted:
            permuted, (wp,), smap = out
        elif self.with_eid:
            permuted, smap = out
        elif weighted:
            permuted, (wp,) = out
        else:
            permuted = out
        if self.with_eid:
            from ..ops.sample import compose_slot_map
            self._rot_eid = compose_slot_map(self._rot_eid, smap, base,
                                             bfly)
        if bfly:
            # (in HOST mode these are re-placed on pinned host in the
            # placement block below, AFTER the rows views are built —
            # pinning first would bounce E-sized arrays
            # host->device->host once per epoch)
            self._permuted = permuted
            self._permuted_w = wp
        as_rows = (as_index_rows_overlapping if self.layout == "overlap"
                   else as_index_rows)
        rows = as_rows(permuted)
        self._rot_w = as_rows(wp) if weighted else None
        if self.mode == "HOST":
            # keep the shuffled topology host-resident (the mode exists
            # because indices don't fit HBM); the sampler's row fetches
            # then stream from host like the exact path's. The E-sized
            # edge-id map and the butterfly's persistent permuted state
            # get the same placement for the same reason.
            arrays = [rows, self._rot_w, self._rot_eid, self._permuted,
                      self._permuted_w]
            got = _pinned_put([a for a in arrays if a is not None],
                              list(rows.devices())[0],
                              self.allow_fallback, "the shuffled rows")
            if got is not None:
                it = iter(got)
                (rows, self._rot_w, self._rot_eid, self._permuted,
                 self._permuted_w) = [
                    next(it) if a is not None else None for a in arrays]
        self._rot = rows

    def _exact_hub_frac(self):
        """Static hub fraction sizing the wide-exact scattered-load
        budget — the degree-bucket split computed once per graph and
        cached on the topology (CSRTopo.exact_bucket_meta); None when
        the wide-fetch exact path is not in play."""
        if self.sampling != "exact" or not self.wide_exact \
                or self.edge_weight is not None or self.mode == "CPU":
            return None
        return float(self.csr_topo.exact_bucket_meta(step=128).frac)

    # -- core ---------------------------------------------------------------
    def _build_fn(self, batch_size: int):
        sizes = self.sizes
        weighted = self.edge_weight is not None
        method = self.sampling
        hub_frac = self._exact_hub_frac()
        eid_mode = "none"
        if self.with_eid:
            # rotation/window always need the co-permuted map; otherwise
            # the topo's eid map if present, else raw CSR slots
            eid_mode = ("map" if (method in ("rotation", "window")
                                  or self.csr_topo.eid is not None)
                        else "slots")

        stride = 128 if self.layout == "overlap" else None
        collect = self.collect_metrics

        def run(indptr, indices, seeds, key, weights=None, rows=None,
                eid_arr=None, w_rows=None):
            from ..ops.sample_multihop import sample_multihop
            eid = {"none": None, "slots": True, "map": eid_arr}[eid_mode]
            col = None
            if collect:
                from ..metrics import Collector
                col = Collector()
            out = sample_multihop(indptr, indices, seeds, sizes, key,
                                  edge_weight=weights if weighted else None,
                                  method=method, indices_rows=rows,
                                  eid=eid,
                                  indices_stride=stride if rows is not None
                                  else None,
                                  weight_rows=w_rows, hub_frac=hub_frac,
                                  collector=col)
            if collect:
                return out + (col.counters(),)
            return out

        return jax.jit(run)

    def _fn_for(self, batch_size: int):
        # keyed on collect_metrics too: the jitted fn's output arity is
        # baked in at build time, so toggling the knob must not reuse a
        # cached fn with the other arity
        key = (batch_size, bool(self.collect_metrics))
        fn = self._fns.get(key)
        if fn is None:
            fn = self._build_fn(batch_size)
            self._fns[key] = fn
        return fn

    def next_key(self):
        self._key, sub = jax.random.split(self._key)
        return sub

    def sample(self, input_nodes):
        """Returns (n_id, batch_size, adjs) — adjs ordered outermost hop
        first, ready for layer-wise message passing (PyG convention)."""
        self.lazy_init_quiver()
        seeds = jnp.asarray(input_nodes, dtype=jnp.int32)
        bs = int(seeds.shape[0])
        indptr, indices = self._placed
        if self.mode == "CPU":
            return self._sample_cpu(seeds, bs)
        fn = self._fn_for(bs)
        if self.edge_weight is not None:
            self._ensure_weights_placed()
        if self.sampling in ("rotation", "window"):
            if self._rot is None:
                self.reshuffle()
            rows = self._rot
            w_rows = self._rot_w
            eid_arr = self._rot_eid
        else:
            # exact mode: the wide-fetch path wants a layout view of the
            # SAME un-shuffled indices (no reshuffle needed — Fisher-
            # Yates positions are uniform under any fixed order); the
            # weighted pool draw has no use for it
            rows = (self._ensure_exact_rows()
                    if self.edge_weight is None and self.wide_exact
                    else None)
            w_rows = None
            eid_arr = (jnp.asarray(self.csr_topo.eid)
                       if self.with_eid and self.csr_topo.eid is not None
                       else None)
        out = fn(jnp.asarray(indptr), jnp.asarray(indices),
                 seeds, self.next_key(), self._weight_placed, rows,
                 eid_arr, w_rows)
        if self.collect_metrics:
            n_id, layers, self.last_counters = out
        else:
            n_id, layers = out
        shapes = layer_shapes(bs, self.sizes)
        adjs = []
        for layer, shape in zip(layers, shapes):
            edge_index = jnp.stack([layer.col, layer.row])
            adjs.append(Adj(edge_index=edge_index,
                            e_id=layer.e_id,
                            size=(shape.n_id_cap, shape.num_seeds),
                            mask=layer.col >= 0,
                            valid_targets=layer.seed_count))
        return n_id, bs, adjs[::-1]

    def _sample_cpu(self, seeds, bs):
        from ..native import cpu_sample_multihop
        indptr, indices = self._placed
        if self.edge_weight is not None and self._weight_np is None:
            # one-time f32 contiguous copy (an E-sized memcpy per batch
            # would dwarf the sampling work on big graphs)
            self._weight_np = np.ascontiguousarray(self.edge_weight,
                                                   dtype=np.float32)
        w = self._weight_np
        out = cpu_sample_multihop(
            indptr, indices, np.asarray(seeds), self.sizes,
            seed=int(jax.random.randint(self.next_key(), (), 0, 2 ** 31 - 1)),
            weights=w, with_slots=self.with_eid)
        if self.with_eid:
            n_id, rows, cols, slot_lists = out
            if self._eid_np is None and self.csr_topo.eid is not None:
                # one-time host copy (E-sized D2H per batch would dwarf
                # the sampling work, like _weight_np above)
                self._eid_np = np.asarray(self.csr_topo.eid)
            eid_map = self._eid_np
        else:
            n_id, rows, cols = out
            slot_lists = [None] * len(rows)
        shapes = layer_shapes(bs, self.sizes)
        adjs = []
        for (row, col, slots), shape in zip(zip(rows, cols, slot_lists),
                                            shapes):
            edge_index = jnp.asarray(np.stack([col, row]))
            e_id = None
            if slots is not None:
                e = (slots if eid_map is None
                     else np.where(slots >= 0,
                                   eid_map[np.clip(slots, 0, None)], -1))
                e_id = jnp.asarray(e)
            adjs.append(Adj(edge_index=edge_index,
                            e_id=e_id,
                            size=(shape.n_id_cap, shape.num_seeds),
                            mask=edge_index[0] >= 0))
        return jnp.asarray(n_id), bs, adjs[::-1]

    # -- aux ----------------------------------------------------------------
    def sample_layer(self, batch, size):
        self.lazy_init_quiver()
        indptr, indices = self._placed
        seeds = jnp.asarray(batch, jnp.int32)
        return sample_layer(jnp.asarray(indptr), jnp.asarray(indices),
                            seeds, size, self.next_key())

    def reindex(self, inputs, outputs, counts=None):
        return compact_layer(jnp.asarray(inputs, jnp.int32),
                             jnp.asarray(outputs, jnp.int32))

    def sample_prob(self, train_idx, total_node_count):
        self.lazy_init_quiver()
        if self.mode == "CPU":
            indptr = jnp.asarray(self._placed[0])
            indices = jnp.asarray(self._placed[1])
        else:
            indptr, indices = self._placed
        return sample_prob(jnp.asarray(indptr), jnp.asarray(indices),
                           jnp.asarray(train_idx), self.sizes,
                           total_node_count)

    # -- process sharing (API compat; jax is single-process-per-host) -------
    def share_ipc(self):
        return (self.csr_topo, self.device, self.mode, self.sizes,
                self.edge_weight, self.sampling, self.with_eid,
                self.layout, self.shuffle, self.wide_exact,
                self.allow_fallback)

    @classmethod
    def lazy_from_ipc_handle(cls, ipc_handle):
        # older short handles (7-tuple: no layout/shuffle; 9-tuple: no
        # wide_exact/allow_fallback) still load and get the ctor
        # defaults, like the Mixed sampler's handle[:6] pattern
        (csr_topo, device, mode, sizes, edge_weight, sampling,
         with_eid) = ipc_handle[:7]
        extras = {}
        for pos, name in ((7, "layout"), (8, "shuffle"),
                          (9, "wide_exact"), (10, "allow_fallback")):
            if len(ipc_handle) > pos:
                extras[name] = ipc_handle[pos]
        return cls(csr_topo, sizes, device=device, mode=mode,
                   edge_weight=edge_weight, sampling=sampling,
                   with_eid=with_eid, **extras)


class SampleJob(Generic[T_co]):
    """Abstract shuffled task source for the mixed sampler
    (reference: sage_sampler.py:180-195)."""

    def __getitem__(self, index) -> T_co:
        raise NotImplementedError

    def __len__(self) -> int:
        raise NotImplementedError

    def shuffle(self) -> None:
        raise NotImplementedError


class MixedGraphSageSampler:
    """Hybrid device+host sampling scheduler.

    Keeps the reference's adaptive work-splitting idea
    (sage_sampler.py:207-368): measure device vs host per-task sample time
    and hand the host a proportional quota each round. The host path uses
    the native C++ sampler (``quiver_tpu.native``) on a thread pool —
    threads, not daemon processes, because the GIL is released inside the
    native call and one process owns the TPU.
    """

    #: EMA smoothing for per-task time estimates (higher = faster adapt)
    EMA_ALPHA = 0.25

    def __init__(self, sample_job: SampleJob, sizes: Sequence[int],
                 csr_topo: CSRTopo, device=None,
                 device_mode: str = "HBM", num_workers: int = 2,
                 seed: int = 0, **device_sampler_kwargs):
        self.job = sample_job
        self.sizes = list(sizes)
        self.num_workers = max(1, num_workers)
        # device_sampler_kwargs pass through to the DEVICE side
        # (sampling="rotation", layout=, shuffle=). edge_weight and
        # with_eid ALSO reach the host side: the native engine's
        # weighted path draws with the device pool draw's contract (k
        # with-replacement picks ~ weight, row_cap truncation) and its
        # samplers emit per-pick CSR slots mapped through CSRTopo.eid —
        # so batches from either engine share one distribution and one
        # e_id semantics regardless of timing-dependent provenance.
        if device_sampler_kwargs.get("edge_weight") is not None and \
                device_sampler_kwargs.get("sampling", "exact") != "exact":
            raise ValueError(
                "mixed weighted sampling pins sampling='exact': the "
                "host engine mirrors the exact weighted pool draw, and "
                "the weighted windowed draw (rotation/window) is a "
                "different distribution — batches would skew depending "
                "on which engine produced them")
        self._device_kwargs = dict(device_sampler_kwargs)
        self.device_sampler = GraphSageSampler(
            csr_topo, sizes, device=device, mode=device_mode, seed=seed,
            **device_sampler_kwargs)
        self.cpu_sampler = GraphSageSampler(
            csr_topo, sizes, mode="CPU", seed=seed + 1,
            edge_weight=device_sampler_kwargs.get("edge_weight"),
            with_eid=bool(device_sampler_kwargs.get("with_eid", False)))
        self._pool = None
        self._device_time = None       # EMA seconds per device task
        self._cpu_time = None          # EMA seconds per host task
        import threading
        self._time_lock = threading.Lock()   # _cpu_one runs on pool threads

    def _ensure_pool(self):
        if self._pool is None:
            import concurrent.futures
            import weakref
            self._pool = concurrent.futures.ThreadPoolExecutor(
                max_workers=self.num_workers)
            # lifecycle: host-sampling threads must not outlive the
            # sampler across long runs — explicit close() below, with a
            # GC finalizer safety net (bound to the pool, not self)
            self._pool_finalizer = weakref.finalize(
                self, self._pool.shutdown, wait=False)

    def close(self):
        """Shut down the host-sampling worker pool (idempotent); safe
        to call between epochs — the next iteration re-creates it."""
        pool, self._pool = self._pool, None
        if pool is not None:
            fin = getattr(self, "_pool_finalizer", None)
            if fin is not None:
                fin.detach()
            pool.shutdown(wait=True, cancel_futures=True)

    def _ema(self, old, dt):
        a = self.EMA_ALPHA
        return dt if old is None else a * dt + (1.0 - a) * old

    def decide_task_num(self):
        device_tasks = max(20, 2 * self.num_workers)
        if not self._device_time or not self._cpu_time:
            return device_tasks, self.num_workers
        ratio = self._cpu_time / max(self._device_time, 1e-9)
        cpu_tasks = min(
            int(device_tasks / max(ratio / self.num_workers, 1e-9)),
            device_tasks * self.num_workers)
        return device_tasks, max(0, cpu_tasks)

    def __iter__(self):
        self.job.shuffle()
        if getattr(self.device_sampler, "sampling", "exact") in (
                "rotation", "window") and \
                getattr(self.device_sampler, "_rot", None) is not None:
            # epoch boundary: the mixed layer knows it (it just
            # reshuffled the job), so it owns the rotation refresh too
            # rather than pushing sampler internals onto callers
            self.device_sampler.reshuffle()
        self._ensure_pool()
        import concurrent.futures as cf
        n = len(self.job)
        idx = 0
        pending: List = []

        def drain_done():
            nonlocal pending
            done = [f for f in pending if f.done()]
            pending = [f for f in pending if not f.done()]
            return done

        while idx < n or pending:
            device_quota, cpu_quota = self.decide_task_num()

            def dispatch_host():
                # keep the pool fed up to its width, within this round's
                # quota; never queue past the width — tasks queued beyond
                # it are pure backlog, and during bootstrap (no host
                # measurement yet) an unbounded queue would commit dozens
                # of batches to a host pool that may turn out to be
                # 1000x slower than the device
                nonlocal idx, cpu_quota
                while (idx < n and cpu_quota > 0
                       and len(pending) < self.num_workers):
                    seeds = self.job[idx]
                    idx += 1
                    cpu_quota -= 1
                    pending.append(self._pool.submit(
                        self._cpu_one, np.asarray(seeds)))

            dispatch_host()
            # run device tasks inline, yielding finished host tasks
            # between them (non-blocking — the reference's round barrier
            # would stall the device on the slowest host task) and
            # refilling the host pool as slots free up
            for _ in range(device_quota):
                if idx >= n:
                    break
                seeds = self.job[idx]
                idx += 1
                t0 = time.perf_counter()
                out = self.device_sampler.sample(seeds)
                jax.block_until_ready(out[0])
                self._device_time = self._ema(
                    self._device_time, time.perf_counter() - t0)
                yield out
                for fut in drain_done():
                    yield fut.result()
                dispatch_host()
            for fut in drain_done():
                yield fut.result()
            if idx >= n and pending:
                # everything dispatched: now blocking is idle-waiting,
                # not stalling — take tasks as they finish
                done, rest = cf.wait(pending,
                                     return_when=cf.FIRST_COMPLETED)
                pending = list(rest)
                for fut in done:
                    yield fut.result()

    def _cpu_one(self, seeds):
        t0 = time.perf_counter()
        out = self.cpu_sampler.sample(seeds)
        dt = time.perf_counter() - t0
        with self._time_lock:          # concurrent pool threads
            self._cpu_time = self._ema(self._cpu_time, dt)
        return out

    def share_ipc(self):
        return (self.job, self.sizes, self.device_sampler.csr_topo,
                self.device_sampler.device, self.device_sampler.mode,
                self.num_workers, self._device_kwargs)

    @classmethod
    def lazy_from_ipc_handle(cls, handle):
        # older 6-tuple handles (no device kwargs) still load
        job, sizes, csr_topo, device, mode, workers = handle[:6]
        kwargs = handle[6] if len(handle) > 6 else {}
        return cls(job, sizes, csr_topo, device=device,
                   device_mode=mode, num_workers=workers, **kwargs)
