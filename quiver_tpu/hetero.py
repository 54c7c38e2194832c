"""Heterogeneous graph support: typed topology + relational k-hop sampler.

Covers the MAG240M-class workload (BASELINE configs[3]; reference
benchmarks/ogbn-mag240m). The reference trains on the homogeneous
paper-cites-paper projection (train_quiver_multi_node.py:90-93) — this
module supports that *and* true multi-relation sampling for R-GCN:

- ``HeteroCSRTopo``: one CSR per relation (src_type, rel, dst_type), each
  an ordinary ``CSRTopo`` over the dst-type id space with src-type ids as
  indices (CSR rows = dst nodes, matching the sampling direction:
  frontier nodes pull their in-neighbors).
- ``HeteroGraphSageSampler``: per hop, every relation samples ``k`` of
  the current dst-type frontier's neighbors; per node type, the frontier
  union is compacted with the same first-occurrence static-shape
  compaction as the homogeneous path.

All shapes static; same -1 masking contract as the homogeneous sampler.
"""

from __future__ import annotations

from typing import Dict, List, NamedTuple, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .ops.sample import (as_index_rows, as_index_rows_overlapping,
                         compact_union, compose_slot_map, edge_row_ids,
                         reshuffle_csr, sample_layer,
                         sample_layer_exact_wide, sample_layer_rotation,
                         sample_layer_window, suggest_hub_cap)
from .ops.weighted import sample_layer_weighted
from .pyg.sage_sampler import Adj
from .utils import CSRTopo

EdgeType = Tuple[str, str, str]          # (src_type, relation, dst_type)


class HeteroCSRTopo:
    """Typed topology: ``rels[(src, rel, dst)] = CSRTopo`` whose row v
    (a dst-type node) lists its src-type in-neighbors."""

    def __init__(self, rels: Dict[EdgeType, CSRTopo],
                 node_counts: Dict[str, int]):
        self.rels = dict(rels)
        self.node_counts = dict(node_counts)
        for (src, rel, dst), topo in self.rels.items():
            if topo.node_count < self.node_counts.get(dst, 0):
                raise ValueError(
                    f"relation {(src, rel, dst)} CSR has {topo.node_count} "
                    f"rows < dst node_count {self.node_counts[dst]}")

    @property
    def edge_types(self) -> List[EdgeType]:
        return list(self.rels.keys())

    @property
    def node_types(self) -> List[str]:
        return list(self.node_counts.keys())


class HeteroLayer(NamedTuple):
    """One sampled hop of a hetero graph.

    adjs:     {edge_type: Adj} — local bipartite COO per relation; source
              local ids index the *next* frontier of the src type, target
              local ids index the current frontier of the dst type.
    frontier: {node_type: n_id array} AFTER this hop (input to next hop /
              feature gather), -1-filled static caps.
    counts:   {node_type: valid count in frontier}
    """

    adjs: Dict[EdgeType, Adj]
    frontier: Dict[str, jax.Array]
    counts: Dict[str, jax.Array]


class HeteroGraphSageSampler:
    """Relational neighbor sampler.

    ``sizes`` is a list of per-hop fanouts; each entry is either an int
    (same fanout for every relation) or a ``{edge_type: k}`` dict.
    ``sample(seeds)`` seeds are nodes of ``seed_type``.

    Performance modes (the same engine as the homogeneous sampler, per
    relation — the reference's MAG240M path only ever samples its
    homogeneous projection, train_quiver_multi_node.py:90-93, so each
    of these is beyond-parity):

    - ``sampling="exact"`` (default): i.i.d. Fisher-Yates draws through
      the wide-fetch path (``sample_layer_exact_wide``) — one/two row
      gathers per low-degree seed per relation, scattered loads only
      for hubs. No reshuffle needed.
    - ``sampling="rotation"`` / ``"window"``: the wide row-fetch draws
      over per-relation shuffled row views; call ``reshuffle()`` per
      epoch (automatic on first sample). ``shuffle="butterfly"`` is the
      ~40x cheaper composed epoch re-mix.
    - ``layout="overlap"``: one 256-wide gather per seed instead of two
      128-wide, at 2x index memory — per relation.

    ``frontier_cap`` bounds each node type's frontier capacity (an int,
    or ``{node_type: int}``): multi-relation expansion otherwise grows
    frontier caps multiplicatively per hop. Sampled edges whose source
    falls past the cap are masked (-1) — the same static-capacity
    truncation contract as every other capped shape here.

    ``edge_weight`` (``{edge_type: CSR-slot-aligned weights}``) switches
    the listed relations to weighted (attention) draws — with
    replacement, proportional to weight, the reference ``weight_sample``
    contract (cuda_random.cu.hpp:178-221); unlisted relations keep the
    uniform exact draw. ``with_eid=True`` stamps every sampled edge's
    ``Adj.e_id`` with its global edge id (the relation's
    ``CSRTopo.eid`` if set, else its CSR slot), -1 where masked —
    in every sampling mode (rotation/window compose per-relation
    permuted slot maps across ``reshuffle()``). ``edge_weight`` is
    exact-mode only (see the ctor guard).
    """

    def __init__(self, topo: HeteroCSRTopo, sizes: Sequence,
                 seed_type: str, seed: int = 0, sampling: str = "exact",
                 layout: str = "pair", shuffle: str = "sort",
                 frontier_cap=None, wide_exact: bool = True,
                 edge_weight: Dict[EdgeType, object] = None,
                 with_eid: bool = False):
        self.topo = topo
        self.seed_type = seed_type
        self.sizes = [s if isinstance(s, dict)
                      else {et: s for et in topo.edge_types}
                      for s in sizes]
        if sampling not in ("exact", "rotation", "window"):
            raise ValueError(f"unknown sampling method {sampling!r}")
        if layout not in ("pair", "overlap"):
            raise ValueError(f"unknown layout {layout!r}")
        if shuffle not in ("sort", "butterfly"):
            raise ValueError(f"unknown shuffle {shuffle!r}")
        max_k = max((k for hop in self.sizes for k in hop.values()),
                    default=0)
        if sampling in ("rotation", "window") and max_k > 128:
            raise ValueError(f"{sampling} sampling supports fanouts <= 128")
        self.sampling = sampling
        self.layout = layout
        self.shuffle = shuffle
        if frontier_cap is not None and not isinstance(frontier_cap, dict):
            frontier_cap = {t: int(frontier_cap) for t in topo.node_types}
        self.frontier_cap = frontier_cap
        # wide_exact=False: skip the per-relation layout views (+E/+2E
        # memory each) and keep the zero-extra-copy scattered exact draw
        self.wide_exact = wide_exact
        # per-relation CSR-slot-aligned weights => weighted (attention)
        # draws for those relations (with replacement, the reference
        # weight_sample contract — cuda_random.cu.hpp:178-221);
        # unlisted relations keep the uniform exact draw. Same coupled-
        # param strictness as the homogeneous ctor: the weighted
        # windowed draw's mandatory hub re-placement only exists on the
        # homogeneous rotation/window path, so WEIGHTED hetero sampling
        # is exact-mode only — an explicit error, not a silent
        # downgrade. (with_eid works in every mode; see below.)
        if edge_weight is not None:
            unknown = set(edge_weight) - set(topo.rels)
            if unknown:
                raise ValueError(
                    f"edge_weight for unknown relation(s) "
                    f"{sorted(unknown)}")
            if sampling != "exact":
                raise ValueError(
                    "per-relation weighted draws support "
                    "sampling='exact' only (rotation/window would need "
                    "the weighted windowed draw's co-permuted weight "
                    "rows — use the homogeneous GraphSageSampler for "
                    "that workload)")
            for et, w in edge_weight.items():
                e = int(topo.rels[et].indices.shape[0])
                # np.shape: no device transfer for the length check
                # (jnp.asarray would ship each E-sized array to HBM
                # just to read its shape)
                if int(np.shape(w)[0]) != e:
                    raise ValueError(
                        f"edge_weight[{et}] has {int(np.shape(w)[0])} "
                        f"entries, relation has {e} edges")
        self.edge_weight = edge_weight
        # with_eid works in every sampling mode: exact modes map raw
        # CSR slots through the relation's eid map; rotation/window
        # maintain per-relation CO-PERMUTED slot maps across reshuffles
        # (the homogeneous sampler's _rot_eid pattern, per relation).
        self.with_eid = with_eid
        self._weights_placed = None
        self._eids_placed = None
        self._rot_eids = {}      # {edge_type: permuted-slot -> edge id}
        self._key = jax.random.key(seed)
        self._fn_cache = {}
        self._hub_fracs = None   # {edge_type: static hub fraction}
        self._rows = None        # {edge_type: rows view}
        self._permuted = {}      # butterfly composition state
        self._row_ids = {}
        self._rels_placed = None  # {edge_type: (indptr, indices)}

    def next_key(self):
        self._key, sub = jax.random.split(self._key)
        return sub

    def _as_rows(self, flat):
        return (as_index_rows_overlapping(flat)
                if self.layout == "overlap" else as_index_rows(flat))

    @property
    def _stride(self):
        return 128 if self.layout == "overlap" else None

    def reshuffle(self, key=None):
        """Per-epoch refresh of every relation's shuffled row view
        (rotation/window freshness source; exact mode needs none)."""
        if self.sampling not in ("rotation", "window"):
            raise ValueError(
                "reshuffle only applies to rotation/window sampling")
        key = key if key is not None else self.next_key()
        bfly = self.shuffle == "butterfly"
        rows = {}
        for i, (et, t) in enumerate(sorted(self.topo.rels.items())):
            indices = jnp.asarray(t.indices)
            rid = self._row_ids.get(et)
            if rid is None:
                rid = jax.jit(edge_row_ids, static_argnums=1)(
                    jnp.asarray(t.indptr), int(indices.shape[0]))
                self._row_ids[et] = rid
            src = (self._permuted.get(et, indices) if bfly else indices)
            out = reshuffle_csr(src, rid, jax.random.fold_in(key, i),
                                method=self.shuffle,
                                with_slot_map=self.with_eid)
            if self.with_eid:
                permuted, smap = out
                # co-permuted edge-id map per relation (shared
                # composition semantics: ops.compose_slot_map). The
                # placed base eid is cached so sort mode doesn't
                # re-transfer E-sized maps every epoch.
                base = None
                if t.eid is not None:
                    if self._eids_placed is None:
                        self._eids_placed = {}
                    base = self._eids_placed.get(et)
                    if base is None:
                        base = jnp.asarray(t.eid)
                        self._eids_placed[et] = base
                self._rot_eids[et] = compose_slot_map(
                    self._rot_eids.get(et), smap, base, bfly)
            else:
                permuted = out
            if bfly:
                self._permuted[et] = permuted
            rows[et] = self._as_rows(permuted)
        self._rows = rows

    def _build(self, batch_size: int):
        sizes = self.sizes
        seed_type = self.seed_type
        node_types = self.topo.node_types
        method = self.sampling
        stride = self._stride
        caps = self.frontier_cap
        with_eid = self.with_eid
        hub_fracs = self._hub_fracs or {}

        # rels/rows enter as jit ARGUMENTS (pytrees), never closures: a
        # closed-over device array is embedded in the HLO as a literal
        # constant, and MAG240M-scale relations would bloat the
        # executable by their size — same hazard bench.py documents
        def run(seeds, key, rows, rels, weights, eids):
            frontier = {t: None for t in node_types}
            frontier[seed_type] = seeds.astype(jnp.int32)
            hops = []
            step = 0
            for hop, fanouts in enumerate(sizes):
                per_rel_samples: Dict[EdgeType, tuple] = {}
                # 1. sample every relation whose dst type has a frontier
                for et, k in fanouts.items():
                    src_t, _, dst_t = et
                    cur = frontier[dst_t]
                    if cur is None or k <= 0:
                        continue
                    sub = jax.random.fold_in(key, step)
                    step += 1
                    indptr, indices = rels[et]

                    def unpack(out):
                        # (nbrs, counts[, slots]) -> (nbrs, slots|None)
                        return (out[0], out[2] if with_eid else None)

                    w = weights.get(et)
                    if w is not None:
                        nbrs, slots = unpack(sample_layer_weighted(
                            indptr, indices, w, cur, k, sub,
                            with_slots=with_eid))
                    elif method == "rotation":
                        nbrs, slots = unpack(sample_layer_rotation(
                            indptr, rows[et], cur, k, sub, stride=stride,
                            with_slots=with_eid))
                    elif method == "window":
                        nbrs, slots = unpack(sample_layer_window(
                            indptr, rows[et], cur, k, sub, stride=stride,
                            with_slots=with_eid))
                    elif rows is not None:
                        # scattered-load budget from the relation's own
                        # cached degree-bucket split (CSRTopo metadata,
                        # shared across batch sizes and epochs); static
                        # because the frontier width is a compile-time
                        # shape
                        nbrs, slots = unpack(sample_layer_exact_wide(
                            indptr, indices, rows[et], cur, k, sub,
                            stride=stride, with_slots=with_eid,
                            hub_cap=suggest_hub_cap(
                                int(cur.shape[0]), hub_fracs.get(et))))
                    else:
                        nbrs, slots = unpack(sample_layer(
                            indptr, indices, cur, k, sub,
                            with_slots=with_eid))
                    if slots is not None and et in eids:
                        # CSR slot -> original COO edge id (CSRTopo.eid)
                        e = eids[et]
                        slots = jnp.where(
                            slots >= 0,
                            e[jnp.clip(slots, 0, e.shape[0] - 1)]
                            .astype(slots.dtype), -1)
                    per_rel_samples[et] = (cur, nbrs, slots)
                # 2. per src type: compact (old frontier ++ all sampled)
                new_frontier = dict(frontier)
                new_counts = {}
                adjs = {}
                by_src: Dict[str, list] = {}
                for et, (cur, nbrs, slots) in per_rel_samples.items():
                    by_src.setdefault(et[0], []).append(
                        (et, cur, nbrs, slots))
                for src_t, group in by_src.items():
                    prev = frontier[src_t]
                    prev = prev if prev is not None else \
                        jnp.full((0,), -1, jnp.int32)
                    all_nbrs = jnp.concatenate(
                        [nbrs.reshape(-1) for _, _, nbrs, _ in group])
                    n_id, n_count, extra_local = compact_union(prev, all_nbrs)
                    cap = caps.get(src_t) if caps else None
                    if cap is not None and n_id.shape[0] > cap:
                        # static-capacity truncation: keep the seeds-
                        # first prefix, mask edges whose source fell
                        # past the cap (same -1 contract as everywhere)
                        n_id = n_id[:cap]
                        n_count = jnp.minimum(n_count, cap)
                        extra_local = jnp.where(
                            extra_local < cap, extra_local, -1)
                    # n_id holds prev ++ unique new, first-occurrence order
                    new_frontier[src_t] = n_id
                    new_counts[src_t] = n_count
                    # 3. per relation: local COO against the merged frontier
                    offset = 0
                    for et, cur, nbrs, slots in group:
                        s, kk = nbrs.shape
                        flat = extra_local[offset:offset + s * kk]
                        offset += s * kk
                        row = jnp.where(
                            flat >= 0,
                            jnp.repeat(jnp.arange(s, dtype=jnp.int32), kk),
                            -1)
                        edge_index = jnp.stack([flat, row])
                        e_id = None
                        if slots is not None:
                            # frontier-cap truncation masks the edge in
                            # flat; its e_id masks with it
                            e_id = jnp.where(flat >= 0,
                                             slots.reshape(-1), -1)
                        adjs[et] = Adj(
                            edge_index=edge_index, e_id=e_id,
                            size=(int(n_id.shape[0]), s),
                            mask=flat >= 0)
                hops.append((adjs, dict(new_frontier), new_counts))
                frontier = new_frontier
            return frontier, hops

        return jax.jit(run)

    def sample(self, seeds):
        seeds = jnp.asarray(seeds, jnp.int32)
        bs = int(seeds.shape[0])
        if self.frontier_cap is not None and \
                self.frontier_cap.get(self.seed_type, bs) < bs:
            raise ValueError(
                f"frontier_cap[{self.seed_type!r}] = "
                f"{self.frontier_cap[self.seed_type]} < batch size {bs}: "
                "the cap would truncate the seeds themselves")
        if self._rows is None:
            if self.sampling in ("rotation", "window"):
                self.reshuffle()
            elif self.wide_exact:
                # exact: static layout views of the un-shuffled indices
                # route every relation through the wide-fetch exact path
                # (weighted relations draw from the pool CDF instead —
                # no view, no +E copy for them)
                self._rows = {et: self._as_rows(jnp.asarray(t.indices))
                              for et, t in self.topo.rels.items()
                              if not (self.edge_weight
                                      and et in self.edge_weight)}
                # one cached degree-bucket split per relation sizes the
                # static hub budget (CSRTopo caches it, so a topology
                # shared by several samplers computes it once)
                self._hub_fracs = {
                    et: float(self.topo.rels[et]
                              .exact_bucket_meta(step=128).frac)
                    for et in self._rows}
        if self._rels_placed is None:
            self._rels_placed = {
                et: (jnp.asarray(t.indptr), jnp.asarray(t.indices))
                for et, t in self.topo.rels.items()}
        if self.edge_weight is not None and self._weights_placed is None:
            self._weights_placed = {et: jnp.asarray(w)
                                    for et, w in self.edge_weight.items()}
        if self.with_eid and self.sampling == "exact" \
                and self._eids_placed is None:
            # rotation/window never read these (they use _rot_eids);
            # building them there would place E-sized arrays for nothing
            self._eids_placed = {
                et: jnp.asarray(t.eid)
                for et, t in self.topo.rels.items() if t.eid is not None}
        # rotation/window slots live in permuted coordinates: map them
        # through the co-permuted per-relation maps instead of the raw
        # topo eids
        eids_arg = (self._rot_eids
                    if self.sampling in ("rotation", "window")
                    else self._eids_placed)
        fn = self._fn_cache.get(bs)
        if fn is None:
            fn = self._build(bs)
            self._fn_cache[bs] = fn
        frontier, hops = fn(seeds, self.next_key(), self._rows,
                            self._rels_placed,
                            self._weights_placed or {},
                            eids_arg or {})
        layers = [HeteroLayer(adjs=a, frontier=f, counts=c)
                  for a, f, c in hops]
        return frontier, bs, layers[::-1]
