"""Per-node-type tiered feature stores for heterogeneous graphs.

The reference's MAG240M path pairs its sampler with a partitioned /
disk-tier feature pipeline (benchmarks/ogbn-mag240m/preprocess.py,
train_quiver_multi_node.py:21-23) — but only for the homogeneous
paper-feature matrix. ``HeteroFeature`` extends the flagship ``Feature``
machinery (HBM cache tiers, replicate/shard policies over the mesh,
numpy/offload host tiers, mmap disk tier, hot-order reindexing,
prefetch double-buffering) across node TYPES: each type gets its own
``Feature`` store with its own budget/policy/dtype, so a MAG240M-shaped
config puts the 100M-row paper matrix in the host (or disk) tier with a
small HBM cache while the author/institution matrices sit fully in HBM.

``lookup(frontier)`` consumes the hetero sampler's per-type frontier
dicts directly, honoring the -1 mask convention (masked rows are
zeroed, matching the hand-rolled gather the R-GCN example used before).
"""

from __future__ import annotations

from typing import Dict, Optional

import jax.numpy as jnp
import numpy as np

from .feature import Feature


class HeteroFeature:
    """``{node_type: Feature}`` with a frontier-shaped lookup.

    Build via :meth:`from_cpu_tensors`; per-type construction knobs come
    from ``configs[node_type]`` overlaid on ``default`` (both plain
    kwarg dicts for :class:`Feature` — ``device_cache_size``,
    ``cache_policy``, ``csr_topo``, ``mesh``, ``dtype``,
    ``host_placement``, ``cold_budget``, ``dedup_cold``,
    ``dtype_policy``...). Hetero frontiers repeat hub nodes across
    relations, so ``default={"dedup_cold": True}`` bounds every type's
    host-tier traffic by its unique cold nodes — and because the knobs
    are per type, a MAG240M-shaped config can store the 100M-row paper
    matrix int8 (quarter the host bytes, fused dequant) while the
    small author/institution matrices stay fp32 in HBM:
    ``configs={"paper": {"dtype_policy": "int8"}}``.
    """

    def __init__(self, stores: Dict[str, Feature]):
        self.stores = dict(stores)
        self._pool = None

    @classmethod
    def from_cpu_tensors(cls, feats: Dict[str, np.ndarray],
                         configs: Optional[Dict[str, dict]] = None,
                         default: Optional[dict] = None) -> "HeteroFeature":
        configs = configs or {}
        default = default or {}
        unknown = set(configs) - set(feats)
        if unknown:
            raise ValueError(
                f"configs for unknown node type(s) {sorted(unknown)}; "
                f"have {sorted(feats)}")
        stores = {}
        for t, arr in feats.items():
            kw = dict(default)
            kw.update(configs.get(t, {}))
            stores[t] = Feature(**kw).from_cpu_tensor(arr)
        return cls(stores)

    @property
    def node_types(self):
        return list(self.stores.keys())

    def __getitem__(self, node_type: str) -> Feature:
        return self.stores[node_type]

    def _lookup_one(self, node_type: str, ids):
        # Feature fuses the clip+gather+mask into one dispatch on the
        # pure-HBM path — one dispatch per node type, not three (see
        # feature.py _build_gather)
        return self.stores[node_type].getitem_masked(ids)

    def lookup(self, frontier: Dict[str, object]) -> Dict[str, object]:
        """Gather features for a hetero frontier dict (``None`` entries
        skipped, -1-masked ids produce zero rows)."""
        return {t: self._lookup_one(t, ids)
                for t, ids in frontier.items() if ids is not None}

    def prefetch(self, frontier: Dict[str, object]):
        """Start ``lookup(frontier)`` on the staging pipeline; returns
        a ``Future`` whose ``result()`` equals the lookup. Same
        double-buffering story as ``Feature.prefetch``: the host-tier
        staging of batch i+1 overlaps batch i's model step. Bounded,
        ordered, shut down by :meth:`close` (or at GC)."""
        if self._pool is None:
            from .pipeline import Pipeline
            self._pool = Pipeline(depth=2, name="quiver-hetero-prefetch")
        snap = {t: (None if ids is None else jnp.asarray(ids))
                for t, ids in frontier.items()}
        return self._pool.submit(self.lookup, snap)

    def close(self):
        """Shut down the prefetch pipeline and every per-type store's
        (idempotent)."""
        pool, self._pool = self._pool, None
        if pool is not None:
            pool.close()
        for store in self.stores.values():
            store.close()

    def size(self, node_type: str, dim: int) -> int:
        return self.stores[node_type].size(dim)

    def __getstate__(self):
        state = dict(self.__dict__)
        state["_pool"] = None
        return state
