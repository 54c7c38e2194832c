"""Tiered, mesh-aware feature store — the flagship component.

TPU-native redesign of the reference ``quiver.Feature`` (feature.py:17-458),
``PartitionInfo``/``DistFeature`` (feature.py:461-567):

tiers (by bandwidth, mirroring HBM > NVLink > pinned host > disk):
  1. HBM cache      — hottest rows (degree- or probability-ordered), either
                      replicated on every chip (``device_replicate``) or
                      row-sharded over the ICI mesh axis
                      (``p2p_clique_replicate`` — a whole TPU slice is one
                      "NVLink clique", so the clique generalizes to the mesh)
  2. host memory    — remaining rows, gathered on host. A plain
                      ``feature[ids]`` is synchronous; ``prefetch(ids)``
                      stages the host rows on a background thread so the
                      next batch's staging overlaps the current batch's
                      compute (the TPU analogue of the reference's UVA
                      kernel reading pinned host memory during the gather,
                      quiver_feature.cu:174-203)
  3. disk (mmap)    — optional numpy-memmap tier via ``disk_map``
                      (reference feature.py:84-93, 309-333)

The id indirection chain is the reference's: lookup ids pass through
``feature_order`` (hot-order permutation) before tier dispatch
(feature.py:296-333). CUDA-IPC plumbing disappears: one process per host
drives all local chips, so ``share_ipc`` degenerates to handing over
construction metadata.
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from . import profiling
from .ops import quant
from .profiling import hot_path
from .utils import CSRTopo, parse_size, reindex_feature


class DeviceConfig:
    """Pre-partitioned construction recipe (reference feature.py:11-14)."""

    def __init__(self, gpu_parts, cpu_part):
        self.gpu_parts = gpu_parts
        self.cpu_part = cpu_part
    # TPU-neutral aliases
    @property
    def device_parts(self):
        return self.gpu_parts

    @property
    def host_part(self):
        return self.cpu_part


def _resolve_tier_policy(policy) -> dict:
    """Normalize a dtype-policy knob to ``{"hot": ..., "cold": ...}``
    with canonical policy names (None = store as-is)."""
    if policy is None or isinstance(policy, str):
        p = quant.resolve_policy(policy)
        return {"hot": p, "cold": p}
    if isinstance(policy, dict):
        unknown = set(policy) - {"hot", "cold"}
        if unknown:
            raise ValueError(
                f"dtype_policy keys must be 'hot'/'cold', got "
                f"{sorted(unknown)}")
        return {"hot": quant.resolve_policy(policy.get("hot")),
                "cold": quant.resolve_policy(policy.get("cold"))}
    raise ValueError(f"cannot parse dtype_policy {policy!r}")


def _resolve_cold_budget(dedup_cold, cold_budget, n: int) -> int:
    """The cold-compaction budget in force for an ``n``-slot lookup —
    the ONE resolution both the fused gather and the numpy-path metric
    mirror use (an explicit ``dedup_cold=int`` wins, then
    ``cold_budget``, then the batch-sized default)."""
    if dedup_cold and not isinstance(dedup_cold, bool):
        return int(dedup_cold)
    if cold_budget is not None:
        return cold_budget
    return quant.default_cold_budget(n)


def _default_mesh(device_list: Optional[Sequence[int]] = None) -> Mesh:
    devs = jax.devices()
    if device_list:
        devs = [devs[i] for i in device_list]
    return Mesh(np.array(devs), axis_names=("cache",))


class Feature:
    """``Feature(rank, device_list, device_cache_size, cache_policy,
    csr_topo)`` — constructor signature kept compatible with the reference
    (feature.py:37-59); ``mesh`` is the TPU-native extra knob."""

    def __init__(self, rank: int = 0,
                 device_list: Optional[Sequence[int]] = None,
                 device_cache_size=0,
                 cache_policy: str = "device_replicate",
                 csr_topo: Optional[CSRTopo] = None,
                 mesh: Optional[Mesh] = None,
                 dtype=None,
                 host_placement: str = "numpy",
                 cold_budget: Optional[int] = None,
                 dedup_cold=False,
                 dtype_policy=None,
                 allow_fallback: bool = True):
        if cache_policy not in ("device_replicate", "p2p_clique_replicate",
                                "shard"):
            raise ValueError(f"unknown cache_policy {cache_policy!r}")
        if host_placement not in ("numpy", "offload"):
            raise ValueError(f"unknown host_placement {host_placement!r}")
        self.rank = rank
        self.device_list = list(device_list) if device_list else None
        self.device_cache_size = device_cache_size
        self.cache_policy = cache_policy
        self.csr_topo = csr_topo
        self.mesh = mesh
        self.dtype = dtype
        # host_placement="offload": keep the cold tier as a pinned_host
        # jax array and FUSE the whole tiered lookup into one jitted
        # dispatch (device rows from HBM, cold rows gathered by XLA
        # straight from pinned host memory — the reference's UVA gather
        # semantics, quiver_feature.cu:174-293). Requires a backend with
        # usable host-offload (TPU/GPU; loud numpy fallback elsewhere).
        self.host_placement = host_placement
        # allow_fallback=False: raise where host_placement="offload"
        # cannot pin the cold tier, instead of the loud numpy fallback
        # (the sampler's knob of the same name)
        self.allow_fallback = allow_fallback
        # static per-batch cap on how many rows the fused offload lookup
        # reads from the host tier (None = max(batch//4, 256)); see
        # _build_gather's lookup_tiered
        self.cold_budget = cold_budget
        # dedup_cold: gather each UNIQUE cold node's host row once and
        # inverse-scatter to frontier positions, so host-tier traffic
        # scales with unique cold nodes, not frontier slots (multi-hop
        # frontiers repeat hubs many times). True uses cold_budget (or
        # its default) as the unique budget; an int sets the unique
        # budget directly. Overflowing batches fall back to the full
        # gather via lax.cond — exact in every case. Pays when the
        # frontier duplicate factor exceeds ~1.3 (docs/api.md).
        self.dedup_cold = dedup_cold
        # dtype_policy: per-tier narrow storage (ops/quant.py). None, a
        # policy name applied to both tiers ("bf16" / "fp16" / "int8"),
        # or {"hot": ..., "cold": ...}. bf16/fp16 are pure casts (half
        # the bytes, lookups return the narrow float); int8 adds
        # per-row fp32 scale/zero sidecars and dequantization is FUSED
        # into every gather, so host-tier and exchange traffic shrink
        # ~4x while models keep consuming float activations. The hot
        # tier is sized bandwidth-aware: the byte budget divides by the
        # STORED row width, so a narrow policy caches 2-4x more rows
        # (quant.plan_hot_capacity logs the expected hit-rate gain).
        self.dtype_policy = _resolve_tier_policy(dtype_policy)
        self.feature_order = None      # old id -> storage row
        self._order_np = None          # (src, host copy) metrics cache
        self.cache_rows = 0
        self.device_part = None        # jnp [cache_rows, dim]
        self.host_part = None          # np  [rest, dim]
        self._host_offload = None      # pinned_host jnp [rest, dim]
        self.mmap_array = None
        self.disk_map = None
        self._disk_map_np = None       # (src, host copy) cache
        self.disk_scale = None
        self.disk_zero = None
        self._cold_prefetch = None     # prefetch.ColdPrefetcher
        self._gather_cached = None
        self._translate = None
        self._lookup_cached = None
        self._lookup_cached_masked = None
        self._lookup_tiered = None
        self._lookup_tiered_raw = None
        self._pool = None              # prefetch staging thread

    # -- sizing (reference feature.py:74-82) --------------------------------
    def cal_size(self, cpu_tensor, cache_memory_budget: int) -> int:
        # bandwidth-aware: divide the byte budget by the STORED row
        # width under the hot-tier dtype policy (sidecars included),
        # not the input width — a narrow policy holds 2-4x more hot
        # rows in the same HBM budget
        row_bytes = quant.row_bytes(
            int(np.prod(cpu_tensor.shape[1:])), self.dtype_policy["hot"],
            cpu_tensor.dtype.itemsize)
        return min(cpu_tensor.shape[0], cache_memory_budget // max(row_bytes, 1))

    def partition(self, cpu_tensor, cache_memory_budget: int):
        rows = self.cal_size(cpu_tensor, cache_memory_budget)
        return [cpu_tensor[:rows], cpu_tensor[rows:]]

    # -- construction -------------------------------------------------------
    def from_cpu_tensor(self, cpu_tensor):
        tensor = np.asarray(cpu_tensor)
        if self.dtype is not None:
            tensor = tensor.astype(self.dtype)
        budget = parse_size(self.device_cache_size)
        if self.cache_policy != "device_replicate":
            # sharded policy: the slice's chips pool their budgets
            budget *= self._mesh_size()

        if self.csr_topo is not None:
            if self.csr_topo.feature_order is None:
                tensor, new_order = reindex_feature(
                    self.csr_topo, tensor, 0)
                self.csr_topo.feature_order = jnp.asarray(new_order)
            else:
                # a topo shared with an earlier store already carries
                # the hot-order permutation: apply it to THIS tensor
                # too, or the lookup indirection would read hot-order
                # storage rows out of an unpermuted array
                order = np.asarray(jax.device_get(
                    self.csr_topo.feature_order))
                storage = np.empty_like(tensor)
                storage[order] = tensor
                tensor = storage
            self.feature_order = jnp.asarray(self.csr_topo.feature_order,
                                             dtype=jnp.int32)

        cache_part, host_part = self.partition(tensor, budget)
        self.cache_rows = int(cache_part.shape[0])
        self._log_hot_plan(tensor, budget)
        self._place(quant.quantize(cache_part, self.dtype_policy["hot"]))
        self.host_part = None
        if host_part.shape[0]:
            self.host_part = quant.tree_map_tier(
                np.ascontiguousarray,
                quant.quantize(host_part, self.dtype_policy["cold"]))
        self._maybe_offload_host()
        self._build_gather()
        self._log_cache_stats()
        return self

    def from_tiers(self, device_part, host_part, feature_order=None):
        """Construct from tiers that EXIST (as ``DistFeature.from_shards``
        does from shards): ``device_part`` the hot rows, storage rows
        ``[0, hot)``, already on the device (or None); ``host_part`` the
        cold rows, storage rows ``[hot, total)``; ``feature_order`` the
        node id -> storage row map (None: ids are storage rows; absent
        an argument, ``csr_topo.feature_order`` where one is set). No
        copy of the table is made: this is the constructor for a table
        too large to pass through one host array (``from_cpu_tensor``'s
        argument), each tier written where it lives by the loader. A
        ``host_part`` that is a jax array in pinned host memory is
        taken as the offload tier as it is; any other goes the way
        ``from_cpu_tensor``'s cold part goes (numpy, pinned under
        ``host_placement="offload"``, refused loudly with
        ``allow_fallback=False`` where pinning is unusable). The tiers
        are stored as given: narrow storage is the caller's
        (``quant.quantize``), so a ``dtype_policy`` is refused."""
        if any(self.dtype_policy.values()):
            raise ValueError(
                "from_tiers stores the tiers as given; hand it quantized "
                "tiers (ops.quant.quantize) rather than a dtype_policy")
        if feature_order is None and self.csr_topo is not None:
            feature_order = self.csr_topo.feature_order
        self.feature_order = None if feature_order is None else \
            jnp.asarray(feature_order, dtype=jnp.int32)
        self.device_part = device_part
        self.cache_rows = 0 if device_part is None \
            else int(quant.tier_rows(device_part))
        leaves = jax.tree_util.tree_leaves(host_part)
        pinned = bool(leaves) and all(
            isinstance(l, jax.Array)
            and l.sharding.memory_kind == "pinned_host" for l in leaves)
        if pinned:
            self._host_offload, self.host_part = host_part, None
        else:
            self.host_part = None if host_part is None else \
                quant.tree_map_tier(np.asarray, host_part)
            self._maybe_offload_host()
        self._build_gather()
        return self

    def _log_hot_plan(self, tensor, budget: int):
        """Log what the dtype policy buys: hot rows held by the budget
        and (with a csr_topo) the expected degree-mass hit-rate gain
        over the width-blind fp32 sizing."""
        import logging

        from .debug import log as _log, logger as _logger
        if self.dtype_policy["hot"] is None or not budget \
                or not _logger.isEnabledFor(logging.INFO):
            return
        degree = (self.csr_topo.degree if self.csr_topo is not None
                  else None)
        plan = quant.plan_hot_capacity(
            budget, tensor.shape[0], int(np.prod(tensor.shape[1:])),
            self.dtype_policy["hot"], tensor.dtype.itemsize, degree)
        if plan.expected_hit_rate is not None:
            _log("Feature: hot dtype policy %s holds %d rows in the "
                 "budget (fp32 sizing: %d); expected hit rate %.1f%% "
                 "(fp32: %.1f%%)", self.dtype_policy["hot"], plan.rows,
                 plan.fp32_rows, 100.0 * plan.expected_hit_rate,
                 100.0 * plan.fp32_hit_rate)
        else:
            _log("Feature: hot dtype policy %s holds %d rows in the "
                 "budget (fp32 sizing: %d)", self.dtype_policy["hot"],
                 plan.rows, plan.fp32_rows)

    def _log_cache_stats(self):
        """Construction-time observability (the reference prints its
        cache ratio, feature.py:208-210; with a csr_topo we can do
        better): under degree-proportional access — what GNN minibatch
        gathers look like — the expected HBM hit rate is the cached
        rows' share of total degree mass."""
        import logging

        from .debug import log as _log, logger as _logger
        if not _logger.isEnabledFor(logging.INFO):
            return        # silenced: skip the O(n) stats work entirely
        n = self.size(0)
        if not n:
            return
        if self.csr_topo is None or self.feature_order is None \
                or not self.cache_rows:
            _log("Feature: %d/%d rows cached in HBM", self.cache_rows, n)
            return
        deg = np.asarray(jax.device_get(self.csr_topo.degree),
                         dtype=np.float64)
        rows = np.asarray(jax.device_get(self.feature_order))
        m = min(deg.shape[0], rows.shape[0])
        cached_mass = float(deg[:m][rows[:m] < self.cache_rows].sum())
        total = float(deg.sum()) or 1.0
        _log("Feature: %d/%d rows cached in HBM (degree-ordered); "
             "expected hit rate ~%.1f%% under degree-proportional "
             "access", self.cache_rows, n, 100.0 * cached_mass / total)

    def _maybe_offload_host(self):
        """host_placement="offload": pin the cold tier to host memory as
        a jax array so the tiered lookup fuses into one dispatch. Loud
        numpy fallback on backends without usable host-offload."""
        if self.host_placement != "offload" or self.host_part is None:
            return
        from .utils.placement import pinned_put
        dev = jax.devices()[self.rank if self.rank < len(jax.devices())
                            else 0]
        # when a mesh is set the HBM cache is mesh-placed (sharded or
        # mesh-replicated); the cold tier must share that device set or
        # _lookup_tiered fails at dispatch — place it host-replicated
        # over the same mesh
        leaves, tree = jax.tree_util.tree_flatten(self.host_part)
        got = pinned_put(leaves, dev, self.allow_fallback,
                         "the Feature host tier", mesh=self.mesh,
                         usage="gather")
        if got is not None:
            # the pinned array OWNS the cold tier — dropping the numpy
            # copy keeps host residency at 1x (pickling round-trips the
            # contents back through numpy, __getstate__). A quantized
            # tier pins all three leaves (int8 rows + sidecars).
            self._host_offload = jax.tree_util.tree_unflatten(tree, got)
            self.host_part = None

    def from_mmap(self, np_array, device_config: DeviceConfig):
        """Construct from pre-partitioned parts (reference feature.py:95-192).
        ``device_config.gpu_parts`` rows land in the HBM tier (concatenated
        in order), ``cpu_part`` in the host tier."""
        parts = [np.asarray(p) for p in device_config.device_parts if p is not None
                 and np.asarray(p).size]
        cache_part = np.concatenate(parts) if parts else \
            np.zeros((0,) + np.asarray(device_config.host_part).shape[1:],
                     dtype=np.asarray(device_config.host_part).dtype)
        self.cache_rows = int(cache_part.shape[0])
        if self.cache_rows:
            self._place(quant.quantize(cache_part,
                                       self.dtype_policy["hot"]))
        host = device_config.host_part
        raw = host if host is not None and np.asarray(host).size else None
        if raw is None and np_array is not None and not self.cache_rows:
            raw = np_array
        # quantize BEFORE the contiguity pass: materializing a full-
        # width contiguous fp32 copy first would transiently double the
        # host tier's footprint only to throw the copy away
        self.host_part = None if raw is None else quant.tree_map_tier(
            np.ascontiguousarray,
            quant.quantize(np.asarray(raw), self.dtype_policy["cold"]))
        self._maybe_offload_host()
        self._build_gather()
        return self

    def _mesh_size(self) -> int:
        if self.mesh is not None:
            return self.mesh.devices.size
        return len(self.device_list) if self.device_list else 1

    def _place(self, cache_part):
        # cache_part is a plain array or a QuantizedTensor; placement
        # (replicate / shard, with row padding) applies leaf-wise so a
        # quantized hot tier's sidecars share the data's sharding
        if quant.tier_rows(cache_part) == 0:
            self.device_part = None
            return
        if self.cache_policy == "device_replicate" or self._mesh_size() == 1:
            mesh = self.mesh
            if mesh is not None:
                sharding = NamedSharding(mesh, P())      # replicated
                put = lambda a: jax.device_put(a, sharding)
            else:
                put = jnp.asarray
            self.device_part = quant.tree_map_tier(put, cache_part)
            return
        # p2p_clique_replicate: row-shard the hot set over the mesh axis
        mesh = self.mesh or _default_mesh(self.device_list)
        self.mesh = mesh
        axis = mesh.axis_names[0]
        n_dev = mesh.devices.size
        rows = quant.tier_rows(cache_part)
        pad = (-rows) % n_dev
        sharding = NamedSharding(mesh, P(axis))

        def put(a):
            if pad:
                a = np.concatenate(
                    [a, np.zeros((pad,) + a.shape[1:], a.dtype)])
            return jax.device_put(a, sharding)

        self.device_part = quant.tree_map_tier(put, cache_part)

    def _build_gather(self):
        cache_rows = self.cache_rows

        def translate(ids, order):
            ids = ids.astype(jnp.int32)
            return order[ids] if order is not None else ids

        self._translate = jax.jit(translate)

        def gather_cached(dev_part, ids):
            safe = jnp.clip(ids, 0, max(cache_rows - 1, 0))
            # fused take+dequant: an int8 hot tier reads narrow rows +
            # per-row sidecars and converts only the gathered rows
            return quant.gather_rows(dev_part, safe)

        self._gather_cached = jax.jit(gather_cached)

        def lookup_cached(dev_part, ids, order):
            return gather_cached(dev_part, translate(ids, order))

        # the pure-HBM fast path is ONE dispatch (translate fused into
        # the gather) — a dispatch per lookup is host time the step
        # does not get back
        self._lookup_cached = jax.jit(lookup_cached)

        def lookup_cached_masked(dev_part, ids, order):
            # -1-mask semantics (masked ids -> zero rows) fused into
            # the same single dispatch; the hetero frontier lookup's
            # hot path
            ids_i = ids.astype(jnp.int32)
            safe = jnp.clip(ids_i, 0, max(cache_rows - 1, 0))
            rows = gather_cached(dev_part, translate(safe, order))
            return rows * (ids_i >= 0).astype(rows.dtype)[:, None]

        self._lookup_cached_masked = jax.jit(lookup_cached_masked)

        cold_budget = self.cold_budget
        dedup = bool(self.dedup_cold)
        dedup_budget = (int(self.dedup_cold)
                        if dedup and not isinstance(self.dedup_cold, bool)
                        else None)

        @hot_path
        def lookup_tiered_body(dev_part, host_part, ids, order,
                               masked=False, collector=None):
            # ALL of the lookup is the frontier's row gather: the two
            # tiers' reads, the translation, the compaction and the merge
            # carry their own scopes beneath this one, the mask only this
            with profiling.scope(profiling.QT_GATHER):
                return lookup_tiered_rows(dev_part, host_part, ids, order,
                                          masked, collector)

        def lookup_tiered_rows(dev_part, host_part, ids, order, masked,
                               collector):
            # one dispatch for the WHOLE tiered lookup: hot rows from
            # the HBM cache, cold rows gathered by XLA directly from
            # the (pinned host) cold tier — no Python round trip, no
            # data-dependent shapes. Semantics identical to the numpy
            # path (tested); placement makes it UVA-like on TPU/GPU.
            #
            # Host-memory traffic scales with the MISS RATE, not the
            # batch — and with ``dedup_cold``, with the UNIQUE miss
            # count (hub repeats in a multi-hop frontier collapse to
            # one host read each): cold positions are compacted (rank +
            # sort, the sample_layer_exact_wide hub-budget pattern) and
            # at most a static ``budget`` of host rows is gathered: the
            # budget sizes the block, the cold count is what a step
            # fetches out of a pinned tier (``take_rows``' ``count``) —
            # the reference's UVA kernel likewise touches only the rows
            # it needs (shard_tensor.cu.hpp:49-58). A batch whose cold
            # count exceeds the budget falls back via ``lax.cond`` to
            # the full-batch host gather — correct in every case, only
            # the traffic bound degrades.
            # masked=True (static): -1 ids produce zero rows, fused into
            # the same dispatch (the hetero frontier path); the mask
            # multiply lands on whichever return below fires
            ids_raw = ids.astype(jnp.int32)
            total = cache_rows + quant.tier_rows(host_part)
            ids = jnp.clip(ids_raw, 0, total - 1) if masked else ids_raw
            # both tiers dequantize into ONE lookup dtype (mixed
            # policies — bf16 hot + int8 cold — merge at the wider)
            out_dt = jnp.result_type(*[
                quant.tier_dtype(p) for p in (dev_part, host_part)
                if p is not None])

            def take_host(hids, count=None):
                # named scope: XProf attributes cold-tier (pinned host)
                # gather time to this stage, not one opaque jit blob
                with profiling.scope(profiling.QT_LOOKUP_COLD):
                    return quant.gather_rows(host_part, hids,
                                             count).astype(out_dt)

            def take_hot(hids):
                with profiling.scope(profiling.QT_LOOKUP_HOT):
                    return gather_cached(dev_part, hids).astype(out_dt)

            def finish(rows):
                if not masked:
                    return rows
                return rows * (ids_raw >= 0).astype(rows.dtype)[:, None]

            # the bookkeeping around the two tiers' reads carries its own
            # names beside theirs (profiling.LOOKUP_STAGES)
            with profiling.scope(profiling.QT_LOOKUP_TRANSLATE):
                t = translate(ids, order)
                hot = t < cache_rows
                if masked:
                    # padding slots classify as HOT regardless of where
                    # clip(−1)→node 0 landed in storage: they must not
                    # consume cold_budget (a padded hetero frontier could
                    # otherwise trip the full-gather fallback every batch)
                    hot = hot | (ids_raw < 0)
            n = t.shape[0]
            if collector is not None:
                # the OBSERVED hit rate plan_hot_capacity predicted:
                # counted on the classification mask the lookup already
                # computed (padding excluded), pure jnp, no host sync
                from .metrics import COLD_ROWS, HOT_ROWS, LOOKUP_CALLS
                collector.add(LOOKUP_CALLS, 1)
                if masked:
                    vmask = ids_raw >= 0
                    hot_valid = jnp.sum(hot & vmask)
                    n_valid = jnp.sum(vmask)
                else:
                    hot_valid = jnp.sum(hot)
                    n_valid = n
                collector.add(HOT_ROWS, hot_valid)
                collector.add(COLD_ROWS, n_valid - hot_valid)
            cold_total = quant.tier_rows(host_part)
            with profiling.scope(profiling.QT_LOOKUP_TRANSLATE):
                cold_idx = jnp.clip(t - cache_rows, 0,
                                    max(cold_total - 1, 0))
            budget = _resolve_cold_budget(dedup_budget, cold_budget, n)

            def count_overflow(also=True):
                # the compaction's own test (``n_cold > budget`` below;
                # padding counts as hot), taken OUTSIDE its lax.cond: the
                # lookup that trips it reads all n slots from the host,
                # not ``budget`` of them
                if collector is not None:
                    from .metrics import COLD_OVERFLOW
                    collector.add(COLD_OVERFLOW,
                                  (jnp.sum(~hot) > budget) & also)

            if dev_part is None:
                if dedup and budget < n:
                    # no HBM cache: every slot is cold — dedup still
                    # bounds the host read to unique rows
                    from .ops.dedup import dedup_take
                    return finish(dedup_take(
                        host_part, cold_idx, budget,
                        collector=collector).astype(out_dt))
                return finish(take_host(cold_idx))

            def naive_full():
                hot_rows = take_hot(jnp.where(hot, t, 0))
                cold_rows = take_host(cold_idx)
                with profiling.scope(profiling.QT_LOOKUP_MERGE):
                    return jnp.where(hot[:, None], hot_rows, cold_rows)

            if budget >= n:
                # budget can't beat a full gather: keep the single
                # unconditional host read (also the tiny-batch path)
                return finish(naive_full())

            def compacted_lookup():
                """The cold-compaction narrow path: hot rows gathered
                per slot, up to ``budget`` cold SLOTS scatter-filled
                from the host tier, its own lax.cond full-gather
                fallback when raw cold count overflows. The non-dedup
                path runs this directly; the dedup path runs it as the
                unique-overflow fallback so enabling dedup can never
                move MORE host bytes than leaving it off (a hot-heavy
                batch can overflow the unique budget while its cold
                slots still fit the compaction budget)."""
                hot_rows = take_hot(jnp.where(hot, t, 0))

                def _full(_):
                    cold_rows = take_host(cold_idx)
                    with profiling.scope(profiling.QT_LOOKUP_MERGE):
                        return jnp.where(hot[:, None], hot_rows, cold_rows)

                with profiling.scope(profiling.QT_LOOKUP_COMPACT):
                    cold = ~hot
                    n_cold = jnp.sum(cold).astype(jnp.int32)
                    iota = jnp.arange(n, dtype=jnp.int32)
                    crank = jnp.cumsum(cold).astype(jnp.int32) - 1
                    okey = jnp.where(cold & (crank < budget), crank,
                                     jnp.iinfo(jnp.int32).max)
                    _, cpos = jax.lax.sort((okey, iota), num_keys=1)
                    cpos = cpos[:budget]  # cold positions (garbage past n_cold)
                    n_fetch = jnp.minimum(n_cold, budget)
                    c_valid = jnp.arange(budget, dtype=jnp.int32) < n_fetch
                    cold_ids = cold_idx[cpos]
                # [budget, dim]; a host tier fetches the first n_fetch
                rows = take_host(cold_ids, n_fetch)
                with profiling.scope(profiling.QT_LOOKUP_MERGE):
                    tgt = jnp.where(c_valid, cpos, n)       # n = drop slot
                    narrow = hot_rows.at[tgt].set(rows, mode="drop")
                return jax.lax.cond(n_cold > budget, _full,
                                    lambda _: narrow, None)

            if dedup:
                # DEDUPLICATED narrow path: unique over the WHOLE
                # translated frontier (hot AND cold) — hub repeats
                # collapse, the host tier is read once per UNIQUE cold
                # row ([budget, dim], the only host read), both tiers
                # merge at budget size, and the batch pays exactly ONE
                # batch-sized op (the inverse expand) where the naive
                # path pays three (hot gather, cold gather, merge).
                # Overflow tests the unique count, so a duplicate-heavy
                # batch whose raw slot count dwarfs the budget still
                # runs narrow; overflowing batches fall back to the
                # cold-compaction path, which keeps its own traffic
                # bound — exact in every case.
                from .ops.dedup import unique_within_budget
                valid_pos = (ids_raw >= 0) if masked else None
                uniq, inv, n_uniq = unique_within_budget(
                    t, budget, valid=valid_pos, collector=collector)
                count_overflow(n_uniq > budget)
                safe_u = jnp.clip(uniq, 0, total - 1)
                hot_u = safe_u < cache_rows
                hot_rows_u = take_hot(jnp.where(hot_u, safe_u, 0))
                cold_u = jnp.clip(safe_u - cache_rows, 0,
                                  max(cold_total - 1, 0))
                cold_rows_u = take_host(cold_u)
                rows_u = jnp.where(hot_u[:, None], hot_rows_u,
                                   cold_rows_u)
                if masked:
                    # padding expands from a dedicated zero row — the
                    # narrow path then needs no batch-sized mask
                    # multiply (the fallback masks inside finish)
                    zrow = jnp.zeros((1,) + rows_u.shape[1:],
                                     rows_u.dtype)
                    rows_u = jnp.concatenate([rows_u, zrow])
                    inv = jnp.where(valid_pos, inv, budget)
                narrow_fn = lambda _: jnp.take(rows_u, inv, axis=0)
                if masked:
                    return jax.lax.cond(
                        n_uniq > budget,
                        lambda _: finish(compacted_lookup()),
                        narrow_fn, None)
                return finish(jax.lax.cond(
                    n_uniq > budget, lambda _: compacted_lookup(),
                    narrow_fn, None))

            count_overflow()
            return finish(compacted_lookup())

        def lookup_tiered(dev_part, host_part, ids, order, masked=False,
                          collect=False):
            """The fused tiered lookup; ``collect=True`` (static) adds
            the device counter vector (``metrics.NUM_COUNTERS`` int32:
            hot/cold row counts, dedup dup stats) as a second output —
            pure jnp accumulation on masks the lookup already computes,
            so rows are bit-identical and no host sync is added."""
            if not collect:
                return lookup_tiered_body(dev_part, host_part, ids,
                                          order, masked)
            from .metrics import Collector
            col = Collector()
            rows = lookup_tiered_body(dev_part, host_part, ids, order,
                                      masked, col)
            return rows, col.counters()

        self._lookup_tiered_raw = lookup_tiered
        self._lookup_tiered = jax.jit(lookup_tiered,
                                      static_argnums=(4, 5))

    # -- lookup (reference feature.py:296-333) ------------------------------
    def __getitem__(self, node_idx):
        ids = jnp.asarray(node_idx)
        if self._host_offload is not None and self.mmap_array is None:
            # fused offload path: one dispatch, cold rows read from
            # pinned host memory by XLA (UVA-gather analogue). Checked
            # FIRST: a successful offload owns the cold tier
            # (host_part is None then).
            return self._lookup_tiered(self.device_part,
                                       self._host_offload, ids,
                                       self.feature_order)
        if self.host_part is None and self.mmap_array is None:
            return self._lookup_cached(self.device_part, ids,
                                       self.feature_order)
        ids = self._translate(ids, self.feature_order)
        # mixed tiers: device rows on device, host/disk rows on host
        if self.device_part is not None:
            out = self._gather_cached(self.device_part, ids)
        else:
            out = None
        ids_np = np.asarray(jax.device_get(ids))
        cold = ids_np >= self.cache_rows
        pos = np.flatnonzero(cold)
        if pos.size == 0 and out is not None:
            return out
        cold_ids = ids_np[pos] - self.cache_rows
        host_rows = self._read_cold(cold_ids)
        if out is None:
            shape = (ids_np.shape[0],) + host_rows.shape[1:]
            out = jnp.zeros(shape, dtype=host_rows.dtype)
        else:
            # mixed dtype policies (bf16 hot + int8 cold) merge at the
            # wider dtype, matching the fused lookup's out_dt
            out_dt = jnp.result_type(out.dtype, host_rows.dtype)
            out = out.astype(out_dt)
            host_rows = host_rows.astype(out_dt)
        # pad the scatter to the next power of two: the cold-row count is
        # data-dependent, and a distinct shape per batch would compile
        # (and cache) a new executable every lookup — unbounded memory
        # growth plus per-batch compile stalls (caught by
        # scripts/check_leak.py). Pad positions land past the end and
        # mode="drop" discards them.
        # (pad on HOST: device-side padding of the unbucketed array would
        # itself compile one concat executable per distinct cold count —
        # the very growth the bucketing exists to stop. The cost is up to
        # 2x H2D bytes on pathological bucket boundaries, ~1x typically.)
        bucket = 1 << max(int(pos.size) - 1, 0).bit_length()
        rows_p = np.zeros((bucket,) + host_rows.shape[1:], host_rows.dtype)
        rows_p[:pos.size] = host_rows
        pos_p = np.full(bucket, out.shape[0], pos.dtype)  # OOB -> dropped
        pos_p[:pos.size] = pos
        return out.at[jnp.asarray(pos_p)].set(jax.device_put(rows_p),
                                              mode="drop")

    def getitem_masked(self, node_idx):
        """``feature[clip(ids)]`` with -1-mask semantics: masked ids
        produce zero rows. ONE dispatch on the pure-HBM and fused
        offload paths (the hetero lookup's hot path);
        the numpy/disk tiers compose the mask around the lookup."""
        ids = jnp.asarray(node_idx)
        if self._host_offload is not None and self.mmap_array is None:
            return self._lookup_tiered(self.device_part,
                                       self._host_offload, ids,
                                       self.feature_order, True)
        if (self.host_part is None and self._host_offload is None
                and self.mmap_array is None):
            return self._lookup_cached_masked(self.device_part, ids,
                                              self.feature_order)
        safe = jnp.clip(ids, 0, self.size(0) - 1)
        rows = self[safe]
        return rows * (ids >= 0).astype(rows.dtype)[:, None]

    def lookup_tiered(self, node_idx, masked=False,
                      collect_metrics=False):
        """Tiered lookup with opt-in telemetry: returns ``rows``, or
        ``(rows, counters)`` with ``collect_metrics=True`` — a
        ``metrics.NUM_COUNTERS`` int32 vector carrying the OBSERVED
        hot/cold row counts (actual hit rate vs the
        ``plan_hot_capacity`` prediction) and, with ``dedup_cold``, the
        batch's dup statistics. On the fused offload path the counters
        are a device array accumulated inside the one dispatch (zero
        host syncs; rows bit-identical to the metrics-off lookup), and
        a pure-HBM store counts on device too (every valid slot is
        hot); the numpy/disk tiers — which round-trip through the host
        anyway — return a numpy vector computed alongside (dup
        STATISTICS only: those tiers never run a compaction, so the
        dedup call/overflow event slots stay zero there). Feed either
        to ``metrics.StepStats.add_counters``."""
        ids = jnp.asarray(node_idx)
        if not collect_metrics:
            return self.getitem_masked(ids) if masked else self[ids]
        if self._host_offload is not None and self.mmap_array is None:
            return self._lookup_tiered(self.device_part,
                                       self._host_offload, ids,
                                       self.feature_order, masked, True)
        if (self.host_part is None and self._host_offload is None
                and self.mmap_array is None):
            # pure-HBM store: everything valid is a hot-tier hit
            from . import metrics as _m
            rows = self.getitem_masked(ids) if masked else self[ids]
            col = _m.Collector()
            col.add(_m.LOOKUP_CALLS, 1)
            col.add(_m.HOT_ROWS,
                    (ids >= 0).sum() if masked else ids.shape[0])
            return rows, col.counters()
        pf = self._cold_prefetch
        pf_before = pf.counters() if pf is not None else None
        rows = self.getitem_masked(ids) if masked else self[ids]
        from . import metrics as _m
        ids_np = np.asarray(jax.device_get(ids)).astype(np.int64)
        valid = (ids_np >= 0) if masked else np.ones_like(ids_np, bool)
        order = self._order_host()
        if order is not None:
            t = order[np.clip(ids_np, 0, order.shape[0] - 1)]
        else:
            t = np.clip(ids_np, 0, max(self.size(0) - 1, 0))
        vec = np.zeros((_m.NUM_COUNTERS,), np.int32)
        hot = int(((t < self.cache_rows) & valid).sum())
        vec[_m.LOOKUP_CALLS] = 1
        vec[_m.HOT_ROWS] = hot
        vec[_m.COLD_ROWS] = int(valid.sum()) - hot
        if self.dedup_cold:
            budget = _resolve_cold_budget(self.dedup_cold,
                                          self.cold_budget,
                                          int(ids_np.shape[0]))
            # mirror the fused path's gate (budget >= n short-circuits
            # to the full gather before any dedup runs) but record only
            # the dup STATISTICS — this tier never runs a compaction,
            # so claiming calls/overflow events would be false
            if budget < int(ids_np.shape[0]):
                vec[_m.DEDUP_TOTAL] = int(valid.sum())
                vec[_m.DEDUP_UNIQUE] = int(np.unique(t[valid]).size)
        if pf_before is not None:
            # the prefetch rows THIS lookup's gather consumed: hit and
            # sync counts are exact (``gather`` ran synchronously on
            # this thread inside the lookup above); staged rows drain —
            # a batch's publication runs during the PREVIOUS step, so
            # everything staged since the last metered lookup is this
            # batch's staged-rows/batch figure
            d = pf.counters() - pf_before
            vec[_m.PREFETCH_HIT_ROWS] = int(d[0])
            vec[_m.PREFETCH_SYNC_ROWS] = int(d[1])
            vec[_m.PREFETCH_STAGED_ROWS] = pf.drain_staged()
            # the parallel-IO facts behind those staged rows (same
            # since-last-metered-lookup attribution): extents issued,
            # rows/bytes the device moved, observed queue-depth peak
            io = pf.drain_io()
            vec[_m.IO_EXTENTS] = int(io[0])
            vec[_m.IO_READ_ROWS] = int(io[1])
            vec[_m.IO_READ_BYTES] = int(min(io[2], 2**31 - 1))
            vec[_m.IO_DEPTH_PEAK] = int(io[3])
            vec[_m.IO_RETRIES] = int(io[4])
            vec[_m.STAGING_RESTARTS] = int(io[5])
        # faults fired since the last metered lookup (process-global:
        # the armed FaultPlan counts every site; 0 when disarmed)
        from . import faults as _faults
        vec[_m.FAULTS_INJECTED] = _faults.drain_injected()
        return rows, vec

    def prefetch(self, node_idx):
        """Start this lookup on the staging pipeline and return a
        ``concurrent.futures.Future`` whose ``result()`` equals
        ``feature[node_idx]``. The expensive part of a tiered lookup is
        host-side (cold-row fancy-index + transfer); staging it off the
        main thread lets batch i+1's staging overlap batch i's model
        step — double-buffering, the TPU answer to the reference's UVA
        gather overlapping transfer with compute
        (quiver_feature.cu:174-293). The pipeline is depth-bounded
        (backpressure past 2 in-flight batches), ordered, and shut down
        by :meth:`close` (or automatically when the store is GC'd)."""
        if self._pool is None:
            from .pipeline import Pipeline
            self._pool = Pipeline(depth=2, name="quiver-feature-prefetch")
        ids = jnp.asarray(node_idx)    # snapshot before caller moves on
        return self._pool.submit(self.__getitem__, ids)

    def close(self):
        """Shut down the staging pipelines (idempotent): the lookup
        prefetch pipeline and, when attached, the cold-tier prefetcher.
        Without an explicit call each pipeline's ``weakref.finalize``
        stops its worker when the store is collected — long runs that
        churn Feature objects no longer accumulate staging threads."""
        pool, self._pool = self._pool, None
        if pool is not None:
            pool.close()
        pf, self._cold_prefetch = self._cold_prefetch, None
        if pf is not None:
            pf.close()

    # -- host copies of immutable device metadata ---------------------------
    def _order_host(self) -> Optional[np.ndarray]:
        """Host copy of ``feature_order`` (immutable once built and
        O(n_nodes) — cached keyed by identity so a rebuilt store
        invalidates, instead of a full D2H transfer per use)."""
        if self.feature_order is None:
            return None
        if (self._order_np is None
                or self._order_np[0] is not self.feature_order):
            self._order_np = (self.feature_order,
                              np.asarray(jax.device_get(
                                  self.feature_order)))
        return self._order_np[1]

    def _disk_map_host(self) -> np.ndarray:
        """Host copy of ``disk_map`` (same identity-keyed caching as
        :meth:`_order_host`; the old per-read ``device_get`` paid a
        full O(n_nodes) transfer on every cold read)."""
        if (self._disk_map_np is None
                or self._disk_map_np[0] is not self.disk_map):
            self._disk_map_np = (self.disk_map,
                                 np.asarray(jax.device_get(
                                     self.disk_map)))
        return self._disk_map_np[1]

    # -- online hot-set rotation (qt-act) -----------------------------------
    def rotate_hot_set(self, promote, demote):
        """Swap ``demote`` (hot nodes) out of the HBM tier for
        ``promote`` (cold nodes) — FastSample-style locality-aware
        cache adaptation (arXiv 2311.17847) through the hot-order
        permutation machinery, ONLINE: stored row bytes (codes AND
        sidecars for a quantized tier) move between tiers verbatim and
        ``feature_order`` swaps the two nodes' storage rows, so every
        lookup is bit-identical across the rotation and no jitted
        program recompiles (``_lookup_tiered`` takes the tiers and the
        order as ARGUMENTS; the swapped arrays keep their shapes and
        dtypes, so the executable cache stays flat —
        ``scripts/check_leak.py`` phase 13 pins both).

        Requirements (refused loudly otherwise — a refused rotation
        must never half-move rows): a built store with
        ``feature_order``, a non-empty HBM tier AND a numpy host tier
        (disk/mmap stores adapt through ``stage_frontier`` ring
        promotion instead; ``host_placement="offload"`` pins the cold
        tier immutably), a replicated hot tier (a row-sharded tier
        would need a cross-device scatter), and IDENTICAL hot/cold
        dtype policies (mixed policies re-encode on crossing, which
        breaks bit-identity).

        A ``ServeEngine`` built over this store captured the tier
        arrays at construction — call ``engine.refresh_feature()``
        after rotating. Returns ``{"rotated": k}``."""
        if self.feature_order is None:
            raise ValueError(
                "rotate_hot_set needs a hot-order store (feature_order "
                "is None — construct with a csr_topo or set_local_order)")
        if not self.cache_rows or self.device_part is None:
            raise ValueError("rotate_hot_set needs a non-empty HBM tier")
        if self.host_part is None:
            raise ValueError(
                "rotate_hot_set needs a numpy host tier (disk/mmap "
                "stores promote through stage_frontier; offloaded cold "
                "tiers are pinned immutably)")
        if self.cache_policy != "device_replicate" and self._mesh_size() > 1:
            raise ValueError(
                "rotate_hot_set supports replicated hot tiers only "
                "(a row-sharded tier would need a cross-device scatter)")
        if self.dtype_policy["hot"] != self.dtype_policy["cold"]:
            raise ValueError(
                f"rotate_hot_set needs identical hot/cold dtype "
                f"policies (got {self.dtype_policy!r}); rows crossing "
                "tiers would re-encode and break bit-identity")
        promote = np.unique(np.asarray(promote, np.int64).reshape(-1))
        demote = np.unique(np.asarray(demote, np.int64).reshape(-1))
        if promote.size != demote.size:
            raise ValueError(
                f"promote/demote must pair 1:1, got {promote.size} vs "
                f"{demote.size} unique ids")
        if promote.size == 0:
            return {"rotated": 0}
        order = np.array(self._order_host(), copy=True)
        n = order.shape[0]
        for ids, what in ((promote, "promote"), (demote, "demote")):
            if ids[0] < 0 or ids[-1] >= n:
                raise ValueError(f"{what} ids out of range [0, {n})")
        rp = order[promote]            # storage rows, must be cold
        rd = order[demote]             # storage rows, must be hot
        if not (rp >= self.cache_rows).all():
            raise ValueError("promote ids must currently be cold rows")
        if not (rd < self.cache_rows).all():
            raise ValueError("demote ids must currently be hot rows")
        host_rows = rp - self.cache_rows
        dev_leaves = quant.tier_parts(self.device_part)
        host_leaves = quant.tier_parts(self.host_part)
        # pad the row sets to a power-of-two bucket: the device gather
        # and scatter below compile once PER SHAPE, and a census-driven
        # rotation produces a different pair count almost every time —
        # unbucketed, each rotation pays a fresh ~200ms compile (a
        # compile storm on the adaptation cadence) and grows the
        # executable set without bound. Padding repeats pair 0, so the
        # duplicate scatter writes are byte-identical to the real one.
        k = int(rd.size)
        pad = (1 << max(3, (k - 1).bit_length())) - k
        rd_pad = np.concatenate([rd, np.full(pad, rd[0], rd.dtype)])
        new_dev = []
        for dl, hl in zip(dev_leaves, host_leaves):
            if dl is None:
                new_dev.append(None)
                continue
            # the demoted hot rows come down once (host sync is fine:
            # rotation is a rare control action, never on the hot path)
            down = np.asarray(jax.device_get(dl[rd_pad]))[:k]
            up = np.asarray(hl[host_rows])
            up_pad = np.concatenate([up, np.repeat(up[:1], pad,
                                                   axis=0)])
            # functional device update -> a NEW array of the same
            # shape/dtype (no recompile); numpy host update in place
            new_dev.append(jnp.asarray(dl).at[rd_pad].set(up_pad))
            hl[host_rows] = down
        if quant.is_quantized(self.device_part):
            self.device_part = quant.QuantizedTensor(*new_dev)
        else:
            self.device_part = new_dev[0]
        order[promote] = rd
        order[demote] = rp
        # a NEW order array: the identity-keyed _order_host cache
        # invalidates itself, and jitted programs see a same-shape arg
        self.feature_order = jnp.asarray(order, dtype=jnp.int32)
        return {"rotated": int(promote.size)}

    # -- cold-tier (disk) prefetch ------------------------------------------
    def enable_cold_prefetch(self, capacity_rows: int = 65_536,
                             depth: int = 2, decode_staged: bool = True,
                             wait_inflight: bool = True,
                             workers: int = 1, io_qd: int = 16,
                             io_cap_bytes: int = 1 << 20,
                             io_engine: str = "auto", io_model=None):
        """Attach a frontier-keyed asynchronous prefetcher to the mmap
        disk tier (requires :meth:`set_mmap_file` first): publish a
        FUTURE batch's frontier with :meth:`stage_frontier` (or drive
        the loop with ``async_sampler.sample_ahead``) and the disk read
        overlaps the current step's compute — lookups consult the
        fixed-capacity staging ring first; a miss waits for a staging
        task still in flight (``wait_inflight`` — the read is already
        running, re-issuing it would pay the disk twice) and finally
        falls back to the synchronous read, counted
        (``metrics.PREFETCH_SYNC_ROWS``), never wrong.

        The staging reads are batched parallel IO: ``workers`` staging
        workers shard each publication's unique-row set, and each
        shard's rows read as coalesced extents at queue depth
        ``io_qd`` through ``quiver_tpu.io.ExtentReader`` (``io_engine``
        "auto" probes O_DIRECT and falls back to buffered preadv;
        "mmap" keeps the per-row fancy-index compat path;
        ``io_cap_bytes`` caps one request's size; ``io_model`` is the
        bench's deterministic queue-depth device model). Returns the
        :class:`~quiver_tpu.prefetch.ColdPrefetcher` (re-attaching
        replaces — and closes — a previous one)."""
        if self.mmap_array is None or self.disk_map is None:
            raise ValueError("enable_cold_prefetch needs an mmap disk "
                             "tier (call set_mmap_file first)")
        from .prefetch import ColdPrefetcher
        if self._cold_prefetch is not None:
            self._cold_prefetch.close()
        self._cold_prefetch = ColdPrefetcher(
            self, capacity_rows, depth=depth,
            decode_staged=decode_staged, wait_inflight=wait_inflight,
            workers=workers, io_qd=io_qd, io_cap_bytes=io_cap_bytes,
            io_engine=io_engine, io_model=io_model)
        return self._cold_prefetch

    def stage_frontier(self, node_idx):
        """Publish a FUTURE batch's frontier ids (-1 padding fine) to
        the cold-tier prefetcher. Non-blocking: returns the staging
        ``Future``, or None when no prefetcher is attached or the
        prefetcher is saturated (the publication is dropped — later
        reads fall back to the synchronous path)."""
        pf = self._cold_prefetch
        if pf is None:
            return None
        return pf.publish(node_idx)

    def _read_cold(self, cold_ids: np.ndarray) -> np.ndarray:
        if self.mmap_array is not None and self.disk_map is not None:
            # disk_map is indexed by storage row (reference feature.py:84-93)
            rows = cold_ids + self.cache_rows
            disk_rows = self._disk_map_host()[rows]
            pf = self._cold_prefetch
            if pf is not None:
                return pf.gather(disk_rows, self._dequant_disk)
            return self._dequant_disk(disk_rows)
        if self.host_part is None:
            raise IndexError("ids beyond the cached tier but no host tier")
        return quant.take_np(self.host_part, cold_ids)

    # -- disk tier (reference feature.py:84-93) -----------------------------
    def set_mmap_file(self, path, disk_map, scale=None, zero=None):
        """``scale``/``zero`` (paths or arrays, [rows] or [rows, 1],
        one per MMAP row) mark the mmap file as an int8-quantized tier:
        disk reads dequantize per-row after the mmap fancy-index, so
        the DISK traffic is the narrow width too (the sidecars are
        resident, ~8 B/row).

        The map and the file are VALIDATED here — a bad ``disk_map``
        (too short, or cold-region entries outside the mmap's rows) or
        a dtype that contradicts the store's policy used to gather
        garbage rows silently (negative entries wrap in numpy fancy
        indexing); every mismatch now raises at attach time. Entries
        for rows below ``cache_rows`` are never read (those rows live
        in HBM) and may hold any sentinel. Re-attaching a tier drops a
        previously enabled cold prefetcher (its ring indexes the old
        file) — call :meth:`enable_cold_prefetch` again after."""
        arr = np.load(path, mmap_mode="r")
        if arr.ndim != 2:
            raise ValueError(
                f"mmap feature file must be [rows, dim], got shape "
                f"{arr.shape}")
        dm = np.asarray(jax.device_get(disk_map) if not
                        isinstance(disk_map, np.ndarray) else disk_map)
        if dm.ndim != 1 or not np.issubdtype(dm.dtype, np.integer):
            raise ValueError(
                "disk_map must be a 1-D integer array mapping storage "
                f"row -> mmap row, got shape {dm.shape} dtype {dm.dtype}")
        if dm.shape[0] < self.cache_rows:
            raise ValueError(
                f"disk_map has {dm.shape[0]} entries but the HBM tier "
                f"already holds {self.cache_rows} rows — the map must "
                "span the full logical id space (it defines shape[0])")
        cold = dm[self.cache_rows:]
        bad = int(((cold < 0) | (cold >= arr.shape[0])).sum())
        if bad:
            raise ValueError(
                f"{bad} disk_map entries in the cold region (storage "
                f"rows >= {self.cache_rows}) fall outside the mmap's "
                f"{arr.shape[0]} rows — negative entries wrap in numpy "
                "fancy indexing and would gather garbage rows silently")
        dim = None
        for tier in (self.device_part, self.host_part,
                     self._host_offload):
            if tier is not None:
                dim = quant.tier_dim(tier)
                break
        if dim is not None and arr.shape[1] != dim:
            raise ValueError(
                f"mmap rows are {arr.shape[1]} wide but the store's "
                f"resident tiers are {dim} wide")
        load = lambda s: (None if s is None else
                          np.load(s) if isinstance(s, str) else np.asarray(s))
        ds, dz = load(scale), load(zero)
        if (ds is None) != (dz is None):
            raise ValueError("quantized disk tier needs BOTH scale and "
                             "zero sidecars")
        if ds is not None:
            ds = ds[:, None] if ds.ndim == 1 else ds
            dz = dz[:, None] if dz.ndim == 1 else dz
            want = (arr.shape[0], 1)
            if tuple(ds.shape) != want or tuple(dz.shape) != want:
                raise ValueError(
                    f"scale/zero sidecars must be [rows, 1] aligned "
                    f"with the mmap ({want}), got {tuple(ds.shape)} / "
                    f"{tuple(dz.shape)}")
            if arr.dtype != np.int8:
                raise ValueError(
                    "scale/zero sidecars mark an int8-quantized tier "
                    f"but the mmap dtype is {arr.dtype}")
        else:
            if arr.dtype == np.int8:
                raise ValueError(
                    "int8 mmap without scale/zero sidecars would be "
                    "returned as raw codes — pass the sidecars (or "
                    "store the file dequantized)")
            if self.dtype_policy["cold"] == "int8":
                raise ValueError(
                    "store's cold dtype policy is int8 but the mmap "
                    f"tier is un-sidecar'd {arr.dtype} — quantize the "
                    "file (partition.save_disk_tier) or drop the policy")
        self.mmap_array = arr
        self.disk_map = jnp.asarray(dm)
        self._disk_map_np = (self.disk_map, dm)
        self.disk_scale = ds
        self.disk_zero = dz
        if self._translate is None:
            # a bare Feature whose ONLY tier is the disk map (no
            # from_cpu_tensor/from_mmap ran) still needs the lookup
            # closures — without this the first lookup dies on a None
            # _translate
            self._build_gather()
        if self._cold_prefetch is not None:
            self._cold_prefetch.close()
            self._cold_prefetch = None

    def _dequant_disk(self, disk_rows: np.ndarray) -> np.ndarray:
        if getattr(self, "disk_scale", None) is None:
            return np.asarray(self.mmap_array[disk_rows])
        # the ONE sidecar-decode convention (ops/quant.py) — the disk
        # tier is just a QuantizedTensor whose data leaf is the mmap
        return quant.take_np(
            quant.QuantizedTensor(self.mmap_array, self.disk_scale,
                                  self.disk_zero), disk_rows)

    def read_mmap(self, ids):
        return self._dequant_disk(np.asarray(ids))

    def set_local_order(self, local_order):
        """Inverse permutation for node-local ordering
        (reference feature.py:283-294)."""
        local_order = jnp.asarray(local_order, jnp.int32)
        n = local_order.shape[0]
        self.feature_order = jnp.zeros((n,), jnp.int32).at[local_order].set(
            jnp.arange(n, dtype=jnp.int32))

    # -- shape protocol ------------------------------------------------------
    @property
    def shape(self):
        if self.disk_map is not None:
            # disk tier active: disk_map spans the FULL logical id
            # space (it is indexed by storage row in _read_cold), so it
            # IS the row count — cache+host alone would under-report
            # (reference feature.py:335-354 likewise reports the full
            # logical space)
            rows = int(self.disk_map.shape[0])
        else:
            cold = (self.host_part if self.host_part is not None
                    else self._host_offload)
            rows = self.cache_rows + (0 if cold is None
                                      else quant.tier_rows(cold))
        dim = None
        for tier in (self.device_part, self.host_part,
                     self._host_offload, self.mmap_array):
            if tier is not None:
                dim = quant.tier_dim(tier)
                break
        return (rows, dim)

    def size(self, dim: int) -> int:
        return self.shape[dim]

    def dim(self) -> int:
        return self.shape[1]

    # -- pickling: drop compiled closures, rebuild on load ------------------
    def __getstate__(self):
        state = {k: getattr(self, k) for k in self.__dict__
                 if k not in ("_gather_cached", "_translate",
                              "_lookup_cached", "_lookup_cached_masked",
                              "_lookup_tiered", "_lookup_tiered_raw",
                              "_host_offload", "_pool",
                              "_cold_prefetch", "_disk_map_np",
                              "_order_np")}
        # the pinned_host array doesn't pickle; round-trip its contents
        # through numpy and re-place on load
        if self._host_offload is not None and state.get("host_part") is None:
            state["host_part"] = quant.tree_map_tier(
                np.asarray, jax.device_get(self._host_offload))
        return state

    def __setstate__(self, state):
        self.__dict__.update(state)
        self._gather_cached = None
        self._translate = None
        self._lookup_cached = None
        self._lookup_cached_masked = None
        self._lookup_tiered = None
        self._lookup_tiered_raw = None
        self._host_offload = None
        self._pool = None
        self._cold_prefetch = None     # threads never round-trip pickle
        self._disk_map_np = None
        self._order_np = None
        # older pickles predate the knobs
        self.__dict__.setdefault("cold_budget", None)
        self.__dict__.setdefault("dedup_cold", False)
        self.__dict__.setdefault("dtype_policy",
                                 {"hot": None, "cold": None})
        self.__dict__.setdefault("disk_scale", None)
        self.__dict__.setdefault("disk_zero", None)
        self._maybe_offload_host()
        self._build_gather()

    # -- process sharing compat ---------------------------------------------
    def share_ipc(self):
        return (self.rank, self.device_list, self.device_cache_size,
                self.cache_policy, self.csr_topo, self)

    @classmethod
    def new_from_ipc_handle(cls, rank, ipc_handle):
        return ipc_handle[-1]

    @classmethod
    def lazy_from_ipc_handle(cls, ipc_handle):
        return ipc_handle[-1]

    def lazy_init_from_ipc_handle(self):
        return self


class ExchangeCapPlan(NamedTuple):
    """Degree-mass-aware sizing of the compact exchange's per-owner
    request-slot budget (the ``exchange_cap`` knob) — the exchange
    analogue of ``quant.plan_hot_capacity``."""

    cap: int             # per-owner request slots ([H, cap] block)
    unique_budget: int   # cap * hosts — the compact unique-table size
    owner_frac: float    # heaviest owner's expected request share
    balanced_cap: int    # the ownership-blind sizing, for the log


class PartitionInfo:
    """Multi-host placement metadata (reference feature.py:461-526):
    ``global2host`` maps node -> owning host; optional per-host replicated
    set; ``global2local`` translates global -> host-local row."""

    def __init__(self, device=None, host: int = 0, hosts: int = 1,
                 global2host=None, replicate=None, global2local=None):
        """``global2local`` (optional, [N] int32) is the book of a store
        whose shards are laid out already (``DistFeature.from_shards``):
        node -> its row on its owner, taken as given and kept where it
        is (a device array stays on its devices; only the per-host row
        counts come to the host). Absent, a node's local row is its rank
        among its owner's nodes in ascending id order, computed on the
        host — the layout ``DistFeature.from_partition`` builds."""
        self.host = host
        self.hosts = hosts
        self.global2host = jnp.asarray(global2host, jnp.int32)
        self.replicate = None if replicate is None else \
            jnp.asarray(replicate, jnp.int32)
        self.node_count = int(self.global2host.shape[0])
        if global2local is None:
            self._init_global2local()
            return
        if replicate is not None:
            raise ValueError(
                "a given global2local describes shards that exist; lay "
                "the replicated rows into them and leave `replicate` "
                "out, or let PartitionInfo compute the book")
        self.global2local = jnp.asarray(global2local, jnp.int32)
        if self.global2local.shape != self.global2host.shape:
            raise ValueError("global2local and global2host differ in shape")
        self.local_sizes = [int(c) for c in jax.device_get(
            jnp.bincount(self.global2host, length=hosts))]

    def _init_global2local(self):
        g2h = np.asarray(jax.device_get(self.global2host))
        g2l = np.zeros(self.node_count, dtype=np.int32)
        self.local_sizes = []
        for h in range(self.hosts):
            owned = np.flatnonzero(g2h == h)
            g2l[owned] = np.arange(owned.size, dtype=np.int32)
            self.local_sizes.append(int(owned.size))
        if self.replicate is not None:
            # replicated nodes live at the tail of *this* host's store
            rep = np.asarray(jax.device_get(self.replicate))
            base = self.local_sizes[self.host]
            g2l[rep] = base + np.arange(rep.size, dtype=np.int32)
        self.global2local = jnp.asarray(g2l)

    def plan_exchange_cap(self, frontier_cap: int, degree=None,
                          dup_factor: float = 8.0,
                          slack: float = 1.25) -> ExchangeCapPlan:
        """Size the compact exchange's per-owner request budget from
        THIS partition's skew (the exchange analogue of
        ``quant.plan_hot_capacity``): a frontier of ``frontier_cap``
        slots holds roughly ``frontier_cap / dup_factor`` distinct ids
        (multi-hop frontiers are mostly -1 padding plus repeated hubs;
        bench fanouts run 10-50x), and each owner's share of those
        requests is proportional to its nodes' degree mass (minibatch
        frontiers hit nodes degree-proportionally) — or to its node
        count when ``degree`` is omitted. ``cap`` is the heaviest
        owner's expected unique-request load times ``slack``; pass it
        as ``exchange_cap`` to the dist step / ``DistFeature``.
        Overflow never costs correctness (the exchange takes further
        rounds of the same block), only time — so ``slack`` trades
        wire bytes against how often a second round runs."""
        uniq = max(int(frontier_cap / max(dup_factor, 1.0)), self.hosts)
        g2h = np.asarray(jax.device_get(self.global2host))
        if degree is not None:
            deg = np.asarray(jax.device_get(degree), np.float64)
            mass = np.zeros(self.hosts, np.float64)
            np.add.at(mass, g2h, deg[:g2h.shape[0]])
        else:
            mass = np.bincount(g2h, minlength=self.hosts).astype(
                np.float64)
        from .comm import cap_for_expected_load
        frac = float(mass.max() / (mass.sum() or 1.0))
        frac = max(frac, 1.0 / self.hosts)
        cap = min(cap_for_expected_load(uniq * frac, slack),
                  int(frontier_cap))
        balanced = cap_for_expected_load(uniq / self.hosts, slack)
        return ExchangeCapPlan(cap, cap * self.hosts, frac, balanced)

    def dispatch(self, ids):
        """Split request ids per owning host; replicated ids resolve
        locally. Returns (per-host local-id arrays, per-host positions)."""
        ids_np = np.asarray(jax.device_get(jnp.asarray(ids)))
        g2h = np.asarray(jax.device_get(self.global2host))
        g2l = np.asarray(jax.device_get(self.global2local))
        owner = g2h[ids_np]
        if self.replicate is not None:
            rep = np.zeros(self.node_count, bool)
            rep[np.asarray(jax.device_get(self.replicate))] = True
            owner = np.where(rep[ids_np], self.host, owner)
        host_ids, host_pos = [], []
        for h in range(self.hosts):
            pos = np.flatnonzero(owner == h)
            host_ids.append(g2l[ids_np[pos]])
            host_pos.append(pos)
        return host_ids, host_pos


class DistFeature:
    """Cross-host feature lookup = dispatch -> collective exchange -> local
    gather -> scatter (reference feature.py:529-567). The hand-scheduled
    NCCL send/recv protocol is replaced by one ``all_to_all`` pair over the
    mesh's host axis.

    Two modes:
    - **SPMD** (``from_partition`` under a mesh): ``dist[ids]`` with
      ``ids`` the concatenated per-host batches [H*B] (-1 fill ok) runs
      dispatch + exchange + scatter as ONE jitted program
      (``comm.build_dist_lookup_fn``) — the production multi-host path;
      identical on a virtual CPU mesh, a TPU slice, or multi-slice DCN.
    - **local/peers** (a ``Feature`` + optional in-process peer registry):
      host-driven dispatch for single-process tests of the protocol.
      NOT a production path: every lookup round-trips the ids through
      numpy (``device_get`` + per-host ``flatnonzero``) and gathers
      per host on the Python side — fine for protocol tests and demos,
      ~unusable at training batch rates. Use ``from_partition`` (the
      one-jitted-program SPMD path) for real workloads.
    """

    def __init__(self, feature: Optional[Feature], info: PartitionInfo,
                 comm, dedup_cold=False, exchange_cap=None,
                 collect_metrics=False, merge_counters=False):
        self.feature = feature
        self.info = info
        self.comm = comm
        # dedup_cold: run the SPMD lookup over the batch's UNIQUE ids
        # (static budget, rounded up to a host multiple) and expand, so
        # the all_to_all exchange ships each remote row once per batch
        # instead of once per frontier slot. True = default budget
        # max(len(ids)//4, hosts); an int sets the budget. Batches
        # whose unique count overflows fall back to the plain
        # full-batch lookup (one scalar D2H sync decides the path).
        self.dedup_cold = dedup_cold
        # exchange_cap: run the exchange itself over the compact
        # deduplicated [H, cap] request block (comm.dist_lookup_local)
        # instead of the dense [H, B] one — dedup + bucketing + the
        # further rounds an overflowing bucket takes all happen INSIDE
        # the jitted program (no host sync; the count of rounds is
        # shard-uniform). True sizes cap per batch shape
        # (comm.default_exchange_cap); an int pins it — prefer
        # info.plan_exchange_cap(...).cap. Composes with dedup_cold
        # (the compact table then sees the already-unique ids).
        self.exchange_cap = exchange_cap
        # collect_metrics: the SPMD lookup program also emits the
        # [H, metrics.NUM_COUNTERS] device counter block (fallback
        # flag, peak bucket load vs cap, dup stats), stashed on
        # ``self.last_counters`` after each lookup — a device array,
        # read it lazily (metrics.StepStats.add_counters) to keep the
        # lookup sync-free. Rows are bit-identical either way.
        self.collect_metrics = bool(collect_metrics)
        # merge_counters: fold the per-shard block over the host axis
        # ON DEVICE before it leaves the lookup (psum add slots, pmax
        # max slots) — ``last_counters`` is then ONE global [N] vector
        # every host can read, instead of a [H, N] block of which a
        # real multi-host process only addresses its own row. Requires
        # collect_metrics.
        self.merge_counters = bool(merge_counters)
        if self.merge_counters and not self.collect_metrics:
            raise ValueError("merge_counters=True requires "
                             "collect_metrics=True")
        self.last_counters = None
        self._spmd_feat = None         # [H*rows_per_host, dim], P(axis)
        self._rows_per_host = None
        self._lookup_fns = {}
        self._rep_args = None

    @classmethod
    def from_partition(cls, feat, info: PartitionInfo, comm,
                       dtype=None, dedup_cold=False,
                       dtype_policy=None,
                       exchange_cap=None,
                       collect_metrics=False,
                       merge_counters=False) -> "DistFeature":
        """Build the SPMD store from the FULL feature array + partition
        metadata: each host's rows land in its shard (replicated nodes
        also in every host's tail), row-sharded over ``comm.mesh``.

        ``dtype_policy`` ("bf16"/"fp16"/"int8") stores the sharded rows
        narrow; the fused lookup then ships the NARROW payload (+ the
        int8 per-row sidecars) through both ``all_to_all`` collectives
        and dequantizes after — DCN bytes per exchanged row drop 2-4x.
        ``exchange_cap`` (``True | int | None``) additionally compacts
        the collectives themselves to a deduplicated [H, cap] request
        block (see ``__init__``) — the two knobs multiply: narrow rows
        x one crossing per distinct remote row. ``collect_metrics=True``
        makes every lookup also emit the device counter block (see
        ``__init__``; stashed on ``last_counters``);
        ``merge_counters=True`` folds it over the host axis on device
        so ``last_counters`` is the GLOBAL [N] vector on every host
        (see ``__init__``).
        """
        if comm.mesh is None:
            raise ValueError("from_partition needs a comm with a mesh")
        feat = np.asarray(feat)
        if dtype is not None:
            feat = feat.astype(dtype)
        hosts = info.hosts
        g2h = np.asarray(jax.device_get(info.global2host))
        rep = (None if info.replicate is None
               else np.asarray(jax.device_get(info.replicate)))
        rep_rows = 0 if rep is None else rep.size
        rows_per_host = max(s + rep_rows for s in info.local_sizes)
        dim = feat.shape[1]
        store = np.zeros((hosts, rows_per_host, dim), feat.dtype)
        for h in range(hosts):
            owned = np.flatnonzero(g2h == h)
            store[h, :owned.size] = feat[owned]
            if rep is not None:
                base = info.local_sizes[h]
                store[h, base:base + rep_rows] = feat[rep]
        axis = comm.axis
        sharding = NamedSharding(comm.mesh, P(axis))
        self = cls(None, info, comm, dedup_cold=dedup_cold,
                   exchange_cap=exchange_cap,
                   collect_metrics=collect_metrics,
                   merge_counters=merge_counters)
        self._spmd_feat = quant.tree_map_tier(
            lambda a: jax.device_put(a, sharding),
            quant.quantize(store.reshape(hosts * rows_per_host, dim),
                           quant.resolve_policy(dtype_policy)))
        self._rows_per_host = rows_per_host
        self._rep_args = cls._rep_args_of(info)
        return self

    @staticmethod
    def _rep_args_of(info: PartitionInfo):
        """(is_rep [N], rep_rank [N], bases [H]) of the lookup's
        replicated-node resolution; None without a replicated set."""
        if info.replicate is None:
            return None
        rep = np.asarray(jax.device_get(info.replicate))
        n = info.node_count
        is_rep = np.zeros(n, bool)
        is_rep[rep] = True
        rep_rank = np.zeros(n, np.int32)
        rep_rank[rep] = np.arange(rep.size, dtype=np.int32)
        bases = np.asarray(info.local_sizes, np.int32)
        return (jnp.asarray(is_rep), jnp.asarray(rep_rank),
                jnp.asarray(bases))

    @classmethod
    def from_shards(cls, spmd_feat, info: PartitionInfo, comm,
                    dedup_cold=False, exchange_cap=None,
                    collect_metrics=False,
                    merge_counters=False) -> "DistFeature":
        """Build the SPMD store from shards that are ALREADY on their
        devices: ``spmd_feat`` [H*rows_per_host, dim], row-sharded over
        ``comm.mesh``'s ``comm.axis`` (a plain array or a
        ``QuantizedTensor`` whose leaves are sharded alike), host ``h``'s
        rows at ``[h*rows_per_host, (h+1)*rows_per_host)`` in the order
        ``info.global2local`` says. No copy of the table is made, on the
        host or anywhere: this is the constructor for a table that no
        one host (or chip) holds — the loader writes each shard where
        it lives and hands over the array. A ``PartitionInfo`` built
        with its own ``global2local`` describes any such layout; one
        that computed its book expects ``from_partition``'s (ascending
        id order on each owner; a replicated set in every shard's tail,
        from row ``info.local_sizes[h]``). The other arguments are
        ``from_partition``'s."""
        if comm.mesh is None:
            raise ValueError("from_shards needs a comm with a mesh")
        hosts = info.hosts
        if comm.mesh.shape[comm.axis] != hosts:
            raise ValueError(
                f"the mesh has {comm.mesh.shape[comm.axis]} shards over "
                f"{comm.axis!r}, the partition {hosts} hosts")
        rows = quant.tier_rows(spmd_feat)
        rep_rows = 0 if info.replicate is None \
            else int(info.replicate.shape[0])
        need = max(info.local_sizes) + rep_rows
        if rows % hosts or rows // hosts < need:
            raise ValueError(
                f"spmd_feat has {rows} rows over {hosts} hosts; the "
                f"partition needs {need} rows on its fullest host")
        self = cls(None, info, comm, dedup_cold=dedup_cold,
                   exchange_cap=exchange_cap,
                   collect_metrics=collect_metrics,
                   merge_counters=merge_counters)
        self._spmd_feat = spmd_feat
        self._rows_per_host = rows // hosts
        self._rep_args = cls._rep_args_of(info)
        return self

    def _getitem_spmd(self, ids):
        ids = jnp.asarray(ids, jnp.int32)
        hosts = self.info.hosts
        if ids.shape[0] % hosts:
            raise ValueError(
                f"SPMD lookup ids length {ids.shape[0]} must be a "
                f"multiple of the host count {hosts} (pad with -1)")
        if self.dedup_cold:
            out = self._getitem_spmd_dedup(ids, hosts)
            if out is not None:
                return out              # None: overflow/tiny — fall through
        return self._getitem_spmd_plain(ids)

    def _getitem_spmd_dedup(self, ids, hosts: int):
        """Exchange each UNIQUE id once: compact the batch into a
        static-budget unique table, run the plain SPMD lookup on it,
        and expand back to batch positions. Fill slots past the unique
        count hold int32-max (clamped to the last node inside the
        lookup, so they exchange one real-but-unused row each — never
        referenced by ``inv``); the batch's own -1 padding dedups to
        one table entry that the lookup maps to zero rows as usual.
        Returns None when the budget can't help (budget >= n) or
        overflows (unique count > budget — exactness preserved by the
        plain full-batch path); the overflow test costs one scalar D2H
        sync."""
        n = ids.shape[0]
        budget = (int(self.dedup_cold)
                  if not isinstance(self.dedup_cold, bool)
                  else max(n // 4, hosts))
        budget = min(-(-budget // hosts) * hosts, n)   # host multiple
        if budget >= n:
            return None
        key = ("dedup", n, budget)
        fns = self._lookup_fns.get(key)
        if fns is None:
            from .ops.dedup import unique_within_budget
            import functools
            compact = jax.jit(functools.partial(
                unique_within_budget, budget=budget))
            expand = jax.jit(
                lambda rows_u, inv: jnp.take(rows_u, inv, axis=0),
                out_shardings=NamedSharding(self.comm.mesh,
                                            P(self.comm.axis)))
            fns = (compact, expand)
            self._lookup_fns[key] = fns
        compact, expand = fns
        uniq, inv, n_uniq = compact(ids)
        if int(n_uniq) > budget:
            return None
        return expand(self._getitem_spmd_plain(uniq), inv)

    def _getitem_spmd_plain(self, ids):
        hosts = self.info.hosts
        b = ids.shape[0] // hosts
        cap = self.exchange_cap
        if cap is True:
            from .comm import default_exchange_cap
            cap = default_exchange_cap(b, hosts)
        elif cap is not None:
            cap = int(cap)
        # dtype passed EXPLICITLY from the store's payload (a bf16 or
        # quantized store must never silently upcast to an fp32 default)
        collect = self.collect_metrics
        merge = self.merge_counters
        key = (b, quant.tier_key(self._spmd_feat),
               self._rep_args is not None, cap, collect, merge)
        fn = self._lookup_fns.get(key)
        if fn is None:
            from .comm import build_dist_lookup_fn
            fn = build_dist_lookup_fn(
                self.comm.mesh, self.comm.axis, self._rows_per_host, b,
                quant.tier_dtype(self._spmd_feat),
                with_replicate=self._rep_args is not None,
                exchange_cap=cap, collect_metrics=collect,
                merge_counters=merge)
            self._lookup_fns[key] = fn
        args = (ids, self.info.global2host.astype(jnp.int32),
                self.info.global2local, self._spmd_feat)
        if self._rep_args is not None:
            args += self._rep_args
        if collect:
            out, self.last_counters = fn(*args)
            return out
        return fn(*args)

    def __getitem__(self, ids):
        if self._spmd_feat is not None:
            return self._getitem_spmd(ids)
        host_ids, host_pos = self.info.dispatch(ids)
        my = self.info.host
        n = int(np.asarray(jax.device_get(jnp.asarray(ids))).shape[0])
        local_rows = self.feature[jnp.asarray(host_ids[my])] \
            if host_ids[my].size else None
        remote = self.comm.exchange(host_ids, self.feature)
        dim = self.feature.shape[1]
        dtype = local_rows.dtype if local_rows is not None else jnp.float32
        out = jnp.zeros((n, dim), dtype=dtype)
        if local_rows is not None:
            out = out.at[jnp.asarray(host_pos[my])].set(local_rows)
        for h, rows in enumerate(remote):
            if rows is not None and host_pos[h].size:
                out = out.at[jnp.asarray(host_pos[h])].set(rows)
        return out
