"""Online inference serving: request-coalescing micro-batch server.

The paper's split — sampling is *latency-critical*, feature collection
is *bandwidth-critical* — was optimized by the training-side PRs for
throughput. This module is the latency side's consumer: a point-query
server for GNN inference (recsys/fraud-style "embed/classify THIS
user now"), where hardware-accelerated sampling only pays off when many
small requests share one fixed-shape device dispatch.

Three layers, smallest first:

**``build_serve_step``** — one jitted, fixed-shape sample -> gather ->
forward program per fanout config: ``step(params, key, feat, forder,
indptr, indices, seeds)`` with ``seeds`` a ``[batch_cap]`` int32 block
(distinct valid ids first, ``-1`` fill at the tail — the training
builders' batch contract) returning ``(next_key, logits[batch_cap,
out_dim])``. The PRNG key is threaded THROUGH the program and its
buffer is donated, so per-dispatch RNG costs zero host work and zero
extra allocations; sampling reuses ``ops.sample_multihop``, the gather
reuses ``masked_feature_gather``/``dedup_feature_gather`` (quantized
stores compose — pass ``quant.quantize(feat, "int8")``), and the
forward is the in-tree flax model applied with ``train=False``.
``collect_metrics=True`` adds the ``metrics.NUM_COUNTERS`` device
counter vector as a third output (zero host syncs — pinned by
``tests/_traffic.host_sync_eqns``).

**``ServeEngine``** — owns the model params, the feature tier, the
topology and a BOUNDED set of pre-compiled fanout variants
(``sizes_variants``, full quality first, cheaper degradation targets
after). Every variant shares the ``[batch_cap]`` seed shape, so the
executable cache holds exactly ``len(sizes_variants)`` serve programs
for the life of the server (``scripts/check_leak.py`` phase 6 pins
flatness across mixed-variant traffic). ``warmup()`` compiles them all
up front — overload is precisely when a compile stall is least
affordable. A ``Feature`` store plugs in directly: its fused tiered
lookup (hot HBM rows + cold host rows, ``-1``-mask semantics,
``dedup_cold`` compaction) runs INSIDE the serve program.

**``MicroBatchServer``** — the async request path. ``submit(node_id)``
admits one request into a bounded queue and returns a
``concurrent.futures.Future``; a coalescer thread drains the queue
into ``[batch_cap]`` batches (duplicate node ids coalesced into the
SAME batch share one seed slot — the dedup convention applied at the
request layer; batches already dispatched are not revisited), a max-wait
deadline bounds how long a lone request can sit waiting for company,
and a ``pipeline.Pipeline`` executes batches so batch i+1 coalesces
while batch i runs. A batch is CLOSED (its seed block built, no more
requests taken) when it is full, or when its deadline has passed AND
the pipeline has room for it: ``pipeline_depth`` closed batches may
exist at once, the running one included, so under load a batch stays
open, and goes on taking the requests that arrive, until the worker
takes the one ahead of it — a closed batch would only wait behind the
ones in flight, and whoever arrived meanwhile would wait a batch more.
Results scatter back to each request's future.
Latency SLOs are first-class: per-REQUEST admission->result latency
lands in ``metrics.StepStats`` (``record_request``) and in a
``metrics.SloBudget`` (target p99 + availability, multi-window
error-budget burn rates), and overload degrades gracefully in two
stages — when queue depth crosses its threshold or the SLO budget
burns unsustainably (``SloBudget.should_shed``: short-window burn
above ``shed_burn_rate`` AND long-window burn above 1.0 — replacing
the raw recent-p99 trigger with a signal that also counts failures and
rejections) the server *sheds quality* (dispatches a smaller
pre-compiled fanout variant); when the admission queue is full it
*sheds load* (``submit`` raises :class:`OverloadError` immediately
instead of queueing unbounded work). ``snapshot()`` is one
JSONL-ready record (kind ``serving``, with an ``slo`` block when a
budget is configured).

Tenancy (qt-capacity) is an OPTIONAL fourth layer over the same
machinery: a ``{name: TenantClass}`` registry (see
``default_tenant_classes`` — interactive / batch / best_effort) makes
``submit(tenant=)`` file every request under an SLO class, and shed
order becomes POLICY instead of arrival luck. Load shed consumes
best-effort first (weighted admission shares under pressure, plus a
full queue displaces the newest lowest-priority queued request to
admit a higher-priority one — never the reverse); quality shed
consumes best-effort first too (under a shed episode batches coalesce
class-pure and each class ignores ``shed_grace`` ladder steps, so
interactive degrades last). Per-tenant accounting — request
histograms, burn/shed/reject counts, an optional per-class
``SloBudget`` — lands as the ``tenant`` JSONL kind
(``emit_tenants``). Tenancy is host-side queue discipline +
accounting only: it never touches the seed block or the compiled
programs, so logits are bit-identical with accounting on or off
(pinned in tests/test_traffic.py) and the executable cache stays flat
(``scripts/check_leak.py`` phase 16).

With ``quiver_tpu.tracing`` enabled every request leaves a span
timeline: per-request ``serve.admission_wait`` / ``serve.coalesce_wait``
/ ``serve.request`` spans (each stamped with its own ``trace_id`` AND
the ``batch`` id of the coalesced batch that carried it) and per-batch
``serve.batch_coalesce`` / ``serve.dispatch`` / ``serve.scatter`` spans
(stamped with the fanout variant) — "where did this request's 100 ms
go?" becomes one Perfetto click-through. Tracing is host-side only:
the jitted serve program is bit-identical with tracing on or off
(pinned in tests/test_serving.py).
"""

from __future__ import annotations

import logging
import queue
import threading
import time
from typing import Callable, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from . import faults, profiling, tracing
from .parallel.frontier import (Walk, documented, feature_splice,
                                layers_to_adjs, walk_doc, walk_frontier)
from .profiling import hot_path
# the typed request-failure vocabulary is shared with the RPC plane
# (quiver_tpu.rpc defines it so the jax-free client can import it):
# ServerClosed = "this replica will never answer; go elsewhere",
# DeadlineExceeded = "the budget is spent; retrying cannot help"
from .rpc import DeadlineExceeded, ServerClosed

_log = logging.getLogger("quiver_tpu.serving")


class OverloadError(RuntimeError):
    """Raised by ``MicroBatchServer.submit`` when the admission queue is
    full — the load-shedding half of overload handling: rejecting at
    admission is the only response that keeps the latency of the
    requests already admitted bounded. When raised from
    ``submit_many``, ``futures`` carries the futures of the requests
    that WERE admitted before the queue filled (they still run)."""

    futures: Sequence = ()


# -- tenancy: per-tenant SLO classes (qt-capacity) ---------------------------


#: the built-in tenant SLO classes, highest priority first; shed order
#: is the REVERSE of this tuple (best_effort absorbs load- and
#: quality-shed first, interactive last). Pinned against
#: docs/observability.md by scripts/lint.sh.
TENANT_CLASS_NAMES = ("interactive", "batch", "best_effort")


class TenantClass:
    """One tenant SLO class — the unit of multi-tenant accounting and
    shed policy in :class:`MicroBatchServer`.

    - ``priority``: admission displacement order. A full queue evicts
      the newest queued request of the lowest priority STRICTLY below
      the arriving request's, never the reverse — so interactive
      admission consumes best-effort queue slots under overload.
    - ``admission_weight``: the class's guaranteed share of the
      admission queue. Under pressure (queue past the shed threshold)
      a class already holding its weighted share is rejected at the
      door while under-share classes still admit — a best-effort flood
      cannot starve interactive admission.
    - ``shed_grace``: how many quality-shed ladder steps this class's
      batches ignore. Grace 0 (best_effort) degrades at the first shed
      step; a grace at least the ladder depth (interactive's default)
      degrades only under a fleet-planned floor — quality shed
      consumes best-effort first, interactive last.
    - ``slo_p99_ms`` (+ the ``slo_*`` shape knobs): arms a per-class
      ``metrics.SloBudget`` for burn accounting. The SERVER's
      aggregate budget still drives the shed trigger; the per-class
      budget is the accounting the ``tenant`` JSONL kind reports.
    """

    def __init__(self, name: str, priority: int,
                 admission_weight: float = 1.0, shed_grace: int = 0,
                 slo_p99_ms: Optional[float] = None,
                 slo_availability: float = 0.99,
                 slo_window_s: float = 300.0,
                 slo_short_window_s: float = 30.0):
        if not name:
            raise ValueError("tenant class needs a name")
        if not admission_weight > 0.0:
            raise ValueError(
                f"admission_weight must be > 0, got {admission_weight}")
        if shed_grace < 0:
            raise ValueError(f"shed_grace must be >= 0, got {shed_grace}")
        self.name = str(name)
        self.priority = int(priority)
        self.admission_weight = float(admission_weight)
        self.shed_grace = int(shed_grace)
        self.slo_p99_ms = (None if slo_p99_ms is None
                           else float(slo_p99_ms))
        self.slo_availability = float(slo_availability)
        self.slo_window_s = float(slo_window_s)
        self.slo_short_window_s = float(slo_short_window_s)

    def make_budget(self):
        """A fresh per-class ``metrics.SloBudget`` (None when this
        class declares no latency target)."""
        from .metrics import SloBudget
        if self.slo_p99_ms is None:
            return None
        return SloBudget(self.slo_p99_ms,
                         availability=self.slo_availability,
                         window_s=self.slo_window_s,
                         short_window_s=self.slo_short_window_s)


def default_tenant_classes(slo_p99_ms: Optional[float] = None) -> dict:
    """The standard three-class registry (``TENANT_CLASS_NAMES``):
    interactive (priority 2, 4x admission weight, never quality-shed
    before the ladder is exhausted, SLO target ``slo_p99_ms``), batch
    (priority 1, 2x weight, one step of grace, 4x the latency target),
    best_effort (priority 0, weight 1, no grace, no latency target —
    it absorbs the shed). Pass the dict to
    ``MicroBatchServer(tenants=...)``."""
    return {
        "interactive": TenantClass(
            "interactive", priority=2, admission_weight=4.0,
            shed_grace=8, slo_p99_ms=slo_p99_ms),
        "batch": TenantClass(
            "batch", priority=1, admission_weight=2.0, shed_grace=1,
            slo_p99_ms=(4.0 * slo_p99_ms if slo_p99_ms is not None
                        else None)),
        "best_effort": TenantClass(
            "best_effort", priority=0, admission_weight=1.0,
            shed_grace=0),
    }


class _TenantState:
    """Per-class accounting the server keeps under ``_counts_lock``
    (except ``budget``, which locks itself)."""

    __slots__ = ("cls", "budget", "hist", "counts", "queued", "share")

    def __init__(self, cls: TenantClass, share: int):
        from .metrics import _Histogram
        self.cls = cls
        self.budget = cls.make_budget()
        self.hist = _Histogram()
        self.queued = 0
        self.share = share
        self.counts = {"requests": 0, "completed": 0, "rejected": 0,
                       "displaced": 0, "deadline_expired": 0,
                       "failed": 0}


# -- the jitted serve step ---------------------------------------------------


# the serve steps have no ``indices_rows`` operand, so none of the knobs
# that read one; the sharded step's gather is the exchange
_SERVE_KNOBS = ("method", "dedup_gather", "fused_hot_hop", "fused_row_cap")
_SHARDED_KNOBS = ("method", "fused_hot_hop", "fused_row_cap")


@documented(walk_doc(_SERVE_KNOBS))
def build_serve_step(model, sizes: Sequence[int], batch_cap: int,
                     gather: Optional[Callable] = None,
                     collect_metrics: bool = False, **walk):
    """Pre-compiled point-inference step for one fanout config.

    Returns ``step(params, key, feat, forder, indptr, indices, seeds)``
    -> ``(next_key, logits)`` (plus the device counter vector with
    ``collect_metrics=True``). ``seeds`` is ``[batch_cap]`` int32,
    distinct valid ids first, -1 fill at the tail (the coalescer
    produces exactly this). Rows of padded slots are garbage — callers
    index only the valid prefix. The ``key`` argument's buffer is
    DONATED: the program splits it internally and returns the successor,
    so the caller threads one key chain through with no per-dispatch
    host-side RNG work (pass a fresh key only at the start).

    ``feat``/``forder``/topology are arguments, not closures (nothing
    large bakes into the executable); ``feat`` may be a quantized store.
    ``gather`` overrides the whole gather callable (the protocol of
    ``parallel.frontier``; it wins over ``dedup_gather``) — the
    ``ServeEngine`` uses this to splice a ``Feature`` store's fused
    tiered lookup into the program, with ``feat`` the ``(device_part,
    host)`` pair; the fused walk over it needs ``gather.hot_rows``. The
    returned step exposes ``.jitted_fns`` (for
    ``StepStats.watch_compiles``) and ``.raw`` (the traceable body, for
    jaxpr pins like ``host_sync_eqns``)."""
    walk = Walk.of("build_serve_step", _SERVE_KNOBS, sizes, walk,
                   gather=gather)

    @hot_path
    def forward(params, key, feat, forder, indptr, indices, seeds,
                collector=None):
        key, sub = jax.random.split(key)
        _, x, layers = walk_frontier(walk, feat, forder, indptr, indices,
                                     seeds, sub, collector=collector)
        adjs = layers_to_adjs(layers, batch_cap, walk.sizes)
        with jax.named_scope("qt_serve_forward"):
            logits = model.apply(params, x, adjs, train=False)
        return key, logits[:batch_cap]

    @hot_path
    def raw(params, key, feat, forder, indptr, indices, seeds):
        if not collect_metrics:
            return forward(params, key, feat, forder, indptr, indices,
                           seeds)
        from .metrics import Collector
        col = Collector()
        key, logits = forward(params, key, feat, forder, indptr,
                              indices, seeds, col)
        return key, logits, col.counters()

    # the key is the one buffer the step both consumes and reproduces —
    # donating it makes the chain alias in place across dispatches
    jitted = jax.jit(raw, donate_argnums=(1,))
    jitted.jitted_fns = (jitted,)
    jitted.raw = raw
    return jitted


# -- the engine: params + tiers + pre-compiled variants ----------------------


def _put_and_launch(engine, step, seeds, *args):
    """The two host stages of an engine's ``run``: the seed block onto
    the device (``serve.put``), then the jitted step's Python call with
    it as last argument (``serve.launch``; returns before the device
    ends). Their seconds land on ``engine.last_stage_s``."""
    with tracing.stage("serve.put") as put:
        block = jnp.asarray(seeds)
    with tracing.stage("serve.launch") as launch:
        out = step(*args, block)
    engine.last_stage_s = (put.dur, launch.dur)
    return out


class ServeEngine:
    """Pre-compiled fanout-variant set over one model + feature tier.

    ``sizes_variants`` is the BOUNDED degradation ladder: index 0 is
    full quality, later entries are the cheaper fanouts the server
    sheds to under pressure (all must have the same hop count — the
    model's layer count). One executable per variant, all sharing the
    ``[batch_cap]`` seed shape; nothing else ever compiles, so the
    executable cache stays flat under any traffic mix.

    ``feat`` is a plain array, a ``quant.QuantizedTensor``, or a
    ``quiver_tpu.Feature`` store — the store's fused tiered lookup
    (HBM hot rows + host cold rows, masked, ``dedup_cold``) is spliced
    into the serve program as its gather stage; stores with a disk/mmap
    tier are refused (their lookup is host-driven and cannot fuse).
    ``collect_metrics=True`` makes every ``run`` also emit the device
    counter vector (stashed on ``last_counters``; read it lazily).

    ``**walk`` goes to every variant's ``build_serve_step`` unopened (the
    walk's knobs, listed there). With ``fused_hot_hop=True`` the ladder
    variants share one census bound, and only cold frontier slots (when
    the store is tiered) take the split lookup.

    ``run(seeds, variant=0)`` is NOT thread-safe (the donated key chain
    is serialized state) — the server funnels all dispatches through
    its single pipeline worker; direct callers must do the same.
    """

    def __init__(self, model, params, topo, feat,
                 sizes_variants: Sequence[Sequence[int]],
                 batch_cap: int,
                 forder=None,
                 collect_metrics: bool = False,
                 seed: int = 0, **walk):
        if not sizes_variants:
            raise ValueError("need at least one fanout variant")
        hops = {len(s) for s in sizes_variants}
        if len(hops) != 1:
            raise ValueError(
                f"all fanout variants must share the model's hop count, "
                f"got lengths {sorted(hops)}")
        self.model = model
        self.params = params
        self.variants: List[List[int]] = [list(s) for s in sizes_variants]
        self.batch_cap = int(batch_cap)
        self.collect_metrics = bool(collect_metrics)
        self.last_counters = None
        # host seconds of the last run's two stages (seed block onto the
        # device, the step's Python call), for the server's counters
        self.last_stage_s = (0.0, 0.0)
        indptr, indices = (topo.indptr, topo.indices) \
            if hasattr(topo, "indptr") else topo
        self._indptr = jnp.asarray(indptr, jnp.int32)
        self._indices = jnp.asarray(indices, jnp.int32)
        gather = None
        self._store = None
        if hasattr(feat, "lookup_tiered"):        # a Feature store
            self._store = feat
            feat, forder, gather = feature_splice(feat)
        elif isinstance(feat, np.ndarray):
            feat = jnp.asarray(feat)
        self._feat = feat
        self._forder = None if forder is None else \
            jnp.asarray(forder, jnp.int32)
        self._steps = [
            build_serve_step(model, sizes, self.batch_cap, gather=gather,
                             collect_metrics=self.collect_metrics, **walk)
            for sizes in self.variants]
        self._key = jax.random.key(seed)

    @property
    def jitted_fns(self):
        """Every jitted serve program (one per variant) — feed to
        ``StepStats.watch_compiles`` so a mid-traffic recompile is a
        reported incident, not silent latency."""
        return tuple(f for s in self._steps for f in s.jitted_fns)

    def pad_seeds(self, node_ids) -> np.ndarray:
        """Host-side batch assembly: distinct valid ids first, -1 fill
        to ``[batch_cap]`` (the serve step's seed contract)."""
        ids = np.asarray(node_ids, np.int32).reshape(-1)
        if ids.shape[0] > self.batch_cap:
            raise ValueError(
                f"{ids.shape[0]} seeds exceed batch_cap={self.batch_cap}")
        out = np.full((self.batch_cap,), -1, np.int32)
        out[:ids.shape[0]] = ids
        return out

    def run(self, seeds, variant: int = 0):
        """Dispatch one ``[batch_cap]`` seed block through the given
        pre-compiled variant. Returns the ``[batch_cap, out_dim]``
        logits device array (no host sync — callers ``device_get`` when
        they scatter). ``seeds`` shorter than ``batch_cap`` are padded
        here; with ``collect_metrics`` the counter vector lands on
        ``last_counters``. The host seconds of ``serve.put`` and
        ``serve.launch`` land on ``last_stage_s``."""
        seeds = np.asarray(seeds, np.int32)
        if seeds.shape[0] != self.batch_cap:
            seeds = self.pad_seeds(seeds)
        out = _put_and_launch(
            self, self._steps[variant], seeds, self.params, self._key,
            self._feat, self._forder, self._indptr, self._indices)
        if self.collect_metrics:
            self._key, logits, self.last_counters = out
        else:
            self._key, logits = out
        return logits

    def warmup(self):
        """Compile every variant now (one dummy dispatch each) so the
        first real request — and the first SHED batch, which arrives
        exactly when the server is drowning — never eats a compile."""
        for v in range(len(self.variants)):
            jax.block_until_ready(self.run(
                np.zeros((self.batch_cap,), np.int32), v))
        return self

    def refresh_feature(self) -> "ServeEngine":
        """Re-splice the underlying ``Feature`` store's tier arrays
        into this engine after an online mutation
        (``Feature.rotate_hot_set``): the engine captured
        ``device_part``/``host_part``/``feature_order`` at
        construction, so a rotation the store applied would otherwise
        serve from the STALE pre-rotation arrays. The gather closure
        itself stays valid (it reads the tiers from program arguments),
        and the refreshed arrays must keep their shapes and dtypes —
        verified here, so a refresh can never recompile (the
        executable-cache flatness ``check_leak`` phase 13 pins)."""
        if self._store is None:
            raise ValueError(
                "refresh_feature needs an engine built over a Feature "
                "store (this one was built over a plain array)")
        feat, forder, _ = feature_splice(self._store)

        def sig(t):
            return [(tuple(l.shape), str(l.dtype))
                    for l in jax.tree_util.tree_leaves(t)]

        if sig(feat) != sig(self._feat):
            raise ValueError(
                "refreshed feature tiers changed shape or dtype — "
                "refusing (the serve programs would recompile)")
        self._feat = feat
        self._forder = None if forder is None else \
            jnp.asarray(forder, jnp.int32)
        return self


# -- sharded serving: one partitioned store under the whole fleet ------------


@documented(walk_doc(_SHARDED_KNOBS))
def build_sharded_serve_step(model, sizes: Sequence[int], batch_cap: int,
                             mesh, axis: str, rows_per_host: int,
                             exchange_cap=None,
                             home: Optional[int] = None,
                             collect_metrics: bool = False, **walk):
    """The serve step over a ``DistFeature``-partitioned store: ONE
    jitted ``shard_map`` program per fanout config whose gather stage is
    the PR 4 compact deduplicated exchange (``comm.dist_lookup_local``)
    instead of a resident-array read.

    Returns ``step(params, key, spmd_feat, g2h, g2l, indptr, indices,
    seeds)`` -> ``(next_key, logits[batch_cap, out_dim])`` (plus the
    GLOBAL ``[metrics.NUM_COUNTERS]`` vector with ``collect_metrics``,
    ``pmerge_counters``-folded over the mesh axis on device).
    ``spmd_feat`` is the ``P(axis)``-sharded ``[H*rows_per_host, dim]``
    store (``DistFeature._spmd_feat``); everything else — topology,
    placement maps, the ``[batch_cap]`` seed block — is replicated, and
    sampling runs REPLICATED (no per-shard key fold), so the frontier,
    the adjacency structure and therefore the logits are bit-identical
    to the single-store ``build_serve_step`` over the same unpartitioned
    array (pinned in tests/test_serving.py; with ``fused_hot_hop`` on
    both, to the fused single-store step): only WHERE the rows live
    changes, never which rows are read.

    ``exchange_cap`` (``True | int | None``): the compact [H, cap]
    request block; a per-owner bucket that overflows it takes further
    rounds of the same [H, cap] exchange (a shard-uniform loop inside
    ``dist_lookup_local``) — row-identical either way, and the whole
    program still performs zero host syncs (qt-verify's
    ``no_host_sync`` / ``collective_divergence`` rules cover the traced
    body; per-variant ``executable_census`` bounds the program count).
    ``True`` sizes the cap from this variant's frontier capacity.

    ``home`` is THIS replica's partition (the one whose rows its hot
    tier holds). With ``collect_metrics``, every valid frontier row is
    classified once (on shard 0 only, so the device-side fold doesn't
    multiply it by the shard count): owned by ``home`` ->
    ``locality_hit_rows``, owned elsewhere -> ``locality_miss_rows`` —
    the router-as-cache-policy payoff counters (miss rows are exactly
    the rows the exchange must ship in from other partitions)."""
    from .comm import dist_lookup_local
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    h_count = mesh.shape[axis]

    def exchange(store, n_id, _forder, collector=None, exchange_cap=None):
        feat, g2h, g2l = store
        return dist_lookup_local(n_id, g2h, g2l, feat, axis, h_count,
                                 rows_per_host, exchange_cap=exchange_cap,
                                 collector=collector)

    walk = Walk.of("build_sharded_serve_step", _SHARDED_KNOBS, sizes, walk,
                   gather=exchange,
                   exchange=(h_count, exchange_cap, batch_cap))

    @hot_path
    def per_shard(params, key, feat, g2h, g2l, indptr, indices, seeds):
        from .metrics import (LOCALITY_HIT_ROWS, LOCALITY_MISS_ROWS,
                              Collector, pmerge_counters)
        col = Collector() if collect_metrics else None
        # rep_col: counters of the REPLICATED compute (sampling,
        # locality classification) — identical on every shard, so they
        # fold in from shard 0 only; the exchange counters stay
        # per-shard in ``col`` (each shard really runs an exchange) and
        # psum to the true mesh-wide totals
        rep_col = Collector() if collect_metrics else None
        key, sub = jax.random.split(key)
        n_id, x, layers = walk_frontier(
            walk, (feat, g2h, g2l), None, indptr, indices, seeds, sub,
            collector=rep_col, rows_collector=col)
        adjs = layers_to_adjs(layers, batch_cap, walk.sizes)
        with jax.named_scope("qt_serve_forward"):
            logits = model.apply(params, x, adjs, train=False)
        if not collect_metrics:
            return key, logits[:batch_cap]
        if home is not None:
            valid = n_id >= 0
            owner = g2h[jnp.clip(n_id, 0)]
            rep_col.add(LOCALITY_HIT_ROWS,
                        jnp.sum(valid & (owner == home)))
            rep_col.add(LOCALITY_MISS_ROWS,
                        jnp.sum(valid & (owner != home)))
        first = jax.lax.axis_index(axis) == 0
        col.absorb(jnp.where(first, rep_col.counters(), 0))
        return key, logits[:batch_cap], pmerge_counters(col.counters(),
                                                        axis)

    outs = (P(), P(), P()) if collect_metrics else (P(), P())
    raw = shard_map(per_shard, mesh=mesh,
                    in_specs=(P(), P(), P(axis), P(), P(), P(), P(), P()),
                    out_specs=outs, check_vma=False)
    jitted = jax.jit(raw, donate_argnums=(1,))
    jitted.jitted_fns = (jitted,)
    jitted.raw = raw
    return jitted


class ShardedServeEngine:
    """A ``ServeEngine`` whose feature tier is ONE partition-sharded
    store shared by the whole replica fleet (``DistFeature``) instead of
    a per-replica copy — the qt-shard path across the single-host
    memory wall: each replica holds ``~1/P`` of the rows, and frontier
    rows owned elsewhere arrive through the compact deduplicated
    exchange INSIDE the jitted serve program.

    ``dist`` must be a ``DistFeature`` built with ``from_partition``
    (the SPMD mode); ``home`` names this replica's own partition
    (default ``dist.info.host``) — it scopes the locality hit/miss
    counters and rides the ``serving`` snapshot so the fleet plane
    (``qt_top``, the locality router) can see per-replica ownership.
    The exchange knob comes from ``dist.exchange_cap``; counters honor
    ``dist.collect_metrics`` semantics but are always folded to the
    GLOBAL vector on device (``merge_counters`` has no per-shard mode
    here — a serving replica wants one picture, not H rows).

    Same dispatch contract as ``ServeEngine`` (``run`` is NOT
    thread-safe; the ``MicroBatchServer`` funnels dispatches through
    its single pipeline worker), same bounded pre-compiled fanout
    ladder, and the logits are bit-identical to a single-store
    ``ServeEngine`` over the unpartitioned array (with
    ``fused_hot_hop=True`` on both — the fused sampling leg of
    ``build_sharded_serve_step`` — the match is against the fused
    single-store engine's kernel-PRNG stream). ``**walk`` goes to every
    variant's ``build_sharded_serve_step`` unopened."""

    def __init__(self, model, params, topo, dist,
                 sizes_variants: Sequence[Sequence[int]],
                 batch_cap: int,
                 home: Optional[int] = None,
                 collect_metrics: bool = False,
                 seed: int = 0, **walk):
        if not sizes_variants:
            raise ValueError("need at least one fanout variant")
        hops = {len(s) for s in sizes_variants}
        if len(hops) != 1:
            raise ValueError(
                f"all fanout variants must share the model's hop count, "
                f"got lengths {sorted(hops)}")
        if getattr(dist, "_spmd_feat", None) is None:
            raise ValueError(
                "ShardedServeEngine needs a DistFeature built with "
                "from_partition (the SPMD mode)")
        if getattr(dist, "_rep_args", None) is not None:
            raise ValueError(
                "ShardedServeEngine does not support replicated-tail "
                "stores yet; partition without replicate=")
        self.model = model
        self.params = params
        self.dist = dist
        self.variants: List[List[int]] = [list(s) for s in sizes_variants]
        self.batch_cap = int(batch_cap)
        self.home = int(dist.info.host if home is None else home)
        self.partitions = int(dist.info.hosts)
        self.collect_metrics = bool(collect_metrics)
        self.last_counters = None
        # host seconds of the last run's two stages (seed block onto the
        # device, the step's Python call), for the server's counters
        self.last_stage_s = (0.0, 0.0)
        indptr, indices = (topo.indptr, topo.indices) \
            if hasattr(topo, "indptr") else topo
        self._indptr = jnp.asarray(indptr, jnp.int32)
        self._indices = jnp.asarray(indices, jnp.int32)
        self._g2h = dist.info.global2host.astype(jnp.int32)
        self._g2l = dist.info.global2local
        self._steps = [
            build_sharded_serve_step(
                model, sizes, self.batch_cap, dist.comm.mesh,
                dist.comm.axis, dist._rows_per_host,
                exchange_cap=dist.exchange_cap, home=self.home,
                collect_metrics=self.collect_metrics, **walk)
            for sizes in self.variants]
        self._key = jax.random.key(seed)

    @property
    def jitted_fns(self):
        return tuple(f for s in self._steps for f in s.jitted_fns)

    pad_seeds = ServeEngine.pad_seeds

    def run(self, seeds, variant: int = 0):
        """Dispatch one ``[batch_cap]`` seed block through the given
        pre-compiled sharded variant (see ``ServeEngine.run``)."""
        seeds = np.asarray(seeds, np.int32)
        if seeds.shape[0] != self.batch_cap:
            seeds = self.pad_seeds(seeds)
        out = _put_and_launch(
            self, self._steps[variant], seeds, self.params, self._key,
            self.dist._spmd_feat, self._g2h, self._g2l, self._indptr,
            self._indices)
        if self.collect_metrics:
            self._key, logits, self.last_counters = out
        else:
            self._key, logits = out
        return logits

    def warmup(self):
        # 4 dispatches per variant, not 1: the donated key buffer's
        # placement settles over the first few executions (uncommitted
        # single-device -> mesh-replicated -> steady), each a distinct
        # jit signature — warming to the steady state keeps serving
        # recompile-free (pinned by scripts/check_leak.py phase 14)
        for v in range(len(self.variants)):
            for _ in range(4):
                jax.block_until_ready(self.run(
                    np.zeros((self.batch_cap,), np.int32), v))
        return self


# -- the server: admission, coalescing, shedding, scatter --------------------


class ServeConfig:
    """Knobs for :class:`MicroBatchServer` (all latency budgets in ms).

    - ``max_wait_ms``: coalescing deadline — how long the FIRST request
      of a batch may wait for company before the batch dispatches
      anyway. The lone-request worst case adds exactly this much.
    - ``queue_depth``: admission bound; a full queue sheds load
      (``submit`` raises :class:`OverloadError`). A batch that waits
      open for room holds at most this many requests before it closes
      like a full one, so a stalled device still fills the queue.
    - ``slo_p99_ms``: per-request latency target. Setting it arms a
      ``metrics.SloBudget`` (target p99 at ``slo_availability`` over
      sliding windows); the server sheds QUALITY — dispatches escalate
      one step down the engine's fanout ladder — while the budget burns
      unsustainably (short-window burn rate above ``shed_burn_rate``
      AND long-window burn above 1.0), and recovers one step after
      ``calm_batches`` consecutive calm decisions (hysteresis,
      unchanged from the old raw-p99 trigger). Failed and
      admission-rejected requests count against the budget too — the
      raw p99 never saw them.
    - ``slo_availability`` / ``slo_window_s`` / ``slo_short_window_s``
      / ``shed_burn_rate``: the budget's shape — tolerated bad
      fraction is ``1 - slo_availability`` (default 0.99: a literal
      p99 target) over ``slo_window_s``, with the reactive burn rate
      measured over ``slo_short_window_s``.
    - ``shed_queue_frac``: queue fullness (0..1) that also triggers a
      quality-shed step — backlog is tomorrow's latency, so the server
      reacts before the SLO is already blown.
    - ``pipeline_depth``: how many CLOSED batches may exist at once,
      the one that runs included (at 2: one runs, one waits behind it,
      and the next stays open, taking requests, until the worker takes
      the waiting one). A batch past ``max_wait_ms`` closes when there
      is room; a FULL batch closes at once (it can take no more) and
      waits for its turn in ``serve.pipe_submit``. More depth adds
      queueing latency, not throughput, past 2; at 1 a batch closes
      only once the worker is idle, so the close is in the cycle.
    """

    def __init__(self, max_wait_ms: float = 2.0, queue_depth: int = 256,
                 slo_p99_ms: Optional[float] = None,
                 slo_availability: float = 0.99,
                 slo_window_s: float = 300.0,
                 slo_short_window_s: float = 30.0,
                 shed_burn_rate: float = 1.0,
                 shed_queue_frac: float = 0.5,
                 calm_batches: int = 8,
                 pipeline_depth: int = 2):
        if queue_depth < 1:
            raise ValueError("queue_depth must be >= 1")
        if not 0.0 < shed_queue_frac <= 1.0:
            raise ValueError("shed_queue_frac must be in (0, 1]")
        self.max_wait_ms = float(max_wait_ms)
        self.queue_depth = int(queue_depth)
        self.slo_p99_ms = slo_p99_ms
        self.slo_availability = float(slo_availability)
        self.slo_window_s = float(slo_window_s)
        self.slo_short_window_s = float(slo_short_window_s)
        self.shed_burn_rate = float(shed_burn_rate)
        self.shed_queue_frac = float(shed_queue_frac)
        self.calm_batches = int(calm_batches)
        self.pipeline_depth = int(pipeline_depth)


def _fail_future(fut, exc) -> bool:
    """Claim-and-fail one request future, tolerating a future some
    OTHER path already resolved: ``submit``'s close-race handler and
    ``close()``'s queue drain can both reach the same queued request
    (the handler completes the future while the request still sits in
    the queue the drain is about to sweep) — stdlib
    ``set_running_or_notify_cancel`` RAISES on a finished future, so
    the loser of that race must treat it as "already handled", not
    crash ``close()``. Returns True when THIS call failed the
    future."""
    try:
        claimed = fut.set_running_or_notify_cancel()
    except RuntimeError:
        return False                 # already resolved elsewhere
    if claimed:
        fut.set_exception(exc)
    return claimed


class _Request:
    __slots__ = ("node_id", "future", "t_enq", "trace_id", "deadline",
                 "tenant")

    def __init__(self, node_id: int, future, t_enq: float,
                 trace_id=None, deadline: Optional[float] = None,
                 tenant: Optional[str] = None):
        self.node_id = node_id
        self.future = future
        self.t_enq = t_enq
        self.trace_id = trace_id
        self.deadline = deadline
        self.tenant = tenant


class MicroBatchServer:
    """Request-coalescing micro-batch front end over a ``ServeEngine``.

    ``submit(node_id)`` -> ``Future`` whose result is that node's
    ``[out_dim]`` numpy logits row (duplicate node ids landing in the
    same coalesced batch share one seed slot and one device read). Life cycle: ``start()`` spins
    the coalescer (done by the constructor unless ``start=False`` —
    tests use the paused form to stage bursts), ``close()`` rejects new
    work, fails queued requests loudly, and shuts the pipeline down
    (idempotent; also a context manager). ``snapshot()`` returns the
    JSONL-ready ``serving`` record; ``emit(sink)`` writes it.

    See :class:`ServeConfig` for the SLO/overload policy and the module
    docstring for the architecture."""

    def __init__(self, engine: ServeEngine,
                 config: Optional[ServeConfig] = None,
                 stats=None, start: bool = True, hub=None,
                 tenants: Optional[dict] = None):
        from .metrics import SloBudget, StepStats, register_report_section
        from .pipeline import Pipeline
        self.engine = engine
        self.config = config or ServeConfig()
        self.stats = stats if stats is not None else StepStats()
        self.stats.watch_compiles(*engine.jitted_fns)
        # hub: a telemetry.TelemetryHub fed per-BATCH series points
        # (fill, dispatch ms, shed level) plus the device counter
        # vectors when the engine collects them — the time-series the
        # batch_cap/max_wait advisor sizes from. Host-side appends on
        # the executor thread; the dispatch path is untouched.
        self.hub = hub
        self._report_name = f"serving@{id(self):x}"
        cfg = self.config
        # the SLO budget is the shed policy's latency signal (burn
        # rates, not raw p99 samples) AND the `slo` JSONL payload;
        # public — read it, or `server.slo.emit(sink)` it, any time
        self.slo: Optional[SloBudget] = None
        if cfg.slo_p99_ms is not None:
            self.slo = SloBudget(cfg.slo_p99_ms,
                                 availability=cfg.slo_availability,
                                 window_s=cfg.slo_window_s,
                                 short_window_s=cfg.slo_short_window_s,
                                 shed_burn_rate=cfg.shed_burn_rate)
        # tenancy (qt-capacity): OPTIONAL {name: TenantClass} registry.
        # None (the default) disables the whole plane; with a registry,
        # every request files under a class (None tenant -> the
        # lowest-priority class) and shed ORDER becomes policy — see
        # the module docstring. Tenancy is host-side accounting + queue
        # discipline only: it never changes the seed block or which
        # programs compile.
        self._tenants: Optional[dict] = None
        self._tenant_default: Optional[str] = None
        self._tenant_states: dict = {}
        # requests popped by the coalescer but deferred to a later
        # batch (class-pure coalescing under a shed episode);
        # coalescer-thread-only, swept by close()/the death watchdog
        self._held: list = []
        if tenants:
            reg = dict(tenants)
            for n, c in reg.items():
                if not isinstance(c, TenantClass):
                    raise TypeError(
                        f"tenants[{n!r}] must be a TenantClass")
                if n != c.name:
                    raise ValueError(
                        f"tenant registry key {n!r} names a class "
                        f"called {c.name!r}")
            self._tenants = reg
            self._tenant_default = min(
                reg, key=lambda n: (reg[n].priority, n))
            wsum = sum(c.admission_weight for c in reg.values())
            for n, c in reg.items():
                share = max(1, int(np.ceil(
                    cfg.queue_depth * c.admission_weight / wsum)))
                self._tenant_states[n] = _TenantState(c, share)
        self._q: "queue.Queue[_Request]" = queue.Queue(
            maxsize=self.config.queue_depth)
        # pipeline_depth bounds the CLOSED batches in flight, the one
        # inside _execute included: the worker holds one, so the
        # pipeline queues one fewer (its own minimum is one slot, so at
        # depth 1 a FULL batch may still queue behind the running one)
        self._pipe = Pipeline(depth=max(1, self.config.pipeline_depth - 1),
                              name="quiver-serving-exec")
        self.stats.watch_pipeline(self._pipe)
        # closed batches handed to the pipeline and not yet through it.
        # Guarded by the admission queue's mutex and announced on its
        # not_empty condition, so the coalescer has ONE wake-up: a
        # request arrived, or room came
        self._in_flight = 0
        self._closed = False
        # broken = the coalescer thread died UNEXPECTEDLY (not close):
        # nothing will ever drain the queue again, so submissions must
        # fail fast with ServerClosed instead of hanging on admission
        self._broken = False
        self._lock = threading.Lock()
        self._thread: Optional[threading.Thread] = None
        # shedding state (coalescer-thread only, except the counters)
        self._shed_level = 0
        self._calm = 0
        # actuation surfaces (quiver_tpu.actuator): the EFFECTIVE
        # coalescing knobs, re-read by the coalescer per batch so a
        # swap lands on the next batch without a restart. The seed
        # shape stays [engine.batch_cap] whatever the fill cap, so no
        # knob swap can ever compile a new program.
        self._max_wait_s = cfg.max_wait_ms / 1e3
        self._fill_cap = engine.batch_cap
        self._shed_floor = 0
        self._counts = {
            "requests": 0, "rejected": 0, "completed": 0, "failed": 0,
            "deadline_expired": 0, "displaced": 0,
            "batches": 0, "coalesced": 0,
            # batches past their deadline and not full, closed only
            # because the pipeline got room (see _coalesce_loop)
            "held_open": 0,
            "variant_batches": [0] * len(engine.variants),
            # host seconds of each stage, summed over batches (and
            # queue_wait_s over requests): see docs/observability.md
            "coalesce_s": 0.0, "pipe_submit_s": 0.0, "execute_s": 0.0,
            "put_s": 0.0, "launch_s": 0.0, "get_s": 0.0, "scatter_s": 0.0,
            "queue_wait_s": 0.0,
        }
        self._counts_lock = threading.Lock()
        # register into the unified qt.metrics.report() LAST — a
        # constructor that raises above must not leak a permanently
        # broken section (close(), which unregisters, is unreachable
        # on a half-built server); unique name so parallel servers
        # coexist
        register_report_section(self._report_name, self.report)
        if start:
            self.start()

    # -- life cycle ---------------------------------------------------------
    def start(self) -> "MicroBatchServer":
        with self._lock:
            if self._closed or self._broken:
                raise ServerClosed("server is closed")
            if self._thread is None:
                t = threading.Thread(target=self._coalesce_guard,
                                     name="quiver-serving-coalescer",
                                     daemon=True)
                t.start()
                self._thread = t
        return self

    def close(self):
        """Reject new submissions, fail queued (never-dispatched)
        requests with ``RuntimeError`` — those of a batch still open,
        waiting for room, among them — drain the in-flight batches,
        stop the coalescer and the pipeline. Idempotent."""
        from .metrics import unregister_report_section
        unregister_report_section(self._report_name)
        with self._lock:
            if self._closed:
                return
            self._closed = True
            t = self._thread
            self._thread = None
        if t is not None and t is not threading.current_thread():
            t.join()
        # the coalescer is gone: anything still queued will never run
        # (held requests — popped but deferred by class-pure
        # coalescing — are safe to sweep here: the thread is joined)
        undispatched = list(self._held)
        self._held = []
        while True:
            try:
                undispatched.append(self._q.get_nowait())
            except queue.Empty:
                break
        self._fail_batch(undispatched)
        # coalesced batches still QUEUED in the pipeline are cancelled
        # by its close; their done-callbacks (armed at submit) fail the
        # request futures — the running batch drains normally first
        self._pipe.close()

    def __enter__(self) -> "MicroBatchServer":
        return self

    def __exit__(self, *exc):
        self.close()

    @property
    def closed(self) -> bool:
        return self._closed

    # -- admission ----------------------------------------------------------
    def _account_shed(self, tenant: Optional[str], key: str) -> None:
        """File one shed outcome (admission ``rejected``,
        ``displaced``, or ``deadline_expired``) into the aggregate
        counters, the aggregate SLO budget, and the owning tenant's
        accounting — one helper so load shed, displacement and
        deadline shed can never drift apart."""
        if self.slo is not None:
            # a shed request is an availability miss — the budget
            # must see it (the old raw-p99 trigger never did)
            self.slo.record(ok=False)
        st = self._tenant_states.get(tenant) if tenant else None
        with self._counts_lock:
            self._counts[key] += 1
            if st is not None:
                st.counts[key] += 1
        if st is not None and st.budget is not None:
            st.budget.record(ok=False)

    def _displace_for(self, priority: int):
        """Queue-discipline load shed: evict the NEWEST queued request
        of the lowest priority STRICTLY below ``priority`` to make
        room for a higher-priority admission (tenancy only). The
        victim's future fails with :class:`OverloadError` and its
        class absorbs the shed. Returns True when a slot was freed."""
        q = self._q
        with q.mutex:
            best_i, best_p = -1, priority
            for i in range(len(q.queue) - 1, -1, -1):
                p = self._tenants[q.queue[i].tenant].priority
                if p < best_p:
                    best_i, best_p = i, p
            if best_i < 0:
                return False
            victim = q.queue[best_i]
            del q.queue[best_i]
            q.not_full.notify()
        with self._counts_lock:
            self._tenant_states[victim.tenant].queued -= 1
        if _fail_future(victim.future, OverloadError(
                "displaced at admission by a higher-priority tenant")):
            self._account_shed(victim.tenant, "displaced")
        return True

    def submit(self, node_id: int, context=None,
               deadline: Optional[float] = None,
               tenant: Optional[str] = None):
        """Admit one point query; returns a ``Future`` resolving to the
        node's logits row (numpy ``[out_dim]``). Raises
        :class:`OverloadError` IMMEDIATELY when the admission queue is
        full — rejecting at the door is the overload policy's last
        stage (see :class:`ServeConfig`) — and
        :class:`~quiver_tpu.rpc.ServerClosed` when the server is
        closed OR its coalescer thread died (the thread-death watchdog:
        a request that nothing will ever drain must fail fast, never
        hang on the admission queue).

        ``deadline`` (absolute ``time.perf_counter()`` instant — the
        RPC front end converts its wire budget) arms per-request
        deadline shedding: a request whose deadline passes while it
        waits is failed with
        :class:`~quiver_tpu.rpc.DeadlineExceeded` at coalesce time,
        BEFORE it wastes a seed slot in a batch the client has already
        given up on.

        ``context`` is optional request metadata carrying a propagated
        trace context (``tracing.inject`` on the client side): when
        tracing is on, this request's spans record under the CLIENT's
        ``trace_id`` instead of a locally minted one, so the client's
        and this replica's exported traces correlate in one merged
        Perfetto view (``tracing.merge_chrome_traces``). A missing or
        mangled context falls back to a local id — never an error.

        ``tenant`` names the request's :class:`TenantClass` when the
        server was built with a registry (``tenants=``): the request
        files under that class's accounting and shed policy (a
        ``None`` tenant lands in the lowest-priority class; an
        unregistered name raises ``ValueError``). Without a registry
        the argument is accepted and ignored — RPC front ends thread
        it through unconditionally."""
        if self._closed or self._broken:
            raise ServerClosed("server is closed"
                               if self._closed else
                               "server is broken (coalescer died)")
        tname = None
        st = None
        if self._tenants is not None:
            tname = tenant if tenant is not None else \
                self._tenant_default
            st = self._tenant_states.get(tname)
            if st is None:
                raise ValueError(
                    f"unknown tenant class {tname!r} (registered: "
                    f"{sorted(self._tenants)})")
        from concurrent.futures import Future
        fut: Future = Future()
        tid = None
        if tracing.enabled():
            ctx = tracing.extract(context) if context is not None \
                else None
            tid = ctx.trace_id if ctx is not None \
                else tracing.new_trace_id()
        req = _Request(int(node_id), fut, time.perf_counter(), tid,
                       deadline, tname)
        cfg = self.config
        if st is not None:
            # weighted admission shares, enforced only under pressure
            # (queue past the shed threshold): a class already holding
            # its share of the queue is rejected at the door while
            # under-share classes still admit — load shed consumes the
            # flooding class first, and a calm queue never rejects
            shed_at = max(1, int(cfg.queue_depth * cfg.shed_queue_frac))
            if self._q.qsize() >= shed_at and st.queued >= st.share:
                self._account_shed(tname, "rejected")
                raise OverloadError(
                    f"admission queue pressed and tenant {tname!r} "
                    f"holds its share ({st.share}); request shed")
        try:
            self._q.put_nowait(req)
        except queue.Full:
            # tenancy: a full queue displaces the newest queued
            # request of a strictly lower priority before giving up —
            # interactive admission consumes best-effort slots, never
            # the reverse (one retry; a lost race with another
            # submitter degrades to an honest reject)
            admitted = False
            if st is not None and self._displace_for(st.cls.priority):
                try:
                    self._q.put_nowait(req)
                    admitted = True
                except queue.Full:
                    pass
            if not admitted:
                self._account_shed(tname, "rejected")
                raise OverloadError(
                    f"admission queue full ({cfg.queue_depth} "
                    "pending); request shed") from None
        if self._closed or self._broken:
            # close() (or the coalescer-death watchdog) raced us: its
            # drain may have run before our put landed, and no
            # coalescer will ever pop the request — reclaim it so the
            # future cannot strand (the claim is exclusive, so if the
            # drain got there first this is a no-op and the future is
            # already failed)
            _fail_future(req.future, ServerClosed("server is closed"))
            raise ServerClosed("server is closed")
        with self._counts_lock:
            self._counts["requests"] += 1
            if st is not None:
                st.counts["requests"] += 1
                st.queued += 1
        return fut

    def submit_many(self, node_ids, context=None,
                    deadline: Optional[float] = None,
                    tenant: Optional[str] = None) -> list:
        """``submit`` per id (one shared ``context`` — a multi-point
        client operation traces as ONE request id across its points).
        If admission overloads mid-list the raised
        :class:`OverloadError` carries the already-admitted futures on
        ``.futures`` — admitted work runs regardless, so its results
        must stay observable (and a retry must not resubmit them)."""
        futs: list = []
        for i in node_ids:
            try:
                futs.append(self.submit(i, context=context,
                                        deadline=deadline,
                                        tenant=tenant))
            except OverloadError as e:
                e.futures = futs
                raise
        return futs

    # -- actuation surfaces (qt-act) ----------------------------------------
    def set_max_wait_ms(self, ms: float) -> None:
        """Swap the effective coalescing deadline (the ``max_wait_ms``
        knob the hub's advisor sizes). Takes effect on the NEXT batch;
        no program input changes, so nothing recompiles."""
        ms = float(ms)
        if not ms > 0.0:
            raise ValueError(f"max_wait_ms must be > 0, got {ms}")
        self._max_wait_s = ms / 1e3

    def set_batch_fill_cap(self, cap: Optional[int]) -> None:
        """Swap the effective coalescing FILL cap (the ``batch_cap``
        knob's safe actuation form): batches stop coalescing at ``cap``
        distinct seeds but still dispatch at the engine's compiled
        ``[batch_cap]`` seed shape (-1 padded), so every value in
        ``[1, engine.batch_cap]`` reuses the census'd executables
        verbatim. ``None`` restores the engine cap. Growing past the
        compiled shape is impossible by construction — the actuator
        refuses such advice instead of recompiling."""
        if cap is None:
            self._fill_cap = self.engine.batch_cap
            return
        cap = int(cap)
        if not 1 <= cap <= self.engine.batch_cap:
            raise ValueError(
                f"batch fill cap must be in [1, "
                f"{self.engine.batch_cap}], got {cap}")
        self._fill_cap = cap

    def set_shed_floor(self, level: int) -> None:
        """Planned fleet-wide quality floor
        (``fleet.HealthRouter.plan_quality``): dispatches never run a
        variant ABOVE quality ``level`` while the floor is raised — the
        local hysteresis still escalates further under local pressure.
        0 restores full local autonomy."""
        level = int(level)
        top = len(self.engine.variants) - 1
        if not 0 <= level <= top:
            raise ValueError(
                f"shed floor must be in [0, {top}], got {level}")
        self._shed_floor = level

    def knobs(self) -> dict:
        """The effective actuation knobs (the ``before``/``after``
        readbacks the ``actuate`` JSONL records carry)."""
        return {"max_wait_ms": round(self._max_wait_s * 1e3, 6),
                "batch_fill_cap": self._fill_cap,
                "shed_floor": self._shed_floor}

    # -- coalescing ---------------------------------------------------------
    def _coalesce_guard(self):
        """The coalescer's thread-death watchdog: any exception
        escaping the loop (an injected ``serve.coalesce`` fault, a bug)
        marks the server BROKEN, fails every queued future with
        ``ServerClosed`` immediately — a dead coalescer means nothing
        will ever drain the queue, and a fast typed failure beats a
        silent hang — then re-raises so the death stays visible."""
        try:
            self._coalesce_loop()
        except BaseException as e:
            if self._closed:
                raise
            self._broken = True
            _log.error("serving coalescer died unexpectedly (%s: %s); "
                       "failing queued requests with ServerClosed",
                       type(e).__name__, e)
            undispatched = list(self._held)
            self._held = []
            while True:
                try:
                    undispatched.append(self._q.get_nowait())
                except queue.Empty:
                    break
            self._fail_batch(undispatched,
                             "coalescer thread died; server is broken",
                             exc_type=ServerClosed)
            raise

    def _shed_expired(self, req) -> bool:
        """Fail ``req`` with DeadlineExceeded if its deadline already
        passed — BEFORE it costs a batch seed slot. Returns True when
        the request was shed (or already claimed elsewhere)."""
        if req.deadline is None or time.perf_counter() <= req.deadline:
            return False
        if _fail_future(req.future, DeadlineExceeded(
                "deadline passed while queued (shed at coalesce — the "
                "client has already given up on this request)")):
            self._account_shed(req.tenant, "deadline_expired")
            if tracing.enabled() and req.trace_id is not None:
                # the request's TERMINAL span, error-stamped: a shed
                # request still completes its trace, so the tail
                # sampler can keep it (deadline_exceeded policy)
                now = time.perf_counter()
                tracing.record("serve.request", req.t_enq,
                               now - req.t_enq, req.trace_id,
                               {"node": req.node_id,
                                "error": "DeadlineExceeded"})
        return True

    def _note_popped(self, req) -> None:
        """Per-tenant queued-count bookkeeping for one admission-queue
        pop (weighted-share admission reads these counts)."""
        if self._tenants is not None:
            with self._counts_lock:
                self._tenant_states[req.tenant].queued -= 1

    def _has_room(self) -> bool:
        """May another batch close? ``pipeline_depth`` closed batches
        may exist at once, the one inside ``_execute`` included."""
        return self._in_flight < self.config.pipeline_depth

    def _left_pipeline(self, batch, pf) -> None:
        """Done-callback of a handed-over batch (it ran, failed, or the
        pipeline cancelled it while queued): its place is free, and the
        coalescer holding a batch open for room is told so."""
        with self._q.not_empty:
            self._in_flight -= 1
            self._q.not_empty.notify()
        # a batch the pipeline cancels while queued (close() drains it)
        # never reaches _execute — fail its futures, don't strand them
        if pf.cancelled():
            self._fail_batch(batch)

    def _pop_next(self, timeout: float, held: bool = True,
                  or_room: bool = False):
        """Next request for the coalescer: deferred (held) requests
        first — oldest first, so class-pure deferral never starves a
        class; ``held=False`` skips them — then the admission queue.
        With ``or_room`` the wait also ends when the pipeline gets room
        for a batch. Raises ``queue.Empty`` when the wait ends with no
        request."""
        if held and self._held:
            return self._held.pop(0)
        if or_room:
            # the queue's own condition: put() notifies it for a
            # request, _left_pipeline for room
            with self._q.not_empty:
                if not self._q.queue and not self._has_room():
                    self._q.not_empty.wait(timeout)
            timeout = 0.0
        req = self._q.get(timeout=timeout)
        self._note_popped(req)
        return req

    def _coalesce_loop(self):
        while not self._closed:
            faults.fire("serve.coalesce")
            # effective knobs re-read per batch: the actuator may swap
            # them mid-traffic (set_max_wait_ms / set_batch_fill_cap),
            # and a swap must land on the NEXT batch without a restart
            max_wait = self._max_wait_s
            cap = min(self._fill_cap, self.engine.batch_cap)
            try:
                first = self._pop_next(0.02)
            except queue.Empty:
                continue
            if self._shed_expired(first):
                continue
            # tenancy: under a shed episode batches coalesce
            # CLASS-PURE (the batch takes only the first request's
            # class; other classes defer to their own next batch), so
            # the per-class shed_grace variant applies per batch —
            # quality shed consumes best-effort first. Calm traffic
            # (shed level 0, no floor) coalesces mixed: every class
            # dispatches variant 0 there, so batch composition — and
            # the logits — are unchanged by tenancy. The first batch
            # of an episode (the one whose _select_variant call raises
            # the level) is still mixed: the discipline lags pressure
            # by exactly one batch.
            bcls = None
            if self._tenants is not None and (
                    self._shed_level > 0 or self._shed_floor > 0):
                bcls = self._tenants[first.tenant]
            # span plumbing: the batch's two stages on this thread,
            # serve.batch_coalesce (first pop -> batch closed) and
            # serve.pipe_submit (closed -> the pipeline took it), go
            # through tracing.stage: profiler, counters and ring see
            # the same interval. When the ring is on, each request also
            # gets admission_wait (queue time before the coalescer saw
            # it) and coalesce_wait (time spent waiting for batch
            # company) records carrying its trace_id + the batch id —
            # the request<->batch correlation the Perfetto view pivots on
            traced = tracing.enabled()
            bid = tracing.new_trace_id() if traced else None
            with tracing.stage("serve.batch_coalesce", bid) as coalesce:
                t_first = coalesce.t0
                pops = [(first, t_first)]
                if traced:
                    tracing.record("serve.admission_wait", first.t_enq,
                                   t_first - first.t_enq, first.trace_id,
                                   {"batch": bid, "node": first.node_id})
                batch = [first]
                slots = {first.node_id: 0}
                if bcls is not None and self._held:
                    # sweep already-deferred requests of THIS class into
                    # the batch up front (one pass — the rest stay held)
                    keep = []
                    for r in self._held:
                        if (len(slots) < cap
                                and self._tenants[r.tenant] is bcls):
                            if self._shed_expired(r):
                                continue
                            batch.append(r)
                            slots.setdefault(r.node_id, len(slots))
                            if traced:
                                t_pop = time.perf_counter()
                                pops.append((r, t_pop))
                                tracing.record(
                                    "serve.admission_wait", r.t_enq,
                                    t_pop - r.t_enq, r.trace_id,
                                    {"batch": bid, "node": r.node_id})
                        else:
                            keep.append(r)
                    self._held = keep
                deadline = t_first + max_wait
                # drain until the seed block is full, or the first
                # request's wait budget is spent AND the pipeline has
                # room: the deadline says when the batch MAY close, room
                # says when closing it gets it to the device any sooner.
                # Until then requests keep joining (a closed batch takes
                # none, and would only wait behind the ones in flight).
                # A lone request on an idle server ships at deadline, a
                # burst splits into back-to-back full batches
                waited_for_room = held_open = False
                late = 0                 # joined while it waited for room
                while len(slots) < cap:
                    remaining = deadline - time.perf_counter()
                    if remaining <= 0:
                        if self._has_room():
                            held_open = waited_for_room
                            break
                        if self._closed:
                            self._fail_batch(batch)
                            return
                        if len(batch) + len(self._held) >= \
                                self.config.queue_depth:
                            # bounded everywhere: duplicates share a
                            # slot, so a stalled device would let an
                            # open batch swallow requests without end.
                            # One that has popped a queue's worth closes
                            # like a full one, and its blocked submit
                            # lets the queue fill and shed at admission
                            break
                        waited_for_room = True
                        remaining = 0.02       # wake to see a close()
                    try:
                        # class-pure: pull from the queue only (held
                        # was filtered above and now holds only other
                        # classes — re-popping it here would spin)
                        req = self._pop_next(remaining, held=bcls is None,
                                             or_room=waited_for_room)
                    except queue.Empty:
                        continue
                    if self._shed_expired(req):
                        continue
                    if bcls is not None and \
                            self._tenants[req.tenant] is not bcls:
                        self._held.append(req)
                        continue
                    batch.append(req)
                    slots.setdefault(req.node_id, len(slots))
                    late += waited_for_room
                    if traced:
                        t_pop = time.perf_counter()
                        pops.append((req, t_pop))
                        tracing.record("serve.admission_wait", req.t_enq,
                                       t_pop - req.t_enq, req.trace_id,
                                       {"batch": bid, "node": req.node_id})
                # the seed block keeps the engine's COMPILED width
                # whatever the fill cap — a fill-cap swap changes
                # padding, never the program shape
                seeds = np.full((self.engine.batch_cap,), -1, np.int32)
                for nid, s in slots.items():
                    seeds[s] = nid
                variant = self._select_variant(late)
                if bcls is not None:
                    # per-class quality-shed order: this class ignores
                    # shed_grace ladder steps of the local shed level;
                    # the fleet-planned floor still lower-bounds everyone
                    top = len(self.engine.variants) - 1
                    graced = max(0, min(self._shed_level, top)
                                 - bcls.shed_grace)
                    variant = max(graced, min(self._shed_floor, top))
                coalesce.args = {"requests": len(batch),
                                 "fill": len(slots), "variant": variant}
            # only a batch that could take no more (full, or a queue's
            # worth) finds the pipeline without room; any other stayed
            # open until there was some. Its submit blocks at depth,
            # device-side backpressure propagates here, the queue
            # absorbs it, and a full queue sheds at admission — bounded
            # everywhere
            with self._q.mutex:
                self._in_flight += 1
            try:
                with tracing.stage("serve.pipe_submit", bid) as handoff:
                    pf = self._pipe.submit(self._execute, batch, slots,
                                           seeds, variant, bid)
            except RuntimeError:
                if self._closed:       # close() raced the coalescer
                    self._fail_batch(batch)
                    return
                raise
            with self._counts_lock:
                self._counts["coalesce_s"] += coalesce.dur
                self._counts["pipe_submit_s"] += handoff.dur
                self._counts["held_open"] += held_open
            if traced:
                t_sub = handoff.t0 + handoff.dur
                for req, t_pop in pops:
                    tracing.record("serve.coalesce_wait", t_pop,
                                   t_sub - t_pop, req.trace_id,
                                   {"batch": bid})
            pf.add_done_callback(
                lambda f, b=batch: self._left_pipeline(b, f))

    # -- shedding policy ----------------------------------------------------
    def _select_variant(self, late: int = 0) -> int:
        """Quality-shed decision for the NEXT batch (coalescer thread
        only; ``late`` = the requests that joined that batch while it
        stayed open for room, backlog like the queue they would
        otherwise sit in). Escalates one fanout step down the ladder
        when queue backlog crosses its threshold or the SLO error budget is
        burning unsustainably (``SloBudget.should_shed`` — the
        multi-window burn-rate signal that replaced the raw recent-p99
        trigger; it reacts to the RATE the budget is being spent, and
        counts failures/rejections the p99 samples never saw); recovers
        one step after ``calm_batches`` consecutive calm decisions —
        hysteresis, unchanged, so the variant mix doesn't flap (each
        flap costs nothing in compiles — every variant is pre-compiled
        — but a stable mix keeps the reported accuracy tradeoff
        meaningful). A planned fleet-wide floor (``set_shed_floor``,
        fed by ``fleet.HealthRouter.plan_quality``) lower-bounds the
        decision without disturbing the local hysteresis state."""
        top = len(self.engine.variants) - 1
        if top == 0:
            return 0
        cfg = self.config
        shed_at = max(1, int(cfg.queue_depth * cfg.shed_queue_frac))
        # held (class-deferred) requests are backlog too — they are
        # admitted work the coalescer has not dispatched yet, as are
        # the late joiners of the batch about to close
        pressed = self._q.qsize() + len(self._held) + late >= shed_at
        if not pressed and self.slo is not None:
            pressed = self.slo.should_shed()
        if pressed:
            self._shed_level = min(self._shed_level + 1, top)
            self._calm = 0
        elif self._shed_level:
            self._calm += 1
            if self._calm >= cfg.calm_batches:
                self._shed_level -= 1
                self._calm = 0
        return max(self._shed_level, min(self._shed_floor, top))

    # -- execution + scatter ------------------------------------------------
    def _fail_batch(self, batch, msg: str = "server closed before "
                                            "dispatch",
                    exc_type=ServerClosed):
        """Fail every not-yet-claimed future in ``batch`` loudly (with
        a TYPED error — ``ServerClosed`` subclasses RuntimeError, so a
        retrying RPC client can route elsewhere while legacy callers
        still catch it). The claim (``set_running_or_notify_cancel``)
        is exclusive, so this composes race-free with ``_execute`` and
        caller-side ``cancel()``; a future ``submit``'s close-race
        handler already failed counts as handled (``_fail_future``)."""
        failed = 0
        failed_reqs = []
        traced = tracing.enabled()
        now = time.perf_counter() if traced else 0.0
        for req in batch:
            if _fail_future(req.future, exc_type(msg)):
                failed += 1
                failed_reqs.append(req)
                if traced and req.trace_id is not None:
                    tracing.record("serve.request", req.t_enq,
                                   now - req.t_enq, req.trace_id,
                                   {"node": req.node_id,
                                    "error": exc_type.__name__})
        if failed:
            if self.slo is not None:
                for _ in range(failed):
                    self.slo.record(ok=False)
            with self._counts_lock:
                self._counts["failed"] += failed
                for req in failed_reqs:
                    st = self._tenant_states.get(req.tenant)
                    if st is not None:
                        st.counts["failed"] += 1
            if self._tenants is not None:
                for req in failed_reqs:
                    st = self._tenant_states.get(req.tenant)
                    if st is not None and st.budget is not None:
                        st.budget.record(ok=False)

    def _execute(self, batch, slots, seeds, variant, bid=None):
        # serve.dispatch is the whole of this call, claim to last future
        # resolved; its children (serve.put and serve.launch inside
        # engine.run, serve.get, serve.scatter) inherit the batch id
        with tracing.stage("serve.dispatch", bid) as run:
            # claim every request's future up front: a caller-side
            # cancel() that lands after this point loses the race
            # cleanly (set_result on a RUNNING future is legal; on a
            # CANCELLED one it raises)
            batch = [r for r in batch
                     if r.future.set_running_or_notify_cancel()]
            if not batch:
                return
            t0 = run.t0
            run.args = {"variant": variant, "fill": len(slots),
                        "requests": len(batch)}
            try:
                faults.fire("serve.execute")
                logits = self.engine.run(seeds, variant)
                with tracing.stage("serve.get") as get:
                    rows = np.asarray(jax.device_get(logits))
            except BaseException as e:
                self._fail_dispatched(batch, bid, e)
                raise
            done = get.t0 + get.dur
            # scatter = stats filing + future resolution (the wake-up
            # cost requests pay after the device answer is back)
            with tracing.stage("serve.scatter",
                               args={"requests": len(batch)}) as scatter:
                counters = (self.engine.last_counters
                            if self.engine.collect_metrics else None)
                self.stats.record_step(done - t0, counters)
                if self.hub is not None:
                    # per-batch series for the telemetry hub's detectors
                    # and the serving advisor (batch_cap from observed
                    # fill, max_wait from observed latency); counters
                    # ride the hub's own lazy fold — still no sync on
                    # the dispatch path
                    self.hub.observe("serve_batch_fill", len(slots))
                    self.hub.observe("serve_batch_ms", 1e3 * (done - t0))
                    self.hub.observe("serve_shed_level", variant)
                    if counters is not None:
                        self.hub.observe_counters(counters)
                # stats and counts land BEFORE the futures resolve: a
                # client woken by result() may immediately snapshot(),
                # and must see its own batch counted
                queue_wait = 0.0
                for req in batch:
                    lat = done - req.t_enq
                    queue_wait += t0 - req.t_enq
                    self.stats.record_request(lat)
                    if self.slo is not None:
                        self.slo.record(lat)
                    if self._tenants is not None:
                        st = self._tenant_states.get(req.tenant)
                        if st is not None and st.budget is not None:
                            st.budget.record(lat)
                with self._counts_lock:
                    self._counts["completed"] += len(batch)
                    self._counts["batches"] += 1
                    self._counts["coalesced"] += len(batch)
                    self._counts["variant_batches"][variant] += 1
                    if self._tenants is not None:
                        for req in batch:
                            st = self._tenant_states.get(req.tenant)
                            if st is not None:
                                st.counts["completed"] += 1
                                st.hist.add(done - req.t_enq)
                for req in batch:
                    req.future.set_result(rows[slots[req.node_id]])
        # the stages' seconds are whole only now, so they are filed
        # together one lock later than the batch's count: a snapshot in
        # between reads one batch more than its seconds cover
        put_s, launch_s = self.engine.last_stage_s
        with self._counts_lock:
            c = self._counts
            c["execute_s"] += run.dur
            c["put_s"] += put_s
            c["launch_s"] += launch_s
            c["get_s"] += get.dur
            c["scatter_s"] += scatter.dur
            c["queue_wait_s"] += queue_wait
        if bid is not None and tracing.enabled():
            t_end = t0 + run.dur
            for req in batch:
                tracing.record("serve.request", req.t_enq,
                               t_end - req.t_enq, req.trace_id,
                               {"batch": bid, "node": req.node_id,
                                "variant": variant})

    def _fail_dispatched(self, batch, bid, e) -> None:
        """Request-failure propagation: the batch's requests all see
        the step's exception; the pipeline records the failure and
        stays up for the next batch."""
        for req in batch:
            if not req.future.done():
                req.future.set_exception(e)
        if self.slo is not None:
            for _ in batch:
                self.slo.record(ok=False)
        with self._counts_lock:
            self._counts["failed"] += len(batch)
            for req in batch:
                st = self._tenant_states.get(req.tenant)
                if st is not None:
                    st.counts["failed"] += 1
        if self._tenants is not None:
            for req in batch:
                st = self._tenant_states.get(req.tenant)
                if st is not None and st.budget is not None:
                    st.budget.record(ok=False)
        if tracing.enabled():
            # error-stamped terminal spans: the failed requests'
            # traces complete with the outcome, so the tail
            # sampler's `error` policy keeps exactly these
            now = time.perf_counter()
            for req in batch:
                if req.trace_id is not None:
                    tracing.record("serve.request", req.t_enq,
                                   now - req.t_enq, req.trace_id,
                                   {"batch": bid,
                                    "node": req.node_id,
                                    "error": type(e).__name__})

    # -- observability ------------------------------------------------------
    def health(self) -> dict:
        """This replica's own health verdict — the same
        ``fleet.health_score`` formula the cross-process aggregator
        applies to every replica (SLO burn rate + shed level; a live
        server is never stale to itself), so a replica's self-report
        and the fleet view can only disagree about staleness, which
        only an outside observer can judge. Returns ``{"score",
        "components"}``."""
        from .fleet import health_score
        if getattr(self, "_broken", False):
            # a dead coalescer serves nothing: the self-report agrees
            # with what the fleet will conclude from staleness
            return {"score": 0.0, "components": {"broken": True}}
        burn = None
        if self.slo is not None:
            s = self.slo.burn_rate(self.slo.short_window_s)
            l = self.slo.burn_rate(self.slo.window_s)
            rates = [r for r in (s, l) if r is not None]
            burn = max(rates) if rates else None
        top = max(len(self.engine.variants) - 1, 1)
        score, components = health_score(
            burn=burn, shed_frac=self._shed_level / top)
        return {"score": score, "components": components}

    def snapshot(self) -> dict:
        """One JSONL-ready record (kind ``serving``): the underlying
        ``StepStats`` snapshot (per-request AND per-batch latency
        percentiles, device counters, recompiles, pipeline queue) plus
        the serving-layer facts — admission/shed counts, batch fill,
        per-variant batch mix, current shed level — and, when an SLO is
        configured, the ``SloBudget`` block (burn rates, remaining
        error budget; also emittable standalone as kind ``slo`` via
        ``server.slo.emit(sink)``)."""
        rec = self.stats.snapshot()
        if self.slo is not None:
            rec["slo"] = self.slo.snapshot()
        with self._counts_lock:
            c = dict(self._counts)
            c["variant_batches"] = list(c["variant_batches"])
        b = c.pop("batches")
        coalesced = c.pop("coalesced")
        rec["serving"] = {
            **c,
            # batch handed to the pipeline -> _execute starts (the time
            # submit blocked at depth is inside it)
            "pipeline_wait_s": self._pipe.stats()["total_wait_s"],
            "batches": b,
            "mean_batch_fill": coalesced / b if b else 0.0,
            "queue_depth": self._q.qsize(),
            "shed_level": self._shed_level,
            "fanout_variants": [list(v) for v in self.engine.variants],
            "health": self.health()["score"],
            "knobs": self.knobs(),
        }
        home = getattr(self.engine, "home", None)
        if home is not None:
            # sharded engine: per-replica partition ownership, the
            # fleet plane's routing/locality pivot (qt_top, the
            # locality router's ownership column)
            rec["serving"]["partition"] = {
                "home": int(home),
                "partitions": int(getattr(self.engine, "partitions", 1)),
            }
        return rec

    def emit(self, sink, kind: str = "serving") -> dict:
        """Append :meth:`snapshot` to a ``metrics.MetricsSink``."""
        return sink.emit(self.snapshot(), kind=kind)

    def tenant_snapshots(self) -> list:
        """One JSONL-ready record per registered tenant class (kind
        ``tenant``): the class declaration (priority, admission weight,
        shed grace), the admission/outcome counters, the derived
        ``shed`` total (rejected + displaced + deadline-expired — every
        request the policy turned away), the per-tenant latency
        histogram summary, and — when the class declares an SLO — its
        ``SloBudget`` block. Empty list when no registry was
        configured, so callers can emit unconditionally."""
        if self._tenants is None:
            return []
        recs = []
        with self._counts_lock:
            frozen = [(name, dict(st.counts), st.queued,
                       st.hist.n, st.hist.total, st.hist.max,
                       st.hist.quantile(0.5), st.hist.quantile(0.99))
                      for name, st in sorted(self._tenant_states.items())]
        for (name, c, queued, n, total, mx, p50, p99) in frozen:
            st = self._tenant_states[name]
            cls = st.cls
            rec = {
                "tenant": name,
                "priority": cls.priority,
                "admission_weight": cls.admission_weight,
                "shed_grace": cls.shed_grace,
                "queued": queued,
                "shed": (c["rejected"] + c["displaced"]
                         + c["deadline_expired"]),
                **c,
                "latency": {
                    "n": n,
                    "mean_ms": 1e3 * total / n if n else None,
                    "p50_ms": 1e3 * p50 if n else None,
                    "p99_ms": 1e3 * p99 if n else None,
                    "max_ms": 1e3 * mx if n else None,
                },
            }
            if st.budget is not None:
                rec["slo"] = st.budget.snapshot()
            recs.append(rec)
        return recs

    def emit_tenants(self, sink) -> list:
        """Append one per-tenant record per registered class to a
        ``metrics.MetricsSink`` as kind ``tenant`` — the per-tenant
        leg of the observability plane (TelemetryHub ingests these
        into ``tenant_*`` series; the fleet aggregator exports them as
        ``qt_tenant_*{tenant=...}``)."""
        recs = self.tenant_snapshots()
        for rec in recs:
            sink.emit(rec, kind="tenant")
        return recs

    def report(self) -> str:
        """Human-readable one-stop summary."""
        s = self.snapshot()
        sv = s["serving"]
        lines = [self.stats.report()]
        lines.append(
            f"serving: {sv['requests']} requests "
            f"({sv['rejected']} shed at admission, {sv['failed']} "
            f"failed), {sv['batches']} batches, mean fill "
            f"{sv['mean_batch_fill']:.1f}/{self.engine.batch_cap}, "
            f"variant mix {sv['variant_batches']}, shed level "
            f"{sv['shed_level']}")
        if "slo" in s:
            sl = s["slo"]
            short = sl["windows"]["short"]["burn_rate"]
            long_ = sl["windows"]["long"]["burn_rate"]
            rem = sl["budget_remaining"]
            fmt = lambda v: "n/a" if v is None else f"{v:.2f}"
            lines.append(
                f"slo: p99 target {sl['target_p99_ms']:.1f} ms at "
                f"{100.0 * sl['availability']:.1f}% — burn rate "
                f"{fmt(short)} (short) / {fmt(long_)} (long), "
                f"budget remaining "
                f"{'n/a' if rem is None else f'{100.0 * rem:.1f}%'}"
                f"{', SHEDDING' if sl['shedding'] else ''}")
        for t in self.tenant_snapshots():
            p99 = t["latency"]["p99_ms"]
            lines.append(
                f"tenant {t['tenant']}: {t['requests']} requests, "
                f"{t['completed']} completed, {t['shed']} shed "
                f"({t['rejected']} rejected, {t['displaced']} "
                f"displaced, {t['deadline_expired']} expired), p99 "
                f"{'n/a' if p99 is None else f'{p99:.1f} ms'}")
        return "\n".join(lines)
