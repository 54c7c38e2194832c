"""Runtime telemetry: jit-safe device counters, step stats, JSONL sinks.

Every adaptive mechanism in this package is *sized* from expected
distributions (``plan_hot_capacity`` predicts a hot-tier hit rate,
``plan_exchange_cap`` picks a 3-sigma per-owner headroom, ``dedup_cold``
pays off only past a duplicate factor of ~1.3) and then runs blind.
This module closes the loop with two halves:

**Device side** — a fixed-slot int32 counter vector accumulated with
pure ``jnp`` ops while a hot path traces (:class:`Collector`). The
instrumented paths (``Feature.lookup_tiered``, ``ops.dedup``,
``comm.dist_lookup_local``, ``ops.sample_multihop``) take an opt-in
``collector`` and record what they already computed — the hot/cold
classification mask, the unique count, the pmax'd fallback flag, the
per-owner bucket loads — so collection adds **zero host syncs per
step**, never touches a ``lax.cond`` predicate, and leaves donation
intact. The counters ride out of the jitted step as ONE auxiliary
int32 array (``[NUM_COUNTERS]``, or ``[shards, NUM_COUNTERS]`` from a
``shard_map`` step); losses are bit-identical with metrics on or off
(pinned in tests/test_metrics.py).

**Host side** — :class:`StepStats` merges those vectors (lazily, in
int64, without blocking on the in-flight step) with wall-clock step
latency (streaming log-bucketed histogram -> p50/p95/p99), pipeline
queue depth/wait (``quiver_tpu.pipeline.Pipeline.stats``), and
recompile detection (jit executable-cache deltas of watched
functions). :class:`MetricsSink` emits the one structured JSONL record
schema shared by ``bench.py``, ``scripts/check_leak.py`` and the
benchmark watch scripts; ``report()`` renders the same snapshot for
interactive use.

JSONL record schema (one object per line)::

    {"ts": <unix seconds>, "kind": "<record kind>", ...payload}

Record kinds emitted in-tree: ``step_stats`` (StepStats.snapshot()),
``bench`` (bench.py's and benchmarks/bench_serving.py's measurement
records), ``serving`` (``serving.MicroBatchServer.snapshot()`` — a ``step_stats``
payload whose ``wall`` block times BATCH dispatches, plus a ``request``
block with per-REQUEST admission->result latency percentiles and a
``serving`` block with admission/shed/variant-mix counts), ``slo``
(:class:`SloBudget.snapshot` — error-budget burn rates),
``anomaly`` / ``advice``
(``telemetry.TelemetryHub`` — change-point detections and advisory
re-planning records), ``regress`` (``scripts/bench_regress.py`` —
per-trajectory-group verdicts), ``profile``
(``quiver_tpu.profile.StageProfiler`` / ``scripts/qt_prof.py`` —
per-entry stage timings, modeled bytes, roofline efficiency),
``meta`` (:class:`MetricsSink`'s self-attribution header — host, pid,
start_ts, replica), ``fleet`` (``quiver_tpu.fleet`` — per-replica
health scores + fleet-global rollup from the cross-process
aggregator), and ``trace`` (``quiver_tpu.tailsampling.TailSampler`` —
one KEPT request trace: the keep policy, the span timeline, the
critical-path attribution). Consumers key on ``kind`` and must ignore
unknown fields;
``scripts/lint.sh`` pins that every kind and every counter slot has a
row in docs/observability.md.
"""

from __future__ import annotations

import collections
import json
import math
import os
import threading
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

# -- the device counter vector ---------------------------------------------
#
# Fixed slot layout: ONE int32 vector per step, so adding a counter is
# an append here, not a schema migration everywhere. Per-step values
# are small (bounded by frontier caps); long-run accumulation happens
# host-side in int64 (StepStats).

HOT_ROWS = 0          # valid tiered-lookup slots served from the HBM tier
COLD_ROWS = 1         # valid tiered-lookup slots served from the cold tier
LOOKUP_CALLS = 2      # tiered lookups recorded
DEDUP_TOTAL = 3       # valid ids entering a dedup compaction
DEDUP_UNIQUE = 4      # true distinct count found (may exceed the budget)
DEDUP_OVERFLOW = 5    # dedup budget overflows (full-gather fallbacks)
EXCH_CALLS = 6        # cross-host exchange lookups
EXCH_FALLBACK = 7     # compact-exchange dense fallbacks taken
EXCH_BUCKET_MAX = 8   # peak per-owner request-bucket load       [max slot]
EXCH_CAP = 9          # the per-owner cap in force               [max slot]
FRONTIER_VALID = 10   # valid final-frontier slots out of sampling
FRONTIER_CAP = 11     # static final-frontier capacity
DEDUP_CALLS = 12      # dedup compactions recorded
PREFETCH_HIT_ROWS = 13    # disk-tier rows served from the staging ring
PREFETCH_SYNC_ROWS = 14   # disk-tier rows read synchronously (ring miss)
PREFETCH_STAGED_ROWS = 15  # rows the cold prefetcher staged into the ring
IO_EXTENTS = 16       # coalesced read requests the cold-IO path issued
IO_READ_ROWS = 17     # disk rows those extents covered
IO_READ_BYTES = 18    # bytes the storage device moved (saturates int32)
IO_DEPTH_PEAK = 19    # peak in-flight read requests observed [max slot]
IO_RETRIES = 20       # transient cold-IO read retries (EINTR/EAGAIN/EIO)
FAULTS_INJECTED = 21  # faults the armed FaultPlan fired (process-wide)
STAGING_RESTARTS = 22  # staging workers auto-replaced / shards retried
LOCALITY_HIT_ROWS = 23   # frontier rows owned by the serving home partition
LOCALITY_MISS_ROWS = 24  # frontier rows owned elsewhere (exchange-remote)
COLD_OVERFLOW = 25    # tiered lookups whose cold count passed cold_budget
#                       (filed with COLD_ROWS: the full host gather ran)
EDGE_VALID = 26       # valid sampled edge slots, all hops of the walk
EDGE_CAP = 27         # static edge-slot capacity of those hops
EDGE_REL_MAX = 28     # valid edge slots of the walk's largest relation
#                       (a typed graph's; 0 on an untyped one)
PROJECT_ROWS = 29     # rows the model's live-rows products multiplied
PROJECT_ROWS_CAP = 30  # rows those products' static shapes hold
TYPE_CAP_DROPS = 31   # picks of a per-relation walk that a node type's
#                       frontier cap could not take (masked, counted)

NUM_COUNTERS = 32

#: slots merged with ``max`` across steps/shards; all others add
MAX_SLOTS = (EXCH_BUCKET_MAX, EXCH_CAP, IO_DEPTH_PEAK)

SLOT_NAMES = {
    HOT_ROWS: "hot_rows", COLD_ROWS: "cold_rows",
    LOOKUP_CALLS: "lookup_calls", DEDUP_TOTAL: "dedup_total",
    DEDUP_UNIQUE: "dedup_unique", DEDUP_OVERFLOW: "dedup_overflow",
    EXCH_CALLS: "exchange_calls", EXCH_FALLBACK: "exchange_fallback",
    EXCH_BUCKET_MAX: "exchange_bucket_max", EXCH_CAP: "exchange_cap",
    FRONTIER_VALID: "frontier_valid", FRONTIER_CAP: "frontier_cap",
    DEDUP_CALLS: "dedup_calls",
    PREFETCH_HIT_ROWS: "prefetch_hit_rows",
    PREFETCH_SYNC_ROWS: "prefetch_sync_rows",
    PREFETCH_STAGED_ROWS: "prefetch_staged_rows",
    IO_EXTENTS: "io_extents",
    IO_READ_ROWS: "io_read_rows",
    IO_READ_BYTES: "io_read_bytes",
    IO_DEPTH_PEAK: "io_depth_peak",
    IO_RETRIES: "io_retries",
    FAULTS_INJECTED: "faults_injected",
    STAGING_RESTARTS: "staging_worker_restarts",
    LOCALITY_HIT_ROWS: "locality_hit_rows",
    LOCALITY_MISS_ROWS: "locality_miss_rows",
    COLD_OVERFLOW: "cold_overflow",
    EDGE_VALID: "edge_valid", EDGE_CAP: "edge_cap",
    EDGE_REL_MAX: "edge_rel_max",
    PROJECT_ROWS: "project_rows", PROJECT_ROWS_CAP: "project_rows_cap",
    TYPE_CAP_DROPS: "type_cap_drops",
}

_MAX_MASK_NP = np.zeros((NUM_COUNTERS,), bool)
_MAX_MASK_NP[list(MAX_SLOTS)] = True


class Collector:
    """Trace-time accumulator for the device counter vector.

    Create ONE per trace (inside the function being jitted — a
    collector that outlives a trace would leak stale tracers into the
    next one), hand it down the hot path, and materialize the vector
    with :meth:`counters` as an auxiliary output of the step.

    ``add``/``peak`` values must be computed OUTSIDE ``lax.cond``
    branches (the instrumented paths all compute their predicates and
    loads before branching, so this costs nothing); integer/bool
    scalars only — the loss path must not depend on anything recorded
    here.
    """

    def __init__(self):
        self._entries: List[tuple] = []
        self._absorbed: List = []

    def add(self, slot: int, value) -> None:
        """Accumulate ``value`` into an additive slot."""
        self._entries.append((int(slot), value, False))

    def peak(self, slot: int, value) -> None:
        """Merge ``value`` into a max slot."""
        self._entries.append((int(slot), value, True))

    def add_sown(self, sown) -> None:
        """Add what a model sowed into its ``counters`` collection
        (``nn.Module.sow("counters", name, value)``, a slot's name of
        ``SLOT_NAMES``) into the additive slots of those names."""
        slots = {name: slot for slot, name in SLOT_NAMES.items()}
        for path, value in jax.tree_util.tree_flatten_with_path(sown)[0]:
            name = [k.key for k in path
                    if isinstance(k, jax.tree_util.DictKey)][-1]
            self.add(slots[name], value)

    def counters(self) -> jax.Array:
        """Materialize the ``[NUM_COUNTERS]`` int32 vector."""
        vec = jnp.zeros((NUM_COUNTERS,), jnp.int32)
        for slot, val, is_max in self._entries:
            v = jnp.asarray(val).astype(jnp.int32)
            vec = vec.at[slot].max(v) if is_max else vec.at[slot].add(v)
        for a in self._absorbed:
            vec = merge_counters(vec, a)
        return vec

    def absorb(self, vec) -> None:
        """Merge a materialized counter VECTOR (another collector's
        :meth:`counters` output from the same trace) into this one —
        how a composite program (e.g. the serving step wrapping a
        Feature store's self-collecting lookup) folds an inner path's
        counters into its own without re-instrumenting it. Folded via
        :func:`merge_counters` at :meth:`counters` time, so the slot
        semantics (add, max on ``MAX_SLOTS``) live in one place."""
        self._absorbed.append(jnp.asarray(vec).astype(jnp.int32))


def merge_counters(a, b):
    """Merge two counter vectors (jnp): add, except ``MAX_SLOTS``."""
    a = jnp.asarray(a)
    b = jnp.asarray(b)
    return jnp.where(jnp.asarray(_MAX_MASK_NP), jnp.maximum(a, b), a + b)


def pmerge_counters(vec, axis: str):
    """DEVICE-side cross-shard merge of a counter vector, callable only
    inside a ``shard_map``/``pmap`` over ``axis``: ``psum`` on additive
    slots, ``pmax`` on ``MAX_SLOTS`` — the same semantics as
    :func:`merge_counters`, applied over the mesh axis. This is how the
    dist builders' ``merge_counters=True`` makes every host's
    ``last_counters`` the GLOBAL picture on a real multi-host mesh
    (where the per-shard ``[H, N]`` output is otherwise only locally
    addressable). Pure collectives on an int32 vector: no host sync, no
    effect on the loss path."""
    summed = jax.lax.psum(vec, axis)
    peaked = jax.lax.pmax(vec, axis)
    return jnp.where(jnp.asarray(_MAX_MASK_NP), peaked, summed)


def merge_named_counters(a: Dict[str, int], b: Dict[str, int]) -> Dict[str, int]:
    """Merge two NAMED counter dicts (``counters_dict`` payloads, e.g.
    from per-host JSONL ``step_stats`` records) with the slot
    semantics: add, except the ``MAX_SLOTS`` names which take max.
    Unknown keys add (forward-compatible with new slots)."""
    max_names = {SLOT_NAMES[s] for s in MAX_SLOTS}
    out = dict(a)
    for k, v in b.items():
        if v is None:
            continue
        cur = out.get(k)
        if cur is None:
            out[k] = v
        else:
            out[k] = max(cur, v) if k in max_names else cur + v
    return out


def reduce_counters(stack) -> np.ndarray:
    """Host-side fold of ``[..., NUM_COUNTERS]`` stacked vectors (e.g. a
    shard_map step's per-shard ``[H, N]`` output) into one int64
    vector: sum over leading axes, max on ``MAX_SLOTS``."""
    arr = np.asarray(jax.device_get(stack)).astype(np.int64)
    arr = arr.reshape(-1, NUM_COUNTERS)
    summed = arr.sum(axis=0)
    peaked = arr.max(axis=0, initial=0)
    return np.where(_MAX_MASK_NP, peaked, summed)


def derive(counters) -> Dict[str, Optional[float]]:
    """Observed ratios from a (host) counter vector — the numbers the
    planners predicted: hot-tier hit rate, frontier duplicate factor,
    dedup/fallback rates, per-owner bucket headroom, frontier fill.
    ``None`` where the denominator never moved (path not exercised)."""
    c = np.asarray(jax.device_get(counters)).astype(np.float64)
    if c.ndim > 1:
        c = reduce_counters(c).astype(np.float64)

    def ratio(num, den):
        return float(num / den) if den > 0 else None

    return {
        "hot_hit_rate": ratio(c[HOT_ROWS], c[HOT_ROWS] + c[COLD_ROWS]),
        "dup_factor": ratio(c[DEDUP_TOTAL], c[DEDUP_UNIQUE]),
        "dedup_overflow_rate": ratio(c[DEDUP_OVERFLOW], c[DEDUP_CALLS]),
        "exchange_fallback_rate": ratio(c[EXCH_FALLBACK], c[EXCH_CALLS]),
        "exchange_bucket_peak_frac": ratio(c[EXCH_BUCKET_MAX], c[EXCH_CAP]),
        "frontier_fill": ratio(c[FRONTIER_VALID], c[FRONTIER_CAP]),
        "prefetch_hit_rate": ratio(
            c[PREFETCH_HIT_ROWS],
            c[PREFETCH_HIT_ROWS] + c[PREFETCH_SYNC_ROWS]),
        "io_coalescing_factor": ratio(c[IO_READ_ROWS], c[IO_EXTENTS]),
        "locality_hit_rate": ratio(
            c[LOCALITY_HIT_ROWS],
            c[LOCALITY_HIT_ROWS] + c[LOCALITY_MISS_ROWS]),
    }


def counters_dict(counters) -> Dict[str, int]:
    """Named raw counters (host ints) for JSONL payloads."""
    c = reduce_counters(counters)
    return {name: int(c[slot]) for slot, name in SLOT_NAMES.items()}


# -- host-side aggregation --------------------------------------------------


class _Histogram:
    """Streaming log2-bucketed latency histogram: O(1) memory, add is
    one ``frexp``; quantiles come from the cumulative bucket counts
    with log-linear interpolation inside the landing bucket."""

    _LO = 1e-6            # 1 us floor; anything faster lands in bucket 0

    def __init__(self):
        self.counts: Dict[int, int] = {}
        self.n = 0
        self.total = 0.0
        self.max = 0.0

    def add(self, x: float) -> None:
        x = max(float(x), 0.0)
        self.n += 1
        self.total += x
        self.max = max(self.max, x)
        b = 0 if x < self._LO else int(math.log2(x / self._LO)) + 1
        self.counts[b] = self.counts.get(b, 0) + 1

    def quantile(self, q: float) -> float:
        if not self.n:
            return 0.0
        target = q * self.n
        seen = 0.0
        for b in sorted(self.counts):
            cnt = self.counts[b]
            if seen + cnt >= target:
                lo = 0.0 if b == 0 else self._LO * 2.0 ** (b - 1)
                hi = self._LO * 2.0 ** b
                frac = (target - seen) / cnt
                return min(lo + (hi - lo) * frac, self.max)
            seen += cnt
        return self.max


class StepStats:
    """Merges device counters with host-observed step facts.

    ``record_step(duration_s, counters=None)`` files one step: the
    latency lands in the streaming histogram; the counter vector (a
    device array — ``[N]`` or a shard_map step's ``[H, N]``) is queued
    and folded into an int64 total LAZILY (every ``fold_every`` steps),
    so recording neither blocks on the in-flight step nor overflows
    int32 over long runs.

    ``watch_compiles(*fns)`` registers jitted functions (anything with
    a ``_cache_size()``, e.g. ``build_train_step(...).jitted_fns``)
    whose executable-cache growth is reported as ``recompiles`` — a
    static-shape regression shows up here as a nonzero delta long
    before memory pressure would.

    ``watch_pipeline(p)`` folds a ``quiver_tpu.pipeline.Pipeline``'s
    queue depth/wait stats into the snapshot.
    """

    def __init__(self, fold_every: int = 64):
        self._fold_every = max(int(fold_every), 1)
        self._hist = _Histogram()
        self._req_hist = _Histogram()
        self._pending: List = []
        self._counters = np.zeros((NUM_COUNTERS,), np.int64)
        self._steps = 0
        self._compile_fns: List = []
        self._compile_base: Optional[int] = None
        self._pipelines: List = []
        self._lock = threading.Lock()

    # -- recording ----------------------------------------------------------
    def record_step(self, duration_s: float, counters=None) -> None:
        with self._lock:
            self._steps += 1
            self._hist.add(duration_s)
            if counters is not None:
                self._pending.append(counters)
                if len(self._pending) > self._fold_every:
                    self._fold_locked(keep=1)

    def request_p99_ms(self) -> Optional[float]:
        """The live per-request p99 in ms (None before any request) —
        the observed window the tail sampler's ``latency_over_p99``
        policy reads (``tailsampling.latency_source_from``)."""
        with self._lock:
            if not self._req_hist.n:
                return None
            return 1e3 * self._req_hist.quantile(0.99)

    def record_request(self, duration_s: float) -> None:
        """File one PER-REQUEST latency (admission -> result) — the
        serving layer's unit of account, distinct from the per-step
        (per-batch) latency ``record_step`` files: a request's latency
        includes its coalescing wait and any queueing behind in-flight
        batches, which is exactly what an SLO is written against.
        Snapshots/reports grow a ``request`` percentile block once any
        request has been recorded."""
        with self._lock:
            self._req_hist.add(duration_s)

    def add_counters(self, counters) -> None:
        """File a counter vector not tied to a timed step (e.g. a
        standalone lookup's aux output)."""
        with self._lock:
            self._pending.append(counters)
            if len(self._pending) > self._fold_every:
                self._fold_locked(keep=1)

    def _fold_locked(self, keep: int = 0) -> None:
        # keep=1 on the recording path: the just-filed vector belongs to
        # the step still in flight — device_get on it would block the
        # host on that step, the one stall the lazy fold exists to avoid
        if keep:
            pending = self._pending[:-keep]
            self._pending = self._pending[-keep:]
        else:
            pending, self._pending = self._pending, []
        for c in pending:
            vec = reduce_counters(c)
            self._counters = np.where(_MAX_MASK_NP,
                                      np.maximum(self._counters, vec),
                                      self._counters + vec)

    # -- watches ------------------------------------------------------------
    def watch_compiles(self, *fns) -> "StepStats":
        # baseline only the newly registered fns: re-deriving it from
        # the full cache totals would erase recompiles already observed
        # on earlier registrations. Re-registering a watched fn (e.g.
        # per epoch) is a no-op — double entries would multiply every
        # real recompile by the registration count.
        known = {id(f) for f in self._compile_fns}
        new = [f for f in fns
               if hasattr(f, "_cache_size") and id(f) not in known]
        self._compile_base = ((self._compile_base or 0)
                              + sum(f._cache_size() for f in new))
        self._compile_fns += new
        return self

    def _cache_total(self) -> int:
        return sum(f._cache_size() for f in self._compile_fns)

    def watch_pipeline(self, pipeline) -> "StepStats":
        self._pipelines.append(pipeline)
        return self

    # -- reading ------------------------------------------------------------
    def counters(self) -> np.ndarray:
        with self._lock:
            self._fold_locked()
            return self._counters.copy()

    def snapshot(self) -> dict:
        """One JSONL-ready record (kind ``step_stats``): step latency
        percentiles, accumulated raw counters, the derived ratios, the
        recompile delta, and merged pipeline queue stats."""
        with self._lock:
            self._fold_locked()
            h = self._hist
            rec = {
                "steps": self._steps,
                "wall": {
                    "total_s": round(h.total, 6),
                    "mean_ms": round(1e3 * h.total / h.n, 3) if h.n else 0.0,
                    "p50_ms": round(1e3 * h.quantile(0.50), 3),
                    "p95_ms": round(1e3 * h.quantile(0.95), 3),
                    "p99_ms": round(1e3 * h.quantile(0.99), 3),
                    "max_ms": round(1e3 * h.max, 3),
                },
                "counters": counters_dict(self._counters),
                "derived": derive(self._counters),
            }
            r = self._req_hist
            if r.n:
                rec["request"] = {
                    "count": r.n,
                    "mean_ms": round(1e3 * r.total / r.n, 3),
                    "p50_ms": round(1e3 * r.quantile(0.50), 3),
                    "p95_ms": round(1e3 * r.quantile(0.95), 3),
                    "p99_ms": round(1e3 * r.quantile(0.99), 3),
                    "max_ms": round(1e3 * r.max, 3),
                }
        if self._compile_fns:
            rec["recompiles"] = self._cache_total() - self._compile_base
        if self._pipelines:
            # counts and wait totals add across pipelines; peaks and the
            # instantaneous depth take max; the mean is re-derived from
            # the merged totals (summing per-pipeline means would
            # inflate it)
            merged: Dict[str, float] = {}
            for p in self._pipelines:
                for k, v in p.stats().items():
                    if k == "mean_wait_s":
                        continue
                    merged[k] = max(merged.get(k, 0), v) \
                        if (k.startswith("max_") or k == "depth") \
                        else merged.get(k, 0) + v
            done = merged.get("completed", 0) + merged.get("failed", 0)
            merged["mean_wait_s"] = (merged.get("total_wait_s", 0.0) / done
                                     if done else 0.0)
            rec["queue"] = merged
        return rec

    def report(self) -> str:
        """Human-readable rendering of :meth:`snapshot`."""
        s = self.snapshot()
        w, d, c = s["wall"], s["derived"], s["counters"]
        fmt = lambda v, pct=False: ("n/a" if v is None else
                                    f"{100.0 * v:.1f}%" if pct
                                    else f"{v:.2f}")
        lines = [
            f"steps: {s['steps']}  "
            f"(p50 {w['p50_ms']:.2f} ms, p95 {w['p95_ms']:.2f} ms, "
            f"p99 {w['p99_ms']:.2f} ms, mean {w['mean_ms']:.2f} ms)",
            f"hot-tier hit rate: {fmt(d['hot_hit_rate'], pct=True)}  "
            f"({c['hot_rows']} hot / {c['cold_rows']} cold rows)",
            f"frontier dup factor: {fmt(d['dup_factor'])}  "
            f"(dedup overflow rate {fmt(d['dedup_overflow_rate'], pct=True)})",
            f"exchange fallback rate: "
            f"{fmt(d['exchange_fallback_rate'], pct=True)}  "
            f"(peak bucket {c['exchange_bucket_max']}/{c['exchange_cap']}"
            f" = {fmt(d['exchange_bucket_peak_frac'], pct=True)} of cap)",
            f"frontier fill: {fmt(d['frontier_fill'], pct=True)}",
        ]
        if c["prefetch_hit_rows"] or c["prefetch_sync_rows"]:
            lines.append(
                f"cold-tier prefetch hit rate: "
                f"{fmt(d['prefetch_hit_rate'], pct=True)}  "
                f"({c['prefetch_staged_rows']} rows staged, "
                f"{c['prefetch_sync_rows']} sync fallbacks)")
        if c["io_extents"]:
            lines.append(
                f"cold-tier IO: {c['io_extents']} extents, "
                f"{fmt(d['io_coalescing_factor'])} rows/extent, "
                f"{c['io_read_bytes'] / 1e6:.1f} MB read, "
                f"depth peak {c['io_depth_peak']}")
        if "request" in s:
            r = s["request"]
            lines.insert(1, (
                f"per-request latency ({r['count']} requests): "
                f"p50 {r['p50_ms']:.2f} ms, p95 {r['p95_ms']:.2f} ms, "
                f"p99 {r['p99_ms']:.2f} ms, mean {r['mean_ms']:.2f} ms"))
        if "recompiles" in s:
            lines.append(f"recompiles since watch: {s['recompiles']}")
        if "queue" in s:
            q = s["queue"]
            lines.append("pipeline: " + ", ".join(
                f"{k}={round(v, 4)}" for k, v in sorted(q.items())))
        return "\n".join(lines)


# -- SLO error-budget accounting --------------------------------------------


class SloBudget:
    """Sliding-window SLO error-budget accounting with multi-window
    burn rates — the control signal overload policies act on, in place
    of raw latency samples.

    The SLO reads "over the window, at least ``availability`` of
    requests complete within ``target_p99_ms``" (the defaults,
    ``availability=0.99``, make ``target_p99_ms`` a literal p99
    target). The error BUDGET is the tolerated bad fraction
    (``1 - availability``); a request is *bad* when it fails or is
    rejected (``ok=False``) or when its latency exceeds the target.
    The BURN RATE over a window is ``observed_bad_fraction / budget``:
    1.0 means spending the budget exactly as fast as the SLO tolerates,
    above 1.0 burns it faster. Burn rates are computed over TWO windows
    (``short_window_s`` inside ``window_s``): the short one reacts to
    pressure *now*, the long one stops a lone spike from flapping the
    policy — :meth:`should_shed` is the AND of both (the multi-window
    burn-rate alert shape), which is what ``serving.MicroBatchServer``
    consults for its quality-shed decision (hysteresis stays the
    server's, unchanged).

    Bookkeeping is per-second buckets in a bounded deque — O(window
    seconds) memory regardless of request rate, safe from any thread.
    :meth:`snapshot` is one JSONL-ready record (kind ``slo``);
    :meth:`emit` appends it to a :class:`MetricsSink`.
    """

    def __init__(self, target_p99_ms: float, availability: float = 0.99,
                 window_s: float = 300.0, short_window_s: float = 30.0,
                 shed_burn_rate: float = 1.0, min_requests: int = 20,
                 clock=None):
        if not 0.0 < availability < 1.0:
            raise ValueError(
                f"availability must be in (0, 1), got {availability}")
        if not 0.0 < short_window_s <= window_s:
            raise ValueError("need 0 < short_window_s <= window_s")
        self.target_p99_ms = float(target_p99_ms)
        self.availability = float(availability)
        self.budget_frac = 1.0 - self.availability
        self.window_s = float(window_s)
        self.short_window_s = float(short_window_s)
        self.shed_burn_rate = float(shed_burn_rate)
        self.min_requests = int(min_requests)
        self._clock = clock if clock is not None else time.monotonic
        self._buckets: "collections.deque" = collections.deque()
        self._total = 0
        self._bad = 0
        self._lock = threading.Lock()

    # -- recording ----------------------------------------------------------
    def record(self, latency_s: Optional[float] = None,
               ok: bool = True) -> None:
        """File one request outcome: *bad* if it failed/was shed
        (``ok=False``) or exceeded the latency target."""
        bad = (not ok) or (latency_s is not None
                           and latency_s * 1e3 > self.target_p99_ms)
        sec = int(self._clock())
        with self._lock:
            b = self._buckets
            # a non-monotonic clock read lands in the newest bucket
            # rather than corrupting the ordering the pruner relies on
            if b and b[-1][0] >= sec:
                slot = b[-1]
            else:
                slot = [sec, 0, 0]
                b.append(slot)
            slot[1] += 1
            slot[2] += int(bad)
            self._total += 1
            self._bad += int(bad)
            lo = self._clock() - self.window_s - 1.0
            while b and b[0][0] < lo:
                b.popleft()

    # -- reading ------------------------------------------------------------
    def _window_counts(self, seconds: float):
        lo = self._clock() - seconds
        total = bad = 0
        with self._lock:
            for sec, n, nb in reversed(self._buckets):
                if sec + 1.0 <= lo:      # bucket wholly before the window
                    break
                total += n
                bad += nb
        return total, bad

    def burn_rate(self, window_s: Optional[float] = None) -> Optional[float]:
        """Observed bad-fraction over the window divided by the budget;
        ``None`` below ``min_requests`` samples (too few to call)."""
        total, bad = self._window_counts(window_s or self.window_s)
        return self._rate(total, bad)

    def _rate(self, total, bad) -> Optional[float]:
        return ((bad / total) / self.budget_frac
                if total >= self.min_requests else None)

    def budget_remaining(self) -> Optional[float]:
        """Fraction of the long-window error budget left: 1.0 untouched,
        0.0 spent exactly, negative overspent; ``None`` below
        ``min_requests`` (the same too-few-to-call guard as
        :meth:`burn_rate` — one bad request out of one must not read
        as a -99x overspend)."""
        total, bad = self._window_counts(self.window_s)
        if total < self.min_requests:
            return None
        return 1.0 - bad / (self.budget_frac * total)

    def should_shed(self) -> bool:
        """True while the budget is burning unsustainably: short-window
        burn above ``shed_burn_rate`` AND long-window burn above 1.0
        (both with enough samples to mean anything)."""
        s = self.burn_rate(self.short_window_s)
        if s is None or s <= self.shed_burn_rate:
            return False
        l = self.burn_rate(self.window_s)
        return l is not None and l > 1.0

    def snapshot(self) -> dict:
        """One JSONL-ready record (kind ``slo``). Every derived field
        (burn rates, remaining budget, the shed verdict) is computed
        from ONE read of each window, so the record is internally
        consistent even while requests land concurrently."""
        short_t, short_b = self._window_counts(self.short_window_s)
        long_t, long_b = self._window_counts(self.window_s)
        srate = self._rate(short_t, short_b)
        lrate = self._rate(long_t, long_b)
        remaining = (1.0 - long_b / (self.budget_frac * long_t)
                     if long_t >= self.min_requests else None)
        shedding = (srate is not None and srate > self.shed_burn_rate
                    and lrate is not None and lrate > 1.0)
        with self._lock:
            total, bad = self._total, self._bad
        return {
            "target_p99_ms": self.target_p99_ms,
            "availability": self.availability,
            "windows": {
                "short": {"window_s": self.short_window_s,
                          "requests": short_t, "bad": short_b,
                          "burn_rate": srate},
                "long": {"window_s": self.window_s,
                         "requests": long_t, "bad": long_b,
                         "burn_rate": lrate},
            },
            "budget_remaining": (None if remaining is None
                                 else round(remaining, 6)),
            "shedding": shedding,
            "total": {"requests": total, "bad": bad},
        }

    def emit(self, sink: "MetricsSink", kind: str = "slo") -> dict:
        """Append :meth:`snapshot` to a :class:`MetricsSink`."""
        return sink.emit(self.snapshot(), kind=kind)


# -- structured emission ----------------------------------------------------


def _json_default(o):
    if isinstance(o, (np.integer,)):
        return int(o)
    if isinstance(o, (np.floating,)):
        return float(o)
    if isinstance(o, (np.ndarray, jax.Array)):
        return np.asarray(jax.device_get(o)).tolist()
    return str(o)


class MetricsSink:
    """Append-only JSONL emitter — the one record schema shared by the
    interactive ``report()``, ``bench.py``'s measurement line, and the
    long-running replicas' heartbeat logs.

    ``path`` is a filesystem path (opened append) or any file-like with
    ``write``. Every record gains ``ts`` (unix seconds) and ``kind``.

    ``max_bytes`` (path-owned sinks only) bounds the file: when an emit
    pushes it past the limit, the file rolls over to ``<path>.1``
    (replacing any previous rollover) and a fresh file starts — a
    week-long replica keeps at most ``2 * max_bytes`` on disk
    instead of growing without bound. Readers that want the full
    window read the seam: :func:`read_jsonl` (and ``scripts/qt_top.py``
    / ``scripts/bench_regress.py``) consume ``<path>.1`` before
    ``<path>``.

    Path-owned sinks are SELF-ATTRIBUTING: the first emit (and the
    first emit into each post-rollover file) is preceded by one
    ``meta`` header record — ``{host, pid, start_ts, replica}``
    (``replica`` from the constructor arg or ``QT_REPLICA``) — so a
    fleet aggregator tailing N replicas' files knows who wrote each
    one without filename conventions. Readers key on ``kind`` and must
    ignore unknown kinds, so old files without the header (and
    consumers that predate it) keep working.
    """

    def __init__(self, path, kind: str = "record",
                 max_bytes: Optional[int] = None,
                 replica: Optional[str] = None):
        self._own = isinstance(path, (str, bytes, os.PathLike))
        self._path = os.fspath(path) if self._own else None
        self._f = open(path, "a") if self._own else path
        self._kind = kind
        self._max_bytes = (int(max_bytes)
                           if max_bytes and self._own else None)
        self._replica = (str(replica) if replica
                         else os.environ.get("QT_REPLICA") or None)
        self._start_ts = time.time()
        self._meta_written = not self._own
        self.write_errors = 0
        self._warned_write = False
        self._lock = threading.Lock()

    def emit(self, record: dict, kind: Optional[str] = None) -> dict:
        rec = {"ts": round(time.time(), 3),
               "kind": kind or record.get("kind", self._kind)}
        rec.update({k: v for k, v in record.items() if k != "kind"})
        line = json.dumps(rec, default=_json_default)
        try:
            from . import faults
            faults.fire("sink.write")    # the injectable disk failure
            with self._lock:
                if not self._meta_written:
                    self._meta_written = True
                    self._write_meta_locked()
                self._f.write(line + "\n")
                self._f.flush()
                if self._max_bytes and self._f.tell() >= self._max_bytes:
                    self._rollover_locked()
        except (OSError, ValueError) as e:
            # a telemetry sink must never kill the data path it
            # observes: the failed write is COUNTED (``write_errors``)
            # and logged once — silently lost records would make a
            # flaky disk look like a healthy quiet system
            with self._lock:
                self.write_errors += 1
                warn = not self._warned_write
                self._warned_write = True
            if warn:
                import logging
                logging.getLogger("quiver_tpu.metrics").warning(
                    "MetricsSink write failed (%s): record dropped; "
                    "counted in write_errors (warning fires once)", e)
        return rec

    def _write_meta_locked(self, kind: str = "meta") -> None:
        # the self-attribution header: who is writing this file. Lazy
        # (first emit, not __init__) so a sink that never emits leaves
        # no file noise, and re-written after each rollover so BOTH
        # halves of the seam carry their provenance.
        import socket
        rec = {"ts": round(time.time(), 3), "kind": kind,
               "host": socket.gethostname(), "pid": os.getpid(),
               "start_ts": round(self._start_ts, 3)}
        if self._replica:
            rec["replica"] = self._replica
        self._f.write(json.dumps(rec, default=_json_default) + "\n")

    def _rollover_locked(self) -> None:
        # whole-record boundary by construction: rollover happens only
        # between emits, so neither file ever holds a torn JSON line
        self._f.close()
        os.replace(self._path, self._path + ".1")
        self._f = open(self._path, "a")
        self._write_meta_locked()

    def emit_stats(self, stats: StepStats, kind: str = "step_stats") -> dict:
        return self.emit(stats.snapshot(), kind=kind)

    def close(self) -> None:
        if self._own:
            self._f.close()

    def __enter__(self) -> "MetricsSink":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


def read_jsonl(path) -> List[dict]:
    """Read a sink's records across the rollover seam: ``<path>.1``
    (the rolled-over older half, when present) then ``<path>`` —
    chronological by construction. Unparseable lines are skipped (a
    crashed writer's torn last line must not poison the history)."""
    path = os.fspath(path)
    out: List[dict] = []
    for p in (path + ".1", path):
        if not os.path.exists(p):
            continue
        with open(p) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    rec = json.loads(line)
                except ValueError:
                    continue
                if isinstance(rec, dict):
                    out.append(rec)
    return out


# -- interactive convenience ------------------------------------------------

_default_stats: Optional[StepStats] = None
_default_lock = threading.Lock()

# the unified report()'s extra sections: components (a MicroBatchServer,
# a telemetry.TelemetryHub) register a zero-arg renderer under a name;
# report() appends each section after the default StepStats block, so
# ONE call shows counters + step/request stats + SLO + prefetch +
# tracer status + latest advice without the caller knowing which
# object owns which block. Registration replaces by name; components
# unregister on close.
_report_sections: "collections.OrderedDict[str, object]" = \
    collections.OrderedDict()


def register_report_section(name: str, fn) -> None:
    """Register a zero-arg ``fn() -> str`` rendered by :func:`report`
    (after the default ``StepStats`` block). Same ``name`` replaces."""
    with _default_lock:
        _report_sections[name] = fn


def unregister_report_section(name: str) -> None:
    with _default_lock:
        _report_sections.pop(name, None)


def stats() -> StepStats:
    """The process-default :class:`StepStats` (created on first use) —
    the aggregator ``report()`` reads when given nothing."""
    global _default_stats
    with _default_lock:
        if _default_stats is None:
            _default_stats = StepStats()
        return _default_stats


def report(obj=None) -> str:
    """Render a telemetry summary: a :class:`StepStats`, or a raw
    counter vector/stack. With no argument, the UNIFIED report: the
    process-default stats (counters + step/request percentiles +
    prefetch lines), the tracer's status, and every registered section
    (a live server's serving/SLO block, a ``TelemetryHub``'s series +
    anomalies + latest advice) — one call, everything observable."""
    if obj is not None:
        if isinstance(obj, StepStats):
            return obj.report()
        c = reduce_counters(obj)
        d = derive(c)
        named = counters_dict(c)
        parts = [f"{k}={v}" for k, v in named.items() if v]
        parts += [f"{k}={v:.3f}" for k, v in d.items() if v is not None]
        return "counters: " + (", ".join(parts) if parts else "(empty)")
    lines = [stats().report()]
    from . import tracing
    tr = tracing.get_tracer()
    lines.append(f"tracing: {'on' if tr.enabled else 'off'} "
                 f"({len(tr)}/{tr.capacity} spans retained)")
    with _default_lock:
        sections = list(_report_sections.items())
    for name, fn in sections:
        try:
            text = fn()
        except Exception as e:      # a dead component must not kill
            text = f"{name}: <report failed: {e!r}>"   # the whole view
        if text:
            lines.append(text)
    return "\n".join(lines)
