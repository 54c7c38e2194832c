"""Runtime telemetry: jit-safe device counters, StepStats, MetricsSink.

The contract under test, in order of importance:

1. **Non-perturbation** — with ``collect_metrics=True`` the losses are
   BIT-identical to the metrics-off step on the same batches (single-
   chip donated step, dist compact-exchange step on both the narrow and
   the forced-fallback branch), and the traced program contains zero
   host-callback/infeed equations (``_traffic.host_sync_eqns``) — the
   counters ride out as a plain device output.
2. **Truth** — the device counters match analytic values computed in
   numpy on the same batches: hot/cold classification counts, the dup
   factor, the dedup budget-overflow flag, the exchange fallback flag
   (cross-checked against ``ops.dedup.compact_exchange_slots``, the
   same analytic mirror the benches use), frontier fill.
3. **Host side** — StepStats folds [N] and per-shard [H, N] vectors
   with add/max slot semantics, detects recompiles, reads pipeline
   queue stats; MetricsSink writes parseable one-line JSONL records
   with the shared {ts, kind, ...} schema.
"""

import json
import os

import numpy as np
import jax
import jax.numpy as jnp
import optax
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import quiver_tpu as qv
from quiver_tpu import metrics as qm
from quiver_tpu.models import GraphSAGE
from quiver_tpu.ops import sample_multihop
from quiver_tpu.ops.dedup import compact_exchange_slots
from quiver_tpu.parallel import build_dist_train_step, build_train_step
from quiver_tpu.parallel.train import (dedup_feature_gather, init_state,
                                       layers_to_adjs,
                                       masked_feature_gather)

from _traffic import host_sync_eqns


class TestCounterPrimitives:
    def test_merge_and_reduce_slot_semantics(self):
        a = np.zeros(qm.NUM_COUNTERS, np.int32)
        b = np.zeros(qm.NUM_COUNTERS, np.int32)
        a[qm.HOT_ROWS], b[qm.HOT_ROWS] = 3, 4            # additive
        a[qm.EXCH_BUCKET_MAX], b[qm.EXCH_BUCKET_MAX] = 7, 5   # max
        merged = np.asarray(qm.merge_counters(jnp.asarray(a),
                                              jnp.asarray(b)))
        assert merged[qm.HOT_ROWS] == 7
        assert merged[qm.EXCH_BUCKET_MAX] == 7
        red = qm.reduce_counters(np.stack([a, b]))
        assert red[qm.HOT_ROWS] == 7
        assert red[qm.EXCH_BUCKET_MAX] == 7
        assert red.dtype == np.int64

    def test_collector_and_derive(self):
        col = qm.Collector()
        col.add(qm.HOT_ROWS, 30)
        col.add(qm.COLD_ROWS, 10)
        col.peak(qm.EXCH_CAP, 8)
        col.peak(qm.EXCH_CAP, 6)                # max, not add
        vec = np.asarray(col.counters())
        assert vec[qm.HOT_ROWS] == 30 and vec[qm.EXCH_CAP] == 8
        d = qm.derive(vec)
        assert d["hot_hit_rate"] == pytest.approx(0.75)
        assert d["dup_factor"] is None          # denominator never moved


@pytest.fixture
def tiered_store(rng):
    n, dim = 800, 8
    feat = rng.standard_normal((n, dim)).astype(np.float32)
    store = qv.Feature(device_cache_size=(n // 4) * dim * 4,
                       dedup_cold=True, cold_budget=64)
    store.from_cpu_tensor(feat)
    host = jnp.asarray(store.host_part)
    return store, host, feat, n


class TestFeatureCounters:
    def _lookup(self, store, host, ids, masked=False):
        return store._lookup_tiered(store.device_part, host,
                                    jnp.asarray(ids),
                                    store.feature_order, masked, True)

    def test_hot_cold_and_dup_match_numpy(self, tiered_store, rng):
        store, host, feat, n = tiered_store
        pool = rng.choice(n, 40, replace=False)
        ids = pool[rng.integers(0, pool.size, 256)].astype(np.int32)
        rows, c = self._lookup(store, host, ids)
        c = np.asarray(c)
        # no csr_topo: ids ARE storage rows — hot iff < cache_rows
        hot = int((ids < store.cache_rows).sum())
        assert c[qm.LOOKUP_CALLS] == 1
        assert c[qm.HOT_ROWS] == hot
        assert c[qm.COLD_ROWS] == ids.shape[0] - hot
        assert c[qm.DEDUP_TOTAL] == ids.shape[0]
        assert c[qm.DEDUP_UNIQUE] == np.unique(ids).size
        assert c[qm.DEDUP_OVERFLOW] == 0       # 40 distinct < budget 64
        d = qm.derive(c)
        assert d["dup_factor"] == pytest.approx(
            ids.shape[0] / np.unique(ids).size)
        # rows bit-identical to the metrics-off lookup
        plain = store._lookup_tiered(store.device_part, host,
                                     jnp.asarray(ids),
                                     store.feature_order)
        assert np.asarray(rows).tobytes() == np.asarray(plain).tobytes()

    def test_overflow_flag_on_forced_overflow_batch(self, tiered_store,
                                                    rng):
        store, host, feat, n = tiered_store
        ids = rng.choice(n, 256, replace=False).astype(np.int32)
        _, c = self._lookup(store, host, ids)
        c = np.asarray(c)
        assert c[qm.DEDUP_UNIQUE] == 256       # true count, > budget 64
        assert c[qm.DEDUP_OVERFLOW] == 1

    def test_masked_counts_exclude_padding(self, tiered_store, rng):
        store, host, feat, n = tiered_store
        ids = rng.integers(0, n, 128).astype(np.int32)
        ids[::4] = -1
        _, c = self._lookup(store, host, ids, masked=True)
        c = np.asarray(c)
        valid = ids[ids >= 0]
        hot = int((valid < store.cache_rows).sum())
        assert c[qm.HOT_ROWS] == hot
        assert c[qm.COLD_ROWS] == valid.size - hot
        assert c[qm.DEDUP_UNIQUE] == np.unique(valid).size

    def test_public_lookup_numpy_path_matches_fused(self, tiered_store,
                                                    rng):
        store, host, feat, n = tiered_store
        pool = rng.choice(n, 40, replace=False)
        ids = pool[rng.integers(0, pool.size, 256)].astype(np.int32)
        _, c_fused = self._lookup(store, host, ids)
        rows, c_np = store.lookup_tiered(jnp.asarray(ids),
                                         collect_metrics=True)
        for slot in (qm.HOT_ROWS, qm.COLD_ROWS, qm.DEDUP_UNIQUE,
                     qm.DEDUP_TOTAL, qm.DEDUP_OVERFLOW):
            assert c_np[slot] == int(np.asarray(c_fused)[slot])
        np.testing.assert_allclose(np.asarray(rows), feat[ids], rtol=1e-6)

    def test_no_host_sync_in_fused_collect_path(self, tiered_store, rng):
        store, host, feat, n = tiered_store
        ids = jnp.asarray(rng.integers(0, n, 256, dtype=np.int32))
        syncs = host_sync_eqns(
            lambda i: store._lookup_tiered_raw(store.device_part, host,
                                               i, store.feature_order,
                                               False, True), (ids,))
        assert syncs == []


class TestSamplerCounters:
    def test_frontier_fill(self, small_graph, rng):
        indptr, indices = small_graph
        topo = qv.CSRTopo(indptr=indptr, indices=indices)
        s = qv.GraphSageSampler(topo, [4, 3], collect_metrics=True)
        seeds = rng.choice(topo.node_count, 16, replace=False)
        n_id, bs, adjs = s.sample(jnp.asarray(seeds, jnp.int32))
        c = np.asarray(s.last_counters)
        assert c[qm.FRONTIER_VALID] == int((np.asarray(n_id) >= 0).sum())
        assert c[qm.FRONTIER_CAP] == int(n_id.shape[0])
        assert 0 < qm.derive(c)["frontier_fill"] <= 1.0


@pytest.fixture
def dist_setup(rng):
    n, dim, classes, hosts = 240, 12, 4, 8
    deg = rng.integers(1, 9, n)
    indptr = np.zeros(n + 1, np.int64)
    np.cumsum(deg, out=indptr[1:])
    indices = rng.integers(0, n, int(indptr[-1]), dtype=np.int32)
    feat = rng.standard_normal((n, dim)).astype(np.float32)
    labels = rng.integers(0, classes, n).astype(np.int32)
    g2h = rng.integers(0, hosts, n).astype(np.int32)
    g2h[:hosts] = np.arange(hosts)
    mesh = Mesh(np.array(jax.devices()), axis_names=("host",))
    info = qv.PartitionInfo(host=0, hosts=hosts, global2host=g2h)
    comm = qv.TpuComm(rank=0, world_size=hosts, mesh=mesh, axis="host")
    return (n, dim, classes, hosts, indptr, indices, feat, labels, g2h,
            mesh, info, comm)


class TestDistCounters:
    def test_lookup_fallback_flag_matches_analytic_mirror(self,
                                                          dist_setup,
                                                          rng):
        (n, dim, classes, hosts, indptr, indices, feat, labels, g2h,
         mesh, info, comm) = dist_setup
        cap = 8
        dist = qv.DistFeature.from_partition(feat, info, comm,
                                             exchange_cap=cap,
                                             collect_metrics=True)
        plain = qv.DistFeature.from_partition(feat, info, comm,
                                              exchange_cap=cap)
        per_shard = 96
        for dup_heavy in (True, False):
            if dup_heavy:
                pool = rng.integers(0, n, 12)
                ids = pool[rng.integers(0, pool.size,
                                        hosts * per_shard)]
            else:
                ids = rng.integers(0, n, hosts * per_shard)
            ids = ids.astype(np.int32)
            out = dist[jnp.asarray(ids)]
            c = qm.reduce_counters(dist.last_counters)
            # the analytic mirror the benches use: compact slots ==
            # cap*hosts on every shard <=> no shard overflowed <=> the
            # pmax'd flag kept every shard on the narrow branch
            fits = all(
                compact_exchange_slots(s, cap, hosts, owner=g2h)
                == cap * hosts
                for s in ids.reshape(hosts, per_shard))
            if fits:
                assert c[qm.EXCH_FALLBACK] == 0
            else:
                # the flag is shard-uniform: all shards record it
                assert c[qm.EXCH_FALLBACK] == hosts
            assert c[qm.EXCH_CALLS] == hosts
            assert c[qm.EXCH_CAP] == cap
            assert c[qm.EXCH_BUCKET_MAX] >= 1
            # rows bit-identical to the metrics-off store
            assert np.asarray(out).tobytes() == np.asarray(
                plain[jnp.asarray(ids)]).tobytes()

    def test_bucket_max_matches_numpy(self, dist_setup, rng):
        (n, dim, classes, hosts, indptr, indices, feat, labels, g2h,
         mesh, info, comm) = dist_setup
        cap = 16
        dist = qv.DistFeature.from_partition(feat, info, comm,
                                             exchange_cap=cap,
                                             collect_metrics=True)
        per_shard = 64
        pool = rng.integers(0, n, 10)
        ids = pool[rng.integers(0, pool.size,
                                hosts * per_shard)].astype(np.int32)
        dist[jnp.asarray(ids)]
        c = qm.reduce_counters(dist.last_counters)
        expect = max(
            np.bincount(g2h[np.unique(s)], minlength=hosts).max()
            for s in ids.reshape(hosts, per_shard))
        assert c[qm.EXCH_BUCKET_MAX] == expect


class TestStepParity:
    def _setup(self, rng, n=900, dim=16, classes=4):
        deg = rng.poisson(8, n).astype(np.int64)
        indptr = np.zeros(n + 1, np.int64)
        np.cumsum(deg, out=indptr[1:])
        indices = rng.integers(0, n, int(indptr[-1]), dtype=np.int32)
        feat = rng.standard_normal((n, dim)).astype(np.float32)
        labels = rng.integers(0, classes, n).astype(np.int32)
        sizes, bs = [4, 3], 32
        model = GraphSAGE(hidden_dim=16, out_dim=classes, num_layers=2,
                          dropout=0.0)
        tx = optax.adam(1e-3)
        ip = jnp.asarray(indptr.astype(np.int32))
        ix = jnp.asarray(indices)
        n_id, layers = sample_multihop(ip, ix,
                                       jnp.arange(bs, dtype=jnp.int32),
                                       sizes, jax.random.key(0))
        state = init_state(model, tx,
                           masked_feature_gather(jnp.asarray(feat), n_id),
                           layers_to_adjs(layers, bs, sizes),
                           jax.random.key(1))
        return (n, model, tx, sizes, bs, ip, ix, jnp.asarray(feat),
                jnp.asarray(labels), state)

    def test_bit_identical_loss_under_donation(self, rng):
        (n, model, tx, sizes, bs, ip, ix, feat, labels,
         state) = self._setup(rng)
        step_off = build_train_step(model, tx, sizes, bs,
                                    dedup_gather=True)
        step_on = build_train_step(model, tx, sizes, bs,
                                   dedup_gather=True,
                                   collect_metrics=True)
        st_off = jax.tree.map(jnp.copy, state)
        st_on = jax.tree.map(jnp.copy, state)
        for it in range(3):                      # donated chains
            seeds = jnp.asarray(rng.choice(n, bs,
                                           replace=False).astype(np.int32))
            y = labels[seeds]
            key = jax.random.key(100 + it)
            st_off, l_off = step_off(st_off, feat, None, ip, ix, seeds,
                                     y, key)
            st_on, l_on, counters = step_on(st_on, feat, None, ip, ix,
                                            seeds, y, key)
            assert np.asarray(l_off).tobytes() == \
                np.asarray(l_on).tobytes()
            c = np.asarray(counters)
            assert c.shape == (qm.NUM_COUNTERS,)
            assert c[qm.FRONTIER_CAP] > 0
        # the donated param chains stayed identical too
        a = jax.tree_util.tree_leaves(st_off.params)
        b = jax.tree_util.tree_leaves(st_on.params)
        for x, y in zip(a, b):
            assert np.asarray(x).tobytes() == np.asarray(y).tobytes()

    def test_no_host_sync_in_metered_step(self, rng):
        (n, model, tx, sizes, bs, ip, ix, feat, labels,
         state) = self._setup(rng)
        step_on = build_train_step(model, tx, sizes, bs, donate=False,
                                   dedup_gather=True,
                                   collect_metrics=True)
        seeds = jnp.asarray(rng.choice(n, bs,
                                       replace=False).astype(np.int32))
        args = (state, feat, None, ip, ix, seeds, labels[seeds],
                jax.random.key(5))
        assert host_sync_eqns(step_on, args) == []

    def test_dist_step_parity_both_branches(self, dist_setup, rng):
        (n, dim, classes, hosts, indptr, indices, feat, labels, g2h,
         mesh, info, comm) = dist_setup
        dist = qv.DistFeature.from_partition(feat, info, comm)
        sizes, per_host = [3, 2], 8
        model = GraphSAGE(hidden_dim=16, out_dim=classes, num_layers=2,
                          dropout=0.0)
        tx = optax.adam(1e-2)
        ip = jnp.asarray(indptr.astype(np.int32))
        ix = jnp.asarray(indices)
        n_id, layers = sample_multihop(
            ip, ix, jnp.arange(per_host, dtype=jnp.int32), sizes,
            jax.random.key(0))
        state = init_state(model, tx,
                           masked_feature_gather(jnp.asarray(feat), n_id),
                           layers_to_adjs(layers, per_host, sizes),
                           jax.random.key(1))
        sharding = NamedSharding(mesh, P("host"))
        common = (dist._spmd_feat, info.global2host.astype(jnp.int32),
                  info.global2local, ip, ix)
        g = hosts * per_host
        labels_j = jnp.asarray(labels)
        # cap=6 forces the dense fallback on a unique-heavy batch while
        # a duplicate-heavy batch stays narrow — parity must hold on
        # BOTH branches of the compact exchange
        for cap in (None, 6):
            off = build_dist_train_step(
                model, tx, sizes, per_host, mesh,
                rows_per_host=dist._rows_per_host, donate=False,
                exchange_cap=cap)
            on = build_dist_train_step(
                model, tx, sizes, per_host, mesh,
                rows_per_host=dist._rows_per_host, donate=False,
                exchange_cap=cap, collect_metrics=True)
            # dense (cap=None) has no narrow/fallback branch to steer —
            # one batch covers it; both batch shapes only matter at cap=6
            for dup_heavy in ((True, False) if cap is not None
                              else (False,)):
                if dup_heavy:
                    pool = rng.integers(0, n, 10)
                    seeds_np = pool[rng.integers(0, pool.size, g)]
                else:
                    seeds_np = rng.choice(n, g, replace=False)
                seeds = jax.device_put(
                    jnp.asarray(seeds_np.astype(np.int32)), sharding)
                y = jax.device_put(labels_j[seeds], sharding)
                key = jax.random.key(31)
                _, l_off = off(state, *common, seeds, y, key)
                _, l_on, counters = on(state, *common, seeds, y, key)
                assert np.asarray(l_off).tobytes() == \
                    np.asarray(l_on).tobytes()
                assert counters.shape == (hosts, qm.NUM_COUNTERS)
                c = qm.reduce_counters(counters)
                assert c[qm.EXCH_CALLS] == hosts
                if cap is not None:
                    assert c[qm.EXCH_CAP] == cap


class TestStepStats:
    def test_fold_and_percentiles(self):
        stats = qm.StepStats(fold_every=4)
        vec = np.zeros(qm.NUM_COUNTERS, np.int32)
        vec[qm.HOT_ROWS] = 10
        vec[qm.EXCH_BUCKET_MAX] = 5
        for i in range(10):
            stats.record_step(0.010 if i < 9 else 0.200,
                              jnp.asarray(vec))
        c = stats.counters()
        assert c[qm.HOT_ROWS] == 100                 # additive
        assert c[qm.EXCH_BUCKET_MAX] == 5            # max
        snap = stats.snapshot()
        assert snap["steps"] == 10
        assert 5.0 <= snap["wall"]["p50_ms"] <= 20.0
        assert snap["wall"]["p99_ms"] >= snap["wall"]["p50_ms"]
        assert snap["wall"]["max_ms"] == pytest.approx(200.0)
        assert snap["counters"]["hot_rows"] == 100

    def test_per_shard_stack_folds(self):
        stats = qm.StepStats()
        stack = np.zeros((8, qm.NUM_COUNTERS), np.int32)
        stack[:, qm.EXCH_FALLBACK] = 1
        stack[:, qm.EXCH_BUCKET_MAX] = np.arange(8)
        stats.record_step(0.001, stack)
        c = stats.counters()
        assert c[qm.EXCH_FALLBACK] == 8
        assert c[qm.EXCH_BUCKET_MAX] == 7

    def test_recompile_watch(self):
        f = jax.jit(lambda x: x * 2)
        f(jnp.ones((4,)))
        stats = qm.StepStats().watch_compiles(f)
        stats.record_step(0.001)
        assert stats.snapshot()["recompiles"] == 0
        f(jnp.ones((8,)))                            # new shape -> miss
        assert stats.snapshot()["recompiles"] == 1

    def test_pipeline_queue_stats(self):
        from quiver_tpu.pipeline import Pipeline
        with Pipeline(depth=2, name="t-metrics") as p:
            stats = qm.StepStats().watch_pipeline(p)
            futs = [p.submit(lambda x: x + 1, i) for i in range(5)]
            assert [f.result() for f in futs] == [1, 2, 3, 4, 5]
            s = p.stats()
            assert s["submitted"] == 5 and s["completed"] == 5
            assert s["failed"] == 0
            assert s["max_depth"] >= 1
            assert s["mean_wait_s"] >= 0.0
            snap = stats.snapshot()
            assert snap["queue"]["submitted"] == 5

    def test_report_renders(self):
        stats = qm.StepStats()
        vec = np.zeros(qm.NUM_COUNTERS, np.int32)
        vec[qm.HOT_ROWS], vec[qm.COLD_ROWS] = 75, 25
        stats.record_step(0.002, vec)
        text = stats.report()
        assert "hot-tier hit rate: 75.0%" in text
        assert "steps: 1" in text
        # module-level conveniences
        assert "counters:" in qm.report(vec)
        assert isinstance(qm.stats(), qm.StepStats)


class TestMetricsSink:
    def test_jsonl_schema_roundtrip(self, tmp_path):
        path = str(tmp_path / "m.jsonl")
        stats = qm.StepStats()
        vec = np.zeros(qm.NUM_COUNTERS, np.int32)
        vec[qm.FRONTIER_VALID], vec[qm.FRONTIER_CAP] = 30, 40
        stats.record_step(0.001, vec)
        with qm.MetricsSink(path) as sink:
            sink.emit_stats(stats)
            sink.emit({"usable": True, "h2d_MBps": 120.0},
                      kind="bench")
            sink.emit({"value": np.float64(1.5),
                       "arr": np.arange(2)})     # numpy-safe encoding
        with open(path) as f:
            recs = [json.loads(l) for l in f if l.strip()]
        assert len(recs) == 4
        for r in recs:
            assert isinstance(r["ts"], float) and "kind" in r
        # the sink self-attributes: one meta header precedes the data
        assert recs[0]["kind"] == "meta" and recs[0]["pid"] == os.getpid()
        assert recs[1]["kind"] == "step_stats"
        assert recs[1]["counters"]["frontier_valid"] == 30
        assert recs[1]["derived"]["frontier_fill"] == pytest.approx(0.75)
        assert recs[2]["kind"] == "bench" and recs[2]["usable"] is True
        assert recs[3]["arr"] == [0, 1]


class TestGatherCollectorPlumbing:
    def test_dedup_feature_gather_records(self, rng):
        feat = jnp.asarray(
            rng.standard_normal((100, 4)).astype(np.float32))
        pool = rng.integers(0, 100, 8)
        ids = jnp.asarray(pool[rng.integers(0, 8, 64)].astype(np.int32))

        def fn(ids):
            col = qm.Collector()
            out = dedup_feature_gather(feat, ids, budget=16,
                                       collector=col)
            return out, col.counters()

        out, c = jax.jit(fn)(ids)
        c = np.asarray(c)
        assert c[qm.DEDUP_TOTAL] == 64
        assert c[qm.DEDUP_UNIQUE] == np.unique(np.asarray(ids)).size
        assert c[qm.DEDUP_OVERFLOW] == 0
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(feat)[np.asarray(ids)],
                                   rtol=1e-6)


class TestServingTelemetry:
    """The ``serving`` record kind's metrics-side half: per-REQUEST
    latency is a first-class histogram next to the per-step one, the
    snapshot/report include it only when present, and Collector.absorb
    folds an inner program's materialized vector with slot semantics
    (the serve step absorbs the Feature lookup's self-collected
    counters this way)."""

    def test_record_request_snapshot_and_report(self):
        stats = qm.StepStats()
        stats.record_step(0.004)
        assert "request" not in stats.snapshot()      # nothing filed yet
        assert "per-request latency" not in stats.report()
        for ms in (1.0, 2.0, 4.0, 50.0):
            stats.record_request(ms / 1e3)
        s = stats.snapshot()
        assert s["request"]["count"] == 4
        assert s["request"]["p99_ms"] == pytest.approx(50.0, rel=0.5)
        assert s["request"]["p50_ms"] < s["request"]["p99_ms"]
        # per-step wall block is untouched by request recording
        assert s["steps"] == 1
        assert "per-request latency (4 requests)" in stats.report()

    def test_serving_kind_jsonl(self, tmp_path):
        path = str(tmp_path / "serving.jsonl")
        stats = qm.StepStats()
        stats.record_request(0.003)
        rec = dict(stats.snapshot())
        rec["serving"] = {"requests": 1, "rejected": 0}
        with qm.MetricsSink(path) as sink:
            sink.emit(rec, kind="serving")
            sink.emit_stats(stats)                    # default unchanged
        with open(path) as f:
            recs = [json.loads(l) for l in f if l.strip()]
        recs = [r for r in recs if r["kind"] != "meta"]  # sink header
        assert recs[0]["kind"] == "serving"
        assert recs[0]["request"]["count"] == 1
        assert recs[0]["serving"]["requests"] == 1
        assert recs[1]["kind"] == "step_stats"

    def test_collector_absorb_slot_semantics(self):
        inner = qm.Collector()
        inner.add(qm.HOT_ROWS, 5)
        inner.add(qm.COLD_ROWS, 3)
        inner.peak(qm.EXCH_CAP, 4)
        outer = qm.Collector()
        outer.add(qm.HOT_ROWS, 2)
        outer.peak(qm.EXCH_CAP, 9)
        outer.absorb(inner.counters())
        vec = np.asarray(outer.counters())
        assert vec[qm.HOT_ROWS] == 7                  # additive
        assert vec[qm.COLD_ROWS] == 3
        assert vec[qm.EXCH_CAP] == 9                  # max, not add

    def test_absorb_inside_jit_matches_eager(self):
        def fn():
            inner = qm.Collector()
            inner.add(qm.HOT_ROWS, jnp.int32(11))
            inner.peak(qm.EXCH_BUCKET_MAX, jnp.int32(6))
            outer = qm.Collector()
            outer.peak(qm.EXCH_BUCKET_MAX, jnp.int32(2))
            outer.absorb(inner.counters())
            return outer.counters()

        jitted = np.asarray(jax.jit(fn)())
        eager = np.asarray(fn())
        np.testing.assert_array_equal(jitted, eager)
        assert jitted[qm.HOT_ROWS] == 11
        assert jitted[qm.EXCH_BUCKET_MAX] == 6


class TestCrossHostCounterMerge:
    """``merge_counters=True``: the per-shard counter block folds over
    the host axis ON DEVICE (psum add slots, pmax max slots) so every
    host's ``last_counters`` is the global vector — the per-slot
    semantics must survive the device-side reduction, the rows/losses
    must stay bit-identical merge on/off, and the merged program must
    stay free of host-sync equations."""

    def test_lookup_merge_matches_host_fold(self, dist_setup, rng):
        (n, dim, classes, hosts, indptr, indices, feat, labels, g2h,
         mesh, info, comm) = dist_setup
        cap = 8
        off = qv.DistFeature.from_partition(feat, info, comm,
                                            exchange_cap=cap,
                                            collect_metrics=True)
        on = qv.DistFeature.from_partition(feat, info, comm,
                                           exchange_cap=cap,
                                           collect_metrics=True,
                                           merge_counters=True)
        per_shard = 96
        for dup_heavy in (True, False):       # narrow AND fallback
            if dup_heavy:
                pool = rng.integers(0, n, 12)
                ids = pool[rng.integers(0, pool.size,
                                        hosts * per_shard)]
            else:
                ids = rng.integers(0, n, hosts * per_shard)
            ids = jnp.asarray(ids.astype(np.int32))
            r_off = off[ids]
            r_on = on[ids]
            assert np.asarray(r_off).tobytes() == \
                np.asarray(r_on).tobytes()
            assert off.last_counters.shape == (hosts, qm.NUM_COUNTERS)
            assert on.last_counters.shape == (qm.NUM_COUNTERS,)
            # device psum/pmax == host add/max fold of the raw block
            np.testing.assert_array_equal(
                np.asarray(jax.device_get(on.last_counters))
                .astype(np.int64),
                qm.reduce_counters(off.last_counters))

    def test_metered_dist_losses_bit_identical_merge_on_off(
            self, dist_setup, rng):
        (n, dim, classes, hosts, indptr, indices, feat, labels, g2h,
         mesh, info, comm) = dist_setup
        from quiver_tpu.models import GraphSAGE
        dist = qv.DistFeature.from_partition(feat, info, comm)
        sizes, per_host = [3, 2], 8
        model = GraphSAGE(hidden_dim=16, out_dim=classes, num_layers=2,
                          dropout=0.0)
        tx = optax.adam(1e-2)
        ip = jnp.asarray(indptr.astype(np.int32))
        ix = jnp.asarray(indices)
        n_id, layers = sample_multihop(
            ip, ix, jnp.arange(per_host, dtype=jnp.int32), sizes,
            jax.random.key(0))
        state = init_state(model, tx,
                           masked_feature_gather(jnp.asarray(feat), n_id),
                           layers_to_adjs(layers, per_host, sizes),
                           jax.random.key(1))
        sharding = NamedSharding(mesh, P("host"))
        common = (dist._spmd_feat, info.global2host.astype(jnp.int32),
                  info.global2local, ip, ix)
        kwargs = dict(rows_per_host=dist._rows_per_host, donate=False,
                      exchange_cap=6, collect_metrics=True)
        off = build_dist_train_step(model, tx, sizes, per_host, mesh,
                                    **kwargs)
        on = build_dist_train_step(model, tx, sizes, per_host, mesh,
                                   merge_counters=True, **kwargs)
        seeds = jax.device_put(jnp.asarray(
            rng.choice(n, hosts * per_host,
                       replace=False).astype(np.int32)), sharding)
        y = jax.device_put(jnp.asarray(labels)[seeds], sharding)
        key = jax.random.key(77)
        _, l_off, c_off = off(state, *common, seeds, y, key)
        _, l_on, c_on = on(state, *common, seeds, y, key)
        assert np.asarray(l_off).tobytes() == np.asarray(l_on).tobytes()
        assert c_off.shape == (hosts, qm.NUM_COUNTERS)
        assert c_on.shape == (qm.NUM_COUNTERS,)
        np.testing.assert_array_equal(
            np.asarray(jax.device_get(c_on)).astype(np.int64),
            qm.reduce_counters(c_off))

    def test_merged_lookup_has_no_host_sync(self, dist_setup, rng):
        (n, dim, classes, hosts, indptr, indices, feat, labels, g2h,
         mesh, info, comm) = dist_setup
        from quiver_tpu.comm import build_dist_lookup_fn
        rows = 40
        fn = build_dist_lookup_fn(mesh, "host", rows_per_host=rows,
                                  batch_per_host=16, exchange_cap=4,
                                  collect_metrics=True,
                                  merge_counters=True)
        ids = jnp.asarray(rng.integers(0, n, hosts * 16, np.int32))
        spmd = jnp.asarray(
            rng.standard_normal((hosts * rows, dim)).astype(np.float32))
        args = (ids, info.global2host.astype(jnp.int32),
                info.global2local, spmd)
        assert host_sync_eqns(fn, args) == []

    def test_e2e_merge_shape_and_no_host_sync(self, rng):
        # abstract pins only (trace, no compile): the DP builder's
        # merged counters leave as ONE global [N] vector and the traced
        # program stays sync-free
        from quiver_tpu.models import GraphSAGE
        from quiver_tpu.parallel import build_e2e_train_step
        n, dim, classes = 200, 8, 4
        deg = rng.integers(1, 6, n)
        indptr = np.zeros(n + 1, np.int64)
        np.cumsum(deg, out=indptr[1:])
        indices = rng.integers(0, n, int(indptr[-1]), dtype=np.int32)
        feat = rng.standard_normal((n, dim)).astype(np.float32)
        labels = rng.integers(0, classes, n).astype(np.int32)
        sizes, per_dev = [3, 2], 4
        ndev = len(jax.devices())
        mesh = Mesh(np.array(jax.devices()), axis_names=("data",))
        model = GraphSAGE(hidden_dim=8, out_dim=classes, num_layers=2,
                          dropout=0.0)
        tx = optax.adam(1e-2)
        ip = jnp.asarray(indptr.astype(np.int32))
        ix = jnp.asarray(indices)
        n_id, layers = sample_multihop(
            ip, ix, jnp.arange(per_dev, dtype=jnp.int32), sizes,
            jax.random.key(0))
        state = init_state(model, tx,
                           masked_feature_gather(jnp.asarray(feat), n_id),
                           layers_to_adjs(layers, per_dev, sizes),
                           jax.random.key(1))
        step = build_e2e_train_step(model, tx, sizes, per_dev, mesh,
                                    donate=False, collect_metrics=True,
                                    merge_counters=True)
        seeds = jnp.asarray(
            rng.choice(n, ndev * per_dev, replace=False).astype(np.int32))
        args = (state, jnp.asarray(feat), None, ip, ix, seeds,
                jnp.asarray(labels)[seeds], jax.random.key(2))
        shapes = jax.eval_shape(step, *args)
        assert shapes[2].shape == (qm.NUM_COUNTERS,)
        assert host_sync_eqns(step, args) == []
        with pytest.raises(ValueError, match="merge_counters"):
            build_e2e_train_step(model, tx, sizes, per_dev, mesh,
                                 merge_counters=True)

    def test_merge_requires_collect(self, dist_setup):
        (n, dim, classes, hosts, indptr, indices, feat, labels, g2h,
         mesh, info, comm) = dist_setup
        from quiver_tpu.comm import build_dist_lookup_fn
        with pytest.raises(ValueError, match="merge_counters"):
            build_dist_lookup_fn(mesh, "host", 10, 8,
                                 merge_counters=True)
        with pytest.raises(ValueError, match="merge_counters"):
            qv.DistFeature.from_partition(feat, info, comm,
                                          merge_counters=True)
