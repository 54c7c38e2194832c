"""Telemetry hub: series rings, change-point detectors, advisory
re-planning, sink rotation, the unified report, and the flight
recorder.

The contracts under test, in order of importance:

1. **The acceptance loop** — an injected regression (hot-tier capacity
   halved mid-run) produces an ``anomaly`` record within the detector
   window AND an ``advice`` record whose recommended hot capacity
   exceeds the degraded one; with telemetry fully enabled the lookups
   stay bit-identical to telemetry-off and the traced program has zero
   host-sync equations (``_traffic.host_sync_eqns``).
2. **Bounded memory** — series rings wrap at capacity; the size-bounded
   ``MetricsSink`` rolls over to ``<path>.1`` and readers consume the
   seam in order.
3. **Cross-process merge** — per-host JSONL ``step_stats`` records fold
   into the hub with the add/max slot semantics
   (``metrics.merge_named_counters`` / ``ingest_jsonl``).
4. **Advisory only** — ``replan()`` emits records; nothing is actuated
   (there is no actuator to call — the advisor returns plain dicts).
"""

import json
import os
import signal
import subprocess
import sys
import threading
import time

import numpy as np
import jax.numpy as jnp
import pytest

import quiver_tpu as qv
from quiver_tpu import metrics as qm
from quiver_tpu import telemetry as qt
from quiver_tpu import tracing

from _traffic import host_sync_eqns

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def vec(**slots):
    v = np.zeros(qm.NUM_COUNTERS, np.int64)
    names = {name: slot for slot, name in qm.SLOT_NAMES.items()}
    for k, val in slots.items():
        v[names[k]] = val
    return v


class TestSeriesRing:
    def test_append_read_chronological(self):
        s = qt.SeriesRing(capacity=8)
        for i in range(5):
            s.append(i)
        assert len(s) == 5 and not s.wrapped
        assert s.values().tolist() == [0, 1, 2, 3, 4]
        assert s.last() == 4.0

    def test_wrap_keeps_most_recent(self):
        s = qt.SeriesRing(capacity=4)
        for i in range(10):
            s.append(i)
        assert len(s) == 4 and s.wrapped and s.total == 10
        assert s.values().tolist() == [6, 7, 8, 9]

    def test_window_stats_and_ewma(self):
        s = qt.SeriesRing(capacity=16)
        for v in [1.0] * 8 + [3.0] * 4:
            s.append(v)
        w = s.window_stats(4)
        assert w["mean"] == 3.0 and w["p50"] == 3.0 and w["n"] == 4
        assert 1.0 < s.ewma() <= 3.0
        assert qt.SeriesRing(4).window_stats(4) is None

    def test_validation(self):
        with pytest.raises(ValueError):
            qt.SeriesRing(capacity=1)


class TestDetectors:
    def test_mean_shift_fires_on_drop_and_rearms(self):
        d = qt.MeanShiftDetector(window=4, direction="down")
        hits = [d.update(v) for v in [0.8] * 4 + [0.4] * 4]
        fired = [h for h in hits if h]
        assert len(fired) == 1
        assert fired[0]["baseline"] == pytest.approx(0.8)
        assert fired[0]["shift"] == pytest.approx(-0.4)
        # re-armed: the new 0.4 regime alone must not refire
        assert all(d.update(0.4) is None for _ in range(8))

    def test_mean_shift_direction_filter(self):
        up = qt.MeanShiftDetector(window=4, direction="up")
        assert all(up.update(v) is None
                   for v in [0.8] * 4 + [0.4] * 8)
        both = qt.MeanShiftDetector(window=4, direction="both")
        assert any(both.update(v) for v in [0.8] * 4 + [0.4] * 4)

    def test_mean_shift_small_noise_does_not_fire(self):
        d = qt.MeanShiftDetector(window=4, direction="down")
        rng = np.random.default_rng(0)
        assert all(d.update(0.7 + 0.005 * rng.standard_normal())
                   is None for _ in range(64))

    def test_page_hinkley_catches_slow_drift(self):
        d = qt.PageHinkleyDetector(delta=0.01, threshold=0.5)
        hits = [d.update(6.0 + 0.05 * i) for i in range(100)]
        assert any(hits)

    def test_spike(self):
        d = qt.SpikeDetector()
        assert d.update(0.0) is None
        hit = d.update(2.0)
        assert hit and hit["value"] == 2.0
        assert d.update(0.0) is None

    def test_unknown_detector_rejected(self):
        with pytest.raises(ValueError, match="unknown detector"):
            qt.TelemetryHub(watches=()).watch("x", "nope")


class _Spy:
    """Counter-vector stand-in that records host materialization —
    pins the fold's laziness (the newest vector must never be fetched
    on the recording path)."""

    def __init__(self, v):
        self.v = v
        self.fetched = False

    def __array__(self, dtype=None, copy=None):
        self.fetched = True
        return np.asarray(self.v, dtype=dtype)


class TestHubCounters:
    def test_per_step_derived_series(self):
        hub = qt.TelemetryHub(capacity=32, window=4, fold_every=4)
        for hot, cold in ((30, 10), (20, 20), (10, 30)):
            hub.observe_counters(vec(hot_rows=hot, cold_rows=cold))
        hub.flush()
        assert hub.series["hot_hit_rate"].values().tolist() == \
            pytest.approx([0.75, 0.5, 0.25])
        c = hub.counters()
        named = {qm.SLOT_NAMES[i]: int(v) for i, v in enumerate(c)}
        assert named["hot_rows"] == 60 and named["cold_rows"] == 60

    def test_max_slot_semantics_in_totals(self):
        hub = qt.TelemetryHub(watches=())
        hub.observe_counters(vec(exchange_bucket_max=7, exchange_cap=8,
                                 exchange_calls=1))
        hub.observe_counters(vec(exchange_bucket_max=5, exchange_cap=8,
                                 exchange_calls=1))
        c = hub.counters()
        assert c[qm.EXCH_BUCKET_MAX] == 7          # max, not 12
        assert c[qm.EXCH_CALLS] == 2               # add
        assert hub.series["exchange_bucket_max"].values().tolist() == \
            [7.0, 5.0]

    def test_lazy_fold_never_fetches_newest(self):
        hub = qt.TelemetryHub(fold_every=2, watches=())
        spies = [_Spy(vec(hot_rows=1)) for _ in range(4)]
        for s in spies:
            hub.observe_counters(s)
        # fold_every=2: older vectors folded, the NEWEST still pending
        assert not spies[-1].fetched
        assert any(s.fetched for s in spies[:-1])
        hub.flush()
        assert all(s.fetched for s in spies)

    def test_recompile_watch_series(self):
        class Fn:
            def __init__(self):
                self.n = 1

            def _cache_size(self):
                return self.n

        fn = Fn()
        hub = qt.TelemetryHub(fold_every=1)
        hub.watch_compiles(fn)
        hub.observe_counters(vec(hot_rows=1))
        hub.flush()
        assert hub.series["recompiles"].values().tolist() == [0.0]
        fn.n += 1                                   # a recompile
        hub.observe_counters(vec(hot_rows=1))
        hub.flush()
        assert hub.series["recompiles"].last() == 1.0
        # the default spike watch turned it into an anomaly
        assert any(a["series"] == "recompiles" for a in hub.anomalies)

    def test_shard_stack_folds(self):
        hub = qt.TelemetryHub(watches=())
        stack = np.stack([vec(hot_rows=3, exchange_bucket_max=4),
                          vec(hot_rows=5, exchange_bucket_max=9)])
        hub.observe_counters(stack)
        hub.flush()
        c = hub.counters()
        assert c[qm.HOT_ROWS] == 8 and c[qm.EXCH_BUCKET_MAX] == 9


class TestCrossProcessMerge:
    def test_merge_named_counters_slot_semantics(self):
        a = {"hot_rows": 3, "exchange_bucket_max": 7}
        b = {"hot_rows": 4, "exchange_bucket_max": 5, "cold_rows": 2}
        m = qm.merge_named_counters(a, b)
        assert m["hot_rows"] == 7
        assert m["exchange_bucket_max"] == 7       # max slot
        assert m["cold_rows"] == 2

    def test_ingest_jsonl_diffs_cumulative_counters(self, tmp_path):
        p = tmp_path / "host0.jsonl"
        recs = [
            {"kind": "step_stats",
             "counters": {"hot_rows": 30, "cold_rows": 10,
                          "exchange_bucket_max": 5}},
            {"kind": "step_stats",
             "counters": {"hot_rows": 50, "cold_rows": 30,
                          "exchange_bucket_max": 7}},
            {"kind": "bench", "metric": "x", "value": 1.0},
        ]
        p.write_text("\n".join(json.dumps(r) for r in recs) + "\n")
        hub = qt.TelemetryHub(watches=())
        assert hub.ingest_jsonl(p) == 2
        c = hub.counters()
        assert c[qm.HOT_ROWS] == 50                # 30 + delta 20
        assert c[qm.COLD_ROWS] == 30
        assert c[qm.EXCH_BUCKET_MAX] == 7          # newest peak
        assert hub.series["hot_hit_rate"].values().tolist() == \
            pytest.approx([0.75, 0.5])

    def test_two_host_sinks_merge(self, tmp_path):
        hub = qt.TelemetryHub(watches=())
        for host, (hot, peak) in enumerate(((30, 5), (10, 9))):
            p = tmp_path / f"host{host}.jsonl"
            p.write_text(json.dumps(
                {"kind": "step_stats",
                 "counters": {"hot_rows": hot, "cold_rows": 10,
                              "exchange_bucket_max": peak}}) + "\n")
            hub.ingest_jsonl(p)
        c = hub.counters()
        assert c[qm.HOT_ROWS] == 40 and c[qm.EXCH_BUCKET_MAX] == 9

    def test_ingest_slo_and_serving_snapshots(self):
        hub = qt.TelemetryHub(watches=())
        hub.ingest_slo({"windows": {"short": {"burn_rate": 2.0},
                                    "long": {"burn_rate": 1.1}},
                        "budget_remaining": 0.4})
        hub.ingest_serving({"request": {"p99_ms": 42.0},
                            "serving": {"queue_depth": 3,
                                        "shed_level": 1,
                                        "mean_batch_fill": 12.5}})
        assert hub.series["slo_burn_short"].last() == 2.0
        assert hub.series["serve_request_p99_ms"].last() == 42.0
        assert hub.series["serve_batch_fill"].last() == 12.5


class TestSinkRotation:
    def test_rollover_and_seam_read(self, tmp_path):
        path = str(tmp_path / "m.jsonl")
        sink = qm.MetricsSink(path, max_bytes=400)
        for i in range(20):
            sink.emit({"i": i, "pad": "x" * 40}, kind="record")
        sink.close()
        assert os.path.exists(path + ".1"), "never rolled over"
        assert os.path.getsize(path) < 22 * 60, "rotation did not bound"
        recs = qm.read_jsonl(path)
        assert 0 < len(recs) < 22           # one backup level: bounded
        idx = [r["i"] for r in recs if r["kind"] == "record"]
        assert idx == sorted(idx)           # seam read is chronological
        assert idx[-1] == 19                # newest record never lost

    def test_unbounded_sink_unchanged(self, tmp_path):
        path = str(tmp_path / "m.jsonl")
        with qm.MetricsSink(path) as sink:
            for i in range(5):
                sink.emit({"i": i})
        assert not os.path.exists(path + ".1")
        assert [r["i"] for r in qm.read_jsonl(path)
                if r["kind"] == "record"] == list(range(5))


def _degraded_run(tmp_path, rng):
    """The injected-regression harness: degree-uniform traffic against
    a full-capacity store, then the SAME traffic against a store with
    the hot tier HALVED — observed counters only, nothing synthetic."""
    n, dim, batch, cap = 2048, 8, 512, 512
    feat = rng.standard_normal((n, dim)).astype(np.float32)
    full = qv.Feature(device_cache_size=cap * dim * 4)
    full.from_cpu_tensor(feat)
    halved = qv.Feature(device_cache_size=(cap // 2) * dim * 4)
    halved.from_cpu_tensor(feat)
    assert full.cache_rows == cap and halved.cache_rows == cap // 2
    sink = qm.MetricsSink(str(tmp_path / "hub.jsonl"))
    degree = np.ones(n)               # uniform: hit rate == rows/n
    hub = qt.TelemetryHub(
        capacity=64, window=4, sink=sink,
        plan=qt.PlanContext(hot_capacity=halved.cache_rows,
                            total_rows=n, degree=degree,
                            expected_hit_rate=cap / n))
    stores = [full] * 8 + [halved] * 8
    rows_pairs = []
    for i, store in enumerate(stores):
        ids = jnp.asarray(rng.integers(0, n, batch, dtype=np.int32))
        host = jnp.asarray(store.host_part)
        rows, counters = store._lookup_tiered(
            store.device_part, host, ids, store.feature_order,
            False, True)
        hub.observe_counters(counters)
        # bit-identity: the metered lookup vs the telemetry-off one
        plain = store._lookup_tiered(store.device_part, host, ids,
                                     store.feature_order)
        rows_pairs.append((np.asarray(rows), np.asarray(plain)))
    hub.flush()
    return hub, sink, rows_pairs, full, halved, cap, n, dim, batch


class TestInjectedRegression:
    """The PR's acceptance loop: halve the hot tier mid-run, observe
    the collapse, advise the fix — without actuating anything."""

    def test_anomaly_and_advice(self, tmp_path, rng):
        (hub, sink, rows_pairs, full, halved, cap, n, dim,
         batch) = _degraded_run(tmp_path, rng)
        # (1) the regime shift raised an anomaly WITHIN the detector
        # window of the injection (step 9 onward; window=4 needs 4
        # degraded points, so it must land by step 12)
        hits = [a for a in hub.anomalies
                if a["series"] == "hot_hit_rate"]
        assert hits, f"no hot_hit_rate anomaly; got {list(hub.anomalies)}"
        assert hits[0]["detector"] == "mean_shift"
        assert 9 <= hits[0]["step"] <= 12
        assert hits[0]["shift"] < 0
        # (2) the advisor recommends MORE capacity than the degraded
        # tier actually has — sized from the observed distribution
        advice = hub.replan()
        rec = {a["key"]: a for a in advice}["hot_capacity"]
        assert rec["current"] == halved.cache_rows
        assert rec["recommended"] > halved.cache_rows
        # uniform degrees: the planned rate needs exactly cap rows
        assert rec["recommended"] == cap
        assert rec["observed"]["hot_hit_rate"] < cap / n
        # (3) both records reached the sink as their documented kinds
        sink.close()
        kinds = [r["kind"] for r in qm.read_jsonl(tmp_path / "hub.jsonl")]
        assert "anomaly" in kinds and "advice" in kinds
        # (4) telemetry never perturbed the data path
        for metered, plain in rows_pairs:
            assert metered.tobytes() == plain.tobytes()

    def test_no_host_sync_with_telemetry_enabled(self, tmp_path, rng):
        (hub, sink, _rows, full, halved, cap, n, dim,
         batch) = _degraded_run(tmp_path, rng)
        host = jnp.asarray(full.host_part)
        ids = jnp.asarray(rng.integers(0, n, batch, dtype=np.int32))
        # the metered lookup's traced program: zero host-callback
        # equations — the hub's ingestion is host-side and lazy
        fn = lambda i: full._lookup_tiered_raw(
            full.device_part, host, i, full.feature_order, False, True)
        assert host_sync_eqns(fn, (ids,)) == []
        sink.close()


class TestAdvisor:
    def test_rows_for_hit_rate_inverts_degree_mass(self):
        deg = np.array([4.0, 3.0, 2.0, 1.0])
        assert qt.rows_for_hit_rate(deg, 0.4) == 1
        assert qt.rows_for_hit_rate(deg, 0.7) == 2
        assert qt.rows_for_hit_rate(deg, 1.0) == 4
        assert qt.rows_for_hit_rate(np.zeros(3), 0.5) == 0

    def _hub(self, **plan):
        return qt.TelemetryHub(window=4, watches=(),
                               plan=qt.PlanContext(**plan))

    def test_exchange_cap_undersized(self):
        hub = self._hub(exchange_cap=512)
        for _ in range(8):
            hub.observe_counters(vec(exchange_calls=1,
                                     exchange_fallback=1,
                                     exchange_bucket_max=450,
                                     exchange_cap=512))
        advice = hub.replan()
        rec = {a["key"]: a for a in advice}["exchange_cap"]
        from quiver_tpu.comm import cap_for_expected_load
        # fallbacks observed: the planner formula on the observed p95
        # peak, floored at one slack step above the current cap (an
        # overflowed table understates its own peaks)
        assert rec["recommended"] == max(cap_for_expected_load(450.0),
                                         cap_for_expected_load(512.0))
        assert rec["recommended"] > 512
        assert "headroom" in rec["reason"]
        assert rec["observed"]["cap_headroom"] == pytest.approx(
            1 - 450 / 512, abs=1e-4)

    def test_exchange_cap_overflowing_never_shrinks(self):
        # fallbacks observed + LOW recorded peaks (an overflowed
        # truncated table understates the real load): the advice must
        # GROW past the current cap, never shrink an overflowing
        # exchange
        hub = self._hub(exchange_cap=512)
        for _ in range(8):
            hub.observe_counters(vec(exchange_calls=1,
                                     exchange_fallback=1,
                                     exchange_bucket_max=300,
                                     exchange_cap=512))
        rec = {a["key"]: a for a in hub.replan()}["exchange_cap"]
        assert rec["recommended"] > 512

    def test_max_wait_grow_capped_below_current_is_silent(self):
        # latency headroom + empty batches, but target/4 < current
        # wait: a "grow" branch that would shrink must stay silent
        hub = self._hub(batch_cap=64, max_wait_ms=20.0,
                        target_p99_ms=50.0)
        for _ in range(8):
            hub.observe("serve_batch_fill", 4)
            hub.observe("serve_request_p99_ms", 20.0)
        assert all(a["key"] != "max_wait_ms" for a in hub.replan())

    def test_exchange_cap_oversized_shrinks(self):
        hub = self._hub(exchange_cap=512)
        for _ in range(8):
            hub.observe_counters(vec(exchange_calls=1,
                                     exchange_bucket_max=40,
                                     exchange_cap=512))
        rec = {a["key"]: a for a in hub.replan()}["exchange_cap"]
        assert rec["recommended"] < 512

    def test_exchange_cap_well_sized_silent(self):
        hub = self._hub(exchange_cap=512)
        for _ in range(8):
            # cap_for_expected_load(390) ~ 547... use a load whose
            # recommendation lands within 10% of the current cap
            hub.observe_counters(vec(exchange_calls=1,
                                     exchange_bucket_max=380,
                                     exchange_cap=512))
        assert all(a["key"] != "exchange_cap" for a in hub.replan())

    def test_dedup_budget_overflow(self):
        hub = self._hub(dedup_budget=256)
        for _ in range(8):
            hub.observe_counters(vec(dedup_calls=1, dedup_total=2048,
                                     dedup_unique=500, dedup_overflow=1))
        rec = {a["key"]: a for a in hub.replan()}["dedup_budget"]
        assert rec["recommended"] > 500
        assert "overflowing" in rec["reason"]

    def test_serving_knobs(self):
        hub = self._hub(batch_cap=32, max_wait_ms=2.0,
                        target_p99_ms=50.0)
        for _ in range(8):
            hub.observe("serve_batch_fill", 32)
            hub.observe("serve_request_p99_ms", 80.0)
        recs = {a["key"]: a for a in hub.replan()}
        assert recs["batch_cap"]["recommended"] == 64
        assert recs["max_wait_ms"]["recommended"] == pytest.approx(1.0)

    def test_no_plan_no_advice(self):
        hub = qt.TelemetryHub(watches=())
        hub.observe_counters(vec(hot_rows=1))
        assert hub.replan() == []


class TestUnifiedReport:
    def test_sections_and_tracer_status(self):
        qm.register_report_section("_test_section", lambda: "HELLO-XYZ")
        try:
            text = qm.report()
            assert "HELLO-XYZ" in text
            assert "tracing:" in text
        finally:
            qm.unregister_report_section("_test_section")
        assert "HELLO-XYZ" not in qm.report()

    def test_failing_section_does_not_kill_report(self):
        qm.register_report_section(
            "_boom", lambda: (_ for _ in ()).throw(RuntimeError("x")))
        try:
            assert "report failed" in qm.report()
        finally:
            qm.unregister_report_section("_boom")

    def test_hub_install_report(self):
        hub = qt.TelemetryHub(watches=())
        hub.observe("x", 1.0)
        hub.install_report("_test_hub")
        try:
            assert "telemetry hub" in qm.report()
        finally:
            hub.uninstall_report()
        assert "telemetry hub" not in qm.report()

    def test_stub_server_feeds_hub_and_registers(self):
        # a stub engine: the server's hub plumbing and report
        # registration without compiling anything
        from quiver_tpu.serving import MicroBatchServer, ServeConfig

        class StubEngine:
            batch_cap = 4
            variants = [[2, 1]]
            jitted_fns = ()
            collect_metrics = False
            last_counters = None
            last_stage_s = (0.0, 0.0)

            def run(self, seeds, variant):
                return np.zeros((4, 3), np.float32)

        hub = qt.TelemetryHub(watches=())
        server = MicroBatchServer(StubEngine(), ServeConfig(
            max_wait_ms=1.0), hub=hub)
        try:
            assert "serving:" in qm.report()
            for f in [server.submit(i) for i in range(3)]:
                assert f.result(timeout=30).shape == (3,)
            assert hub.series["serve_batch_fill"].total >= 1
            assert hub.series["serve_batch_ms"].total >= 1
        finally:
            server.close()
        assert "serving:" not in qm.report()


class TestPrefetchObserveInto:
    def test_interval_deltas(self):
        pf = qv.ColdPrefetcher.__new__(qv.ColdPrefetcher)
        pf._counters = np.array([30, 10, 100], np.int64)
        pf._published, pf._dropped = 4, 1
        pf._truncated = 0
        pf._io_total = np.zeros(6, np.int64)
        pf._hub_last = np.zeros(7, np.int64)
        pf._hub_t = None
        pf._lock = threading.Lock()
        hub = qt.TelemetryHub(watches=())
        d = pf.observe_into(hub)
        assert d == {"hit_rows": 30, "sync_rows": 10,
                     "staged_rows": 100, "published": 4, "dropped": 1,
                     "truncated_rows": 0,
                     "staging_worker_restarts": 0}
        assert hub.series["prefetch_hit_rate"].last() == \
            pytest.approx(0.75)
        assert hub.series["prefetch_drop_rate"].last() == \
            pytest.approx(0.25)
        # the first call armed the interval clock: no rows/s point yet
        assert "cold_staged_rows_per_s" not in hub.series
        pf._counters = np.array([40, 40, 150], np.int64)
        pf._truncated = 7
        pf._io_total[5] = 2        # two staging-worker restarts since
        d = pf.observe_into(hub)                   # the DELTA, not the
        assert d["hit_rows"] == 10                 # lifetime total
        assert d["truncated_rows"] == 7
        assert d["staging_worker_restarts"] == 2
        assert d["staged_rows_per_s"] > 0          # 50 rows / interval
        assert hub.series["prefetch_hit_rate"].last() == \
            pytest.approx(10 / 40)
        assert hub.series["cold_staged_rows_per_s"].last() == \
            pytest.approx(d["staged_rows_per_s"])
        assert hub.series["prefetch_truncated_rows"].last() == 7
        assert hub.series["staging_worker_restarts"].last() == 2


class TestFlightRecorder:
    def _hub(self):
        hub = qt.TelemetryHub(watches=())
        hub.observe("hot_hit_rate", 0.5)
        hub.observe_counters(vec(hot_rows=10, cold_rows=10))
        hub.advice["hot_capacity"] = {"key": "hot_capacity",
                                      "current": 1, "recommended": 2,
                                      "reason": "r"}
        return hub

    def test_dump_payload(self, tmp_path):
        prev_cap = tracing.get_tracer().capacity
        tracing.enable(capacity=64)
        try:
            tracing.record("test.span", 0.0, 0.5, None, {"k": 1})
            fr = qv.FlightRecorder(path=str(tmp_path / "pm.json"),
                                   hub=self._hub())
            out = fr.dump(reason="unit-test")
            doc = json.load(open(out))
        finally:
            # restore the GLOBAL tracer's ring size — a shrunken ring
            # would silently drop spans in later test files
            tracing.enable(capacity=prev_cap)
            tracing.disable()
            tracing.clear()
        assert doc["reason"] == "unit-test"
        assert any(s["name"] == "test.span" for s in doc["spans"])
        assert doc["series"]["hot_hit_rate"] == [0.5, 0.5]
        assert doc["counters"]["hot_rows"] == 10
        assert doc["advice"]["hot_capacity"]["recommended"] == 2

    def test_signal_dump_chains_previous_handler(self, tmp_path):
        calls = []
        prev = signal.signal(signal.SIGUSR1,
                             lambda s, f: calls.append(s))
        fr = qv.FlightRecorder(path=str(tmp_path / "pm.json"),
                               hub=self._hub())
        try:
            fr.install(signals=(signal.SIGUSR1,), excepthook=False)
            os.kill(os.getpid(), signal.SIGUSR1)
            deadline = time.time() + 5
            while not calls and time.time() < deadline:
                time.sleep(0.01)           # handlers run between ops
            assert calls == [signal.SIGUSR1], "previous handler lost"
            assert os.path.exists(tmp_path / "pm.json")
            doc = json.load(open(tmp_path / "pm.json"))
            assert "SIGUSR1" in doc["reason"]
        finally:
            fr.uninstall()
            signal.signal(signal.SIGUSR1, prev)

    def test_excepthook_dump_and_chain(self, tmp_path):
        seen = []
        old = sys.excepthook
        sys.excepthook = lambda *a: seen.append(a[0])
        fr = qv.FlightRecorder(path=str(tmp_path / "pm.json"))
        try:
            fr.install(signals=(), excepthook=True)
            sys.excepthook(ValueError, ValueError("boom"), None)
            assert seen == [ValueError]
            doc = json.load(open(tmp_path / "pm.json"))
            assert "boom" in doc["reason"]
        finally:
            fr.uninstall()
            sys.excepthook = old


class TestQtTop:
    SCRIPT = os.path.join(REPO, "scripts", "qt_top.py")

    def _run(self, *args):
        return subprocess.run(
            [sys.executable, self.SCRIPT, "--once", "--no-color", *args],
            capture_output=True, text=True, timeout=60)

    def test_renders_series_anomalies_advice(self, tmp_path):
        p = tmp_path / "m.jsonl"
        recs = [{"kind": "step_stats", "wall": {"p50_ms": 40.0 + i},
                 "derived": {"hot_hit_rate": 0.8 - 0.02 * i}}
                for i in range(10)]
        recs += [
            {"kind": "anomaly", "series": "hot_hit_rate",
             "detector": "mean_shift", "baseline": 0.8, "value": 0.4,
             "step": 9},
            {"kind": "advice", "key": "hot_capacity", "current": 256,
             "recommended": 512, "reason": "shortfall"},
            {"kind": "regress", "metric": "seps", "platform": "cpu",
             "value": 80.0, "best": 100.0, "ratio": 0.8,
             "regressed": True},
        ]
        recs += [
            {"kind": "slo", "windows": {"short": {"burn_rate": 0.5 * k},
                                        "long": {"burn_rate": 0.4 * k}},
             "budget_remaining": 0.1, "shedding": k == 4}
            for k in (1, 2, 4)
        ]
        p.write_text("\n".join(json.dumps(r) for r in recs) + "\n")
        out = self._run("--jsonl", str(p))
        assert out.returncode == 0, out.stderr
        assert "hot_hit_rate" in out.stdout
        assert "ANOMALY [mean_shift]" in out.stdout
        assert "advice [hot_capacity]: 256 -> 512" in out.stdout
        assert "REGRESSED" in out.stdout
        assert "SHEDDING" in out.stdout
        # EVERY slo record contributes a burn-rate point (the trend,
        # not just the newest value)
        assert "slo_burn_short" in out.stdout and "(n=3" in out.stdout

    def test_reads_across_rollover_seam(self, tmp_path):
        p = tmp_path / "m.jsonl"
        old = {"kind": "step_stats", "derived": {"hot_hit_rate": 0.9}}
        new = {"kind": "step_stats", "derived": {"hot_hit_rate": 0.1}}
        (tmp_path / "m.jsonl.1").write_text(json.dumps(old) + "\n")
        p.write_text(json.dumps(new) + "\n")
        out = self._run("--jsonl", str(p))
        assert "(2 records" in out.stdout
        assert "n=2" in out.stdout

    def test_empty_file_is_calm(self, tmp_path):
        out = self._run("--jsonl", str(tmp_path / "nope.jsonl"))
        assert out.returncode == 0
        assert "no records yet" in out.stdout

    def test_tenant_panel_and_capacity_line(self, tmp_path):
        # the qt-capacity panels: latest tenant record wins per class
        # (rows ordered by priority, highest first), replay p99 series
        # appears, and the newest capacity record renders its verdict
        p = tmp_path / "m.jsonl"
        recs = [
            {"kind": "tenant", "tenant": "interactive", "priority": 2,
             "completed": 10, "shed": 0, "rejected": 0, "displaced": 0,
             "deadline_expired": 0, "latency": {"p99_ms": 12.0},
             "slo": {"windows": {"short": {"burn_rate": 0.4}}}},
            {"kind": "tenant", "tenant": "interactive", "priority": 2,
             "completed": 25, "shed": 0, "rejected": 0, "displaced": 0,
             "deadline_expired": 0, "latency": {"p99_ms": 11.0},
             "slo": {"windows": {"short": {"burn_rate": 0.6}}}},
            {"kind": "tenant", "tenant": "best_effort", "priority": 0,
             "completed": 5, "shed": 3, "rejected": 2, "displaced": 1,
             "deadline_expired": 0, "latency": {"p99_ms": 80.0}},
            {"kind": "replay", "tenant": "interactive",
             "latency": {"p99_ms": 14.0}},
            {"kind": "capacity", "replicas": 1,
             "predicted_rps": 2100.0, "budget_p99_ms": 100.0,
             "fill": 12.4, "batch_cap": 16,
             "verdict": {"within_tol": True, "measured_rps": 1980.0,
                         "ratio": 1.06}},
        ]
        p.write_text("\n".join(json.dumps(r) for r in recs) + "\n")
        out = self._run("--jsonl", str(p))
        assert out.returncode == 0, out.stderr
        # latest-per-tenant dedup: the newest interactive counters
        assert "done 25" in out.stdout and "done 10" not in out.stdout
        assert "shed 3 (rej 2 disp 1 ddl 0)" in out.stdout
        # burn sparkline series saw BOTH records (trend, not latest)
        assert "tenant_burn:interactive" in out.stdout
        assert "replay_p99:interactive" in out.stdout
        # priority order: interactive's row above best_effort's
        lines = out.stdout.splitlines()
        rows = [i for i, l in enumerate(lines)
                if l.lstrip().startswith("tenant ")]
        assert "interactive" in lines[rows[0]]
        assert "best_effort" in lines[rows[1]]
        assert "capacity: 1 replica(s) sustain 2100 req/s" in out.stdout
        assert "WITHIN TOL" in out.stdout


class TestBenchRegressEmission:
    SCRIPT = os.path.join(REPO, "scripts", "bench_regress.py")

    def _bench_file(self, tmp_path, n, value):
        rec = {"metric": "seps", "value": value, "unit": "edges/s"}
        (tmp_path / f"BENCH_r{n:02d}.json").write_text(json.dumps(
            {"n": n, "cmd": "x", "rc": 0, "tail": json.dumps(rec)}))

    def test_regress_kind_emitted_and_exit_code_kept(self, tmp_path):
        self._bench_file(tmp_path, 1, 100.0)
        self._bench_file(tmp_path, 2, 80.0)        # 20% drop
        out_path = tmp_path / "verdicts.jsonl"
        p = subprocess.run(
            [sys.executable, self.SCRIPT, "--bench-dir", str(tmp_path),
             "--emit-jsonl", str(out_path)],
            capture_output=True, text=True, timeout=60)
        assert p.returncode == 1                   # contract unchanged
        recs = [json.loads(l) for l in out_path.read_text().splitlines()]
        assert all(r["kind"] == "regress" for r in recs)
        v = {(r["metric"], r["platform"]): r for r in recs}[
            ("seps", "default")]
        assert v["regressed"] is True
        assert v["value"] == 80.0 and v["best"] == 100.0
        assert v["ratio"] == pytest.approx(0.8)

    def test_reanchor_escape_hatch(self, tmp_path):
        # the box-drift escape hatch: a 20% drop fails the gate, but
        # --reanchor restarts that ONE metric's trajectory — visible
        # (REANCHOR line, `reanchored` + box fingerprint in the
        # verdict record), never silent, other metrics still judged
        self._bench_file(tmp_path, 1, 100.0)
        self._bench_file(tmp_path, 2, 80.0)
        out_path = tmp_path / "verdicts.jsonl"
        p = subprocess.run(
            [sys.executable, self.SCRIPT, "--bench-dir", str(tmp_path),
             "--reanchor", "seps", "--emit-jsonl", str(out_path)],
            capture_output=True, text=True, timeout=60)
        assert p.returncode == 0, p.stdout
        assert "REANCHOR seps" in p.stdout
        recs = [json.loads(l) for l in out_path.read_text().splitlines()]
        v = {(r["metric"], r["platform"]): r for r in recs}[
            ("seps", "default")]
        assert v["reanchored"] is True and not v["regressed"]
        assert v["box"]                      # the fingerprint note
        assert v["best"] == 100.0            # prior kept for the record

    def test_committed_round_reanchor_field(self, tmp_path):
        # the durable reanchor: a round record carrying
        # "reanchor": [...] restarts those metrics' history at that
        # round for EVERY later invocation — no flag needed — while
        # metrics not named are still judged against the full history
        self._bench_file(tmp_path, 1, 100.0)
        rec = {"metric": "seps", "value": 80.0, "unit": "edges/s"}
        (tmp_path / "BENCH_r02.json").write_text(json.dumps(
            {"n": 2, "cmd": "x", "rc": 0, "reanchor": ["seps"],
             "tail": json.dumps(rec)}))
        out_path = tmp_path / "verdicts.jsonl"
        p = subprocess.run(
            [sys.executable, self.SCRIPT, "--bench-dir", str(tmp_path),
             "--emit-jsonl", str(out_path)],
            capture_output=True, text=True, timeout=60)
        assert p.returncode == 0, p.stdout
        recs = [json.loads(l) for l in out_path.read_text().splitlines()]
        v = {(r["metric"], r["platform"]): r for r in recs}[
            ("seps", "default")]
        assert not v["regressed"]
        assert v["best"] is None             # pre-restart history gone
        assert v["value"] == 80.0
        # a LATER drop against the restarted anchor still fails
        self._bench_file(tmp_path, 3, 60.0)  # 25% below the new anchor
        p = subprocess.run(
            [sys.executable, self.SCRIPT, "--bench-dir", str(tmp_path)],
            capture_output=True, text=True, timeout=60)
        assert p.returncode == 1
        assert "80" in p.stdout              # judged vs the new anchor

    def test_clean_trajectory_emits_pass_verdict(self, tmp_path):
        self._bench_file(tmp_path, 1, 100.0)
        self._bench_file(tmp_path, 2, 101.0)
        out_path = tmp_path / "verdicts.jsonl"
        p = subprocess.run(
            [sys.executable, self.SCRIPT, "--bench-dir", str(tmp_path),
             "--emit-jsonl", str(out_path)],
            capture_output=True, text=True, timeout=60)
        assert p.returncode == 0
        recs = [json.loads(l) for l in out_path.read_text().splitlines()]
        assert recs and not any(r["regressed"] for r in recs)

    def test_jsonl_history_read_across_seam(self, tmp_path):
        # a rolled-over history file: the older half lives in .1
        hist = tmp_path / "metrics.jsonl"
        (tmp_path / "metrics.jsonl.1").write_text(json.dumps(
            {"ts": 1.0, "kind": "bench", "metric": "m",
             "value": 100.0}) + "\n")
        hist.write_text(json.dumps(
            {"ts": 2.0, "kind": "bench", "metric": "m",
             "value": 70.0}) + "\n")
        empty = tmp_path / "bench"
        empty.mkdir()
        p = subprocess.run(
            [sys.executable, self.SCRIPT, "--bench-dir", str(empty),
             "--jsonl", str(hist), "--emit-jsonl",
             str(tmp_path / "out.jsonl")],
            capture_output=True, text=True, timeout=60)
        assert p.returncode == 1, p.stdout         # the .1 best was seen
        assert "REGRESSION" in p.stdout
