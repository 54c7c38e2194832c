"""qt-tail: tail-sampled tracing, fleet assembly, exemplars.

The contracts under test:

1. **Bounded pending table** — spans buffer per trace_id; overflow
   LRU-evicts the oldest incomplete trace (COUNTED, never unbounded);
   the high-water mark never exceeds the configured capacity; per-
   trace span truncation is counted too.
2. **The policy chain** (``TAIL_POLICY_NAMES``, first match wins) —
   ``error`` / ``deadline_exceeded`` / ``latency_over_p99`` (live
   threshold) / ``anomaly_window`` (armed by TelemetryHub detector
   firings) / ``head_sample`` (seeded floor); everything else drops.
3. **Assembly** — ``trace`` records sharing a global trace_id stitch
   across sources into one record with cross-segment critical-path
   attribution (dominant span, queue-vs-execute split); the store is
   bounded and idempotent under the aggregator's re-polls.
4. **Exemplars** — ``fleet.prometheus_text`` stamps OpenMetrics
   exemplar syntax on latency series pointing at the newest kept
   trace, and the exposition still passes ``check_exposition``.
5. **End-to-end (the acceptance pin)** — through a REAL jitted engine
   behind ``MicroBatchServer`` + ``RpcServer`` + a tracing
   ``RpcClient`` at sustained load: a seeded slow request
   (``serve.execute`` delay) and a seeded error request are BOTH kept
   and assembled across the client (rpc spans) and replica (serve
   spans) segments with the dominant span identified, while healthy
   traces drop and the pending table stays within capacity.
"""

import importlib.util
import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import quiver_tpu as qv
from quiver_tpu import faults, tailsampling, tracing
from quiver_tpu import fleet as qfleet
from quiver_tpu import rpc as qrpc
from quiver_tpu.metrics import MetricsSink, read_jsonl
from quiver_tpu.models import GraphSAGE
from quiver_tpu.ops import sample_multihop
from quiver_tpu.parallel.train import (init_state, layers_to_adjs,
                                       masked_feature_gather)
from quiver_tpu.tailsampling import (TAIL_POLICY_NAMES, TailSampler,
                                     TraceStore, assemble,
                                     critical_path)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

N, DIM, CLASSES, CAP = 300, 8, 3, 8
FULL = [4, 4]


class ListSink:
    """Duck-typed MetricsSink capturing emitted records in memory."""

    def __init__(self):
        self.records = []

    def emit(self, rec, kind=None):
        self.records.append(dict(rec, kind=kind))
        return rec


@pytest.fixture
def tracer():
    return tracing.Tracer(capacity=128)


def mk(tracer, sink=None, **kw):
    kw.setdefault("head_rate", 0.0)
    s = TailSampler(sink=sink, **kw)
    s.attach(tracer)
    return s


# ---------------------------------------------------------------------------
# the pending table
# ---------------------------------------------------------------------------


class TestPendingTable:
    def test_eviction_counted_and_bounded(self, tracer):
        s = mk(tracer, max_pending=4)
        for i in range(10):                     # 10 open traces, cap 4
            tracer.record("serve.admission_wait", float(i), 0.001, i)
        st = s.stats()
        assert st["pending"] == 4
        assert st["pending_high_water"] <= 4
        assert st["evicted"] == 6
        # an evicted trace's root still completes it (truncated, not
        # lost): trace 0 was evicted, its root re-opens + decides
        tracer.record("serve.request", 0.0, 0.001, 0,
                      {"error": "OSError"})
        st = s.stats()
        assert st["kept"] == 1 and st["completed"] == 1

    def test_span_truncation_counted(self, tracer):
        s = mk(tracer, max_spans_per_trace=3)
        for i in range(8):
            tracer.record("serve.coalesce_wait", float(i), 0.001, 5)
        assert s.stats()["truncated_spans"] == 5
        tracer.record("serve.request", 9.0, 0.001, 5,
                      {"error": "OSError"})
        assert s.stats()["kept"] == 1

    def test_spans_without_trace_id_ignored(self, tracer):
        s = mk(tracer)
        tracer.record("scope.gather", 0.0, 0.001, None)
        assert s.stats()["spans_offered"] == 0

    def test_detach_stops_offers(self, tracer):
        s = mk(tracer)
        s.detach()
        tracer.record("serve.request", 0.0, 0.001, 1)
        assert s.stats()["completed"] == 0


# ---------------------------------------------------------------------------
# the policy chain
# ---------------------------------------------------------------------------


class TestPolicyChain:
    def test_policy_names_tuple_matches_impl(self):
        assert TAIL_POLICY_NAMES == ("error", "deadline_exceeded",
                                     "latency_over_p99",
                                     "anomaly_window", "head_sample")

    def test_healthy_trace_drops(self, tracer):
        sink = ListSink()
        s = mk(tracer, sink=sink, latency_source=lambda: 100.0)
        tracer.record("serve.request", 0.0, 0.010, 1, {"node": 5})
        assert s.stats()["dropped"] == 1 and not sink.records

    def test_error_kept(self, tracer):
        sink = ListSink()
        mk(tracer, sink=sink)
        tracer.record("serve.request", 0.0, 0.010, 1,
                      {"error": "OSError"})
        (rec,) = sink.records
        assert rec["kind"] == "trace" and rec["policy"] == "error"
        assert rec["errors"] == ["OSError"]

    def test_deadline_kept_as_its_own_policy(self, tracer):
        sink = ListSink()
        mk(tracer, sink=sink)
        tracer.record("serve.request", 0.0, 0.010, 1,
                      {"error": "DeadlineExceeded"})
        assert sink.records[0]["policy"] == "deadline_exceeded"

    def test_latency_over_live_threshold_kept(self, tracer):
        sink = ListSink()
        thr = [100.0]
        mk(tracer, sink=sink, latency_source=lambda: thr[0])
        tracer.record("serve.request", 0.0, 0.050, 1)     # 50 < 100
        thr[0] = 20.0                                     # live window
        tracer.record("serve.request", 1.0, 0.050, 2)     # 50 > 20
        assert [r["policy"] for r in sink.records] == \
            ["latency_over_p99"]
        assert sink.records[0]["trace_id"] == 2

    def test_anomaly_window_via_hub_detector(self, tracer):
        # a TelemetryHub spike firing arms the keep-everything window
        # through on_anomaly (called outside the hub lock)
        sink = ListSink()
        clock = [100.0]
        s = TailSampler(sink=sink, anomaly_window_s=5.0,
                        clock=lambda: clock[0])
        s.attach(tracer)
        hub = qv.TelemetryHub(watches=())
        hub.watch("recompiles", "spike")
        s.watch_hub(hub)
        tracer.record("serve.request", 0.0, 0.001, 1)
        assert s.stats()["kept"] == 0            # healthy, no window
        hub.observe("recompiles", 1.0)           # detector fires
        tracer.record("serve.request", 1.0, 0.001, 2)
        assert sink.records[-1]["policy"] == "anomaly_window"
        clock[0] += 6.0                          # window expires
        tracer.record("serve.request", 2.0, 0.001, 3)
        assert s.stats()["kept"] == 1

    def test_head_sample_floor_seeded(self, tracer):
        s = mk(tracer, head_rate=1.0)
        tracer.record("serve.request", 0.0, 0.001, 1)
        assert s.stats()["kept_by_policy"] == {"head_sample": 1}

    def test_latency_source_from_slo_and_stats(self):
        budget = qv.SloBudget(80.0)
        assert tailsampling.latency_source_from(slo=budget)() == 80.0
        stats = qv.StepStats()
        src = tailsampling.latency_source_from(stats=stats)
        assert src() is None                     # no requests yet
        for _ in range(100):
            stats.record_request(0.010)
        assert 5.0 < src() < 25.0                # ~the live p99

    def test_batch_spans_merge_not_pending(self, tracer):
        sink = ListSink()
        s = mk(tracer, sink=sink, max_pending=2)
        # 20 batch ids must not thrash the 2-entry pending table
        for b in range(20):
            tracer.record("serve.dispatch", float(b), 0.200, 1000 + b,
                          {"variant": 0})
        assert s.stats()["evicted"] == 0
        tracer.record("serve.admission_wait", 30.0, 0.001, 7,
                      {"batch": 1019})
        tracer.record("serve.request", 30.0, 0.300, 7,
                      {"batch": 1019, "error": "OSError"})
        (rec,) = sink.records
        names = [sp["name"] for sp in rec["spans"]]
        assert "serve.dispatch" in names         # merged via batch arg
        assert rec["dominant"]["name"] == "serve.dispatch"


# ---------------------------------------------------------------------------
# critical path + assembly
# ---------------------------------------------------------------------------


class TestAssembly:
    def seg(self, root, replica, spans, policy="error", dur=100.0):
        rec = {"trace_id": 7, "policy": policy, "root": root,
               "replica": replica, "duration_ms": dur, "spans": spans}
        rec.update(critical_path(spans, root_name=root,
                                 root_dur_ms=dur))
        return rec

    def test_critical_path_split(self):
        out = critical_path([
            {"name": "serve.admission_wait", "dur_ms": 10.0},
            {"name": "serve.dispatch", "dur_ms": 60.0},
            {"name": "serve.request", "dur_ms": 100.0},
        ], root_name="serve.request", root_dur_ms=100.0)
        assert out["dominant"]["name"] == "serve.dispatch"
        assert out["dominant"]["share"] == pytest.approx(0.6)
        assert out["queue_ms"] == 10.0 and out["execute_ms"] == 60.0

    def test_assemble_cross_process(self):
        client = self.seg("rpc.lookup", "client",
                          [{"name": "rpc.attempt", "dur_ms": 95.0},
                           {"name": "rpc.lookup", "dur_ms": 100.0}],
                          policy="latency_over_p99")
        replica = self.seg("serve.request", "r1",
                           [{"name": "serve.coalesce_wait",
                             "dur_ms": 5.0},
                            {"name": "serve.dispatch", "dur_ms": 96.0},
                            {"name": "serve.request", "dur_ms": 98.0}],
                           policy="latency_over_p99", dur=98.0)
        out = assemble(7, [client, replica])
        assert out["replicas"] == ["client", "r1"]
        assert out["duration_ms"] == 100.0       # the client root
        assert out["dominant"]["name"] == "serve.dispatch"
        assert out["queue_ms"] == pytest.approx(5.0)
        assert out["execute_ms"] == pytest.approx(95.0 + 96.0)

    def test_store_dedups_and_bounds(self):
        st = TraceStore(capacity=2)
        a = self.seg("serve.request", "r0", [])
        assert st.add(a, "r0") and not st.add(a, "r0")   # re-poll
        b = dict(a, trace_id=8)
        c = dict(a, trace_id=9)
        st.add(b, "r0")
        st.add(c, "r0")                          # evicts trace 7
        assert st.evicted == 1 and len(st) == 2
        assert st.get(7) is None
        assert st.latest("r0") == (9, 100.0)
        assert st.latest() == (9, 100.0)
        # client + replica segments of ONE trace coexist per source
        st.add(dict(a, trace_id=9, root="rpc.lookup"), "client")
        assert len(st.get(9)["segments"]) == 2

    def test_chrome_export_events(self):
        rec = self.seg("serve.request", "r0",
                       [{"name": "serve.dispatch", "t0_ms": 1.0,
                         "dur_ms": 60.0, "args": {"variant": 1}}])
        evs = tailsampling.trace_record_to_chrome_events(rec, pid=3)
        assert evs[0]["name"] == "process_name"
        assert evs[0]["args"]["name"] == "r0"
        (x,) = [e for e in evs if e["ph"] == "X"]
        assert x["pid"] == 3 and x["ts"] == 1000.0
        assert x["args"]["trace_id"] == 7


# ---------------------------------------------------------------------------
# fleet wiring: aggregator ingest + /metrics exemplars
# ---------------------------------------------------------------------------


def _load_qt_agg():
    spec = importlib.util.spec_from_file_location(
        "_qt_agg_for_test", os.path.join(REPO, "scripts", "qt_agg.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class TestFleetExemplars:
    def _replica_sink(self, tmp_path):
        p = str(tmp_path / "r0.jsonl")
        with MetricsSink(p, replica="r0") as sink:
            for step in range(3):
                sink.emit({"counters": {"hot_rows": 100 * (step + 1)},
                           "wall": {"p50_ms": 2.0}}, kind="step_stats")
            sink.emit({"trace_id": 424242, "policy": "error",
                       "root": "serve.request", "replica": "r0",
                       "duration_ms": 123.4,
                       "spans": [{"name": "serve.dispatch",
                                  "t0_ms": 0.0, "dur_ms": 100.0}],
                       "dominant": {"name": "serve.dispatch",
                                    "dur_ms": 100.0},
                       "queue_ms": 0.0, "execute_ms": 100.0},
                      kind="trace")
        return p

    def test_aggregator_assembles_and_exposes_exemplars(self, tmp_path):
        agg = qfleet.FleetAggregator(
            {"r0": self._replica_sink(tmp_path)}, interval_s=0.5)
        agg.poll()
        agg.poll()                               # idempotent re-poll
        assert len(agg.traces) == 1
        t = agg.traces.get(424242)
        assert t["dominant"]["name"] == "serve.dispatch"
        text = qfleet.prometheus_text(agg)
        ms_lines = [ln for ln in text.splitlines()
                    if 'name="step_ms"' in ln]
        assert ms_lines and all(
            '# {trace_id="424242"} 123.4' in ln for ln in ms_lines)
        # non-latency series carry no exemplar
        for ln in text.splitlines():
            if 'name="hot_hit_rate"' in ln:
                assert "#" not in ln
        qa = _load_qt_agg()
        assert qa.check_exposition(text) == []
        agg.close()

    def test_exposition_without_traces_unchanged(self, tmp_path):
        p = str(tmp_path / "r0.jsonl")
        with MetricsSink(p, replica="r0") as sink:
            sink.emit({"counters": {"hot_rows": 5},
                       "wall": {"p50_ms": 1.0}}, kind="step_stats")
        agg = qfleet.FleetAggregator({"r0": p}, interval_s=0.5)
        agg.poll()
        text = qfleet.prometheus_text(agg)
        assert _load_qt_agg().check_exposition(text) == []
        assert "trace_id" not in text
        agg.close()


# ---------------------------------------------------------------------------
# the qt_trace CLI
# ---------------------------------------------------------------------------


class TestQtTraceCli:
    SCRIPT = os.path.join(REPO, "scripts", "qt_trace.py")

    def _sink(self, tmp_path):
        p = str(tmp_path / "traces.jsonl")
        recs = [
            {"ts": 1.0, "kind": "trace", "trace_id": 11,
             "policy": "latency_over_p99", "root": "serve.request",
             "replica": "r0", "duration_ms": 250.0,
             "spans": [{"name": "serve.dispatch", "t0_ms": 0.0,
                        "dur_ms": 200.0}],
             "dominant": {"name": "serve.dispatch", "dur_ms": 200.0},
             "queue_ms": 0.0, "execute_ms": 200.0},
            {"ts": 2.0, "kind": "trace", "trace_id": 11,
             "policy": "latency_over_p99", "root": "rpc.lookup",
             "replica": "client", "duration_ms": 260.0,
             "spans": [{"name": "rpc.attempt", "t0_ms": 0.0,
                        "dur_ms": 255.0}],
             "dominant": {"name": "rpc.attempt", "dur_ms": 255.0},
             "queue_ms": 0.0, "execute_ms": 255.0},
            {"ts": 3.0, "kind": "trace", "trace_id": 12,
             "policy": "error", "root": "serve.request",
             "replica": "r0", "duration_ms": 5.0, "spans": [],
             "errors": ["OSError"], "dominant": None,
             "queue_ms": 0.0, "execute_ms": 0.0},
        ]
        with open(p, "w") as f:
            for r in recs:
                f.write(json.dumps(r) + "\n")
        return p

    def run_cli(self, *args):
        return subprocess.run(
            [sys.executable, self.SCRIPT, *args],
            capture_output=True, text=True, timeout=60)

    def test_table_and_filters(self, tmp_path):
        p = self._sink(tmp_path)
        out = self.run_cli("--jsonl", p)
        assert out.returncode == 0, out.stdout + out.stderr
        assert "2 kept traces" in out.stdout
        assert "client+r0" in out.stdout         # assembled replicas
        # the header line carries the wall clock, whose digits can spell
        # either trace id: judge the table rows only
        def rows(*args):
            return self.run_cli("--jsonl", p, *args).stdout.split("\n", 1)[1]

        errs = rows("--errors")
        assert "12" in errs and "11" not in errs
        slow = rows("--slowest", "1")
        assert "11" in slow and "12" not in slow

    def test_detail_and_export(self, tmp_path):
        p = self._sink(tmp_path)
        out_path = str(tmp_path / "perfetto.json")
        out = self.run_cli("--jsonl", p, "--trace-id", "11",
                           "--export", out_path)
        assert out.returncode == 0, out.stdout + out.stderr
        assert "segment r0" in out.stdout
        assert "segment client" in out.stdout
        doc = json.loads(open(out_path).read())
        names = {e.get("name") for e in doc["traceEvents"]}
        assert {"serve.dispatch", "rpc.attempt",
                "process_name"} <= names
        # two segments = two process track groups (distinct pids)
        pids = {e["pid"] for e in doc["traceEvents"]}
        assert len(pids) == 2

    def test_unknown_trace_id_exits_nonzero(self, tmp_path):
        p = self._sink(tmp_path)
        assert self.run_cli("--jsonl", p,
                            "--trace-id", "999").returncode == 1


# ---------------------------------------------------------------------------
# end-to-end: the acceptance pin
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def engine():
    rng = np.random.default_rng(7)
    deg = rng.integers(1, 4, N)
    indptr = np.zeros(N + 1, np.int64)
    np.cumsum(deg, out=indptr[1:])
    indices = rng.integers(0, N, int(indptr[-1]), dtype=np.int32)
    feat = rng.standard_normal((N, DIM)).astype(np.float32)
    model = GraphSAGE(hidden_dim=8, out_dim=CLASSES, num_layers=2,
                      dropout=0.0)
    ij = jnp.asarray(indptr.astype(np.int32))
    xj = jnp.asarray(indices)
    n_id, layers = sample_multihop(ij, xj,
                                   jnp.arange(4, dtype=jnp.int32),
                                   FULL, jax.random.key(0))
    params = init_state(model, optax.adam(1e-3),
                        masked_feature_gather(jnp.asarray(feat), n_id),
                        layers_to_adjs(layers, 4, FULL),
                        jax.random.key(1)).params
    eng = qv.ServeEngine(model, params, (ij, xj), feat,
                         sizes_variants=[FULL], batch_cap=CAP)
    eng.warmup()
    return eng


class TestEndToEndCapture:
    def test_slow_and_error_kept_and_assembled(self, engine, tmp_path):
        """The acceptance criterion: at sustained load through a real
        engine + RPC front end with a tracing client, a seeded slow
        request (``serve.execute`` delay) and a seeded error request
        are both KEPT and ASSEMBLED across client + replica segments
        with the dominant span identified; healthy traces all drop
        (no head floor armed) and the pending table stays bounded."""
        sink_path = str(tmp_path / "tail.jsonl")
        sink = MetricsSink(sink_path)
        tracing.clear()
        sampler = TailSampler(sink=sink, max_pending=64,
                              latency_source=lambda: 150.0,
                              head_rate=0.0).attach()
        server = qv.MicroBatchServer(engine,
                                     qv.ServeConfig(max_wait_ms=1.0))
        rpc_srv = qrpc.RpcServer(server)
        cli = qrpc.RpcClient({"r0": ("127.0.0.1", rpc_srv.port)},
                             retries=0, hedge=False,
                             timeout_ms=10_000.0, seed=2)
        n_req, rate = 240, 150.0
        futs, errors = [], 0
        try:
            t0 = time.perf_counter()
            for k in range(n_req):
                if k == 80:
                    faults.install(qv.FaultPlan(seed=1, rules={
                        "serve.execute": qv.FaultRule(
                            "delay", times=1, delay_ms=400.0)}))
                elif k == 160:
                    faults.install(qv.FaultPlan(seed=2, rules={
                        "serve.execute": qv.FaultRule(
                            "error", exc="runtime", times=1)}))
                target = t0 + k / rate
                d = target - time.perf_counter()
                if d > 0:
                    time.sleep(d)
                futs.append(cli.lookup_future(k % N))
            for f in futs:
                try:
                    f.result(timeout=60)
                except qrpc.RpcError:
                    errors += 1
            st = sampler.stats()
        finally:
            faults.disarm()
            cli.close()
            rpc_srv.close()
            server.close()
            sampler.detach()
            tracing.disable()
            tracing.clear()
            sink.close()
        assert errors >= 1                       # the seeded error ran

        store = TraceStore(capacity=4096)
        for rec in read_jsonl(sink_path):
            if rec.get("kind") == "trace":
                store.add(rec, "local")
        assembled = store.assembled()
        slow = [t for t in assembled
                if "latency_over_p99" in t["policies"]
                and len(t["segments"]) >= 2]
        errs = [t for t in assembled if "error" in t["policies"]
                and len(t["segments"]) >= 2]
        assert slow, "seeded slow request not assembled across " \
                     "client + replica"
        assert errs, "seeded error request not assembled across " \
                     "client + replica"
        # the slow trace's time is attributed: the dominant span is
        # the delayed dispatch (replica) or the attempt that carried
        # it (client), at the injected ~400 ms
        dom = max(slow, key=lambda t: t["duration_ms"])["dominant"]
        assert dom is not None and dom["name"] in ("serve.dispatch",
                                                   "rpc.attempt")
        assert dom["dur_ms"] > 300.0
        # >= 99% of HEALTHY traces dropped: with no head floor and no
        # anomaly window, only outcome policies keep — healthy keeps
        # must be zero, and the kept set stays a sliver overall
        healthy_kept = (st["kept"]
                        - sum(st["kept_by_policy"].get(p, 0)
                              for p in ("error", "deadline_exceeded",
                                        "latency_over_p99")))
        healthy = st["completed"] - (st["kept"] - healthy_kept)
        assert healthy_kept == 0
        assert healthy > 0 and \
            (healthy - healthy_kept) / healthy >= 0.99
        # the kept set is a sliver: beyond the seeded slow/error pair,
        # only requests queued BEHIND the injected 400 ms stall keep
        # (they genuinely busted the threshold — correct behavior),
        # so the bound tolerates that window but not full capture
        assert st["kept"] <= 0.3 * st["completed"]
        assert st["pending_high_water"] <= st["pending_capacity"]

    def test_rpc_client_spans_cover_retries_and_hedges(self):
        """rpc.attempt / rpc.backoff spans ride the injected context:
        a client retrying off a failing replica leaves the whole
        retry story in its kept trace."""
        class FailingBackend:
            def __init__(self):
                self.calls = 0

            def submit(self, node, context=None, deadline=None):
                import concurrent.futures as cf
                self.calls += 1
                fut = cf.Future()
                if self.calls == 1:
                    fut.set_exception(RuntimeError("boom"))
                else:
                    fut.set_result(np.zeros(3, np.float32))
                return fut

        sink = ListSink()
        tracing.clear()
        sampler = TailSampler(sink=sink, head_rate=0.0).attach()
        srv = qrpc.RpcServer(FailingBackend())
        cli = qrpc.RpcClient({"r0": ("127.0.0.1", srv.port)},
                             retries=2, hedge=False, backoff_ms=10.0,
                             seed=0)
        try:
            cli.lookup(5)
        finally:
            cli.close()
            srv.close()
            sampler.detach()
            tracing.disable()
            tracing.clear()
        # first attempt errored -> the trace is kept (error policy)
        # and shows attempt(error) -> backoff -> attempt(ok)
        kept = [r for r in sink.records if r["kind"] == "trace"]
        assert len(kept) == 1
        names = [s["name"] for s in kept[0]["spans"]]
        assert names.count("rpc.attempt") == 2
        assert "rpc.backoff" in names
        assert kept[0]["root"] == "rpc.lookup"
        assert kept[0]["policy"] == "error"
