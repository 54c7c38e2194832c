"""Serving layer: coalescing semantics, scatter fidelity, SLO shedding.

The contracts under test:

1. **Coalescing** — a lone request dispatches at the max-wait deadline
   (never waits indefinitely for company); a burst larger than
   ``batch_cap`` unique seeds splits into back-to-back batches;
   duplicate node ids coalesced into the same batch share one slot.
2. **Scatter fidelity** — under interleaved arrivals every request's
   future resolves to ITS node's logits row. Pinned numerically: the
   test graph's max degree is below the fanout, so the exact sampler
   (without replacement) draws every neighbor and the forward pass is
   key-independent — server results must equal a direct
   ``ServeEngine.run`` of the same node.
3. **Degradation** — admission overload raises ``OverloadError``
   immediately (queue stays bounded); queue pressure sheds dispatches
   to the smaller pre-compiled fanout variant, whose outputs are valid
   (finite, right shape) and counted in the variant mix.
4. **Zero host syncs** — the jitted serve step's traced program
   contains no callback/infeed equations (``_traffic.host_sync_eqns``),
   with metrics collection on or off, for the plain-array and the
   Feature-store-backed gather alike — and independently of whether
   span tracing is enabled (tracing is host-side only).
5. **Tracing + SLO** — served logits are bit-identical with tracing on
   or off; every request leaves admission/coalesce/request spans whose
   ``batch`` arg names a real batch's dispatch span and whose windows
   nest consistently (parent/child); the SLO error-budget burn-rate
   trigger sheds quality (replacing the raw recent-p99 trigger) and
   the budget block rides the ``serving`` JSONL record.
6. **Tenancy** — with a ``TenantClass`` registry, shed ORDER is
   policy: a pressed queue rejects the class already holding its
   weighted share ("holds its share"), a full queue displaces the
   NEWEST lowest-priority request (never the reverse direction), and
   quality shed consumes zero-grace classes first (``shed_grace``
   ladder steps). Accounting is exact per class and lands as kind
   ``tenant`` JSONL. Tenancy is host-side only: served logits are
   bit-identical with the registry on or off, and a server without a
   registry accepts-and-ignores the ``tenant`` argument.
"""

import json
import threading
import time

import numpy as np
import jax
import jax.numpy as jnp
import optax
import pytest

import quiver_tpu as qv
from quiver_tpu import metrics as qm
from quiver_tpu import tracing
from quiver_tpu.models import GraphSAGE
from quiver_tpu.ops import sample_multihop
from quiver_tpu.parallel.train import (init_state, layers_to_adjs,
                                       masked_feature_gather)

from _traffic import host_sync_eqns

N, DIM, CLASSES = 400, 8, 3
CAP = 8
FULL, SHED = [4, 4], [1, 1]


@pytest.fixture(scope="module")
def world():
    """One tiny deterministic serving world shared by the module: max
    degree 3 < fanout 4, so full-fanout outputs are key-independent
    (exact mode draws without replacement)."""
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
    n_id, layers = sample_multihop(ij, xj, jnp.arange(4, dtype=jnp.int32),
                                   FULL, jax.random.key(0))
    state = init_state(model, optax.adam(1e-3),
                       masked_feature_gather(jnp.asarray(feat), n_id),
                       layers_to_adjs(layers, 4, FULL), jax.random.key(1))
    return model, state.params, ij, xj, feat


@pytest.fixture(scope="module")
def engine(world):
    model, params, ij, xj, feat = world
    eng = qv.ServeEngine(model, params, (ij, xj), feat,
                         sizes_variants=[FULL, SHED], batch_cap=CAP)
    eng.warmup()
    return eng


@pytest.fixture(scope="module")
def reference(engine):
    """Direct per-node full-fanout logits (deterministic, see above)."""
    return {v: np.asarray(engine.run(np.array([v], np.int32)))[0]
            for v in range(64)}


class TestServeStep:
    def test_zero_host_syncs_in_traced_step(self, world):
        model, params, ij, xj, feat = world
        store = qv.Feature(device_cache_size=(N // 4) * DIM * 4,
                           dedup_cold=True, cold_budget=32)
        store.from_cpu_tensor(feat)
        for f, collect in ((feat, False), (feat, True),
                           (store, True)):
            eng = qv.ServeEngine(model, params, (ij, xj), f,
                                 sizes_variants=[FULL], batch_cap=CAP,
                                 collect_metrics=collect)
            args = (eng.params, jax.random.key(0), eng._feat,
                    eng._forder, eng._indptr, eng._indices,
                    jnp.zeros((CAP,), jnp.int32))
            assert host_sync_eqns(eng._steps[0].raw, args) == []
        store.close()

    def test_variant_hop_counts_must_match(self, world):
        model, params, ij, xj, feat = world
        with pytest.raises(ValueError, match="hop count"):
            qv.ServeEngine(model, params, (ij, xj), feat,
                           sizes_variants=[[4, 4], [2]], batch_cap=CAP)

    def test_pad_seeds_contract(self, engine):
        s = engine.pad_seeds([5, 9])
        assert s.shape == (CAP,) and s.dtype == np.int32
        assert list(s[:2]) == [5, 9] and (s[2:] == -1).all()
        with pytest.raises(ValueError, match="exceed batch_cap"):
            engine.pad_seeds(np.arange(CAP + 1))

    def test_feature_store_gather_matches_plain_array(self, world,
                                                      engine, reference):
        model, params, ij, xj, feat = world
        store = qv.Feature(device_cache_size=(N // 4) * DIM * 4,
                           dedup_cold=True, cold_budget=32)
        store.from_cpu_tensor(feat)
        eng = qv.ServeEngine(model, params, (ij, xj), store,
                             sizes_variants=[FULL], batch_cap=CAP,
                             collect_metrics=True)
        got = np.asarray(eng.run(np.arange(6, dtype=np.int32)))[:6]
        want = np.stack([reference[v] for v in range(6)])
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
        # the store's tiered lookup counted hot AND cold rows inside
        # the one dispatch (25% HBM cache -> both tiers are hit)
        c = np.asarray(eng.last_counters)
        assert c[qm.LOOKUP_CALLS] == 1
        assert c[qm.HOT_ROWS] > 0 and c[qm.COLD_ROWS] > 0
        store.close()


class TestCoalescing:
    def test_single_request_meets_deadline(self, engine, reference):
        srv = qv.MicroBatchServer(
            engine, qv.ServeConfig(max_wait_ms=30.0, queue_depth=16,
                                   shed_queue_frac=1.0))
        t0 = time.perf_counter()
        row = srv.submit(3).result(timeout=5)
        waited = time.perf_counter() - t0
        np.testing.assert_allclose(row, reference[3], rtol=1e-5,
                                   atol=1e-6)
        # the lone request shipped at (about) the 30 ms coalescing
        # deadline — not at some unbounded "wait for a full batch"
        # horizon (generous multiple: this box lands 100 ms stalls)
        assert waited < 0.5
        s = srv.snapshot()["serving"]
        assert s["batches"] == 1 and s["mean_batch_fill"] == 1.0
        srv.close()

    def test_over_capacity_burst_splits(self, engine):
        srv = qv.MicroBatchServer(
            engine, qv.ServeConfig(max_wait_ms=5.0, queue_depth=64,
                                   shed_queue_frac=1.0), start=False)
        futs = [srv.submit(i) for i in range(2 * CAP + 3)]
        srv.start()
        for f in futs:
            assert f.result(timeout=10).shape == (CLASSES,)
        s = srv.snapshot()["serving"]
        assert s["batches"] == 3                      # 8 + 8 + 3
        assert s["requests"] == 2 * CAP + 3
        assert s["completed"] == 2 * CAP + 3
        srv.close()

    def test_duplicate_ids_share_one_slot(self, engine, reference):
        srv = qv.MicroBatchServer(
            engine, qv.ServeConfig(max_wait_ms=20.0, queue_depth=64,
                                   shed_queue_frac=1.0), start=False)
        # 12 requests, only 3 distinct nodes: fits ONE cap-8 batch
        ids = [4, 9, 4, 2, 9, 4, 2, 2, 9, 4, 9, 2]
        futs = [srv.submit(i) for i in ids]
        srv.start()
        for i, f in zip(ids, futs):
            np.testing.assert_allclose(f.result(timeout=10),
                                       reference[i], rtol=1e-5,
                                       atol=1e-6)
        assert srv.snapshot()["serving"]["batches"] == 1
        srv.close()

    @pytest.mark.parametrize("depth", [1, 2, 3])
    def test_rows_are_engine_runs_of_the_closed_seed_blocks(
            self, world, depth, monkeypatch):
        # the coalescer decides WHICH seed blocks exist and nothing
        # else: a request is served, bit for bit, engine.run's row of
        # its batch's block, the blocks run one at a time in the order
        # they closed (what every pipeline_depth, and the server before
        # batches stayed open for room, serves for these blocks). The
        # SHED fanout is under most degrees, so a row depends on its
        # block's place in the key chain.
        model, params, ij, xj, feat = world
        eng = qv.ServeEngine(model, params, (ij, xj), feat,
                             sizes_variants=[SHED], batch_cap=CAP, seed=17)
        real_run, blocks = eng.run, []

        def recording_run(seeds, variant=0):
            blocks.append((np.array(seeds), variant))
            return real_run(seeds, variant)

        monkeypatch.setattr(eng, "run", recording_run)
        srv = qv.MicroBatchServer(
            eng, qv.ServeConfig(max_wait_ms=1.0, queue_depth=64,
                                pipeline_depth=depth, shed_queue_frac=1.0),
            start=False)
        ids = list(range(2 * CAP + 3))
        futs = [srv.submit(i) for i in ids]
        srv.start()
        served = [f.result(timeout=20) for f in futs]
        srv.close()
        monkeypatch.undo()
        assert [list(b[:3]) for b, _ in blocks] == [[0, 1, 2], [8, 9, 10],
                                                    [16, 17, 18]]
        eng._key = jax.random.key(17)        # rewind the donated chain
        rows = np.concatenate([np.asarray(jax.device_get(eng.run(b, v)))
                               for b, v in blocks])
        for i, row in zip(ids, served):
            assert row.tobytes() == rows[i].tobytes(), i
        # the chain matters: the same block one place later reads another
        again = np.asarray(jax.device_get(eng.run(*blocks[-1])))
        assert not np.array_equal(again[:3], rows[2 * CAP:2 * CAP + 3])

    def test_scatter_under_interleaved_arrivals(self, engine, reference):
        srv = qv.MicroBatchServer(
            engine, qv.ServeConfig(max_wait_ms=2.0, queue_depth=512,
                                   shed_queue_frac=1.0))
        results = {}
        errs = []
        lock = threading.Lock()

        def client(tid):
            rng = np.random.default_rng(tid)
            for k in range(40):
                nid = int(rng.integers(0, 64))
                try:
                    row = srv.submit(nid).result(timeout=20)
                except Exception as e:            # pragma: no cover
                    errs.append(e)
                    return
                with lock:
                    results[(tid, k)] = (nid, row)
                if k % 7 == 0:
                    time.sleep(0.001)

        threads = [threading.Thread(target=client, args=(t,))
                   for t in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errs
        assert len(results) == 160
        for nid, row in results.values():
            np.testing.assert_allclose(row, reference[nid], rtol=1e-5,
                                       atol=1e-6)
        srv.close()


class TestOverloadAndShedding:
    def test_admission_overload_raises(self, engine):
        srv = qv.MicroBatchServer(
            engine, qv.ServeConfig(max_wait_ms=5.0, queue_depth=2),
            start=False)
        f1, f2 = srv.submit(0), srv.submit(1)
        with pytest.raises(qv.OverloadError, match="queue full"):
            srv.submit(2)
        srv.start()
        assert f1.result(timeout=10) is not None
        assert f2.result(timeout=10) is not None
        s = srv.snapshot()["serving"]
        assert s["rejected"] == 1 and s["requests"] == 2
        srv.close()

    def test_queue_pressure_sheds_to_smaller_fanout(self, engine):
        # shed_queue_frac tiny: the staged burst alone crosses the
        # pressure threshold, so some batches MUST take the [1, 1]
        # variant — and its masked outputs are still valid rows
        srv = qv.MicroBatchServer(
            engine, qv.ServeConfig(max_wait_ms=1.0, queue_depth=64,
                                   shed_queue_frac=0.05), start=False)
        futs = [srv.submit(i % 16) for i in range(48)]
        srv.start()
        rows = [f.result(timeout=20) for f in futs]
        for row in rows:
            assert row.shape == (CLASSES,)
            assert np.isfinite(row).all()
        s = srv.snapshot()["serving"]
        assert s["variant_batches"][1] > 0            # shed happened
        assert s["fanout_variants"] == [FULL, SHED]
        assert s["shed_level"] >= 0
        srv.close()

    def test_serving_snapshot_emits_jsonl(self, engine, tmp_path):
        srv = qv.MicroBatchServer(
            engine, qv.ServeConfig(max_wait_ms=2.0, queue_depth=64,
                                   shed_queue_frac=1.0))
        [f.result(timeout=10) for f in srv.submit_many(range(12))]
        path = tmp_path / "serving.jsonl"
        with qm.MetricsSink(str(path)) as sink:
            rec = srv.emit(sink)
        assert rec["kind"] == "serving"
        lines = [json.loads(l) for l in path.read_text().splitlines()]
        # the sink self-attributes: meta header first, then the record
        assert [l["kind"] for l in lines] == ["meta", "serving"]
        got = lines[1]
        assert got["request"]["count"] == 12          # per-REQUEST p99
        assert got["request"]["p99_ms"] > 0
        assert got["serving"]["requests"] == 12
        assert got["wall"]["p99_ms"] > 0              # per-batch too
        assert "recompiles" in got                    # watch armed
        assert got["recompiles"] == 0
        report = srv.report()
        assert "per-request latency" in report
        srv.close()


@pytest.fixture
def traced():
    """Enable the process-default tracer for one test, guaranteed off
    (and emptied) afterwards whatever the test does."""
    tracing.clear()
    tracing.enable()
    yield tracing.get_tracer()
    tracing.disable()
    tracing.clear()


class TestTracingAndSlo:
    def test_traced_logits_bit_identical(self, world):
        # tracing is host-side only: with the key chain reset to the
        # same state, the served logits must match bit for bit with
        # tracing off vs on (not just allclose). One engine, one
        # compile — the chain reset replays the exact same program
        # inputs.
        model, params, ij, xj, feat = world
        eng = qv.ServeEngine(model, params, (ij, xj), feat,
                             sizes_variants=[FULL], batch_cap=CAP,
                             seed=11)
        seeds = np.arange(6, dtype=np.int32)
        off = np.asarray(jax.device_get(eng.run(seeds)))
        eng._key = jax.random.key(11)        # rewind the donated chain
        tracing.enable()
        try:
            on = np.asarray(jax.device_get(eng.run(seeds)))
        finally:
            tracing.disable()
            tracing.clear()
        assert np.array_equal(off, on)

    def test_zero_host_syncs_with_tracing_enabled(self, world, traced):
        # the acceptance pin: tracing+metrics both on, the traced
        # program still round-trips nothing through the host
        model, params, ij, xj, feat = world
        eng = qv.ServeEngine(model, params, (ij, xj), feat,
                             sizes_variants=[FULL], batch_cap=CAP,
                             collect_metrics=True)
        args = (eng.params, jax.random.key(0), eng._feat, eng._forder,
                eng._indptr, eng._indices, jnp.zeros((CAP,), jnp.int32))
        assert host_sync_eqns(eng._steps[0].raw, args) == []

    def test_request_spans_correlate_and_nest(self, engine, traced):
        srv = qv.MicroBatchServer(
            engine, qv.ServeConfig(max_wait_ms=2.0, queue_depth=128,
                                   shed_queue_frac=1.0), start=False)
        futs = [srv.submit(i % 16) for i in range(3 * CAP)]
        srv.start()
        for f in futs:
            f.result(timeout=20)
        srv.close()
        recs = traced.records()
        by_name = {}
        for r in recs:
            by_name.setdefault(r[0], []).append(r)
        n_req = 3 * CAP
        assert len(by_name["serve.request"]) == n_req
        assert len(by_name["serve.admission_wait"]) == n_req
        assert len(by_name["serve.coalesce_wait"]) == n_req
        n_batches = len(by_name["serve.dispatch"])
        assert n_batches == len(by_name["serve.scatter"]) \
            == len(by_name["serve.batch_coalesce"]) >= 3
        # correlation: every request span's batch arg names a batch
        # that really dispatched, and the batch saw it in its count
        batch_ids = {r[4] for r in by_name["serve.dispatch"]}
        per_req = {}
        for r in recs:
            if r[0] in ("serve.request", "serve.admission_wait",
                        "serve.coalesce_wait"):
                assert r[5]["batch"] in batch_ids
                per_req.setdefault(r[4], {})[r[0]] = r
        assert len(per_req) == n_req
        # parent/child: admission_wait then coalesce_wait, both inside
        # the request's total span; the request resolves after its
        # batch's dispatch began (float clocks: allow tiny slack)
        eps = 1e-4
        dispatch_t0 = {r[4]: r[2] for r in by_name["serve.dispatch"]}
        for rid, spans in per_req.items():
            adm = spans["serve.admission_wait"]
            coa = spans["serve.coalesce_wait"]
            req = spans["serve.request"]
            assert adm[5]["batch"] == coa[5]["batch"] \
                == req[5]["batch"]
            assert adm[2] >= req[2] - eps            # starts at enqueue
            assert adm[2] + adm[3] <= coa[2] + eps   # then coalesce
            assert coa[2] + coa[3] <= req[2] + req[3] + eps
            assert req[2] + req[3] >= dispatch_t0[req[5]["batch"]] - eps

    def test_injected_context_propagates_to_replica_trace(
            self, engine, traced, tmp_path):
        # the fleet acceptance pin: a trace context injected
        # CLIENT-side (tracing.inject into request metadata) reappears
        # under the same trace_id in the replica's exported trace —
        # the cross-process correlation the merged Perfetto view
        # pivots on. The injected id is pid-prefixed (globally
        # unique), so it can't collide with locally minted ids.
        ctx = tracing.inject({"app_field": "kept"},
                             replica="client-7")
        client_tid = ctx[tracing.CTX_TRACE_ID]
        assert tracing.extract(ctx).replica == "client-7"
        srv = qv.MicroBatchServer(
            engine, qv.ServeConfig(max_wait_ms=1.0, queue_depth=32,
                                   shed_queue_frac=1.0))
        with srv:
            fut = srv.submit(3, context=ctx)
            plain = srv.submit(4)            # no context: local id
            fut.result(timeout=20)
            plain.result(timeout=20)
        recs = traced.records()
        req_ids = {r[4] for r in recs if r[0] == "serve.request"}
        assert client_tid in req_ids
        # the full request span set carries the propagated id
        names_with_ctx = {r[0] for r in recs if r[4] == client_tid}
        assert {"serve.request", "serve.admission_wait",
                "serve.coalesce_wait"} <= names_with_ctx
        # and it survives into the exported trace's span args under a
        # replica-labeled process track
        out = str(tmp_path / "replica_trace.json")
        traced.export_chrome_trace(out, replica="serve-replica-0")
        doc = json.load(open(out))
        hits = [e for e in doc["traceEvents"]
                if (e.get("args") or {}).get("trace_id") == client_tid]
        assert any(e["name"] == "serve.request" for e in hits)
        procs = [e for e in doc["traceEvents"]
                 if e.get("name") == "process_name"]
        assert procs[0]["args"]["name"] == "serve-replica-0"

    def test_garbled_context_falls_back_to_local_id(self, engine,
                                                    traced):
        srv = qv.MicroBatchServer(
            engine, qv.ServeConfig(max_wait_ms=1.0, queue_depth=32,
                                   shed_queue_frac=1.0))
        with srv:
            srv.submit(5, context={"qt.trace_id": "garbage"}) \
               .result(timeout=20)
        reqs = [r for r in traced.records()
                if r[0] == "serve.request"]
        assert reqs and all(r[4] is not None for r in reqs)

    def test_slo_burn_rate_sheds_quality(self, engine):
        # a sub-ms p99 target makes every CPU request "bad": the short
        # window burns at ~1/budget >> shed_burn_rate once min samples
        # arrive, so later batches MUST take the shed variant (queue
        # trigger disabled at frac 1.0 to isolate the SLO trigger)
        srv = qv.MicroBatchServer(
            engine, qv.ServeConfig(max_wait_ms=1.0, queue_depth=256,
                                   shed_queue_frac=1.0,
                                   slo_p99_ms=0.001), start=False)
        futs = [srv.submit(i % 32) for i in range(120)]
        srv.start()
        for f in futs:
            assert np.isfinite(f.result(timeout=30)).all()
        s = srv.snapshot()
        assert s["serving"]["variant_batches"][1] > 0, \
            "burn-rate trigger never shed"
        assert s["slo"]["windows"]["short"]["bad"] > 0
        assert s["slo"]["budget_remaining"] < 0       # overspent
        srv.close()

    def test_slo_block_and_slo_kind_jsonl(self, engine, tmp_path):
        srv = qv.MicroBatchServer(
            engine, qv.ServeConfig(max_wait_ms=2.0, queue_depth=64,
                                   shed_queue_frac=1.0,
                                   slo_p99_ms=5000.0))
        [f.result(timeout=10) for f in srv.submit_many(range(25))]
        path = tmp_path / "slo.jsonl"
        with qm.MetricsSink(str(path)) as sink:
            rec = srv.emit(sink)                      # kind serving
            srv.slo.emit(sink)                        # kind slo
        assert rec["slo"]["target_p99_ms"] == 5000.0
        lines = [json.loads(l) for l in path.read_text().splitlines()]
        assert [l["kind"] for l in lines] == ["meta", "serving", "slo"]
        lines = lines[1:]                 # past the sink's meta header
        assert lines[0]["slo"]["total"]["requests"] == 25
        assert lines[1]["target_p99_ms"] == 5000.0
        assert "burn_rate" in lines[1]["windows"]["short"]
        # a comfortable 5 s budget on a tiny burst: nothing burns (the
        # target is huge on purpose — this box lands 100 ms stalls)
        assert not lines[1]["shedding"]
        report = srv.report()
        assert "slo:" in report and "budget remaining" in report
        srv.close()

    def test_no_slo_budget_without_target(self, engine):
        srv = qv.MicroBatchServer(
            engine, qv.ServeConfig(max_wait_ms=1.0, queue_depth=16,
                                   shed_queue_frac=1.0))
        assert srv.slo is None
        srv.submit(1).result(timeout=10)
        assert "slo" not in srv.snapshot()
        srv.close()


class TestLifecycle:
    def test_close_fails_queued_requests_loudly(self, engine):
        srv = qv.MicroBatchServer(
            engine, qv.ServeConfig(max_wait_ms=5.0, queue_depth=16),
            start=False)
        futs = [srv.submit(i) for i in range(3)]
        srv.close()
        for f in futs:
            with pytest.raises(RuntimeError, match="closed"):
                f.result(timeout=5)
        with pytest.raises(RuntimeError, match="closed"):
            srv.submit(0)
        srv.close()                                   # idempotent

    def test_close_fails_pipeline_queued_batch(self, engine, monkeypatch):
        # Stage the repro directly: batch A held on the pipeline worker
        # while batch B sits QUEUED in the pipeline; close() must fail
        # B's futures (pipeline cancel -> done-callback), never strand
        # them PENDING.
        srv = qv.MicroBatchServer(
            engine, qv.ServeConfig(max_wait_ms=1.0, queue_depth=64,
                                   shed_queue_frac=1.0), start=False)
        real_run = engine.run
        started, release = threading.Event(), threading.Event()

        def held_run(seeds, variant=0):
            started.set()
            assert release.wait(timeout=30)
            return real_run(seeds, variant)

        monkeypatch.setattr(engine, "run", held_run)
        futs = [srv.submit(i) for i in range(2 * CAP)]   # two full batches
        srv.start()
        assert started.wait(timeout=10)       # A is on the worker
        deadline = time.perf_counter() + 5    # B coalesced + queued
        while srv._q.qsize() > 0 and time.perf_counter() < deadline:
            time.sleep(0.005)
        closer = threading.Thread(target=srv.close)
        closer.start()                        # blocks on A's join
        time.sleep(0.05)
        release.set()                         # let A drain
        closer.join(timeout=30)
        assert not closer.is_alive()
        ok = failed = 0
        for f in futs:
            try:
                f.result(timeout=5)           # never hangs: resolved
                ok += 1                       # or failed, not PENDING
            except RuntimeError:
                failed += 1
        assert ok + failed == 2 * CAP
        assert ok == CAP and failed == CAP    # A served, B failed loudly
        assert srv.snapshot()["serving"]["failed"] == CAP

    def test_step_failure_propagates_to_request_futures(self, engine,
                                                        monkeypatch):
        srv = qv.MicroBatchServer(
            engine, qv.ServeConfig(max_wait_ms=2.0, queue_depth=16))

        def boom(seeds, variant=0):
            raise RuntimeError("device fell over")

        monkeypatch.setattr(srv.engine, "run", boom)
        fut = srv.submit(1)
        with pytest.raises(RuntimeError, match="device fell over"):
            fut.result(timeout=10)
        monkeypatch.undo()
        # the server survives a failed batch: next request succeeds
        assert srv.submit(2).result(timeout=10).shape == (CLASSES,)
        s = srv.snapshot()["serving"]
        assert s["failed"] >= 1 and s["completed"] >= 1
        srv.close()


class TestShardedServe:
    """qt-shard: the serve step over a DistFeature-partitioned store.
    The load-bearing pin: logits bit-identical to the single-store
    engine across the dense, narrow-exchange AND forced-fallback
    paths — partitioning changes WHERE rows live, never which rows the
    model sees."""

    HOSTS = 2

    def _dist(self, feat, exchange_cap, collect=True, rng_seed=3):
        from jax.sharding import Mesh
        rng = np.random.default_rng(rng_seed)
        g2h = rng.integers(0, self.HOSTS, N).astype(np.int32)
        g2h[:self.HOSTS] = np.arange(self.HOSTS)
        mesh = Mesh(np.array(jax.devices()[:self.HOSTS]), ("host",))
        info = qv.PartitionInfo(host=0, hosts=self.HOSTS,
                                global2host=g2h)
        comm = qv.TpuComm(rank=0, world_size=self.HOSTS, mesh=mesh,
                          axis="host")
        return qv.DistFeature.from_partition(
            feat, info, comm, exchange_cap=exchange_cap,
            collect_metrics=collect)

    def _engines(self, world, exchange_cap, collect=True):
        model, params, ij, xj, feat = world
        dist = self._dist(feat, exchange_cap, collect=collect)
        sharded = qv.ShardedServeEngine(
            model, params, (ij, xj), dist,
            sizes_variants=[FULL, SHED], batch_cap=CAP,
            collect_metrics=collect, seed=9)
        single = qv.ServeEngine(model, params, (ij, xj), feat,
                                sizes_variants=[FULL, SHED],
                                batch_cap=CAP,
                                collect_metrics=collect, seed=9)
        return sharded, single

    @pytest.mark.parametrize("cap,expect_fallback", [
        (None, None),   # dense exchange (no compact path at all)
        (32, False),    # narrow: compact path must stay compact
        (2, True),      # forced fallback on every batch
    ])
    def test_bit_identical_to_single_store(self, world, cap,
                                           expect_fallback):
        sharded, single = self._engines(world, cap)
        rng = np.random.default_rng(5)
        saw_fallback = 0
        for i in range(4):
            if i % 2 == 0:     # dup-heavy: few uniques, deep dedup
                seeds = rng.integers(0, 6, CAP).astype(np.int32)
            else:              # unique-heavy: wide frontier
                seeds = rng.choice(N, CAP, replace=False).astype(
                    np.int32)
            variant = i % 2    # both ladder rungs
            got = np.asarray(sharded.run(seeds, variant=variant))
            want = np.asarray(single.run(seeds, variant=variant))
            np.testing.assert_array_equal(got, want)
            if cap is not None:
                c = np.asarray(sharded.last_counters)
                saw_fallback += int(c[qm.EXCH_FALLBACK] > 0)
        if expect_fallback is True:
            assert saw_fallback == 4     # cap 2 can never fit
        elif expect_fallback is False:
            assert saw_fallback == 0     # cap 32 never overflows here

    def test_zero_host_syncs_in_sharded_step(self, world):
        model, params, ij, xj, feat = world
        for collect in (False, True):
            dist = self._dist(feat, 32, collect=collect)
            eng = qv.ShardedServeEngine(model, params, (ij, xj), dist,
                                        sizes_variants=[FULL],
                                        batch_cap=CAP,
                                        collect_metrics=collect)
            args = (eng.params, jax.random.key(0), dist._spmd_feat,
                    eng._g2h, eng._g2l, eng._indptr, eng._indices,
                    jnp.zeros((CAP,), jnp.int32))
            assert host_sync_eqns(eng._steps[0].raw, args) == []

    def test_locality_counters_classify_every_frontier_row(self, world):
        sharded, _ = self._engines(world, 32)
        rng = np.random.default_rng(11)
        seeds = rng.choice(N, CAP, replace=False).astype(np.int32)
        sharded.run(seeds)
        c = np.asarray(sharded.last_counters)
        hit = int(c[qm.LOCALITY_HIT_ROWS])
        miss = int(c[qm.LOCALITY_MISS_ROWS])
        # every VALID frontier row classified exactly once (shard-0
        # fold: the psum must not multiply by the shard count)
        assert hit + miss == int(c[qm.FRONTIER_VALID])
        assert hit > 0 and miss > 0      # a random 2-split has both
        d = qm.derive(c)
        assert d["locality_hit_rate"] == pytest.approx(
            hit / (hit + miss))

    def test_engine_validations(self, world):
        model, params, ij, xj, feat = world
        dist = self._dist(feat, 32)
        with pytest.raises(ValueError, match="hop count"):
            qv.ShardedServeEngine(model, params, (ij, xj), dist,
                                  sizes_variants=[FULL, [2]],
                                  batch_cap=CAP)
        rep = self._dist(feat, 32)
        rep._rep_args = object()         # a replicated-tail store
        with pytest.raises(ValueError, match="replicated-tail"):
            qv.ShardedServeEngine(model, params, (ij, xj), rep,
                                  sizes_variants=[FULL], batch_cap=CAP)

    def test_server_snapshot_names_partition(self, world):
        sharded, _ = self._engines(world, 32)
        srv = qv.MicroBatchServer(sharded,
                                  qv.ServeConfig(max_wait_ms=1.0))
        try:
            assert srv.submit(3).result(timeout=30).shape == (CLASSES,)
            rec = srv.snapshot()["serving"]
            assert rec["partition"] == {"home": 0, "partitions": 2}
        finally:
            srv.close()


class _GateEngine:
    """Jax-free gated engine for deterministic admission tests:
    ``batch_cap=1`` makes every dispatch a single-request batch, and
    ``run`` blocks on ``gate`` — so a test stages EXACT queue contents
    while the first request sits mid-dispatch, then releases the gate
    to drain. ``calls`` records every ``(seeds, variant)`` dispatch."""

    collect_metrics = False
    jitted_fns = ()
    last_stage_s = (0.0, 0.0)

    def __init__(self, n_variants=2):
        self.batch_cap = 1
        self.variants = [[4, 4]] + [[1, 1]] * (n_variants - 1)
        self.gate = threading.Event()
        self.gate.set()
        self.started = threading.Event()
        self.calls = []

    def run(self, seeds, variant=0):
        self.started.set()
        assert self.gate.wait(timeout=10)
        self.calls.append((np.asarray(seeds).copy(), int(variant)))
        out = np.zeros((self.batch_cap, 2), np.float32)
        out[:, 0] = np.asarray(seeds, np.float32)
        return out


class TestTenancy:
    def test_unknown_tenant_rejected(self):
        eng = _GateEngine()
        srv = qv.MicroBatchServer(eng, qv.ServeConfig(max_wait_ms=1.0),
                                  tenants=qv.default_tenant_classes())
        try:
            with pytest.raises(ValueError, match="unknown tenant"):
                srv.submit(1, tenant="nobody")
        finally:
            srv.close()

    def test_tenant_ignored_without_registry(self, engine, reference):
        srv = qv.MicroBatchServer(engine,
                                  qv.ServeConfig(max_wait_ms=1.0))
        try:
            row = srv.submit(3, tenant="whoever").result(timeout=10)
        finally:
            srv.close()
        np.testing.assert_allclose(row, reference[3], rtol=1e-5,
                                   atol=1e-6)
        assert srv.tenant_snapshots() == []

    def test_none_tenant_lands_in_lowest_priority_class(self):
        eng = _GateEngine()
        srv = qv.MicroBatchServer(eng, qv.ServeConfig(max_wait_ms=1.0),
                                  tenants=qv.default_tenant_classes())
        try:
            assert srv.submit(5).result(timeout=10)[0] == 5.0
            snaps = {t["tenant"]: t for t in srv.tenant_snapshots()}
            assert snaps["best_effort"]["requests"] == 1
            assert snaps["best_effort"]["completed"] == 1
            assert snaps["interactive"]["requests"] == 0
            assert snaps["batch"]["requests"] == 0
        finally:
            srv.close()

    def test_share_cap_rejects_flooding_class_only(self):
        # queue_depth=7, weights 4:2:1 -> shares ceil(4)=4 / 2 / 1;
        # shed_at = int(7 * 0.3) = 2. The first best_effort submit is
        # popped into the gated dispatch, two more fill the queue past
        # the threshold with best_effort over its share of 1 — the
        # fourth is shed at the door while interactive still admits.
        eng = _GateEngine()
        eng.gate.clear()
        srv = qv.MicroBatchServer(
            eng, qv.ServeConfig(max_wait_ms=0.5, queue_depth=7,
                                shed_queue_frac=0.3, calm_batches=100),
            tenants=qv.default_tenant_classes())
        try:
            futs = [srv.submit(0, tenant="best_effort")]
            assert eng.started.wait(timeout=10)
            futs += [srv.submit(i, tenant="best_effort")
                     for i in (1, 2)]
            with pytest.raises(qv.OverloadError, match="holds its share"):
                srv.submit(3, tenant="best_effort")
            futs.append(srv.submit(4, tenant="interactive"))
            eng.gate.set()
            assert [f.result(timeout=10)[0] for f in futs] == \
                [0.0, 1.0, 2.0, 4.0]
            snaps = {t["tenant"]: t for t in srv.tenant_snapshots()}
            be = snaps["best_effort"]
            assert be["rejected"] == 1 and be["shed"] == 1
            assert be["requests"] == 3 and be["completed"] == 3
            ia = snaps["interactive"]
            assert ia["rejected"] == 0 and ia["completed"] == 1
        finally:
            eng.gate.set()
            srv.close()

    def test_displacement_evicts_newest_lowest_priority(self):
        # queue_depth=2, shed_queue_frac=1.0 (share cap never fires:
        # shed_at=2 is only reached when the queue is already full).
        # With the dispatch gated and the queue full of best_effort, an
        # interactive submit displaces the NEWEST best_effort request —
        # its future fails typed, the interactive one takes the slot.
        eng = _GateEngine()
        eng.gate.clear()
        srv = qv.MicroBatchServer(
            eng, qv.ServeConfig(max_wait_ms=0.5, queue_depth=2,
                                shed_queue_frac=1.0, calm_batches=100),
            tenants=qv.default_tenant_classes())
        try:
            f0 = srv.submit(0, tenant="best_effort")
            assert eng.started.wait(timeout=10)
            f1 = srv.submit(1, tenant="best_effort")
            f2 = srv.submit(2, tenant="best_effort")   # newest queued
            f3 = srv.submit(3, tenant="interactive")
            with pytest.raises(qv.OverloadError, match="displaced"):
                f2.result(timeout=5)
            eng.gate.set()
            assert f0.result(timeout=10)[0] == 0.0
            assert f1.result(timeout=10)[0] == 1.0
            assert f3.result(timeout=10)[0] == 3.0
            snaps = {t["tenant"]: t for t in srv.tenant_snapshots()}
            be = snaps["best_effort"]
            assert be["displaced"] == 1 and be["shed"] == 1
            assert be["completed"] == 2
            assert snaps["interactive"]["completed"] == 1
            # a best_effort submit into the full queue must NOT
            # displace its own class (no strictly-lower priority left)
            eng.gate.clear()
            eng.started.clear()
            g0 = srv.submit(0, tenant="best_effort")
            assert eng.started.wait(timeout=10)
            g1 = srv.submit(1, tenant="interactive")
            g2 = srv.submit(2, tenant="interactive")
            with pytest.raises(qv.OverloadError, match="queue full"):
                srv.submit(3, tenant="best_effort")
            eng.gate.set()
            for g in (g0, g1, g2):
                assert g.result(timeout=10) is not None
        finally:
            eng.gate.set()
            srv.close()

    def test_shed_grace_orders_quality_shed(self):
        # With the local shed level raised one step, a zero-grace
        # class's batches take the degraded variant while a graced
        # class still dispatches full quality — shed ORDER is policy.
        # calm_batches is huge so the level holds for the whole test.
        eng = _GateEngine(n_variants=2)
        srv = qv.MicroBatchServer(
            eng, qv.ServeConfig(max_wait_ms=0.5, queue_depth=64,
                                shed_queue_frac=1.0, calm_batches=10_000),
            tenants=qv.default_tenant_classes())
        try:
            srv._shed_level = 1
            assert srv.submit(7, tenant="interactive") \
                      .result(timeout=10)[0] == 7.0
            assert srv.submit(8, tenant="best_effort") \
                      .result(timeout=10)[0] == 8.0
            assert srv.submit(9, tenant="batch") \
                      .result(timeout=10)[0] == 9.0
            variants = [v for _, v in eng.calls]
            # interactive: grace 8 swallows the step -> variant 0;
            # best_effort: grace 0 -> variant 1; batch: grace 1 -> 0
            assert variants == [0, 1, 0]
        finally:
            srv.close()

    def test_tenant_snapshots_and_jsonl(self, engine, tmp_path):
        srv = qv.MicroBatchServer(
            engine, qv.ServeConfig(max_wait_ms=2.0, queue_depth=64,
                                   shed_queue_frac=1.0),
            tenants=qv.default_tenant_classes(slo_p99_ms=200.0))
        try:
            futs = [srv.submit(i, tenant=t)
                    for t, k in (("interactive", 3), ("batch", 2),
                                 ("best_effort", 1))
                    for i in range(k)]
            for f in futs:
                assert f.result(timeout=10) is not None
            path = tmp_path / "tenants.jsonl"
            with qm.MetricsSink(str(path)) as sink:
                recs = srv.emit_tenants(sink)
        finally:
            srv.close()
        by = {r["tenant"]: r for r in recs}
        assert sorted(by) == ["batch", "best_effort", "interactive"]
        for name, n in (("interactive", 3), ("batch", 2),
                        ("best_effort", 1)):
            r = by[name]
            assert r["requests"] == n and r["completed"] == n
            assert r["shed"] == 0 and r["queued"] == 0
            assert r["latency"]["n"] == n
            assert r["latency"]["p99_ms"] > 0
        # SLO budget blocks ride only the classes that declare targets
        assert by["interactive"]["slo"]["target_p99_ms"] == 200.0
        assert by["batch"]["slo"]["target_p99_ms"] == 800.0
        assert "slo" not in by["best_effort"]
        lines = [json.loads(l) for l in path.read_text().splitlines()]
        assert [l["kind"] for l in lines] == \
            ["meta", "tenant", "tenant", "tenant"]
        assert sorted(l["tenant"] for l in lines[1:]) == \
            ["batch", "best_effort", "interactive"]

    def test_logits_bit_identical_with_tenancy(self, world):
        # tenancy is host-side accounting + queue discipline ONLY: the
        # seed block and the dispatched program are unchanged, so with
        # the key chain rewound to the same state, calm traffic yields
        # BYTE-identical rows with the registry on vs off — for every
        # class and for the tenant-less default path alike. One
        # engine, one compile (the chain rewind replays the exact same
        # program inputs, as in test_traced_logits_bit_identical).
        model, params, ij, xj, feat = world
        eng = qv.ServeEngine(model, params, (ij, xj), feat,
                             sizes_variants=[FULL, SHED],
                             batch_cap=CAP, seed=13)
        plan = ((3, "interactive"), (9, "batch"), (14, "best_effort"),
                (21, None))
        rows = {}
        for tenants in (None, qv.default_tenant_classes()):
            eng._key = jax.random.key(13)    # rewind the donated chain
            srv = qv.MicroBatchServer(
                eng, qv.ServeConfig(max_wait_ms=1.0, queue_depth=64,
                                    shed_queue_frac=1.0),
                tenants=tenants)
            try:
                for nid, tenant in plan:
                    row = srv.submit(nid, tenant=tenant) \
                             .result(timeout=10)
                    rows.setdefault(nid, []).append(row)
            finally:
                srv.close()
        for nid, (off, on) in rows.items():
            assert off.tobytes() == on.tobytes(), nid
