"""Leak check: repeated sample + gather cycles must not grow buffers.

The TPU analogue of the reference's scripts/check-leak (which watches
CUDA memory across epochs): run many sampler + tiered-feature-lookup +
prefetch cycles and assert that (a) the number of live jax arrays and
(b) host RSS stay bounded — i.e. per-batch work leaks neither device
buffers nor host memory. Runs on the CPU backend so CI can gate on it.

Phase 2 drives the PIPELINED loop 50 batches through a dedup_cold
store plus a donated train step, and additionally pins the EXECUTABLE
caches: the dedup bucketing and the donation path both rely on static
shapes — a shape regression there shows up as per-batch recompiles
(unbounded executable-cache growth), which live-array counts alone
would miss.

Phase 3 repeats the pipelined-lookup loop against an int8-tier store
(dtype_policy="int8"): the per-row scale/zero SIDECARS ride every
gather as extra operands, so this phase pins that they leak neither
executables (the sidecar shapes are as static as the data's) nor live
buffers across 50 batches.

Phase 4 drives 50 pipelined COMPACT-EXCHANGE dist lookups (the
``exchange_cap`` [H, cap] collective, virtual 8-host mesh) alongside
donated compact-exchange dist train steps, alternating duplicate-heavy
batches (one round) with unique-heavy ones (further rounds of the same
[H, cap] exchange): all rounds live in ONE compiled program, so the
executable cache must not grow however many rounds a batch takes.

Phase 5 pins the METRICS path itself: 50 pipelined ``collect=True``
tiered lookups + donated ``collect_metrics=True`` train steps, every
counter vector folded through ``metrics.StepStats`` and snapshots
emitted through a ``MetricsSink`` — the telemetry must add zero new
executables (its counters are static-shape outputs of the same
programs), leak no device buffers (StepStats folds lazily but
bounded), and report zero recompiles via its own watch.

Phase 6 pins the SERVING layer: 200 point requests driven through the
request-coalescing micro-batch server in bursts, so queue pressure
sheds dispatches across the pre-compiled fanout-variant ladder — the
mixed-variant traffic must grow zero executables/buffers and the
server's own recompile watch must stay at zero (overload handling
swaps programs, never compiles one).

Phase 7 pins the TRACING path: 100 served requests with span tracing
AND metrics AND the SLO budget all on. Tracing is host-side only, so
it must add zero executables and zero recompiles; the span ring buffer
is fixed-capacity by construction — the phase runs with a ring smaller
than the span volume so the wrap actually happens, and asserts the
retained span count never exceeds capacity (bounded memory no matter
how long the server runs) and that the Perfetto export round-trips.

Phase 8 pins the COLD-TIER PREFETCH path, PARALLEL-IO staging
included: 50 frontier-ahead prefetched disk-tier steps (publish batch
i+1, gather batch i, jitted compute) with ``workers=2`` staging
workers sharding each publication over the deep-queue extent reader
(``quiver_tpu/io.py``) — zero executable growth, zero recompiles
through the StepStats watch, live arrays flat, and the staging ring
bounded at its capacity (it is sized BELOW the distinct cold rows the
loop touches, so the wraparound eviction path is what gets pinned
UNDER CONCURRENT STAGERS — and the ring buffers must be the SAME
objects at the end: eviction overwrites, never reallocates). After
``close()``, no reader-pool or stager thread survives — the staging
machinery is three thread owners (pipeline worker, stager pool,
reader pool) and all three must reap deterministically.

Phase 9 pins the TELEMETRY HUB: 50 metered lookups + donated metered
train steps with a ``telemetry.TelemetryHub`` fully live — change-point
detectors armed, the advisory re-planner running every 10 steps, a
size-bounded ``MetricsSink`` receiving anomaly/advice records. The hub
is host-side and lazy-folding, so it must add zero executables and
zero recompiles; its per-metric series rings are sized BELOW the step
count so the wrap is exercised (bounded memory for week-long runs),
and the dedup-budget advisor must actually fire (the loop's unique
counts overflow the store's budget — observed, not synthetic).

Phase 10 pins the PROFILER (qt-prof): a full ``StageProfiler`` pass
over the warmed quick-registry entries + the pipeline decomposition —
machine probe taken, every stage timed best-of-N with donation-safe
arg copies, records emitted through a sink and stage-share series fed
into a hub — must add ZERO executables (the pass re-times the already
compiled programs, never builds one), zero recompiles through its own
jitted-fn watch, and leave live-array counts flat (the timing copies
of donated states are transient). The profiler is a separate pass by
construction; this phase is what makes "by construction" a measured
fact.

Phase 11 pins the FAULT layer (qt-chaos): with a seeded ``FaultPlan``
ACTIVELY injecting transient storage errors, slow reads, and a
staging-worker death, 30 prefetched cold-tier lookups + 30 served
requests must grow zero executables and zero recompiles — every
degradation path (retry, per-extent mmap fallback, sync read,
shard-retry) reuses already-compiled programs, and the injections are
counted (``io_retries`` / ``faults_injected`` /
``staging_worker_restarts`` slots), never silent.

Phase 12 pins TAIL SAMPLING (qt-tail): always-on tracing with a
``TailSampler`` attached, driven by bursty serving traffic whose
in-flight trace count EXCEEDS the pending-table capacity — so the
LRU eviction path (the bounded-memory guarantee) is what actually
runs, counted, while every request still completes its keep/drop
decision. The sampler is host-side by construction; this phase makes
it a measured fact: zero executable growth, zero recompiles through
the server's own watch, flat live arrays, the tracer ring within its
capacity, and the pending high-water never past the configured bound.

Phase 13 pins ACTUATION (qt-act): 50 metered int8-tier lookups
spanning three actuated serving-knob swaps (batch fill cap + coalesce
deadline, driven through the Actuator by synthetic advice) and two
online hot-set rotations, each step bit-compared against an UNACTUATED
control store replaying the identical id sequence. The census-first
contract becomes a measured fact: zero executable growth (a swap lands
on an already-counted lattice point; a rotation is a same-shape
functional update), zero recompiles through the engine's watch, rows
bit-identical to the control (for the quantized tiers that is the FMA
decode convention doing its job as rows cross tiers), and live arrays
flat.

Phase 14 pins SHARDED SERVING (qt-shard): 50 serves through a
``ShardedServeEngine`` over a 2-partition ``DistFeature`` store,
alternating duplicate-heavy batches (the compact narrow exchange) with
unique-heavy ones that overflow the per-owner cap (the pmax'd count
of further rounds) — all rounds live in the ONE warmed shard_map
program, so the executable cache must not grow however many rounds a
batch takes, and every batch's logits are
bit-compared against an UNSHARDED single-store engine replaying the
identical seed sequence (same PRNG chain): partitioning changes where
rows live, never what the model computes.

Phase 16 pins TENANCY (qt-capacity): a replayed multi-tenant
flash-crowd trace (``traffic.generate_scenario`` + ``traffic.replay``,
10x best-effort surge) burst through a tenant-registry server with a
tiny admission queue, forcing a shed episode — admission rejects,
displacement, class-pure coalescing, per-class quality shed. Tenancy
is host-side accounting + queue discipline by construction; this phase
makes it measured: zero executable growth, zero recompiles through the
server's watch, flat live arrays, and the per-tenant counters EXACT
against both the replay driver's own per-tenant records and a
hand-fold of the trace (every arrival accounted, nothing double- or
un-counted across the reject/displace/complete paths).

Run: JAX_PLATFORMS=cpu python scripts/check_leak.py
"""

import gc
import os
import resource
import sys
import threading

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# phase 4 needs the virtual 8-host mesh (same setup as tests/conftest.py);
# set before jax import
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

import numpy as np


def rss_mb():
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024


def main():
    import jax
    if os.environ.get("JAX_PLATFORMS"):
        jax.config.update("jax_platforms", os.environ["JAX_PLATFORMS"])
    import jax.numpy as jnp
    import quiver_tpu as qv

    rng = np.random.default_rng(0)
    n, dim = 50_000, 64
    deg = rng.poisson(12, n).astype(np.int64)
    indptr = np.zeros(n + 1, np.int64)
    np.cumsum(deg, out=indptr[1:])
    indices = rng.integers(0, n, int(indptr[-1]))
    topo = qv.CSRTopo(indptr=indptr, indices=indices)
    sampler = qv.GraphSageSampler(topo, [10, 5])
    feat = rng.standard_normal((n, dim)).astype(np.float32)
    store = qv.Feature(device_cache_size=n // 4 * dim * 4, csr_topo=topo)
    store.from_cpu_tensor(feat)

    def cycle(i):
        seeds = jnp.asarray(
            rng.integers(0, n, 512, dtype=np.int32))
        n_id, bs, adjs = sampler.sample(seeds)
        fut = store.prefetch(n_id)
        x = fut.result()
        jax.block_until_ready(x)

    # warmup: compile everything, let caches fill
    for i in range(5):
        cycle(i)
    gc.collect()
    base_arrays = len(jax.live_arrays())
    base_rss = rss_mb()

    for i in range(60):
        cycle(100 + i)
    gc.collect()
    arrays = len(jax.live_arrays())
    rss = rss_mb()

    print(f"live arrays: {base_arrays} -> {arrays}")
    print(f"max RSS: {base_rss:.0f} MB -> {rss:.0f} MB")
    # steady state may wobble by a few in-flight buffers, never grow
    # linearly with cycles (60 cycles x ~10 arrays each would be +600)
    assert arrays <= base_arrays + 16, "device buffer leak"
    assert rss <= base_rss + 256, "host memory leak"
    store.close()
    print("no leak detected (phase 1: prefetch cycles)")

    # ---- phase 2: pipelined dedup lookups + donated train steps ----
    import optax
    from quiver_tpu.models import GraphSAGE
    from quiver_tpu.ops import sample_multihop
    from quiver_tpu.parallel import build_train_step
    from quiver_tpu.parallel.train import (init_state, layers_to_adjs,
                                           masked_feature_gather)
    from quiver_tpu.pipeline import pipelined

    dstore = qv.Feature(device_cache_size=n // 4 * dim * 4, csr_topo=topo,
                        dedup_cold=True, cold_budget=256)
    dstore.from_cpu_tensor(feat)
    host = jnp.asarray(dstore.host_part)

    def dedup_lookup(ids):
        out = dstore._lookup_tiered(dstore.device_part, host, ids,
                                    dstore.feature_order)
        jax.block_until_ready(out)
        return out

    def dup_batches(count, size=2048):
        for i in range(count):
            pool = rng.integers(0, n, size // 4)
            yield jnp.asarray(pool[rng.integers(0, pool.size, size)]
                              .astype(np.int32))

    sizes, bs = [10, 5], 512
    model = GraphSAGE(hidden_dim=32, out_dim=8, num_layers=2, dropout=0.0)
    tx = optax.adam(1e-3)
    indptr_j = jnp.asarray(indptr.astype(np.int32))
    indices_j = jnp.asarray(indices.astype(np.int32))
    feat_j = jnp.asarray(feat)
    labels = jnp.asarray(rng.integers(0, 8, n).astype(np.int32))
    n_id, layers = sample_multihop(indptr_j, indices_j,
                                   jnp.arange(bs, dtype=jnp.int32),
                                   sizes, jax.random.key(0))
    state = init_state(model, tx, masked_feature_gather(feat_j, n_id),
                       layers_to_adjs(layers, bs, sizes),
                       jax.random.key(1))
    step = build_train_step(model, tx, sizes, bs)   # donated state

    def one_step(state, it):
        seeds = jnp.asarray(rng.integers(0, n, bs, dtype=np.int32))
        return step(state, feat_j, None, indptr_j, indices_j, seeds,
                    labels[seeds], jax.random.key(it))

    # warmup: compile the lookup + the step, settle caches
    for _ in pipelined(dedup_lookup, dup_batches(3)):
        pass
    state, _ = one_step(state, 0)
    gc.collect()
    base_arrays = len(jax.live_arrays())
    cache_sizes = {
        "lookup_tiered": dstore._lookup_tiered._cache_size(),
    }

    for i, out in enumerate(pipelined(dedup_lookup, dup_batches(50))):
        state, loss = one_step(state, 100 + i)
    jax.block_until_ready(loss)
    del out
    gc.collect()
    arrays = len(jax.live_arrays())
    grew = dstore._lookup_tiered._cache_size() - cache_sizes[
        "lookup_tiered"]
    print(f"phase 2 live arrays: {base_arrays} -> {arrays}; "
          f"lookup executable-cache growth: {grew}")
    # static shapes => ZERO new executables over 50 same-shape batches
    assert grew == 0, "dedup lookup recompiled mid-loop (shape leak)"
    assert arrays <= base_arrays + 16, \
        "device buffer leak in the pipelined/donated loop"
    dstore.close()
    print("no leak detected (phase 2: pipelined dedup + donated steps)")

    # ---- phase 3: pipelined int8-tier (quantized) lookups ----
    from quiver_tpu.ops import quant

    qstore = qv.Feature(device_cache_size=n // 4 * (dim + 8),
                        csr_topo=topo, dedup_cold=True, cold_budget=256,
                        dtype_policy="int8")
    qstore.from_cpu_tensor(feat)
    qhost = quant.tree_map_tier(jnp.asarray, qstore.host_part)

    def q_lookup(ids):
        out = qstore._lookup_tiered(qstore.device_part, qhost, ids,
                                    qstore.feature_order)
        jax.block_until_ready(out)
        return out

    # warmup: compile the quantized lookup, settle caches
    for _ in pipelined(q_lookup, dup_batches(3)):
        pass
    gc.collect()
    base_arrays = len(jax.live_arrays())
    base_cache = qstore._lookup_tiered._cache_size()

    for out in pipelined(q_lookup, dup_batches(50)):
        pass
    del out
    gc.collect()
    arrays = len(jax.live_arrays())
    grew = qstore._lookup_tiered._cache_size() - base_cache
    print(f"phase 3 live arrays: {base_arrays} -> {arrays}; "
          f"int8 lookup executable-cache growth: {grew}")
    assert grew == 0, \
        "quantized lookup recompiled mid-loop (sidecar shape leak)"
    assert arrays <= base_arrays + 16, \
        "device buffer leak in the int8-tier loop (scale/zero sidecars?)"
    qstore.close()
    print("no leak detected (phase 3: pipelined int8-tier lookups)")

    # ---- phase 4: pipelined compact-exchange dist lookups + steps ----
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from quiver_tpu.parallel import build_dist_train_step

    hosts = 8
    dn, ddim = 400, 16
    dg2h = rng.integers(0, hosts, dn).astype(np.int32)
    dg2h[:hosts] = np.arange(hosts)
    ddeg = rng.integers(1, 7, dn).astype(np.int64)
    dindptr = np.zeros(dn + 1, np.int64)
    np.cumsum(ddeg, out=dindptr[1:])
    dindices = rng.integers(0, dn, int(dindptr[-1]), dtype=np.int32)
    dfeat = rng.standard_normal((dn, ddim)).astype(np.float32)
    dlabels = rng.integers(0, 8, dn).astype(np.int32)

    mesh = Mesh(np.array(jax.devices()), axis_names=("host",))
    dinfo = qv.PartitionInfo(host=0, hosts=hosts, global2host=dg2h)
    dcomm = qv.TpuComm(rank=0, world_size=hosts, mesh=mesh, axis="host")
    # cap small enough that a unique-heavy batch overflows its
    # per-owner buckets (further rounds of the same exchange) while a
    # duplicate-heavy one fits in one — self-checked against the
    # analytic mirror below, so the phase can't silently stop
    # exercising either
    cap = 8
    ddist = qv.DistFeature.from_partition(dfeat, dinfo, dcomm,
                                          exchange_cap=cap)

    def dist_lookup(ids):
        out = ddist[ids]
        jax.block_until_ready(out)
        return out

    size = hosts * 96

    def make_batch(i):
        # even i: duplicate-heavy (16 distinct -> one round);
        # odd i: unique-heavy (~85 distinct per 96-id shard slice,
        # > cap*H=64 request slots -> further rounds)
        if i % 2 == 0:
            pool = rng.integers(0, dn, 16)
            ids = pool[rng.integers(0, pool.size, size)]
        else:
            ids = rng.integers(0, dn, size)
        return ids.astype(np.int32)

    def mixed_batches(count):
        for i in range(count):
            yield jnp.asarray(make_batch(i))

    # the phase's premise, pinned analytically (one shared copy of the
    # rounds logic): every even batch fits one round on every shard,
    # every odd batch overflows on at least one shard (the pmax'd
    # count then takes ALL shards through the further rounds)
    from quiver_tpu.ops.dedup import compact_exchange_slots

    def shard_fits(ids):
        per = ids.reshape(hosts, -1)
        return [compact_exchange_slots(s, cap, hosts, owner=dg2h)
                == cap * hosts for s in per]

    probe_rng_state = rng.bit_generator.state
    assert all(shard_fits(make_batch(0))), "even batch must fit narrow"
    assert not all(shard_fits(make_batch(1))), \
        "odd batch must overflow the cap"
    rng.bit_generator.state = probe_rng_state

    dsizes, dbs = [3, 2], 8
    dmodel = GraphSAGE(hidden_dim=16, out_dim=8, num_layers=2,
                       dropout=0.0)
    dtx = optax.adam(1e-3)
    dindptr_j = jnp.asarray(dindptr.astype(np.int32))
    dindices_j = jnp.asarray(dindices)
    dn_id, dlayers = sample_multihop(dindptr_j, dindices_j,
                                     jnp.arange(dbs, dtype=jnp.int32),
                                     dsizes, jax.random.key(0))
    dstate = init_state(dmodel, dtx,
                        masked_feature_gather(jnp.asarray(dfeat), dn_id),
                        layers_to_adjs(dlayers, dbs, dsizes),
                        jax.random.key(1))
    dstep = build_dist_train_step(dmodel, dtx, dsizes, dbs, mesh,
                                  rows_per_host=ddist._rows_per_host,
                                  exchange_cap=cap)   # donated state
    sharding = NamedSharding(mesh, P("host"))
    labels_j = jnp.asarray(dlabels)

    def one_dist_step(state, it):
        seeds = jax.device_put(jnp.asarray(
            rng.integers(0, dn, hosts * dbs, dtype=np.int32)), sharding)
        return dstep(state, ddist._spmd_feat,
                     dinfo.global2host.astype(jnp.int32),
                     dinfo.global2local, dindptr_j, dindices_j, seeds,
                     labels_j[seeds], jax.random.key(it))

    # warmup: compile the lookup (its one program holds BOTH cond
    # branches) + the donated step, settle caches
    for _ in pipelined(dist_lookup, mixed_batches(4)):
        pass
    dstate, _ = one_dist_step(dstate, 0)
    gc.collect()
    base_arrays = len(jax.live_arrays())
    lookup_fns = list(ddist._lookup_fns.values())
    base_cache = sum(f._cache_size() for f in lookup_fns)

    for i, out in enumerate(pipelined(dist_lookup, mixed_batches(50))):
        dstate, dloss = one_dist_step(dstate, 100 + i)
    jax.block_until_ready(dloss)
    del out
    gc.collect()
    arrays = len(jax.live_arrays())
    assert list(ddist._lookup_fns.values()) == lookup_fns, \
        "compact dist lookup built new programs mid-loop"
    grew = sum(f._cache_size() for f in lookup_fns) - base_cache
    print(f"phase 4 live arrays: {base_arrays} -> {arrays}; "
          f"compact-exchange executable-cache growth: {grew}")
    # both lax.cond branches live in the ONE warmed executable: zero
    # growth even though batches alternate narrow/fallback
    assert grew == 0, \
        "compact exchange recompiled mid-loop (branch/shape leak)"
    assert arrays <= base_arrays + 16, \
        "device buffer leak in the compact-exchange dist loop"
    print("no leak detected (phase 4: pipelined compact-exchange "
          "dist steps)")

    # ---- phase 5: the metrics path leaks nothing either ----
    import tempfile
    import time as _time

    from quiver_tpu import metrics as qm

    mstore = qv.Feature(device_cache_size=n // 4 * dim * 4, csr_topo=topo,
                        dedup_cold=True, cold_budget=256)
    mstore.from_cpu_tensor(feat)
    mhost = jnp.asarray(mstore.host_part)
    stats = qm.StepStats(fold_every=8)
    sink_path = os.path.join(tempfile.mkdtemp(), "metrics.jsonl")
    sink = qm.MetricsSink(sink_path)

    def metered_lookup(ids):
        rows, counters = mstore._lookup_tiered(
            mstore.device_part, mhost, ids, mstore.feature_order,
            False, True)
        jax.block_until_ready(rows)
        stats.add_counters(counters)
        return rows

    mstep = build_train_step(model, tx, sizes, bs,
                             collect_metrics=True)   # donated state
    mstate = init_state(model, tx, masked_feature_gather(feat_j, n_id),
                        layers_to_adjs(layers, bs, sizes),
                        jax.random.key(2))

    def one_metered_step(state, it):
        seeds = jnp.asarray(rng.integers(0, n, bs, dtype=np.int32))
        t0 = _time.perf_counter()
        state, loss, counters = mstep(state, feat_j, None, indptr_j,
                                      indices_j, seeds, labels[seeds],
                                      jax.random.key(it))
        stats.record_step(_time.perf_counter() - t0, counters)
        return state, loss

    # warmup: compile lookup + step, settle caches, arm the watch
    for _ in pipelined(metered_lookup, dup_batches(3)):
        pass
    mstate, _ = one_metered_step(mstate, 0)
    stats.watch_compiles(mstore._lookup_tiered, *mstep.jitted_fns)
    gc.collect()
    base_arrays = len(jax.live_arrays())
    base_cache = mstore._lookup_tiered._cache_size()

    for i, out in enumerate(pipelined(metered_lookup, dup_batches(50))):
        mstate, mloss = one_metered_step(mstate, 100 + i)
        if i % 10 == 9:
            sink.emit_stats(stats)
    jax.block_until_ready(mloss)
    del out
    snap = stats.snapshot()
    sink.close()
    gc.collect()
    arrays = len(jax.live_arrays())
    grew = mstore._lookup_tiered._cache_size() - base_cache
    print(f"phase 5 live arrays: {base_arrays} -> {arrays}; "
          f"metered lookup executable-cache growth: {grew}; "
          f"recompiles seen by StepStats: {snap['recompiles']}")
    assert grew == 0, "metrics-on lookup recompiled mid-loop"
    assert snap["recompiles"] == 0, \
        "metrics-on train step recompiled mid-loop"
    assert arrays <= base_arrays + 16, \
        "device buffer leak in the metrics path (counter vectors?)"
    assert snap["steps"] == 51 and snap["counters"]["frontier_cap"] > 0
    with open(sink_path) as f:
        lines = [l for l in f if l.strip()]
    # 5 data records + the sink's self-attribution meta header
    assert len(lines) == 6, f"expected 6 JSONL records, got {len(lines)}"
    import json as _json
    rec = _json.loads(lines[-1])
    assert rec["kind"] == "step_stats" and "counters" in rec
    assert _json.loads(lines[0])["kind"] == "meta"
    mstore.close()
    print("no leak detected (phase 5: metrics-on pipelined lookups + "
          "donated metered steps)")

    # ---- phase 6: serving — mixed fanout variants, flat executables ----
    # The serving layer's whole overload story rests on the fanout
    # ladder being a BOUNDED pre-compiled set: shedding swaps programs,
    # never compiles one. 200 requests driven through the micro-batch
    # server in bursts (so queue pressure mixes full and shed variants)
    # must grow zero executables, zero live buffers, and report zero
    # recompiles through the server's own StepStats watch.
    from quiver_tpu.serving import MicroBatchServer, ServeConfig, ServeEngine

    sparams = init_state(model, tx, masked_feature_gather(feat_j, n_id),
                         layers_to_adjs(layers, bs, sizes),
                         jax.random.key(3)).params
    engine = ServeEngine(model, sparams, (indptr_j, indices_j), feat_j,
                         sizes_variants=[[10, 5], [4, 2], [2, 1]],
                         batch_cap=64, dedup_gather=True,
                         collect_metrics=True)
    engine.warmup()
    server = MicroBatchServer(engine, ServeConfig(
        max_wait_ms=1.0, queue_depth=256, shed_queue_frac=0.1,
        calm_batches=2))
    # settle: one small wave through every moving part
    for f in [server.submit(int(i)) for i in rng.integers(0, n, 20)]:
        f.result(timeout=60)
    gc.collect()
    base_arrays = len(jax.live_arrays())
    base_cache = sum(f._cache_size() for f in engine.jitted_fns)

    # one 200-request wave: the backlog behind the first [64]-cap batch
    # crosses the shed threshold (256 * 0.1 = 25 queued), so later
    # batches MUST take smaller fanout variants while early/settled
    # ones took the full one — the mixed-variant traffic the phase pins
    futs = [server.submit(int(i)) for i in rng.integers(0, n, 200)]
    for f in futs:
        assert np.isfinite(f.result(timeout=60)).all()
    served = len(futs)
    snap = server.snapshot()
    gc.collect()
    arrays = len(jax.live_arrays())
    grew = sum(f._cache_size() for f in engine.jitted_fns) - base_cache
    mix = snap["serving"]["variant_batches"]
    print(f"phase 6 live arrays: {base_arrays} -> {arrays}; "
          f"serve executable-cache growth: {grew}; "
          f"recompiles seen by the server: {snap['recompiles']}; "
          f"variant mix: {mix}")
    assert served == 200 and snap["serving"]["failed"] == 0
    assert sum(1 for b in mix if b) >= 2, \
        "burst traffic never mixed fanout variants (shed policy dead?)"
    assert grew == 0, "serving recompiled mid-traffic (variant leak)"
    assert snap["recompiles"] == 0, \
        "server's own recompile watch fired mid-traffic"
    assert arrays <= base_arrays + 16, \
        "device buffer leak across 200 served requests"
    assert snap["request"]["count"] >= served
    server.close()
    print("no leak detected (phase 6: 200 served requests across "
          "mixed fanout variants)")

    # ---- phase 7: traced+metered serving — spans on, still flat ----
    # The tracer is host-side: spans must cost zero executables and
    # zero recompiles, and the ring must stay within its capacity (the
    # ring is sized BELOW the span volume here so the wraparound path
    # is what gets pinned, not the easy prefix).
    from quiver_tpu import tracing

    ring_cap = 256      # < the ~400-span volume below => the ring WRAPS
    tracing.enable(capacity=ring_cap)
    server = MicroBatchServer(engine, ServeConfig(
        max_wait_ms=1.0, queue_depth=256, shed_queue_frac=0.1,
        slo_p99_ms=50.0, calm_batches=2))
    # settle (same discipline as phase 6), with tracing already on
    for f in [server.submit(int(i)) for i in rng.integers(0, n, 20)]:
        f.result(timeout=60)
    gc.collect()
    base_arrays = len(jax.live_arrays())
    base_cache = sum(f._cache_size() for f in engine.jitted_fns)

    futs = [server.submit(int(i)) for i in rng.integers(0, n, 100)]
    for f in futs:
        assert np.isfinite(f.result(timeout=60)).all()
    snap = server.snapshot()
    gc.collect()
    arrays = len(jax.live_arrays())
    grew = sum(f._cache_size() for f in engine.jitted_fns) - base_cache
    nspans = len(tracing.get_tracer())
    print(f"phase 7 live arrays: {base_arrays} -> {arrays}; "
          f"traced-serve executable-cache growth: {grew}; "
          f"spans retained: {nspans}/{ring_cap}")
    assert grew == 0, "tracing grew the executable cache (it is "  \
        "host-side only and must not touch the jitted programs)"
    assert snap["recompiles"] == 0, "recompile under traced serving"
    assert arrays <= base_arrays + 16, \
        "device buffer leak across traced serving requests"
    assert nspans == ring_cap, \
        "span ring did not wrap at its fixed capacity (phase premise: " \
        "span volume must exceed the ring)"
    assert snap["slo"]["total"]["requests"] >= 100
    trace_path = os.path.join(tempfile.mkdtemp(), "trace.json")
    exported = tracing.export_chrome_trace(trace_path)
    with open(trace_path) as fh:
        doc = _json.load(fh)
    assert exported == nspans and len(doc["traceEvents"]) >= exported
    server.close()
    tracing.disable()
    tracing.clear()
    print("no leak detected (phase 7: traced+metered serving, bounded "
          "span ring)")

    # ---- phase 8: frontier-ahead cold-tier prefetch, bounded ring ----
    import shutil

    from quiver_tpu.partition import load_disk_tier_store, save_disk_tier

    cn, cdim = 24_000, 32
    ccache = cn // 2
    ccap = 2_048          # << the ~16k distinct cold rows below: WRAPS
    cbatch, ccold = 1_024, 512
    ctmp = tempfile.mkdtemp(prefix="qt_leak_cold_")
    cfeat = rng.standard_normal((cn, cdim)).astype(np.float32)
    save_disk_tier(cfeat, np.arange(cn, dtype=np.int64), ctmp,
                   dtype_policy="int8")
    cstore, _cmeta = load_disk_tier_store(ctmp, hot_rows=ccache,
                                          prefetch_rows=ccap,
                                          workers=2, io_qd=4)
    cpf = cstore._cold_prefetch
    assert cpf.workers == 2 and cpf._stagers is not None, \
        "phase premise: parallel staging (workers>=2) must be active"
    ring_rows_buf = cpf._ring.rows          # identity pinned below
    ring_index_buf = cpf._ring._slot_of
    cw = jnp.asarray(rng.standard_normal((cdim, cdim))
                     .astype(np.float32))
    ccompute = jax.jit(lambda x, w: jnp.sum(jnp.tanh(x @ w)))
    cstats = qm.StepStats(fold_every=8)

    def cold_batch():
        # CONSTANT cold count per batch so the numpy path's
        # power-of-two scatter bucket is one compiled shape
        cold_ids = rng.integers(ccache, cn, ccold)
        hot_ids = rng.integers(0, ccache, cbatch - ccold)
        a = np.concatenate([cold_ids, hot_ids])
        rng.shuffle(a)
        return a.astype(np.int64)

    def cold_cycle(ids_now, ids_next, publish=True):
        rows, counters = cstore.lookup_tiered(ids_now,
                                              collect_metrics=True)
        if publish:
            cstore.stage_frontier(ids_next)
        out = ccompute(rows, cw)
        jax.block_until_ready(out)
        cstats.add_counters(counters)

    # warmup: compile gather + compute, settle caches, arm the watch
    cb = [cold_batch() for _ in range(2)]
    cstore.stage_frontier(cb[0]).result()
    cold_cycle(cb[0], cb[1])
    cold_cycle(cb[1], cb[0])
    cstats.watch_compiles(cstore._gather_cached, cstore._translate,
                          ccompute)
    gc.collect()
    base_arrays = len(jax.live_arrays())
    base_cache = (cstore._gather_cached._cache_size()
                  + ccompute._cache_size())

    ids_next = cold_batch()
    cstore.stage_frontier(ids_next).result()
    for i in range(50):
        ids_now, ids_next = ids_next, cold_batch()
        # every 5th publication deliberately skipped: the NEXT batch
        # then leans on whatever the ring still holds — the sync
        # fallback path is exercised deterministically, not only when
        # the staging worker loses a race
        cold_cycle(ids_now, ids_next, publish=(i % 5 != 4))
        assert cpf._ring.filled <= ccap, "staging ring exceeded capacity"
    gc.collect()
    arrays = len(jax.live_arrays())
    grew = (cstore._gather_cached._cache_size()
            + ccompute._cache_size()) - base_cache
    snap = cstats.snapshot()
    pstats = cpf.stats()
    print(f"phase 8 live arrays: {base_arrays} -> {arrays}; "
          f"prefetched-step executable-cache growth: {grew}; "
          f"recompiles seen by StepStats: {snap['recompiles']}; "
          f"ring filled: {pstats['filled']}/{ccap}, staged "
          f"{pstats['staged_rows']} rows, hit rate "
          f"{pstats['hit_rate']:.2f}")
    assert grew == 0, "cold-tier prefetch recompiled mid-loop"
    assert snap["recompiles"] == 0, \
        "prefetched compute recompiled mid-loop"
    assert arrays <= base_arrays + 16, \
        "device buffer leak in the prefetched cold-tier loop"
    assert cpf._ring.rows is ring_rows_buf \
        and cpf._ring._slot_of is ring_index_buf, \
        "staging ring reallocated (eviction must overwrite in place)"
    assert pstats["filled"] == ccap, \
        "ring never filled — the wraparound path was not exercised " \
        "(phase premise: distinct cold rows must exceed capacity)"
    assert pstats["staged_rows"] > ccap, "ring never wrapped"
    assert pstats["hit_rows"] > 0 and pstats["sync_rows"] > 0, \
        "phase premise: the loop must exercise BOTH ring hits and " \
        "sync fallbacks (capacity < working set)"
    assert snap["counters"]["prefetch_hit_rows"] == pstats["hit_rows"]
    assert pstats["io"]["extents"] > 0, \
        "phase premise: staging must go through the extent reader " \
        "(parallel-IO path), not the mmap compat fallback"
    cstore.close()
    assert cpf.closed, "close() left the prefetch worker running"
    stranded = [t.name for t in threading.enumerate()
                if t.name.startswith(("qt-io-reader", "qt-stager"))]
    assert not stranded, \
        f"close() stranded staging/reader threads: {stranded}"
    shutil.rmtree(ctmp, ignore_errors=True)
    print("no leak detected (phase 8: frontier-ahead cold-tier "
          "prefetch, workers=2 parallel-IO staging, bounded ring, "
          "no stranded reader threads)")

    # ---- phase 9: telemetry hub + detectors + advisor live ----
    # The observe/decide layer must be free: lazy counter folds, ring
    # series, detectors and the advisory re-planner add zero
    # executables, zero recompiles, bounded arrays — and the series
    # rings are sized BELOW the step count so their wraparound (the
    # week-long-run memory bound) is what gets pinned.
    from quiver_tpu.telemetry import PlanContext, TelemetryHub

    RING = 32                 # < 50 loop steps => every series WRAPS
    hub_budget = 256          # the store's dedup budget — the loop's
    #                           ~500-unique batches OVERFLOW it, so the
    #                           advisor has a real shortfall to size
    hstore = qv.Feature(device_cache_size=n // 4 * dim * 4, csr_topo=topo,
                        dedup_cold=True, cold_budget=hub_budget)
    hstore.from_cpu_tensor(feat)
    hhost = jnp.asarray(hstore.host_part)
    hub_sink_path = os.path.join(tempfile.mkdtemp(), "hub.jsonl")
    hub_sink = qm.MetricsSink(hub_sink_path, max_bytes=256_000)
    hub = TelemetryHub(capacity=RING, window=4, fold_every=8,
                       sink=hub_sink,
                       plan=PlanContext(hot_capacity=hstore.cache_rows,
                                        total_rows=n,
                                        dedup_budget=hub_budget))
    hstate = init_state(model, tx, masked_feature_gather(feat_j, n_id),
                        layers_to_adjs(layers, bs, sizes),
                        jax.random.key(4))

    def hub_lookup(ids):
        rows, counters = hstore._lookup_tiered(
            hstore.device_part, hhost, ids, hstore.feature_order,
            False, True)
        jax.block_until_ready(rows)
        hub.observe_counters(counters)
        return rows

    def one_hub_step(state, it):
        seeds = jnp.asarray(rng.integers(0, n, bs, dtype=np.int32))
        t0 = _time.perf_counter()
        state, loss, counters = mstep(state, feat_j, None, indptr_j,
                                      indices_j, seeds, labels[seeds],
                                      jax.random.key(it))
        hub.observe_step(_time.perf_counter() - t0, counters)
        return state, loss

    # warmup: compile lookup + step (mstep is phase 5's — already
    # warm), settle caches, arm the hub's own recompile watch
    hub_lookup(next(iter(dup_batches(1))))
    hstate, _ = one_hub_step(hstate, 0)
    hub.flush()
    hub.watch_compiles(hstore._lookup_tiered, *mstep.jitted_fns)
    gc.collect()
    base_arrays = len(jax.live_arrays())
    base_cache = hstore._lookup_tiered._cache_size()

    for i, ids in enumerate(dup_batches(50)):
        hub_lookup(ids)
        hstate, hloss = one_hub_step(hstate, 200 + i)
        if i % 10 == 9:
            hub.replan()
    jax.block_until_ready(hloss)
    hub.flush()
    gc.collect()
    arrays = len(jax.live_arrays())
    grew = hstore._lookup_tiered._cache_size() - base_cache
    rec_series = hub.series.get("recompiles")
    hit_series = hub.series["hot_hit_rate"]
    print(f"phase 9 live arrays: {base_arrays} -> {arrays}; "
          f"hub-metered lookup executable-cache growth: {grew}; "
          f"hot_hit_rate series {len(hit_series)}/{RING} "
          f"(total {hit_series.total}); advice keys: "
          f"{sorted(hub.advice)}")
    assert grew == 0, "telemetry-hub lookup recompiled mid-loop"
    assert rec_series is not None and float(
        rec_series.values().max()) == 0.0, \
        "hub recompile watch saw executable-cache growth"
    assert not any(a["series"] == "recompiles" for a in hub.anomalies), \
        "spike detector fired on recompiles in a static-shape loop"
    assert arrays <= base_arrays + 16, \
        "device buffer leak in the telemetry-hub loop"
    assert len(hit_series) == RING and hit_series.wrapped, \
        "series ring did not wrap at capacity (phase premise: steps " \
        "must exceed the ring)"
    assert "dedup_budget" in hub.advice and \
        hub.advice["dedup_budget"]["recommended"] > hub_budget, \
        "advisor missed the observed dedup-budget overflow"
    with open(hub_sink_path) as f:
        kinds = [_json.loads(l)["kind"] for l in f if l.strip()]
    assert "advice" in kinds, "advice records never reached the sink"
    hub_sink.close()
    hstore.close()
    print("no leak detected (phase 9: telemetry hub + detectors + "
          "advisor live, wrapped series rings)")

    # ---- phase 10: a full qt-prof pass is free ----
    # The profiler times the SAME compiled programs production runs;
    # a pass over warmed entries must add zero executables, zero
    # recompiles, and leave live arrays flat — donated-state timing
    # copies included.
    from quiver_tpu.profile import StageProfiler, machine_probe

    prof_sink_path = os.path.join(tempfile.mkdtemp(), "prof.jsonl")
    prof_sink = qm.MetricsSink(prof_sink_path)
    prof_hub = TelemetryHub(capacity=32, window=4)
    profiler = StageProfiler(reps=2, probe=machine_probe(quick=True),
                             sink=prof_sink, hub=prof_hub)
    profiler.add_registry(quick=True)
    profiler.add_pipeline()
    profiler.run()                 # warm pass: compiles every stage
    pstats_watch = qm.StepStats()
    pstats_watch.watch_compiles(*profiler.jitted_fns)
    gc.collect()
    base_arrays = len(jax.live_arrays())
    base_cache = sum(f._cache_size() for f in profiler.jitted_fns)

    prof_recs = profiler.run()     # the measured pass
    gc.collect()
    arrays = len(jax.live_arrays())
    grew = sum(f._cache_size() for f in profiler.jitted_fns) - base_cache
    entries = [r["entry"] for r in prof_recs]
    print(f"phase 10 live arrays: {base_arrays} -> {arrays}; "
          f"profile-pass executable-cache growth: {grew}; "
          f"recompiles seen by StepStats: "
          f"{pstats_watch.snapshot()['recompiles']}; "
          f"entries profiled: {entries}")
    assert grew == 0, \
        "the profile pass compiled something (it must only re-time " \
        "the warmed programs)"
    assert pstats_watch.snapshot()["recompiles"] == 0, \
        "profiler recompile watch fired on the second pass"
    assert arrays <= base_arrays + 16, \
        "device buffer leak across a profile pass (donated-arg " \
        "timing copies must be transient)"
    assert "train_pipeline" in entries and "serve_step" in entries
    share_series = [s for s in prof_hub.series
                    if s.startswith("stage_share:")]
    assert share_series, "profile pass fed no stage-share series"
    with open(prof_sink_path) as f:
        kinds = [_json.loads(l)["kind"] for l in f if l.strip()]
    kinds = [k for k in kinds if k != "meta"]    # the sink's header
    assert kinds and all(k == "profile" for k in kinds)
    prof_sink.close()
    print("no leak detected (phase 10: full qt-prof pass over warmed "
          "entries — flat executables, flat arrays)")

    # ---- phase 11: an ACTIVE storage-fault plan is still free ----
    # Chaos must not cost compiles: with a seeded FaultPlan injecting
    # transient read errors (retry ladder), slow reads, and one
    # staging-worker death into the cold-tier path, 30 prefetched
    # lookups + 30 served requests must grow ZERO executables and
    # ZERO recompiles — the fault layer lives entirely on host control
    # paths, and every degradation (retry, mmap fallback, sync read)
    # reuses already-compiled programs.
    from quiver_tpu import faults as qfaults

    ftmp = tempfile.mkdtemp(prefix="qt_leak_faults_")
    ffeat = rng.standard_normal((8_000, 16)).astype(np.float32)
    save_disk_tier(ffeat, np.arange(8_000, dtype=np.int64), ftmp,
                   dtype_policy="int8")
    fstore, _fmeta = load_disk_tier_store(ftmp, hot_rows=4_000,
                                          prefetch_rows=1_024,
                                          workers=2, io_qd=4)
    fcompute = jax.jit(lambda x: jnp.sum(jnp.tanh(x)))
    fstats = qm.StepStats(fold_every=8)

    def fault_batch():
        return np.concatenate([
            rng.integers(4_000, 8_000, 256),
            rng.integers(0, 4_000, 256)]).astype(np.int64)

    fb = [fault_batch() for _ in range(2)]
    fstore.stage_frontier(fb[0])
    rows0, _ = fstore.lookup_tiered(fb[0], collect_metrics=True)
    jax.block_until_ready(fcompute(rows0))
    # pre-fault ground truth for the post-chaos correctness replay
    check_ids = fb[0]
    want = np.asarray(jax.device_get(fstore[check_ids]))
    fserver = MicroBatchServer(engine, ServeConfig(max_wait_ms=1.0))
    for f in [fserver.submit(int(i)) for i in rng.integers(0, n, 10)]:
        f.result(timeout=60)
    fstats.watch_compiles(fstore._gather_cached, fcompute,
                          *engine.jitted_fns)
    gc.collect()
    base_arrays = len(jax.live_arrays())
    base_cache = (fstore._gather_cached._cache_size()
                  + fcompute._cache_size()
                  + sum(f._cache_size() for f in engine.jitted_fns))

    qfaults.install(qfaults.FaultPlan(seed=13, rules={
        "io.read": qfaults.FaultRule("error", errno_name="EINTR",
                                     rate=0.3),
        "io.slow": qfaults.FaultRule("delay", delay_ms=1.0, rate=0.2),
        "prefetch.stager": qfaults.FaultRule("error", exc="runtime",
                                             times=1),
    }))
    try:
        ids_next = fault_batch()
        fstore.stage_frontier(ids_next)
        for i in range(30):
            ids_now, ids_next = ids_next, fault_batch()
            rows, counters = fstore.lookup_tiered(ids_now,
                                                  collect_metrics=True)
            fstore.stage_frontier(ids_next)
            jax.block_until_ready(fcompute(rows))
            fstats.add_counters(counters)
        sfuts = [fserver.submit(int(i))
                 for i in rng.integers(0, n, 30)]
        for f in sfuts:
            assert np.isfinite(f.result(timeout=60)).all()
        injected = qfaults.active().injected
    finally:
        qfaults.disarm()
    gc.collect()
    arrays = len(jax.live_arrays())
    grew = (fstore._gather_cached._cache_size()
            + fcompute._cache_size()
            + sum(f._cache_size() for f in engine.jitted_fns)) \
        - base_cache
    fsnap = fstats.snapshot()
    fc = fsnap["counters"]
    print(f"phase 11 live arrays: {base_arrays} -> {arrays}; "
          f"faulted-loop executable-cache growth: {grew}; "
          f"recompiles: {fsnap['recompiles']}; faults injected: "
          f"{injected}; io_retries: {fc['io_retries']}, "
          f"staging_worker_restarts: {fc['staging_worker_restarts']}")
    assert injected > 0, \
        "phase premise: the armed plan must actually fire"
    assert fc["io_retries"] > 0, \
        "phase premise: the retry ladder must be exercised"
    assert fc["faults_injected"] > 0, \
        "the faults_injected slot never drained the plan's count"
    assert grew == 0, "an active fault plan compiled something"
    assert fsnap["recompiles"] == 0, \
        "recompile watch fired under the fault plan"
    assert arrays <= base_arrays + 16, \
        "device buffer leak under the storage-fault plan"
    # the degraded reads stayed CORRECT: the post-chaos replay must
    # equal the PRE-fault ground truth captured before arming (a
    # faulted path corrupting ring/store state would poison both
    # sides of a read-it-twice check)
    got = np.asarray(jax.device_get(fstore[check_ids]))
    np.testing.assert_array_equal(want, got)
    fserver.close()
    fstore.close()
    shutil.rmtree(ftmp, ignore_errors=True)
    print("no leak detected (phase 11: active storage-fault plan — "
          "flat executables, zero recompiles, faults counted)")

    # ---- phase 12: always-on tail sampling under eviction pressure ----
    # The pending-trace table is sized BELOW the in-flight trace count
    # (bursts of 24 against capacity 8), so the LRU eviction path IS
    # the test: memory stays bounded by construction, evictions are
    # counted, every request still completes its keep/drop decision,
    # and the whole sampler costs zero executables/recompiles (it
    # never enters jit).
    from quiver_tpu.tailsampling import TailSampler

    PENDING_CAP = 8
    ring_cap = 256
    tracing.enable(capacity=ring_cap)
    tail_sink_path = os.path.join(tempfile.mkdtemp(), "tail.jsonl")
    tail_sink = qm.MetricsSink(tail_sink_path)
    sampler = TailSampler(sink=tail_sink, max_pending=PENDING_CAP,
                          latency_source=lambda: 1e9,  # nothing slow
                          head_rate=0.05, seed=3).attach()
    tserver = MicroBatchServer(engine, ServeConfig(
        max_wait_ms=1.0, queue_depth=256, shed_queue_frac=0.5))
    # settle with the sampler already attached
    for f in [tserver.submit(int(i)) for i in rng.integers(0, n, 24)]:
        f.result(timeout=60)
    gc.collect()
    base_arrays = len(jax.live_arrays())
    base_cache = sum(f._cache_size() for f in engine.jitted_fns)

    served = 0
    for _ in range(20):
        futs = [tserver.submit(int(i))
                for i in rng.integers(0, n, 24)]       # 24 > cap of 8
        for f in futs:
            assert np.isfinite(f.result(timeout=60)).all()
        served += len(futs)
    snap = tserver.snapshot()
    st = sampler.stats()
    gc.collect()
    arrays = len(jax.live_arrays())
    grew = sum(f._cache_size() for f in engine.jitted_fns) - base_cache
    print(f"phase 12 live arrays: {base_arrays} -> {arrays}; "
          f"tail-sampled executable-cache growth: {grew}; "
          f"recompiles: {snap['recompiles']}; sampler: "
          f"{st['kept']} kept / {st['dropped']} dropped / "
          f"{st['evicted']} evicted, high-water "
          f"{st['pending_high_water']}/{st['pending_capacity']}")
    assert st["evicted"] > 0, \
        "phase premise: bursts must overflow the pending table"
    assert st["completed"] >= served, \
        "requests completed without a keep/drop decision"
    assert st["pending_high_water"] <= PENDING_CAP, \
        "pending-trace table exceeded its configured capacity"
    assert st["kept"] > 0, \
        "phase premise: the head-sampling floor must keep a few"
    assert len(tracing.get_tracer()) <= ring_cap, \
        "tracer ring exceeded its capacity under tail sampling"
    assert grew == 0, "tail sampling compiled something"
    assert snap["recompiles"] == 0, \
        "recompile watch fired under tail sampling"
    assert arrays <= base_arrays + 16, \
        "device buffer leak under always-on tail sampling"
    with open(tail_sink_path) as f:
        kinds = [_json.loads(l)["kind"] for l in f if l.strip()]
    assert all(k in ("meta", "trace") for k in kinds) and \
        "trace" in kinds, f"unexpected sink kinds: {set(kinds)}"
    sampler.detach()
    tracing.disable()
    tracing.clear()
    tserver.close()
    tail_sink.close()
    print("no leak detected (phase 12: always-on tail sampling with "
          "the pending table under eviction pressure)")

    # ---- phase 13: advice-driven actuation — swaps + rotations, flat ----
    # The qt-act safety contract, measured: an actuated store/server
    # must behave EXACTLY like an unactuated one except for placement.
    # Store A takes three knob swaps (through the Actuator, synthetic
    # advice, fake clock) and two hot-set rotations mid-loop; store B
    # replays the identical 50-step id sequence untouched. Both are
    # int8-tiered, so the bit-compare also pins the FMA decode
    # convention as rotated rows change decode engines (numpy cold
    # tier <-> jitted hot tier).
    from quiver_tpu.actuator import Actuator

    itopoA = qv.CSRTopo(indptr=indptr, indices=indices)
    itopoB = qv.CSRTopo(indptr=indptr, indices=indices)
    act_store = qv.Feature(device_cache_size=n // 4 * dim,
                           csr_topo=itopoA, dtype_policy="int8")
    act_store.from_cpu_tensor(feat)
    ctl_store = qv.Feature(device_cache_size=n // 4 * dim,
                           csr_topo=itopoB, dtype_policy="int8")
    ctl_store.from_cpu_tensor(feat)
    aserver = MicroBatchServer(engine, ServeConfig(
        max_wait_ms=1.0, queue_depth=256, shed_queue_frac=0.5))
    clk = [0.0]
    act = Actuator(clock=lambda: clk[0], cooldown_s=1.0, settle_s=0.0)
    act.attach_server(aserver)
    id_seq = [rng.integers(0, n, 512).astype(np.int32)
              for _ in range(50)]
    # synthetic advice: three swaps across the pre-census'd lattices
    # (fill caps are powers of two under the compiled 64; deadlines on
    # the default lattice), plus one out-of-lattice point that MUST be
    # refused without touching anything
    swap_plan = {10: {"key": "batch_cap", "recommended": 32},
                 20: {"key": "max_wait_ms", "recommended": 0.5},
                 25: {"key": "batch_cap", "recommended": 48},  # refuse
                 30: {"key": "batch_cap", "recommended": 64}}

    # settle both lookup paths and the server, then baseline
    for s in (act_store, ctl_store):
        jax.block_until_ready(s.lookup_tiered(
            jnp.asarray(id_seq[0]), collect_metrics=True)[0])
    for f in [aserver.submit(int(i)) for i in rng.integers(0, n, 20)]:
        f.result(timeout=60)
    gc.collect()
    base_arrays = len(jax.live_arrays())
    base_cache = (sum(f._cache_size() for f in engine.jitted_fns)
                  + act_store._lookup_tiered._cache_size())

    rotations = 0
    for i, ids in enumerate(id_seq):
        clk[0] = float(i)
        if i in swap_plan:
            rec = dict(swap_plan[i], observed={}, reason="phase 13")
            act.tick([rec])
            # the swapped knobs carry real traffic before the next swap
            for f in [aserver.submit(int(v)) for v in ids[:8]]:
                assert np.isfinite(f.result(timeout=60)).all()
        if i in (15, 35):
            order = act_store._order_host()
            cold = np.nonzero(
                order >= act_store.cache_rows)[0][:64]
            act.observe_ids(np.tile(cold, 3), total_rows=n)
            rrec = act.maybe_rotate(act_store, max_rows=64)
            assert rrec is not None and rrec["rotated"] > 0, \
                "phase premise: the rotation must actually rotate"
            rotations += 1
        jids = jnp.asarray(ids)
        rows_a, _ = act_store.lookup_tiered(jids, collect_metrics=True)
        rows_b = ctl_store.lookup_tiered(jids)
        np.testing.assert_array_equal(
            np.asarray(jax.device_get(rows_a)),
            np.asarray(jax.device_get(rows_b)),
            err_msg="actuated rows diverged from the unactuated "
                    "replay")
    snap = aserver.snapshot()
    gc.collect()
    arrays = len(jax.live_arrays())
    grew = (sum(f._cache_size() for f in engine.jitted_fns)
            + act_store._lookup_tiered._cache_size()) - base_cache
    print(f"phase 13 live arrays: {base_arrays} -> {arrays}; "
          f"actuated executable-cache growth: {grew}; "
          f"recompiles seen by the server: {snap['recompiles']}; "
          f"applied {act.applied} / refused {act.refused} "
          f"(rotations {rotations})")
    assert act.applied >= 3 + rotations and rotations == 2, \
        "phase premise: >=3 knob swaps + 2 rotations must land"
    assert act.refused == 1, \
        "phase premise: the out-of-lattice point must be refused"
    assert aserver.knobs()["batch_fill_cap"] == 64 and \
        aserver.knobs()["max_wait_ms"] == 0.5, aserver.knobs()
    assert grew == 0, \
        "actuation compiled something (census safety broken)"
    assert snap["recompiles"] == 0, \
        "recompile watch fired across actuated swaps"
    assert arrays <= base_arrays + 16, \
        "device buffer leak across actuated swaps/rotations"
    aserver.close()
    act_store.close()
    ctl_store.close()
    print("no leak detected (phase 13: 50 metered steps across 3 "
          "actuated knob swaps + 2 hot-set rotations, rows "
          "bit-identical to the unactuated replay)")

    # ---- phase 14: sharded serving — narrow/fallback alternation, ----
    # ---- bit-identical to the unsharded replay ----
    # The qt-shard correctness contract, measured: the serve step over
    # the partitioned store is the SAME computation as the single-store
    # engine (only row placement differs), and its one warmed program
    # serves a batch in one round of the compact exchange or in several.
    from quiver_tpu import metrics as qmetrics
    from quiver_tpu.serving import ServeEngine, ShardedServeEngine

    sh_hosts, sh_cap, sh_bs = 2, 40, 16
    sh_mesh = Mesh(np.array(jax.devices()[:sh_hosts]),
                   axis_names=("host",))
    sh_g2h = (np.arange(dn) % sh_hosts).astype(np.int32)
    sh_info = qv.PartitionInfo(host=0, hosts=sh_hosts,
                               global2host=sh_g2h)
    sh_comm = qv.TpuComm(rank=0, world_size=sh_hosts, mesh=sh_mesh,
                         axis="host")
    sh_dist = qv.DistFeature.from_partition(dfeat, sh_info, sh_comm,
                                            exchange_cap=sh_cap,
                                            collect_metrics=True)
    # the dist-trained params/topology are replicated over the FULL
    # 8-device mesh; re-materialize uncommitted host copies so the
    # 2-device sub-mesh program can place them itself
    sh_params = jax.tree_util.tree_map(
        lambda a: jnp.asarray(np.asarray(a)), dstate.params)
    sh_indptr = jnp.asarray(np.asarray(dindptr_j))
    sh_indices = jnp.asarray(np.asarray(dindices_j))
    sharded_eng = ShardedServeEngine(
        dmodel, sh_params, (sh_indptr, sh_indices), sh_dist,
        sizes_variants=[dsizes], batch_cap=sh_bs,
        collect_metrics=True, seed=5)
    control_eng = ServeEngine(
        dmodel, sh_params, (sh_indptr, sh_indices),
        jnp.asarray(dfeat), sizes_variants=[dsizes], batch_cap=sh_bs,
        seed=5)

    def sh_batch(i):
        # even i: duplicate-heavy — <=4 distinct seeds, so the whole
        # frontier has <=40 uniques: <= the per-owner cap (40) — one
        # round by construction. odd i: 16 distinct seeds, whose 2-hop
        # frontier exceeds the 80 request slots of a round (cap*2), so
        # some owner's bucket overflows — further rounds (pinned at
        # runtime via the per-batch counters below).
        if i % 2 == 0:
            pool = rng.integers(0, dn, 4)
            return pool[rng.integers(0, 4, sh_bs)].astype(np.int32)
        return rng.choice(dn, sh_bs, replace=False).astype(np.int32)

    # warmup: compile both programs, advancing BOTH key chains in
    # lockstep on the same seeds (same engine seed -> same chain, so
    # every later batch stays bit-comparable). FOUR dispatches, not
    # one: the sharded step's donated key buffer settles its placement
    # (uncommitted -> mesh-replicated -> steady) over the first few
    # executions, each a distinct jit signature — the leak gate below
    # measures the steady state, same as ShardedServeEngine.warmup()
    for w in range(4):
        wb = sh_batch(w)
        jax.block_until_ready(sharded_eng.run(wb))
        jax.block_until_ready(control_eng.run(wb))
    gc.collect()
    base_arrays = len(jax.live_arrays())
    sh_fns = list(sharded_eng.jitted_fns) + list(control_eng.jitted_fns)
    base_cache = sum(f._cache_size() for f in sh_fns)

    narrow = fallback = 0
    for i in range(50):
        ids = sh_batch(i)
        got = sharded_eng.run(ids)
        want = control_eng.run(ids)
        np.testing.assert_array_equal(
            np.asarray(got), np.asarray(want),
            err_msg="sharded logits diverged from the unsharded replay")
        c = np.asarray(sharded_eng.last_counters)
        assert c[qmetrics.EXCH_CALLS] > 0
        if i % 2 == 0:
            assert c[qmetrics.EXCH_FALLBACK] == 0, \
                "phase premise: duplicate-heavy batch must stay narrow"
            narrow += 1
        else:
            assert c[qmetrics.EXCH_FALLBACK] > 0, \
                "phase premise: unique-heavy batch must overflow " \
                "the cap"
            fallback += 1
    gc.collect()
    arrays = len(jax.live_arrays())
    grew = sum(f._cache_size() for f in sh_fns) - base_cache
    print(f"phase 14 live arrays: {base_arrays} -> {arrays}; "
          f"sharded-serve executable-cache growth: {grew}; "
          f"batches: {narrow} narrow / {fallback} fallback")
    assert narrow == 25 and fallback == 25
    # every count of rounds runs in the ONE warmed shard_map executable
    assert grew == 0, \
        "sharded serving recompiled mid-loop (branch/shape leak)"
    assert arrays <= base_arrays + 16, \
        "device buffer leak across sharded serves"
    print("no leak detected (phase 14: 50 sharded serves alternating "
          "one-round and overflowing exchanges, logits bit-identical "
          "to the unsharded replay)")

    # ---- phase 15: fused multi-hop walk — 50 train + serve steps, ----
    # ---- walk bit-identical to the split replay ----
    # qt-fuse-deep's leak contract: the whole-ladder fused programs
    # (the fused train step AND the fused serve step over the [3,2]
    # ladder) each hold ONE executable across 50 same-shape
    # dispatches — the in-kernel indptr hops, inter-hop compaction and
    # leaf gather never re-trace — while every dispatch's losses and
    # frontier rows stay bit-identical to the split two-program oracle
    # (per-hop sample kernel + jnp gather) replayed on the same key.
    from quiver_tpu.ops.pallas import fused as _fz
    from quiver_tpu.ops.pallas.fused import (fused_multihop,
                                             fused_multihop_reference,
                                             pad_indices)
    from quiver_tpu.parallel.train import (TrainState,
                                           cross_entropy_logits)
    from quiver_tpu.serving import build_serve_step

    fu_cap = 64
    featf = jnp.asarray(dfeat)
    fidx = pad_indices(dindices_j, fu_cap)
    flabels = jnp.asarray(dlabels)
    fstep = build_train_step(dmodel, dtx, dsizes, dbs,
                             fused_hot_hop=True, fused_row_cap=fu_cap)
    fserve = build_serve_step(dmodel, dsizes, dbs, fused_hot_hop=True,
                              fused_row_cap=fu_cap)

    f_nid, f_layers = sample_multihop(dindptr_j, dindices_j,
                                      jnp.arange(dbs, dtype=jnp.int32),
                                      dsizes, jax.random.key(0))
    f_state0 = init_state(dmodel, dtx,
                          masked_feature_gather(featf, f_nid),
                          layers_to_adjs(f_layers, dbs, dsizes),
                          jax.random.key(2))
    st_f = jax.tree_util.tree_map(jnp.array, f_state0)   # donated copy
    st_o = f_state0

    def f_oracle(state, seeds, key):
        # the split replay of the fused train step's loss: identical
        # PRNG stream (per-hop fold_in), identical dropout derivation
        def loss_of(p):
            n_id, layers, _ = fused_multihop_reference(
                dindptr_j, fidx, seeds, featf, dsizes, key,
                row_cap=fu_cap, rng="hash", interpret=True)
            x = masked_feature_gather(featf, n_id, None)
            adjs = layers_to_adjs(layers, dbs, dsizes)
            logits = dmodel.apply(
                p, x, adjs, train=True,
                rngs={"dropout": jax.random.fold_in(key, 1000)})
            return cross_entropy_logits(logits[:dbs], flabels[seeds])
        loss, grads = jax.value_and_grad(loss_of)(state.params)
        updates, opt = dtx.update(grads, state.opt_state, state.params)
        return TrainState(optax.apply_updates(state.params, updates),
                          opt, state.step + 1), loss

    f_oracle = jax.jit(f_oracle)

    def f_batch():
        return jnp.asarray(
            rng.choice(dn, dbs, replace=False).astype(np.int32))

    def f_iter(skey, serve_params):
        seeds = f_batch()
        # host-side mirror of the serve step's internal split (the key
        # buffer itself is donated to the program); the train chain
        # folds off the same sub-key so the two legs decorrelate
        _, sub = jax.random.split(skey)
        tkey = jax.random.fold_in(sub, 777)
        nxt, logits = fserve(serve_params, skey, featf, None,
                             dindptr_j, dindices_j, seeds)
        jax.block_until_ready(logits)
        # the walk the serve step just ran, fused vs split, bit-exact
        g_nid, g_layers, g_x = fused_multihop(
            dindptr_j, fidx, seeds, featf, dsizes, sub,
            row_cap=fu_cap, rng="hash", interpret=True)
        w_nid, w_layers, w_x = fused_multihop_reference(
            dindptr_j, fidx, seeds, featf, dsizes, sub,
            row_cap=fu_cap, rng="hash", interpret=True)
        assert np.asarray(g_nid).tobytes() == \
            np.asarray(w_nid).tobytes(), \
            "fused frontier diverged from the split replay"
        v = np.asarray(g_nid) >= 0
        assert np.asarray(g_x)[v].tobytes() == \
            np.asarray(w_x)[v].tobytes(), \
            "fused rows diverged from the split replay"
        return nxt, seeds, tkey

    # warmup: compile all four programs (fused step, oracle step,
    # fused serve, the standalone walk pair) and let the serve step's
    # donated key buffer settle its placement (uncommitted -> steady
    # donation chain takes a few dispatches, same as phase 14)
    skey = jax.random.key(21)
    for _ in range(3):
        skey, wseeds, wtkey = f_iter(skey, st_o.params)
    st_f, _ = fstep(st_f, featf, None, dindptr_j, dindices_j, wseeds,
                    flabels[wseeds], wtkey)
    st_o, _ = f_oracle(st_o, wseeds, wtkey)
    gc.collect()
    base_arrays = len(jax.live_arrays())
    f_fns = (list(fstep.jitted_fns) + list(fserve.jitted_fns)
             + [_fz._multihop_impl])
    base_cache = sum(f._cache_size() for f in f_fns)

    for i in range(50):
        skey, seeds, tkey = f_iter(skey, st_o.params)
        st_f, loss_f = fstep(st_f, featf, None, dindptr_j, dindices_j,
                             seeds, flabels[seeds], tkey)
        st_o, loss_o = f_oracle(st_o, seeds, tkey)
        assert np.asarray(loss_f).tobytes() == \
            np.asarray(loss_o).tobytes(), \
            f"fused loss diverged from the split replay at step {i}"
    for a, b in zip(jax.tree_util.tree_leaves(st_f.params),
                    jax.tree_util.tree_leaves(st_o.params)):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes(), \
            "fused params drifted from the split replay after 50 steps"
    gc.collect()
    arrays = len(jax.live_arrays())
    grew = sum(f._cache_size() for f in f_fns) - base_cache
    print(f"phase 15 live arrays: {base_arrays} -> {arrays}; "
          f"fused multi-hop executable-cache growth: {grew}")
    assert grew == 0, \
        "fused multi-hop walk recompiled mid-loop (shape/key leak)"
    assert arrays <= base_arrays + 16, \
        "device buffer leak across fused multi-hop train+serve steps"
    print("no leak detected (phase 15: 50 fused multi-hop train+serve "
          "steps, losses and rows bit-identical to the split replay)")

    # ---- phase 16: replayed multi-tenant load across a shed episode ----
    # qt-capacity's leak contract: tenancy (class registry, weighted
    # admission shares, displacement, class-pure shed batching) is
    # host-side accounting + queue discipline ONLY. A flash-crowd
    # trace replayed at a burst speed that swamps a tiny admission
    # queue must shed — and still grow zero executables, zero
    # recompiles, flat arrays, with per-tenant counters EXACT against
    # the replay driver's records and a hand-fold of the trace.
    from quiver_tpu import traffic
    from quiver_tpu.serving import default_tenant_classes

    tserver = MicroBatchServer(
        engine,                       # phase 6's warmed 3-variant engine
        ServeConfig(max_wait_ms=2.0, queue_depth=16,
                    shed_queue_frac=0.25, calm_batches=2,
                    slo_p99_ms=50.0),
        tenants=default_tenant_classes(slo_p99_ms=50.0))
    # settle: one calm wave through every class (compiles nothing new;
    # the registry reuses phase 6's programs untouched)
    for f in [tserver.submit(int(i), tenant=t)
              for i, t in zip(rng.integers(0, n, 9),
                              ["interactive", "batch", "best_effort"] * 3)]:
        f.result(timeout=60)
    gc.collect()
    base_arrays = len(jax.live_arrays())
    base_cache = sum(f._cache_size() for f in engine.jitted_fns)
    settle = {t["tenant"]: dict(t) for t in tserver.tenant_snapshots()}

    trace = traffic.generate_scenario(
        "flash_crowd", 40.0, 25.0, n, seed=17,
        flash_tenant="best_effort", flash_x=10.0)
    # speed 500 compresses the 40 s trace into ~80 ms of offered wall:
    # ~1000 arrivals against a depth-16 queue GUARANTEES the shed
    # episode (rejects + displacement), timing-independently
    rep = traffic.replay(trace, tserver, speed=500.0)
    snap = tserver.snapshot()
    tenants_now = {t["tenant"]: t for t in tserver.tenant_snapshots()}
    # close() first: the pipeline's in-flight batch slots hold the
    # last dispatches' device buffers until the executor drains
    tserver.close()
    gc.collect()
    arrays = len(jax.live_arrays())
    grew = sum(f._cache_size() for f in engine.jitted_fns) - base_cache

    # hand-fold the trace: per-tenant offered counts are a pure
    # function of the generated arrays
    fold = {name: 0 for name in trace["tenants"]}
    for i in np.asarray(trace["tenant"]).tolist():
        fold[trace["tenants"][i]] += 1
    shed_total = 0
    for name in trace["tenants"]:
        r = rep["tenants"][name]
        base_c = settle[name]
        t = tenants_now[name]
        assert r["offered"] == fold[name], \
            f"replay offered[{name}] drifted from the trace hand-fold"
        # every arrival accounted exactly once in the replay record
        assert (r["completed"] + r["rejected"] + r["deadline_expired"]
                + r["failed"]) == r["offered"], \
            f"replay records leak arrivals for {name}"
        # server counters (minus the settle wave) == replay counters:
        # submit-raise rejects + displaced futures both classify as
        # rejected on the driver side
        assert (t["completed"] - base_c["completed"]) == \
            r["completed"], f"completed drift for {name}"
        assert (t["rejected"] + t["displaced"] - base_c["rejected"]
                - base_c["displaced"]) == r["rejected"], \
            f"reject/displace drift for {name}"
        assert (t["deadline_expired"] - base_c["deadline_expired"]) \
            == r["deadline_expired"], f"deadline drift for {name}"
        assert (t["failed"] - base_c["failed"]) == r["failed"], \
            f"failure drift for {name}"
        shed_total += r["rejected"]
    be_shed = rep["tenants"]["best_effort"]["rejected"]
    ia_shed = rep["tenants"]["interactive"]["rejected"]
    mix = snap["serving"]["variant_batches"]
    print(f"phase 16 live arrays: {base_arrays} -> {arrays}; "
          f"tenant-replay executable-cache growth: {grew}; "
          f"recompiles: {snap['recompiles']}; shed {shed_total} "
          f"(best_effort {be_shed}, interactive {ia_shed}); "
          f"variant mix: {mix}")
    assert shed_total > 0, \
        "the burst never shed (phase premise: the queue must overflow)"
    assert be_shed >= ia_shed, \
        "shed order inverted: best_effort must absorb before interactive"
    assert grew == 0, \
        "tenancy recompiled mid-replay (it must reuse the warmed " \
        "programs untouched)"
    assert snap["recompiles"] == 0, \
        "server's recompile watch fired under tenant-registry traffic"
    assert arrays <= base_arrays + 16, \
        "device buffer leak across the replayed multi-tenant episode"
    print("no leak detected (phase 16: replayed multi-tenant flash "
          "crowd across a shed episode, per-tenant counters exact)")


if __name__ == "__main__":
    main()
