"""Benchmark: sampled-edges/second (SEPS) on an ogbn-products-scale graph.

Metric of record matches the reference (SEPS, benchmarks/sample/
bench_sampler.py:14-16): ogbn-products GraphSAGE fanout [15, 10, 5],
batch 1024. Baseline = single-GPU Quiver UVA 34.29M SEPS
(docs/Introduction_en.md:38-45, BASELINE.md).

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...,
"device": {"platform", "kind", "count"}}.

It needs the chip: where JAX finds no TPU the bench exits 1 and prints
no metric. Any phase that fails raises, so a run that printed a line ran
all of it. ``--platform cpu`` is the harness smoke: a reduced scale on
the CPU backend, and every key of its line carries a ``cpu_`` prefix so
that no CPU figure can be read under a device metric's name.

The synthetic graph is generated ON DEVICE (skewed lognormal degrees,
products-like scale) — no multi-hundred-MB host->device transfer.

Scale knobs (env): QT_BENCH_NODES, QT_BENCH_AVG_DEG, QT_BENCH_BATCHES,
QT_BENCH_BATCH.
"""

import argparse
import json
import os
import sys
import time

BASELINE_SEPS = 34.29e6   # reference Quiver UVA, 1 GPU, products [15,10,5]

METRIC = ("sampled-edges/sec (ogbn-products-scale, "
          "fanout [15,10,5], batch 1024)")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--platform", choices=("cpu",), default=None,
                    help="cpu: the harness smoke at a reduced scale; "
                         "every key it prints is prefixed cpu_")
    cpu_smoke = ap.parse_args(argv).platform == "cpu"
    if cpu_smoke:
        # the sharded-serve figure needs a 2-device host mesh; the
        # flags must land before jax is imported
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=2")
    import jax
    from quiver_tpu.utils.compile_cache import place_compile_cache
    place_compile_cache()
    dev = jax.devices()[0]
    if dev.platform != ("cpu" if cpu_smoke else "tpu"):
        print(f"bench.py: needs a TPU, JAX found {dev.platform!r} (the "
              "harness smoke is --platform cpu)", file=sys.stderr)
        sys.exit(1)
    if cpu_smoke:
        # reduced scale: this mode exists to prove the harness runs, not
        # to produce a comparable number
        defaults = dict(nodes=200_000, deg=10, batches=8)
    else:
        defaults = dict(nodes=2_450_000, deg=25, batches=192)

    n_nodes = int(os.environ.get("QT_BENCH_NODES", defaults["nodes"]))
    avg_deg = int(os.environ.get("QT_BENCH_AVG_DEG", defaults["deg"]))
    # one epoch of ogbn-products train split (196k seeds / batch 1024)
    batches = int(os.environ.get("QT_BENCH_BATCHES", defaults["batches"]))
    batch = int(os.environ.get("QT_BENCH_BATCH", 1024))
    # the epoch permutation supplies at most n_nodes seeds
    batches = min(batches, max(n_nodes // batch, 1))
    sizes = [15, 10, 5]

    import jax.numpy as jnp
    from quiver_tpu import tracing
    from quiver_tpu.ops import (sample_multihop, reshuffle_csr, edge_row_ids,
                                as_index_rows, as_index_rows_overlapping,
                                exact_bucket_meta)
    # rotation row layout: "overlap" = one gather/seed, 2x index memory;
    # "pair" = two gathers/seed; "both" (default) measures the two and
    # reports the better as the metric of record, layout labeled
    layout_env = os.environ.get("QT_BENCH_LAYOUT", "both")
    # per-epoch row-order refresh: "sort" = exact uniform shuffle
    # (permute_csr), "butterfly" = the ~40x cheaper masked swap network.
    # "both" (default) measures both and reports the better, labeled —
    # legitimate because accuracy parity is recorded for BOTH arms
    # (benchmarks/accuracy_parity.py 4-arm run, docs/introduction.md)
    shuffle_env = os.environ.get("QT_BENCH_SHUFFLE", "both")

    key = jax.random.key(0)

    # ---- build the graph on device ----
    @jax.jit
    def make_degrees(k):
        ln = jax.random.normal(k, (n_nodes,)) * 1.0 + jnp.log(float(avg_deg))
        deg = jnp.clip(jnp.exp(ln).astype(jnp.int32), 0, 10_000)
        # products-scale edge counts (~100M) fit comfortably in int32
        indptr = jnp.concatenate([
            jnp.zeros((1,), jnp.int32), jnp.cumsum(deg)])
        return indptr

    indptr = make_degrees(jax.random.fold_in(key, 1))
    e = int(indptr[-1])

    @jax.jit
    def make_indices(k):
        return jax.random.randint(k, (e,), 0, n_nodes, dtype=jnp.int32)

    indices = make_indices(jax.random.fold_in(key, 2))
    jax.block_until_ready(indices)

    row_ids = jax.jit(edge_row_ids, static_argnums=1)(indptr, e)
    jax.block_until_ready(row_ids)

    # degree-bucket split for the wide-exact hub budget: computed once
    # per graph (training caches it on CSRTopo), so it sits outside the
    # timed region like the exact layout views
    hub_frac = exact_bucket_meta(indptr).frac

    # graph arrays go in as jit *arguments*: closed-over device arrays are
    # embedded in the HLO as literal constants (~400MB of indices at this
    # scale). The whole timed region is ONE device dispatch, so no
    # per-batch host round-trip sits in it, and measures a full epoch the
    # way training runs it: one per-epoch row re-shuffle (rotation
    # sampling's freshness source) + `batches` sample_multihop calls.
    _epochs = {}

    def make_epoch(n_batches, method, layout, shuffle):
        # cache per config: the winner's re-measurement must reuse the
        # already-compiled program, not build a fresh jit closure
        ck = (n_batches, method, layout, shuffle)
        if ck in _epochs:
            return _epochs[ck]

        @jax.jit
        def run_epoch(indptr, indices, row_ids, key, rows=None):
            kperm, kseed, kbatch = jax.random.split(key, 3)
            stride = None
            if method in ("rotation", "window"):
                permuted = reshuffle_csr(indices, row_ids, kperm,
                                         method=shuffle)
                if layout == "overlap":
                    rows = as_index_rows_overlapping(permuted)
                    stride = 128
                else:
                    rows = as_index_rows(permuted)
            elif method == "exact" and rows is not None:
                # the wide-fetch exact path: ``rows`` is a layout view
                # of the UN-shuffLED indices built OUTSIDE the timed
                # epoch (training builds it once per run, so the epoch
                # must not re-pay it; the rotation arms' in-epoch
                # reshuffle is genuine per-epoch work)
                permuted = indices
                stride = 128 if layout == "overlap" else None
            else:
                permuted, rows = indices, None
            # epoch batching the way training runs it: a fresh
            # permutation of the node ids sliced into batches (seeds
            # unique within a batch)
            seed_perm = jax.random.permutation(kseed, n_nodes)[
                : n_batches * batch].astype(jnp.int32).reshape(
                    n_batches, batch)

            def body(total, i):
                seeds = jax.lax.dynamic_index_in_dim(
                    seed_perm, i, axis=0, keepdims=False)
                _, layers = sample_multihop(indptr, permuted, seeds, sizes,
                                            jax.random.fold_in(kbatch, i),
                                            method=method,
                                            indices_rows=rows,
                                            indices_stride=stride,
                                            seeds_dense=True,
                                            hub_frac=(hub_frac
                                                      if method == "exact"
                                                      else None))
                edges = sum(l.edge_count.astype(jnp.int32) for l in layers)
                return total + edges, None
            total, _ = jax.lax.scan(
                body, jnp.int32(0), jnp.arange(n_batches, dtype=jnp.int32))
            return total

        _epochs[ck] = run_epoch
        return run_epoch

    exact_rows = {}
    # per-batch wall of the newest FULL sampling epoch; the headline
    # selection code below copies it into the sample-stage row at the
    # points where a run actually BECOMES the headline, so the
    # stage_ms block always attributes the arm of record (a losing
    # full-epoch window probe must not leave its wall behind)
    _full_epoch = {}

    def measure(n_batches, method, layout, salt, shuffle):
        run = make_epoch(n_batches, method, layout, shuffle)
        extra = ()
        if method == "exact":
            # one-time layout view (amortized in real training); built
            # outside the timed region
            if layout not in exact_rows:
                f = (as_index_rows_overlapping if layout == "overlap"
                     else as_index_rows)
                exact_rows[layout] = jax.block_until_ready(
                    jax.jit(f)(indices))
            extra = (exact_rows[layout],)
        jax.block_until_ready(run(indptr, indices, row_ids,
                                  jax.random.fold_in(key, 100 + salt),
                                  *extra))
        t0 = time.perf_counter()
        total_edges = int(run(indptr, indices, row_ids,
                              jax.random.fold_in(key, 200 + salt),
                              *extra))
        dt = time.perf_counter() - t0
        # timeline hook (QT_TRACE): the whole timed epoch is ONE device
        # dispatch, so one span per measured arm is the honest shape
        tracing.record("bench.epoch", t0, dt,
                       args={"method": method, "layout": layout,
                             "shuffle": shuffle, "batches": n_batches,
                             "edges": total_edges})
        if n_batches == batches:
            _full_epoch["ms_per_batch"] = dt / n_batches * 1e3
        return total_edges / dt

    # metric of record: rotation mode, full epoch (accuracy parity with
    # exact mode for every candidate arm: benchmarks/accuracy_parity.py,
    # docs/introduction.md). With layout/shuffle "both", measure the
    # candidate configs and report the better production config, labeled
    # (pair+butterfly is skipped: dominated by overlap+butterfly).
    layouts = ["pair", "overlap"] if layout_env == "both" else [layout_env]
    if shuffle_env == "both":
        # butterfly arm runs on overlap (pair+butterfly is dominated:
        # pair only adds gather traffic) unless a layout was pinned
        bf_layout = "overlap" if layout_env == "both" else layout_env
        cands = [(lay, "sort") for lay in layouts] + \
                [(bf_layout, "butterfly")]
    else:
        cands = [(lay, shuffle_env) for lay in layouts]
    by_cfg = {cfg: measure(batches, "rotation", cfg[0], salt, shuffle=cfg[1])
              for salt, cfg in enumerate(cands)}
    (layout, shuffle), _sel = max(by_cfg.items(), key=lambda kv: kv[1])
    # re-measure ONLY the winning config and report that re-measurement
    # as the headline: max-of-noisy-arms is biased upward (winner's
    # curse); the fresh run is an unbiased estimate of the chosen
    # config. Cheap — the winner is already compiled.
    seps = (measure(batches, "rotation", layout, 50, shuffle=shuffle)
            if len(by_cfg) > 1 else _sel)
    # the headline's sample wall: either the re-measurement just taken
    # or (single-candidate sweep) the sweep's own full-epoch run
    sample_ms_per_batch = _full_epoch.get("ms_per_batch", 0.0)
    rotation_seps = seps          # the rotation row of the per-mode block
    # secondary figures on a shorter epoch slice (clamped to the seeds
    # the node count can supply): exact i.i.d. mode, and window mode
    # (same row fetches as rotation, exact i.i.d. subsets of each
    # seed's shuffled >=129-entry window)
    side_batches = min(max(batches // 6, 4), max(n_nodes // batch, 1))
    exact_seps = measure(side_batches, "exact", layout, 10, shuffle="sort")
    # window's secondary figure stays pinned to the sort shuffle for
    # cross-round comparability (butterfly is legal for unweighted
    # window since the hub random-anchor landed, but the headline sweep
    # already covers the butterfly arm)
    window_seps = measure(side_batches, "window", layout, 11,
                          shuffle="sort")
    # window draws i.i.d. subsets at rotation's fetch cost — the
    # statistically STRONGER mode. If its short-epoch side figure beats
    # the rotation winner, measure it at full epoch length and let it
    # take the headline, labeled. (Accuracy parity is recorded for all
    # arms; the extra full-epoch run is only paid when window leads.)
    mode = "rotation"
    if window_seps > seps:
        window_full = measure(batches, "window", layout, 60,
                              shuffle=shuffle)
        if window_full > seps:
            # same winner's-curse discipline as the rotation sweep: the
            # selection run decided, a FRESH run (already compiled) is
            # the reported headline
            mode = "window"
            seps = measure(batches, "window", layout, 61, shuffle=shuffle)
            sample_ms_per_batch = _full_epoch.get("ms_per_batch", 0.0)

    # ---- feature-gather figure: the BANDWIDTH half of the paper ----
    # (SEPS tracks sampling latency; this tracks tiered feature
    # collection.) A duplicate-heavy, frontier-shaped batch through the
    # fused dedup tiered lookup: 25% HBM cache, cold tier pinned to
    # host where the backend supports it (loud numpy->device fallback
    # on the CPU smoke), dedup_cold on — the production path a split
    # train loop drives. Frontier-slot rows/sec.
    def measure_feature_gather():
        import numpy as _np

        import quiver_tpu as _qv
        f_rows = int(min(n_nodes, 400_000))
        f_dim = 64
        f_batch = int(min(4 * batch, f_rows))
        rngf = _np.random.default_rng(7)
        feat = rngf.standard_normal((f_rows, f_dim)).astype(_np.float32)
        store = _qv.Feature(device_cache_size=(f_rows // 4) * f_dim * 4,
                            host_placement="offload", dedup_cold=True)
        store.from_cpu_tensor(feat)
        host = (store._host_offload if store._host_offload is not None
                else jnp.asarray(store.host_part))
        batches_f = []
        for i in range(8):
            pool = rngf.choice(f_rows, size=max(f_batch // 8, 1),
                               replace=False)
            batches_f.append(jnp.asarray(
                pool[rngf.integers(0, pool.size, f_batch)]))
        jax.block_until_ready(store._lookup_tiered(
            store.device_part, host, batches_f[0], store.feature_order))
        t0 = time.perf_counter()
        for a in batches_f:
            r = store._lookup_tiered(store.device_part, host, a,
                                     store.feature_order)
        jax.block_until_ready(r)
        dt = time.perf_counter() - t0
        rps = f_batch * len(batches_f) / dt
        tracing.record("bench.feature_gather", t0, dt,
                       args={"batches": len(batches_f),
                             "rows_per_s": round(rps, 1)})
        # ---- OBSERVED device counters over the same batches (untimed
        # pass): the telemetry the analytic mirrors below only predict —
        # actual hot-tier hit rate and frontier dup factor out of the
        # fused lookup's own classification masks (quiver_tpu.metrics)
        from quiver_tpu import metrics as qmetrics
        tc0 = time.perf_counter()
        total_c = None
        counter_vecs = []       # per-batch vectors — the telemetry
        for a in batches_f:     # hub's advisory replan feeds on these
            _, c = store._lookup_tiered(store.device_part, host, a,
                                        store.feature_order, False, True)
            counter_vecs.append(c)
            total_c = c if total_c is None else \
                qmetrics.merge_counters(total_c, c)
        observed = qmetrics.derive(total_c)
        # the counter pass's span carries the derived ratios — the
        # observed telemetry lands ON the timeline next to the timed arm
        tracing.record("bench.observed_counters", tc0,
                       time.perf_counter() - tc0, args=dict(observed))
        counts = qmetrics.reduce_counters(total_c)
        observed_cold_rows = (counts[qmetrics.COLD_ROWS]
                              / len(batches_f))
        # ---- bytes/batch, the currency feature collection is paid in
        # (host tier + what a cross-host exchange of this batch ships).
        # Analytic, via the ONE shared mirror of lookup_tiered's branch
        # structure (quant.dedup_rows_read); the jaxpr-level pin for
        # the same bound lives in tests/test_quant.py / test_feature.py
        from quiver_tpu.ops import quant as _quant
        row_b = _quant.row_bytes(f_dim, store.dtype_policy["cold"], 4)
        # no csr_topo on this store -> ids are storage rows directly,
        # so the cold-slot count is a simple threshold test
        host_bytes = sum(
            _quant.dedup_rows_read(
                a, cold_count=int((_np.asarray(jax.device_get(a))
                                   >= store.cache_rows).sum())) * row_b
            for a in batches_f)
        # exchange figure: the SPMD all_to_all pair for this batch
        # shape ships one int32 request + one payload row per slot
        exch_bytes = f_batch * (4 + row_b)
        # compact-exchange figure: the SAME batches through the
        # dedup'd [H, cap] layout (comm.dist_lookup_local with
        # exchange_cap) ship cap*H useful slots per direction — or the
        # full batch on overflow. One shared analytic mirror of the
        # branch logic (ops.dedup.compact_exchange_slots); modeled at
        # the tier-1 virtual mesh's H=8 with a balanced hash partition.
        from quiver_tpu.comm import default_exchange_cap
        from quiver_tpu.ops.dedup import compact_exchange_slots
        exch_hosts = 8
        cap = default_exchange_cap(f_batch, exch_hosts)
        compact_bytes = sum(
            compact_exchange_slots(a, cap, exch_hosts) * (4 + row_b)
            for a in batches_f) / len(batches_f)
        # what the advisory replan needs to compare observation against
        # the plan: the store's actual hot capacity and the EFFECTIVE
        # dedup budget its lookups ran with (dedup_cold=True resolves
        # to the default per-batch budget)
        from quiver_tpu.ops.quant import default_cold_budget
        dedup_budget = None
        if store.dedup_cold:
            dedup_budget = (int(store.dedup_cold)
                            if not isinstance(store.dedup_cold, bool)
                            else default_cold_budget(f_batch))
        plan_facts = {"hot_capacity": int(store.cache_rows),
                      "total_rows": f_rows,
                      "dedup_budget": dedup_budget}
        # modeled bytes the timed loop moved per batch — the roofline
        # numerator for the gather stage: output rows written + hot
        # rows read + (dedup'd) cold-tier bytes + the frontier-id
        # index buffer. Divided by the timed wall and the machine
        # probe's random-gather peak, this is gather_efficiency —
        # "how far from this box's limits the tiered gather runs"
        hot_rows_pb = counts[qmetrics.HOT_ROWS] / len(batches_f)
        gather_bytes_pb = (f_batch * f_dim * 4           # output write
                           + hot_rows_pb * f_dim * 4     # hot reads
                           + host_bytes / len(batches_f)  # cold reads
                           + f_batch * 4)                # frontier ids
        gather_ms_pb = dt / len(batches_f) * 1e3
        return (rps, host_bytes / len(batches_f), exch_bytes, cap,
                compact_bytes, observed, observed_cold_rows,
                counter_vecs, plan_facts, gather_bytes_pb, gather_ms_pb)

    (feature_gather_rps, host_bytes_per_batch, exchange_bytes_per_batch,
     exchange_cap, exchange_compact_bytes_per_batch, observed,
     observed_cold_rows, counter_vecs, plan_facts, gather_bytes_pb,
     gather_ms_per_batch) = measure_feature_gather()

    # ---- cold-tier (disk mmap) figure: the THIRD rung of the
    # hierarchy. A small quantized disk-tier artifact (int8 rows +
    # sidecars, partition.save_disk_tier) served with frontier-ahead
    # prefetch: batch i+1's ids publish before batch i's consume, so
    # the mmap read overlaps — cold rows/sec through the prefetched
    # path plus the OBSERVED ring hit rate (the prefetcher's own
    # counters; benchmarks/bench_feature.py --ab-prefetch carries the
    # on/off A/B at full scale).
    def measure_cold_tier():
        import shutil
        import tempfile

        import numpy as _np

        from quiver_tpu.partition import (load_disk_tier_store,
                                          save_disk_tier)

        c_rows = int(min(n_nodes, 120_000))
        c_dim = 64
        c_batch = int(min(2 * batch, c_rows // 2))
        cache_rows = c_rows // 2
        n_batches_c = 6
        rngc = _np.random.default_rng(11)
        tmp = tempfile.mkdtemp(prefix="qt_bench_cold_")
        try:
            featc = rngc.standard_normal((c_rows, c_dim)).astype(
                _np.float32)
            save_disk_tier(featc, _np.arange(c_rows, dtype=_np.int64),
                           tmp, dtype_policy="int8", overwrite=True)
            store, _meta = load_disk_tier_store(
                tmp, hot_rows=cache_rows, prefetch_rows=2 * c_batch,
                workers=2)      # the parallel-IO staging path (io.py)
            pf = store._cold_prefetch
            # frontier-shaped batches, half the slots on the disk tier
            ids_c = []
            for _ in range(n_batches_c):
                pool = rngc.choice(_np.arange(cache_rows, c_rows),
                                   size=max(c_batch // 8, 1),
                                   replace=False)
                cold_part = pool[rngc.integers(0, pool.size,
                                               c_batch // 2)]
                hot_part = rngc.integers(0, cache_rows,
                                         c_batch - c_batch // 2)
                a = _np.concatenate([cold_part, hot_part])
                rngc.shuffle(a)
                ids_c.append(a.astype(_np.int64))
            # warmup compiles + stage batch 0 (steady state); the
            # timed loop's hit rate comes from a counter DELTA so the
            # warmup's all-sync cold reads don't deflate it
            jax.block_until_ready(store[jnp.asarray(ids_c[0])])
            store.stage_frontier(ids_c[0]).result()
            cold_slots = sum(int((a >= cache_rows).sum()) for a in ids_c)
            base = pf.counters()
            t0 = time.perf_counter()
            for i, a in enumerate(ids_c):
                r = store[jnp.asarray(a)]
                if i + 1 < n_batches_c:
                    store.stage_frontier(ids_c[i + 1])
                jax.block_until_ready(r)
            dt = time.perf_counter() - t0
            hit, sync, staged = (int(v) for v in pf.counters() - base)
            hit_rate = hit / (hit + sync) if hit + sync else 0.0
            tracing.record("bench.cold_tier", t0, dt,
                           args={"batches": n_batches_c,
                                 "hit_rate": round(hit_rate, 4),
                                 "staged_rows": staged})
            store.close()
            # staged delta excludes the pre-loop batch-0 staging: at a
            # steady hit rate the ring stages ~one batch's uniques per
            # batch, so the per-batch figure is the timed delta over
            # the batches that PUBLISHED during the loop
            return (cold_slots / dt, hit_rate,
                    staged / max(n_batches_c - 1, 1), staged / dt,
                    dt / n_batches_c * 1e3)
        finally:
            shutil.rmtree(tmp, ignore_errors=True)

    (cold_rows_per_s, prefetch_hit_rate,
     prefetch_staged_rows_per_batch,
     cold_staged_rows_per_s, cold_ms_per_batch) = measure_cold_tier()

    # ---- qt-prof figures: machine probe + per-stage attribution ----
    # one small probe of what THIS box delivers (quiver_tpu.profile
    # machine_probe — memcpy / random-gather / h2d GB/s), then the
    # gather stage's roofline efficiency = modeled bytes over the
    # timed wall over the probed random-gather peak. The stage_ms /
    # stage_shares block is the coarse per-stage attribution of one
    # bench pass (each stage's per-batch wall at its own bench scale)
    # — the trend bench_regress tracks; scripts/qt_prof.py carries the
    # fine-grained per-entry attribution.
    from quiver_tpu.profile import machine_probe
    probe_gather_gbps = machine_probe(quick=True)["gather_gbps"]
    gather_achieved_gbps = (gather_bytes_pb
                            / (gather_ms_per_batch / 1e3) / 1e9)
    gather_efficiency = gather_achieved_gbps / probe_gather_gbps

    # ---- qt-fuse figures: single-kernel sample+gather hop A/B ----
    # one hop, fused (ops.pallas.fused: picks AND their feature rows out
    # of ONE kernel, frontier ids never in HBM) vs split (the sample
    # kernel then the row gather — the frontier-id HBM round trip).
    # Two numbers: the steps/s ratio fused/split (timed at one BLOCK
    # of seeds), and the fused hop's MODELED gather indexing bytes —
    # zero by construction, verified through the cost model so a
    # regression that reintroduces an HBM frontier array fails loudly.
    def measure_fused_ab(reps=5):
        import numpy as _np
        from quiver_tpu.analysis.costmodel import cost_of
        from quiver_tpu.analysis.registry import build_entry_specs
        from quiver_tpu.ops.pallas.fused import (default_interpret,
                                                 default_rng,
                                                 fused_hot_hop,
                                                 fused_hot_hop_reference,
                                                 pad_indices)
        index_bytes = int(cost_of(
            build_entry_specs("fused_hot_hop")[0]).gather_index_bytes)
        rf = _np.random.default_rng(18)
        n_f, dim_f, bs_f, k_f, cap_f = 4096, 128, 128, 4, 128
        deg_f = rf.integers(0, 24, n_f)
        ip = _np.zeros(n_f + 1, _np.int64)
        ip[1:] = _np.cumsum(deg_f)
        ip = jnp.asarray(ip.astype(_np.int32))
        ix = pad_indices(jnp.asarray(
            rf.integers(0, n_f, int(deg_f.sum())).astype(_np.int32)),
            cap_f)
        # float32 rows: the compiled kernel takes no int8 table (its
        # per-row DMA cannot address rows packed four to a sublane)
        fq = jnp.asarray(
            rf.standard_normal((n_f, dim_f)).astype(_np.float32))
        sds = jnp.asarray(
            rf.choice(n_f, bs_f, replace=False).astype(_np.int32))
        rng_f, interp = default_rng(), default_interpret()

        def run_pair(fn):
            jax.block_until_ready(fn(jnp.int32(0)))     # compile
            t0 = time.perf_counter()
            for r in range(reps):
                out = fn(jnp.int32(r + 1))
            jax.block_until_ready(out)
            return reps / (time.perf_counter() - t0)

        fused_sps = run_pair(lambda s: fused_hot_hop(
            ip, ix, sds, fq, k_f, s, row_cap=cap_f, rng=rng_f,
            interpret=interp))
        split_sps = run_pair(lambda s: fused_hot_hop_reference(
            ip, ix, sds, fq, k_f, s, row_cap=cap_f, rng=rng_f,
            interpret=interp))
        return fused_sps / split_sps, index_bytes

    (fused_vs_split_steps_per_s,
     fused_gather_index_bytes) = measure_fused_ab()

    # ---- qt-fuse-deep figure: the whole ladder in one program ----
    # Multi-hop extension of the A/B above at the production fanouts
    # [15,10,5]: fused (`fused_multihop` — interior hops sample
    # in-kernel, compaction between hops, only leaf rows ever written,
    # the WHOLE walk one jitted program) vs split (per-hop
    # `sample_layer_pallas` + compaction + the jnp row gather — ids
    # round-tripping through HBM every hop, one dispatch per op). The
    # modeled index bytes for the walk live under the registry's
    # `fused_multihop` entry and are pinned at zero by test_analysis;
    # here the timed ratio is the trajectory figure. Batch stays small:
    # the frontier cap grows multiplicatively (bs·16·11·6) and under
    # CPU interpret the leaf gather emulates its DMAs serially.
    def measure_fused_multihop_ab(reps=5):
        import numpy as _np
        from quiver_tpu.ops.pallas.fused import (default_interpret,
                                                 default_rng,
                                                 fused_multihop,
                                                 fused_multihop_reference,
                                                 pad_indices)
        rf = _np.random.default_rng(18)
        n_f, dim_f, bs_f, cap_f = 4096, 128, 8, 128
        sizes_f = [15, 10, 5]
        deg_f = rf.integers(0, 24, n_f)
        ip = _np.zeros(n_f + 1, _np.int64)
        ip[1:] = _np.cumsum(deg_f)
        ip = jnp.asarray(ip.astype(_np.int32))
        ix = pad_indices(jnp.asarray(
            rf.integers(0, n_f, int(deg_f.sum())).astype(_np.int32)),
            cap_f)
        # float32 rows: the compiled kernel takes no int8 table (its
        # per-row DMA cannot address rows packed four to a sublane)
        fq = jnp.asarray(
            rf.standard_normal((n_f, dim_f)).astype(_np.float32))
        sds = jnp.asarray(
            rf.choice(n_f, bs_f, replace=False).astype(_np.int32))
        rng_f, interp = default_rng(), default_interpret()

        def run_pair(fn):
            jax.block_until_ready(fn(0))                # compile
            t0 = time.perf_counter()
            for r in range(reps):
                out = fn(r + 1)
            jax.block_until_ready(out)
            return reps / (time.perf_counter() - t0)

        fused_sps = run_pair(lambda s: fused_multihop(
            ip, ix, sds, fq, sizes_f,
            jax.random.fold_in(jax.random.key(0), s), row_cap=cap_f,
            rng=rng_f, interpret=interp))
        split_sps = run_pair(lambda s: fused_multihop_reference(
            ip, ix, sds, fq, sizes_f,
            jax.random.fold_in(jax.random.key(0), s), row_cap=cap_f,
            rng=rng_f, interpret=interp))
        return fused_sps / split_sps

    fused_multihop_vs_split_steps_per_s = measure_fused_multihop_ab()

    # ---- qt-shard figures: serving over the partitioned store ----
    # A 2-partition block-clustered world served by one homed
    # ShardedServeEngine: aggregate seeds/sec through the jitted
    # shard_map serve step, the per-batch dispatch p99, and the
    # OBSERVED locality hit rate — the fraction of the frontier
    # resident in the home partition's hot tier, which is what the
    # qt-shard router's degree-mass table predicts when it steers a
    # request here. bench_regress tracks all three as trajectory
    # groups (the p99 inverted).
    def measure_sharded(reps=12):
        import numpy as _np
        import optax
        from jax.sharding import Mesh
        import quiver_tpu as qv
        from quiver_tpu import metrics as qmetrics
        from quiver_tpu.models import GraphSAGE
        from quiver_tpu.ops import sample_multihop as _smh
        from quiver_tpu.parallel.train import (init_state,
                                               layers_to_adjs,
                                               masked_feature_gather)
        rs = _np.random.default_rng(21)
        n_s, dim_s, bs_s, hosts = 2048, 64, 64, 2
        sizes_s = [5, 3]
        half = n_s // hosts
        g2h = (_np.arange(n_s) // half).astype(_np.int32)
        deg_s = rs.integers(2, 8, n_s)
        ip = _np.zeros(n_s + 1, _np.int64)
        ip[1:] = _np.cumsum(deg_s)
        # block-clustered edges: ~90% intra-partition, so locality is
        # a real but not total effect — the observed hit rate must
        # land strictly inside (0, 1)
        e_s = int(ip[-1])
        owner = _np.repeat(g2h, deg_s)
        intra = rs.random(e_s) < 0.9
        ix = _np.where(intra,
                       owner * half + rs.integers(0, half, e_s),
                       rs.integers(0, n_s, e_s)).astype(_np.int32)
        feat_s = rs.standard_normal((n_s, dim_s)).astype(_np.float32)
        ij = jnp.asarray(ip.astype(_np.int32))
        xj = jnp.asarray(ix)
        model = GraphSAGE(hidden_dim=32, out_dim=8, num_layers=2,
                          dropout=0.0)
        n_id, layers = _smh(ij, xj,
                            jnp.arange(bs_s, dtype=jnp.int32),
                            sizes_s, jax.random.key(0))
        state = init_state(
            model, optax.adam(1e-3),
            masked_feature_gather(jnp.asarray(feat_s), n_id),
            layers_to_adjs(layers, bs_s, sizes_s), jax.random.key(1))
        mesh = Mesh(_np.array(jax.devices()[:hosts]), ("host",))
        info = qv.PartitionInfo(host=0, hosts=hosts, global2host=g2h)
        comm = qv.TpuComm(rank=0, world_size=hosts, mesh=mesh,
                          axis="host")
        dist = qv.DistFeature.from_partition(feat_s, info, comm,
                                             exchange_cap=256,
                                             collect_metrics=True)
        eng = qv.ShardedServeEngine(model, state.params, (ij, xj),
                                    dist, sizes_variants=[sizes_s],
                                    batch_cap=bs_s, home=0,
                                    collect_metrics=True, seed=3)

        def sh_batch():
            # home-partition-skewed arrivals: the traffic the locality
            # router steers to this replica (10% strays keep the miss
            # counter nonzero)
            k = rs.integers(0, half, bs_s)
            stray = rs.random(bs_s) < 0.1
            return _np.where(stray, k + half, k).astype(_np.int32)

        # compile + settle the donated-key placement signatures so the
        # timed loop below never recompiles
        for _ in range(4):
            jax.block_until_ready(eng.run(sh_batch()))
        hit = miss = 0
        times_ms = []
        t_all = time.perf_counter()
        for _ in range(reps):
            t0 = time.perf_counter()
            jax.block_until_ready(eng.run(sh_batch()))
            times_ms.append((time.perf_counter() - t0) * 1e3)
            c = _np.asarray(eng.last_counters)
            hit += int(c[qmetrics.LOCALITY_HIT_ROWS])
            miss += int(c[qmetrics.LOCALITY_MISS_ROWS])
        agg = reps * bs_s / (time.perf_counter() - t_all)
        p99 = float(_np.percentile(_np.asarray(times_ms), 99))
        return agg, p99, hit / max(hit + miss, 1)

    # a 2-partition store needs two devices: on one chip the figures
    # stay null (not measured), on any mesh a failure is a failure
    sharded_agg_rps = sharded_p99_ms = locality_hit_rate = None
    if len(jax.devices()) >= 2:
        (sharded_agg_rps, sharded_p99_ms,
         locality_hit_rate) = measure_sharded()
    stage_ms = {
        "sample": round(sample_ms_per_batch, 3),
        "gather": round(gather_ms_per_batch, 3),
        "cold_tier": round(cold_ms_per_batch, 3),
    }
    stage_total = sum(stage_ms.values())
    stage_shares = {k: round(v / stage_total, 4) if stage_total else None
                    for k, v in stage_ms.items()}
    out = {
        "metric": METRIC,
        "value": round(seps, 1),
        "unit": "edges/s",
        "vs_baseline": round(seps / BASELINE_SEPS, 3),
        "mode": mode,
        "layout": layout,
        "shuffle": shuffle,
        # per-mode SEPS, uniformly keyed, so the exact-mode gap (the
        # honest exact-vs-exact comparison against the reference's
        # i.i.d. reservoir kernel) is tracked by the official metric
        "rotation_mode_value": round(rotation_seps, 1),
        "rotation_mode_vs_baseline": round(rotation_seps / BASELINE_SEPS, 3),
        "exact_mode_value": round(exact_seps, 1),
        "exact_mode_vs_baseline": round(exact_seps / BASELINE_SEPS, 3),
        "window_mode_value": round(window_seps, 1),
        "window_mode_vs_baseline": round(window_seps / BASELINE_SEPS, 3),
        # the bandwidth half: duplicate-heavy frontier slots/sec through
        # the fused dedup tiered feature lookup (no reference baseline
        # ratio — the reference reports GB/s on a uniform gather), plus
        # bytes/batch — the currency the dtype policy shrinks
        # (benchmarks/bench_feature.py --ab-quant A/Bs the policies)
        "feature_gather_rows_per_s": round(feature_gather_rps, 1),
        "host_bytes_per_batch": round(host_bytes_per_batch, 1),
        "exchange_bytes_per_batch": round(exchange_bytes_per_batch, 1),
        # the compact dedup'd exchange (exchange_cap): same batches,
        # [H, cap] request block at the modeled H=8 mesh — the wire
        # cost the fused dist step pays with the knob on
        "exchange_cap": exchange_cap,
        "exchange_compact_bytes_per_batch":
            round(exchange_compact_bytes_per_batch, 1),
        # OBSERVED device counters (quiver_tpu.metrics) over the same
        # feature-gather batches — the runtime truth next to the
        # analytic mirrors above: the hot tier's actual hit rate (what
        # plan_hot_capacity predicted), the actual frontier dup factor
        # (what dedup_cold's >1.3 payoff threshold assumes), and the
        # cold rows a batch really classified
        "observed_hot_hit_rate": round(observed["hot_hit_rate"], 4)
            if observed["hot_hit_rate"] is not None else None,
        "observed_dup_factor": round(observed["dup_factor"], 3)
            if observed["dup_factor"] is not None else None,
        "observed_cold_rows_per_batch": round(observed_cold_rows, 1),
        # the disk rung, prefetched: cold-tier rows/sec through the
        # frontier-ahead staging path and the OBSERVED ring hit rate
        # (bench_regress.py tracks both as their own trajectory groups)
        "cold_rows_per_s": round(cold_rows_per_s, 1),
        "prefetch_hit_rate": round(prefetch_hit_rate, 4),
        "prefetch_staged_rows_per_batch":
            round(prefetch_staged_rows_per_batch, 1),
        # staging THROUGHPUT through the parallel-IO read path
        # (extents at depth, quiver_tpu/io.py) — its own
        # bench_regress trajectory group from this round on, so a
        # QD/coalescing regression fails the sweep loudly
        "cold_staged_rows_per_s": round(cold_staged_rows_per_s, 1),
        # qt-prof: roofline efficiency of the tiered gather (modeled
        # bytes / timed wall / probed random-gather peak — its own
        # bench_regress trajectory group from this round) + the
        # coarse per-stage attribution of this bench pass
        "gather_efficiency": (round(gather_efficiency, 4)
                              if gather_efficiency is not None else None),
        "gather_achieved_gbps": (round(gather_achieved_gbps, 3)
                                 if gather_achieved_gbps is not None
                                 else None),
        "probe_gather_gbps": probe_gather_gbps,
        # qt-fuse: fused/split steps-per-second ratio for one
        # sample+gather hop, and the fused hop's modeled gather
        # indexing bytes (0 = frontier ids never touch HBM;
        # bench_regress tracks it inverted so any nonzero value — a
        # reintroduced frontier round trip — fails the sweep)
        "fused_vs_split_steps_per_s":
            (round(fused_vs_split_steps_per_s, 4)
             if fused_vs_split_steps_per_s is not None else None),
        "fused_gather_index_bytes": fused_gather_index_bytes,
        "fused_multihop_vs_split_steps_per_s":
            (round(fused_multihop_vs_split_steps_per_s, 4)
             if fused_multihop_vs_split_steps_per_s is not None
             else None),
        # qt-shard: serving over the 2-partition sharded store —
        # aggregate seeds/sec through the jitted shard_map serve step,
        # its per-batch dispatch p99 (bench_regress tracks it
        # INVERTED), and the OBSERVED locality hit rate of
        # home-skewed arrivals (the router-as-cache-policy payoff:
        # miss rows are exactly what the exchange ships in)
        "sharded_agg_rps": (round(sharded_agg_rps, 1)
                            if sharded_agg_rps is not None else None),
        "sharded_p99_ms": (round(sharded_p99_ms, 3)
                           if sharded_p99_ms is not None else None),
        "locality_hit_rate": (round(locality_hit_rate, 4)
                              if locality_hit_rate is not None else None),
        "stage_ms": stage_ms,
        "stage_shares": stage_shares,
    }
    # every measured rotation config, for the record (always present so
    # log consumers never hit a missing key)
    out["rotation_configs"] = {
        f"{lay}/{shuf}": round(v, 1) for (lay, shuf), v in by_cfg.items()}
    # "platform" is what scripts/bench_regress.py groups its histories by
    out["platform"] = dev.platform
    out["device"] = {"platform": dev.platform, "kind": dev.device_kind,
                     "count": len(jax.devices())}
    if cpu_smoke:
        # not comparable to the TPU baseline: null the ratios, and
        # rename every key so that no consumer can file a CPU figure
        # under a device metric's name
        for k in ("vs_baseline", "rotation_mode_vs_baseline",
                  "exact_mode_vs_baseline", "window_mode_vs_baseline"):
            out[k] = None
        out = {f"cpu_{k}": v for k, v in out.items()}
    print(json.dumps(out), flush=True)
    # optional structured emission: the same record, through the one
    # JSONL schema the watch scripts tail (QT_METRICS_JSONL=path)
    sink_path = os.environ.get("QT_METRICS_JSONL")
    if sink_path:
        from quiver_tpu.metrics import MetricsSink
        from quiver_tpu.telemetry import PlanContext, TelemetryHub
        with MetricsSink(sink_path) as sink:
            sink.emit(out, kind="bench")
            # advisory replan over the OBSERVED per-batch counter
            # vectors: the hub re-derives the dedup budget / hot
            # sizing from what the gather pass actually saw and
            # leaves `advice` records beside the `bench` one —
            # observe-only, nothing in the run was adjusted
            hub = TelemetryHub(window=4, sink=sink,
                               plan=PlanContext(**plan_facts))
            for c in counter_vecs:
                hub.observe_counters(c)
            for rec in hub.replan():
                print(f"bench advice: {rec['key']} "
                      f"{rec['current']} -> {rec['recommended']} "
                      f"({rec['reason']})", file=sys.stderr)


if __name__ == "__main__":
    main()
