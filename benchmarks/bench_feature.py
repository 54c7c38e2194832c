"""Feature-collection benchmark: effective gather GB/s.

Mirrors the reference benchmark (benchmarks/feature/bench_feature.py,
GB/s metric at :44-46; published UVA number: 14.82 GB/s,
docs/Introduction_en.md:92-97): random-id row gather from a
products-shaped feature array (N x 100 float32).

Modes:
  (default)    raw device gather: XLA take from HBM
  --pallas     the Pallas DMA gather kernel instead of XLA take
  --tiered F   the real ``quiver_tpu.Feature`` store with fraction F of
               rows HBM-cached (0, 0.2, 1.0 = the VERDICT grid) and the
               rest in the host tier
  --prefetch   with --tiered: pipeline lookups via feature.prefetch()
               (stage batch i+1's host rows while batch i transfers) —
               the double-buffered path a training loop uses

  --ab-dedup   duplicate-heavy frontier A/B: the fused tiered lookup
               with dedup_cold off vs on, masked off vs on, on the SAME
               ids — reports gathered-rows/sec and host bytes moved per
               arm (the bandwidth half of the paper: host traffic per
               unique cold node, not per frontier slot). --dup sets the
               duplicate factor (batch / distinct ids).

  --ab-quant   dtype-policy A/B at EQUAL shapes: the fused dedup tiered
               lookup under fp32 vs bf16 vs int8 tiers on the SAME id
               streams (same batch, same cached-row count) — reports
               gathered-rows/sec, host-tier bytes/batch, and the
               analytic exchange bytes/batch per arm, plus the
               int8-vs-fp32 byte-reduction and rows/s ratios (the
               acceptance gate: >= 2x fewer host+exchange bytes at
               rows/s parity).

  --ab-prefetch  cold-tier (NVMe/mmap) prefetch A/B: the same
               disk-tier store and id streams with frontier-ahead
               staging ON vs synchronous cold reads, per cold fraction
               (--cold-fracs) — end-to-end steps/s (gather + a jitted
               compute the staging overlaps), cold rows/s, prefetch
               hit rate; gathered rows and compute sums pinned
               bit-identical between arms. The ON arm stages through
               the parallel-IO path (--io-workers staging workers,
               coalesced extents at --io-qd in-flight preadv reads;
               quiver_tpu/io.py) and the JSON carries a dedicated
               staged-rows/s pin: the same publication stream through
               the QD1 per-row mmap path vs the deep-queue path.
               Under --storage-latency-us both arms charge a
               deterministic queue-depth device model (one service
               time per request, at most --storage-qd overlapped) so
               a hypervisor page cache cannot hide the win; eviction
               failures are counted per arm in the JSON so a run
               where eviction silently stopped working is
               distinguishable from a regression.

Usage: python benchmarks/bench_feature.py [--rows N] [--dim D]
       [--batch B] [--iters K] [--pallas] [--bf16]
       [--tiered F] [--prefetch] [--ab-dedup] [--ab-quant]
       [--ab-prefetch] [--dup F]
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def run_ab_dedup(args, jax, jnp):
    """Dedup A/B on a duplicate-heavy (multi-hop-frontier-shaped)
    cold-tier workload: same feature table, same id streams, fused
    tiered lookup with dedup_cold {off, on} x masked {off, on}."""
    import quiver_tpu as qv

    rng = np.random.default_rng(0)
    rows, dim, batch, iters = args.rows, args.dim, args.batch, args.iters
    frac = args.tiered if args.tiered is not None else 0.25
    dup = max(args.dup, 1.0)
    feat = rng.standard_normal((rows, dim)).astype(np.float32)
    row_bytes = dim * feat.dtype.itemsize
    cache_rows = int(rows * frac)

    # frontier-shaped ids: each batch draws `batch` slots from a small
    # per-batch pool of distinct nodes (hub revisits across hops)
    ids_np, masked_np = [], []
    for i in range(iters):
        pool = rng.choice(rows, size=max(int(batch / dup), 1),
                          replace=False)
        ids = pool[rng.integers(0, pool.size, batch)]
        ids_np.append(ids.astype(np.int64))
        m = ids.astype(np.int64).copy()
        # frontier-shaped padding: static multi-hop caps run well past
        # the realized frontier, so a third or more of the slots are -1
        # (layer_shapes caps vs realized uniques on power-law graphs)
        m[rng.random(batch) < args.pad] = -1
        masked_np.append(m)

    def host_rows_read(ids, dedup, budget):
        """Analytic host-tier rows read per batch for the path taken
        (mirrors lookup_tiered's branch structure: the dedup overflow
        predicate is the unique count of the WHOLE valid frontier, hot
        and cold, not just the cold slots)."""
        valid = ids >= 0
        cold = valid & (ids >= cache_rows)
        if budget >= batch:
            return batch
        need = (np.unique(ids[valid]).size if dedup
                else int(cold.sum()))
        return budget if need <= budget else batch

    budget = max(batch // 4, 256)                 # lookup default
    stores = {}
    for dedup in (False, True):
        f = qv.Feature(device_cache_size=cache_rows * row_bytes,
                       dedup_cold=dedup)
        f.from_cpu_tensor(feat)
        stores[dedup] = (f, jnp.asarray(f.host_part))

    out = {}
    for masked in (False, True):
        stream = masked_np if masked else ids_np
        ids_dev = [jnp.asarray(a) for a in stream]
        # the arms are timed INTERLEAVED per batch (naive then dedup on
        # the same ids) so machine-load drift across the run cancels
        # out of the A/B ratio instead of landing on one arm
        elapsed = {False: 0.0, True: 0.0}
        for dedup in (False, True):               # compile both
            f, host = stores[dedup]
            jax.block_until_ready(f._lookup_tiered(
                f.device_part, host, ids_dev[0], f.feature_order,
                masked))
        for it, ids in enumerate(ids_dev):
            # alternate which arm goes first: the second arm reads the
            # batch's pool rows cache-warm, a systematic bias that
            # would otherwise always favor one side
            order = (False, True) if it % 2 == 0 else (True, False)
            for dedup in order:
                f, host = stores[dedup]
                t0 = time.perf_counter()
                jax.block_until_ready(f._lookup_tiered(
                    f.device_part, host, ids, f.feature_order, masked))
                elapsed[dedup] += time.perf_counter() - t0
        for dedup in (False, True):
            host_bytes = sum(host_rows_read(a, dedup, budget)
                             for a in stream) * row_bytes
            key = (f"dedup={'on' if dedup else 'off'} "
                   f"masked={'on' if masked else 'off'}")
            out[key] = {"rows_per_s": batch * iters / elapsed[dedup],
                        "host_mb": host_bytes / 1e6}
            print(f"[ab-dedup cache={frac:.0%} dup={dup:g} {key}] "
                  f"{out[key]['rows_per_s'] / 1e6:.2f} Mrows/s, "
                  f"host {out[key]['host_mb']:.1f} MB")
    for f, _ in stores.values():
        f.close()
    for masked in ("off", "on"):
        a = out[f"dedup=off masked={masked}"]
        b = out[f"dedup=on masked={masked}"]
        print(f"[ab-dedup masked={masked}] speedup "
              f"{b['rows_per_s'] / a['rows_per_s']:.2f}x rows/s, "
              f"host bytes {a['host_mb'] / max(b['host_mb'], 1e-9):.1f}x "
              "less")
    print(json.dumps({"bench": "ab_dedup", "rows": rows, "dim": dim,
                      "batch": batch, "iters": iters, "dup": dup,
                      "cache_frac": frac,
                      "results": {k: {kk: round(vv, 1)
                                      for kk, vv in v.items()}
                                  for k, v in out.items()}}))


def run_ab_quant(args, jax, jnp):
    """Dtype-policy A/B: fp32 vs bf16 vs int8 tiers at equal shapes on
    the same duplicate-heavy id streams, through the production path
    (fused tiered lookup, dedup_cold on). Bytes are the analytic
    per-batch traffic mirroring lookup_tiered's branch structure — the
    jaxpr-level pins for the same bounds live in tests/test_quant.py."""
    import quiver_tpu as qv
    from quiver_tpu.ops import quant

    rng = np.random.default_rng(0)
    rows, dim, batch, iters = args.rows, args.dim, args.batch, args.iters
    frac = args.tiered if args.tiered is not None else 0.25
    dup = max(args.dup, 1.0)
    feat = rng.standard_normal((rows, dim)).astype(np.float32)
    cache_rows = int(rows * frac)

    ids_np = []
    for i in range(iters):
        pool = rng.choice(rows, size=max(int(batch / dup), 1),
                          replace=False)
        ids_np.append(pool[rng.integers(0, pool.size, batch)]
                      .astype(np.int64))
    ids_dev = [jnp.asarray(a) for a in ids_np]

    policies = [None, "bf16", "int8"]
    stores = {}
    for pol in policies:
        # EQUAL shapes: pin the byte budget so every arm caches the
        # same row count — the A/B isolates row WIDTH, the capacity
        # planner's extra-rows win is reported separately by the
        # construction log
        f = qv.Feature(
            device_cache_size=cache_rows * quant.row_bytes(dim, pol, 4),
            dedup_cold=True, dtype_policy=pol)
        f.from_cpu_tensor(feat)
        assert f.cache_rows == cache_rows
        stores[pol] = (f, quant.tree_map_tier(jnp.asarray, f.host_part))

    elapsed = {pol: 0.0 for pol in policies}
    for pol in policies:                          # compile every arm
        f, host = stores[pol]
        jax.block_until_ready(f._lookup_tiered(
            f.device_part, host, ids_dev[0], f.feature_order))
    for it, ids in enumerate(ids_dev):
        # interleave arms per batch, rotating which goes first, so
        # machine-load drift and cache warmth cancel out of the ratios
        order = policies[it % len(policies):] + \
            policies[:it % len(policies)]
        for pol in order:
            f, host = stores[pol]
            t0 = time.perf_counter()
            jax.block_until_ready(f._lookup_tiered(
                f.device_part, host, ids, f.feature_order))
            elapsed[pol] += time.perf_counter() - t0

    out = {}
    for pol in policies:
        row_b = quant.row_bytes(dim, pol, 4)
        # the shared analytic mirror of lookup_tiered's branch logic:
        # `budget` host rows on the dedup narrow path and on the
        # compaction fallback, the full batch only when the raw cold
        # count overflows too (no csr_topo -> ids ARE storage rows)
        host_bytes = sum(
            quant.dedup_rows_read(
                a, cold_count=int((a >= cache_rows).sum())) * row_b
            for a in ids_np)
        key = pol or "fp32"
        out[key] = {
            "rows_per_s": batch * iters / elapsed[pol],
            "host_bytes_per_batch": host_bytes / iters,
            "exchange_bytes_per_batch": batch * (4 + row_b),
        }
        print(f"[ab-quant cache={frac:.0%} dup={dup:g} {key}] "
              f"{out[key]['rows_per_s'] / 1e6:.2f} Mrows/s, "
              f"host {out[key]['host_bytes_per_batch'] / 1e6:.2f} "
              f"MB/batch, exchange "
              f"{out[key]['exchange_bytes_per_batch'] / 1e6:.2f} MB/batch")

    fp32, int8 = out["fp32"], out["int8"]
    byte_ratio = ((fp32["host_bytes_per_batch"]
                   + fp32["exchange_bytes_per_batch"])
                  / (int8["host_bytes_per_batch"]
                     + int8["exchange_bytes_per_batch"]))
    speed_ratio = int8["rows_per_s"] / fp32["rows_per_s"]
    print(f"[ab-quant] int8 vs fp32: {byte_ratio:.1f}x fewer "
          f"host+exchange bytes/batch, {speed_ratio:.2f}x rows/s")
    print(json.dumps({
        "bench": "ab_quant", "rows": rows, "dim": dim, "batch": batch,
        "iters": iters, "dup": dup, "cache_frac": frac,
        "int8_byte_reduction": round(byte_ratio, 2),
        "int8_speed_ratio": round(speed_ratio, 3),
        "results": {k: {kk: round(vv, 1) for kk, vv in v.items()}
                    for k, v in out.items()}}))
    for f, _ in stores.values():
        f.close()


class ModeledLatencyMmap:
    """Bench-only storage shim: wraps the artifact's memmap and
    charges every UNIQUE row fancy-indexed through it as one request
    against a shared ``io.StorageModel`` — issued serially from the
    calling thread, which IS queue depth 1 no matter how deep the
    modeled device's queue runs (a serial issuer can't overlap with
    itself). That is exactly the old per-row-page-fault staging
    regime; the parallel staging path instead reads through
    ``io.ExtentReader``, charging the SAME model one request per
    COALESCED extent from each of its reader-pool threads — up to the
    model's ``qd`` overlapped. One price per request, two issue
    disciplines: the A/B measures the discipline, which the box's
    hypervisor page cache (reads swing 1-60 us/row between runs)
    cannot fake. Pass --storage-latency-us 0 (default) for the
    real-eviction regime. Everything else (sidecars, decode, ring,
    scatter) stays the real code path."""

    def __init__(self, mm, model):
        self._mm = mm
        self._model = model

    def __getitem__(self, ids):
        ids_arr = np.asarray(ids)
        if ids_arr.ndim:
            self._model.request(n=int(np.unique(ids_arr).size))
        return self._mm[ids]

    def __getattr__(self, name):
        return getattr(self._mm, name)


def build_cold_artifact(feat, tmp_dir, dtype_policy="int8"):
    """Write ``feat`` as the prefetch A/B's quantized disk-tier
    artifact (identity disk_map) into ``tmp_dir`` — once per arm; the
    per-fraction stores reattach it through the one shared
    artifact-to-store recipe (``partition.load_disk_tier_store``)."""
    from quiver_tpu.partition import save_disk_tier

    save_disk_tier(feat, np.arange(feat.shape[0], dtype=np.int64),
                   tmp_dir, dtype_policy=dtype_policy, overwrite=True)
    return tmp_dir


def run_ab_prefetch(args, jax, jnp):
    """Frontier-ahead cold-tier prefetch A/B: the same disk-tier store
    and id streams, prefetch OFF (every cold read synchronous, the old
    sidecar behavior) vs ON (batch i+1's frontier published before
    batch i's compute, so the mmap read + dequant overlap the step).
    Each step = tiered gather + a jitted compute consuming the rows
    (the model-step stand-in the staging overlaps with); end-to-end
    steps/s per cold fraction, gathered rows pinned bit-identical
    between arms, compute-output sums pinned bit-identical too.

    Unless --keep-page-cache, the artifact's pages are EVICTED from
    the OS page cache before every step in BOTH arms
    (``prefetch.evict_file_cache``): the tier exists for graphs whose
    rows do not fit in RAM, where every first-touch read hits storage
    — on a bench box whose whole artifact fits in the page cache the
    kernel would otherwise serve "disk" reads as memcpy and the A/B
    would measure nothing. The eviction never touches rows already
    staged in the ring (they are RAM copies), so the ON arm's wins are
    exactly the reads it moved off the critical path."""
    import shutil
    import tempfile

    # the shared jaxpr walker lives in tests/ (not a package): path-load
    tests_dir = os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "tests")
    if tests_dir not in sys.path:
        sys.path.insert(0, tests_dir)
    from _traffic import host_sync_eqns

    rng = np.random.default_rng(0)
    rows, dim, batch = args.rows, args.dim, args.batch
    iters = args.iters
    dup = max(args.dup, 1.0)
    cache_rows = rows // 2
    cold_fracs = [float(f) for f in args.cold_fracs.split(",")]
    feat = rng.standard_normal((rows, dim)).astype(np.float32)

    # the compute the staging overlaps with: a jitted tanh-matmul chain
    # over the gathered rows — and a structural pin that the jitted
    # path stays at ZERO host syncs with the prefetch machinery active
    # (the prefetcher is host-side by construction; this keeps it so)
    w = jnp.asarray(rng.standard_normal((dim, dim)).astype(np.float32))

    @jax.jit
    def compute(x, w):
        for _ in range(args.compute_iters):
            x = jnp.tanh(x @ w)
        return jnp.sum(x)

    probe = jnp.zeros((batch, dim), jnp.float32)
    assert host_sync_eqns(compute, (probe, w)) == []

    from quiver_tpu import io as qio
    from quiver_tpu.partition import load_disk_tier_store
    from quiver_tpu.prefetch import evict_file_cache

    # per-arm eviction accounting [calls, failures]: a run where
    # eviction silently stopped working (platform lost posix_fadvise,
    # file moved, ...) measures page-cache memcpy and would otherwise
    # be indistinguishable from a real regression in the JSON
    evict_stats = {"off": [0, 0], "on": [0, 0]}

    def evict(store, mode):
        if args.keep_page_cache:
            return
        ok = evict_file_cache(store.mmap_array.filename,
                              mapped=store.mmap_array)
        evict_stats[mode][0] += 1
        evict_stats[mode][1] += 0 if ok else 1

    # ONE artifact write per arm (separate files so the page-cache
    # eviction regimes stay isolated); the per-fraction stores below
    # just reattach them
    tmp_dirs = {mode: build_cold_artifact(
        feat, tempfile.mkdtemp(prefix="qt_ab_pf_"))
        for mode in ("off", "on")}
    out = {}
    for frac in cold_fracs:
        for v in evict_stats.values():       # per-fraction accounting
            v[0] = v[1] = 0
        n_cold = int(batch * frac)
        ids_np = []
        for _ in range(iters):
            pool = rng.choice(np.arange(cache_rows, rows),
                              size=max(int(n_cold / dup), 1),
                              replace=False)
            cold_ids = pool[rng.integers(0, pool.size, n_cold)]
            hot_ids = rng.integers(0, cache_rows, batch - n_cold)
            ids = np.concatenate([cold_ids, hot_ids])
            rng.shuffle(ids)
            ids_np.append(ids.astype(np.int64))
        ids_dev = [jnp.asarray(a) for a in ids_np]

        # prefetch attaches AFTER the model wrap so the ON arm's
        # ExtentReader and sync fallbacks both run under the model
        stores = {
            mode: load_disk_tier_store(tmp_dirs[mode],
                                       hot_rows=cache_rows)[0]
            for mode in ("off", "on")}
        models = {}
        if args.storage_latency_us:
            for mode, store in stores.items():
                models[mode] = qio.StorageModel(args.storage_latency_us,
                                                qd=args.storage_qd)
                store.mmap_array = ModeledLatencyMmap(
                    store.mmap_array, models[mode])
        ring_rows = args.prefetch_rows or 4 * batch
        pf_kwargs = dict(workers=args.io_workers, io_qd=args.io_qd,
                         io_engine=args.io_engine)
        stores["on"].enable_cold_prefetch(ring_rows,
                                          io_model=models.get("on"),
                                          **pf_kwargs)

        def run_round(mode, lo, hi):
            """One timed round of steps [lo, hi) through an arm's
            store. The ON arm re-enters steady state per round (stage
            its first batch INSIDE the timed region — the honest
            amortized cost of resuming the rhythm)."""
            store = stores[mode]
            batch_sums = []
            t0 = time.perf_counter()
            if mode == "on":
                evict(store, mode)
                f = store.stage_frontier(ids_np[lo])
                if f is not None:
                    f.result()
                for i in range(lo, hi):
                    x = store[ids_dev[i]]
                    if i + 1 < hi:       # publish BEFORE the compute:
                        store.stage_frontier(ids_np[i + 1])
                    y = compute(x, w)    # ...which the disk read overlaps
                    jax.block_until_ready(y)
                    batch_sums.append(y)
                    evict(store, mode)   # bigger-than-RAM: first-touch
            else:
                for i in range(lo, hi):
                    evict(store, mode)
                    x = store[ids_dev[i]]
                    y = compute(x, w)
                    jax.block_until_ready(y)
                    batch_sums.append(y)
            return time.perf_counter() - t0, batch_sums

        # warmup both arms: compile programs off the clock
        for store in stores.values():
            jax.block_until_ready(compute(store[ids_dev[0]], w))
        # the arms run INTERLEAVED in ABBA rounds (off,on,on,off): the
        # box's storage latency drifts by minutes-scale factors, and
        # whole-arm timing hands one arm the slow minutes — the same
        # drift-cancellation discipline as --ab-dedup / --ab-quant, at
        # half-run granularity because the ON arm pays one serial
        # staging to re-enter its publication rhythm per round (at
        # finer rounds that re-entry cost dominates the measurement)
        round_len = max(iters // 2, 2)
        elapsed = {"off": 0.0, "on": 0.0}
        sums = {"off": [], "on": []}
        steps_timed = 0
        for r, lo in enumerate(range(0, iters, round_len)):
            hi = min(lo + round_len, iters)
            order = ("off", "on") if r % 2 == 0 else ("on", "off")
            for mode in order:
                dt, batch_sums = run_round(mode, lo, hi)
                elapsed[mode] += dt
                sums[mode] += [float(y) for y in batch_sums]
            steps_timed += hi - lo
        arms = {}
        io_facts = None
        for mode, store in stores.items():
            pf = store._cold_prefetch
            arms[mode] = {
                "steps_per_s": steps_timed / elapsed[mode],
                "cold_rows_per_s": n_cold * steps_timed / elapsed[mode],
                "prefetch_hit_rate": (pf.stats()["hit_rate"]
                                      if pf is not None else None),
            }
            if pf is not None:
                s = pf.stats()
                io_facts = {"engine": s["io"]["engine"],
                            "extents": s["io"]["extents"],
                            "coalescing_factor":
                                s["io"]["coalescing_factor"],
                            "depth_peak": s["io"]["depth_peak"],
                            "read_mb": s["io"]["bytes_read"] / 1e6,
                            "truncated_rows": s["truncated_rows"]}
        # bit-identity, UNTIMED pass one batch at a time (bounded
        # memory at any scale; gather correctness is ring-state-
        # independent, so verifying after the race-y timed loops is
        # exactly as strong)
        rows_identical = all(
            np.array_equal(np.asarray(stores["off"][ids]),
                           np.asarray(stores["on"][ids]))
            for ids in ids_dev)
        sums_identical = sums["off"] == sums["on"]

        # the staged-rows/s pin: the SAME publication stream staged
        # through (a) the QD1 per-row mmap path (workers=1,
        # io_engine="mmap" — the pre-parallel-IO staging worker) and
        # (b) the deep-queue parallel path (coalesced extents, reader
        # pool, N staging workers). Fresh ring each so both arms stage
        # the same demand; under the model both pay the same price per
        # request — the ratio is pure issue discipline (coalescing x
        # overlap). Untimed region for the step A/B above; runs after
        # the bit-identity pass so the arms' lookup behavior stayed
        # pure while it mattered.
        def staging_rate(store, model, **kwargs):
            pf = store.enable_cold_prefetch(ring_rows, io_model=model,
                                            **kwargs)
            t0 = time.perf_counter()
            for a in ids_np:
                pf.publish(a, block=True).result()
            dt = time.perf_counter() - t0
            return pf.stats()["staged_rows"] / dt

        qd1_rate = staging_rate(stores["off"], None, workers=1,
                                io_engine="mmap")
        qdn_rate = staging_rate(stores["on"], models.get("on"),
                                **pf_kwargs)
        qd_speedup = qdn_rate / max(qd1_rate, 1e-9)

        for store in stores.values():
            store.close()
        speedup = (arms["on"]["steps_per_s"]
                   / arms["off"]["steps_per_s"])
        out[f"cold={frac:g}"] = {
            **{f"{k}_{m}": v for m, arm in arms.items()
               for k, v in arm.items() if v is not None},
            "speedup": speedup,
            "staged_rows_per_s_qd1": qd1_rate,
            "staged_rows_per_s_qdn": qdn_rate,
            "staging_qd_speedup": qd_speedup,
            "rows_bit_identical": rows_identical,
            "sums_bit_identical": sums_identical,
            "evict": {f"{k}_{m}": v for m, (c, f_) in
                      evict_stats.items()
                      for k, v in (("calls", c), ("failures", f_))},
            **({"io": io_facts} if io_facts else {}),
        }
        print(f"[ab-prefetch cold={frac:g}] "
              f"off {arms['off']['steps_per_s']:.2f} steps/s "
              f"({arms['off']['cold_rows_per_s'] / 1e6:.2f} Mcold-rows/s)"
              f" | on {arms['on']['steps_per_s']:.2f} steps/s "
              f"({arms['on']['cold_rows_per_s'] / 1e6:.2f} Mcold-rows/s,"
              f" hit {arms['on']['prefetch_hit_rate']:.1%}) -> "
              f"{speedup:.2f}x, rows identical: {rows_identical}, "
              f"sums identical: {sums_identical}")
        print(f"[ab-prefetch cold={frac:g}] staging: QD1 mmap "
              f"{qd1_rate / 1e3:.1f} Krows/s | parallel "
              f"({pf_kwargs['workers']} workers, io_qd="
              f"{pf_kwargs['io_qd']}) {qdn_rate / 1e3:.1f} Krows/s -> "
              f"{qd_speedup:.2f}x"
              + (f" [{io_facts['engine']}, "
                 f"{io_facts['coalescing_factor']:.1f} rows/extent, "
                 f"depth peak {io_facts['depth_peak']}]"
                 if io_facts and io_facts["coalescing_factor"] else ""))
    for d in tmp_dirs.values():
        shutil.rmtree(d, ignore_errors=True)
    rnd = lambda v: (round(v, 4) if isinstance(v, float) else
                     {kk: (round(vv, 4) if isinstance(vv, float)
                           else vv) for kk, vv in v.items()}
                     if isinstance(v, dict) else v)
    print(json.dumps({"bench": "ab_prefetch", "rows": rows, "dim": dim,
                      "batch": batch, "iters": iters, "dup": dup,
                      "compute_iters": args.compute_iters,
                      "storage_model": {
                          "latency_us": args.storage_latency_us,
                          "qd": args.storage_qd,
                          "io_workers": args.io_workers,
                          "io_qd": args.io_qd},
                      "results": {k: {kk: rnd(vv)
                                      for kk, vv in v.items()}
                                  for k, v in out.items()}}))


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--rows", type=int, default=2_450_000)
    p.add_argument("--dim", type=int, default=100)
    p.add_argument("--batch", type=int, default=400_000,
                   help="ids per gather (~a 3-hop products frontier)")
    p.add_argument("--iters", type=int, default=20)
    p.add_argument("--pallas", action="store_true")
    p.add_argument("--bf16", action="store_true")
    p.add_argument("--tiered", type=float, default=None, metavar="FRAC",
                   help="bench the tiered Feature store with FRAC of "
                        "rows cached in HBM (rest in the host tier)")
    p.add_argument("--prefetch", action="store_true",
                   help="with --tiered: double-buffer via prefetch()")
    p.add_argument("--offload", action="store_true",
                   help="with --tiered: host_placement='offload' — the "
                        "cold tier stays a pinned_host jax array and "
                        "the whole lookup fuses into one dispatch "
                        "(UVA-gather analogue; TPU/GPU only)")
    p.add_argument("--ab-dedup", action="store_true",
                   help="duplicate-heavy frontier A/B: fused tiered "
                        "lookup, dedup on/off x masked on/off")
    p.add_argument("--ab-quant", action="store_true",
                   help="dtype-policy A/B at equal shapes: fp32 vs "
                        "bf16 vs int8 tiers on the same id streams")
    p.add_argument("--ab-prefetch", action="store_true",
                   help="cold-tier (disk mmap) prefetch A/B: "
                        "frontier-ahead staging on vs synchronous "
                        "reads, end-to-end steps/s per cold fraction")
    p.add_argument("--cold-fracs", default="0.25,0.5,0.9",
                   help="with --ab-prefetch: comma-separated cold "
                        "(disk-tier) share of each batch's ids")
    p.add_argument("--compute-iters", type=int, default=6,
                   help="with --ab-prefetch: tanh-matmul rounds in the "
                        "per-step compute the staging overlaps with")
    p.add_argument("--prefetch-rows", type=int, default=None,
                   help="with --ab-prefetch: staging-ring capacity "
                        "(default 4x batch)")
    p.add_argument("--keep-page-cache", action="store_true",
                   help="with --ab-prefetch: skip the per-step "
                        "page-cache eviction — measures the (warm) "
                        "in-RAM regime instead of bigger-than-RAM "
                        "first-touch reads")
    p.add_argument("--storage-latency-us", type=float, default=0.0,
                   help="with --ab-prefetch: charge a deterministic "
                        "per-REQUEST storage service time on every "
                        "disk read in BOTH arms (io.StorageModel; "
                        "sleep releases the GIL so overlap is honest)."
                        " The sync/mmap path issues one request per "
                        "unique row serially (QD1); the parallel "
                        "staging path issues one per coalesced extent "
                        "from its reader pool, overlapped up to "
                        "--storage-qd — the reproducible arm on boxes "
                        "whose hypervisor caches the artifact")
    p.add_argument("--storage-qd", type=int, default=16,
                   help="with --storage-latency-us: the modeled "
                        "device's queue depth (requests it overlaps)")
    p.add_argument("--io-workers", type=int, default=2,
                   help="with --ab-prefetch: staging workers sharding "
                        "each publication's unique-row set (ON arm)")
    p.add_argument("--io-qd", type=int, default=16,
                   help="with --ab-prefetch: the ExtentReader pool's "
                        "queue depth (in-flight preadv requests)")
    p.add_argument("--io-engine", default="auto",
                   choices=("auto", "direct", "pread", "mmap"),
                   help="with --ab-prefetch: ON-arm read engine "
                        "(auto probes O_DIRECT, falls back to "
                        "buffered preadv; mmap = the compat per-row "
                        "fancy-index)")
    p.add_argument("--dup", type=float, default=8.0,
                   help="with --ab-dedup: duplicate factor "
                        "(batch / distinct ids per batch)")
    p.add_argument("--pad", type=float, default=0.35,
                   help="with --ab-dedup: -1 padding share of the "
                        "masked stream (static frontier caps run well "
                        "past realized uniques)")
    args = p.parse_args()

    if args.ab_prefetch and "xla_cpu_multi_thread_eigen" not in \
            os.environ.get("XLA_FLAGS", ""):
        # model DEVICE compute: in the real deployment the per-step
        # compute runs on the accelerator and costs zero host CPU, so
        # the staging thread has the host to itself. The CPU A/B's
        # stand-in compute would otherwise saturate every core and
        # "overlap" could only steal from it — pin the XLA CPU compute
        # to one thread so a core stays free, the way a TPU would
        # leave the whole host free. (Must land before jax init.)
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                                   + " --xla_cpu_multi_thread_eigen"
                                     "=false").strip()
    from _common import configure_jax
    jax = configure_jax()
    import jax.numpy as jnp

    if args.ab_dedup:
        run_ab_dedup(args, jax, jnp)
        return
    if args.ab_quant:
        run_ab_quant(args, jax, jnp)
        return
    if args.ab_prefetch:
        run_ab_prefetch(args, jax, jnp)
        return

    dtype = jnp.bfloat16 if args.bf16 else jnp.float32
    key = jax.random.key(0)

    @jax.jit
    def make_ids(k):
        return jax.random.randint(k, (args.batch,), 0, args.rows,
                                  dtype=jnp.int32)

    if args.tiered is not None:
        import quiver_tpu as qv
        frac = args.tiered
        rng = np.random.default_rng(0)
        feat_np = rng.standard_normal(
            (args.rows, args.dim)).astype(np.float32)
        if args.bf16:
            feat_np = feat_np.astype(jnp.bfloat16)
        row_bytes = args.dim * feat_np.dtype.itemsize
        f = qv.Feature(device_cache_size=int(args.rows * frac) * row_bytes,
                       host_placement="offload" if args.offload
                       else "numpy")
        f.from_cpu_tensor(feat_np)
        label = (f"tiered cache={frac:.0%}"
                 + (" offload" if args.offload else "")
                 + (" prefetch" if args.prefetch else " sync"))
        ids = [make_ids(jax.random.fold_in(key, 10 + i))
               for i in range(args.iters)]
        # warmup (compile both tiers' programs)
        jax.block_until_ready(f[ids[0]])

        t0 = time.perf_counter()
        if args.prefetch:
            fut = f.prefetch(ids[0])
            for i in range(args.iters):
                out = fut.result()
                if i + 1 < args.iters:
                    fut = f.prefetch(ids[i + 1])
                # consume the batch on-device (stand-in for the model
                # step the staging overlaps with)
                s = jnp.sum(out)
            jax.block_until_ready(s)
        else:
            for i in range(args.iters):
                s = jnp.sum(f[ids[i]])
            jax.block_until_ready(s)
        dt = time.perf_counter() - t0
    else:
        from quiver_tpu.ops.pallas.gather import gather_rows
        feat = jax.jit(
            lambda k: jax.random.normal(k, (args.rows, args.dim),
                                        dtype=dtype)
        )(jax.random.fold_in(key, 1))

        if args.pallas:
            if args.dim % 128:
                # pre-pad outside the timed loop: gather_rows would
                # otherwise re-pad the whole table every call and the
                # GB/s figure would measure the pad copy, not the kernel
                feat = jnp.pad(feat, ((0, 0), (0, 128 - args.dim % 128)))
                jax.block_until_ready(feat)
            run = gather_rows
        else:
            # feat MUST be a jit argument: a closed-over device array is
            # embedded in the HLO as a literal constant (~1GB here)
            run = jax.jit(lambda feat, ids: jnp.take(feat, ids, axis=0))

        out = run(feat, make_ids(jax.random.fold_in(key, 2)))
        jax.block_until_ready(out)
        label = "pallas" if args.pallas else "xla-take"

        t0 = time.perf_counter()
        for i in range(args.iters):
            out = run(feat, make_ids(jax.random.fold_in(key, 10 + i)))
        jax.block_until_ready(out)
        dt = time.perf_counter() - t0

    bytes_moved = args.iters * args.batch * args.dim * \
        jnp.dtype(dtype).itemsize
    print(f"[{label} {jnp.dtype(dtype).name}] {bytes_moved / 1e9:.2f} GB "
          f"in {dt:.3f}s -> {bytes_moved / dt / 1e9:.2f} GB/s")


if __name__ == "__main__":
    main()
