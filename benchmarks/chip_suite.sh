#!/bin/sh
# THE on-chip measurement sweep: one process per step, one after the
# other (a chip belongs to one process at a time), each under a
# generous timeout. Appends to benchmarks/chip_suite.log (gitignored).
#
# Usage: sh benchmarks/chip_suite.sh [section ...]
#   sections: verify prof fleet chaos bench dispatch sampler gather
#             tiered offload io e2e exchange mixed hetero micro
#             ablate capacity regress
#   default       = every section
#   quick         = bench only (the metric of record; also warms the
#                   compile cache for a later full sweep)
cd "$(dirname "$0")/.."
LOG=benchmarks/chip_suite.log
# mirror every bench's measurement record to the shared JSONL history
# — the final regress section reads it, so
# THIS sweep's numbers are part of what the sentinel judges
QT_METRICS_JSONL=${QT_METRICS_JSONL:-benchmarks/metrics.jsonl}
export QT_METRICS_JSONL
# sweep start epoch: the final regress section judges only JSONL
# records from >= this instant (what THIS sweep measured)
SUITE_T0=$(date +%s)
. benchmarks/_suite_common.sh

SECTIONS="${*:-verify prof fleet chaos trace bench dispatch sampler fuse gather tiered offload io e2e exchange mixed hetero micro ablate capacity regress}"
[ "$SECTIONS" = "quick" ] && SECTIONS="bench"

want() {
    case " $SECTIONS " in *" $1 "*) return 0;; *) return 1;; esac
}

date | tee -a "$LOG"
echo "sections: $SECTIONS" | tee -a "$LOG"

# static invariant verifier FIRST: host AST rules + jaxpr rules over
# the FULL entry-point registry (CPU, tracing only — never claims the
# chip); ERROR findings land as `lint` JSONL records beside the bench
# history, so qt_top shows them red in the same view
if want verify; then
    step env JAX_PLATFORMS=cpu python -u scripts/qt_verify.py --jsonl "$QT_METRICS_JSONL"
fi

# per-stage attribution + roofline efficiency (qt-prof): best-of-N
# timing of every registered entry + lattice point against the
# analytic cost model and this box's probed peaks — CPU-only like
# verify (never claims the chip); profile records land beside the
# bench history so qt_top shows the stage panel in the same view
if want prof; then
    step env JAX_PLATFORMS=cpu python -u scripts/qt_prof.py --quick --jsonl "$QT_METRICS_JSONL"
fi

# fleet observability plane smoke (qt-agg): synthesize two replica
# sinks (one crossing a rollover seam), aggregate, scrape the real
# /metrics + /healthz endpoints, validate the Prometheus exposition —
# CPU-only like verify/prof (never claims the chip); the fleet/anomaly
# records land beside the bench history so qt_top --fleet shows them
if want fleet; then
    step env JAX_PLATFORMS=cpu python -u scripts/qt_agg.py --smoke --no-color --jsonl "$QT_METRICS_JSONL"
fi

# chaos resilience (qt-chaos): supervisor + 3 REAL serve replicas on
# the CPU backend, a seeded FaultPlan SIGKILLs the victim mid-load and
# arms survivors with a low-rate sink-write fault plan — the verdict
# (accepted-p99 ratio, error rate, detection + recovery latency) lands
# in QT_METRICS_JSONL as lower-is-better trajectory groups the final
# regress section judges. CPU-only like verify/prof/fleet (never
# claims the chip).
if want chaos; then
    step env JAX_PLATFORMS=cpu python -u benchmarks/bench_serving.py --chaos-only
fi

# tail-sampled tracing (qt-tail): 3 REAL serve replicas each running
# an always-on TailSampler into their heartbeat sink, a tracing RPC
# client, and two seeded mid-load faults (one delayed batch, one
# errored batch) — the verdict checks both traces were KEPT and
# ASSEMBLED across client + replica segments with the dominant span
# identified, while healthy traces drop. CPU-only like
# verify/prof/fleet/chaos (never claims the chip).
if want trace; then
    step env JAX_PLATFORMS=cpu python -u benchmarks/bench_serving.py --tail-only
fi

# metric of record: the full default sweep (pair/sort, overlap/sort,
# overlap/butterfly; best wins, labeled) + window + exact side figures
if want bench; then
    step python -u bench.py
fi

# dispatch probe (now exercises the fused single-dispatch Feature path)
if want dispatch; then
    step python -u benchmarks/debug_dispatch.py
fi

# sampling: pallas kernel vs jnp hop-1, exact scattered vs wide-fetch,
# weighted (GAT) exact pool vs windowed draw
if want sampler; then
    step python -u benchmarks/bench_sampler.py --pallas
    step python -u benchmarks/bench_sampler.py --hop1 exact
    step python -u benchmarks/bench_sampler.py --hop1 wide
    step python -u benchmarks/bench_sampler.py --hop1 rotation
    step python -u benchmarks/bench_sampler.py --hop1 wexact
    step python -u benchmarks/bench_sampler.py --hop1 wwindow
fi

# fused single-kernel sample+gather hop (qt-fuse): bit equivalence vs
# the split two-program oracle, fused/split steps-per-s ratio, modeled
# gather_index_bytes=0. Runs on the chip; the CPU interpret-mode A/B
# (the equivalence half on any box) is exercised by the fuse section's
# second line — keep both lines green. Round 21 (qt-fuse-deep) adds
# the multi-hop pair: the whole [15,10,5] ladder as ONE fused program
# vs the per-hop split walk — same bit-equal hard gate, whole-walk
# steps-per-s ratio, modeled index bytes zero across ALL hops (the
# CPU-interpret line is the smoke figure; the chip line is the record)
if want fuse; then
    step python -u benchmarks/bench_fused.py
    step env JAX_PLATFORMS=cpu python -u benchmarks/bench_fused.py --iters 2
    step python -u benchmarks/bench_fused.py --multihop
    step env JAX_PLATFORMS=cpu python -u benchmarks/bench_fused.py --multihop --iters 2
fi

# feature gather GB/s: raw device + pallas (128-aligned and padded)
if want gather; then
    step python -u benchmarks/bench_feature.py
    step python -u benchmarks/bench_feature.py --bf16
    step python -u benchmarks/bench_feature.py --pallas
    step python -u benchmarks/bench_feature.py --pallas --dim 128
    step python -u benchmarks/bench_feature.py --dim 128
fi

# tiered host-tier grid at a reduced scale
if want tiered; then
    step python -u benchmarks/bench_feature.py --tiered 1.0
    step python -u benchmarks/bench_feature.py --tiered 0.2 --rows 300000 --batch 20000 --iters 5
    step python -u benchmarks/bench_feature.py --tiered 0.2 --rows 300000 --batch 20000 --iters 5 --prefetch
    step python -u benchmarks/bench_feature.py --tiered 0.0 --rows 300000 --batch 20000 --iters 5
    step python -u benchmarks/bench_feature.py --tiered 0.0 --rows 300000 --batch 20000 --iters 5 --prefetch
fi

# cold-tier parallel IO: the frontier-ahead prefetch A/B under the
# deterministic queue-depth storage model (CPU is fine — the model is
# the device; the hypervisor page cache cannot hide the win) — pins
# QD-N staged-rows/s vs QD1 and end-to-end steps/s at cold 0.9, plus
# the real-eviction regime for the fio-relative number on honest disks
if want io; then
    step env JAX_PLATFORMS=cpu python -u benchmarks/bench_feature.py --ab-prefetch --rows 120000 --dim 64 --batch 8000 --iters 6 --cold-fracs 0.5,0.9 --storage-latency-us 50 --storage-qd 16 --io-workers 2 --io-qd 16
    step env JAX_PLATFORMS=cpu python -u benchmarks/bench_feature.py --ab-prefetch --rows 120000 --dim 64 --batch 8000 --iters 6 --cold-fracs 0.9
fi

# pinned_host cold tier: does the TPU compiler take pinned_host
# operands, and what does the one-dispatch offload lookup buy?
if want offload; then
    step python -u benchmarks/host_mode_probe.py
    step python -u benchmarks/bench_feature.py --tiered 0.2 --rows 300000 --batch 20000 --iters 5 --offload
    step python -u benchmarks/bench_feature.py --tiered 0.0 --rows 300000 --batch 20000 --iters 5 --offload
fi

# end-to-end epoch seconds vs the reference's 11.1 s
if want e2e; then
    step python -u benchmarks/bench_e2e.py --method rotation --layout overlap
    step python -u benchmarks/bench_e2e.py --method rotation --layout overlap --shuffle butterfly
    step python -u benchmarks/bench_e2e.py --method rotation --layout pair
    step python -u benchmarks/bench_e2e.py --method window --layout overlap
    step python -u benchmarks/bench_e2e.py --method exact
    step python -u benchmarks/bench_e2e.py --method rotation --layout overlap --bf16
fi

# fused dist-step exchange: dense [H, B] vs compact dedup'd [H, cap]
# (multi-host wire bytes; pinned to the virtual CPU mesh — the A/B is
# about bytes and branch behavior, not TPU latency)
if want exchange; then
    step env JAX_PLATFORMS=cpu python -u benchmarks/bench_e2e.py --ab-exchange
fi

# mixed sampler adaptivity: device-only vs mixed + converged split
if want mixed; then
    step python -u benchmarks/bench_mixed.py --sampling rotation
    step python -u benchmarks/bench_mixed.py --sampling exact
    step python -u benchmarks/bench_mixed.py --weighted
fi

# hetero sampler per-mode cost vs homog rotation anchor
if want hetero; then
    step python -u benchmarks/bench_hetero.py
fi

# primitive/gather/layout micro tables for the docs + per-stage profile
if want micro; then
    step python -u benchmarks/micro_ops.py --suite layout --iters 10
    step python -u benchmarks/micro_ops.py --suite gather --iters 10
    step python -u benchmarks/micro_ops.py --suite primitives --iters 10
    step python -u benchmarks/profile_stages.py --iters 10
fi

# fused-epoch stage ablation (how much of a batch is compaction?)
if want ablate; then
    step python -u benchmarks/ablate.py
fi

# replay-verified capacity (qt-capacity): calibrate the capacity
# model on this box, predict the sustainable rate of the default
# tenant mix, then PROVE it — a trace-replay search for the measured
# sustained rate (±25% gate) plus the 10x best-effort flash-crowd
# flood gate (interactive p99 within SLO while best_effort absorbs
# the shed). CPU-only replay smoke (never claims the chip); the
# capacity record + verdict land in QT_METRICS_JSONL, and the
# non-smoke capacity_abs_err_frac is a lower-is-better trajectory
# group the final regress section judges. The capacity report renders
# from the record just emitted.
if want capacity; then
    step env JAX_PLATFORMS=cpu python -u benchmarks/bench_capacity.py --smoke
    step env JAX_PLATFORMS=cpu python -u scripts/qt_capacity.py --jsonl "$QT_METRICS_JSONL" --no-color
fi

# regression sentinel, LAST: judge the records THIS sweep mirrored to
# QT_METRICS_JSONL (--since scopes out stale history lines) against
# the committed BENCH_r*.json trajectory's best prior non-skipped
# values; a >15% drop fails the suite loudly (records without a number
# are ignored, never counted as regressions)
if want regress; then
    step python -u scripts/bench_regress.py --since "$SUITE_T0"
fi

date | tee -a "$LOG"
echo "chip suite complete ($SECTIONS) -> $LOG"
