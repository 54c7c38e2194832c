"""CPU smoke invocation of the official bench harness (tier-1).

Keeps bench.py itself — argument parsing, the epoch program, the JSON
contract, the per-mode SEPS keys — regression-tested on every CI run at
a reduced scale (``--platform cpu``, every key prefixed ``cpu_``), and
pins that without that option a box with no chip gets no metric at all.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_bench_cpu_smoke_json_contract(tmp_path):
    sink_path = str(tmp_path / "metrics.jsonl")
    env = dict(os.environ)
    env.update({
        "QT_METRICS_JSONL": sink_path,
        # smallest honest scale: one rotation arm (pair+sort), two
        # batches — proves the harness runs, not a comparable number
        "QT_BENCH_NODES": "40000",
        "QT_BENCH_AVG_DEG": "8",
        "QT_BENCH_BATCHES": "2",
        "QT_BENCH_BATCH": "256",
        "QT_BENCH_LAYOUT": "pair",
        "QT_BENCH_SHUFFLE": "sort",
    })
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"),
         "--platform", "cpu"],
        env=env, capture_output=True, text=True, timeout=420, cwd=REPO)
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [l for l in proc.stdout.splitlines() if l.strip()]
    assert len(lines) == 1, proc.stdout          # ONE JSON line
    line = json.loads(lines[0])
    # every key says cpu: no figure of this run can be filed under a
    # device metric's name
    assert all(k.startswith("cpu_") for k in line), sorted(line)
    out = {k[len("cpu_"):]: v for k, v in line.items()}
    assert out["device"]["platform"] == "cpu"
    assert out["unit"] == "edges/s"
    assert out["value"] and out["value"] > 0
    # per-mode SEPS tracked by the official metric (exact-mode gap)
    for mode in ("rotation", "exact", "window"):
        assert out[f"{mode}_mode_value"] > 0
        assert out[f"{mode}_mode_vs_baseline"] is None   # not comparable
    # the bandwidth half: dedup tiered feature-gather rows/sec + the
    # bytes/batch currency (host tier + exchange) the dtype policy
    # shrinks
    assert out["feature_gather_rows_per_s"] > 0
    assert out["host_bytes_per_batch"] > 0
    assert out["exchange_bytes_per_batch"] > 0
    # fp32 store in the smoke config: the exchange ships one int32
    # request + one fp32 row per slot — pin the analytic formula so the
    # key can't silently change meaning
    assert out["exchange_bytes_per_batch"] % (4 + 64 * 4) == 0
    # the compact dedup'd exchange model (exchange_cap): duplicate-
    # heavy batches (pool = batch/8 distinct ids) must fit the default
    # cap sizing, so the compact figure is the cap*H block — well under
    # the dense per-slot figure (the >= 4x pin at bench FRONTIER shapes
    # lives in tests/test_dist_train.py's traced-payload test; here the
    # dense side is only batch-sized, so pin 2x)
    assert out["exchange_cap"] > 0
    assert out["exchange_compact_bytes_per_batch"] % (4 + 64 * 4) == 0
    assert (out["exchange_compact_bytes_per_batch"] * 2
            <= out["exchange_bytes_per_batch"])
    # OBSERVED device counters (quiver_tpu.metrics) next to the
    # analytic mirrors: the smoke batches draw from a pool of
    # batch/8 distinct ids, so the dup factor must be well above 1 and
    # the 25%-cache store must see a hit rate strictly inside (0, 1)
    assert 0.0 < out["observed_hot_hit_rate"] < 1.0
    assert out["observed_dup_factor"] > 1.5
    assert out["observed_cold_rows_per_batch"] > 0
    # the disk rung: cold-tier rows/sec through the frontier-ahead
    # prefetch path + the OBSERVED staging-ring hit rate (every batch
    # is published one step ahead and the ring is sized generously, so
    # the rate must be high — and these two keys are what
    # scripts/bench_regress.py tracks as their own trajectory groups)
    assert out["cold_rows_per_s"] > 0
    assert 0.5 < out["prefetch_hit_rate"] <= 1.0
    assert out["prefetch_staged_rows_per_batch"] > 0
    # staging throughput through the parallel-IO extent reader
    # (workers=2) — the third bench_regress trajectory group
    assert out["cold_staged_rows_per_s"] > 0
    # qt-prof: gather roofline efficiency (modeled bytes / timed wall
    # / probed same-pass random-gather peak — the fourth bench_regress
    # trajectory group) + the coarse per-stage attribution block
    assert 0.0 < out["gather_efficiency"] <= 2.0
    assert out["gather_achieved_gbps"] > 0
    assert out["probe_gather_gbps"] > 0
    # qt-shard: the sharded-serve pass over the 2-partition store ran
    # on the forced 2-device host mesh — aggregate throughput, batch
    # dispatch p99 (both bench_regress trajectory groups, the p99
    # inverted) and the OBSERVED locality hit rate: home-skewed
    # arrivals with ~10% strays over a ~90%-intra-partition graph,
    # so the rate must land strictly inside (0, 1)
    assert out["sharded_agg_rps"] > 0
    assert out["sharded_p99_ms"] > 0
    assert 0.0 < out["locality_hit_rate"] < 1.0
    assert set(out["stage_ms"]) == {"sample", "gather", "cold_tier"}
    assert all(v > 0 for v in out["stage_ms"].values())
    assert sum(out["stage_shares"].values()) == pytest.approx(1.0,
                                                              abs=0.01)
    assert out["vs_baseline"] is None
    assert "error" not in out
    # the same record also landed in the structured metrics log
    # (QT_METRICS_JSONL) with the shared {ts, kind, ...} JSONL schema,
    # possibly followed by the telemetry hub's advisory `advice`
    # records (the replan over the observed gather counters)
    with open(sink_path) as f:
        recs = [json.loads(l) for l in f if l.strip()]
    bench_recs = [r for r in recs if r["kind"] == "bench"]
    assert len(bench_recs) == 1
    assert bench_recs[0]["cpu_value"] == out["value"]
    assert isinstance(bench_recs[0]["ts"], float)
    for r in recs:
        assert r["kind"] in ("meta", "bench", "advice")
        if r["kind"] == "advice":
            assert r["recommended"] != r["current"] and r["reason"]


def test_bench_without_a_chip_prints_no_metric():
    """No chip, no ``--platform cpu``: bench.py exits non-zero and
    prints nothing that could be read as a result — a missing device is
    a failure, never a skip record or a CPU figure under a TPU name."""
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",         # a box with no accelerator
        "QT_BENCH_NODES": "40000",
        "QT_BENCH_BATCHES": "2",
        "QT_BENCH_BATCH": "256",
    })
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")],
        env=env, capture_output=True, text=True, timeout=420, cwd=REPO)
    assert proc.returncode != 0
    assert proc.stdout.strip() == "", proc.stdout
    assert "needs a TPU" in proc.stderr


def test_bench_serving_smoke_json_contract(tmp_path):
    """The serving load-generator bench (benchmarks/bench_serving.py)
    keeps its JSON contract CI-tested at smoke scale: one JSON line,
    the serial-vs-coalesced arms both measured, the 2x-overload record
    with the shed variant mix, and the fanout/accuracy agreement table
    — plus the QT_METRICS_JSONL mirror with the shared schema. (The
    comparable numbers — the >=5x coalescing ratio at the 100 ms p99
    budget — come from the full-scale run recorded in
    docs/measurements_r10.md; smoke proves the harness, not the
    ratio.)"""
    sink_path = str(tmp_path / "metrics.jsonl")
    env = dict(os.environ)
    env.update({
        "QT_METRICS_JSONL": sink_path,
        "JAX_PLATFORMS": "cpu",
        "QT_SERVE_SMOKE": "1",
    })
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmarks",
                                      "bench_serving.py")],
        env=env, capture_output=True, text=True, timeout=420, cwd=REPO)
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [l for l in proc.stdout.splitlines() if l.strip()]
    assert len(lines) == 1, proc.stdout          # ONE JSON line
    out = json.loads(lines[0])
    assert "error" not in out
    assert out["unit"] == "requests/s"
    assert out["value"] and out["value"] > 0
    assert out["serial_rps"] > 0
    assert out["p99_budget_ms"] > 0
    # both arms ran at least one open-loop trial against the budget
    assert out["trials"]["serial"] and out["trials"]["coalesced"]
    assert out["trials"]["serial"][0]["mean_batch_fill"] == 1.0
    # the 2x-overload arm reports bounded-latency facts + variant mix
    ov = out["overload"]
    assert ov["rate_rps"] > 0 and ov["p99_ms"] > 0
    assert len(ov["variant_batches"]) == 3       # the shed ladder
    # the fleet-plane A/B ran both arms and the live /metrics scrape
    # against the attached plane answered in valid form
    fab = out["fleet_ab"]
    assert fab["detached"]["completed_rps"] > 0
    assert fab["attached"]["completed_rps"] > 0
    assert fab["rps_ratio"] and fab["rps_ratio"] > 0
    assert fab["scrape_ok"] is True
    assert fab["fleet_status"] in ("ok", "degraded")
    assert 0.0 <= fab["replica_health"] <= 1.0
    # the always-on tail-sampler A/B ran both arms, decided every
    # trace, stayed bounded, and surfaced its bench_regress keys
    tab = out["tail_ab"]
    assert tab["detached"]["completed_rps"] > 0
    assert tab["attached"]["completed_rps"] > 0
    assert out["tail_rps_ratio"] == tab["rps_ratio"] > 0
    assert tab["traces_completed"] > 0
    assert out["tail_kept_frac"] == tab["kept_frac"]
    assert 0.0 <= tab["kept_frac"] < 1.0         # not full capture
    assert tab["pending_high_water"] <= tab["pending_capacity"]
    assert isinstance(ov["p99_bounded"], bool)
    # accuracy/fanout tradeoff: full fanout vs itself is the noise
    # floor; every ladder entry reports an agreement fraction
    agree = out["fanout_argmax_agreement"]
    assert set(agree) == {"[10, 5]", "[4, 2]", "[2, 1]"}
    assert all(0.0 <= v <= 1.0 for v in agree.values())
    # the chaos kill A/B ran (smoke: jax-free fake replicas): the
    # victim died by the seeded plan, was restarted, nothing lost
    ch = out["chaos_ab"]
    assert ch["clean"]["accepted"] == ch["clean"]["requests"]
    assert ch["chaos"]["victim_restarts"] >= 1
    assert ch["chaos"]["accepted"] + sum(
        ch["chaos"]["errors"].values()) == ch["chaos"]["requests"]
    assert ch["chaos_error_rate"] <= 0.05
    assert ch["chaos_recovery_s"] is not None
    # the fake-fleet numbers stay NESTED: the tracked chaos_*
    # trajectory keys must come only from real-replica runs
    assert "chaos_detection_s" not in out
    # mirrored into the structured metrics log with the shared schema
    with open(sink_path) as f:
        recs = [json.loads(l) for l in f if l.strip()]
    recs = [r for r in recs if r["kind"] != "meta"]    # sink header
    assert len(recs) == 1
    assert recs[0]["kind"] == "bench"
    assert recs[0]["value"] == out["value"]


@pytest.mark.slow  # full sharded fleet build x3 partition counts, ~3 min
def test_bench_sharded_smoke_json_contract(tmp_path):
    """The qt-shard payoff bench (benchmarks/bench_sharded.py) keeps
    its JSON contract tested at smoke scale: the P=1/2/4 partition
    sweep with per-P bit-identity probes, and the locality-vs-
    health-only A/B where the honest in-process payoff is EXCHANGE
    BYTES per request (both arms premise-asserted onto the same
    fallback-free narrow program, so wall clock is parity — the bytes
    are what a real multi-host wire turns into latency)."""
    sink_path = str(tmp_path / "metrics.jsonl")
    env = dict(os.environ)
    env.update({
        "QT_METRICS_JSONL": sink_path,
        "JAX_PLATFORMS": "cpu",
        "QT_SHARD_SMOKE": "1",
    })
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmarks",
                                      "bench_sharded.py")],
        env=env, capture_output=True, text=True, timeout=420, cwd=REPO)
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [l for l in proc.stdout.splitlines() if l.strip()]
    assert len(lines) == 1, proc.stdout          # ONE JSON line
    out = json.loads(lines[0])
    assert "error" not in out
    assert out["unit"] == "requests/s"
    assert out["value"] and out["value"] > 0
    assert out["bit_identical"] is True
    # the partition sweep ran every count; P=1 is locality-trivial
    assert set(out["partitions"]) == {"1", "2", "4"}
    for p, row in out["partitions"].items():
        assert row["agg_rps"] > 0 and row["p99_ms"] > 0
    assert out["partitions"]["1"]["locality_hit_rate"] == 1.0
    # ...and the probe logits were identical across partition counts
    checksums = {row["probe_checksum"]
                 for row in out["partitions"].values()}
    assert len(checksums) == 1
    # the A/B: same fixed-shape narrow program in both arms (the
    # concentration-sized exchange_cap premise), strictly fewer
    # exchange bytes per request and a strictly higher hit rate
    # under locality routing
    ab = out["ab"]
    loc, health = ab["locality"], ab["health_only"]
    assert loc["fallback_batches"] == 0
    assert health["fallback_batches"] == 0
    assert loc["exch_bytes_per_req"] < health["exch_bytes_per_req"]
    assert loc["locality_hit_rate"] > health["locality_hit_rate"]
    assert ab["rps_ratio"] > 0
    assert isinstance(ab["locality_ge_health_rps"], bool)
    # mirrored into the structured metrics log with the shared schema
    with open(sink_path) as f:
        recs = [json.loads(l) for l in f if l.strip()]
    recs = [r for r in recs if r["kind"] != "meta"]
    assert len(recs) == 1
    assert recs[0]["kind"] == "bench"
    assert recs[0]["value"] == out["value"]
