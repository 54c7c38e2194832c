"""qt-prof: the analytic cost model, the machine probe, the stage
profiler's attribution + roofline records, the injected-slowdown
acceptance (attribution shifts AND the hub's stage-share watch fires),
and the no-host-sync pin with the profiler imported."""

import json
import os
import tempfile

import pytest

import jax
import jax.numpy as jnp

from quiver_tpu.analysis.costmodel import CostModel, cost_of, cost_of_fn
from quiver_tpu.profile import (PROFILE_SERIES, ProfileGroup,
                                ProfileStage, StageProfiler,
                                machine_probe, render_records)

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# the cost model
# ---------------------------------------------------------------------------


class TestCostModel:
    def test_dot_general_flops(self):
        # [4,8] @ [8,3]: 2 * out(4*3) * K(8) = 192
        c = cost_of_fn(lambda a, b: a @ b,
                       (jnp.ones((4, 8)), jnp.ones((8, 3))))
        assert c.flops == 192

    def test_gather_bytes_and_index_bytes(self):
        # table [100,16] f32, ids [10] i32: reads 10*16*4 = 640 B,
        # index buffer 10*4 = 40 B — the fusion-headroom term
        c = cost_of_fn(lambda t, i: t[i],
                       (jnp.ones((100, 16)), jnp.arange(10)))
        assert c.gather_bytes == 640
        assert c.gather_index_bytes == 40
        # neither the table (gathered) nor the ids (index) count as
        # full-read inputs — no double pricing
        assert c.input_bytes == 0
        assert c.output_bytes == 640

    def test_index_buffer_feeding_two_gathers_counts_once(self):
        def f(t1, t2, i):
            return t1[i], t2[i]
        c = cost_of_fn(f, (jnp.ones((50, 8)), jnp.ones((50, 4)),
                           jnp.arange(10)))
        assert c.gather_index_bytes == 40        # once, not twice
        assert c.gather_bytes == 10 * 8 * 4 + 10 * 4 * 4

    def test_scan_multiplies_by_trip_count(self):
        def f(x, w):
            def body(carry, _):
                return carry @ w, None
            out, _ = jax.lax.scan(body, x, None, length=7)
            return out
        c = cost_of_fn(f, (jnp.ones((4, 4)), jnp.ones((4, 4))))
        assert c.flops == 7 * 2 * 4 * 4 * 4

    def test_gathered_table_inside_scan_not_double_priced(self):
        # origin resolution must cross the scan boundary: a table
        # gathered inside the loop body is priced by its gathers, not
        # ALSO as a full input read
        def f(tbl, idx):
            def body(c, iv):        # iv: [3] vector -> a real gather
                return c + tbl[iv].sum(), None
            out, _ = jax.lax.scan(body, jnp.float32(0), idx)
            return out
        tbl = jnp.ones((100, 8))
        c = cost_of_fn(f, (tbl, jnp.arange(15).reshape(5, 3)))
        assert c.gather_bytes == 5 * 3 * 8 * 4
        # the 3200-byte table must NOT appear as a full input read
        assert c.input_bytes < tbl.size * 4

    def test_cond_prices_min_branch_and_records_spread(self):
        big = jnp.ones((64, 64))

        def f(pred, x):
            return jax.lax.cond(pred, lambda v: (v @ big @ big).sum(),
                                lambda v: v.sum(), x)
        c = cost_of_fn(f, (jnp.asarray(True), jnp.ones((1, 64))))
        # the cheap branch is the floor: no dot flops on it
        assert c.flops == 0

    def test_cond_floor_excludes_branch_only_index_bytes(self):
        # a gather that lives ONLY in the fallback branch (the compact
        # exchange's dense path shape): neither its rows NOR its index
        # buffer may leak into the min-branch floor — both belong to
        # the recorded spread
        def f(pred, t, i):
            return jax.lax.cond(pred,
                                lambda tt, ii: tt[ii].sum(),
                                lambda tt, ii: jnp.float32(0.0), t, i)
        c = cost_of_fn(f, (jnp.asarray(True), jnp.ones((100, 16)),
                           jnp.arange(10)))
        assert c.gather_bytes == 0
        assert c.gather_index_bytes == 0
        assert c.cond_extra_bytes >= 640 + 40   # rows + index spread

    def test_while_counts_once_and_flags(self):
        def f(x):
            return jax.lax.while_loop(lambda v: v.sum() < 10,
                                      lambda v: v + 1, x)
        c = cost_of_fn(f, (jnp.zeros(4),))
        assert c.while_loops == 1

    def test_registry_entry_prices_with_tiers(self):
        from quiver_tpu.analysis.registry import build_entry_specs
        spec = build_entry_specs("lookup_tiered")[0]
        c = cost_of(spec)
        assert isinstance(c, CostModel)
        assert c.gather_bytes > 0 and c.gather_index_bytes > 0
        assert c.tier_bytes            # the declared host tier priced
        assert c.total_bytes >= c.gather_bytes
        rec = c.record()
        assert rec["total_bytes"] == c.total_bytes
        assert "tier_bytes" in rec

    def test_fusion_headroom_on_the_fused_train_step(self):
        # the frontier-id round trip between sample and gather IS the
        # intermediate buffer the fused Pallas kernel (ROADMAP
        # frontier 2) deletes — it must be visible and nonzero on the
        # production fused step
        from quiver_tpu.analysis.registry import build_entry_specs
        c = cost_of(build_entry_specs("train_step")[0])
        assert c.gather_index_bytes > 0
        assert c.flops > 0


# ---------------------------------------------------------------------------
# the machine probe
# ---------------------------------------------------------------------------


class TestMachineProbe:
    def test_quick_probe_shape(self):
        p = machine_probe(quick=True, size_mb=2)
        for k in ("memcpy_gbps", "gather_gbps", "h2d_gbps",
                  "d2h_gbps"):
            assert p[k] > 0, k
        assert p["platform"] == jax.default_backend()
        assert p["size_mb"] == 2


# ---------------------------------------------------------------------------
# the profiler
# ---------------------------------------------------------------------------


def _matmul_stage(name, scale, dim=48):
    """A stage whose cost scales linearly with ``scale`` (scan of
    matmuls) — the injected-slowdown knob."""
    w = jnp.eye(dim)

    def fn(x):
        def body(c, _):
            return c @ w, None
        out, _ = jax.lax.scan(body, x, None, length=scale)
        return out
    jitted = jax.jit(fn)
    args = (jnp.ones((dim, dim)),)
    return ProfileStage(name, jitted, args,
                        cost=cost_of_fn(jitted, args))


def _group(scale_a=2, scale_b=2):
    return ProfileGroup("prof_test", [_matmul_stage("A", scale_a),
                                      _matmul_stage("B", scale_b)])


class TestStageProfiler:
    def test_record_shape_and_shares(self):
        prof = StageProfiler(reps=2, probe=machine_probe(quick=True,
                                                         size_mb=2))
        prof.add_group(_group())
        recs = prof.run()
        assert [r["entry"] for r in recs] == ["__machine__", "prof_test"]
        stages = recs[1]["stages"]
        assert [s["stage"] for s in stages] == ["A", "B"]
        for s in stages:
            assert s["mean_ms"] > 0 and s["best_ms"] <= s["mean_ms"]
            assert s["modeled"]["flops"] > 0
            assert s["achieved_gbps"] > 0
            assert 0 <= s["efficiency"]
        assert sum(s["share"] for s in stages) == pytest.approx(1.0,
                                                                abs=0.01)
        # rendering never crashes, machine line + stage rows present
        text = render_records(recs)
        assert "machine probe" in text and "prof_test" in text

    def test_sink_emits_profile_kind(self):
        from quiver_tpu.metrics import MetricsSink
        path = os.path.join(tempfile.mkdtemp(), "prof.jsonl")
        with MetricsSink(path) as sink:
            prof = StageProfiler(reps=1, sink=sink)
            prof.add_group(_group())
            prof.run()
        recs = [json.loads(l) for l in open(path) if l.strip()]
        recs = [r for r in recs if r["kind"] != "meta"]  # sink header
        assert recs and all(r["kind"] == "profile" for r in recs)
        assert recs[-1]["entry"] == "prof_test"

    def test_second_pass_compiles_nothing(self):
        prof = StageProfiler(reps=2)
        prof.add_group(_group())
        prof.run()
        base = sum(f._cache_size() for f in prof.jitted_fns)
        prof.run()
        assert sum(f._cache_size() for f in prof.jitted_fns) == base

    def test_donated_args_survive_profiling(self):
        # a donating program profiled repeatedly must neither fail on
        # an invalidated buffer nor kill the caller's original args
        @jax.jit
        def step(x):
            return x + 1.0
        donating = jax.jit(lambda x: x + 1.0, donate_argnums=(0,))
        x0 = jnp.arange(16.0)
        st = ProfileStage("donating", donating, (x0,),
                          donate_argnums=(0,),
                          cost=cost_of_fn(step, (x0,)))
        prof = StageProfiler(reps=3)
        prof.add_group(ProfileGroup("donated", [st]))
        prof.run()
        prof.run()
        # the original buffer is still alive and readable
        assert jax.device_get(x0)[5] == 5.0

    def test_ref_stage_share_semantics(self):
        # wide scale separation: both stages are dispatch-bound at
        # tiny scan lengths, which would let noise push part >= whole.
        # "whole" runs for milliseconds, so that one scheduler stall
        # under the six-worker suite (which 120 steps did not survive)
        # cannot carry a one-matmul "part" past it
        g = ProfileGroup("withref", [_matmul_stage("part", 1),
                                     _matmul_stage("whole", 4000)],
                         ref_stage="whole")
        prof = StageProfiler(reps=2)
        prof.add_group(g)
        rec = prof.run()[0]
        shares = {s["stage"]: s["share"] for s in rec["stages"]}
        assert shares["whole"] == pytest.approx(1.0)
        assert 0 < shares["part"] < 1.0


# ---------------------------------------------------------------------------
# the acceptance loop: injected slowdown -> attribution + anomaly
# ---------------------------------------------------------------------------


class TestInjectedSlowdown:
    def test_deoptimized_stage_shifts_attribution_and_raises_anomaly(self):
        from quiver_tpu.telemetry import TelemetryHub
        hub = TelemetryHub(window=4)       # DEFAULT_WATCHES armed,
        #                                    incl. the stage_share:*
        #                                    prefix drift watch
        prof = StageProfiler(reps=2, hub=hub)
        prof.add_group(_group(scale_a=2, scale_b=2))
        for _ in range(8):                 # the healthy baseline
            prof.run()
        # judge the MEDIAN of the baseline window, not the last point:
        # on this 2-vCPU box a single scheduler stall can skew one
        # pass's share of two equal microsecond stages past any sane
        # tolerance (observed under full-suite load), and the stall is
        # box noise, not attribution
        import numpy as np
        series = hub.series["stage_share:prof_test/B"]
        base_share = float(np.median(series.values()[-8:]))
        assert base_share == pytest.approx(0.5, abs=0.25)

        # deploy the de-optimized variant of stage B (1000x the work:
        # at 50x both stages were still dispatch-bound microseconds, and
        # under the six-worker suite the share stalled at 0.73)
        slow = StageProfiler(reps=2, hub=hub)
        slow.add_group(_group(scale_a=2, scale_b=2000))
        for _ in range(8):
            slow.run()
        slow_share = float(np.median(series.values()[-8:]))
        assert slow_share > 0.8, \
            "attribution did not shift to the de-optimized stage"
        anomalies = [a for a in hub.anomalies
                     if a["series"] == "stage_share:prof_test/B"]
        assert anomalies, \
            "stage-share drift never raised an anomaly through the hub"
        assert anomalies[-1]["shift"] > 0   # the share grew

    def test_prefix_watch_arms_per_matching_series(self):
        from quiver_tpu.telemetry import TelemetryHub
        hub = TelemetryHub(window=2, watches=())
        hub.watch("stage_share:*", "spike", threshold=0.9)
        hub.observe("stage_share:x/a", 0.5)      # below threshold
        hub.observe("stage_share:x/b", 0.95)     # above -> fires
        hub.observe("unrelated", 5.0)            # not matched
        assert [a["series"] for a in hub.anomalies] == \
            ["stage_share:x/b"]

    def test_prefix_watch_arms_existing_series(self):
        from quiver_tpu.telemetry import TelemetryHub
        hub = TelemetryHub(window=2, watches=())
        hub.observe("stage_share:x/a", 0.2)
        hub.watch("stage_share:*", "spike", threshold=0.9)
        hub.observe("stage_share:x/a", 0.95)
        assert [a["series"] for a in hub.anomalies] == \
            ["stage_share:x/a"]


# ---------------------------------------------------------------------------
# the invariant: profiling is a separate pass, hot paths stay sync-free
# ---------------------------------------------------------------------------


class TestNoHostSyncWithProfilerImported:
    def test_metered_hot_paths_stay_sync_free(self):
        # importing the profiler must not hook anything into the
        # jitted hot paths: the metered tiered lookup and the fused
        # train step still trace with ZERO host round trips
        import quiver_tpu.profile as _qt_profile
        assert _qt_profile.StageProfiler          # the import IS the setup
        from quiver_tpu.analysis.jaxpr_lint import host_sync_eqns_jaxpr
        from quiver_tpu.analysis.registry import build_entry_specs
        for entry in ("train_step", "lookup_tiered"):
            spec = build_entry_specs(entry)[0]
            assert host_sync_eqns_jaxpr(spec.jaxpr()) == [], entry

    def test_profile_series_names_are_declared(self):
        # the lint contract: the tuple exists and carries the names
        # the profiler/bench actually feed
        assert "stage_share" in PROFILE_SERIES
        assert "stage_ms" in PROFILE_SERIES
        assert "gather_efficiency" in PROFILE_SERIES


# ---------------------------------------------------------------------------
# the CLI
# ---------------------------------------------------------------------------


def _load_qt_prof():
    import importlib.util
    path = os.path.join(_ROOT, "scripts", "qt_prof.py")
    spec = importlib.util.spec_from_file_location("_qt_prof_test", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class TestQtProfCli:
    def test_single_entry_contract(self, capsys):
        # in-process, one cheap entry: the record lands with stage
        # timings, modeled bytes and efficiency — the full --quick
        # matrix is exercised by chip_suite/check_leak (and budgeted
        # <60 s standalone)
        mod = _load_qt_prof()
        path = os.path.join(tempfile.mkdtemp(), "prof.jsonl")
        rc = mod.main(["--entry", "lookup_tiered", "--jsonl", path,
                       "--reps", "2", "--no-color"])
        assert rc == 0
        out = capsys.readouterr().out
        assert "lookup_tiered" in out and "machine probe" in out
        recs = [json.loads(l) for l in open(path) if l.strip()]
        kinds = {r["kind"] for r in recs}
        assert kinds == {"meta", "profile"}    # meta = the sink header
        recs = [r for r in recs if r["kind"] == "profile"]
        by_entry = {r["entry"]: r for r in recs}
        assert "__machine__" in by_entry and "lookup_tiered" in by_entry
        st = by_entry["lookup_tiered"]["stages"][0]
        assert st["mean_ms"] > 0
        assert st["modeled"]["total_bytes"] > 0
        assert "efficiency" in st

    def test_quick_registry_lists_every_quick_entry(self):
        # the --quick matrix covers every quick-registered entry point
        # (the CLI's per-entry record contract) — checked structurally
        # here, timed end-to-end in chip_suite's prof section
        from quiver_tpu.analysis.registry import entry_names
        prof = StageProfiler(reps=1)
        prof.add_registry(quick=True)
        assert [g.name for g in prof.groups] == entry_names(quick=True)
