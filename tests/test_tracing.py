"""Tracing + SLO-budget + regression-sentinel units.

The contracts:

1. **Tracer** — zero records while disabled; a fixed-capacity ring that
   keeps the most recent spans once wrapped (bounded memory by
   construction); Chrome/Perfetto trace-event JSON export with
   ``trace_id`` correlation in span args.
2. **SloBudget** — burn rate = observed bad fraction / (1 -
   availability) per sliding window; ``should_shed`` is the AND of the
   short window (above ``shed_burn_rate``) and the long window (above
   1.0); rejections/failures (``ok=False``) consume budget; the
   snapshot emits through ``MetricsSink`` as kind ``slo``.
3. **bench_regress** — the committed ``BENCH_r*.json`` trajectory
   passes; a synthetic 20%-regressed record fails (exit 1); skipped /
   ``value: null`` outage rounds are ignored, not failed.
"""

import json
import os
import subprocess
import sys
import time

import pytest

from quiver_tpu import tracing
from quiver_tpu.metrics import MetricsSink, SloBudget

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def tracer():
    """A private Tracer per test — the process-default one stays
    untouched (other tests must not see stray spans)."""
    return tracing.Tracer(capacity=16)


@pytest.fixture
def global_tracing():
    tracing.clear()
    tracing.enable()
    yield tracing.get_tracer()
    tracing.disable()
    tracing.clear()


class TestTracer:
    def test_disabled_records_nothing(self, tracer):
        tracer.record("a", 0.0, 1.0)
        assert len(tracer) == 0

    def test_ring_keeps_most_recent_after_wrap(self, tracer):
        tracer.enable()
        for i in range(40):
            tracer.record("s", float(i), 0.5, trace_id=i)
        assert len(tracer) == 16             # bounded, not 40
        assert [r[4] for r in tracer.records()] == list(range(24, 40))

    def test_stage_times_block_into_one_record(self, global_tracing):
        with tracing.stage("work", trace_id=7, args={"k": 1}) as st:
            time.sleep(0.002)
        (name, tid, t0, dur, trace_id, args), = global_tracing.records()
        assert name == "work" and trace_id == 7 and args == {"k": 1}
        assert dur == st.dur >= 0.002

    def test_export_chrome_trace_loads(self, tracer, tmp_path):
        tracer.enable()
        tracer.record("phase.load", 0.5, 0.125, trace_id=3,
                      args={"rows": 8})
        tracer.record("phase.run", 1.0, 0.25)
        path = tmp_path / "trace.json"
        n = tracer.export_chrome_trace(str(path))
        assert n == 2
        doc = json.loads(path.read_text())
        evs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
        metas = [e for e in doc["traceEvents"] if e["ph"] == "M"]
        assert len(evs) == 2 and metas        # thread_name metadata
        by_name = {e["name"]: e for e in evs}
        load = by_name["phase.load"]
        assert load["args"]["trace_id"] == 3
        assert load["args"]["rows"] == 8
        assert load["cat"] == "phase"
        run = by_name["phase.run"]
        assert run["ts"] == pytest.approx(1e6) and \
            run["dur"] == pytest.approx(0.25e6)
        # every complete event has the fields Perfetto requires
        for e in evs:
            assert {"ph", "pid", "tid", "name", "ts", "dur"} <= set(e)

    def test_enable_resize_and_clear(self, tracer):
        tracer.enable(capacity=4)
        for i in range(10):
            tracer.record("s", float(i), 0.1)
        assert len(tracer) == 4
        tracer.clear()
        assert len(tracer) == 0 and tracer.enabled
        tracer.disable()
        assert not tracer.enabled

    def test_module_level_default_tracer(self):
        assert not tracing.enabled()          # tier-1 runs untraced
        before = len(tracing.get_tracer())
        tracing.record("noop", 0.0, 1.0)      # disabled: dropped
        assert len(tracing.get_tracer()) == before


class TestContextExtraction:
    """``extract`` on PARTIAL carriers: a context is whatever subset
    of the ``qt.*`` keys survived the wire — anything with a usable
    trace_id is a context, anything without is simply untraced."""

    def test_trace_id_only_no_parent(self):
        ctx = tracing.extract({"qt.trace_id": 41})
        assert ctx == tracing.TraceContext(41, None, None)

    def test_trace_id_and_replica_no_parent(self):
        ctx = tracing.extract({"qt.trace_id": 41, "qt.replica": "r2"})
        assert ctx.trace_id == 41 and ctx.parent is None
        assert ctx.replica == "r2"

    def test_string_trace_id_tolerated(self):
        # JSON round trips through proxies that stringify: "41" is 41
        assert tracing.extract({"qt.trace_id": "41"}).trace_id == 41

    def test_garbage_is_untraced_not_an_error(self):
        for bad in (None, [], "x", 7,
                    {}, {"qt.parent": "serve.request"},
                    {"qt.trace_id": "not-an-int"},
                    {"qt.trace_id": None}):
            assert tracing.extract(bad) is None

    def test_inject_then_partial_strip_round_trips(self):
        carrier = tracing.inject({}, trace_id=99, parent="rpc.lookup")
        carrier.pop("qt.parent")                 # a lossy proxy
        ctx = tracing.extract(carrier)
        assert ctx.trace_id == 99 and ctx.parent is None


def _mint_global_ids(q, k):
    t = tracing.Tracer(capacity=4)
    q.put((os.getpid(), [t.new_global_trace_id() for _ in range(k)]))


class TestGlobalTraceIds:
    def test_no_collisions_across_forked_replicas(self):
        """The pid rides the high bits: fresh tracers in FORKED
        replicas (each restarting its local counter at 1 — the worst
        case) must never mint colliding global ids."""
        import multiprocessing as mp
        ctx = mp.get_context("fork")
        q = ctx.Queue()
        k = 200
        procs = [ctx.Process(target=_mint_global_ids, args=(q, k))
                 for _ in range(3)]
        for p in procs:
            p.start()
        got = [q.get(timeout=30) for _ in procs]
        for p in procs:
            p.join(timeout=30)
        pids = [pid for pid, _ in got]
        assert len(set(pids)) == 3               # really forked
        parent_tracer = tracing.Tracer(capacity=4)
        own = [parent_tracer.new_global_trace_id() for _ in range(k)]
        all_ids = own + [i for _, ids in got for i in ids]
        assert len(set(all_ids)) == len(all_ids) == 4 * k
        # and every id still extracts through a carrier round trip
        sample = got[0][1][0]
        assert tracing.extract(
            tracing.inject({}, trace_id=sample)).trace_id == sample


class TestSloBudget:
    def _budget(self, **kw):
        clock = [1000.0]
        kw.setdefault("availability", 0.99)
        kw.setdefault("window_s", 300.0)
        kw.setdefault("short_window_s", 30.0)
        kw.setdefault("min_requests", 10)
        b = SloBudget(kw.pop("target_p99_ms", 10.0), clock=lambda: clock[0],
                      **kw)
        return b, clock

    def test_burn_rate_math(self):
        b, _ = self._budget()
        for _ in range(99):
            b.record(0.001)                  # in budget
        b.record(0.050)                      # 50 ms > 10 ms target
        # 1 bad / 100 requests at a 1% budget = burning at exactly 1.0
        assert b.burn_rate(30.0) == pytest.approx(1.0)
        assert b.budget_remaining() == pytest.approx(0.0)

    def test_min_requests_guard(self):
        b, _ = self._budget()
        for _ in range(5):
            b.record(1.0)                    # all bad, but only 5
        assert b.burn_rate(30.0) is None
        # same guard on the remaining-budget integral: 5 bad of 5 must
        # not read as a -99x overspend in reports/JSONL
        assert b.budget_remaining() is None
        assert b.snapshot()["budget_remaining"] is None
        assert not b.should_shed()

    def test_should_shed_needs_both_windows(self):
        b, clock = self._budget(shed_burn_rate=1.0)
        # an old clean majority fills the long window...
        for _ in range(2000):
            b.record(0.001)
        clock[0] += 100.0                    # past short, inside long
        # ...then a fully-bad burst fills the short window
        for _ in range(20):
            b.record(1.0)
        assert b.burn_rate(30.0) == pytest.approx(100.0)
        # long window burns at 20/2020/0.01 ≈ 0.99 < 1.0: budget still
        # intact overall, one spike must not shed
        assert b.burn_rate(300.0) < 1.0
        assert not b.should_shed()
        for _ in range(25):                  # sustained pressure does
            b.record(1.0)
        assert b.should_shed()

    def test_failures_consume_budget(self):
        b, _ = self._budget()
        for _ in range(50):
            b.record(0.001)
        for _ in range(50):
            b.record(ok=False)               # rejected / failed
        assert b.burn_rate(30.0) == pytest.approx(50.0)
        assert b.budget_remaining() < 0      # overspent
        assert b.should_shed()

    def test_window_slides(self):
        b, clock = self._budget()
        for _ in range(50):
            b.record(1.0)                    # all bad
        assert b.should_shed()
        clock[0] += 400.0                    # everything ages out
        for _ in range(50):
            b.record(0.001)
        assert b.burn_rate(300.0) == 0.0
        assert b.budget_remaining() == 1.0
        assert not b.should_shed()

    def test_validation(self):
        with pytest.raises(ValueError, match="availability"):
            SloBudget(10.0, availability=1.0)
        with pytest.raises(ValueError, match="short_window_s"):
            SloBudget(10.0, short_window_s=500.0, window_s=300.0)

    def test_snapshot_emits_slo_kind(self, tmp_path):
        b, _ = self._budget()
        for _ in range(30):
            b.record(0.001)
        b.record(0.050)
        path = tmp_path / "m.jsonl"
        with MetricsSink(str(path)) as sink:
            rec = b.emit(sink)
        assert rec["kind"] == "slo"
        lines = [json.loads(l) for l in path.read_text().splitlines()]
        assert [l["kind"] for l in lines] == ["meta", "slo"]
        got = lines[1]                    # past the sink's meta header
        assert got["target_p99_ms"] == 10.0
        assert got["windows"]["short"]["requests"] == 31
        assert got["windows"]["short"]["bad"] == 1
        assert got["total"] == {"requests": 31, "bad": 1}
        assert "budget_remaining" in got and "shedding" in got


class TestBenchRegress:
    SCRIPT = os.path.join(REPO, "scripts", "bench_regress.py")

    def run_sentinel(self, *args):
        return subprocess.run(
            [sys.executable, self.SCRIPT, *args],
            capture_output=True, text=True, timeout=60)

    @staticmethod
    def bench_file(tmp_path, n, value, skipped=False, error=None):
        rec = {"metric": "sampled-edges/sec", "value": value,
               "unit": "edges/s"}
        if skipped:
            rec["skipped"] = True
        if error:
            rec["error"] = error
        run = {"n": n, "cmd": "python bench.py",
               "rc": 1 if skipped else 0,
               "tail": "some log noise\n" + json.dumps(rec) + "\n"}
        (tmp_path / f"BENCH_r{n:02d}.json").write_text(json.dumps(run))

    def test_current_trajectory_passes(self):
        p = self.run_sentinel("--bench-dir", REPO)
        assert p.returncode == 0, p.stdout + p.stderr
        assert "trajectory clean" in p.stdout

    def test_synthetic_regression_fails(self, tmp_path):
        self.bench_file(tmp_path, 1, 100.0)
        self.bench_file(tmp_path, 2, 110.0)
        self.bench_file(tmp_path, 3, 88.0)       # 20% below best=110
        p = self.run_sentinel("--bench-dir", str(tmp_path))
        assert p.returncode == 1, p.stdout + p.stderr
        assert "REGRESSION" in p.stdout and "20.0%" in p.stdout

    def test_skipped_and_null_rounds_are_not_regressions(self, tmp_path):
        self.bench_file(tmp_path, 1, 100.0)
        self.bench_file(tmp_path, 2, None, skipped=True,
                        error="TPU backend unavailable")
        self.bench_file(tmp_path, 3, None, error="init timed out")
        self.bench_file(tmp_path, 4, 99.0)       # within threshold
        p = self.run_sentinel("--bench-dir", str(tmp_path))
        assert p.returncode == 0, p.stdout + p.stderr
        assert "2 skipped" in p.stdout

    def test_within_threshold_drop_passes(self, tmp_path):
        self.bench_file(tmp_path, 1, 100.0)
        self.bench_file(tmp_path, 2, 90.0)       # 10% < 15%
        p = self.run_sentinel("--bench-dir", str(tmp_path))
        assert p.returncode == 0, p.stdout + p.stderr

    def test_recovered_dip_is_not_a_regression(self, tmp_path):
        # only the LATEST value is judged: an old dip that has since
        # recovered must not fail every future sweep
        self.bench_file(tmp_path, 1, 100.0)
        self.bench_file(tmp_path, 2, 70.0)
        self.bench_file(tmp_path, 3, 105.0)
        p = self.run_sentinel("--bench-dir", str(tmp_path))
        assert p.returncode == 0, p.stdout + p.stderr

    def test_since_scopes_out_stale_jsonl_history(self, tmp_path):
        # a committed improvement supersedes an old history line; the
        # stale line sorts after the whole trajectory (ts and round
        # numbers share no clock), so unscoped it reads as "latest" —
        # --since (what chip_suite.sh passes) scopes it out
        self.bench_file(tmp_path, 1, 100.0)
        self.bench_file(tmp_path, 2, 200.0)
        hist = tmp_path / "metrics.jsonl"
        hist.write_text(json.dumps(
            {"ts": 50.0, "kind": "bench",
             "metric": "sampled-edges/sec", "value": 100.0}) + "\n")
        p = self.run_sentinel("--bench-dir", str(tmp_path),
                              "--jsonl", str(hist))
        assert p.returncode == 1
        p = self.run_sentinel("--bench-dir", str(tmp_path),
                              "--jsonl", str(hist), "--since", "100")
        assert p.returncode == 0, p.stdout + p.stderr

    def test_jsonl_history_extends_trajectory(self, tmp_path):
        self.bench_file(tmp_path, 1, 100.0)
        hist = tmp_path / "metrics.jsonl"
        lines = [
            {"ts": 1.0, "kind": "bench", "metric": "sampled-edges/sec",
             "value": 70.0},                     # 30% drop -> fails
            {"ts": 2.0, "kind": "serving", "metric": "ignored",
             "value": 1.0},                      # wrong kind: ignored
        ]
        hist.write_text("\n".join(json.dumps(l) for l in lines) + "\n")
        p = self.run_sentinel("--bench-dir", str(tmp_path),
                              "--jsonl", str(hist))
        assert p.returncode == 1, p.stdout + p.stderr
        assert "REGRESSION" in p.stdout
