"""Host stages of the serving path: ``tracing.stage`` and what
``MicroBatchServer``, ``ServeEngine`` and ``pipeline`` file through it.

The contracts:

1. **One call site, three readers** — a stage always hands its duration
   to the caller; it files a ring record only while the ring is on; it
   opens a ``TraceAnnotation`` of the same name, which a profiler
   session (and nothing else) stores. Children inherit the enclosing
   stage's ``trace_id`` on their own thread.
2. **Counters** — every stage's seconds are summed per batch into flat
   keys of ``snapshot()["serving"]``, ring on or off alike; the
   children of ``serve.dispatch`` sum to no more than it;
   ``serve.batch_coalesce`` ends where the batch closes and
   ``serve.pipe_submit`` holds the backpressure.
3. **Names** — ``tracing.STAGES`` is every name a ``tracing.stage(``
   call site uses, and no stage is also hand-recorded.
"""

import glob
import os
import re
import threading
import time

import numpy as np
import jax
import jax.numpy as jnp
import optax
import pytest

import quiver_tpu as qv
from quiver_tpu import pipeline, tracing
from quiver_tpu.models import GraphSAGE
from quiver_tpu.ops import sample_multihop
from quiver_tpu.parallel.train import (init_state, layers_to_adjs,
                                       masked_feature_gather)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
STAGE_COUNTERS = ("coalesce_s", "pipe_submit_s", "pipeline_wait_s",
                  "execute_s", "put_s", "launch_s", "get_s", "scatter_s",
                  "queue_wait_s")
N, DIM, CAP, FANOUT = 300, 8, 8, [3, 3]


@pytest.fixture
def ring():
    tracing.clear()
    tracing.enable()
    yield tracing.get_tracer()
    tracing.disable()
    tracing.clear()


class _StubEngine:
    """Jax-free engine: ``run`` sleeps as the put and the launch would
    and hands both durations back the way ``ServeEngine`` does."""

    collect_metrics = False
    jitted_fns = ()

    def __init__(self, batch_cap=4, stage_s=(0.001, 0.002), gate=None):
        self.batch_cap = batch_cap
        self.variants = [[2, 2]]
        self.gate = gate
        self.last_stage_s = (0.0, 0.0)
        self._stage_s = stage_s

    def run(self, seeds, variant=0):
        if self.gate is not None:
            assert self.gate.wait(timeout=10)
        time.sleep(sum(self._stage_s))
        self.last_stage_s = self._stage_s
        out = np.zeros((self.batch_cap, 2), np.float32)
        out[:, 0] = np.asarray(seeds, np.float32)
        return out


def _serve(engine, n, **cfg):
    """``n`` requests through a fresh server, one by one; its snapshot."""
    cfg = dict(dict(max_wait_ms=2.0, queue_depth=64, shed_queue_frac=1.0),
               **cfg)
    srv = qv.MicroBatchServer(engine, qv.ServeConfig(**cfg))
    for i in range(n):
        assert srv.submit(i).result(timeout=20)[0] == i
    return _closed_snapshot(srv)


def _closed_snapshot(srv):
    """The counters once the worker is through: a batch's seconds are
    filed after its futures resolve, so close (which joins) comes first."""
    srv.close()
    return srv.snapshot()["serving"]


@pytest.fixture(scope="module")
def engine():
    rng = np.random.default_rng(3)
    deg = rng.integers(1, 4, N)
    indptr = jnp.asarray(np.concatenate([[0], np.cumsum(deg)]), jnp.int32)
    indices = jnp.asarray(rng.integers(0, N, int(deg.sum())), jnp.int32)
    feat = jnp.asarray(rng.standard_normal((N, DIM)), jnp.float32)
    model = GraphSAGE(hidden_dim=8, out_dim=3, num_layers=2, dropout=0.0)
    n_id, layers = sample_multihop(indptr, indices,
                                   jnp.arange(4, dtype=jnp.int32), FANOUT,
                                   jax.random.key(0))
    state = init_state(model, optax.adam(1e-3),
                       masked_feature_gather(feat, n_id),
                       layers_to_adjs(layers, 4, FANOUT), jax.random.key(1))
    return qv.ServeEngine(model, state.params, (indptr, indices), feat,
                          sizes_variants=[FANOUT], batch_cap=CAP).warmup()


class TestStage:
    def test_off_files_nothing_and_hands_back_a_duration(self):
        assert not tracing.enabled()
        before = len(tracing.get_tracer())
        with tracing.stage("serve.get") as st:
            time.sleep(0.002)
        assert st.dur >= 0.002 and st.t0 > 0
        assert len(tracing.get_tracer()) == before == 0

    def test_on_files_the_record_with_args_set_before_it_closes(self, ring):
        with tracing.stage("serve.batch_coalesce", 41) as st:
            st.args = {"fill": 3}
        (rec,) = ring.records()
        assert rec[0] == "serve.batch_coalesce" and rec[4] == 41
        assert rec[2] == st.t0 and rec[3] == st.dur
        assert rec[5] == {"fill": 3}

    def test_children_inherit_the_enclosing_trace_id(self, ring):
        with tracing.stage("serve.dispatch", 7):
            with tracing.stage("serve.put"):
                pass
            with tracing.stage("serve.get", 9):     # its own id wins
                with tracing.stage("inner"):
                    pass
            with tracing.stage("serve.scatter"):
                pass
        with tracing.stage("after"):                # nothing is left open
            pass
        ids = {r[0]: r[4] for r in ring.records()}
        assert ids == {"serve.dispatch": 7, "serve.put": 7, "serve.get": 9,
                       "inner": 9, "serve.scatter": 7, "after": None}

    def test_inheritance_stays_on_its_thread(self, ring):
        def other():
            with tracing.stage("elsewhere"):
                pass

        with tracing.stage("serve.dispatch", 5):
            t = threading.Thread(target=other)
            t.start()
            t.join()
        ids = {r[0]: r[4] for r in ring.records()}
        assert ids == {"serve.dispatch": 5, "elsewhere": None}

    def test_an_exception_closes_the_stage(self, ring):
        with pytest.raises(KeyError):
            with tracing.stage("serve.dispatch", 3) as st:
                raise KeyError("boom")
        assert st.dur > 0
        with tracing.stage("next"):
            pass
        ids = {r[0]: r[4] for r in ring.records()}
        assert ids == {"serve.dispatch": 3, "next": None}

    def test_stages_lists_every_call_site(self):
        sites, recorded = set(), set()
        for name in ("serving.py", "pipeline.py"):
            src = open(os.path.join(REPO, "quiver_tpu", name)).read()
            sites |= set(re.findall(r'tracing\.stage\(\s*"([\w.]+)"', src))
            recorded |= set(re.findall(r'tracing\.record\(\s*"([\w.]+)"', src))
        assert sites == set(tracing.STAGES)
        # a stage is written once: none is also hand-recorded
        assert not sites & recorded
        assert recorded == {"serve.admission_wait", "serve.coalesce_wait",
                            "serve.request", "pipeline.queue_wait"}


class TestCounters:
    @pytest.mark.parametrize("ring_on", [False, True])
    def test_every_stage_is_counted_ring_on_or_off(self, ring_on):
        if ring_on:
            tracing.clear()
            tracing.enable()
        try:
            snap = _serve(_StubEngine(), 6)
        finally:
            tracing.disable()
            tracing.clear()
        batches, max_wait = snap["batches"], 0.002
        assert batches == 6 and snap["completed"] == 6
        for key in STAGE_COUNTERS:
            assert isinstance(snap[key], float) and snap[key] >= 0.0, key
        assert snap["put_s"] == pytest.approx(6 * 0.001)
        assert snap["launch_s"] == pytest.approx(6 * 0.002)
        inner = (snap["put_s"] + snap["launch_s"] + snap["get_s"]
                 + snap["scatter_s"])
        assert 0 < inner <= snap["execute_s"]
        # a lone request closes its batch when max_wait is spent (the
        # slack is the machine's: a timed wait may overshoot)
        assert batches * max_wait * 0.9 <= snap["coalesce_s"] \
            <= batches * (max_wait + 0.02)
        # admission -> own batch starts: at least the coalescer's wait
        assert snap["queue_wait_s"] >= snap["coalesce_s"] * 0.9
        assert snap["pipeline_wait_s"] < snap["queue_wait_s"]

    def test_a_real_engine_hands_back_its_put_and_launch(self, engine):
        srv = qv.MicroBatchServer(
            engine, qv.ServeConfig(max_wait_ms=1.0, queue_depth=32,
                                   shed_queue_frac=1.0))
        for i in range(3):
            srv.submit(i).result(timeout=20)
        snap = _closed_snapshot(srv)
        put_s, launch_s = engine.last_stage_s
        assert 0 < put_s <= snap["put_s"]
        assert 0 < launch_s <= snap["launch_s"]
        assert snap["put_s"] + snap["launch_s"] < snap["execute_s"]

    def test_backpressure_lands_in_pipe_submit_not_in_coalesce(self):
        gate = threading.Event()
        srv = qv.MicroBatchServer(
            _StubEngine(batch_cap=1, gate=gate),
            qv.ServeConfig(max_wait_ms=1.0, queue_depth=16,
                           pipeline_depth=1, shed_queue_frac=1.0))
        # batch 1 sits in run(), batch 2 fills the pipeline's one slot,
        # batch 3's submit blocks until the gate opens
        futs = [srv.submit(i) for i in range(3)]
        time.sleep(0.15)
        gate.set()
        for f in futs:
            f.result(timeout=20)
        snap = _closed_snapshot(srv)
        assert snap["pipe_submit_s"] >= 0.1
        assert snap["coalesce_s"] <= 0.08
        # the pipeline's clock starts when the batch is handed over, so
        # the blocked submit is inside its wait
        assert snap["pipeline_wait_s"] >= snap["pipe_submit_s"] * 0.9

    def test_dispatch_children_nest_and_share_the_batch_id(self, engine,
                                                           ring):
        srv = qv.MicroBatchServer(
            engine, qv.ServeConfig(max_wait_ms=1.0, queue_depth=32,
                                   shed_queue_frac=1.0))
        srv.submit(5).result(timeout=20)
        snap = _closed_snapshot(srv)
        by = {}
        for r in ring.records():
            by.setdefault(r[0], []).append(r)
        (dispatch,) = by["serve.dispatch"]
        bid, d0, d1 = dispatch[4], dispatch[2], dispatch[2] + dispatch[3]
        assert bid is not None
        ends = []
        for name in ("serve.put", "serve.launch", "serve.get",
                     "serve.scatter"):
            (rec,) = by[name]
            assert rec[4] == bid, name
            assert d0 <= rec[2] and rec[2] + rec[3] <= d1, name
            ends.append((rec[2], rec[2] + rec[3]))
        # in the order they happen, none overlapping the next
        assert all(a[1] <= b[0] for a, b in zip(ends, ends[1:]))
        assert by["serve.batch_coalesce"][0][4] == bid
        assert by["serve.pipe_submit"][0][4] == bid
        # the coalesce span ends where the batch closes, before the
        # pipeline takes it
        co, sub = by["serve.batch_coalesce"][0], by["serve.pipe_submit"][0]
        assert co[2] + co[3] <= sub[2] + 1e-6
        assert co[5]["requests"] == 1 and co[5]["fill"] == 1
        assert (dispatch,) == tuple(
            r for r in by["serve.dispatch"] if r[5]["requests"] == 1)
        # the real engine hands its two stages back
        assert snap["put_s"] > 0 and snap["launch_s"] > 0

    def test_queue_wait_is_admission_to_own_dispatch(self, engine, ring):
        srv = qv.MicroBatchServer(
            engine, qv.ServeConfig(max_wait_ms=1.0, queue_depth=64,
                                   shed_queue_frac=1.0), start=False)
        futs = [srv.submit(i) for i in range(3 * CAP)]
        srv.start()
        for f in futs:
            f.result(timeout=20)
        snap = _closed_snapshot(srv)
        recs = ring.records()
        start = {r[4]: r[2] for r in recs if r[0] == "serve.dispatch"}
        want = sum(start[r[5]["batch"]] - r[2]
                   for r in recs if r[0] == "serve.request")
        assert snap["queue_wait_s"] == pytest.approx(want, rel=1e-6)
        assert snap["queue_wait_s"] > 0


class TestPipelineStages:
    def test_idle_and_execute_are_stages(self, ring):
        with pipeline.Pipeline(depth=2, name="t") as p:
            assert p.submit(lambda: 4).result(timeout=10) == 4
            with pytest.raises(ZeroDivisionError):
                p.submit(lambda: 1 / 0).result(timeout=10)
        names = [r[0] for r in ring.records()]
        assert names.count("pipeline.execute") == 2
        assert names.count("pipeline.queue_wait") == 2
        assert names.count("pipeline.idle") >= 2
        oks = [r[5]["ok"] for r in ring.records()
               if r[0] == "pipeline.execute"]
        assert oks == [True, False]


def test_a_profiler_session_stores_the_stages(engine, tmp_path):
    """On the profiler's clock: a served batch leaves its stages on
    ``/host:CPU`` (the ring is off: the annotation is its own reader)."""
    from jax.profiler import ProfileData
    assert not tracing.enabled()
    srv = qv.MicroBatchServer(
        engine, qv.ServeConfig(max_wait_ms=1.0, queue_depth=32,
                               shed_queue_frac=1.0))
    srv.submit(1).result(timeout=20)          # threads up before the trace
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        for i in range(3):
            srv.submit(i).result(timeout=20)
    finally:
        jax.profiler.stop_trace()
        srv.close()
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                            / "*.xplane.pb"))
    events = [ev.name for plane in ProfileData.from_file(path).planes
              if plane.name == "/host:CPU"
              for line in plane.lines for ev in line.events]
    for name in ("serve.batch_coalesce", "serve.pipe_submit",
                 "serve.dispatch", "serve.put", "serve.launch", "serve.get",
                 "serve.scatter"):
        assert events.count(name) == 3, name
    # the worker's idle span that was open when the session began is not
    # the session's, nor the one open at its end
    assert events.count("pipeline.idle") >= 2
