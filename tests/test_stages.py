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
3. **Room** — ``pipeline_depth`` closed batches may exist, the running
   one included: a batch past its deadline stays open, and takes the
   requests that arrive, until the pipeline has room (``held_open``
   counts those); a full batch closes at once and waits in
   ``serve.pipe_submit``; a lone request on an idle server ships at
   ``max_wait_ms``.
4. **Names** — ``tracing.STAGES`` is every name a ``tracing.stage(``
   call site uses, and no stage is also hand-recorded.
"""

import glob
import os
import re
import sys
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
        self.calls = []              # the seed block of every run, at entry

    def run(self, seeds, variant=0):
        self.calls.append([int(s) for s in seeds])
        if self.gate is not None:
            assert self.gate.wait(timeout=10)
        time.sleep(sum(self._stage_s))
        self.last_stage_s = self._stage_s
        out = np.zeros((self.batch_cap, 2), np.float32)
        out[:, 0] = np.asarray(seeds, np.float32)
        return out


class _Turnstile:
    """The gate a ``_StubEngine`` waits on, one ``run`` through a
    ``let()``."""

    def __init__(self):
        self._sem = threading.Semaphore(0)

    def wait(self, timeout=None):
        return self._sem.acquire(timeout=timeout)

    def let(self, n=1):
        for _ in range(n):
            self._sem.release()


def _until(what, timeout=5.0):
    """Poll ``what()`` true within ``timeout``; its last value."""
    end = time.perf_counter() + timeout
    while not what() and time.perf_counter() < end:
        time.sleep(0.001)
    return what()


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

    @pytest.mark.parametrize("depth", [1, 2, 3])
    def test_backpressure_lands_in_pipe_submit_not_in_coalesce(self, depth):
        gate = threading.Event()
        srv = qv.MicroBatchServer(
            _StubEngine(batch_cap=1, gate=gate),
            qv.ServeConfig(max_wait_ms=1.0, queue_depth=16,
                           pipeline_depth=depth, shed_queue_frac=1.0))
        # every batch is full at its first request, so it closes at
        # once: batch 1 sits in run(), the next fill the pipeline's
        # slots (one at depth 1 and 2, two at depth 3), and the submit
        # of the one after blocks until the gate opens
        futs = [srv.submit(i) for i in range(depth + 2)]
        time.sleep(0.15)
        gate.set()
        for f in futs:
            f.result(timeout=20)
        snap = _closed_snapshot(srv)
        assert snap["pipe_submit_s"] >= 0.1
        assert snap["coalesce_s"] <= 0.08
        assert snap["held_open"] == 0
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


def _in_pipeline(srv):
    """Closed batches in existence: handed over and not yet through."""
    q = srv.snapshot()["queue"]
    return q["submitted"] - q["completed"] - q["failed"] - q["cancelled"]


@pytest.mark.parametrize("depth", [1, 2, 3])
class TestRoom:
    """``pipeline_depth`` closed batches may exist, the running one
    included; until there is room a batch past its deadline stays open."""

    def _fill(self, depth, cap=4):
        """A server whose pipeline is full behind a shut gate: ``depth``
        batches of one request, each closed at its deadline."""
        gate = _Turnstile()
        eng = _StubEngine(batch_cap=cap, stage_s=(0.0, 0.0), gate=gate)
        srv = qv.MicroBatchServer(
            eng, qv.ServeConfig(max_wait_ms=1.0, queue_depth=16,
                                pipeline_depth=depth, shed_queue_frac=1.0))
        futs = []
        for i in range(depth):
            futs.append(srv.submit(i))
            assert _until(lambda: _in_pipeline(srv) == i + 1)
        assert _until(lambda: len(eng.calls) == 1)
        return gate, eng, srv, futs

    def test_a_request_that_finds_the_pipeline_full_rides_the_next_batch(
            self, depth):
        gate, eng, srv, futs = self._fill(depth)
        # twenty deadlines each: the parent closed a batch for either
        a = srv.submit(100)
        time.sleep(0.02)
        assert _in_pipeline(srv) == depth and len(eng.calls) == 1
        b = srv.submit(101)
        time.sleep(0.02)
        assert _in_pipeline(srv) == depth and len(eng.calls) == 1
        assert not a.done() and not b.done()
        gate.let(1)                  # batch 1 leaves: room for ONE more
        assert futs[0].result(timeout=10)[0] == 0
        assert _until(lambda: srv.snapshot()["serving"]["held_open"] == 1)
        assert _in_pipeline(srv) == depth
        gate.let(depth)
        assert a.result(timeout=10)[0] == 100
        assert b.result(timeout=10)[0] == 101
        for i, f in enumerate(futs):
            assert f.result(timeout=10)[0] == i
        # both rode the batch that closed next, the (depth+1)-th
        assert eng.calls == [[i, -1, -1, -1] for i in range(depth)] \
            + [[100, 101, -1, -1]]
        snap = _closed_snapshot(srv)
        assert snap["batches"] == depth + 1 and snap["completed"] == depth + 2
        assert srv.snapshot()["queue"]["max_depth"] <= max(1, depth - 1)

    def test_a_lone_request_ships_at_max_wait_and_is_not_held(self, depth):
        max_wait = 0.02
        srv = qv.MicroBatchServer(
            _StubEngine(stage_s=(0.0, 0.0)),
            qv.ServeConfig(max_wait_ms=1e3 * max_wait, queue_depth=16,
                           pipeline_depth=depth, shed_queue_frac=1.0))
        for i in range(3):
            t0 = time.perf_counter()
            assert srv.submit(i).result(timeout=10)[0] == i
            # the slack is the machine's: a timed wait may overshoot
            assert max_wait * 0.9 <= time.perf_counter() - t0 \
                <= max_wait + 0.1
        snap = _closed_snapshot(srv)
        assert snap["batches"] == 3 and snap["held_open"] == 0
        assert 3 * max_wait * 0.9 <= snap["coalesce_s"] \
            <= 3 * (max_wait + 0.02)

    def test_held_open_counts_the_batches_that_closed_on_room(self, depth):
        gate, eng, srv, futs = self._fill(depth)
        # held past its deadline, closed when room came: counted
        futs += [srv.submit(100), srv.submit(101)]
        assert _until(lambda: srv.snapshot()["serving"]["queue_depth"] == 0)
        time.sleep(0.05)
        gate.let(1)
        assert _until(lambda: _in_pipeline(srv) == depth
                      and srv.snapshot()["queue"]["completed"] == 1)
        # held past its deadline, then FULL: it closes on the cap and
        # waits in serve.pipe_submit like any full batch: not counted
        futs.append(srv.submit(200))
        time.sleep(0.01)
        futs += [srv.submit(i) for i in (201, 202, 203)]
        time.sleep(0.01)
        gate.let(depth + 1)
        for f in futs:
            f.result(timeout=10)
        # alone on an idle server, closed at its deadline: not counted
        gate.let(1)
        assert srv.submit(300).result(timeout=10)[0] == 300
        snap = _closed_snapshot(srv)
        assert eng.calls[depth:] == [[100, 101, -1, -1],
                                     [200, 201, 202, 203],
                                     [300, -1, -1, -1]]
        assert snap["batches"] == depth + 3 and snap["held_open"] == 1

    def test_an_open_batch_takes_a_queues_worth_then_admission_sheds(
            self, depth):
        # duplicates share one slot, so the cap never closes this batch;
        # behind a stalled engine it must not swallow requests for ever
        gate, eng, srv, futs = self._fill(depth)
        depth_q = srv.config.queue_depth
        admitted = 0
        with pytest.raises(qv.OverloadError):
            for _ in range(8 * depth_q):
                futs.append(srv.submit(100))
                admitted += 1
                time.sleep(0.0005)
        # at most: an open batch's worth in the blocked submit (and one
        # before it where the pipeline's one slot was free), the queue
        assert depth_q <= admitted <= 3 * depth_q + 4
        gate.let(depth + 8)
        for f in futs:
            assert f.result(timeout=10)[0] in (*range(depth), 100)
        snap = _closed_snapshot(srv)
        assert snap["completed"] == depth + admitted
        assert snap["rejected"] == 1 and snap["held_open"] == 0

    def test_the_count_of_batches_in_flight_survives_a_stampede(self, depth):
        # the coalescer adds to it and the worker takes from it: more
        # callers than cores and a short switch interval, a lost update
        # would leave the count off zero or let too many batches close
        eng = _StubEngine(stage_s=(0.0, 0.0002))
        srv = qv.MicroBatchServer(
            eng, qv.ServeConfig(max_wait_ms=0.2, queue_depth=256,
                                pipeline_depth=depth, shed_queue_frac=1.0))
        wrong, most = [], [0]

        def caller(k):
            for i in range(150):
                nid = 1000 * k + i
                if srv.submit(nid).result(timeout=20)[0] != nid:
                    wrong.append(nid)
                most[0] = max(most[0], _in_pipeline(srv))

        before = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)
        try:
            threads = [threading.Thread(target=caller, args=(k,))
                       for k in range(16)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=60)
        finally:
            sys.setswitchinterval(before)
        assert not any(t.is_alive() for t in threads) and not wrong
        snap = _closed_snapshot(srv)
        assert snap["completed"] == 16 * 150 and srv._in_flight == 0
        # the one more is a full batch in its blocked submit (at depth 1
        # the pipeline's one slot may also hold a full one)
        assert most[0] <= max(depth, 2) + 1

    def test_close_fails_a_batch_held_open_and_strands_no_future(self,
                                                                 depth):
        gate, eng, srv, futs = self._fill(depth)
        held = [srv.submit(100), srv.submit(101)]
        time.sleep(0.01)
        closer = threading.Thread(target=srv.close)
        closer.start()               # blocks on the batch inside run()
        for f in held:               # failed while the gate is still shut
            assert isinstance(f.exception(timeout=5), qv.ServerClosed)
        with pytest.raises(qv.ServerClosed):
            srv.submit(102)
        # the pipeline's queue is swept while batch 1 still sits in run()
        assert _until(
            lambda: srv.snapshot()["queue"]["cancelled"] == depth - 1)
        gate.let(1)
        closer.join(timeout=10)
        assert not closer.is_alive()
        # the running batch drained, the queued ones were cancelled
        assert futs[0].result(timeout=5)[0] == 0
        for f in futs[1:]:
            assert isinstance(f.exception(timeout=5), qv.ServerClosed)
        assert len(eng.calls) == 1
        snap = srv.snapshot()["serving"]
        assert snap["completed"] == 1 and snap["failed"] == depth + 1
        assert snap["held_open"] == 0


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
