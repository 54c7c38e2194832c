"""``reducers/idle_in_stage.py``: on a trace planted by hand, and on the
serve trace recorded on the chip (PR 25: seven batches of the serve step
through ``MicroBatchServer``), where the worker's four stages split the
idle time inside ``serve.dispatch``."""

import gzip
import os

import pytest

from chipbench import spec, trace

HERE = os.path.dirname(os.path.abspath(__file__))
WORKER = ("serve.put", "serve.launch", "serve.get", "serve.scatter")


def _planted(ops, host, t0=0.0, t1=10.0):
    t = trace.Trace.__new__(trace.Trace)
    t.t0, t.t1 = t0, t1
    t.devices = [[trace.Op(f"%fusion.{i} = f32[8]{{0}} fusion()", s, e)
                  for i, (s, e) in enumerate(ops)]]
    t.host = [("worker/1" if name != "serve.batch_coalesce" else "coalescer/2",
               name, s, e) for name, s, e in host]
    return t


@pytest.fixture
def reducer():
    return spec.plugin("reducers", "idle_in_stage")


def test_a_gap_counts_under_the_span_that_is_open(reducer):
    # the device runs 0..2 and 6..8; the gap 2..6 lies half under serve.get
    # (2..4) and half under no span of the worker; the coalescer's span
    # over all of it decides nothing
    t = _planted([(0.0, 2.0), (6.0, 8.0)],
                 [("serve.get", 1.0, 4.0), ("serve.put", 7.0, 7.5),
                  ("serve.batch_coalesce", 0.0, 10.0)])
    ctx = {"trace": t, "facts": {"batches": 2}}
    assert reducer.read(ctx, "serve.get") == pytest.approx(2.0)
    assert reducer.reduce(ctx, "serve.get") == pytest.approx(2000.0)
    assert reducer.reduce(ctx, "serve.get", "batches") == pytest.approx(1000.0)
    # a busy device reads 0 for a stage, not None
    assert reducer.reduce(ctx, "serve.put", "batches") == 0.0
    # all of the window's idle time: 2..6 and 8..10
    assert reducer.read(ctx, "serve.batch_coalesce") == pytest.approx(6.0)


def test_overlapping_spans_of_one_name_count_once(reducer):
    t = _planted([(0.0, 1.0)],
                 [("serve.get", 2.0, 5.0), ("serve.get", 4.0, 6.0)])
    assert reducer.read({"trace": t}, "serve.get") == pytest.approx(4.0)


def test_spans_are_clipped_to_the_window(reducer):
    t = _planted([(3.0, 4.0)],
                 [("serve.get", 0.0, 3.5), ("serve.get", 8.0, 12.0),
                  ("serve.scatter", 11.0, 12.0)], t0=2.0, t1=9.0)
    ctx = {"trace": t, "facts": {}}
    assert reducer.read(ctx, "serve.get") == pytest.approx(1.0 + 1.0)
    # a span that lies outside the window is no span of the window
    assert reducer.reduce(ctx, "serve.scatter") is None


def test_nothing_to_read_is_none(reducer):
    t = _planted([(0.0, 1.0)], [("serve.get", 0.0, 2.0)])
    ctx = {"trace": t, "facts": {"batches": 1}}
    assert reducer.reduce(ctx, "serve.launch", "batches") is None
    assert reducer.reduce(ctx, "serve.get", "steps") is None
    t.devices = []
    assert reducer.reduce(ctx, "serve.get", "batches") is None


@pytest.fixture(scope="module")
def recorded():
    hlo = gzip.open(os.path.join(
        HERE, "recorded_scopes_serve_hlo.txt.gz"), "rt").read()
    return trace.Trace(os.path.join(HERE, "recorded_scopes_serve.xplane.pb"),
                       trace.scopes_of(hlo), chips=1)


def test_recorded_the_workers_stages_split_the_idle_time(recorded, reducer):
    ctx = {"trace": recorded, "facts": {}}
    idle = recorded.window_s - recorded.busy_s
    by_stage = {s: reducer.read(ctx, s) for s in WORKER}
    assert all(v is not None and v >= 0 for v in by_stage.values()), by_stage
    inside = reducer.read(ctx, "serve.dispatch")
    assert 0 < inside <= idle * (1 + 1e-9)
    # sequential on one thread: they overlap nowhere, so their sum is no
    # more than the window's idle time, and they cover serve.dispatch but
    # for the Python between two stages
    assert sum(by_stage.values()) <= idle * (1 + 1e-9)
    assert sum(by_stage.values()) >= 0.9 * inside
    # the worker waits for the step's rows in serve.get
    assert by_stage["serve.get"] > 0
    assert reducer.reduce(ctx, "no.such.stage") is None
