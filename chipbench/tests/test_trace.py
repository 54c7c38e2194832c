"""The reduction from a trace to numbers: on hand-made ops, and on one
small trace recorded on the chip (PR 24's probe: four products-SAGE train
steps, batch 1024, with the compiled program's text beside it)."""

import gzip
import os
import re

import pytest

from chipbench import trace

HERE = os.path.dirname(os.path.abspath(__file__))


def _op(name, start, end):
    return trace.Op(f"%{name} = f32[8]{{0}} fusion(f32[8]{{0}} %x)", start, end)


def test_self_time_subtracts_nested_ops():
    outer, a, b, after = (_op("while.1", 0.0, 10.0), _op("fusion.1", 1.0, 3.0),
                          _op("fusion.2", 3.0, 4.0), _op("fusion.3", 12.0, 13.0))
    trace._self_times([after, b, outer, a])
    assert outer.self_s == pytest.approx(7.0)
    assert (a.self_s, b.self_s, after.self_s) == (2.0, 1.0, 1.0)
    assert outer.name == "while.1"


def test_union():
    assert trace._union([(0, 2), (1, 3), (5, 6), (6, 7), (9, 9.5)]) == \
        [[0, 3], [5, 7], [9, 9.5]]


def test_scopes_of():
    text = ('  %fusion.7 = f32[4]{0} fusion(f32[4]{0} %p), kind=kLoop, '
            'metadata={op_name="jit(step)/jvp(qt_sample_hop2)/sort" x=1}\n'
            '  ROOT %add.1 = f32[4]{0} add(%a, %b), '
            'metadata={op_name="jit(step)/add"}\n')
    assert trace.scopes_of(text) == {
        "fusion.7": "jit(step)/jvp(qt_sample_hop2)/sort",
        "add.1": "jit(step)/add"}


@pytest.fixture(scope="module")
def recorded():
    hlo = gzip.open(os.path.join(HERE, "recorded_train_hlo.txt.gz"), "rt").read()
    return trace.Trace(os.path.join(HERE, "recorded_train_4steps.xplane.pb"),
                       trace.scopes_of(hlo), chips=1)


def test_recorded_busy_union(recorded):
    ops = recorded.devices[0]
    assert len(recorded.devices) == 1 and len(ops) > 5000
    busy = sum(e - s for s, e in recorded.busy(ops))
    # one device runs one op at a time: self times tile the busy time
    assert sum(o.self_s for o in ops) == pytest.approx(busy, rel=1e-3)
    assert busy == pytest.approx(recorded.busy_s)
    # four steps of ~112.7 ms (the probe's host clock said 123 ms a traced
    # step, enqueue included) inside a 454 ms span
    assert 0.44 < busy < 0.46
    assert 0.45 < recorded.window_s < 0.46


def test_recorded_scope_sums(recorded):
    hop = lambda i: recorded.seconds(
        lambda o: f"qt_sample_hop{i}" in o.scope) / 4 * 1e3
    # per step, by hand from the same file: the last hop's draw and
    # compaction dominate the sampling, the first is a fiftieth of it
    assert 34 < hop(2) < 38 and 6 < hop(1) < 8 and 0.6 < hop(0) < 1.0
    gather = recorded.seconds(
        lambda o: "f32[2449029,100]" in o.text.partition("(")[2]
        and o.text.split(" = ", 1)[1].startswith("f32[1081344,100]")) / 4
    assert 0.0125 < gather < 0.0135            # %fusion.15: 13.1 ms a step
    assert recorded.seconds(lambda o: "no_such_scope" in o.scope) is None


def test_recorded_breakdown(recorded):
    top = recorded.top_ops()
    assert top[0][0] == "qt_sample_hop2" and len(top) == 10
    assert all(a[1] >= b[1] for a, b in zip(top, top[1:]))
    gaps = recorded.idle_gaps()
    assert gaps and all(re.match(r"[\w\- ]+/|no host span", g[0]) for g in gaps)
    idle = recorded.window_s - recorded.busy_s
    assert sum(g[1] for g in gaps) <= idle + 1e-9
