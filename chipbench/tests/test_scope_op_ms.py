"""``reducers/scope_op_ms.py`` on a trace planted by hand: an op counts
where its scope AND its instruction text match, and nowhere else."""

import json
import os

import pytest

from chipbench import spec, trace

COLD = "jit(step)/jvp(qt_gather)/qt_lookup_cold/while/body/dynamic_slice"


def _op(text, start, end, scope):
    o = trace.Op(text, start, end)
    o.scope = scope
    return o


def _planted(chips=1):
    t = trace.Trace.__new__(trace.Trace)
    t.t0, t.t1, t.host = 0.0, 1.0, []
    ops = [
        # a row's transfer as the chip's trace has it (PR 36): its issue,
        # and the wait that names the issue among its operands
        _op("%dynamic-slice-start.4 = ((f32[7,128]{1,0:S(5)}, s32[], s32[]), "
            "f32[1,128]{1,0}, u32[]{:S(2)}) async-start(f32[7,128]{1,0:S(5)} "
            "%p.1, s32[] %i, s32[] %z)", 0.0, 3e-3, COLD),
        _op("%dynamic-slice-done.4 = f32[1,128]{1,0} async-done("
            "((f32[7,128]{1,0:S(5)}, s32[], s32[]), f32[1,128]{1,0}, "
            "u32[]{:S(2)}) %dynamic-slice-start.4)", 3e-3, 4e-3, COLD),
        # the same text under another scope, another text under the scope
        _op("%dynamic-slice-start.9 = ((f32[8]{0}), f32[1]{0}, u32[]) "
            "async-start(f32[8]{0} %q)", 4e-3, 6e-3,
            "jit(step)/jvp(qt_gather)/qt_lookup_hot/gather"),
        _op("%fusion.2 = s32[32]{0} fusion(s32[96]{0} %ids)", 6e-3, 7e-3, COLD),
        _op("%reduce-window.1 = s32[96]{0} reduce-window(s32[96]{0} %c)",
            7e-3, 8e-3, "")]
    t.devices = [ops] + [list(ops) for _ in range(chips - 1)]
    return t


@pytest.fixture
def reduce():
    return spec.plugin("reducers", "scope_op_ms").reduce


def test_an_op_counts_where_scope_and_text_both_match(reduce):
    ctx = {"trace": _planted(), "facts": {"steps": 2}}
    start = "^%dynamic-slice-start"
    assert reduce(ctx, "qt_lookup_cold", start) == pytest.approx(3.0)
    assert reduce(ctx, "qt_lookup_cold", start, "steps") == pytest.approx(1.5)
    # the -done op names the -start among its operands: an unanchored
    # pattern counts it too, one anchored at the op's own name does not
    assert reduce(ctx, "qt_lookup_cold", "dynamic-slice-start") == \
        pytest.approx(4.0)
    assert reduce(ctx, "qt_lookup_cold", "^%dynamic-slice-done") == \
        pytest.approx(1.0)
    assert reduce(ctx, "qt_lookup", start) == pytest.approx(5.0)
    assert reduce(ctx, "qt_lookup_cold", " fusion") == pytest.approx(1.0)
    # the empty scope is a scope too
    assert reduce(ctx, "^(?!.*qt_)", "reduce-window") == pytest.approx(1.0)


def test_nothing_to_read_is_none(reduce):
    ctx = {"trace": _planted(), "facts": {"steps": 2}}
    assert reduce(ctx, "qt_lookup_cold", " all-to-all") is None
    assert reduce(ctx, "qt_exchange", "^%dynamic-slice-start") is None
    assert reduce(ctx, "qt_lookup_cold", " fusion", "batches") is None


def test_the_chips_are_averaged_as_scope_ms_does(reduce):
    t = _planted(chips=2)
    t.devices[1] = t.devices[1][:1]
    ctx = {"trace": t, "facts": {}}
    assert reduce(ctx, "qt_lookup_cold", "^%dynamic-slice-(start|done)") \
        == pytest.approx((4.0 + 3.0) / 2)


def test_the_cells_metric_names_the_issue_alone(reduce):
    """``cold_issue_ms.train`` as its file has it: every row's ``-start``
    and no ``-done``."""
    with open(os.path.join(spec.HERE, "layer_metrics",
                           "cold_issue_ms.train.json")) as f:
        m = json.load(f)
    assert m["reducer"] == "scope_op_ms"
    ctx = {"trace": _planted(), "facts": {"steps": 1}}
    assert reduce(ctx, **m["args"]) == pytest.approx(3.0)
