"""Proof that the harness is open: this directory holds a deployment whose
entry, world, reference, reducer, work functions, configuration, traffic
mix, cell and ``BENCHMARK.json`` no file of ``chipbench/`` names. Laid in
front of ``spec.SEARCH`` it runs through ``run.run_cell`` to a ``correct``
result line with its per-layer metric, on four virtual CPU devices; with
the exchange between the chips left out it does not.

A CPU run shows control flow and results; it gives no time.
"""

import glob
import json
import os

import pytest

from chipbench import harness, readers, run, spec

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "tiny-rows-train"
SEED = 2**31 + 27
OWN = {kind: sorted(os.path.basename(p)[:-3] for p in glob.glob(
    os.path.join(HERE, kind, "*.py"))) for kind in spec.PLUGIN_KINDS}


@pytest.fixture
def outsider(monkeypatch):
    monkeypatch.setattr(spec, "SEARCH", [HERE] + spec.SEARCH)
    monkeypatch.setattr(spec, "BENCHMARK_FILE",
                        os.path.join(HERE, "BENCHMARK.json"))


def test_no_file_of_chipbench_names_what_this_directory_brings():
    assert OWN == {"entries": ["rows_lookup_step"],
                   "worlds": ["rows_over_chips"],
                   "references": ["softmax_rows"], "reducers": ["fact_per"],
                   "work": ["exchange_bytes", "softmax_rows_flops"]}
    names = {n for kind in OWN.values() for n in kind} | {
        CELL, "tiny-rows-over-chips", "exchange_rows.train",
        "exchange_roofline.train"}
    code = [p for p in glob.glob(os.path.join(spec.HERE, "**", "*.py"),
                                 recursive=True)
            if not p.startswith(os.path.join(spec.HERE, "tests"))]
    assert len(code) > 15
    for path in code:
        text = open(path).read()
        assert not [n for n in names if n in text], path
    for kind, own in OWN.items():
        assert not set(own) & set(spec.plugin_files(kind)), kind


def test_the_cell_runs_to_a_correct_line_with_its_metric(outsider, capsys):
    result, compared = run.run_cell(CELL, SEED, 1.0, True, allow_cpu=True)
    assert harness.finish(result, compared) == 0
    line = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert line["correct"] is True
    assert list(line["compared"]) == ["loss_gap", "update_gap",
                                      "nonfinite_losses", "compiles_in_window"]
    assert line["attempted"] > 0 and line["failed"] == 0
    # the outsider's own reducer read the outsider entry's own fact
    assert line["metrics"]["exchange_rows.train"] == {"value": 64.0,
                                                      "unit": "rows"}
    # no device trace on the CPU: a roofline stays silent, it is never 0
    assert "exchange_roofline.train" not in line["metrics"]
    assert line["device"]["platform"] == "cpu" and line["device"]["count"] == 4
    assert line["device"]["window_s"] > 0


def test_end_to_end_metrics_are_the_ones_whose_workloads_name_the_cell(outsider):
    result, compared = run.run_cell(CELL, SEED + 1, 0.5, False, allow_cpu=True)
    assert harness.judge(compared)[0]
    assert set(result["metrics"]) == {"train_seeds_per_s", "setup_s"}
    assert result["metrics"]["train_seeds_per_s"]["value"] > 0


def test_the_exchange_left_out_is_not_correct(outsider):
    _, compared = run.run_cell(CELL, SEED, 0.2, False, allow_cpu=True,
                               faults=("no_exchange",))
    correct, over = harness.judge(compared)
    assert not correct and "loss_gap" in over


def test_the_world_lays_rows_over_the_chips(outsider):
    import jax
    from jax.sharding import Mesh
    import numpy as np
    from chipbench import world
    cfg = spec.Cell(CELL).config
    mesh = Mesh(np.array(jax.devices()[:4]), ("chips",))
    a, b = (world.make_world(cfg, SEED, mesh) for _ in range(2))
    assert {s.data.shape for s in a["feat"].addressable_shards} == {(1024, 16)}
    assert np.array_equal(np.asarray(a["feat"]), np.asarray(b["feat"]))


class _OneScope:
    """A trace with ``seconds`` of device self time under one scope."""

    class Op:
        def __init__(self, scope):
            self.scope = scope

    def __init__(self, scope, seconds):
        self.scope, self.s = scope, seconds

    def seconds(self, pick):
        return self.s if pick(self.Op(self.scope)) else None


def test_scope_roofline_counts_its_work_from_the_cells_shapes(outsider):
    cell = spec.Cell(CELL)
    m = next(m for m in cell.per_layer if m["name"] == "exchange_roofline.train")
    assert m["reducer"] not in readers.REDUCERS     # found as a file
    peaks = spec.peaks("TPU v5 lite")
    ctx = {"trace": _OneScope("jit(step)/rows_exchange/psum", 3e-6),
           "facts": {"steps": 3}, "cell": cell, "peaks": peaks}
    got = readers.reducer(m["reducer"])(ctx, **m["args"])
    # 64 rows of 16 float32 read and written, over 819 GB/s, in 1 us a step
    assert got == pytest.approx(100 * (2 * 64 * 16 * 4 / 819e9) / 1e-6)
    other = dict(ctx, trace=_OneScope("jit(step)/elsewhere", 3e-6))
    assert readers.reducer(m["reducer"])(other, **m["args"]) is None
    assert readers.reducer(m["reducer"])(dict(ctx, peaks=None),
                                         **m["args"]) is None


def test_step_mfu_takes_its_flops_from_the_configurations_work_function(outsider):
    cell = spec.Cell(CELL)

    class Window:
        window_s = 2.0
    ctx = {"trace": Window(), "facts": {"steps": 10}, "cell": cell,
           "peaks": {"bf16_flops_per_s": 1e6}, "chips": 4}
    flops = 3 * 2 * 64 * 16 * 5
    assert readers.step_mfu(ctx, "steps", True) == \
        pytest.approx(100 * flops * 10 / (2.0 * 1e6))


def test_an_unknown_name_lists_both_directories_files(outsider):
    with pytest.raises(SystemExit) as e:
        spec.plugin("entries", "never_heard_of")
    assert "entries/never_heard_of.py" in str(e.value)
    for name in OWN["entries"] + ["train_step", "micro_batch_server"]:
        assert name in str(e.value)
