"""The row-sharded deployment at a size the CPU holds: ``tests/tiny_dist``
is a tiny configuration and two cells over the REAL entry
``entries/dist_train_step.py`` and the REAL world
``worlds/planted_rows_sharded.py``, laid in front of ``spec.SEARCH`` with
a ``BENCHMARK.json`` of its own (``tests/tiny`` stays as it is). Four
virtual devices; a CPU run shows control flow and results, it gives no
time.
"""

import json
import os
import re

import pytest

from chipbench import harness, readers, run, spec, trace

HERE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "tiny_dist")
CELL, SMALL = "tiny-dist-train", "tiny-dist-train-smallcap"
REAL = "papers100m-sage-train-dist4"
SEED = 2**31 + 28
NEW = ["exchange_ms.train", "exchange_roofline.train", "collective_ms.train",
       "exchange_a2a_ms.train", "exchange_bucket_fill.train"]


@pytest.fixture
def tiny_dist(monkeypatch):
    monkeypatch.setattr(spec, "SEARCH", [HERE] + spec.SEARCH)
    monkeypatch.setattr(spec, "BENCHMARK_FILE",
                        os.path.join(HERE, "BENCHMARK.json"))


def test_the_cell_runs_to_a_correct_line(tiny_dist, capsys):
    result, compared = run.run_cell(CELL, SEED, 1.0, True, allow_cpu=True)
    assert harness.finish(result, compared) == 0
    line = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert line["correct"] is True
    assert list(line["compared"]) == [
        "sample_bad", "draw_skew", "loss_gap", "grad_gap", "update_gap",
        "nonfinite_losses", "exchange_overflow", "compiles_in_window"]
    assert line["compared"]["exchange_overflow"] == {"value": 0.0,
                                                     "limit": 0.0}
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["device"]["count"] == 4
    # the counters came out of the step's device block, read after the window
    fill = line["metrics"]["exchange_bucket_fill.train"]
    assert fill["unit"] == "%" and 20 < fill["value"] < 100
    assert line["metrics"]["host_enqueue_ms.train"]["value"] > 0
    # no device trace on the CPU: a roofline stays silent, it is never 0
    assert "exchange_roofline.train" not in line["metrics"]


def test_a_cap_too_small_is_served_in_rounds_and_is_said(tiny_dist):
    """Every row still arrives (the losses are the reference's), and the
    run is NOT correct by ``exchange_overflow`` alone: the cell's cap is
    to be sized so that no step overflows it."""
    result, compared = run.run_cell(SMALL, SEED, 0.3, False, allow_cpu=True)
    correct, over = harness.judge(compared)
    assert not correct and over == ["exchange_overflow"]
    assert compared["exchange_overflow"][0] >= result["attempted"]


def test_the_exchange_left_out_is_not_correct(tiny_dist):
    _, compared = run.run_cell(CELL, SEED, 0.2, False, allow_cpu=True,
                               faults=("no_exchange",))
    correct, over = harness.judge(compared)
    assert not correct and {"loss_gap", "grad_gap"} <= set(over)
    assert compared["sample_bad"][0] == 0       # the sample was sound


@pytest.mark.parametrize("fault", ["half_batch", "state_unchanged"])
def test_the_other_faults_are_not_correct(tiny_dist, fault):
    _, compared = run.run_cell(CELL, SEED, 0.2, False, allow_cpu=True,
                               faults=(fault,))
    correct, over = harness.judge(compared)
    assert not correct and "update_gap" in over


class _Planted:
    """A trace in which every instruction of a compiled program ran once,
    for a microsecond, on one chip."""
    window_s = 1.0

    def __init__(self, hlo: str):
        scopes = trace.scopes_of(hlo)
        self.ops = []
        for line in hlo.splitlines():
            m = re.match(r"\s*(?:ROOT )?(%([\w.\-]+) = .*)", line)
            if m and m.group(2) in scopes:
                op = trace.Op(m.group(1), 0.0, 1e-6)
                op.scope = scopes[op.name]
                self.ops.append(op)
        self.busy_s = 1e-6 * len(self.ops)

    def seconds(self, pick):
        picked = [o.self_s for o in self.ops if pick(o)]
        return sum(picked) if picked else None


def test_every_metric_of_the_cell_reads_the_programs_own_names(tiny_dist):
    """Over the compiled text of the step the tiny cell drives: every
    per-layer metric the real cell lists finds something to read, the new
    ones among them; the exchange's share of its roofline is the work of
    ``work/exchange_rows.py`` over the scope's time."""
    import jax
    cell = spec.Cell(CELL)
    real = [m["name"] for m in json.load(open(os.path.join(
        spec.ROOT, "BENCHMARK.json")))["per_layer"] if REAL in m["workloads"]]
    assert [m["name"] for m in cell.per_layer] == real
    assert set(NEW) <= set(real)
    entry = spec.plugin("entries", cell.entry)
    job = entry.Run(cell, SEED, jax.devices()[:4])
    job.setup()
    counters = job.stop()
    planted = _Planted(job.program_text())
    ctx = {"trace": planted, "facts": {"steps": 1, "enqueue_s": [1e-3]},
           "counters": counters, "cell": cell,
           "peaks": spec.peaks("TPU v5 lite"), "chips": 4}
    got = readers.read_all(ctx)
    assert sorted(got) == sorted(real)
    under = [o for o in planted.ops if "qt_exchange" in o.scope]
    assert got["exchange_ms.train"]["value"] == pytest.approx(
        1e-3 * len(under))
    both = [o for o in planted.ops if " all-to-all(" in o.text]
    assert len(both) == 2 and all(o in under for o in both)
    assert got["exchange_a2a_ms.train"]["value"] == pytest.approx(2e-3)
    assert got["collective_ms.train"]["value"] > 0
    rows = 32 * 5 * 4 * 3                    # batch 32, fanout [4, 3, 2]
    least = rows * (2 * cell.config["feature_dim"] * 4 + 4) / 819e9
    assert got["exchange_roofline.train"]["value"] == pytest.approx(
        100 * least / (1e-6 * len(under)))
    # the exchange stands where the one-chip step has its gather
    assert readers.scope_ms(ctx, "qt_gather", "steps") is None
    assert got["exchange_bucket_fill.train"]["value"] == pytest.approx(
        100.0 * counters["exchange_bucket_max"] / counters["exchange_cap"])


def test_the_names_of_the_real_cell_and_of_this_directory_resolve(monkeypatch):
    for front in (None, HERE):
        if front:
            monkeypatch.setattr(spec, "SEARCH", [front] + spec.SEARCH)
            monkeypatch.setattr(spec, "BENCHMARK_FILE",
                                os.path.join(front, "BENCHMARK.json"))
        for name in ([REAL] if front is None else [CELL, SMALL]):
            cell = spec.Cell(name)
            assert cell.chips == 4 and cell.entry == "dist_train_step"
            assert callable(spec.plugin("entries", cell.entry).Run)
            assert cell.named("world") == "planted_rows_sharded"
            assert callable(spec.plugin("worlds", cell.named("world")).make)
            assert cell.reference is spec.plugin("references", "sage")
            assert callable(spec.plugin("work", cell.named("step_flops")).work)
            assert set(cell.limits) >= {"exchange_overflow", "loss_gap"}
            assert int(cell.cell["exchange_cap"]) > 0
            assert {m["name"] for m in cell.end_to_end} == {
                "train_seeds_per_s", "setup_s"}
            for m in cell.per_layer:
                assert callable(readers.reducer(m["reducer"])), m["name"]
                if "work" in m.get("args", {}):
                    assert callable(spec.plugin(
                        "work", m["args"]["work"]).work)
            assert set(NEW) <= {m["name"] for m in cell.per_layer}
    real = json.load(open(os.path.join(spec.HERE, "configs",
                                       "papers100m-sage-4of8.json")))
    first = json.load(open(os.path.join(spec.HERE, "configs",
                                        "papers100m-sage-1of8.json")))
    # the cut is in the scale alone: every width, the fanout, the depth
    # and the precision are the one-chip configuration's
    assert real["reduced"] == ["nodes", "edges", "train_nodes"]
    for key in ("feature_dim", "num_classes", "hidden_dim", "num_layers",
                "fanout", "dropout", "optimizer", "precision",
                "degree_sigma", "degree_cap", "published"):
        assert real[key] == first[key], key
    # one upstream benchmark, two deployments: each names its own part
    assert real["source"].startswith(first["source"].replace("/tree/",
                                                              "/blob/"))
    assert real["source"].endswith("train_quiver_multi_node.py")
    assert real["nodes"] * 2 == real["published"]["nodes"]
    assert real["edges"] * 2 == real["published"]["directed_edges"]
    assert -(-real["nodes"] // 4) == first["nodes"]
