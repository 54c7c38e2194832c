"""The tiered deployment at a size the CPU holds: ``tests/tiny_tiered`` is
two tiny configurations (the second with a cold budget too small) and two
cells over the REAL entry ``entries/tiered_train_step.py`` and the REAL
world ``worlds/planted_tiered.py``, laid in front of ``spec.SEARCH`` with a
``BENCHMARK.json`` of its own. The CPU backend has pinned host memory, so
the cold tier really sits there; a CPU run shows control flow and results,
it gives no time.
"""

import json
import os
import re

import numpy as np
import pytest

from chipbench import harness, readers, run, spec, trace

HERE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "tiny_tiered")
CELL, SMALL = "tiny-tiered-train", "tiny-tiered-train-smallbudget"
REAL = "papers100m-sage-train-tiered"
SEED = 2**31 + 32
NEW = ["hot_hit_rate.train", "cold_budget_fill.train", "lookup_hot_ms.train",
       "lookup_cold_ms.train", "cold_wait_ms.train", "cold_roofline.train"]


@pytest.fixture
def tiny_tiered(monkeypatch):
    monkeypatch.setattr(spec, "SEARCH", [HERE] + spec.SEARCH)
    monkeypatch.setattr(spec, "BENCHMARK_FILE",
                        os.path.join(HERE, "BENCHMARK.json"))


def test_the_cell_runs_to_a_correct_line(tiny_tiered, capsys):
    result, compared = run.run_cell(CELL, SEED, 1.0, True, allow_cpu=True)
    assert harness.finish(result, compared) == 0
    line = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert line["correct"] is True
    assert list(line["compared"]) == [
        "sample_bad", "draw_skew", "loss_gap", "grad_gap", "update_gap",
        "row_gap", "nonfinite_losses", "cold_overflow", "compiles_in_window"]
    for exact in ("row_gap", "cold_overflow"):
        assert line["compared"][exact] == {"value": 0.0, "limit": 0.0}
    assert line["attempted"] > 0 and line["failed"] == 0
    # the counters came out of the step's device block, read after the window
    hit = line["metrics"]["hot_hit_rate.train"]
    fill = line["metrics"]["cold_budget_fill.train"]
    assert hit["unit"] == "%" and 60 < hit["value"] < 95
    assert 10 < fill["value"] < 100
    assert line["run"]["cold_rows_max_step"] <= line["run"]["cold_budget"]
    # no device trace on the CPU: a roofline stays silent, it is never 0
    for name in NEW[2:]:
        assert name not in line["metrics"]


def test_a_budget_too_small_reads_every_row_right_and_is_said(tiny_tiered):
    """The full host gather is exact (losses, gradients and rows are the
    reference's), and the run is NOT correct by ``cold_overflow`` alone:
    the configuration's budget is to be sized so that no step overflows."""
    result, compared = run.run_cell(SMALL, SEED, 0.3, False, allow_cpu=True)
    correct, over = harness.judge(compared)
    assert not correct and over == ["cold_overflow"]
    assert compared["cold_overflow"][0] >= result["attempted"]
    assert compared["row_gap"][0] == 0


@pytest.mark.parametrize("fault,over", [
    ("no_cold", {"loss_gap", "grad_gap", "row_gap"}),
    ("stale_order", {"loss_gap", "grad_gap", "row_gap"}),
    ("half_batch", {"update_gap"}), ("state_unchanged", {"update_gap"})])
def test_the_faults_are_not_correct(tiny_tiered, fault, over):
    _, compared = run.run_cell(CELL, SEED, 0.2, False, allow_cpu=True,
                               faults=(fault,))
    correct, found = harness.judge(compared)
    assert not correct and over <= set(found)
    assert compared["sample_bad"][0] == 0       # the sample was sound


def test_prove_reads_the_control_and_every_fault_as_not_correct(tiny_tiered):
    import jax
    cell = spec.Cell(CELL)
    job = spec.plugin("entries", cell.entry).Run(cell, SEED,
                                                 jax.devices()[:1])
    seen = {}
    for kind, numbers, shown in job.readings(0.0, True):
        seen[kind] = harness.judge(
            {k: (v, float(cell.limits[k])) for k, v in numbers.items()})
    assert seen.pop("program") == (True, [])
    assert set(seen) == {"control_bfloat16", "fault_half_batch",
                         "fault_no_cold", "fault_stale_order",
                         "fault_state_unchanged"}
    assert not any(correct for correct, _ in seen.values())
    assert "row_gap" in seen["fault_no_cold"][1]
    assert "row_gap" in seen["fault_stale_order"][1]


class _Planted:
    """A trace in which every instruction of a compiled program ran once,
    for a microsecond, one after another, on one chip."""
    window_s = 1.0

    def __init__(self, hlo: str):
        scopes = trace.scopes_of(hlo)
        self.ops = []
        for line in hlo.splitlines():
            m = re.match(r"\s*(?:ROOT )?(%([\w.\-]+) = .*)", line)
            if m and m.group(2) in scopes:
                at = 1e-6 * len(self.ops)
                op = trace.Op(m.group(1), at, at + 1e-6)
                op.scope = scopes[op.name]
                self.ops.append(op)
        self.busy_s = 1e-6 * len(self.ops)
        self.devices = [self.ops]

    def seconds(self, pick):
        picked = [o.self_s for o in self.ops if pick(o)]
        return sum(picked) if picked else None


def test_every_metric_of_the_cell_reads_the_programs_own_names(tiny_tiered):
    """Over the compiled text of the step the tiny cell drives: every
    per-layer metric the real cell lists finds something to read but the
    two that need a host call's ``-start`` / ``-done`` pair, which the CPU
    backend's program has not; those are read off a planted pair."""
    import jax
    cell = spec.Cell(CELL)
    real = [m["name"] for m in json.load(open(os.path.join(
        spec.ROOT, "BENCHMARK.json")))["per_layer"] if REAL in m["workloads"]]
    assert [m["name"] for m in cell.per_layer] == real
    assert set(NEW) <= set(real)
    job = spec.plugin("entries", cell.entry).Run(cell, SEED,
                                                 jax.devices()[:1])
    job.setup()
    counters = job.stop()
    planted = _Planted(job.program_text())
    ctx = {"trace": planted, "facts": {"steps": 1, "enqueue_s": [1e-3]},
           "counters": counters, "cell": cell,
           "peaks": spec.peaks("TPU v5 lite"), "chips": 1}
    got = readers.read_all(ctx)
    assert sorted(got) == sorted(set(real) - {"cold_wait_ms.train",
                                              "cold_roofline.train"})
    cold = [o for o in planted.ops if "qt_lookup_cold" in o.scope]
    hot = [o for o in planted.ops if "qt_lookup_hot" in o.scope]
    assert cold and hot
    assert got["lookup_cold_ms.train"]["value"] == pytest.approx(
        1e-3 * len(cold))
    assert got["lookup_hot_ms.train"]["value"] == pytest.approx(
        1e-3 * len(hot))
    # all of the store's lookup is the step's gather, the tiers beneath it
    under = [o for o in planted.ops if "qt_gather" in o.scope]
    assert set(map(id, cold + hot)) < set(map(id, under))
    assert got["gather_ms.train"]["value"] == pytest.approx(1e-3 * len(under))
    assert got["hot_hit_rate.train"]["value"] == pytest.approx(
        100.0 * counters["hot_rows"] / counters["lookup_rows"])
    assert got["cold_budget_fill.train"]["value"] == pytest.approx(
        100.0 * counters["cold_rows"]
        / (cell.config["cold_budget"] * counters["lookup_calls"]))
    # a host call as the chip's program has it: 2 ms between its start and
    # its done, 0.5 ms of them under another op; one more op under the scope
    def op(name, start, end, scope):
        o = trace.Op(f"%{name} = f32[] custom-call()", start, end)
        o.scope = scope
        return o
    scope = "jit(step)/jvp(qt_lookup_cold)/gather"
    planted.ops = planted.devices[0] = [
        op("while.2", 0.0, 2.25e-3, "jit(step)/jvp(qt_lookup_cold)/while"),
        op("call.3-start", 0.0, 1e-5, scope),
        op("fusion.7", 5e-4, 1e-3, "jit(step)/jvp(qt_lookup_hot)/gather"),
        op("call.3-done", 2e-3 - 1e-5, 2e-3, scope),
        op("fusion.9", 2e-3, 2.25e-3, scope)]
    # the loop's self time is what its ops leave of it (trace._self_times)
    planted.ops[0].self_s = 2.25e-3 - (1e-5 + 5e-4 + 1e-5 + 0.25e-3)
    got = readers.read_all(ctx)
    assert got["cold_wait_ms.train"]["value"] == pytest.approx(1.5)
    bytes_ = cell.config["cold_budget"] * (2 * cell.config["feature_dim"] * 4
                                           + 4)
    # the scope's ops, the loop's own turns among them: all of the 2.25 ms
    # but the 0.5 ms of the other scope's op, each moment once
    assert got["cold_roofline.train"]["value"] == pytest.approx(
        100.0 * (bytes_ / 819e9) / (2.25e-3 - 5e-4))
    assert got["cold_roofline.train"]["value"] < 100


def test_the_world_is_what_the_configuration_says(tiny_tiered):
    """Degrees never rise with the storage row, so the hot rows ARE the
    highest-degree nodes; a neighbour is drawn by degree (the hot half
    holds about Phi(sigma) = 84 % of the endpoints); the same seed gives
    the same world; the cold tier is in pinned host memory; the host
    simulation that sizes the budget counts what the step counts."""
    from chipbench import world
    cell = spec.Cell(CELL)
    cfg, hot = cell.config, cell.config["hot_rows"]
    one = world.make_world(cfg, SEED)
    order, indptr = np.asarray(one["order"]), np.asarray(one["indptr"])
    assert sorted(order.tolist()) == list(range(cfg["nodes"]))
    assert int(indptr[-1]) == cfg["edges"] == one["indices"].shape[0]
    by_row = np.empty(cfg["nodes"], np.int64)
    by_row[order] = np.diff(indptr)
    assert (np.diff(by_row) <= 0).all()
    share = (order[np.asarray(one["indices"])] < hot).mean()
    assert 0.80 < share < 0.88
    assert one["feat_hot"].shape == (hot, cfg["feature_dim"])
    assert one["feat_cold"].shape == (cfg["nodes"] - hot, cfg["feature_dim"])
    assert one["feat_cold"].sharding.memory_kind == "pinned_host"
    again = world.make_world(cfg, SEED)
    other = world.make_world(cfg, SEED + 1)
    for k in one:
        assert (np.asarray(one[k]) == np.asarray(again[k])).all(), k
    assert (np.asarray(other["order"]) != order).mean() > 0.9
    recipe = spec.plugin("worlds", "planted_tiered")
    counts = recipe.cold_counts(cfg, SEED, cell.batch, 20)
    result, _ = run.run_cell(CELL, SEED, 0.2, False, allow_cpu=True)
    assert 0.7 * max(counts) < result["run"]["cold_rows_max_step"] \
        < 1.3 * max(counts)


def test_the_names_of_the_real_cell_and_of_this_directory_resolve(monkeypatch):
    for front in (None, HERE):
        if front:
            monkeypatch.setattr(spec, "SEARCH", [front] + spec.SEARCH)
            monkeypatch.setattr(spec, "BENCHMARK_FILE",
                                os.path.join(front, "BENCHMARK.json"))
        for name in ([REAL] if front is None else [CELL, SMALL]):
            cell = spec.Cell(name)
            assert cell.chips == 1 and cell.entry == "tiered_train_step"
            assert callable(spec.plugin("entries", cell.entry).Run)
            assert cell.named("world") == "planted_tiered"
            assert callable(spec.plugin("worlds", cell.named("world")).make)
            assert cell.reference is spec.plugin("references", "sage")
            assert set(cell.limits) >= {"row_gap", "cold_overflow", "loss_gap"}
            assert 0 < cell.config["hot_rows"] < cell.config["nodes"]
            assert cell.config["cold_budget"] > 0
            assert {m["name"] for m in cell.end_to_end} == {
                "train_seeds_per_s", "setup_s"}
            for m in cell.per_layer:
                assert callable(readers.reducer(m["reducer"])), m["name"]
                if "work" in m.get("args", {}):
                    assert callable(spec.plugin(
                        "work", m["args"]["work"]).work)
            assert set(NEW) <= {m["name"] for m in cell.per_layer}
    real = json.load(open(os.path.join(
        spec.HERE, "configs", "papers100m-sage-1of4-tiered.json")))
    first = json.load(open(os.path.join(spec.HERE, "configs",
                                        "papers100m-sage-1of8.json")))
    # the cut is in the scale alone: every width, the fanout, the depth
    # and the precision are the one-chip configuration's
    assert real["reduced"] == ["nodes", "edges", "train_nodes"]
    for key in ("feature_dim", "num_classes", "hidden_dim", "num_layers",
                "fanout", "dropout", "optimizer", "precision",
                "degree_sigma", "degree_cap", "published"):
        assert real[key] == first[key], key
    bench = json.load(open(os.path.join(spec.ROOT, "BENCHMARK.json")))
    sources = [c["source"] for c in bench["configs"]]
    assert len(set(sources)) == len(sources) and real["source"] in sources
    assert len(real["source"]) <= 200
    assert real["nodes"] * 4 + 0 == real["published"]["nodes"]
    assert real["edges"] * 4 == real["published"]["directed_edges"]
    assert real["hot_rows"] == first["nodes"]
    for key in ("assumed", "deployment", "guarantees"):
        assert real[key]
    assert {"graph", "hot_share", "cold_budget"} <= set(real["assumed"])
