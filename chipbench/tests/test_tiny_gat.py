"""The MAG240M deployment at a size the CPU holds: ``tests/tiny_gat`` is a
tiny configuration and a cell over the REAL entry
``entries/gat_train_step.py``, the REAL world ``worlds/planted_half.py``
and the REAL reference ``references/mag_gat.py``, laid in front of
``spec.SEARCH`` with a ``BENCHMARK.json`` of its own. A CPU run shows
control flow and results, it gives no time.
"""

import json
import os
import re

import numpy as np
import pytest

from chipbench import harness, readers, run, spec, trace

HERE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "tiny_gat")
CELL, REAL = "tiny-gat-train", "mag240m-gat-train"
SEED = 2**31 + 34
NEW = ["project_ms.train", "attention_ms.train", "norm_ms.train",
       "project_mfu.train", "attention_roofline.train",
       "frontier_roofline.train", "frontier_fill.train",
       "attention_fill.train"]


@pytest.fixture
def tiny_gat(monkeypatch):
    monkeypatch.setattr(spec, "SEARCH", [HERE] + spec.SEARCH)
    monkeypatch.setattr(spec, "BENCHMARK_FILE",
                        os.path.join(HERE, "BENCHMARK.json"))


def test_the_cell_runs_to_a_correct_line(tiny_gat, capsys):
    result, compared = run.run_cell(CELL, SEED, 1.0, True, allow_cpu=True)
    assert harness.finish(result, compared) == 0
    line = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert line["correct"] is True
    assert list(line["compared"]) == [
        "sample_bad", "draw_skew", "loss_gap", "grad_gap", "update_gap",
        "nonfinite_losses", "compiles_in_window"]
    assert line["attempted"] > 0 and line["failed"] == 0
    # the counters came out of the step's device block, read after the window
    rows, slots = (line["metrics"][k] for k in ("frontier_fill.train",
                                                "attention_fill.train"))
    assert rows["unit"] == slots["unit"] == "%"
    assert 30 < rows["value"] < 95 and 30 < slots["value"] < 95
    # five leaves are biases in front of a batch norm: their gradient is
    # nought to rounding, and the update's comparison leaves them out
    assert line["run"]["leaves_left_out"] == 5
    # no device trace on the CPU: a roofline stays silent, it is never 0
    for name in NEW[:6]:
        assert name not in line["metrics"]


@pytest.mark.parametrize("fault,over", [
    ("half_batch", {"loss_gap", "grad_gap", "update_gap"}),
    ("norm_over_padding", {"loss_gap", "grad_gap", "update_gap"}),
    ("state_unchanged", {"update_gap"})])
def test_the_faults_are_not_correct(tiny_gat, fault, over):
    _, compared = run.run_cell(CELL, SEED, 0.2, False, allow_cpu=True,
                               faults=(fault,))
    correct, found = harness.judge(compared)
    assert not correct and over <= set(found)
    assert compared["sample_bad"][0] == 0       # the sample was sound


def test_prove_reads_the_control_and_every_fault_as_not_correct(tiny_gat):
    import jax
    cell = spec.Cell(CELL)
    job = spec.plugin("entries", cell.entry).Run(cell, SEED,
                                                 jax.devices()[:1])
    seen, shown = {}, {}
    for kind, numbers, facts in job.readings(0.0, True):
        seen[kind] = harness.judge(
            {k: (v, float(cell.limits[k])) for k, v in numbers.items()})
        shown[kind] = facts
    assert seen.pop("program") == (True, [])
    assert set(seen) == {"control_bfloat16", "fault_half_batch",
                         "fault_no_self_edge", "fault_norm_over_padding",
                         "fault_state_unchanged"}
    assert not any(correct for correct, _ in seen.values())
    for fault in ("fault_no_self_edge", "fault_norm_over_padding"):
        assert {"loss_gap", "grad_gap"} <= set(seen[fault][1])
    assert 0 < shown["program"]["edge_valid"] < shown["program"]["edge_cap"]


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


def test_every_metric_of_the_cell_reads_the_programs_own_names(tiny_gat):
    """Over the compiled text of the step the tiny cell drives: every
    per-layer metric the real cell lists finds something to read."""
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
    assert sorted(got) == sorted(real)
    under = {s: [o for o in planted.ops if s in o.scope]
             for s in ("qt_project", "qt_attention", "qt_norm", "qt_gather",
                       "qt_forward")}
    assert all(under.values())
    for name, scope in (("project_ms.train", "qt_project"),
                        ("attention_ms.train", "qt_attention"),
                        ("norm_ms.train", "qt_norm"),
                        ("gather_ms.train", "qt_gather")):
        assert got[name]["value"] == pytest.approx(1e-3 * len(under[scope]))
    # the model's three scopes lie beneath the forward pass, apart
    model = under["qt_project"] + under["qt_attention"] + under["qt_norm"]
    assert len(set(map(id, model))) == len(model)
    assert set(map(id, model)) < set(map(id, under["qt_forward"]))
    # the attention ran over the slot axis, all of it
    assert all("qt_attention_slots" in o.scope for o in under["qt_attention"])
    work = lambda name, **kw: readers.work_of(cell, name, **kw)
    assert got["project_mfu.train"]["value"] == pytest.approx(
        100.0 * work("gat_matmul", train=True)["flops"] / 197e12
        / (1e-6 * len(under["qt_project"])))
    assert got["attention_roofline.train"]["value"] == pytest.approx(
        100.0 * work("gat_attention")["bytes"] / 819e9
        / (1e-6 * len(under["qt_attention"])))
    assert got["frontier_roofline.train"]["value"] == pytest.approx(
        100.0 * work("frontier_rows")["bytes"] / 819e9
        / (1e-6 * len(under["qt_gather"])))
    assert got["step.mfu.train"]["value"] == pytest.approx(
        100.0 * work("gat_matmul", train=True)["flops"] / 197e12)
    assert got["frontier_fill.train"]["value"] == pytest.approx(
        100.0 * counters["frontier_valid"] / counters["frontier_cap"])
    assert got["attention_fill.train"]["value"] == pytest.approx(
        100.0 * counters["edge_valid"] / counters["edge_cap"])
    # the counters are the walk's own: five steps of set-up, static caps
    caps = [32 * 6, 32 * 6 * 4]
    assert counters["frontier_cap"] == 5 * caps[1]
    assert counters["edge_cap"] == 5 * (32 * 5 + caps[0] * 3)


def test_the_world_is_planted_rounded_once_to_the_storage_dtype(tiny_gat):
    import jax.numpy as jnp
    from chipbench import world
    cfg = spec.Cell(CELL).config
    half = world.make_world(cfg, SEED)
    full = world.make_world(dict(cfg, world="planted"), SEED)
    assert half["feat"].dtype == jnp.float16
    assert half["feat"].shape == (cfg["nodes"], cfg["feature_dim"])
    for k in ("indptr", "indices", "labels"):
        assert (np.asarray(half[k]) == np.asarray(full[k])).all(), k
    assert (np.asarray(half["feat"])
            == np.asarray(full["feat"].astype(jnp.float16))).all()
    wide = world.make_world(dict(cfg, precision=dict(
        cfg["precision"], storage="bfloat16")), SEED)
    assert wide["feat"].dtype == jnp.bfloat16
    with pytest.raises(SystemExit, match="16-bit float"):
        world.make_world(dict(cfg, precision={"storage": "float32"}), SEED)


def test_the_work_counts_are_the_published_models(monkeypatch):
    """At the real cell's shapes: the products, the attention's bytes, the
    gather's bytes at the storage width (ISSUE 34's reckoning)."""
    cell = spec.Cell(REAL)
    work = lambda name, **kw: readers.work_of(cell, name, **kw)
    s, t1, b = 425_984, 26_624, 1024
    fwd = 2.0 * ((s + t1) * 768 * 1024 + (t1 + b) * 1024 * 1024
                 + b * 1024 * (1024 + 153))
    assert work("gat_matmul", train=False)["flops"] == fwd
    assert round(fwd / 1e9) == 772
    both = work("gat_matmul", train=True)["flops"]
    assert round(both / 1e9) == 1605
    assert work("gat_matmul_train")["flops"] == both
    assert work("gat_attention")["bytes"] == 3 * 4096.0 * (
        t1 * 16 + t1 + b * 26 + b)
    assert work("frontier_rows")["bytes"] == s * (2 * 768 * 2 + 4)


def test_the_names_of_the_real_cell_and_of_this_directory_resolve(monkeypatch):
    for front in (None, HERE):
        if front:
            monkeypatch.setattr(spec, "SEARCH", [front] + spec.SEARCH)
            monkeypatch.setattr(spec, "BENCHMARK_FILE",
                                os.path.join(front, "BENCHMARK.json"))
        cell = spec.Cell(REAL if front is None else CELL)
        assert cell.chips == 1 and cell.entry == "gat_train_step"
        assert callable(spec.plugin("entries", cell.entry).Run)
        assert cell.named("world") == "planted_half"
        assert callable(spec.plugin("worlds", "planted_half").make)
        assert cell.reference is spec.plugin("references", "mag_gat")
        assert cell.named("step_flops") == "gat_matmul"
        assert set(cell.limits) == {
            "sample_bad", "draw_skew", "loss_gap", "grad_gap", "update_gap",
            "nonfinite_losses", "compiles_in_window"}
        assert {m["name"] for m in cell.end_to_end} == {
            "train_seeds_per_s", "setup_s"}
        for m in cell.per_layer:
            assert callable(readers.reducer(m["reducer"])), m["name"]
            if "work" in m.get("args", {}):
                assert callable(spec.plugin("work", m["args"]["work"]).work)
        assert set(NEW) <= {m["name"] for m in cell.per_layer}
    real = json.load(open(os.path.join(
        spec.HERE, "configs", "mag240m-gat-1of32.json")))
    # every width is the published one; the cut is in the scale alone
    assert real["reduced"] == ["nodes", "edges", "train_nodes"]
    pub = real["published"]
    for key in ("feature_dim", "hidden_dim", "heads", "num_layers", "fanout",
                "batch", "dropout"):
        assert real[key] == pub[key], key
    assert real["num_classes"] == pub["classes"] == 153
    assert real["feature_dim"] == 768 and real["hidden_dim"] == 1024
    assert real["head_dim"] * real["heads"] == real["hidden_dim"]
    assert real["precision"]["storage"] == pub["feature_dtype"] == "float16"
    assert real["nodes"] == round(pub["nodes"] / 32)
    assert real["edges"] == round(pub["directed_edges"] / 32)
    assert real["train_nodes"] == pub["train_nodes"] // 32
    assert pub["directed_edges"] == 2 * pub["citations"]
    for key in ("assumed", "deployment", "guarantees"):
        assert real[key]
    bench = json.load(open(os.path.join(spec.ROOT, "BENCHMARK.json")))
    sources = [c["source"] for c in bench["configs"]]
    assert len(set(sources)) == len(sources) and real["source"] in sources
    assert len(real["source"]) <= 200
    cell = next(w for w in bench["workloads"] if w["name"] == REAL)
    assert cell["traffic"] == "train-b1024" and cell["chips"] == 1
    mix = json.load(open(os.path.join(spec.HERE, "traffic",
                                      "train-b1024.json")))
    assert mix["batch"] == real["batch"] == 1024
