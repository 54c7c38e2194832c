"""The MAG240M relational deployment at a size the CPU holds:
``tests/tiny_rgat`` is a tiny configuration and a cell over the REAL entry
``entries/rgat_train_step.py``, the REAL world ``worlds/planted_typed.py``
and the REAL reference ``references/mag_rgat.py``, laid in front of
``spec.SEARCH`` with a ``BENCHMARK.json`` of its own. A CPU run shows
control flow and results, it gives no time.
"""

import json
import os

import numpy as np
import pytest

from chipbench import harness, readers, run, spec
from chipbench.tests.test_tiny_gat import _Planted

HERE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "tiny_rgat")
CELL, REAL = "tiny-rgat-train", "mag240m-rgat-train"
SEED = 2**31 + 39
NEW = ["relation_route_ms.train", "relation_project_mfu.train",
       "relation_attention_roofline.train", "relation_skew.train"]


@pytest.fixture
def tiny_rgat(monkeypatch):
    monkeypatch.setattr(spec, "SEARCH", [HERE] + spec.SEARCH)
    monkeypatch.setattr(spec, "BENCHMARK_FILE",
                        os.path.join(HERE, "BENCHMARK.json"))


def test_the_cell_runs_to_a_correct_line(tiny_rgat, capsys):
    result, compared = run.run_cell(CELL, SEED, 1.0, True, allow_cpu=True)
    assert harness.finish(result, compared) == 0
    line = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert line["correct"] is True
    assert list(line["compared"]) == [
        "sample_bad", "draw_skew", "loss_gap", "grad_gap", "update_gap",
        "nonfinite_losses", "compiles_in_window"]
    assert line["attempted"] > 0 and line["failed"] == 0
    skew = line["metrics"]["relation_skew.train"]
    assert skew["unit"] == "%" and 20 < skew["value"] < 100
    # five leaves are biases in front of a batch norm (the convs', stacked
    # over the relations, the skips' and the head's first): their gradient
    # is nought to rounding, and the update's comparison leaves them out
    assert line["run"]["leaves_left_out"] == 5
    # no device trace on the CPU: a roofline stays silent, it is never 0
    for name in NEW[:3]:
        assert name not in line["metrics"]


@pytest.mark.parametrize("fault,over", [
    ("half_batch", {"loss_gap", "grad_gap", "update_gap"}),
    ("norm_over_padding", {"loss_gap", "grad_gap", "update_gap"}),
    ("state_unchanged", {"update_gap"})])
def test_the_faults_are_not_correct(tiny_rgat, fault, over):
    _, compared = run.run_cell(CELL, SEED, 0.2, False, allow_cpu=True,
                               faults=(fault,))
    correct, found = harness.judge(compared)
    assert not correct and over <= set(found)
    assert compared["sample_bad"][0] == 0       # the sample was sound


def test_prove_reads_the_control_and_every_fault_as_not_correct(tiny_rgat):
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
                         "fault_one_relation", "fault_self_edge",
                         "fault_norm_over_padding", "fault_state_unchanged"}
    assert not any(correct for correct, _ in seen.values())
    for fault in ("fault_one_relation", "fault_self_edge",
                  "fault_norm_over_padding"):
        assert {"loss_gap", "grad_gap"} <= set(seen[fault][1])
    got = shown["program"]
    assert 0 < got["edge_rel_max"] < got["edge_valid"] < got["edge_cap"]


def test_every_metric_of_the_cell_reads_the_programs_own_names(tiny_rgat):
    """Over the compiled text of the step the tiny cell drives: every
    per-layer metric the real cell lists finds something to read, the new
    four among them."""
    import jax
    cell = spec.Cell(CELL)
    real = [m["name"] for m in json.load(open(os.path.join(
        spec.ROOT, "BENCHMARK.json")))["per_layer"]
        if REAL in m.get("workloads", [])]
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
             for s in ("qt_project", "qt_attention", "qt_relation_route",
                       "qt_norm", "qt_forward")}
    assert all(under.values())
    assert got["relation_route_ms.train"]["value"] == pytest.approx(
        1e-3 * len(under["qt_relation_route"]))
    # the model's four scopes lie beneath the forward pass, apart
    model = sum((under[s] for s in ("qt_project", "qt_attention",
                                    "qt_relation_route", "qt_norm")), [])
    assert len(set(map(id, model))) == len(model)
    assert set(map(id, model)) < set(map(id, under["qt_forward"]))
    # the attention ran over the slot axis, all of it
    assert all("qt_attention_slots" in o.scope for o in under["qt_attention"])
    work = lambda name, **kw: readers.work_of(cell, name, **kw)
    assert got["relation_project_mfu.train"]["value"] == pytest.approx(
        100.0 * work("rgat_matmul", train=True)["flops"] / 197e12
        / (1e-6 * len(under["qt_project"])))
    assert got["relation_attention_roofline.train"]["value"] == \
        pytest.approx(100.0 * work("rgat_attention")["bytes"] / 819e9
                      / (1e-6 * len(under["qt_attention"])))
    assert got["step.mfu.train"]["value"] == pytest.approx(
        100.0 * work("rgat_matmul", train=True)["flops"] / 197e12)
    assert got["relation_skew.train"]["value"] == pytest.approx(
        100.0 * counters["edge_rel_max"] / counters["edge_valid"])
    # the counters are the walk's own: five steps of set-up, static caps
    assert counters["edge_cap"] == 5 * (32 * 5 + 32 * 6 * 3)


def test_the_world_is_typed_and_its_rows_are_means(tiny_rgat):
    """Every slot's stored relation is its pair of types', every relation
    holds its slots, the rows are stored in float16, and an author's row
    is the mean of its papers' rows, rounded once."""
    from chipbench import world
    cfg = spec.Cell(CELL).config
    w = {k: np.asarray(v) for k, v in world.make_world(cfg, SEED).items()}
    names, starts, counts, rels = spec.plugin(
        "worlds", "planted_typed").layout(cfg)
    assert w["feat"].dtype == np.float16
    assert w["feat"].shape == (cfg["nodes"], cfg["feature_dim"])
    assert w["indices"].shape == (cfg["edges"],)
    kind = np.searchsorted(starts[1:], np.arange(cfg["nodes"]),
                           side="right")
    row = np.repeat(np.arange(cfg["nodes"]), np.diff(w["indptr"]))
    pair = {(t, s): r for r, (t, s, _, _) in enumerate(rels)}
    want = np.array([pair[(a, b)] for a, b in zip(kind[row],
                                                  kind[w["indices"]])])
    assert (w["relation"] == want).all()
    assert np.bincount(w["relation"]).tolist() == [r[2] for r in rels]
    feat = w["feat"].astype(np.float32)
    for node, r in ((starts[1] + 3, 1), (starts[2] + 1, 4)):
        s = slice(w["indptr"][node], w["indptr"][node + 1])
        mine = w["indices"][s][w["relation"][s] == r]
        assert mine.size
        mean = feat[mine].mean(axis=0).astype(np.float16)
        np.testing.assert_allclose(w["feat"][node], mean, rtol=2e-3,
                                   atol=2e-3)


def test_the_work_counts_are_the_published_models():
    """At the real cell's shapes: the products as the mathematics needs
    them, the attention's bytes without self edges (the reckoning:
    about 732 GFLOP forward, 1,522 with the backward pass, 5.56 GB)."""
    cell = spec.Cell(REAL)
    work = lambda name, **kw: readers.work_of(cell, name, **kw)
    t1, b = 26_624, 1024
    fwd = 2.0 * (768 * (t1 * 15 * (1024 + 4) + t1 * 20 + t1 * 1024)
                 + 1024 * (b * 25 * (1024 + 4) + b * 20 + b * 1024)
                 + b * 1024 * (1024 + 153))
    assert work("rgat_matmul", train=False)["flops"] == fwd
    assert round(fwd / 1e9) == 732
    both = work("rgat_matmul", train=True)["flops"]
    assert round(both / 1e9) == 1522
    assert work("rgat_matmul_train")["flops"] == both
    assert work("rgat_attention")["bytes"] == 3 * 4096.0 * (
        t1 * 15 + t1 + b * 25 + b)
    assert round(work("rgat_attention")["bytes"] / 1e7) == 556


def test_the_real_configuration_keeps_the_published_widths():
    real = json.load(open(os.path.join(spec.HERE, "configs",
                                       "mag240m-rgat-1of64.json")))
    assert real["reduced"] == ["nodes", "edges", "train_nodes"]
    pub = real["published"]
    for key in ("feature_dim", "hidden_dim", "heads", "num_layers", "fanout",
                "batch", "dropout", "num_relations"):
        assert real[key] == pub[key], key
    assert real["num_classes"] == pub["classes"] == 153
    assert real["head_dim"] * real["heads"] == real["hidden_dim"] == 1024
    assert real["precision"]["storage"] == pub["feature_dtype"] == "float16"
    # a 64th of every node type and of every relation's slots
    types = real["node_types"]
    for name, key in (("paper", "papers"), ("author", "authors"),
                      ("institution", "institutions")):
        assert types[name] == round(pub[key] / 64)
    assert real["nodes"] == sum(types.values())
    slots = [r["slots"] for r in real["relations"]]
    both = pub["edge_slots"]
    assert slots[0] == round(both["cites_both_ways"] / 64)
    assert slots[1] == slots[2] == round(both["writes_both_ways"] / 128)
    assert slots[3] == slots[4] == round(both["affiliated_both_ways"] / 128)
    assert real["edges"] == sum(slots)
    assert real["train_nodes"] == pub["train_nodes"] // 64
    for key in ("assumed", "deployment", "guarantees"):
        assert real[key]
    bench = json.load(open(os.path.join(spec.ROOT, "BENCHMARK.json")))
    sources = [c["source"] for c in bench["configs"]]
    assert len(set(sources)) == len(sources) and real["source"] in sources
    assert len(real["source"]) <= 200
    cell = spec.Cell(REAL)
    assert cell.chips == 1 and cell.entry == "rgat_train_step"
    assert cell.reference is spec.plugin("references", "mag_rgat")
    assert cell.named("world") == "planted_typed"
    assert {m["name"] for m in cell.end_to_end} == {"train_seeds_per_s",
                                                    "setup_s"}
