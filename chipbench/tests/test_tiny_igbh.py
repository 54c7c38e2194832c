"""The MLPerf R-GAT deployment at a size the CPU holds:
``tests/tiny_igbh`` is a tiny configuration and a cell over the REAL entry
``entries/igbh_train_step.py``, the REAL world ``worlds/planted_igbh.py``
and the REAL reference ``references/igbh_rgat.py``, laid in front of
``spec.SEARCH`` with a ``BENCHMARK.json`` of its own. A CPU run shows
control flow and results, it gives no time.
"""

import json
import os

import numpy as np
import pytest

from chipbench import harness, readers, run, spec
from chipbench.tests.test_tiny_gat import _Planted

HERE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "tiny_igbh")
CELL, REAL = "tiny-igbh-train", "igbh-rgat-train"
SEED = 2**31 + 43
NEW = ["relation_draw_ms.train", "type_union_ms.train",
       "relation_block_mfu.train"]


@pytest.fixture
def tiny_igbh(monkeypatch):
    monkeypatch.setattr(spec, "SEARCH", [HERE] + spec.SEARCH)
    monkeypatch.setattr(spec, "BENCHMARK_FILE",
                        os.path.join(HERE, "BENCHMARK.json"))


def test_the_cell_runs_to_a_correct_line(tiny_igbh, capsys):
    result, compared = run.run_cell(CELL, SEED, 1.0, True, allow_cpu=True)
    assert harness.finish(result, compared) == 0
    line = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert line["correct"] is True
    assert list(line["compared"]) == [
        "sample_bad", "draw_skew", "loss_gap", "grad_gap", "update_gap",
        "cap_drops", "nonfinite_losses", "compiles_in_window"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["compared"]["cap_drops"]["value"] == 0
    fill = line["metrics"]["frontier_fill.train"]
    assert fill["unit"] == "%" and 0 < fill["value"] < 100
    # no device trace on the CPU: the new metrics stay silent, never 0
    for name in NEW:
        assert name not in line["metrics"]


@pytest.mark.parametrize("fault,over", [
    ("half_batch", {"loss_gap", "grad_gap", "update_gap"}),
    ("state_unchanged", {"update_gap"})])
def test_the_faults_are_not_correct(tiny_igbh, fault, over):
    _, compared = run.run_cell(CELL, SEED, 0.2, False, allow_cpu=True,
                               faults=(fault,))
    correct, found = harness.judge(compared)
    assert not correct and over <= set(found)
    assert compared["sample_bad"][0] == 0       # the sample was sound


def test_prove_reads_the_control_and_every_fault_as_not_correct(tiny_igbh):
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
                         "fault_one_relation", "fault_mean_over_relations",
                         "fault_merged_draw", "fault_cap_drop",
                         "fault_state_unchanged"}
    assert not any(correct for correct, _ in seen.values())
    # the sample's faults are seen by the sample check and the count
    assert "sample_bad" in seen["fault_merged_draw"][1]
    assert {"sample_bad", "cap_drops"} <= set(seen["fault_cap_drop"][1])
    for fault in ("fault_one_relation", "fault_mean_over_relations"):
        assert {"loss_gap", "grad_gap"} <= set(seen[fault][1])
    got = shown["program"]
    assert got["type_cap_drops"] == 0
    assert 0 < got["edge_valid"] < got["edge_cap"]
    assert 0 < got["frontier_valid"] < got["frontier_cap"]


def test_every_metric_of_the_cell_reads_the_programs_own_names(tiny_igbh):
    """Over the compiled text of the step the tiny cell drives: every
    per-layer metric the real cell lists finds something to read, the new
    three among them."""
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
             for s in ("qt_draw_relation", "qt_type_union", "qt_project",
                       "qt_attention", "qt_forward")}
    assert all(under.values())
    assert got["relation_draw_ms.train"]["value"] == pytest.approx(
        1e-3 * len(under["qt_draw_relation"]))
    assert got["type_union_ms.train"]["value"] == pytest.approx(
        1e-3 * len(under["qt_type_union"]))
    work = lambda name, **kw: readers.work_of(cell, name, **kw)
    assert got["relation_block_mfu.train"]["value"] == pytest.approx(
        100.0 * work("igbh_matmul", train=True)["flops"] / 197e12
        / (1e-6 * len(under["qt_project"])))
    assert got["step.mfu.train"]["value"] == pytest.approx(
        100.0 * work("igbh_matmul", train=True)["flops"] / 197e12)
    assert got["frontier_fill.train"]["value"] == pytest.approx(
        100.0 * counters["frontier_valid"] / counters["frontier_cap"])


def test_the_world_keeps_each_relations_own_lists(tiny_igbh):
    """Every relation is a CSR of its own over its target type's local
    ids, holding its slots, its entries the source type's local ids; the
    rows are float16, of every type."""
    from chipbench import world
    cfg = spec.Cell(CELL).config
    w = world.make_world(cfg, SEED)
    names, starts, counts, rels = spec.plugin(
        "worlds", "planted_igbh").layout(cfg)
    assert np.asarray(w["feat"]).dtype == np.float16
    assert w["feat"].shape == (cfg["nodes"], cfg["feature_dim"])
    for (t, s, slots), ptr, idx in zip(rels, w["indptr"], w["indices"]):
        ptr, idx = np.asarray(ptr), np.asarray(idx)
        assert ptr.shape == (counts[t] + 1,) and ptr[-1] == slots
        assert (np.diff(ptr) >= 0).all()
        assert idx.shape == (slots,) and 0 <= idx.min() and \
            idx.max() < counts[s]


def test_the_work_counts_are_the_models_at_the_caps():
    """At the real cell's shapes: every relation's source rows at its
    source type's cap by W_src, its targets at the target type's cap
    before the hop by W_dst a_dst, and the head; the first layer's
    products once more for the backward pass, the others twice."""
    cell = spec.Cell(REAL)
    cfg = cell.config
    caps = cfg["frontier_caps"]
    p, a, f, i = (caps[n] for n in ("paper", "author", "field", "institute"))
    hid, heads = 512, 4

    def layer(fan_in, before, after):
        # (targets before, sources after) a relation, in relation order
        pairs = [(before[0], after[0]), (before[0], after[1]),
                 (before[0], after[2]), (before[1], after[0]),
                 (before[1], after[3]), (before[3], after[1]),
                 (before[2], after[0])]
        return sum(2.0 * fan_in * (src * hid + tgt * heads)
                   for tgt, src in pairs if tgt)

    hop = lambda h: [p[h], a[h], f[h], i[h]]
    fwd = [layer(1024, hop(1), hop(2)), layer(hid, hop(0), hop(1)),
           layer(hid, [512, 0, 0, 0], hop(0))]
    head = 2.0 * 512 * hid * 2983
    both = 2 * fwd[0] + 3 * fwd[1] + 3 * fwd[2] + 3 * head
    assert readers.work_of(cell, "igbh_matmul", train=True)["flops"] == both
    assert readers.work_of(cell, "igbh_matmul_train")["flops"] == both
    assert readers.work_of(cell, "igbh_matmul", train=False)["flops"] == \
        sum(fwd) + head


def test_the_real_configuration_keeps_the_published_widths():
    real = json.load(open(os.path.join(spec.HERE, "configs",
                                       "igbh-rgat-1of256.json")))
    assert real["reduced"] == ["nodes", "edges", "train_nodes"]
    pub = real["published"]
    for key in ("feature_dim", "hidden_dim", "heads", "num_layers", "fanout",
                "dropout", "num_relations"):
        assert real[key] == pub[key], key
    assert real["num_classes"] == pub["classes"] == 2983
    assert real["head_dim"] * real["heads"] == real["hidden_dim"] == 512
    assert real["precision"]["storage"] == pub["feature_dtype"] == "float16"
    # a 256th of every node type and of every edge type's slots
    types = real["node_types"]
    for name, key in (("paper", "papers"), ("author", "authors"),
                      ("field", "fields"), ("institute", "institutes")):
        assert types[name] == round(pub[key] / 256)
    assert real["nodes"] == sum(types.values())
    by_type = {r["edge_type"]: r["slots"] for r in real["relations"]}
    for edge_type, count in pub["edges"].items():
        assert by_type[edge_type] == round(count / 256)
        if edge_type != "cites":
            assert by_type["rev_" + edge_type] == by_type[edge_type]
    assert real["edges"] == sum(by_type.values())
    assert real["train_nodes"] == round(pub["train_nodes"] / 256)
    for key in ("assumed", "deployment", "guarantees"):
        assert real[key]
    bench = json.load(open(os.path.join(spec.ROOT, "BENCHMARK.json")))
    sources = [c["source"] for c in bench["configs"]]
    assert len(set(sources)) == len(sources) and real["source"] in sources
    assert len(real["source"]) <= 200
    cell = spec.Cell(REAL)
    assert cell.chips == 1 and cell.entry == "igbh_train_step"
    assert cell.batch == 512
    assert cell.reference is spec.plugin("references", "igbh_rgat")
    assert cell.named("world") == "planted_igbh"
    assert {m["name"] for m in cell.end_to_end} == {"train_seeds_per_s",
                                                    "setup_s"}
