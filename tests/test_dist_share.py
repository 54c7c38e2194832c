"""The share test of the row-sharded deployment, at a size the CPU holds:
four virtual chips, the benchmark's own world recipe
(``chipbench/worlds/planted_rows_sharded.py``) and entry
(``chipbench/entries/dist_train_step.py``) over the tiny configuration of
``chipbench/tests/tiny_dist``.

1. Rows looked up through four shards and an arbitrary seeded book equal
   ``table[ids]`` exactly, dense and compact, with room and overflowing.
2. The dist step's losses, first gradient and Adam update over three
   steps equal the plain reference's (``chipbench/references/sage.py``)
   within the tiny cell's limits, with a cap that fits and with one that
   overflows every step.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

import quiver_tpu as qv
from chipbench import spec, world

HERE = os.path.join(spec.HERE, "tests", "tiny_dist")
SEED = 2**31 + 41
CHIPS = 4


@pytest.fixture
def tiny_dist(monkeypatch):
    monkeypatch.setattr(spec, "SEARCH", [HERE] + spec.SEARCH)
    monkeypatch.setattr(spec, "BENCHMARK_FILE",
                        os.path.join(HERE, "BENCHMARK.json"))


def test_rows_through_four_shards_and_a_seeded_book_equal_the_table(tiny_dist):
    cfg = spec.Cell("tiny-dist-train").config
    mesh = Mesh(np.array(jax.devices()[:CHIPS]), ("host",))
    w = world.make_world(cfg, SEED, mesh)
    nodes, rows = cfg["nodes"], -(-cfg["nodes"] // CHIPS)
    assert {s.data.shape for s in w["feat"].addressable_shards} == {
        (rows, cfg["feature_dim"])}
    g2h, g2l = np.asarray(w["g2h"]), np.asarray(w["g2l"])
    # the book: balanced, a bijection onto the shards' rows, and arbitrary
    assert np.bincount(g2h, minlength=CHIPS).max() == rows
    assert np.unique(g2h * rows + g2l).size == nodes
    assert 0.7 < (g2h != np.arange(nodes) // rows).mean() < 0.8
    table = np.asarray(w["feat"])[g2h * rows + g2l]       # the host's reading
    # features = class centre + noise: a node's row lies nearest its own
    # class's mean row, so the shards hold the rows of the nodes the book names
    labels = np.asarray(w["labels"])
    centres = np.stack([table[labels == c].mean(axis=0)
                        for c in range(cfg["num_classes"])])
    near = np.argmin(((table[:500, None] - centres[None]) ** 2).sum(-1), -1)
    assert (near == labels[:500]).mean() > 0.9

    info = qv.PartitionInfo(hosts=CHIPS, global2host=w["g2h"],
                            global2local=w["g2l"])
    comm = qv.TpuComm(rank=0, world_size=CHIPS, mesh=mesh, axis="host")
    rng = np.random.default_rng(0)
    ids = rng.integers(0, nodes, CHIPS * 256).astype(np.int32)
    ids[::7] = -1
    want = np.where((ids >= 0)[:, None], table[np.clip(ids, 0, None)], 0)
    asked_by = np.arange(ids.size) // 256
    assert 0.7 < (g2h[ids[ids >= 0]] != asked_by[ids >= 0]).mean() < 0.8
    for cap in (None, 128, 9):              # dense, one round, many rounds
        dist = qv.DistFeature.from_shards(w["feat"], info, comm,
                                          exchange_cap=cap)
        np.testing.assert_array_equal(np.asarray(dist[jnp.asarray(ids)]),
                                      want)


@pytest.mark.parametrize("cell_name,overflows", [
    ("tiny-dist-train", False), ("tiny-dist-train-smallcap", True)])
def test_the_dist_step_equals_the_plain_reference(tiny_dist, cell_name,
                                                  overflows):
    cell = spec.Cell(cell_name)
    entry = spec.plugin("entries", cell.entry)
    run = entry.Run(cell, SEED, jax.devices()[:CHIPS])
    kept = run.first_steps()
    counters = run.stop()
    assert (run.overflow_steps == len(kept["steps"])) == overflows
    assert (counters["exchange_bucket_max"] > counters["exchange_cap"]) \
        == overflows
    run.free()
    numbers = entry.compare(run, kept)
    numbers.pop("facts")
    for name, value in numbers.items():
        assert value <= cell.limits[name], (name, value)
    # and it is the reference that is near, not the limits that are wide
    assert numbers["loss_gap"] < 1e-5 and numbers["grad_gap"] < 1e-6
