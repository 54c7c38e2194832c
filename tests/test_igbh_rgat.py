"""The MLPerf R-GAT (``models/rgat.py`` ``RGAT``) over a typed graph whose
relations keep their own lists, trained through ``build_train_step``,
held to the benchmark's plain reference ``chipbench/references/
igbh_rgat.py``, which shares no code with it, on the tiny world of
``chipbench/tests/tiny_igbh`` (four node types, seven relations, fanout
[3, 2, 2]) and seeded weights:

(a) one and three steps against the reference: the losses, the first
    gradient as Adam got it and the weights after the last step, by the
    numbers the benchmark's cell compares, on a batch with a -1 tail; the
    sample the step drew, replayed, holds against the graph;
(b) a relation block with no edge: its targets get the relation's bias
    and nothing else, a target slot that holds no node a zero row, as the
    reference's ``GATConv`` gives;
(c) NaN planted in the rows of the frontier's padding slots: the loss and
    every gradient stay finite;
(d) the model's own faults read over the limits.
"""

import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from chipbench import check, spec
from quiver_tpu.models import RGAT, RelationGATConv
from quiver_tpu.parallel.frontier import (ALL_KNOBS, Walk, layers_to_adjs,
                                          walk_frontier)
from quiver_tpu.parallel.train import TrainState, build_train_step
from quiver_tpu.pyg.sage_sampler import Adj

CONFIG = os.path.join(spec.HERE, "tests", "tiny_igbh", "configs",
                      "tiny-igbh.json")
BATCH, VALID, LR = 16, 13, 1e-3
# the cell's own limits; a target's logit term moves its softmax only
# through the LeakyReLU's kink (it is one number for all of a target's
# slots), so W_dst and a_dst get gradients that are differences of near
# equals, and Adam's first steps (g / |g|) carry their rounding: their
# update reads ~2e-3 here where every gradient agrees to ~2e-7
LIMITS = {"loss_gap": 1e-4, "grad_gap": 1e-3, "update_gap": 2e-2}


@pytest.fixture(autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture(scope="module")
def setup():
    cfg = json.load(open(CONFIG))
    world = spec.plugin("worlds", "planted_igbh").make(cfg, 2**31 + 43)
    entry = spec.plugin("entries", "igbh_train_step")
    ref = spec.plugin("references", "igbh_rgat")
    graph = entry.graph_of(cfg)
    typed = entry.relation_indices(cfg, world["indices"])
    drawn = ref.relations_by_layer(graph["pairs"], 0, cfg["num_layers"])
    layers0 = ref.init_layers(jax.random.key(3), cfg["feature_dim"],
                              cfg["hidden_dim"], cfg["num_classes"],
                              cfg["heads"], drawn)
    model = RGAT(hidden_dim=cfg["hidden_dim"], out_dim=cfg["num_classes"],
                 num_layers=cfg["num_layers"], heads=cfg["heads"],
                 dropout=cfg["dropout"])
    return cfg, world, entry, ref, graph, typed, layers0, model


def _batch(step: int):
    """13 distinct papers, then three empty slots."""
    rng = np.random.default_rng([7, step])
    return np.concatenate([rng.choice(400, VALID, replace=False),
                           -np.ones(BATCH - VALID)]).astype(np.int32)


_JITTED = {}


@functools.lru_cache(maxsize=None)
def _step(model, fanout: tuple):
    """The train step, built once (one compile for every test here)."""
    return build_train_step(model, optax.adam(LR), list(fanout), BATCH,
                            method="exact", donate=False)


def _loss_and_grads(ref, graph, fault=None):
    """The reference's loss and gradients, jitted once a fault (one
    program, not an operation at a time)."""
    if fault not in _JITTED:
        _JITTED[fault] = jax.jit(
            lambda layers, feat, sample, labels, key: ref.loss_and_grads(
                layers, feat, sample, graph, labels, key, fault=fault))
    return _JITTED[fault]


@pytest.mark.parametrize("steps", [1, 3])
def test_steps_against_the_plain_reference(setup, steps):
    cfg, world, entry, ref, graph, typed, layers0, model = setup
    tx = optax.adam(LR)
    params = entry.program_tree(layers0)
    state = TrainState(params, tx.init(params), jnp.zeros((), jnp.int32))
    step = _step(model, tuple(cfg["fanout"]))
    replay = entry._replay_for(tuple(cfg["fanout"]), None, ref)
    ptrs, row_values = entry._graph_reader(world["indptr"], world["indices"])
    labels = np.asarray(world["labels"])
    facts = check.SampleFacts()
    program = {"losses": [], "params0": layers0}
    theirs = {"losses": [], "params0": layers0}
    ours, opt = layers0, ref.adam_init(layers0)
    for t in range(steps):
        seeds = jnp.asarray(_batch(t))
        y = jnp.asarray(np.where(_batch(t) >= 0, labels[_batch(t)], 0))
        key = jax.random.fold_in(jax.random.key(11), t)
        state, loss = step(state, world["feat"], None, world["indptr"],
                           typed, seeds, y, key)
        program["losses"].append(float(loss))
        if t == 0:
            program["grad1"] = jax.tree.map(
                lambda m: np.asarray(m) / (1 - ref.ADAM_B1),
                entry.reference_layers(state.opt_state[0].mu))
        sample, drops = replay(world["indptr"], typed, seeds, key)
        assert int(drops) == 0
        found = entry.check_typed_sample(
            jax.device_get(sample), cfg["fanout"], graph["pairs"], ptrs,
            row_values, np.random.default_rng(t))
        assert found["bad"] == 0 and found["edges"] > 0
        facts.add(found)
        value, grads = _loss_and_grads(ref, graph)(
            ours, world["feat"], sample, y, jax.random.fold_in(key, 1000))
        theirs["losses"].append(float(value))
        if t == 0:
            theirs["grad1"] = grads
        ours, opt = ref.adam_update(ours, grads, opt, LR)
    program["params3"] = entry.reference_layers(state.params)
    theirs["params3"] = ours
    numbers = check.train_numbers(program, theirs, facts)
    assert numbers["sample_bad"] == 0
    for name, limit in LIMITS.items():
        assert numbers[name] <= limit, (name, numbers[name])


@pytest.mark.parametrize("edges", ["none", "some"])
def test_a_relation_block_adds_its_bias_to_every_target(setup, edges):
    """One relation's block, four targets of which three hold a node,
    fanout 2: with no edge at all the valid targets read the bias alone
    (a PyG ``GATConv`` over no edge returns its bias), the empty slot a
    zero row; with edges the layer is the reference's ``GATConv``."""
    _, _, _, ref, _, _, _, _ = setup
    rng = np.random.default_rng(5)
    x_src = jnp.asarray(rng.standard_normal((6, 8)), jnp.float32)
    x_dst = jnp.asarray(rng.standard_normal((5, 8)), jnp.float32)
    src = np.array([3, -1, 0, 5, 2, -1, 4, 1]) if edges == "some" \
        else -np.ones(8, int)
    row = np.where(src >= 0, np.repeat(np.arange(4), 2), -1)
    adj = Adj(jnp.asarray(np.stack([src, row]), jnp.int32), None, (6, 4),
              fanout=2, valid_targets=jnp.int32(3), relation=0, relations=1)
    conv = RelationGATConv(4, heads=2)
    params = conv.init(jax.random.key(0), x_src, x_dst, adj)
    p = params["params"]
    bias = jnp.asarray(rng.standard_normal(8), jnp.float32)
    params = {"params": dict(p, bias=bias)}
    got = conv.apply(params, x_src, x_dst, adj)
    mask = jnp.arange(4) < 3
    want = ref.gat_conv({"w_src": p["lin_src"]["kernel"],
                         "w_dst": p["lin_dst"]["kernel"],
                         "a_src": p["att_src"], "a_dst": p["att_dst"],
                         "b": bias}, x_src, x_dst[:4], row, src, mask,
                        jnp.float32)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    assert not np.asarray(got[3]).any()
    if edges == "none":
        np.testing.assert_array_equal(got[:3], jnp.broadcast_to(bias, (3, 8)))


def test_nan_in_the_padding_rows_stays_out(setup):
    """Every padding row of every type's block (a slot of the frontier
    that holds no node) set to NaN: the rows past a relation's last read
    source and past the valid targets are never multiplied, a padding
    target's row is a zero row, so the loss and every gradient stay
    finite."""
    cfg, world, _, _, _, typed, layers0, model = setup
    entry = spec.plugin("entries", "igbh_train_step")
    walk = Walk.of("test", ALL_KNOBS, cfg["fanout"], {})
    seeds = jnp.asarray(_batch(0))
    _, x, layers = walk_frontier(walk, world["feat"], None, world["indptr"],
                                 typed, seeds, jax.random.key(2))
    blocks = layers_to_adjs(layers, BATCH, cfg["fanout"])
    poisoned = tuple(jnp.where((f >= 0)[:, None], rows, jnp.nan)
                     for f, rows in zip(layers[-1].frontier, x))
    assert any(bool(jnp.isnan(rows).any()) for rows in poisoned)
    params = entry.program_tree(layers0)

    def loss(p):
        logits = model.apply(p, poisoned, blocks, train=True,
                             rngs={"dropout": jax.random.key(1)})
        return optax.softmax_cross_entropy_with_integer_labels(
            logits[:VALID], jnp.zeros((VALID,), jnp.int32)).mean()

    value, grads = jax.jit(jax.value_and_grad(loss))(params)
    assert np.isfinite(float(value))
    assert all(bool(jnp.isfinite(g).all()) for g in jax.tree.leaves(grads))


@pytest.mark.parametrize("fault", ["one_relation", "mean_over_relations"])
def test_the_models_own_faults_read_over_the_limit(setup, fault):
    cfg, world, entry, ref, graph, typed, layers0, _ = setup
    seeds = jnp.asarray(_batch(0))
    key = jax.random.key(4)
    sample, _ = entry._replay_for(tuple(cfg["fanout"]), None, ref)(
        world["indptr"], typed, seeds, key)
    y = jnp.zeros((BATCH,), jnp.int32)
    sound, _ = _loss_and_grads(ref, graph)(layers0, world["feat"], sample,
                                           y, None)
    value, _ = _loss_and_grads(ref, graph, fault)(layers0, world["feat"],
                                                  sample, y, None)
    assert abs(float(value) - float(sound)) / float(sound) \
        > LIMITS["loss_gap"]
