"""The relational attention model over a typed graph through
``build_train_step`` (``ops/relation.py``, ``models/gat.py``'s relational
layer, ``models/mag.py`` ``MAG240MGNN(relations=5)``), held to the
benchmark's plain reference ``chipbench/references/mag_rgat.py``, which
shares no code with them:

(a) three steps of ``build_train_step`` against the reference from the
    same seeded weights: losses, the first gradient, the weights after
    step 3, by the numbers the benchmark's cell compares, on a typed graph
    with an isolated paper, an author with no institution, a sampled
    self-citation, a relation with no edge in a layer and a batch with a
    -1 tail; and one layer's output before its norm, biases and all, in
    the slot form and the segment form;
(b) the group softmax with one relation and a self edge as a slot of its
    own is today's softmax bit for bit, forward and gradient, in the slot
    form and the segment form;
(c) every sampled slot's relation, as the walk states it, is the stored
    relation of the CSR slot it was drawn from, in every exact arm.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from chipbench import check, reference, spec
from quiver_tpu.models import MAG240MGNN
from quiver_tpu.models.gat import GATConv, _group_softmax, segment_softmax
from quiver_tpu.ops import (NodeTypes, TypedIndices, as_index_rows,
                            sample_multihop)
from quiver_tpu.parallel.train import (TrainState, build_train_step,
                                       layers_to_adjs, masked_feature_gather)
from quiver_tpu.pyg.sage_sampler import Adj

PAPERS, AUTHORS, INSTITUTIONS = 40, 30, 3
N = PAPERS + AUTHORS + INSTITUTIONS
PAIRS = ((0, 0), (1, 0), (0, 1), (1, 2), (2, 1))   # (target, source) type
TYPES = NodeTypes(starts=(PAPERS, PAPERS + AUTHORS), relations=PAIRS)
DIM, HIDDEN, HEADS, CLASSES = 24, 32, 4, 7
SIZES, BATCH, VALID, LR = [4, 3], 16, 12, 1e-3
LIMITS = {"loss_gap": 1e-4, "grad_gap": 1e-3, "update_gap": 1e-3}
SELF_CITING, ISOLATED, LONE_AUTHOR = 5, 6, PAPERS + 1


def _kind(ids):
    return np.searchsorted([PAPERS, PAPERS + AUTHORS], ids, side="right")


@pytest.fixture(scope="module")
def graph():
    """A row a node, each relation's slots in relation order: papers cite
    papers and are written by authors, authors write papers and belong to
    institutions, institutions hold authors. Paper 6 has no neighbour,
    paper 5 cites itself and has fewer neighbours than the fanout, author
    41 belongs to no institution."""
    rng = np.random.default_rng(0)
    lo = [0, PAPERS, PAPERS + AUTHORS]
    hi = [PAPERS, PAPERS + AUTHORS, N]
    rows, rels = [], []
    for node in range(N):
        kind = int(_kind(node))
        row, rel = [], []
        for r, (t, s) in enumerate(PAIRS):
            if t != kind:
                continue
            d = int(rng.integers(0, 7 if s == 0 else 3))
            if node == LONE_AUTHOR and s == 2:
                d = 0
            row += list(rng.integers(lo[s], hi[s], d))
            rel += [r] * d
        if node == ISOLATED:
            row, rel = [], []
        if node == SELF_CITING:
            row, rel = [SELF_CITING, 7], [0, 0]
        rows.append(row)
        rels.append(rel)
    indptr = np.concatenate([[0], np.cumsum([len(r) for r in rows])])
    indices = np.concatenate(rows).astype(np.int32)
    relation = np.concatenate(rels).astype(np.int8)
    feat = rng.standard_normal((N, DIM)).astype(np.float16)
    labels = rng.integers(0, CLASSES, N).astype(np.int32)
    return (jnp.asarray(indptr.astype(np.int32)), jnp.asarray(indices),
            jnp.asarray(relation), jnp.asarray(feat), labels)


def _batch(step: int):
    """12 distinct papers, the self-citing and the isolated one among
    them, then four empty slots."""
    rng = np.random.default_rng([7, step])
    rest = rng.permutation(np.setdiff1d(np.arange(PAPERS),
                                        [SELF_CITING, ISOLATED]))[:VALID - 2]
    return np.concatenate([[SELF_CITING, ISOLATED], rest,
                           -np.ones(BATCH - VALID)]).astype(np.int32)


def _stored(indptr, indices, relation, seeds, key):
    """The sample the step drew and each edge's STORED relation, read at
    the CSR slot the same draw reports (``eid=True``)."""
    _, layers = sample_multihop(indptr, indices, seeds, SIZES, key,
                                seeds_dense=True, eid=True)
    rels = [jnp.where(l.col >= 0, relation[jnp.clip(l.e_id, 0)]
                      .astype(jnp.int32), -1) for l in layers]
    return layers, rels


@pytest.fixture(autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


def test_three_steps_against_the_plain_reference(graph):
    indptr, indices, relation, feat, labels = graph
    ref = spec.plugin("references", "mag_rgat")
    entry = spec.plugin("entries", "rgat_train_step")
    layers0 = ref.init_layers(jax.random.key(3), DIM, HIDDEN, CLASSES,
                              len(SIZES), HEADS, len(PAIRS))
    model = MAG240MGNN(model="gat", hidden_dim=HIDDEN, out_dim=CLASSES,
                       num_layers=len(SIZES), heads=HEADS, dropout=0.5,
                       relations=len(PAIRS))
    tx = optax.adam(LR)
    params = entry.program_tree(layers0)
    state = TrainState(params, tx.init(params), jnp.zeros((), jnp.int32))
    step = build_train_step(model, tx, SIZES, BATCH, method="exact",
                            donate=False)
    typed = TypedIndices(indices, TYPES)
    replay = check.sampler_replay(SIZES)
    indptr_host, row_values = check.graph_reader(indptr, indices)
    facts = check.SampleFacts()
    program = {"losses": [], "params0": layers0}
    ours, opt = layers0, ref.adam_init(layers0)
    theirs = {"losses": [], "params0": layers0}
    seen = {"self": False, "absent": set(range(len(PAIRS)))}
    for t in range(3):
        seeds = _batch(t)
        y = jnp.asarray(np.where(seeds >= 0, labels[seeds], 0))
        key = jax.random.fold_in(jax.random.key(11), t)
        state, loss = step(state, feat, None, indptr, typed,
                           jnp.asarray(seeds), y, key)
        program["losses"].append(float(loss))
        if t == 0:
            program["grad1"] = jax.tree.map(
                lambda m: np.asarray(m) / (1 - ref.ADAM_B1),
                entry.reference_layers(state.opt_state[0].mu))
        sample = replay(indptr, indices, jnp.asarray(seeds), key)
        found = reference.check_sample(
            jax.device_get(sample), SIZES, indptr_host, row_values,
            np.random.default_rng(t))
        assert found["bad"] == 0 and found["edges"] > 0
        facts.add(found)
        _, rels = _stored(indptr, indices, relation, jnp.asarray(seeds), key)
        hop0 = sample.hops[0]
        seen["self"] |= bool(((hop0.row == hop0.col) & (hop0.col >= 0)).any())
        seen["absent"] &= set(range(len(PAIRS))) - set(
            np.asarray(rels[0]).tolist())
        value, grads = ref.loss_and_grads(ours, feat, sample, rels, y,
                                          jax.random.fold_in(key, 1000))
        theirs["losses"].append(float(value))
        if t == 0:
            theirs["grad1"] = grads
        ours, opt = ref.adam_update(ours, grads, opt, LR)
    # the sample held what the test is about: a sampled self-citation,
    # relations with no edge in the batch's own layer (authors and
    # institutions are never its targets)
    assert seen["self"] and {1, 3, 4} <= seen["absent"]
    program["params3"] = entry.reference_layers(state.params)
    theirs["params3"] = ours
    numbers = check.train_numbers(program, theirs, facts)
    assert numbers["sample_bad"] == 0
    for name, limit in LIMITS.items():
        assert numbers[name] <= limit, (name, numbers[name])
    # relation 4 has no edge in any sample drawn from papers: no gradient
    g = program["grad1"]["convs"]
    assert all(not np.asarray(c["w"][4]).any() for c in g)
    # and the model's own faults are seen
    sound, _ = ref.loss_and_grads(layers0, feat, sample, rels, y, None)
    for fault in ("one_relation", "self_edge"):
        value, _ = ref.loss_and_grads(layers0, feat, sample, rels, y, None,
                                      fault=fault)
        assert abs(float(value) - float(sound)) / float(sound) \
            > LIMITS["loss_gap"], fault


@pytest.mark.parametrize("with_fanout", [True, False])
def test_a_layer_before_its_norm_is_the_references(graph, with_fanout):
    """One relational ``GATConv`` against the reference's ``rgat_conv`` on
    the same blocks with random biases: the biases of the relations that
    have an edge in the block enter, the others do not."""
    indptr, indices, relation, feat, _ = graph
    ref = spec.plugin("references", "mag_rgat")
    seeds = jnp.asarray(_batch(0))
    key = jax.random.key(5)
    n_id, layers = sample_multihop(indptr, indices, seeds, SIZES, key,
                                   seeds_dense=True, node_types=TYPES)
    _, rels = _stored(indptr, indices, relation, seeds, key)
    adjs = layers_to_adjs(layers, BATCH, SIZES)
    if not with_fanout:
        adjs = [Adj(a.edge_index, a.e_id, a.size, a.mask, None,
                    a.valid_targets, a.relation, a.relations) for a in adjs]
    x = masked_feature_gather(feat, n_id)
    p = ref.init_layers(jax.random.key(4), DIM, HIDDEN, CLASSES, 1, HEADS,
                        len(PAIRS))["convs"][0]
    p["b"] = jax.random.normal(jax.random.key(6), p["b"].shape)
    conv = GATConv(HIDDEN // HEADS, heads=HEADS, relations=len(PAIRS))
    params = {"params": {"kernel": p["w"], "att_src": p["a_src"],
                         "att_dst": p["a_dst"], "bias": p["b"]}}
    adj, hop = adjs[0], layers[-1]
    mask = adj.target_mask()
    got = conv.apply(params, x, adj)
    want = ref.rgat_conv(p, x, reference.Hop(hop.n_id, hop.row, hop.col),
                         rels[-1], mask)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    present = set(np.asarray(rels[-1]).tolist()) - {-1}
    assert {0, 1, 2} <= present and 4 not in present
    # the gate is what a constant bias a column shows: the same output
    # with relation 4's bias moved, a changed one with relation 0's
    moved = dict(params["params"], bias=params["params"]["bias"].at[4].add(1))
    assert (np.asarray(conv.apply({"params": moved}, x, adj))
            == np.asarray(got)).all()
    moved = dict(params["params"], bias=params["params"]["bias"].at[0].add(1))
    assert not np.allclose(conv.apply({"params": moved}, x, adj), got)


@pytest.mark.parametrize("model_relations,graph_relations",
                         [(1, 5), (4, 5), (5, None), (5, 1)],
                         ids=["default-over-typed", "fewer", "over-untyped",
                              "more"])
def test_a_relation_count_unlike_the_graphs_is_refused(
        graph, model_relations, graph_relations):
    """The model's relation count is the graph's, or the step does not
    trace: a model of fewer relations would read slots of the others
    through no weight of theirs, and normalise them over no group."""
    indptr, indices, _, feat, _ = graph
    seeds = jnp.asarray(_batch(0))
    types = None
    if graph_relations is not None:
        types = NodeTypes(starts=TYPES.starts,
                          relations=PAIRS[:graph_relations])
    n_id, layers = sample_multihop(indptr, indices, seeds, SIZES,
                                   jax.random.key(5), seeds_dense=True,
                                   node_types=types)
    adjs = layers_to_adjs(layers, BATCH, SIZES)
    assert [a.relations for a in adjs] == [graph_relations] * len(SIZES)
    model = MAG240MGNN(model="gat", hidden_dim=HIDDEN, out_dim=CLASSES,
                       num_layers=len(SIZES), heads=HEADS,
                       relations=model_relations)
    x = masked_feature_gather(feat, n_id)
    with pytest.raises(ValueError, match="over blocks of a graph with"):
        model.init(jax.random.key(0), x, adjs)


@pytest.mark.parametrize("what", ["forward", "gradient"])
def test_one_relation_is_todays_softmax_bit_for_bit(what):
    rng = np.random.default_rng(3)
    k, t, heads = 5, 9, 4
    logits = jnp.asarray(rng.standard_normal((k + 1, t, heads)), jnp.float32)
    ok = jnp.asarray(rng.random((k + 1, t, 1)) < 0.7)
    one = jnp.zeros((k + 1, t, 1), jnp.int32)        # the self edge's too
    weigh = jnp.asarray(rng.standard_normal((k + 1, t, heads)), jnp.float32)
    todays = lambda l: _group_softmax(l, ok, 0.2)
    grouped = lambda l: _group_softmax(l, ok, 0.2, one, 1)
    if what == "gradient":
        todays, grouped = (jax.grad(lambda l, f=f: (f(l) * weigh).sum())
                           for f in (todays, grouped))
    assert np.asarray(todays(logits)).tobytes() == \
        np.asarray(grouped(logits)).tobytes()
    # the segment form: a (target, relation) group of one relation is the
    # target's segment
    d = jnp.asarray(rng.integers(0, t, 30))
    flat = jnp.asarray(rng.standard_normal((30, heads)), jnp.float32)
    valid = jnp.asarray(rng.random((30, 1)) < 0.8)
    seg = lambda l, ids: segment_softmax(l, ids, t, valid)
    if what == "gradient":
        seg = lambda l, ids: jax.grad(
            lambda v: (segment_softmax(v, ids, t, valid) * flat).sum())(l)
    assert np.asarray(seg(flat, d)).tobytes() == \
        np.asarray(seg(flat, d * 1 + jnp.zeros_like(d))).tobytes()


@pytest.mark.parametrize("arm", ["exact", "exact_wide"])
def test_every_slots_relation_is_the_stored_one(graph, arm):
    indptr, indices, relation, _, _ = graph
    seeds = jnp.asarray(np.concatenate(
        [np.arange(0, N, 3), -np.ones(6)]).astype(np.int32))
    kw = {"indices_rows": as_index_rows(indices)} if arm == "exact_wide" \
        else {}
    for i in range(3):
        key = jax.random.key(20 + i)
        _, layers = sample_multihop(indptr, indices, seeds, SIZES, key,
                                    seeds_dense=True, node_types=TYPES,
                                    eid=True, **kw)
        for l in layers:
            valid = np.asarray(l.col) >= 0
            got = np.asarray(l.rel)
            want = np.asarray(relation)[np.clip(np.asarray(l.e_id), 0, None)]
            assert (got[valid] == want[valid]).all()
            assert (got[~valid] == -1).all()
            assert valid.any()
