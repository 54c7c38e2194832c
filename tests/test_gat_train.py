"""The MAG240M attention model through ``build_train_step``
(``models/mag.py`` ``MAG240MGNN(model="gat")``, ``models/gat.py``,
``models/norm.py``), held to the benchmark's plain reference
``chipbench/references/mag_gat.py``, which shares no code with them:

(a) three steps of ``build_train_step`` over a float16 table against the
    reference from the same seeded weights: losses, the first gradient,
    the weights after step 3, by the numbers the benchmark's cell
    compares, on a graph with isolated nodes, nodes of degree under the
    fanout, a self-loop, and a batch with a -1 tail;
(b) the slot form of the softmax against the segment form on the same
    blocks with and without ``fanout``, forward and gradient;
(c) batch statistics ignore padded rows: the same valid rows with more
    padding give the same output bit for bit.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from chipbench import check, reference, spec
from quiver_tpu.models import MAG240MGNN, masked_batch_norm
from quiver_tpu.models.gat import gat_attention
from quiver_tpu.ops import sample_multihop
from quiver_tpu.parallel.train import (TrainState, build_train_step,
                                       layers_to_adjs, masked_feature_gather)
from quiver_tpu.pyg.sage_sampler import Adj

N, DIM, HIDDEN, HEADS, CLASSES = 300, 24, 32, 4, 7
SIZES, BATCH, VALID, LR = [4, 3], 32, 24, 1e-3
LIMITS = {"loss_gap": 1e-4, "grad_gap": 1e-3, "update_gap": 1e-3}


@pytest.fixture(scope="module")
def graph():
    """Nodes 0-4 isolated, degrees 0..8 elsewhere (many under the fanout),
    node 10's one neighbour is node 10."""
    rng = np.random.default_rng(0)
    deg = rng.integers(0, 9, N)
    deg[:5], deg[10] = 0, 1
    indptr = np.concatenate([[0], np.cumsum(deg)]).astype(np.int32)
    indices = rng.integers(0, N, int(indptr[-1])).astype(np.int32)
    indices[indptr[10]] = 10
    feat = rng.standard_normal((N, DIM)).astype(np.float16)
    labels = rng.integers(0, CLASSES, N).astype(np.int32)
    return (jnp.asarray(indptr), jnp.asarray(indices), jnp.asarray(feat),
            labels)


def _batch(step: int):
    """24 distinct nodes, the isolated ones and the self-loop among them,
    then eight empty slots."""
    rng = np.random.default_rng([7, step])
    rest = rng.permutation(np.arange(11, N))[:VALID - 6]
    seeds = np.concatenate([[0, 1, 2, 3, 4, 10], rest, -np.ones(BATCH - VALID)])
    return seeds.astype(np.int32)


@pytest.fixture(autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


def test_three_steps_against_the_plain_reference(graph):
    indptr, indices, feat, labels = graph
    ref = spec.plugin("references", "mag_gat")
    entry = spec.plugin("entries", "gat_train_step")
    layers0 = ref.init_layers(jax.random.key(3), DIM, HIDDEN, CLASSES,
                              len(SIZES), HEADS)
    model = MAG240MGNN(model="gat", hidden_dim=HIDDEN, out_dim=CLASSES,
                       num_layers=len(SIZES), heads=HEADS, dropout=0.5)
    tx = optax.adam(LR)
    params = entry.program_tree(layers0)
    state = TrainState(params, tx.init(params), jnp.zeros((), jnp.int32))
    step = build_train_step(model, tx, SIZES, BATCH, method="exact",
                            donate=False)
    replay = check.sampler_replay(SIZES)
    indptr_host, row_values = check.graph_reader(indptr, indices)
    facts = check.SampleFacts()
    program = {"losses": [], "params0": layers0}
    ours, opt = layers0, ref.adam_init(layers0)
    theirs = {"losses": [], "params0": layers0}
    for t in range(3):
        seeds = _batch(t)
        y = jnp.asarray(np.where(seeds >= 0, labels[seeds], 0))
        key = jax.random.fold_in(jax.random.key(11), t)
        state, loss = step(state, feat, None, indptr, indices,
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
        # the sample holds what the test is about: the self-loop's edge,
        # seeds without a neighbour, empty batch slots
        hop0 = sample.hops[0]
        assert ((hop0.row == hop0.col) & (hop0.col >= 0)).any()
        assert int(found["edges"]) > 0 and found["bad"] == 0
        facts.add(found)
        value, grads = ref.loss_and_grads(ours, feat, sample, y,
                                          jax.random.fold_in(key, 1000))
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
    # and a fault of the model's own is seen: no target attends to itself
    value, grads = ref.loss_and_grads(layers0, feat, sample, y, None,
                                      fault="no_self_edge")
    sound, _ = ref.loss_and_grads(layers0, feat, sample, y, None)
    assert abs(float(value) - float(sound)) / float(sound) > LIMITS["loss_gap"]


def _blocks(graph, with_fanout: bool):
    indptr, indices, feat, _ = graph
    seeds = jnp.asarray(_batch(0))
    n_id, layers = sample_multihop(indptr, indices, seeds, SIZES,
                                   jax.random.key(5), seeds_dense=True)
    adjs = layers_to_adjs(layers, BATCH, SIZES)
    if not with_fanout:
        adjs = [Adj(a.edge_index, a.e_id, a.size, a.mask, None,
                    a.valid_targets) for a in adjs]
    return masked_feature_gather(feat, n_id), adjs


@pytest.mark.parametrize("what", ["forward", "gradient"])
def test_the_slot_form_is_the_segment_form(graph, what):
    x, slots = _blocks(graph, True)
    _, segments = _blocks(graph, False)
    assert x.dtype == jnp.float32              # a 16-bit table, float32 rows
    rng = np.random.default_rng(1)
    for adj_s, adj_g in zip(slots, segments):
        assert adj_s.fanout is not None and adj_g.fanout is None
        sources = adj_s.size[0]
        h = jnp.asarray(rng.standard_normal((sources, HEADS * 8)), jnp.float32)
        a_src, a_dst = (jnp.asarray(rng.standard_normal((HEADS, 8)),
                                    jnp.float32) for _ in range(2))
        weigh = jnp.asarray(rng.standard_normal((adj_s.size[1], HEADS * 8)),
                            jnp.float32)
        if what == "forward":
            got = gat_attention(h, a_src, a_dst, adj_s)
            want = gat_attention(h, a_src, a_dst, adj_g)
            np.testing.assert_allclose(got, want, rtol=2e-6, atol=2e-6)
            valid = int(adj_s.valid_targets)
            assert 0 < valid < adj_s.size[1]
            assert not np.asarray(got[valid:]).any()   # no node, no row
            assert np.asarray(got[:valid]).any(axis=1).all()
        else:
            f = lambda adj: jax.grad(
                lambda *a: (gat_attention(*a, adj) * weigh).sum(),
                argnums=(0, 1, 2))(h, a_src, a_dst)
            for got, want in zip(f(adj_s), f(adj_g)):
                np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("more", [1, 7, 40])
def test_batch_statistics_ignore_padded_rows(more):
    """The same valid rows under more padding: the same output on the
    valid rows bit for bit, zero rows on the padding, whatever the padding
    holds."""
    rng = np.random.default_rng(2)
    rows = jnp.asarray(rng.standard_normal((19, 16)), jnp.float32)
    scale, bias = (jnp.asarray(rng.standard_normal(16), jnp.float32)
                   for _ in range(2))
    norm = jax.jit(masked_batch_norm)
    alone = norm(rows, jnp.ones(19, bool), scale, bias)
    junk = jnp.asarray(1e3 * rng.standard_normal((more, 16)), jnp.float32)
    padded = norm(jnp.concatenate([rows, junk]),
                  jnp.arange(19 + more) < 19, scale, bias)
    assert np.asarray(padded[:19]).tobytes() == np.asarray(alone).tobytes()
    assert not np.asarray(padded[19:]).any()
    # and it is BatchNorm1d's training mode: mean 0, biased variance 1
    plain = norm(rows, jnp.ones(19, bool), jnp.ones(16), jnp.zeros(16))
    np.testing.assert_allclose(plain.mean(axis=0), 0, atol=1e-6)
    np.testing.assert_allclose((plain ** 2).mean(axis=0), 1, atol=1e-4)
