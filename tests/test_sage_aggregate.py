"""``models.sage.masked_mean_aggregate``: the dense reduce over the fanout
axis against the ``segment_sum`` form it stands in for.

The contracts:

a. on ``layers_to_adjs`` output the dense path gives the scatter path's
   means (1e-6) and gradients (1e-5): only the order of a mean's addends
   may differ;
b. what ``Adj.fanout`` asserts holds for every such layer: a slot's
   target is ``e // fanout`` or nothing;
c. an ``Adj`` that states no fanout (``GraphSageSampler``'s, the hetero
   sampler's) takes the scatter path and gives the numbers it gave
   before there was a choice, bit for bit;
d. the field is static: it crosses ``jit`` and a flatten / unflatten;
e. the forward of ``GraphSAGE.apply`` on ``layers_to_adjs`` output holds
   no scatter.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import quiver_tpu as qv
from quiver_tpu import profiling
from quiver_tpu.hetero import HeteroCSRTopo, HeteroGraphSageSampler
from quiver_tpu.models import GraphSAGE
from quiver_tpu.models.sage import masked_mean_aggregate
from quiver_tpu.ops.sample_multihop import sample_multihop
from quiver_tpu.parallel.train import layers_to_adjs, masked_feature_gather
from quiver_tpu.pyg.sage_sampler import Adj

N, DIM, BATCH, FILL = 300, 12, 16, 5
FANOUTS = [[15, 10, 5], [3]]


def _segment_mean(x_src, edge_index, num_targets):
    """The aggregation as it stood before ``fanout``: the oracle of (c),
    kept to the letter."""
    src, dst = edge_index[0], edge_index[1]
    valid = (src >= 0) & (dst >= 0)
    s = jnp.where(valid, src, 0)
    d = jnp.where(valid, dst, 0)
    msg = x_src[s] * valid[:, None].astype(x_src.dtype)
    agg = jax.ops.segment_sum(msg, d, num_segments=num_targets)
    cnt = jax.ops.segment_sum(valid.astype(x_src.dtype), d,
                              num_segments=num_targets)
    return agg / jnp.maximum(cnt, 1.0)[:, None]


def _without_fanout(adjs):
    return [Adj(a.edge_index, a.e_id, a.size, a.mask) for a in adjs]


@pytest.fixture(scope="module")
def graph():
    """Degree-0 rows, rows shorter than any fanout, rows far longer."""
    rng = np.random.default_rng(3)
    deg = rng.choice([0, 1, 2, 4, 9, 40], N, p=[.15, .2, .2, .2, .15, .1])
    indptr = np.concatenate([[0], np.cumsum(deg)])
    indices = rng.integers(0, N, int(deg.sum()))
    return {"deg": deg, "indptr": jnp.asarray(indptr, jnp.int32),
            "indices": jnp.asarray(indices, jnp.int32),
            "feat": jnp.asarray(rng.normal(size=(N, DIM)), jnp.float32)}


def _sample(graph, sizes, seed=0):
    """A batch with a -1 tail, through the sampler the step builders use."""
    rng = np.random.default_rng(seed)
    batch = np.full(BATCH, -1, np.int32)
    batch[:BATCH - FILL] = rng.choice(N, BATCH - FILL, replace=False)
    n_id, layers = sample_multihop(
        graph["indptr"], graph["indices"], jnp.asarray(batch), sizes,
        jax.random.key(seed), seeds_dense=True)
    return (masked_feature_gather(graph["feat"], n_id),
            layers_to_adjs(layers, BATCH, sizes))


@pytest.mark.parametrize("sizes", FANOUTS, ids=str)
def test_the_graph_has_the_hard_rows(graph, sizes):
    # what (a) claims to cover is in the sample: targets with no
    # neighbour, targets with fewer than k, and padded targets
    _, adjs = _sample(graph, sizes)
    for adj in adjs:
        k = adj.fanout
        picks = np.asarray(adj.edge_index[0] >= 0).reshape(-1, k).sum(1)
        assert (picks == 0).any() and (picks == k).any()
        assert ((picks > 0) & (picks < k)).any()
    assert (graph["deg"] == 0).any()


@pytest.mark.parametrize("sizes", FANOUTS, ids=str)
def test_dense_means_are_the_scatter_means(graph, sizes):
    x, adjs = _sample(graph, sizes)
    for adj in adjs:
        src = jax.random.normal(jax.random.key(1), (adj.size[0], DIM))
        dense = masked_mean_aggregate(src, adj.edge_index, adj.size[1],
                                      adj.fanout)
        scatter = masked_mean_aggregate(src, adj.edge_index, adj.size[1])
        assert dense.shape == scatter.shape == (adj.size[1], DIM)
        np.testing.assert_allclose(dense, scatter, rtol=0, atol=1e-6)


@pytest.mark.parametrize("sizes", FANOUTS, ids=str)
def test_dense_gradients_are_the_scatter_gradients(graph, sizes):
    x, adjs = _sample(graph, sizes)
    model = GraphSAGE(hidden_dim=8, out_dim=4, num_layers=len(sizes),
                      dropout=0.0)
    params = model.init(jax.random.key(2), x, adjs)

    def loss(p, x, adjs):
        return jnp.square(model.apply(p, x, adjs)[:BATCH]).mean()

    grad = jax.grad(loss, argnums=(0, 1))
    gp_d, gx_d = grad(params, x, adjs)
    gp_s, gx_s = grad(params, x, _without_fanout(adjs))
    np.testing.assert_allclose(gx_d, gx_s, rtol=0, atol=1e-5)
    assert float(jnp.abs(gx_s).max()) > 1e-4        # not 0 == 0
    for d, s in zip(jax.tree.leaves(gp_d), jax.tree.leaves(gp_s)):
        np.testing.assert_allclose(d, s, rtol=0, atol=1e-5)


@pytest.mark.parametrize("sizes", FANOUTS, ids=str)
@pytest.mark.parametrize("dense0", [True, False])
def test_a_slot_targets_its_quotient_or_nothing(graph, sizes, dense0):
    # hop 0 keeps the property with or without the sort's shortcut, as
    # long as the batch is valid-first; hops >= 1 always are
    batch = jnp.asarray(np.r_[np.arange(BATCH - FILL), [-1] * FILL],
                        jnp.int32)
    _, layers = sample_multihop(graph["indptr"], graph["indices"], batch,
                                sizes, jax.random.key(5),
                                seeds_dense=dense0)
    adjs = layers_to_adjs(layers, BATCH, sizes)
    assert [a.fanout for a in adjs] == sizes[::-1]
    for adj in adjs:
        src, dst = np.asarray(adj.edge_index)
        assert src.shape[0] == adj.size[1] * adj.fanout
        slot_target = np.arange(src.shape[0]) // adj.fanout
        assert ((dst == -1) | (dst == slot_target)).all()
        assert ((dst == -1) == (src == -1)).all()


def _sampler_adjs(graph, mode):
    topo = qv.CSRTopo(indptr=np.asarray(graph["indptr"]),
                      indices=np.asarray(graph["indices"]))
    sampler = qv.pyg.GraphSageSampler(topo, [4, 3], mode=mode)
    n_id, _, adjs = sampler.sample(np.arange(BATCH))
    return masked_feature_gather(graph["feat"], n_id), adjs


def _hetero_adjs(graph):
    topo = qv.CSRTopo(indptr=np.asarray(graph["indptr"]),
                      indices=np.asarray(graph["indices"]))
    sampler = HeteroGraphSageSampler(
        HeteroCSRTopo({("node", "to", "node"): topo}, {"node": N}),
        sizes=[4], seed_type="node")
    frontier, _, layers = sampler.sample(np.arange(BATCH))
    return (masked_feature_gather(graph["feat"], frontier["node"]),
            list(layers[0].adjs.values()))


@pytest.mark.parametrize("source", ["HBM", "CPU", "hetero"])
def test_no_fanout_is_the_scatter_path_bit_for_bit(graph, source):
    src, adjs = (_hetero_adjs(graph) if source == "hetero"
                 else _sampler_adjs(graph, source))
    assert adjs and all(a.fanout is None for a in adjs)
    adj = adjs[0]                      # the outermost hop: src is its x
    assert src.shape[0] == adj.size[0]
    got = masked_mean_aggregate(src, adj.edge_index, adj.size[1])
    want = _segment_mean(src, adj.edge_index, adj.size[1])
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert float(jnp.abs(want).max()) > 0
    text = str(jax.make_jaxpr(masked_mean_aggregate, static_argnums=2)(
        src, adj.edge_index, adj.size[1]))
    assert text.count("scatter-add") == 2


@pytest.mark.parametrize("fanout", [None, 3])
def test_fanout_is_static_pytree_data(fanout):
    ei = jnp.asarray([[0, 1, -1, 2, 0, -1], [0, 0, -1, 1, 1, -1]], jnp.int32)
    adj = Adj(ei, None, (3, 2), fanout=fanout)
    leaves, treedef = jax.tree_util.tree_flatten(adj)
    back = jax.tree_util.tree_unflatten(treedef, leaves)
    assert back.fanout == fanout and back.size == (3, 2)
    assert all(not isinstance(l, int) for l in leaves)
    # PyG-style destructuring is what it was
    edge_index, e_id, size = back
    assert size == (3, 2) and e_id is None
    seen = []

    @jax.jit
    def f(a):
        seen.append((a.fanout, a.size))      # Python values while tracing
        return jax.tree.map(lambda v: v, a)

    out = f(adj)
    assert seen == [(fanout, (3, 2))]
    assert out.fanout == fanout and out.size == (3, 2)
    np.testing.assert_array_equal(out.edge_index, ei)
    # another fanout is another program, not a silent reuse
    f(Adj(ei, None, (3, 2), fanout=6))
    assert len(seen) == 2


@pytest.mark.parametrize("sizes", FANOUTS, ids=str)
def test_forward_on_layers_to_adjs_holds_no_scatter(graph, sizes):
    x, adjs = _sample(graph, sizes)
    model = GraphSAGE(hidden_dim=8, out_dim=4, num_layers=len(sizes),
                      dropout=0.0)
    params = model.init(jax.random.key(2), x, adjs)
    dense = str(jax.make_jaxpr(model.apply)(params, x, adjs))
    assert "scatter" not in dense
    general = str(jax.make_jaxpr(model.apply)(params, x,
                                              _without_fanout(adjs)))
    assert general.count("scatter-add") == 2 * len(sizes)
    # and the compiled program says which path ran, by name
    names = jax.jit(model.apply).lower(params, x, adjs).compile().as_text()
    under = [l for l in names.splitlines() if profiling.QT_AGGREGATE in l]
    assert under and all(
        f"{profiling.QT_AGGREGATE}/{profiling.QT_AGGREGATE_DENSE}" in l
        for l in under)
