"""``models.live_rows.live_rows_product`` and the attention layers that
project through it (``models/gat.py``, ``models/mag.py``):

(a) the product over its live rows is ``x @ w`` on the rows of the
    blocks up to ``ceil(live / block)`` and zero past them, forward,
    ``dW`` and ``dx``, for every kind of count over a ragged ``N``; rows
    past those blocks are never read (planted NaN stays out); a grouped
    product picks each block's weight; rows that want no gradient get no
    ``[N, in]`` cotangent;
(b) ``GATConv`` in its slot, segment and typed forms and
    ``MAG240MGNN(relations=1)`` / ``(relations=5)`` over blocks with
    padding give the loss and every gradient of the dense product to
    float32 rounding;
(c) a step that collects metrics counts the rows its products
    multiplied (``project_rows``) and the rows their shapes hold
    (``project_rows_cap``): no more than the cap, all of it over a block
    with no padding, nothing where no attention layer runs.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from quiver_tpu import metrics
from quiver_tpu.models import MAG240MGNN, GraphSAGE, gat
from quiver_tpu.models.gat import GATConv
from quiver_tpu.models.live_rows import live_rows_product
from quiver_tpu.ops import NodeTypes, sample_multihop
from quiver_tpu.parallel.train import (build_train_step, init_state,
                                       layers_to_adjs, masked_feature_gather)
from quiver_tpu.pyg.sage_sampler import Adj, layer_shapes

ROWS, FAN_IN, FAN_OUT, BLOCK = 37, 5, 3, 8      # 37 rows: a ragged last block
N, DIM, SIZES, BATCH = 200, 16, [3, 2], 16
TYPES = NodeTypes(starts=(N // 2, N - 20),
                  relations=((0, 0), (1, 0), (0, 1), (1, 2), (2, 1)))


@pytest.fixture(autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


def _operands(seed=0):
    rng = np.random.default_rng(seed)
    return tuple(jnp.asarray(rng.standard_normal(shape), jnp.float32)
                 for shape in ((ROWS, FAN_IN), (FAN_IN, FAN_OUT),
                               (ROWS, FAN_OUT)))


@pytest.mark.parametrize("live,nan", [
    (0, False), (1, False), (BLOCK - 1, False), (BLOCK, False),
    (13, False), (ROWS, False), (13, True)],
    ids=["none", "one", "block-1", "block", "ragged", "all", "nan-past"])
def test_the_product_over_the_live_rows(live, nan):
    """Forward, ``dW`` and ``dx`` against ``x @ w`` on the rows of the
    live blocks and zero past them; with NaN planted past those blocks
    everything stays finite (the dense product's ``dW`` would not)."""
    x, w, g = _operands()
    rows = min(-(-live // BLOCK) * BLOCK, ROWS)
    if nan:
        x = x.at[rows:].set(jnp.nan)
    kept = (jnp.arange(ROWS) < rows)[:, None]

    def loss(x, w):
        (y,), used = live_rows_product(x, (w,), live, BLOCK)
        return (y * g).sum(), (y, used)

    (_, (y, used)), (dx, dw) = jax.value_and_grad(
        loss, argnums=(0, 1), has_aux=True)(x, w)
    xs = jnp.where(kept, x, 0.0)
    want_y = jnp.where(kept, xs @ w, 0.0)
    want_dx, want_dw = jax.grad(
        lambda x, w: (jnp.where(kept, x @ w, 0.0) * g).sum(),
        argnums=(0, 1))(xs, w)
    assert int(used) == rows
    for got, want in ((y, want_y), (dx, want_dx), (dw, want_dw)):
        assert np.isfinite(np.asarray(got)).all()
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-5)
    assert not np.asarray(y[rows:]).any() and not np.asarray(dx[rows:]).any()
    if nan:
        dense = jax.grad(lambda w: ((x @ w) * kept * g).sum())(w)
        assert not np.isfinite(np.asarray(dense)).all()


def test_a_grouped_product_picks_each_blocks_weight():
    """Two weights over the same rows, block ``b`` by ``w[group[b]]``:
    the batched product over the first three of four blocks, forward and
    every gradient."""
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((4 * BLOCK, FAN_IN)), jnp.float32)
    w, v = (jnp.asarray(rng.standard_normal((3, FAN_IN, o)), jnp.float32)
            for o in (FAN_OUT, 2))
    group = jnp.asarray([2, 0, 1, 1], jnp.int32)
    kept = (jnp.arange(4 * BLOCK) < 3 * BLOCK)[:, None]
    g = jnp.asarray(rng.standard_normal((4 * BLOCK, FAN_OUT)), jnp.float32)

    def ours(x, w, v):
        (p, q), used = live_rows_product(x, (w, v), 3 * BLOCK, BLOCK, group)
        assert int(used) == 3 * BLOCK
        return (p * g).sum() + (q ** 2).sum()

    def plain(x, w, v):
        tiles = x.reshape(4, BLOCK, FAN_IN)
        p, q = (jnp.einsum("tif,tfo->tio", tiles, m[group]).reshape(
            4 * BLOCK, -1) * kept for m in (w, v))
        return (p * g).sum() + (q ** 2).sum()

    np.testing.assert_allclose(ours(x, w, v), plain(x, w, v), rtol=1e-6)
    for got, want in zip(jax.grad(ours, argnums=(0, 1, 2))(x, w, v),
                         jax.grad(plain, argnums=(0, 1, 2))(x, w, v)):
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    with pytest.raises(ValueError, match="whole blocks"):
        live_rows_product(x[:-1], (w,), BLOCK, BLOCK, group)


def _shapes_made(jaxpr):
    """Every shape an equation of ``jaxpr`` (and of the jaxprs inside
    it) makes."""
    out = []
    for eqn in jaxpr.eqns:
        out += [tuple(v.aval.shape) for v in eqn.outvars
                if hasattr(v.aval, "shape")]
        for p in eqn.params.values():
            for sub in p if isinstance(p, (tuple, list)) else (p,):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    out += _shapes_made(inner)
    return out


def test_rows_that_want_no_gradient_get_no_cotangent():
    """Differentiated in ``w`` alone, nothing of ``x``'s shape is made;
    differentiated in ``x`` too, ``dx`` is."""
    x, w, g = _operands()
    loss = lambda x, w: (live_rows_product(x, (w,), 20, BLOCK)[0][0]
                         * g).sum()
    alone = jax.make_jaxpr(jax.grad(loss, argnums=1))(x, w)
    both = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(x, w)
    assert x.shape not in _shapes_made(alone.jaxpr)
    assert x.shape in _shapes_made(both.jaxpr)


def _dense(x, ws, live, block, group=None):
    """The unblocked form: every row multiplied."""
    if group is None:
        ys = tuple(x @ m for m in ws)
    else:
        tiles = x.reshape(group.shape[0], -1, x.shape[-1])
        ys = tuple(jnp.einsum("tif,tfo->tio", tiles, m[group]).reshape(
            x.shape[0], -1) for m in ws)
    return ys, jnp.int32(x.shape[0])


@pytest.fixture(scope="module")
def world():
    """A graph with isolated nodes and degrees under the fanout, a float
    table, a batch with an empty tail; sampled untyped and typed."""
    rng = np.random.default_rng(0)
    deg = rng.integers(0, 6, N)
    deg[:4] = 0
    indptr = jnp.asarray(np.concatenate([[0], np.cumsum(deg)]), jnp.int32)
    indices = jnp.asarray(rng.integers(0, N, int(deg.sum())), jnp.int32)
    feat = jnp.asarray(rng.standard_normal((N, DIM)), jnp.float32)
    seeds = jnp.asarray(np.concatenate(
        [rng.permutation(N // 2)[:BATCH - 4], -np.ones(4)]), jnp.int32)
    key = jax.random.key(3)
    out = {"indptr": indptr, "indices": indices, "feat": feat,
           "seeds": seeds, "key": key}
    for name, kw in (("untyped", {}), ("typed", {"node_types": TYPES})):
        n_id, layers = sample_multihop(indptr, indices, seeds, SIZES, key,
                                       seeds_dense=True, **kw)
        out[name] = (masked_feature_gather(feat, n_id),
                     layers_to_adjs(layers, BATCH, SIZES))
    return out


def _loose(adjs):
    """The same blocks without their slot layout: the segment form."""
    return [Adj(a.edge_index, a.e_id, a.size, a.mask, None, a.valid_targets,
                a.relation, a.relations) for a in adjs]


def _against_dense(monkeypatch, loss, args):
    got = jax.value_and_grad(loss)(*args)
    monkeypatch.setattr(gat, "live_rows_product", _dense)
    want = jax.value_and_grad(loss)(*args)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-6)
    leaves = jax.tree.leaves(got[1])
    assert leaves and any(np.asarray(a).any() for a in leaves)
    for a, b in zip(leaves, jax.tree.leaves(want[1])):
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=1e-6)


@pytest.mark.parametrize("form", ["slots", "segments", "typed-slots",
                                  "typed-segments"])
def test_a_layer_is_its_dense_form(world, monkeypatch, form):
    """One ``GATConv`` over the outer hop's block, which holds padding:
    its loss and every gradient are the dense product's."""
    x, adjs = world["typed" if form.startswith("typed") else "untyped"]
    adj = adjs[0] if form.endswith("slots") else _loose(adjs)[0]
    assert int(gat.live_sources(adj)) < x.shape[0]      # padding to skip
    conv = GATConv(4, heads=2, relations=5 if form.startswith("typed") else 1)
    params = conv.init(jax.random.key(0), x, adj)
    weigh = jnp.asarray(np.random.default_rng(2).standard_normal(
        (adj.size[1], 8)), jnp.float32)
    _against_dense(monkeypatch,
                   lambda p: (conv.apply(p, x, adj) * weigh).sum(), (params,))


@pytest.mark.parametrize("relations", [1, 5])
def test_the_model_is_its_dense_form(world, monkeypatch, relations):
    """``MAG240MGNN`` over both hops, skips, norms and head: the loss and
    every gradient are the dense products'."""
    x, adjs = world["typed" if relations > 1 else "untyped"]
    model = MAG240MGNN(model="gat", hidden_dim=8, out_dim=4,
                       num_layers=len(SIZES), heads=2, relations=relations)
    params = model.init(jax.random.key(1), x, adjs)
    labels = jnp.arange(BATCH) % 4

    def loss(p):
        logits = model.apply(p, x, adjs)[:BATCH]
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, labels).mean()

    _against_dense(monkeypatch, loss, (params,))


@pytest.mark.parametrize("model", ["gat", "rgat", "sage"])
def test_the_step_counts_the_rows_it_multiplied(world, model):
    """Through ``build_train_step(collect_metrics=True)``: the attention
    models' products multiplied whole blocks, no more rows than their
    shapes hold and fewer over padded blocks; the one-relation model's
    shapes are the two hops' sources. SAGE runs no such product."""
    net = {"gat": MAG240MGNN(model="gat", hidden_dim=8, out_dim=4,
                             num_layers=len(SIZES), heads=2),
           "rgat": MAG240MGNN(model="gat", hidden_dim=8, out_dim=4,
                              num_layers=len(SIZES), heads=2, relations=5),
           "sage": GraphSAGE(hidden_dim=8, out_dim=4,
                             num_layers=len(SIZES))}[model]
    x, adjs = world["typed" if model == "rgat" else "untyped"]
    tx = optax.adam(1e-3)
    state = init_state(net, tx, x, adjs, jax.random.key(0))
    assert "counters" not in state.params
    step = build_train_step(net, tx, SIZES, BATCH, collect_metrics=True,
                            donate=False)
    indices = world["indices"]
    if model == "rgat":
        from quiver_tpu.ops import TypedIndices
        indices = TypedIndices(indices, TYPES)
    _, _, counters = step(state, world["feat"], None, world["indptr"],
                          indices, world["seeds"],
                          jnp.zeros((BATCH,), jnp.int32), world["key"])
    c = metrics.counters_dict(counters)
    rows, cap = c["project_rows"], c["project_rows_cap"]
    if model == "sage":
        assert rows == cap == 0
        return
    assert 0 < rows < cap
    if model == "gat":
        assert cap == sum(s.n_id_cap for s in layer_shapes(BATCH, SIZES))


def test_a_block_with_no_padding_runs_every_row():
    """Every source read, every target a node: the product ran all rows."""
    targets, k = 6, 3
    sources = targets * (k + 1)
    src = jnp.arange(targets * k, dtype=jnp.int32) + targets
    adj = Adj(jnp.stack([src, jnp.repeat(jnp.arange(targets), k)]), None,
              (sources, targets), fanout=k)
    x = jnp.asarray(np.random.default_rng(3).standard_normal((sources, 5)),
                    jnp.float32)
    conv = GATConv(4, heads=2)
    params = conv.init(jax.random.key(0), x, adj)
    _, sown = conv.apply(params, x, adj, mutable=["counters"])
    c = {k: int(sum(v)) for k, v in sown["counters"].items()}
    assert c == {"project_rows": sources, "project_rows_cap": sources}
