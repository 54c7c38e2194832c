"""The per-relation walk (``ops.sample_multihop.sample_typed_multihop``,
``typed_hop``; ``parallel.frontier.walk_frontier`` over an
``ops.RelationIndices`` graph): every relation of a typed graph keeps its
own neighbour lists and draws its own fanout, and each node type keeps a
frontier of its own.

(a) every pick of relation ``r`` is an ``r``-neighbour of its target;
(b) a target holds ``min(degree in r, fanout)`` distinct picks of ``r``,
    over fanouts 1, 3, degree - 1, degree and past it, and on a hub row;
(c) a relation whose target type has no frontier node draws nothing;
(d) ``type_cap_drops`` is 0 under adequate caps and counts exactly the
    picks past a planted small cap;
(e) hop ``l`` draws relation ``r`` from ``fold_in(fold_in(key, l), r)``
    with the exact draw, so a replay gives the same sample;
(f) the blocks the model reads state their relation (a static int), their
    fanout and their valid targets, and every type's rows are the table's;
(g) an untyped walk and a merged typed walk are the composition of the
    draw and the compaction they were before the per-relation walk.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from quiver_tpu import metrics
from quiver_tpu.metrics import Collector
from quiver_tpu.ops import (NodeTypes, RelationIndices, TypedIndices,
                            compact_layer, sample_layer, sample_multihop,
                            sample_typed_multihop)
from quiver_tpu.parallel.frontier import (ALL_KNOBS, Walk, layers_to_adjs,
                                          masked_feature_gather,
                                          walk_frontier)
from quiver_tpu.pyg.sage_sampler import RelationBlocks

PAPERS, AUTHORS, HUBS = 40, 400, 2
COUNTS = (PAPERS, AUTHORS, HUBS)
STARTS = (0, PAPERS, PAPERS + AUTHORS)
# (target, source): paper<-paper, paper<-author, author<-paper,
# hub<-author, paper<-hub
PAIRS = ((0, 0), (0, 1), (1, 0), (2, 1), (0, 2))
TYPES = NodeTypes(starts=STARTS[1:], relations=PAIRS)
DEGREE = 6          # every paper cites exactly six papers
HUB_DEGREE = 300    # a hub row
FANOUTS = [1, 3, DEGREE - 1, DEGREE, DEGREE + 3]
BATCH = 8


@pytest.fixture(scope="module")
def graph():
    """Per relation a CSR over the target type's local ids, each row's
    neighbours distinct: papers cite six papers, papers have four authors
    (paper 0 none), authors write two papers, a hub holds 300 authors,
    a paper has one hub."""
    rng = np.random.default_rng(0)
    degree = {0: lambda i: DEGREE, 1: lambda i: 0 if i == 0 else 4,
              2: lambda i: 2, 3: lambda i: HUB_DEGREE, 4: lambda i: 1}
    indptrs, indices, rows = [], [], []
    for r, (t, s) in enumerate(PAIRS):
        lists = [rng.choice(COUNTS[s], degree[r](i), replace=False)
                 for i in range(COUNTS[t])]
        rows.append(lists)
        indptrs.append(jnp.asarray(np.concatenate(
            [[0], np.cumsum([len(x) for x in lists])]), jnp.int32))
        indices.append(jnp.asarray(np.concatenate(lists), jnp.int32))
    feat = jnp.asarray(rng.standard_normal((sum(COUNTS), 5)), jnp.float32)
    return tuple(indptrs), tuple(indices), rows, feat


@functools.lru_cache(maxsize=None)
def _jitted(sizes: tuple, counted: bool):
    """The walk, jitted once for each fanout ladder."""

    @jax.jit
    def walk(indptrs, typed, seeds, key):
        col = Collector() if counted else None
        out = sample_typed_multihop(indptrs, typed, seeds, list(sizes), key,
                                    collector=col)
        return out + ((col.counters(),) if counted else ())

    return walk


def _walk(graph, sizes, caps=None, key=7, counted=False):
    """The walk; with ``counted`` also its counters by name."""
    indptrs, indices, _, _ = graph
    out = _jitted(tuple(sizes), counted)(
        indptrs, RelationIndices(indices, TYPES, caps),
        jnp.arange(BATCH, dtype=jnp.int32) * 3, jax.random.key(key))
    if counted:
        return out[:2] + (metrics.counters_dict(np.asarray(out[2])[None]),)
    return out


def _picks(layer, r, before):
    """``(targets, picks)`` of relation ``r`` at a hop, in local ids:
    ``[targets]`` the target type's frontier before the hop, ``[targets,
    k]`` the picks (-1 fill)."""
    t, s = PAIRS[r]
    col = np.asarray(layer.col[r])
    k = col.shape[0] // before[t].shape[0]
    src = np.asarray(layer.frontier[s])
    picks = np.where(col >= 0, src[np.clip(col, 0, None)], -1)
    return before[t], picks.reshape(-1, k)


def _hops(graph, sizes):
    _, layers = _walk(graph, sizes)
    before = [None] * len(COUNTS)
    before[0] = np.arange(BATCH) * 3
    for layer in layers:
        yield layer, before
        before = [None if f is None else np.asarray(f)
                  for f in layer.frontier]


@pytest.mark.parametrize("fanout", FANOUTS)
def test_every_pick_is_a_neighbour_of_its_relation(graph, fanout):
    rows = graph[2]
    seen = 0
    for layer, before in _hops(graph, [fanout, fanout]):
        for r in range(len(PAIRS)):
            if layer.col[r] is None:
                continue
            targets, picks = _picks(layer, r, before)
            for target, mine in zip(targets, picks):
                for p in mine[mine >= 0]:
                    assert target >= 0 and p in rows[r][target], (r, target)
                    seen += 1
    assert seen


@pytest.mark.parametrize("fanout", FANOUTS)
def test_a_target_holds_min_degree_fanout_distinct_picks(graph, fanout):
    rows = graph[2]
    hub = False
    for layer, before in _hops(graph, [fanout, fanout]):
        for r in range(len(PAIRS)):
            if layer.col[r] is None:
                continue
            targets, picks = _picks(layer, r, before)
            for target, mine in zip(targets, picks):
                got = mine[mine >= 0]
                if target < 0:
                    assert got.size == 0
                    continue
                want = min(len(rows[r][target]), fanout)
                assert got.size == want == np.unique(got).size, (r, target)
                hub |= len(rows[r][target]) == HUB_DEGREE
    assert hub                              # a hub row was drawn from


@pytest.mark.parametrize("hop", [0, 1])
def test_a_relation_without_targets_draws_nothing(graph, hop):
    """At hop 0 only the batch's papers are targets: the relations into
    authors and hubs draw nothing, and hubs and authors have no frontier
    before that hop's picks; at hop 1 every relation draws."""
    _, layers = _walk(graph, [3, 2])
    layer = layers[hop]
    drawn = [c is not None for c in layer.col]
    if hop == 0:
        assert drawn == [True, True, False, False, True]
        assert all(layer.edge_count[r] is None for r in (2, 3))
    else:
        assert all(drawn)
    assert all(f is not None for f in layer.frontier)


@pytest.mark.parametrize("case", ["adequate", "papers_small",
                                  "authors_small"])
def test_type_cap_drops_count_exactly_the_picks_past_a_cap(graph, case):
    """Caps at the last hop: under caps that hold every union nothing is
    dropped; under a planted small cap the count is the picks whose place
    in the uncapped union lies past it, and those slots come back -1."""
    sizes = [3, 3]
    _, free = _walk(graph, sizes)
    natural = [int(f.shape[0]) for f in free[-1].frontier]
    caps = [[None, n] for n in natural]
    cut = {"adequate": None, "papers_small": 0, "authors_small": 1}[case]
    if cut is not None:
        caps[cut][1] = int(free[-1].count[cut]) // 2
    _, capped, counters = _walk(graph, sizes, caps, counted=True)
    drops = counters["type_cap_drops"]
    want = 0
    for r, (_, s) in enumerate(PAIRS):
        if free[-1].col[r] is None:
            continue
        full = np.asarray(free[-1].col[r])
        got = np.asarray(capped[-1].col[r])
        past = full >= caps[s][1]
        want += int(past.sum())
        np.testing.assert_array_equal(got, np.where(past, -1, full))
    assert drops == int(capped[-1].drops) == want
    assert (want > 0) == (cut is not None)


def test_a_replay_draws_each_relation_from_its_own_fold(graph):
    """Hop 0's picks of relation ``r`` are ``sample_layer``'s over that
    relation's lists from ``fold_in(fold_in(key, 0), r)``; the walk asked
    again under the same key gives the same sample, leaf for leaf."""
    indptrs, indices, _, _ = graph
    key = jax.random.key(7)
    first = _walk(graph, [3, 2])
    layers = first[1]
    seeds = jnp.arange(BATCH, dtype=jnp.int32) * 3
    for r, (t, s) in enumerate(PAIRS):
        if layers[0].col[r] is None:
            continue
        picks, _ = sample_layer(indptrs[r], indices[r], seeds, 3,
                                jax.random.fold_in(jax.random.fold_in(key, 0),
                                                   r))
        _, mine = _picks(layers[0], r, [np.asarray(seeds)] * 3)
        np.testing.assert_array_equal(mine, np.asarray(picks))
    again = _walk(graph, [3, 2])
    for a, b in zip(jax.tree.leaves(first), jax.tree.leaves(again)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_the_blocks_state_their_relation_fanout_and_targets(graph):
    """Through ``walk_frontier``: the rows of every type are the table's
    rows of its global ids, and ``layers_to_adjs`` gives one
    ``RelationBlocks`` a hop, outermost first, whose ``Adj``s state a
    static relation, the hop's fanout and the target type's count."""
    indptrs, indices, _, feat = graph
    sizes = [3, 2]
    walk = Walk.of("test", ALL_KNOBS, sizes, {})
    typed = RelationIndices(indices, TYPES)
    seeds = jnp.arange(BATCH, dtype=jnp.int32) * 3
    n_id, x, layers = walk_frontier(walk, feat, None, indptrs, typed, seeds,
                                    jax.random.key(7))
    last = layers[-1].frontier
    assert n_id.shape[0] == sum(f.shape[0] for f in last)
    for t, (f, rows) in enumerate(zip(last, x)):
        ids = jnp.where(f >= 0, f + (0, *STARTS[1:])[t], -1)
        np.testing.assert_array_equal(rows, masked_feature_gather(feat, ids))
    blocks = layers_to_adjs(layers, BATCH, sizes)
    assert [type(b) for b in blocks] == [RelationBlocks] * 2
    for b, layer, k in zip(blocks, layers[::-1], sizes[::-1]):
        drawn = [r for r, c in enumerate(layer.col) if c is not None]
        assert [a.relation for a in b.adjs] == drawn
        for a in b.adjs:
            t, s = PAIRS[a.relation]
            assert isinstance(a.relation, int) and a.fanout == k
            assert a.size == (b.sources[s], b.targets[t])
            assert a.edge_index.shape == (2, b.targets[t] * k)
            assert int(a.valid_targets) == int(layer.seed_count[a.relation])
        # static: a jit boundary keeps the relation an int
        again = jax.jit(lambda b: b)(b)
        assert [a.relation for a in again.adjs] == drawn
    assert blocks[-1].targets == (BATCH, 0, 0)


def _parents_walk(indptr, indices, seeds, sizes, key, types=None):
    """The walk as the draw and the compaction compose it: hop ``i`` from
    ``fold_in(key, i)``, ``sample_layer`` then ``compact_layer`` over
    dense seeds, the relation of a slot read off its two ids."""
    cur, layers = seeds, []
    for i, k in enumerate(sizes):
        nbrs, _ = sample_layer(indptr, indices, cur, k,
                               jax.random.fold_in(key, i))
        layer = compact_layer(cur, nbrs, seeds_dense=True)
        if types is not None:
            layer = layer._replace(rel=types.relation(cur[:, None], nbrs)
                                   .reshape(-1), types=types)
        layers.append(layer)
        cur = layer.n_id
    return cur, layers


@pytest.mark.parametrize("kind", ["untyped", "merged_typed"])
def test_the_other_walks_are_the_composition_they_were(kind):
    rng = np.random.default_rng(1)
    n = 120
    deg = rng.integers(0, 9, n)
    indptr = jnp.asarray(np.concatenate([[0], np.cumsum(deg)]), jnp.int32)
    indices = jnp.asarray(rng.integers(0, n, int(deg.sum())), jnp.int32)
    types = NodeTypes(starts=(60, 110), relations=((0, 0), (1, 0), (0, 1),
                                                   (1, 2), (2, 1))) \
        if kind == "merged_typed" else None
    feat = jnp.asarray(rng.standard_normal((n, 4)), jnp.float32)
    seeds = jnp.concatenate([jnp.arange(10, dtype=jnp.int32),
                             jnp.full((2,), -1, jnp.int32)])
    key = jax.random.key(3)
    sizes = [4, 3]
    walk = Walk.of("test", ALL_KNOBS, sizes, {})
    topo = indices if types is None else TypedIndices(indices, types)
    n_id, x, layers = walk_frontier(walk, feat, None, indptr, topo, seeds,
                                    key)
    want_id, want = _parents_walk(indptr, indices, seeds, sizes, key, types)
    np.testing.assert_array_equal(n_id, want_id)
    np.testing.assert_array_equal(x, masked_feature_gather(feat, want_id))
    for got, ref in zip(layers, want):
        assert got.types == ref.types
        for a, b in zip(got[:-1], ref[:-1]):
            if a is None:
                assert b is None
            else:
                np.testing.assert_array_equal(a, b)
    # and the step's other entry, the sampler itself, agrees
    again, _ = sample_multihop(indptr, indices, seeds, sizes, key,
                               seeds_dense=True, node_types=types)
    np.testing.assert_array_equal(again, want_id)
