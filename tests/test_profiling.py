"""``quiver_tpu.profiling``: the ``hot_path`` marker (the host-lint
contract) and the device scopes of the fused steps.

The scope contracts:

1. every scope of ``profiling.DEVICE_SCOPES`` reaches the ``op_name``s
   of the compiled train steps (the forward-only serve step has no
   loss, backward or optimizer); ``qt_draw``/``qt_compact`` sit beneath
   a ``qt_sample_hop<i>`` and nowhere else, and a hop's ``qt_draw`` holds
   three ``gather``s, the data's own (two reads of ``indptr``, one of
   ``indices``: the draw reads its write log by selects); the backward's
   ops read
   ``transpose(jvp(qt_forward))``; every builder's layers state their
   fanout, so all of ``qt_aggregate`` lies under ``qt_aggregate_dense``
   and the forward holds no scatter. The dist step has the exchange
   where the others have ``qt_gather``: every op of its lookup lies
   under ``qt_exchange``, each stage beneath it, and nothing under
   ``qt_gather``. A step over a spliced tiered ``Feature`` store has
   ``qt_lookup_hot`` / ``qt_lookup_cold`` beneath its ``qt_gather``.
   A step over the attention model (``MAG240MGNN(model="gat")``) has
   ``qt_project``, ``qt_attention`` and ``qt_norm`` beneath its
   ``qt_forward``, forward and backward, and no ``qt_aggregate``; every
   builder's layers state their fanout, so all of ``qt_attention`` lies
   under ``qt_attention_slots`` and holds no scatter in the forward
   pass; over an ``Adj`` that states no fanout the same model leaves no
   ``qt_attention_slots`` in its names. A hop's draw names its three
   parts beneath ``qt_draw`` (``profiling.DRAW_STAGES``): the two
   ``indptr`` gathers under ``qt_draw_rows``, the ``scan``'s loop under
   ``qt_draw_picks``, the ``indices`` gather under ``qt_draw_neighbors``.
   The relational model over a typed graph (``MAG240MGNN(relations=5)``)
   has ``qt_relation_route`` beside ``qt_project`` and ``qt_attention``,
   forward and backward, and the one-relation model has none.
   The tiered lookup names its bookkeeping (``profiling.LOOKUP_STAGES``)
   beside the tiers' reads, never inside them: the compaction's ``sort``
   under ``qt_lookup_compact``, the cold block's ``scatter`` under
   ``qt_lookup_merge``. The per-relation walk (a graph whose relations
   keep their own lists, ``RGAT``) names each relation's draw
   ``qt_draw_relation`` inside ``qt_draw`` and each type's union
   ``qt_type_union`` inside ``qt_compact``; its model's products are
   ``qt_project``'s.
2. the scopes are names and nothing else: with ``profiling.scope``
   swapped for a null context the lowered program is the same text.
"""

import collections
import contextlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh

from quiver_tpu import profiling
from quiver_tpu.models import RGAT, MAG240MGNN, GraphSAGE
from quiver_tpu.ops import NodeTypes, RelationIndices, TypedIndices
from quiver_tpu.ops.sample_multihop import sample_multihop
from quiver_tpu.parallel.frontier import ALL_KNOBS, Walk, walk_frontier
from quiver_tpu.parallel.train import (build_e2e_train_step, build_train_step,
                                       init_state, layers_to_adjs,
                                       masked_feature_gather)
from quiver_tpu.profiling import hot_path
from quiver_tpu.serving import build_serve_step

N, DIM, SIZES, BATCH = 400, 16, [3, 2], 8
LOOKUP_SCOPES = {profiling.QT_LOOKUP_HOT, profiling.QT_LOOKUP_COLD}
# the attention model's own, beneath ``qt_forward``
MODEL_SCOPES = {profiling.QT_PROJECT, profiling.QT_ATTENTION,
                profiling.QT_ATTENTION_SLOTS, profiling.QT_NORM,
                profiling.QT_RELATION_ROUTE}
TRAIN_SCOPES = set(profiling.DEVICE_SCOPES) - {profiling.QT_EXCHANGE} \
    - LOOKUP_SCOPES - MODEL_SCOPES
# the relational model over a typed graph routes its slots by relation;
# the one-relation model has no route
RGAT_SCOPES = (TRAIN_SCOPES - {profiling.QT_AGGREGATE,
                               profiling.QT_AGGREGATE_DENSE}) | MODEL_SCOPES
GAT_SCOPES = RGAT_SCOPES - {profiling.QT_RELATION_ROUTE}
# a step over a spliced tiered store has the two tiers' reads beneath its
# ``qt_gather``
TIERED_SCOPES = TRAIN_SCOPES | LOOKUP_SCOPES
DIST_SCOPES = (TRAIN_SCOPES - {profiling.QT_GATHER}) | {
    profiling.QT_EXCHANGE} | set(profiling.EXCHANGE_STAGES)
SERVE_SCOPES = {profiling.QT_DRAW, profiling.QT_COMPACT, profiling.QT_GATHER,
                profiling.QT_AGGREGATE, profiling.QT_AGGREGATE_DENSE}


class TestHotPath:
    def test_stamps_without_wrapping(self):
        def f(x):
            return x

        g = hot_path(f)
        assert g is f                      # NO wrapper: identity kept
        assert f.__qt_hot_path__ is True
        assert f(7) == 7

    def test_scope_is_jax_named_scope(self):
        assert profiling.scope is jax.named_scope


@pytest.fixture(scope="module")
def world():
    rng = np.random.default_rng(0)
    deg = rng.integers(1, 8, N)
    indptr = jnp.asarray(np.concatenate([[0], np.cumsum(deg)]), jnp.int32)
    indices = jnp.asarray(rng.integers(0, N, int(deg.sum())), jnp.int32)
    feat = jnp.asarray(rng.normal(size=(N, DIM)), jnp.float32)
    model = GraphSAGE(hidden_dim=8, out_dim=4, num_layers=len(SIZES))
    tx = optax.adam(1e-3)
    key = jax.random.key(0)
    seeds = jnp.arange(BATCH, dtype=jnp.int32)
    n_id, layers = sample_multihop(indptr, indices, seeds, SIZES, key,
                                   seeds_dense=True)
    state = init_state(model, tx, masked_feature_gather(feat, n_id),
                       layers_to_adjs(layers, BATCH, SIZES), key)
    gat = MAG240MGNN(model="gat", hidden_dim=8, out_dim=4,
                     num_layers=len(SIZES), heads=2)
    gat_state = init_state(gat, tx, masked_feature_gather(feat, n_id),
                           layers_to_adjs(layers, BATCH, SIZES), key)
    # the same graph typed: papers, authors, institutions as id ranges
    types = NodeTypes(starts=(N // 2, N - 20),
                      relations=((0, 0), (1, 0), (0, 1), (1, 2), (2, 1)))
    n_id, typed_layers = sample_multihop(indptr, indices, seeds, SIZES, key,
                                         seeds_dense=True, node_types=types)
    rgat = MAG240MGNN(model="gat", hidden_dim=8, out_dim=4,
                      num_layers=len(SIZES), heads=2, relations=5)
    rgat_state = init_state(rgat, tx, masked_feature_gather(feat, n_id),
                            layers_to_adjs(typed_layers, BATCH, SIZES), key)
    # and as a graph whose relations keep their own lists (each relation
    # a CSR over its target type's local ids), for the MLPerf R-GAT
    counts = (N // 2, N // 2 - 20, 20)
    per_relation = NodeTypes(starts=types.starts, relations=types.relations)
    ptrs, lists = [], []
    for t, src in per_relation.relations:
        d = rng.integers(0, 4, counts[t])
        ptrs.append(jnp.asarray(np.concatenate([[0], np.cumsum(d)]),
                                jnp.int32))
        lists.append(jnp.asarray(rng.integers(0, counts[src], int(d.sum())),
                                 jnp.int32))
    relational = RelationIndices(lists, per_relation)
    igbh = RGAT(hidden_dim=8, out_dim=4, num_layers=len(SIZES), heads=2)
    _, x, hops = walk_frontier(Walk.of("test", ALL_KNOBS, SIZES, {}), feat,
                               None, tuple(ptrs), relational, seeds, key)
    igbh_state = init_state(igbh, tx, x, layers_to_adjs(hops, BATCH, SIZES),
                            key)
    return {"model": model, "tx": tx, "state": state, "feat": feat,
            "indptr": indptr, "indices": indices, "key": key, "gat": gat,
            "gat_state": gat_state, "n_id": n_id, "layers": layers,
            "rgat": rgat, "rgat_state": rgat_state,
            "typed": TypedIndices(indices, types), "igbh": igbh,
            "igbh_state": igbh_state, "relation_ptrs": tuple(ptrs),
            "relational": relational}


def _lower(builder: str, w):
    """The lowered tiny step of one builder, built NOW (so that a patched
    ``profiling.scope`` is the one it traces with)."""
    graph = (w["feat"], None, w["indptr"], w["indices"])
    if builder == "train":
        fn = build_train_step(w["model"], w["tx"], SIZES, BATCH,
                              donate=False)
        return fn.lower(w["state"], *graph, jnp.arange(BATCH, dtype=jnp.int32),
                        jnp.zeros((BATCH,), jnp.int32), w["key"])
    if builder == "gat":
        fn = build_train_step(w["gat"], w["tx"], SIZES, BATCH, donate=False)
        return fn.lower(w["gat_state"], w["feat"].astype(jnp.float16),
                        *graph[1:], jnp.arange(BATCH, dtype=jnp.int32),
                        jnp.zeros((BATCH,), jnp.int32), w["key"])
    if builder == "rgat":
        fn = build_train_step(w["rgat"], w["tx"], SIZES, BATCH, donate=False)
        return fn.lower(w["rgat_state"], w["feat"].astype(jnp.float16), None,
                        w["indptr"], w["typed"],
                        jnp.arange(BATCH, dtype=jnp.int32),
                        jnp.zeros((BATCH,), jnp.int32), w["key"])
    if builder == "igbh":
        fn = build_train_step(w["igbh"], w["tx"], SIZES, BATCH, donate=False)
        return fn.lower(w["igbh_state"], w["feat"].astype(jnp.float16), None,
                        w["relation_ptrs"], w["relational"],
                        jnp.arange(BATCH, dtype=jnp.int32),
                        jnp.zeros((BATCH,), jnp.int32), w["key"])
    if builder == "tiered":
        import quiver_tpu as qv
        from quiver_tpu.parallel.frontier import feature_splice
        rows = np.asarray(w["feat"])
        store = qv.Feature(host_placement="offload", allow_fallback=False,
                           cold_budget=16).from_tiers(
            jnp.asarray(rows[:N // 2]), jax.device_put(
                rows[N // 2:], jax.sharding.SingleDeviceSharding(
                    jax.devices()[0], memory_kind="pinned_host")))
        feat, forder, gather = feature_splice(store)
        fn = build_train_step(w["model"], w["tx"], SIZES, BATCH,
                              gather=gather, donate=False)
        return fn.lower(w["state"], feat, forder, w["indptr"], w["indices"],
                        jnp.arange(BATCH, dtype=jnp.int32),
                        jnp.zeros((BATCH,), jnp.int32), w["key"])
    if builder == "e2e":
        mesh = Mesh(np.array(jax.devices()[:2]), ("data",))
        step = build_e2e_train_step(w["model"], w["tx"], SIZES, BATCH, mesh,
                                    donate=False)
        return step.jitted_fns[-1].lower(
            w["state"], *graph, jnp.arange(2 * BATCH, dtype=jnp.int32),
            jnp.zeros((2 * BATCH,), jnp.int32), w["key"])
    if builder == "dist":
        from quiver_tpu.parallel.dist import build_dist_train_step
        mesh = Mesh(np.array(jax.devices()[:2]), ("host",))
        step = build_dist_train_step(w["model"], w["tx"], SIZES, BATCH, mesh,
                                     rows_per_host=N // 2, exchange_cap=24,
                                     donate=False)
        book = jnp.arange(N, dtype=jnp.int32)
        return step.jitted_fns[-1].lower(
            w["state"], w["feat"], book % 2, book // 2, w["indptr"],
            w["indices"], jnp.arange(2 * BATCH, dtype=jnp.int32),
            jnp.zeros((2 * BATCH,), jnp.int32), w["key"])
    fn = build_serve_step(w["model"], SIZES, BATCH)
    return fn.lower(w["state"].params, w["key"], *graph,
                    jnp.arange(BATCH, dtype=jnp.int32))


def _op_names(lowered):
    return re.findall(r'op_name="([^"]*)"', lowered.compile().as_text())


@pytest.mark.parametrize("builder,scopes", [
    ("train", TRAIN_SCOPES), ("e2e", TRAIN_SCOPES), ("serve", SERVE_SCOPES),
    ("dist", DIST_SCOPES), ("tiered", TIERED_SCOPES), ("gat", GAT_SCOPES),
    ("rgat", RGAT_SCOPES)])
def test_scopes_reach_the_compiled_op_names(world, builder, scopes):
    names = _op_names(_lower(builder, world))
    for scope in scopes:
        assert any(scope in n for n in names), scope
    for n in names:
        # the attention model's scopes lie beneath the forward pass of
        # the step that runs it and nowhere else
        if any(s in n for s in MODEL_SCOPES):
            assert builder in ("gat", "rgat") and "qt_forward" in n, n
        # the softmax ran over the slot axis, all of it
        if profiling.QT_ATTENTION in n:
            assert "qt_attention/qt_attention_slots/" in n, n
        # a slot's way to its relation's weight and back is the route's,
        # beside the products and the attention, never inside them
        if profiling.QT_RELATION_ROUTE in n:
            assert builder == "rgat", n
            assert profiling.QT_PROJECT not in n, n
            assert profiling.QT_ATTENTION not in n, n
    if builder in ("gat", "rgat"):
        assert not any(profiling.QT_AGGREGATE in n for n in names)
        for scope in (profiling.QT_PROJECT, profiling.QT_ATTENTION,
                      profiling.QT_NORM) + (
                          (profiling.QT_RELATION_ROUTE,)
                          if builder == "rgat" else ()):
            assert any(scope in n and "transpose(" in n for n in names), scope
    # draw and compact are the two halves of a hop, never on their own
    for n in names:
        if profiling.QT_DRAW in n or profiling.QT_COMPACT in n:
            assert re.search(r"qt_sample_hop\d\)?/qt_(draw|compact)", n), n
        # the aggregation ran as the dense reduce, all of it
        if profiling.QT_AGGREGATE in n:
            assert "qt_aggregate/qt_aggregate_dense/" in n, n
    for n in names:
        # the tiers' reads lie beneath the store's gather and nowhere else
        if any(s in n for s in LOOKUP_SCOPES):
            assert builder == "tiered"
            assert re.search(r"qt_gather\)?/.*qt_lookup_(hot|cold)\)?/", n), n
    hops = {m.group(0) for n in names
            for m in [re.search(r"qt_sample_hop\d", n)] if m}
    assert hops == {f"qt_sample_hop{i}" for i in range(len(SIZES))}
    if builder == "serve":
        assert any("qt_serve_forward" in n for n in names)
        assert not any("transpose(" in n for n in names)
    else:
        back = [n for n in names if "transpose(jvp(qt_forward))" in n]
        fwd = [n for n in names if "jvp(qt_forward)" in n
               and "transpose(" not in n]
        assert back and fwd
        # the optimizer is outside value_and_grad: no jvp around it
        assert any(re.search(r"(^|/)qt_optimizer/", n) for n in names)


@pytest.mark.parametrize("builder", ["train", "e2e", "serve", "dist", "tiered",
                                     "gat", "rgat"])
def test_the_draws_parts_lie_beneath_the_draw(world, builder):
    """``DRAW_STAGES`` reach every builder's names, each only as
    ``qt_sample_hop<i>/qt_draw/qt_draw_<stage>/``."""
    names = _op_names(_lower(builder, world))
    for stage in profiling.DRAW_STAGES:
        held = [n for n in names if stage in n]
        assert held, stage
        for n in held:
            assert re.search(
                r"qt_sample_hop\d\)?/qt_draw\)?/" + stage + r"\)?/", n), n
            assert sum(n.count(s) for s in profiling.DRAW_STAGES) == 1, n


@pytest.mark.parametrize("builder", ["train", "serve"])
def test_a_hops_gathers_by_the_draws_part(world, builder):
    """Of a hop's three ``gather``s, the two reads of ``indptr`` lie under
    ``qt_draw_rows`` and the one of ``indices`` under
    ``qt_draw_neighbors``; ``qt_draw_picks`` holds none, and holds the
    ``scan``'s loop where the compiler keeps a ``while``."""
    text = _lower(builder, world).compile().as_text()
    gathers, loops = collections.Counter(), collections.Counter()
    for name, line in _names_and_ops(text):
        at = re.search(r"(qt_sample_hop\d)\)?/qt_draw\)?/(qt_draw_\w+?)\)?/",
                       name)
        if at and re.search(r"= \S+ gather\(", line):
            gathers[at.groups()] += 1
        # (threefry's own rounds are a loop too on the CPU backend)
        if re.search(r"qt_sample_hop\d\)?/qt_draw", name) \
                and " while(" in line and "threefry" not in name:
            loops[at.group(2) if at else None] += 1
    assert gathers == {
        (f"qt_sample_hop{i}", stage): count for i in range(len(SIZES))
        for stage, count in ((profiling.QT_DRAW_ROWS, 2),
                             (profiling.QT_DRAW_NEIGHBORS, 1))}
    assert set(loops) <= {profiling.QT_DRAW_PICKS}


def test_the_per_relation_walk_and_blocks_are_named(world):
    """Over a graph whose relations keep their own lists: every op of a
    relation's draw (its three stages among them) lies under
    ``qt_sample_hop<i>/qt_draw/qt_draw_relation``, every op of a type's
    union under ``qt_sample_hop<i>/qt_compact/qt_type_union``, every
    product of the model (its blocks' loops and the head) under
    ``qt_project``, and the walk holds no other builder's names."""
    named = _names_and_ops(_lower("igbh", world).compile().as_text())
    names = [n for n, _ in named]
    for scope, parent in ((profiling.QT_DRAW_RELATION, profiling.QT_DRAW),
                          (profiling.QT_TYPE_UNION, profiling.QT_COMPACT)):
        held = [n for n in names if scope in n]
        assert held, scope
        assert all(re.search(r"qt_sample_hop\d\)?/" + parent + r"\)?/"
                             + scope + r"\)?/", n) for n in held), scope
        hops = {re.search(r"qt_sample_hop\d", n).group(0) for n in held}
        assert hops == {f"qt_sample_hop{i}" for i in range(len(SIZES))}
    for stage in profiling.DRAW_STAGES:
        held = [n for n in names if stage in n]
        assert held and all(
            "qt_draw/qt_draw_relation/" + stage in n for n in held), stage
    # the union's sort is the union's
    sorts = [n for n, line in named if re.search(r" sort\(", line)
             and "qt_sample_hop" in n]
    assert sorts and all(profiling.QT_TYPE_UNION in n for n in sorts)
    dots = [n for n, line in named
            if re.search(r" (dot|convolution)\(", line)
            and "qt_forward" in n]
    assert dots and all(profiling.QT_PROJECT in n for n in dots), dots
    loops = [n for n, line in named
             if " while(" in line and re.search(r"/conv\d_\d", n)]
    assert loops and all(re.search(r"qt_project\)?/while$", n)
                         for n in loops)
    assert any(profiling.QT_ATTENTION_SLOTS in n for n in names)
    assert not any(profiling.QT_RELATION_ROUTE in n or "qt_norm" in n
                   or profiling.QT_AGGREGATE in n for n in names)


def test_the_lookups_bookkeeping_lies_beside_the_tiers(world):
    """``LOOKUP_STAGES`` lie beneath the tiered lookup's ``qt_gather`` as
    siblings of ``qt_lookup_hot`` / ``qt_lookup_cold``, never inside
    them; the compaction's ``sort`` is under ``qt_lookup_compact``, the
    cold block's ``scatter`` under ``qt_lookup_merge``."""
    named = _names_and_ops(_lower("tiered", world).compile().as_text())
    held = collections.defaultdict(list)
    for name, line in named:
        for stage in profiling.LOOKUP_STAGES:
            if stage in name:
                assert re.search(r"qt_gather\)?/.*" + stage + r"\)?/", name), name
                assert not any(s in name for s in LOOKUP_SCOPES), name
                held[stage].append(line)
    assert set(held) == set(profiling.LOOKUP_STAGES)
    for opcode, stage in (("sort", profiling.QT_LOOKUP_COMPACT),
                          ("scatter", profiling.QT_LOOKUP_MERGE)):
        ops = [line for name, line in named
               if re.search(r"qt_gather\)?/", name) and f" {opcode}(" in line]
        assert ops and all(line in held[stage] for line in ops), opcode


@pytest.mark.parametrize("builder", ["train", "e2e", "serve", "dist", "gat",
                                     "rgat"])
def test_no_other_builder_names_the_lookups_bookkeeping(world, builder):
    names = _op_names(_lower(builder, world))
    assert not any(s in n for n in names for s in profiling.LOOKUP_STAGES)


@pytest.mark.parametrize("builder", ["train", "serve", "rgat"])
def test_the_draw_gathers_nothing_but_the_data(world, builder):
    """A hop's draw holds three ``gather``s: the two reads of ``indptr``
    and the one of ``indices``; over a typed graph too, whose slots'
    relations are read off the two ids by compares. Its Fisher–Yates write log is read by
    selects over the log's columns (a gather costs by the index on the
    chip: reading the log that way was 16 ms of an 89 ms step)."""
    text = _lower(builder, world).compile().as_text()
    per_hop = collections.Counter()
    for name, line in _names_and_ops(text):
        hop = re.search(r"(qt_sample_hop\d)\)?/qt_draw", name)
        if hop and re.search(r"= \S+ gather\(", line):
            per_hop[hop.group(1)] += 1
    assert per_hop == {f"qt_sample_hop{i}": 3 for i in range(len(SIZES))}


def test_which_attention_path_ran_is_in_the_names(world):
    """The attention's path is chosen at trace time from what the ``Adj``
    states and recorded as a name: the slot form (``qt_attention_slots``,
    no scatter in the forward pass) where it states its fanout, the
    segment form (scatters, no such name) where it does not."""
    from quiver_tpu.pyg.sage_sampler import Adj
    w = world
    adjs = layers_to_adjs(w["layers"], BATCH, SIZES)
    loose = [Adj(a.edge_index, a.e_id, a.size, a.mask, None, a.valid_targets)
             for a in adjs]
    x = masked_feature_gather(w["feat"], w["n_id"])
    text = {}
    for name, blocks in (("slots", adjs), ("segments", loose)):
        text[name] = jax.jit(w["gat"].apply).lower(
            w["gat_state"].params, x, blocks).compile().as_text()
    slots, segments = (_names_and_ops(text[k]) for k in ("slots", "segments"))
    assert any("qt_attention/qt_attention_slots/" in n for n, _ in slots)
    assert not any("scatter" in op for n, op in slots
                   if profiling.QT_ATTENTION in n)
    assert not any(profiling.QT_ATTENTION_SLOTS in n for n, _ in segments)
    assert any("scatter" in op for n, op in segments
               if profiling.QT_ATTENTION in n)


@pytest.mark.parametrize("builder", ["gat", "rgat"])
def test_the_projections_loops_lie_under_qt_project(world, builder):
    """Each attention layer projects its live row blocks in one loop
    forward and one backward (``models.live_rows``), every one of them
    ``qt_project``'s, and the blocks' products sit in their bodies; over
    a typed graph a turn's pick of its relation's weight (an index into
    the relations' stack, a ``dynamic-slice``) lies there too:
    ``qt_project``, never ``qt_relation_route``."""
    named = _names_and_ops(_lower(builder, world).compile().as_text())
    loops = [n for n, line in named
             if " while(" in line and re.search(r"/conv\d", n)]
    assert len(loops) == 2 * len(SIZES)
    assert sum("transpose(" in n for n in loops) == len(SIZES)
    assert all(re.search(r"qt_project\)?/while$", n) for n in loops), loops
    dots = {n for n, line in named
            if re.search(r" (dot|convolution)\(", line) and "/while/" in n}
    assert dots and all("qt_project/while/body/" in n for n in dots), dots
    # a relation's [in, out] weight out of the [R, in, out] stack
    picks = [n for n, line in named if "/while/" in n and re.search(
        r"= f32\[1,\d+,\d+\]\S* dynamic-slice\(", line)]
    if builder == "rgat":
        assert picks and all("qt_project/while/body/" in n for n in picks)
        assert not any(profiling.QT_RELATION_ROUTE in n for n in picks)
    else:
        assert not picks


def _names_and_ops(text):
    """``(op_name, instruction text)`` of a compiled program's lines."""
    return [(m.group(1), line) for line in text.splitlines()
            for m in [re.search(r'op_name="([^"]*)"', line)] if m]


def test_the_exchange_scopes_cover_the_lookup(world):
    """Every collective and every [.., DIM] row block of the dist step's
    lookup carries ``qt_exchange`` and one stage beneath it; the stages
    lie nowhere else, and the step has no ``qt_gather``."""
    text = _lower("dist", world).compile().as_text()
    names = re.findall(r'op_name="([^"]*)"', text)
    assert not any(profiling.QT_GATHER + "/" in n for n in names)
    for n in names:
        for stage in profiling.EXCHANGE_STAGES:
            if stage in n:
                assert re.search(
                    r"qt_exchange\)?/(while/body/)?(closed_call/)?"
                    + stage + r"\)?/", n), n
    # the two all_to_alls a round, and what they carry
    for line in text.splitlines():
        if re.search(r" all-to-all(-start)?\(", line):
            assert re.search(
                r"qt_exchange\)?/.*qt_exchange_(requests|responses)/", line), line
    # what the lookup hands the model is written under the parent scope
    rows = [n for n in names if "qt_exchange_expand" in n]
    assert rows and all(len(re.findall(r"qt_exchange\)?/", n)) == 1
                        for n in rows)


@pytest.mark.parametrize("builder", ["train", "serve", "dist", "tiered",
                                     "gat", "rgat", "igbh"])
def test_scopes_change_nothing_but_names(world, builder, monkeypatch):
    named = _lower(builder, world).as_text()
    monkeypatch.setattr(profiling, "scope",
                        lambda name: contextlib.nullcontext())
    lowered = _lower(builder, world)
    # the lowered text carries no op_name (locations are not printed),
    # so what is left to differ is the program itself
    assert "qt_" not in named
    assert named == lowered.as_text()
    # and the patch did reach the builders: the names are gone from the
    # locations too (read before any compile cache has a say)
    located = lowered.as_text(debug_info=True)
    assert not any(s in located for s in profiling.DEVICE_SCOPES
                   + profiling.EXCHANGE_STAGES + profiling.DRAW_STAGES
                   + profiling.LOOKUP_STAGES + profiling.TYPED_STAGES)
    assert "qt_sample_hop0" in located
