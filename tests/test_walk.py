"""The neighbourhood stage (``parallel.frontier``): one constructor refuses
for all seven step builders, the stage called by hand, with a
builder's own key fold, returns the sample and the rows that builder's
step used, and the blocks every builder hands its model state how many
of their target slots hold a node."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import quiver_tpu as qv
from quiver_tpu.models import GraphSAGE
from quiver_tpu.ops import (as_index_rows, edge_row_ids, permute_csr,
                            sample_multihop)
from quiver_tpu.parallel import (build_dist_train_step,
                                 build_e2e_train_step,
                                 build_gspmd_train_step,
                                 build_split_train_step, build_train_step,
                                 shard_state)
from quiver_tpu.parallel.frontier import (ALL_KNOBS, SAMPLING_KNOBS, Walk,
                                          layers_to_adjs,
                                          masked_feature_gather,
                                          walk_frontier)
from quiver_tpu.parallel.train import init_state
from quiver_tpu.serving import build_serve_step, build_sharded_serve_step

N, DIM, CLASSES, BATCH, HOSTS = 200, 128, 4, 8, 2
SIZES = [3, 2]
FUSED = {"fused_hot_hop": True, "fused_row_cap": 64}


class World:
    def __init__(self):
        rng = np.random.default_rng(5)
        deg = rng.integers(1, 9, N)
        indptr = np.zeros(N + 1, np.int64)
        np.cumsum(deg, out=indptr[1:])
        indices = rng.integers(0, N, int(indptr[-1]), dtype=np.int32)
        self.feat_np = rng.standard_normal((N, DIM)).astype(np.float32)
        self.indptr = jnp.asarray(indptr.astype(np.int32))
        self.indices = jnp.asarray(indices)
        self.feat = jnp.asarray(self.feat_np)
        self.labels = jnp.asarray(
            rng.integers(0, CLASSES, N).astype(np.int32))
        self.model = GraphSAGE(hidden_dim=16, out_dim=CLASSES,
                               num_layers=2, dropout=0.5)
        self.tx = optax.adam(1e-2)
        n_id, layers = sample_multihop(
            self.indptr, self.indices, jnp.arange(BATCH, dtype=jnp.int32),
            SIZES, jax.random.key(0))
        self.state = init_state(
            self.model, self.tx, masked_feature_gather(self.feat, n_id),
            layers_to_adjs(layers, BATCH, SIZES), jax.random.key(1))
        rids = edge_row_ids(self.indptr, int(indices.shape[0]))
        self.permuted = permute_csr(self.indices, rids, jax.random.key(3))
        self.rows = as_index_rows(self.permuted)
        self.exact_rows = as_index_rows(self.indices)
        self.seeds = jnp.asarray(
            rng.choice(N, HOSTS * BATCH, replace=False).astype(np.int32))
        self.mesh = Mesh(np.array(jax.devices()[:HOSTS]), ("host",))
        g2h = rng.integers(0, HOSTS, N).astype(np.int32)
        g2h[:HOSTS] = np.arange(HOSTS)
        self.info = qv.PartitionInfo(host=0, hosts=HOSTS, global2host=g2h)
        comm = qv.TpuComm(rank=0, world_size=HOSTS, mesh=self.mesh,
                          axis="host")
        self.dist = qv.DistFeature.from_partition(self.feat_np, self.info,
                                                  comm)
        self.g2h = self.info.global2host.astype(jnp.int32)


@pytest.fixture(scope="module")
def w():
    return World()


# -- what each builder takes, and how to build and call it -------------------

_SERVE = ("method", "dedup_gather", "fused_hot_hop", "fused_row_cap")
_SHARDED = ("method", "fused_hot_hop", "fused_row_cap")
TAKES = {"train": ALL_KNOBS, "e2e": ALL_KNOBS, "dist": SAMPLING_KNOBS,
         "gspmd": ("method", "indices_stride"), "split": SAMPLING_KNOBS,
         "serve": _SERVE, "sharded-serve": _SHARDED}
BUILDERS = tuple(TAKES)


def _build(w, which, sizes=SIZES, **kw):
    m, tx = w.model, w.tx
    if which == "train":
        return build_train_step(m, tx, sizes, BATCH, **kw)
    if which == "e2e":
        return build_e2e_train_step(m, tx, sizes, BATCH, w.mesh,
                                    axis="host", **kw)
    if which == "dist":
        return build_dist_train_step(m, tx, sizes, BATCH, w.mesh,
                                     rows_per_host=w.dist._rows_per_host,
                                     **kw)
    if which == "gspmd":
        mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1),
                    ("data", "model"))
        return build_gspmd_train_step(m, tx, sizes, mesh, **kw)
    if which == "split":
        return build_split_train_step(m, tx, sizes, BATCH, **kw)
    if which == "serve":
        return build_serve_step(m, sizes, BATCH, **kw)
    return build_sharded_serve_step(m, sizes, BATCH, w.mesh, "host",
                                    w.dist._rows_per_host, **kw)


def _call(w, which, step, key, rows=None, state=None):
    """One call of ``which``'s step on the world's first batch(es)."""
    extra = () if rows is None else (rows,)
    state = w.state if state is None else state
    indices = w.indices if rows is None or rows is w.exact_rows \
        else w.permuted
    one = w.seeds[:BATCH]
    if which in ("train", "gspmd"):
        return step(state, w.feat, None, w.indptr, indices, one,
                    w.labels[one], key, *extra)
    sh = NamedSharding(w.mesh, P("host"))
    seeds = jax.device_put(w.seeds, sh)
    y = jax.device_put(w.labels[w.seeds], sh)
    if which == "e2e":
        return step(state, w.feat, None, w.indptr, indices, seeds, y, key,
                    *extra)
    if which == "dist":
        return step(state, w.dist._spmd_feat, w.g2h, w.info.global2local,
                    w.indptr, indices, seeds, y, key, *extra)
    if which == "split":
        return step[0](w.indptr, indices, one, key, *extra)
    if which == "serve":
        return step(state.params, key, w.feat, None, w.indptr, indices, one)
    return step(state.params, key, w.dist._spmd_feat, w.g2h,
                w.info.global2local, w.indptr, indices, one)


# -- the constructor's refusals, the same for every builder ------------------

REFUSALS = {
    "fused+rotation": ({"fused_hot_hop": True, "method": "rotation"},
                       "requires method='exact'"),
    "fused+dedup": ({"fused_hot_hop": True, "dedup_gather": True},
                    "dedup_gather does not compose"),
    "fused+stride": ({"fused_hot_hop": True, "indices_stride": 128},
                     "neither indices_stride nor hub_frac"),
    "fused+no-hops": ({"fused_hot_hop": True}, "at least one hop"),
    "unknown:fused_rng": ({"fused_rng": "hash"}, None),
    "unknown:fused_interpret": ({"fused_interpret": True}, None),
}


@pytest.mark.parametrize("case", tuple(REFUSALS))
@pytest.mark.parametrize("which", BUILDERS)
def test_the_constructor_refuses_for_every_builder(w, which, case):
    knobs, text = REFUSALS[case]
    not_taken = [k for k in knobs if k not in TAKES[which]]
    sizes = [] if case == "fused+no-hops" else SIZES
    if not_taken:
        # a knob this builder's step never took, or no knob at all, is
        # refused by name before anything else is looked at
        with pytest.raises(TypeError, match=f"'{not_taken[0]}'"):
            Walk.of(which, TAKES[which], sizes, knobs)
        with pytest.raises(TypeError,
                           match=f"unexpected keyword.*'{not_taken[0]}'"):
            _build(w, which, sizes, **knobs)
        return
    with pytest.raises(ValueError, match=text):
        Walk.of(which, TAKES[which], sizes, knobs)
    with pytest.raises(ValueError, match=text):
        _build(w, which, sizes, **knobs)


@pytest.mark.parametrize("method", ("rotation", "window"))
@pytest.mark.parametrize("which", BUILDERS)
def test_windowed_methods_require_indices_rows(w, which, method):
    step = _build(w, which, method=method)
    with pytest.raises(TypeError, match="requires indices_rows"):
        _call(w, which, step, jax.random.key(0))


# -- the stage by hand returns what the step used ----------------------------

METHODS = {
    "train": ("exact", "exact-wide", "rotation", "window", "fused"),
    "e2e": ("exact", "exact-wide", "rotation", "window", "fused"),
    "dist": ("exact", "rotation", "window"),
    "gspmd": ("exact", "rotation", "window"),
    "split": ("exact", "rotation", "window"),
    "serve": ("exact", "fused"),
    "sharded-serve": ("exact", "fused"),
}
CASES = [(b, m) for b in BUILDERS for m in METHODS[b]]


def _mix(a):
    """A position-weighted checksum in wrapping uint32 arithmetic: equal
    inputs give equal bits whatever XLA fuses, which no float model can
    promise between two programs."""
    a = a.astype(jnp.uint32).reshape(-1)
    return jnp.sum(a * (2 * jnp.arange(a.shape[0], dtype=jnp.uint32) + 1),
                   dtype=jnp.uint32)


class Probe:
    """In a model's place: its "logits" are a checksum of all a step hands
    its model (the rows bit for bit, every hop's edges and mask, the
    dropout key), kept to 23 bits so that float32 holds it exactly."""

    @staticmethod
    def apply(params, x, adjs, train=False, rngs=None):
        h = _mix(jax.lax.bitcast_convert_type(x, jnp.uint32))
        for adj in adjs:
            h += _mix(adj.edge_index) + _mix(adj.mask)
        if rngs:
            h += _mix(jax.random.key_data(rngs["dropout"]))
        low = (h & jnp.uint32(0x7FFFFF)).astype(jnp.float32)
        return jnp.zeros((x.shape[0], CLASSES)) + low + 0.0 * params["w"]


def _probe_loss(logits, labels):
    return logits[0, 0]


def _bits(a):
    return np.asarray(a).tobytes()


@pytest.mark.parametrize("which,method", CASES,
                         ids=[f"{b}-{m}" for b, m in CASES])
def test_the_stage_by_hand_returns_what_the_step_used(w, which, method):
    from quiver_tpu.parallel.train import TrainState
    knobs = FUSED if method == "fused" else \
        {"method": "exact" if method == "exact-wide" else method}
    rows = {"exact-wide": w.exact_rows, "rotation": w.rows,
            "window": w.rows}.get(method)
    indices = w.permuted if method in ("rotation", "window") else w.indices
    # by hand the walk reads the whole table with the plain gather: the
    # exchange of the sharded builders has to return the same rows
    walk = Walk.of("by-hand", ALL_KNOBS, SIZES, knobs)
    key = jax.random.key(17)
    serve = which in ("serve", "sharded-serve")
    tx = optax.sgd(0.1)
    params = {"w": jnp.float32(1.0)}
    state = TrainState(params, tx.init(params), jnp.zeros((), jnp.int32))
    probed = type("ProbedWorld", (), dict(vars(w), model=Probe, tx=tx,
                                          state=state))
    kw = {} if serve else {"loss_fn": _probe_loss}
    if which in ("train", "e2e", "dist", "split"):
        kw["donate"] = False
    step = _build(probed, which, **knobs, **kw)

    @jax.jit
    def by_hand(seeds, key):
        n_id, x, layers = walk_frontier(walk, w.feat, None, w.indptr,
                                        indices, seeds, key, rows)
        adjs = layers_to_adjs(layers, BATCH, SIZES)
        rngs = None if serve else \
            {"dropout": jax.random.fold_in(key, 1000)}
        return n_id, x, adjs, Probe.apply(params, x, adjs, rngs=rngs)[0, 0]

    def check_sample(n_id, x, seeds, key):
        # the frontier is the one ``sample_multihop`` replays from the
        # same key (what the benchmark's check does today), the rows are
        # the table's
        if method != "fused":
            want, _ = sample_multihop(
                w.indptr, indices, seeds, SIZES, key,
                method=walk.method, indices_rows=rows, seeds_dense=True)
            assert _bits(n_id) == _bits(want)
        # (equal, not bit-equal: the fused kernel leaves +0.0 on the
        # padding where the masked gather's multiply leaves -0.0)
        np.testing.assert_array_equal(
            np.asarray(x), np.asarray(masked_feature_gather(w.feat, n_id)))

    one = w.seeds[:BATCH]
    if serve:
        # the serve steps split their chain: the walk takes the second half
        nxt, sub = jax.random.split(key)
        out = _call(probed, which, step, jnp.copy(key))
        n_id, x, _, want = by_hand(one, sub)
        check_sample(n_id, x, one, sub)
        assert _bits(out[1][0, 0]) == _bits(want)
        assert _bits(jax.random.key_data(out[0])) == \
            _bits(jax.random.key_data(nxt))
    elif which == "split":
        # its two stages: the sample, then the model over rows the
        # caller fetched, with the dropout key as given
        got_n_id, adjs = _call(probed, which, step, key, rows)
        n_id, x, want_adjs, _ = by_hand(one, key)
        check_sample(n_id, x, one, key)
        assert _bits(got_n_id) == _bits(n_id)
        _, loss = step[1](state, x, adjs, w.labels[one], key)
        want = Probe.apply(params, x, want_adjs,
                           rngs={"dropout": key})[0, 0]
        assert _bits(loss) == _bits(want)
    elif which in ("train", "gspmd"):
        _, loss = _call(probed, which, step, key, rows)
        n_id, x, _, want = by_hand(one, key)
        check_sample(n_id, x, one, key)
        assert _bits(loss) == _bits(want)
    else:
        # the shard_map steps fold each shard's index into the key
        # before the walk, then pmean the shards' losses
        _, loss = _call(probed, which, step, key, rows)
        per_shard = []
        for h in range(HOSTS):
            seeds = w.seeds[h * BATCH:(h + 1) * BATCH]
            k = jax.random.fold_in(key, h)
            n_id, x, _, value = by_hand(seeds, k)
            check_sample(n_id, x, seeds, k)
            per_shard.append(np.asarray(value))
        assert len({_bits(v) for v in per_shard}) == HOSTS
        want = sum(per_shard) / np.float32(HOSTS)
        assert _bits(loss) == _bits(want)


class Tell:
    """In a model's place: its "logits" spell out the ``valid_targets``
    of the blocks it was handed, outermost hop first, 12 bits a hop."""

    @staticmethod
    def apply(params, x, adjs, train=False, rngs=None):
        told = sum(adj.valid_targets.astype(jnp.float32) * 4096.0 ** j
                   for j, adj in enumerate(adjs))
        return jnp.zeros((x.shape[0], CLASSES)) + told + 0.0 * params["w"]


@pytest.mark.parametrize("which", BUILDERS)
def test_every_builders_blocks_state_their_valid_targets(w, which):
    """Whatever builds the step, the model's ``Adj``s say how many target
    slots hold a node: the batch's valid seeds for the batch's own hop (two
    of each batch's eight slots are -1 here), the valid entries of the
    frontier of the hop before for the others; valid targets come first,
    so ``target_mask()`` is that many ones."""
    from quiver_tpu.parallel.train import TrainState
    key = jax.random.key(23)
    serve = which in ("serve", "sharded-serve")
    tx = optax.sgd(0.1)
    params = {"w": jnp.float32(1.0)}
    state = TrainState(params, tx.init(params), jnp.zeros((), jnp.int32))
    tail = jnp.asarray([BATCH - 2, BATCH - 1, 2 * BATCH - 2, 2 * BATCH - 1])
    told = type("ToldWorld", (), dict(
        vars(w), model=Tell, tx=tx, state=state,
        seeds=w.seeds.at[tail].set(-1)))
    kw = {} if serve else {"loss_fn": _probe_loss}
    if which in ("train", "e2e", "dist", "split"):
        kw["donate"] = False
    step = _build(told, which, **kw)

    def want(seeds, key):
        _, layers = sample_multihop(w.indptr, w.indices, seeds, SIZES, key,
                                    seeds_dense=True)
        counts = [int((seeds >= 0).sum())] + [int(l.n_count)
                                              for l in layers[:-1]]
        assert counts[0] == BATCH - 2 and counts[1] > counts[0]
        return counts[::-1]                      # outermost hop first

    spell = lambda counts: np.float32(sum(c * 4096.0 ** j
                                          for j, c in enumerate(counts)))
    one = told.seeds[:BATCH]
    if which == "split":
        _, adjs = _call(told, which, step, key)
        assert [int(a.valid_targets) for a in adjs] == want(one, key)
        for a in adjs:
            mask = np.asarray(a.target_mask())
            assert mask.shape == (a.size[1],)
            assert mask.sum() == int(a.valid_targets)
            assert mask[:int(a.valid_targets)].all()
    elif serve:
        out = _call(told, which, step, jnp.copy(key))
        sub = jax.random.split(key)[1]
        assert _bits(out[1][0, 0]) == _bits(spell(want(one, sub)))
    elif which in ("train", "gspmd"):
        _, loss = _call(told, which, step, key)
        assert _bits(loss) == _bits(spell(want(one, key)))
    else:
        _, loss = _call(told, which, step, key)
        shards = [spell(want(told.seeds[h * BATCH:(h + 1) * BATCH],
                             jax.random.fold_in(key, h)))
                  for h in range(HOSTS)]
        assert _bits(loss) == _bits(sum(shards) / np.float32(HOSTS))


class TellRelations:
    """In a model's place: its "logits" spell out, outermost hop first,
    12 bits a hop, 1 + the edge slots with a relation of each block that
    states its relations, 0 for a block that states none."""

    @staticmethod
    def apply(params, x, adjs, train=False, rngs=None):
        told = sum(0.0 if adj.relation is None else
                   (1 + (adj.relation >= 0).sum()).astype(jnp.float32)
                   * 4096.0 ** j for j, adj in enumerate(adjs))
        return jnp.zeros((x.shape[0], CLASSES)) + told + 0.0 * params["w"]


# two types, every pair of them a relation: every sampled edge has one
TYPES = qv.ops.NodeTypes(starts=(N // 2,),
                         relations=((0, 0), (0, 1), (1, 0), (1, 1)))


@pytest.mark.parametrize("typed", [True, False], ids=["typed", "untyped"])
@pytest.mark.parametrize("which", BUILDERS)
def test_every_builders_blocks_state_their_relations(w, which, typed):
    """Whatever builds the step, over a typed graph (``indices`` as
    ``ops.TypedIndices``) the model's ``Adj``s state each edge slot's
    relation, one for every sampled edge; over an untyped graph they state
    none."""
    from quiver_tpu.parallel.train import TrainState
    key = jax.random.key(29)
    serve = which in ("serve", "sharded-serve")
    tx = optax.sgd(0.1)
    params = {"w": jnp.float32(1.0)}
    state = TrainState(params, tx.init(params), jnp.zeros((), jnp.int32))
    told = type("ToldWorld", (), dict(
        vars(w), model=TellRelations, tx=tx, state=state,
        indices=qv.ops.TypedIndices(w.indices, TYPES) if typed
        else w.indices))
    kw = {} if serve else {"loss_fn": _probe_loss}
    if which in ("train", "e2e", "dist", "split"):
        kw["donate"] = False
    step = _build(told, which, **kw)

    def want(seeds, key):
        _, layers = sample_multihop(w.indptr, w.indices, seeds, SIZES, key,
                                    seeds_dense=True)
        return [1 + int(l.edge_count) if typed else 0
                for l in layers][::-1]           # outermost hop first

    spell = lambda counts: np.float32(sum(c * 4096.0 ** j
                                          for j, c in enumerate(counts)))
    one = told.seeds[:BATCH]
    if which == "split":
        _, adjs = _call(told, which, step, key)
        assert [a.relation is None for a in adjs] == [not typed] * len(SIZES)
        if typed:
            assert [1 + int((a.relation >= 0).sum()) for a in adjs] == \
                want(one, key)
    elif serve:
        out = _call(told, which, step, jnp.copy(key))
        sub = jax.random.split(key)[1]
        assert _bits(out[1][0, 0]) == _bits(spell(want(one, sub)))
    elif which in ("train", "gspmd"):
        _, loss = _call(told, which, step, key)
        assert _bits(loss) == _bits(spell(want(one, key)))
    else:
        _, loss = _call(told, which, step, key)
        shards = [spell(want(told.seeds[h * BATCH:(h + 1) * BATCH],
                             jax.random.fold_in(key, h)))
                  for h in range(HOSTS)]
        assert _bits(loss) == _bits(sum(shards) / np.float32(HOSTS))


def test_the_fused_walk_refuses_a_typed_graph(w):
    step = _build(w, "train", donate=False, **FUSED)
    with pytest.raises(ValueError, match="typed graph"):
        step(w.state, w.feat, None, w.indptr,
             qv.ops.TypedIndices(w.indices, TYPES), w.seeds[:BATCH],
             w.labels[w.seeds[:BATCH]], jax.random.key(0))


def test_serve_and_train_reach_a_feature_store_through_one_splice(
        w, monkeypatch):
    """``parallel.frontier.feature_splice`` is the ONE function that turns
    a ``Feature`` store into ``(feat_args, forder, gather)``: the engine
    calls it from there, and the same gather under ``build_train_step``
    and ``build_serve_step`` hands the model the rows the stage reads by
    hand through it, which are the table's."""
    from quiver_tpu import serving
    from quiver_tpu.parallel import frontier
    from quiver_tpu.parallel.train import TrainState
    assert serving.feature_splice is frontier.feature_splice
    assert not hasattr(serving, "_feature_gather")
    rng = np.random.default_rng(9)
    order = rng.permutation(N).astype(np.int32)
    storage = np.empty_like(w.feat_np)
    storage[order] = w.feat_np
    dev = jax.devices()[0]
    store = qv.Feature(host_placement="offload", allow_fallback=False,
                       cold_budget=32).from_tiers(
        jnp.asarray(storage[:N // 2]),
        jax.device_put(storage[N // 2:], jax.sharding.SingleDeviceSharding(
            dev, memory_kind="pinned_host")), order)
    calls = []
    real = frontier.feature_splice
    monkeypatch.setattr(serving, "feature_splice",
                        lambda f: calls.append(f) or real(f))
    serving.ServeEngine(w.model, w.state.params, (w.indptr, w.indices),
                        store, [SIZES], BATCH)
    assert calls == [store]
    feat, forder, gather = real(store)
    tx = optax.sgd(0.1)
    params = {"w": jnp.float32(1.0)}
    state = TrainState(params, tx.init(params), jnp.zeros((), jnp.int32))
    key, one = jax.random.key(17), w.seeds[:BATCH]
    walk = Walk.of("by-hand", ALL_KNOBS, SIZES, {}, gather=gather)

    @functools.partial(jax.jit, static_argnums=2)
    def by_hand(seeds, key, train):
        n_id, x, layers = walk_frontier(walk, feat, forder, w.indptr,
                                        w.indices, seeds, key)
        adjs = layers_to_adjs(layers, BATCH, SIZES)
        rngs = {"dropout": jax.random.fold_in(key, 1000)} if train else None
        return n_id, x, Probe.apply(params, x, adjs, rngs=rngs)[0, 0]

    train = build_train_step(Probe, tx, SIZES, BATCH, gather=gather,
                             loss_fn=_probe_loss, donate=False)
    _, loss = train(state, feat, forder, w.indptr, w.indices, one,
                    w.labels[one], key)
    n_id, x, want = by_hand(one, key, True)
    np.testing.assert_array_equal(
        np.asarray(x), np.asarray(masked_feature_gather(w.feat, n_id)))
    assert _bits(loss) == _bits(want)
    serve = build_serve_step(Probe, SIZES, BATCH, gather=gather)
    _, sub = jax.random.split(key)
    _, logits = serve(params, jnp.copy(key), feat, forder, w.indptr,
                      w.indices, one)
    assert _bits(logits[0, 0]) == _bits(by_hand(one, sub, False)[2])
