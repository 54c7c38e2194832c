"""The entry ``igbh_train_step``: ``quiver_tpu.parallel.train
.build_train_step`` on one chip over ``quiver_tpu.models.RGAT``, the
MLPerf GNN model (R-GAT: a bipartite ``GATConv`` a relation, summed a
node type, three layers, a ``Linear`` head), over a typed graph whose
relations keep their own neighbour lists (the world ``planted_igbh``:
papers, authors, fields and institutes, seven relations, a float16
table).

The step is the user's: ``build_train_step(model, adam, fanout, batch,
method="exact", collect_metrics=True)``, handed the float16 table as it
is, the relations' row pointers as ``indptr`` and their neighbour lists
as ``ops.RelationIndices`` (the lists, the node types and the frontier
caps of the configuration): the topology says that every relation draws
its own fanout. Its device counter block is kept a step and read ONCE
after the window; ``stop()`` hands the reducers the counters folded over
the steps, by name, and keeps ``type_cap_drops`` for ``correct``.

``follow`` is the reference through the first steps: the sample replayed
(``replay``: the program's own per-relation walk, asked again with the
same key) and held against the graph (``check_typed_sample``), the
frontiers' rows read from the table by plain indexing, and
``references/igbh_rgat.py`` for the rest. Faults under the timed path:
``half_batch`` and ``state_unchanged``. ``readings`` adds the reference
put in the program's place with a planted fault: the model's own
``one_relation`` and ``mean_over_relations``, and two of the sample:
``merged_draw`` (each target draws its fanout ONCE, from the merged list
of all its relations, and each pick is typed after: the draw of a
merged typed graph) and ``cap_drop`` (the papers' last frontier capped
at half its cap, so that picks are masked).
"""

from __future__ import annotations

import functools

import numpy as np

from chipbench import check, spec, traffic, world
from chipbench.train_cell import TrainRun, _half_batch_loss, _on, _plant

SAMPLE_FAULTS = ("merged_draw", "cap_drop")
PROBE_TARGETS = 2048


def _needs_the_model():
    """Said before anything is built: a program that cannot walk a graph
    whose relations keep their own lists cannot run this cell, and says
    so at once."""
    from quiver_tpu import metrics, models, ops
    lacks = [what for what, has in (
        ("ops.RelationIndices (a topology whose relations keep their own "
         "neighbour lists)", hasattr(ops, "RelationIndices")),
        ("models.RGAT (the model over its relation blocks)",
         hasattr(models, "RGAT")),
        ("metrics.TYPE_CAP_DROPS (the picks a frontier cap could not take)",
         hasattr(metrics, "TYPE_CAP_DROPS"))) if not has]
    if lacks:
        raise SystemExit(
            "chipbench: entry igbh_train_step needs " + ", ".join(lacks)
            + " (the MLPerf R-GAT trained through build_train_step over a "
            "fanout for each relation); this program has none")


def layout(cfg):
    return spec.plugin("worlds", "planted_igbh").layout(cfg)


def graph_of(cfg) -> dict:
    """What the reference reads the ids by: the types' first ids, the
    relations' pairs, the batch's type."""
    _, starts, _, rels = layout(cfg)
    return {"starts": tuple(starts),
            "pairs": tuple((t, s) for t, s, _ in rels), "seed_type": 0}


def relation_indices(cfg, indices, caps=None):
    """The step's ``indices``: the relations' lists, typed, capped."""
    from quiver_tpu.ops import NodeTypes, RelationIndices
    names, starts, _, rels = layout(cfg)
    if caps is None:
        caps = [cfg["frontier_caps"][n] for n in names]
    return RelationIndices(indices, NodeTypes(
        starts=starts[1:], relations=[(t, s) for t, s, _ in rels]), caps)


def program_tree(layers):
    """The reference's weights in the tree ``RGAT`` keeps them in."""
    tree = {}
    for i, convs in enumerate(layers["convs"]):
        for r, p in convs.items():
            tree[f"conv{i}_{r}"] = {
                "lin_src": {"kernel": p["w_src"]},
                "lin_dst": {"kernel": p["w_dst"]},
                "att_src": p["a_src"], "att_dst": p["a_dst"], "bias": p["b"]}
    tree["head"] = {"kernel": layers["head"]["w"],
                    "bias": layers["head"]["b"]}
    return {"params": tree}


def reference_layers(tree):
    t = tree["params"]
    convs = {}
    for name, c in t.items():
        if not name.startswith("conv"):
            continue
        i, r = (int(v) for v in name[len("conv"):].split("_"))
        convs.setdefault(i, {})[r] = {
            "w_src": c["lin_src"]["kernel"], "w_dst": c["lin_dst"]["kernel"],
            "a_src": c["att_src"], "a_dst": c["att_dst"], "b": c["bias"]}
    return {"convs": [convs[i] for i in sorted(convs)],
            "head": {"w": t["head"]["kernel"], "b": t["head"]["bias"]}}


class Run(TrainRun):
    def __init__(self, cell, seed: int, devices, faults=()):
        _needs_the_model()
        import jax
        import jax.numpy as jnp
        import optax
        from quiver_tpu.models import RGAT
        from quiver_tpu.parallel.train import TrainState, build_train_step

        cfg, mix = cell.config, cell.traffic
        self.cell, self.seed = cell, seed
        self.ref = cell.reference
        self.chips = cell.chips
        self.batch = self.global_batch = int(mix["batch"])
        self.run_ahead = int(mix["run_ahead"])
        self.fanout = list(cfg["fanout"])
        self.lr = float(cfg["optimizer"]["learning_rate"])
        self.devices = devices[:1]
        self.faults = tuple(faults)
        self.rep = self.split = jax.sharding.SingleDeviceSharding(
            self.devices[0])
        self.world = world.make_world(cfg, seed, sharding=self.rep)
        self.graph = graph_of(cfg)
        self.typed = relation_indices(cfg, self.world["indices"])
        model = RGAT(hidden_dim=cfg["hidden_dim"], out_dim=cfg["num_classes"],
                     num_layers=cfg["num_layers"], heads=cfg["heads"],
                     dropout=cfg["dropout"])
        tx = optax.adam(self.lr)
        drawn = self.ref.relations_by_layer(self.graph["pairs"], 0,
                                            cfg["num_layers"])

        def make_state(key):
            params = program_tree(self.ref.init_layers(
                key, cfg["feature_dim"], cfg["hidden_dim"],
                cfg["num_classes"], cfg["heads"], drawn))
            return TrainState(params, tx.init(params),
                              jnp.zeros((), jnp.int32))

        self.state = jax.jit(make_state, out_shardings=self.rep)(
            jax.random.fold_in(world.seed_key(seed), 7))
        extra = {"loss_fn": _half_batch_loss} if "half_batch" in faults \
            else {}
        inner = build_train_step(model, tx, self.fanout, self.batch,
                                 method="exact", collect_metrics=True,
                                 **extra)
        self.counter_blocks = []
        self.counters = None

        def step(state, *args):
            # the counters stay on the device until the window is over
            state, loss, block = inner(state, *args)
            self.counter_blocks.append(block)
            return state, loss

        step.jitted_fns = inner.jitted_fns
        self.step = _plant(step, faults)
        self.labels = np.asarray(self.world["labels"])
        # training nodes are of the first type (papers)
        first = next(iter(cfg["node_types"].values()))
        self.batches = traffic.train_batches(
            mix, dict(cfg, nodes=int(first)), seed, self.batch)
        self.base_key = jax.random.fold_in(world.seed_key(seed), 11)
        self.steps_done = 0

    def call(self, fed):
        _, seeds, labels, key = fed
        w = self.world
        self.state, loss = self.step(self.state, w["feat"], None, w["indptr"],
                                     self.typed, seeds, labels, key)
        self.steps_done += 1
        return loss

    def program_text(self) -> str:
        if not hasattr(self.step, "jitted_fns"):    # a planted fault's wrapper
            return ""
        fn, w, fed = self.step.jitted_fns[-1], self.world, self.feed()
        return fn.lower(self.state, w["feat"], None, w["indptr"], self.typed,
                        fed[1], fed[2], fed[3]).compile().as_text()

    def stop(self):
        """The steps' counter blocks, read now and folded over the steps,
        by name (kept: ``correct`` holds ``type_cap_drops`` at 0)."""
        import jax
        from quiver_tpu import metrics
        if self.counter_blocks:
            blocks = np.asarray(jax.device_get(self.counter_blocks))
            got = metrics.counters_dict(blocks)
            self.counters = got if self.counters is None else {
                k: self.counters[k] + v for k, v in got.items()}
        self.counter_blocks = []
        return self.counters

    def outcome(self, win: dict) -> dict:
        numbers = compare(self, self.kept)
        shown = numbers.pop("facts")
        numbers["cap_drops"] = float(self.stop()["type_cap_drops"])
        numbers["nonfinite_losses"] = float(win["nonfinite"])
        return {"numbers": numbers, "shown": shown,
                "values": {"train_seeds_per_s": win["seeds_per_s"]},
                "attempted": win["steps"], "failed": win["nonfinite"],
                "facts": {"steps": win["steps"],
                          "enqueue_s": win["enqueue_s"]}}

    def readings(self, seconds: float, control: bool):
        """``(kind, numbers, shown)`` of a sound run's first steps and,
        with ``control``, of the bfloat16 control and of each fault planted
        in the reference put in the program's place."""
        kept = self.first_steps()
        counters = self.stop()
        self.free()
        ref, facts = follow(self, kept)

        def read(numbers, facts=facts, drops=0.0):
            out = check.train_numbers(numbers, ref, facts)
            out.pop("facts")
            out["cap_drops"] = float(drops)
            return out

        sound = program_numbers(self, kept)
        yield "program", read(sound, drops=counters["type_cap_drops"]), {
            k: counters[k] for k in ("frontier_valid", "frontier_cap",
                                     "edge_valid", "edge_cap",
                                     "type_cap_drops")}
        if control:
            yield "control_bfloat16", read(follow(
                self, kept, precision="bfloat16", verify=False)[0]), {}
            for fault in ("half_batch",) + tuple(self.ref.FAULTS):
                yield "fault_" + fault, read(follow(
                    self, kept, fault=fault, verify=False)[0]), {}
            for fault in SAMPLE_FAULTS:
                numbers, seen = follow(self, kept, fault=fault)
                yield "fault_" + fault, read(numbers, seen, seen.drops), {}
            yield "fault_state_unchanged", read(
                dict(sound, params3=sound["params0"])), {}


def program_numbers(run: Run, kept: dict) -> dict:
    """What the timed steps produced, in the reference's terms."""
    import jax
    return {"losses": [s["loss"] for s in kept["steps"]],
            "grad1": jax.tree.map(
                lambda m: np.asarray(m) / (1 - run.ref.ADAM_B1),
                reference_layers(kept["mu1"])),
            "params0": reference_layers(kept["params0"]),
            "params3": reference_layers(kept["params3"])}


def _merged_draw(indptrs, indices, pairs):
    """The draw of a merged typed graph, as a per-relation walk's
    ``draw``: a target draws its fanout ONCE, without replacement, from
    the concatenation of its relations' lists (relation order), and each
    pick is typed by the list it came from; relation ``r``'s block holds
    that target's picks of ``r``."""
    import jax
    import jax.numpy as jnp
    from quiver_tpu.ops.sample import _fisher_yates_rows
    drawn = {}

    def draw(r, cur, k, sub):
        t = pairs[r][0]
        if t not in drawn:
            mine = [q for q, (a, _) in enumerate(pairs) if a == t]
            safe = jnp.clip(cur, 0)
            start = [indptrs[q][safe] for q in mine]
            deg = [jnp.where(cur >= 0, indptrs[q][safe + 1] - s, 0)
                   for q, s in zip(mine, start)]
            ends = jnp.cumsum(jnp.stack(deg, axis=1), axis=1)   # [C, R_t]
            pos = _fisher_yates_rows(jax.random.fold_in(sub, t),
                                     ends[:, -1], k)
            ok = jnp.arange(k)[None] < jnp.minimum(ends[:, -1], k)[:, None]
            which = jnp.sum(pos[..., None] >= ends[:, None, :], axis=-1)
            blocks = {}
            for j, q in enumerate(mine):
                before = ends[:, j] - deg[j]
                at = jnp.clip(start[j][:, None] + pos - before[:, None], 0,
                              indices[q].shape[0] - 1)
                blocks[q] = jnp.where(ok & (which == j), indices[q][at], -1)
            drawn[t] = blocks
        return drawn[t][r], None

    return draw


@functools.lru_cache(maxsize=None)
def _replay_for(fanout: tuple, fault, ref):
    """``replay(indptrs, graph, seeds, key) -> (ref.Sample, drops)``: the
    sample the timed step drew for these seeds under this key (the
    program's own per-relation walk, asked again), or under ``fault`` the
    planted sample (``merged_draw``; ``cap_drop``'s graph comes capped by
    the caller). The batch's type is the first, whose ids begin at 0."""
    import jax
    import jax.numpy as jnp
    from quiver_tpu.ops import sample_typed_multihop, typed_hop

    @jax.jit
    def replay(indptrs, graph, seeds, key):
        if fault == "merged_draw":
            types = graph.types
            frontier = [None] * types.num_types
            count = [None] * types.num_types
            frontier[0] = seeds
            count[0] = jnp.sum(seeds >= 0, dtype=jnp.int32)
            layers = []
            for i, k in enumerate(fanout):
                layer = typed_hop(
                    frontier, count, types.relations,
                    _merged_draw(indptrs, graph.indices, types.relations),
                    [k] * types.num_relations, jax.random.fold_in(key, i),
                    [graph.cap(t, i) for t in range(types.num_types)],
                    dense=True)
                layers.append(layer)
                frontier, count = layer.frontier, layer.count
        else:
            _, layers = sample_typed_multihop(indptrs, graph, seeds,
                                              list(fanout), key)
        return (ref.Sample(seeds, [ref.Hop(l.frontier, l.row, l.col)
                                   for l in layers]),
                sum(l.drops for l in layers))

    return replay


class TypedFacts(check.SampleFacts):
    """``check.SampleFacts`` with the picks a cap masked beside them."""

    def __init__(self):
        super().__init__()
        self.drops = 0


def check_typed_sample(sample, fanout, pairs, indptrs, row_values,
                       rng: np.random.Generator,
                       probe: int = PROBE_TARGETS) -> dict:
    """Hold a per-relation sample against the graph. ``indptrs[r]`` is
    the host's copy of relation ``r``'s row pointers, ``row_values(r,
    positions)`` its neighbour lists at those positions.

    For every hop and node type: the frontier before the hop is the
    prefix of the frontier after it, valid entries first and distinct,
    and after the hop it holds exactly that prefix and the hop's picks
    of that type, no other node and none missing. For every relation
    drawn: slot ``e`` belongs to target ``e // k``; a target holds
    exactly ``min(degree in r, k)`` picks and one with no node none;
    every pick's place holds a node; and, on all targets of a block of
    at most ``probe`` of them and on ``probe`` drawn from ``rng``
    otherwise, a target's picks are drawn from its r-row WITHOUT
    replacement (as a multiset, contained in the row's). Returns what
    ``check.SampleFacts`` adds up; ``bad`` must be 0. ``position_*``: the
    mean place of a pick in its row, over rows of ``2 k`` or more."""
    bad = edges = position_n = 0
    position_sum = 0.0
    before = [None] * len(sample.hops[0].frontier)
    before[0] = np.asarray(sample.seeds)
    for hop, k in zip(sample.hops, fanout):
        after = [None if f is None else np.asarray(f) for f in hop.frontier]
        picked = [[] for _ in after]
        for r, (t, s) in enumerate(pairs):
            col = None if hop.cols[r] is None else np.asarray(hop.cols[r])
            if col is None:
                bad += int(before[t] is not None)   # a frontier not drawn
                continue
            row = np.asarray(hop.rows[r])
            targets = before[t]
            n = targets.shape[0]
            valid = col >= 0
            bad += int((row[valid] != (np.arange(col.shape[0]) // k)[valid])
                       .sum())
            src = after[s]
            bad += int(((col >= src.shape[0])
                        | (src[np.clip(col, 0, src.shape[0] - 1)] < 0))
                       [valid].sum())
            ids = np.where(valid, src[np.clip(col, 0, src.shape[0] - 1)], -1)
            picked[s].append(ids[valid])
            ip = np.asarray(indptrs[r])
            safe = np.clip(targets, 0, None)
            deg = np.where(targets >= 0, ip[safe + 1] - ip[safe], 0)
            got = valid.reshape(n, k).sum(axis=1)
            bad += int((got != np.minimum(deg, k)).sum())
            edges += int(valid.sum())
            live = np.flatnonzero((targets >= 0) & (deg > 0))
            if live.shape[0] > probe:
                live = rng.choice(live, probe, replace=False)
            if not live.shape[0]:
                continue
            starts = ip[targets[live]].astype(np.int64)
            degs = deg[live].astype(np.int64)
            owner = np.repeat(np.arange(live.shape[0]), degs)
            within = np.arange(int(degs.sum())) - np.repeat(
                np.cumsum(degs) - degs, degs)
            values = np.asarray(row_values(r, starts[owner] + within))
            have = (owner.astype(np.int64) << 32) | values.astype(np.int64)
            mine = ids.reshape(n, k)[live]
            ok = valid.reshape(n, k)[live]
            want = ((np.arange(live.shape[0], dtype=np.int64)[:, None] << 32)
                    | mine.astype(np.int64))[ok]
            row_keys, row_counts = np.unique(have, return_counts=True)
            pick_keys, pick_counts = np.unique(want, return_counts=True)
            at = np.minimum(np.searchsorted(row_keys, pick_keys),
                            max(row_keys.shape[0] - 1, 0))
            held = row_counts[at] * (row_keys[at] == pick_keys)
            bad += int(np.maximum(pick_counts - held, 0).sum())
            order = np.argsort(have, kind="stable")
            first = np.minimum(np.searchsorted(have[order], want),
                               have.shape[0] - 1)
            owner_of = want >> 32
            wide = degs[owner_of] >= 2 * k
            if wide.any():
                place = within[order[first[wide]]] + 0.5
                position_sum += float((place / degs[owner_of[wide]]).sum())
                position_n += int(wide.sum())
        for t, f in enumerate(after):
            if f is None:
                bad += int(before[t] is not None)
                continue
            prev = before[t] if before[t] is not None else \
                np.zeros((0,), np.int32)
            v = int((prev >= 0).sum())
            bad += int((prev[:v] < 0).sum()) + int((f[:v] != prev[:v]).sum())
            nv = int((f >= 0).sum())
            bad += int((f[:nv] < 0).sum())               # valid entries first
            live = f[f >= 0]
            bad += int(live.shape[0] - np.unique(live).shape[0])
            want = np.union1d(prev[prev >= 0], np.concatenate(
                picked[t] + [np.zeros((0,), np.int64)]))
            bad += int(np.setxor1d(want, live).shape[0])
        before = after
    return {"bad": bad, "edges": edges, "position_sum": position_sum,
            "position_n": position_n}


@functools.lru_cache(maxsize=None)
def _grad_fn(ref, graph: tuple, dtype, half, fault):
    """The reference's loss and gradients, jitted once for each
    precision, batch cut and fault (``half``: the first rows only), so a
    second seed of ``readings`` compiles nothing."""
    import jax
    rows = None if half is None else slice(0, half)
    return jax.jit(lambda layers, feat, sample, labels, key:
                   ref.loss_and_grads(layers, feat, sample, dict(graph),
                                      labels, key, dtype=dtype, rows=rows,
                                      fault=fault))


def _graph_reader(indptrs, indices):
    """The host's copies of the relations' row pointers, and
    ``row_values(r, positions)``: relation ``r``'s list read on the
    device."""
    import jax
    import jax.numpy as jnp
    take = jax.jit(lambda idx, pos: idx[pos])

    def row_values(r, pos):
        # padded to a power of two: a few shapes, a few compiles
        n = pos.shape[0]
        wide = np.zeros((1 << max(n - 1, 1).bit_length(),), np.int32)
        wide[:n] = pos
        return np.asarray(take(indices[r], jnp.asarray(wide)))[:n]

    return [np.asarray(p) for p in indptrs], row_values


def follow(run: Run, kept: dict, *, precision="float32", fault=None,
           verify=True):
    """The reference through the first three steps, from the same weights,
    batches and keys, over the sample the step drew (replayed, and held
    against the graph) and rows it reads from the 16-bit table by itself.
    Returns its numbers and the sample check's facts; under a fault of
    the sample, the planted sample's."""
    import jax
    import jax.numpy as jnp
    dev0 = run.devices[0]
    indptrs = tuple(_on(p, dev0) for p in run.world["indptr"])
    indices = tuple(_on(i, dev0) for i in run.world["indices"])
    feat = _on(run.world["feat"], dev0)
    cfg = run.cell.config
    graph = relation_indices(cfg, indices)
    if fault == "cap_drop":
        # the papers' last frontier capped at half its cap
        names = list(cfg["node_types"])
        caps = [list(cfg["frontier_caps"][n]) for n in names]
        caps[0][-1] //= 2
        graph = relation_indices(cfg, indices, caps)
    replay = _replay_for(tuple(run.fanout),
                         fault if fault in SAMPLE_FAULTS else None, run.ref)
    host_ptrs, row_values = _graph_reader(indptrs, indices)
    rng = np.random.default_rng([run.seed, 5])
    dtype = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}[precision]
    half = run.batch // 2 if fault == "half_batch" else None
    own = fault if fault in run.ref.FAULTS else None
    grad_fn = _grad_fn(run.ref, tuple(sorted(run.graph.items())), dtype,
                       half, own)
    layers0 = jax.device_put(reference_layers(kept["params0"]), dev0)
    layers, opt = layers0, run.ref.adam_init(layers0)
    losses, first_grads, facts = [], None, TypedFacts()
    for t, st in enumerate(kept["steps"]):
        seeds = jax.device_put(st["seeds"], dev0)
        sample, drops = replay(indptrs, graph, seeds, st["key"])
        facts.drops += int(drops)
        if verify:
            facts.add(check_typed_sample(
                jax.device_get(sample), run.fanout, run.graph["pairs"],
                host_ptrs, row_values, rng))
        loss, grads = grad_fn(layers, feat, sample,
                              jax.device_put(run.labels[st["seeds"]], dev0),
                              jax.random.fold_in(st["key"], 1000))
        losses.append(float(loss))
        grads = jax.tree.map(lambda g: g.astype(jnp.float32), grads)
        if t == 0:
            first_grads = grads
        layers, opt = run.ref.adam_update(layers, grads, opt, run.lr)
    numbers = {"losses": losses, "grad1": jax.device_get(first_grads),
               "params0": jax.device_get(layers0),
               "params3": jax.device_get(layers)}
    return numbers, facts


def compare(run: Run, kept: dict) -> dict:
    ref, facts = follow(run, kept)
    return check.train_numbers(program_numbers(run, kept), ref, facts)
