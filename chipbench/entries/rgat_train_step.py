"""The entry ``rgat_train_step``: ``quiver_tpu.parallel.train
.build_train_step`` on one chip over ``quiver_tpu.models.MAG240MGNN(
model="gat", relations=R)``, OGB-LSC's R-GAT (per layer one ``GATConv`` a
relation, each edge slot projected by its own relation's weight, a skip
``Linear``, a batch norm over the valid targets of every type, ELU and
dropout, then the MLP head), over a typed graph (the world
``planted_typed``: node types as id ranges, a float16 table).

The step is the user's: ``build_train_step(model, adam, fanout, batch,
method="exact", collect_metrics=True)``, handed the float16 table as it
is and the graph's edge array as ``ops.relation.TypedIndices`` (the edge
array and its node types): the topology says it is typed, and the walk
gives every edge slot its relation. Its device counter block is kept a
step and read ONCE after the window; ``stop()`` hands the reducers the
counters folded over the steps, by name (``edge_valid`` / ``edge_cap``,
``edge_rel_max``: how many of the hops' valid edge slots the largest
relation holds).

``follow`` is the reference through the first steps: the sample replayed
by ``check.sampler_replay`` and held against the graph, each pick's CSR
slot asked of the same sampler (``eid=True``) and held against the graph
too, each edge's relation read from the graph's STORED relation at that
slot, the frontier's rows read from the table by plain indexing, and
``references/mag_rgat.py`` for the rest. Faults under the timed path:
``half_batch``, ``state_unchanged`` and ``norm_over_padding``.
``readings`` adds, planted in the reference put in the program's place,
the model's own ``one_relation``, ``self_edge`` and ``norm_over_padding``.
"""

from __future__ import annotations

import functools

import numpy as np

from chipbench import check, reference, spec, traffic, world
from chipbench.train_cell import TrainRun, _half_batch_loss, _on, _plant


def _needs_the_model():
    """Said before anything is built: a program that cannot train a
    relational model over a typed graph cannot run this cell, and says so
    at once."""
    from quiver_tpu import metrics, models, ops
    from quiver_tpu.pyg.sage_sampler import Adj
    lacks = [what for what, has in (
        ("ops.TypedIndices (a topology that states its node types)",
         hasattr(ops, "TypedIndices")),
        ("Adj.relation (a block that states each edge's relation)",
         "relation" in getattr(Adj, "__slots__", ())),
        ("MAG240MGNN.relations (the relational model)",
         "relations" in getattr(models.MAG240MGNN, "__dataclass_fields__",
                                {})),
        ("metrics.EDGE_REL_MAX (the walk's count of the largest relation)",
         hasattr(metrics, "EDGE_REL_MAX"))) if not has]
    if lacks:
        raise SystemExit(
            "chipbench: entry rgat_train_step needs " + ", ".join(lacks)
            + " (the MAG240M R-GAT trained through build_train_step over a "
            "typed graph); this program has none")


def node_types(cfg):
    """The configuration's types and relations as ``ops.NodeTypes``."""
    from quiver_tpu.ops import NodeTypes
    _, starts, _, rels = spec.plugin("worlds", "planted_typed").layout(cfg)
    return NodeTypes(starts=starts[1:],
                     relations=[(t, s) for t, s, _, _ in rels])


def program_tree(layers):
    """The reference's weights in the tree ``MAG240MGNN(model="gat",
    relations=R)`` keeps them in."""
    tree = {}
    for i, p in enumerate(layers["convs"]):
        tree[f"conv{i}"] = {"kernel": p["w"], "att_src": p["a_src"],
                            "att_dst": p["a_dst"], "bias": p["b"]}
        tree[f"skip{i}"] = {"kernel": p["w_skip"], "bias": p["b_skip"]}
        tree[f"norm{i}"] = {"scale": p["bn_scale"], "bias": p["bn_bias"]}
    head = layers["head"]
    tree["mlp0"] = {"kernel": head["w0"], "bias": head["b0"]}
    tree["mlp_norm"] = {"scale": head["bn_scale"], "bias": head["bn_bias"]}
    tree["mlp1"] = {"kernel": head["w1"], "bias": head["b1"]}
    return {"params": tree}


def reference_layers(tree):
    t = tree["params"]
    convs = []
    for i in range(sum(k.startswith("conv") for k in t)):
        c, s, n = t[f"conv{i}"], t[f"skip{i}"], t[f"norm{i}"]
        convs.append({"w": c["kernel"], "a_src": c["att_src"],
                      "a_dst": c["att_dst"], "b": c["bias"],
                      "w_skip": s["kernel"], "b_skip": s["bias"],
                      "bn_scale": n["scale"], "bn_bias": n["bias"]})
    return {"convs": convs,
            "head": {"w0": t["mlp0"]["kernel"], "b0": t["mlp0"]["bias"],
                     "w1": t["mlp1"]["kernel"], "b1": t["mlp1"]["bias"],
                     "bn_scale": t["mlp_norm"]["scale"],
                     "bn_bias": t["mlp_norm"]["bias"]}}


def _blind_to_padding(model):
    """The fault ``norm_over_padding`` under the timed path: the model is
    handed blocks that do not state their valid targets, so it takes every
    target slot for a node."""
    from quiver_tpu.pyg.sage_sampler import Adj

    class Blind:
        def apply(self, params, x, adjs, **kw):
            return model.apply(params, x, [
                Adj(a.edge_index, a.e_id, a.size, a.mask, a.fanout, None,
                    a.relation, a.relations) for a in adjs], **kw)

    return Blind()


class Run(TrainRun):
    def __init__(self, cell, seed: int, devices, faults=()):
        _needs_the_model()
        import jax
        import jax.numpy as jnp
        import optax
        from quiver_tpu.models import MAG240MGNN
        from quiver_tpu.ops import TypedIndices
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
        self.types = node_types(cfg)
        self.typed = TypedIndices(self.world["indices"], self.types)
        relations = int(cfg["num_relations"])
        model = MAG240MGNN(model="gat", hidden_dim=cfg["hidden_dim"],
                           out_dim=cfg["num_classes"],
                           num_layers=cfg["num_layers"], heads=cfg["heads"],
                           dropout=cfg["dropout"], relations=relations)
        tx = optax.adam(self.lr)

        def make_state(key):
            params = program_tree(self.ref.init_layers(
                key, cfg["feature_dim"], cfg["hidden_dim"],
                cfg["num_classes"], cfg["num_layers"], cfg["heads"],
                relations))
            return TrainState(params, tx.init(params),
                              jnp.zeros((), jnp.int32))

        self.state = jax.jit(make_state, out_shardings=self.rep)(
            jax.random.fold_in(world.seed_key(seed), 7))
        extra = {"loss_fn": _half_batch_loss} if "half_batch" in faults \
            else {}
        if "norm_over_padding" in faults:
            model = _blind_to_padding(model)
        inner = build_train_step(model, tx, self.fanout, self.batch,
                                 method="exact", collect_metrics=True,
                                 **extra)
        self.counter_blocks = []

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
        by name."""
        import jax
        from quiver_tpu import metrics
        blocks = np.asarray(jax.device_get(self.counter_blocks))   # [T, N]
        self.counter_blocks = []
        return metrics.counters_dict(blocks)

    def outcome(self, win: dict) -> dict:
        numbers = compare(self, self.kept)
        shown = numbers.pop("facts")
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

        def read(numbers):
            out = check.train_numbers(numbers, ref, facts)
            out.pop("facts")
            return out

        sound = program_numbers(self, kept)
        yield "program", read(sound), {
            k: counters[k] for k in ("frontier_valid", "frontier_cap",
                                     "edge_valid", "edge_cap",
                                     "edge_rel_max")}
        if control:
            yield "control_bfloat16", read(follow(
                self, kept, precision="bfloat16", verify=False)[0]), {}
            for fault in ("half_batch",) + tuple(self.ref.FAULTS):
                yield "fault_" + fault, read(follow(
                    self, kept, fault=fault, verify=False)[0]), {}
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


@functools.lru_cache(maxsize=None)
def _slot_replay(fanout: tuple):
    """``replay(indptr, indices, relation, seeds, key) -> (sample,
    relations, bad)``: the same draw asked of the program's sampler with
    each pick's CSR slot (``eid=True``), every valid slot held against the
    graph (it lies in its target's row and holds the pick) and the stored
    relation read at it by plain indexing; ``bad`` counts the slots that
    fail."""
    import jax
    import jax.numpy as jnp
    from quiver_tpu.ops import sample_multihop

    @jax.jit
    def replay(indptr, indices, relation, seeds, key):
        _, layers = sample_multihop(indptr, indices, seeds, list(fanout), key,
                                    method="exact", seeds_dense=True,
                                    eid=True)
        rels, bad, targets = [], jnp.int32(0), seeds
        for l in layers:
            slot, valid = l.e_id, l.col >= 0
            at = jnp.clip(slot, 0)
            tgt = targets[jnp.clip(l.row, 0)]
            pick = l.n_id[jnp.clip(l.col, 0)]
            good = (indices[at] == pick) & (indptr[jnp.clip(tgt, 0)] <= slot) \
                & (slot < indptr[jnp.clip(tgt, 0) + 1])
            bad = bad + jnp.sum(valid & ~good) + jnp.sum(valid != (slot >= 0))
            rels.append(jnp.where(valid, relation[at].astype(jnp.int32), -1))
            targets = l.n_id
        return reference.Sample(
            seeds, [reference.Hop(l.n_id, l.row, l.col) for l in layers]), \
            rels, bad

    return replay


def follow(run: Run, kept: dict, *, precision="float32", fault=None,
           verify=True):
    """The reference through the first three steps, from the same weights,
    batches and keys, over the sample the step drew (replayed, and held
    against the graph), the relations the graph stores for its slots and
    rows it reads from the 16-bit table by itself. Returns its numbers and
    the sample check's ``check.SampleFacts``."""
    import jax
    import jax.numpy as jnp
    dev0 = run.devices[0]
    indptr, indices, relation, feat = (
        _on(run.world[k], dev0)
        for k in ("indptr", "indices", "relation", "feat"))
    indptr_host, row_values = check.graph_reader(indptr, indices)
    replay = check.sampler_replay(run.fanout)
    slots = _slot_replay(tuple(run.fanout))
    rng = np.random.default_rng([run.seed, 5])
    dtype = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}[precision]
    rows = slice(0, run.batch // 2) if fault == "half_batch" else None
    own = fault if fault in run.ref.FAULTS else None
    grad_fn = jax.jit(lambda layers, feat, sample, rels, labels, key:
                      run.ref.loss_and_grads(layers, feat, sample, rels,
                                             labels, key, dtype=dtype,
                                             rows=rows, fault=own))
    layers0 = jax.device_put(reference_layers(kept["params0"]), dev0)
    layers, opt = layers0, run.ref.adam_init(layers0)
    losses, first_grads, facts = [], None, check.SampleFacts()
    for t, st in enumerate(kept["steps"]):
        seeds = jax.device_put(st["seeds"], dev0)
        sample = replay(indptr, indices, seeds, st["key"])
        again, rels, bad = slots(indptr, indices, relation, seeds, st["key"])
        if verify:
            got = reference.check_sample(
                jax.device_get(sample), run.fanout, indptr_host, row_values,
                rng)
            # the slot replay drew what the replay drew, slot for slot
            same = sum(int(jnp.sum(a != b)) for h, g in
                       zip(sample.hops, again.hops) for a, b in zip(h, g))
            got["bad"] += int(bad) + same
            facts.add(got)
        loss, grads = grad_fn(layers, feat, sample, rels,
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
