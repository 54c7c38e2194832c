"""The entry ``gat_train_step``: ``quiver_tpu.parallel.train
.build_train_step`` on one chip over ``quiver_tpu.models.MAG240MGNN(
model="gat")``, the MAG240M benchmark's attention model (two ``GATConv``
layers of four heads, each with a skip ``Linear``, a batch norm over the
valid targets, ELU and dropout, then the MLP head), reading a table kept
in a 16-bit float (the world ``planted_half``).

The step is the user's: ``build_train_step(model, adam, fanout, batch,
method="exact", collect_metrics=True)``, handed the 16-bit table as it
is. Its device counter block is kept a step and read ONCE after the
window; ``stop()`` hands the reducers the counters folded over the steps,
by name (``frontier_valid`` / ``frontier_cap``, ``edge_valid`` /
``edge_cap``: what share of the products' rows and of the attention's
edge slots held a node or an edge).

``follow`` is the reference through the first steps: the sample replayed
by ``check.sampler_replay`` and held against the graph, the frontier's
rows read from the 16-bit table by plain indexing, and
``references/mag_gat.py`` for the rest. Faults under the timed path:
``half_batch``, ``state_unchanged`` and ``norm_over_padding`` (the model
is handed blocks that do not say which targets are nodes). ``readings``
adds, planted in the reference put in the program's place, the model's
own ``no_self_edge`` and ``norm_over_padding``.
"""

from __future__ import annotations

import numpy as np

from chipbench import check, reference, traffic, world
from chipbench.train_cell import TrainRun, _half_batch_loss, _on, _plant


def _needs_the_model():
    """Said before anything is built: a program whose ``MAG240MGNN`` is
    not the published model cannot run this cell, and says so at once."""
    from quiver_tpu import metrics, models
    from quiver_tpu.pyg.sage_sampler import Adj
    lacks = [what for what, has in (
        ("models.MaskedBatchNorm (batch statistics over the valid rows)",
         hasattr(models, "MaskedBatchNorm")),
        ("Adj.valid_targets (a block that says which targets are nodes)",
         "valid_targets" in getattr(Adj, "__slots__", ())),
        ("metrics.EDGE_VALID (the walk's edge-slot counters)",
         hasattr(metrics, "EDGE_VALID"))) if not has]
    if lacks:
        raise SystemExit(
            "chipbench: entry gat_train_step needs " + ", ".join(lacks)
            + " (the MAG240M attention model trained through "
            "build_train_step); this program has none")


def program_tree(layers):
    """The reference's weights in the tree ``MAG240MGNN(model="gat")``
    keeps them in."""
    tree = {}
    for i, p in enumerate(layers["convs"]):
        tree[f"conv{i}"] = {"lin": {"kernel": p["w"]}, "att_src": p["a_src"],
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
        convs.append({"w": c["lin"]["kernel"], "a_src": c["att_src"],
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
                Adj(a.edge_index, a.e_id, a.size, a.mask, a.fanout)
                for a in adjs], **kw)

    return Blind()


class Run(TrainRun):
    def __init__(self, cell, seed: int, devices, faults=()):
        _needs_the_model()
        import jax
        import jax.numpy as jnp
        import optax
        from quiver_tpu.models import MAG240MGNN
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
        model = MAG240MGNN(model="gat", hidden_dim=cfg["hidden_dim"],
                           out_dim=cfg["num_classes"],
                           num_layers=cfg["num_layers"], heads=cfg["heads"],
                           dropout=cfg["dropout"])
        tx = optax.adam(self.lr)

        def make_state(key):
            params = program_tree(self.ref.init_layers(
                key, cfg["feature_dim"], cfg["hidden_dim"],
                cfg["num_classes"], cfg["num_layers"], cfg["heads"]))
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
        self.batches = traffic.train_batches(mix, cfg, seed, self.batch)
        self.base_key = jax.random.fold_in(world.seed_key(seed), 11)
        self.steps_done = 0

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
                                     "edge_valid", "edge_cap")}
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


def follow(run: Run, kept: dict, *, precision="float32", fault=None,
           verify=True):
    """The reference through the first three steps, from the same weights,
    batches and keys, over the sample the step drew (replayed, and held
    against the graph) and rows it reads from the 16-bit table by itself.
    Returns its numbers and the sample check's ``check.SampleFacts``."""
    import jax
    import jax.numpy as jnp
    dev0 = run.devices[0]
    indptr, indices, feat = (_on(run.world[k], dev0)
                             for k in ("indptr", "indices", "feat"))
    indptr_host, row_values = check.graph_reader(indptr, indices)
    replay = check.sampler_replay(run.fanout)
    rng = np.random.default_rng([run.seed, 5])
    dtype = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}[precision]
    rows = slice(0, run.batch // 2) if fault == "half_batch" else None
    own = fault if fault in run.ref.FAULTS else None
    grad_fn = jax.jit(lambda layers, feat, sample, labels, key:
                      run.ref.loss_and_grads(layers, feat, sample, labels,
                                             key, dtype=dtype, rows=rows,
                                             fault=own))
    layers0 = jax.device_put(reference_layers(kept["params0"]), dev0)
    layers, opt = layers0, run.ref.adam_init(layers0)
    losses, first_grads, facts = [], None, check.SampleFacts()
    for t, st in enumerate(kept["steps"]):
        sample = replay(indptr, indices, jax.device_put(st["seeds"], dev0),
                        st["key"])
        if verify:
            facts.add(reference.check_sample(
                jax.device_get(sample), run.fanout, indptr_host, row_values,
                rng))
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
