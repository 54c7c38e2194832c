"""What the training entries share (``entries/train_step.py``,
``entries/dp_train_step.py``): a GraphSAGE step of ``quiver_tpu`` over a
replicated world, one batch a chip. An entry is ``TrainRun`` with its own
``build_step``.

Set-up builds ONE object, the compiled step with its state, drives it
from the seed through its first steps (the ones the reference follows)
and hands that same object to the window. The window is a closed loop
with a run-ahead of ``run_ahead`` steps; it ends on ``block_until_ready``
of the last step.
"""

from __future__ import annotations

import collections
import time

import numpy as np

from . import check, reference, traffic, world

CHECKED_STEPS = 3


def program_tree(layers):
    """The reference's layers in the tree ``quiver_tpu.models.GraphSAGE``
    keeps its weights in."""
    return {"params": {
        f"conv{i}": {"lin_root": {"kernel": l["w_root"], "bias": l["b"]},
                     "lin_nbr": {"kernel": l["w_nbr"]}}
        for i, l in enumerate(layers)}}


def reference_layers(tree):
    convs = tree["params"]
    return [{"w_root": convs[f"conv{i}"]["lin_root"]["kernel"],
             "b": convs[f"conv{i}"]["lin_root"]["bias"],
             "w_nbr": convs[f"conv{i}"]["lin_nbr"]["kernel"]}
            for i in range(len(convs))]


class TrainRun:
    """The compiled step, its state and its feed; ``run.py``'s interface
    (``setup``, ``window``, ``stop``, ``program_text``, ``free``,
    ``outcome``) and ``prove.py``'s (``readings``)."""

    def build_step(self, model, tx, mesh, **extra):
        """The program the window drives: the entry's own."""
        raise NotImplementedError

    def __init__(self, cell, seed: int, devices, faults=()):
        import jax
        import jax.numpy as jnp
        import optax
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        from quiver_tpu.models import GraphSAGE
        from quiver_tpu.parallel.train import TrainState

        cfg, mix = cell.config, cell.traffic
        self.cell, self.seed = cell, seed
        self.ref = cell.reference
        self.chips = cell.chips
        self.batch = int(mix["batch"])
        self.global_batch = self.batch * self.chips
        self.run_ahead = int(mix["run_ahead"])
        self.fanout = list(cfg["fanout"])
        self.lr = float(cfg["optimizer"]["learning_rate"])
        self.devices = devices[:self.chips]
        if self.chips > 1:
            mesh = Mesh(np.array(self.devices), ("data",))
            self.rep = NamedSharding(mesh, P())
            self.split = NamedSharding(mesh, P("data"))
        else:
            mesh = None
            self.rep = self.split = jax.sharding.SingleDeviceSharding(
                self.devices[0])
        self.world = world.make_world(cfg, seed, sharding=self.rep)
        model = GraphSAGE(hidden_dim=cfg["hidden_dim"],
                          out_dim=cfg["num_classes"],
                          num_layers=cfg["num_layers"],
                          dropout=cfg["dropout"])
        tx = optax.adam(self.lr)
        dims = cell.dims

        def make_state(key):
            params = program_tree(self.ref.init_layers(key, dims))
            return TrainState(params, tx.init(params),
                              jnp.zeros((), jnp.int32))

        self.state = jax.jit(make_state, out_shardings=self.rep)(
            jax.random.fold_in(world.seed_key(seed), 7))
        extra = {"loss_fn": _half_batch_loss} if "half_batch" in faults \
            else {}
        self.faults = tuple(faults)
        self.step = _plant(self.build_step(model, tx, mesh, **extra), faults)
        self.labels = np.asarray(self.world["labels"])
        self.batches = traffic.train_batches(mix, cfg, seed, self.global_batch)
        self.base_key = jax.random.fold_in(world.seed_key(seed), 11)
        self.steps_done = 0

    def feed(self):
        """The next step's inputs, put on the device: a fresh batch, its
        labels, a fresh key."""
        import jax
        seeds = next(self.batches)
        key = jax.random.fold_in(self.base_key, self.steps_done)
        return (seeds, jax.device_put(seeds, self.split),
                jax.device_put(self.labels[seeds], self.split), key)

    def call(self, fed):
        _, seeds, labels, key = fed
        w = self.world
        self.state, loss = self.step(self.state, w["feat"], None, w["indptr"],
                                     w["indices"], seeds, labels, key)
        self.steps_done += 1
        return loss

    def first_steps(self) -> dict:
        """Steps 1..3 through the window's own call and feed, keeping what
        the reference is compared with: the weights before, Adam's first
        moment after step 1, the weights after step 3, the three losses,
        and each step's batch and key."""
        import jax
        kept = {"params0": jax.device_get(self.state.params), "steps": []}
        for t in range(CHECKED_STEPS):
            fed = self.feed()
            loss = self.call(fed)
            kept["steps"].append({"seeds": fed[0], "key": fed[3],
                                  "loss": float(loss)})
            if t == 0:
                kept["mu1"] = jax.device_get(self.state.opt_state[0].mu)
        kept["params3"] = jax.device_get(self.state.params)
        return kept

    def window(self, seconds: float) -> dict:
        import jax
        inflight = collections.deque()
        enqueue = []
        losses = []
        t0 = time.perf_counter()
        steps = 0
        span = jax.profiler.TraceAnnotation      # free when no trace is on
        while time.perf_counter() - t0 < seconds:
            with span("chipbench.feed"):
                fed = self.feed()
            t = time.perf_counter()
            with span("chipbench.enqueue"):
                loss = self.call(fed)
            enqueue.append(time.perf_counter() - t)
            inflight.append(loss)
            losses.append(loss)
            steps += 1
            if len(inflight) > self.run_ahead:
                with span("chipbench.wait_oldest"):
                    inflight.popleft().block_until_ready()
        jax.block_until_ready((self.state, losses))
        wall = time.perf_counter() - t0
        losses = np.asarray(jax.device_get(losses), np.float64)
        return {"steps": steps, "wall_s": wall, "t0": t0,
                "seeds_per_s": steps * self.global_batch / wall,
                "enqueue_s": enqueue,
                "nonfinite": int((~np.isfinite(losses)).sum()),
                "first_loss": float(losses[0]), "last_loss": float(losses[-1])}

    def setup(self):
        """The first steps, kept for the check, then two settling calls:
        the loop's own rhythm."""
        import jax
        self.kept = self.first_steps()
        for _ in range(2):
            self.call(self.feed())
        jax.block_until_ready(self.state)

    def stop(self):
        """Nothing runs beside the loop, and a step keeps no counters."""
        return None

    def program_text(self) -> str:
        """The compiled text of the step the window drove, for the scopes
        of the trace's instructions (the persistent cache has it)."""
        if not hasattr(self.step, "jitted_fns"):    # a planted fault's wrapper
            return ""
        fn, w, fed = self.step.jitted_fns[-1], self.world, self.feed()
        return fn.lower(self.state, w["feat"], None, w["indptr"], w["indices"],
                        fed[1], fed[2], fed[3]).compile().as_text()

    def free(self):
        """Drop the program's state; the world stays for the reference."""
        self.state = None
        self.step = None

    def outcome(self, win: dict) -> dict:
        """The timed steps against the reference, and what the window
        counted."""
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
        in the reference put in the program's place. No window is needed."""
        kept = self.first_steps()
        self.free()
        ref, facts = follow(self, kept)

        def read(numbers):
            out = check.train_numbers(numbers, ref, facts)
            out.pop("facts")
            return out

        yield "program", read(program_numbers(self, kept)), {}
        if control:
            yield "control_bfloat16", read(follow(
                self, kept, precision="bfloat16", verify=False)[0]), {}
            for fault in ["half_batch"] + (["no_exchange"]
                                           if self.chips > 1 else []):
                yield "fault_" + fault, read(follow(
                    self, kept, fault=fault, verify=False)[0]), {}


def _plant(step, faults):
    """Two of the faults of `correct`'s own tests, planted around the timed
    call: ``state_unchanged`` returns the state it was given, and
    ``no_exchange`` traces the step with ``jax.lax.pmean`` taken out, so
    every chip keeps its own gradients (``half_batch`` is planted where
    the step is built). No command line reaches this."""
    import jax
    import jax.numpy as jnp
    if "no_exchange" in faults:
        inner = step

        def step(*args):
            real = jax.lax.pmean
            jax.lax.pmean = lambda x, axis_name, **kw: x
            try:
                return inner(*args)
            finally:
                jax.lax.pmean = real

    if "state_unchanged" not in faults:
        return step

    def broken(state, *args):
        kept = jax.tree.map(jnp.copy, state)
        _, loss = step(state, *args)
        return kept, loss

    return broken


def _half_batch_loss(logits, labels):
    """Half of the batch left out, the mean taken over the rest."""
    from quiver_tpu.parallel.train import cross_entropy_logits
    h = labels.shape[0] // 2
    return cross_entropy_logits(logits[:h], labels[:h])


def program_numbers(run: TrainRun, kept: dict) -> dict:
    """What the timed steps produced, in the reference's terms."""
    import jax
    return {"losses": [s["loss"] for s in kept["steps"]],
            "grad1": jax.tree.map(
                lambda m: np.asarray(m) / (1 - run.ref.ADAM_B1),
                reference_layers(kept["mu1"])),
            "params0": reference_layers(kept["params0"]),
            "params3": reference_layers(kept["params3"])}


def follow(run: TrainRun, kept: dict, *, precision="float32", fault=None,
           verify=True):
    """The reference through the first three steps, from the same weights,
    batches and keys. ``precision="bfloat16"`` is the control;
    ``fault`` plants ``half_batch`` or ``no_exchange`` in the reference
    put in the program's place. Returns its numbers and the sample check's
    ``check.SampleFacts`` (``verify=False`` skips that check, for a second
    pass over samples a first pass has held against the graph)."""
    import jax
    import jax.numpy as jnp
    dev0 = run.devices[0]
    local = {k: _on(v, dev0) for k, v in run.world.items() if k != "labels"}
    indptr_host, row_values = check.graph_reader(local["indptr"],
                                                 local["indices"])
    replay = check.sampler_replay(run.fanout)
    rng = np.random.default_rng([run.seed, 5])
    dtype = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}[precision]
    rows = slice(0, run.batch // 2) if fault == "half_batch" else None
    grad_fn = jax.jit(lambda layers, feat, sample, labels, key:
                      run.ref.loss_and_grads(layers, feat, sample, labels,
                                             key, dtype=dtype, rows=rows))
    layers0 = jax.device_put(reference_layers(kept["params0"]), dev0)
    layers, opt = layers0, run.ref.adam_init(layers0)
    losses, first_grads, facts = [], None, check.SampleFacts()
    # with the exchange left out, device 0 keeps its own shard's gradients
    shards = range(1) if fault == "no_exchange" else range(run.chips)
    for t, st in enumerate(kept["steps"]):
        outs = []
        for i in shards:
            seeds = st["seeds"][i * run.batch:(i + 1) * run.batch]
            key = st["key"] if run.chips == 1 else \
                jax.random.fold_in(st["key"], i)
            sample = replay(local["indptr"], local["indices"],
                            jax.device_put(seeds, dev0), key)
            if verify:
                facts.add(reference.check_sample(
                    jax.device_get(sample), run.fanout, indptr_host,
                    row_values, rng))
            outs.append(grad_fn(layers, local["feat"], sample,
                                jax.device_put(run.labels[seeds], dev0),
                                jax.random.fold_in(key, 1000)))
        losses.append(float(sum(o[0] for o in outs) / len(outs)))
        grads = jax.tree.map(
            lambda *g: sum(x.astype(jnp.float32) for x in g) / len(g),
            *[o[1] for o in outs])
        if t == 0:
            first_grads = grads
        layers, opt = run.ref.adam_update(layers, grads, opt, run.lr)
    numbers = {"losses": losses, "grad1": jax.device_get(first_grads),
               "params0": jax.device_get(layers0),
               "params3": jax.device_get(layers)}
    return numbers, facts


def compare(run: TrainRun, kept: dict) -> dict:
    """The numbers `correct` compares: the timed steps against the
    reference (``check.train_numbers``)."""
    ref, facts = follow(run, kept)
    return check.train_numbers(program_numbers(run, kept), ref, facts)


def _on(x, device):
    """The copy of a replicated array that sits on ``device``."""
    for s in x.addressable_shards:
        if s.device == device:
            return s.data
    return x
