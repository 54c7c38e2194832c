"""The entry ``dist_train_step``: ``quiver_tpu.parallel.dist
.build_dist_train_step`` over a ``("host",)`` mesh of the cell's chips. The
table is row-sharded over the chips (the world ``planted_rows_sharded``:
no chip and no host holds it whole), the graph and the partition book are
replicated, and a step's feature rows arrive through ``DistFeature``'s
compact exchange under the ``exchange_cap`` the cell's file states.

The store is built the user's way: ``PartitionInfo`` from the book,
``DistFeature.from_shards`` from the shards where they lie, and the step
is handed ``dist._spmd_feat``, ``info.global2host``, ``info.global2local``.
The step runs with ``collect_metrics=True``: its device counter block is
kept a step and read ONCE after the window (``exchange_overflow``, a
number `correct` compares, is the steps whose lookup needed a round beyond
the first; ``stop()`` hands the folded counters to the reducers).

``follow`` is the reference through the first steps, chip by chip: the
step folds ``axis_index`` into its key, so chip ``i``'s sample is replayed
with ``fold_in(key, i)`` and its own slice of the seeds; the frontier's
rows are read from the shards one by one, each on its own chip, through
the book alone (``feat[g2h * rows_per_chip + g2l]``); ``references/sage.py``
does the rest, and the chips' gradients are averaged as the all-reduce
does. Faults: ``no_exchange`` (rows another chip owns come back zero),
``half_batch``, ``state_unchanged``.
"""

from __future__ import annotations

import functools

import numpy as np

from chipbench import check, reference, traffic, world
from chipbench.train_cell import (TrainRun, _half_batch_loss, _on,
                                  program_numbers, program_tree,
                                  reference_layers)

AXIS = "host"


class Run(TrainRun):
    def __init__(self, cell, seed: int, devices, faults=()):
        import jax
        import jax.numpy as jnp
        import optax
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        import quiver_tpu as qv
        from quiver_tpu.models import GraphSAGE
        from quiver_tpu.parallel.dist import build_dist_train_step
        from quiver_tpu.parallel.train import TrainState
        if not hasattr(qv.DistFeature, "from_shards"):
            # said before anything is built: a program without the
            # constructor cannot run this cell, and says so at once
            raise SystemExit(
                "chipbench: entry dist_train_step needs "
                "quiver_tpu.DistFeature.from_shards (a store built from "
                "shards on their devices); this program has none")

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
        self.faults = tuple(faults)
        mesh = Mesh(np.array(self.devices), (AXIS,))
        self.rep = NamedSharding(mesh, P())
        self.split = NamedSharding(mesh, P(AXIS))

        self.world = world.make_world(cfg, seed, sharding=mesh)
        # the labels are the host's to feed; the chips keep no copy
        self.labels = np.asarray(self.world.pop("labels"))
        info = qv.PartitionInfo(hosts=self.chips,
                                global2host=self.world["g2h"],
                                global2local=self.world["g2l"])
        comm = qv.TpuComm(rank=0, world_size=self.chips, mesh=mesh, axis=AXIS)
        cap = int(cell.cell["exchange_cap"])
        self.dist = qv.DistFeature.from_shards(self.world["feat"], info, comm,
                                               exchange_cap=cap)
        self.rows_per_chip = self.dist._rows_per_host

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
        self.step = build_dist_train_step(
            model, tx, self.fanout, self.batch, mesh,
            rows_per_host=self.rows_per_chip, axis=AXIS, exchange_cap=cap,
            collect_metrics=True, **extra)
        self.batches = traffic.train_batches(mix, cfg, seed, self.global_batch)
        self.base_key = jax.random.fold_in(world.seed_key(seed), 11)
        self.steps_done = 0
        self.counter_blocks = []

    def _args(self, fed):
        _, seeds, labels, key = fed
        w, info = self.world, self.dist.info
        return (self.state, self.dist._spmd_feat, info.global2host,
                info.global2local, w["indptr"], w["indices"], seeds, labels,
                key)

    def call(self, fed):
        import jax
        import jax.numpy as jnp
        args = self._args(fed)
        # the step donates its state: the fault hands back a copy of it
        kept = jax.tree.map(jnp.copy, self.state) \
            if "state_unchanged" in self.faults else None
        if "no_exchange" in self.faults:
            with _rows_of_others_zeroed():
                state, loss, block = self.step(*args)
        else:
            state, loss, block = self.step(*args)
        self.state = state if kept is None else kept
        self.counter_blocks.append(block)
        self.steps_done += 1
        return loss

    def program_text(self) -> str:
        fn = self.step.jitted_fns[-1]           # the arity without a rows view
        return fn.lower(*self._args(self.feed())).compile().as_text()

    def stop(self):
        """The steps' counter blocks, read now and folded over steps and
        chips (add slots summed, max slots by their maximum), by name."""
        import jax
        from quiver_tpu import metrics
        blocks = np.asarray(jax.device_get(self.counter_blocks))  # [T, H, N]
        self.counter_blocks = []
        self.overflow_steps = int(
            (blocks[:, :, metrics.EXCH_FALLBACK].max(axis=1) > 0).sum())
        return metrics.counters_dict(blocks)

    def outcome(self, win: dict) -> dict:
        numbers = compare(self, self.kept)
        shown = numbers.pop("facts")
        numbers["nonfinite_losses"] = float(win["nonfinite"])
        numbers["exchange_overflow"] = float(self.overflow_steps)
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
            out["exchange_overflow"] = float(self.overflow_steps)
            return out

        sound = program_numbers(self, kept)
        yield "program", read(sound), {
            k: counters[k] for k in ("exchange_bucket_max", "exchange_cap",
                                     "dedup_unique", "dedup_total")}
        if control:
            yield "control_bfloat16", read(follow(
                self, kept, precision="bfloat16", verify=False)[0]), {}
            for fault in ("half_batch", "no_exchange"):
                yield "fault_" + fault, read(follow(
                    self, kept, fault=fault, verify=False)[0]), {}
            yield "fault_state_unchanged", read(
                dict(sound, params3=sound["params0"])), {}


class _rows_of_others_zeroed:
    """The fault ``no_exchange`` under the timed call: while the step is
    traced, the lookup's rows that another chip owns come back zero."""

    def __enter__(self):
        import jax
        import jax.numpy as jnp
        from quiver_tpu.parallel import dist
        self.real = real = dist.dist_lookup_local

        def broken(ids, g2h, loc, feat, axis, *args, **kw):
            rows = real(ids, g2h, loc, feat, axis, *args, **kw)
            mine = g2h[jnp.clip(ids, 0)] == jax.lax.axis_index(axis)
            return jnp.where(mine[:, None], rows, 0)

        dist.dist_lookup_local = broken

    def __exit__(self, *exc):
        from quiver_tpu.parallel import dist
        dist.dist_lookup_local = self.real


@functools.lru_cache(maxsize=None)
def _shard_reader():
    """``read(shard, g2h, g2l, ids, h)``: the rows of ``ids`` that chip
    ``h`` owns, out of its shard; zero for every other slot."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def read(shard, g2h, g2l, ids, h):
        safe = jnp.clip(ids, 0)
        mine = (ids >= 0) & (g2h[safe] == h)
        return jnp.where(mine[:, None], shard[jnp.where(mine, g2l[safe], 0)],
                         0)

    return read


def frontier_rows(run: Run, n_id, owners=None):
    """The table's rows of a -1-padded frontier, read shard by shard
    through the book alone, each shard on the chip that holds it; brought
    to chip 0. ``owners`` keeps only those chips' rows (the others read
    zero)."""
    import jax
    w, dev0, read = run.world, run.devices[0], _shard_reader()
    total = None
    for h in (range(run.chips) if owners is None else owners):
        dev = run.devices[h]
        part = jax.device_put(
            read(_on(w["feat"], dev), _on(w["g2h"], dev), _on(w["g2l"], dev),
                 jax.device_put(n_id, dev), h), dev0)
        total = part if total is None else total + part
    return total


def follow(run: Run, kept: dict, *, precision="float32", fault=None,
           verify=True):
    """The reference through the first three steps, from the same weights,
    batches and keys, chip by chip. Returns its numbers and the sample
    check's ``check.SampleFacts``."""
    import jax
    import jax.numpy as jnp
    dev0 = run.devices[0]
    indptr, indices = (_on(run.world[k], dev0) for k in ("indptr", "indices"))
    indptr_host, row_values = check.graph_reader(indptr, indices)
    replay = check.sampler_replay(run.fanout)
    rng = np.random.default_rng([run.seed, 5])
    dtype = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}[precision]
    rows = slice(0, run.batch // 2) if fault == "half_batch" else None
    grad_fn = jax.jit(lambda layers, x, sample, labels, key:
                      run.ref.loss_and_grads(layers, x, sample, labels, key,
                                             dtype=dtype, rows=rows))
    layers0 = jax.device_put(reference_layers(kept["params0"]), dev0)
    layers, opt = layers0, run.ref.adam_init(layers0)
    losses, first_grads, facts = [], None, check.SampleFacts()
    for t, st in enumerate(kept["steps"]):
        outs = []
        for i in range(run.chips):
            seeds = st["seeds"][i * run.batch:(i + 1) * run.batch]
            key = jax.random.fold_in(st["key"], i)
            sample = replay(indptr, indices, jax.device_put(seeds, dev0), key)
            if verify:
                facts.add(reference.check_sample(
                    jax.device_get(sample), run.fanout, indptr_host,
                    row_values, rng))
            last = sample.hops[-1]
            x = frontier_rows(run, last.n_id,
                              owners=[i] if fault == "no_exchange" else None)
            # the block stands where the table stood: slot j reads row j
            slots = jnp.where(last.n_id >= 0,
                              jnp.arange(last.n_id.shape[0], dtype=jnp.int32),
                              -1)
            held = reference.Sample(sample.seeds, list(sample.hops[:-1]) + [
                reference.Hop(slots, last.row, last.col)])
            outs.append(grad_fn(layers, x, held,
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


def compare(run: Run, kept: dict) -> dict:
    ref, facts = follow(run, kept)
    return check.train_numbers(program_numbers(run, kept), ref, facts)
