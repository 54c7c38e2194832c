"""The entry ``tiered_train_step``: ``quiver_tpu.parallel.train
.build_train_step`` on one chip over a ``quiver_tpu.Feature`` store that
is LARGER than the chip's memory (the world ``planted_tiered``: rows in
degree order, the hotter ``hot_rows`` in HBM, the rest in the host's
pinned memory, never whole anywhere).

The store is built the user's way: ``Feature(host_placement="offload",
allow_fallback=False, cold_budget=<the configuration's>,
dedup_cold=False).from_tiers(feat_hot, feat_cold, order)`` (a frontier's
``n_id`` is distinct already, so a dedup of the cold ids buys nothing),
``parallel.frontier.feature_splice(store)`` gives ``(feat, forder,
gather)``, and the step is ``build_train_step(..., gather=gather,
collect_metrics=True)``, handed the ``(device_part, host_tier)`` pair and
the order map. A backend that cannot pin the cold tier fails loudly: no
default placement stands in. The step's device counter block is kept a
step and read ONCE after the window; ``stop()`` hands the reducers
``hot_rows``, ``cold_rows``, ``lookup_rows`` (their sum),
``cold_budget_rows`` (``cold_budget`` x lookups) and ``cold_overflow``
(lookups whose cold count passed the budget and read every slot from the
host: a number `correct` compares, limit 0).

``follow`` is the reference through the first steps. It reads the
frontier's rows BY ITSELF, by plain indexing: ``order`` on the host, hot
rows ``feat_hot[t]`` on the device, cold rows out of the host's copy of
the pinned array (``numpy`` fancy indexing), nothing of the store's
lookup. ``row_gap`` holds the store's own lookup of the checked steps'
frontiers (the same ``Feature``, the same tiers, the same budget) against
those rows, exactly. Faults: ``no_cold`` (the cold rows read zero),
``stale_order`` (rows read through another seed's order map),
``half_batch``, ``state_unchanged``.
"""

from __future__ import annotations

import functools

import numpy as np

from chipbench import check, reference, spec
from chipbench.train_cell import (TrainRun, _on, program_numbers,
                                  reference_layers)

OWN_FAULTS = ("no_cold", "stale_order")


def _needs_the_splice():
    """Said before anything is built: a program whose train step takes no
    store cannot run this cell, and says so at once."""
    import inspect
    from quiver_tpu.parallel import frontier, train
    import quiver_tpu as qv
    lacks = [what for what, has in (
        ("parallel.frontier.feature_splice",
         hasattr(frontier, "feature_splice")),
        ("build_train_step(gather=)", "gather" in inspect.signature(
            train.build_train_step).parameters),
        ("Feature.from_tiers", hasattr(qv.Feature, "from_tiers"))) if not has]
    if lacks:
        raise SystemExit(
            "chipbench: entry tiered_train_step needs " + ", ".join(lacks)
            + " (a train step that reads a tiered Feature store through "
            "the one splice); this program has none")


class Run(TrainRun):
    def __init__(self, cell, seed: int, devices, faults=()):
        _needs_the_splice()
        self.hot = int(cell.config["hot_rows"])
        self.cold_budget = int(cell.config["cold_budget"])
        self.counter_blocks = []
        self._host_copies = {}          # made once: see ``host_copy``
        super().__init__(cell, seed, devices, faults)

    def build_step(self, model, tx, mesh, **extra):
        import jax
        import quiver_tpu as qv
        from quiver_tpu.parallel.frontier import feature_splice
        from quiver_tpu.parallel.train import build_train_step
        w = self.world
        cold, order = w["feat_cold"], w["order"]
        if "no_cold" in self.faults:
            cold = jax.device_put(np.zeros(cold.shape, cold.dtype),
                                  cold.sharding)
        if "stale_order" in self.faults:
            order = self.other_order()
        self.store = qv.Feature(
            host_placement="offload", allow_fallback=False,
            cold_budget=self.cold_budget, dedup_cold=False).from_tiers(
                w["feat_hot"], cold, order)
        if self.store._host_offload is None:
            raise SystemExit("chipbench: the cold tier is not in pinned "
                             "host memory")
        self.feat, self.forder, gather = feature_splice(self.store)
        inner = build_train_step(model, tx, self.fanout, self.batch,
                                 method="exact", gather=gather,
                                 collect_metrics=True, **extra)

        def step(state, *args):
            # the counters stay on the device until the window is over
            state, loss, block = inner(state, *args)
            self.counter_blocks.append(block)
            return state, loss

        step.jitted_fns = inner.jitted_fns
        return step

    def other_order(self):
        """The order map of the NEXT seed's world (the fault
        ``stale_order``): a bijection still, the wrong one."""
        import jax
        if "other_order" not in self._host_copies:
            nodes = int(self.cell.config["nodes"])
            recipe = spec.plugin("worlds", self.cell.named("world"))
            self._host_copies["other_order"] = jax.jit(functools.partial(
                recipe.order_map, nodes,
                *recipe.rank_map(nodes, self.seed + 1)))()
        return self._host_copies["other_order"]

    def _args(self, fed):
        _, seeds, labels, key = fed
        w = self.world
        return (self.state, self.feat, self.forder, w["indptr"],
                w["indices"], seeds, labels, key)

    def call(self, fed):
        self.state, loss = self.step(*self._args(fed))
        self.steps_done += 1
        return loss

    def program_text(self) -> str:
        if not hasattr(self.step, "jitted_fns"):    # a planted fault's wrapper
            return ""
        return self.step.jitted_fns[-1].lower(
            *self._args(self.feed())).compile().as_text()

    def stop(self):
        """The steps' counter blocks, read now and folded over the steps,
        by name, with the two sums the metrics divide by."""
        import jax
        from quiver_tpu import metrics
        blocks = np.asarray(jax.device_get(self.counter_blocks))   # [T, N]
        self.counter_blocks = []
        out = metrics.counters_dict(blocks)
        self.overflow_steps = int((blocks[:, metrics.COLD_OVERFLOW] > 0).sum())
        self.cold_max = int(blocks[:, metrics.COLD_ROWS].max())
        out["lookup_rows"] = out["hot_rows"] + out["cold_rows"]
        out["cold_budget_rows"] = self.cold_budget * out["lookup_calls"]
        return out

    # -- the reference's own reading of the table ---------------------------

    def host_copy(self, name: str):
        """The host's numpy copy of one of the world's arrays (the pinned
        cold tier among them), made once."""
        if name not in self._host_copies:
            self._host_copies[name] = np.asarray(self.world[name])
        return self._host_copies[name]

    def read_rows(self, n_id, fault=None):
        """The table's rows of a -1-padded frontier by plain indexing:
        node -> storage row through ``order``, rows under ``hot_rows`` out
        of ``feat_hot``, the others out of ``feat_cold``; empty slots are
        zero. ``fault`` reads as a broken store would."""
        import jax.numpy as jnp
        ids = np.asarray(n_id)
        order = np.asarray(self.other_order()) if fault == "stale_order" \
            else self.host_copy("order")
        t = order[np.clip(ids, 0, None)]
        valid = ids >= 0
        at = np.flatnonzero(valid & (t >= self.hot))
        rows = self.host_copy("feat_cold")[t[at] - self.hot]
        if fault == "no_cold":
            rows = np.zeros_like(rows)
        # padded to a power of two, so that a handful of shapes compile
        pad = (1 << max(len(at) - 1, 0).bit_length()) - len(at)
        at = np.concatenate([at, np.full(pad, len(ids))]).astype(np.int32)
        rows = np.concatenate([rows, np.zeros((pad,) + rows.shape[1:],
                                              rows.dtype)])
        return _assemble()(self.world["feat_hot"],
                           jnp.asarray(np.where(valid & (t < self.hot), t,
                                                -1).astype(np.int32)),
                           jnp.asarray(at), jnp.asarray(rows))

    def store_rows(self, n_id):
        """The same frontier through the store's own lookup."""
        return self.store.getitem_masked(n_id)

    def outcome(self, win: dict) -> dict:
        numbers = compare(self, self.kept)
        shown = dict(numbers.pop("facts"), cold_rows_max_step=self.cold_max,
                     cold_budget=self.cold_budget)
        numbers["nonfinite_losses"] = float(win["nonfinite"])
        numbers["cold_overflow"] = float(self.overflow_steps)
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
        ref, facts, frontiers = follow(self, kept)

        def read(numbers, rows_fault=None):
            out = check.train_numbers(numbers, ref, facts)
            out.pop("facts")
            out["row_gap"] = row_gap(self, frontiers, rows_fault)
            out["cold_overflow"] = float(self.overflow_steps)
            return out

        sound = program_numbers(self, kept)
        yield "program", read(sound), {
            "cold_rows_max_step": self.cold_max,
            **{k: counters[k] for k in ("hot_rows", "cold_rows",
                                        "lookup_calls")}}
        if control:
            yield "control_bfloat16", read(follow(
                self, kept, precision="bfloat16", verify=False)[0]), {}
            yield "fault_half_batch", read(follow(
                self, kept, fault="half_batch", verify=False)[0]), {}
            for fault in OWN_FAULTS:
                yield "fault_" + fault, read(follow(
                    self, kept, fault=fault, verify=False)[0], fault), {}
            yield "fault_state_unchanged", read(
                dict(sound, params3=sound["params0"])), {}


@functools.lru_cache(maxsize=None)
def _assemble():
    """``(feat_hot, hot_t, cold_at, cold_rows) -> x``: hot slots read
    their row of the device tier (``hot_t`` is -1 elsewhere), then the
    cold rows are laid over their slots."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def assemble(feat_hot, hot_t, cold_at, cold_rows):
        x = feat_hot[jnp.clip(hot_t, 0)] * (hot_t >= 0)[:, None].astype(
            feat_hot.dtype)
        return x.at[cold_at].set(cold_rows, mode="drop")

    return assemble


def follow(run: Run, kept: dict, *, precision="float32", fault=None,
           verify=True):
    """The reference through the first three steps, from the same weights,
    batches and keys, over rows it read by itself. Returns its numbers,
    the sample check's ``check.SampleFacts`` and the steps' frontiers."""
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
    frontiers = []
    for t, st in enumerate(kept["steps"]):
        sample = replay(indptr, indices, jax.device_put(st["seeds"], dev0),
                        st["key"])
        if verify:
            facts.add(reference.check_sample(
                jax.device_get(sample), run.fanout, indptr_host, row_values,
                rng))
        last = sample.hops[-1]
        frontiers.append(np.asarray(last.n_id))
        x = run.read_rows(last.n_id,
                          fault if fault in OWN_FAULTS else None)
        # the block stands where the table stood: slot j reads row j
        slots = jnp.where(last.n_id >= 0,
                          jnp.arange(last.n_id.shape[0], dtype=jnp.int32), -1)
        held = reference.Sample(sample.seeds, list(sample.hops[:-1]) + [
            reference.Hop(slots, last.row, last.col)])
        loss, grads = grad_fn(layers, x, held,
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
    return numbers, facts, frontiers


def row_gap(run: Run, frontiers, fault=None) -> float:
    """The widest gap between a row the store's lookup gives for a
    checked step's frontier and the row the reference read by itself:
    exact, so 0. ``fault`` puts a broken store's reading in the lookup's
    place (``prove``'s)."""
    import jax.numpy as jnp
    worst = 0.0
    for n_id in frontiers:
        got = run.read_rows(n_id, fault) if fault else run.store_rows(n_id)
        gap = float(jnp.max(jnp.abs(got - run.read_rows(n_id))))
        if not gap <= worst:                 # also where it is no number
            worst = gap
    return worst


def compare(run: Run, kept: dict) -> dict:
    ref, facts, frontiers = follow(run, kept)
    out = check.train_numbers(program_numbers(run, kept), ref, facts)
    out["row_gap"] = row_gap(run, frontiers)
    return out
