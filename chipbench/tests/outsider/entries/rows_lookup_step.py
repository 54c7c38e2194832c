"""The entry ``rows_lookup_step``: a training step over a table whose rows
are divided over the cell's chips. Every chip looks the batch up in its
own rows, a ``psum`` over the chips is the exchange, and the two-line
model trains on the rows it brings. The step is this file's own jitted
function: the interface ``run.py`` drives is all it shares with the
entries of ``chipbench/entries/``."""

import collections
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from chipbench import traffic, world

CHECKED_STEPS = 3


class Run:
    def __init__(self, cell, seed, devices, faults=()):
        cfg, mix = cell.config, cell.traffic
        self.cell, self.seed, self.ref = cell, seed, cell.reference
        self.batch = int(mix["batch"])
        self.run_ahead = int(mix["run_ahead"])
        self.lr = float(cfg["optimizer"]["learning_rate"])
        self.mesh = Mesh(np.array(devices[:cell.chips]), ("chips",))
        self.rep = NamedSharding(self.mesh, P())
        self.world = world.make_world(cfg, seed, self.mesh)
        self.dims = (int(cfg["feature_dim"]), int(cfg["num_classes"]))
        self.params = jax.jit(lambda k: self.ref.init_layers(k, self.dims),
                              out_shardings=self.rep)(
            jax.random.fold_in(world.seed_key(seed), 7))
        self.step = jax.jit(self._build("no_exchange" in faults))
        self.labels = np.asarray(self.world["labels"])
        self.batches = traffic.train_batches(mix, cfg, seed, self.batch)

    def _build(self, no_exchange):
        rows_a_chip = int(self.cell.config["nodes"]) // self.mesh.size
        lr = self.lr

        def lookup(shard, ids):
            local = ids - jax.lax.axis_index("chips") * rows_a_chip
            mine = (local >= 0) & (local < rows_a_chip)
            x = shard[jnp.clip(local, 0, rows_a_chip - 1)] * mine[:, None]
            with jax.named_scope("rows_exchange"):
                return x if no_exchange else jax.lax.psum(x, "chips")

        fetch = jax.shard_map(lookup, mesh=self.mesh,
                              in_specs=(P("chips", None), P()), out_specs=P(),
                              check_vma=False)

        def step(params, feat, ids, labels):
            x = fetch(feat, ids)

            def loss_of(p):
                logits = x @ p["w"] + p["b"]
                picked = jnp.take_along_axis(logits, labels[:, None], 1)[:, 0]
                return jnp.mean(jax.nn.logsumexp(logits, axis=-1) - picked)

            loss, grads = jax.value_and_grad(loss_of)(params)
            return jax.tree.map(lambda p, g: p - lr * g, params, grads), loss

        return step

    def feed(self):
        seeds = next(self.batches)
        return (seeds, jax.device_put(seeds, self.rep),
                jax.device_put(self.labels[seeds], self.rep))

    def call(self, fed):
        self.params, loss = self.step(self.params, self.world["feat"],
                                      fed[1], fed[2])
        return loss

    def setup(self):
        self.kept = {"params0": jax.device_get(self.params), "steps": []}
        for _ in range(CHECKED_STEPS):
            fed = self.feed()
            self.kept["steps"].append({"seeds": fed[0],
                                       "loss": float(self.call(fed))})
        self.kept["params3"] = jax.device_get(self.params)

    def window(self, seconds):
        inflight, losses = collections.deque(), []
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            loss = self.call(self.feed())
            inflight.append(loss)
            losses.append(loss)
            if len(inflight) > self.run_ahead:
                inflight.popleft().block_until_ready()
        jax.block_until_ready((self.params, losses))
        wall = time.perf_counter() - t0
        losses = np.asarray(jax.device_get(losses), np.float64)
        return {"steps": len(losses), "wall_s": wall,
                "nonfinite": int((~np.isfinite(losses)).sum())}

    def stop(self):
        return None

    def program_text(self):
        fed = self.feed()
        return self.step.lower(self.params, self.world["feat"], fed[1],
                               fed[2]).compile().as_text()

    def free(self):
        self.params = self.step = None

    def outcome(self, win):
        """The reference follows the first three steps over the whole
        table read back to one device."""
        table = jnp.asarray(np.asarray(self.world["feat"]))
        layers = jax.tree.map(jnp.asarray, self.kept["params0"])
        losses = []
        for st in self.kept["steps"]:
            x = self.ref.gather_rows(table, jnp.asarray(st["seeds"]))
            loss, grads = self.ref.loss_and_grads(
                layers, x, jnp.asarray(self.labels[st["seeds"]]))
            layers = self.ref.sgd_update(layers, grads, self.lr)
            losses.append(float(loss))
        got = np.array([s["loss"] for s in self.kept["steps"]])
        moved = lambda end: np.linalg.norm(
            np.asarray(end["w"], np.float64)
            - np.asarray(self.kept["params0"]["w"], np.float64))
        want = moved(layers)
        steps = win["steps"]
        return {"numbers": {
                    "loss_gap": float(np.max(np.abs(got - losses)
                                             / np.abs(losses))),
                    "update_gap": float(abs(moved(self.kept["params3"]) - want)
                                        / want),
                    "nonfinite_losses": float(win["nonfinite"])},
                "values": {"train_seeds_per_s":
                           steps * self.batch / win["wall_s"]},
                "attempted": steps, "failed": win["nonfinite"],
                "facts": {"steps": steps,
                          "rows_exchanged": steps * self.batch},
                "shown": {"losses": got.tolist(), "ref_losses": losses}}
