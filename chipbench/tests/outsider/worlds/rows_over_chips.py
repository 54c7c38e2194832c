"""A table whose rows are divided over the chips of a mesh, labels on
every chip, made on the devices from the seed in one jitted call."""

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from chipbench.world import seed_key


def make(config: dict, seed: int, mesh) -> dict:
    nodes, dim = int(config["nodes"]), int(config["feature_dim"])
    classes = int(config["num_classes"])

    def world(key):
        klab, kcen, kfeat = jax.random.split(key, 3)
        labels = jax.random.randint(klab, (nodes,), 0, classes, jnp.int32)
        centers = jax.random.normal(kcen, (classes, dim), jnp.float32)
        noise = jax.random.normal(kfeat, (nodes, dim), jnp.float32)
        return {"feat": centers[labels] + 0.5 * noise, "labels": labels}

    shardings = {"feat": NamedSharding(mesh, P(mesh.axis_names[0], None)),
                 "labels": NamedSharding(mesh, P())}
    return jax.jit(world, out_shardings=shardings)(seed_key(seed))
