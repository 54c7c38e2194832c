"""The world of a run: arrays made ON THE DEVICE from the seed, by the
recipe the configuration names.

``"world": "<name>"`` in ``configs/<config>.json`` names
``worlds/<name>.py``, whose ``make(config, seed, sharding)`` returns the
run's arrays on the device in whatever layout its entry needs. A
configuration without the key gets ``planted``: a seeded planted-label
graph in CSR form with a float32 table, every array replicated.
"""

from __future__ import annotations

import jax

from . import spec

def seed_key(seed: int) -> jax.Array:
    """A key from any non-negative whole number, also one past 2**31."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)


def make_world(config: dict, seed: int, sharding=None) -> dict:
    """The arrays of ``config``'s world for ``seed``. ``sharding`` is what
    the entry places them with: None (the default device), a ``Sharding``
    every array is replicated over, or whatever else the recipe takes
    there (a ``Mesh`` for one that lays rows over chips)."""
    recipe = spec.plugin("worlds",
                         config.get("world", spec.DEFAULTS["world"]))
    return recipe.make(config, seed, sharding)
