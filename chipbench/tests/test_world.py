"""The world: the configuration's shapes whatever the seed, and the same
arrays from the same seed."""

import jax
import numpy as np

from chipbench import world

CFG = {"nodes": 30_001, "edges": 400_009, "degree_sigma": 1.0,
       "degree_cap": 500, "feature_dim": 20, "num_classes": 7}


def test_shapes_do_not_depend_on_the_seed():
    for seed in (0, 5, 2**31 + 123):
        w = jax.device_get(world.make_world(CFG, seed))
        assert w["indptr"].shape == (30_002,) and w["indptr"].dtype == np.int32
        assert w["indices"].shape == (400_009,)
        assert w["feat"].shape == (30_001, 20) and w["feat"].dtype == np.float32
        assert w["indptr"][0] == 0 and w["indptr"][-1] == 400_009
        deg = np.diff(w["indptr"])
        assert deg.min() >= 0 and deg.max() <= 500 + 1   # the remainder's slot
        assert 0 <= w["indices"].min() and w["indices"].max() < 30_001


def test_same_seed_same_world():
    a = jax.device_get(world.make_world(CFG, 2**31 + 5))
    b = jax.device_get(world.make_world(CFG, 2**31 + 5))
    c = jax.device_get(world.make_world(CFG, 2**31 + 6))
    assert all(np.array_equal(a[k], b[k]) for k in a)
    assert not np.array_equal(a["indices"], c["indices"])


def test_features_carry_their_label():
    w = jax.device_get(world.make_world(CFG, 1))
    centre = np.stack([w["feat"][w["labels"] == c].mean(0) for c in range(7)])
    nearest = ((w["feat"][:2000, None, :] - centre[None]) ** 2).sum(-1).argmin(1)
    assert (nearest == w["labels"][:2000]).mean() > 0.9
