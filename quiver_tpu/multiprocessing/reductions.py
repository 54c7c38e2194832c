"""Pickle reducers for framework objects crossing host process
boundaries (capability analogue of reference reductions.py:5-33)."""

from __future__ import annotations

import copyreg

import jax
import numpy as np


def _reduce_jax_array(arr):
    return (_rebuild_jax_array, (np.asarray(jax.device_get(arr)),))


def _rebuild_jax_array(np_arr):
    import jax.numpy as jnp
    return jnp.asarray(np_arr)


def init_reductions():
    """Register reducers so jax.Array leaves inside Feature / sampler
    objects survive pickling into worker processes.

    Pickler dispatch keys on the *concrete* class (ArrayImpl), not the
    abstract ``jax.Array``, so register the implementation type directly.
    """
    from jax._src.array import ArrayImpl
    copyreg.pickle(ArrayImpl, _reduce_jax_array)
