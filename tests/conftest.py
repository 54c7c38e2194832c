"""Test harness: force an 8-device virtual CPU platform.

This is the survey's answer to the reference's "how do you test multi-node
without a cluster" gap (SURVEY.md §4): all sharding/collective logic runs
against a virtual 8-device mesh, so the full multi-chip path is exercised
in CI with no TPU attached.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

from quiver_tpu.utils.compile_cache import place_compile_cache

# the same persistent compile cache the benches use: the tier-1 suite is
# compile-dominated (every jit program + every subprocess test re-deriving
# them), and the suite has grown past its wall budget paying those compiles
# from scratch on every run. Executables served from the disk cache still
# register in the in-process jit caches, so the recompile-counting tests
# see identical counts either way.
place_compile_cache()

import numpy as np
import pytest


@pytest.fixture
def rng():
    return np.random.default_rng(0)


def random_csr(rng, n, avg_deg, seed_dtype=np.int32):
    """Synthetic random graph as (indptr, indices) numpy arrays."""
    deg = rng.poisson(avg_deg, size=n).astype(np.int64)
    indptr = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(deg, out=indptr[1:])
    e = int(indptr[-1])
    indices = rng.integers(0, n, size=e, dtype=seed_dtype)
    return indptr, indices


@pytest.fixture
def small_graph(rng):
    return random_csr(rng, n=200, avg_deg=8)
