"""Pallas kernel tests: interpret mode on CPU via the kernels' own
``interpret=`` argument, with the portable "hash" PRNG the kernels
default to off-TPU (``_dma.default_rng``; the on-core generator has no
CPU lowering). The jnp ops are the oracles; ``tests/test_chip_compile.py``
asks the chip's compiler about the same kernels."""

import numpy as np
import jax.numpy as jnp
import pytest

from quiver_tpu.ops.pallas.gather import gather_rows, gather_rows_reference
from quiver_tpu.ops.pallas.sample_kernel import (
    BLOCK, pad_indices, sample_layer_pallas)


class TestGatherKernel:
    def test_matches_reference(self, rng):
        feat = jnp.asarray(
            rng.standard_normal((512, 128)).astype(np.float32))
        ids = jnp.asarray(rng.integers(0, 512, 700).astype(np.int32))
        out = gather_rows(feat, ids, interpret=True)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(gather_rows_reference(feat, ids)))

    def test_non_multiple_block(self, rng):
        feat = jnp.asarray(rng.standard_normal((64, 8)).astype(np.float32))
        ids = jnp.asarray(np.array([3, 5, 63], np.int32))
        out = gather_rows(feat, ids, interpret=True)
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(feat)[[3, 5, 63]])


@pytest.fixture
def graph(rng):
    n = 400
    deg = rng.integers(0, 40, n)
    indptr = np.zeros(n + 1, np.int64)
    np.cumsum(deg, out=indptr[1:])
    indices = rng.integers(0, n, int(indptr[-1])).astype(np.int32)
    return indptr, indices


class TestSampleKernel:
    def test_membership_counts_distinct(self, graph, rng):
        indptr, indices = graph
        n = len(indptr) - 1
        ip = jnp.asarray(indptr.astype(np.int32))
        idx = pad_indices(jnp.asarray(indices), 64)
        seeds_np = rng.choice(n, 300, replace=False).astype(np.int32)
        k = 6
        nbrs, counts = sample_layer_pallas(
            ip, idx, jnp.asarray(seeds_np), k, 7, row_cap=64,
            interpret=True)
        nbrs, counts = np.asarray(nbrs), np.asarray(counts)
        deg = np.diff(indptr)[seeds_np]
        np.testing.assert_array_equal(counts, np.minimum(deg, k))
        for i, v in enumerate(seeds_np):
            row = indices[indptr[v]:indptr[v + 1]]
            got = nbrs[i][:counts[i]]
            assert set(got.tolist()) <= set(row.tolist())
            assert (nbrs[i][counts[i]:] == -1).all()
            # distinct positions guarantee (duplicates only via parallel
            # edges in the row itself)
            if len(set(row.tolist())) == len(row):
                assert len(set(got.tolist())) == len(got)

    def test_masked_and_boundary_seeds(self, graph):
        indptr, indices = graph
        ip = jnp.asarray(indptr.astype(np.int32))
        idx = pad_indices(jnp.asarray(indices), 64)
        seeds = jnp.asarray(
            np.array([-1, 0, len(indptr) - 2], np.int32))
        nbrs, counts = sample_layer_pallas(ip, idx, seeds, 4, 3,
                                           row_cap=64, interpret=True)
        assert int(counts[0]) == 0
        assert (np.asarray(nbrs)[0] == -1).all()

    def test_block_padding(self, graph):
        # seeds not a multiple of BLOCK
        indptr, indices = graph
        ip = jnp.asarray(indptr.astype(np.int32))
        idx = pad_indices(jnp.asarray(indices), 64)
        seeds = jnp.arange(BLOCK + 17, dtype=jnp.int32)
        nbrs, counts = sample_layer_pallas(ip, idx, seeds, 3, 11,
                                           row_cap=64, interpret=True)
        assert nbrs.shape == (BLOCK + 17, 3)
