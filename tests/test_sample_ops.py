"""Sampling-op correctness vs numpy oracles.

Mirrors the reference's membership/count checks (test_quiver_cpu.cpp:9-78)
plus distribution and compaction-order properties the reference never
asserted.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from quiver_tpu.ops import sample as sample_ops
from quiver_tpu.ops import sample_layer, compact_layer, sample_prob
from quiver_tpu.ops.sample import _fisher_yates_rows

KEY = jax.random.key(42)


def neighbor_sets(indptr, indices):
    return [set(indices[indptr[v]:indptr[v + 1]].tolist())
            for v in range(len(indptr) - 1)]


class TestSampleLayer:
    def test_membership_and_counts(self, small_graph):
        indptr, indices = small_graph
        nsets = neighbor_sets(indptr, indices)
        seeds = np.arange(len(indptr) - 1, dtype=np.int32)
        k = 5
        nbrs, counts = jax.jit(sample_layer, static_argnums=3)(
            jnp.asarray(indptr), jnp.asarray(indices), jnp.asarray(seeds),
            k, KEY)
        nbrs, counts = np.asarray(nbrs), np.asarray(counts)
        deg = np.diff(indptr)
        np.testing.assert_array_equal(counts, np.minimum(deg, k))
        for i, v in enumerate(seeds):
            got = nbrs[i][nbrs[i] >= 0]
            assert len(got) == counts[i]
            assert set(got.tolist()) <= nsets[v]

    def test_without_replacement_distinct_slots(self, small_graph):
        indptr, indices = small_graph
        seeds = np.arange(len(indptr) - 1, dtype=np.int32)
        k = 4
        # distinct *positions* guaranteed; values may repeat only if the
        # graph itself has parallel edges — rebuild w/o duplicates to check
        uniq_indices = indices.copy()
        for v in range(len(indptr) - 1):
            lo, hi = indptr[v], indptr[v + 1]
            uniq_indices[lo:hi] = (np.arange(hi - lo) * (len(indptr) - 1)
                                   + v) % (10 ** 6) + 1000 + np.arange(hi - lo)
        nbrs, counts = sample_layer(
            jnp.asarray(indptr), jnp.asarray(uniq_indices),
            jnp.asarray(seeds), k, KEY)
        nbrs, counts = np.asarray(nbrs), np.asarray(counts)
        for i in range(len(seeds)):
            got = nbrs[i][:counts[i]]
            assert len(set(got.tolist())) == counts[i], "sampled w/ replacement"

    def test_uniform_distribution(self):
        # one node, 10 neighbors, k=2: each neighbor hit w.p. 0.2
        indptr = np.array([0, 10])
        indices = np.arange(10)
        seeds = jnp.zeros((512,), jnp.int32)  # 512 i.i.d. replicas of node 0
        hits = np.zeros(10)
        for t in range(20):
            nbrs, _ = jax.jit(sample_layer, static_argnums=3)(
                jnp.asarray(indptr), jnp.asarray(indices), seeds, 2,
                jax.random.fold_in(KEY, t))
            ids, cnt = np.unique(np.asarray(nbrs), return_counts=True)
            hits[ids] += cnt
        freq = hits / hits.sum()
        np.testing.assert_allclose(freq, 0.1, atol=0.01)

    def test_masked_seeds(self, small_graph):
        indptr, indices = small_graph
        seeds = jnp.array([-1, 0, -1, 3], jnp.int32)
        nbrs, counts = sample_layer(
            jnp.asarray(indptr), jnp.asarray(indices), seeds, 3, KEY)
        counts = np.asarray(counts)
        assert counts[0] == 0 and counts[2] == 0
        assert (np.asarray(nbrs)[0] == -1).all()

    def test_zero_degree(self):
        indptr = np.array([0, 0, 2])
        indices = np.array([0, 1])
        nbrs, counts = sample_layer(
            jnp.asarray(indptr), jnp.asarray(indices),
            jnp.array([0, 1], jnp.int32), 4, KEY)
        assert int(counts[0]) == 0
        assert int(counts[1]) == 2


def _fisher_yates_rows_by_gather(key, deg, k):
    """The draw as it stood before the log was read by selects (PR 34's
    ``_fisher_yates_rows``, verbatim): a ``[bs, k]`` log, the last match
    found by a max over steps, the logged value fetched by
    ``take_along_axis``. Kept as the oracle of the gather-free form: the
    same keys, the same ``randint``s, the same swaps, so the same picks
    in every element, masked slots included."""
    bs = deg.shape[0]
    steps = jnp.arange(k, dtype=jnp.int32)

    def lookup(pos_log, val_log, x):
        # virtual read a[x]: last write wins; unwritten -> x itself
        match = pos_log == x[:, None]                       # [bs, k]
        last = jnp.max(jnp.where(match, steps[None, :], -1), axis=1)
        logged = jnp.take_along_axis(
            val_log, jnp.maximum(last, 0)[:, None], axis=1)[:, 0]
        return jnp.where(last >= 0, logged, x)

    def body(carry, xs):
        pos_log, val_log = carry
        i, subkey = xs
        span = jnp.maximum(deg - i, 1)
        j = i + jax.random.randint(subkey, (bs,), 0, span).astype(deg.dtype)
        a_j = lookup(pos_log, val_log, j)
        a_i = lookup(pos_log, val_log, jnp.full((bs,), i, dtype=deg.dtype))
        pos_log = jax.lax.dynamic_update_slice_in_dim(
            pos_log, j[:, None], i, axis=1)
        val_log = jax.lax.dynamic_update_slice_in_dim(
            val_log, a_i[:, None], i, axis=1)
        return (pos_log, val_log), a_j

    pos_log = jnp.full((bs, k), -1, dtype=deg.dtype)
    val_log = jnp.zeros((bs, k), dtype=deg.dtype)
    keys = jax.random.split(key, k)
    (_, _), picks = jax.lax.scan(
        body, (pos_log, val_log), (steps, keys))
    return jnp.transpose(picks)                              # [bs, k]


def _gather_ops(compiled_text):
    """The ``gather`` instructions of a compiled module's text. (The
    StableHLO text would not do: it repeats a gather once per outlined
    copy of the function that holds it.)"""
    return [line for line in compiled_text.splitlines()
            if re.search(r"= \S+ gather\(", line)]


class TestFisherYatesLog:
    """The draw reads its own write log by selects over the log's k
    columns. It is the draw of PR 34 in every element, and it left no
    gather behind."""

    @pytest.mark.parametrize("seed", [42, 2147483777])
    @pytest.mark.parametrize("k", [5, 10, 15, 25])
    def test_equals_the_gather_form_in_every_element(self, k, seed):
        rng = np.random.default_rng(seed % 1000 + k)
        deg = jnp.asarray(np.concatenate([
            [0, 1, k - 1, k, k + 1, 10_000],
            rng.integers(0, 4 * k, 3000),            # around the fanout
            rng.integers(0, 10_000, 1000)]), jnp.int32)
        key = jax.random.key(seed)
        got = jax.jit(_fisher_yates_rows, static_argnums=2)(key, deg, k)
        want = jax.jit(_fisher_yates_rows_by_gather, static_argnums=2)(
            key, deg, k)
        assert got.shape == want.shape == (deg.shape[0], k)
        assert got.dtype == want.dtype
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        # and it is a draw: distinct positions inside the row
        got, deg = np.asarray(got), np.asarray(deg)
        for row, d in zip(got[:200], deg[:200]):
            kept = row[:min(d, k)]
            assert len(set(kept.tolist())) == len(kept)
            assert ((kept >= 0) & (kept < max(d, 1))).all()

    @pytest.mark.parametrize("with_slots", [False, True])
    def test_a_hop_compiles_to_the_datas_three_gathers(
            self, small_graph, with_slots, monkeypatch):
        """Two reads of ``indptr`` and one of ``indices``, nothing else of
        opcode ``gather``: the oracle's form holds five."""
        indptr, indices = (jnp.asarray(a) for a in small_graph)
        bs, k = indptr.shape[0] - 1, 5
        seeds = jnp.arange(bs, dtype=jnp.int32)

        def gathers():
            hop = jax.jit(lambda *a: sample_layer(
                *a[:3], k, a[3], with_slots=with_slots))
            return _gather_ops(
                hop.lower(indptr, indices, seeds, KEY).compile().as_text())

        ours = gathers()
        # by what each brings back: a value a seed, twice, and one a pick
        sizes = sorted(
            int(np.prod([int(d) for d in re.search(
                r"= s32\[([\d,]+)\]", line).group(1).split(",")]))
            for line in ours)
        assert sizes == [bs, bs, bs * k], ours
        monkeypatch.setattr(sample_ops, "_fisher_yates_rows",
                            _fisher_yates_rows_by_gather)
        assert len(gathers()) == 5


class TestRotationSampler:
    """sample_layer_rotation + permute_csr: membership/count/distinctness
    per draw; marginal uniformity across epoch re-shuffles."""

    def test_membership_counts_distinct(self, small_graph):
        from quiver_tpu.ops import (sample_layer_rotation, as_index_rows)
        indptr, indices = small_graph
        nsets = neighbor_sets(indptr, indices)
        seeds = np.arange(len(indptr) - 1, dtype=np.int32)
        k = 5
        rows = as_index_rows(jnp.asarray(indices))
        nbrs, counts = sample_layer_rotation(
            jnp.asarray(indptr), rows, jnp.asarray(seeds), k, KEY)
        nbrs, counts = np.asarray(nbrs), np.asarray(counts)
        deg = np.diff(indptr)
        np.testing.assert_array_equal(counts, np.minimum(deg, k))
        for i, v in enumerate(seeds):
            got = nbrs[i][: counts[i]]
            assert set(got.tolist()) <= nsets[v]
            assert (nbrs[i][counts[i]:] == -1).all()
            # distinct positions -> distinct unless graph has parallel edges

    def test_masked_and_zero_degree(self):
        from quiver_tpu.ops import sample_layer_rotation, as_index_rows
        indptr = np.array([0, 0, 2, 2])
        indices = np.array([5, 6])
        rows = as_index_rows(jnp.asarray(indices))
        nbrs, counts = sample_layer_rotation(
            jnp.asarray(indptr), rows, jnp.array([0, 1, -1], jnp.int32), 3,
            KEY)
        counts = np.asarray(counts)
        assert counts.tolist() == [0, 2, 0]
        assert set(np.asarray(nbrs)[1][:2].tolist()) == {5, 6}

    def test_uniform_across_reshuffles(self):
        from quiver_tpu.ops import (sample_layer_rotation, as_index_rows,
                                    permute_csr, edge_row_ids)
        # one node with 10 neighbors, k=2; re-shuffle each "epoch"
        indptr = np.array([0, 10])
        indices = np.arange(100, 110)
        row_ids = edge_row_ids(jnp.asarray(indptr), 10)
        seeds = jnp.zeros((64,), jnp.int32)
        hits = np.zeros(10)
        for t in range(40):
            perm = permute_csr(jnp.asarray(indices), row_ids,
                               jax.random.fold_in(KEY, 1000 + t))
            assert set(np.asarray(perm).tolist()) == set(indices.tolist())
            rows = as_index_rows(perm)
            nbrs, _ = sample_layer_rotation(
                jnp.asarray(indptr), rows, seeds, 2,
                jax.random.fold_in(KEY, t))
            ids, cnt = np.unique(np.asarray(nbrs) - 100, return_counts=True)
            hits[ids] += cnt
        freq = hits / hits.sum()
        np.testing.assert_allclose(freq, 0.1, atol=0.02)

    def test_nondefault_row_width(self, small_graph):
        # width is taken from indices_rows.shape[1]; a 256-wide view must
        # give valid members/counts just like the default 128
        from quiver_tpu.ops import sample_layer_rotation, as_index_rows
        indptr, indices = small_graph
        nsets = neighbor_sets(indptr, indices)
        seeds = np.arange(len(indptr) - 1, dtype=np.int32)
        rows = as_index_rows(jnp.asarray(indices), width=256)
        assert rows.shape[1] == 256
        nbrs, counts = sample_layer_rotation(
            jnp.asarray(indptr), rows, jnp.asarray(seeds), 5, KEY)
        nbrs, counts = np.asarray(nbrs), np.asarray(counts)
        np.testing.assert_array_equal(counts,
                                      np.minimum(np.diff(indptr), 5))
        for i, v in enumerate(seeds):
            got = nbrs[i][nbrs[i] >= 0]
            assert len(got) == counts[i]
            assert set(got.tolist()) <= nsets[v]

    def test_overlapping_layout_identical_draws(self, small_graph):
        # the one-gather overlapping layout must produce EXACTLY the
        # draws of the two-gather pair layout under the same key — it is
        # a memory-layout change, not a sampler change
        from quiver_tpu.ops import (as_index_rows,
                                    as_index_rows_overlapping,
                                    sample_layer_rotation)
        indptr, indices = small_graph
        seeds = np.arange(len(indptr) - 1, dtype=np.int32)
        for k in (3, 15):
            pair = as_index_rows(jnp.asarray(indices))
            over = as_index_rows_overlapping(jnp.asarray(indices))
            assert over.shape[1] == 256
            a, ca = sample_layer_rotation(
                jnp.asarray(indptr), pair, jnp.asarray(seeds), k, KEY)
            b, cb = sample_layer_rotation(
                jnp.asarray(indptr), over, jnp.asarray(seeds), k, KEY,
                stride=128)
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
            np.testing.assert_array_equal(np.asarray(ca), np.asarray(cb))

    def test_overlapping_layout_slots_and_multihop(self, small_graph):
        from quiver_tpu.ops import (as_index_rows,
                                    as_index_rows_overlapping,
                                    sample_layer_rotation, sample_multihop)
        indptr, indices = small_graph
        seeds = np.arange(0, 60, dtype=np.int32)
        pair = as_index_rows(jnp.asarray(indices))
        over = as_index_rows_overlapping(jnp.asarray(indices))
        _, _, sa = sample_layer_rotation(
            jnp.asarray(indptr), pair, jnp.asarray(seeds), 4, KEY,
            with_slots=True)
        _, _, sb = sample_layer_rotation(
            jnp.asarray(indptr), over, jnp.asarray(seeds), 4, KEY,
            with_slots=True, stride=128)
        np.testing.assert_array_equal(np.asarray(sa), np.asarray(sb))
        # end-to-end through sample_multihop
        na, la = sample_multihop(jnp.asarray(indptr), jnp.asarray(indices),
                                 jnp.asarray(seeds), [4, 3], KEY,
                                 method="rotation", indices_rows=pair)
        nb, lb = sample_multihop(jnp.asarray(indptr), jnp.asarray(indices),
                                 jnp.asarray(seeds), [4, 3], KEY,
                                 method="rotation", indices_rows=over,
                                 indices_stride=128)
        np.testing.assert_array_equal(np.asarray(na), np.asarray(nb))
        for A, B in zip(la, lb):
            np.testing.assert_array_equal(np.asarray(A.row),
                                          np.asarray(B.row))
            np.testing.assert_array_equal(np.asarray(A.col),
                                          np.asarray(B.col))

    def test_window_membership_counts_distinct(self, small_graph):
        from quiver_tpu.ops import as_index_rows, sample_layer_window
        indptr, indices = small_graph
        nsets = neighbor_sets(indptr, indices)
        seeds = np.arange(len(indptr) - 1, dtype=np.int32)
        k = 5
        rows = as_index_rows(jnp.asarray(indices))
        nbrs, counts = sample_layer_window(
            jnp.asarray(indptr), rows, jnp.asarray(seeds), k, KEY)
        nbrs, counts = np.asarray(nbrs), np.asarray(counts)
        deg = np.diff(indptr)
        np.testing.assert_array_equal(counts, np.minimum(deg, k))
        for i, v in enumerate(seeds):
            got = nbrs[i][: counts[i]]
            assert set(got.tolist()) <= nsets[v]
            assert (nbrs[i][counts[i]:] == -1).all()

    def test_window_exact_uniform_without_reshuffle(self):
        # for deg <= window the draw is an exact uniform k-subset of the
        # full neighbor list under ANY fixed order — uniformity must
        # hold with NO re-shuffling (rotation needs reshuffles for this)
        from quiver_tpu.ops import as_index_rows, sample_layer_window
        indptr = np.array([0, 10])
        indices = np.arange(100, 110)
        rows = as_index_rows(jnp.asarray(indices))
        seeds = jnp.zeros((64,), jnp.int32)
        hits = np.zeros(10)
        for t in range(40):
            nbrs, _ = sample_layer_window(
                jnp.asarray(indptr), rows, seeds, 2,
                jax.random.fold_in(KEY, t))
            ids, cnt = np.unique(np.asarray(nbrs) - 100, return_counts=True)
            hits[ids] += cnt
        freq = hits / hits.sum()
        np.testing.assert_allclose(freq, 0.1, atol=0.02)

    @pytest.mark.slow  # distribution calibration, ~30-90s
    def test_window_draws_independent_within_epoch(self):
        # two draws of the same node with different keys (same epoch,
        # same fixed order) must not be forced into consecutive runs:
        # collect many 2-subsets of a 12-neighbor node and check far
        # more distinct subsets appear than rotation's 11 runs allow
        from quiver_tpu.ops import as_index_rows, sample_layer_window
        deg = 12
        indptr = np.array([0, deg])
        indices = np.arange(200, 200 + deg)
        rows = as_index_rows(jnp.asarray(indices))
        seeds = jnp.zeros((1,), jnp.int32)
        subsets = set()
        for t in range(80):
            nbrs, _ = sample_layer_window(
                jnp.asarray(indptr), rows, seeds, 2,
                jax.random.fold_in(KEY, 500 + t))
            subsets.add(tuple(sorted(np.asarray(nbrs)[0].tolist())))
        # C(12,2) = 66 possible; rotation could produce at most 11
        assert len(subsets) > 25

    def test_window_overlap_layout_identical(self, small_graph):
        from quiver_tpu.ops import (as_index_rows,
                                    as_index_rows_overlapping,
                                    sample_layer_window)
        indptr, indices = small_graph
        seeds = np.arange(len(indptr) - 1, dtype=np.int32)
        pair = as_index_rows(jnp.asarray(indices))
        over = as_index_rows_overlapping(jnp.asarray(indices))
        a, ca, sa = sample_layer_window(
            jnp.asarray(indptr), pair, jnp.asarray(seeds), 4, KEY,
            with_slots=True)
        b, cb, sb = sample_layer_window(
            jnp.asarray(indptr), over, jnp.asarray(seeds), 4, KEY,
            with_slots=True, stride=128)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        np.testing.assert_array_equal(np.asarray(ca), np.asarray(cb))
        np.testing.assert_array_equal(np.asarray(sa), np.asarray(sb))

    def test_window_hub_truncation_still_members(self):
        # deg 500 hub: picks come from the anchored window only, but
        # must still be real neighbors with k distinct slots
        from quiver_tpu.ops import as_index_rows, sample_layer_window
        deg = 500
        indptr = np.array([0, deg])
        indices = np.arange(1000, 1000 + deg)
        rows = as_index_rows(jnp.asarray(indices))
        nbrs, counts, slots = sample_layer_window(
            jnp.asarray(indptr), rows, jnp.zeros((8,), jnp.int32), 6, KEY,
            with_slots=True)
        nbrs, slots = np.asarray(nbrs), np.asarray(slots)
        assert (np.asarray(counts) == 6).all()
        for i in range(8):
            assert ((nbrs[i] >= 1000) & (nbrs[i] < 1500)).all()
            assert len(set(slots[i].tolist())) == 6
            np.testing.assert_array_equal(indices[slots[i]], nbrs[i])

    def test_window_hub_random_anchor_reaches_whole_segment(self):
        # the hub window anchors at a random per-draw offset, so even
        # with a FIXED order the draws reach the whole segment (under
        # the start-anchored design, positions past ~256 were
        # unreachable until a reshuffle); the positional marginal is
        # edge-ramped over a ~window scale — uniformity comes from the
        # reshuffle (next test). Stays in the fast tier (wide seed
        # batches, few dispatches): it is the distribution guard for
        # the hub arm of the window extraction path.
        from quiver_tpu.ops import as_index_rows, sample_layer_window
        deg = 600
        indptr = np.array([0, deg])
        indices = np.arange(deg, dtype=np.int32)
        rows = as_index_rows(jnp.asarray(indices))
        counts = np.zeros(deg, np.int64)
        for t in range(16):
            nbrs, _ = sample_layer_window(
                jnp.asarray(indptr), rows, jnp.zeros((320,), jnp.int32),
                8, jax.random.key(t))
            got = np.asarray(nbrs).ravel()
            np.add.at(counts, got[got >= 0], 1)
        # the deep interior (past the edge ramp) is hit and near-uniform:
        # ~70 draws land on each position, so one position sits within
        # 0.8 of the interior's mean by more than 6 sigma (the former
        # reference, the count of position 300 alone out of ~17, was
        # as noisy as what it was compared with)
        inner = counts[260:340]
        assert (inner > 0).all()
        np.testing.assert_allclose(inner, inner.mean(), rtol=0.8)
        # positions far beyond the first window are sampled at all —
        # the start-anchored design gave these exactly zero mass
        assert counts[400:].sum() > 0

    @pytest.mark.slow  # distribution calibration, ~30-90s
    def test_window_hub_butterfly_epochs_uniform_marginal(self):
        # with the cheap butterfly reshuffle composed across epochs the
        # hub neighbor marginal approaches uniform — the property that
        # makes window+butterfly a legal combination
        from quiver_tpu.ops import (as_index_rows, butterfly_shuffle,
                                    edge_row_ids, sample_layer_window)
        deg = 600
        indptr = np.array([0, deg])
        base = np.arange(deg, dtype=np.int32)
        row_ids = edge_row_ids(jnp.asarray(indptr), deg)
        counts = np.zeros(deg, np.int64)
        cur = jnp.asarray(base)
        for ep in range(150):
            cur = butterfly_shuffle(cur, row_ids, jax.random.key(700 + ep))
            if ep < 30:
                continue   # let the composition mix away the identity
                           # order's edge bias before counting
            nbrs, _ = sample_layer_window(
                jnp.asarray(indptr), as_index_rows(cur),
                jnp.zeros((16,), jnp.int32), 8, jax.random.key(9000 + ep))
            got = np.asarray(nbrs).ravel()
            np.add.at(counts, got[got >= 0], 1)
        assert (counts > 0).all()
        freq = counts / counts.sum()
        # every-position-reached above is the power assertion (a start-
        # anchored design zeroes all mass past ~position 256); the
        # closeness band is calibrated for the max-of-600-bins extreme:
        # 0.9/deg sat at ~4.6 sigma of the ~26-per-bin count and failed
        # by 2e-5 on this RNG stream — 1.1/deg puts it past 5.5 sigma
        np.testing.assert_allclose(freq, 1 / deg, atol=1.1 / deg)

    def test_window_masked_and_zero_degree(self):
        from quiver_tpu.ops import as_index_rows, sample_layer_window
        indptr = np.array([0, 0, 2, 2])
        indices = np.array([5, 6])
        rows = as_index_rows(jnp.asarray(indices))
        nbrs, counts = sample_layer_window(
            jnp.asarray(indptr), rows, jnp.array([0, 1, -1], jnp.int32), 3,
            KEY)
        counts = np.asarray(counts)
        assert counts.tolist() == [0, 2, 0]
        assert set(np.asarray(nbrs)[1][:2].tolist()) == {5, 6}

    def test_stride_layout_mismatch_raises(self, small_graph):
        # a stride that doesn't match the layout width must error, not
        # silently gather the wrong CSR rows
        from quiver_tpu.ops import as_index_rows, sample_layer_rotation
        indptr, indices = small_graph
        pair = as_index_rows(jnp.asarray(indices))       # width 128
        with pytest.raises(ValueError, match="as_index_rows_overlapping"):
            sample_layer_rotation(jnp.asarray(indptr), pair,
                                  jnp.zeros((4,), jnp.int32), 3, KEY,
                                  stride=128)   # needs width 256, got 128

    def test_multihop_rotation_fallback_is_shuffled(self):
        # ADVICE r1 (medium): rotation with indices_rows=None must not
        # sample consecutive runs of the raw CSR order — the fallback now
        # permutes internally, so the LAST row entry (endpoint) must be
        # drawn with full marginal frequency, not be under-sampled
        from quiver_tpu.ops import sample_multihop
        # 8 seed nodes, each with the SAME raw neighbor row [8..17]
        n_seed, n_nbr = 8, 10
        indptr = np.zeros(19, np.int64)
        indptr[1:n_seed + 1] = np.arange(1, n_seed + 1) * n_nbr
        indptr[n_seed + 1:] = n_seed * n_nbr
        indices = np.tile(np.arange(8, 18), n_seed)
        seeds = jnp.arange(n_seed, dtype=jnp.int32)
        hits = np.zeros(n_nbr)
        for t in range(40):
            _, layers = sample_multihop(jnp.asarray(indptr),
                                        jnp.asarray(indices), seeds, [2],
                                        jax.random.fold_in(KEY, 7000 + t),
                                        method="rotation")
            l = layers[0]
            col = np.asarray(l.col)
            nid = np.asarray(l.n_id)
            picked = nid[col[col >= 0]] - 8
            ids, cnt = np.unique(picked, return_counts=True)
            hits[ids] += cnt
        freq = hits / hits.sum()
        # raw-order rotation gives row-endpoint ids ~1/2 the mass of
        # interior ids (0.056 vs 0.111); the internal shuffle restores
        # uniformity
        np.testing.assert_allclose(freq, 1 / n_nbr, atol=0.025)

    def test_permute_csr_preserves_rows(self, small_graph):
        from quiver_tpu.ops import permute_csr, edge_row_ids
        indptr, indices = small_graph
        row_ids = edge_row_ids(jnp.asarray(indptr), len(indices))
        perm = np.asarray(permute_csr(jnp.asarray(indices), row_ids, KEY))
        for v in range(len(indptr) - 1):
            lo, hi = indptr[v], indptr[v + 1]
            assert sorted(perm[lo:hi].tolist()) == \
                sorted(indices[lo:hi].tolist())


class TestCompactDenseSeeds:
    def test_dense_path_matches_general(self, rng):
        # valid-first prefix (a previous hop's n_id shape): the dense
        # fast path must produce identical outputs to the general path
        from quiver_tpu.ops.sample import _compact_core
        for trial in range(4):
            v = int(rng.integers(1, 40))
            s = 48
            seeds = np.full(s, -1, np.int32)
            seeds[:v] = rng.choice(5000, v, replace=False)
            extras = rng.integers(-1, 5000, 300).astype(np.int32)
            ids = jnp.asarray(np.concatenate([seeds, extras]))
            a = _compact_core(ids, s, seeds_dense=False)
            b = _compact_core(ids, s, seeds_dense=True)
            for x, y, name in zip(a, b, ("n_id", "n_count", "local")):
                if name == "local":
                    # local is garbage where ids < 0; compare valid only
                    m = np.asarray(ids) >= 0
                    np.testing.assert_array_equal(
                        np.asarray(x)[m], np.asarray(y)[m], err_msg=name)
                else:
                    np.testing.assert_array_equal(
                        np.asarray(x), np.asarray(y), err_msg=name)

    def test_multihop_matches_pre_dense_behavior(self, small_graph):
        # the multihop output contract is unchanged by the hop>=1 dense
        # path: membership + seed-slot invariants hold
        from quiver_tpu.ops import sample_multihop
        indptr, indices = small_graph
        seeds = np.arange(24, dtype=np.int32)
        n_id, layers = jax.jit(
            lambda a, b, c, k: sample_multihop(a, b, c, [5, 4, 3], k)
        )(jnp.asarray(indptr), jnp.asarray(indices), jnp.asarray(seeds),
          KEY)
        nsets = neighbor_sets(indptr, indices)
        prev = seeds
        for lay in layers:
            nid = np.asarray(lay.n_id)
            cnt = int(lay.n_count)
            # valid-first, seeds keep their slots
            assert (nid[:cnt] >= 0).all() and (nid[cnt:] == -1).all()
            pv = prev[prev >= 0]
            np.testing.assert_array_equal(nid[: len(pv)], pv)
            row, col = np.asarray(lay.row), np.asarray(lay.col)
            m = col >= 0
            for r, c in zip(row[m], col[m]):
                assert nid[c] in nsets[nid[r]]
            prev = nid


class TestButterflyShuffle:
    """butterfly_shuffle: the cheap per-epoch re-mix must preserve CSR
    structure exactly and actually mix within rows."""

    def _hub_graph(self):
        # rows of assorted sizes incl. a 600-neighbor hub (> 2x the
        # 256 pairing block, exercising the phase-roll path)
        degs = [0, 1, 3, 17, 64, 600, 5, 129]
        indptr = np.zeros(len(degs) + 1, np.int64)
        np.cumsum(degs, out=indptr[1:])
        indices = np.arange(int(indptr[-1]), dtype=np.int32) * 7 % 1000
        return indptr, indices

    def test_preserves_rows(self):
        from quiver_tpu.ops import butterfly_shuffle, edge_row_ids
        indptr, indices = self._hub_graph()
        row_ids = edge_row_ids(jnp.asarray(indptr), len(indices))
        perm = np.asarray(butterfly_shuffle(
            jnp.asarray(indices), row_ids, KEY))
        for v in range(len(indptr) - 1):
            lo, hi = indptr[v], indptr[v + 1]
            assert sorted(perm[lo:hi].tolist()) == \
                sorted(indices[lo:hi].tolist())

    def test_slot_map_contract(self):
        from quiver_tpu.ops import butterfly_shuffle, edge_row_ids
        indptr, indices = self._hub_graph()
        row_ids = edge_row_ids(jnp.asarray(indptr), len(indices))
        perm, smap = butterfly_shuffle(jnp.asarray(indices), row_ids,
                                       KEY, with_slot_map=True)
        np.testing.assert_array_equal(
            np.asarray(perm), indices[np.asarray(smap)])

    @pytest.mark.slow  # distribution calibration, ~30-90s
    def test_mixes_positions_over_epochs(self):
        # composing epochs (output fed back in) must spread the element
        # that starts at a row's first slot over the whole row
        from quiver_tpu.ops import butterfly_shuffle, edge_row_ids
        deg = 64
        indptr = np.array([0, deg], np.int64)
        base = np.arange(deg, dtype=np.int32)
        row_ids = edge_row_ids(jnp.asarray(indptr), deg)
        lands = np.zeros(deg, np.int64)
        trials = 200
        for t in range(trials):
            cur = jnp.asarray(base)
            for ep in range(3):
                cur = butterfly_shuffle(
                    cur, row_ids, jax.random.key(1000 * t + ep))
            lands[int(np.asarray(cur).tolist().index(0))] += 1
        freq = lands / trials
        # uniform would be 1/64 ~ 0.0156; require no position starved
        # or hoarding (loose 4x band — 3 composed epochs, not exact)
        assert freq.max() < 4 / deg
        assert (lands > 0).sum() > deg * 0.5

    def test_orders_differ_across_keys(self):
        from quiver_tpu.ops import butterfly_shuffle, edge_row_ids
        indptr, indices = self._hub_graph()
        row_ids = edge_row_ids(jnp.asarray(indptr), len(indices))
        a = np.asarray(butterfly_shuffle(jnp.asarray(indices), row_ids,
                                         jax.random.key(1)))
        b = np.asarray(butterfly_shuffle(jnp.asarray(indices), row_ids,
                                         jax.random.key(2)))
        assert not np.array_equal(a, b)

    def test_reshuffle_dispatch(self, small_graph):
        from quiver_tpu.ops import (butterfly_shuffle, edge_row_ids,
                                    permute_csr, reshuffle_csr)
        indptr, indices = small_graph
        row_ids = edge_row_ids(jnp.asarray(indptr), len(indices))
        np.testing.assert_array_equal(
            np.asarray(reshuffle_csr(jnp.asarray(indices), row_ids, KEY,
                                     method="sort")),
            np.asarray(permute_csr(jnp.asarray(indices), row_ids, KEY)))
        np.testing.assert_array_equal(
            np.asarray(reshuffle_csr(jnp.asarray(indices), row_ids, KEY,
                                     method="butterfly")),
            np.asarray(butterfly_shuffle(jnp.asarray(indices), row_ids,
                                         KEY)))
        with pytest.raises(ValueError, match="unknown reshuffle"):
            reshuffle_csr(jnp.asarray(indices), row_ids, KEY,
                          method="bogus")

    def test_rotation_uniform_with_butterfly_epochs(self):
        # the rotation draw's neighbor marginal over composed butterfly
        # epochs should approach uniform (the property permute_csr
        # provides exactly, test above at :352-363)
        from quiver_tpu.ops import (as_index_rows, butterfly_shuffle,
                                    edge_row_ids, sample_layer_rotation)
        deg, k = 40, 5
        indptr = np.array([0, deg], np.int64)
        base = np.arange(deg, dtype=np.int32)
        row_ids = edge_row_ids(jnp.asarray(indptr), deg)
        seeds = jnp.zeros((64,), jnp.int32)
        counts = np.zeros(deg, np.int64)
        cur = jnp.asarray(base)
        for ep in range(60):
            cur = butterfly_shuffle(cur, row_ids, jax.random.key(500 + ep))
            nbrs, _ = sample_layer_rotation(
                jnp.asarray(indptr), as_index_rows(cur), seeds, k,
                jax.random.key(9000 + ep))
            got = np.asarray(nbrs).ravel()
            np.add.at(counts, got[got >= 0], 1)
        freq = counts / counts.sum()
        np.testing.assert_allclose(freq, 1 / deg, atol=0.012)


class TestCompactLayer:
    def test_seeds_first_and_unique(self):
        seeds = jnp.array([7, 3, 9], jnp.int32)
        nbrs = jnp.array([[3, 11, -1], [7, 12, 11], [9, -1, -1]], jnp.int32)
        out = compact_layer(seeds, nbrs)
        n_id = np.asarray(out.n_id)
        n = int(out.n_count)
        got = n_id[:n].tolist()
        # first-occurrence order: seeds then new neighbors in scan order
        assert got == [7, 3, 9, 11, 12]
        assert (n_id[n:] == -1).all()

    def test_coo_correctness(self):
        seeds = jnp.array([7, 3], jnp.int32)
        nbrs = jnp.array([[3, 11], [7, -1]], jnp.int32)
        out = compact_layer(seeds, nbrs)
        row = np.asarray(out.row)
        col = np.asarray(out.col)
        # edges: 7->3, 7->11, 3->7 in local ids: 0->1, 0->2, 1->0
        assert row.tolist() == [0, 0, 1, -1]
        assert col.tolist() == [1, 2, 0, -1]
        assert int(out.edge_count) == 3

    def test_random_agrees_with_numpy(self, rng):
        s, k = 64, 7
        seeds = rng.choice(1000, size=s, replace=False).astype(np.int32)
        nbrs = rng.integers(0, 1000, size=(s, k)).astype(np.int32)
        nbrs[rng.random((s, k)) < 0.3] = -1
        out = compact_layer(jnp.asarray(seeds), jnp.asarray(nbrs))
        # oracle: valid seeds keep their slots, then the remaining unique
        # neighbor ids in ascending order (the documented contract)
        seen = set(seeds.tolist())
        extras = sorted(set(x for x in nbrs.reshape(-1).tolist()
                            if x >= 0 and x not in seen))
        order = seeds.tolist() + extras
        n = int(out.n_count)
        assert np.asarray(out.n_id)[:n].tolist() == order
        # every valid edge maps back to the right global ids
        local = {g: i for i, g in enumerate(order)}
        row, col = np.asarray(out.row), np.asarray(out.col)
        for i in range(s):
            for j in range(k):
                e = i * k + j
                if nbrs[i, j] < 0:
                    assert row[e] == -1 and col[e] == -1
                else:
                    assert row[e] == local[seeds[i]]
                    assert col[e] == local[nbrs[i, j]]

    def test_invalid_seed_holes_no_collision(self):
        # a -1 hole *before* a valid seed: seed slots are rank-based, so
        # extras must not collide with the seed's local id
        seeds = jnp.array([-1, 5], jnp.int32)
        nbrs = jnp.array([[-1], [3]], jnp.int32)
        out = compact_layer(seeds, nbrs)
        n = int(out.n_count)
        assert n == 2
        assert np.asarray(out.n_id)[:n].tolist() == [5, 3]
        assert np.asarray(out.row).tolist() == [-1, 0]
        assert np.asarray(out.col).tolist() == [-1, 1]

    def test_jit_static_shapes(self):
        f = jax.jit(compact_layer)
        out1 = f(jnp.array([1, 2], jnp.int32),
                 jnp.array([[3, -1], [1, 4]], jnp.int32))
        out2 = f(jnp.array([5, 6], jnp.int32),
                 jnp.array([[5, 6], [-1, -1]], jnp.int32))
        assert out1.n_id.shape == out2.n_id.shape == (6,)


class TestSampleProb:
    def test_matches_dense_oracle(self, rng):
        n = 40
        indptr, indices = _random_graph(rng, n, 4)
        train = np.array([0, 3, 7])
        sizes = [3, 2]
        got = np.asarray(sample_prob(
            jnp.asarray(indptr), jnp.asarray(indices), jnp.asarray(train),
            sizes, n))
        want = _prob_oracle(indptr, indices, train, sizes, n)
        np.testing.assert_allclose(got, want, rtol=1e-5)

    def test_zero_degree_forced_zero(self):
        # reference quirk: deg(v)==0 => cur[v]=0 even if v is a train node
        indptr = np.array([0, 0, 1])
        indices = np.array([0])
        got = np.asarray(sample_prob(
            jnp.asarray(indptr), jnp.asarray(indices),
            jnp.array([0]), [2], 2))
        assert got[0] == 0.0


def _random_graph(rng, n, avg_deg):
    deg = rng.poisson(avg_deg, size=n)
    indptr = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(deg, out=indptr[1:])
    indices = rng.integers(0, n, size=int(indptr[-1]))
    return indptr, indices


def _prob_oracle(indptr, indices, train, sizes, n):
    last = np.zeros(n, dtype=np.float64)
    last[train] = 1.0
    deg = np.diff(indptr)
    for k in sizes:
        frac = np.where(deg > 0, np.minimum(1.0, k / np.maximum(deg, 1)), 0)
        skip = 1 - last * frac
        cur = np.zeros(n)
        for v in range(n):
            if deg[v] == 0:
                cur[v] = 0.0
                continue
            acc = np.prod(skip[indices[indptr[v]:indptr[v + 1]]])
            cur[v] = 1 - (1 - last[v]) * acc
        last = cur
    return last


class TestRandomWalk:
    def test_steps_are_neighbors(self, small_graph):
        from quiver_tpu.ops import random_walk
        indptr, indices = small_graph
        nsets = neighbor_sets(indptr, indices)
        starts = np.array([v for v in range(len(indptr) - 1)
                           if indptr[v + 1] > indptr[v]], dtype=np.int32)
        paths = np.asarray(random_walk(
            jnp.asarray(indptr), jnp.asarray(indices), jnp.asarray(starts),
            3, KEY))
        assert paths.shape == (len(starts), 4)
        np.testing.assert_array_equal(paths[:, 0], starts)
        for r, s0 in enumerate(starts):
            for t in range(3):
                a, b = paths[r, t], paths[r, t + 1]
                deg = indptr[a + 1] - indptr[a]
                if deg == 0:
                    assert b == a       # stuck walkers stay
                else:
                    assert b in nsets[a]

    def test_zero_degree_stays(self):
        from quiver_tpu.ops import random_walk
        indptr = np.array([0, 0, 1])
        indices = np.array([0])
        paths = np.asarray(random_walk(
            jnp.asarray(indptr), jnp.asarray(indices),
            jnp.array([0, 1], jnp.int32), 2, KEY))
        assert paths[0].tolist() == [0, 0, 0]       # deg 0: stays
        assert paths[1].tolist() == [1, 0, 0]       # 1 -> 0 (only edge)


class TestSampleMultihopDedup:
    def test_duplicate_batch_collapses(self, small_graph):
        from quiver_tpu.ops import sample_multihop_dedup
        indptr, indices = small_graph
        batch = jnp.array([3, 7, 3, 9, 7, 3], jnp.int32)
        n_id, layers, blocals = sample_multihop_dedup(
            jnp.asarray(indptr), jnp.asarray(indices), batch, [3], KEY)
        n_id = np.asarray(n_id)
        blocals = np.asarray(blocals)
        valid = n_id[n_id >= 0]
        assert len(np.unique(valid)) == len(valid)
        # every batch entry maps to its own id's slot
        for i, g in enumerate([3, 7, 3, 9, 7, 3]):
            assert n_id[blocals[i]] == g


class TestExactWide:
    """sample_layer_exact_wide: the wide-fetch exact draw. Same contract
    as sample_layer (i.i.d. uniform min(deg,k)-subsets, distinct
    positions) on every path — low-degree window fetch, capped hub
    scatter, and the cond overflow fallback."""

    @pytest.mark.parametrize("layout", ["pair", "overlap"])
    def test_membership_counts_distinct(self, small_graph, layout):
        from quiver_tpu.ops import (sample_layer_exact_wide, as_index_rows,
                                    as_index_rows_overlapping)
        indptr, indices = small_graph
        nsets = neighbor_sets(indptr, indices)
        seeds = np.arange(len(indptr) - 1, dtype=np.int32)
        k = 5
        ix = jnp.asarray(indices)
        if layout == "overlap":
            rows, stride = as_index_rows_overlapping(ix), 128
        else:
            rows, stride = as_index_rows(ix), None
        nbrs, counts = jax.jit(
            sample_layer_exact_wide, static_argnums=(4, 6))(
            jnp.asarray(indptr), ix, rows, jnp.asarray(seeds), k, KEY,
            stride)
        nbrs, counts = np.asarray(nbrs), np.asarray(counts)
        deg = np.diff(indptr)
        np.testing.assert_array_equal(counts, np.minimum(deg, k))
        for i, v in enumerate(seeds):
            got = nbrs[i][: counts[i]]
            assert set(got.tolist()) <= nsets[v]
            assert (nbrs[i][counts[i]:] == -1).all()

    def _hub_graph(self):
        # node 0: 400 distinct neighbors (hub, deg > any window);
        # nodes 1..20: 6 neighbors each (low path)
        indptr = np.concatenate([[0, 400], 400 + 6 * np.arange(1, 21)])
        indices = np.concatenate(
            [1000 + np.arange(400)] + [2000 + 10 * v + np.arange(6)
                                       for v in range(1, 21)])
        return indptr.astype(np.int64), indices.astype(np.int64)

    @pytest.mark.parametrize("layout", ["pair", "overlap"])
    def test_hub_path_membership_distinct(self, layout):
        from quiver_tpu.ops import (sample_layer_exact_wide, as_index_rows,
                                    as_index_rows_overlapping)
        indptr, indices = self._hub_graph()
        nsets = neighbor_sets(indptr, indices)
        seeds = np.arange(len(indptr) - 1, dtype=np.int32)
        k = 7
        ix = jnp.asarray(indices)
        if layout == "overlap":
            rows, stride = as_index_rows_overlapping(ix), 128
        else:
            rows, stride = as_index_rows(ix), None
        nbrs, counts = sample_layer_exact_wide(
            jnp.asarray(indptr), ix, rows, jnp.asarray(seeds), k, KEY,
            stride=stride)
        nbrs, counts = np.asarray(nbrs), np.asarray(counts)
        deg = np.diff(indptr)
        np.testing.assert_array_equal(counts, np.minimum(deg, k))
        for i in range(len(seeds)):
            got = nbrs[i][: counts[i]]
            assert set(got.tolist()) <= nsets[i]
            assert len(set(got.tolist())) == counts[i]

    def test_hub_overflow_cond_fallback(self):
        # every seed is the hub node; hub_cap=1 forces the cond branch
        from quiver_tpu.ops import sample_layer_exact_wide, as_index_rows
        indptr, indices = self._hub_graph()
        nsets = neighbor_sets(indptr, indices)
        seeds = np.zeros(16, dtype=np.int32)
        ix = jnp.asarray(indices)
        rows = as_index_rows(ix)
        nbrs, counts = sample_layer_exact_wide(
            jnp.asarray(indptr), ix, rows, jnp.asarray(seeds), 5, KEY,
            hub_cap=1)
        nbrs, counts = np.asarray(nbrs), np.asarray(counts)
        assert (counts == 5).all()
        for i in range(16):
            got = nbrs[i][:5]
            assert set(got.tolist()) <= nsets[0]
            assert len(set(got.tolist())) == 5

    def test_hub_uniform_marginal(self):
        # hub with 300 neighbors, k=2: each neighbor hit w.p. 2/300 per
        # draw — exact i.i.d. without any reshuffle
        from quiver_tpu.ops import sample_layer_exact_wide, as_index_rows
        indptr = np.array([0, 300])
        indices = np.arange(300)
        ix = jnp.asarray(indices)
        rows = as_index_rows(ix)
        seeds = jnp.zeros((256,), jnp.int32)
        fn = jax.jit(sample_layer_exact_wide, static_argnums=4)
        hits = np.zeros(300)
        for t in range(40):
            nbrs, _ = fn(jnp.asarray(indptr), ix, rows, seeds, 2,
                         jax.random.fold_in(KEY, t))
            ids, cnt = np.unique(np.asarray(nbrs), return_counts=True)
            hits[ids[ids >= 0]] += cnt[ids >= 0]
        freq = hits / hits.sum()
        np.testing.assert_allclose(freq, 1 / 300, atol=1.7e-3)  # ~4 sigma

    def test_low_uniform_marginal(self):
        # low-degree row (10 nbrs, k=2): wide path must match
        # sample_layer's 0.2 marginal
        from quiver_tpu.ops import sample_layer_exact_wide, as_index_rows
        indptr = np.array([0, 10])
        indices = np.arange(10)
        ix = jnp.asarray(indices)
        rows = as_index_rows(ix)
        seeds = jnp.zeros((512,), jnp.int32)
        fn = jax.jit(sample_layer_exact_wide, static_argnums=4)
        hits = np.zeros(10)
        for t in range(20):
            nbrs, _ = fn(jnp.asarray(indptr), ix, rows, seeds, 2,
                         jax.random.fold_in(KEY, t))
            ids, cnt = np.unique(np.asarray(nbrs), return_counts=True)
            hits[ids] += cnt
        freq = hits / hits.sum()
        np.testing.assert_allclose(freq, 0.1, atol=0.01)

    def test_with_slots_original_csr(self):
        from quiver_tpu.ops import sample_layer_exact_wide, as_index_rows
        indptr, indices = self._hub_graph()
        seeds = np.arange(len(indptr) - 1, dtype=np.int32)
        ix = jnp.asarray(indices)
        rows = as_index_rows(ix)
        nbrs, counts, slots = sample_layer_exact_wide(
            jnp.asarray(indptr), ix, rows, jnp.asarray(seeds), 4, KEY,
            with_slots=True)
        nbrs, counts, slots = map(np.asarray, (nbrs, counts, slots))
        for i in range(len(seeds)):
            for j in range(counts[i]):
                s = slots[i, j]
                assert indptr[i] <= s < indptr[i + 1]
                assert indices[s] == nbrs[i, j]
            assert (slots[i, counts[i]:] == -1).all()

    def test_masked_and_zero_degree(self):
        from quiver_tpu.ops import sample_layer_exact_wide, as_index_rows
        indptr = np.array([0, 0, 2, 2])
        indices = np.array([5, 6])
        ix = jnp.asarray(indices)
        rows = as_index_rows(ix)
        nbrs, counts = sample_layer_exact_wide(
            jnp.asarray(indptr), ix, rows, jnp.array([0, 1, -1], jnp.int32),
            3, KEY)
        counts = np.asarray(counts)
        assert counts.tolist() == [0, 2, 0]
        assert set(np.asarray(nbrs)[1][:2].tolist()) == {5, 6}

    def test_multihop_exact_rows_dispatch(self, small_graph):
        # method="exact" + indices_rows routes through the wide path and
        # keeps the multihop contract (valid frontier, coherent layers)
        from quiver_tpu.ops.sample_multihop import sample_multihop
        from quiver_tpu.ops import as_index_rows_overlapping
        indptr, indices = small_graph
        nsets = neighbor_sets(indptr, indices)
        seeds = jnp.asarray(np.arange(16, dtype=np.int32))
        rows = as_index_rows_overlapping(jnp.asarray(indices))
        n_id, layers = sample_multihop(
            jnp.asarray(indptr), jnp.asarray(indices), seeds, [4, 3], KEY,
            method="exact", indices_rows=rows, indices_stride=128)
        n_id = np.asarray(n_id)
        valid = n_id[n_id >= 0]
        assert len(set(valid.tolist())) == len(valid)
        # every sampled edge's endpoints resolve to a real graph edge
        lay = layers[0]
        nid0 = np.asarray(lay.n_id)
        row, col = np.asarray(lay.row), np.asarray(lay.col)
        for r, c in zip(row, col):
            if c >= 0:
                assert nid0[c] in nsets[nid0[r]]

    def test_weighted_exact_rejects_rows(self, small_graph):
        # exact WEIGHTED sampling would silently drop a built rows view
        # — rejected loudly like the windowed coupled-parameter guards
        from quiver_tpu.ops.sample_multihop import sample_multihop
        from quiver_tpu.ops import as_index_rows
        indptr, indices = small_graph
        rows = as_index_rows(jnp.asarray(indices))
        w = jnp.ones(indices.shape, jnp.float32)
        with pytest.raises(ValueError, match="exact WEIGHTED"):
            sample_multihop(jnp.asarray(indptr), jnp.asarray(indices),
                            jnp.arange(4, dtype=jnp.int32), [3], KEY,
                            edge_weight=w, method="exact",
                            indices_rows=rows)
