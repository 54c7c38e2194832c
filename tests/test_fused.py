"""Fused single-kernel sample+gather hop (``ops.pallas.fused``).

Everything runs the kernel in interpret mode with the portable "hash"
PRNG (the pltpu-native stream has no CPU interpret lowering on this
jax), so the fused kernel and the split two-program oracle
(``sample_layer_pallas`` + ``quant.gather_rows``) draw IDENTICAL
streams and the equivalence pins are exact bit equality — picks AND
dequantized rows, masked ``-1`` tails included.

One tolerance caveat, pinned as such: the KERNEL outputs are bit-exact
against the oracle, but a jnp graph that recomputes the int8 dequant in
a different compilation context (the train step's backward pass
rematerializes it) may round ``code*scale+zero`` through one fused
multiply-add — a 1-ulp wobble that is XLA's, not the kernel's. Forward
losses are bit-equal; int8 gradients are pinned to float tolerance.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from quiver_tpu.models import GraphSAGE
from quiver_tpu.ops import quant
from quiver_tpu.ops.pallas.fused import (fused_hot_hop,
                                         fused_hot_hop_reference,
                                         fused_multihop,
                                         fused_multihop_reference,
                                         fused_sample_multihop,
                                         pad_indices)
from quiver_tpu.ops.sample import compact_layer
from quiver_tpu.parallel.train import (TrainState, build_train_step,
                                       cross_entropy_logits, init_state,
                                       layers_to_adjs,
                                       masked_feature_gather)

K = 4
ROW_CAP = 64
DIM = 128


@pytest.fixture
def graph(rng):
    n = 300
    deg = rng.integers(0, 12, n)
    indptr = np.zeros(n + 1, np.int32)
    indptr[1:] = np.cumsum(deg)
    indices = rng.integers(0, n, indptr[-1]).astype(np.int32)
    return jnp.asarray(indptr), jnp.asarray(indices), n


def _both(indptr, indices, seeds, feat, seed, **kw):
    idx = pad_indices(indices, ROW_CAP)
    got = fused_hot_hop(indptr, idx, seeds, feat, K, seed,
                        row_cap=ROW_CAP, rng="hash", interpret=True, **kw)
    want = fused_hot_hop_reference(indptr, idx, seeds, feat, K, seed,
                                   row_cap=ROW_CAP, rng="hash",
                                   interpret=True, **kw)
    return got, want


def _assert_bitwise(got, want):
    for g, w, name in zip(got, want, ("nbrs", "counts", "seed_rows",
                                      "pick_rows")):
        g, w = np.asarray(g), np.asarray(w)
        assert g.dtype == w.dtype and g.shape == w.shape, name
        assert g.tobytes() == w.tobytes(), \
            f"{name} diverges from the split oracle"


class TestFusedKernel:
    def test_bitwise_int8(self, rng, graph):
        indptr, indices, n = graph
        feat = quant.quantize(jnp.asarray(
            rng.standard_normal((n, DIM)).astype(np.float32)), "int8")
        seeds = jnp.asarray(np.concatenate(
            [rng.choice(n, 5, replace=False), [-1, -1, -1]]
        ).astype(np.int32))
        got, want = _both(indptr, indices, seeds, feat, jnp.int32(7))
        _assert_bitwise(got, want)
        # the masked tail's rows are exactly zero
        assert not np.asarray(got[2])[5:].any()

    def test_bitwise_plain_f32(self, rng, graph):
        indptr, indices, n = graph
        feat = jnp.asarray(
            rng.standard_normal((n, DIM)).astype(np.float32))
        seeds = jnp.asarray(
            rng.choice(n, 8, replace=False).astype(np.int32))
        _assert_bitwise(*_both(indptr, indices, seeds, feat,
                               jnp.int32(3)))

    def test_bitwise_forder_hot_rows(self, rng, graph):
        # permuted storage + a hot-tier boundary: picks landing cold
        # must come back as zero rows, identically in both programs
        indptr, indices, n = graph
        perm = rng.permutation(n).astype(np.int32)
        forder = np.empty(n, np.int32)
        forder[perm] = np.arange(n, dtype=np.int32)
        feat = quant.quantize(jnp.asarray(
            rng.standard_normal((n, DIM)).astype(np.float32)), "int8")
        seeds = jnp.asarray(
            rng.choice(n, 8, replace=False).astype(np.int32))
        got, want = _both(indptr, indices, seeds, feat, jnp.int32(11),
                          feature_order=jnp.asarray(forder),
                          hot_rows=200)
        _assert_bitwise(got, want)
        # some pick actually fell cold, or the hot_rows path is vacuous
        t = forder[np.clip(np.asarray(got[0]), 0, n - 1)]
        assert ((np.asarray(got[0]) >= 0) & (t >= 200)).any()

    def test_rows_match_masked_gather(self, rng, graph):
        # the row outputs ARE masked_feature_gather of the picks — the
        # train/serve reassembly contract
        indptr, indices, n = graph
        feat = quant.quantize(jnp.asarray(
            rng.standard_normal((n, DIM)).astype(np.float32)), "int8")
        seeds = jnp.asarray(np.array([5, -1, 17], np.int32))
        (nbrs, _, seed_rows, pick_rows), _ = _both(
            indptr, indices, seeds, feat, jnp.int32(2))
        want_seed = masked_feature_gather(feat, seeds, None)
        want_pick = masked_feature_gather(
            feat, nbrs.reshape(-1).astype(jnp.int32), None)
        np.testing.assert_array_equal(np.asarray(seed_rows),
                                      np.asarray(want_seed))
        np.testing.assert_array_equal(np.asarray(pick_rows),
                                      np.asarray(want_pick))


def _model_state(dim=DIM, bs=8, out=4):
    model = GraphSAGE(hidden_dim=8, out_dim=out, num_layers=1,
                      dropout=0.0)
    empty = compact_layer(jnp.full((bs,), -1, jnp.int32),
                          jnp.full((bs, K), -1, jnp.int32),
                          seeds_dense=True)
    adjs = layers_to_adjs([empty], bs, [K])
    tx = optax.adam(1e-3)
    state = init_state(model, tx, jnp.zeros((bs * (1 + K), dim)), adjs,
                       jax.random.key(0))
    return model, tx, state


def _model_state_multi(sizes, dim=DIM, bs=8, out=4):
    """A len(sizes)-layer model + state shaped for the ladder's static
    frontier budgets (empty compact layers carry the capacities)."""
    model = GraphSAGE(hidden_dim=8, out_dim=out, num_layers=len(sizes),
                      dropout=0.0)
    layers, cur = [], jnp.full((bs,), -1, jnp.int32)
    for k in sizes:
        layer = compact_layer(cur, jnp.full((cur.shape[0], k), -1,
                                            jnp.int32), seeds_dense=True)
        layers.append(layer)
        cur = layer.n_id
    adjs = layers_to_adjs(layers, bs, sizes)
    tx = optax.adam(1e-3)
    state = init_state(model, tx,
                       jnp.zeros((cur.shape[0], dim)), adjs,
                       jax.random.key(0))
    return model, tx, state


class TestFusedTrainStep:
    def test_loss_bit_equal_and_updates(self, rng, graph):
        indptr, indices, n = graph
        bs = 8
        model, tx, state = _model_state(bs=bs)
        labels = jnp.asarray(rng.integers(0, 4, bs).astype(np.int32))
        seeds = jnp.asarray(np.concatenate(
            [rng.choice(n, 5, replace=False), [-1, -1, -1]]
        ).astype(np.int32))
        key = jax.random.key(42)
        featf = jnp.asarray(
            rng.standard_normal((n, DIM)).astype(np.float32))
        featq = quant.quantize(featf, "int8")

        step = build_train_step(model, tx, [K], bs, fused_hot_hop=True,
                                fused_row_cap=ROW_CAP, donate=False)

        def oracle(state, feat):
            def loss_of(p):
                info = jnp.iinfo(jnp.int32)
                seedv = jax.random.randint(
                    jax.random.fold_in(key, 0), (), info.min, info.max,
                    jnp.int32)
                nbrs, _, _, _ = fused_hot_hop_reference(
                    indptr, pad_indices(indices, ROW_CAP), seeds, feat,
                    K, seedv, row_cap=ROW_CAP, rng="hash",
                    interpret=True)
                layer = compact_layer(seeds, nbrs, seeds_dense=True)
                x = masked_feature_gather(feat, layer.n_id, None)
                adjs = layers_to_adjs([layer], bs, [K])
                logits = model.apply(
                    p, x, adjs, train=True,
                    rngs={"dropout": jax.random.fold_in(key, 1000)})
                return cross_entropy_logits(logits[:bs], labels)
            loss, grads = jax.value_and_grad(loss_of)(state.params)
            updates, opt = tx.update(grads, state.opt_state,
                                     state.params)
            return TrainState(optax.apply_updates(state.params,
                                                  updates),
                              opt, state.step + 1), loss

        oracle = jax.jit(oracle)
        for feat, exact_params in ((featf, True), (featq, False)):
            st_f, loss_f = step(state, feat, None, indptr, indices,
                                seeds, labels, key)
            st_o, loss_o = oracle(state, feat)
            assert np.asarray(loss_f).tobytes() == \
                np.asarray(loss_o).tobytes()
            pf = jax.tree_util.tree_leaves(st_f.params)
            po = jax.tree_util.tree_leaves(st_o.params)
            if exact_params:
                for a, b in zip(pf, po):
                    assert np.asarray(a).tobytes() == \
                        np.asarray(b).tobytes()
            else:
                # int8 backward rematerializes the dequant; XLA may
                # re-round it (module docstring) — 1-ulp tolerance
                for a, b in zip(pf, po):
                    np.testing.assert_allclose(np.asarray(a),
                                               np.asarray(b),
                                               atol=1e-6, rtol=1e-6)

    def test_collect_metrics_frontier_counters(self, rng, graph):
        from quiver_tpu.metrics import FRONTIER_CAP, FRONTIER_VALID
        indptr, indices, n = graph
        bs = 8
        model, tx, state = _model_state(bs=bs)
        labels = jnp.zeros((bs,), jnp.int32)
        seeds = jnp.asarray(np.concatenate(
            [rng.choice(n, 5, replace=False), [-1, -1, -1]]
        ).astype(np.int32))
        feat = quant.quantize(jnp.asarray(
            rng.standard_normal((n, DIM)).astype(np.float32)), "int8")
        plain = build_train_step(model, tx, [K], bs, fused_hot_hop=True,
                                 fused_row_cap=ROW_CAP, donate=False)
        metered = build_train_step(model, tx, [K], bs,
                                   fused_hot_hop=True,
                                   fused_row_cap=ROW_CAP, donate=False,
                                   collect_metrics=True)
        key = jax.random.key(1)
        _, loss_p = plain(state, feat, None, indptr, indices, seeds,
                          labels, key)
        _, loss_m, counters = metered(state, feat, None, indptr,
                                      indices, seeds, labels, key)
        assert np.asarray(loss_p).tobytes() == \
            np.asarray(loss_m).tobytes()
        c = np.asarray(counters)
        assert c[FRONTIER_CAP] == bs * (1 + K)
        assert 0 < c[FRONTIER_VALID] <= c[FRONTIER_CAP]

    def test_knob_validation(self):
        model, tx, _ = _model_state()
        # qt-fuse-deep: multi-hop ladders are LEGAL now — the build
        # must not raise (tracing stays lazy, so no call needed)
        assert callable(build_train_step(model, tx, [4, 4], 8,
                                         fused_hot_hop=True,
                                         donate=False))
        with pytest.raises(ValueError, match="at least one hop"):
            build_train_step(model, tx, [], 8, fused_hot_hop=True)
        with pytest.raises(ValueError, match="exact"):
            build_train_step(model, tx, [4], 8, fused_hot_hop=True,
                             method="rotation")
        with pytest.raises(ValueError, match="exact"):
            build_train_step(model, tx, [4, 4], 8, fused_hot_hop=True,
                             method="rotation")
        with pytest.raises(ValueError, match="dedup_gather"):
            build_train_step(model, tx, [4], 8, fused_hot_hop=True,
                             dedup_gather=True)


class TestFusedServeStep:
    def test_plain_store_matches_oracle(self, rng, graph):
        from quiver_tpu.serving import build_serve_step
        indptr, indices, n = graph
        cap = 8
        model, _, state = _model_state(bs=cap)
        feat = quant.quantize(jnp.asarray(
            rng.standard_normal((n, DIM)).astype(np.float32)), "int8")
        step = build_serve_step(model, [K], cap, fused_hot_hop=True,
                                fused_row_cap=ROW_CAP)
        seeds = np.full((cap,), -1, np.int32)
        seeds[:3] = [3, 7, 11]
        key = jax.random.key(5)
        _, logits = step(state.params, key, feat, None, indptr,
                         indices, jnp.asarray(seeds))

        def oracle(params, key, feat, seeds):
            key, sub = jax.random.split(key)
            info = jnp.iinfo(jnp.int32)
            seedv = jax.random.randint(jax.random.fold_in(sub, 0), (),
                                       info.min, info.max, jnp.int32)
            nbrs, _, _, _ = fused_hot_hop_reference(
                indptr, pad_indices(indices, ROW_CAP), seeds, feat, K,
                seedv, row_cap=ROW_CAP, rng="hash", interpret=True)
            layer = compact_layer(seeds, nbrs, seeds_dense=True)
            x = masked_feature_gather(feat, layer.n_id, None)
            adjs = layers_to_adjs([layer], cap, [K])
            return model.apply(params, x, adjs, train=False)[:cap]

        want = jax.jit(oracle)(state.params, jax.random.key(5), feat,
                               jnp.asarray(seeds))
        np.testing.assert_allclose(np.asarray(logits)[:3],
                                   np.asarray(want)[:3],
                                   atol=1e-6, rtol=1e-6)

    def test_tiered_feature_cold_fixup(self, rng, graph):
        # ServeEngine over a hot+cold Feature store: hot rows from the
        # kernel, cold picks through the store's unchanged tiered
        # lookup — logits match a step that runs the WHOLE frontier
        # through the tiered lookup
        from quiver_tpu.feature import Feature
        from quiver_tpu.parallel.frontier import feature_splice
        from quiver_tpu.serving import ServeEngine
        from quiver_tpu.utils import CSRTopo
        indptr, indices, n = graph
        cap = 8
        model, _, state = _model_state(bs=cap)
        feat = rng.standard_normal((n, DIM)).astype(np.float32)
        topo = CSRTopo(indptr=indptr, indices=indices)
        store = Feature(rank=0, device_cache_size=120 * (DIM + 8),
                        cache_policy="device_replicate", csr_topo=topo,
                        dtype_policy="int8")
        store.from_cpu_tensor(feat)
        assert 0 < store.cache_rows < n     # genuinely tiered
        eng = ServeEngine(model, state.params, topo, store, [[K]], cap,
                          fused_hot_hop=True, fused_row_cap=ROW_CAP)
        seeds = np.full((cap,), -1, np.int32)
        seeds[:3] = [3, 7, 11]
        _, logits = eng._steps[0](state.params, jax.random.key(0),
                                  eng._feat, eng._forder, eng._indptr,
                                  eng._indices, jnp.asarray(seeds))
        _, _, store_gather = feature_splice(store)
        hot = eng._feat[0]

        def oracle(params, key, feat_args, forder, seeds):
            key, sub = jax.random.split(key)
            info = jnp.iinfo(jnp.int32)
            seedv = jax.random.randint(jax.random.fold_in(sub, 0), (),
                                       info.min, info.max, jnp.int32)
            nbrs, _, _, _ = fused_hot_hop_reference(
                indptr, pad_indices(indices, ROW_CAP), seeds, hot, K,
                seedv, row_cap=ROW_CAP, rng="hash", interpret=True,
                feature_order=forder, hot_rows=store.cache_rows)
            layer = compact_layer(seeds, nbrs, seeds_dense=True)
            x = store_gather(feat_args, layer.n_id, forder)
            adjs = layers_to_adjs([layer], cap, [K])
            return model.apply(params, x, adjs, train=False)[:cap]

        want = jax.jit(oracle)(state.params, jax.random.key(0),
                               eng._feat, eng._forder,
                               jnp.asarray(seeds))
        np.testing.assert_allclose(np.asarray(logits)[:3],
                                   np.asarray(want)[:3],
                                   atol=1e-6, rtol=1e-6)


class TestFusedMultihop:
    """qt-fuse-deep: the whole fanout ladder through the fused kernel
    family — interior hops sampling-only (in-kernel indptr), leaf hop
    sample+gather, gather-free compaction between. Parity pins are
    against ``fused_multihop_reference`` (per-hop split Pallas sampler
    + one jnp gather), same "hash" PRNG stream on both sides."""

    def _parity(self, indptr, indices, seeds, feat, sizes, key, **kw):
        idx = pad_indices(indices, ROW_CAP)
        got = fused_multihop(indptr, idx, seeds, feat, sizes, key,
                             row_cap=ROW_CAP, rng="hash",
                             interpret=True, **kw)
        want = fused_multihop_reference(indptr, idx, seeds, feat,
                                        sizes, key, row_cap=ROW_CAP,
                                        rng="hash", interpret=True,
                                        **kw)
        n_id, layers, x = got
        rn, rl, rx = want
        np.testing.assert_array_equal(np.asarray(n_id), np.asarray(rn))
        assert len(layers) == len(rl) == len(sizes)
        for lay, ref in zip(layers, rl):
            for f in ("n_id", "n_count", "row", "col", "edge_count"):
                np.testing.assert_array_equal(
                    np.asarray(getattr(lay, f)),
                    np.asarray(getattr(ref, f)), err_msg=f)
        valid = np.asarray(n_id) >= 0
        gx, wx = np.asarray(x), np.asarray(rx)
        assert gx.dtype == wx.dtype and gx.shape == wx.shape
        # valid slots bit-equal; padding slots zero either way (the
        # fused path's never-scattered slots are +0.0, the oracle's
        # multiply-mask may sign them — the documented wobble)
        assert gx[valid].tobytes() == wx[valid].tobytes(), \
            "frontier rows diverge from the split oracle"
        assert not gx[~valid].any()
        return got, want

    @pytest.mark.parametrize("sizes", [[3, 2], [4, 3, 2]])
    @pytest.mark.parametrize("kind", ["int8", "f32"])
    def test_bitwise_vs_oracle(self, rng, graph, sizes, kind):
        indptr, indices, n = graph
        featf = jnp.asarray(
            rng.standard_normal((n, DIM)).astype(np.float32))
        feat = quant.quantize(featf, "int8") if kind == "int8" else featf
        # -1 tail on the seed block: masked through every hop
        seeds = jnp.asarray(np.concatenate(
            [rng.choice(n, 5, replace=False), [-1, -1, -1]]
        ).astype(np.int32))
        self._parity(indptr, indices, seeds, feat, sizes,
                     jax.random.key(2))

    def test_forder_hot_rows_cold_zeroing(self, rng, graph):
        indptr, indices, n = graph
        perm = rng.permutation(n).astype(np.int32)
        forder = np.empty(n, np.int32)
        forder[perm] = np.arange(n, dtype=np.int32)
        feat = quant.quantize(jnp.asarray(
            rng.standard_normal((n, DIM)).astype(np.float32)), "int8")
        seeds = jnp.asarray(
            rng.choice(n, 8, replace=False).astype(np.int32))
        (n_id, _, x), _ = self._parity(
            indptr, indices, seeds, feat, [3, 2], jax.random.key(7),
            feature_order=jnp.asarray(forder), hot_rows=200)
        nid = np.asarray(n_id)
        t = forder[np.clip(nid, 0, n - 1)]
        cold = (nid >= 0) & (t >= 200)
        assert cold.any()                   # the boundary is exercised
        assert not np.asarray(x)[cold].any()

    def test_fanout_one_ladder(self, rng, graph):
        indptr, indices, n = graph
        feat = jnp.asarray(
            rng.standard_normal((n, DIM)).astype(np.float32))
        seeds = jnp.asarray(
            rng.choice(n, 4, replace=False).astype(np.int32))
        self._parity(indptr, indices, seeds, feat, [1, 1],
                     jax.random.key(4))

    def test_empty_frontier_after_hop1(self, rng):
        # all-isolated graph: hop 0 picks nothing, hops 1..L walk the
        # same seed-only frontier — counts stay zero, rows are exactly
        # the seed rows
        n = 50
        indptr = jnp.zeros((n + 1,), jnp.int32)
        indices = jnp.zeros((0,), jnp.int32)
        feat = jnp.asarray(
            rng.standard_normal((n, DIM)).astype(np.float32))
        seeds = jnp.asarray(np.array([3, 9, -1, -1], np.int32))
        (n_id, layers, x), _ = self._parity(
            indptr, indices, seeds, feat, [3, 2], jax.random.key(0))
        nid = np.asarray(n_id)
        assert set(nid[nid >= 0]) == {3, 9}
        for lay in layers:
            assert not (np.asarray(lay.col) >= 0).any()
        np.testing.assert_array_equal(np.asarray(x)[nid >= 0],
                                      np.asarray(feat)[nid[nid >= 0]])

    def test_sample_multihop_matches_reference_frontier(self, rng,
                                                        graph):
        indptr, indices, n = graph
        idx = pad_indices(indices, ROW_CAP)
        seeds = jnp.asarray(
            rng.choice(n, 8, replace=False).astype(np.int32))
        key = jax.random.key(6)
        n_id, layers = fused_sample_multihop(
            indptr, idx, seeds, [3, 2], key, row_cap=ROW_CAP,
            rng="hash", interpret=True)
        feat = jnp.zeros((n, DIM), jnp.float32)
        rn, rl, _ = fused_multihop_reference(
            indptr, idx, seeds, feat, [3, 2], key, row_cap=ROW_CAP,
            rng="hash", interpret=True)
        np.testing.assert_array_equal(np.asarray(n_id), np.asarray(rn))
        for lay, ref in zip(layers, rl):
            np.testing.assert_array_equal(np.asarray(lay.col),
                                          np.asarray(ref.col))

    @pytest.mark.parametrize("sizes", [[3, 2], [2, 2, 2]])
    def test_train_loss_bit_equal_and_updates(self, rng, graph, sizes):
        indptr, indices, n = graph
        bs = 8
        model, tx, state = _model_state_multi(sizes, bs=bs)
        labels = jnp.asarray(rng.integers(0, 4, bs).astype(np.int32))
        seeds = jnp.asarray(np.concatenate(
            [rng.choice(n, 5, replace=False), [-1, -1, -1]]
        ).astype(np.int32))
        key = jax.random.key(42)
        featf = jnp.asarray(
            rng.standard_normal((n, DIM)).astype(np.float32))
        featq = quant.quantize(featf, "int8")

        step = build_train_step(model, tx, sizes, bs,
                                fused_hot_hop=True,
                                fused_row_cap=ROW_CAP, donate=False)

        def oracle(state, feat):
            def loss_of(p):
                n_id, layers, _ = fused_multihop_reference(
                    indptr, pad_indices(indices, ROW_CAP), seeds, feat,
                    sizes, key, row_cap=ROW_CAP, rng="hash",
                    interpret=True)
                x = masked_feature_gather(feat, n_id, None)
                adjs = layers_to_adjs(layers, bs, sizes)
                logits = model.apply(
                    p, x, adjs, train=True,
                    rngs={"dropout": jax.random.fold_in(key, 1000)})
                return cross_entropy_logits(logits[:bs], labels)
            loss, grads = jax.value_and_grad(loss_of)(state.params)
            updates, opt = tx.update(grads, state.opt_state,
                                     state.params)
            return TrainState(optax.apply_updates(state.params,
                                                  updates),
                              opt, state.step + 1), loss

        oracle = jax.jit(oracle)
        for feat, exact_params in ((featf, True), (featq, False)):
            st_f, loss_f = step(state, feat, None, indptr, indices,
                                seeds, labels, key)
            st_o, loss_o = oracle(state, feat)
            pf = jax.tree_util.tree_leaves(st_f.params)
            po = jax.tree_util.tree_leaves(st_o.params)
            if exact_params:
                assert np.asarray(loss_f).tobytes() == \
                    np.asarray(loss_o).tobytes()
                for a, b in zip(pf, po):
                    assert np.asarray(a).tobytes() == \
                        np.asarray(b).tobytes()
            else:
                # int8: the jitted oracle's dequant (code*scale+zero)
                # may contract to an FMA where the kernel rounds twice,
                # so a row — and with it the loss — can sit 1 ulp apart
                # (XLA:CPU of jax 0.9.0 does for [2, 2, 2]). Adam's
                # first step is lr * g / (|g| + eps), so a gradient
                # entry near eps turns that ulp into a fraction of
                # lr = 1e-3: that is the bound on the parameters.
                np.testing.assert_array_max_ulp(
                    np.asarray(loss_f), np.asarray(loss_o), maxulp=1)
                for a, b in zip(pf, po):
                    np.testing.assert_allclose(np.asarray(a),
                                               np.asarray(b),
                                               atol=1e-3, rtol=0)

    def test_serve_step_matches_oracle(self, rng, graph):
        from quiver_tpu.serving import build_serve_step
        indptr, indices, n = graph
        cap, sizes = 8, [3, 2]
        model, _, state = _model_state_multi(sizes, bs=cap)
        feat = quant.quantize(jnp.asarray(
            rng.standard_normal((n, DIM)).astype(np.float32)), "int8")
        step = build_serve_step(model, sizes, cap, fused_hot_hop=True,
                                fused_row_cap=ROW_CAP)
        seeds = np.full((cap,), -1, np.int32)
        seeds[:3] = [3, 7, 11]
        _, logits = step(state.params, jax.random.key(5), feat, None,
                         indptr, indices, jnp.asarray(seeds))

        def oracle(params, key, feat, seeds):
            key, sub = jax.random.split(key)
            n_id, layers, _ = fused_multihop_reference(
                indptr, pad_indices(indices, ROW_CAP), seeds, feat,
                sizes, sub, row_cap=ROW_CAP, rng="hash",
                interpret=True)
            x = masked_feature_gather(feat, n_id, None)
            adjs = layers_to_adjs(layers, cap, sizes)
            return model.apply(params, x, adjs, train=False)[:cap]

        want = jax.jit(oracle)(state.params, jax.random.key(5), feat,
                               jnp.asarray(seeds))
        np.testing.assert_allclose(np.asarray(logits)[:3],
                                   np.asarray(want)[:3],
                                   atol=1e-6, rtol=1e-6)

    def test_tiered_serve_cold_fixup(self, rng, graph):
        # multi-hop ladder over a hot+cold Feature store: the FINAL
        # frontier's cold slots come from the store's tiered lookup
        from quiver_tpu.feature import Feature
        from quiver_tpu.parallel.frontier import feature_splice
        from quiver_tpu.serving import ServeEngine
        from quiver_tpu.utils import CSRTopo
        indptr, indices, n = graph
        cap, sizes = 8, [3, 2]
        model, _, state = _model_state_multi(sizes, bs=cap)
        feat = rng.standard_normal((n, DIM)).astype(np.float32)
        topo = CSRTopo(indptr=indptr, indices=indices)
        store = Feature(rank=0, device_cache_size=120 * (DIM + 8),
                        cache_policy="device_replicate", csr_topo=topo,
                        dtype_policy="int8")
        store.from_cpu_tensor(feat)
        assert 0 < store.cache_rows < n
        eng = ServeEngine(model, state.params, topo, store, [sizes],
                          cap, fused_hot_hop=True,
                          fused_row_cap=ROW_CAP)
        seeds = np.full((cap,), -1, np.int32)
        seeds[:3] = [3, 7, 11]
        _, logits = eng._steps[0](state.params, jax.random.key(0),
                                  eng._feat, eng._forder, eng._indptr,
                                  eng._indices, jnp.asarray(seeds))
        _, _, store_gather = feature_splice(store)
        hot = eng._feat[0]

        def oracle(params, key, feat_args, forder, seeds):
            key, sub = jax.random.split(key)
            n_id, layers, _ = fused_multihop_reference(
                indptr, pad_indices(indices, ROW_CAP), seeds, hot,
                sizes, sub, row_cap=ROW_CAP, rng="hash",
                interpret=True, feature_order=forder,
                hot_rows=store.cache_rows)
            x = store_gather(feat_args, n_id, forder)
            adjs = layers_to_adjs(layers, cap, sizes)
            return model.apply(params, x, adjs, train=False)[:cap]

        want = jax.jit(oracle)(state.params, jax.random.key(0),
                               eng._feat, eng._forder,
                               jnp.asarray(seeds))
        np.testing.assert_allclose(np.asarray(logits)[:3],
                                   np.asarray(want)[:3],
                                   atol=1e-6, rtol=1e-6)

    def test_sharded_fused_matches_single_store(self, rng, graph):
        # the hot-tier leg of the sharded step: fused in-kernel
        # sampling + the partitioned exchange gather must produce the
        # same logits as the fused single-store engine (same key chain)
        import quiver_tpu as qv
        from jax.sharding import Mesh
        indptr, indices, n = graph
        cap, sizes, hosts = 8, [3, 2], 2
        model, _, state = _model_state_multi(sizes, bs=cap)
        feat = rng.standard_normal((n, DIM)).astype(np.float32)
        g2h = rng.integers(0, hosts, n).astype(np.int32)
        g2h[:hosts] = np.arange(hosts)
        mesh = Mesh(np.array(jax.devices()[:hosts]), ("host",))
        info = qv.PartitionInfo(host=0, hosts=hosts, global2host=g2h)
        comm = qv.TpuComm(rank=0, world_size=hosts, mesh=mesh,
                          axis="host")
        dist = qv.DistFeature.from_partition(feat, info, comm,
                                             exchange_cap=None,
                                             collect_metrics=False)
        sharded = qv.ShardedServeEngine(
            model, state.params, (indptr, indices), dist,
            sizes_variants=[sizes], batch_cap=cap, fused_hot_hop=True,
            fused_row_cap=ROW_CAP, seed=9)
        single = qv.ServeEngine(
            model, state.params, (indptr, indices), feat,
            sizes_variants=[sizes], batch_cap=cap, fused_hot_hop=True,
            fused_row_cap=ROW_CAP, seed=9)
        for i in range(3):
            seeds = rng.choice(n, cap, replace=False).astype(np.int32)
            got = np.asarray(sharded.run(seeds))
            want = np.asarray(single.run(seeds))
            np.testing.assert_array_equal(got, want)
