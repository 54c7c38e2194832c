"""Auxiliary subsystem tests: checkpointing, debug
utils, pickle reductions, async per-layer sampler."""

import pickle

import numpy as np
import jax
import jax.numpy as jnp
import optax

import quiver_tpu as qv
from quiver_tpu import checkpoint
from quiver_tpu.parallel.train import TrainState


class TestCheckpoint:
    def test_state_roundtrip(self, tmp_path):
        params = {"w": jnp.arange(6.0).reshape(2, 3), "b": jnp.ones((3,))}
        tx = optax.adam(1e-3)
        state = TrainState(params, tx.init(params), jnp.zeros((), jnp.int32))
        path = str(tmp_path / "ckpt")
        checkpoint.save_state(path, state)
        restored = checkpoint.restore_state(path, state)
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(np.asarray(a),
                                                    np.asarray(b)),
            state.params, restored.params)

    def test_artifact_roundtrip(self, tmp_path):
        path = str(tmp_path / "art.npz")
        checkpoint.save_artifact(path, book=np.arange(10),
                                 order=np.arange(5)[::-1])
        art = checkpoint.load_artifact(path)
        np.testing.assert_array_equal(art["book"], np.arange(10))
        np.testing.assert_array_equal(art["order"], np.arange(5)[::-1])


class TestDebugLogger:
    def test_no_duplicate_handlers_on_reconfigure(self):
        from quiver_tpu import debug

        before = [h for h in debug.logger.handlers
                  if getattr(h, debug._HANDLER_MARK, False)]
        assert len(before) == 1           # import attached exactly one
        debug._configure()                # re-import / forked worker
        debug._configure()
        after = [h for h in debug.logger.handlers
                 if getattr(h, debug._HANDLER_MARK, False)]
        assert len(after) == 1

    def test_qt_log_level_env(self, monkeypatch):
        import logging

        from quiver_tpu import debug

        old = debug.logger.level
        try:
            monkeypatch.setenv("QT_LOG_LEVEL", "DEBUG")
            debug._configure(force=True)
            assert debug.logger.level == logging.DEBUG
            monkeypatch.setenv("QT_LOG_LEVEL", "15")
            debug._configure(force=True)
            assert debug.logger.level == 15
            # invalid values are ignored, never raise at import
            monkeypatch.setenv("QT_LOG_LEVEL", "bogus")
            debug._configure(force=True)
            assert debug.logger.level == 15
            # unset + force -> back to NOTSET (defer to the app config;
            # the library no longer forces INFO on import)
            monkeypatch.delenv("QT_LOG_LEVEL")
            debug._configure(force=True)
            assert debug.logger.level == logging.NOTSET
        finally:
            debug.logger.setLevel(old)


class TestDebug:
    def test_show_tensor_info(self, capsys):
        info = qv.show_tensor_info(jnp.zeros((4, 2)))
        assert "shape=(4, 2)" in info
        info2 = qv.show_tensor_info(np.zeros(3))
        assert "numpy" in info2


class TestReductions:
    def test_feature_pickles_across_device_arrays(self, rng):
        feat = rng.standard_normal((20, 4)).astype(np.float32)
        f = qv.Feature(device_cache_size=feat.nbytes)
        f.from_cpu_tensor(feat)
        blob = pickle.dumps(f)
        f2 = pickle.loads(blob)
        ids = np.array([0, 7, 19])
        np.testing.assert_allclose(np.asarray(f2[jnp.asarray(ids)]),
                                   feat[ids], rtol=1e-6)

    def test_feature_pickle_preserves_cold_budget(self, rng):
        feat = rng.standard_normal((64, 4)).astype(np.float32)
        f = qv.Feature(device_cache_size=32 * 4 * 4, cold_budget=8)
        f.from_cpu_tensor(feat)
        f2 = pickle.loads(pickle.dumps(f))
        assert f2.cold_budget == 8
        ids = np.array([0, 31, 32, 63])
        np.testing.assert_allclose(np.asarray(f2[jnp.asarray(ids)]),
                                   feat[ids], rtol=1e-6)
        # pre-cold_budget pickles (older state dicts) load with defaults
        state = f.__getstate__()
        state.pop("cold_budget")
        f3 = qv.Feature.__new__(qv.Feature)
        f3.__setstate__(state)
        assert f3.cold_budget is None

    def test_hetero_feature_pickles(self, rng):
        feats = {"a": rng.standard_normal((30, 4)).astype(np.float32),
                 "b": rng.standard_normal((10, 4)).astype(np.float32)}
        hf = qv.HeteroFeature.from_cpu_tensors(
            feats, configs={"a": dict(device_cache_size=10 * 4 * 4)},
            default=dict(device_cache_size="1M"))
        hf.prefetch({"a": jnp.asarray([1, 2])}).result()  # arm the pool
        hf2 = pickle.loads(pickle.dumps(hf))
        out = hf2.lookup({"a": jnp.asarray([0, 29, -1]),
                          "b": jnp.asarray([9])})
        want = feats["a"][[0, 29, 0]].copy()
        want[2] = 0.0
        np.testing.assert_allclose(np.asarray(out["a"]), want, rtol=1e-6)
        np.testing.assert_allclose(np.asarray(out["b"]), feats["b"][[9]],
                                   rtol=1e-6)


class TestAsyncSampler:
    def test_per_layer_api(self, small_graph, rng):
        indptr, indices = small_graph
        topo = qv.CSRTopo(indptr=indptr, indices=indices)
        s = qv.AsyncNeighborSampler(topo)
        seeds = rng.choice(topo.node_count, 16, replace=False)
        nbrs, counts = s.sample_layer(seeds, 4)
        assert nbrs.shape == (16, 4)
        n_id, row, col = s.reindex(jnp.asarray(seeds, jnp.int32), nbrs)
        np.testing.assert_array_equal(np.asarray(n_id)[:16], seeds)
        assert qv.AsyncCudaNeighborSampler is qv.AsyncNeighborSampler
