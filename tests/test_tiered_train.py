"""A ``Feature`` store larger than the chip's memory under the fused
training step: ``build_train_step(gather=<feature_splice(store)>)`` over a
hot tier on the device and a cold tier in pinned host memory is the step
over the same table held whole, bit for bit (losses, gradients through
Adam's first moment, updated weights), whatever share is hot and whether
or not the cold budget overflows; and it is the float32 reference
(``chipbench/references/sage.py``) within the limits of the benchmark's
cell ``papers100m-sage-train-tiered``."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import quiver_tpu as qv
from quiver_tpu import metrics as qm
from quiver_tpu.models import GraphSAGE
from quiver_tpu.ops.sample_multihop import sample_multihop
from quiver_tpu.parallel.frontier import feature_splice
from quiver_tpu.parallel.train import (TrainState, build_train_step,
                                       init_state, layers_to_adjs,
                                       masked_feature_gather)

N, DIM, CLASSES, SIZES, BATCH = 600, 16, 5, [4, 3], 16
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class World:
    def __init__(self):
        rng = np.random.default_rng(32)
        deg = rng.integers(1, 9, N)
        self.indptr = jnp.asarray(np.concatenate([[0], np.cumsum(deg)]),
                                  jnp.int32)
        self.indices = jnp.asarray(rng.integers(0, N, int(deg.sum())),
                                   jnp.int32)
        self.table = rng.normal(size=(N, DIM)).astype(np.float32)
        # node id -> storage row, as a degree order would be: any bijection
        self.order = rng.permutation(N).astype(np.int32)
        self.storage = np.empty_like(self.table)
        self.storage[self.order] = self.table
        self.model = GraphSAGE(hidden_dim=8, out_dim=CLASSES,
                               num_layers=len(SIZES), dropout=0.5)
        self.tx = optax.adam(1e-2)
        self.key = jax.random.key(7)
        self.seeds = jnp.asarray(rng.choice(N, BATCH, replace=False),
                                 jnp.int32)
        self.labels = jnp.asarray(rng.integers(0, CLASSES, BATCH), jnp.int32)
        n_id, layers = sample_multihop(self.indptr, self.indices, self.seeds,
                                       SIZES, self.key, seeds_dense=True)
        self.frontier = n_id
        self.state = init_state(
            self.model, self.tx,
            masked_feature_gather(jnp.asarray(self.table), n_id),
            layers_to_adjs(layers, BATCH, SIZES), jax.random.key(1))
        whole = build_train_step(self.model, self.tx, SIZES, BATCH,
                                 donate=False)
        self.whole = whole(self.state, jnp.asarray(self.table), None,
                           self.indptr, self.indices, self.seeds,
                           self.labels, self.key)

    def store(self, hot: int, budget: int):
        """The table as ``hot`` device rows over the rest in pinned host
        memory, built from the tiers as they lie."""
        dev = jax.devices()[0]
        pinned = jax.sharding.SingleDeviceSharding(dev,
                                                   memory_kind="pinned_host")
        cold = jax.device_put(self.storage[hot:], pinned) if hot < N else None
        return qv.Feature(host_placement="offload", allow_fallback=False,
                          cold_budget=budget, dedup_cold=False).from_tiers(
            jnp.asarray(self.storage[:hot]) if hot else None, cold,
            self.order)

    def tiered(self, hot: int, budget: int, **kw):
        feat, forder, gather = feature_splice(self.store(hot, budget))
        step = build_train_step(self.model, self.tx, SIZES, BATCH,
                                gather=gather,
                                collect_metrics=gather is not None, **kw)
        return step(jax.tree.map(jnp.copy, self.state), feat, forder,
                    self.indptr, self.indices, self.seeds, self.labels,
                    self.key)


@pytest.fixture(scope="module")
def w():
    return World()


# a frontier of this world holds ~100 valid slots of 320; 256 rows of
# budget hold every cold one, 8 do not
@pytest.mark.parametrize("hot,budget,overflows", [
    (0, 256, 0), (N // 2, 256, 0), (N, 256, 0), (N // 2, 8, 1)],
    ids=["hot0", "hot50", "hot100", "hot50-overflow"])
def test_the_tiered_step_is_the_whole_table_step_bit_for_bit(
        w, hot, budget, overflows):
    out = w.tiered(hot, budget)
    state, loss = out[0], out[1]
    ref_state, ref_loss = w.whole
    assert float(loss) == float(ref_loss)
    # Adam's first moment after one step is (1 - b1) x the gradient
    for got, want in zip(jax.tree.leaves(state), jax.tree.leaves(ref_state)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    if hot == N:
        return                       # a pure-HBM store needs no splice
    c = qm.counters_dict(np.asarray(out[2]))
    valid = int((np.asarray(w.frontier) >= 0).sum())
    cold = int((w.order[np.asarray(w.frontier)[np.asarray(w.frontier) >= 0]]
                >= hot).sum())
    assert c["lookup_calls"] == 1
    assert (c["hot_rows"], c["cold_rows"]) == (valid - cold, cold)
    if hot:
        assert (cold > budget) == bool(overflows)
        assert c["cold_overflow"] == overflows
    # the cold tier sits in pinned host memory and nowhere else
    assert w.store(hot, budget)._host_offload.sharding.memory_kind \
        == "pinned_host"


def test_the_splice_of_a_pure_hbm_store_is_no_gather(w):
    feat, forder, gather = feature_splice(w.store(N, 256))
    assert gather is None and feat.shape == (N, DIM)
    np.testing.assert_array_equal(np.asarray(forder), w.order)


def test_from_tiers_takes_the_tiers_as_they_lie(w):
    store = w.store(N // 2, 64)
    assert store.cache_rows == N // 2 and store.host_part is None
    ids = jnp.asarray(np.r_[np.arange(40), -1], jnp.int32)
    want = np.r_[w.table[:40], np.zeros((1, DIM), np.float32)]
    np.testing.assert_array_equal(np.asarray(store.getitem_masked(ids)), want)
    # a numpy cold part goes the way from_cpu_tensor's does
    loose = qv.Feature(host_placement="offload").from_tiers(
        jnp.asarray(w.storage[:N // 2]), w.storage[N // 2:], w.order)
    np.testing.assert_array_equal(np.asarray(loose[ids[:40]]), w.table[:40])
    with pytest.raises(ValueError, match="stores the tiers as given"):
        qv.Feature(dtype_policy="int8").from_tiers(None, w.storage, None)


def test_the_tiered_step_against_the_float32_reference(w):
    """The loss, the first gradient and the weights after a step of the
    tiered program against ``chipbench/references/sage.py`` over rows read
    by plain indexing, under the limits of the benchmark's cell."""
    import sys
    sys.path.insert(0, ROOT)
    from chipbench import check, reference, spec
    from chipbench.train_cell import program_tree, reference_layers
    ref = spec.plugin("references", "sage")
    limits = json.load(open(os.path.join(
        ROOT, "chipbench", "cells", "papers100m-sage-train-tiered.json")))[
            "limits"]
    dims = [DIM, 8, CLASSES]
    with jax.default_matmul_precision("highest"):
        params = program_tree(ref.init_layers(jax.random.key(3), dims))
        state = TrainState(params, w.tx.init(params),
                           jnp.zeros((), jnp.int32))
        feat, forder, gather = feature_splice(w.store(N // 2, 256))
        step = build_train_step(w.model, w.tx, SIZES, BATCH, gather=gather,
                                donate=False)
        new, loss = step(state, feat, forder, w.indptr, w.indices, w.seeds,
                         w.labels, w.key)
        sample = check.sampler_replay(SIZES)(w.indptr, w.indices, w.seeds,
                                             w.key)
        last = sample.hops[-1]
        ids = np.asarray(last.n_id)
        t = w.order[np.clip(ids, 0, None)]
        x = np.where((ids >= 0)[:, None], w.storage[t], 0)   # by itself
        slots = jnp.where(last.n_id >= 0,
                          jnp.arange(len(ids), dtype=jnp.int32), -1)
        held = reference.Sample(sample.seeds, list(sample.hops[:-1]) + [
            reference.Hop(slots, last.row, last.col)])
        layers0 = reference_layers(params)
        ref_loss, grads = ref.loss_and_grads(
            layers0, jnp.asarray(x), held, w.labels,
            jax.random.fold_in(w.key, 1000))
        layers1, _ = ref.adam_update(layers0, grads, ref.adam_init(layers0),
                                     1e-2)
    got = {"losses": [float(loss)],
           "grad1": jax.tree.map(lambda m: np.asarray(m) / (1 - ref.ADAM_B1),
                                 reference_layers(new.opt_state[0].mu)),
           "params0": layers0, "params3": reference_layers(new.params)}
    want = {"losses": [float(ref_loss)], "grad1": grads, "params0": layers0,
            "params3": layers1}
    facts = check.SampleFacts()
    facts.add({"bad": 0, "edges": 1, "position_sum": 0.5, "position_n": 1})
    numbers = check.train_numbers(got, want, facts)
    for name in ("loss_gap", "grad_gap", "update_gap"):
        assert numbers[name] <= limits[name], (name, numbers[name])
