"""``ops.dedup.unique_within_budget``'s contract, held against
``np.unique(..., return_inverse=True)``; ``dedup_take`` equal to
``jnp.take`` on both sides of the overflow; and a structural pin: the
inverse map comes out of the sort (no binary search's ``while`` of
gathers in the jaxpr)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from quiver_tpu.analysis.jaxpr_lint import host_sync_eqns
from quiver_tpu.ops.dedup import I32_MAX, dedup_take, unique_within_budget

BIG = 2**31 - 2                       # the largest id the contract allows


def _case(name):
    """``(ids, valid, budget)`` of one named case."""
    rng = np.random.default_rng(sum(map(ord, name)))
    if name == "heavy_duplicates":
        return rng.integers(0, 7, 200), None, 64
    if name == "no_duplicates":
        return rng.permutation(300)[:128], None, 128
    if name == "masked":
        ids = rng.integers(0, 40, 256)
        return ids, rng.random(256) < 0.6, 96
    if name == "masked_padding":            # a frontier: -1 where invalid
        ids = rng.integers(0, 1000, 192)
        valid = rng.random(192) < 0.5
        return np.where(valid, ids, -1), valid, 192
    if name == "all_invalid":
        return rng.integers(0, 50, 64), np.zeros(64, bool), 16
    if name == "budget_equals_n":
        return rng.integers(0, 30, 100), None, 100
    if name == "overflow":                  # budget < n_uniq
        return rng.permutation(500)[:160], None, 32
    if name == "overflow_masked":
        ids = rng.integers(0, 400, 300)
        return ids, rng.random(300) < 0.8, 24
    if name == "n_not_a_power_of_two":
        return rng.integers(0, 300, 1081), None, 333
    if name == "ids_up_to_int32_max_less_two":
        ids = rng.choice([0, 1, BIG - 5, BIG - 1, BIG], 97)
        return ids, rng.random(97) < 0.7, 8
    if name == "one_slot":
        return np.array([5]), None, 1
    raise KeyError(name)


CASES = ["heavy_duplicates", "no_duplicates", "masked", "masked_padding",
         "all_invalid", "budget_equals_n", "overflow", "overflow_masked",
         "n_not_a_power_of_two", "ids_up_to_int32_max_less_two", "one_slot"]


@pytest.mark.parametrize("jit", [False, True], ids=["eager", "jit"])
@pytest.mark.parametrize("name", CASES)
def test_unique_within_budget_holds_its_contract(name, jit):
    ids, valid, budget = _case(name)
    fn = unique_within_budget
    if jit:
        fn = jax.jit(fn, static_argnames=("budget",))
    uniq, inv, n_uniq = fn(
        jnp.asarray(ids, jnp.int32), budget=budget,
        valid=None if valid is None else jnp.asarray(valid))
    uniq, inv, n_uniq = np.asarray(uniq), np.asarray(inv), int(n_uniq)
    counted = np.ones(len(ids), bool) if valid is None else valid
    want, want_inv = np.unique(ids[counted], return_inverse=True)

    assert uniq.shape == (budget,) and uniq.dtype == np.int32
    assert inv.shape == ids.shape and inv.dtype == np.int32
    assert n_uniq == len(want)                  # the TRUE count, also past
    kept = min(n_uniq, budget)                  # the budget
    np.testing.assert_array_equal(uniq[:kept], want[:kept])
    assert (uniq[kept:] == I32_MAX).all()       # the table stays sorted
    assert inv.min(initial=0) >= 0 and inv.max(initial=0) < budget
    if n_uniq <= budget:
        np.testing.assert_array_equal(inv[counted], want_inv)
        np.testing.assert_array_equal(uniq[inv[counted]], ids[counted])


@pytest.mark.parametrize("quantized", [False, True], ids=["f32", "int8"])
@pytest.mark.parametrize("name,budget", [
    ("fits", 64), ("overflows", 8), ("budget_at_least_n", 4096)])
def test_dedup_take_equals_take(name, budget, quantized):
    from quiver_tpu.ops import quant
    rng = np.random.default_rng(29)
    table = jnp.asarray(rng.normal(size=(500, 12)), jnp.float32)
    ids = jnp.asarray(rng.integers(0, 40, 400), jnp.int32)
    n_uniq = len(np.unique(np.asarray(ids)))
    assert (n_uniq > budget) == (name == "overflows")
    if quantized:
        table = quant.quantize(table, "int8")
    want = jax.jit(quant.gather_rows)(table, ids)   # jitted on both sides
    got = jax.jit(lambda t, i: dedup_take(t, i, budget))(table, ids)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_dedup_take_masked_rows_match_at_valid_positions():
    rng = np.random.default_rng(30)
    table = jnp.asarray(rng.normal(size=(64, 5)), jnp.float32)
    ids = rng.integers(0, 64, 200)
    valid = rng.random(200) < 0.3
    got = dedup_take(table, jnp.asarray(np.where(valid, ids, -1), jnp.int32),
                     96, valid=jnp.asarray(valid))
    np.testing.assert_array_equal(np.asarray(got)[valid],
                                  np.asarray(table)[ids[valid]])


@pytest.mark.parametrize("masked", [False, True], ids=["all", "masked"])
def test_the_inverse_map_is_not_searched_for(masked):
    """What the binary search was: a ``while`` whose body gathers out of
    the unique table, 21 rounds at the dist4 cell's 1.08 M slots (162 ms
    of a 373 ms step: PERF.md, PR 28 / PR 29). The ranks ride back along
    the sort's own permutation instead."""
    n = 4096
    args = (jax.ShapeDtypeStruct((n,), jnp.int32),)
    if masked:
        fn = lambda ids, valid: unique_within_budget(ids, 512, valid=valid)
        args += (jax.ShapeDtypeStruct((n,), jnp.bool_),)
    else:
        fn = lambda ids: unique_within_budget(ids, 512)
    # the walker of the host-sync pin, asked for other primitives: it
    # descends into every inner jaxpr and both branches of a ``cond``
    found = host_sync_eqns(fn, args, prims=("while", "gather", "sort"))
    assert found and set(found) == {"sort"}, found
