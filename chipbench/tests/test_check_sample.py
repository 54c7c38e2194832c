"""``reference.check_sample`` against samples made by hand over a small
graph: a sound draw passes; a pick drawn twice, a pick that is no
neighbour and a draw that always takes a row's first ``k`` do not."""

import numpy as np
import pytest

from chipbench import check, reference

K = 4


def _graph(rng, nodes=600):
    deg = rng.integers(0, 40, nodes)
    indptr = np.concatenate([[0], np.cumsum(deg)]).astype(np.int64)
    # distinct neighbours in every row, so a place in a row is one value
    indices = np.concatenate([rng.choice(nodes, d, replace=False)
                              for d in deg]).astype(np.int32)
    return indptr, indices


def _one_hop(indptr, indices, seeds, choose):
    """A one-hop sample in the program's layout: ``choose(degree)`` gives
    the places of a seed's picks in its row."""
    n_id = list(seeds)
    slot_of = {int(v): i for i, v in enumerate(seeds)}
    row = np.full(len(seeds) * K, -1, np.int32)
    col = np.full(len(seeds) * K, -1, np.int32)
    for s, v in enumerate(seeds):
        start, d = indptr[v], indptr[v + 1] - indptr[v]
        for j, place in enumerate(choose(int(d))):
            nb = int(indices[start + place])
            if nb not in slot_of:
                slot_of[nb] = len(n_id)
                n_id.append(nb)
            row[s * K + j], col[s * K + j] = s, slot_of[nb]
    n_id = np.array(n_id + [-1] * (len(seeds) * (K + 1) - len(n_id)), np.int32)
    return reference.Sample(np.asarray(seeds, np.int32),
                            [reference.Hop(n_id, row, col)])


def _held(sample, indptr, indices):
    facts = check.SampleFacts()
    facts.add(reference.check_sample(
        sample, [K], indptr, lambda pos: indices[pos],
        np.random.default_rng(0)))
    return facts


@pytest.fixture
def world():
    rng = np.random.default_rng(7)
    indptr, indices = _graph(rng)
    seeds = rng.choice(600, 256, replace=False)
    return rng, indptr, indices, seeds


def test_a_uniform_draw_without_replacement_passes(world):
    rng, indptr, indices, seeds = world
    facts = _held(_one_hop(indptr, indices, seeds, lambda d: rng.choice(
        d, min(d, K), replace=False)), indptr, indices)
    assert facts.bad == 0 and facts.position_n > 500
    assert facts.draw_skew < 0.03


def test_a_pick_drawn_twice_is_bad(world):
    rng, indptr, indices, seeds = world
    twice = lambda d: [0, 0] + list(range(1, min(d, K) - 1)) if d >= 2 \
        else range(d)
    assert _held(_one_hop(indptr, indices, seeds, twice),
                 indptr, indices).bad > 0


def test_a_pick_that_is_no_neighbour_is_bad(world):
    rng, indptr, indices, seeds = world
    sample = _one_hop(indptr, indices, seeds, lambda d: range(min(d, K)))
    n_id = sample.hops[0].n_id.copy()
    live = int((n_id >= 0).sum())
    n_id[live - 1] = next(v for v in range(600) if v not in set(n_id[:live]))
    bent = reference.Sample(sample.seeds,
                            [sample.hops[0]._replace(n_id=n_id)])
    assert _held(bent, indptr, indices).bad > 0


def test_always_the_first_k_reads_a_quarter_or_more(world):
    rng, indptr, indices, seeds = world
    facts = _held(_one_hop(indptr, indices, seeds, lambda d: range(min(d, K))),
                  indptr, indices)
    assert facts.bad == 0          # every pick IS a neighbour, drawn once
    assert facts.draw_skew >= 0.25


def test_nothing_to_read_is_no_number():
    assert check.SampleFacts().draw_skew != check.SampleFacts().draw_skew
