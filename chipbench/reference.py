"""The graph's part of the plain reference, shared by every model: what a
sample is (``Hop``, ``Sample``: plain arrays) and ``check_sample``, which
holds one against this file's own reading of the graph.

The model's part (initial layers, forward pass, loss and gradients, the
optimizer's step, the row gather) is a file of its own under
``references/``, found by the configuration's ``reference``
(``spec.Cell.reference``). Neither imports anything of ``quiver_tpu``.
"""

from __future__ import annotations

from typing import NamedTuple, Sequence

import numpy as np


class Hop(NamedTuple):
    """One sampled hop in local ids: edge ``e`` runs from neighbour
    ``n_id[col[e]]`` to seed ``n_id[row[e]]``; -1 marks an empty slot."""
    n_id: np.ndarray
    row: np.ndarray
    col: np.ndarray


class Sample(NamedTuple):
    """``hops`` in sampling order (the batch's own hop first); the last
    hop's ``n_id`` is the frontier whose rows the model reads."""
    seeds: np.ndarray
    hops: Sequence[Hop]


def check_sample(sample: Sample, fanout: Sequence[int], indptr: np.ndarray,
                 row_values, rng: np.random.Generator,
                 probe_seeds: int = 2048) -> dict:
    """Hold a sample against the graph. ``indptr`` is the host's copy;
    ``row_values(positions)`` returns ``indices[positions]``.

    For every hop: the valid seeds come first and keep their slots at
    the head of ``n_id``, valid ``n_id`` entries are distinct, every
    seed holds exactly ``min(degree, k)`` picks in its own ``k`` slots,
    and a seed's picks are drawn from its row WITHOUT replacement: as a
    multiset they are contained in the row's (no pick that is not a
    neighbour, none more often than the row holds it). That is checked
    on all seeds of a hop that has at most ``probe_seeds`` of them and on
    ``probe_seeds`` drawn from ``rng`` otherwise. Returns counts; ``bad``
    must be 0.

    ``position_sum / position_n`` is the mean place of a pick in its row,
    as a share of the row's length, over the probed seeds with at least
    ``2 k`` neighbours: a uniform draw reads 0.5, one that always takes
    the first ``k`` reads 0.25 at the most (``check.draw_skew``)."""
    bad = 0
    edges = 0
    probed = 0
    position_sum, position_n = 0.0, 0
    seeds = np.asarray(sample.seeds)
    for hop, k in zip(sample.hops, fanout):
        n_id, row, col = (np.asarray(a) for a in hop)
        s = seeds.shape[0]
        v = int((seeds >= 0).sum())
        bad += int((seeds[:v] < 0).sum())           # valid seeds come first
        bad += int((n_id[:v] != seeds[:v]).sum())   # and keep their slots
        live = n_id[n_id >= 0]
        bad += int(live.shape[0] - np.unique(live).shape[0])
        valid = col >= 0
        slot = np.arange(col.shape[0]) // k
        bad += int((row[valid] != slot[valid]).sum())
        bad += int(((col >= n_id.shape[0]) | (n_id[np.clip(col, 0, None)] < 0)
                    )[valid].sum())
        deg = np.where(seeds >= 0, indptr[np.clip(seeds, 0, None) + 1]
                       - indptr[np.clip(seeds, 0, None)], 0)
        got = valid.reshape(s, k).sum(axis=1)
        bad += int((got != np.minimum(deg, k)).sum())
        edges += int(valid.sum())
        # neighbourhood: expand the probed seeds' rows and look each pick up
        live_seeds = np.flatnonzero(seeds >= 0)
        if live_seeds.shape[0] > probe_seeds:
            live_seeds = rng.choice(live_seeds, probe_seeds, replace=False)
        starts = indptr[seeds[live_seeds]].astype(np.int64)
        degs = deg[live_seeds].astype(np.int64)
        owner = np.repeat(np.arange(live_seeds.shape[0]), degs)
        within = np.arange(int(degs.sum())) - np.repeat(
            np.cumsum(degs) - degs, degs)
        values = np.asarray(row_values(starts[owner] + within))
        have = (owner.astype(np.int64) << 32) | values.astype(np.int64)
        picks = n_id[np.clip(col.reshape(s, k)[live_seeds], 0, None)]
        pvalid = valid.reshape(s, k)[live_seeds]
        want = ((np.arange(live_seeds.shape[0], dtype=np.int64)[:, None]
                 << 32) | picks.astype(np.int64))[pvalid]
        order = np.argsort(have, kind="stable")
        row_keys, row_counts = np.unique(have, return_counts=True)
        pick_keys, pick_counts = np.unique(want, return_counts=True)
        at = np.minimum(np.searchsorted(row_keys, pick_keys),
                        max(row_keys.shape[0] - 1, 0))
        held = row_counts[at] * (row_keys[at] == pick_keys) \
            if row_keys.shape[0] else np.zeros_like(pick_counts)
        bad += int(np.maximum(pick_counts - held, 0).sum())
        probed += int(pvalid.sum())
        # where in its row each pick sits (its first place, where a row
        # holds a neighbour twice), for the seeds with 2k neighbours or more
        first = np.minimum(np.searchsorted(have[order], want),
                           max(have.shape[0] - 1, 0))   # a stranger is `bad`
        owner_of = want >> 32
        wide = (degs[owner_of] >= 2 * k) if have.shape[0] else \
            np.zeros(want.shape, bool)
        if wide.any():
            place = within[order[first[wide]]] + 0.5
            position_sum += float((place / degs[owner_of[wide]]).sum())
            position_n += int(wide.sum())
        seeds = n_id
    return {"bad": bad, "edges": edges, "probed_edges": probed,
            "position_sum": position_sum, "position_n": position_n}
