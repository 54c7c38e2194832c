"""The plain reference: GraphSAGE over a sampled frontier, its loss, its
gradients and Adam, in straightforward float32 ``jax.numpy``.

It imports nothing of ``quiver_tpu`` and takes no weights from it: the
harness makes the weights from the seed and hands the same ones to the
program and to this file. A sample is plain arrays (``Sample`` below);
``check_sample`` holds it against this file's own reading of the graph.

Layer equations (the reference repo's PyG ``SAGEConv`` with mean
aggregation, as its ogbn-products example stacks them):

    h_t' = W_root h_t + b + W_nbr * mean_{s in N(t)} h_s
    relu and dropout(0.5) after every layer but the last

``dtype`` chooses what the arithmetic runs in: ``float32`` is the
reference (matmuls at ``highest``), ``bfloat16`` is the control of "How
correct is decided": the same mathematics with the table, the weights
and every activation rounded to bfloat16.
"""

from __future__ import annotations

import hashlib
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.999, 1e-8


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


def init_layers(key, dims: Sequence[int]):
    """``len(dims) - 1`` SAGE layers, LeCun-normal kernels, zero biases."""
    layers = []
    for i, (fan_in, fan_out) in enumerate(zip(dims[:-1], dims[1:])):
        kr, kn = jax.random.split(jax.random.fold_in(key, i))
        scale = 1.0 / np.sqrt(fan_in)
        layers.append({
            "w_root": scale * jax.random.normal(kr, (fan_in, fan_out)),
            "b": jnp.zeros((fan_out,), jnp.float32),
            "w_nbr": scale * jax.random.normal(kn, (fan_in, fan_out))})
    return layers


def dropout_key(key, layer: int):
    """The key flax gives the ``layer``-th ``Dropout`` of a module that
    was applied with ``rngs={"dropout": key}``: the key folded with the
    first four bytes of SHA-1("Dropout_<layer>" + b"\\x01")."""
    h = hashlib.sha1()
    h.update(f"Dropout_{layer}".encode())
    h.update((1).to_bytes(1, "big"))
    return jax.random.fold_in(
        key, jnp.uint32(int.from_bytes(h.digest()[:4], "big")))


def gather_rows(feat, n_id):
    """Rows of the table for a -1-padded frontier; empty slots are zero."""
    x = feat[jnp.clip(n_id, 0, feat.shape[0] - 1)]
    return x * (n_id >= 0).astype(x.dtype)[:, None]


def forward(layers, x, hops, targets: Sequence[int], *, dropout=None,
            rate: float = 0.5, dtype=jnp.float32):
    """Logits ``[targets[0], classes]``. ``hops`` in sampling order,
    ``targets[h]`` = number of seed slots of hop ``h``; layer ``i`` runs
    over hop ``len(hops) - 1 - i``. ``dropout`` is the key the step's
    dropout draws from, or None when serving."""
    x = x.astype(dtype)
    n = len(layers)
    for i, layer in enumerate(layers):
        hop = hops[n - 1 - i]
        t = targets[n - 1 - i]
        src, dst = jnp.asarray(hop.col), jnp.asarray(hop.row)
        valid = (src >= 0) & (dst >= 0)
        msg = x[jnp.where(valid, src, 0)] * valid[:, None].astype(dtype)
        total = jax.ops.segment_sum(msg, jnp.where(valid, dst, 0),
                                    num_segments=t)
        count = jax.ops.segment_sum(valid.astype(dtype),
                                    jnp.where(valid, dst, 0), num_segments=t)
        mean = total / jnp.maximum(count, 1)[:, None]
        w_root, w_nbr = (layer[k].astype(dtype) for k in ("w_root", "w_nbr"))
        x = (jnp.matmul(x[:t], w_root, precision="highest")
             + layer["b"].astype(dtype)
             + jnp.matmul(mean.astype(dtype), w_nbr, precision="highest"))
        if i != n - 1:
            x = jax.nn.relu(x)
            if dropout is not None:
                keep = jax.random.bernoulli(dropout_key(dropout, i),
                                            1.0 - rate, x.shape)
                x = jnp.where(keep, x / (1.0 - rate), 0).astype(dtype)
    return x


def cross_entropy(logits, labels):
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.mean(logz - picked)


def loss_and_grads(layers, feat, sample: Sample, labels, dropout, *,
                   dtype=jnp.float32, rows=None):
    """Mean cross-entropy over the batch rows ``rows`` (all by default)
    and its gradient by every weight."""
    hops = sample.hops
    targets = [len(sample.seeds)] + [len(h.n_id) for h in hops[:-1]]
    x = gather_rows(feat, jnp.asarray(hops[-1].n_id))
    labels = jnp.asarray(labels)

    def loss_of(p):
        logits = forward(p, x, hops, targets, dropout=dropout, dtype=dtype)
        logits = logits[:len(sample.seeds)]
        if rows is not None:
            return cross_entropy(logits[rows], labels[rows])
        return cross_entropy(logits, labels)

    return jax.value_and_grad(loss_of)(layers)


def adam_init(layers):
    zeros = jax.tree.map(jnp.zeros_like, layers)
    return {"mu": zeros, "nu": zeros, "count": 0}


def adam_update(layers, grads, opt, lr: float):
    """One step of Adam (Kingma & Ba, bias-corrected; no weight decay)."""
    t = opt["count"] + 1
    mu = jax.tree.map(lambda m, g: ADAM_B1 * m + (1 - ADAM_B1) * g,
                      opt["mu"], grads)
    nu = jax.tree.map(lambda v, g: ADAM_B2 * v + (1 - ADAM_B2) * g * g,
                      opt["nu"], grads)
    c1, c2 = 1 - ADAM_B1 ** t, 1 - ADAM_B2 ** t
    new = jax.tree.map(
        lambda p, m, v: p - lr * (m / c1) / (jnp.sqrt(v / c2) + ADAM_EPS),
        layers, mu, nu)
    return new, {"mu": mu, "nu": nu, "count": t}


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
