"""The reference ``igbh_rgat``: the MLPerf GNN model, R-GAT over IGBH
(``RGNN(model='rgat')`` of GraphLearn-for-PyTorch's ``examples/igbh``: a
PyG ``HeteroConv`` of bipartite ``GATConv``s, one a relation, summed),
over a sampled frontier of a typed graph whose relations keep their own
neighbour lists: its loss, its gradients and Adam, in straightforward
float32 ``jax.numpy``.

It imports nothing of ``quiver_tpu`` and takes no weights from it: the
harness makes the weights from the seed and hands the same ones to the
program and to this file. A sample is plain arrays (``Sample``, ``Hop``
below), held against the graph by the entry's own check before anything
here reads it. ``graph`` says what the ids mean: ``starts[t]`` the first
global id of type ``t`` (its first row in the table), ``pairs[r]``
relation ``r``'s ``(target type, source type)``.

What an entry reaches through ``cell.reference``: ``Sample``, ``Hop``,
``relations_by_layer``, ``init_layers``, ``gather_rows``, ``forward``,
``loss_and_grads``, ``adam_init`` / ``adam_update``, ``ADAM_B1`` and
``FAULTS``.

The equations (PyG ``GATConv((-1, -1), out, heads, add_self_loops=False)``
a relation, ``HeteroConv(aggr='sum')``). Layer ``i`` runs over hop ``L - 1
- i``: its sources are the type frontiers after the hop, its targets the
type frontiers before it (the batch at hop 0), whose rows are the first
rows of their type's sources. For every relation ``r = (t, s)`` drawn at
the hop and its edges ``j -> i`` (``col -> row``):

    H_src = X_s W_src_r      every source row of type s, no bias
    H_dst = X_t W_dst_r      every target row of type t
    e_ij^h = LeakyReLU_0.2(<H_src,j^h, a_src_r^h> + <H_dst,i^h, a_dst_r^h>)
    alpha_ij^h = softmax over the valid j of i's edges of relation r
    conv_r(i) = concat_h sum_j alpha_ij^h H_src,j^h + b_r
    h_t = sum over the relations r into t of conv_r

then ``leaky_relu`` (0.01) and dropout (0.2) after every layer but the
last, and ``Linear(hidden, classes)`` over the batch's rows; the loss is
the mean cross-entropy over them. ``H_src`` and ``H_dst`` are the literal
products of every row by the whole weight, as PyG computes them, one head
at a time under ``jax.checkpoint`` so that the full-size frontier fits
beside the table: the same arithmetic, in blocks.

Choices and departures, each also under the configuration's ``assumed``:
``b_r`` enters every target of ``t`` that holds a node, whether the block
holds an edge of ``r`` or not (a PyG ``GATConv`` over no edge returns its
bias); a target slot that holds no node yields a zero row; a relation is
drawn, and has weights, at a layer whose hop has targets of its target
type (PyG keeps the others, and never moves them); the sample keeps the
block form (each hop's targets are all the nodes found before it); Adam's
learning rate is constant.

``dtype`` chooses what the arithmetic runs in: ``float32`` is the
reference (matmuls at ``highest``), ``bfloat16`` the control of "How
correct is decided". ``fault`` plants one of this model's own faults:
``one_relation`` (every relation's block through relation 0's weights:
the relations are told apart by nothing) or ``mean_over_relations``
(``HeteroConv(aggr='mean')``: a type's relations averaged, not summed).
"""

from __future__ import annotations

import hashlib
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.999, 1e-8
ATT_SLOPE, ACT_SLOPE, DROPOUT = 0.2, 0.01, 0.2
FAULTS = ("one_relation", "mean_over_relations")


class Hop(NamedTuple):
    """One sampled hop in local ids. ``frontier[t]``: type ``t``'s ids
    after the hop (-1 fill; its ids before the hop are the prefix), None
    where it has none. ``rows[r]`` / ``cols[r]``: relation ``r``'s edges,
    ``cols[r][e]`` a place in its source type's ``frontier``,
    ``rows[r][e]`` a place in its target type's frontier before the hop;
    -1 marks an empty slot; None where the relation was not drawn."""
    frontier: tuple
    rows: tuple
    cols: tuple


class Sample(NamedTuple):
    """``seeds``: local ids of the batch's type (-1 fill); ``hops`` in
    sampling order."""
    seeds: np.ndarray
    hops: Sequence[Hop]


def relations_by_layer(pairs, seed_type: int, num_layers: int):
    """The relations drawn at each layer (outermost hop first): those whose
    target type has a node before the layer's hop."""
    reach, hops = {seed_type}, []
    for _ in range(num_layers):
        hops.append([r for r, (t, _) in enumerate(pairs) if t in reach])
        reach = reach | {s for t, s in pairs if t in reach}
    return hops[::-1]


def _glorot(key, shape):
    limit = np.sqrt(6.0 / (shape[-2] + shape[-1]))
    return jax.random.uniform(key, shape, jnp.float32, -limit, limit)


def init_layers(key, feature_dim: int, hidden_dim: int, num_classes: int,
                heads: int, drawn):
    """``{"convs": [{r: {...}} a layer], "head": {...}}``: PyG's own
    initial distributions (Glorot for ``w_src`` / ``w_dst`` [in, hidden]
    and the attention vectors ``a_src`` / ``a_dst`` [heads, hidden //
    heads], zero biases) for the relations ``drawn[i]`` of layer ``i``,
    and ``Linear``'s uniform in ``+-1/sqrt(fan_in)`` for the head."""
    out = hidden_dim // heads
    convs = []
    for i, rels in enumerate(drawn):
        fan_in = feature_dim if i == 0 else hidden_dim
        layer = {}
        for r in rels:
            ks, kd, kas, kad = jax.random.split(
                jax.random.fold_in(jax.random.fold_in(key, i), r), 4)
            layer[r] = {"w_src": _glorot(ks, (fan_in, hidden_dim)),
                        "w_dst": _glorot(kd, (fan_in, hidden_dim)),
                        "a_src": _glorot(kas, (heads, out)),
                        "a_dst": _glorot(kad, (heads, out)),
                        "b": jnp.zeros((hidden_dim,), jnp.float32)}
        convs.append(layer)
    kw, kb = jax.random.split(jax.random.fold_in(key, len(drawn)))
    limit = 1.0 / np.sqrt(hidden_dim)
    return {"convs": convs, "head": {
        "w": jax.random.uniform(kw, (hidden_dim, num_classes), jnp.float32,
                                -limit, limit),
        "b": jax.random.uniform(kb, (num_classes,), jnp.float32, -limit,
                                limit)}}


def dropout_key(key, name: str):
    """The key flax gives a ``Dropout`` named ``name``, a child of the
    module applied with ``rngs={"dropout": key}``, at its first draw: the
    key folded with the first four bytes of SHA-1(name + b"\\x01")."""
    h = hashlib.sha1()
    h.update(name.encode())
    h.update((1).to_bytes(1, "big"))
    return jax.random.fold_in(
        key, jnp.uint32(int.from_bytes(h.digest()[:4], "big")))


def gather_rows(feat, ids):
    """Rows of the table for -1-padded global ids, by plain indexing, as
    stored (a 16-bit table gives 16-bit rows); empty slots are zero."""
    x = feat[jnp.clip(ids, 0, feat.shape[0] - 1)]
    return x * (ids >= 0).astype(x.dtype)[:, None]


def _matmul(a, b):
    return jnp.matmul(a, b, precision="highest")


def _one_head(x_src, x_dst, w_src, w_dst, a_src, a_dst, src, dst, keep,
              targets: int):
    """One relation's one head, ``[targets, out]``: every source row and
    every target row projected, logits on the edge list, a softmax over
    each target's edges, the weighted sum of the sources' rows."""
    h_src = _matmul(x_src, w_src).astype(x_src.dtype)
    h_dst = _matmul(x_dst, w_dst).astype(x_dst.dtype)
    e = jax.nn.leaky_relu((h_src * a_src).sum(-1)[src]
                          + (h_dst * a_dst).sum(-1)[dst], ATT_SLOPE)
    e = jnp.where(keep, e, -jnp.inf)
    top = jax.ops.segment_max(e, dst, num_segments=targets)
    top = jnp.where(jnp.isfinite(top), top, 0)
    wgt = jnp.where(keep, jnp.exp(e - top[dst]), 0).astype(h_src.dtype)
    total = jax.ops.segment_sum(wgt, dst, num_segments=targets)
    alpha = wgt / jnp.where(total > 0, total, 1)[dst]
    return jax.ops.segment_sum(h_src[src] * alpha[:, None], dst,
                               num_segments=targets)


def gat_conv(p, x_src, x_dst, row, col, target_mask, dtype):
    """PyG's bipartite ``GATConv`` of one relation over its edge list:
    ``[targets, hidden]``, zero on the target slots that hold no node."""
    targets = target_mask.shape[0]
    heads, out = p["a_src"].shape
    src, dst = jnp.asarray(col), jnp.asarray(row)
    keep = (src >= 0) & (dst >= 0) & target_mask[jnp.clip(dst, 0)]
    src, dst = jnp.where(keep, src, 0), jnp.where(keep, dst, 0)
    head = jax.checkpoint(_one_head, static_argnums=(9,))
    conv = jnp.concatenate([
        head(x_src, x_dst, p["w_src"][:, i * out:(i + 1) * out].astype(dtype),
             p["w_dst"][:, i * out:(i + 1) * out].astype(dtype),
             p["a_src"][i].astype(dtype), p["a_dst"][i].astype(dtype),
             src, dst, keep, targets)
        for i in range(heads)], axis=-1)
    return jnp.where(target_mask[:, None], conv + p["b"].astype(dtype), 0)


def _dropout(x, key, name: str, dtype):
    if key is None:
        return x
    keep = jax.random.bernoulli(dropout_key(key, name), 1.0 - DROPOUT,
                                x.shape)
    return jnp.where(keep, x / (1.0 - DROPOUT), 0).astype(dtype)


def forward(layers, feat, sample, graph, *, dropout=None,
            dtype=jnp.float32, fault=None):
    """Logits ``[len(sample.seeds), classes]``. ``graph``: ``{"starts",
    "pairs", "seed_type"}``. ``dropout`` is the key the step's dropout
    draws from, or None."""
    pairs, starts = graph["pairs"], graph["starts"]
    hops = sample.hops
    n = len(hops)
    # the type frontiers before each hop: the batch, then each hop's
    before = [[None] * len(starts) for _ in range(n)]
    before[0][graph["seed_type"]] = jnp.asarray(sample.seeds)
    for l in range(1, n):
        before[l] = list(hops[l - 1].frontier)
    x = [None if f is None else gather_rows(
        feat, jnp.where(jnp.asarray(f) >= 0, jnp.asarray(f) + starts[t], -1)
    ).astype(dtype) for t, f in enumerate(hops[-1].frontier)]
    for i, convs in enumerate(layers["convs"]):
        hop = hops[n - 1 - i]
        targets = before[n - 1 - i]
        out = [None] * len(starts)
        for r, p in convs.items():
            t, s = pairs[r]
            mask = jnp.asarray(targets[t]) >= 0
            weights = convs[min(convs)] if fault == "one_relation" else p
            y = gat_conv(weights, x[s], x[t][:mask.shape[0]],
                         hop.rows[r], hop.cols[r], mask, dtype)
            out[t] = y if out[t] is None else out[t] + y
        if fault == "mean_over_relations":
            many = [sum(pairs[r][0] == t for r in convs)
                    for t in range(len(starts))]
            out = [None if y is None else y / many[t]
                   for t, y in enumerate(out)]
        if i < len(layers["convs"]) - 1:
            out = [None if y is None else _dropout(
                jax.nn.leaky_relu(y, ACT_SLOPE), dropout, f"drop{i}_{t}",
                dtype) for t, y in enumerate(out)]
        x = out
    seeds = x[graph["seed_type"]]
    head = layers["head"]
    return _matmul(seeds, head["w"].astype(dtype)) + head["b"].astype(dtype)


def cross_entropy(logits, labels):
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.mean(logz - picked)


def loss_and_grads(layers, feat, sample, graph, labels, dropout, *,
                   dtype=jnp.float32, rows=None, fault=None):
    """Mean cross-entropy over the batch rows ``rows`` (all by default)
    and its gradient by every weight. The frontier's rows are read from
    the table ``feat`` as it is stored (16-bit rows) and converted to
    ``dtype``: exactly, where that is float32."""
    labels = jnp.asarray(labels)

    def loss_of(p):
        logits = forward(p, feat, sample, graph, dropout=dropout,
                         dtype=dtype, fault=fault)
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
