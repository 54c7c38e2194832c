"""The reference ``mag_rgat``: OGB-LSC's R-GAT for MAG240M over a sampled
frontier of a typed graph, its loss, its gradients and Adam, in
straightforward float32 ``jax.numpy``.

It imports nothing of ``quiver_tpu`` and takes no weights from it: the
harness makes the weights from the seed and hands the same ones to the
program and to this file. A sample is plain arrays
(``chipbench.reference.Sample``), held against the graph by
``chipbench.reference.check_sample`` before anything here reads it; edges
are lists (``hop.col[e] -> hop.row[e]``, -1 in an empty slot), and each
edge's relation is handed in beside them as the graph STORES it
(``relations[i][e]``, read by plain indexing of the stored relation at the
edge's CSR slot). Nothing here knows a slot layout.

What an entry reaches through ``cell.reference``: ``init_layers``,
``gather_rows``, ``forward``, ``loss_and_grads``, ``adam_init`` /
``adam_update``, ``ADAM_B1`` and ``FAULTS``.

The equations (OGB ``examples/lsc/mag240m/rgnn.py``, ``RGNN(model='rgat')``:
per relation a PyG ``GATConv(in, out, heads, add_self_loops=False)``,
``torch.nn.BatchNorm1d`` in training mode). A layer has sources ``X_s``
(the frontier after its hop), targets ``X_t = X_s[:T]`` and the hop's
sampled edges ``j -> i``, each with its relation ``r``; no edge is added
or dropped (a sampled ``j == i`` stays):

    H_r = X_s W_r                    every source under every relation,
                                     no bias; targets read H_r[:T]
    e_ij^h = LeakyReLU_0.2(<H_r,j^h, a_src_r^h> + <H_r,i^h, a_dst_r^h>)
    alpha_ij^h = softmax over the valid j in N_r(i)
    conv_r(i) = concat_h sum_j alpha_ij^h H_r,j^h + b_r
                (b_r only if the block holds an edge of relation r)
    z = BN(sum_r conv_r + X_t W_skip + b_skip)   mean and biased variance
                                     over the valid targets of every type
    ELU, dropout(0.5)

and after the layers ``Linear -> BN (over the valid seeds) -> ReLU ->
Dropout(0.5) -> Linear``; the loss is the mean cross-entropy over the
batch's rows.

``H_r`` is the literal product of every source row by every relation's
weight, as PyG computes it. It is computed a relation and a head at a
time under ``jax.checkpoint``, so that the full-size frontier fits beside
the table: the same arithmetic, in blocks.

Departures from the published script, each also under the configuration's
``assumed``: ``BatchNorm1d``'s running averages feed evaluation only and
are not kept; Adam's learning rate is constant (the script's ``StepLR``
acts once per 25 epochs); a target slot that holds no node yields a zero
row after each batch norm (the published blocks have no padding).

``dtype`` chooses what the arithmetic runs in: ``float32`` is the
reference (matmuls at ``highest``), ``bfloat16`` the control of "How
correct is decided". ``fault`` plants one of this model's own faults:
``one_relation`` (every edge taken as relation 0: the relations are
ignored), ``self_edge`` (every valid target given a self edge of
relation 0, as a layer with ``add_self_loops=True`` would) or
``norm_over_padding`` (the layers' batch statistics over every target
slot, nodes or not).

What no comparison of losses, gradients or weights can see: which
relations' biases enter a layer. A bias is a constant a column over the
targets, and the batch norm that follows takes each column's mean away,
so it moves neither the loss nor any other gradient, and its own
gradient is nought to rounding (``check.train_numbers`` leaves such a
leaf out of ``update_gap``). The gate is held to the equations by the
CPU tests, on the layer's output before the norm.
"""

from __future__ import annotations

import hashlib

import jax
import jax.numpy as jnp
import numpy as np

ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.999, 1e-8
NEGATIVE_SLOPE, BN_EPS = 0.2, 1e-5
FAULTS = ("one_relation", "self_edge", "norm_over_padding")


def _glorot(key, shape):
    limit = np.sqrt(6.0 / (shape[-2] + shape[-1]))
    return jax.random.uniform(key, shape, jnp.float32, -limit, limit)


def _linear(key, fan_in, fan_out):
    """``torch.nn.Linear``'s own initial weights: both uniform in
    ``+-1/sqrt(fan_in)``."""
    kw, kb = jax.random.split(key)
    limit = 1.0 / np.sqrt(fan_in)
    return (jax.random.uniform(kw, (fan_in, fan_out), jnp.float32, -limit,
                               limit),
            jax.random.uniform(kb, (fan_out,), jnp.float32, -limit, limit))


def init_layers(key, feature_dim: int, hidden_dim: int, num_classes: int,
                num_layers: int, heads: int, relations: int):
    """``{"convs": [...], "head": {...}}``: PyG's and torch's own initial
    distributions (Glorot for every ``W_r`` and attention vector, zero
    conv biases, ``Linear``'s uniform for the skip and the head, ones and
    zeros for the batch norms). A conv's weights are stacked over the
    relations: ``w`` [R, in, heads x out], ``a_src`` / ``a_dst`` [R,
    heads, out], ``b`` [R, heads x out]."""
    out = hidden_dim // heads
    convs = []
    for i in range(num_layers):
        fan_in = feature_dim if i == 0 else hidden_dim
        kw, ks, kd, kskip = jax.random.split(jax.random.fold_in(key, i), 4)
        w_skip, b_skip = _linear(kskip, fan_in, hidden_dim)
        convs.append({
            "w": _glorot(kw, (relations, fan_in, heads * out)),
            "a_src": _glorot(ks, (relations, heads, out)),
            "a_dst": _glorot(kd, (relations, heads, out)),
            "b": jnp.zeros((relations, heads * out), jnp.float32),
            "w_skip": w_skip, "b_skip": b_skip,
            "bn_scale": jnp.ones((hidden_dim,), jnp.float32),
            "bn_bias": jnp.zeros((hidden_dim,), jnp.float32)})
    k0, k1 = jax.random.split(jax.random.fold_in(key, num_layers))
    w0, b0 = _linear(k0, hidden_dim, hidden_dim)
    w1, b1 = _linear(k1, hidden_dim, num_classes)
    return {"convs": convs,
            "head": {"w0": w0, "b0": b0, "w1": w1, "b1": b1,
                     "bn_scale": jnp.ones((hidden_dim,), jnp.float32),
                     "bn_bias": jnp.zeros((hidden_dim,), jnp.float32)}}


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
    """Rows of the table for a -1-padded frontier, by plain indexing, as
    stored (a 16-bit table gives 16-bit rows); empty slots are zero."""
    x = feat[jnp.clip(n_id, 0, feat.shape[0] - 1)]
    return x * (n_id >= 0).astype(x.dtype)[:, None]


def _matmul(a, b):
    return jnp.matmul(a, b, precision="highest")


def _batch_norm(z, mask, scale, bias, dtype):
    """Training-mode ``BatchNorm1d`` over the rows ``mask`` marks; the
    other rows come back zero."""
    keep = mask[:, None].astype(dtype)
    n = jnp.maximum(mask.sum().astype(dtype), 1)
    mean = (z * keep).sum(axis=0) / n
    var = (((z - mean) * keep) ** 2).sum(axis=0) / n
    y = (z - mean) / jnp.sqrt(var + BN_EPS) * scale.astype(dtype) \
        + bias.astype(dtype)
    return (y * keep).astype(dtype)


def _dropout(x, key, index: int, rate: float, dtype):
    if key is None:
        return x
    keep = jax.random.bernoulli(dropout_key(key, index), 1.0 - rate, x.shape)
    return jnp.where(keep, x / (1.0 - rate), 0).astype(dtype)


def _one_head(x, w, a_src, a_dst, src, dst, keep, targets: int):
    """One relation's one head, ``[targets, out]``: every source row
    projected by the relation's weight, logits on the edge list, a
    softmax over each target's edges of this relation, the weighted sum
    of the sources' rows."""
    h = _matmul(x, w).astype(x.dtype)
    e = jax.nn.leaky_relu((h * a_src).sum(-1)[src]
                          + (h[:targets] * a_dst).sum(-1)[dst],
                          NEGATIVE_SLOPE)
    e = jnp.where(keep, e, -jnp.inf)
    top = jax.ops.segment_max(e, dst, num_segments=targets)
    top = jnp.where(jnp.isfinite(top), top, 0)
    wgt = jnp.where(keep, jnp.exp(e - top[dst]), 0).astype(h.dtype)
    total = jax.ops.segment_sum(wgt, dst, num_segments=targets)
    alpha = wgt / jnp.where(total > 0, total, 1)[dst]
    return jax.ops.segment_sum(h[src] * alpha[:, None], dst,
                               num_segments=targets)


def rgat_conv(p, x, hop, relation, target_mask, *, dtype=jnp.float32,
              fault=None):
    """One layer's sum over the relations of PyG's ``GATConv`` over the
    hop's edge list; ``relation`` is each edge's stored relation;
    ``target_mask`` says which of the first ``len(target_mask)`` sources
    are nodes."""
    targets = target_mask.shape[0]
    relations, heads, out = p["a_src"].shape
    src, dst = jnp.asarray(hop.col), jnp.asarray(hop.row)
    rel = jnp.asarray(relation)
    if fault == "one_relation":
        rel = jnp.zeros_like(rel)
    keep = (src >= 0) & (dst >= 0) & target_mask[jnp.clip(dst, 0)]
    if fault == "self_edge":
        me = jnp.arange(targets, dtype=src.dtype)
        src, dst = jnp.concatenate([src, me]), jnp.concatenate([dst, me])
        rel = jnp.concatenate([rel, jnp.zeros_like(me)])
        keep = jnp.concatenate([keep, target_mask])
    src, dst = jnp.where(keep, src, 0), jnp.where(keep, dst, 0)
    head = jax.checkpoint(_one_head, static_argnums=(7,))
    total = jnp.zeros((targets, heads * out), dtype)
    for r in range(relations):
        mine = keep & (rel == r)
        w = p["w"][r].astype(dtype)
        conv = jnp.concatenate([
            head(x, w[:, i * out:(i + 1) * out], p["a_src"][r, i].astype(dtype),
                 p["a_dst"][r, i].astype(dtype), src, dst, mine, targets)
            for i in range(heads)], axis=-1)
        total = total + conv + jnp.where(jnp.any(mine),
                                         p["b"][r].astype(dtype), 0)
    return total.astype(dtype)


def forward(layers, x, sample, relations, *, dropout=None, rate: float = 0.5,
            dtype=jnp.float32, fault=None):
    """Logits ``[len(sample.seeds), classes]``. ``sample.hops`` are in
    sampling order and ``relations[i]`` is hop ``i``'s edges' stored
    relations; layer ``i`` runs over hop ``len(hops) - 1 - i``, whose
    targets are that hop's seeds: the batch for hop 0, the frontier of the
    hop before otherwise. ``dropout`` is the key the step's dropout draws
    from, or None."""
    x = x.astype(dtype)
    hops = sample.hops
    n = len(hops)
    seeds_of = [jnp.asarray(sample.seeds)] + [jnp.asarray(h.n_id)
                                              for h in hops[:-1]]
    for i, p in enumerate(layers["convs"]):
        mask = seeds_of[n - 1 - i] >= 0
        t = mask.shape[0]
        z = rgat_conv(p, x, hops[n - 1 - i], relations[n - 1 - i], mask,
                      dtype=dtype, fault=fault)
        z = z + _matmul(x[:t], p["w_skip"].astype(dtype)) \
            + p["b_skip"].astype(dtype)
        over = jnp.ones_like(mask) if fault == "norm_over_padding" else mask
        z = _batch_norm(z.astype(dtype), over, p["bn_scale"], p["bn_bias"],
                        dtype)
        x = _dropout(jax.nn.elu(z), dropout, i, rate, dtype)
    head = layers["head"]
    z = _matmul(x, head["w0"].astype(dtype)) + head["b0"].astype(dtype)
    z = _batch_norm(z.astype(dtype), seeds_of[0] >= 0, head["bn_scale"],
                    head["bn_bias"], dtype)
    z = _dropout(jax.nn.relu(z), dropout, len(layers["convs"]), rate, dtype)
    return _matmul(z, head["w1"].astype(dtype)) + head["b1"].astype(dtype)


def cross_entropy(logits, labels):
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.mean(logz - picked)


def loss_and_grads(layers, feat, sample, relations, labels, dropout, *,
                   dtype=jnp.float32, rows=None, fault=None):
    """Mean cross-entropy over the batch rows ``rows`` (all by default)
    and its gradient by every weight. The frontier's rows are read from
    the table ``feat`` as it is stored (16-bit rows) and converted to
    ``dtype``: exactly, where that is float32."""
    x = gather_rows(feat, jnp.asarray(sample.hops[-1].n_id))
    labels = jnp.asarray(labels)

    def loss_of(p):
        logits = forward(p, x, sample, relations, dropout=dropout,
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
