"""The reference ``sage`` (what a configuration gets that names no
``reference``): GraphSAGE over a sampled frontier, its loss, its
gradients and Adam, in straightforward float32 ``jax.numpy``.

It imports nothing of ``quiver_tpu`` and takes no weights from it: the
harness makes the weights from the seed and hands the same ones to the
program and to this file. A sample is plain arrays
(``chipbench.reference.Sample``), held against the graph by
``chipbench.reference.check_sample`` before anything here reads it.

What an entry reaches through ``cell.reference``: ``init_layers``,
``gather_rows``, ``forward``, ``loss_and_grads``, ``adam_init`` /
``adam_update`` and ``ADAM_B1``.

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
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.999, 1e-8


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


def loss_and_grads(layers, feat, sample, labels, dropout, *,
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
