"""GAT in flax over the masked layer format (BASELINE.json configs[4]:
"GAT on ogbn-products with attention-weighted neighbor sampling").

``GATConv`` is PyG's layer as the reference's MAG240M benchmark builds it
(``GATConv(in_channels: int, out, heads)``, train_quiver_multi_node.py):
ONE projection shared by sources and targets, sampled edges ``j -> i``
with ``j == i`` dropped and one self edge given to every VALID target, a
LeakyReLU'd additive logit, a softmax over each target's edges, a bias.

    H = X W                                  [S, heads x out]
    e_ij = LeakyReLU(<H_j, a_src> + <H_i, a_dst>)        per head
    alpha_ij = softmax_{j in N(i) u {i}} e_ij
    out_i = concat_h sum_j alpha_ij H_j + b  (or the heads' mean)

The softmax is masked: an empty (-1) edge slot and a target slot that
holds no node get no attention mass. Where the ``Adj`` states its slot
layout (``fanout``: the layers of ``parallel.train.layers_to_adjs``) the
softmax and the weighted sum run over the slot axis, the self edge as a
slot of its own; otherwise they are segment reductions over the target
ids. One path a layer, chosen at trace time from what the ``Adj`` states;
the slot form leaves ``qt_attention_slots`` in the program's names.
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp

from .. import profiling

NEG_INF = -1e30


def segment_softmax(logits: jax.Array, segment_ids: jax.Array,
                    num_segments: int, valid: jax.Array) -> jax.Array:
    """Softmax over edges grouped by target segment, masked."""
    logits = jnp.where(valid, logits, NEG_INF)
    seg_max = jax.ops.segment_max(logits, segment_ids,
                                  num_segments=num_segments)
    seg_max = jnp.where(jnp.isfinite(seg_max), seg_max, 0.0)
    shifted = jnp.where(valid, logits - seg_max[segment_ids], NEG_INF)
    expd = jnp.where(valid, jnp.exp(shifted), 0.0)
    denom = jax.ops.segment_sum(expd, segment_ids, num_segments=num_segments)
    return expd / jnp.maximum(denom[segment_ids], 1e-16)


def _attend_slots(h, att_src, att_dst, src, target_mask, fanout: int,
                  slope: float):
    """The slot form. ``src`` is row 0 of an ``edge_index`` whose slot
    ``e`` targets ``e // fanout``. Per head, with the targets on the
    minor axis: the neighbours' rows are gathered slot-major
    (``[fanout, T, out]``: the gather's own output under another name, as
    in ``models.sage.masked_mean_aggregate``), the logits are ``[fanout +
    1, T]`` with the self edge as the last slot, and the softmax and the
    weighted sum are dense reduces over that leading axis: no scatter.
    A source's logit term ``<H_j, a_src>`` is taken from the gathered row
    of each slot that holds it: a second gather of one number a slot
    costs more on the chip (+32 ms of a 171 ms step at the MAG240M
    shapes) than the row's 256 products."""
    heads, out = att_src.shape
    t = target_mask.shape[0]
    src = src.reshape(t, fanout).T                       # [k, T]
    ok = (src >= 0) & (src != jnp.arange(t, dtype=src.dtype)) & target_mask
    rows = h[jnp.where(ok, src, 0).reshape(-1)].reshape(fanout, t, -1)
    ok = jnp.concatenate([ok, target_mask[None]])        # [k + 1, T]
    own = h[:t]
    result = []
    for i in range(heads):
        lanes = slice(i * out, (i + 1) * out)
        nbr, me = rows[..., lanes], own[:, lanes]
        at_dst = (me * att_dst[i]).sum(-1)               # [T]
        logits = jnp.concatenate([(nbr * att_src[i]).sum(-1),
                                  (me * att_src[i]).sum(-1)[None]]) + at_dst
        logits = jnp.where(ok, nn.leaky_relu(logits, slope), NEG_INF)
        top = logits.max(axis=0)
        top = jnp.where(top > NEG_INF, top, 0.0)
        e = jnp.where(ok, jnp.exp(logits - top), 0.0)
        alpha = e / jnp.maximum(e.sum(axis=0), 1e-16)    # [k + 1, T]
        result.append((nbr * alpha[:fanout, :, None]).sum(axis=0)
                      + me * alpha[fanout][:, None])
    return jnp.concatenate(result, axis=-1)              # [T, heads x out]


def _attend_segments(h, att_src, att_dst, edge_index, target_mask,
                     slope: float):
    """The general form: the sampled edges and the self edges as one list,
    a segment softmax and a segment sum a head."""
    heads, out = att_src.shape
    t = target_mask.shape[0]
    me = jnp.arange(t, dtype=edge_index.dtype)
    src = jnp.concatenate([edge_index[0], me])
    dst = jnp.concatenate([edge_index[1], me])
    ok = jnp.concatenate([
        (edge_index[0] >= 0) & (edge_index[1] >= 0)
        & (edge_index[0] != edge_index[1])
        & target_mask[jnp.clip(edge_index[1], 0)], target_mask])
    s, d = jnp.where(ok, src, 0), jnp.where(ok, dst, 0)
    h3 = h.reshape(h.shape[0], heads, out)
    at_src = (h3 * att_src).sum(-1)                      # [S, heads]
    at_dst = (h3[:t] * att_dst).sum(-1)                  # [T, heads]
    logits = nn.leaky_relu(at_src[s] + at_dst[d], slope)  # [E + T, heads]
    msgs = h3[s]
    result = []
    for i in range(heads):
        alpha = segment_softmax(logits[:, i], d, t, ok)
        result.append(jax.ops.segment_sum(msgs[:, i] * alpha[:, None], d,
                                          num_segments=t))
    return jnp.concatenate(result, axis=-1)


def gat_attention(h, att_src, att_dst, adj, slope: float = 0.2):
    """``[T, heads x out]``: every valid target's attention-weighted sum
    of the projected rows ``h`` [S, heads x out] of its sampled neighbours
    and of itself (targets are the first ``adj.size[1]`` sources). Rows of
    target slots that hold no node come back zero."""
    mask = adj.target_mask()
    with profiling.scope(profiling.QT_ATTENTION):
        if adj.fanout is not None:
            with profiling.scope(profiling.QT_ATTENTION_SLOTS):
                return _attend_slots(h, att_src, att_dst, adj.edge_index[0],
                                     mask, adj.fanout, slope)
        return _attend_segments(h, att_src, att_dst, adj.edge_index, mask,
                                slope)


class GATConv(nn.Module):
    """``conv(x, adj)``: ``x`` [S, in] are the layer's sources, its
    targets their first ``adj.size[1]`` rows."""

    out_dim: int
    heads: int = 1
    concat: bool = True
    negative_slope: float = 0.2

    @nn.compact
    def __call__(self, x, adj):
        heads, out = self.heads, self.out_dim
        with profiling.scope(profiling.QT_PROJECT):
            h = nn.Dense(heads * out, use_bias=False,
                         kernel_init=nn.initializers.glorot_uniform(),
                         name="lin")(x)
        att_src = self.param("att_src", nn.initializers.glorot_uniform(),
                             (heads, out))
        att_dst = self.param("att_dst", nn.initializers.glorot_uniform(),
                             (heads, out))
        bias = self.param("bias", nn.initializers.zeros,
                          (heads * out if self.concat else out,))
        y = gat_attention(h, att_src, att_dst, adj, self.negative_slope)
        if not self.concat:
            y = y.reshape(-1, heads, out).mean(axis=1)
        return y + bias


class GAT(nn.Module):
    hidden_dim: int
    out_dim: int
    num_layers: int
    heads: int = 4
    dropout: float = 0.5

    @nn.compact
    def __call__(self, x, adjs, *, train: bool = False):
        for i, adj in enumerate(adjs):
            last = i == self.num_layers - 1
            conv = GATConv(self.out_dim if last else self.hidden_dim,
                           heads=1 if last else self.heads,
                           concat=not last, name=f"conv{i}")
            x = conv(x, adj)
            if not last:
                x = nn.elu(x)
                x = nn.Dropout(self.dropout, deterministic=not train)(x)
        return x
