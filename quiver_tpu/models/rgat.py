"""R-GAT over a typed graph whose relations keep their own neighbour
lists: the MLPerf GNN model (``RGNN(model='rgat')`` of GraphLearn-for-
PyTorch's ``examples/igbh``, a PyG ``HeteroConv`` of bipartite
``GATConv``s, one a relation, summed).

It reads the blocks of a per-relation walk (``pyg.sage_sampler
.RelationBlocks``, outermost hop first): each relation's block is a dense
``[targets, fanout]`` slot array whose relation is known when the program
is traced, so no slot is routed or sorted. For relation ``r`` (source type
``s`` -> target type ``t``), a target ``i`` of type ``t`` and its sampled
``r``-neighbours ``N_r(i)``, per head ``h``:

    e_ij = LeakyReLU_0.2(a_src_r . (W_src_r x_j)^h + a_dst_r . (W_dst_r x_i)^h)
    alpha_ij = softmax over j in N_r(i)
    out_r(i) = concat_h sum_j alpha_ij (W_src_r x_j)^h + b_r
    h_i' = sum over the relations r into type(i) of out_r(i)

``W_src_r`` and ``W_dst_r`` are separate (``lin_src`` / ``lin_dst``); the
target's term is ``x_i . (W_dst_r a_dst_r)``, an ``[in, heads]``
product, as the target's own row is never a message. No self edge, no
skip, no norm. ``b_r`` enters every target of ``t`` that holds a node,
whether the block holds an edge of ``r`` or not (PyG: a ``GATConv`` over
no edge returns its bias); a target slot that holds no node yields a
zero row. Between layers ``leaky_relu`` (slope 0.01) and dropout, after
the last a ``Linear`` over the seeds' rows.

The source product of each relation runs over the rows of its source
type's frontier that some slot of the block reads (``live_rows``), and
sows its rows into the ``counters`` collection; the target term's
product runs over the valid targets. Neither reads a row past those
(``clean``): what a padding slot's row holds reaches no output and no
gradient. Every product is ``qt_project``;
the logits, the softmax over each target's slots and the weighted sum
``qt_attention`` (``qt_attention_slots`` beneath: the slot axis,
always).
"""

from __future__ import annotations

import flax.linen as nn
import jax.numpy as jnp

from .. import profiling
from .gat import _group_softmax, _Kernel, _sow_rows, _tile_rows
from .live_rows import live_rows_product


def attend_block(h, dst_term, att_src, adj, slope: float):
    """``[T, heads x out]``: the attention of one relation's block.
    ``h`` [S, heads x out] the source type's rows by ``W_src_r``,
    ``dst_term`` [T, heads] each target's logit term, ``adj`` the block
    (slot ``e`` targets ``e // fanout``). The neighbours' rows are
    gathered slot-major (``[fanout, T, heads, out]``), a slot's logit
    term taken from its gathered row, the softmax and the sum are reduces
    over the slot axis."""
    heads, out = att_src.shape
    k, t = adj.fanout, adj.size[1]
    src = adj.edge_index[0].reshape(t, k).T                    # [k, T]
    ok = (src >= 0) & adj.target_mask()[None]
    rows = h[jnp.where(ok, src, 0).reshape(-1)].reshape(k, t, heads, out)
    logits = (rows * att_src).sum(-1) + dst_term[None]         # [k, T, heads]
    alpha = _group_softmax(logits, ok[..., None], slope)
    return (rows * alpha[..., None]).sum(axis=0).reshape(t, heads * out)


class RelationGATConv(nn.Module):
    """One relation's bipartite ``GATConv(in, out, heads,
    add_self_loops=False)``: ``conv(x_src, x_dst, adj) -> [T, heads x
    out]``, the block's targets the first ``T = adj.size[1]`` rows of
    ``x_dst`` (their type's rows after the hop, whose prefix they are)."""

    out_dim: int
    heads: int
    negative_slope: float = 0.2

    @nn.compact
    def __call__(self, x_src, x_dst, adj):
        heads, out = self.heads, self.out_dim
        w_src = _Kernel((x_src.shape[-1], heads * out), name="lin_src")()
        w_dst = _Kernel((x_dst.shape[-1], heads * out), name="lin_dst")()
        att_src = self.param("att_src", nn.initializers.glorot_uniform(),
                             (heads, out))
        att_dst = self.param("att_dst", nn.initializers.glorot_uniform(),
                             (heads, out))
        bias = self.param("bias", nn.initializers.zeros, (heads * out,))
        valid = adj.target_mask()
        with profiling.scope(profiling.QT_PROJECT):
            # the rows of the source type's frontier that a slot reads,
            # and the targets' logit terms x_i . (W_dst_r a_dst_r)
            live = jnp.max(adj.edge_index[0], initial=-1) + 1
            (h,), rows = live_rows_product(x_src, (w_src,), live,
                                           _tile_rows(x_src.shape[0]),
                                           clean=True)
            v = jnp.einsum("fho,ho->fh", w_dst.reshape(-1, heads, out),
                           att_dst)
            targets = adj.size[1] if adj.valid_targets is None \
                else adj.valid_targets
            (d,), _ = live_rows_product(x_dst, (v,), targets,
                                        _tile_rows(adj.size[1]), clean=True)
        # the target term's [in, heads] product is not counted
        _sow_rows(self, rows, x_src.shape[0])
        with profiling.scope(profiling.QT_ATTENTION):
            with profiling.scope(profiling.QT_ATTENTION_SLOTS):
                y = attend_block(h, d[:adj.size[1]], att_src, adj,
                                 self.negative_slope)
        return jnp.where(valid[:, None], y + bias, 0.0)


class RGAT(nn.Module):
    """``model(x, blocks) -> logits [seeds, out_dim]``: ``x`` the tuple of
    the node types' row blocks at the outermost hop (``walk_frontier``'s
    over a per-relation walk), ``blocks`` its ``RelationBlocks``. Layer
    ``i``'s relation ``r`` is ``conv{i}_{r}``; ``head`` the final
    ``Linear``. ``dropout`` and the slope of the ``leaky_relu`` between
    layers are the published 0.2 and 0.01."""

    hidden_dim: int
    out_dim: int
    num_layers: int
    heads: int = 4
    dropout: float = 0.2
    negative_slope: float = 0.01

    @nn.compact
    def __call__(self, x, blocks, *, train: bool = False):
        for i, hop in enumerate(blocks):
            pairs = hop.types.relations
            out = [None] * len(hop.targets)
            for adj in hop.adjs:
                t, s = pairs[adj.relation]
                conv = RelationGATConv(self.hidden_dim // self.heads,
                                       heads=self.heads,
                                       name=f"conv{i}_{adj.relation}")
                y = conv(x[s], x[t], adj)
                out[t] = y if out[t] is None else out[t] + y
            if i < self.num_layers - 1:
                out = [None if y is None else nn.Dropout(
                    self.dropout, deterministic=not train,
                    name=f"drop{i}_{t}")(
                        nn.leaky_relu(y, self.negative_slope))
                    for t, y in enumerate(out)]
            x = out
        seeds = next(y for y in x if y is not None)
        with profiling.scope(profiling.QT_PROJECT):
            return nn.Dense(self.out_dim, name="head")(seeds)
