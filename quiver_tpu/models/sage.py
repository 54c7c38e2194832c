"""GraphSAGE in flax, over the static-shape masked layer format.

The reference keeps the model in PyG (``SAGEConv``; e.g.
examples/multi_gpu/pyg/ogb-products/dist_sampling_ogb_products_quiver.py)
— the framework's job is feeding it. Here the model is in-tree so the
whole step (sample -> gather -> forward/backward) is one XLA program.

Message passing is mean aggregation over the layer's COO; -1-filled
(invalid) edges contribute nothing because their mask zeroes the message
and the count. Where the ``Adj`` states its slot layout (``fanout``: the
layers of ``parallel.train.layers_to_adjs``) the mean is a dense reduce
over the fanout axis; otherwise it is a ``segment_sum`` over the target
ids.
"""

from __future__ import annotations


import flax.linen as nn
import jax
import jax.numpy as jnp

from .. import profiling


def masked_mean_aggregate(x_src: jax.Array, edge_index: jax.Array,
                          num_targets: int,
                          fanout: int | None = None) -> jax.Array:
    """Mean of neighbor features per target node. edge_index [2, E] with
    row 0 = source local id, row 1 = target local id, -1 fill.

    ``fanout`` is ``Adj.fanout``: slot ``e`` belongs to target
    ``e // fanout`` and ``E == num_targets * fanout``. A target's slots
    then lie side by side and row 1 need not be read: the sum is a dense
    reduce over the fanout axis, no scatter (on the TPU a scatter costs
    what a gather does, per index, and the general form below runs two).
    The rows are gathered slot-major, ``[fanout, num_targets, dim]``,
    because that block is the gather's own ``[E, dim]`` output under
    another name (the TPU tiles the last two axes; a ``fanout`` of 5 in
    their place is padded to 8 in a copy of the whole block), and summed
    over the leading axis in slot order, which is the order
    ``segment_sum`` adds them in. Same rows, mask and divisor as below."""
    with profiling.scope(profiling.QT_AGGREGATE):
        if fanout is not None:
            with profiling.scope(profiling.QT_AGGREGATE_DENSE):
                src = edge_index[0].reshape(num_targets, fanout).T
                valid = src >= 0
                rows = x_src[jnp.where(valid, src, 0).reshape(-1)].reshape(
                    fanout, num_targets, -1)
                agg = (rows * valid[..., None].astype(x_src.dtype)).sum(axis=0)
                cnt = valid.sum(axis=0, dtype=x_src.dtype)
                return agg / jnp.maximum(cnt, 1.0)[:, None]
        src, dst = edge_index[0], edge_index[1]
        valid = (src >= 0) & (dst >= 0)
        s = jnp.where(valid, src, 0)
        d = jnp.where(valid, dst, 0)
        msg = x_src[s] * valid[:, None].astype(x_src.dtype)
        agg = jax.ops.segment_sum(msg, d, num_segments=num_targets)
        cnt = jax.ops.segment_sum(valid.astype(x_src.dtype), d,
                                  num_segments=num_targets)
        return agg / jnp.maximum(cnt, 1.0)[:, None]


class SAGEConv(nn.Module):
    """h_t' = W_root h_t + W_nbr mean_{s in N(t)} h_s"""

    out_dim: int
    use_bias: bool = True

    @nn.compact
    def __call__(self, x_src, x_dst, edge_index, fanout=None):
        num_targets = x_dst.shape[0]
        mean_nbr = masked_mean_aggregate(x_src, edge_index, num_targets,
                                         fanout)
        h = nn.Dense(self.out_dim, use_bias=self.use_bias,
                     name="lin_root")(x_dst)
        h = h + nn.Dense(self.out_dim, use_bias=False,
                         name="lin_nbr")(mean_nbr)
        return h


class GraphSAGE(nn.Module):
    """Layer-wise minibatch GraphSAGE (PyG NeighborSampler pattern:
    ``x_target = x[:size[1]]`` per hop, adjs outermost-first)."""

    hidden_dim: int
    out_dim: int
    num_layers: int
    dropout: float = 0.5

    @nn.compact
    def __call__(self, x, adjs, *, train: bool = False):
        for i, adj in enumerate(adjs):
            num_targets = adj.size[1]
            x_target = x[:num_targets]
            dim = self.out_dim if i == self.num_layers - 1 else self.hidden_dim
            x = SAGEConv(dim, name=f"conv{i}")(x, x_target, adj.edge_index,
                                               adj.fanout)
            if i != self.num_layers - 1:
                x = nn.relu(x)
                x = nn.Dropout(self.dropout, deterministic=not train)(x)
        return x
