"""MAG240M-style deep GNN: GAT or GraphSAGE trunk + skip connections +
batch norm + MLP head.

The reference benchmark's model
(benchmarks/ogbn-mag240m/train_quiver_multi_node.py:187-245,
``GNN(model='gat')``): per hop a conv, for the GAT variant a skip
``Linear`` of the targets, ``BatchNorm1d``, ELU (ReLU for GraphSAGE),
dropout; then ``Linear -> BatchNorm1d -> ReLU -> Dropout -> Linear``.
The batch norms take their statistics over the rows of the block that
hold a node (``Adj.valid_targets``; ``models.norm``): the mean and the
biased variance of this batch, as ``BatchNorm1d`` does in training. Its
running averages feed evaluation only and are not kept.

Scopes beneath ``qt_forward``: ``qt_project`` (every product with a
weight matrix), ``qt_attention`` (``models.gat``), ``qt_norm``.
"""

from __future__ import annotations

import flax.linen as nn

from .. import profiling
from .gat import GATConv
from .norm import MaskedBatchNorm
from .sage import SAGEConv


class MAG240MGNN(nn.Module):
    model: str                      # 'graphsage' | 'gat'
    hidden_dim: int
    out_dim: int
    num_layers: int
    heads: int = 4
    dropout: float = 0.5

    @nn.compact
    def __call__(self, x, adjs, *, train: bool = False):
        assert self.model in ("graphsage", "gat")
        for i, adj in enumerate(adjs):
            x_target = x[:adj.size[1]]
            valid = adj.target_mask()
            if self.model == "gat":
                conv = GATConv(self.hidden_dim // self.heads,
                               heads=self.heads, concat=True,
                               name=f"conv{i}")
                h = conv(x, adj)
                with profiling.scope(profiling.QT_PROJECT):
                    h = h + nn.Dense(self.hidden_dim,
                                     name=f"skip{i}")(x_target)
                h = nn.elu(MaskedBatchNorm(name=f"norm{i}")(h, valid))
            else:
                conv = SAGEConv(self.hidden_dim, name=f"conv{i}")
                h = conv(x, x_target, adj.edge_index, adj.fanout)
                h = nn.relu(MaskedBatchNorm(name=f"norm{i}")(h, valid))
            x = nn.Dropout(self.dropout, deterministic=not train)(h)
        with profiling.scope(profiling.QT_PROJECT):
            h = nn.Dense(self.hidden_dim, name="mlp0")(x)
        h = nn.relu(MaskedBatchNorm(name="mlp_norm")(h, valid))
        h = nn.Dropout(self.dropout, deterministic=not train)(h)
        with profiling.scope(profiling.QT_PROJECT):
            return nn.Dense(self.out_dim, name="mlp1")(h)
