from .sage import SAGEConv, GraphSAGE
from .gat import GATConv, GAT
from .rgcn import RGCNConv, RGCN
from .mag import MAG240MGNN
from .norm import MaskedBatchNorm, masked_batch_norm

__all__ = ["SAGEConv", "GraphSAGE", "GATConv", "GAT",
           "RGCNConv", "RGCN", "MAG240MGNN", "MaskedBatchNorm",
           "masked_batch_norm"]
