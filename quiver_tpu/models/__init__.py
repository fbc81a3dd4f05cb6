from .layers import SAGEConv, GATConv
from .sage import GraphSAGE, full_graph_inference
from .gat import GAT, GNN
from .rgat import RGAT, RGNN, TypedRGNN, rgnn_apply_fn
from .gcn import GCN, GCNConv
