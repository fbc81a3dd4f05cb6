"""GCN over sampled dense blocks.

Parity note: the reference's examples are SAGE/GAT, but PyG users swapping
in quiver routinely run GCN through the same sampler; the dense-block
formulation needs only symmetric-ish degree normalization.  Under neighbor
sampling the exact symmetric normalization is approximated per block (as
PyG's GCNConv does with sampled subgraphs): ``1/sqrt((k_v+1)(k_u+1))``
using the sampled counts.
"""

from __future__ import annotations

from typing import Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..sampler import LayerBlock
from .layers import sources

__all__ = ["GCNConv", "GCN"]


class GCNConv(nn.Module):
    features: int
    dtype: object = None

    @nn.compact
    def __call__(self, x: jax.Array, block: LayerBlock) -> jax.Array:
        t = block.nbr_local.shape[0]
        w = nn.Dense(self.features, use_bias=True, dtype=self.dtype,
                     name="lin")(x)
        w_src = sources(w, block)                           # [T, k, F]
        m = block.mask.astype(x.dtype)[..., None]
        deg = block.mask.sum(axis=1).astype(x.dtype)        # [T]
        # self-loop-augmented normalization with sampled degrees
        norm = 1.0 / jnp.sqrt(deg + 1.0)
        agg = (w_src * m).sum(axis=1) * norm[:, None]
        out = (agg + w[:t]) * norm[:, None]
        return out


class GCN(nn.Module):
    hidden: int
    out_dim: int
    num_layers: int = 2
    dropout: float = 0.5
    dtype: object = None

    @nn.compact
    def __call__(self, x: jax.Array, blocks: Tuple[LayerBlock, ...],
                 train: bool = False) -> jax.Array:
        assert len(blocks) == self.num_layers
        for i, blk in enumerate(blocks):
            last = i == self.num_layers - 1
            x = GCNConv(self.out_dim if last else self.hidden,
                        dtype=self.dtype, name=f"gcn{i}")(x, blk)
            if not last:
                x = nn.relu(x)
                x = nn.Dropout(self.dropout, deterministic=not train)(x)
        return x
