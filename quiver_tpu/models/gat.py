"""GAT over sampled dense blocks, in two forms.

:class:`GNN` is the PUBLISHED model: OGB-LSC's MAG240M baseline
``examples/lsc/mag240m/gnn.py --model gat`` (``snap-stanford/ogb``, KDD Cup
2021, arXiv:2103.09430; the "GAT (NS)" row of the MAG240M leaderboard), the
sister script of the ``rgnn.py --model rgat`` that :class:`~.rgat.RGNN`
holds, over the paper-cites-paper graph alone.  Per layer, with targets
``x_t = x[:T]``::

    out = GATConv(x, block) + skip(x_t)
    x   = dropout(ELU(BatchNorm(out)))

``GATConv`` is PyG's, untyped, self-loops on (:class:`~.layers.GATConv`);
``skip`` a ``Linear``.  After the last layer: ``Linear -> BatchNorm ->
ReLU -> Dropout -> Linear``.  The frame around the convolution
(:func:`lsc_frame`) is ``rgnn.py``'s too, and ``RGNN`` calls it.

:class:`GAT` is this repo's OWN earlier form (ELU between layers, the last
layer a one-head mean; no skip, no BatchNorm, no MLP head), which
``models.inference.full_graph_inference`` also evaluates exactly.
"""

from __future__ import annotations

from typing import Callable, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from .layers import GATConv, MaskedBatchNorm
from ..sampler import LayerBlock
from ..telemetry.device_scopes import MODEL_PROJECT

__all__ = ["GAT", "GNN", "lsc_frame"]


def lsc_frame(mod: nn.Module, conv: Callable, x: jax.Array,
              blocks: Tuple[LayerBlock, ...], n_mask: jax.Array,
              train: bool) -> jax.Array:
    """What OGB-LSC's ``gnn.py`` and ``rgnn.py`` put around a convolution,
    called from the ``nn.compact`` method of ``mod`` (which gives
    ``hidden``, ``out_dim``, ``dropout`` and ``dtype``): per layer
    ``conv(i, x, block) + skip<i>(x[:T])``, BatchNorm over the VALID
    targets (``n_mask``: the frontier's padding is no sample), ELU,
    dropout; then ``Linear -> BatchNorm -> ReLU -> Dropout -> Linear``.
    The submodules are ``mod``'s own: ``skip<i>``, ``norm<i>``,
    ``mlp_lin0``, ``mlp_norm``, ``mlp_lin1``."""

    def dense(features, name):
        return nn.Dense(features, dtype=mod.dtype, name=name)

    for i, blk in enumerate(blocks):
        t = blk.mask.shape[0]
        out = conv(i, x, blk)
        with jax.named_scope(MODEL_PROJECT):
            out = out + dense(mod.hidden, f"skip{i}")(x[:t]).astype(
                out.dtype)
        x = MaskedBatchNorm(name=f"norm{i}")(out, n_mask[:t], train)
        x = nn.Dropout(mod.dropout, deterministic=not train)(nn.elu(x))
    valid = n_mask[:x.shape[0]]
    x = dense(mod.hidden, "mlp_lin0")(x).astype(jnp.float32)
    x = nn.relu(MaskedBatchNorm(name="mlp_norm")(x, valid, train))
    x = nn.Dropout(mod.dropout, deterministic=not train)(x)
    return dense(mod.out_dim, "mlp_lin1")(x).astype(jnp.float32)


class GNN(nn.Module):
    """The published GAT of ``gnn.py --model gat`` (module docstring).

    Called as :class:`~.rgat.RGNN` is (``models.rgnn_apply_fn`` serves
    both): ``model.apply(variables, x, blocks, n_id, n_mask, train=)``,
    the frontier's ids unused (one node type), its mask BatchNorm's;
    ``model.init`` returns ``params`` and ``batch_stats``.

    Args:
      hidden: layer width (``heads`` x the head size).
      out_dim: classes.
    """

    hidden: int
    out_dim: int
    num_layers: int = 2
    heads: int = 4
    dropout: float = 0.5
    dtype: object = None    # e.g. jnp.bfloat16: the matrix products' path

    @nn.compact
    def __call__(self, x: jax.Array, blocks: Tuple[LayerBlock, ...],
                 n_id: jax.Array, n_mask: jax.Array,
                 train: bool = False) -> jax.Array:
        assert len(blocks) == self.num_layers, (
            f"{len(blocks)} blocks for {self.num_layers} layers")

        def conv(i, x, blk):
            return GATConv(self.hidden // self.heads, self.heads,
                           dtype=self.dtype, name=f"conv{i}")(x, blk)

        return lsc_frame(self, conv, x, blocks, n_mask, train)


class GAT(nn.Module):
    """This repo's own earlier form (module docstring)."""

    hidden: int
    out_dim: int
    num_layers: int = 2
    heads: int = 4
    dropout: float = 0.5
    dtype: object = None

    @nn.compact
    def __call__(self, x: jax.Array, blocks: Tuple[LayerBlock, ...],
                 train: bool = False) -> jax.Array:
        assert len(blocks) == self.num_layers
        for i, blk in enumerate(blocks):
            last = i == self.num_layers - 1
            x = GATConv(
                self.out_dim if last else self.hidden,
                heads=1 if last else self.heads,
                concat=not last,
                dtype=self.dtype,
                name=f"gat{i}",
            )(x, blk)
            if not last:
                x = nn.elu(x)
                x = nn.Dropout(self.dropout, deterministic=not train)(x)
        return x
