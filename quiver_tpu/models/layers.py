"""Dense-block GNN layers (Flax).

The reference delegates models to PyG (``SAGEConv``/``GATConv`` consuming
ragged ``edge_index``); examples at
``/root/reference/examples/pyg/ogbn_products_sage_quiver.py:31-70``.  We
keep the same math but consume quiver_tpu's dense ``[T, k]`` neighbor
blocks: aggregation is a masked mean / masked softmax over each target's
``[k, D]`` source rows — batched, static-shaped, fused by XLA into
MXU-friendly matmuls, with no segment-scatter in sight.  :func:`sources`
finds those rows: a slice of ``x`` when the block says it was built
positionally (``LayerBlock.layout``), a gather through ``nbr_local``
otherwise.
"""

from __future__ import annotations

from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..sampler import LayerBlock

__all__ = ["SAGEConv", "GATConv", "sources"]


def sources(x: jax.Array, block: LayerBlock) -> jax.Array:
    """``[T, k, ...]`` rows of ``x`` holding each target's sampled sources.

    A positional block (``block.layout``, see :class:`LayerBlock`) keeps
    target ``b``'s sources at rows ``T + b*k .. T + b*k + k - 1``, so they
    are ``x[T:]`` viewed ``[T, k, ...]``: no gather forward, a pad (not a
    scatter-add) backward.  Masked slots then read their own pad row
    instead of row 0; every consumer multiplies them by a zero mask either
    way.  Any other block is gathered through ``nbr_local``.
    """
    if block.layout is None:
        return jnp.take(x, block.nbr_local, axis=0)
    t, k = block.mask.shape
    if x.shape[0] != t * (1 + k):
        raise ValueError(
            f"positional block with T={t} targets and k={k} expects "
            f"x of length T*(1+k)={t * (1 + k)}, got {x.shape[0]}")
    return x[t:].reshape(t, k, *x.shape[1:])


class SAGEConv(nn.Module):
    """GraphSAGE mean aggregator: ``W_self x + W_nbr mean(x_N(v))``.

    Math parity with PyG's SAGEConv as used in the reference examples.
    ``dtype=jnp.bfloat16`` runs the matmuls on the MXU's native format
    (params stay float32; activations/compute cast — the standard TPU
    mixed-precision recipe).

    With ``edge_feat [T, k, De]`` (rows of an edge-feature table gathered
    via ``LayerBlock.eid``; the caller masks nothing — invalid slots are
    excluded here), aggregation becomes
    ``W_self x + W_nbr concat(mean x_N(v), mean e)``: the masked mean of
    a concat equals the concat of masked means, so the edge half is
    reduced separately and never materializes a ``[T, k, D+De]`` tensor.
    """

    features: int
    use_bias: bool = True
    dtype: object = None

    @nn.compact
    def __call__(self, x: jax.Array, block: LayerBlock,
                 edge_feat: Optional[jax.Array] = None) -> jax.Array:
        t = block.nbr_local.shape[0]
        x_src = sources(x, block)                           # [T, k, D]
        m = block.mask[..., None].astype(x.dtype)
        cnt = jnp.maximum(m.sum(axis=1), 1.0)               # [T, 1]
        mean_nbr = (x_src * m).sum(axis=1) / cnt            # [T, D]
        if edge_feat is not None:
            mean_e = (edge_feat.astype(x.dtype) * m).sum(axis=1) / cnt
            mean_nbr = jnp.concatenate([mean_nbr, mean_e], axis=-1)
        x_tgt = x[:t]
        out = nn.Dense(self.features, use_bias=self.use_bias,
                       dtype=self.dtype, name="lin_self")(x_tgt)
        out = out + nn.Dense(self.features, use_bias=False,
                             dtype=self.dtype, name="lin_nbr")(mean_nbr)
        return out


class GATConv(nn.Module):
    """Multi-head graph attention over dense neighbor blocks.

    Masked softmax over the k sampled neighbors (+ self loop), per head;
    math parity with PyG GATConv under neighbor sampling.
    """

    features: int
    heads: int = 1
    concat: bool = True
    negative_slope: float = 0.2
    dtype: object = None

    @nn.compact
    def __call__(self, x: jax.Array, block: LayerBlock) -> jax.Array:
        h, f = self.heads, self.features
        t = block.nbr_local.shape[0]
        w = nn.Dense(h * f, use_bias=False, dtype=self.dtype,
                     name="lin")(x)
        w = w.reshape(x.shape[0], h, f)
        w_src = sources(w, block)                            # [T, k, H, F]
        w_tgt = w[:t]                                        # [T, H, F]
        a_src = self.param("att_src", nn.initializers.glorot_uniform(),
                           (h, f))
        a_tgt = self.param("att_tgt", nn.initializers.glorot_uniform(),
                           (h, f))
        e_src = (w_src * a_src).sum(-1)                      # [T, k, H]
        e_tgt = (w_tgt * a_tgt).sum(-1)                      # [T, H]
        # self-loop joins the neighbor set, as in GATConv(add_self_loops);
        # its source-side term uses a_src on the node's own features
        e_self = (w_tgt * a_src).sum(-1) + e_tgt             # [T, H]
        e = nn.leaky_relu(
            jnp.concatenate([e_src + e_tgt[:, None], e_self[:, None]],
                            axis=1),
            negative_slope=self.negative_slope,
        )                                                    # [T, k+1, H]
        mask = jnp.concatenate(
            [block.mask, jnp.ones((t, 1), bool)], axis=1
        )[..., None]
        e = jnp.where(mask, e, -jnp.inf)
        alpha = jax.nn.softmax(e, axis=1)
        alpha = jnp.where(mask, alpha, 0.0)
        vals = jnp.concatenate([w_src, w_tgt[:, None]], axis=1)
        out = (alpha[..., None] * vals).sum(axis=1)          # [T, H, F]
        if self.concat:
            return out.reshape(t, h * f)
        return out.mean(axis=1)
