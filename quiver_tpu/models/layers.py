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
from ..telemetry.device_scopes import MODEL_ATTENTION, MODEL_PROJECT

__all__ = ["SAGEConv", "GATConv", "MaskedBatchNorm", "sources", "targets"]


def sources(x: jax.Array, block: LayerBlock) -> jax.Array:
    """``[T, k, ...]`` rows of ``x`` holding each target's sampled sources.

    A positional block (``block.layout``, see :class:`LayerBlock`) keeps
    target ``b``'s sources at rows ``T + b*k .. T + b*k + k - 1``, so they
    are ``x[T:]`` viewed ``[T, k, ...]``: no gather forward, a pad (not a
    scatter-add) backward.  Masked slots then read their own pad row
    instead of row 0; every consumer multiplies them by a zero mask either
    way.  A typed tree's block keeps them at ``sources + b*k ..``, its first
    source row (``block.layout.place``): the slice of its ``T k`` rows.
    Any other block is gathered through ``nbr_local``.
    """
    if block.layout is None:
        return jnp.take(x, block.nbr_local, axis=0)
    t, k = block.mask.shape
    _, s = block.layout.place(t)
    if x.shape[0] < s + t * k:
        raise ValueError(
            f"positional block with T={t} targets and k={k} expects "
            f"x of at least {s + t * k} rows, got {x.shape[0]}")
    return x[s:s + t * k].reshape(t, k, *x.shape[1:])


def targets(x: jax.Array, block: LayerBlock) -> jax.Array:
    """``[T, ...]`` rows of ``x`` that are the block's targets: ``x[:T]``,
    or a positional block's ``(start, count)`` runs in order
    (``block.layout.place``; the untyped tree's is ``x[:T]`` too), sliced
    and put end to end."""
    t = block.mask.shape[0]
    if block.layout is None:
        return x[:t]
    runs, _ = block.layout.place(t)
    return jnp.concatenate([x[a:a + n] for a, n in runs])


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


class MaskedBatchNorm(nn.Module):
    """PyTorch's ``BatchNorm1d`` over the VALID rows only: batch mean and
    biased variance when training, running averages (``batch_stats``:
    ``mean``, ``var``; the variance updated unbiased, as PyTorch does)
    otherwise."""

    momentum: float = 0.1
    eps: float = 1e-5

    @nn.compact
    def __call__(self, x: jax.Array, valid: jax.Array,
                 train: bool) -> jax.Array:
        f = x.shape[-1]
        scale = self.param("scale", nn.initializers.ones, (f,))
        bias = self.param("bias", nn.initializers.zeros, (f,))
        ra_mean = self.variable("batch_stats", "mean",
                                lambda: jnp.zeros((f,), jnp.float32))
        ra_var = self.variable("batch_stats", "var",
                               lambda: jnp.ones((f,), jnp.float32))
        if train:
            m = valid.astype(x.dtype)[:, None]
            n = jnp.maximum(m.sum(), 1.0)
            mean = (x * m).sum(axis=0) / n
            var = (jnp.square(x - mean) * m).sum(axis=0) / n
            if not self.is_initializing():
                mom = self.momentum
                ra_mean.value = (1 - mom) * ra_mean.value + mom * mean
                ra_var.value = ((1 - mom) * ra_var.value
                                + mom * var * n / jnp.maximum(n - 1.0, 1.0))
        else:
            mean, var = ra_mean.value, ra_var.value
        return (x - mean) * jax.lax.rsqrt(var + self.eps) * scale + bias


def _exact(a: jax.Array, b: jax.Array) -> jax.Array:
    """``a @ b`` in float32 proper: for the small products that stand in
    for an elementwise sum (a per-head reduction over lanes), which the
    published model computes in float32."""
    return jnp.dot(a, b, precision=jax.lax.Precision.HIGHEST)


def _widen(alpha: jax.Array, head_of: jax.Array) -> jax.Array:
    """``[..., H] -> [..., H * C]``: each head's weight over its own lanes
    (``head_of [H * C, H]`` is 0/1)."""
    return sum(alpha[..., i, None] * head_of[:, i]
               for i in range(head_of.shape[1]))


@jax.custom_vjp
def _weighted_sum(alpha, s, head_of):
    """``out[t] = sum_k alpha[t, k, head of lane] * s[t, k, lane]`` with a
    backward pass of its own: ONE pass over ``s`` for the weights'
    gradient, a float32 multiply-reduce per head over the head's static
    slice of lanes (head ``i`` owns lanes ``[i F, (i + 1) F)``, ``F =
    s.shape[-1] // H`` with ``H = head_of.shape[1]``, as every caller's
    ``head_of`` says): ``dout``'s product with ``s`` is never written, and
    no ``[.., H, F]`` view is re-laid (the transposed sum is a pass per
    head)."""
    return (_widen(alpha, head_of) * s).sum(axis=1)


def _weighted_sum_fwd(alpha, s, head_of):
    return _weighted_sum(alpha, s, head_of), (alpha, s, head_of)


def _weighted_sum_bwd(res, g):
    alpha, s, head_of = res
    f = s.shape[-1] // head_of.shape[1]
    # stacked [T, H, n], then swapped: stacked on the last axis, the TPU's
    # compiler lays the softmax's [T, n, H] arrays out with n in the lanes,
    # which cost the IGBH step's forward 4.7 ms on a v5e, where n is 5
    d_alpha = jnp.stack([(s[..., i * f:(i + 1) * f]
                          * g[:, None, i * f:(i + 1) * f]).sum(axis=-1)
                         for i in range(head_of.shape[1])], axis=1)
    return (jnp.swapaxes(d_alpha, 1, 2),
            _widen(alpha, head_of) * g[:, None, :], jnp.zeros_like(head_of))


_weighted_sum.defvjp(_weighted_sum_fwd, _weighted_sum_bwd)


def _slots(src: jax.Array, tgt: jax.Array, n: int) -> jax.Array:
    """``[T, n, ...]``: a target's ``k`` sources (``src [T, k, ...]``),
    then the target itself (``tgt [T, ...]``, the self-loop), then zeros
    up to ``n`` slots."""
    a = jnp.concatenate([src, tgt[:, None]], axis=1)
    return jnp.pad(a, ((0, 0), (0, n - a.shape[1]))
                   + ((0, 0),) * (a.ndim - 2))


class GATConv(nn.Module):
    """PyG's ``GATConv(in, features, heads, add_self_loops=True)`` over a
    dense neighbour block: ONE projection ``lin`` for sources and targets
    (no bias), ``e_ts = leaky_relu(<h_s, att_src> + <h_t, att_tgt>)`` per
    head, a softmax over the target's live sampled neighbours AND the
    target itself (the self-loop: a target with no live neighbour returns
    its own projected row), heads concatenated (or averaged), ``+ bias``
    after the aggregation.

    A target's slots (its ``k`` sources, then itself) are kept ``[T, n,
    heads * features]`` with ``n = k + 1`` rounded up to the TPU's 8-row
    tile, the self-loop IN its slot, so the softmax and the weighted sum
    run over one array and no copy puts the target's projection beside its
    neighbours'.  Over a positional block (``LayerBlock.layout``: the
    frontier holds ``T (1 + k)`` rows, a row a slot) the INPUT rows are
    laid out by slot and projected there, the same ``T (1 + k)`` products;
    a ``[T, k, ...]`` view of the PROJECTION would be re-laid by the TPU's
    compiler in both passes, and it is the wider array (PERF.md, PR 30,
    PR 36).  Over any other block every node is projected once and its
    projection gathered through ``nbr_local``.  Per-head sums over the
    lanes are products with a 0/1 matrix in float32 proper, but for the
    weights' gradient, a float32 multiply-reduce over each head's slice of
    lanes (:func:`_weighted_sum`); a ``[.., H, C]`` view is re-laid too.
    ``dtype=jnp.bfloat16``: the projection's product and its result in
    bfloat16, the attention in float32."""

    features: int           # per head
    heads: int = 1
    concat: bool = True
    negative_slope: float = 0.2
    dtype: object = None

    @nn.compact
    def __call__(self, x: jax.Array, block: LayerBlock) -> jax.Array:
        h, f = self.heads, self.features
        t, k = block.mask.shape
        n = -(-(k + 1) // 8) * 8
        lin = nn.Dense(h * f, use_bias=False, dtype=self.dtype, name="lin")
        att_src = self.param("att_src", nn.initializers.glorot_uniform(),
                             (h, f))
        att_tgt = self.param("att_tgt", nn.initializers.glorot_uniform(),
                             (h, f))
        bias = self.param("bias", nn.initializers.zeros,
                          (h * f if self.concat else f,))
        with jax.named_scope(MODEL_PROJECT):
            if block.layout is None:
                w = lin(x).astype(x.dtype)
                s = _slots(jnp.take(w, block.nbr_local, axis=0), w[:t],
                           n).reshape(t * n, h * f)
            else:
                rows = _slots(sources(x, block), x[:t], n)
                s = lin(rows.reshape(t * n, -1)).astype(x.dtype)
        with jax.named_scope(MODEL_ATTENTION):
            # [H * F, H]: lane j belongs to head j // F
            head_of = (jnp.arange(h * f)[:, None] // f
                       == jnp.arange(h)).astype(s.dtype)
            # both scores of every slot in one pass over s: the lanes of
            # head i times att_src[i], summed, and the same for att_tgt
            att = jnp.concatenate([att_src.reshape(h * f, 1) * head_of,
                                   att_tgt.reshape(h * f, 1) * head_of],
                                  axis=1)                       # [HF, 2H]
            a = _exact(s, att).reshape(t, n, 2 * h)
            # a source's score under att_src, its target's (slot k) under
            # att_tgt; slot k is picked by a masked sum, whose backward
            # pass is a select (a slice's is an update of [T, n, 2H] in
            # place, 5.4 ms on the chip for 14 MB: PERF.md, PR 36)
            is_self = (jnp.arange(n) == k)[:, None]
            a_tgt = jnp.where(is_self, a[..., h:], 0).sum(axis=1,
                                                          keepdims=True)
            e = nn.leaky_relu(a[..., :h] + a_tgt,
                              self.negative_slope)              # [T, n, H]
            live = _slots(block.mask, jnp.ones((t,), bool), n)[..., None]
            alpha = jax.nn.softmax(jnp.where(live, e, -jnp.inf), axis=1)
            out = _weighted_sum(alpha, s.reshape(t, n, h * f), head_of)
            if not self.concat:
                out = out.reshape(t, h, f).mean(axis=1)
            return out + bias
