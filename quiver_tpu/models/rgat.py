"""Relational GAT (R-GAT), in three forms.

:class:`RGNN` is the PUBLISHED model: OGB-LSC's MAG240M baseline
(``examples/lsc/mag240m/rgnn.py --model rgat``, which torch-quiver's
``benchmarks/ogbn-mag240m/`` trains over its feature store) in its
homogenised form — ONE id space, node types by id range, ONE sampled
neighbourhood over all relations — over the same homogeneous
``LayerBlock`` s GraphSAGE consumes, so it runs through
``GraphSageSampler`` -> ``Feature`` -> ``pipeline.make_fused_train_step``.
Per layer, with targets ``x_t = x[:T]``::

    out = skip(x_t) + sum over relations r with an edge in this layer of
          GAT_r((x, x_t), edges of r)
    x   = dropout(ELU(BatchNorm(out)))

``GAT_r`` is PyG's bipartite ``GATConv((in, in), hidden/heads, heads,
add_self_loops=False)``: ``s = W_src x``, ``d = W_dst x_t``, ``e_ij =
leaky_relu((s_j . att_src) + (d_i . att_dst))``, a softmax per head over
target i's edges OF THIS RELATION, ``out_i = concat_heads(sum_j alpha_ij
s_j) + bias_r``; a target with no edge of r gets ``bias_r`` alone and a
relation with no edge in the whole layer contributes nothing.  After the
last layer: ``Linear -> BatchNorm -> ReLU -> Dropout -> Linear``.

:class:`TypedRGNN` is the PUBLISHED model of MLPerf Training's GNN
benchmark (MLCommons ``training/graph_neural_network``: ``RGNN(model=
'rgat')`` on IGBH) over a TYPED tree (``GraphSageSampler(schema=...)``,
which draws a fanout per relation): each layer is PyG's ``HeteroConv``
summing, over the relations into a node's type, ``GATConv(in,
hidden/heads, heads, add_self_loops=False)`` with an int ``in_channels``,
so ONE weight ``W_r`` projects a relation's sources and its targets alike::

    h_j = W_r x_j,  g_i = W_r x_i
    e_ij = leaky_relu(a_src_r . h_j + a_dst_r . g_i, 0.2), per head
    o_i^r = concat_heads(sum_j softmax_j(e_ij) h_j) + b_r
    y_i = sum over relations r into type(i) of o_i^r

then leaky ReLU (0.01) and dropout between layers, and one ``Linear`` on
the seeds after the last.  It differs from ``RGNN`` in what the typed tree
makes static: a relation's sources are a block of their own, so each is
ONE dense product of its block (no sort, no row permutes, no
``ragged_dot``); and in the model itself: a shared ``W_r`` (no
``W_src`` / ``W_dst``), no skip, no BatchNorm, no ELU, no MLP head.

:class:`RGAT` is this repo's OWN earlier form over ``hetero.py``'s
per-relation blocks (per-type input projections, ReLU, no skip, no
BatchNorm, no MLP head); it never reaches ``pipeline.py``.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..hetero import HeteroLayerBlock, HeteroSampledBatch
from ..sampler import LayerBlock
from ..telemetry.device_scopes import MODEL_ATTENTION, MODEL_PROJECT
from .gat import lsc_frame
from .layers import (MaskedBatchNorm, _exact, _weighted_sum, sources,
                     targets)

__all__ = ["RGAT", "RGNN", "RelGATConv", "MaskedBatchNorm",
           "grouped_project", "rgnn_apply_fn", "TypedGATConv", "TypedRGNN"]


# ---------------------------------------------------- the published model
def _rows(x, idx):
    """``x[idx]`` for indices that are in range by construction: no
    out-of-range fill, which on a TPU is a pass over the whole result."""
    return x.at[idx].get(mode="promise_in_bounds")


@jax.custom_vjp
def _regroup(x, fwd, bwd, keep):
    """``x[fwd]``, a gather of rows whose backward pass is a gather too
    (``g[bwd]``, zeroed where ``keep`` is false), never a scatter-add:
    ``fwd`` places every row of ``x`` that matters exactly once, ``bwd``
    says where, and ``keep`` which rows of ``x`` those are (None: the
    gradient of a row that does not matter is 0 where it is read)."""
    return _rows(x, fwd)


def _regroup_fwd(x, fwd, bwd, keep):
    return _rows(x, fwd), (bwd, keep)


def _regroup_bwd(res, g):
    bwd, keep = res
    g = _rows(g, bwd)
    if keep is not None:
        g = jnp.where(keep[:, None], g, 0)
    return g, None, None, None


_regroup.defvjp(_regroup_fwd, _regroup_bwd)


def grouped_project(x: jax.Array, group: jax.Array, w: jax.Array,
                    dtype=None, pad_to: Optional[Tuple[int, int]] = None
                    ) -> jax.Array:
    """``y[i] = x[i] @ w[group[i]]``, one product per row.

    ``x [M, D]``, ``w [G, D, N]``, ``group [M]`` in ``0..G``.  A row of
    group ``G`` (no relation, a masked slot) is DEAD: nothing of it is
    computed, its result is a copy of some live row's (finite, for the
    caller to weigh with 0) and its gradient is 0.

    Rows are sorted by group and multiplied by ``jax.lax.ragged_dot``,
    which the TPU's compiler turns into a grouped kernel of ``2 D N``
    FLOPs per live row (forward, and each gradient likewise), where a
    masked product per group costs ``G`` times that and keeps ``G``
    results for the backward pass.  The kernel leaves the rows past its
    last group UNWRITTEN on a TPU (they are zeros only in the CPU's
    lowering), in the product and in the rows' gradient alike, so no dead
    row's place in either is ever read: a dead row reads the first sorted
    row instead.

    ``pad_to = (k, k8)``: rows come in runs of ``k`` per target, and the
    result has ``k8 >= k`` rows per target (``[M // k * k8, N]``, the added
    ones dead too), so that a ``[T, k8, N]`` view of it is free where
    ``k8`` is a multiple of the TPU's 8-row tile and ``k`` is not (a ``[T,
    15, N]`` view of ``[T * 15, N]`` is a pass over all of it)."""
    g, m = w.shape[0], x.shape[0]
    k, k8 = pad_to or (1, 1)
    live = group < g
    perm = jnp.argsort(group, stable=True)      # sorted place -> row
    inv = jnp.argsort(perm)                     # row -> sorted place
    sizes = (group[:, None] == jnp.arange(g)).sum(axis=0, dtype=jnp.int32)
    # into relation order; the rows' gradient comes back by ``inv``
    xs = _regroup(x, perm, inv, live)
    if dtype is not None:
        xs, w = xs.astype(dtype), w.astype(dtype)
    ys = jax.lax.ragged_dot(xs, w, sizes).astype(x.dtype)
    # back into target order, ``k8`` slots a target: slot -> sorted place
    # (a dead slot -> place 0), sorted place -> slot
    place = jnp.pad(jnp.where(live, inv, 0).reshape(m // k, k),
                    ((0, 0), (0, k8 - k))).reshape(-1)
    # (a dead row's own slot weighs 0, so its gradient is 0 as it is read)
    slot = perm // k * k8 + perm % k
    return _regroup(ys, place, slot, None)


class RelGATConv(nn.Module):
    """The sum over relations of bipartite GAT convolutions, over ONE
    dense block whose edges carry a relation each (``rel [T, k]``, -1 for
    none).  Every source is projected ONCE, under its edge's relation
    (:func:`grouped_project`); a target's ``W_dst`` is taken under every
    relation (targets are a sixteenth of the sources or fewer).

    The projection stays ``[T * k8, heads * features]`` throughout (``k8``:
    ``k`` rounded up to 8): a ``[T, k, heads, features]`` view would be
    re-laid by the TPU's compiler (neither ``heads`` nor an odd ``k`` is a
    multiple of its 8-row tile), a pass over 1.6 GB each time.  Per-head
    sums over the lanes are products with a 0/1 matrix (``head_of``), in
    float32 proper, but for the weights' gradient, a float32
    multiply-reduce over each head's slice of lanes
    (``layers._weighted_sum``)."""

    features: int           # per head
    heads: int
    num_relations: int
    negative_slope: float = 0.2
    dtype: object = None

    @nn.compact
    def __call__(self, x: jax.Array, block: LayerBlock,
                 rel: jax.Array) -> jax.Array:
        r, h, c = self.num_relations, self.heads, self.features
        t, k = block.mask.shape
        d_in = x.shape[-1]
        glorot = nn.initializers.glorot_uniform(in_axis=-2, out_axis=-1,
                                                batch_axis=(0,))
        w_src = self.param("w_src", glorot, (r, d_in, h * c))
        w_dst = self.param("w_dst", glorot, (r, d_in, h * c))
        att_src = self.param("att_src", nn.initializers.glorot_uniform(
            batch_axis=(0,)), (r, h, c))
        att_dst = self.param("att_dst", nn.initializers.glorot_uniform(
            batch_axis=(0,)), (r, h, c))
        bias = self.param("bias", nn.initializers.zeros, (r, h * c))

        # k padded to the TPU's 8-row tile: see grouped_project; a masked
        # or padding slot holds a live row's projection and weighs 0
        k8 = -(-k // 8) * 8

        def pad(a):
            return jnp.pad(a, ((0, 0), (0, k8 - k)) + ((0, 0),) * (a.ndim - 2))

        valid = block.mask & (rel >= 0)                         # [T, k]
        rel_c = jnp.clip(rel, 0, r - 1)
        # [T, k8, R]: the edge is a valid one of relation r
        of_r = pad(valid[..., None] & (rel_c[..., None] == jnp.arange(r)))
        # [H * C, H]: lane j belongs to head j // C
        head_of = (jnp.arange(h * c)[:, None] // c
                   == jnp.arange(h)).astype(x.dtype)
        with jax.named_scope(MODEL_PROJECT):
            group = jnp.where(valid, rel, r).reshape(t * k)
            x_src = sources(x, block).reshape(t * k, d_in)
            s = grouped_project(x_src, group, w_src, self.dtype,
                                pad_to=(k, k8))                 # [T k8, HC]
            x_t, w_d = x[:t], w_dst
            if self.dtype is not None:
                x_t, w_d = x_t.astype(self.dtype), w_d.astype(self.dtype)
            d = jnp.einsum("td,rdn->trn", x_t, w_d).astype(x.dtype)
        with jax.named_scope(MODEL_ATTENTION):
            # scores under every relation, one pass over s: the lanes of
            # head h times att_src[r, h], summed, for all (r, h) at once
            a_src = (att_src.reshape(r, 1, h * c) * head_of.T[None]
                     ).reshape(r * h, h * c)
            a_s = _exact(s, a_src.T).reshape(t, k8, r, h)
            a_d = _exact((d * att_dst.reshape(r, h * c)).reshape(
                t * r, h * c), head_of).reshape(t, 1, r, h)
            e = nn.leaky_relu(a_s + a_d, self.negative_slope)   # [T,k8,R,H]
            # the softmax of relation r runs over target i's edges of r
            e = jnp.where(of_r[..., None], e, -jnp.inf)
            top = jax.lax.stop_gradient(e.max(axis=1, keepdims=True))
            top = jnp.where(jnp.isfinite(top), top, 0.0)
            p = jnp.where(of_r[..., None], jnp.exp(e - top), 0.0)
            den = p.sum(axis=1, keepdims=True)
            # an edge has one relation: its weight is the one that is not 0
            alpha = (p / jnp.where(den > 0, den, 1.0)).sum(axis=2)  # [T,k8,H]
            out = _weighted_sum(alpha, s.reshape(t, k8, h * c), head_of)
            # bias_r where the LAYER has an edge of r, for every target
            present = of_r.any(axis=(0, 1))                     # [R]
            # (with no edge at all the projection was never written)
            out = jnp.where(present.any(), out, 0)
            return out + (present[:, None] * bias).sum(axis=0)


class RGNN(nn.Module):
    """The published R-GAT over homogeneous blocks (module docstring).

    Args:
      hidden: layer width (``heads`` x the head size).
      out_dim: classes.
      num_relations: R.
      type_offsets: where each node type's id range starts, and the node
        count last: ``(0, n_type0, n_type0 + n_type1, ..., N)``.
      relation_of: ``relation_of[source type][target type]`` is the
        relation of an edge source -> target, -1 where the schema has none.
    """

    hidden: int
    out_dim: int
    num_relations: int
    type_offsets: Tuple[int, ...]
    relation_of: Tuple[Tuple[int, ...], ...]
    num_layers: int = 2
    heads: int = 4
    dropout: float = 0.5
    dtype: object = None    # e.g. jnp.bfloat16: the matrix products' path

    def edge_relations(self, n_id: jax.Array, block: LayerBlock):
        """``[T, k]`` relation of each edge of ``block`` (-1: the schema
        has none), from its endpoints' id ranges."""
        starts = jnp.asarray(self.type_offsets[1:-1], n_id.dtype)
        ntype = (n_id[:, None] >= starts).sum(axis=-1)
        t = block.mask.shape[0]
        # a select per pair of types, not a gather through a table (a
        # 4-byte gather per edge takes the chip longer than a row gather)
        src, dst = sources(ntype, block), ntype[:t, None]
        rel = jnp.full(src.shape, -1, jnp.int32)
        for a, row in enumerate(self.relation_of):
            for b, q in enumerate(row):
                if q >= 0:
                    rel = jnp.where((src == a) & (dst == b), q, rel)
        return rel

    @nn.compact
    def __call__(self, x: jax.Array, blocks: Tuple[LayerBlock, ...],
                 n_id: jax.Array, n_mask: jax.Array,
                 train: bool = False) -> jax.Array:
        assert len(blocks) == self.num_layers, (
            f"{len(blocks)} blocks for {self.num_layers} layers")

        def conv(i, x, blk):
            rel = self.edge_relations(n_id[:x.shape[0]], blk)
            return RelGATConv(self.hidden // self.heads, self.heads,
                              self.num_relations, dtype=self.dtype,
                              name=f"conv{i}")(x, blk, rel)

        return lsc_frame(self, conv, x, blocks, n_mask, train)


def rgnn_apply_fn(model: nn.Module):
    """The ``apply_fn`` the fused step, the scan epoch, the fused eval and
    ``make_train_step`` take for ``model`` (an :class:`RGNN`, or the
    untyped ``models.GNN``, which is called alike): rows stored narrower than
    float32 are widened, parameters are ``{"params": ...}``, the model
    state ``{"batch_stats": ...}`` (``model.init`` returns both)."""

    def apply_fn(params, x, blocks, train=False, rngs=None, frontier=None,
                 model_state=None):
        variables = {**params, **model_state}
        x = x.astype(jnp.float32)
        if not train:
            return model.apply(variables, x, blocks, *frontier), model_state
        return model.apply(variables, x, blocks, *frontier, train=True,
                           rngs=rngs, mutable=["batch_stats"])

    return apply_fn


# ------------------------------------------- the published IGBH model
class TypedGATConv(nn.Module):
    """PyG's ``HeteroConv({r: GATConv(in, features, heads,
    add_self_loops=False)}, aggr="sum")`` over one layer of a typed tree:
    ``blocks`` are the hop's relation blocks (``LayerBlock.layout``: the
    relation, its targets' runs, its sources' first row), ``t`` the rows
    of the layer's targets (the frontier's prefix).

    A relation's sources are the slice of its ``[n, k]`` block
    (``layers.sources``) and its targets the runs of its target type
    (``layers.targets``): each is ONE dense product with ``W_r``, the one
    weight PyG's ``GATConv`` keeps for an int ``in_channels``.  The softmax
    runs over a target's live draws of the relation; a target with none
    gets ``b_r`` alone.  A row's output is the sum over the relations into
    its type (zeros for a type no relation reaches).  Per-head sums over
    the lanes are products with a 0/1 matrix in float32 proper, but for
    the weights' gradient, a float32 multiply-reduce over each head's
    slice of lanes (``layers._weighted_sum``).
    ``dtype=jnp.bfloat16``: the projections' products and results in
    bfloat16, the attention in float32."""

    features: int           # per head
    heads: int
    num_relations: int
    dtype: object = None

    @nn.compact
    def __call__(self, x: jax.Array, blocks: Tuple[LayerBlock, ...],
                 t: int) -> jax.Array:
        r_all, h, c = self.num_relations, self.heads, self.features
        glorot = nn.initializers.glorot_uniform(in_axis=-2, out_axis=-1,
                                                batch_axis=(0,))
        lin = self.param("lin", glorot, (r_all, x.shape[-1], h * c))
        att_src = self.param("att_src", nn.initializers.glorot_uniform(
            batch_axis=(0,)), (r_all, h, c))
        att_dst = self.param("att_dst", nn.initializers.glorot_uniform(
            batch_axis=(0,)), (r_all, h, c))
        bias = self.param("bias", nn.initializers.zeros, (r_all, h * c))
        # [H * C, H]: lane j belongs to head j // C
        head_of = (jnp.arange(h * c)[:, None] // c
                   == jnp.arange(h)).astype(jnp.float32)

        def project(a, w):
            if self.dtype is not None:
                a, w = a.astype(self.dtype), w.astype(self.dtype)
            return jnp.dot(a, w).astype(jnp.float32)

        summed = {}     # a target type's runs -> the sum of its relations
        for blk in blocks:
            r = blk.layout.relation
            n, k = blk.mask.shape
            with jax.named_scope(MODEL_PROJECT):
                s = project(sources(x, blk).reshape(n * k, -1), lin[r])
                d = project(targets(x, blk), lin[r])
            with jax.named_scope(MODEL_ATTENTION):
                a_s = _exact(s, att_src[r].reshape(h * c, 1) * head_of)
                a_d = _exact(d, att_dst[r].reshape(h * c, 1) * head_of)
                # PyG GATConv's negative_slope
                e = nn.leaky_relu(a_s.reshape(n, k, h) + a_d[:, None],
                                  0.2)                          # [n, k, H]
                live = blk.mask[..., None]
                e = jnp.where(live, e, -jnp.inf)
                top = jax.lax.stop_gradient(e.max(axis=1, keepdims=True))
                top = jnp.where(jnp.isfinite(top), top, 0.0)
                p = jnp.where(live, jnp.exp(e - top), 0.0)
                den = p.sum(axis=1, keepdims=True)
                alpha = p / jnp.where(den > 0, den, 1.0)
                o = _weighted_sum(alpha, s.reshape(n, k, h * c),
                                  head_of) + bias[r]
            runs = blk.layout.targets
            summed[runs] = summed[runs] + o if runs in summed else o
        # the layer's targets in frontier order, run by run
        pieces = []
        for runs, o in summed.items():
            at = 0
            for a, cnt in runs:
                pieces.append((a, o[at:at + cnt]))
                at += cnt
        out, at = [], 0
        for a, rows in sorted(pieces, key=lambda p: p[0]):
            if a > at:
                out.append(jnp.zeros((a - at, h * c), jnp.float32))
            out.append(rows)
            at = a + rows.shape[0]
        if at < t:
            out.append(jnp.zeros((t - at, h * c), jnp.float32))
        return jnp.concatenate(out)


class TypedRGNN(nn.Module):
    """The published R-GAT of MLPerf Training's GNN benchmark over a typed
    tree (module docstring): a :class:`TypedGATConv` per hop, leaky ReLU
    (slope 0.01, ``F.leaky_relu``'s default) and dropout between them, a
    ``Linear`` to ``out_dim`` on the seeds.  ``blocks``: the batch's
    layers, outermost first, each a tuple of relation blocks
    (``GraphSageSampler(schema=...)``); ``x``: the frontier's rows as
    stored (each relation's product widens its own).

    Args:
      hidden: layer width (``heads`` x the head size).
      out_dim: classes.
      num_relations: R, the schema's.
    """

    hidden: int
    out_dim: int
    num_relations: int
    heads: int = 4
    dropout: float = 0.2
    dtype: object = None    # e.g. jnp.bfloat16: the matrix products' path

    @nn.compact
    def __call__(self, x: jax.Array, blocks, train: bool = False
                 ) -> jax.Array:
        for i, hop in enumerate(blocks):
            # the hop appended its blocks' sources to the targets' frontier
            t = x.shape[0] - sum(b.mask.size for b in hop)
            x = TypedGATConv(self.hidden // self.heads, self.heads,
                             self.num_relations, dtype=self.dtype,
                             name=f"conv{i}")(x, hop, t)
            if i != len(blocks) - 1:
                x = nn.leaky_relu(x, 0.01)      # torch's F.leaky_relu
                x = nn.Dropout(self.dropout, deterministic=not train)(x)
        return nn.Dense(self.out_dim, dtype=self.dtype, name="head")(
            x).astype(jnp.float32)


# ------------------------------------ this repo's own per-relation-block form
class _RelAttention(nn.Module):
    """Single-relation multi-head attention (GAT-style) over dense blocks."""

    features: int
    heads: int

    @nn.compact
    def __call__(self, x_src, x_dst, block: HeteroLayerBlock):
        h, f = self.heads, self.features
        t = block.nbr_local.shape[0]
        w_src = nn.Dense(h * f, use_bias=False, name="w_src")(x_src)
        w_src = w_src.reshape(-1, h, f)
        w_dst = nn.Dense(h * f, use_bias=False, name="w_dst")(x_dst[:t])
        w_dst = w_dst.reshape(t, h, f)
        nbr = jnp.take(w_src, block.nbr_local, axis=0)      # [T, k, H, F]
        a_s = self.param("att_src", nn.initializers.glorot_uniform(), (h, f))
        a_d = self.param("att_dst", nn.initializers.glorot_uniform(), (h, f))
        e = nn.leaky_relu(
            (nbr * a_s).sum(-1) + ((w_dst * a_d).sum(-1))[:, None],
            negative_slope=0.2,
        )                                                   # [T, k, H]
        m = block.mask[..., None]
        e = jnp.where(m, e, -jnp.inf)
        alpha = jax.nn.softmax(e, axis=1)
        alpha = jnp.where(m, alpha, 0.0)
        out = (alpha[..., None] * nbr).sum(axis=1)          # [T, H, F]
        return out.reshape(t, h * f)


class RGAT(nn.Module):
    """Hetero R-GAT over ``hetero.py``'s per-relation blocks: this repo's
    own form, NOT the published model (that is :class:`RGNN`).

    Args:
      hidden: per-layer width (= heads * head_dim).
      out_dim: classifier width (applied to the seed type).
      num_layers: must equal the sampler's hop count.
      node_types / in_dims: feature width per node type (for the input
        projection).
    """

    hidden: int
    out_dim: int
    num_layers: int
    in_dims: Dict[str, int]
    heads: int = 4
    dropout: float = 0.5

    @nn.compact
    def __call__(self, xs: Dict[str, jax.Array],
                 batch: HeteroSampledBatch, train: bool = False):
        assert len(batch.layers) == self.num_layers
        # input projection per node type -> common width
        h = {
            t: nn.Dense(self.hidden, name=f"proj_{t}")(x)
            for t, x in xs.items()
        }
        head_dim = self.hidden // self.heads
        for l, hop_blocks in enumerate(batch.layers):
            new_h = {}
            # self transform for every type that has targets this layer
            tgt_len = {}
            for blk in hop_blocks:
                _, _, d_t = blk.relation
                tgt_len[d_t] = max(
                    tgt_len.get(d_t, 0), blk.nbr_local.shape[0]
                )
            for t, ln in tgt_len.items():
                new_h[t] = nn.Dense(self.hidden,
                                    name=f"self_{l}_{t}")(h[t][:ln])
            for blk in hop_blocks:
                s_t, name, d_t = blk.relation
                agg = _RelAttention(
                    head_dim, self.heads,
                    name=f"rel_{l}_{s_t}__{name}__{d_t}",
                )(h[s_t], h[d_t], blk)
                ln = tgt_len[d_t]
                pad = ln - agg.shape[0]
                if pad:
                    agg = jnp.pad(agg, ((0, pad), (0, 0)))
                new_h[d_t] = new_h[d_t] + agg
            # types with no incoming relation this hop keep their prefix
            for t in h:
                if t not in new_h:
                    new_h[t] = h[t]
                else:
                    new_h[t] = nn.relu(new_h[t])
                    new_h[t] = nn.Dropout(
                        self.dropout, deterministic=not train
                    )(new_h[t])
            h = new_h
        return nn.Dense(self.out_dim, name="classifier")(
            h[batch.seed_type][: batch.batch_size]
        )
