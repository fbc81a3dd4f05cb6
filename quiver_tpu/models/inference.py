"""Exact full-graph layer-wise inference for all model families.

The reference evaluates accuracy with PyG's layer-wise ``inference()``
over ALL neighbors (no sampling) — e.g. the test pass of
``examples/pyg/ogbn_products_sage_quiver.py``.  This module does the exact
per-layer math for :class:`GraphSAGE`, :class:`GCN`, and :class:`GAT`
param layouts, streaming the CSR edge array in chunks so papers100M-scale
graphs fit (aggregation is a chunked ``.at[].add`` segment-sum; GAT does
the numerically-stable two-pass streaming softmax with a segment-max
prepass).

Entry point: :func:`full_graph_inference(model, params, x, indptr,
indices)` — dispatches on the flax module type.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["full_graph_inference"]


def _make_edge_stream(indptr_np, n, edge_chunk):
    """Build the CSR row expansion ONCE (E can be 10^8: ~1 GB host array)
    and return a re-iterable stream of (lo, hi, rows-on-device) chunks —
    every layer of every model walks the same chunks."""
    row_of_edge = np.repeat(
        np.arange(n, dtype=np.int64), indptr_np[1:] - indptr_np[:-1]
    )
    e_total = len(row_of_edge)

    def stream():
        for lo in range(0, e_total, edge_chunk):
            hi = min(lo + edge_chunk, e_total)
            yield lo, hi, jnp.asarray(row_of_edge[lo:hi])

    return stream


@jax.jit
def _seg_add(acc, vals, rows):
    return acc.at[rows].add(vals)


@jax.jit
def _seg_max(acc, vals, rows):
    return acc.at[rows].max(vals)


def _mean_aggregate(h, edge_stream, indices_dev, deg):
    acc = jnp.zeros_like(h)
    for lo, hi, rows in edge_stream():
        acc = _seg_add(acc, jnp.take(h, indices_dev[lo:hi], axis=0), rows)
    return acc / jnp.maximum(deg, 1.0)[:, None]


def _sage_layers(p):
    i = 0
    while f"conv{i}" in p:
        i += 1
    return i


def full_graph_inference(model, params=None, x=None, indptr=None,
                         indices=None, num_layers: int = None,
                         edge_chunk: int = 4_000_000):
    """Exact (no-sampling) logits ``[N, out_dim]`` for a trained model.

    Args:
      model: the flax module the params came from — ``GraphSAGE``, ``GCN``
        or ``GAT`` (used to pick the layer math; sampled-block modules and
        this exact path share parameters).  Legacy SAGE form accepted:
        ``full_graph_inference(params, x, indptr, indices, num_layers)``.
      params: flax params (``model.init`` output).
      x: ``[N, D]`` full feature matrix.
      indptr/indices: CSR (host arrays fine; edges streamed in chunks).
    """
    from .sage import GraphSAGE
    from .gcn import GCN
    from .gat import GAT

    if not hasattr(model, "apply"):  # legacy: (params, x, ip, ix, L)
        legacy = (model, params, x, indptr, indices)
        params, x, indptr, indices = legacy[:4]
        if num_layers is None:
            num_layers = legacy[4]
        assert num_layers is not None, "legacy form needs num_layers"
        model = GraphSAGE(hidden=0, out_dim=0, num_layers=num_layers)
        # (hidden/out_dim unused — layer shapes come from the params)

    p = params["params"] if "params" in params else params
    n = x.shape[0]
    indptr_np = np.asarray(indptr[: n + 1])
    indices_dev = jnp.asarray(np.asarray(indices)[: int(indptr_np[-1])])
    deg = jnp.asarray((indptr_np[1:] - indptr_np[:-1]).astype(np.float32))
    x = jnp.asarray(x)
    edge_stream = _make_edge_stream(indptr_np, n, edge_chunk)

    if isinstance(model, GraphSAGE):
        for i in range(model.num_layers):
            conv = p[f"conv{i}"]
            mean_nbr = _mean_aggregate(x, edge_stream, indices_dev, deg)
            x = (x @ jnp.asarray(conv["lin_self"]["kernel"])
                 + jnp.asarray(conv["lin_self"]["bias"])
                 + mean_nbr @ jnp.asarray(conv["lin_nbr"]["kernel"]))
            if i != model.num_layers - 1:
                x = jax.nn.relu(x)
        return x

    if isinstance(model, GCN):
        # TRUE symmetric normalization with self-loops — the semantics the
        # sampled GCNConv approximates with per-block degrees:
        # out_v = sum_{u in N(v) + {v}} w_u / sqrt((deg_u+1)(deg_v+1))
        norm = 1.0 / jnp.sqrt(deg + 1.0)
        for i in range(model.num_layers):
            lin = p[f"gcn{i}"]["lin"]
            w = x @ jnp.asarray(lin["kernel"]) + jnp.asarray(lin["bias"])
            acc = jnp.zeros_like(w)
            wn = w * norm[:, None]
            for lo, hi, rows in edge_stream():
                acc = _seg_add(
                    acc, jnp.take(wn, indices_dev[lo:hi], axis=0), rows
                )
            x = (acc + wn) * norm[:, None]
            if i != model.num_layers - 1:
                x = jax.nn.relu(x)
        return x

    if isinstance(model, GAT):
        for i in range(model.num_layers):
            last = i == model.num_layers - 1
            layer = p[f"gat{i}"]
            heads = 1 if last else model.heads
            wk = jnp.asarray(layer["lin"]["kernel"])
            f = wk.shape[1] // heads
            w = (x @ wk).reshape(n, heads, f)
            a_src = jnp.asarray(layer["att_src"])      # [H, F]
            a_tgt = jnp.asarray(layer["att_tgt"])
            e_src_all = (w * a_src).sum(-1)            # [N, H] src-side term
            e_tgt_all = (w * a_tgt).sum(-1)            # [N, H] tgt-side term
            slope = 0.2
            e_self = jax.nn.leaky_relu(e_src_all + e_tgt_all, slope)
            # pass 1: streaming segment-max of edge scores (incl. self)
            m = e_self
            for lo, hi, rows in edge_stream():
                e = jax.nn.leaky_relu(
                    jnp.take(e_src_all, indices_dev[lo:hi], axis=0)
                    + jnp.take(e_tgt_all, rows, axis=0), slope)
                m = _seg_max(m, e, rows)
            # pass 2: accumulate exp(e - m_v) * w_u and the denominator
            num = jnp.exp(e_self - m)[..., None] * w   # self-loop term
            den = jnp.exp(e_self - m)
            for lo, hi, rows in edge_stream():
                cols = indices_dev[lo:hi]
                e = jax.nn.leaky_relu(
                    jnp.take(e_src_all, cols, axis=0)
                    + jnp.take(e_tgt_all, rows, axis=0), slope)
                a = jnp.exp(e - jnp.take(m, rows, axis=0))
                num = _seg_add(num, a[..., None] * jnp.take(w, cols, axis=0),
                               rows)
                den = _seg_add(den, a, rows)
            out = num / den[..., None]                 # [N, H, F]
            x = out.reshape(n, heads * f) if not last else out.mean(axis=1)
            x = x + jnp.asarray(layer["bias"])
            if not last:
                x = jax.nn.elu(x)
        return x

    raise TypeError(f"unsupported model type {type(model).__name__}")
