"""Synthetic graph generators for benchmarks/examples/tests.

The reference benches on OGB datasets; in no-egress environments we
generate graphs with matching scale and degree skew (lognormal ≈ the
power-law-ish degree profile of products/reddit).  Centralizes the logic
duplicated across bench/example scripts.
"""

from __future__ import annotations

from typing import Tuple

import numpy as np

from .topology import CSRTopo

__all__ = ["synthetic_csr", "synthetic_products", "synthetic_reddit",
           "community_graph"]


def synthetic_csr(n_nodes: int, n_edges: int, seed: int = 0,
                  sigma: float = 1.0) -> Tuple[np.ndarray, np.ndarray]:
    """Degree-skewed random CSR; returns (indptr, indices)."""
    rng = np.random.default_rng(seed)
    raw = rng.lognormal(mean=3.0, sigma=sigma, size=n_nodes)
    deg = np.maximum(raw / raw.sum() * n_edges, 1).astype(np.int64)
    indptr = np.zeros(n_nodes + 1, dtype=np.int64)
    np.cumsum(deg, out=indptr[1:])
    e = int(indptr[-1])
    indices = rng.integers(0, n_nodes, size=e, dtype=np.int32)
    return indptr, indices


def synthetic_products(seed: int = 0) -> CSRTopo:
    """ogbn-products scale: 2.45M nodes, ~123M edges."""
    indptr, indices = synthetic_csr(2_449_029, 123_718_280, seed)
    return CSRTopo(indptr=indptr, indices=indices)


def synthetic_reddit(seed: int = 0) -> CSRTopo:
    """Reddit scale: 233K nodes, ~114.6M edges (mean degree ~490)."""
    indptr, indices = synthetic_csr(232_965, 114_615_892, seed)
    return CSRTopo(indptr=indptr, indices=indices)


def community_graph(n_nodes: int, n_classes: int, intra_deg: int = 6,
                    inter_deg: int = 2, noise: float = 0.3,
                    feat_extra: int = 0, seed: int = 0):
    """SBM-ish learnable graph: features = class one-hot + noise.

    Returns (CSRTopo, features [N, n_classes+feat_extra], labels [N]).
    Used wherever a loss must demonstrably decrease.
    """
    rng = np.random.default_rng(seed)
    comm = rng.integers(0, n_classes, n_nodes)
    order = np.argsort(comm, kind="stable")
    # class -> contiguous slice for O(1) intra sampling
    bounds = np.searchsorted(comm[order], np.arange(n_classes + 1))
    k = intra_deg + inter_deg
    src = np.repeat(np.arange(n_nodes), k)
    dst = np.empty(n_nodes * k, dtype=np.int64)
    # vectorized intra draws: uniform position inside each node's own
    # class slice (fully vectorized so products-scale graphs build in
    # seconds, not minutes)
    lo = bounds[comm]
    hi = np.maximum(bounds[comm + 1], lo + 1)
    u = rng.random((n_nodes, intra_deg))
    intra = order[(lo[:, None] + u * (hi - lo)[:, None]).astype(np.int64)]
    inter = rng.integers(0, n_nodes, (n_nodes, inter_deg))
    dst.reshape(n_nodes, k)[:, :intra_deg] = intra
    dst.reshape(n_nodes, k)[:, intra_deg:] = inter
    topo = CSRTopo(edge_index=np.stack([src, dst]), node_count=n_nodes)
    feat = np.eye(n_classes, dtype=np.float32)[comm]
    feat += rng.normal(0, noise, feat.shape).astype(np.float32)
    if feat_extra:
        feat = np.concatenate(
            [feat, rng.normal(0, noise, (n_nodes, feat_extra))
             .astype(np.float32)], axis=1,
        )
    return topo, feat, comm.astype(np.int32)
