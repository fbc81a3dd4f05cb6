"""End-to-end distributed training driver (shared by the multichip dryrun
and the scaled slow test).

The reference's multi-node path is the papers100M benchmark
(``benchmarks/ogbn-papers100M/train_quiver_multi_node.py:270-306``):
DDP ranks, row-partitioned DistFeature, NCCL exchange.  Here the same
shape runs as one jit program set over a mesh: row-sharded
:class:`DistGraphSampler` (all-to-all seed routing), all-to-all
:class:`DistFeature` over the sampler's row ranges, and a data-parallel
train step (XLA psum = DDP): three launches a step and no host read, the
recipe the benchmark cell ``papers100m-sage-host.train-dist`` times
(``cellbench/programs/sage_dist.py``).

``run_dist_training`` is sized by arguments so the driver's dryrun can run
it tiny and the slow test at 100K+ nodes with the reference fanout.
"""

from __future__ import annotations

from typing import Optional, Sequence

import numpy as np

__all__ = ["run_dist_training"]


def run_dist_training(n_devices: int, n_nodes: int = 256,
                      avg_deg: int = 8, feat_dim: int = 16,
                      batch_per_dev: int = 16,
                      sizes: Sequence[int] = (4, 3),
                      steps: int = 1, classes: int = 8, hidden: int = 32,
                      lr: float = 3e-3, seed: int = 0,
                      learnable_labels: bool = True,
                      hier: Optional[tuple] = None,
                      gather_mode: str = "auto", sample_rng: str = "auto"):
    """Run ``steps`` DP training steps over an ``n_devices`` mesh.

    Returns a dict with per-step ``losses``, the sampler's summed overflow
    counts, and the feature-store overflow counts — callers assert on
    them — plus the ``sampler``, the ``feature`` store and the host truth
    they were built from (``topo``, ``feat``), for callers that compare
    the sharded stack with it.  Labels are a linear function of the
    features by default so the loss can actually decrease (random labels
    can't prove learning).

    ``hier=(n_hosts, hot_frac)`` swaps the flat DistFeature for the
    two-tier :class:`HierFeature` over a ``[n_hosts, n_devices/n_hosts]``
    DCN x ICI mesh (degree-ordered hot set); the result dict then also
    carries summed ``dcn_crossings``.  ``gather_mode`` / ``sample_rng``
    go to the sampler as they are.
    """
    import jax
    import jax.numpy as jnp
    import optax

    from quiver_tpu import CSRTopo, DistFeature
    from quiver_tpu.dist.hier import HierFeature
    from quiver_tpu.dist.sampler import DistGraphSampler
    from quiver_tpu.models import GraphSAGE
    from quiver_tpu.parallel import TrainState, make_train_step
    from quiver_tpu.utils.mesh import make_mesh

    rng = np.random.default_rng(seed)
    deg = rng.poisson(avg_deg, n_nodes).astype(np.int64)
    src = np.repeat(np.arange(n_nodes), deg)
    dst = rng.integers(0, n_nodes, size=len(src))
    topo = CSRTopo(edge_index=np.stack([src, dst]))
    feat = rng.normal(size=(n_nodes, feat_dim)).astype(np.float32)
    if learnable_labels:
        w_true = rng.normal(size=(feat_dim, classes))
        labels = np.argmax(feat @ w_true, axis=1).astype(np.int32)
    else:
        labels = rng.integers(0, classes, n_nodes).astype(np.int32)

    mesh = make_mesh(("data",), devices=jax.devices()[:n_devices])
    hier_feat = None
    if hier is not None:
        from jax.sharding import Mesh

        n_hosts, hot_frac = hier
        C = n_devices // n_hosts
        hmesh = Mesh(
            np.array(jax.devices()[:n_devices]).reshape(n_hosts, C),
            ("dcn", "ici"),
        )
        # degree-descending order so the hot tier holds the high-traffic
        # rows; the sampler keeps GLOBAL ids, so remap at lookup time
        order = np.argsort(-topo.degree, kind="stable")
        old2new = np.empty(n_nodes, dtype=np.int32)
        old2new[order] = np.arange(n_nodes, dtype=np.int32)
        hot_count = int(n_nodes * hot_frac)
        g2h_hier = (np.arange(n_nodes) % n_hosts).astype(np.int32)
        hier_feat = HierFeature.from_global_feature(
            feat[order], hmesh, hot_count=hot_count,
            global2host=g2h_hier)
        hier_old2new = old2new
    sampler = DistGraphSampler(topo, mesh, sizes=list(sizes),
                               gather_mode=gather_mode,
                               sample_rng=sample_rng)
    dist_feat = None
    if hier is None:
        # one partition for graph and table: the sampler's row ranges
        dist_feat = DistFeature.from_row_ranges(feat, mesh,
                                                sampler.row_starts_host)

    model = GraphSAGE(hidden=hidden, out_dim=classes, num_layers=len(sizes),
                      dropout=0.0)
    B = batch_per_dev
    tx = optax.adam(lr)
    step_fn = make_train_step(
        lambda p, x, blocks, train=False, rngs=None: model.apply(
            p, x, blocks, train=train, rngs=rngs
        ),
        tx, mesh=mesh,
    )

    state = None
    losses = []
    sampler_overflow = np.zeros(len(sizes), dtype=np.int64)
    feat_overflow = 0
    dcn_crossings = 0
    masks = jnp.ones((n_devices, B), bool)
    for it in range(steps):
        seeds = rng.integers(0, n_nodes, (n_devices, B))
        n_id, n_mask, num, blocks = sampler.sample(seeds, key=seed + it)
        sampler_overflow += np.asarray(
            sampler.last_overflow
        ).sum(axis=0).astype(np.int64)
        if hier_feat is not None:
            ids = hier_old2new[np.asarray(n_id)]  # hot-order ids
            H, C = hier_feat.H, hier_feat.C
            out = hier_feat.lookup(ids.reshape(H, C, -1))
            st = hier_feat.traffic_stats()
            dcn_crossings += int(st["dcn_crossings"].sum())
            feat_overflow += int(st["drops"].sum())
            xs = jnp.asarray(out).reshape(n_devices, -1, feat_dim)
        else:
            # the device arrays as the sampler returned them: no host trip
            xs = dist_feat.lookup(n_id, n_mask)
            feat_overflow += int(np.asarray(dist_feat.last_overflow).sum())
        if state is None:
            params = model.init(
                jax.random.PRNGKey(1), xs[0],
                jax.tree_util.tree_map(lambda l: l[0], blocks),
            )
            state = TrainState.create(params, tx)
        labels_arr = jnp.asarray(labels[seeds])
        state, loss = step_fn(state, xs, blocks, labels_arr, masks,
                              jax.random.PRNGKey(100 + it))
        losses.append(float(loss))
    return dict(losses=losses, sampler_overflow=sampler_overflow,
                feature_overflow=feat_overflow, mesh=mesh,
                node_count=n_nodes, dcn_crossings=dcn_crossings,
                sampler=sampler, feature=hier_feat or dist_feat,
                topo=topo, feat=feat)
