"""Scaled distributed-training evidence (VERDICT next #7): 100K nodes,
reference fanout [15,10,5], full dist stack on the virtual 8-mesh, loss
decreases over 20+ steps, zero silent drops at exact caps.

A dry run on 8 virtual CPU devices, as the ``MULTICHIP_r0*.json`` files at
the root are: it says the stack is wired and exact, nothing about chips.
The evidence on chips is the benchmark cell
``papers100m-sage-host.train-dist`` (four v5e chips; PERF.md)."""

import numpy as np
import pytest

from quiver_tpu.dist.e2e import run_dist_training


@pytest.mark.slow
def test_dist_training_100k_loss_decreases():
    out = run_dist_training(
        n_devices=8, n_nodes=100_000, avg_deg=12, feat_dim=16,
        batch_per_dev=32, sizes=[15, 10, 5], steps=24, classes=8,
        lr=3e-3, seed=7,
    )
    losses = out["losses"]
    assert len(losses) == 24
    assert all(np.isfinite(l) for l in losses), losses
    early = float(np.mean(losses[:5]))
    late = float(np.mean(losses[-5:]))
    assert late < early, (early, late, losses)
    # exact caps: nothing silently dropped anywhere in the stack
    assert out["sampler_overflow"].sum() == 0, out["sampler_overflow"]
    assert out["feature_overflow"] == 0


def test_dist_training_quick_smoke():
    """Small config (the dryrun shape) stays healthy — quick variant."""
    out = run_dist_training(n_devices=8, n_nodes=2_000, avg_deg=8,
                            feat_dim=8, batch_per_dev=8, sizes=[5, 4],
                            steps=3, seed=1)
    assert all(np.isfinite(l) for l in out["losses"])
    assert out["sampler_overflow"].sum() == 0
    assert out["feature_overflow"] == 0


def test_dist_training_with_hier_feature():
    """ICI x DCN HierFeature inside a real training loop: loss decreases
    and hot-heavy frontiers keep most feature traffic off the DCN axis."""
    out = run_dist_training(n_devices=8, n_nodes=3_000, avg_deg=10,
                            feat_dim=8, batch_per_dev=8, sizes=[5, 4],
                            steps=6, seed=3, hier=(2, 0.4))
    losses = out["losses"]
    assert all(np.isfinite(l) for l in losses)
    assert np.mean(losses[-2:]) < np.mean(losses[:2])
    assert out["feature_overflow"] == 0
    total_queries = 8 * 8 * (1 + 5 + 5 * 4) * 6  # frontier size x steps
    # degree-ordered hot tier: most queried rows resolve on ICI
    assert out["dcn_crossings"] < 0.45 * total_queries


@pytest.mark.slow
def test_dist_training_1m_nodes_zero_overflow():
    """~1M nodes / 12M edges (VERDICT r4 next #8): bucket capacities and
    int32 shard-offset paths near papers100M reality; exact caps drop
    nothing and the loss still moves."""
    out = run_dist_training(
        n_devices=8, n_nodes=1_000_000, avg_deg=12, feat_dim=16,
        batch_per_dev=32, sizes=[15, 10, 5], steps=6, classes=8,
        lr=3e-3, seed=11,
    )
    losses = out["losses"]
    assert all(np.isfinite(l) for l in losses), losses
    assert losses[-1] < losses[0], losses
    assert out["sampler_overflow"].sum() == 0, out["sampler_overflow"]
    assert out["feature_overflow"] == 0
