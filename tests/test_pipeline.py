"""Fused sample+gather+train pipeline tests."""

import numpy as np
import jax
import jax.numpy as jnp
import optax
import pytest

from quiver_tpu import Feature, GraphSageSampler
from quiver_tpu.models import GraphSAGE
from quiver_tpu.parallel import TrainState
from quiver_tpu.pipeline import make_fused_train_step, make_fused_eval_fn
from quiver_tpu.utils.synthetic import community_graph
from tests.conftest import model_primitives, onehot_loss


@pytest.fixture(scope="module")
def setup():
    topo, feat, comm = community_graph(400, 4, seed=3)
    feature = Feature(device_cache_size="1G").from_cpu_tensor(feat)
    sampler = GraphSageSampler(topo, [5, 5])
    model = GraphSAGE(hidden=32, out_dim=4, num_layers=2, dropout=0.0)
    return topo, feature, sampler, model, comm


def test_fused_step_learns(setup):
    topo, feature, sampler, model, comm = setup
    tx = optax.adam(1e-2)
    rng = np.random.default_rng(0)
    B = 32
    seeds0 = jnp.asarray(rng.integers(0, topo.node_count, B), jnp.int32)
    b0 = sampler.sample(np.asarray(seeds0))
    params = model.init(jax.random.PRNGKey(0), feature[b0.n_id], b0.layers)
    state = TrainState.create(params, tx)
    step = make_fused_train_step(
        sampler, feature,
        lambda p, x, blocks, train=False, rngs=None: model.apply(
            p, x, blocks, train=train, rngs=rngs
        ), tx,
    )
    losses = []
    ones = jnp.ones((B,), bool)
    for i in range(25):
        seeds = jnp.asarray(rng.integers(0, topo.node_count, B), jnp.int32)
        labels = jnp.asarray(np.asarray(comm)[np.asarray(seeds)])
        state, loss = step(state, seeds, labels, ones,
                           jax.random.PRNGKey(i))
        losses.append(float(loss))
    assert losses[-1] < losses[0] * 0.7, losses[::5]

    ev = make_fused_eval_fn(
        sampler, feature,
        lambda p, x, blocks, train=False, rngs=None: model.apply(
            p, x, blocks, train=train, rngs=rngs
        ),
    )
    seeds = jnp.asarray(rng.integers(0, topo.node_count, B), jnp.int32)
    logits = ev(state.params, seeds, jax.random.PRNGKey(99))
    pred = np.asarray(jnp.argmax(logits[:B], -1))
    acc = (pred == np.asarray(comm)[np.asarray(seeds)]).mean()
    assert acc > 0.5, acc


def test_fused_requires_full_cache(setup):
    topo, _, sampler, model, _ = setup
    rng = np.random.default_rng(0)
    feat = rng.normal(size=(topo.node_count, 4)).astype(np.float32)
    partial = Feature(device_cache_size=4 * 4 * 10).from_cpu_tensor(feat)
    with pytest.raises(AssertionError):
        make_fused_train_step(sampler, partial, lambda *a, **k: None,
                              optax.adam(1e-3))


def test_scan_epoch(setup):
    import optax

    from quiver_tpu.pipeline import make_scan_epoch

    topo, feature, sampler, model, comm = setup
    tx = optax.adam(1e-2)
    rng = np.random.default_rng(1)
    B, S = 32, 6
    b0 = sampler.sample(np.arange(B, dtype=np.int64))
    params = model.init(jax.random.PRNGKey(0), feature[b0.n_id], b0.layers)
    state = TrainState.create(params, tx)
    epoch = make_scan_epoch(
        sampler, feature,
        lambda p, x, blocks, train=False, rngs=None: model.apply(
            p, x, blocks, train=train, rngs=rngs
        ), tx,
    )
    seeds = jnp.asarray(rng.integers(0, topo.node_count, (S, B)), jnp.int32)
    labels = jnp.asarray(np.asarray(comm)[np.asarray(seeds)])
    state, losses = epoch(state, seeds, labels, jax.random.PRNGKey(5))
    assert losses.shape == (S,)
    assert np.isfinite(np.asarray(losses)).all()
    # a second epoch continues to improve
    state, losses2 = epoch(state, seeds, labels, jax.random.PRNGKey(6))
    assert float(losses2.mean()) < float(losses.mean())


def test_fused_step_with_ici_sharded_feature(setup):
    """Fused pipeline over an ici_shard (p2p-clique-equivalent) feature:
    XLA inserts the cross-device gather collectives automatically."""
    import optax

    from quiver_tpu.utils.mesh import make_mesh

    topo, _, sampler, model, comm = setup
    mesh = make_mesh(("data",))
    rng = np.random.default_rng(2)
    feat = rng.normal(size=(topo.node_count, 8)).astype(np.float32)
    feature = Feature(device_cache_size="1G",
                      cache_policy="p2p_clique_replicate",
                      mesh=mesh).from_cpu_tensor(feat)
    assert feature.cache_count == topo.node_count
    tx = optax.adam(1e-2)
    B = 32
    b0 = sampler.sample(np.arange(B, dtype=np.int64))
    params = model.init(jax.random.PRNGKey(0), feature[b0.n_id], b0.layers)
    state = TrainState.create(params, tx)
    step = make_fused_train_step(
        sampler, feature,
        lambda p, x, blocks, train=False, rngs=None: model.apply(
            p, x, blocks, train=train, rngs=rngs
        ), tx,
    )
    seeds = jnp.asarray(rng.integers(0, topo.node_count, B), jnp.int32)
    labels = jnp.asarray(np.asarray(comm)[np.asarray(seeds)])
    state, loss = step(state, seeds, labels, jnp.ones((B,), bool),
                       jax.random.PRNGKey(1))
    assert np.isfinite(float(loss))


@pytest.mark.parametrize("dedup,gathers", [("none", False), ("hop", True)])
def test_fused_step_model_gathers_follow_the_block_layout(setup, dedup,
                                                          gathers):
    """Positional blocks (dedup='none'): the model's value_and_grad holds
    no gather and no scatter.  Reindexed blocks (dedup='hop'): both are
    there as before."""
    from quiver_tpu.pipeline import _fused_train_impl, _tables

    topo, feature, _, _, _ = setup
    model = GraphSAGE(hidden=32, out_dim=4, num_layers=2, dropout=0.5)
    sampler = GraphSageSampler(topo, [5, 5], dedup=dedup)
    impl = _fused_train_impl(
        sampler, feature,
        lambda p, x, blocks, train=False, rngs=None: model.apply(
            p, x, blocks, train=train, rngs=rngs), onehot_loss)
    B = 32
    seeds = jnp.arange(B, dtype=jnp.int32)
    b0 = sampler.sample(np.arange(B, dtype=np.int64))
    params = model.init(jax.random.PRNGKey(0), feature[b0.n_id], b0.layers)
    state = TrainState.create(params, optax.adam(1e-2))
    jaxpr = jax.make_jaxpr(impl)(
        _tables(sampler, feature), state, seeds, seeds % 4,
        jnp.ones((B,), bool), jax.random.PRNGKey(1))
    names = model_primitives(jaxpr.jaxpr)
    assert "dot_general" in names  # the scope was found
    found = {n for n in names if "gather" in n or "scatter" in n}
    if gathers:
        assert "gather" in found and "scatter-add" in found, found
    else:
        assert not found, found


def test_fused_step_traces_once(setup):
    """The layout marker is static structure, not a value: a second and a
    third call of the fused step trace nothing."""
    from quiver_tpu.sampler import POSITIONAL

    topo, feature, _, model, comm = setup
    sampler = GraphSageSampler(topo, [5, 5], dedup="none")
    traces = []

    def apply_fn(p, x, blocks, train=False, rngs=None):
        traces.append(blocks[0].layout)
        return model.apply(p, x, blocks, train=train, rngs=rngs)

    tx = optax.adam(1e-2)
    B = 32
    b0 = sampler.sample(np.arange(B, dtype=np.int64))
    params = model.init(jax.random.PRNGKey(0), feature[b0.n_id], b0.layers)
    state = TrainState.create(params, tx)
    step = make_fused_train_step(sampler, feature, apply_fn, tx)
    ones = jnp.ones((B,), bool)
    rng = np.random.default_rng(4)
    for i in range(3):
        seeds = jnp.asarray(rng.integers(0, topo.node_count, B), jnp.int32)
        labels = jnp.asarray(np.asarray(comm)[np.asarray(seeds)])
        state, loss = step(state, seeds, labels, ones, jax.random.PRNGKey(i))
        if i == 0:
            first = len(traces)
    assert first >= 1 and traces == [POSITIONAL] * first
    assert np.isfinite(float(loss))


def test_prefetcher_early_abandonment_does_not_leak_worker():
    """Breaking out of a Prefetcher mid-iteration must stop the worker
    thread (pre-fix: it blocked forever on the full bounded queue)."""
    import threading
    import time

    from quiver_tpu.parallel.prefetch import Prefetcher

    made = []

    def make(i):
        made.append(i)
        return i

    before = set(threading.enumerate())
    p = Prefetcher(range(100), make, depth=2)
    for x in p:
        if x == 3:
            break
    # worker must wind down promptly, not keep producing all 100 items
    deadline = time.time() + 5
    def new_alive():
        return [t for t in threading.enumerate()
                if t not in before and t.is_alive()]
    while new_alive() and time.time() < deadline:
        time.sleep(0.05)
    assert not new_alive()
    assert len(made) < 100


def test_prefetcher_completes_and_raises():
    from quiver_tpu.parallel.prefetch import Prefetcher

    assert list(Prefetcher(range(7), lambda i: i * 2, depth=2)) == [
        0, 2, 4, 6, 8, 10, 12]

    def boom(i):
        if i == 2:
            raise ValueError("bad item")
        return i

    with pytest.raises(ValueError, match="bad item"):
        list(Prefetcher(range(5), boom, depth=2))
