"""Tests for tracing, checkpointing, prefetcher, mesh topo."""

import numpy as np
import pytest

from quiver_tpu.utils import trace as trace_mod
from quiver_tpu.utils.trace import (
    trace_scope, Timer, trace_summary, reset_trace, show_tensor_info,
)
from quiver_tpu.utils.checkpoint import (
    save_checkpoint, load_checkpoint, latest_checkpoint,
)
from quiver_tpu.utils.mesh import MeshTopo
from quiver_tpu.parallel.prefetch import Prefetcher, AsyncNeighborSampler


def test_trace_scope_aggregates():
    trace_mod.set_enabled(True)
    reset_trace()
    for _ in range(3):
        with trace_scope("unit"):
            pass
    s = trace_summary()
    assert s["unit"]["count"] == 3
    trace_mod.set_enabled(False)


def test_timer_prints():
    lines = []
    with Timer("t", printer=lines.append):
        pass
    assert lines and "t:" in lines[0]


def test_show_tensor_info():
    lines = []
    show_tensor_info(np.zeros((2, 3)), "x", printer=lines.append)
    assert "shape=(2, 3)" in lines[0]


def test_checkpoint_roundtrip(tmp_path):
    import jax.numpy as jnp
    import optax

    from quiver_tpu.parallel import TrainState

    tx = optax.adam(1e-3)
    params = {"w": jnp.ones((3, 3)), "b": jnp.zeros(3)}
    state = TrainState.create(params, tx)
    f = save_checkpoint(str(tmp_path), state, step=7, extra={"note": "hi"})
    assert latest_checkpoint(str(tmp_path)) == f
    state2, step = load_checkpoint(str(tmp_path), state)
    assert step == 7
    np.testing.assert_array_equal(np.asarray(state2.params["w"]),
                                  np.ones((3, 3)))
    payload = load_checkpoint(f)
    assert payload["extra"]["note"] == "hi"


def test_prefetcher_order_and_exceptions():
    out = list(Prefetcher(range(5), lambda i: i * i, depth=2))
    assert out == [0, 1, 4, 9, 16]

    def boom(i):
        if i == 2:
            raise ValueError("x")
        return i

    with pytest.raises(ValueError):
        list(Prefetcher(range(5), boom))


def test_async_sampler(small_graph):
    s = AsyncNeighborSampler(small_graph, k=4)
    out = s.sample(np.arange(8))
    assert out.nbrs.shape == (8, 4)


def test_mesh_topo():
    t = MeshTopo()
    cliques = t.p2p_clique()
    assert sum(len(v) for v in cliques.values()) == 8  # 8 virtual devices
    assert "Clique" in t.info


def test_mp_reductions_roundtrip(small_graph, rng):
    """ForkingPickler pack/unpack of Feature and sampler (parity: P10)."""
    import io
    import pickle
    from multiprocessing.reduction import ForkingPickler

    import quiver_tpu  # noqa: F401  (registers reducers)
    from quiver_tpu import Feature, GraphSageSampler

    n = small_graph.node_count
    feat = rng.normal(size=(n, 8)).astype(np.float32)
    f = Feature(device_cache_size="1G").from_cpu_tensor(feat)
    buf = io.BytesIO()
    ForkingPickler(buf).dump(f)
    g = pickle.loads(buf.getvalue())
    ids = rng.integers(0, n, 16)
    np.testing.assert_allclose(np.asarray(g[ids]), feat[ids], rtol=1e-6)

    s = GraphSageSampler(small_graph, [4, 3])
    buf = io.BytesIO()
    ForkingPickler(buf).dump(s)
    s2 = pickle.loads(buf.getvalue())
    b = s2.sample(np.arange(8))
    assert b.batch_size == 8


def test_config_env_and_update(monkeypatch):
    import quiver_tpu.config as cfg_mod

    monkeypatch.setattr(cfg_mod, "_config", None)
    monkeypatch.setenv("QUIVER_TPU_CACHE_POLICY", "p2p_clique_replicate")
    c = cfg_mod.get_config()
    assert c.cache_policy == "p2p_clique_replicate"
    cfg_mod.update(cache_policy="device_replicate")
    assert cfg_mod.get_config().cache_policy == "device_replicate"
    import pytest as _pytest

    with _pytest.raises(AttributeError):
        cfg_mod.update(nope=1)
    monkeypatch.setattr(cfg_mod, "_config", None)


def test_checkpoint_root_named_ckpt_prefix(tmp_path):
    """A root dir whose own name starts with ckpt_ still resolves to its
    newest child (content-based, not name-based, detection)."""
    import jax.numpy as jnp
    import optax

    from quiver_tpu.parallel import TrainState

    root = tmp_path / "ckpt_run1"
    tx = optax.adam(1e-3)
    state = TrainState.create({"w": jnp.ones(4)}, tx)
    save_checkpoint(str(root), state, step=5)
    state2, step = load_checkpoint(str(root), state)
    assert step == 5


@pytest.mark.parametrize("helper,nodes,edges", [
    ("synthetic_products", 2_449_029, 123_718_280),
    ("synthetic_reddit", 232_965, 114_615_892)])
def test_synthetic_shapes_are_the_published_ones(monkeypatch, helper,
                                                 nodes, edges):
    """The same constants bench.py and chip_smoke.py use (the Reddit
    helper once built a tenth of the edges)."""
    import bench
    import chip_smoke
    from quiver_tpu.utils import synthetic

    asked = []

    def fake_csr(n, e, seed=0):
        asked.append((n, e))
        return np.zeros(2, np.int64), np.zeros(0, np.int32)

    monkeypatch.setattr(synthetic, "synthetic_csr", fake_csr)
    getattr(synthetic, helper)()
    assert asked == [(nodes, edges)]
    name = helper.split("_")[1].upper()
    assert (getattr(bench, name + "_NODES"),
            getattr(bench, name + "_EDGES")) == (nodes, edges)
    shape = getattr(chip_smoke, name)
    assert (shape.nodes, shape.edges) == (nodes, edges)
