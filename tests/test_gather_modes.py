"""The hop's two gather paths draw the same samples: ``blocked`` (the
accelerator's: a window of two 128-lane rows per target, row gather +
lane select for the rest) against ``xla`` (the CPU's ``jnp.take``, the
reference), op by op and through every consumer of the hop."""

import numpy as np
import jax
import pytest

from quiver_tpu import GraphSageSampler


def test_blocked_equals_xla(small_graph):
    """blocked window gather (one covering-block gather serves all k
    draws of a seed) samples identically to the xla reference path."""
    seeds = np.arange(32, dtype=np.int64)
    key = jax.random.PRNGKey(9)
    b_x = GraphSageSampler(small_graph, [5, 4],
                           gather_mode="xla").sample(seeds, key=key)
    b_b = GraphSageSampler(small_graph, [5, 4],
                           gather_mode="blocked").sample(seeds, key=key)
    np.testing.assert_array_equal(np.asarray(b_x.n_id),
                                  np.asarray(b_b.n_id))
    np.testing.assert_array_equal(np.asarray(b_x.n_id_mask),
                                  np.asarray(b_b.n_id_mask))
    for lx, lb in zip(b_x.layers, b_b.layers):
        np.testing.assert_array_equal(np.asarray(lx.mask),
                                      np.asarray(lb.mask))
        np.testing.assert_array_equal(np.asarray(lx.nbr_local),
                                      np.asarray(lb.nbr_local))


@pytest.mark.parametrize("U", [1, 2, 3])
@pytest.mark.parametrize("frac", [0.25, 0.02])
def test_blocked_op_exact_with_fallback_and_overflow(U, frac):
    """Op-level: graphs with degrees far beyond U*128 route through the
    compacted fallback (frac=0.25) and the lax.cond wholesale-classic
    path (frac=0.02 with many huge rows) — all bitwise equal to take."""
    import jax.numpy as jnp

    from quiver_tpu.ops.blockgather import blocked_window_gather

    rng = np.random.default_rng(U * 100 + int(frac * 100))
    B, k = 64, 7
    # half the seeds get windows much wider than U rows
    deg = np.where(rng.random(B) < 0.5,
                   rng.integers(1, 50, B),
                   rng.integers(U * 128 + 1, 1000, B)).astype(np.int32)
    total = int(deg.sum())
    pad = (-total) % 128
    table = rng.integers(0, 1 << 30, total + pad).astype(np.int32)
    start = np.concatenate([[0], np.cumsum(deg)[:-1]]).astype(np.int32)
    pos = (rng.random((B, k)) * deg[:, None]).astype(np.int32)
    got, nfall = blocked_window_gather(
        jnp.asarray(table).reshape(-1, 128), jnp.asarray(start),
        jnp.asarray(deg), jnp.asarray(pos), U=U, fallback_frac=frac)
    want = table[start[:, None] + pos]
    np.testing.assert_array_equal(np.asarray(got), want)
    spans = ((start + np.maximum(deg - 1, 0)) >> 7) - (start >> 7)
    assert int(nfall) == int((spans >= U).sum())


def test_blocked_weighted_equals_xla(small_graph):
    """Weighted sampling: the one-pass CDF count over the gathered block
    must reproduce the binary search's draws exactly."""
    rng = np.random.default_rng(5)
    w = rng.random(small_graph.edge_count).astype(np.float32) + 0.01
    seeds = np.arange(24, dtype=np.int64)
    key = jax.random.PRNGKey(11)
    b_x = GraphSageSampler(small_graph, [6, 3], gather_mode="xla",
                           edge_weights=w).sample(seeds, key=key)
    b_b = GraphSageSampler(small_graph, [6, 3], gather_mode="blocked",
                           edge_weights=w).sample(seeds, key=key)
    np.testing.assert_array_equal(np.asarray(b_x.n_id),
                                  np.asarray(b_b.n_id))
    for lx, lb in zip(b_x.layers, b_b.layers):
        np.testing.assert_array_equal(np.asarray(lx.mask),
                                      np.asarray(lb.mask))
        np.testing.assert_array_equal(np.asarray(lx.nbr_local),
                                      np.asarray(lb.nbr_local))


def test_blocked_weighted_marginals():
    """High-degree rows (forcing both block and fallback CDF routes):
    draw frequencies track the edge weights."""
    import jax.numpy as jnp

    from quiver_tpu.ops.sample import (row_cumsum_weights,
                                       sample_neighbors_weighted)
    from quiver_tpu.ops.fastgather import pad_table_128

    rng = np.random.default_rng(0)
    N, deg = 4, 300  # deg 300 > 2*128: does NOT fit U=2 windows
    indptr = np.arange(N + 1, dtype=np.int32) * deg
    indices = np.tile(np.arange(deg, dtype=np.int32), N)
    w = np.tile((np.arange(deg) % 3 + 1).astype(np.float32), N)
    cw = pad_table_128(jnp.asarray(row_cumsum_weights(indptr, w)),
                       fill=np.float32(3 * deg))
    idx_pad = pad_table_128(jnp.asarray(indices))
    ip = pad_table_128(jnp.asarray(indptr), fill=np.int32(indptr[-1]))
    counts = np.zeros(deg)
    k = 32
    for t in range(40):
        out = sample_neighbors_weighted(
            ip, idx_pad, cw, jnp.arange(N, dtype=jnp.int32), k,
            jax.random.PRNGKey(t), sample_rng="key",
            gather_mode="blocked")
        nb = np.asarray(out.nbrs)[np.asarray(out.mask)]
        np.add.at(counts, nb, 1)
    # aggregate by weight class: class-c mass must be proportional to
    # c+1 (robust at this draw count, unlike per-neighbor frequencies)
    wclass = np.arange(deg) % 3
    mass = np.array([counts[wclass == c].sum() for c in range(3)])
    frac = mass / mass.sum()
    np.testing.assert_allclose(frac, np.array([1, 2, 3]) / 6, atol=0.02)


# ---- the chip's default: the window fetch at the shipped block width and
# fallback share (ops.blockgather.DEFAULT_U / FALLBACK_FRAC; PERF.md, PR 31)
HEAVY = {"none": 0, "some": 5, "many": 40}   # targets over the window


def _windowed_graph(heavy, n=256, seed=3):
    """``n`` nodes of degree 0..60 (a window of at most 129 entries always
    fits two rows), ``heavy`` of them of degree 300..900 (never fits), a
    few of degree 0; neighbours uniform."""
    from quiver_tpu import CSRTopo

    rng = np.random.default_rng(seed)
    deg = rng.integers(1, 60, n)
    deg[rng.choice(n, 12, replace=False)] = 0
    over = rng.choice(n, heavy, replace=False)
    deg[over] = rng.integers(300, 900, heavy)
    indptr = np.concatenate([[0], np.cumsum(deg)]).astype(np.int64)
    indices = rng.integers(0, n, int(indptr[-1])).astype(np.int32)
    return CSRTopo(indptr=indptr, indices=indices), deg


def _spans(topo, ids, live, U):
    ip = np.asarray(topo.indptr)
    start, deg = ip[ids], np.where(live, ip[ids + 1] - ip[ids], 0)
    return (((start + np.maximum(deg - 1, 0)) >> 7) - (start >> 7)) >= U


@pytest.mark.parametrize("heavy", list(HEAVY))
def test_default_window_op_equals_xla(heavy):
    """One hop at the shipped constants, every field to the bit: no target,
    some targets (the compacted fallback) and more than the fallback's
    slots (the whole hop per draw) over the window; dead targets."""
    import jax.numpy as jnp

    from quiver_tpu.ops.blockgather import (DEFAULT_U, FALLBACK_FRAC,
                                            fallback_slots)
    from quiver_tpu.ops.sample import sample_neighbors

    assert (DEFAULT_U, FALLBACK_FRAC) == (2, 1 / 32)
    topo, deg = _windowed_graph(HEAVY[heavy])
    indptr, indices = topo.to_device()
    n = len(deg)
    seeds = jnp.arange(n, dtype=jnp.int32)
    live = np.arange(n) % 7 != 3
    key = jax.random.PRNGKey(4)
    outs = [sample_neighbors(indptr, indices, seeds, 5, key,
                             seed_mask=jnp.asarray(live), gather_mode=gm,
                             sample_rng="hash")
            for gm in ("xla", "blocked")]
    for field in ("nbrs", "mask", "counts", "eid"):
        np.testing.assert_array_equal(np.asarray(getattr(outs[0], field)),
                                      np.asarray(getattr(outs[1], field)))
    assert outs[0].nfall is None
    misses = int(_spans(topo, np.arange(n), live, DEFAULT_U).sum())
    assert int(outs[1].nfall) == misses
    S = fallback_slots(n)
    assert S == 8
    assert {"none": misses == 0, "some": 0 < misses <= S,
            "many": misses > S}[heavy]


@pytest.mark.parametrize("heavy", list(HEAVY))
def test_default_window_sampler_equals_xla_and_counts(heavy):
    """Through ``GraphSageSampler`` with ``return_eid``: the batch is the
    xla one to the bit, ``window_stats`` says per hop who was served how,
    and ``overflow_stats`` reads as before."""
    from quiver_tpu import GraphSageSampler
    from quiver_tpu.ops.blockgather import DEFAULT_U, fallback_slots

    topo, deg = _windowed_graph(HEAVY[heavy])
    seeds = np.arange(64, dtype=np.int64)
    key = jax.random.PRNGKey(5)
    sizes = [5, 3, 2]
    kw = dict(sample_rng="hash", dedup="none", return_eid=True)
    b_x = GraphSageSampler(topo, sizes, gather_mode="xla",
                           **kw).sample(seeds, key=key)
    s_b = GraphSageSampler(topo, sizes, gather_mode="blocked", **kw)
    b_b = s_b.sample(seeds, key=key)
    np.testing.assert_array_equal(np.asarray(b_x.n_id), np.asarray(b_b.n_id))
    np.testing.assert_array_equal(np.asarray(b_x.n_id_mask),
                                  np.asarray(b_b.n_id_mask))
    for lx, lb in zip(b_x.layers, b_b.layers):
        for field in ("nbr_local", "mask", "eid"):
            np.testing.assert_array_equal(np.asarray(getattr(lx, field)),
                                          np.asarray(getattr(lb, field)))

    stats = s_b.window_stats(b_b)
    assert stats == s_b.window_stats()
    assert len(stats) == 3
    # hop 3 has k = 2 <= U: no window route, the per-draw path alone
    assert stats[2] == {"window": 0, "fallback": 0, "classic": True}
    n_id, n_mask = np.asarray(b_b.n_id), np.asarray(b_b.n_id_mask)
    for hop, targets in ((0, 64), (1, 64 * 6)):
        miss = int(_spans(topo, n_id[:targets], n_mask[:targets],
                          DEFAULT_U).sum())
        if miss > fallback_slots(targets):
            want = {"window": 0, "fallback": 0, "classic": True}
        else:
            want = {"window": targets - miss, "fallback": miss,
                    "classic": False}
        assert stats[hop] == want, (hop, miss)
    if heavy == "none":
        assert not any(s["fallback"] or s["classic"] for s in stats[:2])
    else:
        assert any(s["fallback"] or s["classic"] for s in stats[:2])
    # the path with no window route reports none; the frontier-cap drops
    # keep their meaning on both
    assert all(s == {"window": 0, "fallback": 0, "classic": True}
               for s in GraphSageSampler(topo, sizes, gather_mode="xla",
                                         **kw).window_stats(b_x))
    np.testing.assert_array_equal(s_b.overflow_stats(b_b), [0, 0, 0])
    np.testing.assert_array_equal(s_b.overflow_stats(), [0, 0, 0])


# ---- every consumer of the hop: on a chip each of them runs the window
# path since PR 31, on the CPU none of them but ``GraphSageSampler.sample``
# with ``dedup="none"`` ever did
SIZES = [5, 3]


def _batch(b):
    return [b.n_id, b.n_id_mask,
            [(l.nbr_local, l.mask, l.eid) for l in b.layers]]


def _sampler(topo, gm, **kw):
    kw.setdefault("dedup", "none")
    return GraphSageSampler(topo, SIZES, gather_mode=gm, sample_rng="hash",
                            **kw)


def _dedup_hop(topo, gm, seeds):
    return _batch(_sampler(topo, gm, dedup="hop").sample(
        seeds, key=jax.random.PRNGKey(5)))


def _return_eid(topo, gm, seeds):
    return _batch(_sampler(topo, gm, return_eid=True).sample(
        seeds, key=jax.random.PRNGKey(5)))


def _streaming(topo, gm, seeds):
    """Pending insertions and a tombstone on every fourth seed's first
    base edge: the overlay hop."""
    from quiver_tpu import CSRTopo
    from quiver_tpu.stream import StreamingGraph

    rng = np.random.default_rng(0)
    g = StreamingGraph(CSRTopo(indptr=topo.indptr, indices=topo.indices))
    try:
        n = g.node_count
        g.add_edges(rng.integers(0, n, 200), rng.integers(0, n, 200))
        dead = [u for u in seeds[::4] if topo.degree[u]]
        g.remove_edges(dead, [int(topo.indices[topo.indptr[u]])
                              for u in dead])
        assert g.pending_deltas and g.tombstone_count
        return _batch(_sampler(g, gm, return_eid=True).sample(
            seeds, key=jax.random.PRNGKey(5)))
    finally:
        g.close()


def _uva(topo, gm, seeds):
    s = _sampler(topo, gm, mode="UVA", uva_budget=topo.edge_count * 4 // 3)
    b = s.sample(seeds, key=jax.random.PRNGKey(5))
    assert s._uva.stats()["cold_edges"]
    return _batch(b)


def _hetero(topo, gm, seeds):
    from quiver_tpu.hetero import HeteroCSRTopo, HeteroGraphSageSampler

    ht = HeteroCSRTopo({("a", "r", "a"): topo}, {"a": topo.node_count})
    return HeteroGraphSageSampler(
        ht, SIZES, seed_type="a", gather_mode=gm,
        sample_rng="hash").sample(seeds, key=jax.random.PRNGKey(5))


def _dist(topo, gm, seeds):
    from quiver_tpu.dist.sampler import DistGraphSampler
    from quiver_tpu.utils.mesh import make_mesh

    s = DistGraphSampler(topo, make_mesh(("data",)), sizes=SIZES,
                         gather_mode=gm, sample_rng="hash")
    n_id, n_mask, num, blocks = s.sample(seeds.reshape(8, 8), key=7)
    return [n_id, n_mask, num, [(b.nbr_local, b.mask) for b in blocks]]


def _mesh(topo, gm, seeds):
    from quiver_tpu.mesh import MeshSampler

    out = MeshSampler(topo.indptr, topo.indices, n_shards=4, gather_mode=gm,
                      sample_rng="hash").sample(seeds, 5,
                                                jax.random.PRNGKey(5))
    return [out.nbrs, out.mask, out.counts, out.eid]


def _model_setup(topo, gm, seeds):
    """A sampler on ``gm``, every feature row in HBM, a small SAGE and its
    parameters (the same for both paths: made from a fixed key on shapes
    that do not depend on the path)."""
    import jax.numpy as jnp

    from quiver_tpu import Feature
    from quiver_tpu.models import GraphSAGE

    feat = np.random.default_rng(1).normal(
        size=(topo.node_count, 8)).astype(np.float32)
    feature = Feature(device_cache_size="1G").from_cpu_tensor(feat)
    sampler = _sampler(topo, gm)
    model = GraphSAGE(hidden=8, out_dim=4, num_layers=2, dropout=0.0)
    b0 = sampler.sample(seeds[:8], key=jax.random.PRNGKey(0))
    params = model.init(jax.random.PRNGKey(0), feature[b0.n_id], b0.layers)

    def apply_fn(p, x, blocks, train=False, rngs=None):
        return model.apply(p, x, blocks, train=train, rngs=rngs)

    labels = jnp.asarray(seeds % 4, jnp.int32)
    return sampler, feature, apply_fn, params, labels


def _fused_train(topo, gm, seeds):
    import jax.numpy as jnp
    import optax

    from quiver_tpu.parallel import TrainState
    from quiver_tpu.pipeline import make_fused_train_step

    sampler, feature, apply_fn, params, labels = _model_setup(topo, gm, seeds)
    tx = optax.adam(1e-2)
    state, loss = make_fused_train_step(sampler, feature, apply_fn, tx)(
        TrainState.create(params, tx), jnp.asarray(seeds, jnp.int32), labels,
        jnp.ones((64,), bool), jax.random.PRNGKey(5))
    return [loss, state.params]


def _scan_epoch(topo, gm, seeds):
    import jax.numpy as jnp
    import optax

    from quiver_tpu.parallel import TrainState
    from quiver_tpu.pipeline import make_scan_epoch

    sampler, feature, apply_fn, params, labels = _model_setup(topo, gm, seeds)
    tx = optax.adam(1e-2)
    state, losses = make_scan_epoch(sampler, feature, apply_fn, tx)(
        TrainState.create(params, tx),
        jnp.asarray(seeds, jnp.int32).reshape(2, 32), labels.reshape(2, 32),
        jax.random.PRNGKey(5))
    return [losses, state.params]


def _fused_eval(topo, gm, seeds):
    import jax.numpy as jnp

    from quiver_tpu.pipeline import make_fused_eval_fn

    sampler, feature, apply_fn, params, _ = _model_setup(topo, gm, seeds)
    return [make_fused_eval_fn(sampler, feature, apply_fn)(
        params, jnp.asarray(seeds, jnp.int32), jax.random.PRNGKey(5))]


def _serving_bucket(topo, gm, seeds):
    """``InferenceServer``'s fused bucket of 64 (it draws its key from
    numpy's global stream: seeded here)."""
    import queue

    from quiver_tpu import InferenceServer

    sampler, feature, apply_fn, params, _ = _model_setup(topo, gm, seeds)
    server = InferenceServer(sampler, feature, apply_fn, params,
                             queue.Queue())
    assert server._fused
    np.random.seed(5)
    return [server._fused_forward(seeds)]


CONSUMERS = {f.__name__.lstrip("_"): f for f in (
    _dedup_hop, _return_eid, _streaming, _uva, _hetero, _dist, _mesh,
    _fused_train, _scan_epoch, _fused_eval, _serving_bucket)}


@pytest.mark.parametrize("heavy", list(HEAVY))
@pytest.mark.parametrize("consumer", list(CONSUMERS))
def test_accelerator_path_equals_cpu_path(consumer, heavy):
    """What a TPU resolves to, ``("blocked", "hash")``, against ``("xla",
    "hash")`` through every consumer of the hop, every output to the bit,
    on the three frontiers: every window fits; a few of the seeds take
    the compacted fallback slots; more of them than the slots, so a hop
    that has a window route goes per draw as a whole."""
    from quiver_tpu.ops.blockgather import DEFAULT_U, fallback_slots

    topo, deg = _windowed_graph(HEAVY[heavy])
    # 64 seeds, the targets over the window first
    seeds = np.argsort(-deg, kind="stable")[:64].astype(np.int64)
    misses = int(_spans(topo, seeds, True, DEFAULT_U).sum())
    S = fallback_slots(len(seeds))
    assert {"none": misses == 0, "some": 0 < misses <= S,
            "many": misses > S}[heavy]
    got, want = (
        jax.tree_util.tree_leaves(CONSUMERS[consumer](topo, gm, seeds))
        for gm in ("blocked", "xla"))
    assert len(got) == len(want) and len(got) >= 1
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_window_counters_reach_the_registry():
    """The sampler-level ``window_stats()`` counts fallback targets and
    wholesale per-draw hops into the registry once per ``sample`` call,
    as ``overflow_stats()`` does the frontier-cap drops."""
    from quiver_tpu import GraphSageSampler, telemetry

    topo, _ = _windowed_graph(HEAVY["many"])
    s = GraphSageSampler(topo, [5, 3], gather_mode="blocked",
                         sample_rng="hash", dedup="none")

    def read(name):
        return telemetry.counter(name, mode="tpu").value

    names = ("sampler_window_fallback_targets_total",
             "sampler_window_classic_hops_total")
    before = [read(n) for n in names]
    assert s.window_stats() is None
    s.sample(np.arange(64, dtype=np.int64), key=jax.random.PRNGKey(5))
    stats = s.window_stats()
    after = [read(n) for n in names]
    assert after[0] - before[0] == sum(h["fallback"] for h in stats)
    assert after[1] - before[1] == sum(h["classic"] for h in stats) > 0
    s.window_stats()                       # a second read counts nothing
    assert [read(n) for n in names] == after


def test_hop_within_block_width_lowers_without_cond():
    """k <= U: a window of U rows saves nothing over k per-draw rows, so
    the hop lowers to the per-draw path alone; k > U is routed."""
    import jax.numpy as jnp

    from quiver_tpu.ops.sample import sample_neighbors

    topo, deg = _windowed_graph(0)
    indptr, indices = topo.to_device()
    seeds = jnp.arange(64, dtype=jnp.int32)

    def lowered(k):
        return sample_neighbors.lower(
            indptr, indices, seeds, k, jax.random.PRNGKey(0),
            gather_mode="blocked", sample_rng="hash").as_text()

    assert "stablehlo.case" not in lowered(2)
    assert "stablehlo.sort" not in lowered(2)
    assert "stablehlo.case" in lowered(3)
