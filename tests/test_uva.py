"""UVA-mode (single-chip big-graph tier) tests: hot/cold split correctness
(VERDICT missing #2)."""

import numpy as np
import jax
import pytest

from quiver_tpu import GraphSageSampler
from quiver_tpu.uva import UVAGraph


def _check_valid(topo, batch):
    """Every sampled edge is a true edge; counts = min(deg, k) per hop."""
    n_id = np.asarray(batch.n_id)
    for blk in batch.layers:
        local = np.asarray(blk.nbr_local)
        m = np.asarray(blk.mask)
        t = local.shape[0]
        for v in range(min(t, 64)):
            vid = n_id[v]
            row = set(topo.indices[
                topo.indptr[vid]: topo.indptr[vid + 1]].tolist())
            for j in range(local.shape[1]):
                if m[v, j]:
                    assert int(n_id[local[v, j]]) in row


def test_uva_split_budget(power_graph):
    g = UVAGraph(power_graph, budget=power_graph.edge_count * 4 // 3)
    st = g.stats()
    assert 0 < st["hot_edges"] < power_graph.edge_count
    assert st["hot_edges"] + st["cold_edges"] == power_graph.edge_count
    assert st["hbm_bytes"] <= power_graph.edge_count * 4 // 3
    # hot rows are the high-degree ones
    deg = power_graph.degree
    if st["hot_rows"] < power_graph.node_count:
        assert deg[g.is_hot].min() >= np.sort(deg[~g.is_hot])[-1] - 1


def test_uva_sampling_correct_partial_budget(power_graph):
    s = GraphSageSampler(power_graph, [5, 4], mode="UVA",
                         uva_budget=power_graph.edge_count * 4 // 3)
    assert s.mode == "UVA" and s._uva is None  # lazy
    b = s.sample(np.arange(32, dtype=np.int64), key=jax.random.PRNGKey(0))
    assert s._uva.stats()["cold_edges"] > 0
    _check_valid(power_graph, b)
    # counts contract on both tiers
    blk = b.layers[-1]  # innermost hop: targets are the seeds
    m = np.asarray(blk.mask)
    deg = power_graph.degree
    for v in range(32):
        assert m[v].sum() == min(deg[v], 5)


def test_uva_budget_zero_all_cold(small_graph):
    s = GraphSageSampler(small_graph, [4], mode="UVA", uva_budget=0)
    b = s.sample(np.arange(16, dtype=np.int64), key=jax.random.PRNGKey(1))
    assert s._uva.stats()["hot_edges"] == 0
    _check_valid(small_graph, b)


def test_uva_no_budget_is_tpu_mode(small_graph):
    s = GraphSageSampler(small_graph, [4], mode="UVA")
    assert s.mode == "TPU"  # degenerate: everything fits


def test_uva_rejects_dedup_and_weights(small_graph):
    with pytest.raises(AssertionError):
        GraphSageSampler(small_graph, [4], mode="UVA", uva_budget=10,
                         dedup="hop")


def test_uva_pinned_key_replays_both_tiers(power_graph):
    s = GraphSageSampler(power_graph, [5, 4], mode="UVA",
                         uva_budget=power_graph.edge_count * 4 // 3)
    k = jax.random.PRNGKey(9)
    b1 = s.sample(np.arange(24, dtype=np.int64), key=k)
    b2 = s.sample(np.arange(24, dtype=np.int64), key=k)
    np.testing.assert_array_equal(np.asarray(b1.n_id), np.asarray(b2.n_id))
    for l1, l2 in zip(b1.layers, b2.layers):
        np.testing.assert_array_equal(np.asarray(l1.mask),
                                      np.asarray(l2.mask))


def test_uva_element_gather_covers_tail_nodes():
    """Regression: the element gather truncates tables to a 128 multiple
    and clips indices — an unpadded [n+1] indptr returned a WRONG row's
    pointers for the last (n+1) % 128 node ids.  Sample exactly those
    tail nodes with gather_mode='blocked' on an all-hot UVA graph and
    verify every edge against the CSR."""
    rng = np.random.default_rng(7)
    n = 300  # n+1 = 301: 45 tail ids past the 256 truncation boundary
    deg = rng.integers(1, 6, n)
    indptr = np.zeros(n + 1, np.int64)
    np.cumsum(deg, out=indptr[1:])
    indices = rng.integers(0, n, indptr[-1])
    from quiver_tpu import CSRTopo

    topo = CSRTopo(indptr=indptr, indices=indices)
    s = GraphSageSampler(topo, [4], mode="UVA",
                         uva_budget=topo.edge_count * 4,  # all hot
                         gather_mode="blocked")
    tail = np.arange(256, n, dtype=np.int64)  # ids the clip used to eat
    b = s.sample(tail, key=jax.random.PRNGKey(2))
    assert s._uva.stats()["cold_edges"] == 0
    _check_valid(topo, b)
    # also: counts must equal min(deg, k) — wrong pointers under-sample
    counts = np.asarray(b.layers[-1].mask).sum(axis=1)
    np.testing.assert_array_equal(counts, np.minimum(deg[tail], 4))


def test_uva_overlap_ab_bit_identical(small_graph):
    """overlap=False (serialized A/B baseline) must produce bit-identical
    samples to the overlapped path under the same key, and the timings
    dict must accumulate the cold tier's host wall."""
    from quiver_tpu.utils.rng import make_key

    budget = small_graph.edge_count * 4 // 3  # 1/3 hot
    t = {}
    s1 = GraphSageSampler(small_graph, [4, 3], mode="UVA",
                          uva_budget=budget, uva_timings=t)
    s2 = GraphSageSampler(small_graph, [4, 3], mode="UVA",
                          uva_budget=budget, uva_overlap=False)
    seeds = np.arange(32, dtype=np.int32)
    b1 = s1.sample(seeds, key=make_key(5))
    b2 = s2.sample(seeds, key=make_key(5))
    np.testing.assert_array_equal(np.asarray(b1.n_id), np.asarray(b2.n_id))
    np.testing.assert_array_equal(np.asarray(b1.n_id_mask),
                                  np.asarray(b2.n_id_mask))
    assert t.get("host_s", 0) > 0  # cold tier ran and was timed
