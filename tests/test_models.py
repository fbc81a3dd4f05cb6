"""Model forward-pass tests over sampled dense blocks."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from quiver_tpu import GraphSageSampler
from quiver_tpu.models import GraphSAGE, GAT, SAGEConv


@pytest.fixture
def sampled(small_graph):
    s = GraphSageSampler(small_graph, [4, 3])
    seeds = np.arange(16, dtype=np.int64)
    return s.sample(seeds, key=jax.random.PRNGKey(0))


def test_sage_forward(sampled, rng):
    x = jnp.asarray(rng.normal(size=(sampled.n_id.shape[0], 12)),
                    jnp.float32)
    model = GraphSAGE(hidden=32, out_dim=5, num_layers=2)
    params = model.init(jax.random.PRNGKey(0), x, sampled.layers)
    out = model.apply(params, x, sampled.layers)
    assert out.shape == (16, 5)
    assert np.isfinite(np.asarray(out)).all()


def test_gat_forward(sampled, rng):
    x = jnp.asarray(rng.normal(size=(sampled.n_id.shape[0], 12)),
                    jnp.float32)
    model = GAT(hidden=8, out_dim=5, num_layers=2, heads=2)
    params = model.init(jax.random.PRNGKey(0), x, sampled.layers)
    out = model.apply(params, x, sampled.layers)
    assert out.shape == (16, 5)
    assert np.isfinite(np.asarray(out)).all()


def test_sageconv_mean_matches_manual(small_graph, rng):
    """SAGEConv aggregation equals a hand-computed masked mean."""
    s = GraphSageSampler(small_graph, [4])
    seeds = np.arange(8, dtype=np.int64)
    b = s.sample(seeds, key=jax.random.PRNGKey(1))
    blk = b.layers[0]
    x = jnp.asarray(rng.normal(size=(b.n_id.shape[0], 6)), jnp.float32)
    conv = SAGEConv(7)
    params = conv.init(jax.random.PRNGKey(0), x, blk)
    out = np.asarray(conv.apply(params, x, blk))

    w_self = np.asarray(params["params"]["lin_self"]["kernel"])
    b_self = np.asarray(params["params"]["lin_self"]["bias"])
    w_nbr = np.asarray(params["params"]["lin_nbr"]["kernel"])
    xs = np.asarray(x)
    local = np.asarray(blk.nbr_local)
    m = np.asarray(blk.mask)
    for i in range(8):
        nb = xs[local[i][m[i]]]
        mean = nb.mean(axis=0) if len(nb) else np.zeros(6)
        ref = xs[i] @ w_self + b_self + mean @ w_nbr
        np.testing.assert_allclose(out[i], ref, rtol=1e-4, atol=1e-5)


def test_masked_padding_does_not_leak(small_graph, rng):
    """Changing features of masked (padding) frontier rows must not change
    the model output for valid targets."""
    s = GraphSageSampler(small_graph, [4, 3])
    seeds = np.arange(8, dtype=np.int64)
    b = s.sample(seeds, key=jax.random.PRNGKey(2))
    P = b.n_id.shape[0]
    x1 = rng.normal(size=(P, 6)).astype(np.float32)
    x2 = x1.copy()
    pad = ~np.asarray(b.n_id_mask)
    x2[pad] = 1e6  # poison padding rows
    model = GraphSAGE(hidden=16, out_dim=3, num_layers=2)
    params = model.init(jax.random.PRNGKey(0), jnp.asarray(x1), b.layers)
    o1 = np.asarray(model.apply(params, jnp.asarray(x1), b.layers))
    o2 = np.asarray(model.apply(params, jnp.asarray(x2), b.layers))
    np.testing.assert_allclose(o1[:8], o2[:8], rtol=1e-5)


def test_gcn_forward_and_trains(small_graph, rng):
    import optax

    from quiver_tpu.models import GCN

    s = GraphSageSampler(small_graph, [4, 3])
    seeds = np.arange(16, dtype=np.int64)
    b = s.sample(seeds, key=jax.random.PRNGKey(4))
    x = jnp.asarray(rng.normal(size=(b.n_id.shape[0], 12)), jnp.float32)
    model = GCN(hidden=16, out_dim=5, num_layers=2, dropout=0.0)
    params = model.init(jax.random.PRNGKey(0), x, b.layers)
    out = model.apply(params, x, b.layers)
    assert out.shape == (16, 5)
    labels = jnp.asarray(rng.integers(0, 5, 16))
    tx = optax.adam(1e-2)
    opt = tx.init(params)

    def loss_fn(p):
        return optax.softmax_cross_entropy_with_integer_labels(
            model.apply(p, x, b.layers), labels
        ).mean()

    l0 = float(loss_fn(params))
    for _ in range(5):
        g = jax.grad(loss_fn)(params)
        upd, opt = tx.update(g, opt, params)
        params = optax.apply_updates(params, upd)
    assert float(loss_fn(params)) < l0


def test_full_graph_inference_matches_manual(small_graph, rng):
    """Exact inference equals brute-force numpy layer computation."""
    from quiver_tpu.models.sage import full_graph_inference
    from quiver_tpu.models import GraphSAGE
    from quiver_tpu import GraphSageSampler

    n = small_graph.node_count
    x0 = rng.normal(size=(n, 6)).astype(np.float32)
    model = GraphSAGE(hidden=8, out_dim=3, num_layers=2, dropout=0.0)
    s = GraphSageSampler(small_graph, [3, 3])
    b = s.sample(np.arange(4, dtype=np.int64))
    params = model.init(jax.random.PRNGKey(0),
                        jnp.asarray(x0)[b.n_id], b.layers)

    indptr, indices = small_graph.indptr, small_graph.indices
    out = np.asarray(full_graph_inference(
        params, jnp.asarray(x0), indptr, indices, 2, edge_chunk=500
    ))

    # numpy brute force
    p = params["params"]
    h = x0
    for i in range(2):
        ws, bs = np.asarray(p[f"conv{i}"]["lin_self"]["kernel"]), \
            np.asarray(p[f"conv{i}"]["lin_self"]["bias"])
        wn = np.asarray(p[f"conv{i}"]["lin_nbr"]["kernel"])
        mean = np.zeros_like(h)
        for v in range(n):
            row = indices[indptr[v]: indptr[v + 1]]
            if len(row):
                mean[v] = h[row].mean(axis=0)
        h = h @ ws + bs + mean @ wn
        if i != 1:
            h = np.maximum(h, 0)
    np.testing.assert_allclose(out, h, rtol=2e-4, atol=2e-5)


def test_gatconv_matches_manual(small_graph, rng):
    """GATConv (1 head) equals a hand-computed masked-softmax attention
    with the self-loop term a_src·Wx_i + a_tgt·Wx_i."""
    from quiver_tpu.models import GATConv

    s = GraphSageSampler(small_graph, [3])
    seeds = np.arange(6, dtype=np.int64)
    b = s.sample(seeds, key=jax.random.PRNGKey(8))
    blk = b.layers[0]
    x = jnp.asarray(rng.normal(size=(b.n_id.shape[0], 5)), jnp.float32)
    conv = GATConv(4, heads=1, concat=True)
    params = conv.init(jax.random.PRNGKey(0), x, blk)
    out = np.asarray(conv.apply(params, x, blk))

    w = np.asarray(params["params"]["lin"]["kernel"])      # [5, 4]
    a_s = np.asarray(params["params"]["att_src"])[0]       # [4]
    a_t = np.asarray(params["params"]["att_tgt"])[0]       # [4]
    xs = np.asarray(x)
    local = np.asarray(blk.nbr_local)
    m = np.asarray(blk.mask)

    def leaky(v):
        return np.where(v > 0, v, 0.2 * v)

    for i in range(6):
        wi = xs[i] @ w
        nbr_ids = local[i][m[i]]
        wn = xs[nbr_ids] @ w if len(nbr_ids) else np.zeros((0, 4))
        e = [leaky(wn[j] @ a_s + wi @ a_t) for j in range(len(nbr_ids))]
        e.append(leaky(wi @ a_s + wi @ a_t))  # self loop
        e = np.array(e)
        al = np.exp(e - e.max())
        al = al / al.sum()
        vals = np.concatenate([wn, wi[None]], axis=0)
        ref = (al[:, None] * vals).sum(axis=0)
        np.testing.assert_allclose(out[i], ref, rtol=1e-4, atol=1e-5)


def test_gcnconv_matches_manual(small_graph, rng):
    """GCNConv equals the hand-computed sampled-degree normalization."""
    from quiver_tpu.models import GCNConv

    s = GraphSageSampler(small_graph, [3])
    seeds = np.arange(6, dtype=np.int64)
    b = s.sample(seeds, key=jax.random.PRNGKey(9))
    blk = b.layers[0]
    x = jnp.asarray(rng.normal(size=(b.n_id.shape[0], 5)), jnp.float32)
    conv = GCNConv(4)
    params = conv.init(jax.random.PRNGKey(0), x, blk)
    out = np.asarray(conv.apply(params, x, blk))

    w = np.asarray(params["params"]["lin"]["kernel"])
    bias = np.asarray(params["params"]["lin"]["bias"])
    xs = np.asarray(x)
    local, m = np.asarray(blk.nbr_local), np.asarray(blk.mask)
    for i in range(6):
        wi = xs[i] @ w + bias
        wn = xs[local[i][m[i]]] @ w + bias
        norm = 1.0 / np.sqrt(m[i].sum() + 1.0)
        ref = (wn.sum(axis=0) * norm + wi) * norm
        np.testing.assert_allclose(out[i], ref, rtol=1e-4, atol=1e-5)


def test_full_graph_inference_gcn_matches_numpy(small_graph, rng):
    """Exact GCN inference == brute-force symmetric-norm computation."""
    from quiver_tpu.models.inference import full_graph_inference
    from quiver_tpu.models import GCN
    from quiver_tpu import GraphSageSampler

    n = small_graph.node_count
    x0 = rng.normal(size=(n, 5)).astype(np.float32)
    model = GCN(hidden=7, out_dim=3, num_layers=2, dropout=0.0)
    s = GraphSageSampler(small_graph, [3, 3])
    b = s.sample(np.arange(4, dtype=np.int64))
    params = model.init(jax.random.PRNGKey(0),
                        jnp.asarray(x0)[b.n_id], b.layers)
    indptr, indices = small_graph.indptr, small_graph.indices
    out = np.asarray(full_graph_inference(
        model, params, jnp.asarray(x0), indptr, indices, edge_chunk=333
    ))

    p = params["params"]
    deg = (indptr[1:] - indptr[:-1]).astype(np.float64)
    norm = 1.0 / np.sqrt(deg + 1.0)
    h = x0.astype(np.float64)
    for i in range(2):
        k = np.asarray(p[f"gcn{i}"]["lin"]["kernel"], np.float64)
        bias = np.asarray(p[f"gcn{i}"]["lin"]["bias"], np.float64)
        w = h @ k + bias
        acc = np.zeros_like(w)
        for v in range(n):
            for u in indices[indptr[v]:indptr[v + 1]]:
                acc[v] += w[u] * norm[u]
        h = (acc + w * norm[:, None]) * norm[:, None]
        if i != 1:
            h = np.maximum(h, 0)
    np.testing.assert_allclose(out, h, rtol=2e-4, atol=2e-5)


def test_full_graph_inference_gat_matches_full_fanout_blocks(small_graph,
                                                             rng):
    """With fanout >= max degree the sampled GAT forward sees every
    neighbor, so it must equal the exact layer-wise path."""
    from quiver_tpu.models.inference import full_graph_inference
    from quiver_tpu.models import GAT
    from quiver_tpu import GraphSageSampler

    n = small_graph.node_count
    kmax = int(small_graph.degree.max())
    x0 = rng.normal(size=(n, 4)).astype(np.float32)
    model = GAT(hidden=6, out_dim=3, num_layers=1, heads=1, dropout=0.0)
    s = GraphSageSampler(small_graph, [kmax], dedup="hop")
    seeds = np.arange(n, dtype=np.int64)
    b = s.sample(seeds)
    params = model.init(jax.random.PRNGKey(1),
                        jnp.asarray(x0)[b.n_id], b.layers)

    x_in = jnp.asarray(x0)[b.n_id]
    sampled = np.asarray(model.apply(params, x_in, b.layers))[:n]
    exact = np.asarray(full_graph_inference(
        model, params, jnp.asarray(x0), small_graph.indptr,
        small_graph.indices, edge_chunk=200
    ))
    np.testing.assert_allclose(sampled, exact, rtol=2e-4, atol=2e-5)


def test_bfloat16_models_train(small_graph, rng):
    """dtype=bfloat16 models: finite outputs, loss decreases, params
    stay float32 (mixed precision, the MXU recipe)."""
    import optax
    from quiver_tpu.models import GraphSAGE
    from quiver_tpu import GraphSageSampler

    n = small_graph.node_count
    x0 = jnp.asarray(rng.normal(size=(n, 8)).astype(np.float32))
    model = GraphSAGE(hidden=16, out_dim=4, num_layers=2, dropout=0.0,
                      dtype=jnp.bfloat16)
    s = GraphSageSampler(small_graph, [4, 3])
    b = s.sample(np.arange(16, dtype=np.int64))
    params = model.init(jax.random.PRNGKey(0), x0[b.n_id], b.layers)
    leaves = jax.tree_util.tree_leaves(params)
    assert all(l.dtype == jnp.float32 for l in leaves)
    out = model.apply(params, x0[b.n_id], b.layers)
    assert out.dtype == jnp.bfloat16
    assert bool(jnp.isfinite(out.astype(jnp.float32)).all())

    tx = optax.adam(1e-2)
    opt = tx.init(params)
    labels = jnp.asarray(rng.integers(0, 4, 16))

    def loss_fn(p):
        logits = model.apply(p, x0[b.n_id], b.layers).astype(jnp.float32)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits[:16], labels).mean()

    l0 = float(loss_fn(params))
    for _ in range(8):
        g = jax.grad(loss_fn)(params)
        upd, opt = tx.update(g, opt, params)
        params = optax.apply_updates(params, upd)
    assert float(loss_fn(params)) < l0


# ---- positional blocks: sources read by slice, not by gather -------------
def _ragged_positional_batch():
    """Blocks from the positional sampler over a graph whose degrees run
    0..6 against fanouts [5, 4]: zero-degree targets, under-fanout rows and
    full rows all occur."""
    from quiver_tpu import CSRTopo

    n = 70
    deg = np.arange(n) % 7
    src = np.repeat(np.arange(n), deg)
    dst = (src * 3 + np.concatenate([np.arange(d) for d in deg]) * 11) % n
    topo = CSRTopo(edge_index=np.stack([src, dst]))
    s = GraphSageSampler(topo, [5, 4], dedup="none", return_eid=True)
    return s.sample(np.arange(28, dtype=np.int64), key=jax.random.PRNGKey(5))


def _conv_case(name):
    from quiver_tpu.models.gcn import GCNConv
    from quiver_tpu.models import GATConv

    return {
        "sage": (SAGEConv(7), False),
        "sage-edge_feat": (SAGEConv(7), True),
        "gcn": (GCNConv(7), False),
        "gat": (GATConv(4, heads=2), False),
    }[name]


@pytest.mark.parametrize("name", ["sage", "sage-edge_feat", "gcn", "gat"])
def test_positional_sources_match_gather(name, rng):
    """A conv over a POSITIONAL block (slice) gives what it gives with the
    marker stripped (gather through nbr_local): output exactly, gradients
    w.r.t. parameters and x to float32 rounding."""
    from quiver_tpu.sampler import POSITIONAL

    conv, with_edges = _conv_case(name)
    batch = _ragged_positional_batch()
    blk = batch.layers[0]
    assert blk.layout is POSITIONAL
    m = np.asarray(blk.mask)
    cnt = m.sum(axis=1)
    assert (cnt == 0).any() and ((cnt > 0) & (cnt < m.shape[1])).any() \
        and (cnt == m.shape[1]).any()
    x = jnp.asarray(rng.normal(size=(batch.n_id.shape[0], 6)), jnp.float32)
    extra = ()
    if with_edges:
        extra = (jnp.asarray(rng.normal(size=m.shape + (3,)), jnp.float32),)
    params = conv.init(jax.random.PRNGKey(0), x, blk, *extra)
    w = jnp.asarray(rng.normal(size=conv.apply(params, x, blk,
                                               *extra).shape), jnp.float32)

    def run(block):
        def f(p, xx):
            out = conv.apply(p, xx, block, *extra)
            return (out * w).sum(), out
        (_, out), grads = jax.jit(
            jax.value_and_grad(f, argnums=(0, 1), has_aux=True))(params, x)
        return out, grads

    out_s, grads_s = run(blk)
    out_g, grads_g = run(blk._replace(layout=None))
    np.testing.assert_array_equal(np.asarray(out_s), np.asarray(out_g))
    for a, b in zip(jax.tree.leaves(grads_s), jax.tree.leaves(grads_g)):
        scale = float(np.abs(np.asarray(b)).max())
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-6 * scale)


def test_positional_block_rejects_wrong_length_at_trace_time(rng):
    batch = _ragged_positional_batch()
    blk = batch.layers[0]
    t, k = blk.mask.shape
    conv = SAGEConv(7)
    x = jnp.zeros((batch.n_id.shape[0], 6), jnp.float32)
    params = conv.init(jax.random.PRNGKey(0), x, blk)
    short = jax.ShapeDtypeStruct((x.shape[0] - 1, 6), jnp.float32)
    with pytest.raises(ValueError) as e:
        jax.eval_shape(lambda xx: conv.apply(params, xx, blk), short)
    assert str(t * (1 + k)) in str(e.value)
    assert str(x.shape[0] - 1) in str(e.value)
    # the same x is fine for the gather form: nothing is promised there
    jax.eval_shape(lambda xx: conv.apply(params, xx,
                                         blk._replace(layout=None)), short)
