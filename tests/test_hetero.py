"""Hetero sampler + R-GAT tests (mag240m-style 3-type schema)."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from quiver_tpu.hetero import HeteroCSRTopo, HeteroGraphSageSampler
from quiver_tpu.models.rgat import RGAT


N_PAPER, N_AUTHOR, N_INST = 300, 200, 40


@pytest.fixture(scope="module")
def mag_topo():
    rng = np.random.default_rng(0)

    def edges(n_src, n_dst, avg):
        deg = rng.poisson(avg, n_dst)
        dst = np.repeat(np.arange(n_dst), deg)
        src = rng.integers(0, n_src, len(dst))
        return np.stack([src, dst])

    ei = {
        ("paper", "cites", "paper"): edges(N_PAPER, N_PAPER, 6),
        ("author", "writes", "paper"): edges(N_AUTHOR, N_PAPER, 3),
        ("institution", "employs", "author"): edges(N_INST, N_AUTHOR, 2),
    }
    return HeteroCSRTopo.from_edge_index_dict(
        ei, {"paper": N_PAPER, "author": N_AUTHOR, "institution": N_INST}
    ), ei


def test_hetero_sample_shapes(mag_topo):
    topo, _ = mag_topo
    s = HeteroGraphSageSampler(topo, sizes=4, num_hops=2, seed_type="paper")
    seeds = np.arange(16)
    b = s.sample(seeds, key=jax.random.PRNGKey(0))
    assert b.batch_size == 16
    assert len(b.layers) == 2
    # paper frontier grows from seeds; author/institution appear
    assert b.n_id["paper"].shape[0] > 16
    assert b.n_id["author"].shape[0] > 0
    # hop1 (outermost processed last... layers are outermost-first):
    # the innermost hop must have paper targets == seeds
    inner = b.layers[-1]
    paper_blocks = [blk for blk in inner
                    if blk.relation[2] == "paper"]
    assert paper_blocks and all(
        int(blk.num_targets) == 16 for blk in paper_blocks
    )



def _assert_block_edges_real(topo, b, blk, max_targets=24):
    """Shared ground-truth check: every masked (src, dst) in a hetero
    block is a real edge of its relation; invalid targets sample nothing."""
    s_t, _, d_t = blk.relation
    rel_topo = topo.relations[blk.relation]
    n_src = np.asarray(b.n_id[s_t])
    n_dst = np.asarray(b.n_id[d_t])
    m = np.asarray(blk.mask)
    local = np.asarray(blk.nbr_local)
    dmask = np.asarray(b.n_id_mask[d_t])
    for t in range(min(local.shape[0], max_targets)):
        if not dmask[t]:
            assert not m[t].any()
            continue
        tgt = n_dst[t]
        row = set(rel_topo.indices[
            rel_topo.indptr[tgt]: rel_topo.indptr[tgt + 1]
        ].tolist())
        for j in range(local.shape[1]):
            if m[t, j]:
                assert n_src[local[t, j]] in row


def test_hetero_edges_are_real(mag_topo):
    topo, ei = mag_topo
    s = HeteroGraphSageSampler(topo, sizes=3, num_hops=2, seed_type="paper")
    seeds = np.arange(12)
    b = s.sample(seeds, key=jax.random.PRNGKey(1))
    for hop_blocks in b.layers:
        for blk in hop_blocks:
            _assert_block_edges_real(topo, b, blk)


def test_rgat_forward(mag_topo, rng):
    topo, _ = mag_topo
    s = HeteroGraphSageSampler(topo, sizes=3, num_hops=2, seed_type="paper")
    seeds = np.arange(8)
    b = s.sample(seeds, key=jax.random.PRNGKey(2))
    dims = {"paper": 16, "author": 8, "institution": 4}
    xs = {
        t: jnp.asarray(
            rng.normal(size=(b.n_id[t].shape[0], dims[t])), jnp.float32
        )
        for t in dims
    }
    model = RGAT(hidden=16, out_dim=5, num_layers=2, in_dims=dims,
                 heads=2, dropout=0.0)
    params = model.init(jax.random.PRNGKey(0), xs, b)
    out = model.apply(params, xs, b)
    assert out.shape == (8, 5)
    assert np.isfinite(np.asarray(out)).all()


def test_rgat_trains(mag_topo, rng):
    """One gradient step decreases loss on a fixed batch."""
    import optax

    topo, _ = mag_topo
    s = HeteroGraphSageSampler(topo, sizes=3, num_hops=2, seed_type="paper")
    seeds = np.arange(16)
    b = s.sample(seeds, key=jax.random.PRNGKey(3))
    dims = {"paper": 16, "author": 8, "institution": 4}
    xs = {
        t: jnp.asarray(
            rng.normal(size=(b.n_id[t].shape[0], dims[t])), jnp.float32
        )
        for t in dims
    }
    labels = jnp.asarray(rng.integers(0, 5, 16))
    model = RGAT(hidden=16, out_dim=5, num_layers=2, in_dims=dims,
                 heads=2, dropout=0.0)
    params = model.init(jax.random.PRNGKey(0), xs, b)
    tx = optax.adam(1e-2)
    opt = tx.init(params)

    def loss_fn(p):
        logits = model.apply(p, xs, b)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, labels
        ).mean()

    l0 = loss_fn(params)
    for _ in range(5):
        g = jax.grad(loss_fn)(params)
        upd, opt = tx.update(g, opt, params)
        params = optax.apply_updates(params, upd)
    assert float(loss_fn(params)) < float(l0)


def test_hetero_feature_lookup(mag_topo, rng):
    from quiver_tpu import HeteroFeature

    topo, _ = mag_topo
    dims = {"paper": 8, "author": 4, "institution": 2}
    tensors = {t: rng.normal(size=(n, dims[t])).astype(np.float32)
               for t, n in topo.node_counts.items()}
    hf = HeteroFeature.from_cpu_tensors(tensors)
    s = HeteroGraphSageSampler(topo, sizes=3, num_hops=1, seed_type="paper")
    b = s.sample(np.arange(8), key=jax.random.PRNGKey(0))
    xs = hf.lookup(b)
    for t in dims:
        assert xs[t].shape == (b.n_id[t].shape[0], dims[t]) or (
            xs[t].shape[0] == 0
        )
    # values match ground truth for the paper frontier
    pid = np.asarray(b.n_id["paper"])
    np.testing.assert_allclose(np.asarray(xs["paper"]),
                               tensors["paper"][pid], rtol=1e-6)


def test_rel_attention_matches_manual(mag_topo, rng):
    """_RelAttention (1 head) equals hand-computed masked softmax."""
    from quiver_tpu.models.rgat import _RelAttention

    topo, _ = mag_topo
    s = HeteroGraphSageSampler(topo, sizes=3, num_hops=1, seed_type="paper")
    b = s.sample(np.arange(5), key=jax.random.PRNGKey(4))
    blk = [x for x in b.layers[0]
           if x.relation == ("author", "writes", "paper")][0]
    x_src = jnp.asarray(
        rng.normal(size=(b.n_id["author"].shape[0], 4)), jnp.float32)
    x_dst = jnp.asarray(
        rng.normal(size=(b.n_id["paper"].shape[0], 4)), jnp.float32)
    att = _RelAttention(3, heads=1)
    params = att.init(jax.random.PRNGKey(0), x_src, x_dst, blk)
    out = np.asarray(att.apply(params, x_src, x_dst, blk))

    p = params["params"]
    ws, wd = np.asarray(p["w_src"]["kernel"]), np.asarray(p["w_dst"]["kernel"])
    a_s, a_d = np.asarray(p["att_src"])[0], np.asarray(p["att_dst"])[0]
    xs, xd = np.asarray(x_src), np.asarray(x_dst)
    local, m = np.asarray(blk.nbr_local), np.asarray(blk.mask)

    def leaky(v):
        return np.where(v > 0, v, 0.2 * v)

    for i in range(min(5, local.shape[0])):
        if not m[i].any():
            np.testing.assert_allclose(out[i], 0.0, atol=1e-6)
            continue
        wn = xs[local[i][m[i]]] @ ws
        wdi = xd[i] @ wd
        e = leaky(wn @ a_s + wdi @ a_d)
        al = np.exp(e - e.max()); al /= al.sum()
        ref = (al[:, None] * wn).sum(axis=0)
        np.testing.assert_allclose(out[i], ref, rtol=1e-4, atol=1e-5)


def test_hetero_hash_rng_executes(mag_topo):
    """The accelerator-default sample_rng='hash' must EXECUTE through the
    hetero per-relation hops (every sampler variant ships hash on TPU)."""
    topo, _ = mag_topo
    s = HeteroGraphSageSampler(topo, sizes=3, num_hops=2,
                               seed_type="paper", sample_rng="hash")
    assert s.sample_rng == "hash"
    b1 = s.sample(np.arange(12), key=jax.random.PRNGKey(1))
    b2 = s.sample(np.arange(12), key=jax.random.PRNGKey(1))
    b3 = s.sample(np.arange(12), key=jax.random.PRNGKey(2))
    for t in b1.n_id:
        np.testing.assert_array_equal(np.asarray(b1.n_id[t]),
                                      np.asarray(b2.n_id[t]))
    assert any(
        not np.array_equal(np.asarray(b1.n_id[t]), np.asarray(b3.n_id[t]))
        for t in b1.n_id)
    # sampled edges are real under hash too
    for hop_blocks in b1.layers:
        for blk in hop_blocks:
            _assert_block_edges_real(topo, b1, blk, max_targets=12)
