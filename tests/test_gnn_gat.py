"""The published GAT (``models.GNN`` over ``layers.GATConv``) against its
plain reference (``cellbench/references/gat.py``, which imports nothing of
quiver_tpu), on seeded weights, on the CPU, at a small size, float32
products (``highest``): through ``GraphSageSampler`` -> ``Feature`` ->
``make_fused_train_step`` for three steps, and piece by piece.

Tolerances, as ``tests/test_rgnn.py`` states them for the typed model.
Program and reference compute the same float32 sums in another order (a
row projected in its slot against a node projected once and gathered, one
softmax over 8 slots against neighbours and self-loop side by side, a 0/1
product against a lane sum), so a value differs by a few float32
roundings: 1e-5 relative to the leaf's largest entry.  After three Adam
steps an ELEMENT may differ by far more, because Adam divides by
``sqrt(v)``: one whose gradient is a few roundings from 0 steps +-lr on
either side, so elements are held to 5% of ``lr`` x steps and each leaf's
walk, as a norm, to 1e-4 (the benchmark's ``delta_gap``).  A bias added
BEFORE a BatchNorm in training mode (``conv<i>/bias``, ``skip<i>/bias``,
``mlp_lin0/bias``) has a gradient of exactly 0 in real arithmetic; what
both sides compute is rounding noise, which Adam turns into steps of
+-lr, so those leaves are held to |gradient| < 1e-6 and |step| <= lr x
steps and to nothing else.  The model's own lower-precision path
(``dtype=bfloat16``) rounds every projection to 8 bits: it misses the 1e-5
of the logits by two orders of magnitude, and has to.
"""

import functools
import importlib
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (ROOT, os.path.join(ROOT, "cellbench")):
    if p not in sys.path:
        sys.path.insert(0, p)

from quiver_tpu import CSRTopo, Feature, GraphSageSampler, make_key  # noqa: E402
from quiver_tpu.models import GNN, RGNN, GATConv, rgnn_apply_fn  # noqa: E402
from quiver_tpu.parallel import TrainState  # noqa: E402
from quiver_tpu.pipeline import (make_fused_eval_fn,  # noqa: E402
                                 make_fused_train_step)
from quiver_tpu.sampler import POSITIONAL, LayerBlock  # noqa: E402

ref = importlib.import_module("references.gat")

CFG = dict(papers=4000, edges_cites=40000, feature_dim=16, classes=7,
           hidden=32, heads=4, num_layers=2, fanout=[5, 3], batch=64,
           dropout=0.5, lr=1e-3)
SEED = 2**31 + 5
PRE_NORM_BIASES = ("conv0/bias", "conv1/bias", "skip0/bias", "skip1/bias",
                   "mlp_lin0/bias")
tm = jax.tree_util.tree_map


@pytest.fixture(autouse=True)
def float32_products():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture(scope="module")
def data():
    return ref.make_data(CFG, SEED)


def model_of(cfg=CFG, **kw):
    return GNN(hidden=cfg["hidden"], out_dim=cfg["classes"],
               heads=cfg["heads"], dropout=cfg["dropout"], **kw)


def leaves(tree):
    return {"/".join(k.key for k in path[1:]): np.asarray(a) for path, a
            in jax.tree_util.tree_leaves_with_path(tree)}


def close(a, b, rel=1e-5):
    return np.abs(a - b).max() <= rel * max(np.abs(b).max(), 1e-6)


def as_blocks(layers, layout=None):
    return tuple(LayerBlock(jnp.asarray(n), jnp.asarray(m),
                            jnp.asarray(m.shape[0], jnp.int32),
                            layout=layout) for n, m in layers)


# ------------------------------------------------------------ the generator
def test_citation_graph_is_symmetric_in_count_and_has_no_self_edge(data):
    indptr, indices = data["indptr"], data["indices"]
    assert ref.type_offsets(CFG) == (0, 4000)
    assert len(indptr) == 4001 and len(indices) == 2 * CFG["edges_cites"]
    row = np.repeat(np.arange(4000), np.diff(indptr))
    assert not (indices == row).any()
    assert indices.min() >= 0 and indices.max() < 4000
    assert (np.diff(indptr) == 0).any()     # a paper nobody is drawn for
    other = ref.make_data(CFG, SEED + 1)
    assert other["indices"].shape == indices.shape      # same shapes
    assert not np.array_equal(other["indices"], indices)
    assert data["features"].dtype == np.float16


# ------------------------------------------ three steps through the pipeline
@pytest.fixture(scope="module")
def followed(data):
    """Three fused steps of the program and the reference's three."""
    with jax.default_matmul_precision("highest"):
        topo = CSRTopo(indptr=data["indptr"], indices=data["indices"])
        sampler = GraphSageSampler(topo, CFG["fanout"])
        feature = Feature(device_cache_size=CFG["papers"],
                          cache_unit="rows", dtype=jnp.float16
                          ).from_cpu_tensor(data["features"])
        model = model_of()
        apply_fn = rgnn_apply_fn(model)
        tx = optax.adam(CFG["lr"])
        state = TrainState.create(tm(jnp.asarray, data["params"]), tx,
                                  tm(jnp.asarray, data["model_state"]))
        step = make_fused_train_step(sampler, feature, apply_fn, tx)
        seeds = (np.arange(64, dtype=np.int32) * 7) % CFG["papers"]
        # two papers without any neighbour: only their self-loops
        seeds[:2] = np.flatnonzero(np.diff(data["indptr"]) == 0)[:2]
        labels = data["labels"][seeds]
        batches, losses, grad = [], [], None
        for i in range(3):
            key = make_key(i)
            ks, kd = jax.random.split(key)
            bt = sampler.sample(jnp.asarray(seeds), key=ks)
            n_id = np.asarray(bt.n_id)
            batches.append(dict(
                rows=data["features"][n_id].astype(np.float32),
                layers=[(np.asarray(b.nbr_local), np.asarray(b.mask))
                        for b in bt.layers],
                n_id=n_id, n_mask=np.asarray(bt.n_id_mask), labels=labels,
                drop_key=kd))
            state, loss = step(state, jnp.asarray(seeds),
                               jnp.asarray(labels), jnp.ones(64, bool), key)
            losses.append(float(loss))
            if i == 0:
                grad = tm(lambda a: np.asarray(a) / 0.1,
                          state.opt_state[0].mu)
        theirs = ref.train_follow(data["params"], data["model_state"],
                                  batches, CFG, "highest")
        evaluate = make_fused_eval_fn(sampler, feature, apply_fn)
        logits = evaluate(state.params, jnp.asarray(seeds), make_key(9),
                          state.model_state)
        return dict(losses=losses, grad=grad, state=state, theirs=theirs,
                    batches=batches, model=model, logits=logits)


def test_the_sampler_drew_no_target_as_its_own_neighbour(followed, data):
    for i, b in enumerate(followed["batches"]):
        bad, edges = ref.check_sample(
            data["indptr"], data["indices"], CFG["fanout"],
            b["n_id"][:64], b["n_id"], b["n_mask"], b["layers"])
        assert edges > 0 and not any(bad.values()), (i, bad)
        # the two seeds without a neighbour have only their self-loop
        assert not b["layers"][-1][1][:2].any()


def test_loss_of_three_steps(followed):
    ours, theirs = followed["losses"], followed["theirs"][0]
    assert np.allclose(ours, theirs, rtol=1e-5), (ours, theirs)


def test_every_gradient_leaf(followed):
    ours, theirs = leaves(followed["grad"]), leaves(followed["theirs"][1])
    assert ours.keys() == theirs.keys() and len(ours) == 22
    for name in ours:
        if name in PRE_NORM_BIASES:
            assert np.abs(ours[name]).max() < 1e-6, name
            assert np.abs(theirs[name]).max() < 1e-6, name
        else:
            assert close(ours[name], theirs[name]), name
    assert ref.tree_distance(followed["grad"], followed["theirs"][1]) < 1e-5


def test_parameters_after_three_adam_steps(followed):
    before = leaves(followed["theirs"][2])
    ours = leaves(followed["state"].params)
    walked = 3 * CFG["lr"]
    start = leaves(ref.gat_params(CFG, SEED)[0])
    for name, theirs in before.items():
        if name in PRE_NORM_BIASES:
            assert np.abs(ours[name] - start[name]).max() <= 1.01 * walked
            continue
        assert np.abs(ours[name] - theirs).max() <= 0.05 * walked, name
        assert np.abs(theirs - start[name]).max() > 0.5 * walked, name
    gap = ref.leaf_norm_gap(
        tm(lambda a, b: np.asarray(a) - b, followed["state"].params,
           ref.gat_params(CFG, SEED)[0]),
        tm(lambda a, b: a - b, followed["theirs"][2],
           ref.gat_params(CFG, SEED)[0]),
        skip_below=followed["theirs"][1])
    assert gap <= 1e-4, gap


def test_batchnorm_running_averages(followed):
    """The variances to float32 rounding; a mean follows the pre-norm
    biases, which walk +-lr a step on either side (module docstring), so it
    is held to momentum x that walk."""
    ours = leaves(followed["state"].model_state)
    theirs = leaves(followed["theirs"][3])
    assert sorted(ours) == sorted(theirs) and len(ours) == 6
    for name in ours:
        if name.endswith("var"):
            assert close(ours[name], theirs[name]), name
            assert np.abs(ours[name] - 1.0).max() > 1e-3    # they moved
        else:
            assert np.abs(ours[name] - theirs[name]).max() <= \
                0.1 * 3 * 2 * 3 * CFG["lr"], name


def test_fused_eval_reads_the_running_averages(followed):
    logits = np.asarray(followed["logits"])
    assert logits.shape == (64, CFG["classes"])
    assert np.isfinite(logits).all()


# ------------------------------------------------------------ piece by piece
@functools.partial(jax.jit, static_argnums=(0,))
def _apply(model, variables, x, blocks, n_id, n_mask):
    return model.apply(variables, x, blocks, n_id, n_mask, train=True,
                       rngs={"dropout": jax.random.key(3)},
                       mutable=["batch_stats"])


def logits_of(model, data, b, blocks, x=None):
    variables = {**tm(jnp.asarray, data["params"]),
                 **tm(jnp.asarray, data["model_state"])}
    return _apply(model, variables,
                  jnp.asarray(b["rows"] if x is None else x), blocks,
                  jnp.asarray(b["n_id"]), jnp.asarray(b["n_mask"]))


_reference = jax.jit(ref.gat_forward, static_argnums=(5, 6))


def reference_logits(data, b):
    layers = [(jnp.asarray(n), jnp.asarray(m)) for n, m in b["layers"]]
    shapes = [(n.shape[0], CFG["hidden"]) for n, _ in layers]
    shapes.append(shapes[-1])
    drop = ref.sage.dropout_masks(jax.random.key(3), shapes, CFG["dropout"])
    return _reference(
        tm(jnp.asarray, data["params"]), tm(jnp.asarray, data["model_state"]),
        jnp.asarray(b["rows"]), layers, jnp.asarray(b["n_mask"]),
        CFG["heads"], "highest", drop)


def test_logits_and_new_state_of_one_batch(followed, data):
    b = followed["batches"][0]
    ours, state = logits_of(followed["model"], data, b,
                            as_blocks(b["layers"]))
    theirs, their_state = reference_logits(data, b)
    assert close(np.asarray(ours), np.asarray(theirs))
    for name, a in leaves(state).items():
        assert close(a, leaves(their_state)[name]), name


def test_the_models_bfloat16_path_misses_the_tolerance(followed, data):
    """``GNN(dtype=bfloat16)`` (the cell's control) against the same
    reference: the tolerance that the stated precision keeps is tight
    enough to tell the two apart."""
    b = followed["batches"][0]
    lower, _ = logits_of(model_of(dtype=jnp.bfloat16), data, b,
                         as_blocks(b["layers"]))
    theirs, _ = reference_logits(data, b)
    assert np.isfinite(np.asarray(lower)).all()
    assert not close(np.asarray(lower), np.asarray(theirs), rel=1e-4)


def test_sources_by_slice_and_by_gather_agree(followed, data):
    """The sampler's blocks are positional: the convolution lays the INPUT
    rows out by slot and projects them there.  The same blocks without the
    marker project every node once and gather the projection through
    ``nbr_local``; the reference always does."""
    b = followed["batches"][0]
    by_gather, _ = logits_of(followed["model"], data, b,
                             as_blocks(b["layers"]))
    by_slice, _ = logits_of(followed["model"], data, b,
                            as_blocks(b["layers"], POSITIONAL))
    assert close(np.asarray(by_slice), np.asarray(by_gather))


def _one_block(rng, t=6, k=3, d=5, live=None):
    """A positional block by hand: ``t`` targets, ``k`` slots each."""
    nbr = (t + np.arange(t)[:, None] * k + np.arange(k)).astype(np.int32)
    mask = np.ones((t, k), bool) if live is None else live
    x = rng.standard_normal((t * (1 + k), d)).astype(np.float32)
    return x, nbr, mask


@pytest.mark.parametrize("layout", [None, POSITIONAL],
                         ids=["gathered", "positional"])
def test_a_target_with_no_live_neighbour_returns_its_own_row(layout):
    """The self-loop alone: the softmax over one entry is 1, so the output
    is the target's own projected row plus the bias, whatever the dead
    slots hold."""
    rng = np.random.default_rng(2)
    live = np.ones((6, 3), bool)
    live[[1, 4]] = False
    x, nbr, mask = _one_block(rng, live=live)
    conv = GATConv(4, heads=2)
    blk = as_blocks([(nbr, mask)], layout)[0]
    params = conv.init(jax.random.key(0), jnp.asarray(x), blk)
    params = tm(np.array, params)
    params["params"]["bias"] = rng.standard_normal(8).astype(np.float32)
    out = np.asarray(conv.apply(params, jnp.asarray(x), blk))
    own = x[:6] @ params["params"]["lin"]["kernel"] + params["params"]["bias"]
    assert close(out[[1, 4]], own[[1, 4]])
    assert not close(out[[0, 2, 3, 5]], own[[0, 2, 3, 5]], rel=1e-2)


@pytest.mark.parametrize("layout", [None, POSITIONAL],
                         ids=["gathered", "positional"])
def test_a_masked_slot_changes_nothing(layout):
    """What a dead slot's row holds reaches neither the output nor any
    gradient: huge rows there, the same answers."""
    rng = np.random.default_rng(3)
    live = rng.random((6, 3)) < 0.6
    live[0] = True
    x, nbr, mask = _one_block(rng, live=live)
    conv = GATConv(4, heads=2)
    blk = as_blocks([(nbr, mask)], layout)[0]
    params = conv.init(jax.random.key(0), jnp.asarray(x), blk)
    w = jnp.asarray(rng.standard_normal((6, 8)), jnp.float32)

    def loss(p, x):
        return (conv.apply(p, x, blk) * w).sum()

    huge = x.copy()
    huge[nbr[~live]] = 1e4
    out = conv.apply(params, jnp.asarray(x), blk)
    again = conv.apply(params, jnp.asarray(huge), blk)
    assert close(np.asarray(again), np.asarray(out))
    g, gx = jax.grad(loss, argnums=(0, 1))(params, jnp.asarray(x))
    g2, gx2 = jax.grad(loss, argnums=(0, 1))(params, jnp.asarray(huge))
    for name, a in leaves(g).items():
        assert close(leaves(g2)[name], a), name
    assert not np.asarray(gx)[nbr[~live]].any()
    assert not np.asarray(gx2)[nbr[~live]].any()


def test_self_loop_weighs_in_with_the_neighbours():
    """One target, two live neighbours, by hand: the softmax runs over
    three entries, the target's own scored with ``att_src`` on its own
    row."""
    rng = np.random.default_rng(4)
    x, nbr, mask = _one_block(rng, t=1, k=2, d=3)
    conv = GATConv(4, heads=1)
    blk = as_blocks([(nbr, mask)], POSITIONAL)[0]
    params = conv.init(jax.random.key(1), jnp.asarray(x), blk)
    p = tm(np.asarray, params)["params"]
    h = x @ p["lin"]["kernel"]
    e = h @ p["att_src"][0] + h[0] @ p["att_tgt"][0]     # neighbours + self
    e = np.where(e > 0, e, 0.2 * e)[[1, 2, 0]]
    alpha = np.exp(e - e.max()) / np.exp(e - e.max()).sum()
    want = alpha @ h[[1, 2, 0]] + p["bias"]
    out = np.asarray(conv.apply(params, jnp.asarray(x), blk))[0]
    assert close(out, want)


def test_gnn_and_rgnn_share_one_frame():
    """``lsc_frame`` is what both published models put around their
    convolution: the same submodules under the same names, one call
    convention (``rgnn_apply_fn`` serves both)."""
    rng = np.random.default_rng(5)
    x, nbr, mask = _one_block(rng, t=4, k=2, d=6)
    blocks = as_blocks([(nbr, mask)])
    n_id, n_mask = jnp.arange(12, dtype=jnp.int32), jnp.ones(12, bool)
    untyped = GNN(hidden=8, out_dim=3, num_layers=1, heads=2)
    typed = RGNN(hidden=8, out_dim=3, num_relations=1, type_offsets=(0, 12),
                 relation_of=((0,),), num_layers=1, heads=2)
    va = untyped.init(jax.random.key(0), jnp.asarray(x), blocks, n_id, n_mask)
    vb = typed.init(jax.random.key(0), jnp.asarray(x), blocks, n_id, n_mask)
    frame = {"skip0", "norm0", "mlp_lin0", "mlp_norm", "mlp_lin1"}
    assert set(va["params"]) == set(vb["params"]) == frame | {"conv0"}
    assert leaves(va["batch_stats"]).keys() == leaves(vb["batch_stats"]).keys()
    for model, v in ((untyped, va), (typed, vb)):
        logits, state = rgnn_apply_fn(model)(
            {"params": v["params"]}, jnp.asarray(x, jnp.float16), blocks,
            train=True, rngs={"dropout": jax.random.key(1)},
            frontier=(n_id, n_mask),
            model_state={"batch_stats": v["batch_stats"]})
        assert logits.shape == (4, 3) and "batch_stats" in state
