"""The published R-GAT (``models.RGNN``) against its plain reference
(``cellbench/references/rgat.py``, which imports nothing of quiver_tpu), on
seeded weights, on the CPU, at a small size, float32 products
(``highest``): through ``GraphSageSampler`` -> ``Feature`` ->
``make_fused_train_step`` for three steps, and piece by piece.

Tolerances.  Program and reference compute the same float32 sums in
another order (a grouped product against five whole ones, a slice against
a gather, one softmax pass against five), so a value differs by a few
float32 roundings: 1e-5 relative to the leaf's largest entry.  After
three Adam steps an ELEMENT may differ by far more, because Adam divides
by ``sqrt(v)``: one whose gradient is a few roundings from 0 (a ``W_dst``
entry of a target with a single edge, whose softmax is constant) steps
+-lr on either side, so elements are held to 5% of ``lr`` x steps and
each leaf's walk, as a norm, to 1e-4 (the benchmark's ``delta_gap``).  A bias added BEFORE a BatchNorm in
training mode (``conv<i>/bias``, ``skip<i>/bias``, ``mlp_lin0/bias``) has
a gradient of exactly 0 in real arithmetic; what both sides compute is
rounding noise, which Adam turns into steps of +-lr, so those leaves are
held to |gradient| < 1e-6 and |step| <= lr x steps and to nothing else.
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
from quiver_tpu.models import RGNN, rgnn_apply_fn  # noqa: E402
from quiver_tpu.models.rgat import RelGATConv, grouped_project  # noqa: E402
from quiver_tpu.parallel import Frontier, TrainState, make_train_step  # noqa: E402
from quiver_tpu.pipeline import (make_fused_eval_fn,  # noqa: E402
                                 make_fused_train_step, make_scan_epoch)
from quiver_tpu.sampler import POSITIONAL, LayerBlock  # noqa: E402

ref = importlib.import_module("references.rgat")

CFG = dict(papers=2000, authors=1900, institutions=40, edges_cites=20000,
           edges_writes=6000, edges_affiliated_with=700, feature_dim=16,
           classes=7, hidden=32, heads=4, num_layers=2, num_relations=5,
           fanout=[5, 3], batch=64, dropout=0.5, lr=1e-3)
PRE_NORM_BIASES = ("conv0/bias", "conv1/bias", "skip0/bias", "skip1/bias",
                   "mlp_lin0/bias")
tm = jax.tree_util.tree_map


@pytest.fixture(autouse=True)
def float32_products():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture(scope="module")
def data():
    return ref.make_data(CFG, 2**31 + 5)


def model_of(cfg=CFG, **kw):
    return RGNN(hidden=cfg["hidden"], out_dim=cfg["classes"],
                num_relations=cfg["num_relations"],
                type_offsets=ref.type_offsets(cfg),
                relation_of=ref.RELATION_OF, heads=cfg["heads"],
                dropout=cfg["dropout"], **kw)


def leaves(tree):
    return {"/".join(k.key for k in path[1:]): np.asarray(a) for path, a
            in jax.tree_util.tree_leaves_with_path(tree)}


def close(a, b, rel=1e-5):
    return np.abs(a - b).max() <= rel * max(np.abs(b).max(), 1e-6)


# ------------------------------------------------------- the typed generator
def test_typed_graph_has_its_edges_between_the_right_ranges(data):
    off = ref.type_offsets(CFG)
    assert off == (0, 2000, 3900, 3940)
    indptr, indices = data["indptr"], data["indices"]
    row = np.repeat(np.arange(off[-1]), np.diff(indptr))
    table = np.asarray(ref.RELATION_OF)
    rel = table[ref.node_types(indices, off), ref.node_types(row, off)]
    want = [2 * CFG["edges_cites"], CFG["edges_writes"], CFG["edges_writes"],
            CFG["edges_affiliated_with"], CFG["edges_affiliated_with"]]
    assert (rel >= 0).all()
    assert np.bincount(rel, minlength=5).tolist() == want
    other = ref.make_data(CFG, 2**31 + 6)
    assert other["indices"].shape == indices.shape      # same shapes
    assert not np.array_equal(other["indices"], indices)
    assert data["features"].dtype == np.float16
    assert np.abs(data["features"].astype(np.float32)).min() >= 0.5


def test_float16_rows_pass_through_the_feature_store_bit_exact(data):
    rows = data["features"]
    feature = Feature(device_cache_size=len(rows), cache_unit="rows",
                      dtype=jnp.float16).from_cpu_tensor(rows)
    ids = np.random.default_rng(0).integers(0, len(rows), 500)
    got = np.asarray(feature[jnp.asarray(ids, jnp.int32)])
    assert got.dtype == np.float16
    assert np.array_equal(got.view(np.uint16), rows[ids].view(np.uint16))


# ------------------------------------------ three steps through the pipeline
@pytest.fixture(scope="module")
def followed(data):
    """Three fused steps of the program and the reference's three."""
    with jax.default_matmul_precision("highest"):
        off = ref.type_offsets(CFG)
        topo = CSRTopo(indptr=data["indptr"], indices=data["indices"])
        sampler = GraphSageSampler(topo, CFG["fanout"])
        feature = Feature(device_cache_size=off[-1], cache_unit="rows",
                          dtype=jnp.float16).from_cpu_tensor(
                              data["features"])
        model = model_of()
        apply_fn = rgnn_apply_fn(model)
        tx = optax.adam(CFG["lr"])
        state = TrainState.create(tm(jnp.asarray, data["params"]), tx,
                                  tm(jnp.asarray, data["model_state"]))
        step = make_fused_train_step(sampler, feature, apply_fn, tx)
        seeds = (np.arange(64, dtype=np.int32) * 7) % CFG["papers"]
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


def test_loss_of_three_steps(followed):
    ours, theirs = followed["losses"], followed["theirs"][0]
    assert np.allclose(ours, theirs, rtol=1e-5), (ours, theirs)


def test_every_gradient_leaf(followed):
    ours, theirs = leaves(followed["grad"]), leaves(followed["theirs"][1])
    assert ours.keys() == theirs.keys() and len(ours) == 24
    for name in ours:
        if name in PRE_NORM_BIASES:
            assert np.abs(ours[name]).max() < 1e-6, name
            assert np.abs(theirs[name]).max() < 1e-6, name
        else:
            assert close(ours[name], theirs[name]), name


def test_parameters_after_three_adam_steps(followed):
    before = leaves(followed["theirs"][2])
    ours = leaves(followed["state"].params)
    walked = 3 * CFG["lr"]
    start = leaves(ref.rgat_params(CFG, 2**31 + 5)[0])
    for name, theirs in before.items():
        if name in PRE_NORM_BIASES:
            assert np.abs(ours[name] - start[name]).max() <= 1.01 * walked
            continue
        assert np.abs(ours[name] - theirs).max() <= 0.05 * walked, name
        assert np.abs(theirs - start[name]).max() > 0.5 * walked, name
    gap = ref.leaf_norm_gap(
        tm(lambda a, b: np.asarray(a) - b, followed["state"].params,
           ref.rgat_params(CFG, 2**31 + 5)[0]),
        tm(lambda a, b: a - b, followed["theirs"][2],
           ref.rgat_params(CFG, 2**31 + 5)[0]),
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


def test_scan_epoch_carries_the_model_state_like_the_fused_step(data):
    off = ref.type_offsets(CFG)
    topo = CSRTopo(indptr=data["indptr"], indices=data["indices"])
    sampler = GraphSageSampler(topo, CFG["fanout"])
    feature = Feature(device_cache_size=off[-1], cache_unit="rows",
                      dtype=jnp.float16).from_cpu_tensor(data["features"])
    apply_fn = rgnn_apply_fn(model_of())
    tx = optax.adam(CFG["lr"])

    def fresh():
        return TrainState.create(tm(jnp.asarray, data["params"]), tx,
                                 tm(jnp.asarray, data["model_state"]))

    seeds = jnp.asarray(np.arange(128, dtype=np.int32).reshape(2, 64))
    labels = jnp.asarray(data["labels"][np.asarray(seeds)])
    key = make_key(3)
    state, losses = make_scan_epoch(sampler, feature, apply_fn, tx)(
        fresh(), seeds, labels, key)
    step = make_fused_train_step(sampler, feature, apply_fn, tx)
    one = fresh()
    for i, k in enumerate(jax.random.split(key, 2)):
        one, loss = step(one, seeds[i], labels[i], jnp.ones(64, bool), k)
        assert np.isclose(float(loss), float(losses[i]), rtol=1e-5)
    for name, a in leaves(state.model_state).items():
        b = leaves(one.model_state)[name]
        if name.endswith("var"):
            assert close(a, b), name
        else:   # follows the pre-norm biases' walk: module docstring
            assert np.abs(a - b).max() <= 0.1 * 2 * 6 * CFG["lr"], name


# ------------------------------------------------------------ piece by piece
def first_batch(followed):
    b = followed["batches"][0]
    blocks = tuple(LayerBlock(jnp.asarray(n), jnp.asarray(m),
                              jnp.asarray(m.shape[0], jnp.int32))
                   for n, m in b["layers"])
    return b, blocks


@functools.partial(jax.jit, static_argnums=(0,))
def _apply(model, variables, x, blocks, n_id, n_mask):
    return model.apply(variables, x, blocks, n_id, n_mask, train=True,
                       rngs={"dropout": jax.random.key(3)},
                       mutable=["batch_stats"])


def logits_of(model, data, b, blocks, x=None, n_mask=None, params=None):
    variables = {**tm(jnp.asarray, params or data["params"]),
                 **tm(jnp.asarray, data["model_state"])}
    return _apply(model, variables,
                  jnp.asarray(b["rows"] if x is None else x), blocks,
                  jnp.asarray(b["n_id"]),
                  jnp.asarray(b["n_mask"] if n_mask is None else n_mask))


_reference = jax.jit(ref.rgat_forward, static_argnums=(6, 7, 8))


def reference_logits(data, b, x=None, n_mask=None, params=None):
    layers = [(jnp.asarray(n), jnp.asarray(m)) for n, m in b["layers"]]
    shapes = [(n.shape[0], CFG["hidden"]) for n, _ in layers]
    shapes.append(shapes[-1])
    drop = ref.sage.dropout_masks(jax.random.key(3), shapes, CFG["dropout"])
    return _reference(
        tm(jnp.asarray, params or data["params"]),
        tm(jnp.asarray, data["model_state"]),
        jnp.asarray(b["rows"] if x is None else x), layers,
        jnp.asarray(b["n_id"]),
        jnp.asarray(b["n_mask"] if n_mask is None else n_mask),
        ref.type_offsets(CFG), CFG["heads"], "highest", drop)


def test_logits_and_new_state_of_one_batch(followed, data):
    b, blocks = first_batch(followed)
    ours, state = logits_of(followed["model"], data, b, blocks)
    theirs, their_state = reference_logits(data, b)
    assert close(np.asarray(ours), np.asarray(theirs))
    for name, a in leaves(state).items():
        assert close(a, leaves(their_state)[name]), name


def test_sources_by_slice_and_by_gather_agree(followed, data):
    """The sampler's blocks are positional: the convolution reads its
    sources as a slice.  The same blocks without the marker go through
    ``nbr_local``; the reference always does."""
    b, blocks = first_batch(followed)
    assert all(blk.layout is None for blk in blocks)
    marked = tuple(blk._replace(layout=POSITIONAL) for blk in blocks)
    by_gather, _ = logits_of(followed["model"], data, b, blocks)
    by_slice, _ = logits_of(followed["model"], data, b, marked)
    assert close(np.asarray(by_slice), np.asarray(by_gather))


def test_grouped_projection_against_five_masked_ones():
    rng = np.random.default_rng(1)
    m, d, n, g = 600, 24, 40, 5
    x = jnp.asarray(rng.standard_normal((m, d)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((g, d, n)), jnp.float32)
    group = jnp.asarray(rng.integers(0, g + 1, m), jnp.int32)  # g: dead
    live = np.asarray(group) < g

    def masked(x, w):
        return sum(((group == q)[:, None] * x) @ w[q] for q in range(g))

    ours = np.asarray(grouped_project(x, group, w))
    assert close(ours[live], np.asarray(masked(x, w))[live])
    # a dead row holds some live row's result: finite, for a weight of 0
    assert np.isfinite(ours[~live]).all()
    # the same with 5 slots a target padded to 8: [T * 8, N]
    padded = np.asarray(grouped_project(x, group, w, pad_to=(5, 8)))
    assert padded.shape == (m // 5 * 8, n)
    assert np.array_equal(padded.reshape(-1, 8, n)[:, :5].reshape(m, n)[live],
                          ours[live])
    # both gradients, the weights' and the rows' (0 for a dead row), of a
    # loss that weighs dead rows with 0 as every caller does
    weigh = jnp.asarray(live[:, None], jnp.float32)

    def loss(f):
        return lambda x, w: ((f(x, w) * weigh) ** 2).sum()

    got = jax.grad(loss(lambda x, w: grouped_project(x, group, w)),
                   argnums=(0, 1))(x, w)
    want = jax.grad(loss(masked), argnums=(0, 1))(x, w)
    assert close(np.asarray(got[0]), np.asarray(want[0]))
    assert close(np.asarray(got[1]), np.asarray(want[1]))
    assert not np.asarray(got[0])[~live].any()


def hand_batch(data, author_targets):
    """One layer pair by hand: 8 targets at layer 1, 24 at layer 0, every
    id chosen, so that a relation can be left without any edge."""
    off = ref.type_offsets(CFG)
    rng = np.random.default_rng(4)
    t1, k1, k0 = 8, 2, 3
    t0 = t1 * (1 + k1)
    p = t0 * (1 + k0)
    papers = rng.integers(0, off[1], p)
    authors = rng.integers(off[1], off[2], p)
    n_id = papers.copy()
    if author_targets:
        n_id[t1:t0:2] = authors[t1:t0:2]      # layer-0 targets: some authors
        n_id[t0::3] = authors[t0::3]          # and some author sources
    layers = []
    for t, k in ((t0, k0), (t1, k1)):
        nbr = (t + np.arange(t)[:, None] * k + np.arange(k)).astype(np.int32)
        layers.append((nbr, np.ones((t, k), bool)))
    return dict(rows=data["features"][n_id].astype(np.float32),
                layers=layers, n_id=n_id.astype(np.int32),
                n_mask=np.ones(p, bool))


def test_a_relation_with_no_edge_in_the_layer_adds_not_even_its_bias(data):
    """Paper targets and paper sources only: ``cites`` alone has edges, in
    both layers.  The biases of the four other relations are made large:
    they must not reach the output, and the reference (which has the rule)
    agrees; with authors among sources and targets, ``writes`` and its
    reverse wake up and their biases are added to EVERY target of layer 0."""
    params = tm(np.array, data["params"])
    for i in range(2):
        params["params"][f"conv{i}"]["bias"][1:] = 50.0
    model = model_of()
    quiet = hand_batch(data, author_targets=False)
    blocks = tuple(LayerBlock(jnp.asarray(n), jnp.asarray(m),
                              jnp.asarray(m.shape[0], jnp.int32))
                   for n, m in quiet["layers"])
    ours, state = logits_of(model, data, quiet, blocks, params=params)
    plain, plain_state = logits_of(model, data, quiet, blocks)
    theirs, their_state = reference_logits(data, quiet, params=params)
    assert close(np.asarray(ours), np.asarray(theirs))
    # a bias added to every target is taken out again by the BatchNorm
    # that follows, so the logits cannot tell; the running mean can
    for name in ("norm0/mean", "norm1/mean"):
        assert close(leaves(state)[name], leaves(their_state)[name]), name
        assert close(leaves(state)[name], leaves(plain_state)[name]), name
    loud = hand_batch(data, author_targets=True)
    ours, state = logits_of(model, data, loud, blocks, params=params)
    plain, plain_state = logits_of(model, data, loud, blocks)
    theirs, their_state = reference_logits(data, loud, params=params)
    assert close(np.asarray(ours), np.asarray(theirs))
    assert close(leaves(state)["norm0/mean"],
                 leaves(their_state)["norm0/mean"])
    shift = leaves(state)["norm0/mean"] - leaves(plain_state)["norm0/mean"]
    # writes and its reverse woke up in layer 0: 0.1 x 2 x (50 - 0.01-ish)
    assert np.abs(shift - 10.0).max() < 0.1, shift


def test_padded_targets_do_not_move_the_statistics(data):
    """Half of layer 0's targets are padding (``n_mask`` false).  Their
    rows are then made huge: logits of the valid seeds and the running
    averages stay where they were, as the reference's do."""
    model = model_of()
    b = hand_batch(data, author_targets=True)
    t1, t0 = 8, 24
    n_mask = np.ones(len(b["n_id"]), bool)
    n_mask[t1 + 1:t0:2] = False                 # padded layer-0 targets
    # a padded target is nobody's valid source either
    layers = [(n, m.copy()) for n, m in b["layers"]]
    layers[1][1][:] = n_mask[layers[1][0]]
    b = dict(b, layers=layers)
    blocks = tuple(LayerBlock(jnp.asarray(n), jnp.asarray(m),
                              jnp.asarray(m.shape[0], jnp.int32))
                   for n, m in layers)
    ours, state = logits_of(model, data, b, blocks, n_mask=n_mask)
    theirs, their_state = reference_logits(data, b, n_mask=n_mask)
    assert close(np.asarray(ours), np.asarray(theirs))
    for name, a in leaves(state).items():
        assert close(a, leaves(their_state)[name]), name
    huge = b["rows"].copy()
    huge[np.flatnonzero(~n_mask)] *= 1e3
    again, state2 = logits_of(model, data, b, blocks, x=huge, n_mask=n_mask)
    assert close(np.asarray(again), np.asarray(ours))
    for name, a in leaves(state2).items():
        assert close(a, leaves(state)[name]), name
    # and counting them would have moved both
    counted, state3 = logits_of(model, data, b, blocks, x=huge)
    assert not close(leaves(state3)["norm0/var"], leaves(state)["norm0/var"],
                     rel=1e-2)


# -------------------------------------------------- the two-stage train step
def test_make_train_step_carries_frontier_and_state(followed, data):
    b, blocks = first_batch(followed)
    model = followed["model"]
    tx = optax.adam(CFG["lr"])
    state = TrainState.create(tm(jnp.asarray, data["params"]), tx,
                              tm(jnp.asarray, data["model_state"]))
    step = make_train_step(rgnn_apply_fn(model), tx)
    labels = jnp.asarray(b["labels"])
    state, loss = step(state, jnp.asarray(b["rows"]), blocks, labels,
                       jnp.ones(64, bool), b["drop_key"],
                       Frontier(jnp.asarray(b["n_id"]),
                                jnp.asarray(b["n_mask"])))
    assert np.isclose(float(loss), followed["losses"][0], rtol=1e-5)
    moved = leaves(state.model_state)["norm0/var"]
    assert np.abs(moved - 1.0).max() > 1e-3


def test_a_plain_model_has_no_state_to_carry():
    state = TrainState.create({"w": jnp.ones(3)}, optax.sgd(0.1))
    assert state.model_state == {}
    assert len(jax.tree_util.tree_leaves(state)) == 1
    again = jax.tree_util.tree_unflatten(*jax.tree_util.tree_flatten(state)[::-1])
    assert again.model_state == {}


def test_relation_of_an_edge_is_its_endpoints_types(data):
    off = ref.type_offsets(CFG)
    model = model_of()
    n_id = jnp.asarray([5, off[1] + 5, off[2] + 5, 7, off[1] + 9, 11],
                       jnp.int32)      # paper, author, inst | sources
    nbr = jnp.asarray([[3], [4], [4]], jnp.int32)
    blk = LayerBlock(nbr, jnp.ones((3, 1), bool), jnp.asarray(3))
    rel = np.asarray(model.edge_relations(n_id, blk))[:, 0]
    # paper->paper cites; author->author none; author->institution
    assert rel.tolist() == [0, -1, 3]
    conv = RelGATConv(8, 4, 5)
    x = jnp.ones((6, 16))
    out, _ = jax.jit(conv.init_with_output)(
        jax.random.key(0), x, blk, jnp.asarray(rel)[:, None])
    assert out.shape == (3, 32)
