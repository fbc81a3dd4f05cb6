"""MLPerf's R-GAT over a typed tree (``models.TypedRGNN``) against its plain
reference (``cellbench/references/igbh_rgat.py``, which imports nothing of
quiver_tpu), on seeded weights, on the CPU, at a small size of IGBH's six
types and eleven relations, float32 products (``highest``): through
``GraphSageSampler(schema=...)`` -> ``Feature`` -> ``make_fused_train_step``
for three steps, and piece by piece.

Tolerances, as ``tests/test_rgnn.py`` sets them and for the same reasons.
Program and reference compute the same float32 sums in another order (a
slice and one product per relation block against a gather and a
scatter-add by position), so a value differs by a few float32 roundings:
1e-5 relative to the leaf's largest entry.  After three Adam steps an
element whose gradient is a few roundings from 0 steps +-lr on either side,
so elements are held to 5% of lr x steps and each leaf's walk, as a norm,
to 1e-4 (the benchmark's ``delta_gap``).  Such elements are many here: a
target's attention scores shift by its own side ``a_dst . g_i`` alike
wherever the leaky ReLU keeps them on one side of 0, so ``att_dst`` has a
gradient of exactly 0 there (and ``att_src`` too where a target has one
draw, as a paper has of its journal): an element whose first gradient is
under 1e-4 of its leaf's largest is held to |step| <= lr x steps and to
nothing else."""

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
from quiver_tpu.models import TypedRGNN  # noqa: E402
from quiver_tpu.models.rgat import TypedGATConv  # noqa: E402
from quiver_tpu.parallel import TrainState  # noqa: E402
from quiver_tpu.pipeline import (make_fused_eval_fn,  # noqa: E402
                                 make_fused_train_step)
from quiver_tpu.sampler import LayerBlock, Schema, _Positional  # noqa: E402

ref = importlib.import_module("references.igbh_rgat")

CFG = dict(papers=600, authors=500, institutes=12, fields=40, journals=9,
           conferences=5, edges_cites=4000, edges_written_by=1500,
           edges_affiliated_to=300, edges_topic=2000, edges_published=240,
           edges_venue=120, feature_dim=16, classes=7, hidden=32, heads=4,
           num_layers=3, fanout=[4, 3, 2], batch=6, dropout=0.2, lr=1e-3)
SEED = 2**31 + 23
R = len(ref.RELATIONS)
tm = jax.tree_util.tree_map


@pytest.fixture(autouse=True)
def float32_products():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture(scope="module")
def data():
    return ref.make_data(CFG, SEED)


def schema():
    return Schema(ref.type_offsets(CFG),
                  tuple((s, t) for _, s, t in ref.RELATIONS))


def model_of(**kw):
    return TypedRGNN(hidden=CFG["hidden"], out_dim=CFG["classes"],
                     num_relations=R, heads=CFG["heads"],
                     dropout=CFG["dropout"], **kw)


def leaves(tree):
    return {"/".join(k.key for k in path[1:]): np.array(a) for path, a
            in jax.tree_util.tree_leaves_with_path(tree)}


def close(a, b, rel=1e-5):
    return np.abs(a - b).max() <= rel * max(np.abs(b).max(), 1e-6)


def apply_fn_of(model):
    def apply_fn(p, x, blocks, train=False, rngs=None):
        return model.apply(p, x, blocks, train=train, rngs=rngs)
    return apply_fn


# ------------------------------------------ three steps through the pipeline
@pytest.fixture(scope="module")
def followed(data):
    """Three fused steps of the program and the reference's three; the
    first batch repeats a paper (two positions of one node)."""
    with jax.default_matmul_precision("highest"):
        off = ref.type_offsets(CFG)
        topo = CSRTopo(indptr=data["indptr"], indices=data["indices"])
        sampler = GraphSageSampler(topo, CFG["fanout"], schema=schema())
        feature = Feature(device_cache_size=off[-1], cache_unit="rows",
                          dtype=jnp.float16).from_cpu_tensor(
                              data["features"])
        model = model_of()
        tx = optax.adam(CFG["lr"])
        state = TrainState.create(tm(jnp.asarray, data["params"]), tx)
        step = make_fused_train_step(sampler, feature, apply_fn_of(model),
                                     tx)
        # the most-cited paper, twice
        deg = ref.relation_degrees(data["indptr"], data["indices"], off)
        top = int(np.argmax(deg[0][:off[1]]))
        seeds = np.array([top, top, 3, 50, 222, 431], np.int32)
        labels = data["labels"][seeds]
        batches, losses, grad = [], [], None
        for i in range(3):
            key = make_key(i)
            ks, kd = jax.random.split(key)
            bt = sampler.sample(jnp.asarray(seeds), key=ks)
            n_id = np.asarray(bt.n_id)
            batches.append(dict(
                rows=data["features"][n_id].astype(np.float32),
                layers=[[(np.asarray(b.nbr_local), np.asarray(b.mask))
                         for b in hop] for hop in bt.layers],
                n_id=n_id, n_mask=np.asarray(bt.n_id_mask), labels=labels,
                drop_key=kd, blocks=bt.layers))
            state, loss = step(state, jnp.asarray(seeds),
                               jnp.asarray(labels), jnp.ones(6, bool), key)
            losses.append(float(loss))
            if i == 0:
                grad = tm(lambda a: np.asarray(a) / 0.1,
                          state.opt_state[0].mu)
        theirs = ref.train_follow(data["params"], batches, CFG, "highest")
        evaluate = make_fused_eval_fn(sampler, feature, apply_fn_of(model))
        return dict(losses=losses, grad=grad, state=state, theirs=theirs,
                    batches=batches, model=model, evaluate=evaluate,
                    seeds=seeds, sampler=sampler, deg=deg)


def test_loss_of_three_steps(followed):
    ours, theirs = followed["losses"], followed["theirs"][0]
    assert np.allclose(ours, theirs, rtol=1e-5), (ours, theirs)


def test_every_gradient_leaf(followed):
    ours, theirs = leaves(followed["grad"]), leaves(followed["theirs"][1])
    assert ours.keys() == theirs.keys() and len(ours) == 14
    for name in ours:
        assert close(ours[name], theirs[name]), name
    # the innermost layer's relations out of the papers have no block (its
    # targets are the seeds): their slices of conv2 have no gradient
    assert not np.abs(ours["conv2/lin"][5:]).any()


def test_parameters_after_three_adam_steps(followed, data):
    theirs = leaves(followed["theirs"][2])
    ours = leaves(followed["state"].params)
    start = leaves(ref.igbh_params(CFG, SEED))
    grad = leaves(followed["theirs"][1])
    walked = 3 * CFG["lr"]
    for name in theirs:
        noise = np.abs(grad[name]) < 1e-4 * np.abs(grad[name]).max()
        assert np.abs(ours[name] - start[name])[noise].max(
            initial=0) <= 1.01 * walked, name
        assert np.abs(ours[name] - theirs[name])[~noise].max(
            initial=0) <= 0.05 * walked, name
    # each leaf's walk as a norm, over the elements that are no noise
    keep = {n: np.abs(g) >= 1e-4 * np.abs(g).max() for n, g in grad.items()}
    gap = ref.leaf_norm_gap(
        [np.where(keep[n], ours[n] - start[n], 0) for n in sorted(theirs)],
        [np.where(keep[n], theirs[n] - start[n], 0) for n in sorted(theirs)],
        skip_below=[grad[n] for n in sorted(theirs)])
    assert gap <= 1e-4, gap
    assert np.abs(theirs["conv0/lin"] - start["conv0/lin"]).max() > \
        0.5 * walked


_reference = jax.jit(ref.igbh_forward, static_argnums=(3, 4, 5, 6))


def reference_logits(b, params):
    layers = [[(jnp.asarray(n), jnp.asarray(m)) for n, m in hop]
              for hop in b["layers"]]
    return _reference(tm(jnp.asarray, params), jnp.asarray(b["rows"]),
                      layers, CFG["batch"], tuple(CFG["fanout"]),
                      CFG["heads"], "highest", None)


@functools.partial(jax.jit, static_argnums=(0,))
def _apply(model, params, x, blocks):
    return model.apply(params, x, blocks)


def test_logits_of_one_batch_in_eval_mode(followed, data):
    b = followed["batches"][0]
    ours = _apply(followed["model"], tm(jnp.asarray, data["params"]),
                  jnp.asarray(data["features"][b["n_id"]]), b["blocks"])
    theirs = reference_logits(b, data["params"])
    assert ours.shape == (CFG["batch"], CFG["classes"])
    assert close(np.asarray(ours), np.asarray(theirs))


def test_a_node_at_two_positions_is_two_trees(followed, data):
    """The departure from PyG the reference notes: the seed drawn twice is
    two positions with a neighbourhood each (the sampler's tree), where
    PyG's sampler would merge them into one node with one embedding.  Its
    two rows of logits differ, and the reference reads the same two."""
    b = followed["batches"][0]
    theirs = np.asarray(reference_logits(b, data["params"]))
    ours = np.asarray(_apply(followed["model"],
                             tm(jnp.asarray, data["params"]),
                             jnp.asarray(data["features"][b["n_id"]]),
                             b["blocks"]))
    hop1 = b["layers"][-1][0]       # cites, into the seeds
    assert followed["deg"][0][followed["seeds"][0]] > CFG["fanout"][0]
    assert not np.array_equal(b["n_id"][hop1[0][0]], b["n_id"][hop1[0][1]])
    assert np.abs(ours[0] - ours[1]).max() > 1e-3
    assert close(ours[:2], theirs[:2])


def test_fused_eval_is_the_model_over_the_samplers_draw(followed, data):
    ks = make_key(11)
    seeds = jnp.asarray(followed["seeds"])
    logits = followed["evaluate"](tm(jnp.asarray, data["params"]), seeds, ks)
    bt = followed["sampler"].sample(seeds, key=ks)
    want = _apply(followed["model"], tm(jnp.asarray, data["params"]),
                  jnp.asarray(data["features"][np.asarray(bt.n_id)]),
                  bt.layers)
    assert close(np.asarray(logits), np.asarray(want))


# ------------------------------------------------------------ piece by piece
def test_a_target_with_no_source_of_a_relation_gets_its_bias_alone():
    """Two relations into one run of four targets; target 1 has no live
    draw of relation 0 and target 2 none of either: a target's row is the
    sum over the relations of (its attention over its live draws, or
    nothing) + b_r; a type no relation reaches (rows 8..9) reads zeros."""
    k, n, d = 3, 4, 8
    x = jax.random.normal(jax.random.key(0), (10 + 2 * n * k, d))
    mask = np.ones((n, k), bool)
    m0, m1 = mask.copy(), mask.copy()
    m0[1] = m0[2] = False
    m1[2] = False
    m0[3, 1:] = False
    blocks = tuple(
        LayerBlock(jnp.zeros((n, k), jnp.int32), jnp.asarray(m),
                   jnp.int32(n), layout=_Positional(
                       relation=r, targets=((4, n),), sources=10 + r * n * k))
        for r, m in ((0, m0), (1, m1)))
    conv = TypedGATConv(features=2, heads=2, num_relations=2)
    params = conv.init(jax.random.key(1), x, blocks, 10)
    params = tm(lambda a: a + 0.1, params)          # biases not zero
    out = np.asarray(conv.apply(params, x, blocks, 10))
    bias = np.asarray(params["params"]["bias"])
    assert out.shape == (10, 4)
    np.testing.assert_allclose(out[6], bias[0] + bias[1], rtol=1e-6)
    assert not np.allclose(out[5], bias[0] + bias[1])
    assert not np.abs(out[:4]).any() and not np.abs(out[8:]).any()
    # relation 0's one draw of target 3: its softmax weight is 1
    w = np.asarray(params["params"]["lin"][0])
    one = (np.asarray(x[10 + 3 * k]) @ w) + bias[0]
    lone = TypedGATConv(features=2, heads=2, num_relations=2).apply(
        params, x, blocks[1:], 10)
    np.testing.assert_allclose(out[7] - np.asarray(lone)[7], one,
                               rtol=1e-5, atol=1e-6)
