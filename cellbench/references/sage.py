"""The plain reference ``sage`` (a configuration names it under
``"reference"``): the seeded data of a GraphSAGE deployment (host CSR, host
table, labels, weights), and GraphSAGE with its loss, gradients and Adam in
straightforward ``jax.numpy``.

It imports nothing of ``quiver_tpu`` and is handed nothing the program has
made except the one thing that cannot be predicted: which neighbours a
random sampler drew.  That draw is read back through the program's own
sampler on the step's own key, held here to the configuration's guarantee
(every neighbour is a neighbour in the host CSR, every target drew
``min(degree, fanout)`` of them, masks are prefixes, the frontier starts
with the seeds), and from there on every number is this file's: rows from
the host table, weights from the seed, forward, loss, backward, Adam.

Precision, as each configuration file states it.  ``matmul`` says what a
matrix product does with its float32 operands:

  * ``"bf16_operands"`` - operands rounded to bfloat16, products summed in
    float32: what a float32 ``dot`` is on a TPU at default precision, and
    so what the program runs as deployed;
  * ``"highest"``       - float32 throughout;
  * ``"bf16_all"``      - the control: the products' results are bfloat16
    as well, as ``GraphSAGE(dtype=bfloat16)`` computes them.
"""

import functools

import numpy as np

import datagen


# --------------------------------------------------- the data, from the seed
def model_dims(cfg):
    return ([cfg["feature_dim"]] + [cfg["hidden"]] * (cfg["num_layers"] - 1)
            + [cfg["classes"]])


def sage_params(dims, seed):
    """GraphSAGE weights in the tree ``flax`` reads them from
    (``params/conv<i>/lin_self/{kernel,bias}``, ``lin_nbr/kernel``):
    kernels normal / sqrt(fan_in), biases small and not zero so that no
    leaf's gradient is hidden behind a zero."""
    rng = np.random.default_rng(seed + 3)
    convs = {}
    for i, (d_in, d_out) in enumerate(zip(dims[:-1], dims[1:])):
        def kernel():
            return (rng.standard_normal((d_in, d_out)) /
                    np.sqrt(d_in)).astype(np.float32)
        convs[f"conv{i}"] = {
            "lin_self": {"kernel": kernel(),
                         "bias": (0.01 * rng.standard_normal(d_out))
                         .astype(np.float32)},
            "lin_nbr": {"kernel": kernel()},
        }
    return {"params": convs}


def make_data(cfg, seed):
    """Graph, table, labels and weights, all from the seed.  Runs on the
    host while JAX reaches the chip, so nothing at this file's top level
    imports jax."""
    indptr, indices = datagen.csr(cfg["nodes"], cfg["edges"], seed)
    return {
        "indptr": indptr, "indices": indices,
        "features": datagen.features(cfg["nodes"], cfg["feature_dim"], seed,
                                     cfg["feature_dtype"]),
        "labels": datagen.labels(cfg["nodes"], cfg["classes"], seed),
        "params": sage_params(model_dims(cfg), seed),
    }


# ------------------------------------------------------------ the sampler
def neighbours_valid(indptr, indices, src, nbr):
    """Count of ``i`` for which ``nbr[i]`` is NOT a neighbour of ``src[i]``
    in the host CSR: a scan of each row with the unresolved pairs only."""
    start = indptr[src]
    deg = indptr[src + 1] - start
    found = np.zeros(len(src), bool)
    active = np.flatnonzero(deg > 0)
    t = 0
    while active.size:
        hit = indices[start[active] + t] == nbr[active]
        found[active[hit]] = True
        t += 1
        active = active[~hit]
        active = active[deg[active] > t]
    return int((~found).sum())


def check_sample(indptr, indices, fanout, seeds, n_id, n_mask, layers):
    """Hold one sampled batch to the guarantee.  ``layers`` are
    ``(nbr_local, mask)`` pairs, outermost first.  Returns counts of
    breaches, all of which have to be 0, and the edges looked at."""
    deg = indptr[1:] - indptr[:-1]
    bad = {"bad_shape": 0, "bad_masks": 0, "bad_neighbours": 0}
    if len(layers) != len(fanout) or not np.array_equal(
            n_id[:len(seeds)], seeds):
        bad["bad_shape"] += 1
    edges = 0
    for k, (nbr_local, mask) in zip(fanout, layers[::-1]):
        t = mask.shape[0]
        if mask.shape != (t, k):
            bad["bad_shape"] += 1
            continue
        want = np.where(n_mask[:t], np.minimum(deg[n_id[:t]], k), 0)
        bad["bad_masks"] += int(
            (mask != (np.arange(k)[None, :] < want[:, None])).sum())
        tgt, col = np.nonzero(mask)
        bad["bad_neighbours"] += neighbours_valid(
            indptr, indices, n_id[tgt], n_id[nbr_local[tgt, col]])
        edges += len(tgt)
    return bad, edges


# -------------------------------------------------------------- the model
def _round(x, dtype):
    import jax.numpy as jnp

    return x.astype(dtype).astype(jnp.float32)


@functools.lru_cache(maxsize=None)
def _matmul(mode):
    """``x @ w`` under the stated precision, with the backward pass stated
    as well: each of its two products takes its operands the same way."""
    import jax
    import jax.numpy as jnp

    hi = jax.lax.Precision.HIGHEST

    def dot(a, b):
        if mode == "highest":
            return jnp.dot(a, b, precision=hi)
        out = jnp.dot(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                      precision=hi, preferred_element_type=jnp.float32)
        return _round(out, jnp.bfloat16) if mode == "bf16_all" else out

    @jax.custom_vjp
    def mm(x, w):
        return dot(x, w)

    def fwd(x, w):
        return dot(x, w), (x, w)

    def bwd(res, dy):
        x, w = res
        return dot(dy, w.T), dot(x.T, dy)

    mm.defvjp(fwd, bwd)
    return mm


def sage_forward(params, x, layers, matmul="bf16_operands", drop=None):
    """Mean-aggregator GraphSAGE over dense blocks, outermost first:
    ``h_v = W_self x_v + b + W_nbr mean(x_u, u drawn for v)``, ReLU and
    (in training) dropout between layers.  ``drop``: one keep-mask per
    hidden layer, already scaled by 1 / keep."""
    import jax
    import jax.numpy as jnp

    mm = _matmul(matmul)
    last = len(layers) - 1
    for i, (nbr_local, mask) in enumerate(layers):
        p = params["params"][f"conv{i}"]
        t = nbr_local.shape[0]
        m = mask[..., None].astype(jnp.float32)
        cnt = jnp.maximum(m.sum(axis=1), 1.0)
        mean = (x[nbr_local] * m).sum(axis=1) / cnt
        x = (mm(x[:t], p["lin_self"]["kernel"]) + p["lin_self"]["bias"]
             + mm(mean, p["lin_nbr"]["kernel"]))
        if matmul == "bf16_all":
            x = _round(x, jnp.bfloat16)
        if i != last:
            x = jax.nn.relu(x)
            if drop is not None:
                x = x * drop[i]
    return x


def loss_fn(params, x, layers, labels, matmul, drop, label_mask=None):
    """Mean softmax cross-entropy over the seeds (``label_mask`` picks the
    seeds that count; the faults test leaves half of them out)."""
    import jax
    import jax.numpy as jnp

    logits = sage_forward(params, x, layers, matmul, drop)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, labels[:, None], axis=1)[:, 0]
    if label_mask is None:
        return nll.mean()
    m = label_mask.astype(nll.dtype)
    return (nll * m).sum() / jnp.maximum(m.sum(), 1.0)


def dropout_masks(key, shapes, rate):
    """The keep-masks ``flax.linen.Dropout`` draws under ``rngs={'dropout':
    key}`` as the i-th ``Dropout`` child of a root module - which is how
    the configuration states its dropout.  Scaled by 1 / keep."""
    import flax.linen as nn
    import jax.numpy as jnp

    class Masks(nn.Module):
        @nn.compact
        def __call__(self):
            return [nn.Dropout(rate, deterministic=False)(
                jnp.ones(s, jnp.float32)) for s in shapes]

    return Masks().apply({}, rngs={"dropout": key})


def adam_update(params, grads, m, v, step, lr, b1=0.9, b2=0.999, eps=1e-8):
    """One step of Adam (Kingma & Ba, with bias correction), ``step``
    counted from 1.  Returns ``(params, m, v)``."""
    import jax

    tm = jax.tree_util.tree_map
    m = tm(lambda a, g: b1 * a + (1 - b1) * g, m, grads)
    v = tm(lambda a, g: b2 * a + (1 - b2) * g * g, v, grads)
    c1, c2 = 1 - b1 ** step, 1 - b2 ** step
    params = tm(lambda p, a, b: p - lr * (a / c1) / ((b / c2) ** 0.5 + eps),
                params, m, v)
    return params, m, v


def train_follow(params0, batches, cfg, matmul, fault=None):
    """Follow the first steps of training.  ``batches``: per step a dict
    of ``rows`` [P, D] (from the host table), ``layers``, ``labels`` and
    ``drop_key``.  Returns per-step losses, the first gradient, and the
    parameters after the last step - all host numpy.

    ``fault`` plants one of the faults a training step can have, for the
    tests and the readings in PERF.md: ``"half_batch"`` (the mean taken
    over the first half of the seeds), ``"stale_state"`` (the state
    returned unchanged)."""
    import jax
    import jax.numpy as jnp

    tm = jax.tree_util.tree_map
    params = tm(jnp.asarray, params0)
    m = tm(jnp.zeros_like, params)
    v = tm(jnp.zeros_like, params)
    grad = jax.jit(jax.value_and_grad(loss_fn), static_argnums=(4,))
    losses, first = [], None
    for i, b in enumerate(batches):
        layers = [(jnp.asarray(n), jnp.asarray(k)) for n, k in b["layers"]]
        drop = None
        if cfg["dropout"] > 0:
            shapes = [(lay[0].shape[0], cfg["hidden"])
                      for lay in layers[:-1]]
            drop = dropout_masks(b["drop_key"], shapes, cfg["dropout"])
        mask = None
        if fault == "half_batch":
            mask = jnp.arange(len(b["labels"])) < len(b["labels"]) // 2
        loss, g = grad(params, jnp.asarray(b["rows"]), layers,
                       jnp.asarray(b["labels"]), matmul, drop, mask)
        losses.append(float(loss))
        if first is None:
            first = tm(np.asarray, g)
        if fault != "stale_state":
            params, m, v = adam_update(params, g, m, v, i + 1, cfg["lr"])
    return losses, first, tm(np.asarray, params)


# ------------------------------------------------------- the comparisons
def leaf_norm_gap(prog, ref, skip_below=None):
    """Worst leaf of | ||prog|| - ||ref|| | over max(||ref||, the median
    leaf's ||ref||).  ``skip_below``: per-leaf reference gradient norms;
    leaves under a thousandth of their median are left out (they move
    under Adam by round-off alone)."""
    import jax

    p = [float(np.linalg.norm(a)) for a in jax.tree_util.tree_leaves(prog)]
    r = [float(np.linalg.norm(a)) for a in jax.tree_util.tree_leaves(ref)]
    keep = [True] * len(r)
    if skip_below is not None:
        g = [float(np.linalg.norm(a))
             for a in jax.tree_util.tree_leaves(skip_below)]
        keep = [x >= 1e-3 * float(np.median(g)) for x in g]
    med = float(np.median(r))
    return max(abs(a - b) / max(b, med)
               for a, b, k in zip(p, r, keep) if k)
