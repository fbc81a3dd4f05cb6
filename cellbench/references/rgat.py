"""The plain reference ``rgat`` (a configuration names it under
``"reference"``): the seeded data of a typed-graph deployment (host CSR over
ONE id space, host table, labels, weights) and the published R-GAT with its
loss, gradients, Adam and BatchNorm state in straightforward ``jax.numpy``.

The model is OGB-LSC's MAG240M baseline, ``examples/lsc/mag240m/rgnn.py
--model rgat``, in its homogenised form.  Per layer, with targets ``x_t =
x[:T]`` and sources ``x``::

    out = skip(x_t) + sum over relations r with an edge in this layer of
          GAT_r((x, x_t), edges of r)
    x   = dropout(ELU(BatchNorm(out)))

``GAT_r`` is PyG's bipartite ``GATConv((in, in), hidden / heads, heads,
add_self_loops=False)``: ``s = W_src x``, ``d = W_dst x_t``, ``e_ij =
leaky_relu((s_j . att_src) + (d_i . att_dst), 0.2)``, ``alpha = softmax_j``
per head over i's edges of r, ``out_i = concat_heads(sum_j alpha_ij s_j) +
bias_r``; a target with no edge of r gets ``bias_r`` alone, a relation with
no edge in the whole layer adds nothing.  Then ``Linear -> BatchNorm ->
ReLU -> Dropout -> Linear`` and softmax cross-entropy on the seeds.
BatchNorm is PyTorch's ``BatchNorm1d``: batch mean and biased variance when
training, running averages with momentum 0.1 (the variance unbiased).

It imports nothing of ``quiver_tpu``.  Sources are gathered through
``nbr_local`` edge by edge and projected under EVERY relation (five
products where the program makes one), so it checks the program's slice
and its grouping alike.  Departures from ``rgnn.py``, all of form:

  * the relation of an edge is looked up from its endpoints' id ranges
    (``rgnn.py`` stores it per edge; MAG240M's schema has one relation per
    pair of types, so the values are the same);
  * a source is projected per EDGE (``x[nbr_local]`` first), not per node:
    the same rows, and a layer computed in blocks of targets
    (``jax.checkpoint``, ``lax.map``) fits the chip at the cell's size;
  * BatchNorm's statistics are over the frontier's valid targets: PyG's
    sampler has no padded rows;
  * dropout masks are those ``flax.linen.Dropout`` draws from the step's
    key (``references/sage.dropout_masks``).

What any training cell's reference needs and ``references/sage.py`` already
has is taken from there, not written again: the stated matrix product
(``_matmul``: ``bf16_operands``, ``highest``, and the control ``bf16_all``:
the products' results bfloat16 too, and here what a layer hands on), Adam,
the dropout masks, the sampler's guarantee and the comparison of leaf norms.
"""

import importlib

import numpy as np

import datagen

sage = importlib.import_module("references.sage")
leaf_norm_gap = sage.leaf_norm_gap


def tree_distance(prog, ref):
    """``||prog - ref|| / ||ref||`` over all leaves as ONE vector.  Where
    ``leaf_norm_gap`` compares norms (blind to an error that keeps a
    leaf's length, and second order in a random one), this is first order
    in any error; taken over the whole tree, the large leaves (the
    kernels) carry it, so the rounding noise that a sum of cancelling
    terms makes of a small leaf (a BatchNorm scale, an attention vector)
    does not."""
    import jax

    p, r = (np.concatenate([np.ravel(a) for a in
                            jax.tree_util.tree_leaves(t)]) for t in (prog, ref))
    return float(np.linalg.norm(p - r) / np.linalg.norm(r))


# MAG240M's schema: node types by id range in this order, and the relation
# of an edge source -> target; -1 where the schema has none
TYPES = ("papers", "authors", "institutions")
RELATION_OF = ((0, 2, -1),      # paper  -> paper: cites; -> author: rev. writes
               (1, -1, 3),      # author -> paper: writes; -> institution
               (-1, 4, -1))     # institution -> author: rev. affiliated_with
BN_MOMENTUM, BN_EPS, SLOPE = 0.1, 1e-5, 0.2
BLOCK = 2048        # targets of a layer computed at a time


# --------------------------------------------------- the data, from the seed
def type_offsets(cfg):
    """``(0, papers, papers + authors, nodes)``: a node's type is its id's
    range."""
    return tuple(int(v) for v in np.cumsum([0] + [cfg[t] for t in TYPES]))


def _degrees(rng, rows, edges):
    """Lognormal degrees over ``rows`` rows that sum to exactly ``edges``
    (``datagen.csr``'s rule, but a row may have none)."""
    raw = rng.lognormal(mean=3.0, sigma=1.0, size=rows)
    deg = (raw / raw.sum() * edges).astype(np.int64)
    diff = int(edges - deg.sum())
    deg += np.bincount(rng.integers(0, rows, diff), minlength=rows)
    return deg


def typed_csr(cfg, seed):
    """One CSR over the homogenised id space.  A node's row holds its
    sources relation by relation; each relation has exactly the
    configuration's count of edges for every seed (so the tables' shapes
    never change), lognormal degrees over its target type and uniform
    sources in its source type's range.  Both directions of an undirected
    relation are drawn, each on its own (mutual pairs are not merged)."""
    off = type_offsets(cfg)
    nodes = off[-1]
    # (target type, source type, directed edges): the row order of a type
    segments = [(0, 0, 2 * cfg["edges_cites"]), (0, 1, cfg["edges_writes"]),
                (1, 0, cfg["edges_writes"]),
                (1, 2, cfg["edges_affiliated_with"]),
                (2, 1, cfg["edges_affiliated_with"])]
    rng = np.random.default_rng(seed)
    degs = []
    for dst, _, edges in segments:
        full = np.zeros(nodes, np.int64)
        full[off[dst]:off[dst + 1]] = _degrees(rng, cfg[TYPES[dst]], edges)
        degs.append(full)
    indptr = np.zeros(nodes + 1, np.int64)
    np.cumsum(sum(degs), out=indptr[1:])
    total = int(indptr[-1])
    # a slot's source range: the segment its offset within the row falls in
    row = np.repeat(np.arange(nodes, dtype=np.int32), np.diff(indptr))
    within = np.arange(total, dtype=np.int64) - indptr[:-1][row]
    lo = np.zeros(total, np.int32)
    span = np.zeros(total, np.int32)
    before = np.zeros(nodes, np.int64)
    for (dst, src, _), deg in zip(segments, degs):
        here = (within >= before[row]) & (within < (before + deg)[row])
        lo[here] = off[src]
        span[here] = cfg[TYPES[src]]
        before += deg
    del row, within
    u = datagen._chunked(total, seed, 0,
                         lambda r, n: r.random(n, dtype=np.float32),
                         np.empty(total, np.float32))
    indices = lo + np.minimum((u * span).astype(np.int32), span - 1)
    return indptr, indices


def float16_rows(nodes, dim, seed):
    """``[nodes, dim]`` float16, drawn as ``datagen.features`` draws
    bfloat16: a random sign and a random 10-bit mantissa under the exponent
    of 0.5, so uniform on +-[0.5, 1)."""
    def draw(r, n):
        b = r.integers(0, 1 << 16, size=(n, dim), dtype=np.uint16)
        b &= 0x83FF
        b |= 0x3800
        return b

    bits = datagen._chunked(nodes, seed, 1, draw,
                            np.empty((nodes, dim), dtype=np.uint16))
    return bits.view(np.float16)


def rgat_params(cfg, seed):
    """``(params, model_state)`` in the trees ``flax`` reads them from:
    kernels normal / sqrt(fan_in), every bias and BatchNorm shift small and
    not zero, BatchNorm scales near 1, running averages 0 / 1."""
    rng = np.random.default_rng(seed + 3)
    r, h = cfg["num_relations"], cfg["heads"]
    hidden, c = cfg["hidden"], cfg["hidden"] // cfg["heads"]

    def normal(*shape, scale=1.0):
        return (scale * rng.standard_normal(shape)).astype(np.float32)

    def dense(d_in, d_out):
        return {"kernel": normal(d_in, d_out, scale=d_in ** -0.5),
                "bias": normal(d_out, scale=0.01)}

    def norm():
        return {"scale": 1.0 + normal(hidden, scale=0.01),
                "bias": normal(hidden, scale=0.01)}

    params, stats = {}, {}
    d_in = cfg["feature_dim"]
    for i in range(cfg["num_layers"]):
        params[f"conv{i}"] = {
            "w_src": normal(r, d_in, hidden, scale=d_in ** -0.5),
            "w_dst": normal(r, d_in, hidden, scale=d_in ** -0.5),
            "att_src": normal(r, h, c, scale=c ** -0.5),
            "att_dst": normal(r, h, c, scale=c ** -0.5),
            "bias": normal(r, hidden, scale=0.01)}
        params[f"skip{i}"] = dense(d_in, hidden)
        params[f"norm{i}"] = norm()
        d_in = hidden
    params["mlp_lin0"] = dense(hidden, hidden)
    params["mlp_norm"] = norm()
    params["mlp_lin1"] = dense(hidden, cfg["classes"])
    for name in [f"norm{i}" for i in range(cfg["num_layers"])] + ["mlp_norm"]:
        stats[name] = {"mean": np.zeros(hidden, np.float32),
                       "var": np.ones(hidden, np.float32)}
    return {"params": params}, {"batch_stats": stats}


def make_data(cfg, seed):
    """Graph, table, labels and weights, all from the seed.  Runs on the
    host while JAX reaches the chip: nothing at this file's top level
    imports jax."""
    nodes = type_offsets(cfg)[-1]
    indptr, indices = typed_csr(cfg, seed)
    params, model_state = rgat_params(cfg, seed)
    return {"indptr": indptr, "indices": indices,
            "features": float16_rows(nodes, cfg["feature_dim"], seed),
            "labels": datagen.labels(nodes, cfg["classes"], seed),
            "params": params, "model_state": model_state}


# ------------------------------------------------------------ the sampler
def node_types(ids, offsets):
    """0, 1, 2...: which id range each id falls in (numpy or jax)."""
    return sum((ids >= o).astype(np.int32) for o in offsets[1:-1])


def check_sample(indptr, indices, fanout, seeds, n_id, n_mask, layers,
                 offsets):
    """``references/sage.check_sample`` and the typed guarantee: every
    drawn edge joins two types that the schema relates."""
    bad, edges = sage.check_sample(indptr, indices, fanout, seeds, n_id,
                                   n_mask, layers)
    ntype = node_types(n_id, offsets)
    table = np.asarray(RELATION_OF)
    bad["bad_relations"] = 0
    for nbr_local, mask in layers:
        tgt, col = np.nonzero(mask)
        rel = table[ntype[nbr_local[tgt, col]], ntype[tgt]]
        bad["bad_relations"] += int((rel < 0).sum())
    return bad, edges


# -------------------------------------------------------------- the model
def _blocks(t):
    """The fewest equal blocks of at most ``BLOCK`` targets."""
    return next(n for n in range(1, t + 1)
                if t % n == 0 and t // n <= BLOCK)


def rel_gat(p, x, nbr_local, valid, rel, heads, mm):
    """The sum over relations of bipartite GAT convolutions, targets
    ``x[:T]``: every edge's source row under EVERY relation, a relation's
    softmax over its own edges, its bias where the layer has an edge of
    it.  Computed ``BLOCK`` targets at a time."""
    import jax
    import jax.numpy as jnp

    t, k = nbr_local.shape
    r, _, hc = p["w_src"].shape
    c = hc // heads
    has = [(valid & (rel == q)).any().astype(jnp.float32) for q in range(r)]

    @jax.checkpoint
    def block(x_t, nbr, valid, rel):
        b = x_t.shape[0]
        x_src = x[nbr].reshape(b * k, -1)
        out = jnp.zeros((b, hc), jnp.float32)
        for q in range(r):
            m = (valid & (rel == q))[..., None]
            s = mm(x_src, p["w_src"][q]).reshape(b, k, heads, c)
            d = mm(x_t, p["w_dst"][q]).reshape(b, heads, c)
            e = ((s * p["att_src"][q]).sum(-1)
                 + (d * p["att_dst"][q]).sum(-1)[:, None])
            e = jnp.where(m, jnp.where(e > 0, e, SLOPE * e), -jnp.inf)
            top = jax.lax.stop_gradient(e.max(axis=1, keepdims=True))
            w = jnp.where(m, jnp.exp(e - jnp.where(jnp.isfinite(top), top,
                                                    0.0)), 0.0)
            den = w.sum(axis=1, keepdims=True)
            alpha = w / jnp.where(den > 0, den, 1.0)
            agg = (alpha[..., None] * s).sum(axis=1).reshape(b, hc)
            out = out + has[q] * (agg + p["bias"][q])
        return out

    n = _blocks(t)

    def cut(a):
        return a.reshape(n, t // n, *a.shape[1:])

    out = jax.lax.map(lambda a: block(*a),
                      (cut(x[:t]), cut(nbr_local), cut(valid), cut(rel)))
    return out.reshape(t, hc)


def batch_norm(p, stats, x, valid):
    """Training-mode ``BatchNorm1d`` over the valid rows; returns the
    output and the running averages after this batch."""
    import jax
    import jax.numpy as jnp

    m = valid.astype(jnp.float32)[:, None]
    n = jnp.maximum(m.sum(), 1.0)
    mean = (x * m).sum(axis=0) / n
    var = (jnp.square(x - mean) * m).sum(axis=0) / n
    new = {"mean": (1 - BN_MOMENTUM) * stats["mean"] + BN_MOMENTUM * mean,
           "var": ((1 - BN_MOMENTUM) * stats["var"]
                   + BN_MOMENTUM * var * n / jnp.maximum(n - 1.0, 1.0))}
    y = (x - mean) * jax.lax.rsqrt(var + BN_EPS) * p["scale"] + p["bias"]
    return y, new


def rgat_forward(params, model_state, x, layers, n_id, n_mask, offsets,
                 heads, matmul="bf16_operands", drop=None):
    """Logits of the seeds and the BatchNorm running averages after the
    batch (training mode).  ``layers``: ``(nbr_local, mask)`` outermost
    first; ``drop``: one keep-mask per dropout (each layer's, then the
    head's), scaled by 1 / keep."""
    import jax
    import jax.numpy as jnp

    mm = sage._matmul(matmul)
    p, stats = params["params"], model_state["batch_stats"]
    table = jnp.asarray(RELATION_OF)
    new_stats = {}

    def lin(q, a):
        return mm(a, q["kernel"]) + q["bias"]

    def below(a):
        """One precision below the stated one, the control's: what passes
        from a layer to the next is bfloat16 as well as every product
        (``references/sage.sage_forward`` rounds a layer's output alike)."""
        return sage._round(a, jnp.bfloat16) if matmul == "bf16_all" else a

    for i, (nbr_local, mask) in enumerate(layers):
        t = nbr_local.shape[0]
        ntype = node_types(n_id[:x.shape[0]], offsets)
        rel = table[ntype[nbr_local], ntype[:t, None]]
        valid = mask & (rel >= 0)
        out = lin(p[f"skip{i}"], x[:t]) + rel_gat(
            p[f"conv{i}"], x, nbr_local, valid, rel, heads, mm)
        x, new_stats[f"norm{i}"] = batch_norm(
            p[f"norm{i}"], stats[f"norm{i}"], below(out), n_mask[:t])
        x = below(jax.nn.elu(x))
        if drop is not None:
            x = x * drop[i]
    x, new_stats["mlp_norm"] = batch_norm(
        p["mlp_norm"], stats["mlp_norm"], lin(p["mlp_lin0"], x),
        n_mask[:x.shape[0]])
    x = jax.nn.relu(x)
    if drop is not None:
        x = x * drop[len(layers)]
    return lin(p["mlp_lin1"], x), {"batch_stats": new_stats}


def loss_fn(params, model_state, x, layers, n_id, n_mask, labels, offsets,
            heads, matmul, drop, label_mask=None):
    """``(mean softmax cross-entropy over the seeds, new model state)``;
    ``label_mask`` picks the seeds that count (the faults test leaves half
    of them out)."""
    import jax
    import jax.numpy as jnp

    logits, new_state = rgat_forward(params, model_state, x, layers, n_id,
                                     n_mask, offsets, heads, matmul, drop)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, labels[:, None], axis=1)[:, 0]
    if label_mask is None:
        return nll.mean(), new_state
    m = label_mask.astype(nll.dtype)
    return (nll * m).sum() / jnp.maximum(m.sum(), 1.0), new_state


def train_follow(params0, state0, batches, cfg, matmul, fault=None):
    """Follow the first steps of training.  ``batches``: per step a dict
    of ``rows`` [P, D] (from the host table), ``layers``, ``n_id``,
    ``n_mask``, ``labels`` and ``drop_key``.  Returns per-step losses, the
    first gradient, the parameters and the model state after the last step
    - all host numpy.  ``fault``: as ``references/sage.train_follow``."""
    import jax
    import jax.numpy as jnp

    tm = jax.tree_util.tree_map
    params, state = tm(jnp.asarray, params0), tm(jnp.asarray, state0)
    m, v = tm(jnp.zeros_like, params), tm(jnp.zeros_like, params)
    grad = jax.jit(jax.value_and_grad(loss_fn, has_aux=True),
                   static_argnums=(7, 8, 9))
    offsets, hidden = type_offsets(cfg), cfg["hidden"]
    losses, first = [], None
    for i, b in enumerate(batches):
        layers = [(jnp.asarray(n), jnp.asarray(k)) for n, k in b["layers"]]
        drop = None
        if cfg["dropout"] > 0:
            shapes = [(lay[0].shape[0], hidden) for lay in layers]
            shapes.append((layers[-1][0].shape[0], hidden))
            drop = sage.dropout_masks(b["drop_key"], shapes, cfg["dropout"])
        mask = None
        if fault == "half_batch":
            mask = jnp.arange(len(b["labels"])) < len(b["labels"]) // 2
        (loss, new_state), g = grad(
            params, state, jnp.asarray(b["rows"]), layers,
            jnp.asarray(b["n_id"]), jnp.asarray(b["n_mask"]),
            jnp.asarray(b["labels"]), offsets, cfg["heads"], matmul, drop,
            mask)
        losses.append(float(loss))
        if first is None:
            first = tm(np.asarray, g)
        if fault != "stale_state":
            params, m, v = sage.adam_update(params, g, m, v, i + 1,
                                            cfg["lr"])
            state = new_state
    return losses, first, tm(np.asarray, params), tm(np.asarray, state)
