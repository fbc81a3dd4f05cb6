"""The plain reference ``igbh_rgat`` (a configuration names it under
``"reference"``): the seeded data of a six-type, eleven-relation graph in
the shape of IGBH-full (host CSR over ONE id space, host table, labels,
weights) and the R-GAT of MLPerf Training's GNN benchmark with its loss,
gradients and Adam in straightforward ``jax.numpy``.

The model is MLCommons ``training/graph_neural_network``'s ``RGNN(model=
'rgat')``: per layer PyG's ``HeteroConv`` summing, over the relations into
a node's type, ``GATConv(in, hidden / heads, heads, add_self_loops=False)``
with an int ``in_channels`` (ONE weight ``W_r`` for a relation's sources
and targets)::

    h_j = W_r x_j,  g_i = W_r x_i
    e_ij = leaky_relu(a_src_r . h_j + a_dst_r . g_i, 0.2), per head
    o_i^r = concat_heads(sum_j softmax_j(e_ij) h_j) + b_r
    y_i = sum over relations r into type(i) of o_i^r

a target with no source of r gets ``b_r`` alone; leaky ReLU (0.01) and
dropout between layers, ``Linear(hidden, classes)`` on the seeds after the
last, softmax cross-entropy.  ``first_logits`` is the forward alone, at
the weights from the seed and with no dropout: the number a lower
precision moves element by element.

It imports nothing of ``quiver_tpu``.  The typed tree (which frontier
positions are which type, which relation's block sits where) is worked out
here from the schema, the batch and the fanout alone; a relation's sources
are gathered through ``nbr_local`` and its output added into the layer's
targets by position (``y.at[positions].add``), where the program slices
and concatenates.  Departures from the published model, all of form:

  * the tree is a tree: a node drawn twice is two positions, each with its
    own neighbourhood (the sampler's ``dedup="none"``), where PyG's
    sampler merges them into one node of a subgraph whose every node is
    updated in every layer; the convolutions are PyG's over that tree;
  * dropout masks are those ``flax.linen.Dropout`` draws from the step's
    key (``references/sage.dropout_masks``).

What any training reference needs and ``references/sage.py`` has (the
stated matrix product ``_matmul``, Adam, the dropout masks, the neighbour
test, the comparison of leaf norms), and what ``references/rgat.py`` has
for a typed graph (degrees that may be 0, float16 rows, the gradient as
one vector), is taken from there, not written again; but for one
precision below the stated one (``bf16_all``) a result is rounded by
``lax.reduce_precision`` (``_round``), not by ``sage._round``'s float32 ->
bfloat16 -> float32 round trip, which XLA may drop where it allows excess
precision and on the TPU does (PERF.md, PR 41).
"""

import functools

import numpy as np

import datagen
from run import load_named

sage = load_named("references", "sage")
rgat = load_named("references", "rgat")
leaf_norm_gap = sage.leaf_norm_gap
tree_distance = rgat.tree_distance

# IGBH's schema: node types by id range in this order, and the relations
# (name, source type, target type); a CSR row holds its sources relation
# by relation in the order of their source types
TYPES = ("papers", "authors", "institutes", "fields", "journals",
         "conferences")
RELATIONS = (("cites", 0, 0), ("rev_written_by", 1, 0), ("rev_topic", 3, 0),
             ("rev_published", 4, 0), ("rev_venue", 5, 0),
             ("written_by", 0, 1), ("rev_affiliated_to", 2, 1),
             ("affiliated_to", 1, 2), ("topic", 0, 3), ("published", 0, 4),
             ("venue", 0, 5))
# the configuration's edge count of each relation (both directions of a
# pair share one; cites holds both directions in one relation) and whether
# a target has at most one source of it
EDGES = ("edges_cites", "edges_written_by", "edges_topic", "edges_published",
         "edges_venue", "edges_written_by", "edges_affiliated_to",
         "edges_affiliated_to", "edges_topic", "edges_published",
         "edges_venue")
AT_MOST_ONE = {"rev_published", "rev_venue"}
SLOPE, LAYER_SLOPE = 0.2, 0.01


# --------------------------------------------------- the data, from the seed
def type_offsets(cfg):
    """``(0, papers, papers + authors, ..., nodes)``: a node's type is its
    id's range."""
    return tuple(int(v) for v in np.cumsum([0] + [cfg[t] for t in TYPES]))


def relation_edges(cfg, r):
    """Directed edges of relation ``r`` (cites: both directions)."""
    name = RELATIONS[r][0]
    return cfg[EDGES[r]] * (2 if name == "cites" else 1)


def typed_csr(cfg, seed):
    """One CSR over the id space.  Each relation has exactly its count of
    edges for every seed (the tables' shapes never change): degrees over
    its target type lognormal (``references/rgat._degrees``: a node may
    have none) or, for a paper's journal and conference, 0 or 1; sources
    uniform in its source type's range.  The two directions of a pair are
    drawn each on its own.  A row holds its relations in the order of their
    source types, which is ``RELATIONS``' order among those into its
    type."""
    off = type_offsets(cfg)
    nodes = off[-1]
    rng = np.random.default_rng(seed)
    deg = []
    for r, (name, s, t) in enumerate(RELATIONS):
        n_t, e = off[t + 1] - off[t], relation_edges(cfg, r)
        if name in AT_MOST_ONE:
            d = np.zeros(n_t, np.int64)
            d[rng.choice(n_t, e, replace=False)] = 1
        else:
            d = rgat._degrees(rng, n_t, e)
        deg.append(d)
    full = np.zeros(nodes, np.int64)
    for (_, _, t), d in zip(RELATIONS, deg):
        full[off[t]:off[t + 1]] += d
    indptr = np.zeros(nodes + 1, np.int64)
    np.cumsum(full, out=indptr[1:])
    indices = np.empty(int(indptr[-1]), np.int32)
    before = np.zeros(nodes, np.int64)      # entries of a row placed so far
    for r, ((_, s, t), d) in enumerate(zip(RELATIONS, deg)):
        rows = np.arange(off[t], off[t + 1])
        first = indptr[rows] + before[rows]
        e = int(d.sum())
        starts = np.repeat(first - np.concatenate([[0], np.cumsum(d)[:-1]]),
                           d)
        pos = starts + np.arange(e)
        n_s = off[s + 1] - off[s]
        u = datagen._chunked(e, seed, 10 + r,
                             lambda g, n: g.random(n, dtype=np.float32),
                             np.empty(e, np.float32))
        indices[pos] = off[s] + np.minimum((u * n_s).astype(np.int32),
                                           n_s - 1)
        before[rows] += d
    return indptr, indices


def igbh_params(cfg, seed):
    """The weights in the tree ``flax`` reads them from: per layer ``lin``
    ``[R, d_in, hidden]`` normal / sqrt(d_in), ``att_src`` / ``att_dst``
    ``[R, heads, hidden / heads]`` normal / sqrt(head size), ``bias``
    ``[R, hidden]`` small and not zero; the head's kernel and bias."""
    rng = np.random.default_rng(seed + 3)
    r, h = len(RELATIONS), cfg["heads"]
    hidden, c = cfg["hidden"], cfg["hidden"] // cfg["heads"]

    def normal(*shape, scale=1.0):
        return (scale * rng.standard_normal(shape)).astype(np.float32)

    params, d_in = {}, cfg["feature_dim"]
    for i in range(cfg["num_layers"]):
        params[f"conv{i}"] = {
            "lin": normal(r, d_in, hidden, scale=d_in ** -0.5),
            "att_src": normal(r, h, c, scale=c ** -0.5),
            "att_dst": normal(r, h, c, scale=c ** -0.5),
            "bias": normal(r, hidden, scale=0.01)}
        d_in = hidden
    params["head"] = {"kernel": normal(hidden, cfg["classes"],
                                       scale=hidden ** -0.5),
                      "bias": normal(cfg["classes"], scale=0.01)}
    return {"params": params}


def make_data(cfg, seed):
    """Graph, table, labels and weights, all from the seed.  Runs on the
    host while JAX reaches the chip: nothing at this file's top level
    imports jax."""
    nodes = type_offsets(cfg)[-1]
    indptr, indices = typed_csr(cfg, seed)
    return {"indptr": indptr, "indices": indices,
            "features": rgat.float16_rows(nodes, cfg["feature_dim"], seed),
            "labels": datagen.labels(nodes, cfg["classes"], seed),
            "params": igbh_params(cfg, seed)}


# ------------------------------------------------------------ the sampler
@functools.lru_cache(maxsize=None)
def tree(batch, fanout):
    """The typed tree of ``batch`` paper seeds: per hop, hop 1 first, a
    ``(relation, target positions, first source position)`` for each
    relation in order whose target type the frontier holds; the relation's
    ``[n, k]`` sources are appended to the frontier in that order.  Also
    the type of every position of the last frontier."""
    ptype = np.zeros(batch, np.int32)           # the seeds are papers
    hops = []
    for k in fanout:
        runs, grown = [], []
        size = len(ptype)
        for r, (_, s, t) in enumerate(RELATIONS):
            pos = np.flatnonzero(ptype == t)
            if len(pos):
                runs.append((r, pos, size))
                grown.append(np.full(len(pos) * k, s, np.int32))
                size += len(pos) * k
        hops.append(runs)
        ptype = np.concatenate([ptype] + grown)
    return hops, ptype


def node_types(ids, offsets):
    return np.searchsorted(np.asarray(offsets[1:-1]), ids, side="right")


def relation_degrees(indptr, indices, offsets):
    """``[types, N]``: how many entries of each row have a source of each
    type."""
    etype = node_types(indices, offsets)
    out = []
    for s in range(len(offsets) - 1):
        c = np.zeros(len(etype) + 1, np.int64)
        np.cumsum(etype == s, out=c[1:])
        out.append(c[indptr[1:]] - c[indptr[:-1]])
    return np.stack(out)


def check_sample(indptr, indices, fanout, seeds, n_id, n_mask, layers, cfg,
                 degrees=None):
    """Hold one sampled batch to the guarantee of the typed draw.
    ``layers``: per hop, outermost first, the ``(nbr_local, mask)`` of each
    relation block in relation order.  Every live draw is a neighbour in
    the host CSR of its target, of the relation's source type; a target
    draws ``min(its degree under the relation, fanout)`` (0 where it is no
    node), masks are prefixes; target ``b``'s draws sit at the positions
    the tree gives; the frontier starts with the seeds.  Returns counts of
    breaches, all of which have to be 0, and the edges looked at."""
    offsets = type_offsets(cfg)
    if degrees is None:
        degrees = relation_degrees(indptr, indices, offsets)
    hops, _ = tree(len(seeds), tuple(fanout))
    bad = {"bad_shape": 0, "bad_masks": 0, "bad_neighbours": 0,
           "bad_relations": 0, "bad_positions": 0}
    if len(layers) != len(fanout) or not np.array_equal(
            n_id[:len(seeds)], seeds):
        bad["bad_shape"] += 1
    edges = 0
    for k, runs, blocks in zip(fanout, hops, layers[::-1]):
        if len(blocks) != len(runs):
            bad["bad_shape"] += 1
            continue
        for (r, pos, first), (nbr_local, mask) in zip(runs, blocks):
            n = len(pos)
            if mask.shape != (n, k):
                bad["bad_shape"] += 1
                continue
            s = RELATIONS[r][1]
            tgt = n_id[pos]
            want = np.where(n_mask[pos], np.minimum(degrees[s][tgt], k), 0)
            bad["bad_masks"] += int(
                (mask != (np.arange(k)[None, :] < want[:, None])).sum())
            slot = first + np.arange(n * k).reshape(n, k)
            bad["bad_masks"] += int((n_mask[slot] != mask).sum())
            b, j = np.nonzero(mask)
            bad["bad_positions"] += int((nbr_local[b, j] != slot[b, j]).sum())
            src = n_id[nbr_local[b, j]]
            bad["bad_relations"] += int(
                (node_types(src, offsets) != s).sum())
            bad["bad_neighbours"] += sage.neighbours_valid(
                indptr, indices, tgt[b], src)
            edges += len(b)
    return bad, edges


# -------------------------------------------------------------- the model
def _round(x):
    """``x`` rounded to bfloat16 and kept float32, by an operation XLA
    keeps (a convert pair it may fold away)."""
    import jax

    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


@functools.lru_cache(maxsize=None)
def _matmul(mode):
    """``references/sage._matmul`` (``x @ w`` with its backward pass at the
    stated precision), but at ``bf16_all`` each of the three products'
    results is rounded by :func:`_round`."""
    if mode != "bf16_all":
        return sage._matmul(mode)
    import jax
    import jax.numpy as jnp

    def dot(a, b):
        return _round(jnp.dot(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                              precision=jax.lax.Precision.HIGHEST,
                              preferred_element_type=jnp.float32))

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


def relation_conv(p, r, x_t, x, nbr_local, mask, heads, mm):
    """``o^r`` of a relation's targets: its sources gathered through
    ``nbr_local``, both sides projected by ``lin[r]``, a softmax per head
    over the live draws, ``bias[r]`` added to every target."""
    import jax
    import jax.numpy as jnp

    n, k = nbr_local.shape
    w = p["lin"][r]
    hc = w.shape[1]
    c = hc // heads
    s = mm(x[nbr_local].reshape(n * k, -1), w).reshape(n, k, heads, c)
    d = mm(x_t, w).reshape(n, heads, c)
    e = ((s * p["att_src"][r]).sum(-1)
         + (d * p["att_dst"][r]).sum(-1)[:, None])              # [n, k, H]
    m = mask[..., None]
    e = jnp.where(m, jnp.where(e > 0, e, SLOPE * e), -jnp.inf)
    top = jax.lax.stop_gradient(e.max(axis=1, keepdims=True))
    w_ = jnp.where(m, jnp.exp(e - jnp.where(jnp.isfinite(top), top, 0.0)),
                   0.0)
    den = w_.sum(axis=1, keepdims=True)
    alpha = w_ / jnp.where(den > 0, den, 1.0)
    return (alpha[..., None] * s).sum(axis=1).reshape(n, hc) + p["bias"][r]


def igbh_forward(params, x, layers, batch, fanout, heads,
                 matmul="bf16_operands", drop=None):
    """Logits of the seeds.  ``layers``: per hop, outermost first, the
    ``(nbr_local, mask)`` of each relation block; ``drop``: one keep-mask
    per dropout, scaled by 1 / keep.  A relation's block is computed under
    ``jax.checkpoint`` so that the backward pass fits the chip."""
    import jax
    import jax.numpy as jnp

    mm = _matmul(matmul)
    p = params["params"]
    hops, _ = tree(batch, tuple(fanout))
    n_layers = len(layers)
    for i, blocks in enumerate(layers):
        runs = hops[n_layers - 1 - i]
        t = x.shape[0] - sum(len(pos) * nbr.shape[1]
                             for (_, pos, _), (nbr, _) in zip(runs, blocks))
        y = jnp.zeros((t, p[f"conv{i}"]["lin"].shape[-1]), jnp.float32)
        for (r, pos, _), (nbr_local, mask) in zip(runs, blocks):
            conv = jax.checkpoint(functools.partial(
                relation_conv, r=r, heads=heads, mm=mm))
            y = y.at[pos].add(conv(p[f"conv{i}"], x_t=x[pos], x=x,
                                   nbr_local=nbr_local, mask=mask))
        if matmul == "bf16_all":    # one precision below: what a layer
            y = _round(y)                       # hands on is bfloat16 too
        x = y
        if i != n_layers - 1:
            x = jnp.where(x > 0, x, LAYER_SLOPE * x)
            if drop is not None:
                x = x * drop[i]
    return mm(x, p["head"]["kernel"]) + p["head"]["bias"]


def loss_fn(params, x, layers, labels, batch, fanout, heads, matmul, drop,
            label_mask=None):
    """Mean softmax cross-entropy over the seeds (``label_mask``: the
    seeds that count; the faults leave half of them out)."""
    import jax
    import jax.numpy as jnp

    logits = igbh_forward(params, x, layers, batch, fanout, heads, matmul,
                          drop)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, labels[:, None], axis=1)[:, 0]
    if label_mask is None:
        return nll.mean()
    m = label_mask.astype(nll.dtype)
    return (nll * m).sum() / jnp.maximum(m.sum(), 1.0)


@functools.lru_cache(maxsize=None)
def _grad():
    """``loss_fn``'s value and gradient, jitted once for the process (the
    controls and faults of one seed reuse what the first call compiled)."""
    import jax

    return jax.jit(jax.value_and_grad(loss_fn), static_argnums=(4, 5, 6, 7))


@functools.lru_cache(maxsize=None)
def _forward():
    import jax

    return jax.jit(igbh_forward, static_argnums=(3, 4, 5, 6))


def first_logits(params, rows, layers, cfg, matmul):
    """Logits of the seeds at ``params``, with no dropout, every product at
    ``matmul``, over one batch's ``rows`` and ``layers``: host numpy."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        layers = [[(jnp.asarray(n), jnp.asarray(k)) for n, k in hop]
                  for hop in layers]
        return np.asarray(_forward()(
            jax.tree_util.tree_map(jnp.asarray, params), jnp.asarray(rows),
            layers, cfg["batch"], tuple(cfg["fanout"]), cfg["heads"],
            matmul))


def train_follow(params0, batches, cfg, matmul, fault=None):
    """Follow the first steps of training, every product at the stated
    precision and everything else float32 proper.  ``batches``: per step a
    dict of ``rows`` [P, D] (from the host table), ``layers``, ``labels``
    and ``drop_key``.  Returns per-step losses, the first gradient and the
    parameters after the last step - all host numpy.  ``fault``: as
    ``references/sage.train_follow``."""
    import jax
    import jax.numpy as jnp

    tm = jax.tree_util.tree_map
    batch, fanout = cfg["batch"], tuple(cfg["fanout"])
    hops, _ = tree(batch, fanout)
    with jax.default_matmul_precision("highest"):
        params = tm(jnp.asarray, params0)
        m, v = tm(jnp.zeros_like, params), tm(jnp.zeros_like, params)
        grad = _grad()
        losses, first = [], None
        for i, b in enumerate(batches):
            layers = [[(jnp.asarray(n), jnp.asarray(k)) for n, k in hop]
                      for hop in b["layers"]]
            x = jnp.asarray(b["rows"])
            drop = None
            if cfg["dropout"] > 0:
                # each layer's output but the last's: [its targets, hidden]
                shapes, t = [], x.shape[0]
                for k, runs in zip(fanout[::-1][:-1], hops[::-1]):
                    t -= k * sum(len(pos) for _, pos, _ in runs)
                    shapes.append((t, cfg["hidden"]))
                drop = sage.dropout_masks(b["drop_key"], shapes,
                                          cfg["dropout"])
            mask = None
            if fault == "half_batch":
                mask = jnp.arange(len(b["labels"])) < len(b["labels"]) // 2
            loss, g = grad(params, x, layers, jnp.asarray(b["labels"]),
                           batch, fanout, cfg["heads"], matmul, drop, mask)
            losses.append(float(loss))
            if first is None:
                first = tm(np.asarray, g)
            if fault != "stale_state":
                params, m, v = sage.adam_update(params, g, m, v, i + 1,
                                                cfg["lr"])
    return losses, first, tm(np.asarray, params)
