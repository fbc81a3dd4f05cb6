"""The plain reference ``gat`` (a configuration names it under
``"reference"``): the seeded data of a citation-graph deployment (host CSR,
host table, labels, weights) and the published GAT with its loss,
gradients, Adam and BatchNorm state in straightforward ``jax.numpy``.

The model is OGB-LSC's MAG240M baseline, ``examples/lsc/mag240m/gnn.py
--model gat``: ``GNN(model='gat', in, out, hidden, num_layers, heads,
dropout)`` over the paper-cites-paper graph made symmetric.  For layer
``i`` with sources ``x`` (the targets are its first ``T`` rows), ``H``
heads of ``C = hidden / H`` lanes::

    h     = x W_i                        one matrix for sources and targets
                                         (PyG's shared ``lin``), no bias
    a_s   = <h, att_src>, a_t = <h[:T], att_tgt>             per head
    e_ts  = leaky_relu(a_s[s] + a_t[t], 0.2)   s over t's sampled neighbours
                                               AND t itself (the self-loop)
    alpha = softmax_s(e_ts)              a masked slot is out
    out_t = concat_heads(sum_s alpha_ts h_s) + bias_i
    x'    = dropout(ELU(BatchNorm(out + x[:T] S_i + c_i)))   ``skip``

then ``Linear -> BatchNorm -> ReLU -> Dropout -> Linear`` and softmax
cross-entropy on the seeds.  BatchNorm is PyTorch's ``BatchNorm1d``
(``references/rgat.batch_norm``).

It imports nothing of ``quiver_tpu``.  A source's projection is gathered
through ``nbr_local`` edge by edge and the self-loop's terms are computed
beside the neighbours', so it checks the program's positional slice, its
slot layout and its one softmax over both alike.  Departures from
``gnn.py``, all of form:

  * a layer's attention is computed in blocks of targets
    (``jax.checkpoint``, ``lax.map``): the same sums, and the gathered
    ``[T, k, hidden]`` rows fit the chip at the cell's size;
  * BatchNorm's statistics are over the frontier's valid targets: PyG's
    sampler has no padded rows;
  * dropout masks are those ``flax.linen.Dropout`` draws from the step's
    key (``references/sage.dropout_masks``);
  * the learning rate is constant: ``gnn.py``'s ``StepLR(25, 0.25)`` steps
    once in 25 EPOCHS, never inside a window here.

What any training cell's reference needs is taken from ``references/
sage.py`` (the stated matrix product ``_matmul``: ``bf16_operands``,
``highest``, and the control ``bf16_all``; Adam; the dropout masks; the
sampler's guarantee; the comparison of leaf norms) and what a stateful one
needs from ``references/rgat.py`` (BatchNorm, the float16 rows, the
degrees, the gradient's distance as a vector), not written again.  The
one node type is presented as ``kinds/train_typed.py`` reads types:
``type_offsets(cfg) == (0, papers)``.
"""

import importlib

import numpy as np

import datagen

sage = importlib.import_module("references.sage")
rgat = importlib.import_module("references.rgat")
leaf_norm_gap = sage.leaf_norm_gap
tree_distance = rgat.tree_distance

SLOPE = 0.2
BLOCK = 2048        # targets of a layer computed at a time


# --------------------------------------------------- the data, from the seed
def type_offsets(cfg):
    """``(0, papers)``: one node type, the labelled one."""
    return (0, int(cfg["papers"]))


def citation_csr(cfg, seed):
    """The citation graph made symmetric: ``2 x edges_cites`` directed
    edges for every seed (so the tables' shapes never change), both
    directions drawn on their own (mutual pairs are not merged), lognormal
    degrees (a paper may have none), sources uniform over the OTHER
    papers: no self-edge, so the convolution's self-loop is the only
    diagonal entry, as after PyG's ``remove_self_loops`` +
    ``add_self_loops``."""
    nodes, edges = cfg["papers"], 2 * cfg["edges_cites"]
    deg = rgat._degrees(np.random.default_rng(seed), nodes, edges)
    indptr = np.zeros(nodes + 1, np.int64)
    np.cumsum(deg, out=indptr[1:])
    u = datagen._chunked(edges, seed, 0,
                         lambda r, n: r.random(n, dtype=np.float32),
                         np.empty(edges, np.float32))
    indices = np.minimum((u * (nodes - 1)).astype(np.int32), nodes - 2)
    indices += indices >= np.repeat(np.arange(nodes, dtype=np.int32), deg)
    return indptr, indices


def gat_params(cfg, seed):
    """``(params, model_state)`` in the trees ``flax`` reads them from:
    kernels normal / sqrt(fan_in), every bias and BatchNorm shift small and
    not zero, BatchNorm scales near 1, running averages 0 / 1."""
    rng = np.random.default_rng(seed + 3)
    h, hidden = cfg["heads"], cfg["hidden"]
    c = hidden // h

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
            "lin": {"kernel": normal(d_in, hidden, scale=d_in ** -0.5)},
            "att_src": normal(h, c, scale=c ** -0.5),
            "att_tgt": normal(h, c, scale=c ** -0.5),
            "bias": normal(hidden, scale=0.01)}
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
    nodes = cfg["papers"]
    indptr, indices = citation_csr(cfg, seed)
    params, model_state = gat_params(cfg, seed)
    return {"indptr": indptr, "indices": indices,
            "features": rgat.float16_rows(nodes, cfg["feature_dim"], seed),
            "labels": datagen.labels(nodes, cfg["classes"], seed),
            "params": params, "model_state": model_state}


# ------------------------------------------------------------ the sampler
def check_sample(indptr, indices, fanout, seeds, n_id, n_mask, layers,
                 offsets=None):
    """``references/sage.check_sample`` and this graph's own guarantee: no
    drawn neighbour is its target (the CSR holds no self-edge; the
    self-loop is the convolution's)."""
    bad, edges = sage.check_sample(indptr, indices, fanout, seeds, n_id,
                                   n_mask, layers)
    bad["bad_self_edges"] = 0
    for nbr_local, mask in layers:
        tgt, col = np.nonzero(mask)
        bad["bad_self_edges"] += int(
            (n_id[nbr_local[tgt, col]] == n_id[tgt]).sum())
    return bad, edges


# -------------------------------------------------------------- the model
def gat_conv(p, x, nbr_local, mask, heads, mm):
    """PyG's ``GATConv`` with self-loops over one dense block, targets
    ``x[:T]``: every node projected once, a target's softmax over its live
    neighbours (gathered through ``nbr_local``) and itself.  Computed
    ``BLOCK`` targets at a time."""
    import jax
    import jax.numpy as jnp

    t, k = nbr_local.shape
    h = mm(x, p["lin"]["kernel"])
    hc = h.shape[-1]
    c = hc // heads

    def leaky(e):
        return jnp.where(e > 0, e, SLOPE * e)

    @jax.checkpoint
    def block(h_t, nbr, live):
        b = h_t.shape[0]
        h_t = h_t.reshape(b, heads, c)
        h_s = h[nbr].reshape(b, k, heads, c)
        a_t = (h_t * p["att_tgt"]).sum(-1)                      # [b, H]
        e = leaky((h_s * p["att_src"]).sum(-1) + a_t[:, None])  # [b, k, H]
        e_self = leaky((h_t * p["att_src"]).sum(-1) + a_t)      # [b, H]
        live = live[..., None]
        e = jnp.where(live, e, -jnp.inf)
        top = jax.lax.stop_gradient(jnp.maximum(e.max(axis=1), e_self))
        w = jnp.where(live, jnp.exp(e - top[:, None]), 0.0)
        w_self = jnp.exp(e_self - top)
        den = w.sum(axis=1) + w_self
        out = ((w[..., None] * h_s).sum(axis=1)
               + w_self[..., None] * h_t) / den[..., None]
        return out.reshape(b, hc)

    n = rgat._blocks(t)

    def cut(a):
        return a.reshape(n, t // n, *a.shape[1:])

    out = jax.lax.map(lambda a: block(*a),
                      (cut(h[:t]), cut(nbr_local), cut(mask)))
    return out.reshape(t, hc) + p["bias"]


def gat_forward(params, model_state, x, layers, n_mask, heads,
                matmul="bf16_operands", drop=None):
    """Logits of the seeds and the BatchNorm running averages after the
    batch (training mode).  ``layers``: ``(nbr_local, mask)`` outermost
    first; ``drop``: one keep-mask per dropout (each layer's, then the
    head's), scaled by 1 / keep."""
    import jax
    import jax.numpy as jnp

    mm = sage._matmul(matmul)
    p, stats = params["params"], model_state["batch_stats"]
    new_stats = {}

    def lin(q, a):
        return mm(a, q["kernel"]) + q["bias"]

    def below(a):
        """One precision below the stated one, the control's: what passes
        from a layer to the next is bfloat16 as well as every product
        (``references/rgat.rgat_forward`` alike)."""
        return sage._round(a, jnp.bfloat16) if matmul == "bf16_all" else a

    for i, (nbr_local, mask) in enumerate(layers):
        t = nbr_local.shape[0]
        out = lin(p[f"skip{i}"], x[:t]) + gat_conv(
            p[f"conv{i}"], x, nbr_local, mask, heads, mm)
        x, new_stats[f"norm{i}"] = rgat.batch_norm(
            p[f"norm{i}"], stats[f"norm{i}"], below(out), n_mask[:t])
        x = below(jax.nn.elu(x))
        if drop is not None:
            x = x * drop[i]
    x, new_stats["mlp_norm"] = rgat.batch_norm(
        p["mlp_norm"], stats["mlp_norm"], lin(p["mlp_lin0"], x),
        n_mask[:x.shape[0]])
    x = jax.nn.relu(x)
    if drop is not None:
        x = x * drop[len(layers)]
    return lin(p["mlp_lin1"], x), {"batch_stats": new_stats}


def loss_fn(params, model_state, x, layers, n_mask, labels, heads, matmul,
            drop, label_mask=None):
    """``(mean softmax cross-entropy over the seeds, new model state)``;
    ``label_mask`` picks the seeds that count (the faults test leaves half
    of them out)."""
    import jax
    import jax.numpy as jnp

    logits, new_state = gat_forward(params, model_state, x, layers, n_mask,
                                    heads, matmul, drop)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, labels[:, None], axis=1)[:, 0]
    if label_mask is None:
        return nll.mean(), new_state
    m = label_mask.astype(nll.dtype)
    return (nll * m).sum() / jnp.maximum(m.sum(), 1.0), new_state


def train_follow(params0, state0, batches, cfg, matmul, fault=None):
    """Follow the first steps of training.  ``batches``: per step a dict
    of ``rows`` [P, D] (from the host table), ``layers``, ``n_mask``,
    ``labels`` and ``drop_key`` (``n_id`` is there and unread: one node
    type).  Returns per-step losses, the first gradient, the parameters
    and the model state after the last step - all host numpy.  ``fault``:
    as ``references/sage.train_follow``."""
    import jax
    import jax.numpy as jnp

    tm = jax.tree_util.tree_map
    params, state = tm(jnp.asarray, params0), tm(jnp.asarray, state0)
    m, v = tm(jnp.zeros_like, params), tm(jnp.zeros_like, params)
    grad = jax.jit(jax.value_and_grad(loss_fn, has_aux=True),
                   static_argnums=(6, 7))
    hidden = cfg["hidden"]
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
            jnp.asarray(b["n_mask"]), jnp.asarray(b["labels"]),
            cfg["heads"], matmul, drop, mask)
        losses.append(float(loss))
        if first is None:
            first = tm(np.asarray, g)
        if fault != "stale_state":
            params, m, v = sage.adam_update(params, g, m, v, i + 1,
                                            cfg["lr"])
            state = new_state
    return losses, first, tm(np.asarray, params), tm(np.asarray, state)
