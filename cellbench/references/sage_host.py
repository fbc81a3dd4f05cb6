"""The plain reference ``sage_host`` (a configuration names it under
``"reference"``): GraphSAGE trained data-parallel over the ranks of one
host, each rank sampling from and gathering out of the WHOLE graph and
table.  The data, the model, its loss, Adam and the comparisons are
``references/sage.py``'s, loaded by name; this file states what the ranks
add, and imports nothing of ``quiver_tpu``.

What is read back of the program is stacked over the ranks (leading axis
``R``).  A draw is held, rank by rank, to the host CSR - all of it, not a
rank's rows: where a frontier id is another rank's, the program had to ask
that rank, and a wrong or dropped answer is a breach here.  The rows the
feature store answered with are held to the host table bit for bit.  A
step is followed as DDP states it: each rank computes loss and gradient of
the float32 ``jax.numpy`` model over its own frontier under its own
dropout key (``jax.random.split(key, R)[r]``, as ``make_train_step(mesh=)``
draws them), the step's loss is the mean of the ranks' masked means, its
gradient the mean of theirs, and one Adam step follows.
"""

import numpy as np

from run import load_named

sage = load_named("references", "sage")

make_data = sage.make_data
leaf_norm_gap = sage.leaf_norm_gap


def per_rank(a, ranks):
    return np.asarray(a).reshape((ranks, -1) + np.shape(a)[1:])


def check_sample(indptr, indices, fanout, seeds, n_id, n_mask, layers):
    """``sage.check_sample`` for every rank against the whole CSR;
    ``seeds`` is the host's batch, cut into the ranks' in order."""
    ranks = n_id.shape[0]
    total, edges = {}, 0
    for r, s in enumerate(per_rank(seeds, ranks)):
        bad, e = sage.check_sample(
            indptr, indices, fanout, s, n_id[r], n_mask[r],
            [(nbr[r], m[r]) for nbr, m in layers])
        for k, v in bad.items():
            total[k] = total.get(k, 0) + v
        edges += e
    return total, edges


def check_rows(features, n_id, n_mask, rows):
    """Count of frontier slots whose looked-up row is not the host
    table's row of that id, bit for bit, or not zero where the slot is
    dead."""
    bits = np.dtype(f"uint{8 * rows.dtype.itemsize}")
    want = np.where(n_mask[..., None], features[n_id].view(bits), 0)
    return int((rows.view(bits) != want).any(axis=-1).sum())


def train_follow(params0, batches, cfg, matmul, fault=None):
    """``sage.train_follow`` over the ranks.  ``batches``: per step
    ``rows`` [R, P, D], ``layers`` [(nbr_local [R, T, k], mask), ...],
    ``labels`` [R * B] and ``drop_key``.  ``fault`` as there:
    ``"half_batch"`` is the first half of the HOST's batch (the first
    ranks'), ``"stale_state"`` the state returned unchanged; and
    ``"rank0_alone"``, this deployment's own: the exchange of gradients
    left out, loss and gradient rank 0's and no mean over the ranks."""
    import jax
    import jax.numpy as jnp

    tm = jax.tree_util.tree_map
    ranks = cfg["ranks"]
    params = tm(jnp.asarray, params0)
    m = tm(jnp.zeros_like, params)
    v = tm(jnp.zeros_like, params)
    grad = jax.jit(jax.value_and_grad(sage.loss_fn), static_argnums=(4,))
    losses, first = [], None
    for i, b in enumerate(batches):
        labels = per_rank(b["labels"], ranks)
        counted = np.ones(labels.shape, bool)
        if fault == "half_batch":
            counted = per_rank(
                np.arange(labels.size) < labels.size // 2, ranks)
        keys = jax.random.split(b["drop_key"], ranks)
        loss, g = 0.0, None
        used = 1 if fault == "rank0_alone" else ranks
        for r in range(used):
            layers = [(jnp.asarray(n[r]), jnp.asarray(k[r]))
                      for n, k in b["layers"]]
            drop = None
            if cfg["dropout"] > 0:
                shapes = [(lay[0].shape[0], cfg["hidden"])
                          for lay in layers[:-1]]
                drop = sage.dropout_masks(keys[r], shapes, cfg["dropout"])
            lr, gr = grad(params, jnp.asarray(b["rows"][r]), layers,
                          jnp.asarray(labels[r]), matmul, drop,
                          jnp.asarray(counted[r]))
            loss += float(lr) / used
            g = gr if g is None else tm(jnp.add, g, gr)
        g = tm(lambda a: a / used, g)
        losses.append(loss)
        if first is None:
            first = tm(np.asarray, g)
        if fault != "stale_state":
            params, m, v = sage.adam_update(params, g, m, v, i + 1,
                                            cfg["lr"])
    return losses, first, tm(np.asarray, params)
