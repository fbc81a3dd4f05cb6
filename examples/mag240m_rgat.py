"""Heterogeneous R-GAT training — mag240m-class schema.

Of the two R-GAT forms in ``quiver_tpu/models/rgat.py`` this shows the
repo's OWN one: ``HeteroGraphSageSampler`` (per-relation blocks and
fanouts, a feature store per node type) -> ``models.RGAT``, the
two-stage loop.  The model the reference's mag240m benchmark
(``/root/reference/benchmarks/ogbn-mag240m/``, OGB-LSC's ``rgnn.py
--model rgat``) actually trains is the HOMOGENISED form, ``models.RGNN``:
one id space through ``GraphSageSampler`` -> ``Feature`` ->
``pipeline.make_fused_train_step``; ``cellbench/programs/rgat_fused.py``
builds it and ``tests/test_rgnn.py`` holds it to its plain reference.
Synthetic schema-compatible data unless the real dataset is wired in.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import argparse
import time

import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--papers", type=int, default=20_000)
    ap.add_argument("--authors", type=int, default=10_000)
    ap.add_argument("--institutions", type=int, default=500)
    ap.add_argument("--dim", type=int, default=64)
    ap.add_argument("--classes", type=int, default=20)
    ap.add_argument("--batch-size", type=int, default=64)
    ap.add_argument("--steps", type=int, default=20)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import optax

    from quiver_tpu import Feature
    from quiver_tpu.hetero import HeteroCSRTopo, HeteroGraphSageSampler
    from quiver_tpu.models import RGAT

    rng = np.random.default_rng(0)

    def edges(n_src, n_dst, avg):
        deg = rng.poisson(avg, n_dst)
        dst = np.repeat(np.arange(n_dst), deg)
        return np.stack([rng.integers(0, n_src, len(dst)), dst])

    counts = {"paper": args.papers, "author": args.authors,
              "institution": args.institutions}
    topo = HeteroCSRTopo.from_edge_index_dict(
        {
            ("paper", "cites", "paper"): edges(args.papers, args.papers, 8),
            ("author", "writes", "paper"): edges(args.authors, args.papers, 4),
            ("institution", "employs", "author"):
                edges(args.institutions, args.authors, 2),
        },
        counts,
    )
    dims = {"paper": args.dim, "author": args.dim // 2, "institution": 16}
    from quiver_tpu import HeteroFeature

    feats = HeteroFeature.from_cpu_tensors(
        {t: rng.normal(size=(counts[t], dims[t])).astype(np.float32)
         for t in counts},
        device_cache_size="10G",
    )
    labels = rng.integers(0, args.classes, args.papers)

    sampler = HeteroGraphSageSampler(
        topo,
        sizes=[{("paper", "cites", "paper"): 8,
                ("author", "writes", "paper"): 4,
                ("institution", "employs", "author"): 2}] * 2,
        seed_type="paper",
    )
    model = RGAT(hidden=64, out_dim=args.classes, num_layers=2,
                 in_dims=dims, heads=4, dropout=0.0)
    tx = optax.adam(1e-3)
    B = args.batch_size

    fetch = feats.lookup

    b0 = sampler.sample(np.arange(B), key=jax.random.PRNGKey(0))
    params = model.init(jax.random.PRNGKey(1), fetch(b0), b0)
    opt = tx.init(params)

    @jax.jit
    def step(params, opt, xs, batch, labs):
        def loss_fn(p):
            logits = model.apply(p, xs, batch)
            return optax.softmax_cross_entropy_with_integer_labels(
                logits, labs
            ).mean()

        loss, g = jax.value_and_grad(loss_fn)(params)
        upd, opt = tx.update(g, opt, params)
        return optax.apply_updates(params, upd), opt, loss

    t0 = time.perf_counter()
    for i in range(args.steps):
        seeds = rng.integers(0, args.papers, B)
        batch = sampler.sample(seeds, key=jax.random.PRNGKey(2 + i))
        params, opt, loss = step(params, opt, fetch(batch), batch,
                                 jnp.asarray(labels[seeds]))
        if i % 5 == 0 or i == args.steps - 1:
            print(f"step {i}: loss {float(loss):.4f}")
    dt = time.perf_counter() - t0
    print(f"{args.steps} R-GAT steps in {dt:.2f}s "
          f"({dt / args.steps * 1e3:.0f} ms/step)")


if __name__ == "__main__":
    main()
