"""GAT training on a citation graph — OGB-LSC's MAG240M ``gnn.py --model
gat`` at a toy size.

The sibling of ``mag240m_rgat.py`` for the UNTYPED published model:
``models.GNN`` (per layer PyG's ``GATConv`` with self-loops + ``skip`` +
BatchNorm + ELU + dropout, then the MLP head) over the paper-cites-paper
graph alone, through the library's ordinary entry points and nothing
else: ``CSRTopo`` -> ``GraphSageSampler`` -> ``Feature`` (float16 rows,
all in HBM) -> ``pipeline.make_fused_train_step``, BatchNorm's running
averages carried in ``TrainState.model_state`` and read by
``make_fused_eval_fn``.  ``cellbench/programs/gat_fused.py`` builds the
same thing at the published widths (768 -> 1024 -> 1024 -> 153, fanout
[25, 15], batch 1,024) for the cell ``mag240m-gat.train-fused-stateful``,
and ``tests/test_gnn_gat.py`` holds it to its plain reference.

    python examples/mag240m_gat.py --papers 3000 --steps 30
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
    ap.add_argument("--classes", type=int, default=16)
    ap.add_argument("--hidden", type=int, default=64)
    ap.add_argument("--heads", type=int, default=4)
    ap.add_argument("--fanout", type=int, nargs=2, default=[10, 5])
    ap.add_argument("--batch-size", type=int, default=128)
    ap.add_argument("--steps", type=int, default=40)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import optax

    from quiver_tpu import Feature, GraphSageSampler, make_key
    from quiver_tpu.models import GNN, rgnn_apply_fn
    from quiver_tpu.parallel import TrainState
    from quiver_tpu.pipeline import make_fused_eval_fn, make_fused_train_step
    from quiver_tpu.utils.synthetic import community_graph

    # a graph whose labels can be learned from its neighbourhoods
    topo, feat, labels = community_graph(args.papers, args.classes,
                                         feat_extra=16, seed=0)
    feature = Feature(device_cache_size=args.papers, cache_unit="rows",
                      dtype=jnp.float16).from_cpu_tensor(
                          feat.astype(np.float16))
    sampler = GraphSageSampler(topo, list(args.fanout))
    model = GNN(hidden=args.hidden, out_dim=args.classes,
                heads=args.heads, dropout=0.5)
    B = args.batch_size
    b0 = sampler.sample(np.arange(B), key=make_key(0))
    v = model.init(jax.random.PRNGKey(1), feature[b0.n_id].astype(
        jnp.float32), b0.layers, b0.n_id, b0.n_id_mask)
    tx = optax.adam(1e-3)
    apply_fn = rgnn_apply_fn(model)
    state = TrainState.create({"params": v["params"]}, tx,
                              {"batch_stats": v["batch_stats"]})
    step = make_fused_train_step(sampler, feature, apply_fn, tx)
    evaluate = make_fused_eval_fn(sampler, feature, apply_fn)

    rng = np.random.default_rng(0)
    ones = jnp.ones((B,), bool)
    t0 = time.perf_counter()
    for i in range(args.steps):
        seeds = rng.integers(0, args.papers, B).astype(np.int32)
        state, loss = step(state, jnp.asarray(seeds),
                           jnp.asarray(labels[seeds]), ones, make_key(2 + i))
        if i % 10 == 0 or i == args.steps - 1:
            print(f"step {i}: loss {float(loss):.4f}")
    dt = time.perf_counter() - t0
    seeds = rng.integers(0, args.papers, B).astype(np.int32)
    logits = evaluate(state.params, jnp.asarray(seeds), make_key(1),
                      state.model_state)
    acc = float((np.asarray(logits).argmax(-1) == labels[seeds]).mean())
    print(f"{args.steps} GAT steps in {dt:.2f}s "
          f"({dt / args.steps * 1e3:.0f} ms/step); accuracy on {B} fresh "
          f"seeds {acc:.2f}")


if __name__ == "__main__":
    main()
