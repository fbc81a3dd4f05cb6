"""Sampling throughput benchmark (SEPS) across configurations.

Mirrors the reference's sampling benchmarks
(``/root/reference/benchmarks/ogbn_products/bench_quiver_sampler.py``-style
scripts behind docs/Introduction_en.md:38-45).  Run on the real TPU chip:

    python benchmarks/bench_sampling.py [--nodes N --edges E]

Prints a table over {batch size} x {dedup mode}, on the backend's gather path.
"""

import argparse
import sys
import time

sys.path.insert(0, ".")

import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--nodes", type=int, default=2_449_029)
    ap.add_argument("--edges", type=int, default=123_718_280)
    ap.add_argument("--fanout", type=int, nargs="+", default=[15, 10, 5])
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--batches", type=int, nargs="+",
                    default=[512, 1024, 2048])
    args = ap.parse_args()

    import jax

    from bench import build_graph  # repo-root bench utilities
    from quiver_tpu import CSRTopo, GraphSageSampler

    indptr, indices = build_graph(args.nodes, args.edges)
    topo = CSRTopo(indptr=indptr, indices=indices)
    topo.to_device()
    print(f"graph: N={topo.node_count:,} E={topo.edge_count:,} "
          f"fanout={args.fanout}")

    rows = []
    for dedup in ("none", "hop"):
        for B in args.batches:
            s = GraphSageSampler(topo, args.fanout, dedup=dedup)
            gm = s.gather_mode
            rng = np.random.default_rng(0)
            batches = [rng.integers(0, topo.node_count, B,
                                    dtype=np.int32)
                       for _ in range(args.iters + 2)]
            out = s.sample(batches[0], key=jax.random.PRNGKey(0))
            out.n_id.block_until_ready()
            s.sample(batches[1]).n_id.block_until_ready()
            t0 = time.perf_counter()
            outs = [s.sample(batches[2 + i],
                             key=jax.random.PRNGKey(i))
                    for i in range(args.iters)]
            outs[-1].n_id.block_until_ready()
            dt = time.perf_counter() - t0
            edges = sum(
                int(np.asarray(b.mask).sum())
                for o in outs for b in o.layers
            )
            seps = edges / dt
            rows.append((dedup, gm, B, seps))
            print(f"dedup={dedup:<5} gather={gm:<7} B={B:<5} "
                  f"{seps / 1e6:8.2f}M SEPS "
                  f"({dt / args.iters * 1e3:.1f} ms/batch)")
    best = max(rows, key=lambda r: r[3])
    print(f"\nbest: dedup={best[0]} gather={best[1]} B={best[2]} "
          f"-> {best[3] / 1e6:.2f}M SEPS "
          f"(reference UVA baseline: 34.29M)")


if __name__ == "__main__":
    main()
