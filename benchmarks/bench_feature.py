"""Feature-collection throughput benchmark (GB/s).

Mirrors the reference's feature benchmarks behind
docs/Introduction_en.md:90-126 (single-device cache 14.82 GB/s; NVLink
clique 108.6 GB/s).  Compares:
  * XLA row gather (``jnp.take``) — the Feature hot path
  * Pallas masked DMA gather (``ops.pallas.gather_rows``; rows of 128
    32-bit words only: ``--dim 128``.  What it is for, a frontier with
    dead slots, is timed by ``probe_feature_gather.py``)
  * Feature with partial cache (hot/cold mix, host tail)
"""

import argparse
import sys
import time

sys.path.insert(0, ".")

import numpy as np


def bench(name, fn, *args, iters=20, bytes_per_iter=0):
    import jax

    fn(*args)
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    outs = [fn(*args) for _ in range(iters)]
    jax.block_until_ready(outs[-1])
    dt = time.perf_counter() - t0
    gbs = bytes_per_iter * iters / dt / 1e9
    print(f"{name:<42} {gbs:8.2f} GB/s  ({dt / iters * 1e3:.2f} ms)")
    return gbs


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--nodes", type=int, default=2_449_029)
    ap.add_argument("--dim", type=int, default=100)
    ap.add_argument("--rows", type=int, default=500_000)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from quiver_tpu import CSRTopo, Feature
    from quiver_tpu.ops.pallas.gather_kernel import gather_rows

    rng = np.random.default_rng(0)
    n, d, m = args.nodes, args.dim, args.rows
    feat = rng.normal(size=(n, d)).astype(np.float32)
    table = jnp.asarray(feat)
    idx = jnp.asarray(rng.integers(0, n, m, dtype=np.int32))
    nbytes = m * d * 4

    take = jax.jit(lambda t, i: jnp.take(t, i, axis=0))
    bench("XLA row gather (full HBM)", take, table, idx,
          bytes_per_iter=nbytes)
    try:
        bench("Pallas DMA row gather", gather_rows, table, idx,
              bytes_per_iter=nbytes)
    except Exception as e:
        print(f"pallas gather failed: {e}")

    # Feature with 20% HBM cache, degree-ordered (reference's headline
    # config: 20% cache -> 14.82 GB/s on ogbn-products)
    deg_like = rng.lognormal(3, 1, n)
    order = np.argsort(-deg_like)
    indptr = np.zeros(n + 1, dtype=np.int64)
    indptr[1:] = np.cumsum(
        np.maximum(deg_like / deg_like.sum() * (n * 10), 1).astype(int)
    )
    topo = CSRTopo(indptr=indptr,
                   indices=np.zeros(int(indptr[-1]), dtype=np.int32))
    f20 = Feature(device_cache_size=int(n * 0.2) * d * 4,
                  csr_topo=topo).from_cpu_tensor(feat)
    host_idx = np.asarray(rng.integers(0, n, m))

    def feature_gather():
        return f20[host_idx]

    bench("quiver Feature (20% HBM cache + host tail)", feature_gather,
          bytes_per_iter=nbytes, iters=5)
    full = Feature(device_cache_size="100G").from_cpu_tensor(feat)
    bench("quiver Feature (100% HBM)", lambda: full[host_idx],
          bytes_per_iter=nbytes, iters=10)


if __name__ == "__main__":
    main()
