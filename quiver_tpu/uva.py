"""Single-chip big-graph tier — the TPU answer to UVA mode.

Reference: ``quiver<T,CUDA>`` mode ``ZERO_COPY`` keeps the CSR in pinned
host memory and lets sampling kernels read it over PCIe
(``srcs/cpp/include/quiver/quiver.cu.hpp:16-26, 155-464``), so one GPU can
sample a graph larger than its HBM.  TPU kernels cannot dereference host
memory mid-kernel, so a literal port is impossible; the tpu-first
equivalent mirrors the feature store's hot/cold split:

  * **hot rows** — the byte-budgeted, degree-ordered top rows' edge lists
    live in HBM as a compacted sub-CSR; their sampling runs on device at
    HBM bandwidth (the common case: power-law graphs put most sampled
    edges in few rows).
  * **cold rows** — remaining edge lists stay in host RAM (or mmap) and
    sample through the multithreaded native CPU sampler
    (``cpp/csrc/quiver_cpu.cpp``) — RAM plays pinned memory, the CPU
    plays the PCIe engine.

Each hop dispatches the device program first (async) and samples the cold
subset while it runs, so the host tier hides behind the device tier
exactly like the reference's zero-copy reads hide behind the kernel.

Activated by ``GraphSageSampler(..., mode="UVA", uva_budget="1G")``.
With no budget (or a budget covering all edges) every row is hot and the
mode degenerates to plain TPU sampling of an HBM graph.
"""

from __future__ import annotations

from typing import Optional, Union

import numpy as np

from .utils.topology import CSRTopo, parse_size

__all__ = ["UVAGraph"]


class UVAGraph:
    """Hot/cold split of a CSR's edge lists (see module docstring)."""

    def __init__(self, topo: CSRTopo, budget: Union[int, str, None],
                 n_threads: int = 0):
        import jax.numpy as jnp

        deg = topo.degree.astype(np.int64)
        n = topo.node_count
        budget_b = None if budget is None else parse_size(budget)
        if budget_b is None or budget_b >= topo.edge_count * 4:
            hot_mask = np.ones(n, dtype=bool)
        else:
            order = np.argsort(-deg, kind="stable")
            cum = np.cumsum(deg[order]) * 4  # indices are int32
            hot_mask = np.zeros(n, dtype=bool)
            hot_mask[order[cum <= budget_b]] = True
        self.is_hot = hot_mask
        self.hot_edges = int(deg[hot_mask].sum())
        self.cold_edges = int(topo.edge_count - self.hot_edges)

        # compacted hot sub-CSR over ALL node ids: cold rows have degree 0
        hot_deg = np.where(hot_mask, deg, 0)
        indptr_hot = np.zeros(n + 1, dtype=np.int64)
        np.cumsum(hot_deg, out=indptr_hot[1:])
        if self.hot_edges >= 2**31:  # same guard as CSRTopo.to_device
            raise ValueError(
                f"hot tier has {self.hot_edges:,} edges — int32 positions "
                "overflow; lower uva_budget (or shard over a mesh)"
            )
        edge_is_hot = np.repeat(hot_mask, deg)
        indices_hot = topo.indices[edge_is_hot].astype(np.int32)
        # pad to a non-empty multiple of 128 (the blocked gather path;
        # empty tables break jnp.take even when fully masked)
        pad = (-len(indices_hot)) % 128 or (128 if not len(indices_hot)
                                            else 0)
        if pad:
            indices_hot = np.concatenate(
                [indices_hot, np.zeros(pad, np.int32)]
            )
        # indptr needs the same 128 padding: the element gather truncates
        # the table to a 128 multiple and CLIPS indices — an unpadded
        # [n+1] indptr silently returns a wrong row's pointers for the
        # last (n+1) % 128 node ids
        indptr_pad = indptr_hot.astype(np.int32)
        ppad = (-len(indptr_pad)) % 128
        if ppad:
            # repeat the final offset: padded "rows" read as degree 0
            indptr_pad = np.concatenate(
                [indptr_pad, np.full(ppad, indptr_pad[-1], np.int32)]
            )
        self.indptr_dev = jnp.asarray(indptr_pad)
        self.indices_dev = jnp.asarray(indices_hot)

        from .cpp.native import CPUSampler

        # the host tier keeps the FULL CSR (cold rows are read from it);
        # with an mmap-backed topo this never materializes in RAM
        self.cpu = CPUSampler(topo.indptr, topo.indices,
                              n_threads=n_threads)

    def stats(self) -> dict:
        return dict(hot_edges=self.hot_edges, cold_edges=self.cold_edges,
                    hot_rows=int(self.is_hot.sum()),
                    hbm_bytes=int(self.hot_edges * 4))


def sample_uva(uva: UVAGraph, sizes, input_nodes, key, gather_mode="xla",
               sample_rng="auto", overlap=True, timings=None):
    """Host-driven multi-hop loop over the hot/cold split.

    Per hop: device samples the hot rows (dispatched async), the native
    CPU sampler covers the cold rows meanwhile, blocks merge host-side
    with the same positional no-dedup relabeling as the TPU pipeline.
    Returns the ``(n_id, n_id_mask, num_nodes, blocks)`` tuple the caller
    wraps into a :class:`SampledBatch`.

    ``overlap=False`` forces the device sync BEFORE the host tier runs —
    the serialized baseline the overlap claim is measured against
    (bench's ``sampling_uva`` section reports the A/B as
    ``overlap_factor``).  ``timings``: optional dict accumulating
    ``host_s`` (cold-tier wall inside this call) for tier attribution.
    """
    import time as _time

    import jax
    import jax.numpy as jnp

    from . import telemetry
    from .ops.sample import sample_neighbors

    frontier = np.asarray(input_nodes, dtype=np.int32)
    fmask = np.ones(len(frontier), dtype=bool)
    blocks = []
    keys = jax.random.split(key, len(sizes))
    # One host readback covers every hop's cold-tier seed (it used to be a
    # per-hop sync inside the loop): the host tier's RNG derives from the
    # same jax keys, so a pinned key still replays BOTH tiers.
    # quiverlint: ignore[QT001] — single pre-loop sync replaces L per-hop syncs
    key_data = np.asarray(jax.random.key_data(keys))
    for l, k in enumerate(sizes):
        hot = uva.is_hot[frontier] & fmask
        # device first (returns immediately — XLA async dispatch) ...
        out = sample_neighbors(uva.indptr_dev, uva.indices_dev,
                               jnp.asarray(frontier), k, keys[l],
                               seed_mask=jnp.asarray(hot),
                               gather_mode=gather_mode,
                               sample_rng=sample_rng)
        if not overlap:  # serialized A/B baseline: wait for device first
            # quiverlint: ignore[QT001] — overlap=False A/B baseline
            # serializes device-then-host on purpose (measures the win)
            out.nbrs.block_until_ready()
        # ... host tier runs while the device works
        cold_idx = np.nonzero(fmask & ~hot)[0]
        if len(cold_idx):
            hop_seed = int(key_data[l, -1])
            t0 = _time.perf_counter()
            cn, cm, _ = uva.cpu.sample_neighbors(frontier[cold_idx], k,
                                                 seed=hop_seed)
            host_dt = _time.perf_counter() - t0
            if timings is not None:
                timings["host_s"] = timings.get("host_s", 0.0) + host_dt
            telemetry.histogram("uva_host_tier_seconds").observe(host_dt)
        # per-hop hot/cold seed attribution: how much of the frontier the
        # HBM sub-CSR actually covered (the UVA design bet)
        telemetry.counter("uva_seeds_total", tier="hot").inc(
            float(hot.sum()))
        telemetry.counter("uva_seeds_total", tier="cold").inc(
            float(len(cold_idx)))
        # hot/cold merge happens on host: this is the UVA design's one
        # deliberate sync per hop, overlapped with the host tier above
        # quiverlint: ignore[QT001]
        nbrs = np.asarray(out.nbrs).copy()   # sync point
        mask = np.asarray(out.mask).copy()   # quiverlint: ignore[QT001]
        if len(cold_idx):
            nbrs[cold_idx] = cn
            mask[cold_idx] = cm
        t = len(frontier)
        pos = (t + np.arange(t, dtype=np.int32)[:, None] * k
               + np.arange(k, dtype=np.int32)[None, :])
        blocks.append((np.where(mask, pos, 0), mask, int(fmask.sum())))
        frontier = np.concatenate(
            [frontier, np.where(mask, nbrs, 0).reshape(-1)]
        ).astype(np.int32)
        fmask = np.concatenate([fmask, mask.reshape(-1)])
    return frontier, fmask, int(fmask.sum()), blocks[::-1]
