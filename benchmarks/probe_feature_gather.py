"""Time the fused step's feature-row gather on the chip, one variant a line.

A fused step asks the feature table for one row per slot of the sampled
frontier, and most slots are dead (the sampler writes id 0 there and
every consumer multiplies the row by a zero mask).  This script samples
one frontier of each cell's shape (a lognormal-degree graph of the cell's
size made on the device, the library's own sampler), then times, out of
a table of the cell's size and row type:

  (i)   XLA's gather of every slot's row against the same over the live
        ids alone: is a dead row as dear as a live one?  And with the
        dead slots sent elsewhere than row 0 (random rows, the row of the
        slot's own position, every 16th row, ...): which row is the
        cheapest to ask for where any row will do?  ``lookup.with_mask``
        is what ``feature._lookup_tables`` does with a mask.
  (ii)  the masked DMA kernel (``ops/pallas/gather_kernel.py``) over the
        table stored as word rows, every slot live: does it reach XLA's
        rate?  (SAGE shape only: a row has to be 128 32-bit words.)
  (iii) the same with the frontier's own mask: what does a skipped slot
        cost?

and what placing the table either way takes at set-up.  Every variant's
rows are compared, bit for bit, with a plain ``jnp.take`` on the device
(its live slots, where the dead ones may read any row) before it is
timed.  PERF.md (PR 33) has the chip's numbers.

    python benchmarks/probe_feature_gather.py            # on the chip
    JAX_PLATFORMS=cpu python benchmarks/probe_feature_gather.py --small

A time from a CPU run says nothing about the chip and is not printed as
one: ``--small`` only rehearses the script.
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmarks.probe_window_gather import LANES, build, timed  # noqa: E402

# name -> (nodes, edges, batch, fanout, row width, row type): the two
# cells of BENCHMARK.json (PERF.md section 4)
SHAPES = {
    "sage": (27_764_989, 403_921_468, 1024, (15, 10, 5), 128, "bfloat16"),
    "typed": (3_815_006, 54_023_314, 1024, (25, 15), 768, "float16"),
}
SMALL = {
    "sage": (4_001, 60_000, 16, (5, 4, 3), 128, "bfloat16"),
    "typed": (2_001, 30_000, 16, (5, 3), 768, "float16"),
}
# (block, window, unroll) of the kernel; the first is what it ships with
KERNEL_GRID = [(2048, 128, 8), (2048, 32, 8), (512, 32, 8), (512, 32, 1),
               (512, 8, 1)]
KERNEL_GRID_SMALL = [(128, 8, 1), (384, 32, 8)]


def _hop_slots(batch, fanout):
    """Slots each hop adds to the positional frontier: every node of the
    frontier so far draws ``k``."""
    total = batch
    for k in fanout:
        yield total * k
        total += total * k


def sample_frontier(nodes, edges, batch, fanout, seed):
    """``(n_id, n_mask)`` of one batch, as the fused step's sampler makes
    them on this device, and the live slots of each hop."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from quiver_tpu.config import (resolve_dedup, resolve_gather_mode,
                                   resolve_sample_rng)
    from quiver_tpu.sampler import run_pipeline

    indptr, table2d = build(nodes, edges, seed)
    pad = (-indptr.shape[0]) % LANES
    indptr = jnp.concatenate([indptr, jnp.full((pad,), indptr[-1])])
    indices = table2d.reshape(-1)
    seeds = jax.random.randint(jax.random.key(seed + 2), (batch,), 0, nodes)
    n_id, n_mask, *_ = jax.jit(
        lambda ip, ix, s, k: run_pipeline(
            resolve_dedup("auto"), ip, ix, s, k, tuple(fanout),
            (None,) * len(fanout), gather_mode=resolve_gather_mode("auto"),
            sample_rng=resolve_sample_rng("auto")))(
        indptr, indices, seeds, jax.random.key(seed + 3))
    mask = np.asarray(n_mask)
    hops, lo = [], 0
    for width in [batch] + list(_hop_slots(batch, fanout)):
        hops.append({"slots": width, "live": int(mask[lo:lo + width].sum())})
        lo += width
    assert lo == mask.shape[0], (lo, mask.shape)
    return n_id, n_mask, hops


def host_table(nodes, dim, dtype, seed):
    """``[nodes, dim]`` 16-bit floats on the host: random finite bits."""
    import ml_dtypes
    import numpy as np

    dt = {"bfloat16": ml_dtypes.bfloat16, "float16": np.float16}[dtype]
    top = {"bfloat16": 0x7F80, "float16": 0x7C00}[dtype]
    rng = np.random.default_rng(seed)
    return rng.integers(0, top, (nodes, dim), dtype=np.uint16).view(dt)


def bits(x):
    import numpy as np

    x = np.asarray(x)
    return x.view({2: np.uint16, 4: np.uint32}[x.dtype.itemsize])


def probe(name, shape, grid, args, emit):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from quiver_tpu.feature import _lookup_tables
    from quiver_tpu.ops.pallas.gather_kernel import (gather_rows,
                                                     pack_word_rows,
                                                     pick_word_rows)

    nodes, edges, batch, fanout, dim, dtype = shape
    interpret = jax.devices()[0].platform != "tpu"
    n_id, n_mask, hops = sample_frontier(nodes, edges, batch, fanout,
                                         args.seed)
    m = int(n_id.shape[0])
    mask_np = np.asarray(n_mask)
    live = int(mask_np.sum())
    emit({"shape": name, "frontier": hops, "slots": m, "live": live})
    iota = jnp.arange(m, dtype=jnp.int32)
    # where a dead slot may be sent (the sampler sends it to row 0)
    dead_to = {
        "dead_row0": n_id,
        "dead_random": jnp.where(n_mask, n_id, jax.random.randint(
            jax.random.key(args.seed + 4), (m,), 0, nodes)),
        "dead_own_row": jnp.where(n_mask, n_id, iota % nodes),
        "dead_own_row_x16": jnp.where(n_mask, n_id, (iota * 16) % nodes),
        "dead_row_of_8": jnp.where(n_mask, n_id, (iota >> 3) % nodes),
    }
    live_ids = jnp.asarray(np.asarray(n_id)[mask_np])
    ones = jnp.ones((m,), bool)

    def record(variant, f, fargs, rows, want, where=None):
        ms, out = timed(jax.jit(f), fargs, args.iters)
        got = bits(out)
        if where is not None:       # dead slots may read any row
            got, want = got[where], want[where]
        line = {"shape": name, "variant": variant, "rows_asked": rows,
                "equal_to_take": bool(np.array_equal(got, want))}
        if not args.small:
            line["ms"] = round(ms, 4)
            line["ns_per_row"] = round(ms * 1e6 / rows, 3)
        emit(line)

    host = host_table(nodes, dim, dtype, args.seed)

    # ---- the table as the library stores it: [N, dim] rows
    t0 = time.perf_counter()
    plain = jax.block_until_ready(jnp.asarray(host))
    place_plain_s = time.perf_counter() - t0
    take = jax.jit(lambda t, i: jnp.take(t, i, axis=0))
    want_all = bits(take(plain, n_id))
    want_live = bits(take(plain, live_ids))

    def in_bounds(t, i):
        return t.at[i].get(mode="promise_in_bounds")

    record("xla.take.dead_row0", lambda t, i: jnp.take(t, i, axis=0),
           (plain, n_id), m, want_all)
    record("xla.in_bounds.live_only", in_bounds, (plain, live_ids), live,
           want_live)
    for tag, ids in dead_to.items():
        record(f"xla.in_bounds.{tag}", in_bounds, (plain, ids), m, want_all,
               where=mask_np)
    record("lookup.with_mask", lambda t, i, mk: _lookup_tables(
        (t, None), i, mk), (plain, n_id, n_mask), m, want_all,
        where=mask_np)
    del plain
    line = {"shape": name, "variant": "setup",
            "table_bytes": int(host.nbytes)}
    if dim == LANES:
        # ---- the table as word rows: int32[ceil(N/2), 128]
        t0 = time.perf_counter()
        words = jax.block_until_ready(pack_word_rows(host))
        place_words_s = time.perf_counter() - t0
        want_masked = np.where(mask_np[:, None], want_all, 0)

        def xla_words(t, i):
            return pick_word_rows(in_bounds(t, i >> 1), i, host.dtype)

        record("xla.words.dead_row0", xla_words, (words, n_id), m, want_all)
        for block, window, unroll in grid:
            def dma(t, i, mk, kw=dict(block=block, window=window,
                                      unroll=unroll, interpret=interpret)):
                return pick_word_rows(gather_rows(t, i >> 1, mk, **kw), i,
                                      host.dtype)

            tag = f"block{block}.window{window}.unroll{unroll}"
            record(f"dma.all_live.{tag}", dma, (words, n_id, ones), m,
                   want_all)
            record(f"dma.all_live.dead_random.{tag}", dma,
                   (words, dead_to["dead_random"], ones), m, want_all,
                   where=mask_np)
            record(f"dma.masked.{tag}", dma, (words, n_id, n_mask), live,
                   want_masked)
        if not args.small:
            line["place_words_s"] = round(place_words_s, 3)
    if not args.small:
        line["place_plain_s"] = round(place_plain_s, 3)
    emit(line)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--small", action="store_true",
                    help="tiny shapes on any backend: a rehearsal, no times")
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--only", default="", help="comma list of shape names")
    ap.add_argument("--out", default="chiprun_out/probe_feature_gather.jsonl")
    args = ap.parse_args()

    import jax

    dev = jax.devices()[0]
    if not args.small and dev.platform != "tpu":
        sys.exit("probe_feature_gather: no TPU here (rehearse with --small)")
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    lines = []

    def emit(line):
        line["device"] = dev.device_kind
        lines.append(line)
        print(json.dumps(line), flush=True)

    shapes, grid = ((SMALL, KERNEL_GRID_SMALL) if args.small
                    else (SHAPES, KERNEL_GRID))
    for name, shape in shapes.items():
        if not args.only or name in args.only.split(","):
            probe(name, shape, grid, args, emit)
    with open(args.out, "w") as fh:
        fh.writelines(json.dumps(x) + "\n" for x in lines)


if __name__ == "__main__":
    main()
