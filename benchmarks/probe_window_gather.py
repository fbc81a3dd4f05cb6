"""Time the pieces of a hop's draw fetch on the chip, one shape a line.

The k draws of one target read one CSR window of ``indices``; this
script times, at the cells' own shapes, the ways of fetching that window
(``ops/blockgather.py``) against the per-draw ``lanes`` fetch
(``ops/fastgather.element_gather``), piece by piece: the row gather, the
lane select, the compaction of the targets whose window does not fit and
the whole routed op.  Its numbers chose ``DEFAULT_U`` and
``FALLBACK_FRAC`` (PERF.md, PR 31).  Every variant's values are compared
with a plain ``jnp.take`` on the device before it is timed.

    python benchmarks/probe_window_gather.py            # on the chip
    JAX_PLATFORMS=cpu python benchmarks/probe_window_gather.py --small

A time from a CPU run says nothing about the chip and is not printed as
one: ``--small`` only rehearses the script.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

LANES = 128


def build(nodes, edges, seed):
    """A lognormal-degree CSR (sigma 1, as ``cellbench/datagen.csr``) made
    on the device: ``indptr [nodes+1]``, ``table2d [rows, 128]``."""
    import jax
    import jax.numpy as jnp

    k0, k1 = jax.random.split(jax.random.key(seed))
    raw = jnp.exp(3.0 + jax.random.normal(k0, (nodes,), jnp.float32))
    deg = jnp.maximum(raw / raw.sum() * edges, 1.0).astype(jnp.int32)
    indptr = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                              jnp.cumsum(deg, dtype=jnp.int32)])
    rows = -(-int(indptr[-1]) // LANES)
    table2d = jax.random.randint(k1, (rows, LANES), 0, nodes, jnp.int32)
    return indptr, table2d


def frontier(indptr, B, k, dead, seed):
    """``start, deg, pos`` of one hop's targets: uniform node ids (the
    generator's endpoints are uniform), a ``dead`` share masked to degree
    0 at node 0 as the positional frontier has them."""
    import jax
    import jax.numpy as jnp

    from quiver_tpu.ops.sample import _hash_uniform, _stratified_positions

    ka, kb, kc = jax.random.split(jax.random.key(seed + 1), 3)
    nodes = indptr.shape[0] - 1
    live = jax.random.uniform(ka, (B,)) >= dead
    t = jnp.where(live, jax.random.randint(kb, (B,), 0, nodes), 0)
    start = jnp.take(indptr, t)
    deg = jnp.where(live, jnp.take(indptr, t + 1) - start, 0)
    pos = _stratified_positions(_hash_uniform(kc, (B, k)), deg, k)
    return start, deg, pos


def variants(U, frac):
    """name -> f(table2d, start, deg, pos); ``vals``-valued ones are
    checked against the plain take."""
    import jax
    import jax.numpy as jnp

    from quiver_tpu.ops import blockgather as bg
    from quiver_tpu.ops.fastgather import element_gather

    def idx_of(t, start, pos):
        return jnp.clip(start[:, None] + pos, 0, t.shape[0] * LANES - 1)

    def r0_of(t, start):
        return jnp.clip(start >> 7, 0, t.shape[0] - U)

    def rel_of(t, start, pos):
        return jnp.clip(start[:, None] + pos - (r0_of(t, start)[:, None] << 7),
                        0, U * LANES - 1)

    # ---- the gathers alone (what comes back is written to HBM)
    def g_lanes(t, start, deg, pos):
        return jnp.take(t, idx_of(t, start, pos).reshape(-1) >> 7, axis=0)

    def g_rows_umajor(t, start, deg, pos):
        r0 = r0_of(t, start)
        ids = jnp.concatenate([r0 + u for u in range(U)])
        return jnp.take(t, ids, axis=0)

    def g_rows_bu(t, start, deg, pos):
        r0 = r0_of(t, start)
        return jnp.take(t, r0[:, None] + jnp.arange(U)[None, :], axis=0)

    def g_slice(t, start, deg, pos):
        return jax.lax.gather(
            t, r0_of(t, start)[:, None],
            jax.lax.GatherDimensionNumbers(
                offset_dims=(1, 2), collapsed_slice_dims=(),
                start_index_map=(0,)),
            slice_sizes=(U, LANES),
            mode=jax.lax.GatherScatterMode.PROMISE_IN_BOUNDS)

    def g_wide(t, start, deg, pos):      # one 1-KB row of the [R/2, 256] view
        w = t[:t.shape[0] // 2 * 2].reshape(-1, 2 * LANES)
        return jnp.take(w, jnp.minimum(start >> 8, w.shape[0] - 1), axis=0)

    # ---- gather + select, no routing (every target through the window)
    select_rows = bg._block_select     # the shipped select

    def select_flat(blk, rel):
        hot = rel[..., None] == jax.lax.broadcasted_iota(
            jnp.int32, (1, 1, blk.shape[1]), 2)
        return jnp.sum(jnp.where(hot, blk[:, None, :], 0), axis=2,
                       dtype=blk.dtype)

    def w_umajor_rows(t, start, deg, pos):
        B = start.shape[0]
        rows = g_rows_umajor(t, start, deg, pos)
        return select_rows([rows[u * B:(u + 1) * B] for u in range(U)],
                           rel_of(t, start, pos))

    def w_sep_rows(t, start, deg, pos):      # U gathers of [B] rows each
        r0 = r0_of(t, start)
        return select_rows([jnp.take(t, r0 + u, axis=0) for u in range(U)],
                           rel_of(t, start, pos))

    def w_bu_flat(t, start, deg, pos):
        B = start.shape[0]
        return select_flat(g_rows_bu(t, start, deg, pos).reshape(B, -1),
                           rel_of(t, start, pos))

    def w_slice_flat(t, start, deg, pos):
        B = start.shape[0]
        return select_flat(g_slice(t, start, deg, pos).reshape(B, -1),
                           rel_of(t, start, pos))

    def w_slice_rows(t, start, deg, pos):
        blk = g_slice(t, start, deg, pos)
        return select_rows([blk[:, u, :] for u in range(U)],
                           rel_of(t, start, pos))

    def w_umajor_along(t, start, deg, pos):
        B = start.shape[0]
        rows = g_rows_umajor(t, start, deg, pos)
        blk = jnp.concatenate([rows[u * B:(u + 1) * B] for u in range(U)],
                              axis=1)
        return jnp.take_along_axis(blk, rel_of(t, start, pos), axis=1)

    # ---- the fit test and the compaction alone
    def fits_of(t, start, deg):
        last = start + jnp.maximum(deg - 1, 0)
        return ((last >> 7) - (start >> 7)) < U

    def c_cumsum_scatter(t, start, deg, pos):
        B = start.shape[0]
        S = bg.fallback_slots(B, frac)
        miss = ~fits_of(t, start, deg)
        slot = jnp.where(miss, jnp.cumsum(miss) - 1, S)
        return jnp.zeros((S,), jnp.int32).at[slot].set(
            jnp.arange(B, dtype=jnp.int32), mode="drop"), jnp.sum(miss)

    def c_sort(t, start, deg, pos):
        B = start.shape[0]
        S = bg.fallback_slots(B, frac)
        fits = fits_of(t, start, deg)
        return bg._compact(fits, S)[0], jnp.sum(~fits)   # the shipped one

    def c_sort_stable(t, start, deg, pos):
        B = start.shape[0]
        S = bg.fallback_slots(B, frac)
        miss = ~fits_of(t, start, deg)
        iota = jnp.arange(B, dtype=jnp.int32)
        return jnp.sort(jnp.where(miss, iota, B))[:S], jnp.sum(miss)

    # ---- the whole routed op
    def classic(t, start, deg, pos):
        return element_gather(t, idx_of(t, start, pos))

    def routed(t, start, deg, pos):
        return bg.blocked_window_gather(t, start, deg, pos, U=U,
                                        fallback_frac=frac)[0]

    return {
        "classic": classic, "routed": routed,
        "gather.lanes": g_lanes, "gather.rows_umajor": g_rows_umajor,
        "gather.rows_bu": g_rows_bu, "gather.slice": g_slice,
        "gather.wide256": g_wide,
        "window.umajor_rows": w_umajor_rows, "window.sep_rows": w_sep_rows,
        "window.bu_flat": w_bu_flat,
        "window.slice_flat": w_slice_flat, "window.slice_rows": w_slice_rows,
        "window.umajor_along": w_umajor_along,
        "compact.cumsum_scatter": c_cumsum_scatter, "compact.sort": c_sort,
        "compact.sort_stable": c_sort_stable,
    }


def timed(f, args, iters):
    import jax

    out = f(*args)
    jax.block_until_ready(out)
    jax.block_until_ready(f(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = f(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e3, out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--small", action="store_true",
                    help="tiny shapes on any backend: a rehearsal, no times")
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--only", default="", help="comma list of name prefixes")
    ap.add_argument("--out", default="chiprun_out/probe_window_gather.jsonl")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from quiver_tpu.ops import blockgather as bg

    dev = jax.devices()[0]
    if not args.small and dev.platform != "tpu":
        sys.exit("probe_window_gather: no TPU here (rehearse with --small)")

    # (graph, B, k, dead share): the SAGE cell's hops 3, 2, 1 and the typed
    # cell's hops 2, 1 (PERF.md section 4); the dead shares are the cells'
    graphs = {"papers": (27_764_989, 403_921_468),
              "mag": (3_815_006, 54_023_314)}
    shapes = [("papers", 180_224, 5, 0.3), ("papers", 16_384, 10, 0.1),
              ("papers", 1_024, 15, 0.0), ("mag", 26_624, 15, 0.1),
              ("mag", 1_024, 25, 0.0)]
    if args.small:
        graphs = {"papers": (4_000, 60_000), "mag": (2_000, 30_000)}
        shapes = [("papers", 704, 5, 0.3), ("mag", 128, 15, 0.1)]
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    lines = []
    built = {}
    for gname, B, k, dead in shapes:
        if gname not in built:
            built.clear()             # one graph on the device at a time
            built[gname] = build(*graphs[gname], args.seed)
        indptr, t = built[gname]
        start, deg, pos = frontier(indptr, B, k, dead, args.seed)
        want = np.asarray(jnp.take(t.reshape(-1), start[:, None] + pos))
        live = np.asarray(deg)[:, None] > 0
        for U, frac in ((2, 1 / 32), (2, 1 / 4), (1, 1 / 4), (3, 1 / 4)):
            last = start + jnp.maximum(deg - 1, 0)
            fit = np.asarray(((last >> 7) - (start >> 7)) < U)[:, None]
            miss = int((~fit).sum())
            for name, f in variants(U, frac).items():
                if args.only and not name.startswith(
                        tuple(args.only.split(","))):
                    continue
                if (U, frac) != (2, 1 / 32) and name not in (
                        "routed", "window.umajor_rows"):
                    continue
                ms, out = timed(jax.jit(f), (t, start, deg, pos), args.iters)
                ok = None
                if name in ("classic", "routed"):
                    ok = bool(np.array_equal(
                        np.where(live, np.asarray(out), 0),
                        np.where(live, want, 0)))
                elif name.startswith("window."):   # no fallback in these
                    ok = bool(np.array_equal(
                        np.where(live & fit, np.asarray(out), 0),
                        np.where(live & fit, want, 0)))
                line = {"graph": gname, "B": B, "k": k, "U": U,
                        "slots": bg.fallback_slots(B, frac), "misses": miss,
                        "variant": name, "equal_to_take": ok,
                        "device": dev.device_kind}
                if not args.small:
                    line["ms"] = round(ms, 4)
                lines.append(line)
                print(json.dumps(line), flush=True)
    with open(args.out, "w") as fh:
        fh.writelines(json.dumps(x) + "\n" for x in lines)


if __name__ == "__main__":
    main()
