"""Time the sharded exchange's unpacking on one chip, one form a line.

``dist.exchange.exchange`` unpacks a round's answers into request order:
one gather out of the ``[n * bucket, *row]`` rows that came back, one row
per request slot, then ``where(mine, got, answers)``.  A slot that sent
nothing in the round may read any row.  The four-chip cell's lookup
unpacks 1,081,344 slots a step out of 1,098,240 received rows of 128
bfloat16, 43.5% of them live (PERF.md section 5); hop 3 unpacks 180,224
slots of five int32 draws out of 187,392.  This script lays out the same
pattern on one chip (four owners' monotone streams interleaved, dead
slots among them) and times, by the device trace:

  parent    every dead slot clipped to the last row, ``jnp.take``
            (the exchange before PR 39);
  clip_promised  the same row, but the gather promised in bounds: how
            much of the parent's time is ``jnp.take``'s own fill pass;
  stride16  each dead slot a row of its own, ``feature._dead_rows``
            (``_DEAD_STRIDE`` rows apart), promised in bounds: what the
            exchange does now;
  stride1   rows of their own one apart.

Every form's answers are compared, bit for bit, with the parent's before
it is timed.  PERF.md (PR 39) has the chip's numbers.  The hop-3 shape's
promised forms read 0.31 ms dearer than ``parent`` here, and that is this
script's program, not the exchange's: left to choose its result's layout,
it adds a column-major copy to those forms (AOT for a described v5e), which
``jit_qt_dist_sample`` does not; the cell's trace reads hop 3 unchanged.

    chiprun -- python benchmarks/probe_unpack.py
    JAX_PLATFORMS=cpu python benchmarks/probe_unpack.py --small

A time from a CPU run says nothing about the chip and is not printed as
one: ``--small`` only rehearses the script.
"""

import argparse
import json
import os
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "cellbench")]

RANKS = 4
# name -> (request slots, bucket, row shape, row type, live share): the
# cell's lookup and hop 3 (PERF.md sections 5 and 6, PR 35)
SHAPES = {
    "lookup": (1_081_344, 274_560, (128,), "bfloat16", 0.435),
    "hop3": (180_224, 46_848, (5,), "int32", 0.5),
}
SMALL = {
    "lookup": (4_096, 1_152, (128,), "bfloat16", 0.435),
    "hop3": (1_024, 384, (5,), "int32", 0.5),
}


def pattern(F, bucket, live_share, seed):
    """``(dest, mine)`` of one round: each live slot's owner drawn
    uniformly, its row ``owner * bucket + `` its rank among that owner's
    requests; a dead slot's ``dest`` is ``RANKS * bucket`` (nowhere), as
    the exchange sets it."""
    import numpy as np

    rng = np.random.default_rng(seed)
    mine = rng.random(F) < live_share
    owner = rng.integers(0, RANKS, F)
    at = np.zeros(F, np.int64)
    for p in range(RANKS):
        hit = mine & (owner == p)
        at[hit] = np.arange(hit.sum())
    assert at.max() < bucket, (at.max(), bucket)
    dest = np.where(mine, owner * bucket + at, RANKS * bucket)
    return dest.astype(np.int32), mine


def forms():
    import jax
    import jax.numpy as jnp

    from quiver_tpu.feature import _dead_rows

    def finish(got, mine, answers):
        return jnp.where(mine.reshape((-1,) + (1,) * (got.ndim - 1)), got,
                         answers)

    def unpack_parent(back, dest, mine, answers):
        got = jnp.take(back, jnp.clip(dest, 0, back.shape[0] - 1), axis=0)
        return finish(got, mine, answers)

    def unpack_clip_promised(back, dest, mine, answers):
        got = back.at[jnp.clip(dest, 0, back.shape[0] - 1)].get(
            mode="promise_in_bounds")
        return finish(got, mine, answers)

    def unpack_stride16(back, dest, mine, answers):
        own = _dead_rows(dest.shape[0], back.shape[0], dest.dtype)
        got = back.at[jnp.where(mine, dest, own)].get(
            mode="promise_in_bounds")
        return finish(got, mine, answers)

    def unpack_stride1(back, dest, mine, answers):
        own = (jnp.arange(dest.shape[0], dtype=jnp.uint32)
               % back.shape[0]).astype(dest.dtype)
        got = back.at[jnp.where(mine, dest, own)].get(
            mode="promise_in_bounds")
        return finish(got, mine, answers)

    return {f.__name__[len("unpack_"):]: jax.jit(f) for f in (
        unpack_parent, unpack_clip_promised, unpack_stride16,
        unpack_stride1)}


def probe(name, shape, args, emit):
    import jax
    import jax.numpy as jnp
    import numpy as np

    import trace_reduce

    F, bucket, row, dtype, share = shape
    dest_np, mine_np = pattern(F, bucket, share, args.seed)
    bits = jax.random.bits(jax.random.key(args.seed),
                           (RANKS * bucket,) + row, jnp.uint16
                           if dtype == "bfloat16" else jnp.uint32)
    if dtype == "bfloat16":     # finite values only: no NaN bit patterns
        bits = bits & jnp.uint16(0xBF7F)
    back = jax.lax.bitcast_convert_type(bits, jnp.dtype(dtype))
    dest, mine = jnp.asarray(dest_np), jnp.asarray(mine_np)
    answers = jnp.zeros((F,) + row, back.dtype)
    fargs = (back, dest, mine, answers)
    jax.block_until_ready(fargs)
    emit({"shape": name, "slots": F, "received": RANKS * bucket,
          "row": list(row), "dtype": dtype, "live": int(mine_np.sum())})
    fns = forms()
    want = None
    for form, f in fns.items():
        out = np.asarray(f(*fargs))
        if want is None:
            want = out
        line = {"shape": name, "form": form,
                "equal_to_parent": out.tobytes() == want.tobytes()}
        emit(line)
    if args.small:
        return
    # the device's own times: every form launched ``iters`` times, in
    # turn, inside one trace; a form's seconds are its program's on the
    # device, over its launches
    trace_dir = tempfile.mkdtemp(prefix="probe_unpack_")
    host = {form: 0.0 for form in fns}
    with jax.profiler.trace(trace_dir):
        for _ in range(args.iters):
            for form, f in fns.items():
                t0 = time.perf_counter()
                jax.block_until_ready(f(*fargs))
                host[form] += time.perf_counter() - t0
    red = trace_reduce.reduce_trace(trace_dir) or {"modules": {}}
    for form in fns:
        mod = red["modules"].get(f"jit_unpack_{form}")
        line = {"shape": name, "form": form,
                "host_ms": round(host[form] / args.iters * 1e3, 4)}
        if mod:
            ms = mod["seconds"] / mod["launches"] * 1e3
            line.update(launches=mod["launches"], device_ms=round(ms, 4),
                        ns_per_slot=round(ms * 1e6 / F, 3))
        emit(line)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--small", action="store_true",
                    help="tiny shapes on any backend: a rehearsal, no times")
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--seed", type=int, default=3900000017)
    ap.add_argument("--out", default="chiprun_out/probe_unpack.jsonl")
    args = ap.parse_args()

    import jax

    dev = jax.devices()[0]
    if not args.small and dev.platform != "tpu":
        sys.exit("probe_unpack: no TPU here (rehearse with --small)")
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    lines = []

    def emit(line):
        line["device"] = dev.device_kind
        lines.append(line)
        print(json.dumps(line), flush=True)

    for name, shape in (SMALL if args.small else SHAPES).items():
        probe(name, shape, args, emit)
    with open(args.out, "w") as fh:
        fh.writelines(json.dumps(x) + "\n" for x in lines)


if __name__ == "__main__":
    main()
