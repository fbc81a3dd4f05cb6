"""What the sharded step costs the CALLER's thread, read without a trace.

``papers100m-sage-host.train-dist`` drives sample -> lookup -> data-parallel
step, three launches and some eager glue a step, with two steps in flight.
This script builds that cell's program through the benchmark's own files
and drives its step three ways, reading ``telemetry.get_tracer().summary()``
(count, mean and longest call of every host span: always on, no trace)
after each:

  * ``drained``: the caller waits for every step's loss before the next
    call, so every call finds the device idle and a span reads the host's
    OWN work (Python, argument placement, the launch);
  * ``in_flight``: the caller waits for the loss of two steps ago, as the
    cell's window does; what a span reads beyond ``drained`` is the runtime
    holding the caller while the device's queue is full;
  * ``free``: the caller never waits, so the loop's wall time a step is how
    fast the host can hand steps over at all.

If ``drained``'s host time a step is far under the device's step, the
device bounds the cell and stays the bound until the device's step falls to
about that; if it is near it, the host does.  PERF.md, PR 38, has the
reading that decided the order of ROADMAP S9's items.

    chiprun --chips 4 -- python benchmarks/probe_host_path.py
    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \\
        python benchmarks/probe_host_path.py --small

A time from a CPU run says nothing about the chip: ``--small`` only
rehearses the script.
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "cellbench")]

CELL = "papers100m-sage-host.train-dist"


def drive(step, state, feed, first, steps, in_flight):
    """``steps`` calls of ``step`` from batch ``first`` on, waiting for the
    loss of ``in_flight`` steps ago (0: of this one; None: never); returns
    the state, the loop's seconds and the seconds inside ``step``."""
    import jax
    import jax.numpy as jnp

    from quiver_tpu import telemetry

    items = [feed(first + i) for i in range(steps)]
    ones = jnp.ones(items[0][0].shape, bool)
    jax.block_until_ready((items, ones))
    pending, inside = [], 0.0
    telemetry.reset()
    t0 = time.perf_counter()
    for seeds, labels, key in items:
        t = time.perf_counter()
        state, loss = step(state, seeds, labels, ones, key)
        inside += time.perf_counter() - t
        pending.append(loss)
        if in_flight is not None and len(pending) > in_flight:
            jax.block_until_ready(pending.pop(0))
    loop_s = time.perf_counter() - t0
    jax.block_until_ready(state.params)
    return state, loop_s, inside, time.perf_counter() - t0


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=3800000099)
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--small", action="store_true")
    args = ap.parse_args(argv)

    import run

    _, cell, cfg, traffic = run.find_cell(CELL)
    if args.small:
        run.rehearsal_size(cfg, traffic)

    import jax
    import jax.numpy as jnp

    from quiver_tpu import telemetry

    devices = jax.devices()[:cell["chips"]]
    if not args.small and devices[0].platform != "tpu":
        print(f"needs a TPU, JAX found {devices[0].platform!r}",
              file=sys.stderr)
        return 2
    import compile_cache

    compile_cache.cache_dir()
    import datagen

    data = run.make_data(cfg, args.seed)
    prog = run.parts_of(cfg, traffic)["program"].Program(cfg, data, devices)
    B = cfg["batch"] * cfg["ranks"]
    order = datagen.train_order(dict(cfg, batch=B), args.seed, 2)
    base = prog.make_key(args.seed % (2 ** 31 - 1))

    def feed(i):
        s = order[i % len(order)]
        return (jnp.asarray(s), jnp.asarray(data["labels"][s]),
                jax.random.fold_in(base, i))

    state, step = prog.fused_train_step()
    state, *_ = drive(step, state, feed, 0, 6, 0)       # compile, warm
    first = 6
    for name, in_flight in (("drained", 0),
                            ("in_flight", traffic["steps_in_flight"]),
                            ("free", None), ("drained_again", 0)):
        state, loop_s, inside, whole_s = drive(step, state, feed, first,
                                               args.steps, in_flight)
        first += args.steps
        spans = telemetry.get_tracer().summary()
        top = sum(spans[k]["total_s"] for k in (
            "sampler.sample", "feature.lookup", "step.train") if k in spans)
        print(json.dumps({
            "regime": name, "steps": args.steps, "rehearsal": args.small,
            "device": devices[0].device_kind,
            "loop_ms_a_step": 1e3 * loop_s / args.steps,
            "until_done_ms_a_step": 1e3 * whole_s / args.steps,
            "inside_step_ms": 1e3 * inside / args.steps,
            "library_spans_ms": 1e3 * top / args.steps,
            "glue_ms": 1e3 * (inside - top) / args.steps,
            "spans": spans}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
