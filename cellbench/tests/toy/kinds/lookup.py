"""The toy's kind: lookups back to back, each answer waited for; once the
window has closed every answer of the last round is held to the
reference's rows."""

import gc
import time

import numpy as np

from cells import settle, span

FAULTS = ("no_exchange",)


def run(prog, ref, cfg, traffic, data, seed, seconds, tracer, watch):
    import jax

    B, n = cfg["batch"], traffic["batches"]
    ids = np.random.default_rng(seed).integers(
        0, cfg["rows"], (n, B)).astype(np.int32)
    jax.block_until_ready(prog.lookup(ids[0]))

    compiles0 = watch.compiles
    answers, steps, traced_steps = [None] * n, 0, None
    settle()
    tracer.start()
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < seconds:
        with span("dispatch"):
            out = prog.lookup(ids[steps % n])
        with span("wait_result"):
            jax.block_until_ready(out)
        answers[steps % n] = out
        steps += 1
        if tracer.due():
            tracer.stop()
            traced_steps = steps
    elapsed = time.perf_counter() - t0
    gc.unfreeze()
    if tracer.on and tracer.t1 is None:
        tracer.stop()
        traced_steps = steps
    tracer.join()

    end_to_end = {"lookup_rows_per_s": steps * B / elapsed}
    facts = {"kind": "lookup", "steps": steps, "elapsed_s": elapsed,
             "window_compiles": watch.compiles - compiles0,
             "traced_steps": traced_steps, "batch": B,
             "traced_s": (tracer.t1 - tracer.t0) if tracer.on else None,
             "t_setup_end": t0, "attempted": steps, "failed": 0}

    def replay():
        got = [(i, np.asarray(a)) for i, a in enumerate(answers)
               if a is not None]
        prog.free()
        return got

    def numbers(replayed, precision):
        return {"wrong_rows": float(sum(
            ref.wrong_rows(data, ids[i], got) for i, got in replayed))}

    return end_to_end, facts, (replay, numbers)
